"""The reader of `sparse_shared_entry_share` (the share of a sparse row's
taken blocks that its K/V heads read together through the walk's
both-heads copy) on step records made by hand, after the pattern of
`sparse_rows_share`'s cases in `test_minicpm_sala_cell.py`: a value where
the steps carry the counter, nothing for records without it, for a train
record and for broken ones, and its place in the manifest."""

import pytest

from benchmarks.harness import manifest

NAME = "sparse_shared_entry_share"
CELL = "minicpm_sala.longdoc_sessions"


def _steps(shared, sparse=32, blocks=64.0, dense_tokens=0):
    return [{"kind": "decode", "slots": 32, "live": 32,
             "sparse_rows": sparse, "blocks_selected": blocks,
             "shared_entries": shared, "dense_tokens": dense_tokens,
             "kc_entries": sparse * 1874} for _ in range(10)]


def _rec(steps):
    return {"kind": "serve", "program": {"steps": steps}}


@pytest.mark.parametrize("shared,sparse,blocks,want", [
    # 33 or 34 forced blocks of a row's 64: the cell
    (32 * 33 + 16, 32, 64.0, 33.5 / 64),
    (32 * 34, 32, 64.0, 34 / 64),
    # the both-heads copy never engaged
    (0, 32, 64.0, 0.0),
    # short contexts: every taken block is a forced one
    (8 * 12, 8, 12.0, 1.0)])
def test_the_share_of_taken_blocks_read_for_both_heads(shared, sparse,
                                                       blocks, want):
    read = manifest.layer_metric_reader(NAME)
    assert read(_rec(_steps(shared, sparse, blocks))) == pytest.approx(want)


def test_steps_with_rows_under_dense_len_are_left_out():
    """A row at or under `dense_len` shares its whole list and selects
    nothing: a step that holds one says nothing of the selection's share,
    and a window of such steps alone gives nothing."""
    read = manifest.layer_metric_reader(NAME)
    pure = _steps(32 * 33)
    mixed = _steps(24 * 33 + 8 * 79, sparse=24, dense_tokens=8 * 5000)
    assert read(_rec(pure + mixed)) == pytest.approx(33 / 64)
    assert read(_rec(mixed)) is None
    assert read(_rec(_steps(0, sparse=0, blocks=0.0,
                            dense_tokens=32 * 5000))) is None


@pytest.mark.parametrize("rec", [
    # the parent's program: step records without the counter
    _rec([{"kind": "decode", "slots": 32, "live": 32, "sparse_rows": 32,
           "blocks_selected": 64.0, "dense_tokens": 0, "kc_entries": 9}]),
    # another family's: no counter at all
    _rec([{"kind": "decode", "slots": 16, "live": 16}] * 5),
    # prompts alone in the window
    _rec([{"kind": "prefill", "slots": 1, "live": 1}]),
    {"kind": "train", "program": {"steps": _steps(32 * 33)}},
    {"kind": "serve"}, {"kind": "serve", "program": None}, {},
    _rec([])], ids=["parent", "other_family", "prefill_only", "train",
                    "no_program", "program_none", "empty", "no_steps"])
def test_nothing_where_there_is_nothing_to_read(rec):
    assert manifest.layer_metric_reader(NAME)(rec) is None


def test_the_manifest_lists_it_for_the_sparse_cell_alone():
    bench = manifest.load_manifest()
    (metric,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert metric == {
        "name": NAME, "unit": "share", "better": "higher",
        "source": "program_counter", "layer": "decode kernels",
        "moves": "serve_tokens_per_s", "workloads": [CELL]}
    assert bench["per_layer"][-1]["name"] == NAME
    assert NAME in {m["name"] for m in manifest.cell_metrics(
        bench, CELL, "per_layer")}

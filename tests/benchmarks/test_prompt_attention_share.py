"""The reader of `prompt_attention_share` (the prefill programs' device
seconds under the scope `attention` over all of their seconds) on a scope
reduction made by hand: a value where a traced window ran prefill programs,
nothing for an untraced run, a window of decode steps alone, a train record
and a trace without scopes, and its place in the manifest."""

import pytest

from benchmarks.harness import manifest

NAME = "prompt_attention_share"
CELLS = ["gpt2_large.doc_closed", "joyai_llm_flash.rag_closed"]


def _rec(programs, kind="serve", trace=True, scoped_ops=10):
    return {"kind": kind, "trace": {"busy_s": 4.0} if trace else None,
            "scopes": {"scoped_ops": scoped_ops, "busy_s": 4.0,
                       "programs": programs}}


_DECODE = {"total_s": 2.0, "by_scope": {"attention": 1.0, "mlp": 1.0}}


@pytest.mark.parametrize("programs,want", [
    # doc_closed at the parent: 0.61 s of splash and its copies in 1.94 s
    ({"jit__prefill_fn": {"total_s": 1.94,
                          "by_scope": {"attention": 0.61, "mlp": 0.9}},
      "jit__decode_fn": _DECODE}, 0.61 / 1.94),
    # two prefill programs (two buckets) are one sum
    ({"jit__prefill_fn": {"total_s": 1.0, "by_scope": {"attention": 0.1}},
      "jit__prefill_fn_1": {"total_s": 3.0, "by_scope": {"attention": 0.5}},
      "jit__decode_fn": _DECODE}, 0.6 / 4.0),
    # prompts whose attention carries no such scope read 0, not nothing
    ({"jit__prefill_fn": {"total_s": 1.0, "by_scope": {"mlp": 1.0}}}, 0.0)])
def test_the_share_of_the_prefill_programs_under_attention(programs, want):
    read = manifest.layer_metric_reader(NAME)
    assert read(_rec(programs)) == pytest.approx(want)


@pytest.mark.parametrize("rec", [
    _rec({"jit__decode_fn": _DECODE}),
    _rec({"jit__prefill_fn": {"total_s": 0.0, "by_scope": {}}}),
    _rec({"jit__prefill_fn": {"total_s": 1.0, "by_scope": {}}}, trace=False),
    _rec({"jit__prefill_fn": {"total_s": 1.0, "by_scope": {}}},
         scoped_ops=0),
    _rec({"jit_step_fn": {"total_s": 1.0, "by_scope": {"attention": 0.1}}},
         kind="train"),
    {"kind": "serve"}],
    ids=["decode-only", "empty-prefill", "untraced", "no-scopes", "train",
         "bare"])
def test_nothing_to_read_gives_nothing(rec):
    assert manifest.layer_metric_reader(NAME)(rec) is None


def test_its_entry_in_the_manifest():
    man = manifest.load_manifest()
    entry = next(m for m in man["per_layer"] if m["name"] == NAME)
    assert entry == {"name": NAME, "unit": "share", "better": "lower",
                     "source": "device_trace",
                     "layer": "models and XLA kernels",
                     "moves": "serve_tokens_per_s", "workloads": CELLS}
    for cell in CELLS:
        assert NAME in [m["name"] for m in manifest.cell_metrics(
            man, cell, "per_layer")]
    assert NAME not in [m["name"] for m in manifest.cell_metrics(
        man, "gpt2_large.chat_open", "per_layer")]

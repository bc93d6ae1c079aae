"""What `test_sparse_shared_entry_share.py` asserts of its metric's place in
BENCHMARK.json, run while later metrics stand after it.

That file's `test_the_manifest_lists_it_for_the_sparse_cell_alone` (PR 57)
pins its metric to the LAST place of `per_layer`, which no PR that appends a
metric can keep (the contract puts a new entry at the end of its list) and
which such a PR may not edit; `tests/conftest.py` expects that one test to
fail from then on. So that what it holds besides is not lost (the entry's
keys and its one cell), the same test body runs here against the manifest
cut after that entry: the list as PR 57 left it. The next `benchmark` PR
should make the pinned line compare the entry with its own place and take
this file and the hook's third row out."""

import pytest

from benchmarks.harness import manifest
from tests.benchmarks import test_sparse_shared_entry_share as pinned

PLACE = 110     # the 111th per-layer metric: where PR 57's stands


def test_the_entry_is_as_its_pr_left_it(monkeypatch):
    bench = manifest.load_manifest()
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index(pinned.NAME) == PLACE
    as_left = dict(bench, per_layer=bench["per_layer"][:PLACE + 1])
    monkeypatch.setattr(manifest, "load_manifest",
                        lambda *a, **k: as_left)
    pinned.test_the_manifest_lists_it_for_the_sparse_cell_alone()


def test_the_pin_is_the_only_line_that_fails_on_the_whole_list():
    """On the manifest as it stands the pinned test fails, and at the pin:
    what `tests/conftest.py` expects is that line and no other."""
    if manifest.load_manifest()["per_layer"][-1]["name"] == pinned.NAME:
        pytest.skip("no metric follows it: the pin holds")
    with pytest.raises(AssertionError) as failed:
        pinned.test_the_manifest_lists_it_for_the_sparse_cell_alone()
    assert failed.traceback[-1].statement.lines[0].strip().startswith(
        'assert bench["per_layer"][-1]["name"] == NAME')

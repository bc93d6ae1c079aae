"""What `test_granite_cell.py` asserts of its cell's place in BENCHMARK.json,
run while later cells stand after it.

That file's `test_the_cell_is_found_with_its_readers` (PR 58) pins the
benchmark to 13 cells and 10 configurations, which no PR that adds a cell can
keep and which such a PR may not edit; `tests/conftest.py` expects that one
test to fail from then on (`_PINNED_COUNT`). So that what it holds besides is
not lost (the cell, its traffic, its readers and their layers), the same test
body runs here against the manifest cut after PR 58's entries: the lists as
PR 58 left them. The next `benchmark` PR should drop the two counts from the
pinned line and take this file and the hook's row out."""

import json
import os

import pytest

from benchmarks.harness import manifest
from tests.benchmarks import test_granite_cell as pinned

CELLS, CONFIGS = 13, 10     # where PR 58's entries stand: the last of each


def _config():
    with open(os.path.join(manifest.BENCH_DIR, "configs",
                           "granite4_h_small.json")) as f:
        return json.load(f)


def test_the_entries_are_as_their_pr_left_them(monkeypatch):
    bench = manifest.load_manifest()
    assert bench["workloads"][CELLS - 1]["name"] == pinned.CELL
    assert bench["configs"][CONFIGS - 1]["name"] == "granite4_h_small"
    as_left = dict(bench, workloads=bench["workloads"][:CELLS],
                   configs=bench["configs"][:CONFIGS])
    monkeypatch.setattr(manifest, "load_manifest", lambda *a, **k: as_left)
    pinned.test_the_cell_is_found_with_its_readers(_config())


def test_the_pin_is_the_only_line_that_fails_on_the_whole_lists():
    """On the manifest as it stands the pinned test fails, and at the pin:
    what `tests/conftest.py` expects is that line and no other."""
    if len(manifest.load_manifest()["workloads"]) == CELLS:
        pytest.skip("no cell follows it: the pin holds")
    with pytest.raises(AssertionError) as failed:
        pinned.test_the_cell_is_found_with_its_readers(_config())
    assert failed.traceback[-1].statement.lines[0].strip().startswith(
        'assert len(bench["workloads"]) == 13')

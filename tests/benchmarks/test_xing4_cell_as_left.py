"""Everything `test_xing4_cell.py` asserts of its cell in BENCHMARK.json,
run while a later cell stands after it.

That file's `test_the_cell_is_found_with_its_readers_and_the_issues_traffic`
(PR 45) ends by pinning its cell to the LAST place of `workloads`, which no
PR that appends a cell can keep and which a PR that adds a cell may not
edit; `tests/conftest.py` expects that one test to fail from then on. So
that none of what it holds besides is lost (the traffic's parameters,
`with_context`, the eleven per-layer metrics with their layers, `moves` and
readers, the 25 metrics of the cell, the end-to-end pair, the slot count of
the byte counts), the same test body runs here against the manifest cut
after the cell's entry: the list as PR 45 left it, where the pin means what
it meant, that the entry stands at the place it was appended at and nothing
before it moved. The next `benchmark` PR should make the pinned line
compare the entry with its own place and take this file out."""

import pytest

from benchmarks.harness import manifest
from tests.benchmarks import test_xing4_cell as pinned

PLACE = 9       # the tenth cell: where PR 45 appended it

config = pinned.config      # the module's fixture, under its own name


def test_the_xing4_cell_is_as_its_pr_left_it(config, monkeypatch):
    bench = manifest.load_manifest()
    names = [w["name"] for w in bench["workloads"]]
    assert names.index(pinned.CELL) == PLACE
    as_left = dict(bench, workloads=bench["workloads"][:PLACE + 1])
    monkeypatch.setattr(manifest, "load_manifest",
                        lambda *a, **k: as_left)
    pinned.test_the_cell_is_found_with_its_readers_and_the_issues_traffic(
        config)


def test_the_pin_is_the_only_line_that_fails_on_the_whole_list(config):
    """On the manifest as it stands the pinned test fails, and at the pin:
    what `tests/conftest.py` expects is that line and no other."""
    if manifest.load_manifest()["workloads"][-1]["name"] == pinned.CELL:
        pytest.skip("no cell follows it: the pin holds")
    with pytest.raises(AssertionError) as failed:
        pinned.test_the_cell_is_found_with_its_readers_and_the_issues_traffic(
            config)
    assert failed.traceback[-1].statement.lines[0].strip().startswith(
        'assert bench["workloads"][-1] == entry')

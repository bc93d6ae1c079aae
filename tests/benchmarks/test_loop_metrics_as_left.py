"""What `test_loop_metrics.py` asserts of its ten metrics' place in
BENCHMARK.json, run while a later metric stands after them.

That file's `test_benchmark_json_lists_the_ten_under_the_decode_engine`
(PR 56) begins by pinning its ten to the LAST ten places of `per_layer`,
which no PR that appends a metric can keep (the contract puts a new entry
at the end of its list) and which such a PR may not edit;
`tests/conftest.py` expects that one test to fail from then on. So that
what it holds besides is not lost (each metric's layer, `moves`, source,
direction, unit and cells), the same test body runs here against the
manifest cut after the tenth of them: the list as PR 56 left it. The next
`benchmark` PR should make the pinned line compare the ten with their own
places and take this file and the hook's second row out."""

import pytest

from benchmarks.harness import manifest
from tests.benchmarks import test_loop_metrics as pinned

PLACE = 109     # the 110th per-layer metric: where PR 56's last one stands


def test_the_ten_are_as_their_pr_left_them(monkeypatch):
    bench = manifest.load_manifest()
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index(pinned.NAMES[-1]) == PLACE
    as_left = dict(bench, per_layer=bench["per_layer"][:PLACE + 1])
    monkeypatch.setattr(manifest, "load_manifest",
                        lambda *a, **k: as_left)
    pinned.test_benchmark_json_lists_the_ten_under_the_decode_engine()


def test_the_pin_is_the_only_line_that_fails_on_the_whole_list():
    """On the manifest as it stands the pinned test fails, and at the pin:
    what `tests/conftest.py` expects is that line and no other."""
    if manifest.load_manifest()["per_layer"][-1]["name"] == pinned.NAMES[-1]:
        pytest.skip("no metric follows them: the pin holds")
    with pytest.raises(AssertionError) as failed:
        pinned.test_benchmark_json_lists_the_ten_under_the_decode_engine()
    assert failed.traceback[-1].statement.lines[0].strip().startswith(
        'assert [m["name"] for m in bench["per_layer"]][-10:]')

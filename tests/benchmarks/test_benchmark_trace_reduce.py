"""The reduction from the profiler's trace to numbers: interval arithmetic
on synthetic records, and the loader on one small trace recorded on a
TPU v5e (three runs of a two-matmul program under `dispatch` / `sync`
annotations) and one on its four-chip host (matmul, all-reduce, update);
both from PR 23's chip runs."""

import os

import pytest

from benchmarks.harness import trace_reduce as tr

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "tiny_v5e.xplane.pb")


def test_union_subtract_and_clip():
    assert tr.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert tr.total(tr.union([(0, 2), (1, 3), (5, 6)])) == 4
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5),
                                                         (7, 10)]
    assert tr.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]
    assert tr.clip([(0, 5), (8, 12)], 2, 10) == [(2, 5), (8, 10)]


def test_busy_idle_per_op_and_collective_exposure():
    ops = [("%fusion.1 = f32[8,8]{1,0} fusion(f32[8,8] %p)", 0.0, 4.0),
           ("%all-reduce.2 = f32[8]{0} all-reduce(f32[8] %g)", 3.0, 6.0),
           ("%fusion.3 = f32[8,8]{1,0} fusion(f32[8,8] %q)", 7.0, 9.0),
           # a loop's wrapper spans its body and counts for nothing
           ("%while.4 = (s32[]) while((s32[]) %t), body=%b", 0.0, 10.0)]
    r = tr.reduce_device(ops, 0.0, 10.0)
    assert r["busy_s"] == pytest.approx(8.0)       # [0,6] and [7,9]
    assert r["idle_gaps"] == [(6.0, 7.0), (9.0, 10.0)]
    assert r["collective_s"] == pytest.approx(3.0)
    # [3,4] of the all-reduce hides behind the fusion; [4,6] is exposed
    assert r["collective_exposed_s"] == pytest.approx(2.0)
    assert sum(r["per_op"].values()) == pytest.approx(9.0)


def test_idle_gaps_go_to_the_host_span_that_covers_them():
    gaps = [(6.0, 7.0), (9.0, 10.0)]
    host = [("sync", 5.5, 6.4), ("dispatch", 6.4, 6.9)]
    out = tr.attribute_gaps(gaps, host, "harness_other")
    assert out["sync"]["seconds"] == pytest.approx(0.4)
    assert out["dispatch"]["seconds"] == pytest.approx(0.5)
    assert out["harness_other"]["seconds"] == pytest.approx(1.1)
    assert out["harness_other"]["longest"] == pytest.approx(1.0)


def test_stable_labels_for_ops():
    assert tr.short_name(
        "%fusion.2568 = u32[128]{0:T(128)} fusion(), kind=kLoop") == \
        "fusion__u32_128"
    assert tr.short_name(
        "%copy-start.17 = (f32[256,128]{1,0:T(8,128)}, u32[]{:S(2)}) "
        "copy-start(f32[256,128]{1,0} %x)") == "copy-start__f32_256_128__u32"
    assert tr.op_base("%all-reduce-start.1 = f32[4]{0} all-reduce-start("
                      "f32[4] %p)") == "all-reduce-start.1"
    assert tr.COLLECTIVE.search("all-reduce-start.1")
    assert tr.WRAPPER.match("while.3") and not tr.WRAPPER.match("fusion.3")


def test_the_recorded_v5e_trace_reduces():
    loaded = tr.load_xplane(TRACE, ("dispatch", "sync"))
    assert list(loaded["devices"]) == [0]
    assert {n for n, _, _ in loaded["host"]} == {"dispatch", "sync"}
    red = tr.reduce_loaded(loaded, "harness_other")
    assert red["devices_seen"] == 1
    assert 0 < red["busy_s"] < red["window_s"]
    assert 0 < red["idle_share_worst"] < 1
    # per-op sums add up to the busy time (no op overlaps another here)
    assert sum(s for _, s in red["device_ops"]) == pytest.approx(
        red["busy_s"], rel=1e-6)
    assert red["modules"]["jit_f"]["count"] == 3
    assert red["collective_exposed_share_worst"] == 0.0
    names = {n for n, _ in red["idle_gaps"]}
    assert {"sync", "dispatch", "harness_other"} <= names
    assert len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10


def test_the_recorded_four_chip_trace_shows_its_collective():
    loaded = tr.load_xplane(os.path.join(os.path.dirname(TRACE),
                                         "tiny_v5e_4chips.xplane.pb"),
                            ("dispatch", "sync"))
    assert sorted(loaded["devices"]) == [0, 1, 2, 3]
    red = tr.reduce_loaded(loaded, "harness_other")
    assert red["devices_seen"] == 4
    assert red["device_ops"][0][0] == "all-reduce__bf16_512_512"
    # nothing else runs while the all-reduce does: all of it is exposed
    assert red["collective_share_worst"] > 0
    assert red["collective_exposed_share_worst"] == pytest.approx(
        red["collective_share_worst"])
    assert red["modules"]["jit_f"]["count"] == 12  # 3 runs x 4 devices
    # busy is the mean over the four devices, never more than the window
    assert 0 < red["busy_s"] < red["window_s"]


def test_a_trace_without_device_ops_reads_as_nothing():
    assert tr.reduce_loaded({"devices": {}, "host": []}, "x") == {
        "devices_seen": 0}

"""The ten per-layer metrics of the engine loop's turn from inside (PR 56):
what `benchmarks/harness/loop_records.py` and the readers make of hand-made
records; that every reader gives a value or None and never raises, on a
record with its inputs, on the PARENT's shape of record (the driver runs
the parent's program under this benchmark: only `turn_unnamed_share` reads
a number there), on a train record and on an empty window; that a long
turn's excess goes to the host where a hiccup overlaps it and to the device
where it lies inside a `.wait` under none; and that BENCHMARK.json lists
the ten as PERF.md does."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import loop_records, manifest  # noqa: E402
from paddle_tpu.observability import tracing  # noqa: E402

NAMES = ("turn_unnamed_share", "dispatch_build_p50_ms",
         "dispatch_call_p50_ms", "loop_starved_share", "loop_cpu_share",
         "loop_offcpu_share", "admit_company_share", "loop_long_turn_share",
         "pause_host_share", "pause_device_share")
SERVE_TPUT_CELLS = [
    "gpt2_large.doc_closed", "olmoe_1b_7b.gen_closed",
    "joyai_llm_flash.rag_closed", "nemotron3_nano.reason_closed",
    "nemotron3_nano.doc_sessions", "minicpm_sala.longdoc_sessions",
    "xing4_29b_a4b.ctx12k_sessions", "jamba2_3b.chat_closed",
    "longcat_flash_chat.chat_closed"]
W0, W1 = 500.0, 510.0       # the window on the store's clock
LOOP_TID, HTTP_SID = 7, 9000


class _Spans:
    """Rows of `rec["program"]["spans"]` as `program_trace.collect` makes
    them: (name, t0, t1, thread, facts), `sid` and `parent` among the
    facts."""

    def __init__(self):
        self.rows, self._sid = [], 0

    def add(self, name, t0, t1, parent=None, **facts):
        self._sid += 1
        facts["sid"] = self._sid
        if parent is not None:
            facts["parent"] = parent
        self.rows.append((name, W0 + t0, W0 + t1, LOOP_TID, facts))
        return self._sid

    def turn(self, t0, dur=0.010, inside=True, wait=0.004, admits=None,
             starved=None):
        """One turn of `dur` seconds at `t0`: (admit > prefill >
        prefill.wait), grow, dispatch (build, call), flush, resolve >
        resolve.wait of `wait` seconds, 0.5 ms under no child; `inside`
        False gives the parent's shape (no parts, no flush, no facts)."""
        new = {"cpu_s": 0.003, "loop": "lazy"} if inside \
            else {"loop": "lazy"}
        sid = self.add("decode.turn", t0, t0 + dur, **new)
        at = t0 + 0.0002
        if admits is not None:
            self.add("decode.admit", at, at + 0.001, parent=sid)
            facts = {"same_bucket_waiting": admits, "queue_empty": False} \
                if inside else {}
            # a request's spans are its http.generate's children
            fill = self.add("decode.prefill", at, at + 0.001,
                            parent=HTTP_SID, **facts)
            self.add("decode.prefill.wait", at + 0.0002, at + 0.0008,
                     parent=fill)
            at += 0.001
        self.add("decode.grow", at, at + 0.0002, parent=sid)
        at += 0.0002
        probed = {} if not inside else {"queue_empty": False} \
            if starved is None \
            else {"queue_empty": True, "starved_s": starved}
        d = self.add("decode.dispatch", at, at + 0.002, parent=sid,
                     **probed)
        if inside:
            self.add("decode.dispatch.build", at, at + 0.0012, parent=d)
            self.add("decode.dispatch.call", at + 0.0012, at + 0.002,
                     parent=d)
        at += 0.002
        if inside:
            self.add("decode.flush", at, at + 0.0005, parent=sid, items=4)
        at += 0.0005        # the parent's flush lies under no child
        end = t0 + dur - 0.0003
        r = self.add("decode.resolve", at, end, parent=sid)
        self.add("decode.resolve.wait", at, at + wait, parent=r)
        return sid


def _rec(spans, kind="serve"):
    return {"kind": kind, "window_s": W1 - W0,
            "program": {"spans": spans.rows, "steps": [], "requests": [],
                        "window": (W0, W1), "dropped": 0}}


def _read(rec):
    out = {}
    for name in NAMES:
        read = manifest.layer_metric_reader(name)
        assert read is not None, name
        out[name] = read(rec)
    return out


@pytest.fixture(autouse=True)
def _store():
    tracing.stop_recording()
    tracing.clear_spans()
    yield
    tracing.clear_spans()


def _steady(inside=True, turns=100):
    spans = _Spans()
    for i in range(turns):
        spans.turn(0.1 * i, inside=inside,
                   admits=(i % 4 == 0) if i % 2 == 0 else None,
                   starved=0.0015 if i % 10 == 0 else None)
    return spans


STEADY = {
    # 0.2 ms before the first child, 0.3 ms after the last, of 10 ms
    "turn_unnamed_share": 0.05,
    "dispatch_build_p50_ms": 1.2,
    "dispatch_call_p50_ms": 0.8,
    # ten dispatches of a hundred saw the queue empty for 1.5 ms
    "loop_starved_share": 10 * 0.0015 / 10.0,
    "loop_cpu_share": 100 * 0.003 / 10.0,
    # 10 ms a turn less 4 ms waited less 3 ms on the processor, and an
    # admission's 0.6 ms in `decode.prefill.wait`
    "loop_offcpu_share": (100 * 0.003 - 50 * 0.0006) / 10.0,
    # fifty admissions, every second one with company
    "admit_company_share": 0.5,
    # no turn over its limit: none of no seconds has an owner, and the
    # line still holds both (a cell that lists a metric reports it in
    # every traced run)
    "loop_long_turn_share": 0.0,
    "pause_host_share": 0.0, "pause_device_share": 0.0}


@pytest.mark.parametrize("name", NAMES)
def test_a_reader_on_a_record_that_has_its_inputs(name):
    got = _read(_rec(_steady()))[name]
    assert got == pytest.approx(STEADY[name]), (name, got)


@pytest.mark.parametrize("cell", [c for c in SERVE_TPUT_CELLS
                                  if not c.startswith("xing4_")])
def test_a_cell_reports_every_metric_that_lists_it_in_a_quiet_window(cell):
    """What refused this PR's first check: a metric that a cell lists has
    to be in EVERY traced line of it, and a window may hold no pause (any
    cell) and no admission (a `sessions` cell). On a steady window of the
    change's shape, for the metrics `BENCHMARK.json` gives the cell."""
    quiet = _Spans()
    for i in range(100):
        quiet.turn(0.1 * i, admits=None if cell.endswith("_sessions")
                   else i % 2)
    listed = [m["name"] for m in manifest.cell_metrics(
        manifest.load_manifest(), cell, "per_layer") if m["name"] in NAMES]
    assert len(listed) == (9 if cell.endswith("_sessions") else 10)
    line = _read(_rec(quiet))
    assert [n for n in listed if line[n] is None] == []


@pytest.mark.parametrize("name", NAMES)
def test_a_reader_on_the_parents_shape_of_record(name):
    """The parent's turns carry no parts, no flush and no facts: its flush
    lies under no child (0.5 ms more of a turn), and nothing else reads."""
    got = _read(_rec(_steady(inside=False)))[name]
    if name == "turn_unnamed_share":
        assert got == pytest.approx(0.1)
    else:
        assert got is None, (name, got)


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("case", ["train", "untraced", "empty_window",
                                  "no_window", "broken_rows"])
def test_a_reader_without_its_record_gives_none_and_never_raises(name,
                                                                 case):
    rec = {
        "train": {"kind": "train", "window_s": 40.0, "program": None},
        "untraced": {"kind": "serve", "window_s": 40.0, "program": None},
        "empty_window": _rec(_Spans()),
        "no_window": {"kind": "serve", "program": {"spans": []}},
        "broken_rows": {"kind": "serve", "program": {
            "window": (W0, W1),
            "spans": [("decode.turn", W0, W0 + 1, LOOP_TID, {})]}},
    }[case]
    assert _read(rec)[name] is None


def test_a_train_record_with_spans_reads_nothing():
    assert set(_read(_rec(_steady(), kind="train")).values()) == {None}


def _paused(hiccup=None, wait=0.004):
    """A hundred turns of 10 ms and one of 0.3 s at 5.0 s, whose
    `.wait` holds `wait` seconds; a hiccup row (`t`, `late_s`) if given."""
    spans = _Spans()
    for i in range(100):
        spans.turn(0.1 * i if i < 50 else 0.1 * i + 0.5)
    spans.turn(5.0, dur=0.3, wait=wait)
    if hiccup is not None:
        tracing.add_record("host.hiccups", {"t": W0 + hiccup[0],
                                            "late_s": hiccup[1]})
    return _rec(spans)


# the limit is 50 ms + 3 x 10 ms: 0.22 s of the long turn's 0.3 s exceed it
EXCESS = 0.3 - 0.08


@pytest.mark.parametrize("hiccup,wait,host,device", [
    # the process stood still for the whole pause: the host's
    ((5.01, 0.28), 0.004, 1.0, 0.0),
    # no hiccup, and the turn sat in its wait: the device's (the 4 ms a
    # turn usually waits are not a pause)
    (None, 0.28, 0.0, 1.0),
    # no hiccup and no wait to speak of: the loop's thread alone
    (None, 0.004, 0.0, 0.0),
    # a hiccup over part of the wait: its seconds are the host's first,
    # and the rest of the excess, waited under no hiccup, the device's
    ((5.05, 0.1), 0.28, 0.1 / EXCESS, (EXCESS - 0.1) / EXCESS),
    # ... as far as the turn waited beyond the usual 4 ms
    ((5.05, 0.1), 0.154, 0.1 / EXCESS, 0.05 / EXCESS),
    # a hiccup elsewhere in the window owns nothing of this turn
    ((2.0, 0.2), 0.004, 0.0, 0.0),
])
def test_a_long_turn_is_the_hosts_under_a_hiccup_and_the_devices_in_a_wait(
        hiccup, wait, host, device):
    got = _read(_paused(hiccup, wait))
    assert got["loop_long_turn_share"] == pytest.approx(EXCESS / 10.0)
    assert got["pause_host_share"] == pytest.approx(host, abs=1e-9)
    assert got["pause_device_share"] == pytest.approx(device, abs=1e-9)


def test_hiccups_outside_the_window_and_malformed_rows_are_left_out():
    rec = _paused()
    tracing.add_record("host.hiccups", {"t": W1 + 1.0, "late_s": 0.2})
    tracing.add_record("host.hiccups", {"t": W0 - 1.0, "late_s": 0.2})
    assert loop_records.load(rec)["hiccups"] == []
    tracing.add_record("host.hiccups", {"late_s": 0.2})     # no `t`
    tracing.add_record("host.hiccups", {"t": W0 + 5.01, "late_s": 0.28})
    assert _read(rec)["pause_host_share"] == 1.0    # the good row counts


def test_benchmark_json_lists_the_ten_under_the_decode_engine():
    bench = manifest.load_manifest()
    rows = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-10:] == list(NAMES)
    for name in NAMES:
        m = rows[name]
        assert m["layer"] == "decode engine" \
            and m["moves"] == "serve_tokens_per_s", m
        assert m["source"] == ("program_counter"
                               if name == "admit_company_share"
                               else "program_span")
        assert m["better"] == ("higher" if name == "admit_company_share"
                               else "lower")
        assert m["unit"] == ("ms" if name.endswith("_ms") else "share")
        # an admission's company is read where the window admits: the
        # `sessions` cells' windows hold no admission. And none lists
        # `xing4_29b_a4b.ctx12k_sessions`, whose per-layer metrics
        # `tests/benchmarks/test_xing4_cell.py` pins at 25: that file is
        # the benchmark's, for a `benchmark` PR to edit
        cells = [c for c in SERVE_TPUT_CELLS
                 if not c.startswith("xing4_")
                 and not (name == "admit_company_share"
                          and c.endswith("_sessions"))]
        assert m["workloads"] == cells, name

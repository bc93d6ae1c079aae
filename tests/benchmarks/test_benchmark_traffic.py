"""The traffic generator and the load generator: the schedule comes from
the seed, every seed holds the same work in another order, the open loop
sends when a request is due whatever the server does, and times from then;
the generator's process never imports JAX."""

import http.server
import json
import subprocess
import sys
import threading
import time

import pytest

from benchmarks.harness import loadgen, traffic, window

OPEN = {"kind": "serve", "loop": "open", "rate_per_s": 0.8,
        "prompt_len": {"dist": "loguniform", "lo": 32, "hi": 512},
        "output_len": {"dist": "loguniform", "lo": 32, "hi": 256}}


def test_the_schedule_is_reproduced_from_the_seed():
    a = traffic.schedule(OPEN, 3000000011, 60.0)
    assert a == traffic.schedule(OPEN, 3000000011, 60.0)
    assert a != traffic.schedule(OPEN, 3000000012, 60.0)
    assert traffic.prompt_ids(2 ** 31 + 9, 5, 40, 50257) == \
        traffic.prompt_ids(2 ** 31 + 9, 5, 40, 50257)
    assert traffic.prompt_ids(1, 5, 40, 50257) != \
        traffic.prompt_ids(1, 6, 40, 50257)


def test_every_seed_holds_the_same_work_in_another_order():
    a = traffic.schedule(OPEN, 1, 60.0)
    b = traffic.schedule(OPEN, 2 ** 31 + 77, 60.0)
    for key in ("prompt_len", "max_new"):
        assert sorted(r[key] for r in a) == sorted(r[key] for r in b)
        assert [r[key] for r in a] != [r[key] for r in b]

    def gaps(rows):
        due = [r["due_s"] for r in rows]
        return sorted(round(y - x, 9) for x, y in zip(due, due[1:]))

    # the same inter-arrival gaps but the first, which opens the schedule
    assert len(set(gaps(a)) ^ set(gaps(b))) <= 2
    assert all(32 <= r["prompt_len"] <= 512 and 32 <= r["max_new"] <= 256
               for r in a)
    # a Poisson process at the stated rate: the schedule spans the time
    assert a[-1]["due_s"] == pytest.approx(len(a) / 0.8, rel=0.1)


def test_closed_loop_table_has_no_due_times():
    rows = traffic.schedule({"loop": "closed", "clients": 4,
                             "table_size": 16,
                             "prompt_len": {"dist": "uniform", "lo": 512,
                                            "hi": 960},
                             "output_len": {"dist": "fixed", "value": 32}},
                            7, 10.0)
    assert len(rows) == 16 and all(r["due_s"] is None for r in rows)
    assert {r["max_new"] for r in rows} == {32}
    assert min(r["prompt_len"] for r in rows) >= 512


def test_the_load_generator_never_imports_jax():
    code = ("import sys; import benchmarks.harness.loadgen; "
            "assert 'jax' not in sys.modules, 'jax was imported'")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)


class _SlowStream(http.server.BaseHTTPRequestHandler):
    """Answers /v1/generate like the server: ndjson token lines, then a
    done record. The first request stalls the (single-threaded) server."""
    protocol_version = "HTTP/1.1"
    stall_s = 0.6

    def log_message(self, *a):
        pass

    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers[
            "Content-Length"])))
        time.sleep(self.stall_s)
        self.send_response(200)
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()
        n = body["max_new_tokens"]
        for line in [{"token": i} for i in range(n)] + [
                {"done": True, "finish_reason": "length", "tokens": n}]:
            data = (json.dumps(line) + "\n").encode()
            self.wfile.write(f"{len(data):x}\r\n".encode() + data + b"\r\n")
            self.wfile.flush()
        self.wfile.write(b"0\r\n\r\n")
        self.close_connection = True


def test_open_loop_sends_when_due_and_times_from_the_due_time(tmp_path):
    # one thread serves one request at a time: a stall delays the next
    # request's ANSWER, never its sending
    server = http.server.HTTPServer(("127.0.0.1", 0), _SlowStream)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        mix = {"loop": "open", "rate_per_s": 10.0,
               "prompt_len": {"dist": "fixed", "value": 8},
               "output_len": {"dist": "fixed", "value": 3}}
        t0 = time.monotonic() + 0.2
        job = {"port": server.server_address[1], "seed": 5, "traffic": mix,
               "vocab_size": 100, "t0": t0, "t_stop": t0 + 2.0,
               "timeout_s": 10, "out": str(tmp_path / "requests.jsonl")}
        loadgen._Run(job).run()
    finally:
        server.shutdown()
        thread.join(timeout=5)
        server.server_close()
    rows = traffic.schedule(mix, 5, 2.0)
    recs = [json.loads(x) for x in open(job["out"])]
    assert len(recs) >= 8
    for r in recs:
        assert r["due"] == pytest.approx(t0 + rows[r["idx"]]["due_s"])
        # sent on schedule although the server was stalling on others (a
        # generator that waited for answers would run seconds late)
        assert 0 <= r["sent"] - r["due"] < _SlowStream.stall_s - 0.1
    win = window.stream_window(recs, t0, t0 + 2.0)
    done = [r for r in recs if r["done"]]
    assert done and all(len(r["token_times"]) == 3 for r in done)
    # the queue behind the stall shows in the time from the DUE time
    assert max(win["ttft_s"]) > 2 * _SlowStream.stall_s
    assert any(r["cut"] for r in recs)  # streams still open at t_stop

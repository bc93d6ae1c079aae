"""The load generator: a process of its own that NEVER imports JAX (the
parent holds the chip and runs engine and HTTP server), so that clients do
not share the engine loop's interpreter lock.

    python3 loadgen.py <job.json>

The job names the server's port, the traffic file's parameters, the seed,
the schedule's start `t0` and its end `t_stop` on CLOCK_MONOTONIC (one
clock for every process of the machine), and where to write the records.
Open loop: every request is sent when it is DUE on the seeded schedule,
whatever the server does, and is timed from when it was due. Closed loop:
`clients` threads each send their next request when the last one ended.
At `t_stop` the streams still open are cut (marked `cut`, not failed)."""

from __future__ import annotations

import http.client
import json
import os
import socket
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.harness import traffic as traffic_mod  # noqa: E402


class _Run:
    def __init__(self, job):
        self.job = job
        self.records = []
        self.lock = threading.Lock()
        self.open_conns = set()
        self.stopping = threading.Event()

    def one_request(self, row, due):
        job = self.job
        rec = {"idx": row["idx"], "due": due, "prompt_len": row["prompt_len"],
               "asked": row["max_new"], "token_times": [], "tokens": [],
               "done": False, "cut": False, "error": None, "sent": None}
        ids = traffic_mod.prompt_ids(job["seed"], row["idx"],
                                     row["prompt_len"], job["vocab_size"])
        body = json.dumps({"ids": ids, "max_new_tokens": row["max_new"]})
        conn = http.client.HTTPConnection("127.0.0.1", job["port"],
                                          timeout=job["timeout_s"])
        with self.lock:
            self.open_conns.add(conn)
        try:
            rec["sent"] = time.monotonic()
            conn.request("POST", "/v1/generate", body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            if resp.status != 200:
                rec["error"] = f"HTTP {resp.status}: {resp.read(200)!r}"
            else:
                for line in resp:
                    now = time.monotonic()
                    if not line.strip():
                        continue
                    msg = json.loads(line)
                    if "token" in msg:
                        rec["token_times"].append(now)
                        rec["tokens"].append(msg["token"])
                    elif msg.get("done"):
                        rec["done"] = "error" not in msg
                        rec["finish_reason"] = msg.get("finish_reason")
                        if "error" in msg:
                            rec["error"] = str(msg["error"])
                        break
                if not rec["done"] and rec["error"] is None:
                    rec["error"] = "stream ended without its done record"
        except Exception as e:  # a broken stream is a failed request
            rec["error"] = f"{type(e).__name__}: {e}"
        finally:
            if self.stopping.is_set() and not rec["done"]:
                rec["cut"], rec["error"] = True, None
            with self.lock:
                self.open_conns.discard(conn)
                self.records.append(rec)
            conn.close()
        return rec

    def open_loop(self, rows):
        t0 = self.job["t0"]
        threads = []
        for row in rows:
            due = t0 + row["due_s"]
            if due >= self.job["t_stop"]:
                break
            delay = due - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            th = threading.Thread(target=self.one_request, args=(row, due),
                                  daemon=True)
            th.start()
            threads.append(th)
        return threads

    def closed_loop(self, rows):
        n = int(self.job["traffic"]["clients"])

        def client(c):
            i = c
            while not self.stopping.is_set():
                # a closed-loop request is due the moment its client is free
                rec = self.one_request(dict(rows[i % len(rows)], idx=i),
                                       max(time.monotonic(), self.job["t0"]))
                if rec["error"]:
                    time.sleep(0.1)  # a refusing server is not hammered
                i += n

        delay = self.job["t0"] - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        threads = [threading.Thread(target=client, args=(c,), daemon=True)
                   for c in range(n)]
        for th in threads:
            th.start()
        return threads

    def run(self):
        job = self.job
        rows = traffic_mod.schedule(job["traffic"], job["seed"],
                                    job["t_stop"] - job["t0"])
        loop = self.open_loop if job["traffic"]["loop"] == "open" \
            else self.closed_loop
        threads = loop(rows)
        delay = job["t_stop"] - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        self.stopping.set()
        # cut the streams still open; again until every thread has ended,
        # for a request caught between its start and its connection
        deadline = time.monotonic() + 10.0
        while any(th.is_alive() for th in threads) \
                and time.monotonic() < deadline:
            with self.lock:
                conns = list(self.open_conns)
            for conn in conns:
                try:
                    if conn.sock is not None:
                        conn.sock.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass
            time.sleep(0.05)
        with self.lock:
            records = list(self.records)
        tmp = job["out"] + ".tmp"
        with open(tmp, "w") as f:
            for rec in sorted(records, key=lambda r: r["idx"]):
                f.write(json.dumps(rec) + "\n")
        os.replace(tmp, job["out"])


def main(argv):
    with open(argv[1]) as f:
        job = json.load(f)
    _Run(job).run()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))

"""BoxPS-analogue: a trainer-resident hot-row embedding cache over the PS.

Reference: framework/fleet/box_wrapper.h (BoxWrapper::PullSparse :41,
PushSparseGrad :46, BeginPass/EndPass :38-40) + operators/
pull_box_sparse_op.cc / push_box_sparse_op.cc — BoxPS keeps the hot rows
of giant CTR embeddings resident near the trainer so most lookups never
touch the remote parameter server; gradients are applied locally (read-
your-writes within a pass) and flushed to the PS asynchronously; pass
boundaries (BeginPass/EndPass) resynchronize with the server.

Here the "box" is a host-side LRU over (table, id) -> row:

  pull_sparse : cache hits are served locally; misses fan out to the
                sharded PS (ps/sparse_table.pull_rows) and populate the
                LRU. Hit/miss counters expose the hit rate.
  push_sparse_grad : the SGD update is applied to the cached rows
                immediately AND enqueued for a background flush thread
                that batches pushes to the PS — the trainer never blocks
                on the push RPC (box_wrapper's async PushSparseGrad).
  begin_pass / end_pass : end_pass drains the flush queue synchronously;
                begin_pass invalidates the cache so the next pull reads
                server-fresh rows (multi-trainer staleness is bounded by
                a pass, exactly the BoxPS contract).

Single-trainer note: local-apply + server-apply see the SAME gradient
once each, so cached and server rows stay bit-identical between passes;
with multiple trainers the cache serves each trainer its own
read-your-writes view while the server accumulates everyone's updates —
the next begin_pass picks them up.
"""

from __future__ import annotations

import queue
import threading
import warnings
from collections import OrderedDict
from typing import Dict, Optional, Tuple

import numpy as np

from ..observability import events as _events
from .client import GRAD_DROPS, PSClient
from .sparse_table import pull_rows, push_row_grads


class BoxSparseCache:
    """Hot-row LRU embedding tier with async gradient flush."""

    def __init__(self, client: PSClient, capacity_rows: int = 1 << 16,
                 flush_queue_size: int = 64):
        self.client = client
        self.capacity = int(capacity_rows)
        # (table, id) -> np row; OrderedDict in LRU order (front = oldest)
        self._rows: "OrderedDict[Tuple[str, int], np.ndarray]" = OrderedDict()
        # Read-your-writes bookkeeping (all bounded, all under _lock):
        #   _pending     (table,id) -> pushes queued but not yet applied
        #                on the PS (decremented after each flush RPC;
        #                bounded by the flush queue). While >0, a PS
        #                fetch may predate the write — don't cache it,
        #                and don't evict the locally-updated row.
        #   _fetching    (table,id) -> refcount of in-flight pull misses
        #                (bounded by concurrent pull batch sizes).
        #   _fetch_dirty keys pushed while a fetch for them was in
        #                flight: the fetched value predates the push —
        #                don't cache it. Cleared when the last fetcher
        #                for the key leaves.
        self._pending: Dict[Tuple[str, int], int] = {}
        self._fetching: Dict[Tuple[str, int], int] = {}
        self._fetch_dirty: set = set()
        self._lock = threading.Lock()
        self._flushq: "queue.Queue" = queue.Queue(maxsize=flush_queue_size)
        self._stop = threading.Event()
        self._flusher: Optional[threading.Thread] = None
        # flusher health: an RPC failure drops that batch (counted in
        # paddle_tpu_ps_grad_drops_total + a ps_failover event, never
        # silent); anything ELSE kills the flusher and is re-raised to
        # the owner at the next end_pass()/close() — a background thread
        # must not die with the error only on stderr
        self._flusher_exc: Optional[BaseException] = None
        self.flush_drops = 0    # rows whose flush RPC failed
        self.hits = 0
        self.misses = 0

    # -- stats ---------------------------------------------------------------

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def stats(self) -> Dict[str, float]:
        return {"hits": self.hits, "misses": self.misses,
                "hit_rate": round(self.hit_rate, 4),
                "resident_rows": len(self._rows)}

    # -- pass lifecycle (box_wrapper.h BeginPass/EndPass) --------------------

    def begin_pass(self):
        """Invalidate the cache: next pulls read server-fresh rows."""
        self.end_pass()
        with self._lock:
            self._rows.clear()
            self._pending.clear()
            self._fetch_dirty.clear()

    def end_pass(self):
        """Drain pending gradient flushes synchronously — and surface a
        dead flusher: if the background thread died on an unexpected
        exception since the last pass boundary, it is re-raised HERE,
        on the owner's thread (join-and-reraise)."""
        self._stop.set()
        try:
            if self._flusher is not None:
                self._flusher.join(timeout=30)
                if self._flusher.is_alive():
                    # wedged mid-RPC: keep the reference so the spawn
                    # check in push_sparse_grad (is_alive) can't start a
                    # second flusher racing this one for the queue
                    warnings.warn("box cache flusher still alive after "
                                  "30s end_pass join (wedged push RPC?); "
                                  "keeping it as the active flusher")
                else:
                    self._flusher = None
            while True:
                try:
                    name, ids, grads, lr = self._flushq.get_nowait()
                except queue.Empty:
                    break
                try:
                    push_row_grads(self.client, name, ids, grads, lr)
                except Exception as e:  # keep draining the remaining
                    # batches and let begin_pass still invalidate — an
                    # aborted drain would leave ids uncacheable and skip
                    # the cache clear (same policy as _flush_loop)
                    self._count_flush_drop(name, ids, e, site="end_pass")
                finally:
                    # even on RPC failure: counts must drop or the ids
                    # stay uncacheable/unevictable forever (the lost
                    # gradient is the PS contract's async-push risk)
                    self._mark_flushed(name, ids)
            if self._flusher_exc is not None:
                exc, self._flusher_exc = self._flusher_exc, None
                raise RuntimeError(
                    "box-cache flusher thread died on an unexpected "
                    "error (re-raised at the pass boundary)") from exc
        finally:
            self._stop.clear()  # a raised drain must not brick pushes

    def close(self):
        """Final drain + join-and-reraise — call at trainer shutdown."""
        self.end_pass()

    def _count_flush_drop(self, name, ids, e, site: str):
        n = int(np.asarray(ids).size)
        self.flush_drops += n
        GRAD_DROPS.inc(n, var=name)
        _events.emit("ps_failover", action="flush_drop", var=name,
                     rows=n, site=site,
                     error=f"{type(e).__name__}: {str(e)[:120]}")
        warnings.warn(f"box-cache {site} flush RPC failed "
                      f"({type(e).__name__}: {str(e)[:120]}); "
                      f"{n} row gradient(s) dropped")

    # -- pull / push ---------------------------------------------------------

    def pull_sparse(self, name: str, ids: np.ndarray,
                    dim: int) -> np.ndarray:
        ids = np.asarray(ids).reshape(-1)
        # operate on UNIQUE ids (CTR batches are duplicate-heavy): one
        # dict probe per unique id and one vectorized gather at the end —
        # per-ROW python work would make the cache slower than the raw
        # RPC it is meant to avoid
        uniq, inv = np.unique(ids, return_inverse=True)
        uniq_rows = np.empty((uniq.size, dim), np.float32)
        miss_pos = []
        with self._lock:
            for j, rid in enumerate(uniq):
                row = self._rows.get((name, int(rid)))
                if row is not None:
                    self._rows.move_to_end((name, int(rid)))
                    uniq_rows[j] = row
                else:
                    miss_pos.append(j)
                    # registered in the SAME critical section as the miss
                    # scan: a push landing any time after this is seen at
                    # insert time (via _fetch_dirty), with no window
                    key = (name, int(rid))
                    self._fetching[key] = self._fetching.get(key, 0) + 1
            # counters updated under the lock: concurrent trainer
            # threads must not lose increments
            self.misses += len(miss_pos)
            self.hits += int(ids.size - len(miss_pos))
        if miss_pos:
            # the PS fetch runs OUTSIDE the lock; a fetched value may
            # predate a local write if the id was pushed while we
            # fetched (_fetch_dirty) or pushed earlier with the flush
            # still queued (_pending) — caching it would violate
            # read-your-writes within the pass. The refcounts registered
            # above MUST be released even if the RPC raises, or the key
            # becomes permanently uncacheable.
            fetched = None
            try:
                fetched = pull_rows(self.client, name, uniq[miss_pos],
                                    dim=dim)
            finally:
                with self._lock:
                    for j, u in enumerate(uniq[miss_pos]):
                        key = (name, int(u))
                        self._fetching[key] -= 1
                        if self._fetching[key] <= 0:
                            del self._fetching[key]
                            dirty = key in self._fetch_dirty
                            self._fetch_dirty.discard(key)
                        else:
                            dirty = key in self._fetch_dirty
                        if fetched is None:
                            continue  # RPC failed: bookkeeping only
                        if dirty or self._pending.get(key, 0) > 0:
                            continue  # may be stale: don't cache
                        if key in self._rows:
                            continue  # another pull populated it
                        self._insert(name, int(u),
                                     fetched[j].astype(np.float32))
            uniq_rows[miss_pos] = fetched
        return uniq_rows[inv]

    def _insert(self, name: str, rid: int, row: np.ndarray):
        self._rows[(name, rid)] = row
        self._rows.move_to_end((name, rid))
        while len(self._rows) > self.capacity:
            # evict the coldest CLEAN row: a dirty row (pending flush)
            # holds a local update the PS doesn't have yet — evicting it
            # would serve stale reads on the next pull. Dirty rows are
            # bounded by the flush queue, so the overshoot is too.
            victim = next((k for k in self._rows
                           if self._pending.get(k, 0) == 0), None)
            if victim is None:
                break
            self._rows.pop(victim)

    def push_sparse_grad(self, name: str, ids: np.ndarray,
                         grads: np.ndarray, lr: float = 0.01):
        ids = np.asarray(ids).reshape(-1)
        grads = np.asarray(grads, np.float32).reshape(ids.size, -1)
        # 1) local apply: read-your-writes inside the pass. _pending is
        # bumped for EVERY id (cached or not) so pulls won't cache a PS
        # value that predates this write, and in-flight fetches for the
        # id are marked dirty.
        with self._lock:
            for rid, g in zip(ids, grads):
                key = (name, int(rid))
                self._pending[key] = self._pending.get(key, 0) + 1
                if key in self._fetching:
                    self._fetch_dirty.add(key)
                row = self._rows.get(key)
                if row is not None:
                    row -= lr * g
        # 2) async flush to the PS (bounded queue back-pressures like the
        # communicator's send queues). The check-then-spawn is under the
        # lock: two concurrent pushes must not each start a flusher
        # (end_pass joins only the tracked thread).
        with self._lock:
            if self._flusher is None or not self._flusher.is_alive():
                self._flusher = threading.Thread(target=self._flush_loop,
                                                 daemon=True)
                self._flusher.start()
        self._flushq.put((name, ids.copy(), grads.copy(), lr))

    def _mark_flushed(self, name: str, ids: np.ndarray):
        """The PS has applied this batch: drop its _pending marks."""
        with self._lock:
            for rid in ids:
                key = (name, int(rid))
                n = self._pending.get(key, 0) - 1
                if n <= 0:
                    self._pending.pop(key, None)
                else:
                    self._pending[key] = n

    def _flush_loop(self):
        try:
            while not self._stop.is_set():
                try:
                    name, ids, grads, lr = self._flushq.get(timeout=0.05)
                except queue.Empty:
                    continue
                try:
                    push_row_grads(self.client, name, ids, grads, lr)
                except Exception as e:  # keep the flusher alive; count
                    # the dropped batch — never a silent loss
                    self._count_flush_drop(name, ids, e, site="flusher")
                finally:
                    self._mark_flushed(name, ids)
        except BaseException as e:  # noqa: BLE001 — anything that
            # escapes the per-batch handling (a bug in the bookkeeping,
            # MemoryError, ...) must reach the owner, not die with the
            # thread: recorded + evented here, re-raised on the OWNER'S
            # thread by the next end_pass()/close() (raising here would
            # only spam stderr from a thread nobody joins on error)
            self._flusher_exc = e
            _events.emit("ps_failover", action="flusher_error",
                         error=f"{type(e).__name__}: {str(e)[:200]}")


_BOX: Optional[BoxSparseCache] = None


def init_box_cache(client: PSClient, capacity_rows: int = 1 << 16
                   ) -> BoxSparseCache:
    global _BOX
    _BOX = BoxSparseCache(client, capacity_rows)
    return _BOX


def get_box_cache() -> BoxSparseCache:
    if _BOX is None:
        raise RuntimeError(
            "box cache not initialized — call ps.box_cache.init_box_cache "
            "(the BoxWrapper::GetInstance of this rebuild)")
    return _BOX

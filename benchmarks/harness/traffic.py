"""The one general traffic generator: a traffic file of parameters plus a
seed give the whole schedule. Imports no JAX (the load generator's process
uses it). Every seed draws the SAME set of lengths and inter-arrival gaps,
in another order, so that a seed changes which request meets which and not
how much work the window holds."""

from __future__ import annotations

import math
from typing import Dict, List

import numpy as np


def _quantiles(spec: Dict, n: int) -> np.ndarray:
    """n values at the stratified quantiles (i + 0.5) / n of `spec`:
    {"dist": "fixed"|"uniform"|"loguniform", "lo", "hi"} or {"value"}."""
    dist = spec.get("dist", "fixed")
    if dist == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    u = (np.arange(n) + 0.5) / n
    lo, hi = float(spec["lo"]), float(spec["hi"])
    if dist == "uniform":
        vals = lo + u * (hi - lo)
    elif dist == "loguniform":
        vals = np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def request_count(traffic: Dict, span_s: float) -> int:
    """How many requests the schedule holds for `span_s` seconds of load."""
    if traffic["loop"] == "open":
        return int(math.ceil(float(traffic["rate_per_s"]) * span_s)) + 2
    return int(traffic["table_size"])


def schedule(traffic: Dict, seed: int, span_s: float) -> List[Dict]:
    """Request table [{"idx", "due_s", "prompt_len", "max_new"}]. Open loop:
    `due_s` is the Poisson arrival offset from the schedule's start
    (stratified exponential gaps, permuted by the seed). Closed loop:
    `due_s` is None; client c sends rows c, c + clients, ... in turn."""
    n = request_count(traffic, span_s)
    rng = np.random.default_rng([int(seed), 0x7AF1C])
    plen = rng.permutation(_quantiles(traffic["prompt_len"], n))
    onew = rng.permutation(_quantiles(traffic["output_len"], n))
    if traffic["loop"] == "open":
        u = (np.arange(n) + 0.5) / n
        gaps = rng.permutation(-np.log1p(-u) / float(traffic["rate_per_s"]))
        due = np.cumsum(gaps) - gaps[0]
    else:
        due = [None] * n
    return [{"idx": i, "due_s": None if due[i] is None else float(due[i]),
             "prompt_len": int(plen[i]), "max_new": int(onew[i])}
            for i in range(n)]


def prompt_ids(seed: int, idx: int, length: int, vocab: int) -> List[int]:
    """Token ids of request `idx`: distinct per request (no shared prefix),
    the same in the load generator and in the reference check."""
    rng = np.random.default_rng([int(seed), 0x9E3779, int(idx)])
    return rng.integers(0, vocab, size=int(length)).tolist()

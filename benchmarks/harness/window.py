"""Window arithmetic of the benchmark, free of JAX: what a chunk, a token
and a gap count for. Kept here so that no change to the program moves it."""

from __future__ import annotations

import statistics
from typing import Dict, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Linear-interpolated percentile (q in 0..100); None when empty."""
    vals = sorted(values)
    if not vals:
        return None
    pos = (len(vals) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(vals) - 1)
    return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)


def chunk_rates(chunks: Sequence[Dict], window_s: Optional[float] = None
                ) -> Dict:
    """The rates of a window of chunks, each {"seconds", "tokens"}.
    `tokens_per_s` is the end-to-end `train_tokens_per_s`: the tokens of ALL
    chunks over the whole window (`window_s`, from the first chunk's start
    to the last one's end; the chunks' sum if not given), so a stall counts
    in full. Beside it, for the per-layer readers: `steady_tokens_per_s`
    leaves the single slowest chunk out (one stray stall falls into one
    chunk; one that recurs is in two or more and still counts), and
    `stall_share` is 1 - median chunk time x chunks / window: what a stall
    or any slower-than-median chunk cost the end-to-end rate."""
    if len(chunks) < 2:
        raise ValueError("a window needs at least two chunks")
    secs = [float(c["seconds"]) for c in chunks]
    if window_s is None:
        window_s = sum(secs)
    slowest = max(range(len(secs)), key=secs.__getitem__)
    kept = [c for i, c in enumerate(chunks) if i != slowest]
    return {
        "tokens_per_s": sum(c["tokens"] for c in chunks) / window_s,
        "steady_tokens_per_s": sum(c["tokens"] for c in kept)
        / sum(float(c["seconds"]) for c in kept),
        "slowest_chunk": slowest,
        "window_s": window_s,
        "stall_share": max(0.0, 1.0 - statistics.median(secs) * len(secs)
                           / window_s),
    }


def stream_window(requests: Sequence[Dict], w0: float, w1: float) -> Dict:
    """Client-side accounting of streamed requests against the window
    [w0, w1). Each request: {"due", "sent", "token_times": [...], "asked",
    "done", "error", "cut"}. A token counts where it was RECEIVED inside the
    window, whichever request it belongs to; a gap counts where it ENDS
    inside; a request is attempted where it was DUE inside, timed from when
    it was due, and failed if it was refused, broke, or ended short."""
    tokens = 0
    gaps: List[float] = []
    ttft: List[float] = []
    late: List[float] = []
    attempted = failed = 0
    for r in requests:
        times = r.get("token_times") or []
        tokens += sum(1 for t in times if w0 <= t < w1)
        gaps.extend(b - a for a, b in zip(times, times[1:]) if w0 <= b < w1)
        if not w0 <= r["due"] < w1:
            continue
        attempted += 1
        late.append(r["sent"] - r["due"])
        bad = bool(r.get("error")) or (
            not r.get("cut") and (not r.get("done")
                                  or len(times) != r["asked"]))
        if bad or not times:
            # cut before its first token: it never met any limit
            failed += 1
            continue
        ttft.append(times[0] - r["due"])
    return {"tokens": tokens, "window_s": w1 - w0,
            "tokens_per_s": tokens / (w1 - w0), "gaps_s": gaps,
            "ttft_s": ttft, "late_s": late, "attempted": attempted,
            "failed": failed}

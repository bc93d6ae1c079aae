"""Per-device-kind peak-throughput table: the denominator of the live
`paddle_tpu_mfu` gauge (observability/perfwatch.py).

Numbers are public per-chip peak dense bf16 matmul throughput, HBM
bandwidth and capacity. `ici_bytes_per_s` is a one-direction aggregate
inter-chip figure used only for the collective-time ESTIMATE in the
step-time breakdown — it is labeled an estimate everywhere it
surfaces.

Stdlib-only by contract: perfwatch (imported by core/executor.py at
module load) pulls this in, and tools/obsdump.py loads observability
modules standalone by file path.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

__all__ = ["DevicePeak", "PEAKS", "DEFAULT_PEAK", "lookup", "peak_flops"]


class DevicePeak(NamedTuple):
    """Peak per-chip figures. flops is dense bf16 (the training/serving
    number every MFU in this repo is quoted against)."""
    flops: float             # peak bf16 matmul FLOP/s per chip
    hbm_bytes_per_s: float   # HBM bandwidth
    hbm_bytes: float         # HBM capacity
    ici_bytes_per_s: float   # approx one-direction inter-chip aggregate


# Keyed by a lowercase substring of jax's device_kind ("TPU v5 lite",
# "TPU v4", ...). Order matters: first match wins, so more specific
# kinds precede generic ones.
PEAKS = (
    ("v5 lite", DevicePeak(197e12, 819e9, 16e9, 186e9)),   # v5e
    ("v5e", DevicePeak(197e12, 819e9, 16e9, 186e9)),
    ("v5p", DevicePeak(459e12, 2765e9, 95e9, 600e9)),
    ("v6 lite", DevicePeak(918e12, 1640e9, 32e9, 448e9)),  # v6e / Trillium
    ("v6e", DevicePeak(918e12, 1640e9, 32e9, 448e9)),
    ("v4", DevicePeak(275e12, 1228e9, 32e9, 268e9)),
    ("v3", DevicePeak(123e12, 900e9, 32e9, 70e9)),
    ("v2", DevicePeak(45e12, 700e9, 16e9, 62e9)),
)

# Unknown hardware (CPU test rigs, emulators): a deliberately generous
# 1 TF/s strawman so MFU stays finite and obviously-not-a-TPU numbers
# read as such instead of flattering anyone.
DEFAULT_PEAK = DevicePeak(1e12, 100e9, 8e9, 10e9)

def lookup(device_kind: Optional[str]) -> DevicePeak:
    """Peak figures for a jax device_kind string (case-insensitive
    substring match); DEFAULT_PEAK when unknown."""
    dk = (device_kind or "").lower()
    for key, peak in PEAKS:
        if key in dk:
            return peak
    return DEFAULT_PEAK


def peak_flops(device_kind: Optional[str]) -> float:
    return lookup(device_kind).flops

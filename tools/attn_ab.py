"""A/B attention paths on the real chip: XLA bf16-scores vs legacy flash
vs splash (several block configs), fwd+bwd, at long sequence lengths.

Usage: python tools/attn_ab.py [T ...]   (default 1024 2048 4096 8192)

Timing protocol: each measurement scan-chains ITERS attention fwd+bwd
passes inside ONE jit and divides, so per-dispatch host cost is
amortized; the carry feeds dq back into q so XLA cannot dead-code or
constant-fold any iteration. Numbers are per fwd+bwd pass.
"""

from __future__ import annotations

import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp

ITERS = 16
N_HEADS, HEAD_DIM = 12, 64


def xla_attn(q, k, v, scale, causal):
    from paddle_tpu.ops.pallas.attention import _xla_mha, _merge_causal
    mask = _merge_causal(None, q.shape[1]) if causal else None
    return _xla_mha(q, k, v, mask, scale)


def legacy_flash(q, k, v, scale, causal):
    from jax.experimental.pallas.ops.tpu.flash_attention import flash_attention
    out = flash_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                          v.transpose(0, 2, 1, 3), causal=causal,
                          sm_scale=float(scale))
    return out.transpose(0, 2, 1, 3)


def splash_kernel(T, n_heads, causal, bq, bkv, bqb, bkvb, fused):
    # fresh per call — caching the kernel pytree across traces leaks
    # tracer-wrapped mask-info arrays (UnexpectedTracerError in bwd)
    from jax.experimental.pallas.ops.tpu import splash_attention as sa
    kw = dict(block_q=bq, block_kv=bkv, block_kv_compute=bkv,
              block_q_dkv=bqb, block_kv_dkv=bkvb, block_kv_dkv_compute=bkvb)
    if fused:
        sizes = sa.BlockSizes(use_fused_bwd_kernel=True, **kw)
    else:
        sizes = sa.BlockSizes(block_q_dq=bqb, block_kv_dq=bkvb, **kw)
    one = sa.CausalMask((T, T)) if causal else sa.FullMask((T, T))
    return sa.make_splash_mha(sa.MultiHeadMask([one] * n_heads),
                              head_shards=1, q_seq_shards=1,
                              block_sizes=sizes)


def splash_attn(q, k, v, scale, causal, cfg):
    kernel = splash_kernel(q.shape[1], q.shape[2], causal, *cfg)
    qt = (q * jnp.asarray(scale, q.dtype)).transpose(0, 2, 1, 3)
    out = jax.vmap(kernel)(qt, k.transpose(0, 2, 1, 3),
                           v.transpose(0, 2, 1, 3))
    return out.transpose(0, 2, 1, 3)


def measure(name, fn, B, T, causal):
    scale = 1.0 / math.sqrt(HEAD_DIM)
    shape = (B, T, N_HEADS, HEAD_DIM)
    key = jax.random.key(0)
    ks = jax.random.split(key, 4)
    q, k, v, ct = (jax.random.normal(kk, shape, jnp.bfloat16) for kk in ks)

    def one(q, k, v, ct):
        out, vjp = jax.vjp(lambda a, b, c: fn(a, b, c, scale, causal), q, k, v)
        dq, dk, dv = vjp(ct)
        return dq, out

    @jax.jit
    def chain(q, k, v, ct):
        def body(carry, _):
            q, _ = carry
            dq, out = one(q, k, v, ct)
            # feed dq back so iterations serialize; renormalize to avoid
            # bf16 overflow across 16 chained vjps
            qn = dq / jnp.maximum(jnp.abs(dq).max(), 1e-3).astype(dq.dtype)
            return (qn, out.mean()), None
        (qf, m), _ = jax.lax.scan(body, (q, 0.0), None, length=ITERS)
        return m

    try:
        jax.block_until_ready(chain(q, k, v, ct))  # compile + warm
        t0 = time.perf_counter()
        m = jax.block_until_ready(chain(q, k, v, ct))
        dt = (time.perf_counter() - t0) / ITERS
        print(f"  {name:34s} {1000*dt:8.2f} ms/pass", flush=True)
        return dt
    except Exception as e:
        print(f"  {name:34s} FAIL: {str(e)[:110]}", flush=True)
        return None


def main():
    Ts = [int(a) for a in sys.argv[1:]] or [1024, 2048, 4096, 8192]
    cfgs = {
        "splash-def128": (128, 128, 128, 128, False),
        "splash-512/1024": (512, 1024, 512, 512, False),
        "splash-512/512-fused": (512, 512, 512, 512, True),
        "splash-1024/2048": (1024, 2048, 512, 1024, False),
    }
    for T in Ts:
        B = max(1, 2 ** 25 // (T * T // 128))  # keep score bytes bounded
        B = min(B, 8)
        for causal in (False, True):
            print(f"T={T} B={B} causal={causal}", flush=True)
            measure("xla_bf16", xla_attn, B, T, causal)
            if not causal:
                measure("legacy_flash", legacy_flash, B, T, causal)
            for cname, cfg in cfgs.items():
                if cfg[0] > T or cfg[1] > T:
                    continue
                measure(cname, lambda q, k, v, s, c, _cfg=cfg:
                        splash_attn(q, k, v, s, c, _cfg), B, T, causal)


if __name__ == "__main__":
    from paddle_tpu.core.tpu_lock import tpu_singleflight

    with tpu_singleflight():  # one real chip: serialize vs bench/tools
        main()

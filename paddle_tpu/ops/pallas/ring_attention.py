"""Ring attention: sequence-parallel exact attention over the 'sp' mesh axis.

No reference counterpart (SURVEY §2.5: the reference has no sequence/context
parallelism — its long-sequence story is LoD). TPU-native: each device holds
a sequence chunk of Q/K/V; K/V blocks rotate around the ring via
lax.ppermute while a flash-style online softmax accumulates partial results,
overlapping compute with ICI transfers. Memory per device is O(T/sp), so
context length scales linearly with the ring size.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _shard_map_mesh(mesh):
    """Mesh to hand jax.shard_map: under an active context mesh (set by
    jit/mesh_guard) the ABSTRACT mesh must be passed — a concrete mesh no
    longer matches (jax 0.9 behavior). Shared by every shard_map site in
    the attention stack so an API shift is a one-line fix."""
    abstract = jax.sharding.get_abstract_mesh()
    return abstract if (abstract is not None and not abstract.empty) \
        else mesh


def _block_attn(q, k, v, scale, q_off, k_off, causal, Tq, Tk):
    """Partial (unnormalized) attention of local q against one k/v block.
    q: [B,Tq,N,H]; k,v: [B,Tk,N,H]. Returns (acc, m, l) contributions."""
    logits = jnp.einsum("btnh,bsnh->bnts", q, k).astype(jnp.float32) * scale
    if causal:
        qpos = q_off + jnp.arange(Tq)
        kpos = k_off + jnp.arange(Tk)
        mask = qpos[:, None] >= kpos[None, :]
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    m = jnp.max(logits, axis=-1)                          # [B,N,Tq]
    p = jnp.exp(logits - m[..., None])
    l = jnp.sum(p, axis=-1)                               # [B,N,Tq]
    acc = jnp.einsum("bnts,bsnh->btnh", p.astype(v.dtype), v)
    return acc, m, l


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   mesh: Mesh, axis: str = "sp",
                   causal: bool = True,
                   scale: Optional[float] = None) -> jax.Array:
    """Exact attention with q/k/v sharded [B, T, N, H] on T over `axis`.

    Must run inside jit under `mesh`. Equivalent to full attention; the
    sequence never materializes on one device.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    S = mesh.shape[axis]
    if S == 1:
        from .attention import mha

        return mha(q, k, v, scale=scale, causal=causal)

    perm = [(i, (i + 1) % S) for i in range(S)]

    sm_mesh = _shard_map_mesh(mesh)

    @functools.partial(
        jax.shard_map, mesh=sm_mesh,
        in_specs=(P(None, axis, None, None),) * 3,
        out_specs=P(None, axis, None, None),
        axis_names={axis},
        check_vma=False)
    def run(q, k, v):
        s = jax.lax.axis_index(axis)
        B, Tl, N, H = q.shape
        q_off = s * Tl

        def step(carry, i):
            kv, acc, m, l = carry
            kb, vb = kv
            # block index currently held: it started at (s - i) ... ring hops
            src = (s - i) % S
            k_off = src * Tl
            a, bm, bl = _block_attn(q, kb, vb, scale, q_off, k_off,
                                    causal, Tl, Tl)
            m_new = jnp.maximum(m, bm)
            c_old = jnp.exp(m - m_new)
            c_blk = jnp.exp(bm - m_new)
            acc = (acc * c_old.transpose(0, 2, 1)[..., None]
                   + a.astype(jnp.float32) * c_blk.transpose(0, 2, 1)[..., None])
            l = l * c_old + bl * c_blk
            kv = jax.tree.map(lambda x: jax.lax.ppermute(x, axis, perm),
                              (kb, vb))
            return (kv, acc, m_new, l), None

        acc0 = jnp.zeros((B, Tl, N, H), jnp.float32)
        m0 = jnp.full((B, N, Tl), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, N, Tl), jnp.float32)
        (kv, acc, m, l), _ = jax.lax.scan(
            step, ((k, v), acc0, m0, l0), jnp.arange(S))
        out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
        return out.astype(q.dtype)

    return run(q, k, v)


def ring_splash(q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh,
                s_axis: str = "sp", b_axis: Optional[str] = "dp",
                h_axis: Optional[str] = "tp",
                scale: Optional[float] = None,
                interpret: bool = False) -> jax.Array:
    """Full-mask ring attention whose per-block attention is the tuned
    splash kernel (VERDICT r5 item 4: T>=1024 splash speedups must
    compose with dp/sp/tp).

    The manual region covers EVERY mesh axis (a Mosaic kernel lowers in
    no other; attention._mesh_partitionable) and splits (batch, seq,
    heads), so the pallas kernel sees fully local blocks; the ring
    rotates K/V over `s_axis` via
    ppermute while normalized block outputs are merged through their
    logsumexp residuals (save_residuals=True), which is numerically the
    same online-softmax combine as ring_attention's unnormalized form:
    out = sum_b out_b * exp(lse_b - m) / sum_b exp(lse_b - m).

    Full (bidirectional) masks only — a splash mask is static per trace
    and cannot track the rotating block's causal diagonal; causal ring
    stays on ring_attention's exact XLA blocks. `interpret` is mha()'s
    one interpreter request handed down (attention._interpret_requested)
    — with it CPU-mesh tests execute (not just compile) this path;
    without it the block kernel compiles for the TPU.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    S = mesh.shape[s_axis]
    if S == 1:
        from .attention import mha

        return mha(q, k, v, scale=scale, causal=False)
    perm = [(i, (i + 1) % S) for i in range(S)]
    axes = {s_axis} | {a for a in (b_axis, h_axis)
                       if a and mesh.shape.get(a, 1) > 1}
    spec = P(b_axis if b_axis in axes else None, s_axis,
             h_axis if h_axis in axes else None, None)
    sm_mesh = _shard_map_mesh(mesh)

    @functools.partial(
        jax.shard_map, mesh=sm_mesh, in_specs=(spec,) * 3, out_specs=spec,
        axis_names=set(mesh.axis_names), check_vma=False)
    def run(q, k, v):
        return _ring_splash_local(float(scale), s_axis, S, tuple(perm),
                                  interpret, q, k, v)

    return run(q, k, v)


# --- per-shard ring-splash with a custom VJP -------------------------------
# splash's save_residuals variant has no AD rule ("Higher-order AD not
# supported"), so the ring takes the standard memory-efficient route:
# FORWARD runs the tuned splash kernel per block and merges by logsumexp;
# BACKWARD is the flash-attention backward done blockwise in XLA einsums
# against the saved GLOBAL logsumexp — p_b = exp(q k_b^T * scale - lse)
# is exactly the global softmax restricted to block b, so each block's
# dq/dk/dv contribution is independent; dk/dv accumulators ride around
# the ring WITH their block and are home after S hops. O(Tl^2) score
# blocks, never the full T^2.


def _t(x):  # [B,N,Tl] -> [B,Tl,N,1] broadcast helper
    return x.transpose(0, 2, 1)[..., None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _ring_splash_local(scale, s_axis, S, perm, interpret, q, k, v):
    out, _ = _ring_splash_fwd_impl(scale, s_axis, S, perm, interpret,
                                   q, k, v)
    return out


def _ring_splash_fwd_impl(scale, s_axis, S, perm, interpret, q, k, v):
    from .attention import _splash_block_with_lse

    B, Tl, N, H = q.shape
    qs = q * jnp.asarray(scale, q.dtype)  # splash applies no sm_scale

    def step(carry, _):
        kv, acc, m, w = carry
        kb, vb = kv
        out_b, lse_b = _splash_block_with_lse(qs, kb, vb,
                                              interpret=interpret)
        # merge normalized block outputs by logsumexp weight
        m_new = jnp.maximum(m, lse_b)                 # [B,N,Tl]
        c_old = jnp.exp(m - m_new)
        c_blk = jnp.exp(lse_b - m_new)
        acc = acc * _t(c_old) + out_b.astype(jnp.float32) * _t(c_blk)
        w = w * c_old + c_blk
        kv = jax.tree.map(lambda x: jax.lax.ppermute(x, s_axis, perm),
                          (kb, vb))
        return (kv, acc, m_new, w), None

    acc0 = jnp.zeros((B, Tl, N, H), jnp.float32)
    m0 = jnp.full((B, N, Tl), NEG_INF, jnp.float32)
    w0 = jnp.zeros((B, N, Tl), jnp.float32)
    (kv, acc, m, w), _ = jax.lax.scan(
        step, ((k, v), acc0, m0, w0), None, length=S)
    out = (acc / jnp.maximum(w, 1e-30).transpose(0, 2, 1)[..., None]
           ).astype(q.dtype)
    lse = m + jnp.log(jnp.maximum(w, 1e-30))          # global logsumexp
    return out, (q, k, v, out, lse)


def _ring_splash_fwd(scale, s_axis, S, perm, interpret, q, k, v):
    out, res = _ring_splash_fwd_impl(scale, s_axis, S, perm, interpret,
                                     q, k, v)
    return out, res


def _ring_splash_bwd(scale, s_axis, S, perm, interpret, res, dout):
    q, k, v, out, lse = res
    qf = q.astype(jnp.float32)
    doutf = dout.astype(jnp.float32)
    # delta_i = sum_h dout_ih * out_ih  (rowwise correction term)
    delta = jnp.einsum("btnh,btnh->bnt", doutf, out.astype(jnp.float32))

    def step(carry, _):
        (kb, vb, dkb, dvb), dq = carry
        kbf, vbf = kb.astype(jnp.float32), vb.astype(jnp.float32)
        logits = jnp.einsum("btnh,bsnh->bnts", qf, kbf,
                            preferred_element_type=jnp.float32) * scale
        p = jnp.exp(logits - lse[..., None])          # global softmax slice
        dvb = dvb + jnp.einsum("bnts,btnh->bsnh", p, doutf)
        dp = jnp.einsum("btnh,bsnh->bnts", doutf, vbf)
        ds = p * (dp - delta[..., None])
        dq = dq + jnp.einsum("bnts,bsnh->btnh", ds, kbf) * scale
        dkb = dkb + jnp.einsum("bnts,btnh->bsnh", ds, qf) * scale
        rotated = jax.tree.map(
            lambda x: jax.lax.ppermute(x, s_axis, perm),
            (kb, vb, dkb, dvb))
        return (rotated, dq), None

    B, Tl, N, H = q.shape
    zeros = jnp.zeros((B, Tl, N, H), jnp.float32)
    ((kb, vb, dk, dv), dq), _ = jax.lax.scan(
        step, ((k, v, zeros, zeros), zeros), None, length=S)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_splash_local.defvjp(_ring_splash_fwd, _ring_splash_bwd)

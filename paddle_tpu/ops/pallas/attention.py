"""Fused multi-head attention.

Reference: no TPU counterpart — the reference computes attention from
unfused matmul/softmax ops (e.g. the BERT graph in
inference/tests/api/analyzer_bert_tester.cc). TPU-native: the gate
(_use_splash / _multichip_splash_route) picks a Pallas
kernel route or the XLA einsum+softmax path from the shape, the mesh,
the platform and FLAGS_flash_attention — and the pick is final: a
selected kernel that fails to trace or compile raises, it is never
replaced by another path. The f32 XLA path is semantically identical to
the kernels, so tests run on CPU; for bf16 inputs it stores the T x T
logits in bf16 (f32-accumulated, f32 softmax — halves score-buffer HBM
traffic; see PROFILE.md), which rounds logits to bf16 precision relative
to the kernel's f32 score pipeline.
"""

from __future__ import annotations

import collections
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

# Trace-time gate observability: which attention path was selected.
# Keys: "splash" (single-device / manual region), "splash_shardmap"
# (dp/tp shard_map wrapper), "ring_splash" (sp ring with splash blocks),
# "ring_xla" (sp ring, XLA blocks),
# "xla". Incremented once per mha() trace; reset with GATE_COUNTS.clear()
# in tests/dryruns to assert a path actually engaged (VERDICT r5 item 4).
GATE_COUNTS: collections.Counter = collections.Counter()


def _xla_mha(q, k, v, mask, scale):
    """[B,T,N,H] attention via plain XLA ops (the gate's non-kernel
    route, and the tests' reference).

    bf16 inputs keep the T x T score tensor in bf16 (the einsum still
    accumulates in f32 on the MXU; softmax upcasts to f32 after the
    max-subtraction-safe store) — at BERT shapes the f32 score buffers
    were ~15% of step HBM traffic (measured 172->153 ms fwd+bwd, bs=256
    seq=128 v5e). Wider dtypes keep the fully-f32 path."""
    if q.dtype == jnp.bfloat16:
        # f32 accumulation made explicit; the immediate bf16 cast fuses
        # into the matmul epilogue so only bf16 buffers reach HBM
        logits = jnp.einsum(
            "btnh,bsnh->bnts", q, k,
            preferred_element_type=jnp.float32).astype(jnp.bfloat16) * \
            jnp.asarray(scale, jnp.bfloat16)
        if mask is not None:
            logits = logits + mask.astype(logits.dtype)
        probs = jax.nn.softmax(logits.astype(jnp.float32),
                               axis=-1).astype(v.dtype)
        return jnp.einsum("bnts,bsnh->btnh", probs, v,
                          preferred_element_type=jnp.float32).astype(v.dtype)
    logits = jnp.einsum("btnh,bsnh->bnts", q, k).astype(jnp.float32) * scale
    if mask is not None:
        logits = logits + mask.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bnts,bsnh->btnh", probs, v)


def _platform(q) -> str:
    """Where this computation will actually run. Tracers carry no devices;
    the active mesh (if any) decides — it may be a CPU mesh even when the
    default backend is TPU."""
    if isinstance(q, jax.Array) and not isinstance(q, jax.core.Tracer):
        return next(iter(q.devices())).platform
    from paddle_tpu.parallel.mesh import current_mesh
    m = current_mesh()
    if m is not None:
        return m.devices.flat[0].platform
    return jax.default_backend()


def _flag_mode() -> str:
    from ...core.flags import get_flag

    return str(get_flag("FLAGS_flash_attention")).lower()


def _interpret_requested(q) -> bool:
    """The ONE request for the pallas interpreter:
    FLAGS_flash_attention=splash where the computation will not run on a
    TPU — how CPU-mesh tests and the CPU dry run execute the real kernel
    bodies. mha() asks once and hands the answer down as an argument;
    no kernel wrapper works it out from the platform on its own, and
    without the request an off-chip gate picks the XLA path."""
    return _flag_mode() == "splash" and _platform(q) != "tpu"


def _mesh_partitionable(q) -> bool:
    """A Mosaic kernel cannot be partitioned: under a >1-device mesh it
    lowers only inside a shard_map region that is manual over EVERY mesh
    axis, where shapes are already per-device local. Outside one XLA
    would have to all-gather the operands; inside a partly manual one
    (the 'pp' pipeline keeps tp automatic) the TPU lowering refuses it
    ("Mosaic kernels cannot be automatically partitioned") — which the
    interpreter never checks."""
    from paddle_tpu.parallel.mesh import current_mesh

    m = current_mesh()
    if m is None or m.devices.size == 1:
        return True
    abstract = jax.sharding.get_abstract_mesh()
    return (not abstract.empty
            and set(abstract.manual_axes) == set(abstract.axis_names))


def _multichip_splash_route(q, k, mask, causal):
    """Pick the multi-chip splash composition (VERDICT r5 item 4): under
    a >1-device mesh OUTSIDE a manual region, a bare pallas_call cannot
    be GSPMD-partitioned — but attention itself shards cleanly, so mha
    builds the manual region around the kernel:

    - seq axis unsharded  -> "shardmap": manualize (batch, heads); the
      tuned kernel runs on per-device local blocks, zero collectives.
    - seq axis sharded    -> "ring": full-mask ring attention over sp
      with splash(lse) blocks (ring_attention.ring_splash); causal ring
      keeps the exact XLA blocks ("ring_xla") because a splash mask is
      static per trace and cannot track the rotating KV block's
      diagonal.

    Returns None (no reroute), "shardmap", "ring", or "ring_xla".
    """
    from paddle_tpu.parallel.mesh import current_mesh
    from paddle_tpu.parallel.sharding import current_rules, in_manual_region

    m = current_mesh()
    if m is None or m.devices.size == 1 or q.ndim != 4 or mask is not None:
        return None
    if in_manual_region():
        return None  # already inside a manual region: _use_splash applies
    mode = _flag_mode()
    force = mode == "splash"
    if _platform(q) != "tpu" and not force:
        return None  # interpret-mode execution is explicit opt-in
    if not (force or (mode == "auto" and q.shape[1] >= _SPLASH_MIN_T)):
        return None
    rules = current_rules()

    def _size(ax):
        return m.shape.get(ax, 1) if ax else 1

    b_ax, s_ax, h_ax = (rules.mesh_axis("batch"), rules.mesh_axis("seq"),
                        rules.mesh_axis("heads"))
    B, T, N, H = q.shape
    Tk = k.shape[1]
    sp = _size(s_ax)
    if sp > 1:
        if T % sp or Tk != T:
            return None
        if causal or (T // sp) % 128 or H % 64 or B % _size(b_ax) \
                or N % _size(h_ax):
            return "ring_xla"
        return "ring"
    if _size(b_ax) * _size(h_ax) == 1:
        return None  # replicated: the plain paths handle it
    if B % _size(b_ax) or N % _size(h_ax) or T % 128 or Tk % 128 or H % 64:
        return None
    return "shardmap"


def _shardmap_splash_mha(q, k, v, scale, causal, interpret):
    """Splash composed with dp/tp: attention is independent across batch
    and heads, so splitting those axes feeds the tuned kernel per-device
    local blocks with NO collectives. The region is manual over every
    mesh axis (_mesh_partitionable); q/k/v are replicated over the axes
    the spec does not name."""
    from paddle_tpu.parallel.mesh import current_mesh
    from paddle_tpu.parallel.sharding import current_rules

    m = current_mesh()
    rules = current_rules()
    b_ax, h_ax = rules.mesh_axis("batch"), rules.mesh_axis("heads")
    axes = {a for a in (b_ax, h_ax) if a and m.shape.get(a, 1) > 1}
    spec = jax.sharding.PartitionSpec(
        b_ax if b_ax in axes else None, None,
        h_ax if h_ax in axes else None, None)
    from .ring_attention import _shard_map_mesh

    sm_mesh = _shard_map_mesh(m)

    @functools.partial(jax.shard_map, mesh=sm_mesh, in_specs=(spec,) * 3,
                       out_specs=spec, axis_names=set(m.axis_names),
                       check_vma=False)
    def run(ql, kl, vl):
        return _splash_mha(ql, kl, vl, scale, causal, interpret=interpret)

    return run(q, k, v)


def mha(q: jax.Array, k: jax.Array, v: jax.Array,
        mask: Optional[jax.Array] = None, scale: Optional[float] = None,
        causal: bool = False) -> jax.Array:
    """Multi-head attention over [B, T, N, H] tensors.

    mask: additive [B, 1, 1, T] or [B, N, T, T] (float, -inf style), or None.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    interpret = _interpret_requested(q)
    if _use_splash(q, k, mask, causal):
        out = _splash_mha(q, k, v, scale, causal, interpret=interpret)
        GATE_COUNTS["splash"] += 1
        return out
    route = _multichip_splash_route(q, k, mask, causal)
    if route == "shardmap":
        out = _shardmap_splash_mha(q, k, v, scale, causal, interpret)
        GATE_COUNTS["splash_shardmap"] += 1
        return out
    if route is not None:
        from paddle_tpu.parallel.mesh import current_mesh
        from paddle_tpu.parallel.sharding import current_rules
        from . import ring_attention as ra

        m = current_mesh()
        rules = current_rules()
        if route == "ring":
            out = ra.ring_splash(
                q, k, v, m, s_axis=rules.mesh_axis("seq"),
                b_axis=rules.mesh_axis("batch"),
                h_axis=rules.mesh_axis("heads"), scale=scale,
                interpret=interpret)
            GATE_COUNTS["ring_splash"] += 1
        else:  # "ring_xla": exact ring, XLA blocks
            out = ra.ring_attention(
                q, k, v, m, axis=rules.mesh_axis("seq"),
                causal=causal, scale=scale)
            GATE_COUNTS["ring_xla"] += 1
        return out
    out = _xla_mha(q, k, v, mask if not causal else _merge_causal(mask, q.shape[1]), scale)
    GATE_COUNTS["xla"] += 1
    return out.astype(q.dtype)


def _merge_causal(mask, T):
    cm = jnp.where(jnp.tril(jnp.ones((T, T), jnp.bool_)), 0.0, -1e9)[None, None]
    return cm if mask is None else mask + cm


# ---------------------------------------------------------------------------
# SplashAttention (the production TPU attention kernel shipped with jax)
# ---------------------------------------------------------------------------

# Measured on v5e before PR 21 (fwd+bwd, bf16, 12 heads, head_dim 64):
# splash with the block sizes below beats the XLA bf16-scores path for
# T >= _SPLASH_MIN_T on full (bidirectional) masks and at every causal
# shape.
_SPLASH_MIN_T = 1024


def _use_splash(q, k, mask, causal) -> bool:
    """Splash handles the padding-free (mask=None) and causal cases; an
    arbitrary additive mask takes the XLA path."""
    if q.ndim != 4 or mask is not None:
        return False  # additive masks (padding) take the XLA path
    T, Tk, hd = q.shape[1], k.shape[1], q.shape[-1]
    if T % 128 or Tk % 128 or hd % 64:
        return False
    if not _mesh_partitionable(q):
        return False
    mode = _flag_mode()
    if mode == "splash":
        # explicit opt-in ALSO runs off-TPU, via the pallas interpreter
        # (_interpret_requested) — how CPU-mesh tests execute the real
        # kernel
        return True
    if _platform(q) != "tpu":
        return False
    if mode not in ("auto",):
        return False  # explicit off respected
    return T >= _SPLASH_MIN_T


def _block(cap: int, T: int) -> int:
    """The largest multiple of 128 that divides `T` and is at most `cap`
    (`T` itself when it is smaller)."""
    return max(b for b in range(128, min(cap, T) + 1, 128) if T % b == 0)


def _splash_kernel(Tq: int, Tk: int, n_heads: int, causal: bool,
                   interpret: bool = False, save_residuals: bool = False):
    # NOT cached: the kernel pytree holds mask-info arrays; under a vjp
    # trace those are tracers of that trace, and caching them across
    # traces raises UnexpectedTracerError in the backward pass. Creation
    # is cheap (lazy Full/Causal masks process block-wise in numpy).
    from jax.experimental.pallas.ops.tpu import splash_attention as sa

    # Block sizes tuned on v5e before PR 21 (fwd+bwd, bf16, bs=8):
    # at T=4096 full-mask this config runs 17.0 ms vs 37.4 ms XLA
    # bf16-scores and 114 ms with the jax default all-128 blocks; at
    # T=8192 it is 56 ms where the XLA path cannot even compile (13 GB
    # of score buffers). Big fwd KV blocks amortize the online-softmax
    # rescale; bwd q-blocks stay at 512 to fit dq/dkv accumulators in
    # VMEM.
    # A block has to divide its sequence (the gate lets in every multiple
    # of 128): the tuned size where it does, as at every power of two, else
    # the largest multiple of 128 under it that does (T=3072: KV blocks of
    # 1536).
    bq = _block(1024, Tq)
    bkv = _block(2048, Tk)
    bqb = _block(512, Tq)
    # bwd dkv/dq kv-block: 2048 wins at T>=4096 (17.0 vs 19.0 ms), 1024
    # wins at T<=2048 (6.8 vs 9.2 ms at T=2048)
    bkvb = _block(2048 if Tk >= 4096 else 1024, Tk)
    sizes = sa.BlockSizes(
        block_q=bq, block_kv=bkv, block_kv_compute=bkv,
        block_q_dkv=bqb, block_kv_dkv=bkvb, block_kv_dkv_compute=bkvb,
        block_q_dq=bqb, block_kv_dq=bkvb)
    one = (sa.CausalMask((Tq, Tk)) if causal else sa.FullMask((Tq, Tk)))
    mask = sa.MultiHeadMask([one] * n_heads)
    # interpret=True runs the very same kernel via the pallas CPU
    # interpreter — how the multi-chip compositions are executed (not
    # just compile-checked) on the virtual CPU mesh; save_residuals
    # returns the per-row logsumexp the ring merge needs.
    return sa.make_splash_mha(mask, head_shards=1, q_seq_shards=1,
                              block_sizes=sizes, interpret=interpret,
                              save_residuals=save_residuals)


def _splash_mha(q, k, v, scale, causal, interpret=False):
    B, T, N, H = q.shape
    kernel = _splash_kernel(T, k.shape[1], N, bool(causal),
                            interpret=interpret)
    # kernel wants [N, T, H] per example; scale is folded into q (splash
    # applies no sm_scale itself)
    qt = (q * jnp.asarray(scale, q.dtype)).transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = jax.vmap(kernel)(qt, kt, vt)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def _splash_block_with_lse(q, k, v, interpret=False):
    """One full-mask splash block returning (out, logsumexp) — the ring
    merge's building block. q,k,v: [B,T,N,H] (q pre-scaled); out
    [B,T,N,H], lse [B,N,T] (f32)."""
    B, T, N, H = q.shape
    kernel = _splash_kernel(T, k.shape[1], N, causal=False,
                            interpret=interpret, save_residuals=True)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out, (lse,) = jax.vmap(kernel)(qt, kt, vt)
    return out.transpose(0, 2, 1, 3), lse

"""Fused multi-head attention.

Reference: no TPU counterpart — the reference computes attention from
unfused matmul/softmax ops (e.g. the BERT graph in
inference/tests/api/analyzer_bert_tester.cc). TPU-native: the gate
(_use_kernel with _short_shape / _causal_shape, _use_splash,
_multichip_splash_route) picks a Pallas kernel route or the XLA
einsum+softmax path from the shape, the mesh, the platform and
FLAGS_flash_attention — and the pick is final: a selected kernel that fails
to trace or compile raises, it is never replaced by another path. Four
routes, tried in this order:

- _short_mha (since PR 54; BERT's route: T = 128, no mask, not causal):
  short bidirectional sequences, T in _SHORT_T. One kernel forward and one
  backward over [B, T, heads*head_dim] as it lies; a grid step holds whole
  sequences with all their heads, the [T, T] scores live in VMEM in f32,
  and the backward is one pass (no dq / dkv split). Nothing of shape
  [., T, T] or [B, N, T, H] reaches HBM.
- _causal_mha (since PR 59; a served prompt's route: GPT-2-large's bucket
  of 1024): causal self-attention of whole sequences, T in _CAUSAL_T, q, k
  and v of one shape, no mask. Forward only, over [B, T, heads*head_dim]
  as it lies: a query block walks the key blocks 0 .. its own, the
  diagonal block alone under a mask, nothing above the diagonal read;
  probabilities go to the MXU in v's dtype, sums in f32. Differentiated,
  it IS _splash_mha, forward and backward.
- _splash_mha: the other long sequences (T >= _SPLASH_MIN_T: full masks,
  training's causal passes, heads whose q / k and v differ in width), the
  splash kernel shipped with jax, blocked over T, heads-major operands
  (two transposes a call); under a causal mask its forward blocks are
  small enough that pairs above the diagonal are skipped.
- _xla_mha: everything else (additive masks, odd shapes, off the chip,
  FLAGS_flash_attention=off). The f32 XLA path is semantically identical to
  the kernels, so tests run on CPU; for bf16 inputs it stores the T x T
  logits in bf16 (f32-accumulated, f32 softmax — halves score-buffer HBM
  traffic; see PROFILE.md), which rounds logits to bf16 precision relative
  to the kernels' f32 score pipeline.
"""

from __future__ import annotations

import collections
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Trace-time gate observability: which attention path was selected.
# Keys: "short" (the short kernel, single-device / manual region),
# "short_shardmap" (the same under the dp/tp shard_map wrapper), "causal"
# (the causal prompt kernel, single-device / manual region), "splash"
# (single-device / manual region), "splash_shardmap"
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

    A _short_shape takes the first composition with _short_mha as its
    kernel ("shardmap_short"), at any length the single-device gate admits.

    Returns None (no reroute), "shardmap", "shardmap_short", "ring", or
    "ring_xla".
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
    splash = force or (mode == "auto" and q.shape[1] >= _SPLASH_MIN_T)
    short = mode in ("auto", "splash") and _short_shape(q, k, mask, causal)
    if not (splash or short):
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
        if T % sp or Tk != T or not splash:
            return None
        if causal or (T // sp) % 128 or H % 64 or B % _size(b_ax) \
                or N % _size(h_ax):
            return "ring_xla"
        return "ring"
    if _size(b_ax) * _size(h_ax) == 1:
        return None  # replicated: the plain paths handle it
    if B % _size(b_ax) or N % _size(h_ax) or T % 128 or Tk % 128 or H % 64:
        return None
    if short and (N // _size(h_ax) * H) % 128 == 0:
        return "shardmap_short"  # a device's heads still fill whole tiles
    return "shardmap" if splash else None


def _shardmap_splash_mha(q, k, v, scale, causal, interpret, kernel=None):
    """A kernel route (`kernel`: _splash_mha, or _short_mha) composed with
    dp/tp: attention is independent across batch
    and heads, so splitting those axes feeds the tuned kernel per-device
    local blocks with NO collectives. The region is manual over every
    mesh axis (_mesh_partitionable); q/k/v are replicated over the axes
    the spec does not name."""
    kernel = kernel or _splash_mha
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
        return kernel(ql, kl, vl, scale, causal, interpret=interpret)

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
    short = _short_shape(q, k, mask, causal)
    if short and _use_kernel(q):
        out = _short_mha(q, k, v, scale, interpret=interpret)
        GATE_COUNTS["short"] += 1
        return out
    if _causal_shape(q, k, v, mask, causal) and _use_kernel(q):
        out = _causal_mha(q, k, v, scale, interpret)
        GATE_COUNTS["causal"] += 1
        return out
    if not short and _use_splash(q, k, mask, causal):
        out = _splash_mha(q, k, v, scale, causal, interpret=interpret)
        GATE_COUNTS["splash"] += 1
        return out
    route = _multichip_splash_route(q, k, mask, causal)
    if route == "shardmap_short":
        out = _shardmap_splash_mha(q, k, v, scale, causal, interpret,
                                   kernel=_short_mha)
        GATE_COUNTS["short_shardmap"] += 1
        return out
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

# Measured on v5e (fwd+bwd, bf16, bs 8, 12 heads, head_dim 64): splash with
# the block sizes below beats the XLA bf16-scores path for T >=
# _SPLASH_MIN_T on full (bidirectional) masks (before PR 21: 17.0 against
# 37.4 ms at T = 4096) and under a causal mask (PR 59: 2.13 against 3.00 ms
# at T = 1024, 19.00 against 40.96 at T = 4096). Under `auto` a shorter
# sequence, causal or not, keeps the XLA route.
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

    # FULL masks: block sizes tuned on v5e before PR 21 (fwd+bwd, bf16,
    # bs=8): at T=4096 this config runs 17.0 ms vs 37.4 ms XLA bf16-scores
    # and 114 ms with the jax default all-128 blocks; at T=8192 it is 56 ms
    # where the XLA path cannot even compile (13 GB of score buffers). Big
    # fwd KV blocks amortize the online-softmax rescale; bwd q-blocks stay
    # at 512 to fit dq/dkv accumulators in VMEM.
    # A block has to divide its sequence (the gate lets in every multiple
    # of 128): the tuned size where it does, as at every power of two, else
    # the largest multiple of 128 under it that does (T=3072: KV blocks of
    # 1536).
    bq, bkv = _block(1024, Tq), _block(2048, Tk)
    bkvc = bkv
    if causal:
        # CAUSAL masks, the forward's three sizes (PR 59). Splash skips a
        # (query block, key block) pair only where the mask is empty over
        # the whole pair, and at (1024, 2048) a prompt of 1024 or 2048 was
        # ONE column of pairs: every score above the diagonal computed and
        # masked. Forward alone on a v5e, bf16, 36 calls in one jit, us a
        # call at (1024, 2048, 2048) -> (512, 512, 512): [1, 1024, 20, 64]
        # 95.5 -> 77.9; [1, T, 32, 192 | 128] at T = 1024 / 2048 / 3072
        # 192 -> 160, 725 -> 490, 1370 -> 1070; at T = 4096 2151 -> 1924,
        # and 1777 at (1024, 1024, 512), which 4096 and longer take. Smaller
        # blocks skip more and lose it to the grid step: (256, 256, 256)
        # reads 132 / 240 / 823 / 2059 / 3949. Fwd+bwd at bs 8, 12 heads of
        # 64 (the backward's sizes as they were): T = 1024 2.16 -> 2.13 ms
        # (XLA 3.00), T = 4096 20.07 -> 19.00 (XLA 40.96).
        cap = 1024 if min(Tq, Tk) >= 4096 else 512
        bq, bkv = _block(cap, Tq), _block(cap, Tk)
        bkvc = _block(512, bkv)
    bqb = _block(512, Tq)
    # bwd dkv/dq kv-block: 2048 wins at T>=4096 (17.0 vs 19.0 ms), 1024
    # wins at T<=2048 (6.8 vs 9.2 ms at T=2048)
    bkvb = _block(2048 if Tk >= 4096 else 1024, Tk)
    sizes = sa.BlockSizes(
        block_q=bq, block_kv=bkv, block_kv_compute=bkvc,
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


# ---------------------------------------------------------------------------
# Short bidirectional attention: scores stay in VMEM, heads stay in the lanes
# ---------------------------------------------------------------------------

# Sequence lengths the short kernel takes: those timed on a v5e against
# _xla_mha alone (fwd+bwd, bf16, 32768 tokens of 12 heads of 64, 12 calls
# in one jit: 16.7 / 20.7 / 30.9 ms where XLA's take 43 / 58 / 93; PERF.md
# section 6, PR 54).
_SHORT_T = (128, 256, 512)
# One grid step's block of an operand: one 128-lane tile of whole sequences,
# up to this many bytes (8 sequences of 128 tokens in bf16).
_SHORT_TILE_BYTES = 1 << 18
_SHORT_VMEM_LIMIT = 64 << 20
# Sequences a loop iteration of the kernel (_each_sequence): 1 / 2 / 4 / 8
# read 24.5 / 21.3 / 16.7 / 14.8 ms for the 12 calls, and cost a boot 0.6 /
# 0.9 / 2.0 / 3.7 s of lowering (the kernel's text grows with them).
_SHORT_SEQS = 4

_NT = (((1,), (1,)), ((), ()))  # a . b^T
_TN = (((0,), (0,)), ((), ()))  # a^T . b


def _short_shape(q, k, mask, causal) -> bool:
    """What the short kernel can take, from shapes alone."""
    if q.ndim != 4 or mask is not None or causal or q.shape != k.shape:
        return False
    _, T, N, H = q.shape
    return T in _SHORT_T and H in (64, 128) and (N * H) % 128 == 0


def _use_kernel(q) -> bool:
    """A _short_shape goes to _short_mha, and a _causal_shape to
    _causal_mha, on a TPU (or where FLAGS_flash_attention=splash asks for
    the interpreter); `off` keeps the XLA route."""
    mode = _flag_mode()
    if mode != "splash" and (mode != "auto" or _platform(q) != "tpu"):
        return False
    return _mesh_partitionable(q)


def _short_tile(B: int, T: int, D: int, itemsize: int) -> int:
    """Sequences a grid step: what _SHORT_TILE_BYTES hold of [T, D] rows,
    a whole number of _each_sequence's loop iterations."""
    Bt = max(1, min(B, _SHORT_TILE_BYTES // (T * D * itemsize)))
    return Bt - Bt % min(Bt, _SHORT_SEQS)


def _own_lanes(T: int, head_dim: int):
    """For each head of a 128-lane tile, the lanes that hold it ([None]:
    the tile is one head)."""
    if head_dim == 128:
        return [None]
    lane = jax.lax.broadcasted_iota(jnp.int32, (T, 128), 1)
    return [lane // head_dim == j for j in range(128 // head_dim)]


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _each_sequence(Bt: int, one):
    """`one(b)` for every sequence of a block, _SHORT_SEQS of them a loop
    iteration: independent chains in one basic block, which the scheduler
    interleaves (the kernel is bound by a head's dependent chain, not by
    its bytes: PERF.md section 6, PR 54)."""
    seqs = min(Bt, _SHORT_SEQS)
    assert Bt % seqs == 0, (Bt, seqs)

    def body(i, carry):
        for u in range(seqs):
            one(i * seqs + u)
        return carry

    jax.lax.fori_loop(0, Bt // seqs, body, None)


def _only(own, x):
    return x if own is None else jnp.where(own, x, jnp.zeros_like(x))


def _short_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale,
                      head_dim):
    """One 128-lane tile (two 64-wide heads, or one of 128) of a block of
    whole sequences. Scores are held TRANSPOSED, [Tk, Tq]: the softmax's
    reductions run down the sublanes and the row statistics come out as
    rows [1, Tq], which is how `lse` is stored. A head is taken out of its
    tile by zeroing the other head's lanes of K and V (the MXU pass is
    half filled either way), so the context is written a whole tile at a
    time."""
    Bt, T, _ = q_ref.shape
    owns = _own_lanes(T, head_dim)

    def one(b):
        q, k, v = q_ref[b], k_ref[b], v_ref[b]
        ctx = None
        for j, own in enumerate(owns):
            st = _dot(_only(own, k), q, _NT) * scale            # [Tk, Tq]
            m = jnp.max(st, axis=0, keepdims=True)
            e = jnp.exp(st - m)
            l = jnp.sum(e, axis=0, keepdims=True)
            pt = (e * (1.0 / l)).astype(v.dtype)
            c = _dot(pt, _only(own, v), _TN)                    # [Tq, 128]
            ctx = c if ctx is None else ctx + c
            lse_ref[b, 0, pl.ds(j, 1), :] = m + jnp.log(l)
        o_ref[b] = ctx.astype(o_ref.dtype)

    _each_sequence(Bt, one)


def _short_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                      dq_ref, dk_ref, dv_ref, *, scale, head_dim):
    """The whole backward of a tile's heads in one grid step: T fits, so
    there is no dq / dkv split and nothing accumulates across steps.
    Transposed scores as in the forward; delta = rowsum(P * dP), which
    equals rowsum(dO * O) and wants no read of the context."""
    Bt, T, _ = q_ref.shape
    owns = _own_lanes(T, head_dim)

    def one(b):
        q, k, v, do = q_ref[b], k_ref[b], v_ref[b], do_ref[b]
        dq = dk = dv = None
        for j, own in enumerate(owns):
            qm, km, dom = _only(own, q), _only(own, k), _only(own, do)
            lse = lse_ref[b, 0, pl.ds(j, 1), :]                 # [1, Tq]
            pt = jnp.exp(_dot(km, q, _NT) * scale - lse)        # [Tk, Tq]
            dpt = _dot(v, dom, _NT)                             # [Tk, Tq]
            delta = jnp.sum(pt * dpt, axis=0, keepdims=True)
            dst = (pt * (dpt - delta) * scale).astype(q.dtype)
            dv_j = _dot(pt.astype(do.dtype), dom)               # [Tk, 128]
            dk_j = _dot(dst, qm)                                # [Tk, 128]
            dq_j = _dot(dst, km, _TN)                           # [Tq, 128]
            dq = dq_j if dq is None else dq + dq_j
            dk = dk_j if dk is None else dk + dk_j
            dv = dv_j if dv is None else dv + dv_j
        dq_ref[b] = dq.astype(dq_ref.dtype)
        dk_ref[b] = dk.astype(dk_ref.dtype)
        dv_ref[b] = dv.astype(dv_ref.dtype)

    _each_sequence(Bt, one)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _short_call(operands, lse, heads, scale, interpret):
    """One pallas_call over (blocks of whole sequences) x (128-lane tiles
    of their heads). Forward (`lse` None): `operands` q, k, v -> the
    context and `lse` [B, tiles, heads a tile, T]; backward: q, k, v, dO
    and `lse` -> dq, dk, dv; every other array [B, T, heads*head_dim].
    The tile is the grid's, not the kernel's, so the kernel's text is one
    tile's; and the call is jitted, so that a model's layers, which make
    the same call, share ONE trace of the kernel and one lowering (12
    layers x 2 kernels, each with all six tiles in its text, were 28 s of
    a boot's lowering)."""
    forward = lse is None
    kernel, name, n_out, passes = (
        (_short_fwd_kernel, "short_mha_fwd", 1, 2) if forward
        else (_short_bwd_kernel, "short_mha_bwd", 3, 5))
    B, T, D = operands[0].shape
    dtype = operands[0].dtype
    tiles, per = D // 128, heads * 128 // D
    Bt = _short_tile(B, T, 128, dtype.itemsize)
    rows = pl.BlockSpec((Bt, T, 128), lambda i, t: (i, 0, t))
    stats = pl.BlockSpec((Bt, 1, per, T), lambda i, t: (i, t, 0, 0))
    out_shape = [jax.ShapeDtypeStruct((B, T, D), dtype)] * n_out
    out_specs = [rows] * n_out
    in_specs = [rows] * len(operands)
    if forward:
        out_shape.append(
            jax.ShapeDtypeStruct((B, tiles, per, T), jnp.float32))
        out_specs.append(stats)
    else:
        operands = (*operands, lse)
        in_specs.append(stats)
    moved = (len(in_specs) + len(out_specs)) * B * T * D * dtype.itemsize
    return pl.pallas_call(
        functools.partial(kernel, scale=scale, head_dim=D // heads),
        grid=(pl.cdiv(B, Bt), tiles), in_specs=in_specs,
        out_specs=out_specs, out_shape=out_shape, name=name,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_SHORT_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=passes * 2 * B * heads * T * T * (D // heads),
            transcendentals=B * heads * T * T, bytes_accessed=moved),
    )(*operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _short_attention(q, k, v, heads, scale, interpret):
    return _short_attention_fwd(q, k, v, heads, scale, interpret)[0]


def _short_attention_fwd(q, k, v, heads, scale, interpret):
    out, lse = _short_call((q, k, v), None, heads, scale, interpret)
    return out, (q, k, v, lse)


def _short_attention_bwd(heads, scale, interpret, res, dout):
    q, k, v, lse = res
    return tuple(_short_call((q, k, v, dout.astype(q.dtype)), lse, heads,
                             scale, interpret))


_short_attention.defvjp(_short_attention_fwd, _short_attention_bwd)


def _short_mha(q, k, v, scale, causal=False, interpret=False):
    """[B,T,N,H] short bidirectional attention through the fused kernel:
    operands go in as [B, T, N*H], as they lie (the reshape is free), and
    the context and all three gradients come back the same way; nothing of
    shape [., T, T] or [B, N, T, H] reaches HBM, forward or backward."""
    assert not causal
    B, T, N, H = q.shape
    flat = (B, T, N * H)
    out = _short_attention(q.reshape(flat), k.reshape(flat), v.reshape(flat),
                           N, float(scale), bool(interpret))
    return out.reshape(q.shape)


# ---------------------------------------------------------------------------
# Causal attention of whole prompts: only the blocks on and under the diagonal
# ---------------------------------------------------------------------------

# Sequence lengths the causal kernel takes: those it was timed at on a v5e
# (forward, bf16, 36 calls in one jit, us a call at T = 1024 / 2048 / 3072 /
# 4096: the kernel, splash at its causal blocks below, splash with the full
# mask's blocks as every causal call had them before PR 59, the last two
# with their head-view copies): [1, T, 20, 64] 63 / 186 / 371 / 618, 78 /
# 231 / 466 / 749, 95 / 341 / 625 / 999; [1, T, 16, 128] 55 / 162 / 324 /
# 538, 65 / 189 / 381 / 604, 77 / 273 / 503 / 804 (PERF.md section 6, PR 59).
_CAUSAL_T = (1024, 2048, 3072, 4096)
# Query rows and key rows of a block: a query block walks the key blocks
# 0 .. its own. Timed at 128 / 256 / 512 rows: 158 / 100 / 66 us a call at
# [1, 1024, 20, 64], where 36 of 64, 10 of 16 and 3 of 4 pairs are kept: a
# block's running maximum and the rescaling of its sums are [block, 128]
# passes whatever the block holds, and the smaller block loses more to them
# than it saves above the diagonal.
_CAUSAL_BLOCK = 512
# What a masked score reads (finite: exp(mask - mask) must not be a NaN).
_CAUSAL_MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)


def _causal_shape(q, k, v, mask, causal) -> bool:
    """What the causal kernel can take, from shapes alone: the short
    kernel's rule with `causal` in place of "not causal" and its own
    lengths."""
    if q.ndim != 4 or mask is not None or not causal \
            or not q.shape == k.shape == v.shape:
        return False
    _, T, N, H = q.shape
    return T in _CAUSAL_T and H in (64, 128) and (N * H) % 128 == 0


def _causal_fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                       scale, head_dim):
    """One query block of one 128-lane tile (two 64-wide heads, or one of
    128) of one sequence, against the tile's K and V `[T, 128]`, which stay
    in VMEM while the tile's query blocks go by. Key blocks under the
    diagonal go through an online softmax with no mask; the diagonal block
    alone is compared with an iota; nothing above it is read. A head is
    taken out of its tile by zeroing the other head's lanes of Q (the MXU
    pass is half filled either way); its probabilities meet all 128 lanes
    of V, and the context keeps each head's own lanes. The running sum of a
    row's probabilities is kept a lane class at a time, `[block, 128]`, and
    reduced across lanes once, at the end (66 -> 63 us a call)."""
    block = q_ref.shape[1]
    i = pl.program_id(2)
    owns = _own_lanes(block, head_dim)
    q = q_ref[0]
    # the scale goes into Q, as _splash_mha folds it (exact for heads of 64)
    q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    qs = [_only(own, q) for own in owns]
    m_ref[...] = jnp.full_like(m_ref, _CAUSAL_MASKED)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def one_block(j, diagonal):
        rows = pl.ds(pl.multiple_of(j * block, block), block)
        k, v = k_ref[0, rows, :], v_ref[0, rows, :]
        ctx = alphas = None
        for h, own in enumerate(owns):
            s = _dot(qs[h], k, _NT)                           # [block, block]
            if diagonal:
                row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                s = jnp.where(row >= col, s, _CAUSAL_MASKED)
            m_prev, l_prev = m_ref[h], l_ref[h]               # [block, 128]
            m_next = jnp.maximum(m_prev, s.max(axis=-1)[:, None])
            p = jnp.exp(s - jnp.tile(m_next, (1, block // 128)))
            alpha = jnp.exp(m_prev - m_next)
            m_ref[h] = m_next
            l_ref[h] = alpha * l_prev + sum(
                p[:, t:t + 128] for t in range(0, block, 128))
            c = _dot(p.astype(v.dtype), v)                    # [block, 128]
            ctx = c if ctx is None else jnp.where(own, c, ctx)
            alphas = alpha if alphas is None else jnp.where(own, alpha,
                                                            alphas)
        acc_ref[...] = alphas * acc_ref[...] + ctx

    jax.lax.fori_loop(0, i, lambda j, c: one_block(j, False), None)
    one_block(i, True)
    inv = None
    for h, own in enumerate(owns):
        r = jnp.broadcast_to(1.0 / l_ref[h].sum(axis=-1, keepdims=True),
                             acc_ref.shape)
        inv = r if inv is None else jnp.where(own, r, inv)
    o_ref[0] = (acc_ref[...] * inv).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _causal_call(q, k, v, heads, scale, interpret):
    """One pallas_call over (sequence, 128-lane tile of its heads, query
    block), every array `[B, T, heads*head_dim]`. K's and V's block is the
    tile's whole `[T, 128]` and its index does not name the query block, so
    it is fetched once a tile. Jitted, as _short_call is: a model's layers
    share one trace of the kernel and one lowering."""
    B, T, D = q.shape
    block = _CAUSAL_BLOCK
    per = heads * 128 // D
    rows = pl.BlockSpec((1, block, 128), lambda b, t, i: (b, i, t))
    whole = pl.BlockSpec((1, T, 128), lambda b, t, i: (b, 0, t))
    stat = pltpu.VMEM((per, block, 128), jnp.float32)
    pairs = T // block * (T // block + 1) // 2
    return pl.pallas_call(
        functools.partial(_causal_fwd_kernel, scale=scale,
                          head_dim=D // heads),
        grid=(B, D // 128, T // block), in_specs=[rows, whole, whole],
        out_specs=rows, out_shape=jax.ShapeDtypeStruct((B, T, D), q.dtype),
        scratch_shapes=[stat, stat,
                        pltpu.VMEM((block, 128), jnp.float32)],
        name="causal_mha_fwd", interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_SHORT_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=4 * B * heads * pairs * block * block * (D // heads),
            transcendentals=B * heads * pairs * block * block,
            bytes_accessed=4 * B * T * D * q.dtype.itemsize),
    )(q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _causal_mha(q, k, v, scale, interpret=False):
    """[B,T,N,H] causal attention of whole prompts through the kernel above:
    operands go in as [B, T, N*H], as they lie, and the context comes back
    the same way. Forward only: under differentiation both passes are
    _splash_mha's (the rules below), so an inference trace takes this
    kernel, a `jax.grad` trace takes splash as it did before, and no
    caller says which it is."""
    B, T, N, H = q.shape
    flat = (B, T, N * H)
    out = _causal_call(q.reshape(flat), k.reshape(flat), v.reshape(flat),
                       N, float(scale), bool(interpret))
    return out.reshape(q.shape)


def _causal_grad_fwd(q, k, v, scale, interpret):
    return jax.vjp(lambda q, k, v: _splash_mha(q, k, v, scale, True,
                                               interpret=interpret), q, k, v)


def _causal_grad_bwd(scale, interpret, vjp, dout):
    return vjp(dout)


_causal_mha.defvjp(_causal_grad_fwd, _causal_grad_bwd)

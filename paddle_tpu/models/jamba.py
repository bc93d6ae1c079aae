"""AI21 Jamba (`model_type` `jamba`, ai21labs/AI21-Jamba2-3B `config.json`):
a decoder whose layers are a mixer then a feed-forward, the mixer attention
in layer `l` where `l % attn_period == attn_offset` and a Mamba-1 mixer
elsewhere (26 of 28 at the published 14 and 7). `num_experts` is 1: every
feed-forward is the dense SwiGLU. No bias but the two named; eps 1e-6.

    h = h + mixer(RMSNorm_in(h));  h = h + W_d (silu(W_g y) * W_u y),
                                   y = RMSNorm_ff(h)

Mamba-1 mixer (inner = expand x hidden, N state lanes, R = dt_rank, K taps):

    [x | z]     = y W_in                       hidden -> inner | inner
    x           = silu(conv_K(x) + b_conv)     depthwise, causal, bias
    [r | B | C] = x W_x                        inner -> R | N | N
    r, B, C     = RMSNorm_dt(r), RMSNorm_B(B), RMSNorm_C(C)   a gain a lane
    dt          = softplus(r W_dt + b_dt)      R -> inner, bias, float32
    A           = -exp(A_log)                  a value a (state lane, channel)
    S_t[n, c]   = exp(dt_t[c] A[n, c]) S_{t-1}[n, c] + dt_t[c] x_t[c] B_t[n]
    y_t[c]      = sum_n S_t[n, c] C_t[n] + D[c] x_t[c]
    out         = (y * silu(z)) W_out          inner -> hidden

the SELECTIVE recurrence of `ops/ssm.py` (a decay a channel and a state
lane, not Mamba-2's scalar a head). `A_log` is kept `[N, inner]`, the
published `[inner, N]` with the channels in the lanes: a state row `[N,
inner]` is then whole tiles. Attention: `heads` query heads over ONE
key/value head (multi-query), causal softmax at 1/sqrt(head_dim), NO
position encoding: the Mamba layers carry order. A final RMSNorm and the
embedding read as the output head (tied).

What a sequence keeps between tokens: K and V a token in the attention
layers (the paged pools, 2 of 28 layers, 128 lanes each), and in every
Mamba layer the convolution's tail (the last K-1 inputs, `inner` wide, in
the served dtype) and the state `[N, inner]` in float32, in row pools
(`JambaServe.state_pools`). Behind `models/decoder.py` a layer is two blocks
of the pattern: `ME` a Mamba layer, `*E` an attention layer.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..ops import ssm as _ssm
from ..ops.pallas import ssm_scan as _scan, ssm_update as _update
from ..parallel.sharding import shard
from . import decoder as _decoder
from .common import Params, rms_norm as _rms_norm
from .joyai import NORM_STD
# why a seeded attention score is drawn at a deviation of 5 and not 1
from .nemotron_h import ATTN_SCORE_STD

KINDS = {"M": "mamba", "*": "attn", "E": "mlp"}


@dataclasses.dataclass
class JambaConfig:
    vocab_size: int = 65536
    hidden: int = 2560
    n_layers: int = 28          # `num_hidden_layers`
    attn_period: int = 14       # `attn_layer_period`
    attn_offset: int = 7        # `attn_layer_offset`
    mlp_dim: int = 8192         # `intermediate_size`
    # M
    expand: int = 2             # `mamba_expand`
    ssm_state: int = 16         # `mamba_d_state`
    dt_rank: int = 160          # `mamba_dt_rank`
    conv_kernel: int = 4        # `mamba_d_conv`
    dt_min: float = 0.001       # Mamba-1's published draw of dt
    dt_max: float = 0.1
    dt_floor: float = 1e-4
    # *
    heads: int = 20
    kv_heads: int = 1
    head_dim: int = 128
    max_len: int = 262144
    rms_eps: float = 1e-6
    dtype: str = "bfloat16"

    @staticmethod
    def tiny() -> "JambaConfig":
        return JambaConfig(
            vocab_size=512, hidden=64, n_layers=4, attn_period=2,
            attn_offset=1, mlp_dim=128, ssm_state=16, dt_rank=8,
            heads=5, kv_heads=1, head_dim=16, max_len=128)

    @property
    def pattern(self) -> str:
        """A character a block for `decoder.mixer_layers`: a layer is its
        mixer (`*` attention, `M` Mamba) then the SwiGLU (`E`)."""
        return "".join(
            "*E" if l % self.attn_period == self.attn_offset else "ME"
            for l in range(self.n_layers))

    @property
    def inner(self) -> int:
        return self.expand * self.hidden

    def count(self, kind: str) -> int:
        return self.pattern.count(kind)

    def serve_model(self) -> "JambaServe":
        """This configuration behind the interface the decode engine
        drives (models/decoder.py)."""
        return JambaServe(self)


_TOP_AXES = {"wte.w": ("vocab", "embed"), "ln_f.scale": (None,)}
_KIND_AXES = {
    "M": {"norm.scale": (None,), "in_proj": ("embed", "mlp"),
          "conv_w": (None, None), "conv_b": (None,),
          "x_proj": ("mlp", None), "dt_norm": (None,), "b_norm": (None,),
          "c_norm": (None,), "dt_proj": (None, "mlp"), "dt_bias": (None,),
          "A_log": (None, None), "D": (None,),
          "out_proj": ("mlp", "embed")},
    "*": {"norm.scale": (None,), "wq": ("embed", "heads"),
          "wk": ("embed", None), "wv": ("embed", None),
          "wo": ("heads", "embed")},
    "E": {"norm.scale": (None,), "w_gate": ("embed", "mlp"),
          "w_up": ("embed", "mlp"), "w_down": ("mlp", "embed")},
}


def init_layer(rng: jax.Array, cfg: JambaConfig, l, kind=None) -> Params:
    """Block `l` (counted over the whole pattern) of `init(rng, cfg)`
    alone, in float32 and under the prefix `blk.`: every block has a key of
    its own, so that the float32 set (12.1 GB at the published widths) is
    never whole on the device. Matrices normal at 1/sqrt(fan_in), every
    block's residual output (`out_proj`, `wo`, `w_down`) scaled by
    1/sqrt(blocks); norm gains 1 + `NORM_STD` x normal; Mamba-1's own draws
    for its per-channel parameters: `A_log` = log(1..N) a channel, dt
    log-uniform in [dt_min, dt_max], floored, through the inverse softplus
    into `dt_bias`, D = 1."""
    kind = kind or cfg.pattern[l]
    H = cfg.hidden
    keys = iter(jax.random.split(
        jax.random.fold_in(jax.random.fold_in(rng, 1), l), 12))

    def normal(shape, scale):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    def gains(n):
        return 1.0 + normal((n,), NORM_STD)

    a = math.sqrt(1.0 / H)
    res = 1.0 / math.sqrt(len(cfg.pattern))
    lp = {"blk.norm.scale": gains(H)}
    if kind == "M":
        C, N, R, K = cfg.inner, cfg.ssm_state, cfg.dt_rank, cfg.conv_kernel
        dt = jnp.exp(jax.random.uniform(next(keys), (C,), jnp.float32)
                     * (math.log(cfg.dt_max) - math.log(cfg.dt_min))
                     + math.log(cfg.dt_min))
        dt = jnp.maximum(dt, cfg.dt_floor)
        lp.update({
            "blk.in_proj": normal((H, 2 * C), a),
            "blk.conv_w": normal((K, C), math.sqrt(1.0 / K)),
            "blk.conv_b": normal((C,), 0.1),
            "blk.x_proj": normal((C, R + 2 * N), math.sqrt(1.0 / C)),
            "blk.dt_norm": gains(R), "blk.b_norm": gains(N),
            "blk.c_norm": gains(N),
            "blk.dt_proj": normal((R, C), math.sqrt(1.0 / R)),
            "blk.dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "blk.A_log": jnp.broadcast_to(jnp.log(jnp.arange(
                1, N + 1, dtype=jnp.float32))[:, None], (N, C)),
            "blk.D": jnp.ones((C,), jnp.float32),
            "blk.out_proj": normal((C, H), math.sqrt(1.0 / C) * res),
        })
    elif kind == "*":
        q, kv = cfg.heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
        # q and k each at sqrt(ATTN_SCORE_STD) of the plain draw
        peak = math.sqrt(ATTN_SCORE_STD)
        lp.update({
            "blk.wq": normal((H, q), a * peak),
            "blk.wk": normal((H, kv), a * peak),
            "blk.wv": normal((H, kv), a),
            "blk.wo": normal((q, H), math.sqrt(1.0 / q) * res),
        })
    elif kind == "E":
        M = cfg.mlp_dim
        lp.update({
            "blk.w_gate": normal((H, M), a), "blk.w_up": normal((H, M), a),
            "blk.w_down": normal((M, H), math.sqrt(1.0 / M) * res),
        })
    else:
        raise ValueError(f"unknown block kind {kind!r} in the pattern")
    return lp


def init_top(rng: jax.Array, cfg: JambaConfig) -> Params:
    """The parameters outside the blocks, in float32: the embedding, which
    is the head too, and the final norm."""
    k_emb, k_norm = jax.random.split(jax.random.fold_in(rng, 0), 2)
    V, H = cfg.vocab_size, cfg.hidden
    return {
        "wte.w": jax.random.normal(k_emb, (V, H), jnp.float32) * 0.02,
        "ln_f.scale": 1.0 + NORM_STD * jax.random.normal(
            k_norm, (H,), jnp.float32),
    }


def kind_layers(cfg: JambaConfig, kind: str):
    """The pattern's positions of the blocks of `kind`, in order."""
    return [l for l, c in enumerate(cfg.pattern) if c == kind]


def init(rng: jax.Array, cfg: JambaConfig, dtype=jnp.float32
         ) -> Tuple[Params, Dict]:
    """The blocks of a kind stacked under the kind's prefix (`mamba.`,
    `attn.`, `mlp.`), made one block and one tensor at a time and cast to
    `dtype` as each is made."""
    params = {k: v.astype(dtype) for k, v in init_top(rng, cfg).items()}
    axes = dict(_TOP_AXES)
    for kind, prefix in KINDS.items():
        where = jnp.asarray(kind_layers(cfg, kind), jnp.int32)
        if not where.size:
            continue
        for name, ax in _KIND_AXES[kind].items():
            params[f"{prefix}.{name}"] = jax.lax.map(
                lambda l: init_layer(rng, cfg, l, kind)["blk." + name]
                .astype(dtype), where)
            axes[f"{prefix}.{name}"] = ("layer",) + ax
    return params, axes


def block_params(params: Params, kind: str, i: int) -> Params:
    """Block `i` of its kind out of the flat set, under `blk.`."""
    prefix = KINDS[kind] + "."
    return {"blk." + k[len(prefix):]: v[i] for k, v in params.items()
            if k.startswith(prefix)}


# Layer scopes: `ln`; a Mamba block's `ssm` (holding `ssm_in`: the input
# projection, and after the convolution the projection to dt, B and C with
# its norms; `conv`; `scan`: the recurrence; `state_read` / `state_write`;
# `ssm_out`: the gate and the output projection); an attention block's
# `qkv`, `kv_write`, `attention`, `proj`; `mlp`; `head`.
# tests/test_jamba.py holds the list.


def _gained(x, gain, eps):
    """RMSNorm over the last axis in float32, a learned gain a lane."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * gain.astype(jnp.float32)


@jax.named_scope("ssm_in")
def _split_in(lp, y, cfg: JambaConfig):
    """(x BEFORE the convolution, z), `[..., inner]` each."""
    xz = y @ lp["blk.in_proj"].astype(y.dtype)
    return xz[..., :cfg.inner], xz[..., cfg.inner:]


@jax.named_scope("ssm_in")
def _selection(lp, x, cfg: JambaConfig):
    """The convolved x `[..., inner]` -> (dt `[..., inner]` after its
    softplus, B and C `[..., N]`), all float32: the low-rank dt and the two
    state vectors out of one projection, each through its own norm."""
    R, N = cfg.dt_rank, cfg.ssm_state
    rbc = jnp.dot(x, lp["blk.x_proj"].astype(x.dtype),
                  preferred_element_type=jnp.float32)
    r = _gained(rbc[..., :R], lp["blk.dt_norm"], cfg.rms_eps)
    Bm = _gained(rbc[..., R:R + N], lp["blk.b_norm"], cfg.rms_eps)
    Cm = _gained(rbc[..., R + N:], lp["blk.c_norm"], cfg.rms_eps)
    dt = jnp.dot(r.astype(x.dtype), lp["blk.dt_proj"].astype(x.dtype),
                 preferred_element_type=jnp.float32)
    dt = jax.nn.softplus(dt + lp["blk.dt_bias"].astype(jnp.float32))
    return dt, Bm, Cm


def _decay_rates(lp):
    return -jnp.exp(lp["blk.A_log"].astype(jnp.float32))        # [N, inner]


@jax.named_scope("ssm_out")
def _ssm_out(lp, y, z, dtype):
    """y `[..., inner]` float32 gated by z, through the output
    projection."""
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(dtype)
    return y @ lp["blk.out_proj"].astype(dtype)


def mamba_prompt(lp, y, length, cfg: JambaConfig):
    """The Mamba mixer over whole sequences y [B, T, hidden] of true length
    `length` (a scalar, or None: all T count) from a zero state -> (out
    [B, T, hidden], the convolution's tail [B, K-1, inner] and the state
    [B, N, inner] float32 AFTER position length - 1). Positions at or past
    `length` leave both as they were: their `dt` is 0, and the tail is
    taken at `length`."""
    T = y.shape[1]
    x, z = _split_in(lp, y, cfg)
    with jax.named_scope("conv"):
        tail = _ssm.conv_tail(x, T if length is None else length,
                              cfg.conv_kernel)
        x = jax.nn.silu(_ssm.causal_conv(x, lp["blk.conv_w"],
                                         lp["blk.conv_b"]))
    dt, Bm, Cm = _selection(lp, x, cfg)
    if length is not None:
        dt = jnp.where((jnp.arange(T) < length)[None, :, None], dt, 0.0)
    # on a TPU the scan keeps its state in VMEM (ops/pallas/ssm_scan.py);
    # elsewhere the recurrence as written, token by token
    kernel = _scan.use_kernel(x, cfg.ssm_state)
    _update.GATE_COUNTS["scan_kernel" if kernel else "scan_xla"] += 1
    with jax.named_scope("scan"):
        if kernel:
            out, state = _scan.selective_scan(x, dt, _decay_rates(lp), Bm,
                                              Cm)
            out = out + lp["blk.D"].astype(jnp.float32) \
                * x.astype(jnp.float32)
        else:
            out, state = _ssm.selective_recurrent(
                x, dt, _decay_rates(lp), Bm, Cm, lp["blk.D"])
    return _ssm_out(lp, out, z, y.dtype), tail, state


def _token_inputs(lp, y, tail, cfg: JambaConfig, advance=_ssm.conv_step):
    """One token a row through the input projection, the convolution and
    the selection: (z, x [S, inner], dt, B, C, the new tail).
    `advance(tail, x, w, b)` is the convolution's step: `ops/ssm.conv_step`
    over gathered tails, or the tails' pool moved on where it lies."""
    x, z = _split_in(lp, y, cfg)
    with jax.named_scope("conv"):
        x, tail = advance(tail, x, lp["blk.conv_w"], lp["blk.conv_b"])
        x = jax.nn.silu(x)
    return (z, x) + _selection(lp, x, cfg) + (tail,)


def mamba_token(lp, y, tail, state, cfg: JambaConfig,
                advance=_ssm.conv_step):
    """One token a row: y [S, hidden], tail [S, K-1, inner], state [S, N,
    inner] float32 -> (out [S, hidden], tail, state)."""
    z, x, dt, Bm, Cm, tail = _token_inputs(lp, y, tail, cfg, advance)
    with jax.named_scope("scan"):
        out, state = _ssm.selective_step(
            state, x, dt, _decay_rates(lp), Bm, Cm, lp["blk.D"])
    return _ssm_out(lp, out, z, y.dtype), tail, state


@jax.named_scope("qkv")
def _qkv(lp, y):
    """No position enters: the published attention applies none."""
    return (y @ lp["blk.wq"].astype(y.dtype),
            y @ lp["blk.wk"].astype(y.dtype),
            y @ lp["blk.wv"].astype(y.dtype))


@jax.named_scope("proj")
def _proj(lp, ctx, res):
    return res + ctx @ lp["blk.wo"].astype(ctx.dtype)


@jax.named_scope("mlp")
def _swiglu(lp, y):
    up = jax.nn.silu(y @ lp["blk.w_gate"].astype(y.dtype)) \
        * (y @ lp["blk.w_up"].astype(y.dtype))
    return up @ lp["blk.w_down"].astype(y.dtype)


class JambaServe(_decoder.ServeModel):
    """The blocks for the serve programs (models/decoder.py): `ME` a Mamba
    layer and `*E` an attention layer, K/V for the attention layers alone
    under ONE K/V head, a tail and a state a Mamba layer in row pools."""

    def __init__(self, cfg: JambaConfig):
        self.cfg = cfg
        self.pattern = cfg.pattern
        self.layers, self.heads = len(cfg.pattern), cfg.heads
        self.head_dim = cfg.head_dim
        self.vocab_size, self.max_len = cfg.vocab_size, cfg.max_len

    @property
    def kv_heads(self):
        return self.cfg.kv_heads

    @property
    def kv_layers(self):
        return self.cfg.count("*")

    def state_pools(self, rows: int, dtype):
        """Per Mamba layer and row: the convolution's tail in the served
        dtype, its K-1 inputs end to end as whole lane tiles `[120, 128]`
        (one contiguous 30 KB block a row, for the reasons
        `NemotronHServe.state_pools` gives), and the state `[N, inner]` in
        float32 with the channels in the lanes (16 x 5120: 2 x 40 whole
        tiles, 320 KB a row): every token multiplies a value by a decay
        near 1 and adds a little to it, and in bf16 the additions under
        1/256 of a value are lost."""
        cfg, n = self.cfg, self.cfg.count("M")
        if not n:
            return ()
        tail = (cfg.conv_kernel - 1) * cfg.inner
        lanes = 128 if tail % 128 == 0 else tail
        return (((n, rows, tail // lanes, lanes), dtype),
                ((n, rows, cfg.ssm_state, cfg.inner), jnp.float32))

    def block_params(self, params, kind, i):
        return block_params(params, kind, i)

    def embed(self, params, ids, positions):
        return params["wte.w"][ids]         # no position anywhere

    def norm(self, lp, h):
        return _rms_norm(h, lp["blk.norm.scale"], self.cfg.rms_eps)

    def qkv(self, lp, y, positions):
        return _qkv(lp, y)

    def proj(self, lp, ctx, res):
        return _proj(lp, ctx, res)

    def mlp(self, lp, y, params, l):
        return _swiglu(lp, y), None

    def ssm_prompt(self, lp, y, length, state, i, row):
        conv, pool = state
        out, tail, s = mamba_prompt(lp, y, length, self.cfg)
        with jax.named_scope("state_write"):
            conv = conv.at[i, row].set(
                tail[0].reshape(conv.shape[2:]).astype(conv.dtype))
            pool = pool.at[i, row].set(s[0])
        return out, (conv, pool)

    def ssm_token(self, lp, y, state, i, rows, positions=None):
        """On a TPU both pools are advanced where they lie, a kernel each
        and several rows a grid step (`ops/pallas/ssm_update.py`: the
        convolution's tails, 30 KB a row, by `advance_tails`, the states,
        320 KB a row, by `selective_update`); elsewhere a pool's rows are
        gathered, advanced and scattered back. Each pool has its own gate."""
        cfg = self.cfg
        conv, pool = state
        kernel = _update.use_selective_kernel(y, pool)
        in_place = _update.use_tail_kernel(y, conv, cfg.conv_kernel)
        _update.GATE_COUNTS["kernel" if kernel else "xla"] += 1
        _update.GATE_COUNTS["tail_kernel" if in_place else "tail_xla"] += 1
        if in_place:
            tail = conv

            def advance(conv, x, w, b):
                return _update.advance_tails(conv, jnp.int32(i), rows, x, w,
                                             b)
        else:
            advance = _ssm.conv_step
            with jax.named_scope("state_read"):
                tail = conv[i, rows].reshape(
                    rows.shape[0], cfg.conv_kernel - 1, cfg.inner)
        if not kernel:
            with jax.named_scope("state_read"):
                s = pool[i, rows]
            out, tail, s = mamba_token(lp, y, tail, s, cfg, advance)
            with jax.named_scope("state_write"):
                pool = pool.at[i, rows].set(s)
        else:
            z, x, dt, Bm, Cm, tail = _token_inputs(lp, y, tail, cfg, advance)
            with jax.named_scope("scan"):
                xf = x.astype(jnp.float32)
                out, pool = _update.selective_update(
                    pool, jnp.int32(i), rows, dt, dt * xf, _decay_rates(lp),
                    Bm, Cm)
                out = out + lp["blk.D"].astype(jnp.float32)[None] * xf
            out = _ssm_out(lp, out, z, y.dtype)
        if in_place:
            return out, (tail, pool)
        with jax.named_scope("state_write"):
            conv = conv.at[i, rows].set(
                tail.reshape(rows.shape[:1] + conv.shape[2:])
                .astype(conv.dtype))
        return out, (conv, pool)

    def head(self, params, x, prev_ids, eos_id):
        return _decoder.rms_head(params, x, prev_ids, eos_id,
                                 self.cfg.rms_eps, tied=True)


def _block(kind, lp, x, cfg: JambaConfig):
    """One block of the full forward pass, x [B, T, hidden]."""
    y = _rms_norm(x, lp["blk.norm.scale"], cfg.rms_eps)
    if kind == "M":
        with jax.named_scope("ssm"):
            out, _, _ = mamba_prompt(lp, y, None, cfg)
        x = x + out
    elif kind == "E":
        x = x + _swiglu(lp, y)
    else:
        q, k, v = _qkv(lp, y)
        with jax.named_scope("attention"):
            ctx = _decoder.gqa_prompt(q, k, v, cfg.heads, cfg.kv_heads)
        x = _proj(lp, ctx, x)
    return shard(x, ("batch", "seq", "embed"))


def apply(params: Params, cfg: JambaConfig, ids: jax.Array) -> jax.Array:
    """ids [B, T] -> logits [B, T, vocab]."""
    adt = jnp.dtype(cfg.dtype)
    with jax.named_scope("embed"):
        x = params["wte.w"][ids].astype(adt)
    x = shard(x, ("batch", "seq", "embed"))
    with jax.named_scope("layers"):
        for kind, i in _decoder.pattern_blocks(cfg.pattern):
            x = _block(kind, block_params(params, kind, i), x, cfg)
    with jax.named_scope("head"):
        x = _rms_norm(x, params["ln_f.scale"], cfg.rms_eps)
        logits = jax.lax.dot_general(
            x, params["wte.w"].astype(x.dtype), (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    return shard(logits, ("batch", "seq", "vocab"))

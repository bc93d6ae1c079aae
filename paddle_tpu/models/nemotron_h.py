"""NVIDIA Nemotron-H / Nemotron-3-Nano (`model_type` `nemotron_h`,
nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 `config.json`): a decoder whose
blocks hold ONE mixer each, in a published pattern. Every block is

    h = h + mixer(RMSNorm(h))                       eps 1e-5, no biases

and the mixer's kind follows `hybrid_override_pattern`, a character a
block: `M` a Mamba-2 layer, `E` sparse experts, `*` attention. After the
last block a final RMSNorm and an untied head.

`M` (heads x head width = inner; groups of heads share B and C):

    [z | xBC | dt] = y W_in                 inner | inner + 2 G N | heads
    xBC  = silu(conv_4(xBC) + b)            depthwise, causal, width 4
    [x | B | C] = xBC                       heads x P | G x N | G x N
    dt   = softplus(dt + dt_bias);  A = -exp(A_log)           per head
    S_t  = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
    out  = (RMSNorm_groups(y * silu(z)) * w) W_out   groups of inner / G

`E`: router logits in float32, sigmoid; the top-k of score + correction
bias, weights the scores alone, normalised and scaled (`models/moe.py`);
an expert is `down(relu(up x)^2)`, two matrices; one shared expert of the
same form, added unweighted. `*`: `heads` query heads over `kv_heads`
key/value heads (grouped-query attention), causal softmax at
1/sqrt(head_dim), NO position encoding: the Mamba layers carry order.

What a sequence keeps between tokens is therefore of two kinds: K and V a
token in the attention layers (the paged pools of serving/kv_cache.py, as
many layers as the pattern has `*`), and in every `M` layer a fixed-size
STATE: the convolution's tail (the last 3 inputs, `inner + 2 G N` wide)
and the SSM state `[heads, P, N]` in float32. States live in row pools
`[M layers, rows, ...]` (`NemotronHServe.state_pools`); the engine hands a
sequence a row at admission and the programs the row ids
(serving/decode.py). Prefill overwrites its row from a zero state; a
decode step updates its rows in place.

The routed experts' width (1856 published) is not whole lane tiles; the
stacks are laid out padded to `expert_pad` = 1920 with zero columns (up)
and zero rows (down), which relu^2 keeps exact: relu(0)^2 = 0.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..ops import ssm as _ssm
from ..parallel.sharding import shard
from . import decoder as _decoder, moe as _moe
from .common import Params, rms_norm as _rms_norm
# models/joyai.py says why a seeded sigmoid router needs the first two: a
# layer's routed experts share a base (a near-tied pick that bf16 rounding
# flips then swaps only the experts' own parts), and the correction bias is
# drawn; and why norm gains are spread about 1
from .joyai import BIAS_STD, EXPERT_SPREAD, NORM_STD

KINDS = {"M": "mamba", "E": "moe", "*": "attn"}

# Deviation of a seeded attention score q.k / sqrt(head_dim). At the plain
# draw it is 1, and a softmax over a thousand such scores is an average of
# a third of the positions: whatever changes the scores (a position
# encoding the model does not have) leaves the result where it was. At 3 a
# query's largest few scores hold most of the weight, as a trained head's do.
ATTN_SCORE_STD = 5.0


@dataclasses.dataclass
class NemotronHConfig:
    vocab_size: int = 131072
    hidden: int = 2688
    pattern: str = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    # M
    ssm_heads: int = 64         # `mamba_num_heads`
    ssm_head_dim: int = 64      # `mamba_head_dim`
    ssm_groups: int = 8         # `n_groups`
    ssm_state: int = 128        # `ssm_state_size`
    conv_kernel: int = 4
    chunk: int = 128            # `chunk_size`
    dt_min: float = 0.001       # `time_step_min`
    dt_max: float = 0.1         # `time_step_max`
    dt_floor: float = 1e-4      # `time_step_floor`
    # E
    expert_dim: int = 1856      # `moe_intermediate_size`
    shared_dim: int = 3712      # `moe_shared_expert_intermediate_size`
    n_experts: int = 128
    top_k: int = 6
    route_scale: float = 2.5
    # *
    heads: int = 32
    kv_heads: int = 2
    head_dim: int = 128
    max_len: int = 262144
    rms_eps: float = 1e-5
    dtype: str = "bfloat16"

    @staticmethod
    def tiny() -> "NemotronHConfig":
        return NemotronHConfig(
            vocab_size=512, hidden=64, pattern="MEM*E", ssm_heads=8,
            ssm_head_dim=8, ssm_groups=2, ssm_state=16, chunk=8,
            expert_dim=24, shared_dim=48, n_experts=8, top_k=2, heads=4,
            kv_heads=2, head_dim=16, max_len=128)

    @property
    def layers(self) -> int:
        return len(self.pattern)

    @property
    def inner(self) -> int:
        """The Mamba layers' inner width: heads x head width (`expand` of
        the published configuration is unread)."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        """What the convolution runs over: x, B and C side by side."""
        return self.inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def expert_pad(self) -> int:
        """The routed experts' width as laid out: whole lane tiles."""
        return -(-self.expert_dim // 128) * 128

    def count(self, kind: str) -> int:
        return self.pattern.count(kind)

    @property
    def routing(self) -> _moe.Routing:
        return _moe.Routing(self.n_experts, self.top_k, score="sigmoid",
                            bias=True, normalise=True,
                            scale=self.route_scale, shared=True,
                            form="relu2")

    def serve_model(self) -> "NemotronHServe":
        """This configuration behind the interface the decode engine
        drives (models/decoder.py)."""
        return NemotronHServe(self)


# A layer's parameters carry the prefix `blk.` inside every function here;
# in the flat set the layers of a kind are stacked on a leading axis under
# the kind's prefix (`mamba.`, `moe.`, `attn.`), in the pattern's order.
_TOP_AXES = {"wte.w": ("vocab", "embed"), "ln_f.scale": (None,),
             "head.w": ("embed", "vocab")}
_KIND_AXES = {
    "M": {"norm.scale": (None,), "in_proj": ("embed", "mlp"),
          "conv_w": (None, None), "conv_b": (None,), "dt_bias": (None,),
          "A_log": (None,), "D": (None,), "gnorm.scale": (None,),
          "out_proj": ("mlp", "embed")},
    "E": {"norm.scale": (None,), "router": ("embed", None),
          "router_bias": (None,),
          "w_up": ("expert", "embed", "mlp"),
          "w_down": ("expert", "mlp", "embed"),
          "shared_up": ("embed", "mlp"), "shared_down": ("mlp", "embed")},
    "*": {"norm.scale": (None,), "wq": ("embed", "heads"),
          "wk": ("embed", None), "wv": ("embed", None),
          "wo": ("heads", "embed")},
}


def init_layer(rng: jax.Array, cfg: NemotronHConfig, l, kind=None,
               pad: bool = True) -> Params:
    """Block `l` (counted over the whole pattern) of `init(rng, cfg)`
    alone, in float32 and with the prefix `blk.`: every block has a key of
    its own, so that a model whose float32 set does not fit the device can
    be made, and checked, one block at a time. `kind` is the block's
    character; left out, `cfg.pattern[l]` of a Python int `l`. `pad`
    False leaves the routed experts at their published width (the same
    values without the layout's zero columns and rows)."""
    kind = kind or cfg.pattern[l]
    H = cfg.hidden
    keys = iter(jax.random.split(
        jax.random.fold_in(jax.random.fold_in(rng, 1), l), 12))

    def normal(shape, scale):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    def gains(n):
        return 1.0 + normal((n,), NORM_STD)

    a = math.sqrt(1.0 / H)
    res = 1.0 / math.sqrt(cfg.layers)       # one residual output a block
    lp = {"blk.norm.scale": gains(H)}
    if kind == "M":
        nh, inner = cfg.ssm_heads, cfg.inner
        # Mamba-2's own: A uniform in [1, 16]; dt log-uniform in
        # [dt_min, dt_max], floored, through the inverse softplus; D = 1
        dt = jnp.exp(jax.random.uniform(next(keys), (nh,), jnp.float32)
                     * (math.log(cfg.dt_max) - math.log(cfg.dt_min))
                     + math.log(cfg.dt_min))
        dt = jnp.maximum(dt, cfg.dt_floor)
        lp.update({
            "blk.in_proj": normal((H, inner + cfg.conv_dim + nh), a),
            "blk.conv_w": normal((cfg.conv_kernel, cfg.conv_dim),
                                 math.sqrt(1.0 / cfg.conv_kernel)),
            "blk.conv_b": normal((cfg.conv_dim,), 0.1),
            "blk.dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "blk.A_log": jnp.log(jax.random.uniform(
                next(keys), (nh,), jnp.float32, 1.0, 16.0)),
            "blk.D": jnp.ones((nh,), jnp.float32),
            "blk.gnorm.scale": gains(inner),
            "blk.out_proj": normal((inner, H), math.sqrt(1.0 / inner) * res),
        })
    elif kind == "E":
        E, M, Ms = cfg.n_experts, cfg.expert_dim, cfg.shared_dim
        Mp = cfg.expert_pad if pad else M

        def experts(shape, scale):
            own, base = normal(shape, scale), normal(shape[1:], scale)
            return math.sqrt(1.0 - EXPERT_SPREAD ** 2) * base \
                + EXPERT_SPREAD * own

        up = experts((E, H, M), a)
        down = experts((E, M, H), math.sqrt(1.0 / M) * res)
        lp.update({
            "blk.router": normal((H, E), a),
            "blk.router_bias": normal((E,), BIAS_STD),
            "blk.w_up": jnp.pad(up, [(0, 0), (0, 0), (0, Mp - M)]),
            "blk.w_down": jnp.pad(down, [(0, 0), (0, Mp - M), (0, 0)]),
            "blk.shared_up": normal((H, Ms), a),
            "blk.shared_down": normal((Ms, H), math.sqrt(1.0 / Ms) * res),
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
    else:
        raise ValueError(f"unknown block kind {kind!r} in the pattern")
    return lp


def init_top(rng: jax.Array, cfg: NemotronHConfig) -> Params:
    """The parameters of `init(rng, cfg)` outside the blocks, in float32:
    embedding, final norm, head."""
    k_emb, k_head, k_norm = jax.random.split(jax.random.fold_in(rng, 0), 3)
    V, H = cfg.vocab_size, cfg.hidden
    return {
        "wte.w": jax.random.normal(k_emb, (V, H), jnp.float32) * 0.02,
        "ln_f.scale": 1.0 + NORM_STD * jax.random.normal(
            k_norm, (H,), jnp.float32),
        "head.w": jax.random.normal(k_head, (H, V), jnp.float32)
        * math.sqrt(1.0 / H),
    }


def kind_layers(cfg: NemotronHConfig, kind: str):
    """The pattern's positions of the blocks of `kind`, in order."""
    return [l for l, c in enumerate(cfg.pattern) if c == kind]


def init(rng: jax.Array, cfg: NemotronHConfig, dtype=jnp.float32
         ) -> Tuple[Params, Dict]:
    """The blocks of a kind stacked under the kind's prefix, made one
    block and ONE TENSOR at a time and cast to `dtype` as each is made: the
    float32 form of an expert layer's two stacks (2.6 GB each at the
    published widths) is never whole on the device beside the other."""
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


def block_params(params: Params, kind: str, i: int, skip=()) -> Params:
    """Block `i` of its kind out of the flat set, under `blk.`, without
    the tensors named in `skip`."""
    prefix = KINDS[kind] + "."
    return {"blk." + k[len(prefix):]: v[i] for k, v in params.items()
            if k.startswith(prefix) and k[len(prefix):] not in skip}


# Layer scopes: `ln`; then by the block's kind `ssm` (holding `ssm_in`: the
# input projection, `conv`, `scan`: the recurrence, chunked or one token,
# `ssm_out`: the gated norm and the output projection), `mlp` (models/moe.py's
# `router`, `moe_route`, `experts`, `shared_expert`), or `qkv`, `attention`,
# `proj`; `head`. tests/test_nemotron_h.py holds the list.


def _ssm_inputs(lp, y, cfg: NemotronHConfig):
    """(z [..., inner], xBC [..., conv_dim] BEFORE the convolution, dt
    [..., heads] float32 after its softplus)."""
    with jax.named_scope("ssm_in"):
        zxd = y @ lp["blk.in_proj"].astype(y.dtype)
        z = zxd[..., :cfg.inner]
        xbc = zxd[..., cfg.inner:cfg.inner + cfg.conv_dim]
        dt = jax.nn.softplus(zxd[..., cfg.inner + cfg.conv_dim:]
                             .astype(jnp.float32)
                             + lp["blk.dt_bias"].astype(jnp.float32))
    return z, xbc, dt


def _split_xbc(xbc, cfg: NemotronHConfig):
    """The convolved xBC -> x [..., heads, P], B and C [..., G, N]."""
    lead = xbc.shape[:-1]
    gn = cfg.ssm_groups * cfg.ssm_state
    x = xbc[..., :cfg.inner].reshape(lead + (cfg.ssm_heads,
                                             cfg.ssm_head_dim))
    Bm = xbc[..., cfg.inner:cfg.inner + gn].reshape(
        lead + (cfg.ssm_groups, cfg.ssm_state))
    Cm = xbc[..., cfg.inner + gn:].reshape(
        lead + (cfg.ssm_groups, cfg.ssm_state))
    return x, Bm, Cm


@jax.named_scope("ssm_out")
def _ssm_out(lp, y, z, cfg: NemotronHConfig, dtype):
    """y [..., heads, P] float32 gated by z, normalised in groups of
    inner / G, through the output projection."""
    lead = y.shape[:-2]
    y = y.reshape(lead + (cfg.inner,)) \
        * jax.nn.silu(z.astype(jnp.float32))
    g = y.reshape(lead + (cfg.ssm_groups, cfg.inner // cfg.ssm_groups))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + cfg.rms_eps)
    y = g.reshape(lead + (cfg.inner,)) \
        * lp["blk.gnorm.scale"].astype(jnp.float32)
    return y.astype(dtype) @ lp["blk.out_proj"].astype(dtype)


def mamba_prompt(lp, y, length, cfg: NemotronHConfig, init=None):
    """The Mamba mixer over whole sequences y [B, T, hidden] of true length
    `length` (a scalar, or None: all T count) from a zero state -> (out
    [B, T, hidden], the convolution's tail [B, K-1, conv_dim] and the SSM
    state [B, heads, P, N] float32 AFTER position length - 1). Positions at
    or past `length` leave both as they were: their `dt` is 0, and the
    tail is taken at `length`. `init`: (tail, state) as the part of the
    sequence BEFORE y left them, where y is a slice of a longer prompt
    (`length` then counts from y's first position); `cfg` is any
    configuration with this one's `ssm_*`, `conv_kernel`, `chunk` and
    `rms_eps` (models/granite_hybrid.py's too)."""
    T = y.shape[1]
    z, xbc, dt = _ssm_inputs(lp, y, cfg)
    if length is None:
        length = T
    else:
        dt = jnp.where((jnp.arange(T) < length)[None, :, None], dt, 0.0)
    before, state = init if init is not None else (None, None)
    with jax.named_scope("conv"):
        tail = _ssm.conv_tail(xbc, length, cfg.conv_kernel, before)
        xbc = jax.nn.silu(_ssm.causal_conv(xbc, lp["blk.conv_w"],
                                           lp["blk.conv_b"], before))
    x, Bm, Cm = _split_xbc(xbc, cfg)
    with jax.named_scope("scan"):
        out, state = _ssm.ssd_chunked(
            x, dt, -jnp.exp(lp["blk.A_log"].astype(jnp.float32)), Bm, Cm,
            lp["blk.D"], cfg.chunk, state)
    return _ssm_out(lp, out, z, cfg, y.dtype), tail, state


def _token_inputs(lp, y, tail, cfg: NemotronHConfig):
    """One token a row through the input projection and the convolution:
    (z, x [S, heads, P], B and C [S, G, N], dt [S, heads], the new tail)."""
    z, xbc, dt = _ssm_inputs(lp, y, cfg)
    with jax.named_scope("conv"):
        xbc, tail = _ssm.conv_step(tail, xbc, lp["blk.conv_w"],
                                   lp["blk.conv_b"])
        xbc = jax.nn.silu(xbc)
    return (z,) + _split_xbc(xbc, cfg) + (dt, tail)


def mamba_token(lp, y, tail, state, cfg: NemotronHConfig):
    """One token a row: y [S, hidden], tail [S, K-1, conv_dim], state
    [S, heads, P, N] float32 -> (out [S, hidden], tail, state)."""
    z, x, Bm, Cm, dt, tail = _token_inputs(lp, y, tail, cfg)
    with jax.named_scope("scan"):
        out, state = _ssm.ssd_step(
            state, x, dt, -jnp.exp(lp["blk.A_log"].astype(jnp.float32)),
            Bm, Cm, lp["blk.D"])
    return _ssm_out(lp, out, z, cfg, y.dtype), tail, state


@jax.named_scope("qkv")
def _qkv(lp, y):
    """No position enters: the published attention applies none."""
    return (y @ lp["blk.wq"].astype(y.dtype),
            y @ lp["blk.wk"].astype(y.dtype),
            y @ lp["blk.wv"].astype(y.dtype))


@jax.named_scope("proj")
def _proj(lp, ctx, res):
    return res + ctx @ lp["blk.wo"].astype(ctx.dtype)


_EXPERTS = ("w_up", "w_down")


class NemotronHServe(_decoder.ServeModel):
    """The blocks for the serve programs (models/decoder.py): one mixer a
    block in `pattern`, K/V for the attention blocks alone, and a state a
    Mamba block in row pools."""

    def __init__(self, cfg: NemotronHConfig):
        self.cfg = cfg
        self.pattern = cfg.pattern
        self.layers, self.heads = cfg.layers, cfg.heads
        self.head_dim = cfg.head_dim
        self.vocab_size, self.max_len = cfg.vocab_size, cfg.max_len

    @property
    def kv_heads(self):
        return self.cfg.kv_heads

    @property
    def kv_layers(self):
        return self.cfg.count("*")

    def state_pools(self, rows: int, dtype):
        """Per Mamba block and row: the convolution's tail in the served
        dtype (it holds activations as the prefill saw them) and the SSM
        state in float32 (64 x 64 x 128 values that every token multiplies
        by a decay near 1 and adds a little to: in bf16 the additions under
        1/256 of a value are lost). A tail's K-1 inputs lie end to end as
        whole lane tiles `[144, 128]`, so that a row is one contiguous 36 KB
        block: as `[rows, K-1, conv_dim]` three inputs pad to a 16-row tile
        each and the TPU's compiler relaid the whole pool out and back every
        step, and as one row of lanes `[rows, 18432]` it interleaved the
        layers in the sublanes and a row's write became 144 partial tiles
        (chip runs of PR 34)."""
        cfg, n = self.cfg, self.cfg.count("M")
        if not n:
            return ()
        tail = (cfg.conv_kernel - 1) * cfg.conv_dim
        lanes = 128 if tail % 128 == 0 else tail
        return (((n, rows, tail // lanes, lanes), dtype),
                ((n, rows, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                 jnp.float32))

    def block_params(self, params, kind, i):
        # the expert stacks stay whole: `expert_mlp` addresses them in place
        return block_params(params, kind, i, skip=_EXPERTS)

    def embed(self, params, ids, positions):
        return params["wte.w"][ids]         # no position anywhere

    def norm(self, lp, h):
        return _rms_norm(h, lp["blk.norm.scale"], self.cfg.rms_eps)

    def qkv(self, lp, y, positions):
        return _qkv(lp, y)

    def proj(self, lp, ctx, res):
        return _proj(lp, ctx, res)

    def mlp(self, lp, y, params, l):
        lp = dict(lp, **{"blk." + k: params["moe." + k] for k in _EXPERTS})
        return _moe.expert_mlp(lp, y, self.cfg.routing, layer=l)

    def ssm_prompt(self, lp, y, length, state, i, row):
        conv, pool = state
        out, tail, s = mamba_prompt(lp, y, length, self.cfg)
        with jax.named_scope("state_write"):
            conv = conv.at[i, row].set(
                tail[0].reshape(conv.shape[2:]).astype(conv.dtype))
            pool = pool.at[i, row].set(s[0])
        return out, (conv, pool)

    def ssm_token(self, lp, y, state, i, rows, positions=None):
        """The convolution's tails are gathered and scattered (37 KB a
        row); the SSM states, 2 MB a row, are advanced where they lie by
        the kernel of ops/pallas/ssm_update.py on a TPU, and gathered,
        advanced and scattered back elsewhere."""
        from ..ops.pallas import ssm_update as su

        cfg = self.cfg
        conv, pool = state
        kernel = su.use_kernel(y, pool, cfg.ssm_groups)
        su.GATE_COUNTS["kernel" if kernel else "xla"] += 1
        with jax.named_scope("state_read"):
            tail = conv[i, rows].reshape(
                rows.shape[0], cfg.conv_kernel - 1, cfg.conv_dim)
        if not kernel:
            with jax.named_scope("state_read"):
                s = pool[i, rows]
            out, tail, s = mamba_token(lp, y, tail, s, cfg)
            with jax.named_scope("state_write"):
                pool = pool.at[i, rows].set(s)
        else:
            z, x, Bm, Cm, dt, tail = _token_inputs(lp, y, tail, cfg)
            with jax.named_scope("scan"):
                xf = x.astype(jnp.float32)
                A = -jnp.exp(lp["blk.A_log"].astype(jnp.float32))
                out, pool = su.state_update(
                    pool, jnp.int32(i), rows, jnp.exp(dt * A),
                    dt[..., None] * xf, Bm, Cm)
                out = out + lp["blk.D"].astype(jnp.float32)[:, None] * xf
            out = _ssm_out(lp, out, z, cfg, y.dtype)
        with jax.named_scope("state_write"):
            conv = conv.at[i, rows].set(
                tail.reshape(rows.shape[:1] + conv.shape[2:])
                .astype(conv.dtype))
        return out, (conv, pool)

    def head(self, params, x, prev_ids, eos_id):
        return _decoder.rms_head(params, x, prev_ids, eos_id,
                                 self.cfg.rms_eps)

    def step_facts(self, stats) -> Dict:
        return _moe.step_facts(stats)


def _block(kind, lp, x, cfg: NemotronHConfig):
    """One block of the full forward pass, x [B, T, hidden]."""
    y = _rms_norm(x, lp["blk.norm.scale"], cfg.rms_eps)
    if kind == "M":
        with jax.named_scope("ssm"):
            out, _, _ = mamba_prompt(lp, y, None, cfg)
        x = x + out
    elif kind == "E":
        out, _ = _moe.expert_mlp(lp, y, cfg.routing)
        x = x + out
    else:
        q, k, v = _qkv(lp, y)
        with jax.named_scope("attention"):
            ctx = _decoder.gqa_prompt(q, k, v, cfg.heads, cfg.kv_heads)
        x = _proj(lp, ctx, x)
    return shard(x, ("batch", "seq", "embed"))


def apply(params: Params, cfg: NemotronHConfig, ids: jax.Array) -> jax.Array:
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
        logits = jnp.dot(x, params["head.w"].astype(x.dtype),
                         preferred_element_type=jnp.float32)
    return shard(logits, ("batch", "seq", "vocab"))

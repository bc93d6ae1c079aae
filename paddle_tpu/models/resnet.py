"""ResNet v1.5 (50/101/152) — ImageNet CNN config of the ladder.

Reference capability: ResNet-50 is the reference's flagship CV benchmark
(contrib/float16/float16_benchmark.md:40; test_dist_se_resnext lineage).
TPU-first design: NHWC layout (TPU conv native), bf16 activations, fused
batch-norm as explicit scale/shift math (XLA fuses into the conv), batch
stats via masked mean (sync-BN over 'dp' comes from GSPMD when the batch is
sharded — BuildStrategy.sync_batch_norm for free).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..parallel.sharding import shard
from .common import ParamStore, Params, dense

DEPTHS = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}


@dataclasses.dataclass
class ResNetConfig:
    depth: int = 50
    n_classes: int = 1000
    width: int = 64
    dtype: str = "bfloat16"
    bn_momentum: float = 0.9
    bn_eps: float = 1e-5
    # Pallas fused matmul+BN for the bottleneck 1x1 convs
    # (ops/pallas/fused_dense_bn.py): conv1/conv3 run as matmuls with BN
    # stats in the epilogue and the preceding BN-apply+relu in conv3's
    # prologue. Default OFF (the XLA path is the settled baseline);
    # training-mode, single-device-or-manual-region only (pallas has no
    # GSPMD rule).
    fused_1x1: bool = False

    @staticmethod
    def resnet50():
        return ResNetConfig(50)

    @staticmethod
    def tiny():
        return ResNetConfig(depth=50, n_classes=10, width=8)

    def flops_per_image(self, hw: int = 224) -> float:
        # RN50@224 fwd = 4.089 G multiply-accumulates = 8.18 GFLOPs (the
        # often-quoted "4.1 GFLOPs" counts MACs). x3 for training
        # (fwd + dgrad + wgrad). Width/resolution scale quadratically.
        base = 8.18e9 * (self.width / 64) ** 2 * (hw / 224) ** 2
        return 3 * base * (1 if self.depth == 50 else self.depth / 50)


def _bn_init(s: ParamStore, name: str, dim: int):
    s.bn(name, dim)


def init(rng: jax.Array, cfg: ResNetConfig) -> Tuple[Params, Dict]:
    s = ParamStore(rng)
    w = cfg.width
    s.conv("stem", 7, 7, 3, w)
    _bn_init(s, "stem.bn", w)
    cin = w
    for gi, blocks in enumerate(DEPTHS[cfg.depth]):
        mid = w * (2 ** gi)
        cout = mid * 4
        for bi in range(blocks):
            p = f"g{gi}.b{bi}"
            s.conv(f"{p}.conv1", 1, 1, cin, mid)
            _bn_init(s, f"{p}.bn1", mid)
            s.conv(f"{p}.conv2", 3, 3, mid, mid)
            _bn_init(s, f"{p}.bn2", mid)
            s.conv(f"{p}.conv3", 1, 1, mid, cout)
            _bn_init(s, f"{p}.bn3", cout)
            if bi == 0:
                s.conv(f"{p}.proj", 1, 1, cin, cout)
                _bn_init(s, f"{p}.proj.bn", cout)
            cin = cout
    s.dense("head", cin, cfg.n_classes, axes=("embed", "vocab"))
    return s.params, s.axes


def _conv(params, name, x, stride=1, padding="SAME"):
    from .common import conv2d_nhwc_auto

    return conv2d_nhwc_auto(params, name, x, stride, padding)


def _bn_ema(params, state_updates, name, mean, var, cfg):
    """Write the running-stat EMA updates for batch stats (mean, var)."""
    m = cfg.bn_momentum
    state_updates[f"{name}.mean"] = m * params[f"{name}.mean"] + (1 - m) * mean
    state_updates[f"{name}.var"] = m * params[f"{name}.var"] + (1 - m) * var


def _bn_stats(x):
    """One-pass batch stats: E[x] and E[x^2] fuse into a single read of
    the activations (jnp.var's (x-mean)^2 forces a second pass; measured
    116->105 ms fwd+bwd for RN50 bs=256 — PROFILE.md). Promoted (f32, or
    f64 under x64 rigs) accumulation keeps the cancellation benign (the
    cudnn approach). Shared by _bn and the fused-1x1 path so stats
    semantics cannot diverge."""
    xf = x.astype(jnp.promote_types(x.dtype, jnp.float32))
    mean = xf.mean((0, 1, 2))
    var = jnp.maximum((xf * xf).mean((0, 1, 2)) - mean * mean, 0.0)
    return mean, var


def _fused_1x1_ok(params, p, cfg, train: bool) -> bool:
    """Gate for the pallas fused-1x1 path: opt-in, training mode, fp
    weights (the int8 serving path must keep conv2d_nhwc_auto's scale
    dispatch), and a context where a pallas_call is legal (single
    device / manual region)."""
    if not (cfg.fused_1x1 and train):
        return False
    if params[f"{p}.conv1.w"].dtype == jnp.int8 or \
            params[f"{p}.conv3.w"].dtype == jnp.int8:
        return False
    from ..parallel.mesh import current_mesh

    m = current_mesh()
    return m is None or m.devices.size == 1


def _fused_block_tail(params, upd, p, x, cfg):
    """conv1+bn1-stats, relu; conv2(3x3) unchanged via XLA; bn2-apply+
    relu fused into conv3's prologue with bn3 stats in its epilogue.
    Only the stride-1 non-proj shape runs fused (stride lives on conv2).
    Returns the block's pre-residual output h (bn3-normalized)."""
    from ..ops.pallas import fused_dense_bn as F

    B, H, W, C = x.shape
    w1 = params[f"{p}.conv1.w"].astype(x.dtype).reshape(C, -1)
    h1, m1, v1 = F.matmul_stats(x.reshape(-1, C), w1)
    _bn_ema(params, upd, f"{p}.bn1", m1, v1, cfg)
    s1, b1 = F.fold_bn(m1, v1, params[f"{p}.bn1.scale"],
                       params[f"{p}.bn1.bias"], cfg.bn_eps)
    h1 = jnp.maximum(h1.astype(s1.dtype) * s1 + b1, 0.0).astype(x.dtype)
    return h1.reshape(B, H, W, -1)


def _fused_conv3(params, upd, p, h2raw, cfg):
    """bn2-apply+relu (prologue) -> conv3 1x1 (matmul) -> bn3 stats
    (epilogue), one kernel; h2raw is conv2's RAW output."""
    from ..ops.pallas import fused_dense_bn as F

    B, H, W, C = h2raw.shape
    m2, v2 = _bn_stats(h2raw)
    _bn_ema(params, upd, f"{p}.bn2", m2, v2, cfg)
    s2, b2 = F.fold_bn(m2, v2, params[f"{p}.bn2.scale"],
                       params[f"{p}.bn2.bias"], cfg.bn_eps)
    w3 = params[f"{p}.conv3.w"].astype(h2raw.dtype).reshape(C, -1)
    h3, m3, v3 = F.bn_act_matmul_stats(h2raw.reshape(-1, C), s2, b2, w3,
                                       relu=True)
    _bn_ema(params, upd, f"{p}.bn3", m3, v3, cfg)
    s3, b3 = F.fold_bn(m3, v3, params[f"{p}.bn3.scale"],
                       params[f"{p}.bn3.bias"], cfg.bn_eps)
    h3 = (h3.astype(s3.dtype) * s3 + b3).astype(h2raw.dtype)
    return h3.reshape(B, H, W, -1)


def _bn(params, state_updates, name, x, cfg, train: bool):
    """BN in fp32; updates running stats into state_updates when training.
    When the batch axis is sharded over 'dp', XLA computes the mean/var with
    a cross-device reduction — sync-BN semantics by construction.

    Stats promote to f64 for f64 activations (x64 test runs): the one-pass
    E[x^2]-E[x]^2 form has f32 cancellation noise that changes with shard
    summation order, which would mask dp-vs-single parity checks."""
    xf = x.astype(jnp.promote_types(x.dtype, jnp.float32))
    if train:
        mean, var = _bn_stats(x)
        _bn_ema(params, state_updates, name, mean, var, cfg)
    else:
        mean = params[f"{name}.mean"]
        var = params[f"{name}.var"]
    inv = jax.lax.rsqrt(var + cfg.bn_eps) * params[f"{name}.scale"]
    y = (xf - mean) * inv + params[f"{name}.bias"]
    return y.astype(x.dtype)


def apply(params: Params, cfg: ResNetConfig, img: jax.Array,
          train: bool = False,
          data_format: str = "NCHW") -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """img -> (logits, bn_updates).

    data_format="NHWC" is the native TPU path; "NCHW" is an API-parity
    shim for reference-style [B,3,H,W] feeds whose in-graph transpose
    XLA folds into the stem conv (measured neutral at bs=256 on v5e —
    PROFILE.md round 3). Benches feed NHWC anyway: it is what a real TPU
    input pipeline delivers."""
    adt = jnp.dtype(cfg.dtype)
    if data_format == "NCHW":
        x = img.transpose(0, 2, 3, 1).astype(adt)  # NHWC
    else:
        assert data_format == "NHWC", data_format
        x = img.astype(adt)
    x = shard(x, ("batch", None, None, None))
    upd: Dict[str, jax.Array] = {}
    x = _conv(params, "stem", x, stride=2)
    x = jax.nn.relu(_bn(params, upd, "stem.bn", x, cfg, train))
    x = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)), constant_values=-jnp.inf if False else 0)
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "VALID")
    for gi, blocks in enumerate(DEPTHS[cfg.depth]):
        for bi in range(blocks):
            p = f"g{gi}.b{bi}"
            stride = 2 if (bi == 0 and gi > 0) else 1
            sc = x
            if bi == 0:
                sc = _conv(params, f"{p}.proj", x, stride=stride)
                sc = _bn(params, upd, f"{p}.proj.bn", sc, cfg, train)
            if _fused_1x1_ok(params, p, cfg, train):
                # pallas fused 1x1 path (byte-floor attack): conv1 with
                # bn1 stats in its epilogue; bn2-apply+relu in conv3's
                # prologue with bn3 stats in its epilogue
                h = _fused_block_tail(params, upd, p, x, cfg)
                h2raw = _conv(params, f"{p}.conv2", h, stride=stride)
                h = _fused_conv3(params, upd, p, h2raw, cfg)
            else:
                h = jax.nn.relu(_bn(params, upd, f"{p}.bn1",
                                    _conv(params, f"{p}.conv1", x), cfg,
                                    train))
                h = jax.nn.relu(_bn(params, upd, f"{p}.bn2",
                                    _conv(params, f"{p}.conv2", h,
                                          stride=stride),
                                    cfg, train))
                h = _bn(params, upd, f"{p}.bn3",
                        _conv(params, f"{p}.conv3", h), cfg, train)
            x = jax.nn.relu(h + sc)
    x = x.mean((1, 2))  # global avg pool
    logits = dense(params, "head", x.astype(jnp.float32))
    return logits, upd


def loss_fn(params: Params, cfg: ResNetConfig, batch, rng=None,
            train: bool = True, data_format: str = "NCHW"):
    logits, upd = apply(params, cfg, batch["img"], train=train,
                        data_format=data_format)
    labels = batch["label"].reshape(-1)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    loss = -jnp.take_along_axis(logp, labels[:, None], 1).mean()
    return loss, upd


def make_batch(rng: jax.Array, cfg: ResNetConfig, batch_size: int,
               hw: int = 224, data_format: str = "NCHW"):
    k1, k2 = jax.random.split(rng)
    assert data_format in ("NCHW", "NHWC"), data_format
    shape = (batch_size, 3, hw, hw) if data_format == "NCHW" \
        else (batch_size, hw, hw, 3)
    return {
        "img": jax.random.normal(k1, shape, jnp.float32),
        "label": jax.random.randint(k2, (batch_size,), 0, cfg.n_classes),
    }

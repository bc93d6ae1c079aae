"""Inference-latency benchmark against the reference's OWN published
numbers (BASELINE.md — the float16 benchmarks in
paddle/contrib/float16/float16_benchmark.md are the only hard perf
numbers the reference ships):

| config                      | reference (V100 fp16) |
| VGG16 ImageNet   mb=1       | 3.32 ms  |
| VGG16 ImageNet   mb=64      | 60.23 ms |
| ResNet50 ImageNet mb=1      | 6.13 ms  |
| ResNet50 ImageNet mb=128    | 64.52 ms |

Measurement: DEVICE latency via an on-device chain — N model calls
inside one lax.scan, each iteration's input data-dependent on the
previous iteration's logits, so the device executes them strictly
serially and per-call host dispatch is excluded. This matches what the
reference's local harness measures (its host dispatch is ~0.1 ms); the
host round trip of one tiny call is reported separately as
host_roundtrip_ms for context.

Prints one JSON line per config; vs_baseline = reference_ms / device_ms
(>1 means this framework on one v5e chip beats the reference's V100
fp16 number). Run: python tools/infer_bench.py
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax
import jax.numpy as jnp
import numpy as np

REF_MS = {
    ("vgg16", 1): 3.32,
    ("vgg16", 64): 60.23,
    ("resnet50", 1): 6.13,
    ("resnet50", 128): 64.52,
}

N_CHAIN = 30


def _device_latency_ms(model_fn, params, img):
    """Serialized on-device per-call latency: scan N_CHAIN model calls,
    each input perturbed by (0 x sum(prev logits)) to force a data
    dependency (no cross-iteration parallelism, no host in the loop)."""

    @jax.jit
    def chain(p, x0):
        def step(x, _):
            logits = model_fn(p, x)
            dep = (jnp.sum(logits) * 0.0).astype(x.dtype)
            return x + dep, ()

        xn, _ = jax.lax.scan(step, x0, None, length=N_CHAIN)
        return jnp.sum(xn)

    float(chain(params, img))           # warmup + compile
    t0 = time.perf_counter()
    float(chain(params, img))
    total = (time.perf_counter() - t0) * 1000
    return total / N_CHAIN


def _host_roundtrip_ms(n=5):
    """Serial host->device->host round trip of one tiny jitted call."""
    tiny = jax.jit(lambda x: x + 1.0)
    z = jnp.zeros(())
    float(tiny(z))
    t0 = time.perf_counter()
    for _ in range(n):
        float(tiny(z))
    return (time.perf_counter() - t0) / n * 1000


def main():
    from paddle_tpu.models import resnet, vgg

    platform = jax.devices()[0].platform
    rtt = _host_roundtrip_ms()

    vcfg = vgg.VGGConfig.vgg16()
    vparams, _ = vgg.init(jax.random.key(0), vcfg)

    rcfg = resnet.ResNetConfig.resnet50()
    rparams, _ = resnet.init(jax.random.key(1), rcfg)

    def vgg_fn(p, x):
        return vgg.apply(p, vcfg, x)

    def rn_fn(p, x):
        return resnet.apply(p, rcfg, x, train=False)[0]

    # INT8 variants: per-output-channel int8 conv weights + dynamic
    # per-tensor activation scales, int32 MXU accumulation
    # (models/common.quantize_conv_weights_int8; the reference's analogue
    # is mkldnn INT8 inference, mkldnn_quantizer.cc)
    from paddle_tpu.models.common import quantize_conv_weights_int8

    vparams_q = quantize_conv_weights_int8(vparams)
    rparams_q = quantize_conv_weights_int8(rparams)

    configs = [("vgg16", vgg_fn, vparams, 1, "bf16"),
               ("vgg16", vgg_fn, vparams, 64, "bf16"),
               ("resnet50", rn_fn, rparams, 1, "bf16"),
               ("resnet50", rn_fn, rparams, 128, "bf16"),
               ("vgg16_int8", vgg_fn, vparams_q, 64, "int8"),
               ("resnet50_int8", rn_fn, rparams_q, 128, "int8")]
    for name, fn, params, bs, prec in configs:
        img = jax.random.normal(jax.random.key(2), (bs, 3, 224, 224),
                                jnp.float32)
        ms = _device_latency_ms(fn, params, img)
        base = name.replace("_int8", "")
        ref = REF_MS[(base, bs)]
        detail = {"batch_size": bs, "platform": platform,
                  "precision": prec,
                  "reference_v100_fp16_ms": ref,
                  "chained_serial_calls": N_CHAIN,
                  "host_roundtrip_ms": round(rtt, 3),
                  "source": "contrib/float16/float16_benchmark.md"}
        if prec == "int8":
            # accuracy delta vs the bf16 path over 32 probe images (3%
            # top-1 granularity; random-init logits are near-tied, so
            # tiny samples make agreement meaninglessly coarse, while
            # the full 128-image batch costs two more large compiles)
            probe = img[:32]
            fp = np.asarray(jax.jit(fn)(
                vparams if base == "vgg16" else rparams, probe),
                np.float32)
            qt = np.asarray(jax.jit(fn)(params, probe), np.float32)
            detail["int8_vs_bf16_max_abs_logit_delta"] = round(
                float(np.abs(fp - qt).max()), 4)
            detail["int8_vs_bf16_rel_logit_delta"] = round(
                float(np.abs(fp - qt).max() / (np.abs(fp).max() + 1e-9)), 4)
            detail["int8_vs_bf16_top1_agreement"] = round(
                float((fp.argmax(-1) == qt.argmax(-1)).mean()), 4)
        print(json.dumps({
            "metric": f"{name}_infer_device_latency_ms_bs{bs}",
            "value": round(ms, 3), "unit": "ms",
            "vs_baseline": round(ref / ms, 3),
            "detail": detail,
        }), flush=True)
    return 0


if __name__ == "__main__":
    from paddle_tpu.core.tpu_lock import tpu_singleflight

    with tpu_singleflight():  # one real chip: serialize vs bench/tools
        sys.exit(main())

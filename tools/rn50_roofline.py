"""Per-stage roofline for ResNet-50 training on the real chip.

For each stage (stem+maxpool, residual groups g0..g3, head+loss) this
times fwd+bwd in isolation (chained via lax.scan so the device stays
busy and per-call dispatch overhead amortizes), and prints a table of
analytic FLOPs, modeled HBM bytes, measured time, achieved TFLOP/s and
GB/s vs the v5e peaks (197 TFLOP/s bf16, 819 GB/s).

Traffic model (bf16=2B, f32=4B), per training step, per tensor pass:
  fwd conv:   read in_act + read weights + write out_act
  fwd BN:     read out_act (one-pass stats) + read out_act + write normed
              (stats can't fuse with apply: reduction must finish first)
  bwd BN+relu: read grad + read act + write grad
  bwd conv:   dgrad (read grad+W, write dx) and wgrad (read grad + read act)
Residual add reads/writes are folded into the adjacent BN passes where
XLA fuses them; this model is approximate but stated, which is the point.
"""

import time

import jax

jax.config.update("jax_default_prng_impl", "unsafe_rbg")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import importlib.util  # noqa: E402
import os  # noqa: E402


def _load_device_peaks():
    """File-path import of the shared peak table (stdlib-only) — keeps
    this tool runnable as `python tools/rn50_roofline.py` with no
    paddle_tpu on sys.path."""
    p = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                     "paddle_tpu", "observability", "device_peaks.py")
    spec = importlib.util.spec_from_file_location("_rn50_device_peaks", p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_V5E = _load_device_peaks().lookup("TPU v5 lite")
PEAK_TF = _V5E.flops
PEAK_BW = _V5E.hbm_bytes_per_s
BS = 256
BF = 2  # bytes bf16


def conv_flops(n, h, w, cin, cout, kh, kw, stride):
    oh, ow = h // stride, w // stride
    return 2 * n * oh * ow * cin * cout * kh * kw


def timeit_vjp(fn, x, iters=40):
    """Time fwd+bwd of fn at input x: vjp with a RANDOM cotangent passed
    through the scan carry (a closed-over cotangent would be embedded in
    the HLO as a giant constant, and grad-of-sum lets XLA constant-fold
    chunks of the backward). iters=40 amortizes the fixed per-invocation
    dispatch cost."""
    y = jax.eval_shape(fn, x)
    yb = jax.random.normal(jax.random.key(99), y.shape, y.dtype)

    def body(c, _):
        a, yb = c
        _, pull = jax.vjp(fn, a)
        (gx,) = pull(yb)
        return (gx, yb), 0.0

    f = jax.jit(lambda a, yb: jax.lax.scan(body, (a, yb), None,
                                           length=iters)[0][0])
    r = f(x, yb)
    float(jnp.sum(r))
    t0 = time.perf_counter()
    r = f(x, yb)
    float(jnp.sum(r))
    return (time.perf_counter() - t0) / iters


def _convbn(key, kh, kw, cin, cout):
    w = jax.random.normal(key, (kh, kw, cin, cout), jnp.bfloat16) * 0.05
    sc = jnp.ones((cout,), jnp.float32)

    def f(x, st=1, relu=True):
        x = jax.lax.conv_general_dilated(
            x, w, (st, st), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        xf = x.astype(jnp.float32)
        mean = xf.mean((0, 1, 2))
        var = jnp.maximum((xf * xf).mean((0, 1, 2)) - mean * mean, 0.0)
        y = (xf - mean) * jax.lax.rsqrt(var + 1e-5) * sc
        if relu:
            y = jax.nn.relu(y)
        return y.astype(jnp.bfloat16)

    return f


def conv_cost(h, cin, cout, kh, kw, st):
    """fwd+bwd (flops, bytes) for one conv+BN at input [BS,h,h,cin]."""
    f1 = conv_flops(BS, h, h, cin, cout, kh, kw, st)
    oh = h // st
    a_in = BS * h * h * cin * BF
    a_out = BS * oh * oh * cout * BF
    wb = kh * kw * cin * cout * BF
    # fwd: conv(read in + w, write out) + BN stats(read out)
    #      + BN apply(read out, write out)
    # bwd: BN bwd(read g, read act, write g) + dgrad(read g + w, write gx)
    #      + wgrad(read g + read act)
    by = (a_in + wb + a_out) + a_out + 2 * a_out \
        + 3 * a_out + (a_out + wb + a_in) + (a_out + a_in)
    return 3 * f1, by


def make_group(gi, blocks, cin, key):
    """Real bottleneck-group topology (residual adds included)."""
    mid = 64 * (2 ** gi)
    cout = mid * 4
    keys = iter(jax.random.split(key, blocks * 4))
    layers = []
    c = cin
    for bi in range(blocks):
        st = 2 if (bi == 0 and gi > 0) else 1
        l1 = _convbn(next(keys), 1, 1, c, mid)
        l2 = _convbn(next(keys), 3, 3, mid, mid)
        l3 = _convbn(next(keys), 1, 1, mid, cout)
        proj = _convbn(next(keys), 1, 1, c, cout) if bi == 0 else None
        layers.append((l1, l2, l3, proj, st))
        c = cout

    def fn(x):
        for l1, l2, l3, proj, st in layers:
            sc = proj(x, st=st, relu=False) if proj is not None else x
            h = l1(x)
            h = l2(h, st=st)
            h = l3(h, relu=False)
            x = jax.nn.relu(h + sc)
        return x

    return fn, cout


def group_cost(gi, blocks, cin, h):
    fl = by = 0
    mid = 64 * (2 ** gi)
    cout = mid * 4
    c = cin
    for bi in range(blocks):
        st = 2 if (bi == 0 and gi > 0) else 1
        f, b = conv_cost(h, c, mid, 1, 1, 1)
        fl, by = fl + f, by + b
        f, b = conv_cost(h, mid, mid, 3, 3, st)
        fl, by = fl + f, by + b
        oh = h // st
        f, b = conv_cost(oh, mid, cout, 1, 1, 1)
        fl, by = fl + f, by + b
        if bi == 0:
            f, b = conv_cost(h, c, cout, 1, 1, st)
            fl, by = fl + f, by + b
        # residual add + relu: fwd read sc (+h already in BN write) + write,
        # bwd one extra grad pass
        a_out = BS * oh * oh * cout * BF
        by += 3 * a_out
        h, c = oh, cout
    return fl, by


def main():
    rows = []
    # stem: 7x7/2 conv+BN+relu then 3x3/2 maxpool
    stem_cb = _convbn(jax.random.key(1), 7, 7, 3, 64)

    def stem_fn(x):
        x = stem_cb(x, st=2)
        x = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
        return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                     (1, 3, 3, 1), (1, 2, 2, 1), "VALID")

    x = jax.random.normal(jax.random.key(1), (BS, 224, 224, 3), jnp.bfloat16)
    t = timeit_vjp(stem_fn, x)
    fl, by = conv_cost(224, 3, 64, 7, 7, 2)
    # maxpool: fwd read 112^2 + write 56^2, bwd select-and-scatter ~2 passes
    by += BS * (112 * 112 + 56 * 56) * 64 * BF * 3
    rows.append(("stem+maxpool", fl, by, t))

    # group input spatial sizes: stride-2 happens inside g1..g3's block 0
    group_h = {0: 56, 1: 56, 2: 28, 3: 14}
    cin = 64
    for gi, blocks in enumerate((3, 4, 6, 3)):
        h = group_h[gi]
        fn, cout = make_group(gi, blocks, cin, jax.random.key(2 + gi))
        x = jax.random.normal(jax.random.key(2 + gi),
                              (BS, h, h, cin), jnp.bfloat16)
        t = timeit_vjp(fn, x)
        fl, by = group_cost(gi, blocks, cin, h)
        rows.append((f"g{gi} x{blocks}", fl, by, t))
        cin = cout

    # head: global avg pool + fp32 dense 2048->1000 + softmax-CE
    whead = jax.random.normal(jax.random.key(9), (2048, 1000),
                              jnp.float32) * 0.02

    def head_fn(x):
        p = x.mean((1, 2)).astype(jnp.float32)
        lo = p @ whead
        return jax.nn.log_softmax(lo)

    x = jax.random.normal(jax.random.key(10), (BS, 7, 7, 2048), jnp.bfloat16)
    t = timeit_vjp(head_fn, x)
    fl = 3 * 2 * BS * 2048 * 1000
    by = BS * 7 * 7 * 2048 * BF * 2 + BS * 2048 * 4 * 4 + 2048 * 1000 * 4 * 3
    rows.append(("head+loss", fl, by, t))

    tot_t = sum(r[3] for r in rows)
    tot_f = sum(r[1] for r in rows)
    tot_b = sum(r[2] for r in rows)
    print(f"{'stage':<14}{'ms':>8}{'GFLOP':>9}{'GB':>8}"
          f"{'TFLOP/s':>9}{'MFU':>7}{'GB/s':>8}{'%BW':>6}")
    for name, fl, by, t in rows:
        print(f"{name:<14}{1e3 * t:>8.2f}{fl / 1e9:>9.1f}{by / 1e9:>8.2f}"
              f"{fl / t / 1e12:>9.1f}{fl / t / PEAK_TF:>7.1%}"
              f"{by / t / 1e9:>8.0f}{by / t / PEAK_BW:>6.0%}")
    print(f"{'TOTAL':<14}{1e3 * tot_t:>8.2f}{tot_f / 1e9:>9.1f}"
          f"{tot_b / 1e9:>8.2f}{tot_f / tot_t / 1e12:>9.1f}"
          f"{tot_f / tot_t / PEAK_TF:>7.1%}{tot_b / tot_t / 1e9:>8.0f}"
          f"{tot_b / tot_t / PEAK_BW:>6.0%}")
    print(f"\nisolated-stage sum: {1e3 * tot_t:.1f} ms for bs={BS} "
          f"(full step measured ~103 ms)")
    print(f"roofline: bytes-bound step floor = {tot_b / PEAK_BW * 1e3:.1f} ms"
          f"  | flops-bound floor = {tot_f / PEAK_TF * 1e3:.1f} ms")


if __name__ == "__main__":
    from paddle_tpu.core.tpu_lock import tpu_singleflight

    with tpu_singleflight():  # one real chip: serialize vs bench/tools
        main()

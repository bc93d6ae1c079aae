"""Fused matmul+BN kernels (ops/pallas/fused_dense_bn.py) — the forward
half of the ResNet byte-floor line-item (PROFILE.md round 5). Executed
on CPU via the pallas interpreter (the real kernel bodies, not a
fallback): value + gradient parity vs the XLA reference, and an
end-to-end fused "bottleneck slice" (1x1 -> BN -> relu -> 1x1) vs its
unfused equivalent. The interpreter is ASKED for here (interpret=True);
the kernels never pick it from the platform, and their TPU compiles are
checked by tests/test_tpu_aot_compile.py."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops.pallas import fused_dense_bn as F

matmul_stats = functools.partial(F.matmul_stats, interpret=True)
bn_act_matmul = functools.partial(F.bn_act_matmul, interpret=True)
bn_act_matmul_stats = functools.partial(F.bn_act_matmul_stats,
                                        interpret=True)


def _xw(rng, M=256, K=128, N=256, dtype=jnp.float32):
    x = jnp.asarray(rng.randn(M, K), dtype)
    w = jnp.asarray(rng.randn(K, N) * 0.1, dtype)
    return x, w


def test_matmul_stats_parity(rng):
    x, w = _xw(rng)
    y, mean, var = jax.jit(matmul_stats)(x, w)
    yr, mr, vr = F._mm_stats_ref(x, w)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(mean), np.asarray(mr),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(var), np.asarray(vr),
                               rtol=1e-3, atol=1e-3)


def test_matmul_stats_grads(rng):
    x, w = _xw(rng, M=128, K=64, N=128)
    cty = jnp.asarray(rng.randn(128, 128), jnp.float32)
    ctm = jnp.asarray(rng.randn(128), jnp.float32)
    ctv = jnp.asarray(rng.randn(128), jnp.float32)

    def loss(fn, x, w):
        y, m, v = fn(x, w)
        return (y * cty).sum() + (m * ctm).sum() + (v * ctv).sum()

    gx, gw = jax.grad(lambda x, w: loss(matmul_stats, x, w),
                      argnums=(0, 1))(x, w)
    gxr, gwr = jax.grad(lambda x, w: loss(F._mm_stats_ref, x, w),
                        argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gxr),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gwr),
                               rtol=1e-4, atol=1e-4)


def test_bn_act_matmul_parity_and_grads(rng):
    x, w = _xw(rng, M=128, K=128, N=128)
    scale = jnp.asarray(rng.rand(128) + 0.5, jnp.float32)
    shift = jnp.asarray(rng.randn(128) * 0.1, jnp.float32)
    for relu in (True, False):
        y = jax.jit(lambda *a: bn_act_matmul(*a, relu=relu))(
            x, scale, shift, w)
        yr = F._bn_mm_ref(x, scale, shift, w, relu)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=1e-5, atol=1e-5)
    ct = jnp.asarray(rng.randn(128, 128), jnp.float32)

    def loss(fn):
        return lambda x, s, b, w: (fn(x, s, b, w) * ct).sum()

    g = jax.grad(loss(lambda *a: bn_act_matmul(*a, relu=True)),
                 argnums=(0, 1, 2, 3))(x, scale, shift, w)
    gr = jax.grad(loss(lambda *a: F._bn_mm_ref(*a, True)),
                  argnums=(0, 1, 2, 3))(x, scale, shift, w)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_fused_bottleneck_slice_matches_unfused(rng):
    """1x1 conv -> BN -> relu -> 1x1 conv, fused (stats in epilogue,
    apply in consumer prologue — the normalized tensor never exists as
    a standalone array) vs the plain XLA composition, values + grads."""
    M, C1, C2, C3 = 256, 64, 128, 64
    x = jnp.asarray(rng.randn(M, C1), jnp.float32)
    w1 = jnp.asarray(rng.randn(C1, C2) * 0.1, jnp.float32)
    w2 = jnp.asarray(rng.randn(C2, C3) * 0.1, jnp.float32)
    gamma = jnp.asarray(rng.rand(C2) + 0.5, jnp.float32)
    beta = jnp.asarray(rng.randn(C2) * 0.1, jnp.float32)

    def fused(x, w1, gamma, beta, w2):
        y, mean, var = matmul_stats(x, w1)
        scale, shift = F.fold_bn(mean, var, gamma, beta)
        return bn_act_matmul(y, scale, shift, w2, relu=True)

    def unfused(x, w1, gamma, beta, w2):
        y = x @ w1
        mean = jnp.mean(y, axis=0)
        var = jnp.maximum(jnp.mean(y * y, axis=0) - mean * mean, 0.0)
        yn = (y - mean) * jax.lax.rsqrt(var + 1e-5) * gamma + beta
        return jnp.maximum(yn, 0.0) @ w2

    out_f = jax.jit(fused)(x, w1, gamma, beta, w2)
    out_u = unfused(x, w1, gamma, beta, w2)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_u),
                               rtol=2e-4, atol=2e-4)

    ct = jnp.asarray(rng.randn(M, C3), jnp.float32)
    gf = jax.grad(lambda *a: (fused(*a) * ct).sum(),
                  argnums=(0, 1, 2, 3, 4))(x, w1, gamma, beta, w2)
    gu = jax.grad(lambda *a: (unfused(*a) * ct).sum(),
                  argnums=(0, 1, 2, 3, 4))(x, w1, gamma, beta, w2)
    for a, b in zip(gf, gu):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


def test_resnet_fused_1x1_matches_unfused(rng, monkeypatch):
    """ResNetConfig(fused_1x1=True): same loss and same BN running-stat
    updates as the XLA path on a single device. f64: conv-vs-matmul
    reduction-order noise at f32 gets amplified to percent level by
    ReLU-kink subgradient flips through 16 BN layers (the same
    phenomenon the dp-parity tests hit — see dryrun path 4 notes), so
    the tight comparison runs in x64 like they do."""
    import dataclasses

    from paddle_tpu.models import resnet

    # the model path compiles the kernels for the chip; here the test
    # steers it onto the interpreter
    monkeypatch.setattr(F, "matmul_stats", matmul_stats)
    monkeypatch.setattr(F, "bn_act_matmul_stats", bn_act_matmul_stats)
    base = dataclasses.replace(resnet.ResNetConfig.tiny(),
                               dtype="float64")
    batch = resnet.make_batch(jax.random.key(1), base, 8, hw=32,
                              data_format="NHWC")
    out = {}
    params, _ = resnet.init(jax.random.key(0), base)  # same for both
    for tag, fused in (("xla", False), ("fused", True)):
        cfg = dataclasses.replace(base, fused_1x1=fused)

        def fwd(p):
            return resnet.loss_fn(p, cfg, batch, None,
                                  data_format="NHWC")

        # jitted: op-by-op dispatch of ~50 interpreted kernel calls and
        # the eager backward cost 4x the one compile
        (l, aux), grads = jax.jit(
            jax.value_and_grad(fwd, has_aux=True))(params)
        out[tag] = (float(l), aux, grads)
    l_x, upd_x, g_x = out["xla"]
    l_f, upd_f, g_f = out["fused"]
    assert abs(l_x - l_f) < 1e-9 * max(1.0, abs(l_x)), (l_x, l_f)
    # BN running-stat updates agree (the fused stats epilogues feed the
    # same EMA contract)
    for k in upd_x:
        np.testing.assert_allclose(np.asarray(upd_f[k]),
                                   np.asarray(upd_x[k]),
                                   rtol=1e-8, atol=1e-10, err_msg=k)
    flat_x = jax.tree_util.tree_leaves(g_x)
    flat_f = jax.tree_util.tree_leaves(g_f)
    # 1e-6: the classifier head computes in f32 by design, capping grad
    # agreement at f32 noise even under x64 activations
    for a, b in zip(flat_f, flat_x):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-8)

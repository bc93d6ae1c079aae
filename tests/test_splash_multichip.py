"""Multi-chip splash attention (VERDICT r5 item 4): the tuned kernel must
COMPOSE with dp/sp/tp instead of falling back to XLA scores under >1-device
meshes. These tests EXECUTE the real splash kernel on the virtual CPU mesh
via the pallas interpreter (interpret=True runs the same kernel body), and
assert the gate's own counters so a silent fallback fails the test.

Routes under test (ops/pallas/attention.py _multichip_splash_route):
- "shardmap":  seq unsharded -> manualize (batch, heads), zero collectives
- "ring":      seq sharded, full mask -> ring_splash (lse-merged blocks)
- "ring_xla":  seq sharded, causal -> exact XLA-block ring (static splash
               masks cannot track the rotating block's diagonal)
- single-device "splash" path must be unaffected (no regression).
- since PR 54 the "shardmap" wrapper also carries the short kernel
  (_short_mha) for short unmasked bidirectional shapes: "short_shardmap".
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core.flags import set_flags
from paddle_tpu.ops.pallas import attention as A
from paddle_tpu.parallel import MeshConfig, make_mesh, mesh_guard


@pytest.fixture(autouse=True)
def _splash_mode():
    """Force the gate (auto needs T>=1024 AND a TPU platform; 'splash' is
    the explicit opt-in that also runs interpret-mode off-TPU)."""
    set_flags({"FLAGS_flash_attention": "splash"})
    A.GATE_COUNTS.clear()
    yield
    set_flags({"FLAGS_flash_attention": "auto"})


def _qkv(rng, B, T, N, H, dtype=jnp.float32):
    q = jnp.asarray(rng.randn(B, T, N, H), dtype)
    k = jnp.asarray(rng.randn(B, T, N, H), dtype)
    v = jnp.asarray(rng.randn(B, T, N, H), dtype)
    return q, k, v


def _ref(q, k, v, causal=False):
    scale = 1.0 / math.sqrt(q.shape[-1])
    mask = None
    if causal:
        T = q.shape[1]
        mask = jnp.where(jnp.tril(jnp.ones((T, T), jnp.bool_)),
                         0.0, -1e9)[None, None]
    return A._xla_mha(q, k, v, mask, scale)


@pytest.mark.parametrize("T,tp,counter", [
    (384, 2, "splash_shardmap"),  # a length the short kernel does not take
    (256, 2, "short_shardmap"),   # the short kernel, two heads a device
    (128, 1, "short_shardmap"),   # dp alone, as bert_base.dp4 shards it
])
def test_shardmap_splash_dp_tp(rng, T, tp, counter):
    """seq unsharded: the kernel under shard_map(batch, heads) — fwd+bwd
    parity vs the XLA path and the gate counter proves the route ran. ONE
    wrapper (_shardmap_splash_mha) carries splash and, since PR 54, the
    short kernel for the lengths it takes."""
    mesh = make_mesh(MeshConfig(dp=4 // tp, tp=tp), devices=jax.devices()[:4])
    q, k, v = _qkv(rng, 4, T, 4, 64)
    with mesh_guard(mesh):
        out = jax.jit(lambda a, b, c: A.mha(a, b, c))(q, k, v)
        out.block_until_ready()
    assert A.GATE_COUNTS[counter] >= 1, dict(A.GATE_COUNTS)
    assert set(A.GATE_COUNTS) == {counter}, dict(A.GATE_COUNTS)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(q, k, v)),
                               atol=2e-5, rtol=2e-5)
    # backward composes too (splash ships a custom vjp)
    ct = jnp.asarray(rng.randn(*q.shape), jnp.float32)

    def loss(q, k, v):
        return (A.mha(q, k, v) * ct).sum()

    with mesh_guard(mesh):
        g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    # the grad trace must have taken the sharded-splash route again, not
    # a silent XLA fallback
    assert A.GATE_COUNTS["xla"] == 0, dict(A.GATE_COUNTS)
    gr = jax.grad(lambda q, k, v: (_ref(q, k, v) * ct).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_ring_splash_dp_sp_tp(rng):
    """seq sharded, full mask: ring_splash merges normalized splash
    blocks by logsumexp across the sp ring — exact attention."""
    mesh = make_mesh(MeshConfig(dp=2, sp=2, tp=2),
                     devices=jax.devices()[:8])
    q, k, v = _qkv(rng, 2, 512, 2, 64)  # local T = 256 per sp shard
    with mesh_guard(mesh):
        out = jax.jit(lambda a, b, c: A.mha(a, b, c))(q, k, v)
        out.block_until_ready()
    assert A.GATE_COUNTS["ring_splash"] >= 1, dict(A.GATE_COUNTS)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(q, k, v)),
                               atol=2e-5, rtol=2e-5)
    ct = jnp.asarray(rng.randn(*q.shape), jnp.float32)

    def loss(q, k, v):
        return (A.mha(q, k, v) * ct).sum()

    with mesh_guard(mesh):
        g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    # fwd AND grad traces both rode the ring-splash route (the custom
    # VJP's blockwise ring backward) — zero XLA fallbacks
    assert A.GATE_COUNTS["ring_splash"] >= 2, dict(A.GATE_COUNTS)
    assert A.GATE_COUNTS["xla"] == 0, dict(A.GATE_COUNTS)
    gr = jax.grad(lambda q, k, v: (_ref(q, k, v) * ct).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_ring_xla_for_causal_sp(rng):
    """seq sharded + causal: exact XLA-block ring (splash masks are
    static per trace), still inside the one mha() entry point."""
    mesh = make_mesh(MeshConfig(sp=2), devices=jax.devices()[:2])
    q, k, v = _qkv(rng, 2, 256, 2, 64)
    with mesh_guard(mesh):
        out = jax.jit(lambda q, k, v: A.mha(q, k, v, causal=True))(q, k, v)
        out.block_until_ready()
    assert A.GATE_COUNTS["ring_xla"] >= 1, dict(A.GATE_COUNTS)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_ref(q, k, v, causal=True)),
        atol=2e-5, rtol=2e-5)


@pytest.mark.slow
def test_ring_splash_parity_T1024(rng):
    """The verdict's named shape: T=1024 under sp=2, splash blocks vs the
    XLA path (fwd)."""
    mesh = make_mesh(MeshConfig(sp=2), devices=jax.devices()[:2])
    q, k, v = _qkv(rng, 1, 1024, 2, 64)
    with mesh_guard(mesh):
        out = jax.jit(lambda a, b, c: A.mha(a, b, c))(q, k, v)
        out.block_until_ready()
    assert A.GATE_COUNTS["ring_splash"] >= 1, dict(A.GATE_COUNTS)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(q, k, v)),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("T,counter", [(384, "splash"), (256, "short")])
def test_single_device_splash_unchanged(rng, T, counter):
    """No single-chip regression: a 1-device mesh still takes the plain
    kernel path (here via the interpreter), not a sharded wrapper."""
    mesh = make_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
    q, k, v = _qkv(rng, 2, T, 2, 64)
    with mesh_guard(mesh):
        out = jax.jit(lambda a, b, c: A.mha(a, b, c))(q, k, v)
        out.block_until_ready()
    assert dict(A.GATE_COUNTS) == {counter: 1}
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(q, k, v)),
                               atol=2e-5, rtol=2e-5)

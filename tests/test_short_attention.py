"""The fused short-sequence attention kernel (`ops/pallas/attention.py
_short_mha`) and its place in `mha`'s gate.

The kernel bodies run here under the Pallas interpreter, which
`FLAGS_flash_attention=splash` asks for off the chip; what the TPU's compiler
makes of them is `tests/test_tpu_aot_compile.py`'s, and the chip's own answer
`chip_smoke.py`'s phase `short_attention`. The reference is `_xla_mha` in
float32, the route the kernel took BERT's attention from."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core.flags import set_flags
from paddle_tpu.ops.pallas import attention as A


@pytest.fixture
def splash_flag():
    set_flags({"FLAGS_flash_attention": "splash"})
    A.GATE_COUNTS.clear()
    yield
    set_flags({"FLAGS_flash_attention": "auto"})


def _operands(shape, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    return [jax.random.normal(k, shape, jnp.float32) for k in ks]


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


# B, T, heads, head_dim, sequences a grid step: batches that are and are not
# a multiple of the step's block, one head and two heads a 128-lane tile, a
# block of one, two and three loop iterations of the kernel
_SHAPES = [(4, 128, 12, 64, 2), (3, 128, 12, 64, 2), (2, 256, 4, 128, 1),
           (3, 256, 2, 64, 2), (5, 128, 4, 128, 4), (9, 128, 2, 64, 8),
           (13, 128, 2, 64, 12)]


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 1.5e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("B,T,N,H,tile", _SHAPES)
def test_context_and_gradients_match_the_xla_route(monkeypatch, B, T, N, H,
                                                   tile, dtype, tol):
    """Context, dq, dk and dv against `_xla_mha` in float32. In float32 the
    kernel is the same arithmetic (2e-5); in bf16 it rounds its operands and
    its probabilities once (0.2-0.9% here; the XLA route's bf16 scores are
    further from the float32 answer than that)."""
    q, k, v, ct = _operands((B, T, N, H), seed=B * T + N)
    scale = 1.0 / math.sqrt(H)
    monkeypatch.setattr(A, "_SHORT_TILE_BYTES",
                        tile * T * 128 * jnp.dtype(dtype).itemsize)
    assert A._short_tile(B, T, 128, jnp.dtype(dtype).itemsize) == tile

    def ref(q, k, v):
        out = A._xla_mha(q, k, v, None, scale)
        return (out * ct).sum(), out

    def new(q, k, v):
        out = A._short_mha(q.astype(dtype), k.astype(dtype), v.astype(dtype),
                           scale, interpret=True)
        assert out.dtype == dtype and out.shape == q.shape
        return (out.astype(jnp.float32) * ct).sum(), out

    (_, want), gw = jax.value_and_grad(ref, (0, 1, 2), has_aux=True)(q, k, v)
    (_, got), gg = jax.jit(jax.value_and_grad(new, (0, 1, 2), has_aux=True)
                           )(q, k, v)
    assert _rel(got, want) < tol
    for a, b in zip(gg, gw):
        assert np.all(np.isfinite(np.asarray(a)))
        assert _rel(a, b) < tol


def test_row_statistics_are_the_logsumexp_of_the_scaled_scores():
    """The forward's residual `lse [B, tiles, heads a tile, T]` float32
    (head n at tile n // 2, row n % 2 for 64-wide heads), which the
    backward's probabilities are rebuilt from."""
    q, k, v, _ = _operands((2, 128, 4, 64), seed=7)
    flat = (2, 128, 256)
    _, (_, _, _, lse) = A._short_attention_fwd(
        q.reshape(flat), k.reshape(flat), v.reshape(flat), 4, 0.25, True)
    want = jax.nn.logsumexp(
        jnp.einsum("btnh,bsnh->bnts", q, k) * 0.25, axis=-1)
    assert lse.shape == (2, 2, 2, 128) and lse.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(lse).reshape(2, 4, 128),
                               np.asarray(want), atol=2e-5, rtol=2e-5)


def test_the_gate_counts_the_bert_shape_as_short(splash_flag):
    """BERT's call: `[B, 128, 12, 64]`, no mask, not causal. One count a
    trace, and the backward is the kernel's own (no second trace of mha)."""
    q, k, v, ct = _operands((2, 128, 12, 64))
    out, grads = jax.jit(jax.value_and_grad(
        lambda q, k, v: (A.mha(q, k, v) * ct).sum(), (0, 1, 2)))(q, k, v)
    assert dict(A.GATE_COUNTS) == {"short": 1}
    want = jax.grad(lambda q, k, v: (A._xla_mha(q, k, v, None, 0.125)
                                     * ct).sum(), (0, 1, 2))(q, k, v)
    for a, b in zip(grads, want):
        assert _rel(a, b) < 2e-5


@pytest.mark.parametrize("case,key", [
    ("causal", "splash"), ("masked", "xla"), ("T64", "xla"),
    ("T1024", "splash"), ("T384", "splash"), ("head32", "xla"),
    ("cross", "xla"), ("lanes64", "splash")])
def test_the_gate_keeps_the_old_routes(splash_flag, case, key):
    """Everything the short kernel does not take goes where it went: causal
    and long sequences to splash, masks and odd shapes to XLA; so does a
    length nobody timed (384) and a head count that leaves half a tile."""
    T = {"T64": 64, "T1024": 1024, "T384": 384}.get(case, 128)
    N, H = {"head32": (4, 32), "lanes64": (1, 64)}.get(case, (2, 64))
    q = jnp.ones((1, T, N, H), jnp.float32)
    k = jnp.ones((1, 64 if case == "cross" else T, N, H), jnp.float32)
    mask = jnp.zeros((1, 1, 1, T), jnp.float32) if case == "masked" else None
    jax.eval_shape(lambda q, k: A.mha(q, k, k, mask=mask,
                                      causal=case == "causal"), q, k)
    assert dict(A.GATE_COUNTS) == {key: 1}


@pytest.mark.parametrize("mode,platform,key", [
    ("off", "tpu", "xla"), ("off", "cpu", "xla"), ("auto", "cpu", "xla"),
    ("auto", "tpu", "short"), ("splash", "cpu", "short")])
def test_flag_and_platform_decide_as_for_splash(monkeypatch, mode, platform,
                                                key):
    """`off` keeps the XLA route on the chip too; off the chip nothing but
    the explicit request runs a kernel."""
    monkeypatch.setattr(A, "_platform", lambda q: platform)
    monkeypatch.setattr(A, "_short_mha", lambda q, *a, **kw: q)
    q = jnp.ones((2, 128, 12, 64), jnp.bfloat16)
    set_flags({"FLAGS_flash_attention": mode})
    A.GATE_COUNTS.clear()
    try:
        jax.eval_shape(lambda q: A.mha(q, q, q), q)
    finally:
        set_flags({"FLAGS_flash_attention": "auto"})
    assert dict(A.GATE_COUNTS) == {key: 1}


@pytest.mark.parametrize("T", [128, 256, 512, 384, 1024])
def test_only_timed_lengths_are_admitted(T):
    q = jax.ShapeDtypeStruct((2, T, 12, 64), jnp.bfloat16)
    assert A._short_shape(q, q, None, False) == (T in A._SHORT_T)
    assert all(t < A._SPLASH_MIN_T and t % 128 == 0 for t in A._SHORT_T)


def test_bert_encoder_trains_through_the_kernel(splash_flag):
    """`models/bert.py`'s layer as the cells run it (no mask), two layers at
    T = 128: the loss and every parameter's gradient against the XLA
    route's."""
    from paddle_tpu.models import bert

    cfg = bert.BertConfig(vocab_size=256, hidden=128, layers=2, heads=2,
                          mlp_dim=256, max_len=128, dropout=0.0,
                          dtype="float32")
    params, _ = bert.init(jax.random.key(0), cfg)
    ids = jax.random.randint(jax.random.key(1), (2, 128), 0, cfg.vocab_size)

    def loss(p):
        return jnp.square(bert.encode(p, cfg, ids)).mean()

    got = jax.jit(jax.value_and_grad(loss))(params)
    assert dict(A.GATE_COUNTS) == {"short": 2}
    set_flags({"FLAGS_flash_attention": "off"})
    want = jax.jit(jax.value_and_grad(loss))(params)
    assert A.GATE_COUNTS["xla"] == 2
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-5)
    for name in want[1]:
        np.testing.assert_allclose(np.asarray(got[1][name]),
                                   np.asarray(want[1][name]),
                                   atol=1e-5, rtol=1e-3, err_msg=name)

"""The causal prompt kernel (`ops/pallas/attention.py _causal_mha`), its
place in `mha`'s gate, and splash's forward blocks under a causal mask.

The kernel body runs here under the Pallas interpreter, which
`FLAGS_flash_attention=splash` asks for off the chip; what the TPU's compiler
makes of it is `tests/test_tpu_aot_compile.py`'s. The reference is
`_xla_mha` in float32 under a causal mask."""

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
    ks = jax.random.split(jax.random.key(seed), 3)
    return [jax.random.normal(k, shape, jnp.float32) for k in ks]


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _reference(q, k, v, scale):
    return A._xla_mha(q, k, v, A._merge_causal(None, q.shape[1]), scale)


# B, T, heads, head_dim: two heads and one head a 128-lane tile, one tile
# and two, four query blocks and eight
_SHAPES = [(1, 1024, 2, 64), (2, 1024, 4, 64), (1, 1024, 1, 128),
           (1, 2048, 2, 128), (1, 2048, 2, 64)]


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 1.5e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("B,T,N,H", _SHAPES)
def test_context_matches_the_xla_route(B, T, N, H, dtype, tol):
    """In float32 the kernel is the same arithmetic, blocked; in bf16 it
    rounds its operands and, a block at a time, its probabilities (0.3-0.4%
    here)."""
    q, k, v = _operands((B, T, N, H), seed=T + N)
    scale = 1.0 / math.sqrt(H)
    got = A._causal_mha(q.astype(dtype), k.astype(dtype), v.astype(dtype),
                        scale, True)
    assert got.dtype == dtype and got.shape == q.shape
    assert np.all(np.isfinite(np.asarray(got, np.float32)))
    assert _rel(got, _reference(q, k, v, scale)) < tol


@pytest.mark.parametrize("N,H", [(2, 64), (1, 128)])
def test_a_row_sees_nothing_to_its_right(N, H):
    """Keys and values 924 .. 1023 are replaced: the context of rows
    0 .. 923 keeps its bits (nothing above the diagonal is read into a
    row's sums, not even multiplied by zero), the rows after change."""
    q, k, v = [x.astype(jnp.bfloat16)
               for x in _operands((1, 1024, N, H), seed=3)]
    k2 = k.at[:, 924:].set(k[:, 924:] * -3 + 1)
    v2 = v.at[:, 924:].set(v[:, 924:] + 5)
    a = np.asarray(A._causal_mha(q, k, v, 0.125, True), np.float32)
    b = np.asarray(A._causal_mha(q, k2, v2, 0.125, True), np.float32)
    assert np.array_equal(a[:, :924], b[:, :924])
    assert np.all(np.any(a[:, 924:] != b[:, 924:], axis=(0, 2, 3)))


@pytest.mark.parametrize("case,key", [
    ("gpt2", "causal"), ("olmoe", "causal"), ("T4096", "causal"),
    # JoyAI's expanded heads: q and k of 192, v of 128
    ("joyai", "splash"),
    # not causal, a length nobody timed, half a tile of lanes, a mask
    ("full", "splash"), ("T1536", "splash"), ("lanes64", "splash"),
    ("masked", "xla"), ("head32", "xla")])
def test_the_gate_takes_whole_causal_prompts(splash_flag, case, key):
    T = {"T4096": 4096, "T1536": 1536}.get(case, 1024)
    N, H = {"gpt2": (20, 64), "olmoe": (16, 128), "lanes64": (1, 64),
            "head32": (4, 32)}.get(case, (2, 64))
    q = jax.ShapeDtypeStruct((1, T, N, 192 if case == "joyai" else H),
                             jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, T, N, 128 if case == "joyai" else H),
                             jnp.bfloat16)
    mask = jnp.zeros((1, 1, 1, T), jnp.float32) if case == "masked" else None
    jax.eval_shape(lambda q, v: A.mha(q, q, v, mask=mask,
                                      causal=case != "full"), q, v)
    assert dict(A.GATE_COUNTS) == {key: 1}


@pytest.mark.parametrize("mode,platform,T,key", [
    ("off", "tpu", 1024, "xla"), ("auto", "cpu", 1024, "xla"),
    ("auto", "tpu", 1024, "causal"), ("splash", "cpu", 1024, "causal"),
    # under 1024 the chip's prompts keep the XLA route, as before
    ("auto", "tpu", 512, "xla"), ("auto", "cpu", 512, "xla")])
def test_flag_and_platform_decide_as_for_the_other_kernels(
        monkeypatch, mode, platform, T, key):
    monkeypatch.setattr(A, "_platform", lambda q: platform)
    monkeypatch.setattr(A, "_causal_mha", lambda q, *a, **kw: q)
    q = jnp.ones((1, T, 20, 64), jnp.bfloat16)
    set_flags({"FLAGS_flash_attention": mode})
    A.GATE_COUNTS.clear()
    try:
        jax.eval_shape(lambda q: A.mha(q, q, q, causal=True), q)
    finally:
        set_flags({"FLAGS_flash_attention": "auto"})
    assert dict(A.GATE_COUNTS) == {key: 1}


@pytest.mark.parametrize("T", [512, 1024, 1536, 2048, 3072, 4096, 8192])
def test_only_timed_lengths_are_admitted(T):
    q = jax.ShapeDtypeStruct((1, T, 20, 64), jnp.bfloat16)
    assert A._causal_shape(q, q, q, None, True) == (T in A._CAUSAL_T)
    assert not A._causal_shape(q, q, q, None, False)


def test_a_grad_trace_runs_splash_for_both_passes(splash_flag):
    """Differentiated, `mha(causal=True)` is `_splash_mha` forward and
    backward, as it was before the kernel: the lowered text names splash's
    forward with residuals and its dq and dkv kernels and not
    `causal_mha_fwd` (so does its jaxpr), and the gradients are
    `_splash_mha`'s to the bit. An inference trace of the same call holds
    `causal_mha_fwd` and no kernel of splash."""
    q, k, v = [x.astype(jnp.bfloat16)
               for x in _operands((1, 1024, 2, 64), seed=5)]
    ct = _operands((1, 1024, 2, 64), seed=6)[0]

    def loss(attn):
        return lambda q, k, v: (attn(q, k, v).astype(jnp.float32) * ct).sum()

    new = jax.jit(jax.value_and_grad(
        loss(lambda q, k, v: A.mha(q, k, v, causal=True)), (0, 1, 2)))
    old = jax.jit(jax.value_and_grad(
        loss(lambda q, k, v: A._splash_mha(q, k, v, 0.125, True,
                                           interpret=True)), (0, 1, 2)))
    text = new.lower(q, k, v).as_text(debug_info=True)
    traced = str(jax.make_jaxpr(new)(q, k, v))
    assert dict(A.GATE_COUNTS) == {"causal": 1}
    for kernel in ("splash_mha_fwd_residuals", "splash_mha_dq",
                   "splash_mha_dkv"):
        assert kernel in text and kernel in traced, kernel
    assert "causal_mha_fwd" not in text and "causal_mha_fwd" not in traced
    (lw, gw), (lg, gg) = old(q, k, v), new(q, k, v)
    assert np.array_equal(np.asarray(lw), np.asarray(lg))
    for a, b in zip(gg, gw):
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))
    forward = str(jax.make_jaxpr(
        lambda q, k, v: A.mha(q, k, v, causal=True))(q, k, v))
    assert "causal_mha_fwd" in forward and "splash_mha" not in forward


@pytest.mark.parametrize("T", [1024, 1152, 1408, 1536, 2048, 3072, 4096,
                               8192])
def test_splash_skips_something_under_every_causal_mask(T):
    """Splash skips a (query block, key block) pair only where the mask is
    empty over the whole pair: at every causal length the gate admits, the
    forward's blocks leave some pair above the diagonal (a 0 in the block
    mask; 1 is a pair under an iota compare, 2 one with no mask)."""
    blocks = np.asarray(
        A._splash_kernel(T, T, 2, True).fwd_mask_info.block_mask)
    assert (blocks == 0).any(), blocks[0]
    full = np.asarray(
        A._splash_kernel(T, T, 2, False).fwd_mask_info.block_mask)
    assert (full == 2).all()


def test_engine_status_reports_the_prompts_route():
    """`status()["prompt_attention"]` is the gate's counts, a count a traced
    `mha` call: one for a prefill program's layer scan; off the chip a
    prompt takes XLA's ops."""
    from paddle_tpu.models import gpt
    from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine

    cfg = gpt.GPTConfig.tiny()
    params, _ = gpt.init(jax.random.key(0), cfg)
    A.GATE_COUNTS.clear()
    engine = DecodeEngine(params, cfg, DecodeConfig(
        block_size=8, num_blocks=17, decode_slots=(2,), prefill_buckets=(8,),
        max_len=32))
    try:
        assert engine.submit([1, 2, 3], max_new_tokens=2).result(
            timeout_s=120)
        assert engine.status()["prompt_attention"] == {"xla": 1}
    finally:
        engine.stop()

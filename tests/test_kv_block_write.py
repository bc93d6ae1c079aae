"""The block-write kernel (ops/pallas/kv_block_write.py) against the scatter
it replaces on a TPU, on noise-filled pools, through the Pallas TPU
interpreter on the CPU: scalar prefetch, HBM-to-HBM DMAs through a table, a
pool aliased from operand to result all run there. What the interpreter
cannot see (tiling, what the compiler plans for the pool) is
`tests/test_tpu_aot_compile.py`'s, and the chip itself is `chip_smoke.py`'s
serve phases and the benchmark's `correct`.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.models import decoder, gpt
from paddle_tpu.ops.pallas import attention as A
from paddle_tpu.ops.pallas import kv_block_write as BW
from paddle_tpu.serving import kv_cache as kvc

L, NB, BS, MB = 3, 41, 16, 8


def _noise(shape, dtype, seed):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       jnp.dtype(dtype))


# (tokens' lanes, dtype): GPT-2-large's and OLMoE's lane counts scaled to a
# few tiles, bf16 as served and float32 (an engine with precision "f32")
@pytest.mark.parametrize("hd,dtype", [(256, "bfloat16"), (128, "float32"),
                                      (384, "bfloat16")])
@pytest.mark.parametrize("bucket,owned", [(16, 1), (64, 4), (128, 8),
                                          (128, 3), (64, 1)])
def test_kernel_equals_the_block_scatter(bucket, owned, hd, dtype):
    """Full tables, partly allocated ones (tail entries 0: several copies
    land in the null block) and one block: every block but the null one
    equals what the scatter leaves, in every layer."""
    rng = np.random.default_rng(bucket + owned)
    pool = _noise((L, NB, BS, hd), dtype, 1)
    nb = bucket // BS
    kv = _noise((nb, BS, hd), dtype, 2)
    table = kvc.build_block_table(
        rng.permutation(np.arange(1, NB))[:owned], MB)[:nb]
    layer = jnp.int32(2)
    want = np.array(pool.at[layer, table].set(kv))
    got = jax.jit(functools.partial(
        BW.write_blocks, interpret=pltpu.InterpretParams()))(
        pool, layer, kv, jnp.asarray(table))
    got = np.asarray(got)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_array_equal(got[:, 1:], want[:, 1:])
    assert (want[2, table[0]] != np.asarray(pool)[2, table[0]]).any()


def test_gate_is_shut_off_the_tpu_and_at_blocks_that_are_not_whole_tiles(
        monkeypatch):
    bf = jnp.bfloat16
    pool = jnp.zeros((2, 5, 16, 128), bf)
    kv = jnp.zeros((3, 16, 128), bf)
    assert not BW.use_dma(kv, pool)                     # the CPU
    monkeypatch.setattr(A, "_platform", lambda q: "tpu")
    assert BW.use_dma(kv, pool)
    assert BW.use_dma(kv.astype(jnp.float32), pool.astype(jnp.float32))
    assert not BW.use_dma(kv.astype(jnp.float32), pool)         # a cast
    assert not BW.use_dma(kv[:, :8], pool[:, :, :8])    # half a bf16 tile
    assert not BW.use_dma(kv[..., :64], pool[..., :64])         # half lanes
    assert not BW.use_dma(jnp.zeros((3, 16, 2, 64), bf),
                          jnp.zeros((2, 5, 16, 2, 64), bf))     # 5-D pool


def test_prefill_through_the_kernel_agrees_with_the_scatter(monkeypatch):
    """The whole prefill program with the gate answered for (as the AOT
    compile tests do) and the kernel interpreted: the first token and both
    pools equal the scatter's, bit for bit."""
    cfg = gpt.GPTConfig(vocab_size=97, hidden=128, layers=2, heads=2,
                        mlp_dim=256, max_len=MB * BS, dtype="float32")
    params, _ = gpt.init(jax.random.key(3), cfg)
    kv = kvc.KVCacheConfig(layers=2, kv_heads=2, head_dim=64,
                           max_len=cfg.max_len, block_size=BS, num_blocks=NB,
                           dtype="float32")
    pools = (_noise(kv.pool_shape, "float32", 4),
             _noise(kv.pool_shape, "float32", 5))
    ids = jnp.asarray(np.random.default_rng(6).integers(0, 97, (1, 64)),
                      jnp.int32)
    table = jnp.asarray(kvc.build_block_table([7, 3, 30], MB))

    def prefill():
        return jax.jit(lambda p, *a: decoder.prefill(
            cfg.serve_model(), p, *a, block_size=BS, eos_id=-1))(
            params, ids, jnp.int32(37), *pools, table)

    kvc.PREFILL_WRITE_UNITS.clear()
    tok, kp, vp = prefill()
    assert kvc.PREFILL_WRITE_UNITS == {"blocks": 2}
    monkeypatch.setattr(A, "_platform", lambda q: "tpu")
    calls = []

    def interpreted(*a, **kw):
        calls.append(a[2].shape)
        return write(*a, interpret=pltpu.InterpretParams(), **kw)

    write = BW.write_blocks
    monkeypatch.setattr(BW, "write_blocks", interpreted)
    tok2, kp2, vp2 = prefill()
    assert calls == [(4, BS, 128)] * 2, calls
    np.testing.assert_array_equal(np.asarray(tok), np.asarray(tok2))
    np.testing.assert_array_equal(np.asarray(kp)[:, 1:], np.asarray(kp2)[:, 1:])
    np.testing.assert_array_equal(np.asarray(vp)[:, 1:], np.asarray(vp2)[:, 1:])


def test_engine_status_reports_the_write_unit():
    from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine

    cfg = gpt.GPTConfig.tiny()
    params, _ = gpt.init(jax.random.key(0), cfg)
    kvc.PREFILL_WRITE_UNITS.clear()
    engine = DecodeEngine(params, cfg, DecodeConfig(
        block_size=8, num_blocks=17, decode_slots=(2,),
        prefill_buckets=(4, 16), max_len=32))
    try:
        assert engine.submit([1, 2, 3], max_new_tokens=2).result(
            timeout_s=120)
        assert engine.submit(list(range(1, 11)), max_new_tokens=2).result(
            timeout_s=120)
        # K and V of one program each: bucket 4 is under a block of 8
        assert engine.status()["prefill_write"] == {"rows": 2, "blocks": 2}
    finally:
        engine.stop()

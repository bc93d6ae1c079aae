"""Sandbox AOT compile of the benchmark's serving width: GPT-2-large's
decode step at 16 slots and every prefill bucket a serve cell's traffic
file names (64 to 1024: `chat_open`, `doc_closed`), over the
pool `KVCacheConfig.pool_shape` describes, for a DESCRIBED v5e (no chip
attached), with the compiler's memory plan under 5 GB (what the engine
holds resident plus temporaries) of the chip's 16. The topology is
described inside a fixture, never at import: one process at a time can load
the TPU compiler (on-chip-measurement guide, section 2). A compile that
passes is not a chip run."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HBM_BYTES = 16e9
# weights 1.55 GB + pools 3.02 GB resident, temporaries under 0.1 GB (PERF.md
# section 4): a program that copies the pool again plans over 7 GB
PLAN_BYTES = 5e9
SLOTS, BLOCK, CONTEXT = 16, 16, 1024


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_x64_no_cache():
    """Compile as the program runs (x64 off), and keep these compiles out
    of JAX's persistent cache: they cannot be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    x64 = jax.config.jax_enable_x64
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_x64", False)
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_x64", x64)
    jax.config.update("jax_enable_compilation_cache", cache)
    cc.reset_cache()


@pytest.fixture(scope="module")
def gpt2_large(one_chip, no_x64_no_cache):
    from paddle_tpu.models import gpt
    from paddle_tpu.serving.kv_cache import KVCacheConfig

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "gpt2_large.json")) as f:
        model = json.load(f)["model"]
    cfg = gpt.GPTConfig(**model)
    shapes = jax.eval_shape(lambda k: gpt.init(k, cfg)[0], jax.random.key(0))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = {k: sds(v.shape, jnp.bfloat16) for k, v in shapes.items()}
    # the pool as the engine makes it (the layout is KVCacheConfig's alone)
    kv = KVCacheConfig(layers=cfg.layers, kv_heads=cfg.heads,
                       head_dim=cfg.head_dim, max_len=CONTEXT,
                       block_size=BLOCK,
                       num_blocks=SLOTS * (CONTEXT // BLOCK) + 1,
                       dtype="bfloat16")
    pool = sds(kv.pool_shape, jnp.bfloat16)
    return gpt, cfg, params, pool, sds


def _planned(compiled):
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)


def test_gpt2_large_decode_step_16_slots_fits_v5e(gpt2_large):
    gpt, cfg, params, pool, sds = gpt2_large

    def decode(p, ids, positions, kp, vp, bts):
        return gpt.apply_decode_step(p, cfg, ids, positions, kp, vp, bts,
                                     block_size=BLOCK, eos_id=-1)

    compiled = jax.jit(decode, donate_argnums=(3, 4)).lower(
        params, sds((SLOTS,), np.int32), sds((SLOTS,), np.int32), pool, pool,
        sds((SLOTS, CONTEXT // BLOCK), np.int32)).compile()
    assert _planned(compiled) < PLAN_BYTES < HBM_BYTES, \
        compiled.memory_analysis()


@pytest.mark.parametrize("bucket", [64, 128, 256, 512, 1024])
def test_gpt2_large_prefill_bucket_fits_v5e(gpt2_large, bucket):
    gpt, cfg, params, pool, sds = gpt2_large

    def prefill(p, ids, length, kp, vp, bt):
        return gpt.apply_prefill(p, cfg, ids, length, kp, vp, bt,
                                 block_size=BLOCK, eos_id=-1)

    compiled = jax.jit(prefill, donate_argnums=(3, 4)).lower(
        params, sds((1, bucket), np.int32), sds((), np.int32), pool, pool,
        sds((CONTEXT // BLOCK,), np.int32)).compile()
    assert _planned(compiled) < PLAN_BYTES < HBM_BYTES, \
        compiled.memory_analysis()

"""Sandbox AOT compile of the benchmark's serving width: GPT-2-large's
decode step at 16 slots and its 512 and 1024 prefill buckets, for a
DESCRIBED v5e (no chip attached), with the compiler's memory plan under the
chip's 16 GB. The topology is described inside a fixture, never at import:
one process at a time can load the TPU compiler (on-chip-measurement guide,
section 2). A compile that passes is not a chip run."""

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

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "gpt2_large.json")) as f:
        model = json.load(f)["model"]
    cfg = gpt.GPTConfig(**model)
    shapes = jax.eval_shape(lambda k: gpt.init(k, cfg)[0], jax.random.key(0))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = {k: sds(v.shape, jnp.bfloat16) for k, v in shapes.items()}
    n_blocks = SLOTS * (CONTEXT // BLOCK) + 1
    pool = sds((cfg.layers, n_blocks, BLOCK, cfg.heads, cfg.head_dim),
               jnp.bfloat16)
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
    assert _planned(compiled) < HBM_BYTES, compiled.memory_analysis()


@pytest.mark.parametrize("bucket", [512, 1024])
def test_gpt2_large_prefill_bucket_fits_v5e(gpt2_large, bucket):
    gpt, cfg, params, pool, sds = gpt2_large

    def prefill(p, ids, length, kp, vp, bt):
        return gpt.apply_prefill(p, cfg, ids, length, kp, vp, bt,
                                 block_size=BLOCK, eos_id=-1)

    compiled = jax.jit(prefill, donate_argnums=(3, 4)).lower(
        params, sds((1, bucket), np.int32), sds((), np.int32), pool, pool,
        sds((CONTEXT // BLOCK,), np.int32)).compile()
    assert _planned(compiled) < HBM_BYTES, compiled.memory_analysis()

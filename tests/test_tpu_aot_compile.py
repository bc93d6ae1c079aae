"""The main path's Pallas kernels, compiled by the TPU's own compiler for
a DESCRIBED v5e (no chip attached, `interpret=False`) at the widths the
models run them at. Interpret-mode tests cannot see what the Mosaic
lowering refuses (block tiling, VMEM, partitioning): `_mm_stats_pallas`
passed every interpreter test while its (1, bn) stats blocks were
refused at every ResNet-50 shape. A pass here is a compile, not a chip
run — `chip_smoke.py` is the chip run.

Kernels (~0.1-2 s each), and the serve programs of GPT-2-large (the
benchmark's serving width, ~3 s each): what the compiler PLANS for the KV
pool is the one thing about them a CPU run cannot show. Since PR 28 the
decode programs are compiled both ways: with the gather path the gate picks
here, and with the gate answered for the described chip, where decode
attention is the paged kernel of ops/pallas/paged_attention.py.
"""

import collections
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs land in /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops.pallas import attention as A
from paddle_tpu.ops.pallas import fused_dense_bn as F


@pytest.fixture(autouse=True)
def _leave_no_gate_counts():
    """The gates' counters are the PROCESS's: what a compile here counted
    (routes answered for the described chip: `megablox`, `paged`, `short`)
    is cleared when its test ends, so a file that runs after this one in
    the same xdist worker reads its own traces alone
    (`tests/test_olmoe.py` asserts that no `megablox` route was taken)."""
    yield
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    from paddle_tpu.ops.pallas import paged_attention as PA
    from paddle_tpu.ops.pallas import ssm_update as SU

    for counts in (A.GATE_COUNTS, gm.GATE_COUNTS, gm.TILES, PA.GATE_COUNTS,
                   SU.GATE_COUNTS):
        counts.clear()


@pytest.fixture(scope="module")
def v5e():
    """The described 2x2 v5e; module-scoped because describing it loads
    the TPU compiler. JAX's persistent compilation cache is switched off
    around these compiles: an entry written for a described device
    cannot be read back without a chip and only produces warnings. So is
    conftest's x64 mode: the program never enables it, and under it the
    kernels' index maps turn i64, which Mosaic refuses."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    with jax.enable_x64(False):
        yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _splash(causal, grad):
    def fwd(q, k, v):
        return A._splash_mha(q, k, v, 0.125, causal)

    if not grad:
        return fwd
    return jax.grad(lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2))


# ResNet-50's 1x1-conv matmuls at bs=256, (M, K, N): stage-1 conv3,
# stage-3 conv1, stage-4 conv3
_RN50 = [(802816, 64, 256), (50176, 1024, 256), (12544, 512, 2048)]


def _fused(kernel):
    fn = getattr(F, kernel)
    if kernel == "matmul_stats":
        return lambda x, s, b, w: fn(x, w)
    return lambda x, s, b, w: fn(x, s, b, w)


def _paged(heads):
    from paddle_tpu.ops.pallas import paged_attention as PA

    return lambda q, kp, vp, l, t, p: PA.paged_attention(
        q, kp, vp, l, t, p, heads=heads)


def _paged_latent(ql, qr, cp, rp, l, t, p):
    from paddle_tpu.ops.pallas import paged_attention as PA

    return PA.paged_latent_attention(ql, qr, cp, rp, l, t, p,
                                     scale=192 ** -0.5)


def _paged_gqa(heads, kv_heads):
    from paddle_tpu.ops.pallas import paged_attention as PA

    return lambda q, kp, vp, l, t, p: PA.paged_gqa_attention(
        q, kp, vp, l, t, p, heads=heads, kv_heads=kv_heads)


def _state_update(pool, layer, rows, decay, dtx, b, c):
    from paddle_tpu.ops.pallas import ssm_update as SU

    return SU.state_update(pool, layer, rows, decay, dtx, b, c)


def _selective_update(pool, layer, rows, dt, dtx, a, b, c):
    from paddle_tpu.ops.pallas import ssm_update as SU

    return SU.selective_update(pool, layer, rows, dt, dtx, a, b, c)


def _advance_tails(pool, layer, rows, x, w, b):
    from paddle_tpu.ops.pallas import ssm_update as SU

    return SU.advance_tails(pool, layer, rows, x, w, b)


def _selective_scan(x, dt, a, b, c):
    from paddle_tpu.ops.pallas import ssm_scan as SS

    return SS.selective_scan(x, dt, a, b, c)


def _block_write(pool, layer, kv, blocks):
    from paddle_tpu.ops.pallas import kv_block_write as BW

    return BW.write_blocks(pool, layer, kv, blocks)


_QKV = "qkv"
_PAGED = "paged"    # shape: (slots, table blocks, layers, heads, head_dim)
_LATENT = "latent"  # shape: (slots, table blocks, layers, heads, dtype)
_BLOCKS = "blocks"  # shape: (bucket, layers, lanes a token, dtype)
_GQA = "gqa"        # shape: (slots, table blocks, layers, heads, kv, d, dtype)
_STATE = "state"    # shape: (slots, layers, heads, P, N, groups)
_SELECTIVE = "selective"    # shape: (slots, layers, state lanes, channels)
_TAILS = "tails"    # shape: (slots, layers, taps, channels, dtype)
_SCAN = "scan"      # shape: (sequences, tokens, state lanes, channels)
_CASES = [
    # BERT long-seq cell (bs 8, T 4096, 12 heads of 64, bf16), full mask
    pytest.param(_splash(False, False), _QKV, (8, 4096, 12, 64),
                 id="splash-fwd-T4096"),
    pytest.param(_splash(False, True), _QKV, (8, 4096, 12, 64),
                 id="splash-fwdbwd-T4096"),
    # the auto gate's threshold for full masks
    pytest.param(_splash(False, True), _QKV, (8, 1024, 12, 64),
                 id="splash-fwdbwd-T1024"),
    # causal: auto takes splash at EVERY 128-aligned causal shape on the
    # chip, so GPT training reaches it at short T ...
    pytest.param(_splash(True, True), _QKV, (8, 128, 12, 64),
                 id="splash-causal-fwdbwd-T128"),
    pytest.param(_splash(True, True), _QKV, (8, 1024, 12, 64),
                 id="splash-causal-fwdbwd-T1024"),
    # ... and the decode engine's whole-prompt prefill at [1, bucket]
    pytest.param(_splash(True, False), _QKV, (1, 512, 12, 64),
                 id="splash-causal-prefill-T512"),
    # the causal prompt kernel (PR 59) at GPT-2-large's 1024 bucket, and
    # one head a tile at the longest length it takes
    pytest.param(lambda q, k, v: A._causal_mha(q, k, v, 0.125, False), _QKV,
                 (1, 1024, 20, 64), id="causal-prompt-gpt2-large-T1024"),
    pytest.param(lambda q, k, v: A._causal_mha(q, k, v, 128 ** -0.5, False),
                 _QKV, (1, 4096, 16, 128), id="causal-prompt-16x128-T4096"),
    # one ring shard's block (T 4096 over sp=2, 12 heads over tp=2)
    pytest.param(lambda q, k, v: A._splash_block_with_lse(q, k, v), _QKV,
                 (8, 2048, 6, 64), id="splash-block-lse-ringshard"),
    # decode attention through the block table, alone: GPT-2-large's and
    # OLMoE's widths at the benchmark's 16 slots x 1024 tokens; OLMoE's
    # published 4096-token context at 32 slots; float32 pools (an engine
    # with precision "f32"), whose buffers are twice as large
    pytest.param(_paged(20), _PAGED, (16, 64, 36, 20, 64, jnp.bfloat16),
                 id="paged-gpt2-large"),
    pytest.param(_paged(16), _PAGED, (16, 64, 8, 16, 128, jnp.bfloat16),
                 id="paged-olmoe"),
    pytest.param(_paged(16), _PAGED, (32, 256, 4, 16, 128, jnp.bfloat16),
                 id="paged-olmoe-4096x32"),
    pytest.param(_paged(16), _PAGED, (16, 64, 4, 16, 128, jnp.float32),
                 id="paged-olmoe-f32"),
    # latent (MLA) decode attention: 32 heads over ONE 512-lane row a token
    # and the 128-lane pool of the rotary key, at the benchmark's 32 slots
    # x 4608 tokens, and float32 pools
    pytest.param(_paged_latent, _LATENT, (32, 288, 5, 32, jnp.bfloat16),
                 id="paged-latent-joyai-4608x32"),
    pytest.param(_paged_latent, _LATENT, (16, 64, 3, 32, jnp.float32),
                 id="paged-latent-f32"),
    # the same walk under xing4's table of 20480 tokens: a chunk of
    # `_LONG_CHUNK` tokens a copy, the step in sub-tiles (`chunk_tokens`)
    pytest.param(_paged_latent, _LATENT, (32, 1280, 6, 32, jnp.bfloat16),
                 id="paged-latent-xing4-20480x32"),
    # grouped-query decode attention: Nemotron-3-Nano's 32 query heads over
    # 2 K/V heads of 128 at the benchmark's 64 slots x 2560 tokens, and
    # float32 pools
    pytest.param(_paged_gqa(32, 2), _GQA,
                 (64, 160, 1, 32, 2, 128, jnp.bfloat16),
                 id="paged-gqa-nemotron-2560x64"),
    pytest.param(_paged_gqa(32, 2), _GQA,
                 (16, 64, 2, 32, 2, 128, jnp.float32), id="paged-gqa-f32"),
    # the decode step's state update where the rows lie: 64 slots of the
    # 65-row pool of 4 Mamba-2 layers, 64 heads of [64, 128] float32
    pytest.param(_state_update, _STATE, (64, 4, 64, 64, 128, 8),
                 id="ssm-state-update-nemotron-64"),
    # and where a block of heads is PART of the one group: Granite 4.0-H's
    # 128 heads on one B and C, 48 slots of the 49-row pool of 9 layers
    pytest.param(_state_update, _STATE, (48, 9, 128, 64, 128, 1),
                 id="ssm-state-update-granite-128x1"),
    # the selective recurrence's rows where they lie: 128 slots of the
    # 129-row pool of Jamba2-3B's 26 Mamba-1 layers, [16, 5120] float32 a row;
    # and a slot count that is not whole tiles of eight
    pytest.param(_selective_update, _SELECTIVE, (128, 26, 16, 5120),
                 id="ssm-selective-update-jamba-128"),
    pytest.param(_selective_update, _SELECTIVE, (12, 2, 16, 1024),
                 id="ssm-selective-update-12-slots"),
    # (the walk takes 8 such rows a grid step, 16 of the narrow ones: 12
    # and 100 slots leave a last step that is filled up with the null row)
    pytest.param(_selective_update, _SELECTIVE, (100, 2, 16, 5120),
                 id="ssm-selective-update-100-slots"),
    # the convolution's tails where they lie: a row the last 3 inputs of
    # 5120 bf16 channels end to end, [120, 128]: 7.5 bf16 tiles, widened in
    # VMEM to 15 float32 ones; 64 rows a grid step
    pytest.param(_advance_tails, _TAILS, (128, 26, 4, 5120, jnp.bfloat16),
                 id="ssm-advance-tails-jamba-128"),
    pytest.param(_advance_tails, _TAILS, (100, 2, 4, 5120, jnp.bfloat16),
                 id="ssm-advance-tails-100-slots"),
    pytest.param(_advance_tails, _TAILS, (12, 2, 4, 1024, jnp.float32),
                 id="ssm-advance-tails-f32-12-slots"),
    # a prompt's selective scan with its state in VMEM: Jamba2-3B's 5120
    # channels of 16 lanes over the cell's longest bucket, and two
    # sequences of 24 tokens (a block of eight tokens)
    pytest.param(_selective_scan, _SCAN, (1, 512, 16, 5120),
                 id="ssm-selective-scan-jamba-512"),
    pytest.param(_selective_scan, _SCAN, (2, 24, 16, 1024),
                 id="ssm-selective-scan-24-tokens"),
    # multi-query decode attention: Jamba2-3B's 20 query heads over ONE
    # K/V head of 128 (the query block filled up to 32 rows) at the
    # benchmark's 128 slots x 1024 tokens
    pytest.param(_paged_gqa(20, 1), _GQA,
                 (128, 64, 2, 20, 1, 128, jnp.bfloat16),
                 id="paged-mqa-jamba-1024x128"),
    # the latent and the rotary key of a 4096-token prompt into their pools
    pytest.param(_block_write, _BLOCKS, (4096, 5, 512, jnp.bfloat16),
                 id="block-write-latent-4096"),
    pytest.param(_block_write, _BLOCKS, (4096, 5, 128, jnp.bfloat16),
                 id="block-write-rotary-key-4096"),
    # a prompt's K or V into the pool, one DMA a block: the benchmark's
    # longest bucket at GPT-2-large's width, OLMoE's, a 4096-token prompt
    # (256 copies in flight), and float32 pools, whose tile is 8 rows
    pytest.param(_block_write, _BLOCKS, (1024, 36, 1280, jnp.bfloat16),
                 id="block-write-gpt2-large-1024"),
    pytest.param(_block_write, _BLOCKS, (256, 8, 2048, jnp.bfloat16),
                 id="block-write-olmoe-256"),
    pytest.param(_block_write, _BLOCKS, (4096, 4, 2048, jnp.bfloat16),
                 id="block-write-olmoe-4096"),
    pytest.param(_block_write, _BLOCKS, (64, 4, 1280, jnp.float32),
                 id="block-write-f32-64"),
] + [
    pytest.param(_fused(kernel), "xsbw", shape,
                 id=f"{kernel}-{'x'.join(map(str, shape))}")
    for kernel in ("matmul_stats", "bn_act_matmul", "bn_act_matmul_stats")
    for shape in _RN50
]


@pytest.mark.parametrize("fn,kind,shape", _CASES)
def test_kernel_compiles_for_v5e(v5e, fn, kind, shape):
    one = SingleDeviceSharding(v5e[0])

    def sds(s, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(s, dt, sharding=one)

    if kind == _QKV:
        args = (sds(shape),) * 3
    elif kind == _PAGED:
        slots, blocks, layers, heads, d, dt = shape
        pool = sds((layers, slots * blocks + 1, 16, heads * d), dt)
        args = (sds((slots, heads * d), dt), pool, pool, sds((), jnp.int32),
                sds((slots, blocks), jnp.int32), sds((slots,), jnp.int32))
    elif kind == _LATENT:
        slots, blocks, layers, heads, dt = shape
        nb = slots * blocks + 1
        args = (sds((slots, heads, 512), dt), sds((slots, heads, 128), dt),
                sds((layers, nb, 16, 512), dt), sds((layers, nb, 16, 128), dt),
                sds((), jnp.int32), sds((slots, blocks), jnp.int32),
                sds((slots,), jnp.int32))
    elif kind == _GQA:
        slots, blocks, layers, heads, kv, d, dt = shape
        pool = sds((layers, slots * blocks + 1, 16, kv * d), dt)
        args = (sds((slots, heads * d), dt), pool, pool, sds((), jnp.int32),
                sds((slots, blocks), jnp.int32), sds((slots,), jnp.int32))
    elif kind == _STATE:
        slots, layers, H, P, N, G = shape
        f32 = jnp.float32
        args = (sds((layers, slots + 1, H, P, N), f32), sds((), jnp.int32),
                sds((slots,), jnp.int32), sds((slots, H), f32),
                sds((slots, H, P), f32), sds((slots, G, N), f32),
                sds((slots, G, N), f32))
        dt = f32
    elif kind == _SELECTIVE:
        slots, layers, N, C = shape
        f32 = jnp.float32
        args = (sds((layers, slots + 1, N, C), f32), sds((), jnp.int32),
                sds((slots,), jnp.int32), sds((slots, C), f32),
                sds((slots, C), f32), sds((N, C), f32), sds((slots, N), f32),
                sds((slots, N), f32))
        dt = f32
    elif kind == _TAILS:
        slots, layers, K, C, dt = shape
        args = (sds((layers, slots + 1, (K - 1) * C // 128, 128), dt),
                sds((), jnp.int32), sds((slots,), jnp.int32),
                sds((slots, C), dt), sds((K, C), dt), sds((C,), dt))
    elif kind == _SCAN:
        B, T, N, C = shape
        f32 = jnp.float32
        args = (sds((B, T, C)), sds((B, T, C), f32), sds((N, C), f32),
                sds((B, T, N), f32), sds((B, T, N), f32))
    elif kind == _BLOCKS:
        bucket, layers, hd, dt = shape
        blocks = bucket // 16       # 16 slots of a 1024-token table or more
        args = (sds((layers, 16 * max(blocks, 64) + 1, 16, hd), dt),
                sds((), jnp.int32), sds((blocks, 16, hd), dt),
                sds((blocks,), jnp.int32))
    else:
        M, K, N = shape
        args = (sds((M, K)), sds((K,), jnp.float32),
                sds((K,), jnp.float32), sds((K, N)))
    in_place = kind in (_BLOCKS, _STATE, _SELECTIVE, _TAILS)
    compiled = jax.jit(fn, donate_argnums=(0,) if in_place else ()
                       ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    if kind in (_PAGED, _LATENT, _GQA):
        # a narrow cache's walk takes the pools as their rows `[L, NB*BS,
        # width]` (a run of blocks is one span of rows, one copy): the same
        # bytes, read where they lie, never a copy of a pool or of a layer
        ma = compiled.memory_analysis()
        pool = args[2]
        layer_bytes = np.prod(pool.shape[1:]) * pool.dtype.itemsize
        assert ma.temp_size_in_bytes < layer_bytes / 8, (ma, layer_bytes)
    if in_place:            # the pool is written where it lies
        ma = compiled.memory_analysis()
        assert ma.alias_size_in_bytes >= np.prod(args[0].shape) \
            * jnp.dtype(dt).itemsize and ma.temp_size_in_bytes < 1e6, ma


@pytest.mark.parametrize("T", [2048, 3072])
def test_splash_prefill_at_latent_widths_compiles_for_v5e(v5e, T):
    """A whole prompt of the latent model: scores 192 wide, values 128,
    32 heads, at a bucket that is a power of two and at 3072, which the
    tuned 2048-token KV block does not divide (the kernel's blocks are
    then the largest multiples of 128 that do: `A._block`)."""
    one = SingleDeviceSharding(v5e[0])
    qk = jax.ShapeDtypeStruct((1, T, 32, 192), jnp.bfloat16, sharding=one)
    v = jax.ShapeDtypeStruct((1, T, 32, 128), jnp.bfloat16, sharding=one)
    compiled = jax.jit(
        lambda q, k, v: A._splash_mha(q, k, v, 192 ** -0.5, True)
    ).lower(qk, qk, v).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.output_shardings is not None
    assert [A._block(c, T) for c in (1024, 2048, 512)] \
        == ([1024, 2048, 512] if T == 2048 else [1024, 1536, 512])
    # the sizes every shape had before are the tuned ones still
    for t in (128, 512, 1024, 2048, 4096, 8192):
        assert A._block(1024, t) == min(1024, t)
        assert A._block(2048, t) == min(2048, t)


@pytest.mark.parametrize("tp,sp,counter", [
    (2, 1, "splash_shardmap"),   # seq unsharded: dp x tp shard_map wrapper
    (2, 2, "ring_splash"),       # seq sharded: ring with splash blocks
])
def test_multichip_route_compiles_for_v5e_2x2(v5e, tp, sp, counter):
    """mha() under a mesh of the four described chips takes the route the
    gate picks on the real host (the mesh's platform is "tpu", so no
    interpreter and no flag), and forward + backward partition and
    compile. Both routes were refused ("Mosaic kernels cannot be
    automatically partitioned") while their shard_map regions were
    manual over only the axes they split."""
    from paddle_tpu.parallel import MeshConfig, make_mesh, mesh_guard

    mesh = make_mesh(MeshConfig(dp=-1, tp=tp, sp=sp), devices=v5e)
    spec = P("dp", "sp" if sp > 1 else None, "tp", None)
    qkv = jax.ShapeDtypeStruct((8, 4096, 12, 64), jnp.bfloat16,
                               sharding=NamedSharding(mesh, spec))
    A.GATE_COUNTS.clear()
    def loss(q, k, v):
        return A.mha(q, k, v).astype(jnp.float32).sum()

    with mesh_guard(mesh):
        compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))
                           ).lower(qkv, qkv, qkv).compile()
    assert A.GATE_COUNTS[counter] == 1, dict(A.GATE_COUNTS)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    if sp > 1:
        assert "collective-permute" in text  # the ring's ppermute


@pytest.mark.parametrize("B,T,N,H", [
    (256, 128, 12, 64),   # bert_base.pretrain128's call
    (128, 256, 12, 64), (64, 512, 12, 64),   # the other admitted lengths
    (37, 128, 4, 128),    # one head a tile, a batch no tile divides
])
def test_short_attention_compiles_for_v5e(v5e, B, T, N, H):
    """The short kernel, forward and its one-pass backward, at every length
    the gate admits: Mosaic takes the transposed-operand products, the
    masked head pairs and the single-row `lse` stores, inside the VMEM
    limit the call sets."""
    one = SingleDeviceSharding(v5e[0])
    x = jax.ShapeDtypeStruct((B, T, N, H), jnp.bfloat16, sharding=one)
    compiled = jax.jit(jax.value_and_grad(
        lambda q, k, v: A._short_mha(q, k, v, H ** -0.5).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))).lower(x, x, x).compile()
    text = compiled.as_text()
    assert text.count("short_mha_fwd") >= 1 and "short_mha_bwd" in text
    assert "[%d,%d,%d,%d]" % (B, N, T, T) not in text


@pytest.mark.parametrize("tp", [1, 2])
def test_short_attention_under_a_mesh_compiles_for_v5e_2x2(v5e, tp):
    """`bert_base.dp4`'s call (dp = 4, 256 sequences a chip), and the same
    under dp x tp: the gate wraps the short kernel in the shard_map region
    splash already had, with no collective in forward or backward."""
    from paddle_tpu.parallel import MeshConfig, make_mesh, mesh_guard

    mesh = make_mesh(MeshConfig(dp=-1, tp=tp), devices=v5e)
    qkv = jax.ShapeDtypeStruct(
        (1024, 128, 12, 64), jnp.bfloat16,
        sharding=NamedSharding(mesh, P("dp", None, "tp", None)))
    A.GATE_COUNTS.clear()
    with mesh_guard(mesh):
        compiled = jax.jit(jax.value_and_grad(
            lambda q, k, v: A.mha(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))).lower(qkv, qkv, qkv).compile()
    assert dict(A.GATE_COUNTS) == {"short_shardmap": 1}
    text = compiled.as_text()
    assert "short_mha_fwd" in text and "short_mha_bwd" in text
    assert not re.search(r"all-gather|collective-permute|all-to-all", text)
    # the one all-reduce is this test's own: the scalar it differentiates
    assert all(re.search(r"= f32\[\][^ ]* all-reduce\(", ln) for ln in
               text.splitlines() if " all-reduce(" in ln)


# ---------------------------------------------------------------------------
# The serve programs and the KV pool (PERF.md section 6, PR 25). With the
# pools as the layer scan's xs/ys, the compiled decode step sliced every
# layer out of a [36,1025,16,20,64] pool, re-laid it out, wrote it back and
# copied a pool whole: 3.80 GB of temporaries, three quarters of the step's
# device time. Lane-dense pools in the loop's carry are written and read in
# place; these compiles hold the programs to that.
# ---------------------------------------------------------------------------

_BLOCK, _CONTEXT = 16, 1024
_MOVES = ("copy", "copy-start", "copy-done", "dynamic-slice",
          "dynamic-update-slice")


def _described(v5e, module, cfg, slots, context, block=_BLOCK):
    """A family at the cell's widths as shapes on the described chip, the
    way `DecodeEngine.__init__` builds it: (cfg, params, (k_pool, v_pool),
    the programs' `state` (the row pools, then the rated entries' pools),
    the pools' geometry, sds)."""
    from paddle_tpu.serving.kv_cache import KVCacheConfig

    one = SingleDeviceSharding(v5e[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    shapes = jax.eval_shape(lambda k: module.init(k, cfg)[0],
                            jax.random.key(0))
    params = {k: sds(v.shape, jnp.bfloat16) for k, v in shapes.items()}
    sm = cfg.serve_model()
    kv = KVCacheConfig(
        layers=sm.kv_layers, widths=sm.stored, max_len=context,
        block_size=block, rated=tuple(sm.rated),
        num_blocks=slots * (context // block) + 1)
    pools = tuple(sds(shape, jnp.dtype(kv.dtype)) for shape in kv.pool_shapes)
    state = tuple(sds(shape, dt) for shape, dt in
                  sm.state_pools(slots + 1, jnp.bfloat16)) \
        + tuple(sds(shape, jnp.dtype(kv.dtype))
                for shape in kv.rated_pool_shapes)
    return cfg, params, pools, state, kv, sds


@pytest.fixture(scope="module")
def gpt2_large(v5e):
    from paddle_tpu.models import gpt

    cfg = gpt.GPTConfig(vocab_size=50257, hidden=1280, layers=36, heads=20,
                        mlp_dim=5120, max_len=_CONTEXT, dtype="bfloat16")
    _, params, _, _, _, sds = _described(v5e, gpt, cfg, 16, _CONTEXT)
    return gpt, cfg, params, sds


def _pool(cfg, sds, slots):
    """The pool as the ENGINE makes it: KVCacheConfig's shape and dtype."""
    from paddle_tpu.serving.kv_cache import KVCacheConfig

    kv = KVCacheConfig(layers=cfg.layers, kv_heads=cfg.heads,
                       head_dim=cfg.head_dim, max_len=_CONTEXT,
                       block_size=_BLOCK,
                       num_blocks=slots * (_CONTEXT // _BLOCK) + 1)
    return sds(kv.pool_shape, jnp.dtype(kv.dtype))


def _compile_decode(gpt2_large, slots):
    gpt, cfg, params, sds = gpt2_large
    pool = _pool(cfg, sds, slots)
    return pool, jax.jit(
        lambda p, ids, pos, kp, vp, bts: gpt.apply_decode_step(
            p, cfg, ids, pos, kp, vp, bts, block_size=_BLOCK, eos_id=-1),
        donate_argnums=(3, 4)).lower(
        params, sds((slots,), np.int32), sds((slots,), np.int32), pool, pool,
        sds((slots, _CONTEXT // _BLOCK), np.int32)).compile()


def _compile_prefill(gpt2_large, bucket):
    from paddle_tpu.serving import kv_cache as kvc

    gpt, cfg, params, sds = gpt2_large
    pool = _pool(cfg, sds, 16)
    kvc.PREFILL_WRITE_UNITS.clear()
    return pool, jax.jit(
        lambda p, ids, n, kp, vp, bt: gpt.apply_prefill(
            p, cfg, ids, n, kp, vp, bt, block_size=_BLOCK, eos_id=-1),
        donate_argnums=(3, 4)).lower(
        params, sds((1, bucket), np.int32), sds((), np.int32), pool, pool,
        sds((_CONTEXT // _BLOCK,), np.int32)).compile()


def _pool_movers(text, pool_shape):
    """Ops of `_MOVES` whose result is a whole pool `[L, NB, ...]` or one
    layer's slice of it `[1, NB, ...]` / `[NB, ...]`, as (op, dims)."""
    whole = ",".join(map(str, pool_shape))
    rest = ",".join(map(str, pool_shape[1:]))
    shapes = {whole, "1," + rest, rest}
    found = []
    for dims, op in re.findall(
            r"= \(?\w+\[([\d,]+)\]\S* ([a-z-]+)\(", text):
        if op in _MOVES and dims in shapes:
            found.append((op, dims))
    return found


def _kernels(text):
    """The `op_name` of every Mosaic kernel in a compiled program."""
    return re.findall(
        r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"', text)


def _pool_scatters(text, pool_shape):
    """Every scatter into a pool (its result has the pool's element count,
    however XLA has flattened it), as (result dims, update_window_dims).
    A window of ONE dimension is a token's lanes, so one update a token:
    until PR 30 every prefill held two a layer, the largest device op of
    `gpt2_large.doc_closed`: `bf16[590400,1280] scatter(bf16[590400,1280],
    s32[1024,1], bf16[1024,1280]), update_window_dims={1},
    inserted_window_dims={0}` (590400 = 36 x 1025 x 16). A block's window
    is `{1,2}`: `[16,1280]`."""
    return [(dims, window) for dims, window in re.findall(
        r"= \w+\[([\d,]+)\]\S* scatter\(.*?update_window_dims=\{([\d,]*)\}",
        text)
        if np.prod(list(map(int, dims.split(",")))) == np.prod(pool_shape)]


@pytest.mark.parametrize("program", [
    "decode@16", "prefill@512", "prefill@1024",
    # with the gate answered for the described chip, as the program runs
    # there: splash attention and the block-write kernel
    "prefill@512-on-chip", "prefill@1024-on-chip"])
def test_gpt2_large_serve_program_addresses_the_pool_in_place(
        gpt2_large, program, monkeypatch):
    from paddle_tpu.serving import kv_cache as kvc

    program, _, on_chip = program.partition("-")
    if on_chip:
        monkeypatch.setattr(A, "_platform", lambda q: "tpu")
    kind, n = program.split("@")
    A.GATE_COUNTS.clear()
    pool, compiled = (_compile_decode if kind == "decode"
                      else _compile_prefill)(gpt2_large, int(n))
    assert pool.shape == (36, 1025, 16, 1280)
    if kind == "prefill":
        # a prompt's attention: one trace for the 36 layers of the scan;
        # the 1024 bucket takes the causal kernel on the chip (PR 59) with
        # no head-view copy beside it, the 512 bucket XLA's ops as before
        route = "causal" if on_chip and n == "1024" else "xla"
        assert A.GATE_COUNTS == {route: 1}, A.GATE_COUNTS
        if route == "causal":
            assert not re.findall(r"= bf16\[1,20,1024,64\]",
                                  compiled.as_text())
    ma = compiled.memory_analysis()
    # parent (pools in xs/ys, [.., 20, 64]): 3.80 GB decode, 3.60 prefill
    assert ma.temp_size_in_bytes < 0.5e9, ma
    # both pools are written where they lie: the outputs alias the inputs
    assert ma.alias_size_in_bytes >= 2 * np.prod(pool.shape) * 2, ma
    text = compiled.as_text()
    movers = _pool_movers(text, pool.shape)
    assert not movers, movers
    if kind == "decode":
        return
    # a prompt goes in a block at a time (PERF.md section 6, PR 30): K and V
    assert kvc.PREFILL_WRITE_UNITS == {"blocks": 2}, kvc.PREFILL_WRITE_UNITS
    writers = [k for k in _kernels(text) if "kv_block_write" in k]
    scatters = _pool_scatters(text, pool.shape)
    if on_chip:     # one DMA a block, and a profile finds it under kv_write
        assert len(writers) == 2 and all(
            "/layers/" in k and "/kv_write/" in k for k in writers), writers
        assert not scatters, scatters
    else:           # the route off the TPU: one scatter, a block a window
        #             (the parent's row scatter had the window "1")
        assert not writers and [w for _, w in scatters] == ["1,2"] * 2, \
            scatters


@pytest.mark.parametrize("route", ["gather", "paged"])
def test_gpt2_large_decode_step_at_32_slots_leaves_room_on_v5e(
        gpt2_large, route, monkeypatch):
    """Weights 1.55 + pools 6.04 + temporaries: 7.8 GB planned, half the
    chip (the parent planned 15.59 GB and left no room for a prefill)."""
    from paddle_tpu.ops.pallas import paged_attention as PA

    if route == "paged":
        monkeypatch.setattr(A, "_platform", lambda q: "tpu")
    PA.GATE_COUNTS.clear()
    pool, compiled = _compile_decode(gpt2_large, 32)
    assert PA.GATE_COUNTS == {route: 1}, PA.GATE_COUNTS
    assert pool.shape == (36, 2049, 16, 1280)
    ma = compiled.memory_analysis()
    planned = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
               + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    assert planned < 9e9, ma


# ---------------------------------------------------------------------------
# OLMoE-1B-7B at the benchmark's cut (8 layers, 16 slots x 1024 tokens): the
# expert stacks [8,64,2048,1024] reach the grouped-matmul kernel whole. As
# the layer scan's xs each layer's slice was a copy for the kernel's sake:
# 0.8 GB of temporaries, written and read again every layer of every step.
# The gate of ops/pallas/grouped_matmul.py asks where the program will run;
# here that is a described chip, so the test answers for it.
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def olmoe_8l(v5e):
    from paddle_tpu.models import olmoe

    cfg, params, pools, _, _, sds = _described(
        v5e, olmoe, olmoe.OlmoeConfig(layers=8, max_len=_CONTEXT), 16,
        _CONTEXT)
    return cfg, params, pools[0], sds


@pytest.mark.parametrize("program", ["decode@16", "prefill@256"])
def test_olmoe_serve_program_fits_and_leaves_the_experts_in_place(
        olmoe_8l, program, monkeypatch):
    from paddle_tpu.models import decoder
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    from paddle_tpu.serving import kv_cache as kvc

    monkeypatch.setattr(A, "_platform", lambda q: "tpu")
    gm.GATE_COUNTS.clear()
    gm.TILES.clear()
    cfg, params, pool, sds = olmoe_8l
    sm = cfg.serve_model()
    kw = dict(block_size=_BLOCK, eos_id=-1)
    kind, n = program.split("@")
    kvc.PREFILL_WRITE_UNITS.clear()
    if kind == "decode":
        fn, args = decoder.decode_step, (
            sds((16,), np.int32), sds((16,), np.int32), pool, pool,
            sds((16, _CONTEXT // _BLOCK), np.int32))
    else:
        fn, args = decoder.prefill, (
            sds((1, int(n)), np.int32), sds((), np.int32), pool, pool,
            sds((_CONTEXT // _BLOCK,), np.int32))
    compiled = jax.jit(lambda p, *a: fn(sm, p, *a, **kw),
                       donate_argnums=(3, 4)).lower(params, *args).compile()
    assert pool.shape == (8, 1025, 16, 2048)
    ma = compiled.memory_analysis()
    # weights 7.13 GB + pools 1.07 GB resident, the rest temporaries
    planned = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
               + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    assert 8.0e9 < planned < 10e9, ma
    assert ma.temp_size_in_bytes < 0.2e9, ma
    assert ma.alias_size_in_bytes >= 2 * np.prod(pool.shape) * 2, ma
    text = compiled.as_text()
    assert not _pool_movers(text, pool.shape)
    # no op makes a layer's slice of an expert stack: [64,2048,1024] or
    # [64,1024,2048] appears nowhere as a result
    slices = re.findall(r"= \(?bf16\[64,(?:2048,1024|1024,2048)\]", text)
    assert not slices, slices[:3]
    # the three grouped matmuls are the megablox kernel, and the profile
    # will find them under mlp/experts
    assert gm.GATE_COUNTS == {"megablox": 3}, gm.GATE_COUNTS
    assert gm.TILES == {(2048, 1024): (128, 1024, 1024),
                        (1024, 2048): (128, 512, 2048)}, gm.TILES
    kernels = _kernels(text)
    # (the decode program's fourth kernel is attention's: below; the
    # prefill's other two write the prompt's K and V a block at a time)
    assert sum("/mlp/experts/" in k for k in kernels) == 3 and all(
        "/mlp/experts/" in k or "/attention/" in k or "/kv_write/" in k
        for k in kernels), kernels
    if kind == "prefill":
        assert kvc.PREFILL_WRITE_UNITS == {"blocks": 2}
        assert sum("kv_block_write" in k for k in kernels) == 2, kernels
        assert not _pool_scatters(text, pool.shape)


@pytest.mark.parametrize("k, n", [(2688, 1920), (1920, 2688), (1920, 1024)])
def test_grouped_matmuls_gradient_fits_at_the_rules_tiles(v5e, k, n,
                                                          monkeypatch):
    """The backward pass of `grouped_matmul` takes the forward's tuple
    (megablox's `custom_vjp`): a transposed `gmm` for the rows and `tgmm`
    for the matrices, whose float32 accumulator and two output tiles have
    the WEIGHT tile's shape. At Nemotron's widths (tiles of 3.4 MB) and at
    a matrix that is one tile of exactly `TILE_BYTES` it fits the kernel's
    16 MiB; at 4 MiB `tgmm` asked for 16.38 (PERF.md section 6, PR 47)."""
    from paddle_tpu.ops.pallas import grouped_matmul as gm

    monkeypatch.setattr(A, "_platform", lambda q: "tpu")
    gm.GATE_COUNTS.clear()
    gm.TILES.clear()
    one = SingleDeviceSharding(v5e[0])

    def loss(x, w, sizes):
        return gm.grouped_matmul(x, w, sizes).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        jax.ShapeDtypeStruct((384, k), jnp.bfloat16, sharding=one),
        jax.ShapeDtypeStruct((128, k, n), jnp.bfloat16, sharding=one),
        jax.ShapeDtypeStruct((128,), jnp.int32, sharding=one)).compile()
    assert gm.GATE_COUNTS == {"megablox": 1}, gm.GATE_COUNTS
    tm, tk, tn = gm.TILES[k, n]
    assert tn == n and 3.4e6 < tk * tn * 2 <= gm.TILE_BYTES
    # the rows' gradient and the matrices' (the sum's gradient needs no
    # forward value, so the forward kernel is not in the program)
    kernels = _kernels(compiled.as_text())
    assert sorted(k.split("/")[-2] for k in kernels) == [
        "transpose(jvp(jit(gmm)))", "transpose(jvp(jit(tgmm)))"], kernels


# ---------------------------------------------------------------------------
# Decode attention through the block table (PERF.md section 6, PR 28). With
# the gate answered for the described chip the decode programs hold the
# paged kernel where the gather path wrote `[S, 1024, H*D]` for K and for V
# a layer and viewed it as heads (at 64-wide heads a second, padded copy).
# The kernel takes the pools whole and by reference: a plan that grew by a
# pool's size would mean the operand is copied.
# ---------------------------------------------------------------------------

_GATHERED = ("16,1024,20,64", "1024,16,1280", "16,1024,1280",
             "1024,16,2048", "16,1024,2048", "16,1024,16,128")


@pytest.mark.parametrize("family", ["gpt2_large", "olmoe_8l"])
def test_decode_program_reads_the_live_blocks_through_the_table(
        family, request, monkeypatch):
    from paddle_tpu.models import decoder
    from paddle_tpu.ops.pallas import paged_attention as PA

    monkeypatch.setattr(A, "_platform", lambda q: "tpu")
    PA.GATE_COUNTS.clear()
    if family == "gpt2_large":
        pool, compiled = _compile_decode(
            request.getfixturevalue("gpt2_large"), 16)
    else:
        cfg, params, pool, sds = request.getfixturevalue("olmoe_8l")
        sm = cfg.serve_model()
        compiled = jax.jit(
            lambda p, *a: decoder.decode_step(sm, p, *a, block_size=_BLOCK,
                                              eos_id=-1),
            donate_argnums=(3, 4)).lower(
            params, sds((16,), np.int32), sds((16,), np.int32), pool, pool,
            sds((16, _CONTEXT // _BLOCK), np.int32)).compile()
    assert PA.GATE_COUNTS == {"paged": 1}, PA.GATE_COUNTS
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < 0.5e9, ma
    assert ma.alias_size_in_bytes >= 2 * np.prod(pool.shape) * 2, ma
    text = compiled.as_text()
    assert not _pool_movers(text, pool.shape)
    gathered = [d for d in re.findall(r"= \(?\w+\[([\d,]+)\]", text)
                if d in _GATHERED]
    assert not gathered, gathered[:3]
    # one kernel a layer, and a profile finds it under `attention`
    paged = [k for k in _kernels(text) if "paged_attention" in k]
    assert len(paged) == 1 and "/layers/" in paged[0] \
        and "/attention/" in paged[0], _kernels(text)
    # a wide cache's walk takes a block a copy: no run is counted
    assert not re.findall(r"= s32\[[\d,]+\]\S* reduce-window\(.*cumprod",
                          text)


# ---------------------------------------------------------------------------
# The latent model at the benchmark's size (1 dense + 4 expert layers of
# 256 experts, 32 slots x 4608 tokens): what the compiler plans for the
# weights, the two pools and the cached context. Per-head keys and values
# of ONE layer's pool would be 3.0 GB; the decode program's temporaries are
# MBs, because all heads read the one stored row through the kernel.
# ---------------------------------------------------------------------------

_JOYAI_SLOTS, _JOYAI_CONTEXT = 32, 4608


@pytest.fixture(scope="module")
def joyai_5l(v5e):
    from paddle_tpu.models import joyai

    cfg, params, pools, _, kv, sds = _described(
        v5e, joyai, joyai.JoyaiConfig(layers=5, max_len=_JOYAI_CONTEXT),
        _JOYAI_SLOTS, _JOYAI_CONTEXT)
    return cfg, params, pools, kv, sds


@pytest.mark.parametrize("program", ["decode@32", "prefill@1024"])
def test_joyai_serve_program_fits_and_reads_the_latent_cache_in_place(
        joyai_5l, program, monkeypatch):
    from paddle_tpu.models import decoder
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    from paddle_tpu.ops.pallas import paged_attention as PA
    from paddle_tpu.serving import kv_cache as kvc

    monkeypatch.setattr(A, "_platform", lambda q: "tpu")
    cfg, params, pools, kv, sds = joyai_5l
    sm = cfg.serve_model()
    kw = dict(block_size=_BLOCK, eos_id=-1)
    kind, n = program.split("@")
    n = int(n)
    mb = _JOYAI_CONTEXT // _BLOCK
    for counts in (gm.GATE_COUNTS, gm.TILES, PA.GATE_COUNTS, A.GATE_COUNTS,
                   kvc.PREFILL_WRITE_UNITS):
        counts.clear()
    if kind == "decode":
        fn, args = decoder.decode_step, (
            sds((n,), np.int32), sds((n,), np.int32), *pools,
            sds((n, mb), np.int32))
    else:
        fn, args = decoder.prefill, (
            sds((1, n), np.int32), sds((), np.int32), *pools,
            sds((mb,), np.int32))
    compiled = jax.jit(lambda p, *a: fn(sm, p, *a, **kw),
                       donate_argnums=(3, 4)).lower(params, *args).compile()
    assert kv.pool_shapes == ((5, 9217, 16, 512), (5, 9217, 16, 128))
    assert kv.bytes_per_token() == 1280
    ma = compiled.memory_analysis()
    # weights 11.12 GB + pools 0.94 GB resident, the rest temporaries
    planned = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
               + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    assert 12.0e9 < planned < 12.5e9, ma
    assert ma.temp_size_in_bytes < (0.02e9 if kind == "decode" else 0.2e9), ma
    assert ma.alias_size_in_bytes >= kv.pool_bytes(), ma
    text = compiled.as_text()
    for pool in pools:
        assert not _pool_movers(text, pool.shape)
    # no op makes a layer's slice of an expert stack
    slices = re.findall(r"= \(?bf16\[256,(?:2048,768|768,2048)\]", text)
    assert not slices, slices[:3]
    # the expert layers' grouped matmuls are the megablox kernel (traced
    # once: the four expert layers are one scan body), under mlp/experts
    assert gm.GATE_COUNTS == {"megablox": 3}, gm.GATE_COUNTS
    # each matrix whole in one tile of 3.1 MB
    assert gm.TILES == {(2048, 768): (128, 2048, 768),
                        (768, 2048): (128, 768, 2048)}, gm.TILES
    kernels = _kernels(text)
    assert sum("/mlp/experts/" in k for k in kernels) == 3 and all(
        "/mlp/experts/" in k or "/attention/" in k or "/kv_write/" in k
        for k in kernels), kernels
    if kind == "decode":
        assert PA.GATE_COUNTS == {"paged_latent": 1}, PA.GATE_COUNTS
        # the tables' runs (`Tables.runs`: a cumulative product over the
        # 31 pairs of neighbours of each of the 9 chunks of 32 slots'
        # tables) are counted ONCE a step, in the entry computation, for
        # both kernels below and every layer of the scan
        entry = text[text.index("\nENTRY "):]
        windows = re.findall(r"= s32\[32,9,31\]\S* reduce-window\(", text)
        assert len(windows) == 1 and windows[0] in entry, windows
        # the leading dense layer's and the scan body's: two kernels
        latent = [k for k in kernels if "paged_latent_attention" in k]
        assert len(latent) == 2 and all("/attention/" in k for k in latent)
        # nothing holds a slot's cached context, gathered or expanded to
        # heads: no result has a dimension of the context's 4608 tokens or
        # of a table's 288 blocks x 16 (the tables themselves are [32, 288])
        held = [d for d in re.findall(r"= \(?\w+\[([\d,]+)\]", text)
                if {"4608", "288,16"} & {d, *re.findall(r"288,16|4608", d)}]
        assert not held, held[:5]
    else:
        # the leading layer and the scan body: two traces of each
        assert A.GATE_COUNTS == {"splash": 2}, A.GATE_COUNTS
        assert kvc.PREFILL_WRITE_UNITS == {"blocks": 4}
        assert sum("kv_block_write" in k for k in kernels) == 4, kernels
        for pool in pools:
            assert not _pool_scatters(text, pool.shape)


_NEMOTRON_SLOTS, _NEMOTRON_CONTEXT = 64, 2560


@pytest.fixture(scope="module")
def nemotron_9l(v5e):
    from paddle_tpu.models import nemotron_h

    return _described(
        v5e, nemotron_h, nemotron_h.NemotronHConfig(
            pattern="MEMEM*EME", max_len=_NEMOTRON_CONTEXT),
        _NEMOTRON_SLOTS, _NEMOTRON_CONTEXT)


@pytest.mark.parametrize("program", ["decode@64", "prefill@1024"])
def test_nemotron_serve_program_fits_and_updates_the_state_in_place(
        nemotron_9l, program, monkeypatch):
    """One period of Nemotron-3-Nano (4 Mamba-2, 4 expert, 1 attention
    block at the published widths, all 128 experts, the whole vocabulary)
    as the cell serves it: 12.5 GB of weights as laid out, a K/V pool of
    ONE layer and the state row pools, all three donated and written where
    they lie. No op of the decode program holds the slots' states outside
    the pool (the gathered form would: `f32[64,64,64,128]`, 134 MB a
    layer, three times)."""
    from paddle_tpu.models import decoder
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    from paddle_tpu.ops.pallas import paged_attention as PA
    from paddle_tpu.ops.pallas import ssm_update as SU
    from paddle_tpu.serving import kv_cache as kvc

    monkeypatch.setattr(A, "_platform", lambda q: "tpu")
    cfg, params, pools, state, kv, sds = nemotron_9l
    sm = cfg.serve_model()
    kw = dict(block_size=_BLOCK, eos_id=-1)
    kind, n = program.split("@")
    n = int(n)
    mb = _NEMOTRON_CONTEXT // _BLOCK
    for counts in (gm.GATE_COUNTS, gm.TILES, PA.GATE_COUNTS, SU.GATE_COUNTS,
                   kvc.PREFILL_WRITE_UNITS):
        counts.clear()
    if kind == "decode":
        fn, args = decoder.decode_step, (
            sds((n,), np.int32), sds((n,), np.int32), *pools,
            sds((n, mb), np.int32), state, sds((n,), np.int32))
    else:
        fn, args = decoder.prefill, (
            sds((1, n), np.int32), sds((), np.int32), *pools,
            sds((mb,), np.int32), state, sds((), np.int32))
    compiled = jax.jit(lambda p, *a: fn(sm, p, *a, **kw),
                       donate_argnums=(3, 4, 6)).lower(params,
                                                       *args).compile()
    assert kv.pool_shapes == ((1, 10241, 16, 256),) * 2
    assert kv.bytes_per_token() == 1024
    assert [s.shape for s in state] == [(4, 65, 144, 128),
                                        (4, 65, 64, 64, 128)]
    ma = compiled.memory_analysis()
    planned = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
               + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    # weights 12.50 GB + K/V 0.17 GB + state 0.55 GB resident, the rest
    # temporaries
    assert 13.2e9 < planned < 13.6e9, ma
    assert ma.temp_size_in_bytes < (0.05e9 if kind == "decode" else 0.25e9), ma
    state_bytes = sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in state)
    assert ma.alias_size_in_bytes >= kv.pool_bytes() + state_bytes, ma
    text = compiled.as_text()
    for pool in pools:
        assert not _pool_movers(text, pool.shape)
    tails, ssm = state
    # the SSM states, 0.55 GB: the decode program's kernel updates the rows
    # where they lie and nothing else touches the pool; a prefill writes its
    # one row a Mamba block into the donated pool
    moved = collections.Counter(op for op, _ in _pool_movers(text, ssm.shape))
    assert moved == ({} if kind == "decode"
                     else {"dynamic-update-slice": 4}), moved
    # the convolution's tails, 9.6 MB in all (a row's 3 x 6144 values as
    # 144 whole lane tiles, one contiguous block): the prefill writes its
    # row a block into the donated pool, the decode program scatters the
    # slots' rows into it; the pool is small enough that XLA may prefetch
    # it into VMEM (an asynchronous copy), but nothing relays it out and no
    # program slices it by row
    moved = collections.Counter(op for op, _ in
                                _pool_movers(text, tails.shape))
    if kind == "decode":
        assert set(moved) <= {"copy-start", "copy-done"}, moved
    else:
        assert moved == {"dynamic-update-slice": 4}, moved
    # no op makes a layer's slice of an expert stack
    slices = re.findall(r"= \(?bf16\[128,(?:2688,1920|1920,2688)\]", text)
    assert not slices, slices[:3]
    # two grouped matmuls an expert block, the megablox kernel, its
    # columns whole in tiles of 3.4 MB (2688 = 21 x 128 and 1920 = 15 x
    # 128: `grouped_matmul.tiles` goes by divisors)
    assert gm.GATE_COUNTS == {"megablox": 8}, gm.GATE_COUNTS
    assert gm.TILES == {(2688, 1920): (128, 896, 1920),
                        (1920, 2688): (128, 640, 2688)}, gm.TILES
    kernels = _kernels(text)
    assert sum("/mlp/experts/" in k for k in kernels) == 8, kernels
    if kind == "decode":
        assert PA.GATE_COUNTS == {"paged_gqa": 1}, PA.GATE_COUNTS
        assert SU.GATE_COUNTS == {"kernel": 4}, SU.GATE_COUNTS
        assert sum("/attention/" in k for k in kernels) == 1
        assert sum("/ssm/scan/" in k for k in kernels) == 4, kernels
        held = re.findall(r"= \(?f32\[64,64,64,128\]", text)
        assert not held, held[:3]
    else:
        assert kvc.PREFILL_WRITE_UNITS == {"blocks": 2}
        assert sum("kv_block_write" in k for k in kernels) == 2, kernels


@pytest.mark.parametrize("heads,groups,kernel", [
    (128, 1, True),     # Granite 4.0-H: a block of 32 heads inside the group
    (64, 8, True),      # Nemotron-3-Nano: a block of 32 heads is 4 groups
    (64, 64, True),     # lightning attention: a group a head
    (96, 2, False),     # 48 heads a group: a block of 32 would straddle two
])
def test_the_state_updates_gate_takes_whole_groups_or_a_part_of_one(
        heads, groups, kernel, monkeypatch):
    from paddle_tpu.ops.pallas import paged_attention as PA
    from paddle_tpu.ops.pallas import ssm_update as SU

    monkeypatch.setattr(PA, "_on_one_tpu", lambda x: True)
    pool = jax.ShapeDtypeStruct((2, 9, heads, 64, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 256), jnp.bfloat16)
    assert SU._heads_per_block(pool) == 32
    assert SU.use_kernel(x, pool, groups) is kernel
    assert not SU.use_kernel(x, jax.ShapeDtypeStruct(pool.shape,
                                                     jnp.bfloat16), groups)


_GRANITE_SLOTS, _GRANITE_CONTEXT = 48, 8192


@pytest.fixture(scope="module")
def granite_10l(v5e):
    from paddle_tpu.models import granite_hybrid

    return _described(
        v5e, granite_hybrid, granite_hybrid.GraniteHybridConfig(
            pattern="MMMMM*MMMM", held=(0, 36), vocab_size=50176,
            max_len=_GRANITE_CONTEXT),
        _GRANITE_SLOTS, _GRANITE_CONTEXT)


@pytest.mark.parametrize("program", ["decode@48", "prefill@4096"])
def test_granite_serve_program_fits_and_updates_the_state_in_place(
        granite_10l, program, monkeypatch):
    """One period of Granite-4.0-H-Small as the cell serves it (9 Mamba-2
    and 1 attention layer, each followed by 36 held of 72 experts, half the
    vocabulary): 9.51 GB of weights, a K/V pool of ONE layer and 49 state
    rows of 4.2 MB a layer, all donated and written where they lie. The
    decode program's state rows cross HBM twice: the kernel of
    ops/pallas/ssm_update.py in all 9 Mamba layers, at ONE B/C group of 128
    heads (a block of 32 heads is a PART of the group), and no op holds the
    slots' states outside the pool (the gathered form would:
    `f32[48,128,64,128]`, 201 MB a layer, three times). The prefill walks
    its 4096 tokens in slices of 1024."""
    from paddle_tpu.models import decoder
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    from paddle_tpu.ops.pallas import paged_attention as PA
    from paddle_tpu.ops.pallas import ssm_update as SU
    from paddle_tpu.serving import kv_cache as kvc

    monkeypatch.setattr(A, "_platform", lambda q: "tpu")
    cfg, params, pools, state, kv, sds = granite_10l
    sm = cfg.serve_model()
    kw = dict(block_size=_BLOCK, eos_id=-1)
    kind, n = program.split("@")
    n = int(n)
    mb = _GRANITE_CONTEXT // _BLOCK
    for counts in (gm.GATE_COUNTS, gm.TILES, PA.GATE_COUNTS, SU.GATE_COUNTS,
                   kvc.PREFILL_WRITE_UNITS):
        counts.clear()
    if kind == "decode":
        fn, args = decoder.decode_step, (
            sds((n,), np.int32), sds((n,), np.int32), *pools,
            sds((n, mb), np.int32), state, sds((n,), np.int32))
    else:
        fn, args = decoder.prefill, (
            sds((1, n), np.int32), sds((), np.int32), *pools,
            sds((mb,), np.int32), state, sds((), np.int32))
    compiled = jax.jit(lambda p, *a: fn(sm, p, *a, **kw),
                       donate_argnums=(3, 4, 6)).lower(params,
                                                       *args).compile()
    assert kv.pool_shapes == ((1, 24577, 16, 1024),) * 2
    assert kv.bytes_per_token() == 4096
    assert [s.shape for s in state] == [(9, 49, 198, 128),
                                        (9, 49, 128, 64, 128)]
    weights = sum(int(np.prod(p.shape)) * 2 for p in params.values())
    assert weights == 2 * 4_757_211_776, weights
    ma = compiled.memory_analysis()
    planned = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
               + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    print(program, "planned", planned, ma)
    # weights 9.51 GB + K/V 1.61 GB + state 1.87 GB resident, the rest
    # temporaries
    assert 12.9e9 < planned < 14.2e9, ma
    state_bytes = sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in state)
    assert ma.alias_size_in_bytes >= kv.pool_bytes() + state_bytes, ma
    text = compiled.as_text()
    for pool in pools:
        assert not _pool_movers(text, pool.shape)
    tails, ssm = state
    moved = collections.Counter(op for op, _ in _pool_movers(text, ssm.shape))
    if kind == "decode":
        assert moved == {}, moved
    # no op makes a layer's slice of an expert stack
    slices = re.findall(r"= \(?bf16\[36,(?:4096,768|768,4096)\]", text)
    assert not slices, slices[:3]
    kernels = _kernels(text)
    if kind == "decode":
        # three grouped matmuls an expert layer, the megablox kernel
        assert gm.GATE_COUNTS == {"megablox": 30}, gm.GATE_COUNTS
        assert sum("/mlp/experts/" in k for k in kernels) == 30, kernels
        assert PA.GATE_COUNTS == {"paged_gqa": 1}, PA.GATE_COUNTS
        assert SU.GATE_COUNTS == {"kernel": 9}, SU.GATE_COUNTS
        assert sum("/attention/" in k for k in kernels) == 1
        updates = [k for k in kernels if "/ssm/scan/" in k]
        assert len(updates) == 9 and all(
            "ssm_state_update" in k for k in updates), kernels
        held = re.findall(r"= \(?f32\[48,128,64,128\]", text)
        assert not held, held[:3]
        assert ma.temp_size_in_bytes < 0.1e9, ma
    else:
        assert ma.temp_size_in_bytes < 1.2e9, ma


_SALA_SLOTS, _SALA_CONTEXT, _SALA_BLOCK = 32, 49152, 64


@pytest.fixture(scope="module")
def minicpm_8l(v5e):
    from paddle_tpu.models import minicpm_sala

    return _described(
        v5e, minicpm_sala, minicpm_sala.MiniCPMSALAConfig(
            mixers="SLLLLLLS", max_len=_SALA_CONTEXT),
        _SALA_SLOTS, _SALA_CONTEXT, _SALA_BLOCK)


@pytest.mark.parametrize("program", ["decode@32", "prefill@32768",
                                     "prefill@16384"])
def test_minicpm_sala_serve_program_fits_whatever_the_prompts_length(
        minicpm_8l, program, monkeypatch):
    """MiniCPM-SALA's stage of 8 layers (2 block-sparse, 6 lightning, the
    published widths and the whole vocabulary) as the cell serves it: 5.64
    GB of weights, K/V and compressed-key pools of 32 x 49152 tokens (3.32
    GB) and 33 state rows (0.42 GB), all donated and written where they
    lie. The decode step takes the block-sparse walk's kernel in both
    sparse layers and the row update's in all six lightning layers; the
    prefill program walks its prompt in slices of 2048 tokens, so its
    temporaries are the same at 16k and at 32k tokens (one pass over 32k
    tokens would hold float32 scores of 32 x T x T: 137 GB)."""
    from paddle_tpu.models import decoder
    from paddle_tpu.ops.pallas import paged_attention as PA
    from paddle_tpu.ops.pallas import ssm_update as SU
    from paddle_tpu.serving import kv_cache as kvc

    monkeypatch.setattr(A, "_platform", lambda q: "tpu")
    cfg, params, pools, state, kv, sds = minicpm_8l
    sm = cfg.serve_model()
    kw = dict(block_size=_SALA_BLOCK, eos_id=-1)
    kind, n = program.split("@")
    n = int(n)
    mb = _SALA_CONTEXT // _SALA_BLOCK
    for counts in (PA.GATE_COUNTS, SU.GATE_COUNTS, kvc.PREFILL_WRITE_UNITS):
        counts.clear()
    if kind == "decode":
        fn, args = decoder.decode_step, (
            sds((n,), np.int32), sds((n,), np.int32), *pools,
            sds((n, mb), np.int32), state, sds((n,), np.int32))
    else:
        fn, args = decoder.prefill, (
            sds((1, n), np.int32), sds((), np.int32), *pools,
            sds((mb,), np.int32), state, sds((), np.int32))
    compiled = jax.jit(lambda p, *a: fn(sm, p, *a, **kw),
                       donate_argnums=(3, 4, 6)).lower(params,
                                                       *args).compile()
    assert kv.pool_shapes == ((2, 24577, 64, 256),) * 2
    assert kv.rated_pool_shapes == ((2, 24584, 1024),)     # whole tiles
    assert kv.bytes_per_token() == 1056 and kv.walk_bytes_per_token() == 1024
    assert [s.shape for s in state] == [(6, 33, 32, 128, 128),
                                        (2, 24584, 1024)]
    ma = compiled.memory_analysis()
    planned = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
               + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    # weights 5.64 GB + block pools 3.32 GB + state 0.42 GB resident
    assert 9.3e9 < ma.argument_size_in_bytes < 9.5e9, ma
    # the stated budget of the temporaries: a decode step a quarter of a
    # GB, a prompt 2 GB WHATEVER its length
    budget = 0.25e9 if kind == "decode" else 2.0e9
    assert ma.temp_size_in_bytes < budget, ma
    assert planned < 11.5e9, ma
    state_bytes = sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in state)
    assert kv.pool_bytes() + state_bytes - 2 * 24577 * 1024 * 2 \
        <= ma.alias_size_in_bytes, ma
    text = compiled.as_text()
    for pool in pools:
        assert not _pool_movers(text, pool.shape), pool.shape
    # the lightning states, 0.42 GB: the decode program's kernel updates
    # the rows where they lie; a prompt's slice writes its one row a layer
    # into the donated pool
    moved = collections.Counter(
        op for op, _ in _pool_movers(text, state[0].shape))
    assert moved == ({} if kind == "decode"
                     else {"dynamic-update-slice": 6}), moved
    kernels = _kernels(text)
    if kind == "decode":
        assert PA.GATE_COUNTS == {"paged_sparse": 1, "select_paged": 1}, \
            PA.GATE_COUNTS
        assert SU.GATE_COUNTS == {"kernel": 6}, SU.GATE_COUNTS
        # a sparse layer: the compressed keys' scores read where they lie,
        # under `select` and no other scope, then the taken blocks' walk;
        # the gather of every slot's whole table is the other branch of
        # ONE conditional a sparse layer (`Tables.few` chooses in the
        # program: a pool that has fragmented)
        assert sum("/attention/" in k for k in kernels) == 4, kernels
        select = [k for k in kernels if "/paged_select_scores/" in k]
        assert len(select) == 2 and all(
            "/attention/select/" in k for k in select), kernels
        assert len(re.findall(r" conditional\(", text)) == 2
        # nor is the pool turned around for the kernel: its blocks are
        # whole tiles of rows (`kv_cache.RATED_ROW_TILE`), so it lies as
        # its shape says and the kernel takes a layer's rows where they
        # lie (`[2, 24577, 1024]` lay with its LAYERS minor)
        assert "bf16[2,24584,1024]{2,1,0:T(8,128)(2,1)}" in text
        assert "copy" not in [
            op for op, _ in _pool_movers(text, state[1].shape)]
        assert sum("/ssm/scan/" in k for k in kernels) == 6, kernels
    else:
        # a slice's K and V go in a block at a time: two sparse layers
        assert kvc.PREFILL_WRITE_UNITS == {"blocks": 4}
        assert not PA.GATE_COUNTS and not SU.GATE_COUNTS


def test_the_sparse_walk_compiles_for_v5e(v5e, monkeypatch):
    """The block-sparse walk alone, at the cell's published shapes (32
    slots, 2 K/V heads of 128 lanes, lists 128 entries wide of which a
    sparse row fills 64, blocks of 64 tokens) and at blocks of 16: a copy
    takes a block's 256 lanes where a slot's heads share the entry and ONE
    K/V head's 128 lanes, into that head's lanes of the buffer, where they
    do not: static lane slices of source AND destination, which the
    interpreter cannot refuse and Mosaic can; with the lists counted
    beside it (`pair_lists`) and at every chunk the route can pick, the
    whole list's 4096 tokens (8 MB of double buffers) among them."""
    from paddle_tpu.ops.pallas import paged_attention as PA

    one = SingleDeviceSharding(v5e[0])

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    for bs, nb, list_tokens, chunk in ((64, 24577, 4096, 4096),
                                       (64, 24577, 2048, 2048),
                                       (16, 513, 1024, 1024),
                                       (16, 513, None, 2048)):
        fn = jax.jit(lambda q, k, v, l, t, p: PA.paged_sparse_attention(
            q, k, v, l, t, p, heads=32, kv_heads=2, list_tokens=list_tokens))
        text = fn.lower(sds((32, 4096)), sds((2, nb, bs, 256)),
                        sds((2, nb, bs, 256)), sds((), np.int32),
                        sds((64, 128), np.int32),
                        sds((64,), np.int32)).compile().as_text()
        assert PA.WALK_CHUNKS["paged_sparse"] == chunk
        assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype,layers,nb,slots,mb", [
    (jnp.bfloat16, 2, 24584, 32, 768), (jnp.bfloat16, 8, 4104, 32, 768),
    (jnp.bfloat16, 3, 4104, 8, 100), (jnp.float32, 2, 1032, 32, 768),
    (jnp.bfloat16, 1, 24584, 64, 1024), (jnp.bfloat16, 2, 24584, 4, 15000)])
def test_the_selections_walk_compiles_for_v5e(v5e, monkeypatch, dtype,
                                              layers, nb, slots, mb):
    """The compressed keys' scores alone, at the cell's 32 slots over a
    table of 768 blocks and at the largest tables and scores the gate
    lets through (`use_paged_select`: the tables and the pieces' counts
    are the scalar memory's, a slot's scores the vector memory's): a
    block of the third pool is ONE row of 1024 lanes, under a tile, and a
    copy addresses whole tiles (a row copy is what Mosaic refuses and the
    interpreter cannot). The pool's blocks are whole tiles
    (`kv_cache.RATED_ROW_TILE`), so the compiler lays it out as its shape
    says and nothing of the pool's size is made beside it."""
    from paddle_tpu.ops.pallas import paged_attention as PA

    one = SingleDeviceSharding(v5e[0])

    def sds(shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    pool = sds((layers, nb, 1024))
    monkeypatch.setattr(PA, "_on_one_tpu", lambda x: True)
    assert PA.use_paged_select(sds((slots, 4096)), pool, 32, 2, 4, 1, mb)
    fn = jax.jit(lambda q, p, l, t, pos: PA.paged_select_scores(
        q, p, l, PA.with_rows(PA.Tables(t, None), pos, 64), pos, kv_heads=2,
        stride=16, block_size=64))
    compiled = fn.lower(sds((slots, 4096)), pool, sds((), np.int32),
                        sds((slots, mb), np.int32),
                        sds((slots,), np.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    laid = re.search(r"entry_computation_layout=\{.*?\w+\[%d,%d,1024\]"
                     r"\{([\d,]+)" % (layers, nb), text).group(1)
    assert laid == "2,1,0", laid
    assert compiled.memory_analysis().temp_size_in_bytes < 4e6


# ---------------------------------------------------------------------------
# Xing4.0-29B-A4B's serve programs at the shapes of its cell
# (xing4_29b_a4b.ctx12k_sessions): 2 dense + 4 expert layers, 32 slots of
# 20480 tokens. Resident: 8.35 GB of bf16 weights + 5.03 GB of latent cache.
# The 12288-token prefill walks the prompt in slices of 2048: its
# temporaries (a slice's four streams, the expanded keys and values of one
# chunk of 1024 keys, float32 scores of 32 heads x 2048 x 1024, the dense
# MLP's 2048 x 9216 twice) are those of ONE slice whatever the bucket; the
# decode step's are a few rows of 14336 lanes.
# ---------------------------------------------------------------------------

_XING4_SLOTS, _XING4_CONTEXT = 32, 20480


@pytest.fixture(scope="module")
def xing4_6l(v5e):
    from paddle_tpu.models import xing4

    cfg, params, pools, _, kv, sds = _described(
        v5e, xing4, xing4.Xing4Config(layers=6, max_len=_XING4_CONTEXT),
        _XING4_SLOTS, _XING4_CONTEXT)
    return cfg, params, pools, kv, sds


@pytest.mark.parametrize("program", ["decode@32", "prefill@12288",
                                     "prefill@8192"])
def test_xing4_serve_program_fits_whatever_the_prompts_length(
        xing4_6l, program, monkeypatch):
    from paddle_tpu.models import decoder
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    from paddle_tpu.ops.pallas import paged_attention as PA
    from paddle_tpu.serving import kv_cache as kvc

    monkeypatch.setattr(A, "_platform", lambda q: "tpu")
    cfg, params, pools, kv, sds = xing4_6l
    sm = cfg.serve_model()
    kw = dict(block_size=_BLOCK, eos_id=-1)
    kind, n = program.split("@")
    n = int(n)
    mb = _XING4_CONTEXT // _BLOCK
    for counts in (gm.GATE_COUNTS, gm.TILES, PA.GATE_COUNTS, A.GATE_COUNTS,
                   kvc.PREFILL_WRITE_UNITS):
        counts.clear()
    if kind == "decode":
        fn, args = decoder.decode_step, (
            sds((n,), np.int32), sds((n,), np.int32), *pools,
            sds((n, mb), np.int32))
    else:
        fn, args = decoder.prefill, (
            sds((1, n), np.int32), sds((), np.int32), *pools,
            sds((mb,), np.int32))
    compiled = jax.jit(lambda p, *a: fn(sm, p, *a, **kw),
                       donate_argnums=(3, 4)).lower(params, *args).compile()
    assert kv.pool_shapes == ((6, 40961, 16, 512), (6, 40961, 16, 128))
    assert kv.pool_bytes() == pytest.approx(5.03e9, rel=1e-2)
    ma = compiled.memory_analysis()
    resident = ma.argument_size_in_bytes
    assert 13.3e9 < resident < 13.5e9, ma
    # the temporaries the issue allows: 0.1 GB a decode step, 1.2 GB a
    # prefill, and the same for the 8192 bucket as for the 12288 one
    assert ma.temp_size_in_bytes < (0.1e9 if kind == "decode" else 1.2e9), ma
    assert ma.alias_size_in_bytes >= kv.pool_bytes(), ma
    text = compiled.as_text()
    for pool in pools:
        assert not _pool_movers(text, pool.shape)
    # no op makes a layer's slice of an expert stack
    slices = re.findall(r"= \(?bf16\[64,(?:3584,1024|1024,3584)\]", text)
    assert not slices, slices[:3]
    assert gm.GATE_COUNTS == {"megablox": 3}, gm.GATE_COUNTS
    assert gm.TILES == {(3584, 1024): (128, 1792, 1024),
                        (1024, 3584): (128, 512, 3584)}, gm.TILES
    kernels = _kernels(text)
    # nothing of the residual path is a kernel, and nothing of it lies
    # under `attention` or `mlp`
    assert not [k for k in kernels if "/mhc" in k], kernels
    assert "/mhc/mhc_map/" in text and "/mhc/mhc_post/" in text
    assert not re.findall(r"/(?:attention|mlp)/[^\"]*mhc", text)
    if kind == "decode":
        assert PA.GATE_COUNTS == {"paged_latent": 1}, PA.GATE_COUNTS
        # the two leading dense layers' and the scan body's
        latent = [k for k in kernels if "paged_latent_attention" in k]
        assert len(latent) == 3 and all("/attention/" in k for k in latent)
        # under a table of 20480 tokens the walk's chunk is `_LONG_CHUNK`
        # tokens (`chunk_tokens`): 20 chunks of 64 blocks a slot, and of
        # each the tables count TWO runs (`Tables.runs`: the lead, a
        # cumulative product over its 63 pairs of neighbours, and the run
        # after the first break, a cumulative sum), ONCE a step, in the
        # entry computation, for the three kernels and every layer
        assert PA.chunk_tokens(kv.walk_bytes_per_token(),
                               _XING4_CONTEXT) == 1024
        entry = text[text.index("\nENTRY "):]
        windows = re.findall(r"= s32\[32,20,63\]\S* reduce-window\(", text)
        assert len(windows) == 2 and all(w in entry for w in windows), \
            windows
        # nothing holds a slot's cached context: no result has a dimension
        # of the context's 20480 tokens or of a table's 1280 blocks x 16
        held = [d for d in re.findall(r"= \(?\w+\[([\d,]+)\]", text)
                if re.search(r"(?:^|,)(?:20480|1280,16)(?:,|$)", d)]
        assert not held, held[:5]
    else:
        # a slice's latent and rotary key go in a block at a time (two
        # leading layers and the scan body, two pools)
        assert kvc.PREFILL_WRITE_UNITS == {"blocks": 6}
        assert sum("kv_block_write" in k for k in kernels) == 6, kernels
        for pool in pools:
            assert not _pool_scatters(text, pool.shape)
        # no temporary is as long as the bucket: the widest thing the
        # prompt's length reaches is its ids
        # (8192 is also W_kvb's width, 32 heads x 256: the 12288 bucket says)
        long = [d for d in re.findall(r"= \(?(?:bf16|f32)\[([\d,]+)\]", text)
                if re.search(r"(?:^|,)12288(?:,|$)", d)]
        assert not long, long[:5]


_JAMBA_SLOTS, _JAMBA_CONTEXT = 128, 1024


@pytest.fixture(scope="module")
def jamba_28l(v5e):
    from paddle_tpu.models import jamba

    return _described(v5e, jamba, jamba.JambaConfig(max_len=_JAMBA_CONTEXT),
                      _JAMBA_SLOTS, _JAMBA_CONTEXT)


# the smallest of the cell's four buckets: a prefill program holds 26 scan
# kernels and takes from half a minute (64 tokens) to a minute and a half
# (512) to compile here; the larger ones compile on the chip at every boot
@pytest.mark.parametrize("program", ["decode@128", "prefill@64"])
def test_jamba_serve_program_fits_and_updates_the_state_in_place(
        jamba_28l, program, monkeypatch):
    """AI21-Jamba2-3B whole (26 Mamba-1 and 2 attention layers at the
    published widths, the whole vocabulary) as `jamba2_3b.chat_closed`
    serves it: 6.06 GB of weights, a K/V pool of TWO layers and the state
    row pools, all three donated and written where they lie. The decode
    program moves the slots' states across HBM twice (the kernel's read
    and write of each row) and not five times: no op holds them outside
    the pool (the gathered form would: `f32[128,16,5120]`, 42 MB a layer,
    three times)."""
    from paddle_tpu.models import decoder
    from paddle_tpu.ops.pallas import paged_attention as PA
    from paddle_tpu.ops.pallas import ssm_update as SU
    from paddle_tpu.serving import kv_cache as kvc

    monkeypatch.setattr(A, "_platform", lambda q: "tpu")
    cfg, params, pools, state, kv, sds = jamba_28l
    sm = cfg.serve_model()
    kw = dict(block_size=_BLOCK, eos_id=-1)
    kind, n = program.split("@")
    n = int(n)
    mb = _JAMBA_CONTEXT // _BLOCK
    for counts in (PA.GATE_COUNTS, SU.GATE_COUNTS, kvc.PREFILL_WRITE_UNITS):
        counts.clear()
    if kind == "decode":
        fn, args = decoder.decode_step, (
            sds((n,), np.int32), sds((n,), np.int32), *pools,
            sds((n, mb), np.int32), state, sds((n,), np.int32))
    else:
        fn, args = decoder.prefill, (
            sds((1, n), np.int32), sds((), np.int32), *pools,
            sds((mb,), np.int32), state, sds((), np.int32))
    compiled = jax.jit(lambda p, *a: fn(sm, p, *a, **kw),
                       donate_argnums=(3, 4, 6)).lower(params,
                                                       *args).compile()
    assert kv.pool_shapes == ((2, 8193, 16, 128),) * 2
    assert kv.bytes_per_token() == 512      # a layer; two layers hold K/V
    assert [s.shape for s in state] == [(26, 129, 120, 128),
                                        (26, 129, 16, 5120)]
    ma = compiled.memory_analysis()
    planned = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
               + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    # weights 6.06 GB + K/V 0.13 GB + state 1.20 GB resident, the rest
    # temporaries: a prompt's are bounded by its bucket's rows of
    # activations, not by a state a token
    assert 7.35e9 < planned < 7.75e9, ma
    assert ma.temp_size_in_bytes < (0.08e9 if kind == "decode" else 0.3e9), ma
    state_bytes = sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in state)
    assert state_bytes == pytest.approx(1.202e9, rel=1e-3)
    assert ma.alias_size_in_bytes >= kv.pool_bytes() + state_bytes, ma
    text = compiled.as_text()
    # the K/V pools, 33.5 MB each (two layers of ONE K/V head): nothing
    # slices or relays them; they are small enough that XLA may prefetch
    # one into VMEM around a prefill's block writes (an asynchronous copy
    # in and out, 0.1 ms of a prefill's several)
    for pool in pools:
        moved = {op for op, _ in _pool_movers(text, pool.shape)}
        assert moved <= ({"copy-start", "copy-done"} if kind == "prefill"
                         else set()), moved
    tails, ssm = state
    # the states, 1.10 GB: the decode program's kernel updates the rows
    # where they lie and nothing else touches the pool; a prefill writes its
    # one row a Mamba layer into the donated pool
    moved = collections.Counter(op for op, _ in _pool_movers(text, ssm.shape))
    assert moved == ({} if kind == "decode"
                     else {"dynamic-update-slice": 26}), moved
    # and the tails, 0.10 GB, the same: the decode program's kernel moves
    # each row on where it lies (no gather, scatter or slice of the pool,
    # no copy of the slots' tails outside it)
    moved = collections.Counter(op for op, _ in
                                _pool_movers(text, tails.shape))
    assert moved == ({} if kind == "decode"
                     else {"dynamic-update-slice": 26}), moved
    kernels = _kernels(text)
    if kind == "decode":
        assert PA.GATE_COUNTS == {"paged_gqa": 1}, PA.GATE_COUNTS
        assert SU.GATE_COUNTS == {"kernel": 26, "tail_kernel": 26}, \
            SU.GATE_COUNTS
        assert sum("/attention/" in k for k in kernels) == 2, kernels
        updates = [k for k in kernels if "/ssm/scan/" in k]
        assert len(updates) == 26 and all(
            "ssm_selective_update" in k for k in updates), kernels
        advances = [k for k in kernels if "/ssm/conv/" in k]
        assert len(advances) == 26 and all(
            "ssm_advance_tails" in k for k in advances), kernels
        held = re.findall(r"= \(?(?:f32\[128,16,5120|bf16\[128,"
                          r"(?:3,5120|120,128|15360))\]", text)
        assert not held, held[:3]
    else:
        assert kvc.PREFILL_WRITE_UNITS == {"blocks": 4}
        assert sum("kv_block_write" in k for k in kernels) == 4, kernels
        # a prompt's scan keeps its state in VMEM, a kernel a Mamba layer,
        # and holds never a state a token of the bucket
        assert SU.GATE_COUNTS == {"scan_kernel": 26}, SU.GATE_COUNTS
        scans = [k for k in kernels if "/ssm/scan/" in k]
        assert len(scans) == 26 and all(
            "ssm_selective_scan" in k for k in scans), kernels
        held = re.findall(r"= \(?f32\[(?:1,)?%d,16,5120\]" % n, text)
        assert not held, held[:3]


_LONGCAT_SLOTS, _LONGCAT_CONTEXT = 128, 1024


@pytest.fixture(scope="module")
def longcat_4l(v5e):
    from paddle_tpu.models import longcat

    cfg, params, pools, _, kv, sds = _described(
        v5e, longcat, longcat.LongcatConfig(
            layers=4, vocab_size=16384, held=(0, 16),
            max_len=_LONGCAT_CONTEXT), _LONGCAT_SLOTS, _LONGCAT_CONTEXT)
    return cfg, params, pools, kv, sds


@pytest.mark.parametrize("program", ["decode@128", "prefill@512"])
def test_longcat_serve_program_fits_its_share_and_both_paths(
        longcat_4l, program, monkeypatch):
    """LongCat-Flash-Chat as `longcat_flash_chat.chat_closed` serves it: 4
    layers of two latent-attention sub-layers (8 cache layers), both dense
    MLPs of 12288, the router over 768 outputs and the 16 experts this
    chip holds of 512, an eighth of the vocabulary: 10.35 GB of weights
    and 1.34 GB of latent cache, donated and written where they lie. The
    latent kernel walks 64 heads a slot (twice the widest before), the
    grouped matmuls run at 6144 x 2048 and 2048 x 6144 over the held
    experts' stacks in place."""
    from paddle_tpu.models import decoder
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    from paddle_tpu.ops.pallas import paged_attention as PA
    from paddle_tpu.serving import kv_cache as kvc

    monkeypatch.setattr(A, "_platform", lambda q: "tpu")
    cfg, params, pools, kv, sds = longcat_4l
    sm = cfg.serve_model()
    kw = dict(block_size=_BLOCK, eos_id=-1)
    kind, n = program.split("@")
    n = int(n)
    mb = _LONGCAT_CONTEXT // _BLOCK
    for counts in (gm.GATE_COUNTS, gm.TILES, PA.GATE_COUNTS, A.GATE_COUNTS,
                   kvc.PREFILL_WRITE_UNITS):
        counts.clear()
    if kind == "decode":
        fn, args = decoder.decode_step, (
            sds((n,), np.int32), sds((n,), np.int32), *pools,
            sds((n, mb), np.int32))
    else:
        fn, args = decoder.prefill, (
            sds((1, n), np.int32), sds((), np.int32), *pools,
            sds((mb,), np.int32))
    compiled = jax.jit(lambda p, *a: fn(sm, p, *a, **kw),
                       donate_argnums=(3, 4)).lower(params, *args).compile()
    assert kv.pool_shapes == ((8, 8193, 16, 512), (8, 8193, 16, 128))
    assert kv.bytes_per_token() == 1280     # a cache layer; 8 of them
    weights = sum(int(np.prod(v.shape)) * 2 for v in params.values())
    assert weights == pytest.approx(10.345e9, rel=1e-3)
    assert params["blk.w_up"].shape == (4, 16, 6144, 2048)
    assert params["blk.router"].shape == (4, 6144, 768)
    ma = compiled.memory_analysis()
    planned = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
               + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    # weights 10.35 GB + pools 1.34 GB resident, the rest temporaries: 14
    # MB of a step's, 0.31 GB of a 512-token prompt's (6144 pairs a layer)
    assert 11.65e9 < planned < 12.05e9, ma
    assert ma.temp_size_in_bytes < (0.03e9 if kind == "decode" else 0.35e9), ma
    assert ma.alias_size_in_bytes >= kv.pool_bytes(), ma
    text = compiled.as_text()
    for pool in pools:
        assert not _pool_movers(text, pool.shape)
    # no op makes a layer's slice of an expert stack (and a step's 14 MB of
    # temporaries hold no copy of a dense matrix, 151 MB, either: a layer's
    # slice of each is read inside the fusion that multiplies by it)
    slices = re.findall(r"= \(?bf16\[16,(?:6144,2048|2048,6144)\]", text)
    assert not slices, slices[:3]
    # the held experts' grouped matmuls are the megablox kernel, under the
    # expert path's own scope and not under `mlp`; whole columns, a tile
    # of 3.1 MB (`grouped_matmul.tiles`)
    assert gm.GATE_COUNTS == {"megablox": 3}, gm.GATE_COUNTS
    assert gm.TILES == {(6144, 2048): (128, 768, 2048),
                        (2048, 6144): (128, 256, 6144)}, gm.TILES
    kernels = _kernels(text)
    assert sum("/shortcut_experts/experts/" in k for k in kernels) == 3 \
        and not any("/mlp/" in k for k in kernels), kernels
    if kind == "decode":
        # the gate admits 64 heads (4 sublane tiles of bf16) and Mosaic
        # takes the kernel's VMEM at them: one kernel a sub-block
        assert PA.GATE_COUNTS == {"paged_latent": 1}, PA.GATE_COUNTS
        latent = [k for k in kernels if "paged_latent_attention" in k]
        assert len(latent) == 2 and all("/attention/" in k for k in latent)
        # (a slot's context gathered, 134 MB a cache layer, would not fit
        # the 14 MB of temporaries above)
    else:
        assert kvc.PREFILL_WRITE_UNITS == {"blocks": 4}
        assert sum("kv_block_write" in k for k in kernels) == 4, kernels
        for pool in pools:
            assert not _pool_scatters(text, pool.shape)


# -- the BERT-base train step (bert_base.pretrain128's shapes) ---------------

_BERT_BATCH, _BERT_SEQ, _BERT_MASKED = 256, 128, 20


def _described_train_step(v5e):
    """The step of `bert_base.pretrain128` as `benchmarks/kinds/train.py`
    builds it (the family's loss, adamw, optimizer states sharded over a
    `dp` of one), lowered for shapes on the described chip."""
    import optax

    from paddle_tpu.models import bert
    from paddle_tpu.parallel import MeshConfig, make_mesh, mesh_guard
    from paddle_tpu.parallel import train as T

    cfg = bert.BertConfig(vocab_size=30522, hidden=768, layers=12, heads=12,
                          mlp_dim=3072, max_len=512, dropout=0.1,
                          dtype="bfloat16")
    mesh = make_mesh(MeshConfig(dp=1), devices=list(v5e[:1]))
    repl = NamedSharding(mesh, P())

    def sds(x, sharding=repl):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    with mesh_guard(mesh):
        axes = {}   # the logical axes are plain Python: kept on the way

        def init(key):
            params, named = bert.init(key, cfg)
            axes.update(named)
            return params

        shapes = jax.eval_shape(init, jax.random.key(0))
        tx = optax.adamw(1e-4)
        _, step = T.make_train_step(
            lambda p, b, r: bert.pretrain_loss(p, cfg, b, rng=r,
                                               deterministic=False),
            tx, mesh, axes,
            strategy=T.TrainStrategy(shard_optimizer_states=True))
        sharded = T.param_shardings(mesh, axes, T.current_rules())
        params = {k: sds(v, sharded[k]) for k, v in shapes.items()}
        opt = jax.tree.map(sds, jax.eval_shape(      # every one trainable
            optax.masked(tx, lambda p: dict.fromkeys(p, True)).init, params))
        state = T.TrainState(params, opt, sds(jnp.zeros((), jnp.int32)))
        rows = NamedSharding(mesh, P("dp"))
        batch = {k: jax.ShapeDtypeStruct(s, jnp.int32, sharding=rows)
                 for k, s in {
                     "input_ids": (_BERT_BATCH, _BERT_SEQ),
                     "token_type_ids": (_BERT_BATCH, _BERT_SEQ),
                     "masked_positions": (_BERT_BATCH, _BERT_MASKED),
                     "masked_labels": (_BERT_BATCH, _BERT_MASKED),
                     "nsp_labels": (_BERT_BATCH,)}.items()}
        return cfg, step.lower(state, batch,
                               sds(jax.eval_shape(jax.random.key, 0)))


def _threefry_evaluations(text, shape):
    """Evaluations of threefry2x32 over `shape` in an optimized HLO text,
    through nested fusions: its `xor`s over `u32[shape]` in every
    computation, 21 an evaluation (20 rounds and the fold of the two
    halves); and the number of computations that hold any."""
    xor = re.compile(r"= u32\[%s\][^ ]* xor\(" % ",".join(map(str, shape)))
    held = collections.Counter()
    name = None
    for line in text.splitlines():
        if line and not line[0].isspace():
            m = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(", line)
            name = m.group(1) if m else None
        elif name and xor.search(line):
            held[name] += 1
    total = sum(held.values())
    assert total % 21 == 0, held
    return total // 21, len(held)


@pytest.fixture(scope="module")
def bert_step(v5e):
    """`bert_base.pretrain128`'s step compiled ONCE for the described chip
    (~70 s), with what the attention gate counted while it was traced."""
    A.GATE_COUNTS.clear()
    cfg, lowered = _described_train_step(v5e)
    gate = dict(A.GATE_COUNTS)
    return cfg, lowered.compile(), gate


def test_bert_base_train_step_evaluates_each_dropout_mask_once(bert_step):
    """24 masks a step (two a layer, `bool[256,128,768]`), each drawn ONCE:
    `common.dropout` pins its mask, so that XLA cannot run the generator
    again inside every fusion that reads it. Without the pin this program
    reads 96 evaluations in 80 computations (the forward's matmul, the
    backward's input and weight gradients, and the bias gradients' eight
    reductions with three masks each), 63 ms more of a 221 ms step on the
    chip (PERF.md, PR 50). And the 24 stored masks (0.6 GB) leave the step
    under the chip's memory."""
    cfg, compiled, _ = bert_step
    masks = 2 * cfg.layers
    evaluations, computations = _threefry_evaluations(
        compiled.as_text(), (_BERT_BATCH, _BERT_SEQ, cfg.hidden))
    assert evaluations == masks, (evaluations, computations)
    assert computations <= masks
    ma = compiled.memory_analysis()
    planned = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
               + ma.output_size_in_bytes - ma.alias_size_in_bytes
               + ma.generated_code_size_in_bytes)
    # parameters and both moments in float32 1.32 GB; temporaries 7.56 GB,
    # of them 0.6 GB the masks. Until PR 54 they were 8.8 GB (planned 9.5 to
    # 11.0): the XLA route kept a bf16 [256,12,128,128] of probabilities a
    # layer for the backward, 12 x 100.7 MB = 1.21 GB; the short kernel
    # keeps lse [256,12,128] float32, 12 x 1.6 MB. 1.32 + 7.56 + the
    # step's outputs less what they alias = 9.07 GB; the chip has 16
    assert 8.6e9 < planned < 9.6e9, ma


def test_bert_base_train_step_attends_through_the_short_kernel(bert_step):
    """Attention at T = 128 is one fused kernel forward and one backward a
    layer (`attention._short_mha`, PERF.md section 6, PR 54): 12 + 12
    custom calls, chosen by the gate from the shape alone, and nothing of
    the XLA route's left in the step: no `[256,12,128,128]` score buffer
    and no heads-major `[256,12,128,64]` copy of q, k, v, the context or a
    gradient, of any dtype."""
    cfg, compiled, gate = bert_step
    assert gate == {"short": cfg.layers}, gate
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln
             and " custom-call(" in ln]
    assert sum("short_mha_fwd" in ln for ln in calls) == cfg.layers
    assert sum("short_mha_bwd" in ln for ln in calls) == cfg.layers
    assert len(calls) == 2 * cfg.layers, len(calls)
    # ... and the benchmark's scope reduction books every one of them to
    # `attention`, the backward's through `transpose(jvp(layers))`
    from benchmarks.harness import program_trace
    names = [re.search(r'op_name="([^"]*)"', ln).group(1) for ln in calls]
    assert {program_trace.scope_of(n) for n in names} == {"attention"}, names
    assert sum("transpose(jvp(" in n for n in names) == cfg.layers, names
    heads, hd = cfg.heads, cfg.head_dim
    for shape in ((_BERT_BATCH, heads, _BERT_SEQ, _BERT_SEQ),
                  (_BERT_BATCH, heads, _BERT_SEQ, hd)):
        assert "[%s]" % ",".join(map(str, shape)) not in text, shape


_TRINITY_SLOTS, _TRINITY_CONTEXT = 32, 40960


@pytest.fixture(scope="module")
def trinity_8l(v5e):
    """Trinity-Mini as the cell serves it, with the WINDOW kind's pools (6
    layers, a ring of 193 blocks a slot and the null block) behind the
    programs' `state`, as `DecodeEngine` sizes them."""
    from paddle_tpu.models import afmoe
    from paddle_tpu.serving import kv_cache as kvc

    cfg, params, pools, state, kv, sds = _described(
        v5e, afmoe, afmoe.AfmoeConfig(
            pattern="WWW*WWW*", held=(0, 64), vocab_size=100096,
            max_len=_TRINITY_CONTEXT),
        _TRINITY_SLOTS, _TRINITY_CONTEXT)
    sm = cfg.serve_model()
    ring = kvc.ring_blocks(sm.window, sm.prompt_slice, _BLOCK)
    wkv = kvc.KVCacheConfig(
        layers=sm.window_layers, widths=sm.stored, max_len=_TRINITY_CONTEXT,
        block_size=_BLOCK, num_blocks=_TRINITY_SLOTS * ring + 1)
    state += tuple(sds(shape, jnp.dtype(wkv.dtype))
                   for shape in wkv.pool_shapes)
    return cfg, params, pools, state, kv, wkv, ring, sds


@pytest.mark.parametrize("program", ["decode@32", "prefill@32768"])
def test_trinity_serve_program_walks_both_cache_kinds(trinity_8l, program,
                                                      monkeypatch):
    """Two periods of Trinity-Mini as the cell serves it (6 sliding-window
    and 2 full layers, 2 dense MLPs then 6 layers of 64 held of 128
    experts, half the vocabulary): 6.32 GB of weights, a GLOBAL pool of 2
    layers and a WINDOW pool of 6, all donated and written where they lie.
    The decode program takes the paged grouped-query kernel in all 8
    attention layers; the six window layers' walks run under tables of 129
    blocks (`window_blocks`) whatever the sequence's table is wide (2560),
    from each slot's window's first block. The prefill walks its 32768
    tokens in slices of 1024."""
    from paddle_tpu.models import decoder
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    from paddle_tpu.ops.pallas import paged_attention as PA
    from paddle_tpu.serving import kv_cache as kvc

    monkeypatch.setattr(A, "_platform", lambda q: "tpu")
    cfg, params, pools, state, kv, wkv, ring, sds = trinity_8l
    sm = cfg.serve_model()
    kw = dict(block_size=_BLOCK, eos_id=-1)
    kind, n = program.split("@")
    n = int(n)
    mb = _TRINITY_CONTEXT // _BLOCK
    for counts in (gm.GATE_COUNTS, gm.TILES, PA.GATE_COUNTS,
                   kvc.PREFILL_WRITE_UNITS):
        counts.clear()
    if kind == "decode":
        fn, args = decoder.decode_step, (
            sds((n,), np.int32), sds((n,), np.int32), *pools,
            sds((n, mb), np.int32), state, sds((n,), np.int32),
            sds((n, mb), np.int32))
    else:
        fn, args = decoder.prefill, (
            sds((1, n), np.int32), sds((), np.int32), *pools,
            sds((mb,), np.int32), state, sds((), np.int32),
            sds((mb,), np.int32))
    compiled = jax.jit(lambda p, *a: fn(sm, p, *a, **kw),
                       donate_argnums=(3, 4, 6)).lower(params,
                                                       *args).compile()
    assert ring == 193
    assert kv.pool_shapes == ((2, 81921, 16, 512),) * 2
    assert wkv.pool_shapes == ((6, 6177, 16, 512),) * 2
    assert [s.shape for s in state] == [(6, 6177, 16, 512)] * 2
    weights = sum(int(np.prod(p.shape)) * 2 for p in params.values())
    assert weights == 2 * 3_158_905_600, weights
    ma = compiled.memory_analysis()
    planned = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
               + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    print(program, "planned", planned, ma)
    # weights 6.32 GB + global 5.37 GB + window 1.21 GB resident
    assert 12.8e9 < planned < 14.5e9, ma
    assert ma.alias_size_in_bytes >= kv.pool_bytes() + wkv.pool_bytes(), ma
    text = compiled.as_text()
    for pool in pools + state:
        assert not _pool_movers(text, pool.shape)
    # no op makes a layer's slice of an expert stack
    slices = re.findall(r"= \(?bf16\[64,(?:2048,1024|1024,2048)\]", text)
    assert not slices, slices[:3]
    kernels = _kernels(text)
    if kind == "decode":
        # three grouped matmuls an expert layer, the megablox kernel
        assert gm.GATE_COUNTS == {"megablox": 18}, gm.GATE_COUNTS
        assert PA.GATE_COUNTS == {"paged_gqa": 1, "paged_gqa_window": 1}, \
            PA.GATE_COUNTS
        walks = [k for k in kernels if "paged_gqa_attention" in k]
        assert sum("/window_attention/" in k for k in walks) == 6, kernels
        assert sum("/attention/" in k for k in walks) == 2, kernels
        # a window layer's kernel prefetches tables of 129 blocks, a full
        # layer's the sequence's 2560
        assert PA.window_blocks(sm.window, _BLOCK) == 129
        assert len(re.findall(r"s32\[32,129\]", text)) >= 6
        assert ma.temp_size_in_bytes < 0.2e9, ma
    else:
        assert ma.temp_size_in_bytes < 1.5e9, ma


@pytest.mark.parametrize("family", ["nemotron", "granite"])
def test_a_walk_without_a_window_lowers_as_before(family, monkeypatch):
    """The start is a table the windowed call alone prefetches: the
    grouped-query walk of a model of ONE cache kind takes the scalars it
    took (layer, tables, positions and, narrow, the runs) and no mask of a
    first key."""
    from paddle_tpu.ops.pallas import paged_attention as PA

    kv_heads = {"nemotron": 2, "granite": 8}[family]
    pool = jax.ShapeDtypeStruct((1, 65, 16, kv_heads * 128), jnp.bfloat16)
    q = jax.ShapeDtypeStruct((8, 32 * 128), jnp.bfloat16)
    tables = jax.ShapeDtypeStruct((8, 64), jnp.int32)
    pos = jax.ShapeDtypeStruct((8,), jnp.int32)

    def lowered(**kw):
        return jax.jit(lambda q, k, v, t, p: PA.paged_gqa_attention(
            q, k, v, jnp.int32(0), t, p, heads=32, kv_heads=kv_heads,
            interpret=True, **kw)).lower(q, pool, pool, tables, pos).as_text()

    plain = lowered()
    assert "first" not in plain
    n_scalars = 4 if PA.narrow(PA._token_bytes(pool, pool)) else 3
    kernel, scalars, _ = PA._call_form(
        lambda *a, **k: None, jnp.int32(0), jnp.zeros((8, 64), jnp.int32),
        jnp.zeros((8,), jnp.int32), jnp.zeros(pool.shape, pool.dtype),
        jnp.zeros(pool.shape, pool.dtype))
    assert len(scalars) == n_scalars
    assert lowered(window=64) != plain

"""The paged decode-attention kernel (ops/pallas/paged_attention.py) against
the mathematics of the gather path it replaces in `decoder.decode_step`, on
noise-filled pools, through the Pallas TPU interpreter on the CPU: the
kernel's constructs (scalar prefetch, DMAs out of an HBM pool into VMEM
buffers, DMA semaphores, scratch that outlives a grid step) all run there.
What the interpreter cannot see (tiling, VMEM, what the compiler plans for
the pools) is `tests/test_tpu_aot_compile.py`'s, and the chip itself is
`chip_smoke.py`'s `paged_attention` phase.
"""

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.models import decoder, gpt
from paddle_tpu.ops.pallas import attention as A
from paddle_tpu.ops.pallas import paged_attention as PA
from paddle_tpu.serving import kv_cache as kvc

L, S, MB, BS = 3, 5, 20, 16          # a full table: 320 tokens, two chunks
NB = 1 + S * MB
WIDTHS = [(20, 64), (16, 128)]       # GPT-2-large's heads, OLMoE's
# tokens each slot attends (0: an inactive slot, its table all null)
PATTERNS = {
    "inactive": [0, 0, 0, 0, 0],
    "one-token": [1, 1, 1, 1, 1],
    "block-edge": [16, 17, 15, 32, 33],
    "chunk-edge": [256, 257, 255, 16, 1],
    "full-table": [MB * BS] * S,
    "mixed": [0, 1, 300, 17, MB * BS],
}
# the latent walk under a table of `_LONG_TABLE` tokens: a chunk is
# `_LONG_CHUNK` = 1024 tokens, its step two sub-tiles of 512. The same
# pattern names; rows that end in a chunk's first sub-tile, on a
# sub-tile's edge, in the last sub-tile, on the chunk's edge
LONG_MB = PA._LONG_TABLE // BS
LONG_NB = 1 + LONG_MB + 64
LONG_PATTERNS = {
    "inactive": [0, 0, 0, 0, 0],
    "one-token": [1, 1, 1, 1, 1],
    "block-edge": [16, 17, 511, 512, 513],
    "chunk-edge": [1024, 1025, 1023, 2048, 2049],
    "full-table": [LONG_MB * BS, 0, 0, 0, 0],
    "mixed": [0, 1537, 1024 + 300, 4096 + 1024, 3 * 512],
}


def gather_math(q, k_pool, v_pool, layer, tables, positions, heads):
    """decode_step's gather path, in float32 throughout."""
    s, hd = q.shape
    d = hd // heads
    keys = kvc.gather_kv(k_pool, layer, tables).astype(jnp.float32)
    vals = kvc.gather_kv(v_pool, layer, tables).astype(jnp.float32)
    m = keys.shape[1]
    scores = jnp.einsum("snd,smnd->snm",
                        q.astype(jnp.float32).reshape(s, heads, d),
                        keys.reshape(s, m, heads, d),
                        precision="highest") / np.sqrt(d)
    mask = jnp.arange(m)[None, :] <= positions[:, None]
    att = jax.nn.softmax(jnp.where(mask[:, None, :], scores, -1e9), axis=-1)
    return jnp.einsum("snm,smnd->snd", att, vals.reshape(s, m, heads, d),
                      precision="highest").reshape(s, hd)


def latent_math(ql, qr, c_pool, r_pool, layer, tables, positions, scale):
    """The absorbed latent attention over gathered rows, in float32."""
    f32 = jnp.float32
    live = int(np.asarray(positions).max()) // BS + 1   # the rest is masked
    keys = kvc.gather_kv(c_pool, layer, tables[:, :live]).astype(f32)
    rot = kvc.gather_kv(r_pool, layer, tables[:, :live]).astype(f32)
    sc = (jnp.einsum("snc,smc->snm", ql.astype(f32), keys)
          + jnp.einsum("snr,smr->snm", qr.astype(f32), rot))
    seen = jnp.arange(keys.shape[1])[None, :] <= positions[:, None]
    sc = jnp.where(seen[:, None, :], sc * scale, -jnp.inf)
    return jnp.einsum("snm,smc->snc", jax.nn.softmax(sc, -1),
                      keys).reshape(ql.shape[0], -1)


class Walk(NamedTuple):
    """A kernel over noise-filled pools: `run` / `ref` `(queries, k_pool,
    v_pool, layer, tables, positions) -> [S, lanes]`, its table's width
    and pool's blocks, and the lengths its cases walk."""

    queries: tuple              # one array or two, a row a slot
    k_pool: jax.Array
    v_pool: jax.Array
    run: Callable
    ref: Callable
    blocks: int                 # a table's width
    pool_blocks: int
    patterns: dict
    garbage: list               # `test_garbage_past_the_length_does_not_show`
    # `test_a_slots_result_does_not_depend_on_its_neighbours`: the tokens
    # of the row that keeps its table and length; (every slot's length,
    # where the row sits among them) a call
    row: int
    neighbours: list
    tol: float

    def args(self, layer, tables, positions):
        return (self.queries, self.k_pool, self.v_pool, jnp.int32(layer),
                tables, positions)

    def tables_for(self, lens, rng):
        """Block tables over scattered blocks, and positions, for slots
        that attend `lens` tokens."""
        tables = np.zeros((S, self.blocks), np.int32)
        positions = np.zeros((S,), np.int32)
        free = list(rng.permutation(np.arange(1, self.pool_blocks)))
        for s, n in enumerate(lens):
            for b in range(-(-n // BS)):
                tables[s, b] = free.pop()
            positions[s] = max(n - 1, 0)
        return jnp.asarray(tables), jnp.asarray(positions)


def _noise(rng, *shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)


def _multi_head(heads, d):
    rng = np.random.default_rng(heads)
    k_pool, v_pool = (_noise(rng, L, NB, BS, heads * d) for _ in range(2))
    q = _noise(rng, S, heads * d)
    run = jax.jit(lambda q, kp, vp, l, t, p: PA.paged_attention(
        q[0], kp, vp, l, t, p, heads=heads,
        interpret=pltpu.InterpretParams()).astype(jnp.float32))
    ref = lambda q, kp, vp, l, t, p: gather_math(       # noqa: E731
        q[0], kp, vp, l, t, p, heads)
    # bf16 weights and a bf16 result over unit-normal values: 2^-8 of 3.5
    return Walk((q,), k_pool, v_pool, run, ref, MB, NB, PATTERNS,
                [17, 1, 250, 33, 0], 273,
                [([0, 0, 273, 0, 0], 2), ([320, 1, 273, 17, 256], 2),
                 ([16, 300, 273, 0, 5], 2), ([100, 0, 0, 31, 273], 4)], 0.03)


def _latent_long():
    heads, latent, rope, scale = 16, 128, 128, 0.1
    rng = np.random.default_rng(46)
    c_pool = _noise(rng, L, LONG_NB, BS, latent)
    r_pool = _noise(rng, L, LONG_NB, BS, rope)
    ql, qr = _noise(rng, S, heads, latent), _noise(rng, S, heads, rope)
    assert PA.chunk_tokens(2 * (latent + rope), LONG_MB * BS) \
        == PA._LONG_CHUNK == 2 * PA._SUB
    run = jax.jit(lambda q, cp, rp, l, t, p: PA.paged_latent_attention(
        *q, cp, rp, l, t, p, scale=scale,
        interpret=pltpu.InterpretParams()).astype(jnp.float32).reshape(
            S, heads * latent))
    ref = lambda q, cp, rp, l, t, p: latent_math(       # noqa: E731
        *q, cp, rp, l, t, p, scale)
    row = 2048 + 513        # ends in its third chunk's second sub-tile
    return Walk((ql, qr), c_pool, r_pool, run, ref, LONG_MB, LONG_NB,
                LONG_PATTERNS, [17, 513, 2050, 1537, 0], row,
                [([0, 0, row, 0, 0], 2), ([4096, 1, row, 17, 2048], 2),
                 ([16, 3000, row, 0, 511], 2), ([1025, 0, 0, 31, row], 4)],
                0.02)


@pytest.fixture(scope="module", params=WIDTHS + ["latent-long"],
                ids=lambda w: w if isinstance(w, str) else f"{w[0]}x{w[1]}")
def width(request):
    """Noise in every slot of every block, the null block included, and
    the kernel jitted once a width."""
    if request.param == "latent-long":
        return _latent_long()
    return _multi_head(*request.param)


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("pattern", list(PATTERNS))
def test_kernel_matches_the_gather_paths_mathematics(width, pattern, layer):
    lens = width.patterns[pattern]
    tables, positions = width.tables_for(lens, np.random.default_rng(layer))
    args = width.args(layer, tables, positions)
    out = np.asarray(width.run(*args))
    live = np.asarray(lens) > 0
    if live.any():
        ref = np.asarray(width.ref(*args))
        assert np.abs(out[live] - ref[live]).max() < width.tol
    assert not out[~live].any()      # an inactive slot reads nothing


def test_garbage_past_the_length_does_not_show(width):
    """Slots past a sequence's length in its last block, every block it
    does not own and the null block hold huge values; the result is
    bit-identical to the one over a pool that holds zeros there."""
    lens = width.garbage
    tables, positions = width.tables_for(lens, np.random.default_rng(5))
    own = np.zeros((width.pool_blocks, BS), bool)
    for s, n in enumerate(lens):
        for t in range(n):
            own[int(tables[s, t // BS]), t % BS] = True
    keep = jnp.asarray(own)[None, :, :, None]
    outs = []
    for junk in (0.0, 3e4):
        kp, vp = (jnp.where(keep, p, jnp.asarray(junk, p.dtype))
                  for p in (width.k_pool, width.v_pool))
        outs.append(np.asarray(width.run(width.queries, kp, vp, jnp.int32(1),
                                         tables, positions)))
    assert np.isfinite(outs[1]).all()
    np.testing.assert_array_equal(outs[0], outs[1])


def test_a_slots_result_does_not_depend_on_its_neighbours(width):
    """Row independence (`ServeModel`'s contract): slot 2 keeps its table
    and length while every other slot's change, and where it sits among
    them; its context is bit-identical."""
    rng = np.random.default_rng(9)
    base_t, base_p = width.tables_for(width.neighbours[0][0], rng)
    row = None
    for lens, at in width.neighbours:
        others = [n if s != at else 0 for s, n in enumerate(lens)]
        tables, positions = width.tables_for(others,
                                             np.random.default_rng(at))
        tables = tables.at[at].set(base_t[2])
        positions = positions.at[at].set(base_p[2])
        queries = tuple(q.at[at].set(q[2]) for q in width.queries)
        out = np.asarray(width.run(queries, width.k_pool, width.v_pool,
                                   jnp.int32(2), tables, positions))[at]
        if row is None:
            row = out
            assert np.abs(row).max() > 0
        np.testing.assert_array_equal(out, row)


# -- runs of consecutive blocks: one copy a pool -----------------------------
#
# A narrow cache's walk fetches what the table names in a row from a
# chunk's first entry with one copy a pool (the binary pieces of the
# count), block by block only where the ids do not follow each other; a
# wide cache's stays a block a copy. What a slot's blocks HOLD decides its
# result, never where they lie: the same
# contents under a table of runs and under a descending table (no two ids
# in a row: the per-block walk) give the same bits.

R_S, R_MB, R_L = 4, 36, 2       # 36 blocks: 2 1/4 chunks of 256 tokens, or
R_NB = 1 + R_S * R_MB           # 1 1/8 of 512 (a narrow cache's:
#                                 `chunk_tokens`); slot s's region starts at
#                                 block 1 + s*MB


def _asc(n):
    return list(range(n))


def _desc(n):
    return list(range(R_MB - 1, R_MB - 1 - n, -1))


def _pairs(n):
    """1, 0, 3, 2, ...: scattered, no id followed by the next one."""
    return [j ^ 1 if j ^ 1 < n else j for j in range(n)]


def _tail(n):
    """A run that ends with the region's last block."""
    return list(range(R_MB - n, R_MB))


# a slot: (tokens it attends, where in its region block j lies); and what
# `kv_cache.run_chunks` counts over the pattern's tables: every chunk a run
# ("all"), none ("none"), or some
RUN_PATTERNS = {
    "all-runs": ([(570, _asc), (256, _asc), (100, _asc), (17, _asc)],
                 "all"),
    "descending": ([(570, _desc), (256, _desc), (100, _desc), (17, _desc)],
                   "none"),
    # chunk 0 a run; chunk 1 eight scattered blocks, then a run of eight;
    # chunk 2 a run of four
    "mixed": ([(576, lambda n: _asc(16) + [16 + j for j in _pairs(8)]
                + list(range(24, 36))),
               (300, _pairs), (40, _asc), (0, _asc)], "some"),
    # a run of 21 blocks ends inside chunk 1, the rest descends
    "run-ends-mid-chunk": ([(480, lambda n: _asc(21)
                             + list(range(35, 26, -1))),
                            (200, lambda n: _asc(5) + _desc(n - 5)),
                            (0, _asc), (16, _asc)], "some"),
    "under-a-chunk": ([(5, _asc), (40, _asc), (100, _asc), (250, _asc)],
                      "all"),
    # slot 3's run ends with block NB - 1, inside its second chunk
    "last-block": ([(256, _asc), (0, _asc), (33, _tail), (317, _tail)],
                   "all"),
    "inactive-between": ([(300, _asc), (0, _asc), (290, _asc), (0, _asc)],
                         "all"),
}


# The latent walk under a table of `_LONG_TABLE` tokens (`LONG_MB` entries;
# a chunk is `_LONG_CHUNK` tokens, 64 blocks, its step two sub-tiles of
# 512): a slot's region is `LONG_R_MB` = 300 blocks, 4 2/3 chunks
LONG_R_MB = 300


def _long_desc(n):
    return list(range(LONG_R_MB - 1, LONG_R_MB - 1 - n, -1))


LONG_RUN_PATTERNS = {
    # rows that end in their last chunk's first sub-tile, on a sub-tile's
    # edge, in the last sub-tile and on the chunk's edge
    "long-all-runs": ([(4096 + 300, _asc), (2048 + 1024, _asc),
                       (2047, _asc), (1536, _asc)], "all"),
    "long-descending": ([(4096 + 300, _long_desc), (2048 + 1024, _long_desc),
                         (2047, _long_desc), (1536, _long_desc)], "none"),
    # a prompt's run and its growth's: the break lies inside chunk 2 (at
    # block 150; the growth 20 blocks from block 200 on), the second row's
    # inside chunk 0, on no sub-tile's edge; the third's first two chunks
    # are runs and its third scattered
    "long-run-breaks-mid-chunk": (
        [(170 * 16 - 5, lambda n: _asc(150) + list(range(200, 220))),
         (1900, lambda n: _asc(37) + list(range(100, 100 + n - 37))),
         (2048 + 200, lambda n: _asc(128) + [128 + j for j in _pairs(13)]),
         (0, _asc)], "some"),
    "long-under-a-sub-tile": ([(5, _asc), (511, _asc), (512, _asc),
                               (513, _asc)], "all"),
}


class _Geometry(NamedTuple):
    region: int         # blocks of the pool a slot's table draws from
    blocks: int         # a table's width
    patterns: dict
    desc: Callable

    @property
    def pool_blocks(self):
        return 1 + R_S * self.region


_SHORT = _Geometry(R_MB, R_MB, RUN_PATTERNS, _desc)
_LONG = _Geometry(LONG_R_MB, LONG_MB, LONG_RUN_PATTERNS, _long_desc)


def _tables(geo, pattern, per_block):
    """(tables, positions) of a pattern; `per_block`: the same slots and
    lengths with every table descending, which the walk takes a block at
    a time."""
    tables = np.zeros((R_S, geo.blocks), np.int32)
    positions = np.zeros((R_S,), np.int32)
    for s, (n, where) in enumerate(geo.patterns[pattern][0]):
        blocks = -(-n // BS)
        offs = geo.desc(blocks) if per_block else where(blocks)
        assert len(offs) == blocks and len(set(offs)) == blocks
        tables[s, :blocks] = 1 + s * geo.region + np.asarray(offs, np.int32)
        positions[s] = max(n - 1, 0)
    return tables, positions


def _laid_out(geo, contents, tables, seed):
    """The pools `[L, NB, BS, width]` that hold slot s's block j
    (`contents[i][:, s, j]`) where `tables` says, noise everywhere else."""
    rng = np.random.default_rng(seed)
    pools = []
    for c in contents:
        pool = rng.standard_normal(
            (R_L, geo.pool_blocks, BS, c.shape[-1])).astype(np.float32)
        for s in range(R_S):
            live = np.flatnonzero(tables[s])
            pool[:, tables[s, live]] = c[:, s, live]
        pools.append(jnp.asarray(pool, jnp.bfloat16))
    return pools


def _paged_case(rng, heads=20, d=64):
    """5 KB a token: a wide cache, a block a copy, chunks of 256 tokens;
    2 heads of 64: a narrow one, runs whole, chunks of 512."""
    q = jnp.asarray(rng.standard_normal((R_S, heads * d)), jnp.bfloat16)
    run = jax.jit(lambda kp, vp, t, p: PA.paged_attention(
        q, kp, vp, jnp.int32(1), t, p, heads=heads,
        interpret=pltpu.InterpretParams()))
    ref = lambda kp, vp, t, p: gather_math(     # noqa: E731
        q, kp, vp, 1, t, p, heads)
    return (heads * d,) * 2, run, ref, 0.03


def _gqa_case(rng):
    heads, kv_heads, d = 16, 2, 128         # 1 KB a token: chunks of 512
    q = jnp.asarray(rng.standard_normal((R_S, heads * d)), jnp.bfloat16)
    run = jax.jit(lambda kp, vp, t, p: PA.paged_gqa_attention(
        q, kp, vp, jnp.int32(1), t, p, heads=heads, kv_heads=kv_heads,
        interpret=pltpu.InterpretParams()))
    f32 = jnp.float32
    ref = lambda kp, vp, t, p: decoder.mha_cached(      # noqa: E731
        q.astype(f32)[:, None], kvc.gather_kv(kp, 1, t).astype(f32),
        kvc.gather_kv(vp, 1, t).astype(f32), p[:, None], heads,
        kv_heads)[:, 0]
    return (kv_heads * d,) * 2, run, ref, 0.02


def _latent_case(rng):
    heads, latent, rope, scale = 16, 128, 128, 0.1  # 512 B: chunks of 512
    ql = jnp.asarray(rng.standard_normal((R_S, heads, latent)), jnp.bfloat16)
    qr = jnp.asarray(rng.standard_normal((R_S, heads, rope)), jnp.bfloat16)
    run = jax.jit(lambda cp, rp, t, p: PA.paged_latent_attention(
        ql, qr, cp, rp, jnp.int32(1), t, p, scale=scale,
        interpret=pltpu.InterpretParams()).reshape(R_S, heads * latent))

    ref = lambda cp, rp, t, p: latent_math(     # noqa: E731
        ql, qr, cp, rp, 1, t, p, scale)
    return (latent, rope), run, ref, 0.02


_RUN_KERNELS = {"paged": _paged_case,
                "paged-narrow": functools.partial(_paged_case, heads=2),
                "gqa": _gqa_case, "latent": _latent_case,
                "latent-long": _latent_case}
_RUN_CASES = [(k, p) for k in _RUN_KERNELS
              for p in (LONG_RUN_PATTERNS if k == "latent-long"
                        else RUN_PATTERNS)]


@pytest.fixture(scope="module")
def run_kernel(request):
    """A kernel jitted once, its reference, and what every slot's blocks
    hold (both pools), wherever a table puts them."""
    rng = np.random.default_rng(11)
    geo = _LONG if request.param == "latent-long" else _SHORT
    widths, run, ref, tol = _RUN_KERNELS[request.param](rng)
    contents = [rng.standard_normal((R_L, R_S, geo.blocks, BS, w)).astype(
        np.float32) for w in widths]
    per_chunk = PA.blocks_per_chunk(BS, 2 * sum(widths), geo.blocks * BS)
    assert per_chunk == {"paged": 16, "latent-long": 64}.get(
        request.param, 32)
    return geo, contents, run, ref, tol, per_chunk


@pytest.mark.parametrize("run_kernel,pattern", _RUN_CASES,
                         indirect=["run_kernel"],
                         ids=[f"{k}-{p}" for k, p in _RUN_CASES])
def test_a_run_of_blocks_in_one_copy_gives_the_per_block_walks_bits(
        run_kernel, pattern):
    geo, contents, run, ref, tol, per_chunk = run_kernel
    outs = []
    for per_block in (False, True):
        tables, positions = _tables(geo, pattern, per_block)
        live = [t[t > 0] for t in tables]
        runs, chunks = np.sum([kvc.run_chunks(t, per_chunk) for t in live],
                              axis=0)
        if per_block:       # no two ids in a row: nothing to take together
            assert not any((np.diff(t) == 1).any() for t in live)
        else:
            assert {"all": runs == chunks, "none": runs == 0,
                    "some": 0 < runs < chunks}[geo.patterns[pattern][1]]
            assert tables.max() <= geo.pool_blocks - 1
        pools = _laid_out(geo, contents, tables, seed=int(per_block))
        t, p = jnp.asarray(tables), jnp.asarray(positions)
        outs.append(np.asarray(run(*pools, t, p), np.float32))
    np.testing.assert_array_equal(outs[0], outs[1])
    want = np.asarray(ref(*pools, t, p))
    active = np.asarray([n > 0 for n, _ in geo.patterns[pattern][0]])
    assert np.abs(outs[0] - want)[active].max() < tol
    assert not outs[0][~active].any()       # an inactive slot reads nothing


@pytest.mark.parametrize("heads,scalars,pool_rank", [(20, 3, 4), (2, 4, 3)],
                         ids=["wide", "narrow"])
def test_a_wide_caches_call_is_the_one_it_always_was(heads, scalars,
                                                     pool_rank):
    """The cache's shape decides in ONE place (`_call_form`): a wide
    cache's kernel prefetches layer, tables and positions, takes the pools
    as they lie, and nothing counts runs for it (on the chip its decode
    program then takes the device time it took before runs: PERF.md
    section 6, PR 40); a narrow cache's prefetches the tables' runs as a
    fourth array and takes the pools as their rows."""
    pool = jax.ShapeDtypeStruct((R_L, R_NB, BS, heads * 64), jnp.bfloat16)
    assert PA.narrow(PA._token_bytes(pool, pool)) == (scalars == 4)
    jaxpr = jax.make_jaxpr(lambda q, kp, vp, t, p: PA.paged_attention(
        q, kp, vp, jnp.int32(1), t, p, heads=heads))(
            jax.ShapeDtypeStruct((R_S, heads * 64), jnp.bfloat16), pool, pool,
            jax.ShapeDtypeStruct((R_S, R_MB), jnp.int32),
            jax.ShapeDtypeStruct((R_S,), jnp.int32)).jaxpr
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert call.params["grid_mapping"].num_index_operands == scalars
    assert [v.aval.ndim for v in call.invars[-2:]] == [pool_rank] * 2
    counting = {"eq", "reduce_sum"} & {e.primitive.name for e in jaxpr.eqns}
    assert len(counting) == (2 if scalars == 4 else 0)


def _latent_call(blocks):
    """The traced `pallas_call` of the latent walk (the latent cache's 512
    + 128 lanes) under tables `blocks` wide."""
    sds = jax.ShapeDtypeStruct
    bf16 = jnp.bfloat16
    jaxpr = jax.make_jaxpr(
        lambda ql, qr, cp, rp, t, p: PA.paged_latent_attention(
            ql, qr, cp, rp, jnp.int32(1), t, p, scale=0.1))(
                sds((R_S, 32, 512), bf16), sds((R_S, 32, 128), bf16),
                sds((R_L, 65, BS, 512), bf16), sds((R_L, 65, BS, 128), bf16),
                sds((R_S, blocks), jnp.int32), sds((R_S,), jnp.int32)).jaxpr
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    return call


# a table's width in blocks of 16 tokens: `rag_closed`'s 4608 tokens,
# `doc_sessions`' 9216, one block under `_LONG_TABLE`, `_LONG_TABLE`, and
# `ctx12k_sessions`' 20480
@pytest.mark.parametrize("blocks,long", [
    (288, False), (576, False), (LONG_MB - 1, False), (LONG_MB, True),
    (1280, True)])
def test_the_narrow_walks_chunk_follows_the_tables_width(blocks, long):
    """The chunk is ONE number (`chunk_tokens`), from the cache's token
    bytes and the table's width alone: under `_LONG_TABLE` tokens a narrow
    cache's walk traces to the scratch it always traced to, `[2, 512,
    lanes]` a pool and a run count a chunk of 32 blocks; from there on to
    `_LONG_CHUNK` tokens a buffer, and its table counts two runs a chunk
    (`Tables.runs`). The allocator's count goes by the same number."""
    chunk = PA._LONG_CHUNK if long else 512
    assert PA.chunk_tokens(1280, blocks * BS) == chunk
    assert PA.chunk_tokens(2 * 2 * 1280, blocks * BS) == 256    # a wide one
    call = _latent_call(blocks)
    grid = call.params["grid_mapping"]
    scratch = call.params["jaxpr"].invars[-grid.num_scratch_operands:]
    assert [v.aval.shape for v in scratch[:2]] == [(2, chunk, 512),
                                                   (2, chunk, 128)]
    runs = call.invars[grid.num_index_operands - 1].aval.shape
    chunks = -(-blocks * BS // chunk)
    assert runs == (R_S, 2 * chunks if long else chunks)
    al = kvc.BlockAllocator(kvc.KVCacheConfig(
        layers=1, widths=(512, 128), max_len=blocks * BS, block_size=BS,
        num_blocks=9))
    assert al.per_chunk == chunk // BS == PA.blocks_per_chunk(
        BS, 1280, blocks * BS)
    assert al.stats()["walk_chunk_tokens"] == chunk


# -- the gate -----------------------------------------------------------------


def _pool(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("pool,heads,takes", [
    (_pool((36, 1025, 16, 1280)), 20, True),          # GPT-2-large
    (_pool((8, 1025, 16, 2048)), 16, True),           # OLMoE
    (_pool((2, 9, 16, 128), jnp.float32), 2, True),
    (_pool((2, 9, 16, 2, 64)), 2, False),             # a 5-D pool
    (_pool((2, 9, 16, 64)), 2, False),                # H*D under a lane tile
    (_pool((2, 9, 16, 128)), 4, False),               # 32-wide heads
    (_pool((2, 9, 8, 128)), 2, False),                # half a bf16 tile a block
], ids=["gpt2-large", "olmoe", "f32", "5d", "narrow", "head32", "bs8"])
def test_gate_reads_the_route_from_the_platform_and_the_shapes(
        monkeypatch, pool, heads, takes):
    q = _pool((4, int(np.prod(pool.shape[3:]))), pool.dtype)
    assert not PA.use_paged(q, pool, heads)     # here: the CPU
    monkeypatch.setattr(A, "_platform", lambda q: "tpu")
    assert PA.use_paged(q, pool, heads) == takes


def test_gate_stays_shut_under_a_mesh_that_would_partition_the_kernel(
        monkeypatch):
    from paddle_tpu.parallel import MeshConfig, make_mesh, mesh_guard

    monkeypatch.setattr(A, "_platform", lambda q: "tpu")
    pool = _pool((2, 9, 16, 128))
    with mesh_guard(make_mesh(MeshConfig(dp=-1), devices=jax.devices()[:2])):
        assert not PA.use_paged(_pool((4, 128)), pool, 2)


# -- in the decode program ----------------------------------------------------


@pytest.fixture(scope="module")
def small_gpt():
    """Two heads of 64: the narrowest model the kernel takes."""
    cfg = gpt.GPTConfig(vocab_size=97, hidden=128, layers=2, heads=2,
                        mlp_dim=256, max_len=64, dtype="float32")
    params, _ = gpt.init(jax.random.key(3), cfg)
    rng = np.random.default_rng(3)
    pools = tuple(jnp.asarray(rng.standard_normal((2, 17, 16, 128)),
                              jnp.float32) for _ in range(2))
    tables = np.zeros((4, 4), np.int32)
    tables[0, :3], tables[1, :1], tables[3, :2] = (1, 2, 3), (4,), (5, 6)
    return (cfg, params, jnp.asarray([5, 6, 0, 7], jnp.int32),
            jnp.asarray([40, 0, 0, 16], jnp.int32), *pools,
            jnp.asarray(tables))


def _decode(small_gpt):
    cfg, params, *args = small_gpt
    return jax.jit(lambda p, *a: decoder.decode_step(
        cfg.serve_model(), p, *a, block_size=16, eos_id=-1))(params, *args)


def test_decode_step_takes_the_gather_path_off_the_tpu(small_gpt):
    PA.GATE_COUNTS.clear()
    _decode(small_gpt)
    assert PA.GATE_COUNTS == {"gather": 1}


def test_decode_step_through_the_kernel_agrees_with_the_gather_path(
        small_gpt, monkeypatch):
    """The whole decode program with the gate answered for (as the AOT
    compile tests do) and the kernel interpreted: the live slots' tokens
    and both pools equal the gather path's."""
    import functools

    toks, kp, vp, _ = _decode(small_gpt)
    monkeypatch.setattr(A, "_platform", lambda q: "tpu")
    monkeypatch.setattr(PA, "paged_attention", functools.partial(
        PA.paged_attention, interpret=pltpu.InterpretParams()))
    PA.GATE_COUNTS.clear()
    toks2, kp2, vp2, _ = _decode(small_gpt)
    assert PA.GATE_COUNTS == {"paged": 1}
    live = [0, 1, 3]
    np.testing.assert_array_equal(np.asarray(toks)[live],
                                  np.asarray(toks2)[live])
    # layer 0's writes are the same; layer 1's see attention's rounding
    np.testing.assert_array_equal(np.asarray(kp[0]), np.asarray(kp2[0]))
    own = np.asarray(small_gpt[-1]).ravel()
    own = own[own > 0]
    np.testing.assert_allclose(np.asarray(kp)[:, own], np.asarray(kp2)[:, own],
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(vp)[:, own], np.asarray(vp2)[:, own],
                               atol=1e-4)


def test_engine_status_reports_the_walks_chunk():
    """`status()["kv"]["walk_chunk_tokens"]`: what the walk over this
    engine's cache takes a chunk, the unit `run_chunk_share` counts by
    (two heads of 8: a narrow cache, under a table of 32 tokens)."""
    from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine

    cfg = gpt.GPTConfig.tiny()
    params, _ = gpt.init(jax.random.key(0), cfg)
    engine = DecodeEngine(params, cfg, DecodeConfig(
        block_size=8, num_blocks=17, decode_slots=(2,), prefill_buckets=(8,),
        max_len=32))
    try:
        kv = engine.status()["kv"]
        assert kv["walk_chunk_tokens"] == PA.chunk_tokens(
            engine.kv_cfg.walk_bytes_per_token(), 32) == 512
        assert kv["walk_chunk_tokens"] == 8 * engine._alloc.per_chunk
        assert kv["run_chunk_share"] is None
    finally:
        engine.stop()


def test_engine_status_reports_the_route():
    from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine

    cfg = gpt.GPTConfig.tiny()
    params, _ = gpt.init(jax.random.key(0), cfg)
    PA.GATE_COUNTS.clear()
    engine = DecodeEngine(params, cfg, DecodeConfig(
        block_size=8, num_blocks=17, decode_slots=(2,), prefill_buckets=(8,),
        max_len=32))
    try:
        assert engine.submit([1, 2, 3], max_new_tokens=2).result(
            timeout_s=120)
        assert engine.status()["decode_attention"] == {"gather": 1}
    finally:
        engine.stop()


# -- a windowed walk (a model with a window kind of cache) -------------------


@pytest.mark.parametrize("kv_heads", [2, 4], ids=["2kv", "4kv-at-the-limit"])
def test_a_windowed_walk_reads_the_window_alone_through_a_repeated_ring(
        kv_heads):
    """`paged_gqa_attention(window=...)` against the gathered form under
    the same mask, at positions under, at and far past the window, through
    tables that are a ring REPEATED (`kv_cache.window_table`; one ring not
    in order): the walk starts at the window's first block, its tables are
    `window_blocks` wide whatever the sequence's are, and the first block's
    keys before the window are masked. 4 K/V heads of 128 in bf16 are 2048 B
    a token, AT the narrow limit, and walk runs as 2 do."""
    heads, d, bs, window = 16, 128, 16, 64
    ring = kvc.ring_blocks(window, 32, bs)      # 7 blocks, 112 tokens
    slots, mb, layers = 5, 40, 2
    rng = np.random.default_rng(kv_heads)
    shape = (layers, slots * ring + 1, bs, kv_heads * d)
    kp, vp = (jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
              for _ in range(2))
    q = jnp.asarray(rng.standard_normal((slots, heads * d)), jnp.bfloat16)
    pos = np.array([10, 63, 64, 500, 0], np.int32)
    tables = np.zeros((slots, mb), np.int32)
    for s in range(slots - 1):      # the last slot is idle
        blocks = list(range(1 + s * ring, 1 + (s + 1) * ring))
        blocks = blocks[:min(pos[s] // bs + 1, ring)]
        if s == 3:
            blocks = blocks[3:] + blocks[:3]
        tables[s] = kvc.window_table(blocks, ring, mb)
    assert PA.narrow(PA._token_bytes(kp, vp))
    out = PA.paged_gqa_attention(
        q, kp, vp, jnp.int32(1), jnp.asarray(tables), jnp.asarray(pos),
        heads=heads, kv_heads=kv_heads, window=window,
        interpret=pltpu.InterpretParams())
    f32 = jnp.float32
    want = decoder.mha_cached(
        q.astype(f32)[:, None], kvc.gather_kv(kp, 1, tables).astype(f32),
        kvc.gather_kv(vp, 1, tables).astype(f32), jnp.asarray(pos)[:, None],
        heads, kv_heads, window=window)[:, 0]
    out, want = np.asarray(out, np.float32), np.asarray(want)
    assert np.abs(out - want)[:4].max() < 0.02
    assert not out[4].any()         # an idle slot reads nothing
    # the same keys WITHOUT the window differ: the mask is doing something
    full = decoder.mha_cached(
        q.astype(f32)[:, None], kvc.gather_kv(kp, 1, tables).astype(f32),
        kvc.gather_kv(vp, 1, tables).astype(f32), jnp.asarray(pos)[:, None],
        heads, kv_heads)[:, 0]
    assert np.abs(np.asarray(full) - want)[2:4].max() > 0.1
    t = PA.window_tables(jnp.asarray(tables), jnp.asarray(pos), window, kp,
                         vp)
    assert t.ids.shape == (slots, PA.window_blocks(window, bs)) == (5, 5)
    assert t.first.tolist() == [0, 0, 1, 5, 0]
    assert t.newest.tolist() == [10, 63, 64, 68, 0]

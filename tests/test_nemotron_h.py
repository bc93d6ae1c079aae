"""Nemotron-H (models/nemotron_h.py) at a tiny size on the CPU. What every
served family must do is `tests/serve_contract.py`'s, bound here against the
benchmark's plain float32 reference (benchmarks/reference/nemotron_h_ref.py:
the recurrence token by token, every expert for every token, no cache); what
is this model's own follows it: the chunked scan against the recurrence,
what a padded bucket, a reused state row and a cancelled request may NOT
change, and the two kernels this model brings (grouped-query paged attention,
the in-place state update) through the Pallas TPU interpreter. What the
interpreter cannot see is tests/test_tpu_aot_compile.py's; the chip is
chip_smoke.py's `serve_nemotron` phase."""

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from benchmarks.reference import nemotron_h_ref as ref_mod
from paddle_tpu.models import decoder, moe, nemotron_h as nh
from paddle_tpu.ops import ssm
from paddle_tpu.ops.pallas import grouped_matmul as gm
from paddle_tpu.ops.pallas import paged_attention as PA
from paddle_tpu.ops.pallas import ssm_update as SU
from paddle_tpu.serving import kv_cache as kvc
from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine
from serve_contract import (BS, ROW, Family, ServeContract, pools, program,
                            seeded, served_alone, table)


@functools.cache
def _tiny():
    cfg = nh.NemotronHConfig.tiny()
    cfg.dtype = "float32"
    return cfg, seeded(nh, cfg, 3)


def _normal(key, shape):
    """float32 whatever conftest's x64 mode makes the default."""
    return jax.random.normal(key, shape, jnp.float32)


def _ids(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(0, cfg.vocab_size, n)


FAMILY = Family(
    module=nh, tiny=_tiny, ref=ref_mod,
    logits=lambda params, model, ids: ref_mod.logits_rows(
        params, model, jnp.asarray(ids), 0, len(ids),
        prompt_len=model.get("prompt_len")),
    tol=2e-4, tol_why="float32 on both sides; every rule of the layers, "
                      "left out of the REFERENCE, moves the logits by ten "
                      "times that or more",
    far=10.0,
    faults=(("conv-bias-dropped", {"conv_bias": False}),
            ("D-dropped", {"skip_D": True}),
            ("one-norm-group", {"norm_groups": 1}),
            ("dt-bias-left-out", {"dt_bias": False}),
            ("relu-for-relu2", {"act": "relu"}),
            ("silu-for-relu2", {"act": "silu"}),
            ("shared-expert-dropped", {"shared_expert": False}),
            ("scale-left-out", {"route_scale": 1.0}),
            ("rotary-positions", {"rope": True}),
            ("bf16-state", {"state_dtype": "bfloat16"}),
            ("stale-state-row", {"stale_state": 5}),
            ("padded-tail-counts", {"pad_tail": 3, "prompt_len": 20})),
    engine=dict(num_blocks=65, prefill_buckets=(16, 32), max_len=96),
    engine_prompts=tuple(
        np.random.default_rng(n).integers(0, 512, n).tolist()
        for n in (5, 16, 27)),
    tight=(dict(block_size=4, num_blocks=12, decode_slots=(2,),
                prefill_buckets=(8, 40), max_len=40),
           ([1, 2, 3, 4], [5, 6, 7]), 24),
    counters={"experts_hit": (2, 16), "expert_load_max": (1, 4)},
    scopes=frozenset({"ssm", "ssm_in", "conv", "scan", "ssm_out", "router",
                      "moe_route", "experts", "shared_expert"}),
    stepping=frozenset({"state_read", "state_write"}))


class TestContract(ServeContract):
    family = FAMILY

    def test_the_engine_reports_the_state_rows(self, engine):
        served_alone(engine, [[5, 6, 7]], 3)
        status = engine.status()
        assert status["state"]["rows"] == 4 and status["state"]["used"] == 0
        assert status["state"]["bytes"] == sum(
            int(np.prod(s)) * np.dtype(dt).itemsize
            for s, dt in engine._state_specs)
        assert status["state"]["update"].get("xla")
        assert status["kv"]["bytes_per_token_layer"] == 2 * 32 * 4

    def test_a_reused_row_serves_the_same_tokens(self, engine):
        """A slot's tokens are the same when it takes the state row another
        sequence has just given back."""
        a_ids, b_ids = [1, 2, 3, 4], [9, 9, 200, 17, 5]
        solo_a, = served_alone(engine, [a_ids], 14)
        solo_b, = served_alone(engine, [b_ids], 9)
        # fill every row, let them go, and take them again in another order
        others = [engine.submit([7, i + 1, 3], max_new_tokens=5)
                  for i in range(4)]
        for h in others:
            h.result(timeout_s=120)
        b = engine.submit(b_ids, max_new_tokens=9)
        a = engine.submit(a_ids, max_new_tokens=14)
        assert a.result(timeout_s=120) == solo_a
        assert b.result(timeout_s=120) == solo_b
        assert engine.status()["state"]["used"] == 0

    def test_a_cancelled_request_gives_its_row_back(self, engine):
        h = engine.submit([5, 6, 7], max_new_tokens=60)
        next(iter(h.tokens(timeout_s=120)))
        assert engine.status()["state"]["used"] == 1
        engine.cancel(h)
        deadline = time.monotonic() + 30
        while engine.status()["state"]["used"] \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        assert engine.status()["state"]["used"] == 0

    def test_step_records_carry_the_rows(self, engine):
        from paddle_tpu.observability import tracing

        with tracing.recorded():
            served_alone(engine, [[1, 2, 3]], 6)
            steps = [s for s in tracing.get_records("decode.steps")
                     if s["kind"] == "decode"]
        assert len(steps) >= 3
        for s in steps:
            assert s["state_rows"] == 4 and 0 <= s["state_rows_used"] <= 4
        assert any(s["state_rows_used"] == 1 for s in steps)

    def test_a_larger_bucket_leaves_the_same_state_and_token(self,
                                                             programs):
        """A prompt edge-padded to a bucket twice its own: the padded tail
        must not advance the state, nor move the convolution's tail."""
        sm, params = programs.sm, programs.params
        ids = _ids(programs.cfg, 13, seed=2)
        fresh = programs.fresh()
        row16, small = programs.prefill(ids, fresh, blocks=[3, 4, 5, 6])
        padded = np.full((1, 32), ids[-1], np.int32)
        padded[0, :13] = ids
        args = (params, jnp.asarray(padded), jnp.int32(13), fresh.k,
                fresh.v, jnp.asarray(table([3, 4, 5, 6], programs.width)),
                fresh.state, jnp.int32(ROW))
        row32, _, _, large = program(sm, decoder.prefill, *args)(*args)
        assert row16.argmax() == np.asarray(row32)[0].argmax()
        for a, b in zip(small.state, large):
            np.testing.assert_allclose(a[:, ROW], b[:, ROW], atol=2e-6)
            assert np.abs(np.asarray(a[:, ROW])).max() > 0
            # and no other row was touched
            assert not np.asarray(a[:, :ROW]).any() \
                and not np.asarray(a[:, ROW + 1:]).any()

    def test_prefill_overwrites_whatever_the_row_held(self, programs):
        ids = _ids(programs.cfg, 9, seed=4)
        clean = programs.fresh()
        _, want = programs.prefill(ids, clean, blocks=[1, 2])
        dirty = clean._replace(
            state=tuple(jnp.full_like(s, 7.0) for s in clean.state))
        _, got = programs.prefill(ids, dirty, blocks=[1, 2])
        for a, b in zip(got.state, want.state):
            np.testing.assert_array_equal(a[:, ROW], b[:, ROW])


# -- the layers --------------------------------------------------------------


def test_the_parameters_are_one_stack_a_kind_in_the_patterns_order():
    cfg = _tiny()[0]
    params, axes = nh.init(jax.random.key(3), cfg)
    assert cfg.pattern == "MEM*E" and cfg.layers == 5
    assert params["mamba.in_proj"].shape == (
        2, cfg.hidden, cfg.inner + cfg.conv_dim + cfg.ssm_heads)
    # the routed experts laid out at whole lane tiles, the padding zero
    assert cfg.expert_pad == 128 and cfg.expert_dim == 24
    assert params["moe.w_up"].shape == (2, 8, cfg.hidden, 128)
    assert not np.asarray(params["moe.w_up"][..., 24:]).any()
    assert not np.asarray(params["moe.w_down"][:, :, 24:]).any()
    assert params["attn.wk"].shape == (1, cfg.hidden, 2 * cfg.head_dim)
    assert set(axes) == set(params)
    assert axes["moe.w_up"] == ("layer", "expert", "embed", "mlp")
    # a block alone is the block of the stack (block 2 is the 2nd Mamba)
    alone = nh.init_layer(jax.random.key(3), cfg, 2)
    np.testing.assert_array_equal(alone["blk.A_log"], params["mamba.A_log"][1])
    # Mamba-2's own draws
    A = np.exp(np.asarray(params["mamba.A_log"]))
    assert (A >= 1.0).all() and (A <= 16.0).all()
    dt = np.log1p(np.exp(np.asarray(params["mamba.dt_bias"])))
    assert (dt >= cfg.dt_min * 0.999).all() and (dt <= cfg.dt_max * 1.001).all()
    assert (np.asarray(params["mamba.D"]) == 1.0).all()


def test_relu2_experts_through_the_shared_expert_layer():
    """`expert_mlp` in the two-matrix form against every expert computed
    for every row; the three-matrix form is tests/test_olmoe.py's."""
    cfg, params = _tiny()
    ref = dataclasses.asdict(cfg)
    lp = nh.block_params(params, "E", 1)
    y = _normal(jax.random.key(1), (11, cfg.hidden))
    got, stats = moe.expert_mlp(lp, y, cfg.routing)
    rlp = ref_mod.layer_of(params, ref, 4)
    assert rlp["blk.w_up"].shape[-1] == cfg.expert_dim
    with jax.default_matmul_precision("highest"):
        want = ref_mod._experts(rlp, y, ref)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert 2 <= int(stats["experts_hit"]) <= 8
    with pytest.raises(ValueError, match="unknown expert form"):
        moe.expert_mlp(lp, y, dataclasses.replace(cfg.routing, form="glu"))


# every (K, N) the four sparse configurations hand the grouped matmul:
# OLMoE's, JoyAI's and Xing4's gate/up then down, and Nemotron's up and down
_OTHERS = [(2048, 1024), (1024, 2048), (2048, 768), (768, 2048),
           (3584, 1024), (1024, 3584)]
_NEMOTRON = [(2688, 1920), (1920, 2688)]


# those, then a K and an N too long for a whole dimension, and one lane tile
@pytest.mark.parametrize("k, n, tile", [
    (2048, 1024, (1024, 1024)), (1024, 2048, (512, 2048)),
    (2048, 768, (2048, 768)), (768, 2048, (768, 2048)),
    (3584, 1024, (1792, 1024)), (1024, 3584, (512, 3584)),
    (2688, 1920, (896, 1920)), (1920, 2688, (640, 2688)),
    (65536, 1024, (1024, 1024)), (1024, 65536, (128, 8192)),
    (128, 128, (128, 128))])
def test_the_grouped_matmuls_tiles_follow_the_shapes(k, n, tile):
    """`grouped_matmul.tiles` reads (K, N, itemsize) alone: the tiles divide
    the operands, a weight tile is within the budget, what the kernel
    holds fits its VMEM, and the columns are whole wherever a tile of
    whole columns fits."""
    tm, tk, tn = gm.tiles(k, n, 2)
    assert (tk, tn) == tile and tm == gm.ROW_TILE
    assert k % tk == 0 and n % tn == 0 and tk % 128 == 0 and tn % 128 == 0
    assert tk * tn * 2 <= gm.TILE_BYTES < 4 * 2 ** 20
    assert gm.planned_vmem((tm, tk, tn), 2) <= gm.VMEM_BYTES == 16 * 2 ** 20
    if 128 * n * 2 <= gm.TILE_BYTES:
        assert tn == n
    # float32 operands: half the elements, the same bytes
    fm, fk, fn = gm.tiles(k, n, 4)
    assert k % fk == 0 and n % fn == 0 and fk * fn * 4 <= gm.TILE_BYTES
    assert gm.planned_vmem((fm, fk, fn), 4) <= gm.VMEM_BYTES


def test_nemotrons_tiles_are_no_smaller_than_the_other_models():
    """The widths that are no power of two (2688 = 21 x 128, 1920 = 15 x
    128) once fell to the smallest tiles of the four, 1.15 MB in pieces a
    third of a row long; the rule gives them whole rows and tiles as large
    as anyone's."""
    def tile_bytes(kn):
        _, tk, tn = gm.tiles(*kn, 2)
        return tk * tn * 2
    ours = [tile_bytes(kn) for kn in _NEMOTRON]
    theirs = [tile_bytes(kn) for kn in _OTHERS]
    assert min(ours) >= min(theirs) and min(ours) > 3e6


# -- the recurrence ----------------------------------------------------------


def _scan_case(T, seed=0, B=2, H=8, P=4, G=2, N=16):
    k = jax.random.split(jax.random.key(seed), 6)
    return (_normal(k[0], (B, T, H, P)),
            jax.nn.softplus(_normal(k[1], (B, T, H))),
            -jnp.exp(_normal(k[2], (H,))),
            _normal(k[3], (B, T, G, N)), _normal(k[4], (B, T, G, N)),
            _normal(k[5], (H,)))


@pytest.mark.parametrize("T, chunk", [(37, 8), (64, 16), (5, 8), (128, 128)])
def test_the_chunked_scan_is_the_recurrence(T, chunk):
    x, dt, A, Bm, Cm, D = _scan_case(T)
    y0, s0 = ssm.ssd_recurrent(x, dt, A, Bm, Cm, D)
    y1, s1 = ssm.ssd_chunked(x, dt, A, Bm, Cm, D, chunk)
    np.testing.assert_allclose(y1, y0, rtol=3e-4, atol=5e-5)
    np.testing.assert_allclose(s1, s0, rtol=3e-4, atol=5e-6)


def test_positions_whose_dt_is_zero_leave_the_state_alone():
    x, dt, A, Bm, Cm, D = _scan_case(40)
    n = 23
    counted = (jnp.arange(40) < n)[None, :, None]
    y, s = ssm.ssd_chunked(x, dt * counted, A, Bm, Cm, D, 8)
    y0, s0 = ssm.ssd_recurrent(x[:, :n], dt[:, :n], A, Bm[:, :n],
                               Cm[:, :n], D)
    np.testing.assert_allclose(s, s0, atol=5e-6)
    np.testing.assert_allclose(y[:, :n], y0, atol=5e-5)


def test_the_convolutions_tail_is_taken_at_the_length():
    k = jax.random.split(jax.random.key(0), 3)
    x = _normal(k[0], (2, 12, 6))
    w, b = _normal(k[1], (4, 6)), _normal(k[2], (6,))
    full = ssm.causal_conv(x, w, b)
    for n in (0, 2, 7):
        out, tail = ssm.conv_step(ssm.conv_tail(x, n, 4), x[:, n], w, b)
        np.testing.assert_allclose(out, full[:, n], atol=1e-6)
        np.testing.assert_array_equal(tail, ssm.conv_tail(x, n + 1, 4))
    assert not np.asarray(ssm.conv_tail(x, 1, 4)[:, :2]).any()


# -- the cache ---------------------------------------------------------------


def test_the_pools_are_the_models():
    cfg, _ = _tiny()
    sm = cfg.serve_model()
    kv, _, state = pools(sm, 24, 64)
    # K/V for the ONE attention block, of 2 K/V heads of 16
    assert kv.pool_shapes == ((1, 24, BS, 32), (1, 24, BS, 32))
    assert sm.kv_layers == 1 and sm.layers == 5 and sm.kv_heads == 2
    # a tail (its 3 inputs end to end, as whole lane tiles) and a float32
    # state a Mamba block and row
    assert [s.shape for s in state] == [(2, 5, 3 * cfg.conv_dim // 128, 128),
                                        (2, 5, 8, 8, 16)]
    assert state[1].dtype == jnp.float32
    assert sm.state_pools(5, jnp.bfloat16)[0][1] == jnp.bfloat16
    # a model whose layers are alike keeps nothing but its blocks
    from paddle_tpu.models import olmoe

    plain = olmoe.OlmoeConfig.tiny().serve_model()
    assert plain.pattern is None and plain.kv_layers == plain.layers
    assert plain.state_pools(5, jnp.float32) == ()


# -- the two kernels, through the interpreter --------------------------------


def test_the_state_update_kernel_is_the_recurrences_step():
    L, R, H, P, N, G, S = 2, 6, 16, 8, 128, 4, 5
    rng = np.random.default_rng(0)
    f32 = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731,E501
    pool = f32(L, R, H, P, N)
    rows = jnp.asarray([3, 0, 5, 0, 1], jnp.int32)
    x, Bm, Cm = f32(S, H, P), f32(S, G, N), f32(S, G, N)
    dt = jnp.asarray(rng.uniform(0.01, 0.5, (S, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(1, 16, (H,)), jnp.float32)
    want_y, want_s = ssm.ssd_step(pool[1, rows], x, dt, A, Bm, Cm,
                                  jnp.zeros((H,)))
    old = SU._BLOCK_BYTES
    SU._BLOCK_BYTES = 8 * P * N * 4     # two blocks of heads a slot
    try:
        y, new = jax.jit(lambda *a: SU.state_update(
            *a, interpret=pltpu.InterpretParams()))(
            pool, jnp.int32(1), rows, jnp.exp(dt * A), dt[..., None] * x,
            Bm, Cm)
    finally:
        SU._BLOCK_BYTES = old
    live = np.asarray(rows) > 0
    np.testing.assert_allclose(y[live], want_y[live], atol=2e-5)
    np.testing.assert_allclose(new[1, rows][live], want_s[live], atol=2e-6)
    # the other layer and the rows no slot holds are as they were
    np.testing.assert_array_equal(new[0], pool[0])
    np.testing.assert_array_equal(new[1, jnp.asarray([2, 4])],
                                  pool[1, jnp.asarray([2, 4])])


def test_the_state_updates_gate(monkeypatch):
    from paddle_tpu.ops.pallas import attention as A

    pool = jax.ShapeDtypeStruct((4, 65, 64, 64, 128), jnp.float32)
    x = jnp.zeros((64, 2688), jnp.bfloat16)
    assert not SU.use_kernel(x, pool, 8)        # off the TPU
    monkeypatch.setattr(A, "_platform", lambda q: "tpu")
    assert SU.use_kernel(x, pool, 8) and SU._heads_per_block(pool) == 32
    assert not SU.use_kernel(
        x, jax.ShapeDtypeStruct(pool.shape, jnp.bfloat16), 8)
    assert not SU.use_kernel(
        x, jax.ShapeDtypeStruct((4, 65, 64, 64, 16), jnp.float32), 8)


GQA = dict(heads=32, kv_heads=2, d=128, L=2, S=5, MB=20)
GQA_PATTERNS = {"inactive": [0, 0, 0, 0, 0], "block-edge": [16, 17, 15, 32, 1],
                "chunk-edge": [256, 257, 255, 16, 1],
                "mixed": [0, 1, 300, 17, 320]}


@pytest.fixture(scope="module")
def gqa():
    c = GQA
    nb = 1 + c["S"] * c["MB"]
    rng = np.random.default_rng(1)
    pools = tuple(jnp.asarray(rng.standard_normal(
        (c["L"], nb, 16, c["kv_heads"] * c["d"])), jnp.bfloat16)
        for _ in range(2))
    q = jnp.asarray(rng.standard_normal((c["S"], c["heads"] * c["d"])),
                    jnp.bfloat16)
    run = jax.jit(lambda q, kp, vp, l, t, p: PA.paged_gqa_attention(
        q, kp, vp, l, t, p, heads=c["heads"], kv_heads=c["kv_heads"],
        interpret=pltpu.InterpretParams()))
    return q, pools, run, nb


@pytest.mark.parametrize("pattern", sorted(GQA_PATTERNS))
def test_the_gqa_kernel_matches_the_gathered_form(gqa, pattern):
    q, (kp, vp), run, nb = gqa
    c, lens = GQA, GQA_PATTERNS[pattern]
    rng = np.random.default_rng(7)
    tables = np.zeros((c["S"], c["MB"]), np.int32)
    free = list(rng.permutation(np.arange(1, nb)))
    for s, n in enumerate(lens):
        for b in range(-(-n // 16)):
            tables[s, b] = free.pop()
    pos = jnp.asarray([max(n - 1, 0) for n in lens], jnp.int32)
    tables = jnp.asarray(tables)
    got = np.asarray(run(q, kp, vp, jnp.int32(1), tables, pos), np.float32)
    f32 = jnp.float32
    want = np.asarray(decoder.mha_cached(
        q.astype(f32)[:, None], kvc.gather_kv(kp, 1, tables).astype(f32),
        kvc.gather_kv(vp, 1, tables).astype(f32), pos[:, None], c["heads"],
        c["kv_heads"])[:, 0])
    live = np.asarray(lens) > 0
    if live.any():
        assert np.abs(got - want)[live].max() < 0.02
    assert not got[~live].any()


def test_grouped_attention_reads_the_groups_kv_head():
    """`gqa_prompt` and `mha_cached` against K and V repeated to as many
    heads as the queries."""
    k = jax.random.split(jax.random.key(0), 3)
    B, T, H, KV, D = 2, 9, 8, 2, 4
    q = _normal(k[0], (B, T, H * D))
    kk, vv = (_normal(x, (B, T, KV * D)) for x in k[1:])
    rep = lambda a: jnp.repeat(a.reshape(B, T, KV, D), H // KV,  # noqa: E731
                               axis=2).reshape(B, T, H * D)
    pos = jnp.broadcast_to(jnp.arange(T), (B, T))
    want = decoder.mha_cached(q, rep(kk), rep(vv), pos, H)
    np.testing.assert_allclose(decoder.gqa_prompt(q, kk, vv, H, KV), want,
                               atol=1e-5)
    np.testing.assert_allclose(decoder.mha_cached(q, kk, vv, pos, H, KV),
                               want, atol=1e-5)


def test_the_gqa_gate(monkeypatch):
    from paddle_tpu.ops.pallas import attention as A

    pool = jax.ShapeDtypeStruct((1, 10241, 16, 256), jnp.bfloat16)
    x = jnp.zeros((64, 2688), jnp.bfloat16)
    sm = nh.NemotronHConfig().serve_model()
    assert sm.paged_route(x, pool, pool) is None        # off the TPU
    monkeypatch.setattr(A, "_platform", lambda q: "tpu")
    assert sm.paged_route(x, pool, pool) == "paged_gqa"
    assert not PA.use_paged_gqa(x, pool, 24, 2)     # not whole sublane tiles
    assert not PA.use_paged_gqa(
        x, jax.ShapeDtypeStruct((1, 64, 16, 128), jnp.bfloat16), 32, 2)


# -- the engine --------------------------------------------------------------


@pytest.mark.parametrize("knobs, reason", [
    (dict(prefill_chunk=16), "prefill_chunk: a prompt's slices"),
    (dict(prefill_chunk=16, prefix_cache=True), "prefix_cache: a shared"),
    (dict(spec_k=2), "spec_k: a rejected draft"),
])
def test_boot_refuses_what_needs_a_snapshot_of_the_state(knobs, reason):
    cfg, params = _tiny()
    draft = (params, cfg) if "spec_k" in knobs else None
    with pytest.raises(ValueError, match="recurrent state") as e:
        DecodeEngine(params, cfg, DecodeConfig(
            block_size=BS, num_blocks=33, decode_slots=(4,), max_len=64,
            precision="f32", **knobs), draft=draft)
    assert reason in str(e.value)


def test_the_state_rows_allocator():
    alloc = kvc.StateRowAllocator(4, [((2, 4, 3), "float32")])
    assert alloc.stats() == {"rows": 3, "used": 0, "bytes": 96}
    rows = [alloc.alloc() for _ in range(3)]
    assert sorted(rows) == [1, 2, 3] and alloc.used_rows() == 3
    with pytest.raises(kvc.NoBlocksError):
        alloc.alloc()
    alloc.free(2)
    assert alloc.alloc() == 2
    with pytest.raises(ValueError, match="null row"):
        alloc.free(0)
    alloc.free(1)
    with pytest.raises(ValueError, match="double free"):
        alloc.free(1)
    with pytest.raises(ValueError):
        kvc.StateRowAllocator(1)

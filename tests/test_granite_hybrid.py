"""Granite 4.0-H (models/granite_hybrid.py) at a tiny size on the CPU. What
every served family must do is `tests/serve_contract.py`'s, bound here
against the benchmark's plain float32 reference
(benchmarks/reference/granite_hybrid_ref.py: the recurrence token by token,
the published router's order, every held expert for every token, no cache);
what is this model's own follows it: a prompt walked in slices against one
pass, the router's two orders, the two shares of a layer against the uncut
layer, a reused state row, the pools' dtypes, and the in-place state update
at ONE B/C group through the Pallas TPU interpreter. What the interpreter
cannot see is tests/test_tpu_aot_compile.py's (`-k granite`)."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas import tpu as pltpu

from benchmarks.reference import granite_hybrid_ref as ref_mod
from paddle_tpu.models import decoder, granite_hybrid as gh, moe
from paddle_tpu.ops import ssm
from paddle_tpu.ops.pallas import ssm_update as SU
from serve_contract import (ROW, Family, ServeContract, program, seeded,
                            served_alone)


@functools.cache
def _tiny():
    cfg = gh.GraniteHybridConfig.tiny()
    cfg.dtype = "float32"
    # 64 lanes of embedding at 0.02 over 16 would leave logits of 0.01
    cfg.logits_scaling = 2.0
    return cfg, seeded(gh, cfg, 3)


def _normal(key, shape):
    """float32 whatever conftest's x64 mode makes the default."""
    return jax.random.normal(key, shape, jnp.float32)


FAMILY = Family(
    module=gh, tiny=_tiny, ref=ref_mod,
    logits=lambda params, model, ids: ref_mod.logits_rows(
        params, model, jnp.asarray(ids), 0, len(ids),
        prompt_len=model.get("prompt_len")),
    tol=1e-5, tol_why="float32 on both sides, logits of deviation 0.09 (64 "
                      "lanes of a 0.02 embedding over `logits_scaling` 2): "
                      "the two sides agree to 1e-6, and every rule of the "
                      "layer, changed in the REFERENCE, moves the logits by "
                      "ten tolerances or more",
    far=10.0,
    faults=(("residual-multiplier-1", {"residual_multiplier": 1.0}),
            ("embedding-multiplier-dropped", {"embedding_multiplier": 1.0}),
            ("softmax-at-rsqrt-d", {"attention_multiplier": 0.25}),
            ("logits-scaling-dropped", {"logits_scaling": 1.0}),
            ("rotary-positions", {"rope": True}),
            ("norm-in-groups", {"norm_groups": 4}),
            ("B-and-C-a-head", {"bc_per_head": True}),
            ("D-dropped", {"skip_D": True}),
            ("dt-bias-left-out", {"dt_bias": False}),
            ("conv-bias-dropped", {"conv_bias": False}),
            ("shared-expert-dropped", {"shared_expert": False}),
            ("kept-weights-not-renormalised", {"norm_topk": False}),
            ("silu-gate-dropped", {"act": "none"}),
            ("held-term-dropped", {"held_term": False}),
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
    # 4 layers of 4 held experts; 4 slots x top-3
    counters={"experts_hit": (0, 16), "expert_load_max": (0, 4),
              "held_pairs": (0, 48), "zero_pairs": (0, 0),
              "pairs": (48, 48)},
    scopes=frozenset({"ssm", "ssm_in", "conv", "scan", "ssm_out", "router",
                      "moe_route", "experts", "shared_expert"}),
    stepping=frozenset({"state_read", "state_write"}))


class TestContract(ServeContract):
    family = FAMILY

    def test_the_engine_reports_the_model_and_the_rows(self, engine):
        served_alone(engine, [[5, 6, 7]], 3)
        status = engine.status()
        assert status["state"]["rows"] == 4 and status["state"]["used"] == 0
        assert status["state"]["update"].get("xla")
        assert status["model"]["blocks"] == "MEME*EME"
        assert status["model"]["held_experts"] == [0, 4]
        assert status["model"]["multipliers"]["residual"] == 0.22
        # K and V of the ONE attention layer
        assert status["kv"]["bytes_per_token_layer"] == 2 * 32 * 4

    def test_a_sequence_in_a_row_just_freed_gets_the_tokens_it_gets_alone(
            self, engine):
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

    def test_a_prompt_in_slices_leaves_what_one_pass_leaves(self, programs):
        """The contract's prefill walks its bucket of 16 in two slices of 8
        (`prompt_slice`); the same prompt in ONE slice (a model whose slice
        covers the bucket) gives the same logits, K/V and state row: the
        second slice started from the row the first left, its convolution
        from the first's last 3 inputs, its attention from the cache."""
        ids = programs.seq[:13]
        sliced_row, sliced = programs.prefill(ids, programs.fresh())
        cfg = dataclasses.replace(programs.cfg, prompt_slice=16)
        fresh = programs.fresh()
        args = (programs.params, programs._padded(ids, 16), jnp.int32(13),
                fresh.k, fresh.v, jnp.asarray(programs.table), fresh.state,
                jnp.int32(ROW))
        row, k, v, state = program(cfg.serve_model(), decoder.prefill,
                                   *args)(*args)
        assert np.abs(np.asarray(row)[0] - sliced_row).max() < FAMILY.tol
        used = programs.blocks[:2]
        for a, b in ((k, sliced.k), (v, sliced.v)):
            a, b = np.asarray(a)[:, used], np.asarray(b)[:, used]
            assert np.abs(a).max() > 0.1 and np.abs(a - b).max() < 1e-5
        for a, b in zip(state, sliced.state):   # values of 1 to 4 in float32
            np.testing.assert_allclose(a[:, ROW], b[:, ROW], atol=1e-5)
            assert np.abs(np.asarray(a[:, ROW])).max() > 0

    def test_the_state_pool_is_float32_and_the_tail_the_served_dtype(
            self, programs):
        tails, states = programs.sm.state_pools(5, jnp.bfloat16)
        cfg = programs.cfg
        assert states == ((3, 5, cfg.ssm_heads, cfg.ssm_head_dim,
                           cfg.ssm_state), jnp.float32)
        assert tails[1] == jnp.bfloat16
        assert int(np.prod(tails[0][2:])) == 3 * cfg.conv_dim
        big = gh.GraniteHybridConfig(pattern="MMMMM*MMMM").serve_model()
        (tail, dt), (state, sdt) = big.state_pools(49, jnp.bfloat16)
        assert tail == (9, 49, 198, 128) and dt == jnp.bfloat16
        assert state == (9, 49, 128, 64, 128) and sdt == jnp.float32
        assert big.kv_layers == 1 and big.stored == (1024, 1024)
        assert big.pattern == "MEMEMEMEME*EMEMEMEME"


# -- the layer ---------------------------------------------------------------


def test_the_parameters_are_one_stack_a_kind():
    cfg, params = _tiny()
    axes = {}
    jax.eval_shape(lambda k: axes.update(gh.init(k, cfg)[1]),
                   jax.random.key(3))
    assert gh.blocks(cfg.pattern) == "MEME*EME" and cfg.layers == 4
    assert params["mamba.in_proj"].shape == (
        3, cfg.hidden, cfg.inner + cfg.conv_dim + cfg.ssm_heads)
    assert params["moe.w_gate"].shape == (4, 4, cfg.hidden, cfg.expert_dim)
    assert params["moe.router"].shape == (4, cfg.hidden, 8)
    assert params["attn.wk"].shape == (1, cfg.hidden, 2 * cfg.head_dim)
    assert "head.w" not in params           # the head is the embedding
    assert set(axes) == set(params)
    # block 4 is the attention layer's mixer, block 7 the last experts
    def block7(held):       # one program a share: eagerly, an op a tensor
        return jax.jit(lambda k: gh.init_layer(
            k, dataclasses.replace(cfg, held=held), 7))(jax.random.key(3))

    alone = block7(cfg.held)
    np.testing.assert_array_equal(alone["blk.router"],
                                  params["moe.router"][3])
    # a share holds what the whole layer holds at those ids
    whole, other = block7(None), block7((4, 8))
    np.testing.assert_array_equal(whole["blk.w_down"][:4],
                                  alone["blk.w_down"])
    np.testing.assert_array_equal(whole["blk.w_up"][4:], other["blk.w_up"])


def test_the_published_router_is_softmax_then_normalise():
    """Top-k of the LOGITS and a softmax over the kept (the published
    order, the reference's) against `Routing(softmax, normalise)`: softmax
    over all, top-k, divide by the kept's sum."""
    cfg = _tiny()[0]
    logits = 2.0 * _normal(jax.random.key(5), (64, 72))
    routing = moe.Routing(72, 10, score="softmax", normalise=True)
    weight, expert = moe.route(logits, routing)
    want = np.asarray(ref_mod.route(logits, {"top_k": 10}))
    got = np.zeros_like(want)
    np.put_along_axis(got, np.asarray(expert), np.asarray(weight), axis=-1)
    assert (np.count_nonzero(want, axis=-1) == 10).all()
    np.testing.assert_allclose(got, want, atol=2e-7)
    assert cfg.routing == moe.Routing(8, 3, score="softmax", normalise=True,
                                      shared=True, form="swiglu",
                                      held=(0, 4))


def test_the_two_shares_of_a_layer_sum_to_the_uncut_layer():
    """`held` (0, 4) and (4, 8) of one expert layer through `expert_mlp`,
    the shared expert counted once, against the reference's layer with all
    8 experts."""
    cfg = _tiny()[0]
    key = jax.random.key(3)
    whole = jax.jit(lambda k: gh.init_layer(
        k, dataclasses.replace(cfg, held=None), 1))(key)
    y = _normal(jax.random.key(9), (24, cfg.hidden))
    with jax.default_matmul_precision("highest"):
        model = dict(dataclasses.asdict(cfg), held=None)
        want = np.asarray(ref_mod.experts(whole, y, model))
        only_shared = np.asarray(ref_mod.experts(
            whole, y, dict(model, held_term=False)))
        parts, pairs = [], 0
        for held in ((0, 4), (4, 8)):
            share = dataclasses.replace(cfg, held=held)
            out, stats = jax.jit(lambda k, y, share=share: moe.expert_mlp(
                gh.init_layer(k, share, 1), y, share.routing))(key, y)
            parts.append(np.asarray(out))
            pairs += int(stats["held_pairs"])
            assert int(stats["pairs"]) == 24 * cfg.top_k
    assert pairs == 24 * cfg.top_k
    assert np.abs(parts[0] - only_shared).max() > 0.1
    np.testing.assert_allclose(parts[0] + parts[1] - only_shared, want,
                               atol=2e-5)


def test_the_chunked_scan_continues_from_a_slice():
    """`mamba_prompt` over a sequence in two parts, the second from the
    tail and the state the first left, against one pass; the second part's
    padded tail leaves both as they were."""
    from paddle_tpu.models import nemotron_h as nh

    cfg, params = _tiny()
    lp = gh.block_params(params, "M", 0)
    y = _normal(jax.random.key(2), (1, 24, cfg.hidden))

    def both(lp, y):
        want = nh.mamba_prompt(lp, y, jnp.int32(21), cfg)
        a, tail_a, state_a = nh.mamba_prompt(lp, y[:, :16], jnp.int32(16),
                                             cfg)
        return want, a, nh.mamba_prompt(lp, y[:, 16:], jnp.int32(5), cfg,
                                        init=(tail_a, state_a))

    with jax.default_matmul_precision("highest"):
        (want, tail, state), a, (b, tail_b, state_b) = jax.jit(both)(lp, y)
    got = np.concatenate([a, b], axis=1)
    np.testing.assert_allclose(got[:, :21], np.asarray(want)[:, :21],
                               atol=2e-6)
    np.testing.assert_allclose(tail_b, tail, atol=1e-6)
    np.testing.assert_allclose(state_b, state, atol=2e-6)


def test_the_state_update_kernel_at_one_group_is_the_recurrence():
    """`ssm_update.state_update` in the interpreter where a block of heads
    is PART of the one group (32 heads of [8, 128] a block, 64 heads on one
    B and C) against `ops/ssm.ssd_step`, idle slots on row 0."""
    L, R, H, P, N, S = 2, 5, 64, 8, 128, 4
    keys = jax.random.split(jax.random.key(0), 6)
    pool = _normal(keys[0], (L, R, H, P, N))
    assert SU._heads_per_block(pool) == 64
    # a block smaller than the group: the cell's 32 of 128
    old, SU._BLOCK_BYTES = SU._BLOCK_BYTES, 32 * P * N * 4
    try:
        assert SU._heads_per_block(pool) == 32
        rows = jnp.asarray([3, 0, 1, 0], jnp.int32)
        x = _normal(keys[1], (S, H, P))
        dt = jax.nn.softplus(_normal(keys[2], (S, H)))
        A = -jnp.exp(_normal(keys[3], (H,)))
        Bm, Cm = _normal(keys[4], (S, 1, N)), _normal(keys[5], (S, 1, N))
        want_y, want_s = ssm.ssd_step(pool[1, rows], x, dt, A, Bm, Cm,
                                      jnp.zeros((H,)))
        y, new = SU.state_update(
            pool, jnp.int32(1), rows, jnp.exp(dt * A), dt[..., None] * x,
            Bm, Cm, interpret=pltpu.InterpretParams())
    finally:
        SU._BLOCK_BYTES = old
    for s in (0, 2):    # the live slots: row 0 is stale by its second turn
        np.testing.assert_allclose(y[s], want_y[s], atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(new[1, rows[s]], want_s[s], atol=2e-5,
                                   rtol=2e-5)
    np.testing.assert_array_equal(new[0], pool[0])
    np.testing.assert_array_equal(new[1, (2, 4), ...], pool[1, (2, 4), ...])


def test_gqa_slice_is_causal_attention_over_the_cache():
    """`decoder.gqa_slice` for the second slice of a prompt against the
    whole causal attention's rows, at a scale that is not 1/sqrt(D)."""
    from paddle_tpu.serving import kv_cache as kvc

    heads, kvh, D, BS, T, C = 4, 2, 16, 8, 32, 16
    keys = jax.random.split(jax.random.key(4), 3)
    q = _normal(keys[0], (1, T, heads * D))
    k = _normal(keys[1], (1, T, kvh * D))
    v = _normal(keys[2], (1, T, kvh * D))
    kv = kvc.KVCacheConfig(layers=2, widths=(kvh * D,) * 2, max_len=T,
                           block_size=BS, num_blocks=12, dtype="float32")
    kp, vp = kvc.init_pools(kv)
    blocks = jnp.asarray([7, 2, 9, 4], jnp.int32)
    kp = kvc.write_prefill_kv(kp, jnp.int32(1), k[0], blocks, BS)
    vp = kvc.write_prefill_kv(vp, jnp.int32(1), v[0], blocks, BS)
    with jax.default_matmul_precision("highest"):
        want = decoder.gqa_prompt(q, k, v, heads, kvh, 0.4)
        got = decoder.gqa_slice(q[:, C:], kp, vp, jnp.int32(1), blocks,
                                jnp.int32(C), BS, heads, kvh, 0.4)
        plain = decoder.gqa_prompt(q, k, v, heads, kvh)
    np.testing.assert_allclose(got, want[:, C:], atol=2e-6)
    assert np.abs(np.asarray(plain - want)).max() > 0.01

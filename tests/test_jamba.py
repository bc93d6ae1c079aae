"""Jamba (models/jamba.py) at a tiny size on the CPU. What every served
family must do is `tests/serve_contract.py`'s, bound here against the
benchmark's plain float32 reference (benchmarks/reference/jamba_ref.py: the
selective recurrence token by token, no cache); what is this model's own
follows it: the prompt's form of the selective scan and its one-token form
against the recurrence, what a padded bucket and a reused state row may NOT
change, the in-place row update's kernel, the prompt's scan kernel and
multi-query paged attention at a group of 20 through the Pallas TPU
interpreter. What the interpreter
cannot see is tests/test_tpu_aot_compile.py's; the chip is chip_smoke.py's
`serve_jamba` phase."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from benchmarks.reference import jamba_ref as ref_mod
from paddle_tpu.models import decoder, jamba
from paddle_tpu.ops import ssm
from paddle_tpu.ops.pallas import paged_attention as PA
from paddle_tpu.ops.pallas import ssm_scan as SS
from paddle_tpu.ops.pallas import ssm_update as SU
from paddle_tpu.serving import kv_cache as kvc
from serve_contract import (BS, ROW, Family, ServeContract, pools, program,
                            seeded, served_alone, table)


@functools.cache
def _tiny():
    cfg = jamba.JambaConfig.tiny()
    cfg.dtype = "float32"
    return cfg, seeded(jamba, cfg, 3)


def _normal(key, shape):
    """float32 whatever conftest's x64 mode makes the default."""
    return jax.random.normal(key, shape, jnp.float32)


def _ids(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(0, cfg.vocab_size, n)


FAMILY = Family(
    module=jamba, tiny=_tiny, ref=ref_mod,
    logits=lambda params, model, ids: ref_mod.logits_rows(
        params, model, jnp.asarray(ids), 0, len(ids),
        prompt_len=model.get("prompt_len")),
    tol=5e-5, tol_why="float32 on both sides (they agree to 4e-6); the "
                      "tied head's logits have a deviation of 0.18 at the "
                      "tiny width, and every rule of the layers, left out "
                      "of the REFERENCE, moves them by 0.02 and more",
    far=100.0,
    faults=(("bf16-state", {"state_dtype": "bfloat16"}),
            ("one-decay-a-channel", {"scalar_decay": True}),
            ("dt-norm-left-out", {"dt_norm": False}),
            ("B-norm-left-out", {"b_norm": False}),
            ("C-norm-left-out", {"c_norm": False}),
            ("conv-bias-dropped", {"conv_bias": False}),
            ("dt-bias-left-out", {"dt_bias": False}),
            ("D-dropped", {"skip_D": True}),
            ("rotary-positions", {"rope": True}),
            ("positions-added", {"learned_pos": True}),
            ("padded-tail-counts", {"pad_tail": 3, "prompt_len": 20}),
            ("tail-from-the-buckets-end", {"pad_conv": 3,
                                           "prompt_len": 20})),
    engine=dict(num_blocks=65, prefill_buckets=(16, 32), max_len=96),
    engine_prompts=tuple(
        np.random.default_rng(n).integers(0, 512, n).tolist()
        for n in (5, 16, 27)),
    tight=(dict(block_size=4, num_blocks=12, decode_slots=(2,),
                prefill_buckets=(8, 40), max_len=40),
           ([1, 2, 3, 4], [5, 6, 7]), 24),
    scopes=frozenset({"ssm", "ssm_in", "conv", "scan", "ssm_out"}),
    stepping=frozenset({"state_read", "state_write"}))


class TestContract(ServeContract):
    family = FAMILY

    def test_the_engine_reports_the_state_rows_and_the_route(self, engine):
        served_alone(engine, [[5, 6, 7]], 3)
        status = engine.status()
        assert status["state"]["rows"] == 4 and status["state"]["used"] == 0
        assert status["state"]["bytes"] == sum(
            int(np.prod(s)) * np.dtype(dt).itemsize
            for s, dt in engine._state_specs)
        # one count a Mamba layer of the decode program, gathered here
        assert status["state"]["update"].get("xla")
        assert status["state"]["update"].get("scan_xla")
        assert not status["state"]["update"].get("kernel")
        assert not status["state"]["update"].get("scan_kernel")
        # K and V of ONE head of 16 a token, in float32
        assert status["kv"]["bytes_per_token_layer"] == 2 * 16 * 4

    def test_a_reused_row_serves_the_same_tokens(self, engine):
        a_ids, b_ids = [1, 2, 3, 4], [9, 9, 200, 17, 5]
        solo_a, = served_alone(engine, [a_ids], 14)
        solo_b, = served_alone(engine, [b_ids], 9)
        others = [engine.submit([7, i + 1, 3], max_new_tokens=5)
                  for i in range(4)]
        for h in others:
            h.result(timeout_s=120)
        b = engine.submit(b_ids, max_new_tokens=9)
        a = engine.submit(a_ids, max_new_tokens=14)
        assert a.result(timeout_s=120) == solo_a
        assert b.result(timeout_s=120) == solo_b
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
        np.testing.assert_allclose(row16, np.asarray(row32)[0], atol=2e-6)
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
    params, axes = jamba.init(jax.random.key(3), cfg)
    assert cfg.pattern == "ME*EME*E" and cfg.n_layers == 4
    assert cfg.inner == 128 and cfg.count("M") == 2 and cfg.count("E") == 4
    assert params["mamba.in_proj"].shape == (2, cfg.hidden, 2 * cfg.inner)
    assert params["mamba.x_proj"].shape == (
        2, cfg.inner, cfg.dt_rank + 2 * cfg.ssm_state)
    assert params["mamba.A_log"].shape == (2, cfg.ssm_state, cfg.inner)
    assert params["attn.wk"].shape == (2, cfg.hidden, cfg.head_dim)
    assert params["mlp.w_gate"].shape == (4, cfg.hidden, cfg.mlp_dim)
    assert "head.w" not in params       # the embedding is the head
    assert set(axes) == set(params)
    # a block alone is the block of the stack (block 4 is the 2nd Mamba)
    alone = jamba.init_layer(jax.random.key(3), cfg, 4)
    np.testing.assert_allclose(alone["blk.in_proj"],
                               params["mamba.in_proj"][1], rtol=1e-6)
    # Mamba-1's own draws: A = 1..N a channel, dt in [dt_min, dt_max], D 1
    A = np.exp(np.asarray(params["mamba.A_log"]))
    np.testing.assert_allclose(
        A, np.broadcast_to(np.arange(1, 17)[None, :, None], A.shape),
        rtol=1e-6)
    dt = np.log1p(np.exp(np.asarray(params["mamba.dt_bias"])))
    assert (dt >= cfg.dt_min * 0.999).all() \
        and (dt <= cfg.dt_max * 1.001).all()
    assert (np.asarray(params["mamba.D"]) == 1.0).all()
    # the published layout: attention where l % 14 == 7
    big = jamba.JambaConfig()
    assert big.pattern == ("ME" * 7 + "*E" + "ME" * 6) * 2
    assert big.count("M") == 26 and big.count("*") == 2 and big.inner == 5120


# -- the recurrence ----------------------------------------------------------


def _scan_case(T, seed=0, B=2, C=24, N=16):
    k = jax.random.split(jax.random.key(seed), 6)
    return (_normal(k[0], (B, T, C)),
            jax.nn.softplus(_normal(k[1], (B, T, C))),
            -jnp.exp(_normal(k[2], (N, C))),
            _normal(k[3], (B, T, N)), _normal(k[4], (B, T, N)),
            _normal(k[5], (C,)))


@pytest.mark.parametrize("T", [37, 64, 5, 12])
def test_the_prompts_form_is_the_selective_recurrence(T):
    """`selective_recurrent` (a scan whose body holds `SELECTIVE_UNROLL`
    tokens) against `selective_step` token by token in a plain loop: at
    lengths that are whole bodies, hold a remainder, or are under one."""
    x, dt, A, Bm, Cm, D = _scan_case(T)

    def by_hand(state):
        ys = []
        for t in range(T):
            y, state = ssm.selective_step(state, x[:, t], dt[:, t], A,
                                          Bm[:, t], Cm[:, t], D)
            ys.append(y)
        return jnp.stack(ys, axis=1), state

    y0, s0 = by_hand(jnp.zeros((x.shape[0],) + A.shape, jnp.float32))
    y1, s1 = ssm.selective_recurrent(x, dt, A, Bm, Cm, D)
    np.testing.assert_allclose(y1, y0, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s1, s0, rtol=1e-5, atol=1e-6)
    # and from a state that is not zero
    init = _normal(jax.random.key(9), s0.shape)
    y2, s2 = by_hand(init)
    y3, s3 = ssm.selective_recurrent(x, dt, A, Bm, Cm, D, init)
    np.testing.assert_allclose(y3, y2, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s3, s2, rtol=1e-5, atol=1e-6)


def test_the_selective_step_is_the_recurrence_as_written():
    """A decay for every (state lane, channel) pair: checked value by
    value in numpy, and against `ssd_step` where the lanes decay alike."""
    x, dt, A, Bm, Cm, D = _scan_case(1, seed=3, B=3)
    state = _normal(jax.random.key(4), (3, 16, 24))
    y, new = ssm.selective_step(state, x[:, 0], dt[:, 0], A, Bm[:, 0],
                                Cm[:, 0], D)
    s, xs, dts = (np.asarray(a, np.float64)
                  for a in (state, x[:, 0], dt[:, 0]))
    want = np.exp(dts[:, None, :] * np.asarray(A)[None]) * s \
        + (dts * xs)[:, None, :] * np.asarray(Bm[:, 0])[:, :, None]
    np.testing.assert_allclose(new, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        y, (want * np.asarray(Cm[:, 0])[:, :, None]).sum(1)
        + np.asarray(D) * xs, rtol=1e-5, atol=1e-5)
    # one scalar a channel is Mamba-2's recurrence with heads of width 1
    flat = jnp.broadcast_to(A.mean(0, keepdims=True), A.shape)
    y1, s1 = ssm.selective_step(state, x[:, 0], dt[:, 0], flat, Bm[:, 0],
                                Cm[:, 0], D)
    y2, s2 = ssm.ssd_step(jnp.swapaxes(state, 1, 2)[:, :, None, :],
                          x[:, 0][..., None], dt[:, 0], flat[0],
                          Bm[:, :1], Cm[:, :1], D)
    np.testing.assert_allclose(y1, y2[..., 0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s1, jnp.swapaxes(s2[:, :, 0], 1, 2),
                               rtol=1e-5, atol=1e-6)
    assert np.abs(np.asarray(y1 - y)).max() > 0.05


def test_positions_whose_dt_is_zero_leave_the_state_alone():
    x, dt, A, Bm, Cm, D = _scan_case(40)
    n = 23
    counted = (jnp.arange(40) < n)[None, :, None]
    y, s = ssm.selective_recurrent(x, dt * counted, A, Bm, Cm, D)
    y0, s0 = ssm.selective_recurrent(x[:, :n], dt[:, :n], A, Bm[:, :n],
                                     Cm[:, :n], D)
    np.testing.assert_allclose(s, s0, atol=2e-6)
    np.testing.assert_allclose(y[:, :n], y0, atol=2e-5)


def test_a_prompts_scan_holds_nothing_that_grows_with_the_prompt():
    """The compiled prompt form at 32 and at 256 tokens: the temporaries'
    bytes beyond the inputs and the `[B, T, C]` output are the same."""
    def temp(T):
        args = [jax.ShapeDtypeStruct(a.shape, a.dtype)
                for a in _scan_case(T, B=1, C=128)]
        ma = jax.jit(ssm.selective_recurrent) \
            .lower(*args).compile().memory_analysis()
        return ma.temp_size_in_bytes - 4 * T * 128 * 4
    assert temp(256) <= temp(32) + 64 * 1024


# -- the cache ---------------------------------------------------------------


def test_the_pools_are_the_models():
    cfg, _ = _tiny()
    sm = cfg.serve_model()
    kv, _, state = pools(sm, 24, 64)
    # K/V for the TWO attention layers, of ONE K/V head of 16
    assert kv.pool_shapes == ((2, 24, BS, 16), (2, 24, BS, 16))
    assert sm.kv_layers == 2 and sm.layers == 8 and sm.kv_heads == 1
    # a tail (its 3 inputs end to end, as whole lane tiles) and a float32
    # state, channels in the lanes, a Mamba layer and row
    assert [s.shape for s in state] == [(2, 5, 3, 128), (2, 5, 16, 128)]
    assert state[1].dtype == jnp.float32
    assert sm.state_pools(5, jnp.bfloat16)[0][1] == jnp.bfloat16
    big = jamba.JambaConfig(max_len=1024).serve_model()
    assert [s for s, _ in big.state_pools(129, jnp.bfloat16)] == [
        (26, 129, 120, 128), (26, 129, 16, 5120)]
    assert big.stored == (128, 128) and big.kv_layers == 2


# -- the two kernels, through the interpreter --------------------------------


# (S slots' rows, rows a grid step or None for the rule's, channels): what
# the row walk must get right whatever the kernel's body
_IDLE = [3, 0, 5, 0, 1, 6, 2, 0, 4, 0, 0]
_WALKS = [
    pytest.param(_IDLE, None, 256, id="fewer-slots-than-a-step-256"),
    pytest.param(_IDLE, None, 1024, id="fewer-slots-than-a-step-1024"),
    pytest.param(_IDLE, 4, 512, id="a-last-step-filled-up"),
    pytest.param([6, 2, 7, 1, 5, 3, 9, 4], 4, 512,
                 id="whole-steps-rows-out-of-order"),
    pytest.param([0, 0, 5, 0, 1, 0, 0, 2, 0, 8, 0, 0], 4, 512,
                 id="idle-slots-beside-live-rows"),
    pytest.param([9, 4, 1, 12, 7, 2, 11, 5, 3, 8, 6, 10, 13], 2, 512,
                 id="seven-steps-through-two-slots"),
    pytest.param([5], None, 512, id="one-slot"),
]
_POOL_ROWS = 16


def _walks_rows(monkeypatch, rows, per_step, row_bytes):
    """`rows` as an array, with the rule steered to `per_step` rows a grid
    step for a row of `row_bytes` (the rule itself has a test of its own)."""
    if per_step is not None:
        monkeypatch.setattr(SU, "_STEP_BYTES", per_step * row_bytes)
    assert SU.rows_per_step(row_bytes, len(rows)) == (
        per_step or 1 << (len(rows) - 1).bit_length())
    return jnp.asarray(rows, jnp.int32)


def _left_alone(new, old, layer, rows):
    """The other layer, and this layer's rows that `rows` does not name, bit
    for bit as they were: nothing else of a pool moves (but the null row,
    which a last step is filled up with)."""
    np.testing.assert_array_equal(new[1 - layer], old[1 - layer])
    rest = sorted(set(range(1, old.shape[1]))
                  - set(np.asarray(rows).tolist()))
    assert rest
    np.testing.assert_array_equal(new[layer, rest], old[layer, rest])


@pytest.mark.parametrize("rows, per_step, C", _WALKS)
def test_the_selective_update_kernel_is_the_recurrences_step(
        monkeypatch, rows, per_step, C):
    L, N, S = 2, 16, len(rows)
    rows = _walks_rows(monkeypatch, rows, per_step, N * C * 4)
    rng = np.random.default_rng(0)
    f32 = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731,E501
    pool = f32(L, _POOL_ROWS, N, C)
    x, Bm, Cm = f32(S, C), f32(S, N), f32(S, N)
    dt = jnp.asarray(rng.uniform(0.01, 0.5, (S, C)), jnp.float32)
    A = -jnp.asarray(rng.uniform(1, 16, (N, C)), jnp.float32)
    want_y, want_s = ssm.selective_step(pool[1, rows], x, dt, A, Bm, Cm,
                                        jnp.zeros((C,)))
    y, new = jax.jit(lambda *a: SU.selective_update(
        *a, interpret=pltpu.InterpretParams()))(
        pool, jnp.int32(1), rows, dt, dt * x, A, Bm, Cm)
    assert y.shape == (S, C)
    live = np.asarray(rows) > 0
    np.testing.assert_allclose(y[live], want_y[live], atol=2e-5)
    np.testing.assert_allclose(new[1, rows][live], want_s[live], atol=2e-6)
    _left_alone(new, pool, 1, rows)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("rows, per_step, C", _WALKS[1:])
def test_the_tails_kernel_is_the_convolutions_step(monkeypatch, rows,
                                                   per_step, C, dtype):
    C = max(C, 1024)        # a position is whole float32 tiles: the gate
    L, K, S = 2, 4, len(rows)
    rows = _walks_rows(monkeypatch, rows, per_step,
                       (K - 1) * C * jnp.dtype(dtype).itemsize)
    rng = np.random.default_rng(1)
    draw = lambda *s: jnp.asarray(rng.standard_normal(s), dtype)  # noqa: E731
    pool = draw(L, _POOL_ROWS, (K - 1) * C // 128, 128)
    x, w, b = draw(S, C), draw(K, C), draw(C)
    want, want_tail = ssm.conv_step(
        pool[1, rows].reshape(S, K - 1, C), x, w, b)
    out, new = jax.jit(lambda *a: SU.advance_tails(
        *a, interpret=pltpu.InterpretParams()))(
        pool, jnp.int32(1), rows, x, w, b)
    assert out.shape == (S, C) and out.dtype == dtype
    live = np.asarray(rows) > 0
    as_f32 = lambda a: np.asarray(a.astype(jnp.float32))    # noqa: E731
    # float32 sums in another order, then (bf16) one rounding to 8 bits
    np.testing.assert_allclose(
        as_f32(out)[live], as_f32(want)[live], atol=1e-5,
        rtol=2 ** -7 if dtype == jnp.bfloat16 else 1e-5)
    # the stored tail bit for bit: the same inputs, moved on by one
    np.testing.assert_array_equal(
        as_f32(new[1, rows]).reshape(S, K - 1, C)[live],
        as_f32(want_tail)[live])
    _left_alone(as_f32(new), as_f32(pool), 1, rows)


def test_a_prompts_tail_is_advanced_token_by_token_where_it_lies():
    """`ssm_prompt` writes a prompt's tail into the pool; `advance_tails`
    then moves it on a token at a time, and row and convolution agree with
    `mamba_prompt` over the prompt grown by those tokens."""
    cfg = jamba.JambaConfig(vocab_size=64, hidden=512, n_layers=1,
                            attn_period=2, attn_offset=1, mlp_dim=64,
                            dt_rank=8, heads=4, head_dim=16, max_len=32,
                            dtype="float32")
    sm, T0, T, row = cfg.serve_model(), 5, 9, 2
    lp = jamba.init_layer(jax.random.PRNGKey(0), cfg, 0, "M")
    y = _normal(jax.random.PRNGKey(1), (1, T, cfg.hidden))
    state = tuple(jnp.ones(s, d) for s, d in sm.state_pools(4, jnp.float32))
    assert state[0].shape == (1, 4, 24, 128)
    bucket = jnp.pad(y[:, :T0], [(0, 0), (0, 8 - T0), (0, 0)])
    _, (conv, _) = jax.jit(lambda b, s: sm.ssm_prompt(
        lp, b, jnp.int32(T0), s, 0, row))(bucket, state)
    grown = jax.jit(lambda n: jamba.mamba_prompt(lp, y, n, cfg)[1])
    xs = jamba._split_in(lp, y, cfg)[0]
    convolved = ssm.causal_conv(xs, lp["blk.conv_w"], lp["blk.conv_b"])
    step = jax.jit(lambda c, x: SU.advance_tails(
        c, jnp.int32(0), jnp.asarray([row], jnp.int32), x, lp["blk.conv_w"],
        lp["blk.conv_b"], interpret=pltpu.InterpretParams()))
    # (to float32 rounding: the projection of 8, of 9 and of one token are
    # three matmuls; what the kernel itself stores is exact, above)
    close = functools.partial(np.testing.assert_allclose, atol=1e-5,
                              rtol=1e-5)
    close(conv[0, row].reshape(3, cfg.inner), grown(T0)[0])
    for t in range(T0, T):
        out, conv = step(conv, xs[:, t])
        close(out, convolved[:, t])
        close(conv[0, row].reshape(3, cfg.inner), grown(t + 1)[0])
    np.testing.assert_array_equal(conv[0, [0, 1, 3]], state[0][0, [0, 1, 3]])


def test_the_walks_rows_a_step_come_from_the_shapes():
    # a state row of Jamba2-3B (16 x 5120 float32, 320 KB) and a tail (3 x
    # 5120 bf16, 30 KB) at 128 slots: the powers of two nearest 2 MB a step
    assert SU.rows_per_step(16 * 5120 * 4, 128) == 8
    assert SU.rows_per_step(3 * 5120 * 2, 128) == 64
    assert SU.rows_per_step(16 * 8192 * 4, 128) == 4
    assert SU.rows_per_step(8 << 20, 128) == 1
    # and never more than cover the slots
    assert [SU.rows_per_step(3 * 5120 * 2, s) for s in (1, 2, 3, 16, 17,
                                                        100)] \
        == [1, 2, 4, 16, 32, 64]


def test_the_selective_updates_gate(monkeypatch):
    from paddle_tpu.ops.pallas import attention as A

    pool = jax.ShapeDtypeStruct((26, 129, 16, 5120), jnp.float32)
    x = jnp.zeros((128, 2560), jnp.bfloat16)
    assert not SU.use_selective_kernel(x, pool)         # off the TPU
    monkeypatch.setattr(A, "_platform", lambda q: "tpu")
    assert SU.use_selective_kernel(x, pool)
    assert not SU.use_selective_kernel(
        x, jax.ShapeDtypeStruct(pool.shape, jnp.bfloat16))
    assert not SU.use_selective_kernel(
        x, jax.ShapeDtypeStruct((26, 129, 16, 1280), jnp.float32))
    # and Mamba-2's gate stays shut for this state, as ever
    assert not SU.use_kernel(x, pool, 1)


def test_the_tails_gate(monkeypatch):
    from paddle_tpu.ops.pallas import attention as A

    sds = jax.ShapeDtypeStruct
    pool = sds((26, 129, 120, 128), jnp.bfloat16)
    x = jnp.zeros((128, 2560), jnp.bfloat16)
    assert not SU.use_tail_kernel(x, pool, 4)           # off the TPU
    monkeypatch.setattr(A, "_platform", lambda q: "tpu")
    assert SU.use_tail_kernel(x, pool, 4)
    assert SU.use_tail_kernel(x, sds(pool.shape, jnp.float32), 4)
    # a position must be whole float32 tiles of 8 x 128: 5120 channels are
    # 40 rows of lanes, the tiny model's 128 one
    assert not SU.use_tail_kernel(x, sds((2, 5, 3, 128), jnp.bfloat16), 4)
    assert not SU.use_tail_kernel(x, pool, 5)
    assert not SU.use_tail_kernel(x, sds((26, 129, 384), jnp.bfloat16), 4)
    assert not SU.use_tail_kernel(x, sds(pool.shape, jnp.float16), 4)


@pytest.mark.parametrize("T, C, n", [(24, 512, 19), (256, 1024, 256),
                                     (64, 128, 1)])
def test_the_prompts_scan_kernel_is_the_selective_recurrence(T, C, n):
    """The state in VMEM over blocks of 8, 128 and 64 tokens, one and two
    blocks of channels; positions at or past `n` (dt 0) leave the state as
    position n - 1 left it and give finite outputs."""
    x, dt, A, Bm, Cm, _ = _scan_case(T, C=C)
    dt = dt * (jnp.arange(T) < n)[None, :, None]
    y0, s0 = ssm.selective_recurrent(x[:, :n], dt[:, :n], A, Bm[:, :n],
                                     Cm[:, :n], jnp.zeros((C,)))
    y, s = jax.jit(lambda *a: SS.selective_scan(
        *a, interpret=pltpu.InterpretParams()))(x, dt, A, Bm, Cm)
    assert y.shape == (2, T, C) and s.shape == (2, 16, C)
    np.testing.assert_allclose(y[:, :n], y0, rtol=1e-5, atol=2e-5)
    np.testing.assert_allclose(s, s0, rtol=1e-5, atol=2e-6)
    assert np.isfinite(np.asarray(y)).all()


def test_the_prompts_scans_gate(monkeypatch):
    from paddle_tpu.ops.pallas import attention as A

    x = jnp.zeros((1, 256, 5120), jnp.bfloat16)
    assert not SS.use_kernel(x, 16)                     # off the TPU
    monkeypatch.setattr(A, "_platform", lambda q: "tpu")
    assert SS.use_kernel(x, 16)
    assert [SS._token_block(t) for t in (64, 128, 256, 512, 24, 20)] \
        == [64, 128, 128, 128, 8, 0]
    assert not SS.use_kernel(jnp.zeros((1, 20, 5120)), 16)   # no tile of 8
    assert not SS.use_kernel(jnp.zeros((1, 64, 128)), 16)    # narrow


MQA = dict(heads=20, d=128, L=2, S=5, MB=20)
MQA_PATTERNS = {"inactive": [0, 0, 0, 0, 0], "block-edge": [16, 17, 15, 32, 1],
                "mixed": [0, 1, 300, 17, 320]}


@pytest.fixture(scope="module")
def mqa():
    c = MQA
    nb = 1 + c["S"] * c["MB"]
    rng = np.random.default_rng(1)
    pools = tuple(jnp.asarray(rng.standard_normal(
        (c["L"], nb, 16, c["d"])), jnp.bfloat16) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((c["S"], c["heads"] * c["d"])),
                    jnp.bfloat16)
    run = jax.jit(lambda q, kp, vp, l, t, p: PA.paged_gqa_attention(
        q, kp, vp, l, t, p, heads=c["heads"], kv_heads=1,
        interpret=pltpu.InterpretParams()))
    return q, pools, run, nb


@pytest.mark.parametrize("pattern", sorted(MQA_PATTERNS))
def test_twenty_query_heads_read_one_kv_head_through_the_table(mqa, pattern):
    """Multi-query attention at a group of 20, which is not whole sublane
    tiles: the kernel's query block is filled up with rows of zeros."""
    q, (kp, vp), run, nb = mqa
    c, lens = MQA, MQA_PATTERNS[pattern]
    rng = np.random.default_rng(7)
    tables = np.zeros((c["S"], c["MB"]), np.int32)
    free = list(rng.permutation(np.arange(1, nb)))
    for s, n in enumerate(lens):
        for b in range(-(-n // 16)):
            tables[s, b] = free.pop()
    pos = jnp.asarray([max(n - 1, 0) for n in lens], jnp.int32)
    tables = jnp.asarray(tables)
    got = np.asarray(run(q, kp, vp, jnp.int32(1), tables, pos), np.float32)
    assert got.shape == (c["S"], c["heads"] * c["d"])
    f32 = jnp.float32
    want = np.asarray(decoder.mha_cached(
        q.astype(f32)[:, None], kvc.gather_kv(kp, 1, tables).astype(f32),
        kvc.gather_kv(vp, 1, tables).astype(f32), pos[:, None], c["heads"],
        1)[:, 0])
    live = np.asarray(lens) > 0
    if live.any():
        assert np.abs(got - want)[live].max() < 0.02
    assert not got[~live].any()


def test_the_mqa_gate(monkeypatch):
    from paddle_tpu.ops.pallas import attention as A

    pool = jax.ShapeDtypeStruct((2, 8193, 16, 128), jnp.bfloat16)
    x = jnp.zeros((128, 2560), jnp.bfloat16)
    sm = jamba.JambaConfig().serve_model()
    assert sm.paged_route(x, pool, pool) is None        # off the TPU
    monkeypatch.setattr(A, "_platform", lambda q: "tpu")
    assert sm.paged_route(x, pool, pool) == "paged_gqa"
    # under two K/V heads rows of zeros would move the groups
    two = jax.ShapeDtypeStruct((2, 8193, 16, 256), jnp.bfloat16)
    assert not PA.use_paged_gqa(x, two, 20, 2)
    with pytest.raises(ValueError, match="whole sublane tiles"):
        PA.paged_gqa_attention(
            jnp.zeros((4, 24 * 128), jnp.bfloat16),
            jnp.zeros((1, 9, 16, 256), jnp.bfloat16),
            jnp.zeros((1, 9, 16, 256), jnp.bfloat16), jnp.int32(0),
            jnp.zeros((4, 2), jnp.int32), jnp.zeros((4,), jnp.int32),
            heads=24, kv_heads=2)

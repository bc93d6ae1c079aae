"""models/joyai.py against the plain reference (benchmarks/reference/
joyai_ref.py) at a tiny size on the CPU, seeded random weights, float32:
what every served family must do is `tests/serve_contract.py`'s, bound here
(the full forward pass in the expanded form, the serve programs through the
latent paged cache in the absorbed one); what is JoyAI's own follows it: the
router's properties one by one, the latent pool's layout, the two forms of
attention, and the latent kernel through the interpreter."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import joyai_ref
from paddle_tpu.models import decoder, joyai, moe
from paddle_tpu.serving import kv_cache as kvc
from serve_contract import (BS, SLOTS, Family, ServeContract, pools,
                            program, seeded, table)


@functools.cache
def _tiny():
    cfg = joyai.JoyaiConfig.tiny()      # hidden 64, 4 heads of 16+8 / 16,
    cfg.dtype = "float32"               # latent 32, 1 dense + 2 expert layers
    params = seeded(joyai, cfg)         # of 8 experts, top-2
    # a correction bias that matters at 8 experts (the configuration's own
    # spread is sized for 256): it changes which experts are chosen
    params["blk.router_bias"] = 0.3 * jax.random.normal(
        jax.random.key(9), params["blk.router_bias"].shape, jnp.float32)
    return cfg, params


# The latent model's own parts nest inside the shared names: the two
# low-rank projections and RoPE under `qkv`, the absorb products (in the
# programs that read the cache) under `attention`, the dense layer's MLP
# and the expert layer's parts, the shared expert among them, under `mlp`
NESTED = {"mlp": frozenset({"router", "moe_route", "experts",
                            "shared_expert", "dense_mlp"}),
          "qkv": frozenset({"mla_q", "mla_kv", "rope"}),
          "attention": frozenset({"absorb"})}

FAMILY = Family(
    module=joyai, tiny=_tiny, ref=joyai_ref,    # which reads them by name
    tol=2e-4, tol_why="float32 on both sides: rounding on logits of unit "
                      "scale; each of the faults the bf16 tolerance of the "
                      "benchmark's cell may or may not tell apart moves a "
                      "logit by 100 times that or more",
    faults=(("shared_expert_dropped", {"shared_expert": False}),
            ("scale_left_out", {"route_scale": 1.0}),
            ("softmax_for_sigmoid", {"score": "softmax"}),
            ("bias_left_out_of_the_selection", {"bias_selects": False}),
            ("bias_let_into_the_weights", {"bias_weighs": True}),
            ("unnormalised_weights", {"norm_topk_prob": False}),
            ("rotate_half_for_interleaved", {"rope": "half"}),
            ("rope_over_the_wrong_64", {"rope_on": "nope"}),
            ("latent_used_before_its_norm", {"kv_norm": False}),
            ("one_expert_fewer", {"top_k": 1})),
    # 4 slots x top-2 pairs a layer, 2 expert layers
    counters={"experts_hit": (2, 16), "expert_load_max": (1, SLOTS)},
    nested=NESTED, reading=frozenset({"kv_gather", "absorb"}),
    # the leading dense layer runs before the scan, inside `layers`; the
    # expert layers in its body
    paths=(r"/layers/mlp/dense_mlp/", r"/layers/while/body/.*mlp/experts/"))


class TestContract(ServeContract):
    family = FAMILY

    def test_the_pools_hold_the_rotary_key_in_its_first_lanes(self,
                                                              programs):
        cfg, sm = programs.cfg, programs.sm
        n = FAMILY.prompts[0]
        served = programs.served("whole", n)
        # the counters are the expert layers': the leading dense layer has
        # none
        assert served.stats["experts_hit"].shape == (cfg.expert_layers,)
        facts = sm.step_facts(jax.device_get(served.stats))
        assert 2 <= facts["experts_hit"] \
            <= cfg.expert_layers * cfg.top_k * SLOTS
        assert 1 <= facts["expert_load_max"] <= SLOTS
        # what lies in the pools: the rotary key in its first lanes, zeros
        # after
        used = np.asarray(served.cache.v)[:, programs.blocks[:3]]
        assert np.abs(used[..., :cfg.rope_dim]).max() > 0.1
        assert not used[..., cfg.rope_dim:].any()

    def test_the_engine_reports_the_stored_layout(self, programs, engine):
        cfg = programs.cfg
        engine.submit([5, 6, 7], max_new_tokens=3).result(timeout_s=120)
        assert engine.kv_cfg.pool_shapes == (
            (cfg.layers, 64, BS, cfg.kv_rank), (cfg.layers, 64, BS, 128))
        status = engine.status()
        assert status["kv"]["bytes_per_token_layer"] \
            == (cfg.kv_rank + 128) * 4
        assert status["kv"]["pool_bytes"] == engine.kv_cfg.pool_bytes() \
            == cfg.layers * 64 * BS * (cfg.kv_rank + 128) * 4
        assert status["decode_attention"].get("gather", 0) >= 1

    def test_the_warm_start_digest_covers_the_cache_layout(self, engine,
                                                           monkeypatch):
        """The digest hashes the pool geometry's repr, which names the
        stored layout: two geometries that differ in the entries' widths
        alone do not share warm-start artifacts."""
        import dataclasses

        assert "widths=(32, 128)" in repr(engine.kv_cfg)
        digest = engine._model_digest()
        monkeypatch.setattr(engine, "kv_cfg", dataclasses.replace(
            engine.kv_cfg, widths=(32, 256)))
        assert engine._model_digest() != digest


# -- the router's properties, one by one -------------------------------------


@pytest.fixture(scope="module")
def routed():
    """16 rows through the routing rule alone, with what it should say
    written out in numpy."""
    cfg, _ = _tiny()
    logits = np.asarray(jax.random.normal(jax.random.key(5),
                                          (16, cfg.n_experts))) * 1.5
    bias = np.asarray(jax.random.normal(jax.random.key(6),
                                        (cfg.n_experts,))) * 0.3
    weight, expert = moe.route(jnp.asarray(logits), cfg.routing,
                               jnp.asarray(bias))
    score = 1.0 / (1.0 + np.exp(-logits))
    return cfg, score, bias, np.asarray(weight), np.asarray(expert)


def test_router_scores_are_sigmoids_not_a_softmax(routed):
    cfg, score, _, weight, expert = routed
    # the kept scores, before normalising, are in the ratio of the sigmoids
    kept = np.take_along_axis(score, expert, axis=-1)
    np.testing.assert_allclose(weight / weight.sum(-1, keepdims=True),
                               kept / kept.sum(-1, keepdims=True), rtol=1e-5)
    assert score.sum(-1).max() > 1.5        # nothing sums to one


def test_router_selects_on_score_plus_bias(routed):
    cfg, score, bias, _, expert = routed
    want = np.argsort(-(score + bias), axis=-1)[:, :cfg.top_k]
    assert np.array_equal(np.sort(expert, -1), np.sort(want, -1))
    plain = np.argsort(-score, axis=-1)[:, :cfg.top_k]
    # and the bias does change the choice for some rows
    assert (np.sort(plain, -1) != np.sort(want, -1)).any()


def test_router_weights_come_from_the_scores_alone(routed):
    cfg, score, bias, weight, expert = routed
    kept = np.take_along_axis(score, expert, axis=-1)
    np.testing.assert_allclose(
        weight, kept / (kept.sum(-1, keepdims=True) + 1e-20)
        * cfg.route_scale, rtol=1e-5)
    biased = np.take_along_axis(score + bias, expert, axis=-1)
    assert np.abs(weight - biased / biased.sum(-1, keepdims=True)
                  * cfg.route_scale).max() > 0.01


def test_router_weights_are_normalised_then_scaled(routed):
    cfg, _, _, weight, _ = routed
    np.testing.assert_allclose(weight.sum(-1), cfg.route_scale, rtol=1e-5)
    assert cfg.route_scale == 2.5


def test_the_shared_expert_is_always_on():
    """The expert layer alone against the formula written out: the routed
    sum plus one more SwiGLU that every row gets unweighted."""
    cfg, params = _tiny()
    lp = {k: np.asarray(v[0]) for k, v in params.items()
          if k.startswith("blk.")}
    y = np.asarray(jax.random.normal(jax.random.key(2), (6, cfg.hidden)))

    def swiglu(x, g, u, d):
        a = x @ g
        return (a / (1 + np.exp(-a)) * (x @ u)) @ d

    score = 1.0 / (1.0 + np.exp(-(y @ lp["blk.router"])))
    want = swiglu(y, lp["blk.shared_gate"], lp["blk.shared_up"],
                  lp["blk.shared_down"])
    shared = want.copy()
    for t in range(len(y)):
        chosen = np.argsort(-(score[t] + lp["blk.router_bias"]))[:cfg.top_k]
        total = score[t, chosen].sum()
        for e in chosen:
            want[t] += score[t, e] / total * cfg.route_scale * swiglu(
                y[t], lp["blk.w_gate"][e], lp["blk.w_up"][e],
                lp["blk.w_down"][e])
    with jax.default_matmul_precision("highest"):
        got, stats = moe.expert_mlp(lp, jnp.asarray(y), cfg.routing)
    scale = np.abs(want).max()
    assert np.abs(np.asarray(got) - want).max() < 1e-5 * max(1.0, scale)
    assert np.abs(np.asarray(got) - (want - shared)).max() > 0.05 * scale
    assert 1 <= int(stats["experts_hit"]) <= cfg.n_experts
    assert int(stats["expert_load_max"]) >= 2   # 12 pairs on 8 experts


def test_the_configurations_bias_changes_a_few_percent_of_the_sets():
    """`joyai.BIAS_STD` at the published 256 experts, top-8: the sets of
    some tokens differ from the unbiased ones, most do not."""
    routing = joyai.JoyaiConfig().routing
    logits = jax.random.normal(jax.random.key(3), (4096, routing.n_experts))
    bias = joyai.BIAS_STD * jax.random.normal(jax.random.key(4),
                                              (routing.n_experts,))
    _, with_bias = moe.route(logits, routing, bias)
    _, without = moe.route(logits, routing, jnp.zeros_like(bias))
    differ = (np.sort(np.asarray(with_bias), -1)
              != np.sort(np.asarray(without), -1)).any(-1).mean()
    assert 0.01 < differ < 0.10, differ


def test_the_experts_of_a_layer_share_a_base():
    """`EXPERT_SPREAD`: every entry keeps the plain deviation (the shared
    expert's, whose draw is its own), and two experts of a layer agree in
    `1 - spread^2` of it."""
    lp = joyai.init_layer(jax.random.key(0), joyai.JoyaiConfig.tiny(), 1)
    for name, plain in (("blk.w_gate", "blk.shared_gate"),
                        ("blk.w_up", "blk.shared_up"),
                        ("blk.w_down", "blk.shared_down")):
        w = np.asarray(lp[name])
        assert abs(w.std() / np.asarray(lp[plain]).std() - 1.0) < 0.06, name
        a, b = w[0].ravel(), w[1].ravel()
        shared = float(a @ b) / float(np.linalg.norm(a) * np.linalg.norm(b))
        assert abs(shared - (1.0 - joyai.EXPERT_SPREAD ** 2)) < 0.02, name
        assert np.abs(w[0] - w[1]).max() > 0      # and no two are one


def test_a_norms_gains_are_not_all_one():
    """With gains of 1 on an input the init scales hold at unit RMS a norm
    is the identity and nothing could see it left out (the cached latent
    stored BEFORE its norm): the gains spread by `NORM_STD` about 1."""
    lp = joyai.init_layer(jax.random.key(0), joyai.JoyaiConfig.tiny(), 1)
    top = joyai.init_top(jax.random.key(0), joyai.JoyaiConfig.tiny())
    for g in (lp["blk.ln_in.scale"], lp["blk.q_norm.scale"],
              lp["blk.kv_norm.scale"], lp["blk.ln_post.scale"],
              top["ln_f.scale"]):
        g = np.asarray(g)
        assert abs(g.mean() - 1.0) < 0.15
        assert 0.5 * joyai.NORM_STD < g.std() < 1.5 * joyai.NORM_STD


# -- the latent pool and the two forms of attention -------------------------


def test_the_pool_stores_the_latent_and_the_rotary_key_and_nothing_else():
    """At the published widths a token stores 512 + 64 values a layer, the
    rotary key in a pool of one whole lane tile: 1280 bytes in bf16, not
    the 20480 of per-head keys and values; `pool_bytes` says so. The
    multi-head pools keep their shape and bytes."""
    cfg = joyai.JoyaiConfig(layers=5, max_len=4608)
    sm = cfg.serve_model()
    assert sm.stored == (512, 128)
    kv = kvc.KVCacheConfig(layers=5, kv_heads=sm.kv_heads,
                           head_dim=sm.head_dim, max_len=4608,
                           block_size=16, num_blocks=32 * 288 + 1,
                           widths=sm.stored)
    assert kv.pool_shapes == ((5, 9217, 16, 512), (5, 9217, 16, 128))
    assert kv.bytes_per_token() == 1280 <= 1280
    assert kv.pool_bytes() == 5 * 9217 * 16 * 1280
    assert kv.pool_bytes() < 0.07 * (
        5 * 9217 * 16 * 32 * (192 + 128) * 2)       # per-head K and V
    with pytest.raises(ValueError):
        kv.pool_shape                   # the two pools differ
    mha = kvc.KVCacheConfig(layers=36, kv_heads=20, head_dim=64,
                            max_len=1024, block_size=16, num_blocks=1025)
    assert mha.pool_shapes == ((36, 1025, 16, 1280),) * 2
    assert mha.pool_shape == (36, 1025, 16, 1280)
    assert mha.pool_bytes() == 2 * 36 * 1025 * 16 * 1280 * 2
    assert mha.bytes_per_token() == 2 * 1280 * 2
    _, (kp, vp), _ = pools(joyai.JoyaiConfig.tiny().serve_model(), 24, 64)
    assert kp.shape == (3, 24, BS, 32) and vp.shape == (3, 24, BS, 128)


def test_absorbed_attention_equals_expanded_attention():
    """The two forms of one layer's attention on the same rows: the
    prompt's expanded form (per-head keys and values through W_kvb, scores
    192 wide) and the cache's absorbed form (W_UK in the query, the
    context through W_UV), row by causal row."""
    cfg, params = _tiny()
    sm = cfg.serve_model()
    lp = sm.lead_params(params)[0]
    T = 12
    y = jax.random.normal(jax.random.key(12), (1, T, cfg.hidden))
    pos = jnp.arange(T, dtype=jnp.int32)[None]
    with jax.default_matmul_precision("highest"):
        q, c, kr = sm.qkv(lp, y, pos)
        assert c.shape == (1, T, cfg.kv_rank) and kr.shape == (1, T, 128)
        expanded = sm.attend_prompt(lp, q, c, kr)
        absorbed = sm.attend_cached(lp, q, c, kr, pos)
    assert expanded.shape == (1, T, cfg.heads * cfg.v_dim)
    assert np.abs(np.asarray(expanded)).max() > 0.1
    assert np.abs(np.asarray(expanded) - np.asarray(absorbed)).max() < 1e-5


# -- the latent kernel against the gathered form -----------------------------


def _latent_case(dtype, positions, heads=16, latent=128, rope=128, mb=40,
                 layers=2, seed=0):
    """Pools of `layers` layers, a table a slot (position 0 with no block:
    inactive), queries; returns the kernel's arguments."""
    S = len(positions)
    nb = 1 + S * mb
    rng = np.random.default_rng(seed)
    cp = jnp.asarray(rng.normal(size=(layers, nb, BS, latent)), dtype)
    rp = jnp.asarray(rng.normal(size=(layers, nb, BS, rope)),
                     dtype).at[..., 64:].set(0)
    ql = jnp.asarray(rng.normal(size=(S, heads, latent)), dtype)
    qr = jnp.asarray(rng.normal(size=(S, heads, rope)), dtype)
    tables = np.zeros((S, mb), np.int32)
    free = iter(rng.permutation(np.arange(1, nb)))
    for s, p in enumerate(positions):
        if p is not None:
            for j in range(p // BS + 1):
                tables[s, j] = next(free)
    pos = np.asarray([p or 0 for p in positions], np.int32)
    return ql, qr, cp, rp, jnp.asarray(tables), jnp.asarray(pos)


def _gathered(ql, qr, cp, rp, layer, tables, pos, scale):
    keys = kvc.gather_kv(cp, layer, tables).astype(jnp.float32)
    rope = kvc.gather_kv(rp, layer, tables).astype(jnp.float32)
    sc = (jnp.einsum("snc,smc->snm", ql.astype(jnp.float32), keys)
          + jnp.einsum("snr,smr->snm", qr.astype(jnp.float32), rope)) * scale
    seen = jnp.arange(keys.shape[1])[None, :] <= pos[:, None]
    sc = jnp.where(seen[:, None, :], sc, -jnp.inf)
    return jnp.einsum("snm,smc->snc", jax.nn.softmax(sc, -1), keys)


@pytest.mark.parametrize("layer", [0, 1])
@pytest.mark.parametrize("dtype, tol", [("float32", 1e-5),
                                        ("bfloat16", 2e-2)])
def test_the_latent_kernel_matches_the_gathered_form(dtype, tol, layer):
    """One token, one block to its last slot, a context over several
    chunks, an inactive slot, and a full table (40 blocks of 8)."""
    from jax.experimental.pallas import tpu as pltpu

    from paddle_tpu.ops.pallas import paged_attention as PA

    positions = [0, BS - 1, 300, None, 319, 257]
    args = _latent_case(dtype, positions)
    ql, qr, cp, rp, tables, pos = args
    got = PA.paged_latent_attention(
        ql, qr, cp, rp, jnp.int32(layer), tables, pos, scale=0.1,
        interpret=pltpu.InterpretParams())
    assert got.shape == ql.shape and got.dtype == ql.dtype
    want = _gathered(ql, qr, cp, rp, layer, tables, pos, 0.1)
    active = [s for s, p in enumerate(positions) if p is not None]
    err = np.abs(np.asarray(got, np.float32) - np.asarray(want))
    assert err[active].max() < tol, err.max(axis=(1, 2))
    assert not np.asarray(got, np.float32)[3].any()     # inactive: zeros


def test_a_slots_latent_result_does_not_depend_on_its_neighbours():
    from jax.experimental.pallas import tpu as pltpu

    from paddle_tpu.ops.pallas import paged_attention as PA

    outs = []
    for positions in ([300, 40, 17], [300, None, 319], [300, 0, 5]):
        ql, qr, cp, rp, tables, pos = _latent_case("bfloat16", positions,
                                                   seed=1)
        # slot 0's own blocks and queries are those of the first case
        if outs:
            tables = tables.at[0].set(first[4][0])
            ql, qr = ql.at[0].set(first[0][0]), qr.at[0].set(first[1][0])
            cp, rp = first[2], first[3]
        else:
            first = (ql, qr, cp, rp, tables)
        outs.append(np.asarray(PA.paged_latent_attention(
            ql, qr, cp, rp, jnp.int32(0), tables, pos, scale=0.07,
            interpret=pltpu.InterpretParams())[0], np.float32))
    assert np.array_equal(outs[0], outs[1])
    assert np.array_equal(outs[0], outs[2])


def test_decode_step_through_the_latent_kernel_agrees_with_the_gather(
        monkeypatch):
    """`decode_step` with the gate answered yes and the kernel in the
    interpreter against the route the gate picks here."""
    from jax.experimental.pallas import tpu as pltpu

    from paddle_tpu.ops.pallas import paged_attention as PA

    # a latent width the kernel's lanes can hold; the rest stays tiny
    cfg = joyai.JoyaiConfig.tiny()
    cfg.dtype, cfg.kv_rank, cfg.heads = "float32", 128, 8
    params, _ = joyai.init(jax.random.key(0), cfg)
    sm = cfg.serve_model()
    _, (kp, vp), _ = pools(sm, 24, 64)
    ids = np.full((1, 16), 7, np.int32)
    ids[0, :11] = np.arange(20, 31)
    bt = table([3, 5], 8)
    fill = (params, jnp.asarray(ids), jnp.int32(11), kp, vp, jnp.asarray(bt))
    _, kp, vp = program(sm, decoder.prefill, *fill)(*fill)
    args = (params, jnp.asarray([0, 44, 0], jnp.int32),
            jnp.asarray([0, 11, 0], jnp.int32), kp, vp,
            jnp.asarray(np.stack([table([], 8), bt, table([], 8)])))
    PA.GATE_COUNTS.clear()
    want = program(sm, decoder.decode_step, *args)(*args)[0]
    assert PA.GATE_COUNTS == {"gather": 1}
    monkeypatch.setattr(PA, "use_paged_latent", lambda *a: True)
    real = PA.paged_latent_attention
    monkeypatch.setattr(
        PA, "paged_latent_attention",
        lambda *a, **k: real(*a, interpret=pltpu.InterpretParams(), **k))
    PA.GATE_COUNTS.clear()
    got = program(sm, decoder.decode_step, *args)(*args)[0]
    assert PA.GATE_COUNTS == {"paged_latent": 1}
    assert np.abs(np.asarray(got)[1] - np.asarray(want)[1]).max() < 1e-4

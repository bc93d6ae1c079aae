"""models/longcat.py against the plain reference (benchmarks/reference/
longcat_ref.py) at a tiny size on the CPU, seeded random weights, float32:
what every served family must do is `tests/serve_contract.py`'s, bound here
(2 layers = 4 cache layers, 4 heads, 8 routed + 4 zero-compute experts,
top-3, 2 of the routed experts held); what is LongCat's own follows it: the
rows whose picks reach no matrix, the counters against a count by hand, the
two MLA factors, the shares of a layer summed against the whole layer, and
`expert_mlp` as it was for the models that hold all their experts."""

import dataclasses
import functools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import longcat_ref
from paddle_tpu.models import joyai, longcat, moe, nemotron_h, olmoe
from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul
from serve_contract import (PROGRAMS, SLOTS, Family, ServeContract, seeded,
                            served_alone)
from test_joyai import NESTED as JOYAI_NESTED

EXPERT_PATH = frozenset({"router", "moe_route", "experts", "zero_experts"})


@functools.cache
def _tiny():
    cfg = longcat.LongcatConfig.tiny()
    cfg.dtype = "float32"
    params = seeded(longcat, cfg)
    # a bias that changes the set: the 12 softmax scores lie near 0.08
    params["blk.router_bias"] = 0.05 * jax.random.normal(
        jax.random.key(9), params["blk.router_bias"].shape, jnp.float32)
    return cfg, params


FAMILY = Family(
    module=longcat, tiny=_tiny, ref=longcat_ref,
    tol=2e-4, tol_why="float32 on both sides: rounding on logits of unit "
                      "scale; each fault the cell's control run plants "
                      "moves a logit by 100 times that or more",
    faults=(("held_experts_term_dropped", {"held_term": False}),
            ("zero_experts_term_dropped", {"zero_term": False}),
            ("shortcut_added_one_sub_block_early", {"shortcut": "early"}),
            ("q_lora_scale_left_out", {"q_lora_scale": False}),
            ("kv_lora_scale_left_out", {"kv_lora_scale": False}),
            ("route_scale_left_out", {"route_scale": 1.0}),
            ("kept_scores_normalised", {"norm_topk_prob": True}),
            ("sigmoid_for_softmax", {"score": "sigmoid"}),
            ("bias_left_out_of_the_selection", {"bias_selects": False}),
            ("rotate_half_for_interleaved", {"rope": "half"})),
    # 4 slots x top-3 pairs a layer, 2 layers of 2 held experts
    counters={"experts_hit": (0, 4), "expert_load_max": (0, SLOTS),
              "held_pairs": (0, 24), "zero_pairs": (0, 24),
              "pairs": (24, 24)},
    # the expert path is a layer scope of its own, a SIBLING of `mlp` (the
    # dense MLPs), so that a profile divides into the two paths
    scopes=frozenset({longcat.SCOPE}),
    nested={"mlp": frozenset({"dense_mlp"}), longcat.SCOPE: EXPERT_PATH,
            "qkv": JOYAI_NESTED["qkv"], "attention": frozenset({"absorb"})},
    reading=frozenset({"kv_gather", "absorb"}),
    paths=(r"/layers/while/body/.*shortcut_experts/experts/",
           r"/layers/while/body/.*shortcut_experts/zero_experts/",
           r"/layers/while/body/.*mlp/dense_mlp/"))


class TestContract(ServeContract):
    family = FAMILY

    def test_the_pools_hold_two_cache_layers_a_layer(self, programs):
        cfg, sm = programs.cfg, programs.sm
        assert sm.sub_blocks == 2 and sm.kv_layers == 2 * cfg.layers == 4
        cache = programs.served("whole", FAMILY.prompts[0]).prefilled
        k = np.asarray(cache.k)
        assert k.shape[0] == 4
        used = programs.blocks[:2]
        # every cache layer holds this prompt's own latent: no two alike
        norms = [np.abs(k[l][used]).sum() for l in range(4)]
        assert min(norms) > 1.0 and len({round(float(n), 3)
                                         for n in norms}) == 4

    def test_a_steps_counters_are_the_held_experts(self, programs):
        stats = programs.served("whole", FAMILY.prompts[0]).stats
        assert stats["held_pairs"].shape == (programs.cfg.layers,)
        facts = programs.sm.step_facts(jax.device_get(stats))
        assert set(facts) == set(FAMILY.counters)
        assert facts["pairs"] == SLOTS * 3 * programs.cfg.layers
        assert facts["held_pairs"] + facts["zero_pairs"] <= facts["pairs"]
        assert facts["experts_hit"] <= 2 * programs.cfg.layers

    def test_a_prompts_counters_come_back_from_the_prefill_program(
            self, programs):
        """After the pools, over every row of the bucket: the padded rows
        are computed too, as a decode step's idle slots are."""
        run, params = programs.compiled("prefill")
        ids = programs.seq[:FAMILY.prompts[0]]
        k, v, _ = programs.fresh()
        out = run(params, programs._padded(ids, FAMILY.bucket),
                  jnp.int32(len(ids)), k, v, jnp.asarray(programs.table))
        assert len(out) == 4
        facts = programs.sm.step_facts(jax.device_get(out[3]))
        assert set(facts) == set(FAMILY.counters)
        assert facts["pairs"] == FAMILY.bucket * 3 * programs.cfg.layers
        assert 0 < facts["held_pairs"] + facts["zero_pairs"] \
            <= facts["pairs"]

    def test_a_prefills_record_carries_the_prompts_counters(self, engine):
        from paddle_tpu.observability import tracing

        with tracing.recorded():
            served_alone(engine, [[1, 2, 3, 4, 5]], 4)
            fills = [s for s in tracing.get_records("decode.steps")
                     if s["kind"] == "prefill"]
        assert len(fills) == 1
        bucket = engine.prefill_buckets[0]
        assert fills[0]["pairs"] == bucket * 3 * 2      # rows x top-3 x layers
        assert set(FAMILY.counters) <= set(fills[0])
        # `status()` keeps the newest DECODE step's facts
        assert engine.status()["step_facts"]["pairs"] == SLOTS * 3 * 2

    @pytest.mark.parametrize("which", PROGRAMS)
    def test_the_two_paths_are_sibling_scopes(self, programs, which):
        for op_name in re.findall(r'op_name="([^"]*)"',
                                  programs.text(which)):
            path = op_name.split("/")[:-1]
            if longcat.SCOPE in path:
                assert "mlp" not in path, op_name

    def test_the_engine_reports_the_share(self, engine):
        assert engine.status()["model"] == {
            "sub_blocks": 2, "router_outputs": 12, "zero_experts": 4,
            "held_experts": [2, 4]}
        assert engine.kv_cfg.layers == 4     # two cache layers a layer


# -- the expert path ----------------------------------------------------------


@functools.cache
def _whole():
    """The tiny model with EVERY routed expert held."""
    cfg = dataclasses.replace(_tiny()[0], held=None)
    return cfg, seeded(longcat, cfg)


def _layer(held=(2, 4), l=0):
    """(cfg, layer l's parameters under sub-block 0's names with the
    experts `held` of the whole layer's, rows)."""
    whole, params = _whole()
    cfg = dataclasses.replace(whole, held=held)
    lp = longcat.sub_params(longcat_ref.layer_of(params, None, l), 0)
    lp.update({k: lp[k][held[0]:held[1]] for k in longcat._EXPERTS})
    y = jax.random.normal(jax.random.key(4), (24, cfg.hidden), jnp.float32)
    return cfg, lp, y


def _pushed(lp, onto, n_outputs=12):
    """`lp` with a bias that puts every row's picks among `onto`."""
    bias = np.zeros((n_outputs,), np.float32)
    bias[list(onto)] = 10.0
    return dict(lp, **{"blk.router_bias": jnp.asarray(bias)})


def test_a_row_whose_picks_are_all_zero_experts_is_scaled_and_no_more():
    cfg, lp, y = _layer()
    lp = _pushed(lp, range(8, 12))
    out, stats = moe.expert_mlp(lp, y, cfg.routing, scope=longcat.SCOPE)
    s = jax.nn.softmax(y @ lp["blk.router"], axis=-1)[:, 8:]
    want = 6.0 * (s.sum(-1) - s.min(-1))[:, None] * y   # the top 3 of 4
    assert np.abs(np.asarray(out - want)).max() < 1e-5
    assert int(stats["zero_pairs"]) == 24 * 3 == int(stats["pairs"])
    assert int(stats["held_pairs"]) == 0 == int(stats["experts_hit"])


def test_a_row_whose_picks_are_all_absent_gets_nothing():
    cfg, lp, y = _layer()
    lp = _pushed(lp, (0, 1, 4, 5, 6, 7))        # routed, held elsewhere
    out, stats = moe.expert_mlp(lp, y, cfg.routing, scope=longcat.SCOPE)
    assert np.array_equal(np.asarray(out), np.zeros_like(y))
    assert int(stats["held_pairs"]) == 0 == int(stats["zero_pairs"])
    assert int(stats["expert_load_max"]) == 0


def test_unwritten_rows_of_the_grouped_matmul_are_selected_away(monkeypatch):
    """On the chip the rows past the last group come back unwritten: a NaN
    there must not reach a row's sum (a zero weight would not stop it)."""
    def unwritten(x, w, sizes):
        out = grouped_matmul(x, w, sizes)
        live = jnp.arange(out.shape[0]) < jnp.sum(sizes)
        return jnp.where(live[:, None], out, jnp.nan)

    cfg, lp, y = _layer()
    want, _ = moe.expert_mlp(lp, y, cfg.routing)
    monkeypatch.setattr(moe, "grouped_matmul", unwritten)
    got, _ = moe.expert_mlp(lp, y, cfg.routing)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("held", [(2, 4), (0, 8), (7, 8)])
def test_held_and_zero_pairs_against_a_count_by_hand(held):
    cfg, lp, y = _layer(held)
    model = dataclasses.asdict(cfg)
    w = np.asarray(longcat_ref.route(lp, y, model))
    picked = w > 0
    assert (picked.sum(-1) == 3).all()
    out, stats = moe.expert_mlp(lp, y, cfg.routing)
    assert int(stats["zero_pairs"]) == picked[:, 8:].sum()
    assert int(stats["held_pairs"]) == picked[:, held[0]:held[1]].sum()
    loads = picked[:, held[0]:held[1]].sum(0)
    assert int(stats["experts_hit"]) == (loads > 0).sum()
    assert int(stats["expert_load_max"]) == loads.max()
    with jax.default_matmul_precision("highest"):
        want = longcat_ref.experts(lp, y, model)
        got, _ = jax.jit(lambda lp, y: moe.expert_mlp(lp, y, cfg.routing))(
            lp, y)
    assert np.abs(np.asarray(got - want)).max() < 2e-5


def test_the_shares_of_a_layer_sum_to_the_whole_layer():
    """Every `held` range in turn, each share's layer computed by the
    MODEL; summed, with what every chip computes alike (the residual
    stream, both attentions, both dense MLPs, the zero-compute experts'
    term) counted once, they are the uncut reference's whole layer."""
    base, _ = _tiny()
    whole = dataclasses.replace(base, held=None)
    x = 0.5 * jax.random.normal(jax.random.key(5), (1, 20, base.hidden),
                                jnp.float32)
    positions = jnp.arange(20, dtype=jnp.int32)[None]
    ranges = [(0, 2), (2, 4), (4, 6), (6, 8)]

    def layer_of(cfg):
        return jax.jit(lambda k: longcat.init_layer(k, cfg, 1))(
            jax.random.key(0))

    full = layer_of(whole)
    with jax.default_matmul_precision("highest"):
        total = 0.0
        for held in ranges:
            cfg = dataclasses.replace(base, held=held)
            lp = layer_of(cfg)
            # a share holds exactly what the whole layer holds at its ids
            for k in longcat._EXPERTS:
                assert np.array_equal(np.asarray(lp[k]),
                                      np.asarray(full[k][held[0]:held[1]]))
            total = total + jax.jit(
                lambda lp, x, cfg=cfg: longcat._block(lp, x, positions, cfg)
            )(lp, x)[0]
        model = dataclasses.asdict(whole)
        alike = longcat_ref.block(full, x[0], dict(model, held_term=False))
        want = longcat_ref.block(full, x[0], model)
    got = total - (len(ranges) - 1) * alike
    assert np.abs(np.asarray(want - alike)).max() > 0.01    # the experts count
    # float32 sums of four shares, on values the experts' gain makes of 10
    assert np.abs(np.asarray(got - want)).max() \
        < 5e-6 * max(1.0, np.abs(np.asarray(want)).max())


@pytest.mark.parametrize("moved,reads", [(8, 0.0), (9, 3.0), (1, 0.0)])
def test_the_verdict_sets_an_eighth_of_the_tokens_aside(moved, reads):
    """A flipped last pick of a held expert is the held term dropped for
    ONE token, so the few worst tokens are not judged; the rest are, by the
    worst of them or sixteen times their mean."""
    gaps = np.zeros(64)
    gaps[:moved] = 3.0
    assert longcat_ref.spared(64) == 8 and longcat_ref.spared(168) == 21
    assert longcat_ref.verdict(gaps) == reads
    # a fault that moves every token a little shows in the mean
    assert longcat_ref.verdict(np.full(64, 0.05)) == pytest.approx(0.8)
    # ... and the order of the gaps is nothing to it
    assert longcat_ref.verdict(gaps[::-1]) == reads


def test_the_two_mla_factors_are_the_ranks_ratios():
    cfg = longcat.LongcatConfig()
    assert cfg.lora_scales == (2.0, math.sqrt(12.0))
    assert joyai.JoyaiConfig().lora_scales == (1.0, 1.0)
    tiny, params = _tiny()
    lp = longcat.sub_params(longcat_ref.layer_of(params, None, 0), 1)
    y = jax.random.normal(jax.random.key(6), (1, 5, tiny.hidden))
    pos = jnp.arange(5)[None]
    _, c, kr = joyai._qkv(lp, y, pos, tiny)
    plain = dataclasses.replace(joyai.JoyaiConfig.tiny(), rms_eps=1e-5,
                                rope_theta=tiny.rope_theta)
    _, c1, kr1 = joyai._qkv(lp, y, pos, plain)
    assert np.allclose(c, c1 * math.sqrt(64 / 32), rtol=1e-6)
    assert np.array_equal(kr, kr1)          # the rotary key is not scaled


def test_the_seeded_router_speaks_and_its_bias_moves_a_few_sets():
    """The init's two stated choices at the published router: logits of
    unit variance (the twelve kept weights sum to about 0.77, not 0.09),
    and a bias that changes the top-12 set of a few percent of the rows."""
    n, H, R = 4000, 256, 768
    k1, k2, k3 = jax.random.split(jax.random.key(11), 3)
    y = jax.random.normal(k1, (n, H))
    logits = y @ (jax.random.normal(k2, (H, R)) * math.sqrt(1.0 / H))
    assert 0.9 < float(logits.std()) < 1.1
    routing = longcat.LongcatConfig().routing
    bias = longcat.BIAS_STD * jax.random.normal(k3, (R,))
    w, e = moe.route(logits, routing, bias)
    _, e0 = moe.route(logits, routing, jnp.zeros((R,)))
    assert 0.6 < float(w.sum(-1).mean()) < 0.95
    moved = (np.sort(e, -1) != np.sort(e0, -1)).any(-1).mean()
    assert 0.01 < moved < 0.12
    assert 0.30 < float((e >= 512).mean()) < 0.37      # 256 of 768


def test_the_seeded_experts_weigh_what_a_rows_routed_pairs_would(monkeypatch):
    """The init's third stated choice: the held experts' `w_down`, and
    nothing else of a layer, carries `EXPERT_GAIN`, so that a pair on a
    held expert (weight 0.04-0.13) adds about a third of a unit row to it
    and dropping the held term moves a logit by more than rounding."""
    cfg, _ = _tiny()
    key = jax.random.key(3)
    gain = longcat.EXPERT_GAIN
    made = longcat.init_layer(key, cfg, 1)
    monkeypatch.setattr(longcat, "EXPERT_GAIN", 1.0)
    plain = longcat.init_layer(key, cfg, 1)
    for name in made:
        ratio = gain if name == "blk.w_down" else 1.0
        assert np.allclose(made[name], ratio * plain[name], rtol=1e-6), name
    # at the published widths: silu(g) * u of unit-variance g, u has RMS
    # 0.6; down at 1/sqrt(2048), the residual scale 1/4, the gain
    H, M, n = 256, 512, 2000
    k1, k2, k3, k4 = jax.random.split(key, 4)
    y = jax.random.normal(k1, (n, H))
    out = longcat_ref._swiglu(
        y, jax.random.normal(k2, (H, M)) / math.sqrt(H),
        jax.random.normal(k3, (H, M)) / math.sqrt(H),
        jax.random.normal(k4, (M, H)) / math.sqrt(M) * 0.25 * gain)
    rms = float(jnp.sqrt((out ** 2).mean()))
    assert 0.13 * gain < rms < 0.17 * gain and 0.2 < 0.064 * rms < 0.4


# -- `expert_mlp` for the models that hold every expert -----------------------


def _expert_mlp_as_before(lp, y, routing):
    """`moe.expert_mlp` as it stood before a router's output could be
    anything but a matrix of the layer (PR 51), one layer, no stack."""
    E, K = routing.n_experts, routing.top_k
    gated = routing.form == "swiglu"
    x = y.reshape(-1, y.shape[-1])
    n = x.shape[0]
    logits = jnp.dot(x, lp["blk.router"].astype(x.dtype),
                     preferred_element_type=jnp.float32)
    weight, expert = moe.route(logits, routing, lp.get("blk.router_bias"))
    expert = expert.reshape(-1)
    order = jnp.argsort(expert, stable=True)
    counts = jnp.zeros((E,), jnp.int32).at[expert].add(1)
    xs = x[order // K]
    w = {k: lp[k].astype(x.dtype) for k in lp if k.startswith("blk.w_")}
    if gated:
        mid = jax.nn.silu(grouped_matmul(xs, w["blk.w_gate"], counts)) \
            * grouped_matmul(xs, w["blk.w_up"], counts)
    else:
        mid = moe.relu2(grouped_matmul(xs, w["blk.w_up"], counts))
    ys = grouped_matmul(mid, w["blk.w_down"], counts)
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(n * K, dtype=order.dtype))
    ys = ys[back].reshape(n, K, -1).astype(jnp.float32)
    out = jnp.sum(ys * weight[..., None], axis=1)
    if routing.shared:
        shared = moe.swiglu(x, lp["blk.shared_gate"], lp["blk.shared_up"],
                            lp["blk.shared_down"]) if gated \
            else moe.relu2_mlp(x, lp["blk.shared_up"], lp["blk.shared_down"])
        out = out + shared.astype(jnp.float32)
    return out.astype(y.dtype).reshape(y.shape), \
        {"experts_hit": jnp.sum(counts > 0).astype(jnp.int32),
         "expert_load_max": jnp.max(counts)}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("cfg", [olmoe.OlmoeConfig.tiny(),
                                 joyai.JoyaiConfig.tiny(),
                                 nemotron_h.NemotronHConfig.tiny()],
                         ids=["olmoe", "joyai", "nemotron"])
def test_a_layer_that_holds_every_expert_is_bit_for_bit_as_before(cfg,
                                                                  dtype):
    routing = cfg.routing
    assert not routing.partial and routing.held_range == (0, cfg.n_experts)
    H, M, E = cfg.hidden, cfg.expert_dim, cfg.n_experts
    keys = iter(jax.random.split(jax.random.key(13), 12))

    def normal(*shape):
        return (jax.random.normal(next(keys), shape)
                / math.sqrt(shape[-2] if len(shape) > 1 else 1.0)
                ).astype(dtype)

    lp = {"blk.router": normal(H, E), "blk.router_bias": 0.1 * normal(E),
          "blk.w_gate": normal(E, H, M), "blk.w_up": normal(E, H, M),
          "blk.w_down": normal(E, M, H), "blk.shared_gate": normal(H, M),
          "blk.shared_up": normal(H, M), "blk.shared_down": normal(M, H)}
    y = normal(3, 7, H)
    for held in (routing, dataclasses.replace(routing, held=(0, E))):
        got, stats = jax.jit(lambda lp, y: moe.expert_mlp(lp, y, held))(
            lp, y)
        want, counted = jax.jit(
            lambda lp, y: _expert_mlp_as_before(lp, y, routing))(lp, y)
        assert np.array_equal(np.asarray(got, np.float32),
                              np.asarray(want, np.float32))
        assert set(stats) == {"experts_hit", "expert_load_max"}
        assert all(int(stats[k]) == int(counted[k]) for k in stats)

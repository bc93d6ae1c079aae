"""models/olmoe.py against the plain reference (benchmarks/reference/
olmoe_ref.py) at a tiny size on the CPU, seeded random weights, float32:
what every served family must do is `tests/serve_contract.py`'s, bound here;
what is OLMoE's own (the routing rule written out, the grouped matmul's
route, the counters' ranges, the train driver) follows it."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import olmoe_ref
from paddle_tpu.models import moe, olmoe
from serve_contract import BS, SLOTS, Family, ServeContract, seeded


@functools.cache
def _tiny():
    cfg = olmoe.OlmoeConfig.tiny()      # hidden 64, 4 heads of 16, 8
    cfg.dtype = "float32"               # experts top-2 of width 32, 2 layers
    return cfg, seeded(olmoe, cfg)


# OLMoE's own parts nest INSIDE the shared names, so that a reduction by
# the innermost of the harness's fixed scopes lands the expert layer under
# `mlp` and RoPE / QK-norm under `qkv`
FAMILY = Family(
    module=olmoe, tiny=_tiny, ref=olmoe_ref,
    gaps=lambda params, model, *a: olmoe_ref.stream_gaps(
        {k: v for k, v in params.items() if not k.startswith("blk.")},
        lambda i: olmoe_ref.layer_of(params, i), model, *a),
    tol=2e-4, tol_why="float32 on both sides: rounding on logits of unit "
                      "scale; every fault moves a logit by 0.1 or more",
    far=500.0,
    # routing and position semantics: a reference that renormalises the
    # kept probabilities, keeps one expert fewer, leaves the QK-norm out or
    # rotates the queries one position late
    faults=(("norm_topk_prob", {"norm_topk_prob": True}),
            ("one_expert_fewer", {"top_k": 1}),
            ("no_qk_norm", {"qk_norm": False}),
            ("rope_one_position_off", {"rope_q_offset": 1})),
    # 4 slots x top-2 pairs a layer, 2 layers
    counters={"experts_hit": (2, 16), "expert_load_max": (1, SLOTS)},
    nested={"mlp": frozenset({"router", "moe_route", "experts"}),
            "qkv": frozenset({"qk_norm", "rope"})},
    # and the norms are `ln`'s, not the QK-norm's: some op sits directly
    # under ln inside the layer loop
    paths=(r"/layers/.*/ln/[^/]+$",))


class TestContract(ServeContract):
    family = FAMILY

    def test_a_steps_counters_are_a_layers_and_within_the_batch(
            self, programs):
        cfg, sm = programs.cfg, programs.sm
        stats = programs.served("whole", FAMILY.prompts[0]).stats
        assert stats["experts_hit"].shape == (cfg.layers,)
        facts = sm.step_facts(jax.device_get(stats))
        assert 2 <= facts["experts_hit"] <= cfg.layers * cfg.top_k * SLOTS
        assert 1 <= facts["expert_load_max"] <= SLOTS

    def test_status_names_the_grouped_matmuls_route_and_tiles(self, engine,
                                                              monkeypatch):
        """`status()["expert_matmul"]`: the route the expert layers' traces
        took (off the chip `ragged_dot`, which has no tiles) and, where the
        kernel ran, its tiles by matrix, in a form JSON carries."""
        import json

        from paddle_tpu.ops.pallas import grouped_matmul as gm

        gm.grouped_matmul(jnp.ones((4, 16)), jnp.ones((2, 16, 8)),
                          jnp.asarray([1, 3], jnp.int32))
        assert engine.kv_cfg.pool_shape == (2, 64, BS, 64)
        got = engine.status()["expert_matmul"]
        assert got["routes"] == dict(gm.GATE_COUNTS) \
            and got["routes"]["xla"] >= 1
        assert "megablox" not in got["routes"] and got["tiles"] == {}
        # what a traced kernel call leaves behind, as `grouped_matmul` does
        monkeypatch.setattr(gm, "TILES", {
            (k, n): gm.tiles(k, n, 2)
            for k, n in [(2688, 1920), (1920, 2688)]})
        got = json.loads(json.dumps(engine.status()))["expert_matmul"]
        assert got["tiles"] == {"1920x2688": [128, 640, 2688],
                                "2688x1920": [128, 896, 1920]}


def test_kept_probabilities_are_not_renormalised():
    """The expert layer alone against the formula written out: the k
    largest probabilities sum to under 1 and weigh their experts as they
    are; dividing by their sum gives another result."""
    cfg, params = _tiny()
    lp = {k: np.asarray(v[0]) for k, v in params.items()
          if k.startswith("blk.")}
    y = np.asarray(jax.random.normal(jax.random.key(2), (6, cfg.hidden)))
    logits = y @ lp["blk.router"]
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    want = np.zeros_like(y)
    kept = np.zeros(len(y))
    for t in range(len(y)):
        for e in np.argsort(probs[t])[-cfg.top_k:]:
            g, u = y[t] @ lp["blk.w_gate"][e], y[t] @ lp["blk.w_up"][e]
            want[t] += probs[t, e] * ((g / (1 + np.exp(-g)) * u)
                                      @ lp["blk.w_down"][e])
            kept[t] += probs[t, e]
    assert kept.max() < 1.0
    with jax.default_matmul_precision("highest"):
        got, stats = moe.expert_mlp(lp, jnp.asarray(y), cfg.routing)
    scale = np.abs(want).max()
    assert np.abs(np.asarray(got) - want).max() < 1e-5 * max(1.0, scale)
    assert np.abs(np.asarray(got) - want / kept[:, None]).max() > 0.1 * scale
    assert 1 <= int(stats["experts_hit"]) <= cfg.n_experts
    assert int(stats["expert_load_max"]) >= 2   # 12 pairs on 8 experts


def test_grouped_matmul_off_the_chip_is_the_groups_own_matmuls():
    """Off the chip the gate picks XLA's ragged_dot; groups without rows
    (a stack of every layer's experts with one layer's rows) cost no row."""
    from paddle_tpu.ops.pallas import grouped_matmul as gm

    x = jax.random.normal(jax.random.key(7), (12, 16), jnp.float32)
    w = jax.random.normal(jax.random.key(8), (6, 16, 8), jnp.float32)
    sizes = np.asarray([0, 5, 0, 3, 4, 0], np.int32)
    gm.GATE_COUNTS.clear()
    got = np.asarray(gm.grouped_matmul(x, w, jnp.asarray(sizes)))
    assert gm.GATE_COUNTS == {"xla": 1}
    start = 0
    for g, n in enumerate(sizes):
        np.testing.assert_allclose(
            got[start:start + n], np.asarray(x[start:start + n] @ w[g]),
            rtol=1e-5, atol=1e-5)
        start += n


def test_lm_loss_falls_through_the_train_driver():
    import optax

    from paddle_tpu.parallel import MeshConfig, make_mesh, mesh_guard
    from paddle_tpu.parallel.train import make_train_step

    cfg, _ = _tiny()
    mesh = make_mesh(MeshConfig(dp=-1), devices=jax.devices()[:1])
    with mesh_guard(mesh):
        params, axes = olmoe.init(jax.random.key(4), cfg)
        init_state, step = make_train_step(
            lambda p, b, r: olmoe.lm_loss(p, cfg, b, r),
            optax.adamw(3e-3), mesh, axes)
        state = init_state(params)
        batch = {"ids": jax.random.randint(jax.random.key(5), (4, 33), 0,
                                           cfg.vocab_size)}
        losses = []
        for _ in range(4):
            state, loss = step(state, batch, jax.random.key(6))
            losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[3] < losses[2] < losses[1] < losses[0]

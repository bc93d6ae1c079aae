"""models/olmoe.py against the plain reference (benchmarks/reference/
olmoe_ref.py) at a tiny size on the CPU, seeded random weights, float32:
the full forward pass, the serve programs through the paged cache, the
engine end to end, and what the comparison tells apart. Tolerances:
float32 on both sides, so 2e-4 on logits of unit scale is rounding; every
fault below moves a logit by 0.1 or more."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import olmoe_ref
from paddle_tpu.models import decoder, moe, olmoe
from paddle_tpu.serving import kv_cache as kvc
from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine

TOL = 2e-4
BS = 8      # block size


@pytest.fixture(scope="module")
def model():
    cfg = olmoe.OlmoeConfig.tiny()      # hidden 64, 4 heads of 16, 8
    cfg.dtype = "float32"               # experts top-2 of width 32, 2 layers
    params, _ = olmoe.init(jax.random.key(0), cfg)
    ref = {"layers": cfg.layers, "heads": cfg.heads, "top_k": cfg.top_k}
    return params, cfg, ref


def _ref_logits(params, ref, ids):
    with jax.default_matmul_precision("highest"):
        return np.asarray(olmoe_ref.logits_rows(
            params, ref, jnp.asarray(ids), 0, len(ids)))


def test_full_forward_matches_the_reference(model):
    params, cfg, ref = model
    ids = np.asarray(jax.random.randint(jax.random.key(1), (2, 40), 0,
                                        cfg.vocab_size))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(olmoe.apply(params, cfg, jnp.asarray(ids)))
    for b in range(2):
        want = _ref_logits(params, ref, ids[b])
        assert want.std() > 0.5                 # logits of unit scale
        assert np.abs(got[b] - want).max() < TOL


@pytest.mark.parametrize("fault, switch", [
    ("norm_topk_prob", {"norm_topk_prob": True}),
    ("one_expert_fewer", {"top_k": 1}),
    ("no_qk_norm", {"qk_norm": False}),
    ("rope_one_position_off", {"rope_q_offset": 1})])
def test_the_comparison_fails_a_wrong_reference(model, fault, switch):
    """Routing and position semantics are pinned: a reference that
    renormalises the kept probabilities, keeps one expert fewer, leaves
    the QK-norm out or rotates the queries one position late is 500
    tolerances away."""
    params, cfg, ref = model
    ids = np.asarray(jax.random.randint(jax.random.key(1), (40,), 0,
                                        cfg.vocab_size))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(olmoe.apply(params, cfg, jnp.asarray(ids)[None]))[0]
    wrong = _ref_logits(params, dict(ref, **switch), ids)
    assert np.abs(got - wrong).max() > 500 * TOL, fault


def test_kept_probabilities_are_not_renormalised(model):
    """The expert layer alone against the formula written out: the k
    largest probabilities sum to under 1 and weigh their experts as they
    are; dividing by their sum gives another result."""
    params, cfg, _ = model
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


# -- the serve programs through the paged cache, on logits ------------------


@pytest.fixture()
def logits_head(monkeypatch):
    """The programs return the head's float32 logits in place of the
    greedy pick."""
    monkeypatch.setattr(decoder, "beam_top1",
                        lambda prev, logits, eos: logits.astype(jnp.float32))


def _pools(cfg, num_blocks=24):
    kv = kvc.KVCacheConfig(layers=cfg.layers, kv_heads=cfg.heads,
                           head_dim=cfg.head_dim, max_len=64, block_size=BS,
                           num_blocks=num_blocks, dtype="float32")
    return kvc.init_pools(kv)


def _table(blocks, width=8):
    return np.asarray(list(blocks) + [0] * (width - len(blocks)), np.int32)


@pytest.mark.parametrize("chunked", [False, True])
def test_prefill_then_decode_matches_the_reference(model, logits_head,
                                                   chunked):
    params, cfg, ref = model
    sm = cfg.serve_model()
    kw = dict(block_size=BS, eos_id=-1)
    seq = np.asarray(jax.random.randint(jax.random.key(3), (30,), 0,
                                        cfg.vocab_size), np.int32)
    want = _ref_logits(params, ref, seq)
    n = 13                                   # prompt length
    kp, vp = _pools(cfg)
    bt = _table([3, 5, 7, 9])
    prefill, prefill_chunk, decode_step = (
        jax.jit(lambda *a, f=f: f(sm, *a, **kw)) for f in (
            decoder.prefill, decoder.prefill_chunk, decoder.decode_step))
    with jax.default_matmul_precision("highest"):
        if chunked:
            for start in (0, 8):
                ids = np.full((1, 8), seq[n - 1], np.int32)
                seg = seq[start:min(start + 8, n)]
                ids[0, :len(seg)] = seg
                row, kp, vp = prefill_chunk(
                    params, ids, np.int32(start), np.int32(n), kp, vp, bt)
        else:
            ids = np.full((1, 16), seq[n - 1], np.int32)
            ids[0, :n] = seq[:n]
            row, kp, vp = prefill(params, ids, np.int32(n), kp, vp, bt)
        assert np.abs(np.asarray(row)[0] - want[n - 1]).max() < TOL
        # teacher-forced decode steps, the sequence in slot 1 of 3
        for t in range(n, len(seq)):
            ids = np.asarray([0, seq[t], 0], np.int32)
            pos = np.asarray([0, t, 0], np.int32)
            bts = np.stack([_table([]), bt, _table([])])
            rows, kp, vp, stats = decode_step(params, ids, pos, kp, vp,
                                              bts)
            assert np.abs(np.asarray(rows)[1] - want[t]).max() < TOL, t
    assert stats["experts_hit"].shape == (cfg.layers,)
    facts = sm.step_facts(jax.device_get(stats))
    assert 2 <= facts["experts_hit"] <= cfg.layers * cfg.top_k * 3
    assert 1 <= facts["expert_load_max"] <= 3


def test_a_rows_logits_do_not_depend_on_its_batch(model, logits_head):
    """Dropless routing: the same row beside different neighbours (other
    tokens, other experts hit, idle slots) gives the same bits, in
    float32 and in bfloat16."""
    params, cfg, _ = model
    kw = dict(block_size=BS, eos_id=-1)
    sm = cfg.serve_model()
    step = jax.jit(lambda p, i, po, k, v, b: decoder.decode_step(
        sm, p, i, po, k, v, b, **kw)[0])
    for dt in ("float32", "bfloat16"):
        p = {k: v.astype(dt) for k, v in params.items()}
        rows = []
        for others in ([0, 0, 0], [17, 400, 3], [255, 1, 99]):
            kp, vp = (a.astype(dt) for a in _pools(cfg))
            ids = np.asarray([others[0], 42, others[1], others[2]], np.int32)
            pos = np.asarray([2, 5, 0, 9], np.int32)
            bts = np.stack([_table([2, 4]), _table([1]), _table([]),
                            _table([6, 8])])
            rows.append(np.asarray(step(p, ids, pos, kp, vp, bts))[1])
        assert np.array_equal(rows[0], rows[1]), dt
        assert np.array_equal(rows[0], rows[2]), dt


# -- the engine end to end --------------------------------------------------


def _engine(model, **kw):
    params, cfg, _ = model
    base = dict(block_size=BS, num_blocks=64, decode_slots=(4,),
                prefill_buckets=(8, 16), precision="f32", max_len=64)
    base.update(kw)
    return DecodeEngine(params, cfg, DecodeConfig(**base))


@pytest.fixture(scope="module")
def engine(model):
    eng = _engine(model)
    eng.warmup()
    yield eng
    eng.stop()


def test_the_engine_serves_olmoe_within_the_reference(model, engine):
    """Prefill then decode through the engine's loop, allocator and pool:
    every generated token is the reference's argmax at its position, or
    within rounding of it."""
    params, cfg, ref = model
    prompts = [[5, 6, 7, 8, 9], list(range(100, 113)), [400, 3]]
    handles = [engine.submit(p, max_new_tokens=12) for p in prompts]
    streams = [h.result(timeout_s=120) for h in handles]
    assert all(len(s) == 12 for s in streams)
    top = {k: v for k, v in params.items() if not k.startswith("blk.")}
    gap, exact = olmoe_ref.stream_gaps(
        top, lambda i: olmoe_ref.layer_of(params, i), ref, prompts,
        streams, 32)
    assert gap < TOL and exact >= 35
    assert engine.kv_cfg.pool_shape == (cfg.layers, 64, BS, cfg.hidden)


def test_chunked_prefill_serves_the_same_tokens(model, engine):
    prompts = [list(range(100, 113)), [5, 6, 7, 8, 9, 10, 11, 12, 13]]
    want = [engine.submit(p, max_new_tokens=10).result(timeout_s=120)
            for p in prompts]
    chunked = _engine(model, prefill_chunk=8)
    try:
        got = [chunked.submit(p, max_new_tokens=10).result(timeout_s=120)
               for p in prompts]
    finally:
        chunked.stop()
    assert got == want


def test_admit_mid_decode_bit_identical(engine):
    """A slot's tokens are the same whether it decodes alone or another
    request joins the running batch: no token is dropped for capacity."""
    solo = engine.submit([1, 2, 3, 4],
                         max_new_tokens=14).result(timeout_s=120)
    a = engine.submit([1, 2, 3, 4], max_new_tokens=14)
    time.sleep(0.02)
    b = engine.submit([9, 9, 200], max_new_tokens=6)
    assert a.result(timeout_s=120) == solo
    assert len(b.result(timeout_s=120)) == 6


def test_step_records_count_the_experts_only_while_recording(model, engine):
    from paddle_tpu.observability import tracing

    cfg = model[1]
    engine.submit([1, 2, 3], max_new_tokens=5).result(timeout_s=120)
    assert engine.status()["step_facts"] is None     # never fetched
    with tracing.recorded():
        engine.submit([1, 2, 3], max_new_tokens=6).result(timeout_s=120)
        steps = [s for s in tracing.get_records("decode.steps")
                 if s["kind"] == "decode"]
    # the counters join a step's record when its tokens are resolved: the
    # step still in flight when the request ended may not have them yet
    assert len(steps) >= 4 and all("experts_hit" in s for s in steps[:-1])
    steps = [s for s in steps if "experts_hit" in s]
    for s in steps:
        # 4 slots x top-2 pairs a layer, 2 layers
        assert 2 <= s["experts_hit"] <= cfg.layers * 4 * cfg.top_k
        assert 1 <= s["expert_load_max"] <= 4
    assert set(engine.status()["step_facts"]) == {"experts_hit",
                                                  "expert_load_max"}


def test_status_names_the_grouped_matmuls_route_and_tiles(engine,
                                                          monkeypatch):
    """`status()["expert_matmul"]`: the route the expert layers' traces
    took (off the chip `ragged_dot`, which has no tiles) and, where the
    kernel ran, its tiles by matrix, in a form JSON carries."""
    import json

    from paddle_tpu.ops.pallas import grouped_matmul as gm

    gm.grouped_matmul(jnp.ones((4, 16)), jnp.ones((2, 16, 8)),
                      jnp.asarray([1, 3], jnp.int32))
    got = engine.status()["expert_matmul"]
    assert got["routes"] == dict(gm.GATE_COUNTS) and got["routes"]["xla"] >= 1
    assert "megablox" not in got["routes"] and got["tiles"] == {}
    # what a traced kernel call leaves behind, as `grouped_matmul` does
    monkeypatch.setattr(gm, "TILES", {
        (k, n): gm.tiles(k, n, 2) for k, n in [(2688, 1920), (1920, 2688)]})
    got = json.loads(json.dumps(engine.status()))["expert_matmul"]
    assert got["tiles"] == {"1920x2688": [128, 640, 2688],
                            "2688x1920": [128, 896, 1920]}


def test_lm_loss_falls_through_the_train_driver(model):
    import optax

    from paddle_tpu.parallel import MeshConfig, make_mesh, mesh_guard
    from paddle_tpu.parallel.train import make_train_step

    _, cfg, _ = model
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

"""models/xing4.py against the plain reference (benchmarks/reference/
xing4_ref.py) at a tiny size on the CPU, seeded random weights, float32: the
full forward pass, the serve programs through the latent paged cache (the
prompt walked in slices, chunks, verified spans, decode steps), the engine
end to end, the residual maps one by one (Sinkhorn's 20 rounds, the sums of
H_res, the counter a step records), YaRN's frequencies and scale against
values computed by hand, and the tie between the new residual hooks and
`ServeModel`'s defaults. Tolerances: float32 on both sides, so 2e-4 on
logits of unit scale is rounding; every fault below moves a logit by 100
times that or more."""

import dataclasses
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import xing4_ref
from paddle_tpu.models import decoder, joyai, xing4
from paddle_tpu.serving import kv_cache as kvc
from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine

TOL = 2e-4
BS = 8      # block size


def _ref_model(cfg):
    return dataclasses.asdict(cfg)      # the reference reads them by name


@pytest.fixture(scope="module")
def model():
    cfg = xing4.Xing4Config.tiny()      # hidden 64 x 4 streams, 4 heads of
    cfg.dtype = "float32"               # 16+8 / 16, latent 32, 2 dense + 2
    params, _ = xing4.init(jax.random.key(0), cfg)  # expert layers, slice 16
    params["blk.router_bias"] = 0.3 * jax.random.normal(
        jax.random.key(9), params["blk.router_bias"].shape, jnp.float32)
    return params, cfg, _ref_model(cfg)


def _ref_logits(params, ref, ids):
    with jax.default_matmul_precision("highest"):
        return np.asarray(xing4_ref.logits_rows(
            params, ref, jnp.asarray(ids), 0, len(ids)))


def test_full_forward_matches_the_reference(model):
    params, cfg, ref = model
    ids = np.asarray(jax.random.randint(jax.random.key(1), (2, 40), 0,
                                        cfg.vocab_size))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(xing4.apply(params, cfg, jnp.asarray(ids)))
    for b in range(2):
        want = _ref_logits(params, ref, ids[b])
        assert want.std() > 0.5                 # logits of unit scale
        assert np.abs(got[b] - want).max() < TOL


@pytest.mark.parametrize("fault, switch", [
    ("h_res_the_identity", {"h_res_identity": True}),
    ("h_post_without_its_2", {"post_gain": 1.0}),
    ("a_stream_dropped_from_the_sum", {"drop_stream": 2}),
    ("plain_frequencies_for_yarn", {"yarn": False}),
    ("scale_without_mscale_squared", {"mscale2": False}),
    ("one_sinkhorn_round", {"sinkhorn_iters": 1}),
    ("shared_expert_dropped", {"shared_expert": False}),
    ("route_scale_left_out", {"route_scale": 1.0})])
def test_the_comparison_fails_a_wrong_reference(model, fault, switch):
    """Each fault of the chip's control run (and two of the shared block's)
    is 100 tolerances away in float32."""
    params, cfg, ref = model
    ids = np.asarray(jax.random.randint(jax.random.key(1), (40,), 0,
                                        cfg.vocab_size))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(xing4.apply(params, cfg, jnp.asarray(ids)[None]))[0]
    wrong = _ref_logits(params, dict(ref, **switch), ids)
    assert np.abs(got - wrong).max() > 100 * TOL, fault


# -- the residual maps -------------------------------------------------------


def _hard_maps(cfg, rows=24):
    """A sub-layer's parameters whose residual logits reach the clamp, so
    that Sinkhorn converges slowly, and carried rows for them."""
    n, width = cfg.hc_mult, cfg.hc_mult * cfg.hidden
    k = iter(jax.random.split(jax.random.key(21), 8))
    hp = {"phi": jax.random.normal(next(k), (2 * n + n * n, width))
          / math.sqrt(width),
          "a": jnp.asarray([1.0, 1.0, 14.0]),
          "b_pre": 0.5 * jax.random.normal(next(k), (n,)),
          "b_post": 0.5 * jax.random.normal(next(k), (n,)),
          # an upper-triangular support: the only doubly stochastic matrix
          # in it is the identity, which Sinkhorn nears like 1/rounds
          "b_res": jnp.triu(jnp.full((n, n), 25.0)) - 12.0}
    x = jax.random.normal(next(k), (rows, width))
    return hp, x


def _ref_maps(hp, x, ref, cfg, **switch):
    lp = {"blk.hc_attn." + k: v for k, v in hp.items()}
    X = x.reshape(x.shape[0], cfg.hc_mult, cfg.hidden)
    with jax.default_matmul_precision("highest"):
        return [np.asarray(m) for m in
                xing4_ref.maps(lp, X, dict(ref, **switch), "attn")]


def test_sinkhorn_runs_all_twenty_rounds(model):
    """The program's H_res equals the reference's 20 rounds to 1e-6 and
    NOT its 19: on logits that reach the clamp a round still moves an entry
    by 1e-3."""
    _, cfg, ref = model
    hp, x = _hard_maps(cfg)
    with jax.default_matmul_precision("highest"):
        pre, post, res, err = jax.jit(
            lambda hp, x: xing4.mhc_maps(hp, x, cfg))(hp, x)
    got = np.moveaxis(np.asarray(res), -1, 0)           # [rows, n, n]
    want_pre, want_post, want = _ref_maps(hp, x, ref, cfg)
    assert np.abs(got - want).max() < 1e-6
    assert np.abs(np.asarray(pre).T - want_pre).max() < 1e-6
    assert np.abs(np.asarray(post).T - want_post).max() < 1e-6
    _, _, short = _ref_maps(hp, x, ref, cfg, sinkhorn_iters=19)
    assert np.abs(got - short).max() > 1e-4
    # rows are exact after the last row pass; the columns say how far the
    # rounds got, and the counter is their largest deviation
    assert np.abs(got.sum(axis=2) - 1.0).max() < 1e-5
    cols = np.abs(got.sum(axis=1) - 1.0).max()
    assert cols > 1e-3 and abs(float(err) - cols) < 1e-6


def test_the_seeded_maps_matter_and_are_doubly_stochastic(model):
    """Over rows, H_pre and H_post vary by tens of percent, H_res lies
    between the identity and the uniform matrix, and 20 rounds take most
    rows' columns to float32's rounding and the slowest row of 256 to
    under a hundredth: what `mhc_col_err` reads is that slowest row."""
    params, cfg, _ = model
    lp = joyai._layer_params(params)
    hp = {k: v[0] for k, v in xing4._hc(lp, "mlp").items()}
    x = xing4.widen(jax.random.normal(jax.random.key(4), (256, cfg.hidden)),
                    cfg.hc_mult) \
        + 0.5 * jax.random.normal(jax.random.key(5),
                                  (256, cfg.hc_mult * cfg.hidden))
    pre, post, res, err = (np.asarray(a)
                           for a in xing4.mhc_maps(hp, x, cfg))
    assert pre.std(axis=1).mean() > 0.1 and 0.2 < pre.mean() < 0.8
    assert post.std(axis=1).mean() > 0.2 and 0.5 < post.mean() < 1.5
    diag = res[np.arange(4), np.arange(4)].mean()
    assert 0.4 < diag < 0.9                 # identity 1, uniform 0.25
    assert res.min() > 0.0
    assert np.abs(res.sum(axis=1) - 1.0).max() < 1e-5
    cols = np.abs(res.sum(axis=0) - 1.0).max(axis=0)    # a row's worst
    assert np.median(cols) < 1e-4 and cols.max() < 0.05
    assert abs(float(err) - cols.max()) < 1e-6


def test_yarn_at_the_published_keys():
    cfg = xing4.Xing4Config()
    inv = cfg.rope_inv_freq
    f = 10000.0 ** (-2.0 * np.arange(32) / 64)
    # d(32) = 64 ln(4096 / (32 x 2 pi)) / (2 ln 10000) = 10.47 -> low 10;
    # d(1) = 64 ln(4096 / (2 pi)) / (2 ln 10000) = 22.51 -> high 23
    assert inv.shape == (32,) and inv.dtype == np.float32
    np.testing.assert_allclose(inv[:11], f[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], f[23:] / 64.0, rtol=1e-6)
    g = 6.0 / 13.0                                      # pair 16
    np.testing.assert_allclose(inv[16], (1 - g) * f[16] + g * f[16] / 64,
                               rtol=1e-6)
    assert abs(inv[16] - 0.00545673) < 1e-7     # 0.01 x (7/13 + 6/(13 x 64))
    # m = 0.1 ln 64 + 1 = 1.415888; the scale m^2 / sqrt(192)
    assert abs(cfg.softmax_scale - 2.0047397 / math.sqrt(192)) < 1e-8
    assert joyai.JoyaiConfig().softmax_scale == 1.0 / math.sqrt(192)
    assert joyai.JoyaiConfig().rope_inv_freq is None
    ref_inv, ref_scale = xing4_ref.yarn({
        "rope_dim": 64, "nope_dim": 128, "rope_theta": 10000,
        "rope_factor": 64, "rope_orig_len": 4096})
    np.testing.assert_allclose(np.asarray(ref_inv), inv, rtol=1e-6)
    assert abs(ref_scale - cfg.softmax_scale) < 1e-9
    with pytest.raises(ValueError, match="mscale"):
        xing4.Xing4Config(rope_mscale=0.5)


# -- the serve programs through the latent paged cache, on logits -----------


@pytest.fixture()
def logits_head(monkeypatch):
    monkeypatch.setattr(decoder, "beam_top1",
                        lambda prev, logits, eos: logits.astype(jnp.float32))


def _pools(cfg, num_blocks=24):
    sm = cfg.serve_model()
    return kvc.init_pools(kvc.KVCacheConfig(
        layers=sm.layers, kv_heads=sm.kv_heads, head_dim=sm.head_dim,
        max_len=64, block_size=BS, num_blocks=num_blocks, dtype="float32",
        widths=sm.stored))


def _table(blocks, width=8):
    return np.asarray(list(blocks) + [0] * (width - len(blocks)), np.int32)


@pytest.mark.parametrize("n, slices", [(13, 1), (29, 2), (41, 3)])
def test_sliced_prefill_then_decode_matches_the_reference(model,
                                                          logits_head, n,
                                                          slices):
    """A prompt walked in 1, 2 and 3 slices of 16 inside one 48-token
    prefill program (each slice's `c` and rotary key written, then its
    queries over the cache so far), then decode steps through the latent
    pool, against the reference's full forward pass; the carried state is
    `[4 x 64]` a row all the way."""
    params, cfg, ref = model
    sm = cfg.serve_model()
    assert sm.prompt_slice == 16 and -(-n // 16) == slices
    kw = dict(block_size=BS, eos_id=-1)
    seq = np.asarray(jax.random.randint(jax.random.key(3), (46,), 0,
                                        cfg.vocab_size), np.int32)
    want = _ref_logits(params, ref, seq)
    kp, vp = _pools(cfg)
    bt = _table([3, 5, 7, 9, 11, 13])
    prefill, decode_step = (
        jax.jit(lambda *a, f=f: f(sm, *a, **kw))
        for f in (decoder.prefill, decoder.decode_step))
    with jax.default_matmul_precision("highest"):
        ids = np.full((1, 48), seq[n - 1], np.int32)
        ids[0, :n] = seq[:n]
        row, kp, vp = prefill(params, ids, np.int32(n), kp, vp, bt)
        assert np.abs(np.asarray(row)[0] - want[n - 1]).max() < TOL
        for t in range(n, min(n + 5, len(seq))):
            ids = np.asarray([0, seq[t], 0], np.int32)
            pos = np.asarray([0, t, 0], np.int32)
            bts = np.stack([_table([]), bt, _table([])])
            rows, kp, vp, stats = decode_step(params, ids, pos, kp, vp, bts)
            assert np.abs(np.asarray(rows)[1] - want[t]).max() < TOL, t
    # the leading dense layers count their maps too, beside the stack
    assert len(stats["lead"]) == cfg.dense_layers
    assert stats["stack"]["mhc_col_err"].shape == (cfg.expert_layers,)
    facts = sm.step_facts(jax.device_get(stats))
    assert set(facts) == {"mhc_col_err", "experts_hit", "expert_load_max"}
    assert 0.0 <= facts["mhc_col_err"] < 0.05


def test_chunks_and_verified_spans_match_the_reference(model, monkeypatch):
    """`prefill_chunk` (slices of the synchronous loop) and `verify_step`
    (W tokens a slot) carry the four streams as the other programs do:
    compared on each row's largest logit."""
    monkeypatch.setattr(
        decoder, "beam_top1",
        lambda prev, logits, eos: logits.astype(jnp.float32).max(-1))
    params, cfg, ref = model
    sm = cfg.serve_model()
    kw = dict(block_size=BS, eos_id=-1)
    seq = np.asarray(jax.random.randint(jax.random.key(11), (24,), 0,
                                        cfg.vocab_size), np.int32)
    want = _ref_logits(params, ref, seq).max(-1)
    bt = jnp.asarray(_table([3, 6, 8]))
    kp, vp = _pools(cfg)
    with jax.default_matmul_precision("highest"):
        for start in (0, 8, 16):
            ids = np.full((1, 8), seq[19], np.int32)
            seg = seq[start:min(start + 8, 20)]
            ids[0, :len(seg)] = seg
            row, kp, vp = decoder.prefill_chunk(
                sm, params, ids, np.int32(start), np.int32(20), kp, vp, bt,
                **kw)
        assert abs(float(row[0]) - want[19]) < TOL
        bts = jnp.stack([bt, jnp.asarray(_table([]))])
        span, _, _ = decoder.verify_step(
            sm, params, np.stack([seq[20:24], np.zeros(4, np.int32)]),
            np.asarray([20, 0], np.int32), kp, vp, bts, **kw)
    for j in range(4):
        assert abs(float(span[0, j]) - want[20 + j]) < TOL, j


def test_one_stream_with_identity_maps_is_the_plain_block(model, monkeypatch):
    """`hc_mult` 1 with H_pre = H_post = H_res = 1 through the model's
    hooks gives, bit for bit, what `ServeModel`'s default residual path
    gives the same block: the hooks' defaults and the new path are the same
    arithmetic."""
    cfg = xing4.Xing4Config.tiny()
    cfg.dtype, cfg.hc_mult = "float32", 1
    params, _ = xing4.init(jax.random.key(2), cfg)

    def identity(hp, x, cfg):
        lead = x.shape[:-1]
        return (jnp.ones((1,) + lead), jnp.ones((1,) + lead),
                jnp.ones((1, 1) + lead), jnp.float32(0.0))

    monkeypatch.setattr(xing4, "mhc_maps", identity)
    monkeypatch.setattr(decoder, "beam_top1",
                        lambda prev, logits, eos: logits.astype(jnp.float32))

    class Plain(joyai.JoyaiServe):      # the same block, default hooks
        prompt_slice = 16

    kw = dict(block_size=BS, eos_id=-1)
    ids = np.asarray(jax.random.randint(jax.random.key(6), (1, 32), 0,
                                        cfg.vocab_size), np.int32)
    bt = _table([2, 4, 6, 8])
    out = []
    for sm in (xing4.Xing4Serve(cfg), Plain(cfg)):
        row, kp, vp = decoder.prefill(sm, params, ids, np.int32(27),
                                      *_pools(cfg), bt, **kw)
        step = decoder.decode_step(
            sm, params, np.asarray([7, 0], np.int32),
            np.asarray([27, 0], np.int32), kp, vp,
            np.stack([bt, _table([])]), **kw)
        out.append([np.asarray(a) for a in (row, step[0], kp, vp)])
    for a, b in zip(*out):
        assert np.array_equal(a, b)


def test_the_model_is_imported_only_where_it_is_built():
    """No other cell's boot pays for this module: importing the serving
    package and the models package does not import it (nor its family's
    adapter)."""
    code = ("import sys, paddle_tpu.serving, paddle_tpu.models, "
            "paddle_tpu.models.decoder, paddle_tpu.models.joyai, "
            "benchmarks.harness.manifest; "
            "bad = [m for m in sys.modules if 'xing4' in m]; "
            "sys.exit(1 if bad else 0)")
    assert subprocess.run([sys.executable, "-c", code], timeout=300,
                          env={**os.environ,
                               "JAX_PLATFORMS": "cpu"}).returncode == 0


# -- the engine ---------------------------------------------------------------


@pytest.fixture(scope="module")
def engine(model):
    params, cfg, _ = model
    eng = DecodeEngine(params, cfg, DecodeConfig(
        block_size=BS, num_blocks=64, decode_slots=(4,),
        prefill_buckets=(16, 32), precision="f32", max_len=64))
    eng.warmup()
    yield eng
    eng.stop()


def test_the_engine_serves_the_model_within_the_reference(model, engine):
    from paddle_tpu.observability import tracing

    params, cfg, ref = model
    prompts = [[5, 6, 7, 8, 9], list(range(100, 121)), [400, 3]]
    with tracing.recorded():
        handles = [engine.submit(p, max_new_tokens=12) for p in prompts]
        streams = [h.result(timeout_s=120) for h in handles]
        steps = [s for s in tracing.get_records("decode.steps")
                 if s["kind"] == "decode"]
    assert all(len(s) == 12 for s in streams)
    top = {k: v for k, v in params.items()
           if not k.startswith(("blk.", "dense."))}
    gap, exact = xing4_ref.stream_gaps(
        top, lambda i: xing4_ref.layer_of(params, ref, i), ref, prompts,
        streams, 64)
    assert gap < TOL and exact >= 35
    # every step record of this model carries the maps' counter beside the
    # experts'
    assert len(steps) >= 3
    for s in steps:
        assert 0.0 <= s["mhc_col_err"] < 0.05
        assert 2 <= s["experts_hit"] <= cfg.expert_layers * 4 * cfg.top_k
    status = engine.status()
    assert status["model"] == {"residual_streams": 4, "sinkhorn_iters": 20,
                               "carried_lanes": 256}
    assert status["kv"]["entry_widths"] == [cfg.kv_rank, 128]


def test_admit_mid_decode_bit_identical(engine):
    import time

    solo = engine.submit([1, 2, 3, 4],
                         max_new_tokens=14).result(timeout_s=120)
    a = engine.submit([1, 2, 3, 4], max_new_tokens=14)
    time.sleep(0.02)
    b = engine.submit([9, 9, 200], max_new_tokens=6)
    assert a.result(timeout_s=120) == solo
    assert len(b.result(timeout_s=120)) == 6

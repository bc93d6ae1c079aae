"""models/xing4.py against the plain reference (benchmarks/reference/
xing4_ref.py) at a tiny size on the CPU, seeded random weights, float32:
what every served family must do is `tests/serve_contract.py`'s, bound here
(the prompt walked in 1, 2 and 3 slices of 16 inside one 48-token prefill
program, chunks, verified spans, decode steps, all through the latent paged
cache with the carried state `[4 x 64]` a row); what is Xing4's own follows
it: the residual maps one by one (Sinkhorn's 20 rounds, the sums of H_res,
the counter a step records), YaRN's frequencies and scale against values
computed by hand, and the tie between the new residual hooks and
`ServeModel`'s defaults."""

import dataclasses
import functools
import math
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import xing4_ref
from paddle_tpu.models import decoder, joyai, xing4
from serve_contract import (PROGRAMS, SLOTS, Family, ServeContract, pools,
                            program, seeded, table)
from test_joyai import NESTED as JOYAI_NESTED


@functools.cache
def _tiny():
    cfg = xing4.Xing4Config.tiny()      # hidden 64 x 4 streams, 4 heads of
    cfg.dtype = "float32"               # 16+8 / 16, latent 32, 2 dense + 2
    params = seeded(xing4, cfg)         # expert layers, slice 16
    params["blk.router_bias"] = 0.3 * jax.random.normal(
        jax.random.key(9), params["blk.router_bias"].shape, jnp.float32)
    return cfg, params


FAMILY = Family(
    module=xing4, tiny=_tiny, ref=xing4_ref,    # which reads them by name
    tol=2e-4, tol_why="float32 on both sides: rounding on logits of unit "
                      "scale; each fault of the chip's control run (and "
                      "two of the shared block's) moves a logit by 100 "
                      "times that or more",
    faults=(("h_res_the_identity", {"h_res_identity": True}),
            ("h_post_without_its_2", {"post_gain": 1.0}),
            ("a_stream_dropped_from_the_sum", {"drop_stream": 2}),
            ("plain_frequencies_for_yarn", {"yarn": False}),
            ("scale_without_mscale_squared", {"mscale2": False}),
            ("one_sinkhorn_round", {"sinkhorn_iters": 1}),
            ("shared_expert_dropped", {"shared_expert": False}),
            ("route_scale_left_out", {"route_scale": 1.0})),
    # 1, 2 and 3 slices of 16 inside one 48-token prefill program
    prompts=(13, 29, 41), total=46, bucket=48,
    engine=dict(prefill_buckets=(16, 32)),
    engine_prompts=([5, 6, 7, 8, 9], list(range(100, 121)), [400, 3]),
    # every step record of this model carries the maps' counter beside the
    # experts'
    # (4 slots x top-2 pairs a layer, 2 expert layers)
    counters={"mhc_col_err": (0.0, 0.05), "experts_hit": (2, 16),
              "expert_load_max": (1, SLOTS)},
    # the residual path is a layer scope of its own: `mhc`, a SIBLING of
    # `ln` / `qkv` / `attention` / `proj` / `mlp` (whose seconds existing
    # readers divide by), holding `mhc_map`, `mhc_pre` and `mhc_post`; the
    # block inside it is the latent model's, scope for scope
    scopes=frozenset({"mhc"}),
    nested=dict(JOYAI_NESTED,
                mhc=frozenset({"mhc_map", "mhc_pre", "mhc_post"})),
    reading=frozenset({"kv_gather", "absorb"}),
    paths=(r"/layers/mhc/mhc_map/", r"/layers/while/body/.*mhc/mhc_post/"))


class TestContract(ServeContract):
    family = FAMILY

    def test_the_walk_is_in_slices_and_counts_its_maps(self, programs):
        cfg, sm = programs.cfg, programs.sm
        assert sm.prompt_slice == 16
        assert [-(-n // 16) for n in FAMILY.prompts] == [1, 2, 3]
        stats = programs.served("whole", FAMILY.prompts[0]).stats
        # the leading dense layers count their maps too, beside the stack
        assert len(stats["lead"]) == cfg.dense_layers
        assert stats["stack"]["mhc_col_err"].shape == (cfg.expert_layers,)
        facts = sm.step_facts(jax.device_get(stats))
        assert set(facts) == set(FAMILY.counters)
        assert 0.0 <= facts["mhc_col_err"] < 0.05

    @pytest.mark.parametrize("which", PROGRAMS)
    def test_mhc_lies_in_no_other_layer_scope_nor_another_in_it(
            self, programs, which):
        siblings = {"ln", "qkv", "attention", "proj", "mlp"}
        for op_name in re.findall(r'op_name="([^"]*)"',
                                  programs.text(which)):
            path = op_name.split("/")[:-1]
            if "mhc" in path:
                assert not siblings & set(path), op_name

    def test_the_engine_reports_the_residual_path(self, engine):
        assert engine.status()["model"] == {"residual_streams": 4,
                                            "sinkhorn_iters": 20,
                                            "carried_lanes": 256}


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


def test_sinkhorn_runs_all_twenty_rounds():
    """The program's H_res equals the reference's 20 rounds to 1e-6 and
    NOT its 19: on logits that reach the clamp a round still moves an entry
    by 1e-3."""
    cfg, _ = _tiny()
    ref = dataclasses.asdict(cfg)
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


def test_the_seeded_maps_matter_and_are_doubly_stochastic():
    """Over rows, H_pre and H_post vary by tens of percent, H_res lies
    between the identity and the uniform matrix, and 20 rounds take most
    rows' columns to float32's rounding and the slowest row of 256 to
    under a hundredth: what `mhc_col_err` reads is that slowest row."""
    cfg, params = _tiny()
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


def test_one_stream_with_identity_maps_is_the_plain_block(monkeypatch):
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

    class Plain(joyai.JoyaiServe):      # the same block, default hooks
        prompt_slice = 16

    ids = jax.random.randint(jax.random.key(6), (1, 32), 0, cfg.vocab_size,
                             jnp.int32)
    bt = table([2, 4, 6, 8], 8)
    out = []
    for sm in (xing4.Xing4Serve(cfg), Plain(cfg)):
        _, (kp, vp), _ = pools(sm, 24, 64)
        fill = (params, ids, jnp.int32(27), kp, vp, jnp.asarray(bt))
        row, kp, vp = program(sm, decoder.prefill, *fill)(*fill)
        args = (params, jnp.asarray([7, 0], jnp.int32),
                jnp.asarray([27, 0], jnp.int32), kp, vp,
                jnp.asarray(np.stack([bt, table([], 8)])))
        step = program(sm, decoder.decode_step, *args)(*args)
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

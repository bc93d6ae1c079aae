"""Trinity (models/afmoe.py) at a tiny size on the CPU: a window of 32 keys
under contexts of 100 and more. What every served family must do is
`tests/serve_contract.py`'s, bound here against the benchmark's plain float32
reference (benchmarks/reference/afmoe_ref.py: no cache, no ring, no slices,
every held expert for every token); what is this model's own follows it: the
two kinds of pool (a ring reused, a short sequence beside long ones, a padded
bucket's tail), a prompt in slices against one slice, the published router
against `moe.Routing`, the two shares of a layer against the uncut layer, and
what the engine refuses at boot. The windowed walk's kernel is
tests/test_paged_attention.py's, what the interpreter cannot see
tests/test_tpu_aot_compile.py's (`-k trinity`)."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import afmoe_ref as ref_mod
from paddle_tpu.models import afmoe, decoder, moe
from paddle_tpu.observability import tracing
from paddle_tpu.serving import kv_cache as kvc
from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine
from serve_contract import (BS, ENGINE, ROW, Family, ServeContract, boot,
                            program, seeded, served_alone)

Q = 16      # the reference's query block: a slice of the tiny model


@functools.cache
def _tiny():
    cfg = afmoe.AfmoeConfig.tiny()
    cfg.dtype = "float32"
    return cfg, seeded(afmoe, cfg, 3)


def _model(cfg):
    return dict(dataclasses.asdict(cfg), q_block=Q, block=BS)


def _logits(params, model, ids):
    """Every position's logits; the reference takes whole query blocks."""
    padded = np.zeros((-(-len(ids) // Q) * Q,), np.int32)
    padded[:len(ids)] = ids
    return ref_mod.logits_rows(params, model, jnp.asarray(padded), 0,
                               len(ids), prompt_len=model.get("prompt_len"))


FAMILY = Family(
    module=afmoe, tiny=_tiny, ref=ref_mod, ref_model=_model, logits=_logits,
    tol=2e-4, tol_why="float32 on both sides, logits of deviation 1 (a "
                      "normed row through a head drawn at 1/sqrt(hidden)): "
                      "the two sides agree to 2e-5 through 8 sub-layers of "
                      "norms, and every rule of the layer, changed in the "
                      "REFERENCE, moves the logits by ten tolerances or more",
    far=10.0,
    faults=(("window-ignored", {"window_all": True}),
            ("window-halved", {"window": 16}),
            ("window-doubled", {"window": 64}),
            ("window-one-key-short", {"window": 31}),
            ("window-one-key-long", {"window": 33}),
            ("rotary-on-full-layers", {"rope_full": True}),
            ("rotary-dropped", {"rope_sliding": False}),
            ("output-gate-dropped", {"output_gate": False}),
            ("qk-norm-dropped", {"qk_norm": False}),
            ("post-norms-dropped", {"post_norms": False}),
            ("embedding-multiplier-dropped", {"mup_enabled": False}),
            ("route-scale-1", {"route_scale": 1.0}),
            ("kept-weights-not-normalised", {"norm_topk": False}),
            ("pick-without-the-bias", {"bias_selects": False}, 0.0),
            ("shared-expert-dropped", {"shared_expert": False}),
            ("held-term-dropped", {"held_term": False}),
            ("ring-two-blocks-short", {"ring_short": 2, "prompt_len": 60}),
            ("padded-tail-on-a-key", {"pad_tail": 3, "prompt_len": 60}),
            ("stale-ring-read", {"stale_ring": 4})),
    prompts=(13, 60), total=110, bucket=64,
    engine=dict(num_blocks=65, prefill_buckets=(16, 32, 64), max_len=128),
    engine_prompts=tuple(
        np.random.default_rng(n).integers(0, 512, n).tolist()
        for n in (5, 40, 64)),
    tight=(dict(block_size=4, num_blocks=12, decode_slots=(2,),
                prefill_buckets=(8, 48), max_len=48),
           ([1, 2, 3, 4], [5, 6, 7]), 24),
    # 3 expert layers of 4 held experts (and a dense one that counts
    # nothing); 4 slots x top-3
    counters={"experts_hit": (0, 12), "expert_load_max": (0, 4),
              "held_pairs": (0, 36), "zero_pairs": (0, 0),
              "pairs": (36, 36)},
    scopes=frozenset({"window_attention", "qk_norm", "rope", "router",
                      "moe_route", "experts", "shared_expert"}))


class TestContract(ServeContract):
    family = FAMILY

    def test_the_engine_reports_both_cache_kinds(self, engine):
        served_alone(engine, [[5, 6, 7]], 3)
        status = engine.status()
        assert status["model"]["blocks"] == "WEWE*EWE"
        assert status["model"]["held_experts"] == [0, 4]
        kinds = status["kv"]["kinds"]
        assert kinds["global"]["layers"] == 1
        assert kinds["window"]["layers"] == 3
        assert kinds["window"]["window"] == 32
        # (32 + 16) / 8 + 1 blocks a ring, a ring a slot
        assert kinds["window"]["ring_blocks"] == 7
        assert kinds["window"]["blocks_total"] == 4 * 7
        assert kinds["window"]["blocks_used"] == 0
        assert status["kv"]["pool_bytes"] == kinds["global"]["pool_bytes"] \
            + kinds["window"]["pool_bytes"]
        assert status["decode_attention"].get("gather_window")

    def test_step_records_speak_by_kind(self, engine):
        with tracing.recorded():
            served_alone(engine, [list(range(1, 41))], 30)
            steps = [s for s in tracing.get_records("decode.steps")
                     if s["kind"] == "decode"]
            spans = [s for s in tracing.get_spans()
                     if s.name == "decode.dispatch"]
        assert steps and spans
        for s in steps:
            assert s["window_blocks_usable"] == 28
            assert s["window_tokens"] == min(s["live_tokens"], 32)
            # the global kind keeps every token, the window kind a ring
            assert s["blocks_used"] == -(-s["live_tokens"] // BS)
            assert s["window_blocks_used"] == min(s["blocks_used"], 7)
        assert max(s["live_tokens"] for s in steps) > 56   # past the ring
        assert "window_tokens" in spans[-1].args

    def test_a_ring_just_freed_and_a_short_sequence_beside_long_ones(
            self, engine):
        """A sequence served in a ring another has just freed, and one
        admitted beside longer ones, get the tokens they get alone, to the
        bit: nothing of a ring's last holder is read, and a sequence under
        the window holds its own length."""
        rng = np.random.default_rng(7)
        long_a, long_b = (rng.integers(0, 512, n).tolist() for n in (60, 45))
        short = [9, 9, 200, 17, 5]
        solo_a, = served_alone(engine, [long_a], 40)
        solo_short, = served_alone(engine, [short], 12)
        # fill every ring past its wrap, let them go, take them again
        others = [engine.submit(rng.integers(0, 512, 50).tolist(),
                                max_new_tokens=30) for _ in range(4)]
        for h in others:
            h.result(timeout_s=300)
        a = engine.submit(long_a, max_new_tokens=40)
        b = engine.submit(long_b, max_new_tokens=40)
        s = engine.submit(short, max_new_tokens=12)
        assert s.result(timeout_s=300) == solo_short
        assert a.result(timeout_s=300) == solo_a
        b.result(timeout_s=300)
        kinds = engine.status()["kv"]["kinds"]
        assert kinds["window"]["blocks_used"] == 0
        assert kinds["global"]["blocks_used"] == 0

    def test_a_padded_bucket_tail_costs_no_key(self, programs, engine):
        """A prompt of 33 tokens goes through the bucket of 64: its last
        slice's padded tail is written into the ring, 15 positions past the
        newest token, and the tokens are the reference's all the same (the
        ring holds the window AND a slice)."""
        prompt = np.random.default_rng(33).integers(0, 512, 33).tolist()
        stream, = served_alone(engine, [prompt], 40)
        gap, exact = FAMILY.reference_gaps(
            programs.params, programs.model, [prompt], [stream], 128)
        assert gap < FAMILY.tol and exact >= 39

    def test_a_prompt_in_slices_leaves_what_one_slice_leaves(self, programs):
        """The contract's prefill walks its bucket of 64 in slices of 16
        through a ring of 7 blocks; the same prompt in ONE slice (a model
        whose slice covers the bucket, and a ring to match) gives the same
        logits."""
        ids = programs.seq[:60]
        sliced_row, _ = programs.prefill(ids, programs.fresh())
        cfg = dataclasses.replace(programs.cfg, prompt_slice=64)
        sm = cfg.serve_model()
        ring = kvc.ring_blocks(sm.window, sm.prompt_slice, BS)
        kv = kvc.KVCacheConfig(layers=sm.window_layers, widths=sm.stored,
                               max_len=programs.width * BS, block_size=BS,
                               num_blocks=ring + 1, dtype="float32")
        fresh = programs.fresh()
        state = fresh.state[:-2] + kvc.init_pools(kv)
        wtable = kvc.window_table(list(range(1, ring + 1)), ring,
                                  programs.width)
        args = (programs.params, programs._padded(ids, 64), jnp.int32(60),
                fresh.k, fresh.v, jnp.asarray(programs.table), state,
                jnp.int32(ROW), jnp.asarray(wtable))
        row, *_ = program(sm, decoder.prefill, *args)(*args)
        assert np.abs(np.asarray(row)[0] - sliced_row).max() < FAMILY.tol


def test_the_engine_refuses_what_a_ring_cannot_give():
    cfg, params = _tiny()
    for over, why in ((dict(prefix_cache=True, prefill_chunk=8),
                       "prefix_cache"),
                      (dict(prefill_chunk=8), "prefill_chunk")):
        with pytest.raises(ValueError, match="window kind") as e:
            boot(FAMILY, params, cfg, **over)
        assert why in str(e.value)
    with pytest.raises(ValueError, match="window kind") as e:
        DecodeEngine(params, cfg, DecodeConfig(
            **{**ENGINE, **FAMILY.engine, "spec_k": 2}), draft=(params, cfg))
    assert "spec_k" in str(e.value)
    whole = dataclasses.replace(cfg, prompt_slice=None)
    with pytest.raises(ValueError, match="slices"):
        boot(FAMILY, params, whole)


def test_the_published_router_is_the_shared_routing_rule():
    """`modeling_afmoe.py`'s router, written out (sigmoid scores, the top-k
    of score + bias, the scores gathered, divided by their sum + 1e-20,
    times `route_scale`), equals `moe.route` under `AfmoeConfig.routing`."""
    cfg, _ = _tiny()
    key = jax.random.key(5)
    logits = jax.random.normal(key, (64, cfg.n_experts), jnp.float32) * 2
    bias = jax.random.normal(jax.random.fold_in(key, 1), (cfg.n_experts,),
                             jnp.float32) * 0.3
    scores = jax.nn.sigmoid(logits)
    _, picked = jax.lax.top_k(scores + bias, cfg.top_k)
    w = jnp.take_along_axis(scores, picked, axis=1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * cfg.route_scale
    got_w, got_e = moe.route(logits, cfg.routing, bias)
    assert np.array_equal(np.asarray(picked), np.asarray(got_e))
    np.testing.assert_allclose(np.asarray(got_w), np.asarray(w), rtol=1e-6)
    # and the reference's dense form of the same rule
    dense = ref_mod.route(scores, bias, _model(cfg))
    want = np.zeros(dense.shape, np.float32)
    np.put_along_axis(want, np.asarray(picked), np.asarray(w), axis=1)
    np.testing.assert_allclose(np.asarray(dense), want, rtol=1e-6)


def test_the_two_shares_of_a_layer_sum_to_the_uncut_layer():
    """`held` (0, 4) and (4, 8) of an expert layer, the shared expert
    counted once, are the uncut reference's layer: expert e is drawn from a
    key of its own id."""
    cfg, _ = _tiny()
    b = 2 * cfg.dense_layers + 1        # the first expert block
    whole = dataclasses.replace(cfg, held=None)
    key = jax.random.key(11)
    y = jax.random.normal(jax.random.fold_in(key, 9), (24, cfg.hidden),
                          jnp.float32)
    uncut = afmoe.init_layer(key, whole, b)
    with jax.default_matmul_precision("highest"):
        want = ref_mod.experts(uncut, y, _model(whole))
        shared = ref_mod._swiglu(y, uncut["blk.shared_gate"],
                                 uncut["blk.shared_up"],
                                 uncut["blk.shared_down"])
        total = -shared
        for held in ((0, 4), (4, 8)):
            share = dataclasses.replace(cfg, held=held)
            lp = afmoe.init_layer(key, share, b)
            np.testing.assert_array_equal(
                lp["blk.w_up"], uncut["blk.w_up"][held[0]:held[1]])
            out, stats = moe.expert_mlp(lp, y, share.routing)
            total = total + out
    assert int(stats["pairs"]) == 24 * cfg.top_k
    assert np.abs(np.asarray(total - want)).max() < 1e-5
    assert np.abs(np.asarray(want)).max() > 0.1

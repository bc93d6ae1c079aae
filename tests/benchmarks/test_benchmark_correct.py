"""What `correct` can tell apart, at the published widths on the CPU: the
comparisons of benchmarks/kinds/*.py with the tolerances of the
configuration files pass the program's own precision and fail a dropped
layer and a wrong position row. Precision INSIDE bf16 (LayerNorm statistics
in bf16) is not told apart from the bf16 the program computes in by design:
the configuration files say so, and the last case pins it."""

import json
import os

import numpy as np
import pytest

from benchmarks.families import bert as bert_family
from benchmarks.families import gpt as gpt_family
from benchmarks.harness import manifest, traffic
from benchmarks.reference import bert_ref, gpt_ref

SEED = 3000000011


def _config(name):
    with open(os.path.join(manifest.BENCH_DIR, "configs", name)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bert():
    config = _config("bert_base.json")
    model = config["model"]
    cfg = bert_family.make_config(model)
    params, _ = bert_family.init(cfg, SEED)
    mix = {"seq_len": 128, "mask_rate": 0.15}
    batch = next(bert_family.host_batches(model, mix, 8, SEED))
    want = bert_ref.encode_f32(params, model, batch["input_ids"],
                               batch["token_type_ids"])
    return config, cfg, params, batch, want


def _rel(got, want):
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def test_bert_program_precision_passes_both_tolerances(bert):
    config, cfg, params, batch, _ = bert
    model = config["model"]
    assert bert_family.forward_gap(params, cfg, model, batch, 8) \
        <= config["forward_rel_tol"] / 2
    import jax

    loss = bert_family.loss_fn(cfg)
    got = float(jax.jit(lambda p, b: loss(p, b, None, True))(params, batch))
    want = bert_family.reference_loss(params, model, batch, 8)
    assert abs(got - want) / want <= config["loss_rel_tol"] / 2


@pytest.mark.parametrize("fault", ["dropped_layer", "shifted_positions"])
def test_bert_forward_tolerance_fails_a_fault(bert, fault):
    config, _, params, batch, want = bert
    model, p = config["model"], dict(params)
    if fault == "dropped_layer":
        model = dict(model, layers=model["layers"] - 1)
    else:
        p["embeddings.position.w"] = np.roll(
            np.asarray(p["embeddings.position.w"]), 1, axis=0)
    got = bert_ref.encode_f32(p, model, batch["input_ids"],
                              batch["token_type_ids"])
    assert _rel(got, want) > 2 * config["forward_rel_tol"]


def test_bert_bf16_layernorm_is_not_told_apart(bert, monkeypatch):
    import jax.numpy as jnp

    config, _, params, batch, want = bert

    def ln16(p, name, x, eps=1e-12):
        x = x.astype(jnp.bfloat16)
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        y = (x - mu) / jnp.sqrt(var + eps).astype(jnp.bfloat16)
        return (y * p[name + ".scale"].astype(jnp.bfloat16)
                + p[name + ".bias"].astype(jnp.bfloat16)
                ).astype(jnp.float32)

    monkeypatch.setattr(bert_ref, "_ln", ln16)
    got = bert_ref.encode_f32(params, config["model"], batch["input_ids"],
                              batch["token_type_ids"])
    # as far from the reference as the program's own bf16 activations
    assert 0.001 < _rel(got, want) <= config["forward_rel_tol"]


# GPT-2-large at its published widths, cut to 6 layers for the CPU
GPT_LAYERS, WIDTH, N_NEW = 6, 64, 8


@pytest.fixture(scope="module")
def gpt():
    import jax.numpy as jnp

    config = _config("gpt2_large.json")
    model = dict(config["model"], layers=GPT_LAYERS)
    params, _ = gpt_family.init(gpt_family.make_config(model), SEED)
    p32 = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    prompts = [traffic.prompt_ids(SEED, i, 20 + 4 * i, model["vocab_size"])
               for i in range(2)]
    return config, model, p32, prompts


def _greedy(params, model, prompt):
    """Greedy decoding by the plain full forward pass of `params`."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda p, ids, first: gpt_ref.logits_rows(
        p, model, ids, first, 1))
    ids = np.zeros((WIDTH,), np.int32)
    ids[:len(prompt)] = prompt
    n, out = len(prompt), []
    with jax.default_matmul_precision("highest"):
        for _ in range(N_NEW):
            row = np.asarray(fn(params, jnp.asarray(ids), np.int32(n - 1)))
            out.append(int(row[0].argmax()))
            ids[n] = out[-1]
            n += 1
    return out


@pytest.mark.parametrize("fault", ["none_bf16_weights", "dropped_last_layer",
                                   "shifted_positions"])
def test_gpt_logit_gap_tolerance(gpt, fault):
    import jax.numpy as jnp

    config, model, p32, prompts = gpt
    served, served_model = dict(p32), model
    if fault == "none_bf16_weights":
        served = {k: v.astype(jnp.bfloat16).astype(jnp.float32)
                  for k, v in p32.items()}
    elif fault == "dropped_last_layer":
        served = {k: (v[:-1] if k.startswith("blk.") else v)
                  for k, v in p32.items()}
        served_model = dict(model, layers=GPT_LAYERS - 1)
    else:
        served["wpe.w"] = jnp.roll(p32["wpe.w"], 1, axis=0)
    streams = [_greedy(served, served_model, p) for p in prompts]
    gap, _ = gpt_ref.stream_gaps(p32, model, prompts, streams, WIDTH)
    if fault == "none_bf16_weights":
        assert gap <= config["logit_gap_tol"]
    else:
        assert gap > 2 * config["logit_gap_tol"], gap
        # and the earlier tolerance of 0.5 let a dropped last layer pass
        assert fault != "dropped_last_layer" or gap < 0.5

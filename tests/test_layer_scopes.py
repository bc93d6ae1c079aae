"""The layer scopes of the device programs (PERF.md section 3): every name
a profile is reduced by is in the HLO of the step functions, forward and
backward, so a refactoring that drops one fails here, on the CPU. A scope
is HLO metadata (`op_name`): it adds no op. The serve programs' scopes are
a row of tests/serve_contract.py, run a family in its own file; here are
the kernel under its scope and the training steps."""

import re

import jax
import jax.numpy as jnp
import optax
import pytest

from paddle_tpu.models import bert, decoder, gpt
from serve_contract import scopes_of as _scopes

LAYER = {"ln", "qkv", "attention", "proj", "mlp"}
S, MB, NB, BS = 4, 8, 17, 16


def test_the_paged_kernel_sits_under_the_attention_scope(monkeypatch):
    """On a TPU the decode program's attention is one Mosaic kernel
    (ops/pallas/paged_attention.py); lowered for that platform, its
    custom call carries `attention/paged_attention`, so a profile's
    reduction by scope finds the kernel's time where the gather path's
    einsums were, and `kv_gather` has nothing left under it."""
    from paddle_tpu.ops.pallas import attention as A

    monkeypatch.setattr(A, "_platform", lambda q: "tpu")
    cfg = gpt.GPTConfig(vocab_size=97, hidden=128, layers=2, heads=2,
                        mlp_dim=256, max_len=64, dtype="float32")
    i32 = jnp.int32
    # conftest's x64 mode off, as the program runs: under it a kernel's
    # index arithmetic turns i64, which Mosaic refuses
    with jax.enable_x64(False):
        params, _ = gpt.init(jax.random.key(0), cfg)
        pool = jnp.zeros((cfg.layers, NB, BS, cfg.heads * cfg.head_dim))
        text = jax.jit(lambda p, *a: decoder.decode_step(
            cfg.serve_model(), p, *a, block_size=BS, eos_id=1)).trace(
            params, jnp.zeros((S,), i32), jnp.zeros((S,), i32), pool, pool,
            jnp.zeros((S, MB), i32)).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
    calls = re.findall(r"@tpu_custom_call.*loc\((#loc\d+)\)$", text, re.M)
    assert len(calls) == 1, calls
    name = re.search(rf'^{calls[0]} = loc\("([^"]*)"', text, re.M).group(1)
    assert name.startswith("attention/paged_attention/"), name
    assert "kv_gather" not in text


def test_the_short_kernel_sits_under_the_attention_scope(monkeypatch):
    """BERT's attention on a TPU is two Mosaic kernels a layer since PR 54
    (`attention._short_mha`: forward, and the one-pass backward of its
    custom vjp), each lowered ONCE as a function of its own
    (`jit(_short_call)`) that every layer calls. Lowered for that platform
    every call site carries `attention` (the backward as
    `transpose(jvp(...))`), so a compiled kernel's `op_name`, call site
    then kernel, falls under the scope that the benchmark's
    `attention_share` reads (the compiled step's own names:
    `tests/test_tpu_aot_compile.py`)."""
    from benchmarks.harness import program_trace
    from paddle_tpu.ops.pallas import attention as A

    monkeypatch.setattr(A, "_platform", lambda q: "tpu")
    cfg = bert.BertConfig(vocab_size=128, hidden=128, layers=2, heads=2,
                          mlp_dim=128, max_len=128, dropout=0.0,
                          dtype="bfloat16")
    A.GATE_COUNTS.clear()
    with jax.enable_x64(False):
        params, _ = bert.init(jax.random.key(0), cfg)
        ids = jnp.zeros((2, 128), jnp.int32)
        text = jax.jit(jax.grad(lambda p: bert.encode(p, cfg, ids).astype(
            jnp.float32).sum())).trace(params).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert dict(A.GATE_COUNTS) == {"short": cfg.layers}
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    kernels = [locs[c] for c in re.findall(
        r"@tpu_custom_call.*loc\((#loc\d+)\)$", text, re.M)]
    assert sorted(kernels) == ["short_mha_bwd/pallas_call",
                               "short_mha_fwd/pallas_call"], kernels
    sites = [locs[c] for c in re.findall(
        r"call @_short_call.*loc\((#loc\d+)\)$", text, re.M)]
    assert len(sites) == 2 * cfg.layers, sites
    backward = [n for n in sites if "transpose(jvp(" in n]
    assert len(backward) == cfg.layers, sites
    assert {program_trace.scope_of(f"{site}/{kernel}")
            for site in sites for kernel in kernels} == {"attention"}, sites


def test_gpt_training_forward_carries_the_scopes():
    cfg = gpt.GPTConfig.tiny()
    cfg.dtype = "float32"
    params, _ = gpt.init(jax.random.key(0), cfg)
    text = jax.jit(lambda p, i: gpt.apply(p, cfg, i)).lower(
        params, jnp.zeros((2, 16), jnp.int32)).compile().as_text()
    missing = (LAYER | {"embed", "layers", "head"}) - _scopes(text)
    assert not missing, missing


@pytest.fixture(scope="module")
def bert_step_op_names():
    """Every `op_name` of the tiny BERT's compiled train step (dropout 0.1,
    the configuration's default)."""
    from paddle_tpu.parallel import MeshConfig, make_mesh, mesh_guard
    from paddle_tpu.parallel.train import TrainStrategy, make_train_step

    cfg = bert.BertConfig(vocab_size=128, hidden=32, layers=2, heads=2,
                          mlp_dim=64, max_len=32)
    mesh = make_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
    with mesh_guard(mesh):
        params, axes = bert.init(jax.random.key(0), cfg)
        init_state, step = make_train_step(
            lambda p, b, r: bert.pretrain_loss(p, cfg, b, r),
            optax.adamw(1e-4), mesh, axes,
            strategy=TrainStrategy(clip_global_norm=1.0))
        state = init_state(params)
        batch = bert.make_batch(jax.random.key(1), cfg, 4, 16)
        text = step.lower(state, batch, jax.random.key(2)).compile(
            ).as_text()
    return re.findall(r'op_name="([^"]*)"', text)


def _scopes_of_names(names):
    return _scopes("\n".join(f'op_name="{n}"' for n in names))


def test_bert_train_step_carries_the_scopes_forward_and_backward(
        bert_step_op_names):
    names = bert_step_op_names
    missing = (LAYER | {"embed", "layers", "mlm_head", "nsp_head", "loss",
                        "clip", "optimizer"}) - _scopes_of_names(names)
    assert not missing, missing
    # the backward pass keeps the scopes; autodiff wraps the outermost:
    # transpose(jvp(layers))/attention/..., transpose(jvp(mlm_head))/...
    backward = _scopes_of_names(n for n in names if "transpose(jvp(" in n)
    missing = (LAYER | {"layers", "mlm_head"}) - backward
    assert not missing, missing


def test_a_dropout_masks_ops_sit_under_dropout_inside_proj_and_mlp(
        bert_step_op_names):
    """The generator of a mask carries `dropout` in its `op_name`, inside
    the scope of the layer that drops (`proj`, `mlp`), and only there; the
    benchmark's reduction still books it to that layer, since `dropout` is
    not among its `SCOPES` (a `dropout_share` is a benchmark PR's to add)."""
    from benchmarks.harness import program_trace

    names = bert_step_op_names
    mask = [n for n in names if n.endswith(("/xor", "/shift_left"))
            and "_bernoulli" in n]
    assert mask
    for n in mask:
        path = n.split("/")
        at = path.index("dropout")
        assert path[at - 1] in ("proj", "mlp"), n
        assert path[at + 1] == "jit(_bernoulli)", n
    assert {program_trace.scope_of(n) for n in mask} == {"proj", "mlp"}
    assert "dropout" not in program_trace.SCOPES
    # nothing else of the step moved under the new scope: the select and
    # its transpose stay the layer's own ops
    assert all("_bernoulli" in n for n in names if "/dropout/" in n)

"""The layer scopes of the device programs (PERF.md section 3): every name
a profile is reduced by is in the HLO of the step functions, forward and
backward, so a refactoring that drops one fails here, on the CPU. A scope
is HLO metadata (`op_name`): it adds no op."""

import re

import jax
import jax.numpy as jnp
import optax
import pytest

from paddle_tpu.models import bert, decoder, gpt, joyai, olmoe

LAYER = {"ln", "qkv", "attention", "proj", "mlp"}
SERVE = LAYER | {"embed", "layers", "head", "kv_write"}
S, MB, NB, BS = 4, 8, 17, 16


def _scopes(text: str):
    """Every path component of every op_name, unwrapped:
    `transpose(jvp(mlp))` counts as `mlp`."""
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        for part in op_name.split("/")[:-1]:
            found.update(re.findall(r"[A-Za-z_][A-Za-z0-9_.]*", part))
    return found


@pytest.fixture(scope="module")
def tiny_gpt():
    cfg = gpt.GPTConfig.tiny()
    cfg.dtype = "float32"
    params, _ = gpt.init(jax.random.key(0), cfg)
    pool = jnp.zeros((cfg.layers, NB, BS, cfg.heads * cfg.head_dim))
    return cfg, params, pool


def _lower_serve(kind, cfg, params, pool):
    kw = dict(block_size=BS, eos_id=1)
    i32 = jnp.int32
    if kind == "decode":
        return jax.jit(lambda p, i, po, k, v, b: gpt.apply_decode_step(
            p, cfg, i, po, k, v, b, **kw)).lower(
            params, jnp.zeros((S,), i32), jnp.zeros((S,), i32), pool, pool,
            jnp.zeros((S, MB), i32))
    if kind == "verify":
        return jax.jit(lambda p, i, po, k, v, b: gpt.apply_verify_step(
            p, cfg, i, po, k, v, b, **kw)).lower(
            params, jnp.zeros((S, 3), i32), jnp.zeros((S,), i32), pool,
            pool, jnp.zeros((S, MB), i32))
    if kind == "prefill":
        return jax.jit(lambda p, i, n, k, v, b: gpt.apply_prefill(
            p, cfg, i, n, k, v, b, **kw)).lower(
            params, jnp.zeros((1, 32), i32), i32(5), pool, pool,
            jnp.zeros((MB,), i32))
    return jax.jit(lambda p, i, s, n, k, v, b: gpt.apply_prefill_chunk(
        p, cfg, i, s, n, k, v, b, **kw)).lower(
        params, jnp.zeros((1, 16), i32), i32(0), i32(5), pool, pool,
        jnp.zeros((MB,), i32))


@pytest.mark.parametrize("kind,extra", [
    ("decode", {"kv_gather"}), ("verify", {"kv_gather"}),
    ("chunk", {"kv_gather"}), ("prefill", set())])
def test_serving_step_functions_carry_every_scope(tiny_gpt, kind, extra):
    text = _lower_serve(kind, *tiny_gpt).compile().as_text()
    missing = (SERVE | extra) - _scopes(text)
    assert not missing, (kind, missing)


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


# OLMoE's own parts nest INSIDE the shared names, so that a reduction by
# the innermost of the harness's fixed scopes lands the expert layer under
# `mlp` and RoPE / QK-norm under `qkv`
OLMOE_NESTED = {"mlp": {"router", "moe_route", "experts"},
                "qkv": {"qk_norm", "rope"}}


@pytest.fixture(scope="module")
def tiny_olmoe():
    cfg = olmoe.OlmoeConfig.tiny()
    cfg.dtype = "float32"
    params, _ = olmoe.init(jax.random.key(0), cfg)
    pool = jnp.zeros((cfg.layers, NB, BS, cfg.heads * cfg.head_dim))
    return cfg, params, pool


def _lower_olmoe(kind, cfg, params, pool, v_pool=None):
    sm = cfg.serve_model()
    kw = dict(block_size=BS, eos_id=1)
    i32 = jnp.int32
    v_pool = pool if v_pool is None else v_pool
    slots = (jnp.zeros((S,), i32), pool, v_pool, jnp.zeros((S, MB), i32))
    one = (pool, v_pool, jnp.zeros((MB,), i32))
    fn, args = {
        "decode": (decoder.decode_step, (jnp.zeros((S,), i32),) + slots),
        "verify": (decoder.verify_step, (jnp.zeros((S, 3), i32),) + slots),
        "prefill": (decoder.prefill, (jnp.zeros((1, 32), i32), i32(5)) + one),
        "chunk": (decoder.prefill_chunk,
                  (jnp.zeros((1, 16), i32), i32(0), i32(5)) + one)}[kind]
    return jax.jit(lambda p, *a: fn(sm, p, *a, **kw)).lower(params, *args)


@pytest.mark.parametrize("kind,extra", [
    ("decode", {"kv_gather"}), ("verify", {"kv_gather"}),
    ("chunk", {"kv_gather"}), ("prefill", set())])
def test_olmoe_serve_programs_carry_every_scope(tiny_olmoe, kind, extra):
    text = _lower_olmoe(kind, *tiny_olmoe).compile().as_text()
    nested = set().union(*OLMOE_NESTED.values())
    missing = (SERVE | extra | nested) - _scopes(text)
    assert not missing, (kind, missing)
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        path = op_name.split("/")[:-1]
        for outer, inner in OLMOE_NESTED.items():
            for name in inner & set(path):
                assert outer in path[:path.index(name)], op_name
    # and the norms are `ln`'s, not the QK-norm's: some op sits directly
    # under ln inside the layer loop
    assert any(re.search(r"/layers/.*/ln/[^/]+$", n)
               for n in re.findall(r'op_name="([^"]*)"', text))


# The latent model's own parts nest inside the shared names too: the two
# low-rank projections and RoPE under `qkv`, the absorb products (in the
# programs that read the cache) under `attention`, the dense layer's MLP
# and the expert layer's parts, the shared expert among them, under `mlp`
JOYAI_NESTED = {"mlp": {"router", "moe_route", "experts", "shared_expert",
                        "dense_mlp"},
                "qkv": {"mla_q", "mla_kv", "rope"}}


@pytest.fixture(scope="module")
def tiny_joyai():
    cfg = joyai.JoyaiConfig.tiny()
    cfg.dtype = "float32"
    params, _ = joyai.init(jax.random.key(0), cfg)
    widths = cfg.serve_model().stored
    return (cfg, params) + tuple(jnp.zeros((cfg.layers, NB, BS, w))
                                 for w in widths)


@pytest.mark.parametrize("kind,extra", [
    ("decode", {"kv_gather", "absorb"}), ("verify", {"kv_gather", "absorb"}),
    ("chunk", {"kv_gather", "absorb"}), ("prefill", set())])
def test_joyai_serve_programs_carry_every_scope(tiny_joyai, kind, extra):
    text = _lower_olmoe(kind, *tiny_joyai).compile().as_text()
    nested = set().union(*JOYAI_NESTED.values())
    missing = (SERVE | extra | nested) - _scopes(text)
    assert not missing, (kind, missing)
    names = re.findall(r'op_name="([^"]*)"', text)
    for op_name in names:
        path = op_name.split("/")[:-1]
        for outer, inner in dict(JOYAI_NESTED, attention={"absorb"}).items():
            for name in inner & set(path):
                assert outer in path[:path.index(name)], op_name
    # the leading dense layer runs before the scan, inside `layers`; the
    # expert layers in its body
    assert any(re.search(r"/layers/mlp/dense_mlp/", n) for n in names)
    assert any(re.search(r"/layers/while/body/.*mlp/experts/", n)
               for n in names)


# Xing4's residual path is a layer scope of its own: `mhc`, a SIBLING of
# `ln` / `qkv` / `attention` / `proj` / `mlp` (whose seconds existing
# readers divide by), holding `mhc_map`, `mhc_pre` and `mhc_post`; the
# block inside it is the latent model's, scope for scope
XING4_NESTED = dict(JOYAI_NESTED, mhc={"mhc_map", "mhc_pre", "mhc_post"})


@pytest.fixture(scope="module")
def tiny_xing4():
    from paddle_tpu.models import xing4

    cfg = xing4.Xing4Config.tiny()
    cfg.dtype = "float32"
    params, _ = xing4.init(jax.random.key(0), cfg)
    widths = cfg.serve_model().stored
    return (cfg, params) + tuple(jnp.zeros((cfg.layers, NB, BS, w))
                                 for w in widths)


@pytest.mark.parametrize("kind,extra", [
    ("decode", {"kv_gather", "absorb"}), ("verify", {"kv_gather", "absorb"}),
    ("chunk", {"kv_gather", "absorb"}), ("prefill", set())])
def test_xing4_serve_programs_carry_every_scope(tiny_xing4, kind, extra):
    text = _lower_olmoe(kind, *tiny_xing4).compile().as_text()
    nested = set().union(*XING4_NESTED.values())
    missing = (SERVE | extra | nested | {"mhc"}) - _scopes(text)
    assert not missing, (kind, missing)
    names = re.findall(r'op_name="([^"]*)"', text)
    siblings = {"ln", "qkv", "attention", "proj", "mlp"}
    for op_name in names:
        path = op_name.split("/")[:-1]
        for outer, inner in dict(XING4_NESTED, attention={"absorb"}).items():
            for name in inner & set(path):
                assert outer in path[:path.index(name)], op_name
        # never inside another layer scope, nor another inside it
        if "mhc" in path:
            assert not siblings & set(path), op_name
    assert any(re.search(r"/layers/mhc/mhc_map/", n) for n in names)
    assert any(re.search(r"/layers/while/body/.*mhc/mhc_post/", n)
               for n in names)


def test_gpt_training_forward_carries_the_scopes(tiny_gpt):
    cfg, params, _ = tiny_gpt
    text = jax.jit(lambda p, i: gpt.apply(p, cfg, i)).lower(
        params, jnp.zeros((2, 16), jnp.int32)).compile().as_text()
    missing = (LAYER | {"embed", "layers", "head"}) - _scopes(text)
    assert not missing, missing


def test_bert_train_step_carries_the_scopes_forward_and_backward():
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
    names = re.findall(r'op_name="([^"]*)"', text)
    missing = (LAYER | {"embed", "layers", "mlm_head", "nsp_head", "loss",
                        "clip", "optimizer"}) - _scopes(text)
    assert not missing, missing
    # the backward pass keeps the scopes; autodiff wraps the outermost:
    # transpose(jvp(layers))/attention/..., transpose(jvp(mlm_head))/...
    backward = _scopes("\n".join(f'op_name="{n}"' for n in names
                                 if "transpose(jvp(" in n))
    missing = (LAYER | {"layers", "mlm_head"}) - backward
    assert not missing, missing

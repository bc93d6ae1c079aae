"""Model zoo + sharded train-step tests on the 8-device CPU mesh
(reference analogue: test_parallel_executor_transformer.py / _mnist.py —
same-model-multi-config loss agreement)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from paddle_tpu.models import bert, lenet, resnet
from paddle_tpu.parallel import MeshConfig, make_mesh, mesh_guard
from paddle_tpu.parallel.train import TrainStrategy, make_train_step


def _train_bert(mesh_cfg, strategy, steps=3, bs=16):
    cfg = bert.BertConfig.tiny()
    params, axes = bert.init(jax.random.key(0), cfg)
    import math

    sizes = [getattr(mesh_cfg, a) for a in ("dp", "tp", "pp", "sp", "ep")]
    n = len(jax.devices()) if -1 in sizes else math.prod(sizes)
    mesh = make_mesh(mesh_cfg, devices=jax.devices()[:n])
    with mesh_guard(mesh):
        def loss_fn(p, b, r):
            return bert.pretrain_loss(p, cfg, b, rng=r, deterministic=True)

        init_state, step = make_train_step(
            loss_fn, optax.adamw(1e-3), mesh, axes, strategy=strategy)
        state = init_state(params)
        batch = bert.make_batch(jax.random.key(1), cfg, batch_size=bs,
                                seq_len=32)
        losses = []
        for i in range(steps):
            state, loss = step(state, batch, jax.random.key(10 + i))
            losses.append(float(loss))
    return losses


def test_bert_dp_tp_sp_matches_single_device():
    single = _train_bert(MeshConfig(dp=1, tp=1, sp=1), TrainStrategy())
    multi = _train_bert(MeshConfig(dp=2, tp=2, sp=2), TrainStrategy())
    np.testing.assert_allclose(single, multi, rtol=2e-2)
    assert single[-1] < single[0]


def test_bert_zero1_and_grad_accum_match():
    base = _train_bert(MeshConfig(dp=8), TrainStrategy(
        shard_optimizer_states=False), bs=16)
    zero1 = _train_bert(MeshConfig(dp=8), TrainStrategy(
        shard_optimizer_states=True), bs=16)
    np.testing.assert_allclose(base, zero1, rtol=1e-3)
    # grad accumulation over 2 microbatches ≈ full batch (same data split)
    accum = _train_bert(MeshConfig(dp=2), TrainStrategy(accum_steps=2), bs=16)
    np.testing.assert_allclose(base[0], accum[0], rtol=5e-2)


def test_recompute_policies_preserve_numerics():
    """Rematerialization (reference: RecomputeOptimizer with a
    checkpoints list, optimizer.py:3267) trades FLOPs for memory without
    changing math: every recompute policy must reproduce the no-remat
    loss trajectory exactly (same graph, different schedule)."""
    base = _train_bert(MeshConfig(dp=2), TrainStrategy(recompute=False))
    for pol in (None, "nothing", "dots", "dots_no_batch"):
        got = _train_bert(MeshConfig(dp=2),
                          TrainStrategy(recompute=True,
                                        recompute_policy=pol))
        np.testing.assert_allclose(got, base, rtol=1e-6, atol=1e-7,
                                   err_msg=f"policy={pol}")
    with pytest.raises(ValueError, match="recompute_policy"):
        _train_bert(MeshConfig(dp=2),
                    TrainStrategy(recompute=True,
                                  recompute_policy="bogus"))
    # a policy without recompute=True is a configuration error, not a no-op
    with pytest.raises(ValueError, match="recompute=False"):
        _train_bert(MeshConfig(dp=2),
                    TrainStrategy(recompute=False,
                                  recompute_policy="dots"))


def test_bert_grad_clip_runs():
    losses = _train_bert(MeshConfig(dp=2, tp=2, sp=2),
                         TrainStrategy(clip_global_norm=1.0))
    assert all(np.isfinite(losses))


def test_resnet_trains_with_bn_state():
    cfg = resnet.ResNetConfig.tiny()
    params, axes = resnet.init(jax.random.key(0), cfg)
    mesh = make_mesh(MeshConfig(dp=-1))
    with mesh_guard(mesh):
        def loss_fn(p, b, r):
            return resnet.loss_fn(p, cfg, b, r)

        init_state, step = make_train_step(
            loss_fn, optax.sgd(0.05, momentum=0.9), mesh, axes, has_aux=True)
        state = init_state(params)
        batch = resnet.make_batch(jax.random.key(1), cfg, 16, hw=32)
        losses = []
        for i in range(4):
            state, loss = step(state, batch, jax.random.key(i))
            losses.append(float(loss))
    assert losses[-1] < losses[0]
    assert float(jnp.abs(state.params["stem.bn.mean"]).sum()) > 0


def test_resnet_dp_matches_single_device_sync_bn():
    """Sync-BN-via-GSPMD correctness (VERDICT r3 #3): a conv+BN model
    trained dp-sharded over 8 devices must produce the SAME losses as
    the single-device run on the same global batch — this is exactly
    the sync-BN-via-GSPMD claim (ops/nn.py batch_norm NOTE): the BN
    batch reductions are global, i.e. per-device batch statistics do
    NOT diverge from the global ones (reference needs
    BuildStrategy.sync_batch_norm + sync_batch_norm_op.cu).

    f64 end-to-end isolates the property: per-device BN stats would be a
    STRUCTURAL divergence (each device normalizing by 2-sample instead of
    16-sample statistics) visible at any precision, while at f32 the
    shard summation order perturbs the one-pass E[x^2]-E[x]^2 variance by
    ~1e-6 and ReLU-kink subgradient flips amplify that to percent-level
    loss divergence within 2 steps (measured; see _bn's docstring). At
    f64 the trajectories agree to ~1e-7 for 3 full steps."""
    import dataclasses

    cfg = dataclasses.replace(resnet.ResNetConfig.tiny(), dtype="float64")
    batch = resnet.make_batch(jax.random.key(1), cfg, 16, hw=32)
    batch["img"] = batch["img"].astype(jnp.float64)

    def run(mesh):
        params, axes = resnet.init(jax.random.key(0), cfg)
        with mesh_guard(mesh):
            init_state, step = make_train_step(
                lambda p, b, r: resnet.loss_fn(p, cfg, b, r),
                optax.sgd(0.05, momentum=0.9), mesh, axes, has_aux=True)
            state = init_state(params)
            losses = []
            for i in range(3):
                state, loss = step(state, batch, jax.random.key(10 + i))
                losses.append(float(loss))
            bn_mean = np.asarray(state.params["stem.bn.mean"], np.float64)
        return losses, bn_mean

    dp_losses, dp_bn = run(make_mesh(MeshConfig(dp=8)))
    ref_losses, ref_bn = run(make_mesh(MeshConfig(dp=1),
                                       devices=jax.devices()[:1]))
    # step-for-step trajectory parity: unsynced BN is an O(1) structural
    # difference; the 1e-5 bound leaves 2 orders of headroom over the
    # measured 1e-7 numerical floor
    np.testing.assert_allclose(dp_losses, ref_losses, rtol=1e-5)
    # the running BN statistics agree too: they are the direct sync-BN
    # observable (per-shard means would differ from the global mean)
    np.testing.assert_allclose(dp_bn, ref_bn, rtol=1e-5, atol=1e-8)
    assert dp_losses[-1] < dp_losses[0]


def test_resnet_nhwc_matches_nchw():
    """The NHWC-native path (TPU bench path) and the NCHW reference-API
    shim compute identical logits for the same image content."""
    cfg = resnet.ResNetConfig.tiny()
    params, _ = resnet.init(jax.random.key(0), cfg)
    b_nchw = resnet.make_batch(jax.random.key(1), cfg, 4, hw=32,
                               data_format="NCHW")
    img_nhwc = jnp.transpose(b_nchw["img"], (0, 2, 3, 1))
    lo_a, _ = jax.jit(lambda p, v: resnet.apply(p, cfg, v, train=False))(
        params, b_nchw["img"])
    lo_b, _ = jax.jit(lambda p, v: resnet.apply(
        p, cfg, v, train=False, data_format="NHWC"))(params, img_nhwc)
    np.testing.assert_allclose(np.asarray(lo_a, np.float32),
                               np.asarray(lo_b, np.float32),
                               rtol=1e-5, atol=1e-5)


def test_lenet_convergence():
    params, _ = lenet.init(jax.random.key(0))
    imgs = jax.random.normal(jax.random.key(1), (64, 1, 28, 28), jnp.float32)
    labels = jax.random.randint(jax.random.key(2), (64,), 0, 10)
    tx = optax.adam(1e-3)
    opt = tx.init(params)

    @jax.jit
    def step(params, opt):
        loss, g = jax.value_and_grad(lenet.loss_fn)(
            params, {"img": imgs, "label": labels})
        upd, opt = tx.update(g, opt)
        return optax.apply_updates(params, upd), opt, loss

    losses = []
    for _ in range(20):
        params, opt, loss = step(params, opt)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.5  # memorizes random labels


def test_bert_attention_mask_respected():
    """Padding positions must not influence unpadded outputs."""
    cfg = bert.BertConfig.tiny()
    params, _ = bert.init(jax.random.key(0), cfg)
    ids = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    mask = jnp.concatenate([jnp.ones((2, 8), jnp.int32),
                            jnp.zeros((2, 8), jnp.int32)], axis=1)
    out1 = bert.encode(params, cfg, ids, attention_mask=mask)
    # change padded tokens — visible region must be unaffected
    ids2 = ids.at[:, 8:].set(0)
    out2 = bert.encode(params, cfg, ids2, attention_mask=mask)
    np.testing.assert_allclose(np.asarray(out1[:, :8], np.float32),
                               np.asarray(out2[:, :8], np.float32),
                               atol=2e-2)


def test_vgg16_forward_shapes_and_grad():
    from paddle_tpu.models import vgg

    cfg = vgg.VGGConfig.tiny()
    params, _ = vgg.init(jax.random.key(0), cfg)
    img = jax.random.normal(jax.random.key(1), (2, 3, 32, 32),
                            jnp.float32)
    logits = jax.jit(lambda p, x: vgg.apply(p, cfg, x))(params, img)
    assert logits.shape == (2, 10)
    assert jnp.isfinite(logits.astype(jnp.float32)).all()

    def loss(p):
        lg = vgg.apply(p, cfg, img).astype(jnp.float32)
        return -jax.nn.log_softmax(lg)[jnp.arange(2), jnp.arange(2)].mean()

    g = jax.grad(loss)(params)
    gn = sum(float(jnp.sum(jnp.abs(v.astype(jnp.float32))))
             for k, v in g.items() if k.endswith(".w"))
    assert gn > 0


def test_sharded_train_state_checkpoint_roundtrip(tmp_path):
    """Save/resume of the jax-native TrainState with ZeRO-1-sharded
    optimizer moments on an 8-device mesh: training resumed from the
    checkpoint must continue bit-identically to the uninterrupted run
    (reference capability: save/load_persistables, io.py:501/769; here
    sharding-aware via orbax)."""
    import pytest as _pytest

    from paddle_tpu.parallel import (latest_step_dir, make_mesh,
                                     mesh_guard, MeshConfig,
                                     restore_train_state,
                                     save_train_state)

    cfg = bert.BertConfig.tiny()
    params, axes = bert.init(jax.random.key(0), cfg)
    mesh = make_mesh(MeshConfig(dp=2, tp=2, sp=2))
    with mesh_guard(mesh):
        def loss_fn(p, b, r):
            return bert.pretrain_loss(p, cfg, b, rng=r, deterministic=True)

        init_state, step = make_train_step(
            loss_fn, optax.adamw(1e-3), mesh, axes,
            strategy=TrainStrategy(shard_optimizer_states=True))
        state = init_state(params)
        batch = bert.make_batch(jax.random.key(1), cfg, batch_size=8,
                                seq_len=64)
        # two steps, checkpoint, two more (the "uninterrupted" trace)
        for i in range(2):
            state, _ = step(state, batch, jax.random.key(2 + i))
        ckpt = str(tmp_path / "step_2")
        save_train_state(ckpt, state)
        # overwriting an existing checkpoint in place is refused (a
        # death mid-save must never destroy the only checkpoint)
        with _pytest.raises(Exception):
            save_train_state(ckpt, state)
        # remember the template's moment shardings before training on
        tmpl_shardings = [x.sharding for x in
                          jax.tree.leaves(state.opt_state)
                          if getattr(x, "ndim", 0) > 0]
        base_losses = []
        for i in range(2):
            state, loss = step(state, batch, jax.random.key(4 + i))
            base_losses.append(float(loss))
        assert int(state.step) == 4

        # fresh differently-seeded state, restore, resume
        (tmp_path / "step_10").write_text("stray file, not a checkpoint")
        assert latest_step_dir(str(tmp_path)) == ckpt  # non-dirs skipped
        state2 = restore_train_state(
            latest_step_dir(str(tmp_path)),
            init_state(bert.init(jax.random.key(9), cfg)[0]))
        assert int(state2.step) == 2
        # restored moments keep their EXACT NamedShardings (ZeRO-1: the
        # 'dp' axis must appear in at least one moment's spec)
        got_shardings = [x.sharding for x in
                         jax.tree.leaves(state2.opt_state)
                         if getattr(x, "ndim", 0) > 0]
        assert got_shardings == tmpl_shardings
        assert any("dp" in str(s.spec) for s in got_shardings)
        resumed = []
        for i in range(2):
            state2, loss = step(state2, batch, jax.random.key(4 + i))
            resumed.append(float(loss))
    # bit-identical continuation (same compiled step, same layouts)
    assert resumed == base_losses, (resumed, base_losses)

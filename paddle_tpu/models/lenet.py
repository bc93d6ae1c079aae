"""LeNet / MNIST — the minimum end-to-end slice (reference:
python/paddle/fluid/tests/book/test_recognize_digits.py).

Provides BOTH API levels: `build_program` constructs the fluid-style static
graph (exercising the Program IR path end-to-end), and init/apply give the
JAX-native path.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .common import ParamStore, Params, dense


def build_program(pt, img_shape=(1, 28, 28), n_classes=10, lr=0.01):
    """Static-graph LeNet (conv_pool x2 + fc ladder) via paddle_tpu.layers.
    Returns (main, startup, feeds, loss, acc)."""
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        img = pt.layers.data(name="img", shape=list(img_shape), dtype="float32")
        label = pt.layers.data(name="label", shape=[1], dtype="int64")
        c1 = pt.layers.conv2d(input=img, num_filters=20, filter_size=5, act="relu")
        p1 = pt.layers.pool2d(input=c1, pool_size=2, pool_stride=2, pool_type="max")
        c2 = pt.layers.conv2d(input=p1, num_filters=50, filter_size=5, act="relu")
        p2 = pt.layers.pool2d(input=c2, pool_size=2, pool_stride=2, pool_type="max")
        fc1 = pt.layers.fc(input=p2, size=500, act="relu")
        logits = pt.layers.fc(input=fc1, size=n_classes)
        loss = pt.layers.mean(pt.layers.softmax_with_cross_entropy(
            logits=logits, label=label))
        acc = pt.layers.accuracy(input=pt.layers.softmax(logits), label=label)
        pt.optimizer.Adam(learning_rate=lr).minimize(loss)
    return main, startup, ("img", "label"), loss, acc


def init(rng: jax.Array, n_classes: int = 10) -> Tuple[Params, Dict]:
    s = ParamStore(rng)
    s.conv("conv1", 5, 5, 1, 20)
    s.conv("conv2", 5, 5, 20, 50)
    s.dense("fc1", 4 * 4 * 50, 500)
    s.dense("fc2", 500, n_classes, axes=("embed", None))
    return s.params, s.axes


def apply(params: Params, img: jax.Array) -> jax.Array:
    """img: [B, 1, 28, 28] -> logits [B, 10]."""
    x = img.transpose(0, 2, 3, 1)  # NHWC for TPU conv
    for name in ("conv1", "conv2"):
        x = jax.lax.conv_general_dilated(
            x, params[f"{name}.w"], (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        x = jax.nn.relu(x)
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                  (1, 2, 2, 1), "VALID")
    x = x.reshape(x.shape[0], -1)
    x = dense(params, "fc1", x, act=jax.nn.relu)
    return dense(params, "fc2", x)


def loss_fn(params: Params, batch, rng=None) -> jax.Array:
    logits = apply(params, batch["img"]).astype(jnp.float32)
    labels = batch["label"].reshape(-1)
    logp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(logp, labels[:, None], 1).mean()

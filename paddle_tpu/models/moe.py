"""The sparse expert layer of the mixture-of-experts decoders
(models/olmoe.py, models/joyai.py, models/nemotron_h.py): dropless top-k
routing over experts of the model's form, with the routing rule and an
optional always-on expert given by the model.

What the models share is everything after the router has spoken: the
(row, expert) pairs sorted by expert, the projections as grouped matmuls
over the ragged groups, the weighted fixed-order combine and the two
counters of a step. What they differ in is `Routing`: how the router's
logits become scores (softmax over the experts | a sigmoid an expert), what
the top-k is taken of (the scores | the scores plus a per-expert
correction bias, which selects and never weighs), whether the kept scores
are normalised to sum to 1 and scaled, whether one more expert sees every
row, and what an expert IS (`form`: three matrices, `down(silu(gate x) *
up x)`, or two, `down(relu(up x)^2)`).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..ops.pallas.grouped_matmul import grouped_matmul


@dataclasses.dataclass(frozen=True)
class Routing:
    """A model's routing rule. `bias`: the top-k is taken of score +
    `lp["blk.router_bias"]`, the weights of the score alone. `shared`: the
    layer holds one always-on expert (`blk.shared_gate`, `blk.shared_up`,
    `blk.shared_down`) whose result is added unweighted. `form`: an
    expert's matrices, routed or shared: "swiglu" (`w_gate`, `w_up`,
    `w_down`) or "relu2" (`w_up`, `w_down`; no `*_gate` parameter)."""

    n_experts: int
    top_k: int
    score: str = "softmax"      # "softmax" | "sigmoid"
    bias: bool = False
    normalise: bool = False     # kept scores / (their sum + 1e-20)
    scale: float = 1.0
    shared: bool = False
    form: str = "swiglu"        # "swiglu" | "relu2"


def route(logits: jax.Array, routing: Routing, bias=None):
    """(weight [n, K] float32, expert [n, K] int32) of the router's float32
    `logits` [n, E]: the K experts of each row and what each weighs."""
    if routing.score == "softmax":
        score = jax.nn.softmax(logits, axis=-1)
    elif routing.score == "sigmoid":
        score = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"unknown router score {routing.score!r}")
    if routing.bias:
        _, expert = jax.lax.top_k(score + bias.astype(jnp.float32),
                                  routing.top_k)
        weight = jnp.take_along_axis(score, expert, axis=-1)
    else:
        weight, expert = jax.lax.top_k(score, routing.top_k)
    if routing.normalise:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    if routing.scale != 1.0:
        weight = weight * routing.scale
    return weight, expert.astype(jnp.int32)


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate.astype(x.dtype))
            * (x @ up.astype(x.dtype))) @ down.astype(x.dtype)


def relu2(x):
    """relu(x)^2, the two-matrix expert's activation."""
    return jnp.square(jax.nn.relu(x))


def relu2_mlp(x, up, down):
    return relu2(x @ up.astype(x.dtype)) @ down.astype(x.dtype)


@jax.named_scope("mlp")
def expert_mlp(lp, y, routing: Routing, layer=None):
    """The sparse expert layer for the rows `y` [..., hidden]: dropless
    top_k routing. Every (row, chosen expert) pair is computed: the pairs
    are sorted by expert, the projections (three of a SwiGLU expert, two
    of a relu^2 one: `routing.form`) run as grouped matmuls
    over the ragged groups (ops/pallas/grouped_matmul.py: the megablox
    kernel on the chip, `jax.lax.ragged_dot` off it), and each pair's result
    goes back to its row weighted as `route` says. A row's result depends
    on that row alone, to the bit.

    `lp` holds this layer's router (and `blk.router_bias`, and the shared
    expert, where `routing` has them) and the expert tensors (`blk.w_gate`,)
    `blk.w_up`, `blk.w_down`: the layer's own `[E, ...]` (`layer` None:
    the full forward pass, whose scan slices them), or the stacks of ALL
    expert layers `[L, E, ...]` with `layer` this one's index in the stack
    (the serve programs). A stack is addressed in place, as L*E groups of
    which only this layer's E hold rows: a kernel's operand cannot be a
    slice without being a copy, and a copy of a layer's experts is 0.8 GB
    (OLMoE) to 2.4 GB (256 experts of 768) written and read again, more
    than a decode step reads of them at all.

    Returns (out [..., hidden], {"experts_hit": experts with at least one
    pair, "expert_load_max": most pairs on one expert}), the counters of
    THIS layer and call."""
    E, K = routing.n_experts, routing.top_k
    if routing.form not in ("swiglu", "relu2"):
        raise ValueError(f"unknown expert form {routing.form!r}")
    gated = routing.form == "swiglu"
    x = y.reshape(-1, y.shape[-1])
    n = x.shape[0]
    with jax.named_scope("router"):
        # float32 out of the matmul, not a rounded bf16 widened again: a
        # near-tie between the 8th and 9th expert is decided as exactly
        # as the inputs allow
        logits = jnp.dot(x, lp["blk.router"].astype(x.dtype),
                         preferred_element_type=jnp.float32)
        weight, expert = route(logits, routing, lp.get("blk.router_bias"))
    with jax.named_scope("moe_route"):
        expert = expert.reshape(-1)                      # pair (row, k)
        order = jnp.argsort(expert, stable=True)         # pairs by expert
        counts = jnp.zeros((E,), jnp.int32).at[expert].add(1)
        xs = x[order // K]                               # [n*K, hidden]
        groups = counts
        if layer is not None:
            n_layers = lp["blk.w_up"].shape[0]
            groups = jax.lax.dynamic_update_slice(
                jnp.zeros((n_layers * E,), jnp.int32), counts, (layer * E,))
    with jax.named_scope("experts"):
        def experts(name):      # [E or L*E, in, out], in the rows' dtype
            w = lp[name]
            return w.reshape((-1,) + w.shape[-2:]).astype(x.dtype)

        if gated:
            gate = grouped_matmul(xs, experts("blk.w_gate"), groups)
            up = grouped_matmul(xs, experts("blk.w_up"), groups)
            mid = jax.nn.silu(gate) * up
        else:
            mid = relu2(grouped_matmul(xs, experts("blk.w_up"), groups))
        ys = grouped_matmul(mid, experts("blk.w_down"), groups)
    with jax.named_scope("moe_route"):
        # back to (row, k) order, then each row's K results summed in k's
        # order: a gather and a fixed-order sum, not a scatter-add, so a
        # row's bits do not depend on where its pairs were sorted to
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(n * K, dtype=order.dtype))
        ys = ys[back].reshape(n, K, -1).astype(jnp.float32)
        out = jnp.sum(ys * weight[..., None], axis=1)
    if routing.shared:
        with jax.named_scope("shared_expert"):
            shared = swiglu(x, lp["blk.shared_gate"], lp["blk.shared_up"],
                            lp["blk.shared_down"]) if gated \
                else relu2_mlp(x, lp["blk.shared_up"],
                               lp["blk.shared_down"])
            out = out + shared.astype(jnp.float32)
    stats = {"experts_hit": jnp.sum(counts > 0).astype(jnp.int32),
             "expert_load_max": jnp.max(counts)}
    return out.astype(y.dtype).reshape(y.shape), stats


def step_facts(stats) -> dict:
    """`ServeModel.step_facts` of a model whose layers are `expert_mlp`s:
    `experts_hit`: distinct experts selected, summed over the layers (what
    a step must read of the expert weights); `expert_load_max`: most pairs
    on one expert in any layer. Both count every row of the step's batch,
    idle slots included: the device computes them all."""
    return {"experts_hit": int(stats["experts_hit"].sum()),
            "expert_load_max": int(stats["expert_load_max"].max())}

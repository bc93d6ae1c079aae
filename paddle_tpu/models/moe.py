"""The sparse expert layer of the mixture-of-experts decoders
(models/olmoe.py, models/joyai.py, models/nemotron_h.py,
models/longcat.py): dropless top-k routing over experts of the model's
form, with the routing rule and an optional always-on expert given by the
model.

What the models share is everything after the router has spoken: the
(row, expert) pairs sorted by expert, the projections as grouped matmuls
over the ragged groups, the weighted fixed-order combine and the two
counters of a step. What they differ in is `Routing`: how the router's
logits become scores (softmax over the experts | a sigmoid an expert), what
the top-k is taken of (the scores | the scores plus a per-expert
correction bias, which selects and never weighs), whether the kept scores
are normalised to sum to 1 and scaled, whether one more expert sees every
row, and what an expert IS (`form`: three matrices, `down(silu(gate x) *
up x)`, or two, `down(relu(up x)^2)`), and two things a router's output may
be that is no matrix of this layer:

- a ZERO-COMPUTE expert (`zero_experts`: the router's last outputs, after
  the `n_experts` routed ones) returns its input: a pair on one adds
  `weight * y` and reaches no matmul, so all of a row's zero experts
  together cost one scale of `y`;
- an expert NOT HELD here (`held`: the range of the routed ids whose
  matrices this layer holds, a chip's share of a layer that an
  expert-parallel deployment spreads over several): the router still scores
  every output and picks among all of them, this layer computes its own
  experts' part for the pairs routed to them and leaves out what the absent
  experts would add. Nothing here stands in for the other shares or for the
  exchange of rows between them (an all-to-all over an `ep` mesh axis:
  ROADMAP M6); the shares' partial results, summed, are the whole layer
  (tests/test_longcat.py).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

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
    `w_down`) or "relu2" (`w_up`, `w_down`; no `*_gate` parameter).
    `zero_experts`: the router has that many outputs AFTER the `n_experts`
    routed ones (`blk.router` and `blk.router_bias` are `n_experts +
    zero_experts` wide) and a pair on one of them is the identity: it adds
    `weight * y`. `held`: `(first, past the last)` of the routed ids whose
    matrices the layer holds, `blk.w_*` then `[past - first, ...]`; a pair
    on a routed expert outside it contributes nothing HERE. None: all."""

    n_experts: int
    top_k: int
    score: str = "softmax"      # "softmax" | "sigmoid"
    bias: bool = False
    normalise: bool = False     # kept scores / (their sum + 1e-20)
    scale: float = 1.0
    shared: bool = False
    form: str = "swiglu"        # "swiglu" | "relu2"
    zero_experts: int = 0
    held: Optional[Tuple[int, int]] = None

    @property
    def held_range(self) -> Tuple[int, int]:
        return self.held if self.held is not None else (0, self.n_experts)

    @property
    def partial(self) -> bool:
        """Some of the router's outputs are no matrix of this layer."""
        first, past = self.held_range
        return bool(self.zero_experts) or past - first != self.n_experts


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


def expert_mlp(lp, y, routing: Routing, layer=None, scope: str = "mlp"):
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

    Where some of the router's outputs are no matrix of this layer
    (`routing.partial`: zero-compute experts, or a share `held` of the
    routed ones, E then the number HELD), the pairs that reach no matrix
    sort behind the last group: the grouped matmuls visit no tile for them
    (their rows of the static `[n * top_k, ...]` shape come back unwritten
    and are selected away, not multiplied by zero), a zero-compute pair
    adds `weight * y` (`zero_experts`: one scale a row), and a pair on an
    absent expert adds nothing.

    `scope` is the layer scope all of this runs under: `mlp`, or a name of
    its own for a model whose expert path runs BESIDE its dense MLPs
    (models/longcat.py).

    Returns (out [..., hidden], {"experts_hit": experts with at least one
    pair, "expert_load_max": most pairs on one expert; where
    `routing.partial`, both over the held experts, and `held_pairs`: pairs
    on an expert held here, `zero_pairs`: pairs on a zero-compute expert,
    `pairs`: all n * top_k}), the counters of THIS layer and call."""
    with jax.named_scope(scope):
        return _expert_mlp(lp, y, routing, layer)


def _expert_mlp(lp, y, routing: Routing, layer):
    K = routing.top_k
    first, past = routing.held_range
    E = past - first                    # the experts whose matrices are here
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
        picked = expert
        expert = expert.reshape(-1)                      # pair (row, k)
        if routing.partial:
            # a pair that reaches no matrix here goes to group E, behind
            # the last expert's: sorted away, and dropped from the counts
            here = (expert >= first) & (expert < past)
            zero = picked >= routing.n_experts     # on a zero-compute expert
            expert = jnp.where(here, expert - first, E)
        order = jnp.argsort(expert, stable=True)         # pairs by expert
        counts = jnp.zeros((E,), jnp.int32).at[expert].add(
            1, mode="drop" if routing.partial else None)
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
        if routing.partial:
            ys = jnp.where(here.reshape(n, K, 1), ys, 0.0)
        out = jnp.sum(ys * weight[..., None], axis=1)
    if routing.zero_experts:
        with jax.named_scope("zero_experts"):
            identity = jnp.sum(jnp.where(zero, weight, 0.0), axis=-1)
            out = out + identity[:, None] * x.astype(jnp.float32)
    if routing.shared:
        with jax.named_scope("shared_expert"):
            shared = swiglu(x, lp["blk.shared_gate"], lp["blk.shared_up"],
                            lp["blk.shared_down"]) if gated \
                else relu2_mlp(x, lp["blk.shared_up"],
                               lp["blk.shared_down"])
            out = out + shared.astype(jnp.float32)
    stats = {"experts_hit": jnp.sum(counts > 0).astype(jnp.int32),
             "expert_load_max": jnp.max(counts)}
    if routing.partial:
        stats.update(
            held_pairs=jnp.sum(counts),
            zero_pairs=jnp.sum(zero).astype(jnp.int32),
            pairs=jnp.int32(n * K))
    return out.astype(y.dtype).reshape(y.shape), stats


def step_facts(stats) -> dict:
    """`ServeModel.step_facts` of a model whose layers are `expert_mlp`s:
    `experts_hit`: distinct experts selected, summed over the layers (what
    a step must read of the expert weights); `expert_load_max`: most pairs
    on one expert in any layer. Both count every row of the step's batch,
    idle slots included (and of a prompt's bucket, its padded rows
    included): the device computes them all. Where the layers
    are `Routing.partial`, both count the HELD experts, and beside them
    `held_pairs` (pairs routed to an expert held here), `zero_pairs` (pairs
    routed to a zero-compute expert) and `pairs` (the step's rows x top_k),
    each summed over the layers."""
    facts = {"experts_hit": int(stats["experts_hit"].sum()),
             "expert_load_max": int(stats["expert_load_max"].max())}
    for name in ("held_pairs", "zero_pairs", "pairs"):
        if name in stats:
            facts[name] = int(stats[name].sum())
    return facts

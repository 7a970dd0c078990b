"""Expert parallelism: mixture-of-experts over the ``expert`` mesh axis.

No reference equivalent (SURVEY §2.3 "EP: NO"). TPU-native design follows
GShard/Switch: routing is expressed as dense one-hot einsums with a fixed
per-expert capacity — static shapes, so XLA can tile everything onto the
MXU and lower the token shuffle to all-to-all/reduce-scatter collectives
over ICI. Two surfaces:

* `MoELayer` — GSPMD flax module: expert weights carry an ``expert``
  partition annotation, dispatch/combine are einsums with sharding
  constraints, and the SPMD partitioner inserts the collectives.
* `expert_alltoall_dispatch` / `expert_alltoall_combine` — the explicit
  `lax.all_to_all` shuffle for shard_map code that wants the comm visible
  (one all-to-all each way, the EP analogue of NCCL alltoall in
  GPU MoE stacks).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

import flax.linen as nn

from horovod_tpu.parallel.mesh import AXIS_EXPERT, constrain


def top_k_gating(logits: jax.Array, k: int) -> Tuple[jax.Array, jax.Array,
                                                     jax.Array]:
    """Top-k router.

    Args:
      logits: [tokens..., E] raw router scores.
    Returns:
      (gates [..., k] normalized weights of the chosen experts,
       indices [..., k] chosen expert ids,
       aux_loss scalar — Switch-style load-balancing loss,
       E * Σ_e fraction_tokens(e) · mean_prob(e), minimized at uniform).
    """
    probs = jax.nn.softmax(logits, axis=-1)
    gates, indices = lax.top_k(probs, k)
    gates = gates / jnp.clip(gates.sum(-1, keepdims=True), 1e-9)
    E = logits.shape[-1]
    me = probs.reshape(-1, E).mean(0)
    ce = jax.nn.one_hot(indices[..., 0].reshape(-1), E).mean(0)
    aux = E * jnp.sum(me * ce)
    return gates, indices, aux


def _dispatch_combine(gates, indices, num_experts, capacity):
    """[T,k] routing → dispatch [T,E,C] {0,1} and combine [T,E,C] floats.

    Tokens beyond an expert's capacity are dropped (their combine weight
    is 0 — the residual connection carries them), the standard
    Switch/GShard overflow policy.
    """
    T, k = indices.shape
    onehot = jax.nn.one_hot(indices, num_experts, dtype=jnp.float32)
    # Priority: k-th choices claim capacity after all (k-1)-th choices.
    flat = onehot.transpose(1, 0, 2).reshape(k * T, num_experts)
    pos_flat = jnp.cumsum(flat, axis=0) - flat          # [k*T, E]
    pos = pos_flat.reshape(k, T, num_experts).transpose(1, 0, 2)
    within = (pos < capacity) * onehot                   # [T, k, E]
    slot = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                          dtype=jnp.float32)             # [T, k, E, C]
    dispatch = jnp.einsum("tke,tkec->tec", within, slot)
    combine = jnp.einsum("tk,tke,tkec->tec", gates, within, slot)
    return dispatch, combine


def real_tokens(lead, count):
    """bool [tokens]: which of the flattened tokens of an input
    [*lead, d] (lead = [.., S]) are real, where only the first
    ``count`` positions of the sequence axis are and the rest are pad
    (`models.transformer.TransformerLM.__call__`'s ``count``)."""
    return jnp.broadcast_to(jnp.arange(lead[-1]) < count,
                            lead).reshape(-1)


class MoELayer(nn.Module):
    """Mixture-of-experts MLP, experts sharded over ``expert``.

    Capacity C = ceil(k·T/E · capacity_factor) with T the global token
    count per call; dropped tokens ride the residual. The aux
    load-balancing loss is stored in the ``losses`` collection under
    ``moe_aux`` (sow), to be added to the task loss by the train step.

    ``count`` to `__call__` (traced int32 in 1 .. S; x [.., S, d] a
    chunk whose tail is pad): a pad token claims no expert's capacity.
    """

    num_experts: int
    hidden: int
    k: int = 2
    capacity_factor: float = 1.25
    dtype: Any = None
    activation: Callable = nn.gelu

    @nn.compact
    def __call__(self, x: jax.Array,
                 count: Optional[jax.Array] = None) -> jax.Array:
        *lead, d = x.shape
        T = 1
        for s in lead:
            T *= s
        E = self.num_experts
        capacity = max(1, math.ceil(self.capacity_factor * self.k * T / E))

        router = self.param("router", nn.initializers.lecun_normal(),
                            (d, E), jnp.float32)
        w1 = self.param(
            "w1", nn.with_partitioning(nn.initializers.lecun_normal(),
                                       (AXIS_EXPERT, None, None)),
            (E, d, self.hidden), jnp.float32)
        w2 = self.param(
            "w2", nn.with_partitioning(nn.initializers.lecun_normal(),
                                       (AXIS_EXPERT, None, None)),
            (E, self.hidden, d), jnp.float32)

        xt = x.reshape(T, d)
        logits = xt.astype(jnp.float32) @ router
        gates, indices, aux = top_k_gating(logits, self.k)
        self.sow("losses", "moe_aux", aux)
        if count is not None:       # id E: `one_hot` gives it no expert
            indices = jnp.where(real_tokens(lead, count)[:, None],
                                indices, E)

        dispatch, combine = _dispatch_combine(gates, indices, E, capacity)
        compute_dtype = self.dtype or x.dtype
        # Token shuffle in, expert MLP, shuffle out. The t-contraction
        # crosses the data axis; GSPMD lowers it to the EP all-to-all /
        # reduce-scatter pattern over ICI.
        ein = jnp.einsum("tec,td->ecd", dispatch.astype(compute_dtype),
                         xt.astype(compute_dtype))
        ein = constrain(ein, AXIS_EXPERT, None, None)
        h = self.activation(
            jnp.einsum("ecd,edh->ech", ein, w1.astype(compute_dtype)))
        out = jnp.einsum("ech,ehd->ecd", h, w2.astype(compute_dtype))
        out = constrain(out, AXIS_EXPERT, None, None)
        y = jnp.einsum("tec,ecd->td", combine.astype(compute_dtype), out)
        return y.reshape(*lead, d).astype(x.dtype)


# ---------------------------------------------------------------------------
# Explicit SPMD shuffle (inside shard_map over the ``expert`` axis).
# ---------------------------------------------------------------------------

def expert_alltoall_dispatch(expert_inputs: jax.Array,
                             *, axis_name: str = AXIS_EXPERT) -> jax.Array:
    """[E, C_local, d] per-rank dispatch buffers → each rank receives the
    buffers destined for ITS experts: [E/ep, ep·C_local, d]."""
    return lax.all_to_all(expert_inputs, axis_name, split_axis=0,
                          concat_axis=1, tiled=True)


def expert_alltoall_combine(expert_outputs: jax.Array,
                            *, axis_name: str = AXIS_EXPERT) -> jax.Array:
    """Inverse shuffle: [E/ep, ep·C_local, d] → [E, C_local, d]."""
    return lax.all_to_all(expert_outputs, axis_name, split_axis=1,
                          concat_axis=0, tiled=True)


# ---------------------------------------------------------------------------
# Dropless expert layer over the experts HELD here (one chip's share of an
# expert-parallel deployment).
# ---------------------------------------------------------------------------

def product_plan(tokens, k, routed, w_gate, impl=None):
    """`ops.grouped_matmul.grouped_product_plan` for ``tokens`` tokens
    that each choose ``k`` of ``routed`` router outputs, over held
    experts' weights ``w_gate`` [E, d, f], under the ambient mesh."""
    from horovod_tpu.ops.grouped_matmul import grouped_product_plan
    from horovod_tpu.parallel.tensor import _mesh_is_trivial
    E, d, f = w_gate.shape
    return grouped_product_plan(
        tokens, k, routed, d, f, held=E, dtype=w_gate.dtype,
        trivial_mesh=_mesh_is_trivial(), impl=impl)


def _grouped_experts(x, key, weight, w_gate, w_up, w_down, *,
                     routed=None, impl=None):
    """sum_k weight[t, k] * expert_{key[t, k]}(x[t]) over the E experts
    held (key == E: the chosen expert lives on another chip and adds
    nothing here). x [T, d]; key, weight [T, k]; w_* [E, ., .]. The
    (token, expert) pairs are sorted by expert and go through one
    grouped matrix product per projection: however uneven the
    routing, no pair is dropped. Which product - the weight-streaming
    kernel for few rows an expert, or `lax.ragged_dot` - is
    `product_plan`'s to say, from T, k and the ``routed`` router
    outputs the tokens chose among (None: the E held)."""
    from horovod_tpu.ops.grouped_matmul import expert_products
    T, k = key.shape
    E = w_gate.shape[0]
    plan = product_plan(T, k, routed or E, w_gate, impl)
    flat = key.reshape(-1)
    order = jnp.argsort(flat, stable=True)              # held pairs first
    sizes = jnp.sum(flat[:, None] == jnp.arange(E), axis=0,
                    dtype=jnp.int32)
    rows = order // k
    if plan.path == "kernel":       # whole row tiles: no group owns the pad
        rows = jnp.pad(rows, (0, -(T * k) % plan.rows))
    xs = jnp.take(x, rows, axis=0)                      # [T k, d]
    y = expert_products(xs, sizes, w_gate, w_up, w_down, plan)[:T * k]
    held = (flat < E)[order]
    # a select: the kernel leaves the rows of no group unwritten
    y = jnp.where(held[:, None],
                  y * weight.reshape(-1)[order][:, None].astype(y.dtype),
                  0)
    back = jnp.argsort(order)                           # the pairs' own order
    return jnp.take(y, back, axis=0).reshape(T, k, -1).sum(1)


@functools.lru_cache(maxsize=None)
def _make_grouped(routed, impl):
    """`grouped_experts` for one pair of its static arguments."""
    layer = functools.partial(_grouped_experts, routed=routed, impl=impl)
    merged = jax.custom_batching.custom_vmap(layer)

    @merged.def_vmap
    def _rule(axis_size, in_batched, x, key, weight, *ws):
        if any(in_batched[3:]):     # per-lane weights: nothing to merge
            axes = tuple(0 if b else None for b in in_batched)
            return jax.vmap(functools.partial(
                _grouped_experts, routed=routed, impl="lax"), axes)(
                x, key, weight, *ws), True
        x, key, weight = (
            a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
            for a, b in zip((x, key, weight), in_batched[:3]))
        y = merged(x.reshape(-1, x.shape[-1]),
                   key.reshape(-1, key.shape[-1]),
                   weight.reshape(-1, weight.shape[-1]), *ws)
        return y.reshape(axis_size, -1, y.shape[-1]), True

    # Reverse mode does not pass a `custom_vmap` (nor a Pallas call):
    # the backward is the lax formula's own, whichever product ran
    # forward.
    grouped = jax.custom_vjp(lambda *args: merged(*args))

    def fwd(*args):
        return merged(*args), args

    def bwd(args, g):
        x, key, weight, *ws = args
        _, vjp = jax.vjp(
            lambda x, weight, *ws: _grouped_experts(
                x, key, weight, *ws, routed=routed, impl="lax"),
            x, weight, *ws)
        dx, dweight, *dws = vjp(g)
        return (dx, None, dweight, *dws)

    grouped.defvjp(fwd, bwd)
    return grouped


def grouped_experts(x, key, weight, w_gate, w_up, w_down, *,
                    routed=None, impl=None):
    """`_grouped_experts`, batching itself: a `vmap` over lanes (the
    serving tick is a vmap of B = 1 applies) hands the layer every
    lane's tokens at once - one grouped product over all of them, not
    `axis_size` products of one token each - and the rule for the
    product sees all of them too. ``impl`` "lax" | "pallas" forces the
    product's path (the oracle; the kernel, in interpret mode off the
    chip). Differentiable: the backward is `lax.ragged_dot`'s, on
    either path."""
    return _make_grouped(routed, impl)(x, key, weight, w_gate, w_up,
                                       w_down)


def group_limited(pick, n_group, topk_group):
    """The DeepSeek-V3 family's group-limited choice, as a mask on the
    scores the choice is made over: ``pick`` [T, outputs] (scores, +
    bias where the gate has one) in ``n_group`` groups of consecutive
    outputs; a group's score is the sum of its two largest entries;
    the ``topk_group`` best groups are kept (ties as `lax.top_k` breaks
    them: the lower index) and every other group's entries become
    -inf, so that the `top_k` that follows chooses among the kept
    groups' outputs alone. float32 in, float32 out."""
    T, outputs = pick.shape
    grouped = pick.reshape(T, n_group, outputs // n_group)
    best2, _ = lax.top_k(grouped, 2)
    _, kept = lax.top_k(best2.sum(-1), topk_group)      # [T, topk_group]
    mask = (kept[..., None] == jnp.arange(n_group)).any(-2)
    return jnp.where(mask[..., None], grouped, -jnp.inf).reshape(
        T, outputs)


def token_chips(chosen, per_chip, real=None):
    """Summed over the tokens of ``chosen`` [T, k] (those of ``real``
    [T] bool, where given), the number of DISTINCT chips (``id //
    per_chip``) a token's k experts lie on: the fan-out of the
    exchange an expert-parallel deployment would pay. int32 scalar.
    Counted as the entries no earlier entry of the token equals (k x k
    comparisons that fuse; no sort)."""
    chips = chosen // per_chip
    same = chips[:, :, None] == chips[:, None, :]           # [T, k, k]
    k = chosen.shape[-1]
    earlier = jnp.arange(k)[None, :] < jnp.arange(k)[:, None]
    first = ~(same & earlier).any(-1)
    if real is not None:
        first &= real[:, None]
    return jnp.sum(first, dtype=jnp.int32)


class HeldExpertsMoE(nn.Module):
    """Mixture of experts as one chip of an expert-parallel deployment
    computes it: the router scores all ``num_experts``, each token's
    ``k`` experts and their weights are chosen over all of them, and
    this chip adds the part of the result that ITS experts give -
    ``held = (first, count)``, expert ids first .. first+count-1 - plus
    the shared expert that every chip computes alike. What the absent
    experts would add is left out (on a real mesh the exchange brings
    it; nothing here stands in for it). ``held=None`` holds them all.

    Routing, ``router``: "sigmoid" (the DeepSeek-V3 family's) -
    scores = sigmoid(x W_r) in float32, the k largest of scores + bias
    chosen (the bias, a parameter, takes part in the choice only);
    "softmax" (the Qwen-MoE family's) - scores = softmax(x W_r) over
    all router outputs in float32, the k largest chosen, no bias.
    ``router_bias`` overrides whether the choice has the bias (None:
    as above; LongCat's softmax router has one; A.X-K1's sigmoid
    gate - `topk_method` "none", balance by an auxiliary loss - has
    none, where DeepSeek-V3's `noaux_tc` gate is the biased one).
    ``groups = (n_group, topk_group)`` limits the choice to groups
    (`group_limited`: the router outputs in n_group groups of
    consecutive ids, a group scored by the sum of its two largest
    scores (+ bias where there is one), the topk_group best groups
    kept, the k largest chosen among their outputs); the choice is
    still over ALL router outputs, whatever is held here. None and
    (1, 1) are the unlimited choice, the same program. Weights = the chosen
    scores, normalised to sum to one unless ``normalize`` is False
    (the published `norm_topk_prob`), times ``scale`` (the published
    `routed_scaling_factor`). Experts are SwiGLU MLPs of width
    ``hidden``. Dropless: see `grouped_experts`.

    ``zero_experts`` (LongCat-Flash's `zero_expert_num`, type
    "identity"): the router has ``num_experts + zero_experts`` outputs,
    and an id past the real experts is an expert that returns the
    layer's own input. They have no weights and need no exchange, so
    every chip computes them for its own tokens - here for every
    token: one add of the input times the sum of its weights on such
    ids, not ``zero_experts`` experts. On an expert-parallel mesh this
    part must be added ONCE a token (by the token's own chip), while
    the routed parts are summed over the chips that hold the experts.
    ``held`` is a range of the real experts.

    Sows into the "moe_stats" collection (when the caller makes it
    mutable) `pairs`: the (token, expert) pairs per held expert, int32
    [count]; with ``zero_experts`` also `routed`: int32 [2], the pairs
    that fell on identity experts - they cost nothing - and all the
    pairs the tokens chose (tokens x k); with ``groups`` on a share
    (``held``) also `token_chips`: int32 scalar, `token_chips` of the
    choice at ``count`` experts a chip - the fan-out that the group
    limit exists to bound.

    ``count`` to `__call__` (traced int32 in 1 .. S; x [.., S, d] a
    chunk whose tail is pad): a pad token is routed NOWHERE - its pairs
    join no expert's group, so they add no row to the grouped product
    and no expert is read for them - and none of the sown counts
    counts a pad pair."""

    num_experts: int
    hidden: int
    k: int = 8
    held: Optional[Tuple[int, int]] = None
    shared_hidden: int = 0           # 0 = no shared expert
    router: str = "sigmoid"          # | "softmax"
    scale: float = 1.0
    dtype: Any = None
    zero_experts: int = 0
    normalize: bool = True
    router_bias: Optional[bool] = None   # None: sigmoid yes, softmax no
    groups: Optional[Tuple[int, int]] = None     # (n_group, topk_group)

    @nn.compact
    def __call__(self, x: jax.Array,
                 count: Optional[jax.Array] = None) -> jax.Array:
        from horovod_tpu.parallel.tensor import ParallelSwiGLU
        *lead, d = x.shape
        first, E = self.held or (0, self.num_experts)
        if not 0 <= first <= first + E <= self.num_experts or E < 1:
            raise ValueError(
                f"held={self.held} is no range of the {self.num_experts} "
                f"experts")
        dtype = self.dtype or x.dtype
        init = nn.initializers.lecun_normal()
        if self.router not in ("sigmoid", "softmax"):
            raise ValueError(
                f"router must be sigmoid|softmax, got {self.router!r}")
        outputs = self.num_experts + self.zero_experts
        if self.groups is not None:
            n_group, topk_group = self.groups
            if self.zero_experts:
                raise ValueError(
                    "groups (a group-limited choice) is not defined "
                    "beside zero_experts: no published gate has both")
            if (not 1 <= topk_group <= n_group or outputs % n_group
                    or topk_group * (outputs // n_group) < self.k
                    or (n_group > 1 and outputs // n_group < 2)):
                raise ValueError(
                    f"groups={self.groups} does not divide {outputs} "
                    f"router outputs into groups that can give "
                    f"{self.k} experts a token")
        router = self.param("router", init, (d, outputs), jnp.float32)

        def experts(name, shape):
            return self.param(name, nn.with_partitioning(
                init, (AXIS_EXPERT, None, None)), shape,
                jnp.float32).astype(dtype)

        w_gate = experts("w_gate", (E, d, self.hidden))
        w_up = experts("w_up", (E, d, self.hidden))
        w_down = experts("w_down", (E, self.hidden, d))

        xt = x.reshape(-1, d)
        logits = jnp.matmul(
            xt.astype(jnp.float32), router.astype(jnp.float32),
            precision=lax.Precision.HIGHEST)
        scores = (jax.nn.sigmoid(logits) if self.router == "sigmoid"
                  else jax.nn.softmax(logits, axis=-1))
        biased = (self.router == "sigmoid" if self.router_bias is None
                  else self.router_bias)
        pick = scores
        if biased:
            pick = scores + self.param(
                "router_bias", nn.initializers.zeros, (outputs,),
                jnp.float32)
        if self.groups not in (None, (1, 1)):
            pick = group_limited(pick, *self.groups)
        _, chosen = lax.top_k(pick, self.k)
        weight = jnp.take_along_axis(scores, chosen, axis=-1)
        if self.normalize:
            weight = weight / weight.sum(-1, keepdims=True)
        if self.scale != 1.0:
            weight = weight * self.scale
        local = chosen - first
        key = jnp.where((local >= 0) & (local < E), local, E)
        real = None
        if count is not None:
            real = real_tokens(lead, count)
            key = jnp.where(real[:, None], key, E)
        self.sow("moe_stats", "pairs",
                 jnp.sum(key.reshape(-1, 1) == jnp.arange(E), axis=0,
                         dtype=jnp.int32),
                 reduce_fn=lambda _, new: new, init_fn=lambda: None)
        # only where it is asked for: an apply that does not collect the
        # stats keeps the program it had (`pairs` above predates the rule)
        if (self.groups is not None and self.held is not None
                and self.is_mutable_collection("moe_stats")):
            self.sow("moe_stats", "token_chips",
                     token_chips(chosen, E, real),
                     reduce_fn=lambda _, new: new, init_fn=lambda: None)
        self.sow("intermediates", "chosen", chosen,
                 reduce_fn=lambda _, new: new, init_fn=lambda: None)
        y = grouped_experts(xt.astype(dtype), key, weight, w_gate, w_up,
                            w_down, routed=outputs)
        if self.zero_experts:
            zero = chosen >= self.num_experts
            pairs = jnp.int32(zero.size)
            if real is not None:
                zero &= real[:, None]
                pairs = real.sum(dtype=jnp.int32) * self.k
            self.sow("moe_stats", "routed",
                     jnp.stack([zero.sum(dtype=jnp.int32), pairs]),
                     reduce_fn=lambda _, new: new, init_fn=lambda: None)
            y = y + (jnp.where(zero, weight, 0.0).sum(-1, keepdims=True)
                     .astype(dtype) * xt.astype(dtype))
        if self.shared_hidden:
            y = y + ParallelSwiGLU(hidden=self.shared_hidden, out=d,
                                   dtype=dtype, name="shared")(
                xt.astype(dtype))
        return y.reshape(*lead, d).astype(x.dtype)

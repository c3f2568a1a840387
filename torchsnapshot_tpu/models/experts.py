"""The expert layer of the ``kimi_linear`` and ``glm_dsa`` families: one
device's share of an expert-parallel mixture of experts, and the routers'
correction biases, which the optimizer does not see.

- **Routing**: sigmoid scores over ``n_routed_experts``, the top
  ``experts_per_token`` by score plus a correction bias, the chosen scores
  (without the bias) renormalised and scaled by ``routed_scaling_factor``.
- **The held experts**: this device holds experts ``first_expert ..
  first_expert + n_experts`` and computes their part of the result alone,
  for the tokens routed to them (sort by expert, grouped matmuls, unsort).
- **The shared experts**: a SwiGLU on every token, its columns whatever the
  leaves hold.
- **The bias rule**: each step moves a router's correction bias by
  ``router_bias_rate * sign(mean load - load)`` over the step's assignments
  (DeepSeek-V3's auxiliary-loss-free balancing, arXiv:2412.19437 §2.1.2).

A family lays an expert layer out as a block's ``"moe"`` subtree with the
leaves :func:`moe` reads; the bias is its :data:`BIAS` leaf, float32. Both
families build their parameters from a layout of :class:`Leaf` (shape,
sharding, initialiser) and their train state and step here: adamw over
every leaf but the biases, which move by the step's loads.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .transformer import Params, TrainState, TransformerConfig, _optimizer

BIAS = "router_bias"


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array) -> jax.Array:
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def route(cfg: TransformerConfig, p: Params, x: jax.Array):
    """(chosen experts (t, k), their weights (t, k) float32, load over every
    expert (n_routed_experts,) float32)."""
    scores = jax.nn.sigmoid(x.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(p[BIAS]), cfg.experts_per_token)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return chosen, weights * cfg.routed_scaling_factor, counts(chosen, cfg.n_routed_experts)


def counts(ids: jax.Array, n: int) -> jax.Array:
    """How many of ``ids`` are each of ``0 .. n - 1``, float32: a compare and
    a sum, whose cost no value of ``ids`` changes (a scatter-add's can)."""
    return jnp.sum(ids.reshape(-1, 1) == jnp.arange(n), axis=0, dtype=jnp.float32)


def dispatch(cfg: TransformerConfig, chosen: jax.Array):
    """The (token, slot) assignments sorted by held expert, the others last:
    (the order, whether each sorted row is held, the group sizes). The others
    are counted into the last held expert's group, so the groups cover every
    row whatever the routing."""
    t, k = chosen.shape
    local = (chosen - cfg.first_expert).reshape(-1)
    held = (local >= 0) & (local < cfg.n_experts)
    order = jnp.argsort(jnp.where(held, local, cfg.n_experts), stable=True)
    sizes = counts(jnp.where(held, local, -1), cfg.n_experts).astype(jnp.int32)
    sizes = sizes.at[-1].add(t * k - jnp.sum(sizes))
    return order, jnp.take(held, order), sizes


def held_experts(cfg: TransformerConfig, p: Params, x: jax.Array, chosen: jax.Array,
                 weights: jax.Array) -> jax.Array:
    """What the held experts give, computed for the tokens routed to them
    alone: the (token, slot) assignments sorted by held expert, run through
    grouped matmuls, put back in (token, slot) order and summed by their
    weights.

    The assignments to other devices' experts go through the last held
    expert's matmuls as rows of zeros, which give zeros and add nothing to a
    gradient. So the grouped matmuls do the same work whatever the routing
    lands on: with those rows left out of every group, the step's time on a
    v5e followed how many assignments the seed's routing held (PERF.md)."""
    t, k = chosen.shape
    order, mine, sizes = dispatch(cfg, chosen)
    rows = jnp.where(mine[:, None], jnp.take(x, order // k, axis=0), 0)

    def grouped(lhs: jax.Array, w: jax.Array) -> jax.Array:
        return jax.lax.ragged_dot(lhs, w, sizes)

    out = grouped(jax.nn.silu(grouped(rows, p["w_gate"])) * grouped(rows, p["w_up"]),
                  p["w_down"])
    unsort = jnp.zeros_like(order).at[order].set(jnp.arange(t * k, dtype=order.dtype))
    out = jnp.take(out, unsort, axis=0).reshape(t, k, -1)
    return jnp.einsum("tkd,tk->td", out, weights.astype(out.dtype))


def moe(cfg: TransformerConfig, p: Params, x: jax.Array,
        family: str) -> Tuple[jax.Array, jax.Array]:
    """The layer on ``x`` (batch, seq, d): (its output, the load over every
    expert), under the named scopes ``<family>:router``, ``<family>:experts``
    and ``<family>:shared``."""
    b, s, d = x.shape
    flat = x.reshape(b * s, d)
    with jax.named_scope(f"{family}:router"):
        chosen, weights, load = route(cfg, p, flat)
    with jax.named_scope(f"{family}:experts"):
        y = held_experts(cfg, p, flat, chosen, weights)
    with jax.named_scope(f"{family}:shared"):
        y = y + swiglu(flat, p["shared_gate"], p["shared_up"], p["shared_down"])
    return y.reshape(b, s, d), load


def bias_step(cfg: TransformerConfig, bias: jax.Array, load: jax.Array) -> jax.Array:
    """A router's correction bias after a step whose assignments gave ``load``."""
    return bias + cfg.router_bias_rate * jnp.sign(jnp.mean(load) - load)


# ----------------------------------------------------------------------
# The biases beside the optimizer
# ----------------------------------------------------------------------


def trainable(params: Params) -> Params:
    """``params`` (or a tree laid out like it) without the routers' biases:
    the tree the optimizer sees."""
    return {**params, "layers": [
        {**block, "moe": {k: v for k, v in block["moe"].items() if k != BIAS}}
        if "moe" in block else block for block in params["layers"]]}


def with_biases(trained: Params, biases: List[Optional[jax.Array]]) -> Params:
    return {**trained, "layers": [
        {**block, "moe": {**block["moe"], BIAS: bias}} if bias is not None else block
        for block, bias in zip(trained["layers"], biases)]}


def biases(params: Params) -> List[Optional[jax.Array]]:
    return [block["moe"][BIAS] if "moe" in block else None for block in params["layers"]]


def moved_biases(cfg: TransformerConfig, old: List[Optional[jax.Array]],
                 loads: List[jax.Array]) -> List[Optional[jax.Array]]:
    """Each expert layer's bias moved by its load, in layer order."""
    loads = iter(loads)
    return [None if bias is None else bias_step(cfg, bias, next(loads)) for bias in old]


# ----------------------------------------------------------------------
# Parameters from a layout, the train state and the step
# ----------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Leaf:
    shape: Tuple[int, ...]
    spec: Tuple[Any, ...]
    init: str = "normal"  # normal (1/sqrt(fan_in)), ones, zeros, or a family's own
    dtype: Any = None  # None: cfg.dtype


def _is_leaf(x: Any) -> bool:
    return isinstance(x, Leaf)


def shardings(layout: Params, mesh: Mesh) -> Params:
    return jax.tree_util.tree_map(
        lambda leaf: NamedSharding(mesh, P(*leaf.spec)), layout, is_leaf=_is_leaf
    )


def init_leaf(cfg: TransformerConfig, leaf: Leaf, key: jax.Array) -> jax.Array:
    dtype = leaf.dtype or cfg.dtype
    if leaf.init == "ones":
        return jnp.ones(leaf.shape, dtype)
    if leaf.init == "zeros":
        return jnp.zeros(leaf.shape, dtype)
    std = 1.0 / math.sqrt(leaf.shape[-2])
    return (jax.random.normal(key, leaf.shape, jnp.float32) * std).astype(dtype)


def init_params(layout: Params, init: Callable[[Leaf, jax.Array], jax.Array],
                rng: jax.Array, mesh: Optional[Mesh] = None) -> Params:
    """Initialise on the device, each device materialising its own shard."""

    def _init(rng: jax.Array) -> Params:
        leaves, treedef = jax.tree_util.tree_flatten(layout, is_leaf=_is_leaf)
        keys = jax.random.split(rng, len(leaves))
        return jax.tree_util.tree_unflatten(
            treedef, [init(leaf, key) for leaf, key in zip(leaves, keys)])

    if mesh is None:
        return jax.jit(_init)(rng)
    return jax.jit(_init, out_shardings=shardings(layout, mesh))(rng)


def init_train_state(cfg: TransformerConfig, layout: Params,
                     init: Callable[[Leaf, jax.Array], jax.Array], seed: int = 0,
                     mesh: Optional[Mesh] = None) -> TrainState:
    rng = jax.random.PRNGKey(seed)
    params = init_params(layout, init, rng, mesh=mesh)
    opt = _optimizer(cfg)
    step = jnp.zeros((), dtype=jnp.int32)
    params_t = trainable(params)
    if mesh is None:
        opt_state = jax.jit(opt.init)(params_t)
    else:
        replicated = NamedSharding(mesh, P())
        opt_shardings = optax.tree_map_params(
            opt, lambda _, sharding: sharding, jax.eval_shape(opt.init, params_t),
            trainable(shardings(layout, mesh)), transform_non_params=lambda _: replicated)
        opt_state = jax.jit(opt.init, out_shardings=opt_shardings)(params_t)
        step, rng = jax.device_put((step, rng), replicated)
    return TrainState(params=params, opt_state=opt_state, step=step, rng=rng)


def make_train_step(
    cfg: TransformerConfig,
    loss_fn: Callable[[Params, List[Optional[jax.Array]], jax.Array], Tuple[jax.Array, Any]],
    mesh: Optional[Mesh] = None,
) -> Callable[[TrainState, jax.Array], Tuple[TrainState, jax.Array]]:
    """The jitted step: ``loss_fn(trainable, biases, tokens)`` -> (the loss,
    each expert layer's load), its gradient, and adamw over every leaf but
    the routers' biases, which move by the step's loads. Returns the loss."""
    opt = _optimizer(cfg)

    def train_step(state: TrainState, tokens: jax.Array) -> Tuple[TrainState, jax.Array]:
        if mesh is not None:
            tokens = jax.lax.with_sharding_constraint(tokens, NamedSharding(mesh, P("dp", None)))
        params_t, old = trainable(state.params), biases(state.params)
        (loss, loads), grads = jax.value_and_grad(loss_fn, has_aux=True)(params_t, old, tokens)
        updates, new_opt_state = opt.update(grads, state.opt_state, params_t)
        new_biases = moved_biases(cfg, old, loads)
        new_params = with_biases(optax.apply_updates(params_t, updates), new_biases)
        return (
            TrainState(params=new_params, opt_state=new_opt_state, step=state.step + 1,
                       rng=jax.random.fold_in(state.rng, state.step)),
            loss,
        )

    return jax.jit(train_step, donate_argnums=(0,))

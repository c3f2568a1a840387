"""Kimi Linear (arXiv:2510.26692; moonshotai/Kimi-Linear-48B-A3B-Instruct):
the second block family of the workload, chosen by
``TransformerConfig.family == "kimi_linear"``.

Layers by ``layer_kinds`` (``"K"`` KDA linear attention, ``"M"`` latent
attention), the first ``n_layers`` of the string taken:

- **KDA** (``ops/kda.py``): q/k/v through a depthwise causal convolution and
  SiLU, q and k L2-normalised per head, a per-channel log-decay ``g =
  -exp(A_log) * softplus(x W_f1 W_f2 + dt_bias)``, a per-head ``beta``, the
  chunked gated delta rule, then a per-head RMSNorm gated by ``sigmoid(x W_g1
  W_g2 + b_g)`` and the output projection.
- **MLA** without rotary (``mla_use_nope``) and without a query LoRA
  (``q_lora_rank`` null): keys and values from a ``kv_lora_rank`` latent, a
  ``qk_rope_head_dim`` key part shared by the heads, causal softmax.
- The first ``n_dense_layers`` layers have a dense SwiGLU of width ``d_ff``;
  every other layer an **expert layer**: sigmoid scores over
  ``n_routed_experts``, the top ``experts_per_token`` by score plus a
  correction bias, the chosen scores renormalised and scaled by
  ``routed_scaling_factor``; this device holds experts ``first_expert ..
  first_expert + n_experts`` and computes their part of the result alone, for
  the tokens routed to them (sort by expert, grouped matmuls, unsort), with
  ``n_shared_experts`` shared SwiGLU experts on every token.

The routers' correction biases are float32 leaves of ``params`` that the
optimizer does not see: each step moves them by ``router_bias_rate *
sign(mean load - load)`` over the step's assignments (DeepSeek-V3's
auxiliary-loss-free balancing, arXiv:2412.19437 §2.1.2). Every other leaf is
in ``cfg.dtype`` and trained by adamw, as the dense family's are.

Sharding follows the dense family: 2-d weights column- or row-parallel over
``tp`` with their other dimension over ``dp``, expert stacks ``(E, d, f)``
with the expert dimension over ``sp`` (ep = sp), small leaves replicated.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.attention import causal_attention
from ..ops.kda import chunk_kda
from .transformer import Params, TrainState, TransformerConfig, _optimizer

EPS = 1e-5
BIAS = "router_bias"


@dataclasses.dataclass(frozen=True)
class _Leaf:
    shape: Tuple[int, ...]
    spec: Tuple[Any, ...]
    init: str = "normal"  # normal (1/sqrt(fan_in)), ones, zeros, a_log, dt_bias
    dtype: Any = None  # None: cfg.dtype


_DENSE_ONLY = ("attn_impl", "lora_rank", "moe_every")


def _kinds(cfg: TransformerConfig) -> str:
    dense_only = [f.name for f in dataclasses.fields(cfg)
                  if f.name in _DENSE_ONLY and getattr(cfg, f.name) != f.default]
    if dense_only:
        raise ValueError(f"{dense_only} set: the dense family's fields, which the "
                         f"kimi_linear family does not read")
    kinds = cfg.layer_kinds[: cfg.n_layers]
    if len(kinds) != cfg.n_layers or set(kinds) - {"K", "M"}:
        raise ValueError(
            f"layer_kinds {cfg.layer_kinds!r} must give 'K' or 'M' for each of "
            f"{cfg.n_layers} layers"
        )
    if cfg.first_expert + cfg.n_experts > cfg.n_routed_experts or not 0 < (
        cfg.experts_per_token
    ) <= cfg.n_routed_experts:
        raise ValueError(
            f"experts {cfg.first_expert}..{cfg.first_expert + cfg.n_experts} held, "
            f"{cfg.experts_per_token} a token, of {cfg.n_routed_experts}"
        )
    return kinds


def _layout(cfg: TransformerConfig) -> Params:
    """Every parameter's shape, sharding and initialiser, laid out as the
    parameter tree."""
    d, h = cfg.d_model, cfg.n_heads
    dk = cfg.kda_head_dim
    hk = h * dk
    col, row = ("dp", "tp"), ("tp", "dp")
    layers = []
    for i, kind in enumerate(_kinds(cfg)):
        block: Dict[str, Any] = {"ln1_scale": _Leaf((d,), (None,), "ones"),
                                 "ln2_scale": _Leaf((d,), (None,), "ones")}
        if kind == "K":
            block["kda"] = {
                **{w: _Leaf((d, hk), col) for w in ("wq", "wk", "wv")},
                **{c: _Leaf((cfg.conv_kernel, hk), (None, "tp"))
                   for c in ("conv_q", "conv_k", "conv_v")},
                "f_a": _Leaf((d, dk), ("dp", None)),
                "f_b": _Leaf((dk, hk), (None, "tp")),
                "g_a": _Leaf((d, dk), ("dp", None)),
                "g_b": _Leaf((dk, hk), (None, "tp")),
                "g_bias": _Leaf((hk,), (None,), "zeros"),
                "b_proj": _Leaf((d, h), ("dp", None)),
                "a_log": _Leaf((h,), (None,), "a_log"),
                "dt_bias": _Leaf((hk,), (None,), "dt_bias"),
                "o_norm": _Leaf((dk,), (None,), "ones"),
                "wo": _Leaf((hk, d), row),
            }
        else:
            dqk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
            block["mla"] = {
                "wq": _Leaf((d, h * dqk), col),
                "wkv_a": _Leaf((d, cfg.kv_lora_rank + cfg.qk_rope_head_dim), ("dp", None)),
                "kv_norm": _Leaf((cfg.kv_lora_rank,), (None,), "ones"),
                "wkv_b": _Leaf((cfg.kv_lora_rank, h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                               (None, "tp")),
                "wo": _Leaf((h * cfg.v_head_dim, d), row),
            }
        if i < cfg.n_dense_layers:
            block["mlp"] = {"w_gate": _Leaf((d, cfg.d_ff), col),
                            "w_up": _Leaf((d, cfg.d_ff), col),
                            "w_down": _Leaf((cfg.d_ff, d), row)}
        else:
            e, f = cfg.n_experts, cfg.expert_d_ff
            fs = f * cfg.n_shared_experts
            block["moe"] = {
                "router": _Leaf((d, cfg.n_routed_experts), (None, None)),
                BIAS: _Leaf((cfg.n_routed_experts,), (None,), "zeros", jnp.float32),
                "w_gate": _Leaf((e, d, f), ("sp", "dp", "tp")),
                "w_up": _Leaf((e, d, f), ("sp", "dp", "tp")),
                "w_down": _Leaf((e, f, d), ("sp", "tp", "dp")),
                "shared_gate": _Leaf((d, fs), col),
                "shared_up": _Leaf((d, fs), col),
                "shared_down": _Leaf((fs, d), row),
            }
        layers.append(block)
    return {
        "embed": _Leaf((cfg.vocab_size, d), (None, "tp")),
        "layers": layers,
        "ln_f_scale": _Leaf((d,), (None,), "ones"),
        "unembed": _Leaf((d, cfg.vocab_size), col),
    }


def _is_leaf(x: Any) -> bool:
    return isinstance(x, _Leaf)


def param_shardings(cfg: TransformerConfig, mesh: Mesh) -> Params:
    return jax.tree_util.tree_map(
        lambda leaf: NamedSharding(mesh, P(*leaf.spec)), _layout(cfg), is_leaf=_is_leaf
    )


def _init_leaf(cfg: TransformerConfig, leaf: _Leaf, key: jax.Array) -> jax.Array:
    dtype = leaf.dtype or cfg.dtype
    if leaf.init == "ones":
        return jnp.ones(leaf.shape, dtype)
    if leaf.init == "zeros":
        return jnp.zeros(leaf.shape, dtype)
    if leaf.init == "a_log":  # A ~ U(1, 16), as FLA's KDA initialises it
        return jnp.log(jax.random.uniform(key, leaf.shape, jnp.float32, 1.0, 16.0)).astype(dtype)
    if leaf.init == "dt_bias":  # softplus^-1 of dt ~ logU(1e-3, 1e-1), as FLA's
        dt = jnp.exp(jax.random.uniform(key, leaf.shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    std = 1.0 / math.sqrt(leaf.shape[-2])
    return (jax.random.normal(key, leaf.shape, jnp.float32) * std).astype(dtype)


def init_params(cfg: TransformerConfig, rng: jax.Array, mesh: Optional[Mesh] = None) -> Params:
    """Initialise on the device, each device materialising its own shard."""
    layout = _layout(cfg)

    def _init(rng: jax.Array) -> Params:
        leaves, treedef = jax.tree_util.tree_flatten(layout, is_leaf=_is_leaf)
        keys = jax.random.split(rng, len(leaves))
        return jax.tree_util.tree_unflatten(
            treedef, [_init_leaf(cfg, leaf, key) for leaf, key in zip(leaves, keys)])

    if mesh is None:
        return jax.jit(_init)(rng)
    return jax.jit(_init, out_shardings=param_shardings(cfg, mesh))(rng)


# ----------------------------------------------------------------------
# Forward
# ----------------------------------------------------------------------


def _rmsnorm(x: jax.Array, scale: jax.Array) -> jax.Array:
    x32 = x.astype(jnp.float32)
    x32 = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + EPS)
    return x32.astype(x.dtype) * scale


def _l2norm(x: jax.Array) -> jax.Array:
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.sum(jnp.square(x32), axis=-1, keepdims=True) + 1e-6)).astype(
        x.dtype)


def _swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array) -> jax.Array:
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def _causal_conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """Depthwise causal convolution over the sequence: x (b, s, c), w (taps, c)."""
    taps, s = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(w[j] * padded[:, j:j + s] for j in range(taps))


def _kda(cfg: TransformerConfig, p: Params, x: jax.Array) -> jax.Array:
    b, s, _ = x.shape
    h, dk = cfg.n_heads, cfg.kda_head_dim

    def branch(w: str, conv: str) -> jax.Array:
        return jax.nn.silu(_causal_conv(x @ p[w], p[conv])).reshape(b, s, h, dk)

    q = _l2norm(branch("wq", "conv_q"))
    k = _l2norm(branch("wk", "conv_k"))
    v = branch("wv", "conv_v")
    # dt_bias is added in float32, as FLA's fused KDA gate does.
    f = (x @ p["f_a"] @ p["f_b"]).astype(jnp.float32) + p["dt_bias"].astype(jnp.float32)
    g = -jnp.exp(p["a_log"].astype(jnp.float32))[:, None] * jax.nn.softplus(
        f.reshape(b, s, h, dk))
    beta = jax.nn.sigmoid((x @ p["b_proj"]).astype(jnp.float32))
    o = chunk_kda(q, k, v, g, beta, scale=dk ** -0.5)
    gate = jax.nn.sigmoid(x @ p["g_a"] @ p["g_b"] + p["g_bias"]).reshape(b, s, h, dk)
    o = _rmsnorm(o, p["o_norm"]) * gate
    return o.reshape(b, s, h * dk) @ p["wo"]


def _mla(cfg: TransformerConfig, p: Params, x: jax.Array) -> jax.Array:
    b, s, _ = x.shape
    h, dn, dr, dv, r = (cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                        cfg.v_head_dim, cfg.kv_lora_rank)
    q = (x @ p["wq"]).reshape(b, s, h, dn + dr)
    latent = x @ p["wkv_a"]
    c = _rmsnorm(latent[..., :r], p["kv_norm"])
    kv = (c @ p["wkv_b"]).reshape(b, s, h, dn + dv)
    k_pe = jnp.broadcast_to(latent[:, :, None, r:], (b, s, h, dr))
    k = jnp.concatenate([kv[..., :dn], k_pe], axis=-1)
    attn = causal_attention(q, k, kv[..., dn:])  # scale 1/sqrt(dn + dr), from q
    return attn.reshape(b, s, h * dv) @ p["wo"]


def _route(cfg: TransformerConfig, p: Params, x: jax.Array):
    """(chosen experts (t, k), their weights (t, k) float32, load over every
    expert (n_routed_experts,) float32)."""
    scores = jax.nn.sigmoid(x.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(p[BIAS]), cfg.experts_per_token)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return chosen, weights * cfg.routed_scaling_factor, _counts(chosen, cfg.n_routed_experts)


def _counts(ids: jax.Array, n: int) -> jax.Array:
    """How many of ``ids`` are each of ``0 .. n - 1``, float32: a compare and
    a sum, whose cost no value of ``ids`` changes (a scatter-add's can)."""
    return jnp.sum(ids.reshape(-1, 1) == jnp.arange(n), axis=0, dtype=jnp.float32)


def _dispatch(cfg: TransformerConfig, chosen: jax.Array):
    """The (token, slot) assignments sorted by held expert, the others last:
    (the order, whether each sorted row is held, the group sizes). The others
    are counted into the last held expert's group, so the groups cover every
    row whatever the routing."""
    t, k = chosen.shape
    local = (chosen - cfg.first_expert).reshape(-1)
    held = (local >= 0) & (local < cfg.n_experts)
    order = jnp.argsort(jnp.where(held, local, cfg.n_experts), stable=True)
    sizes = _counts(jnp.where(held, local, -1), cfg.n_experts).astype(jnp.int32)
    sizes = sizes.at[-1].add(t * k - jnp.sum(sizes))
    return order, jnp.take(held, order), sizes


def _held_experts(cfg: TransformerConfig, p: Params, x: jax.Array, chosen: jax.Array,
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
    order, mine, sizes = _dispatch(cfg, chosen)
    rows = jnp.where(mine[:, None], jnp.take(x, order // k, axis=0), 0)

    def grouped(lhs: jax.Array, w: jax.Array) -> jax.Array:
        return jax.lax.ragged_dot(lhs, w, sizes)

    out = grouped(jax.nn.silu(grouped(rows, p["w_gate"])) * grouped(rows, p["w_up"]),
                  p["w_down"])
    unsort = jnp.zeros_like(order).at[order].set(jnp.arange(t * k, dtype=order.dtype))
    out = jnp.take(out, unsort, axis=0).reshape(t, k, -1)
    return jnp.einsum("tkd,tk->td", out, weights.astype(out.dtype))


def _moe(cfg: TransformerConfig, p: Params, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    b, s, d = x.shape
    flat = x.reshape(b * s, d)
    with jax.named_scope("kimi:router"):
        chosen, weights, load = _route(cfg, p, flat)
    with jax.named_scope("kimi:experts"):
        y = _held_experts(cfg, p, flat, chosen, weights)
    with jax.named_scope("kimi:shared"):
        y = y + _swiglu(flat, p["shared_gate"], p["shared_up"], p["shared_down"])
    return y.reshape(b, s, d), load


def forward(cfg: TransformerConfig, params: Params,
            tokens: jax.Array) -> Tuple[jax.Array, List[jax.Array]]:
    """Token ids ``(batch, seq)`` -> (logits ``(batch, seq, vocab)`` float32,
    each expert layer's load over every expert). On a mesh, XLA lays the
    activations out from the parameters' shardings."""
    x = jnp.take(params["embed"], tokens, axis=0)
    loads = []
    for kind, block in zip(_kinds(cfg), params["layers"]):
        h = _rmsnorm(x, block["ln1_scale"])
        if kind == "K":
            with jax.named_scope("kimi:kda"):
                x = x + _kda(cfg, block["kda"], h)
        else:
            with jax.named_scope("kimi:mla"):
                x = x + _mla(cfg, block["mla"], h)
        h = _rmsnorm(x, block["ln2_scale"])
        if "moe" in block:
            y, load = _moe(cfg, block["moe"], h)
            loads.append(load)
        else:
            y = _swiglu(h, **block["mlp"])
        x = x + y
    x = _rmsnorm(x, params["ln_f_scale"])
    return jnp.einsum("bsd,dv->bsv", x, params["unembed"]).astype(jnp.float32), loads


# ----------------------------------------------------------------------
# Training state + step
# ----------------------------------------------------------------------


def _trainable(params: Params) -> Params:
    """``params`` (or a tree laid out like it) without the routers' biases:
    the tree the optimizer sees."""
    return {**params, "layers": [
        {**block, "moe": {k: v for k, v in block["moe"].items() if k != BIAS}}
        if "moe" in block else block for block in params["layers"]]}


def _with_biases(trainable: Params, biases: List[Optional[jax.Array]]) -> Params:
    return {**trainable, "layers": [
        {**block, "moe": {**block["moe"], BIAS: bias}} if bias is not None else block
        for block, bias in zip(trainable["layers"], biases)]}


def _biases(params: Params) -> List[Optional[jax.Array]]:
    return [block["moe"][BIAS] if "moe" in block else None for block in params["layers"]]


def init_train_state(cfg: TransformerConfig, seed: int = 0,
                     mesh: Optional[Mesh] = None) -> TrainState:
    rng = jax.random.PRNGKey(seed)
    params = init_params(cfg, rng, mesh=mesh)
    opt = _optimizer(cfg)
    step = jnp.zeros((), dtype=jnp.int32)
    trainable = _trainable(params)
    if mesh is None:
        opt_state = jax.jit(opt.init)(trainable)
    else:
        replicated = NamedSharding(mesh, P())
        opt_shardings = optax.tree_map_params(
            opt, lambda _, sharding: sharding, jax.eval_shape(opt.init, trainable),
            _trainable(param_shardings(cfg, mesh)), transform_non_params=lambda _: replicated)
        opt_state = jax.jit(opt.init, out_shardings=opt_shardings)(trainable)
        step, rng = jax.device_put((step, rng), replicated)
    return TrainState(params=params, opt_state=opt_state, step=step, rng=rng)


def make_train_step(
    cfg: TransformerConfig, mesh: Optional[Mesh] = None
) -> Callable[[TrainState, jax.Array], Tuple[TrainState, jax.Array]]:
    """The jitted step: forward, loss, backward and adamw over every leaf but
    the routers' biases, which move by the step's loads."""
    opt = _optimizer(cfg)

    def loss_fn(trainable: Params, biases: List[Optional[jax.Array]], tokens: jax.Array):
        logits, loads = forward(cfg, _with_biases(trainable, biases), tokens)
        losses = optax.softmax_cross_entropy_with_integer_labels(logits[:, :-1], tokens[:, 1:])
        return jnp.mean(losses), loads

    def train_step(state: TrainState, tokens: jax.Array) -> Tuple[TrainState, jax.Array]:
        if mesh is not None:
            tokens = jax.lax.with_sharding_constraint(tokens, NamedSharding(mesh, P("dp", None)))
        trainable, biases = _trainable(state.params), _biases(state.params)
        (loss, loads), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            trainable, biases, tokens)
        updates, new_opt_state = opt.update(grads, state.opt_state, trainable)
        loads = iter(loads)
        new_biases = []
        for bias in biases:
            if bias is not None:
                load = next(loads)
                bias = bias + cfg.router_bias_rate * jnp.sign(jnp.mean(load) - load)
            new_biases.append(bias)
        new_params = _with_biases(optax.apply_updates(trainable, updates), new_biases)
        return (
            TrainState(params=new_params, opt_state=new_opt_state, step=state.step + 1,
                       rng=jax.random.fold_in(state.rng, state.step)),
            loss,
        )

    return jax.jit(train_step, donate_argnums=(0,))

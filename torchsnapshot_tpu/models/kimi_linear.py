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
  every other layer an **expert layer** (``models/experts.py``, shared with
  the ``glm_dsa`` family): sigmoid scores over
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
import functools
import math
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh

from ..ops.attention import causal_attention
from ..ops.kda import chunk_kda
from . import experts
from .experts import BIAS
from .experts import Leaf as _Leaf
from .experts import biases as _biases  # noqa: F401 - tests route through these
from .experts import dispatch as _dispatch  # noqa: F401
from .experts import route as _route  # noqa: F401
from .experts import swiglu as _swiglu
from .experts import trainable as _trainable  # noqa: F401
from .experts import with_biases as _with_biases
from .transformer import Params, TrainState, TransformerConfig

EPS = 1e-5


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


def param_shardings(cfg: TransformerConfig, mesh: Mesh) -> Params:
    return experts.shardings(_layout(cfg), mesh)


def _init_leaf(cfg: TransformerConfig, leaf: _Leaf, key: jax.Array) -> jax.Array:
    dtype = leaf.dtype or cfg.dtype
    if leaf.init == "a_log":  # A ~ U(1, 16), as FLA's KDA initialises it
        return jnp.log(jax.random.uniform(key, leaf.shape, jnp.float32, 1.0, 16.0)).astype(dtype)
    if leaf.init == "dt_bias":  # softplus^-1 of dt ~ logU(1e-3, 1e-1), as FLA's
        dt = jnp.exp(jax.random.uniform(key, leaf.shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return experts.init_leaf(cfg, leaf, key)


def init_params(cfg: TransformerConfig, rng: jax.Array, mesh: Optional[Mesh] = None) -> Params:
    """Initialise on the device, each device materialising its own shard."""
    return experts.init_params(_layout(cfg), functools.partial(_init_leaf, cfg), rng, mesh)


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


def _moe(cfg: TransformerConfig, p: Params, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    return experts.moe(cfg, p, x, "kimi")


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


def init_train_state(cfg: TransformerConfig, seed: int = 0,
                     mesh: Optional[Mesh] = None) -> TrainState:
    return experts.init_train_state(cfg, _layout(cfg), functools.partial(_init_leaf, cfg),
                                    seed, mesh)


def _loss(cfg: TransformerConfig, trainable: Params, biases: List[Optional[jax.Array]],
          tokens: jax.Array):
    logits, loads = forward(cfg, _with_biases(trainable, biases), tokens)
    losses = optax.softmax_cross_entropy_with_integer_labels(logits[:, :-1], tokens[:, 1:])
    return jnp.mean(losses), loads


def make_train_step(
    cfg: TransformerConfig, mesh: Optional[Mesh] = None
) -> Callable[[TrainState, jax.Array], Tuple[TrainState, jax.Array]]:
    """The jitted step: forward, loss, backward and adamw over every leaf but
    the routers' biases, which move by the step's loads."""
    return experts.make_train_step(cfg, functools.partial(_loss, cfg), mesh)

"""GLM-5 (zai-org/GLM-5, ``model_type`` ``glm_moe_dsa``): the third block
family of the workload, chosen by ``TransformerConfig.family == "glm_dsa"``.
Every layer is latent attention made sparse by DeepSeek-V3.2's DSA
(arXiv:2512.02556), then a dense SwiGLU (the first ``n_dense_layers``) or an
expert layer (``models/experts.py``, shared with ``kimi_linear``).

- **MLA with a query latent**: ``c_Q = RMSNorm(x W_qa)`` (``q_lora_rank``),
  ``q = c_Q W_qb`` with ``qk_nope_head_dim`` + ``qk_rope_head_dim`` a head;
  ``[c_KV, k_r] = x W_kva``, ``c_KV = RMSNorm(c_KV)``, ``k_r`` one rotary
  key part shared by the heads; ``[k_nope, v] = c_KV W_kvb``; scores
  ``(q_nope.k_nope + q_rope.k_r) / sqrt(qk_nope + qk_rope)``; ``W_o``.
- **The lightning indexer**, on inputs detached from the main graph:
  ``q^I = c_Q W_iq`` (``index_n_heads`` x ``index_head_dim``), ``k^I =
  LayerNorm(x W_ik)`` (one head), rotary on the first ``qk_rope_head_dim``
  dims of both, ``w = x W_iw / sqrt(index_n_heads * index_head_dim)``, and
  ``I[t, s] = sum_j w[t, j] ReLU(q^I[t, j] . k^I[s])`` over ``s <= t``, in
  float32 (a bfloat16 score reorders keys near the cut).
- **Selection and sparse attention**: query ``t`` attends only to ``S_t``,
  the ``index_topk`` keys of largest ``I[t, s]``. It is computed as causal
  attention masked to ``S_t``, a block of :data:`QUERY_BLOCK` queries at a
  time, each block rematerialised in the backward pass.
- **The indexer's loss** (DSA's sparse training stage): ``KL(p_t ||
  softmax(I[t, S_t]))`` with ``p_t`` the main attention summed over the held
  heads and normalised over ``S_t``, detached; its mean over the queries,
  summed over the layers, is added to the loss with weight
  :data:`INDEX_LOSS_WEIGHT`. Its gradient reaches the indexer alone, and the
  language-model loss's never does.

Rotary: GLM-5's, theta :data:`ROPE_THETA` over interleaved pairs ``(x[2i],
x[2i+1])`` (``rope_interleave`` and ``indexer_rope_interleave``).

One device's share of a tensor-parallel job: ``n_heads`` counts the heads it
holds (``W_qb``, ``W_kvb`` and ``W_o`` by head; the latents, their norms and
the indexer whole), and it holds ``1 / tp_size`` of the columns of the dense
SwiGLU (``d_ff``) and of the shared expert (``expert_d_ff *
n_shared_experts``). What the other ranks would add is left out.

Each layer's attention and FFN halves are rematerialised in the backward
pass, and the loss over the vocabulary slice is taken :data:`LOSS_CHUNK`
positions at a time, so that a step at 4,096 tokens keeps its activations
beside a state that fills most of a chip.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.scipy.special import xlogy
from jax.sharding import Mesh

from . import experts
from .experts import BIAS, Leaf
from .transformer import Params, TrainState, TransformerConfig

EPS = 1e-5
LN_EPS = 1e-6
QUERY_BLOCK = 512
LOSS_CHUNK = 1024
INDEX_LOSS_WEIGHT = 1.0
ROPE_THETA = 1e6
_NEG = -jnp.inf

_DENSE_ONLY = ("attn_impl", "lora_rank", "moe_every")


def _check(cfg: TransformerConfig) -> None:
    dense_only = [f.name for f in dataclasses.fields(cfg)
                  if f.name in _DENSE_ONLY and getattr(cfg, f.name) != f.default]
    if dense_only:
        raise ValueError(f"{dense_only} set: the dense family's fields, which the "
                         f"glm_dsa family does not read")
    if cfg.first_expert + cfg.n_experts > cfg.n_routed_experts or not 0 < (
        cfg.experts_per_token
    ) <= cfg.n_routed_experts:
        raise ValueError(
            f"experts {cfg.first_expert}..{cfg.first_expert + cfg.n_experts} held, "
            f"{cfg.experts_per_token} a token, of {cfg.n_routed_experts}"
        )
    widths = {"d_ff": cfg.d_ff, "expert_d_ff * n_shared_experts":
              cfg.expert_d_ff * cfg.n_shared_experts}
    for name, width in widths.items():
        if cfg.tp_size < 1 or width % cfg.tp_size:
            raise ValueError(f"{name} {width} is not a whole number of tp_size "
                             f"{cfg.tp_size} shares")
    if not (cfg.q_lora_rank and cfg.index_n_heads and cfg.index_head_dim
            and cfg.index_topk) or cfg.qk_rope_head_dim > cfg.index_head_dim:
        raise ValueError("q_lora_rank, index_n_heads, index_head_dim and index_topk "
                         "must be set, and index_head_dim hold the rotary dims")


def _layout(cfg: TransformerConfig) -> Params:
    """Every parameter's shape, sharding and initialiser, laid out as the
    parameter tree."""
    _check(cfg)
    d, h, r, rq = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank, cfg.q_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    col, row = ("dp", "tp"), ("tp", "dp")
    f_dense = cfg.d_ff // cfg.tp_size
    f_shared = cfg.expert_d_ff * cfg.n_shared_experts // cfg.tp_size
    layers = []
    for i in range(cfg.n_layers):
        block: Dict[str, Any] = {
            "ln1_scale": Leaf((d,), (None,), "ones"),
            "ln2_scale": Leaf((d,), (None,), "ones"),
            "mla": {
                "wq_a": Leaf((d, rq), ("dp", None)),
                "q_norm": Leaf((rq,), (None,), "ones"),
                "wq_b": Leaf((rq, h * (dn + dr)), (None, "tp")),
                "wkv_a": Leaf((d, r + dr), ("dp", None)),
                "kv_norm": Leaf((r,), (None,), "ones"),
                "wkv_b": Leaf((r, h * (dn + dv)), (None, "tp")),
                "wo": Leaf((h * dv, d), row),
            },
            "indexer": {
                "wq": Leaf((rq, cfg.index_n_heads * cfg.index_head_dim), (None, "tp")),
                "wk": Leaf((d, cfg.index_head_dim), ("dp", None)),
                "k_norm_scale": Leaf((cfg.index_head_dim,), (None,), "ones"),
                "k_norm_bias": Leaf((cfg.index_head_dim,), (None,), "zeros"),
                "w": Leaf((d, cfg.index_n_heads), ("dp", None)),
            },
        }
        if i < cfg.n_dense_layers:
            block["mlp"] = {"w_gate": Leaf((d, f_dense), col),
                            "w_up": Leaf((d, f_dense), col),
                            "w_down": Leaf((f_dense, d), row)}
        else:
            e, f = cfg.n_experts, cfg.expert_d_ff
            block["moe"] = {
                "router": Leaf((d, cfg.n_routed_experts), (None, None)),
                BIAS: Leaf((cfg.n_routed_experts,), (None,), "zeros", jnp.float32),
                "w_gate": Leaf((e, d, f), ("sp", "dp", "tp")),
                "w_up": Leaf((e, d, f), ("sp", "dp", "tp")),
                "w_down": Leaf((e, f, d), ("sp", "tp", "dp")),
                "shared_gate": Leaf((d, f_shared), col),
                "shared_up": Leaf((d, f_shared), col),
                "shared_down": Leaf((f_shared, d), row),
            }
        layers.append(block)
    return {
        "embed": Leaf((cfg.vocab_size, d), (None, "tp")),
        "layers": layers,
        "ln_f_scale": Leaf((d,), (None,), "ones"),
        "unembed": Leaf((d, cfg.vocab_size), col),
    }


def param_shardings(cfg: TransformerConfig, mesh: Mesh) -> Params:
    return experts.shardings(_layout(cfg), mesh)


def init_params(cfg: TransformerConfig, rng: jax.Array, mesh: Optional[Mesh] = None) -> Params:
    """Initialise on the device, each device materialising its own shard."""
    return experts.init_params(_layout(cfg), functools.partial(experts.init_leaf, cfg), rng,
                               mesh)


# ----------------------------------------------------------------------
# Forward
# ----------------------------------------------------------------------


def _rmsnorm(x: jax.Array, scale: jax.Array) -> jax.Array:
    x32 = x.astype(jnp.float32)
    x32 = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + EPS)
    return x32.astype(x.dtype) * scale


def _layernorm(x: jax.Array, scale: jax.Array, bias: jax.Array) -> jax.Array:
    """float32 in, float32 out."""
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * scale.astype(jnp.float32) + bias.astype(
        jnp.float32)


def rope(x: jax.Array) -> jax.Array:
    """Rotary position embedding over the last axis of ``x`` (batch, seq,
    ..., dims), positions ``0 .. seq - 1``, interleaved pairs, in float32,
    returned in ``x.dtype``."""
    s, dims = x.shape[1], x.shape[-1]
    inv = ROPE_THETA ** (-jnp.arange(0, dims, 2, dtype=jnp.float32) / dims)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv  # (s, dims / 2)
    ang = ang.reshape((1, s) + (1,) * (x.ndim - 3) + (dims // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (dims // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape).astype(
        x.dtype)


def _projections(cfg: TransformerConfig, p: Params, ip: Params, x: jax.Array):
    """MLA's queries, keys and values (batch, seq, heads, dims), and the
    indexer's float32 queries (b, s, index heads, dims), key (b, s, dims) and
    head weights (b, s, index heads), from inputs detached."""
    b, s, _ = x.shape
    h, dn, dr, dv, r = (cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                        cfg.v_head_dim, cfg.kv_lora_rank)
    with jax.named_scope("glm:mla"):
        c_q = _rmsnorm(x @ p["wq_a"], p["q_norm"])
        q = (c_q @ p["wq_b"]).reshape(b, s, h, dn + dr)
        q = jnp.concatenate([q[..., :dn], rope(q[..., dn:])], axis=-1)
        latent = x @ p["wkv_a"]
        c_kv = _rmsnorm(latent[..., :r], p["kv_norm"])
        k_r = rope(latent[..., r:])
        kv = (c_kv @ p["wkv_b"]).reshape(b, s, h, dn + dv)
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_r[:, :, None], (b, s, h, dr))],
                            axis=-1)
    with jax.named_scope("glm:indexer"):
        x_i, c_i = jax.lax.stop_gradient(x), jax.lax.stop_gradient(c_q)
        hi, di = cfg.index_n_heads, cfg.index_head_dim

        def rotate(t: jax.Array) -> jax.Array:
            return jnp.concatenate([rope(t[..., :dr]), t[..., dr:]], -1)

        qi = rotate(jnp.matmul(c_i, ip["wq"], preferred_element_type=jnp.float32)
                    .reshape(b, s, hi, di))
        ki = rotate(_layernorm(jnp.matmul(x_i, ip["wk"], preferred_element_type=jnp.float32),
                               ip["k_norm_scale"], ip["k_norm_bias"]))
        w = jnp.matmul(x_i, ip["w"], preferred_element_type=jnp.float32) * (hi * di) ** -0.5
    return q, k, kv[..., dn:], qi, ki, w


def index_scores(qi: jax.Array, ki: jax.Array, w: jax.Array) -> jax.Array:
    """``I[b, t, s] = sum_j w[b, t, j] ReLU(qi[b, t, j] . ki[b, s])``, float32
    at full precision: qi (b, t, heads, dims), ki (b, s, dims), w (b, t,
    heads)."""
    dots = jnp.einsum("btjd,bsd->btjs", qi, ki, precision=jax.lax.Precision.HIGHEST)
    return jnp.einsum("btjs,btj->bts", jax.nn.relu(dots), w,
                      precision=jax.lax.Precision.HIGHEST)


def selection(scores: jax.Array, q_pos: jax.Array, topk: int) -> jax.Array:
    """Which keys each query keeps: of ``s <= t``, the ``topk`` with the
    largest scores (all of them where there are fewer; of equal scores the
    earlier key). scores (b, t, s), q_pos (t,) the queries' positions."""
    b, t, s = scores.shape
    causal = jnp.arange(s)[None, :] <= q_pos[:, None]
    masked = jnp.where(causal, jax.lax.stop_gradient(scores), _NEG)
    _, chosen = jax.lax.top_k(masked, min(topk, s))
    keep = jnp.zeros((b, t, s), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(t)[None, :, None], chosen].set(True)
    return causal & keep


def _attend_block(cfg: TransformerConfig, k: jax.Array, v: jax.Array, ki: jax.Array,
                  q: jax.Array, qi: jax.Array, w: jax.Array, q_pos: jax.Array):
    """One block of queries: (their attention output (b, t, h, dv), the
    indexer's KL summed over the block's queries)."""
    with jax.named_scope("glm:indexer"):
        scores = index_scores(qi, ki, w)
    with jax.named_scope("glm:select"):
        keep = selection(scores, q_pos, cfg.index_topk)
    with jax.named_scope("glm:sparse_attn"):
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
        logits = logits / math.sqrt(q.shape[-1])
        probs = jax.nn.softmax(jnp.where(keep[:, None], logits, _NEG), axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    with jax.named_scope("glm:indexer"):
        target = jax.lax.stop_gradient(jnp.mean(probs, axis=1))
        log_q = jax.nn.log_softmax(jnp.where(keep, scores, _NEG), axis=-1)
        # The inner where keeps -inf out of the product: its gradient
        # would be nan where the outer one masks the value.
        kl = jnp.sum(jnp.where(keep, xlogy(target, target) - target * jnp.where(keep, log_q, 0.0),
                               0.0))
    return out, kl


def sparse_attention(cfg: TransformerConfig, q, k, v, qi, ki, w,
                     query_block: int = QUERY_BLOCK):
    """DSA over the held heads, ``query_block`` queries at a time, each block
    rematerialised in the backward pass: (the attention output (b, s, h,
    dv), the indexer's KL, mean over the queries)."""
    b, s = q.shape[:2]
    bq = min(query_block, s)
    if s % bq:
        raise ValueError(f"seq {s} is not a multiple of the query block {bq}")
    n = s // bq

    def blocks(x: jax.Array) -> jax.Array:
        return jnp.moveaxis(x.reshape((b, n, bq) + x.shape[2:]), 1, 0)

    body = jax.checkpoint(lambda qb, qib, wb, pos: _attend_block(cfg, k, v, ki, qb, qib, wb, pos))

    def step(total, xs):
        out, kl = body(*xs)
        return total + kl, out

    kl, out = jax.lax.scan(step, jnp.zeros((), jnp.float32),
                           (blocks(q), blocks(qi), blocks(w), jnp.arange(s).reshape(n, bq)))
    out = jnp.moveaxis(out, 0, 1).reshape((b, s) + out.shape[3:])
    return out, kl / (b * s)


def attention(cfg: TransformerConfig, block: Params, x: jax.Array,
              query_block: int = QUERY_BLOCK) -> Tuple[jax.Array, jax.Array]:
    """The attention half of a layer on its normed input: (its output, the
    indexer's KL)."""
    b, s, _ = x.shape
    q, k, v, qi, ki, w = _projections(cfg, block["mla"], block["indexer"], x)
    out, kl = sparse_attention(cfg, q, k, v, qi, ki, w, query_block)
    with jax.named_scope("glm:mla"):
        return out.reshape(b, s, -1) @ block["mla"]["wo"], kl


def _attention_half(cfg: TransformerConfig, block: Params, x: jax.Array):
    y, kl = attention(cfg, block, _rmsnorm(x, block["ln1_scale"]))
    return x + y, kl


def _ffn_half(cfg: TransformerConfig, block: Params, x: jax.Array):
    h = _rmsnorm(x, block["ln2_scale"])
    if "moe" in block:
        y, load = experts.moe(cfg, block["moe"], h, "glm")
        return x + y, load
    return x + experts.swiglu(h, **block["mlp"]), None


def hidden(cfg: TransformerConfig, params: Params, tokens: jax.Array):
    """Token ids ``(batch, seq)`` -> (the final normed hidden states, each
    expert layer's load over every expert, each layer's indexer KL)."""
    _check(cfg)
    x = jnp.take(params["embed"], tokens, axis=0)
    attn = jax.checkpoint(lambda block, x: _attention_half(cfg, block, x))
    ffn = jax.checkpoint(lambda block, x: _ffn_half(cfg, block, x))
    loads, kls = [], []
    for block in params["layers"]:
        x, kl = attn(block, x)
        x, load = ffn(block, x)
        kls.append(kl)
        if load is not None:
            loads.append(load)
    return _rmsnorm(x, params["ln_f_scale"]), loads, kls


def forward(cfg: TransformerConfig, params: Params, tokens: jax.Array):
    """Token ids -> (logits ``(batch, seq, vocab)`` float32, the loads, the
    indexer KLs)."""
    x, loads, kls = hidden(cfg, params, tokens)
    return jnp.einsum("bsd,dv->bsv", x, params["unembed"]).astype(jnp.float32), loads, kls


def lm_loss(x: jax.Array, unembed: jax.Array, tokens: jax.Array,
            chunk: int = LOSS_CHUNK) -> jax.Array:
    """Mean cross-entropy of next-token prediction over the vocabulary slice,
    ``chunk`` positions at a time, each chunk's logits rematerialised in the
    backward pass."""
    b, s, d = x.shape
    targets = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1).reshape(-1)
    weight = jnp.broadcast_to(jnp.arange(s) < s - 1, (b, s)).reshape(-1).astype(jnp.float32)
    rows = x.reshape(b * s, d)
    c = min(chunk, b * s)
    if (b * s) % c:
        raise ValueError(f"{b * s} positions are not a multiple of the loss chunk {c}")

    @jax.checkpoint
    def piece(xs, ts, ws):
        logits = (xs @ unembed).astype(jnp.float32)
        return jnp.sum(optax.softmax_cross_entropy_with_integer_labels(logits, ts) * ws)

    def step(total, xs):
        return total + piece(*xs), None

    n = b * s // c
    total, _ = jax.lax.scan(step, jnp.zeros((), jnp.float32),
                            (rows.reshape(n, c, d), targets.reshape(n, c), weight.reshape(n, c)))
    return total / (b * (s - 1))


def losses(cfg: TransformerConfig, trainable: Params, biases: List[Optional[jax.Array]],
           tokens: jax.Array):
    """(the cross-entropy, each layer's indexer KL, each expert layer's
    load)."""
    x, loads, kls = hidden(cfg, experts.with_biases(trainable, biases), tokens)
    return lm_loss(x, trainable["unembed"], tokens), kls, loads


def loss_fn(cfg: TransformerConfig, trainable: Params, biases: List[Optional[jax.Array]],
            tokens: jax.Array):
    """(the step's loss: the cross-entropy plus the indexers' KL, the
    loads)."""
    ce, kls, loads = losses(cfg, trainable, biases, tokens)
    return ce + INDEX_LOSS_WEIGHT * sum(kls), loads


# ----------------------------------------------------------------------
# Training state + step
# ----------------------------------------------------------------------


def init_train_state(cfg: TransformerConfig, seed: int = 0,
                     mesh: Optional[Mesh] = None) -> TrainState:
    return experts.init_train_state(cfg, _layout(cfg), functools.partial(experts.init_leaf, cfg),
                                    seed, mesh)


def make_train_step(
    cfg: TransformerConfig, mesh: Optional[Mesh] = None
) -> Callable[[TrainState, jax.Array], Tuple[TrainState, jax.Array]]:
    """The jitted step: forward, loss (cross-entropy plus the indexers' KL),
    backward and adamw over every leaf but the routers' biases, which move by
    the step's loads. Returns the loss."""
    _check(cfg)
    return experts.make_train_step(cfg, functools.partial(loss_fn, cfg), mesh)

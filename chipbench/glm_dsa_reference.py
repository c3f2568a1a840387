"""GLM-5 written down plainly: the reference the model family ``glm_dsa``
(``torchsnapshot_tpu/models/glm_dsa.py``) is held to.

Straightforward float32 ``jax.numpy``, to be called under
``jax.default_matmul_precision("highest")``: the whole causal score matrix at
once, no query blocks, no rematerialisation, no grouped matmuls, a top-k of
its own (a stable sort of each query's scores), and nothing imported from the
library. It reads its sizes from any object with the fields of
``TransformerConfig`` that the family uses.

The equations (zai-org/GLM-5's config.json; DeepSeek-V3.2, arXiv:2512.02556,
for DSA):

- MLA with a query latent: ``c_Q = RMSNorm(x W_qa)``; ``q = c_Q W_qb``,
  ``[q_nope, q_rope]`` a head; ``[c_KV, k_r] = x W_kva``, ``c_KV =
  RMSNorm(c_KV)``; ``[k_nope, v] = c_KV W_kvb``; rotary on ``q_rope`` and
  ``k_r`` (theta 1e6 over interleaved pairs, as ``rope_parameters`` and
  ``rope_interleave`` give);
  score ``(q_nope.k_nope + q_rope.k_r) / sqrt(qk_nope + qk_rope)``.
- The indexer, on ``x`` and ``c_Q`` detached: ``q^I = c_Q W_iq``, ``k^I =
  LayerNorm(x W_ik)`` (eps 1e-6), rotary on the first ``qk_rope_head_dim``
  dims of both; ``w = x W_iw / sqrt(index_n_heads index_head_dim)``;
  ``I[t, s] = sum_j w[t, j] ReLU(q^I[t, j] . k^I[s])``.
- ``S_t``: the ``index_topk`` keys ``s <= t`` of largest ``I[t, s]`` (every
  one where there are fewer); attention softmax over ``S_t`` alone.
- The indexer's loss: ``KL(p_t || softmax(I[t, S_t]))``, ``p_t`` the
  attention probabilities averaged over the held heads, detached; mean over
  queries, summed over layers, weight 1.
- Experts: sigmoid scores over ``n_routed_experts``; the top
  ``experts_per_token`` by score plus the correction bias; the chosen scores
  renormalised and scaled by ``routed_scaling_factor``; SwiGLU experts, plus
  the shared expert on every token. The first ``n_dense_layers`` have a
  dense SwiGLU. Pre-RMSNorm (eps 1e-5) everywhere, residuals, untied head.

Departures from the published model, each the model's too:

- One chip's share of a tensor- and expert-parallel job: the ``n_heads``
  held heads, ``1 / tp_size`` of the dense and shared SwiGLU columns (as the
  leaves hold them), experts ``first_expert .. first_expert + n_experts``
  (each a masked dense SwiGLU over every token here), the vocabulary slice
  of ``vocab_size`` rows. What the other chips would add is left out; the
  KL's target averages the held heads alone.
- The correction bias is moved each step by ``router_bias_rate * sign(mean
  load - load)`` (DeepSeek-V3's auxiliary-loss-free balancing); no
  multi-token-prediction module; no FP8 and no Hadamard rotation in the
  indexer (the rotation is orthogonal and changes no dot product).
"""

import math

import jax
import jax.numpy as jnp

EPS = 1e-5
LN_EPS = 1e-6
ROPE_THETA = 1e6  # rope_parameters.rope_theta


def rmsnorm(x, scale):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * scale


def layernorm(x, scale, bias):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * scale + bias


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def rope(x):
    """Rotary over the last axis of x (batch, seq, ..., dims), position t at
    seq index t: pairs (x[2i], x[2i+1]), each turned by t * ROPE_THETA^(-2i /
    dims)."""
    s, dims = x.shape[1], x.shape[-1]
    freq = ROPE_THETA ** (-jnp.arange(dims // 2) * 2.0 / dims)
    ang = jnp.arange(s)[:, None] * freq
    ang = ang.reshape((1, s) + (1,) * (x.ndim - 3) + (dims // 2,))
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      a * jnp.sin(ang) + b * jnp.cos(ang)], axis=-1).reshape(x.shape)


def index_scores(cfg, ip, x, c_q):
    """I (batch, queries, keys) on the detached inputs, every pair."""
    b, s, _ = x.shape
    hi, di, dr = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    x, c_q = jax.lax.stop_gradient(x), jax.lax.stop_gradient(c_q)

    def rotate(t):
        return jnp.concatenate([rope(t[..., :dr]), t[..., dr:]], -1)

    qi = rotate((c_q @ ip["wq"]).reshape(b, s, hi, di))
    ki = rotate(layernorm(x @ ip["wk"], ip["k_norm_scale"], ip["k_norm_bias"]))
    w = (x @ ip["w"]) / math.sqrt(hi * di)
    dots = jnp.einsum("btjd,bsd->btjs", qi, ki)
    return jnp.einsum("btjs,btj->bts", jax.nn.relu(dots), w)


def select(cfg, scores):
    """S_t as a mask (batch, queries, keys): each query's causal keys ranked
    by a stable descending sort of their scores, the first ``index_topk``."""
    s = scores.shape[-1]
    causal = jnp.tril(jnp.ones((s, s), bool))
    masked = jnp.where(causal, jax.lax.stop_gradient(scores), -jnp.inf)
    order = jnp.argsort(-masked, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1)
    return causal & (rank < min(cfg.index_topk, s))


def attention(cfg, block, x, keep=None):
    """The attention half of a layer on its normed input: (output, the
    indexer's KL mean over queries, S_t, I). ``keep`` given: attend to those
    keys instead of the reference's own selection (the KL then uses them)."""
    p, ip = block["mla"], block["indexer"]
    b, s, _ = x.shape
    h, dn, dr, dv, r = (cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                        cfg.v_head_dim, cfg.kv_lora_rank)
    c_q = rmsnorm(x @ p["wq_a"], p["q_norm"])
    q = (c_q @ p["wq_b"]).reshape(b, s, h, dn + dr)
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:])
    latent = x @ p["wkv_a"]
    c_kv = rmsnorm(latent[..., :r], p["kv_norm"])
    k_r = rope(latent[..., r:])
    kv = (c_kv @ p["wkv_b"]).reshape(b, s, h, dn + dv)
    logits = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, kv[..., :dn])
              + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_r)) / math.sqrt(dn + dr)
    scores = index_scores(cfg, ip, x, c_q)
    if keep is None:
        keep = select(cfg, scores)
    probs = jax.nn.softmax(jnp.where(keep[:, None], logits, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, kv[..., dn:]).reshape(b, s, h * dv) @ p["wo"]
    target = jax.lax.stop_gradient(jnp.mean(probs, axis=1))
    log_q = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
    kl = jnp.sum(jnp.where(keep, jax.scipy.special.xlogy(target, target)
                           - target * jnp.where(keep, log_q, 0.0), 0.0)) / (b * s)
    return out, kl, keep, scores


def route(cfg, p, x):
    """(the chosen experts (..., k), their weights (..., k), the load over
    every expert (n_routed_experts,))."""
    scores = jax.nn.sigmoid(x @ p["router"])
    _, chosen = jax.lax.top_k(scores + p["router_bias"], cfg.experts_per_token)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    load = jnp.sum(jax.nn.one_hot(chosen, cfg.n_routed_experts), axis=tuple(range(chosen.ndim)))
    return chosen, weights * cfg.routed_scaling_factor, load


def moe(cfg, p, x):
    """Held experts, each a dense SwiGLU over every token masked by its
    routing weight, plus the shared expert: (output, load)."""
    chosen, weights, load = route(cfg, p, x)
    y = swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"])
    for e in range(cfg.n_experts):
        gate = jnp.sum(jnp.where(chosen == cfg.first_expert + e, weights, 0.0), axis=-1,
                       keepdims=True)
        y = y + gate * swiglu(x, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
    return y, load


def layer(cfg, block, x):
    """One layer: (its output, the expert load or None, the indexer's KL)."""
    y, kl, _, _ = attention(cfg, block, rmsnorm(x, block["ln1_scale"]))
    x = x + y
    h = rmsnorm(x, block["ln2_scale"])
    if "moe" in block:
        y, load = moe(cfg, block["moe"], h)
        return x + y, load, kl
    return x + swiglu(h, **block["mlp"]), None, kl


def f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def forward(cfg, params, tokens):
    """(logits, the loads, each layer's KL)."""
    p = f32(params)
    x = p["embed"][tokens]
    loads, kls = [], []
    for block in p["layers"]:
        x, load, kl = layer(cfg, block, x)
        kls.append(kl)
        if load is not None:
            loads.append(load)
    return rmsnorm(x, p["ln_f_scale"]) @ p["unembed"], loads, kls


def loss_of_logits(logits, tokens):
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))


def loss(cfg, params, tokens):
    logits, _, kls = forward(cfg, params, tokens)
    return loss_of_logits(logits, tokens) + sum(kls)


def without_biases(params):
    """The trained leaves: every parameter but the routers' correction biases."""
    return {**params, "layers": [
        {**block, "moe": {k: v for k, v in block["moe"].items() if k != "router_bias"}}
        if "moe" in block else block for block in params["layers"]]}


def with_biases(trained, params):
    return {**trained, "layers": [
        {**block, "moe": {**block["moe"], "router_bias": full["moe"]["router_bias"]}}
        if "moe" in full else block for block, full in zip(trained["layers"], params["layers"])]}


def loss_and_grads(cfg, params, tokens):
    """The loss and its gradient in every trained leaf, in float32."""
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(lambda t: loss(cfg, with_biases(t, params), tokens))(
            f32(without_biases(params)))


def bias_step(cfg, bias, load):
    """The correction bias after one step whose assignments gave ``load``."""
    return bias + cfg.router_bias_rate * jnp.sign(jnp.mean(load) - load)

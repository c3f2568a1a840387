"""Kimi Linear written down plainly: the reference the model family
``kimi_linear`` (``torchsnapshot_tpu/models/kimi_linear.py``) is held to.

Straightforward float32 ``jax.numpy``, to be called under
``jax.default_matmul_precision("highest")``: no chunking, no grouping, no
kernels, and nothing imported from the library. It reads its sizes from any
object with the fields of ``TransformerConfig`` that the family uses.

The equations (arXiv:2510.26692; moonshotai/Kimi-Linear-48B-A3B-Instruct's
config.json), layer kinds by ``layer_kinds``:

- KDA: ``q, k, v = SiLU(conv(x W))`` per branch, a depthwise causal
  convolution of ``conv_kernel`` taps; ``q`` and ``k`` L2-normalised per head;
  ``beta = sigmoid(x W_b)``; ``g = -exp(A_log) * softplus(x W_f1 W_f2 +
  dt_bias)``; the recurrence of ``ops/kda.py`` token by token; ``o =
  RMSNorm_head(o) * sigmoid(x W_g1 W_g2 + b_g)``, then ``W_o``.
- MLA with ``q_lora_rank`` null and no rotary (``mla_use_nope``): ``q = x
  W_q``; ``[c, k_pe] = x W_kva``, ``c = RMSNorm(c)``; ``[k_nope, v] = c
  W_kvb``; ``k = [k_nope, k_pe]`` with ``k_pe`` shared by the heads; causal
  softmax over ``q.k / sqrt(qk_nope + qk_rope)``, then ``W_o``.
- Experts: sigmoid scores over ``n_routed_experts``; the top
  ``experts_per_token`` by score plus the correction bias; the chosen scores,
  without the bias, renormalised and scaled by ``routed_scaling_factor``;
  SwiGLU experts, plus the shared expert on every token.
- The first ``n_dense_layers`` have a dense SwiGLU of width ``d_ff``. Every
  layer is pre-RMSNorm (eps 1e-5) with residuals; embedding and head untied.

Departures from the published model, each the model's too:

- The expert share: only experts ``first_expert .. first_expert + n_experts``
  of the router's ``n_routed_experts`` are held, each as a masked dense SwiGLU
  over every token; what the absent experts would add is left out, as on one
  chip of an expert-parallel job without its exchange.
- The vocabulary slice: the embedding, the head and the loss are over
  ``vocab_size`` rows, one chip's slice.
- The correction bias is a state of its own, moved each step by
  ``router_bias_rate * sign(mean load - load)`` over the step's assignments
  (DeepSeek-V3's auxiliary-loss-free balancing, arXiv:2412.19437 §2.1.2); the
  published checkpoint ships a trained bias and no rule.
- RMSNorm's epsilon is 1e-5 everywhere, the latent norm's included.
"""

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-5


def rmsnorm(x, scale):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * scale


def l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def causal_conv(x, w):
    """Depthwise causal convolution: ``y_t = sum_j w[j] x_{t - K + 1 + j}``,
    ``x`` (batch, seq, channels), ``w`` (K, channels)."""
    taps, s = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(w[j] * padded[:, j:j + s] for j in range(taps))


def kda_recurrence(q, k, v, g, beta, scale):
    """The gated delta rule token by token: q, k, g (b, s, h, K), v (b, s, h,
    V), beta (b, s, h); o (b, s, h, V)."""
    b, _, h, dk = q.shape

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = jnp.exp(g_t)[..., None] * state
        state = state - b_t[..., None, None] * k_t[..., :, None] * jnp.einsum(
            "bhk,bhkv->bhv", k_t, state)[..., None, :]
        state = state + b_t[..., None, None] * k_t[..., :, None] * v_t[..., None, :]
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state) * scale

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    state = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    return jnp.moveaxis(jax.lax.scan(step, state, xs)[1], 0, 1)


def kda(cfg, p, x):
    b, s, _ = x.shape
    h, dk = cfg.n_heads, cfg.kda_head_dim

    def branch(w, conv):
        return jax.nn.silu(causal_conv(x @ w, conv)).reshape(b, s, h, dk)

    q = l2norm(branch(p["wq"], p["conv_q"]))
    k = l2norm(branch(p["wk"], p["conv_k"]))
    v = branch(p["wv"], p["conv_v"])
    g = -jnp.exp(p["a_log"])[:, None] * jax.nn.softplus(
        (x @ p["f_a"] @ p["f_b"] + p["dt_bias"]).reshape(b, s, h, dk))
    beta = jax.nn.sigmoid(x @ p["b_proj"])
    o = kda_recurrence(q, k, v, g, beta, dk ** -0.5)
    gate = jax.nn.sigmoid(x @ p["g_a"] @ p["g_b"] + p["g_bias"]).reshape(b, s, h, dk)
    o = rmsnorm(o, p["o_norm"]) * gate
    return o.reshape(b, s, h * dk) @ p["wo"]


def mla(cfg, p, x):
    b, s, _ = x.shape
    h, dn, dr, dv, r = (cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                        cfg.v_head_dim, cfg.kv_lora_rank)
    q = (x @ p["wq"]).reshape(b, s, h, dn + dr)
    latent = x @ p["wkv_a"]
    c, k_pe = rmsnorm(latent[..., :r], p["kv_norm"]), latent[..., r:]
    kv = (c @ p["wkv_b"]).reshape(b, s, h, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe[:, :, None], (b, s, h, dr))], -1)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(dn + dr)
    mask = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(mask, logits, -jnp.inf), axis=-1)
    attn = jnp.einsum("bhqk,bkhd->bqhd", probs, kv[..., dn:])
    return attn.reshape(b, s, h * dv) @ p["wo"]


def route(cfg, p, x):
    """(the chosen experts (..., k), their weights (..., k), the load over
    every expert (n_routed_experts,))."""
    scores = jax.nn.sigmoid(x @ p["router"])
    _, chosen = jax.lax.top_k(scores + p["router_bias"], cfg.experts_per_token)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    load = jnp.sum(jax.nn.one_hot(chosen, cfg.n_routed_experts), axis=tuple(range(chosen.ndim)))
    return chosen, weights * cfg.routed_scaling_factor, load


def experts(cfg, p, x, first_expert=None, n_experts=None):
    """The routed part of an expert layer that experts ``first_expert ..
    first_expert + n_experts`` give (the config's share by default): each a
    dense SwiGLU over every token, masked by its routing weight."""
    first = cfg.first_expert if first_expert is None else first_expert
    held = cfg.n_experts if n_experts is None else n_experts
    chosen, weights, load = route(cfg, p, x)
    y = jnp.zeros_like(x)
    for e in range(held):
        gate = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=-1, keepdims=True)
        y = y + gate * swiglu(x, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
    return y, load


def moe(cfg, p, x):
    y, load = experts(cfg, p, x)
    return y + swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"]), load


def layer(cfg, kind, block, x):
    """One layer: (its output, the expert load or None)."""
    x = x + (kda if kind == "K" else mla)(cfg, block["kda" if kind == "K" else "mla"],
                                          rmsnorm(x, block["ln1_scale"]))
    h = rmsnorm(x, block["ln2_scale"])
    if "moe" in block:
        y, load = moe(cfg, block["moe"], h)
        return x + y, load
    return x + swiglu(h, **block["mlp"]), None


def f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def logits_and_loads(cfg, params, tokens):
    p = f32(params)
    x = p["embed"][tokens]
    loads = []
    for kind, block in zip(cfg.layer_kinds[:cfg.n_layers], p["layers"]):
        x, load = layer(cfg, kind, block, x)
        if load is not None:
            loads.append(load)
    return rmsnorm(x, p["ln_f_scale"]) @ p["unembed"], loads


def loss_of_logits(logits, tokens):
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))


def loss(cfg, params, tokens):
    return loss_of_logits(logits_and_loads(cfg, params, tokens)[0], tokens)


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

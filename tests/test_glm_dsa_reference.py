"""The ``glm_dsa`` family (``models/glm_dsa.py``) against the benchmark's plain
reference (``chipbench/glm_dsa_reference.py``, loaded by its path), at toy widths on seeded random
weights with the routers' biases drawn nonzero and an ``index_topk`` below
the sequence, so that the selection drops keys; and what a checkpoint of
such a state counts on: the bias moves outside the optimizer, the state
round-trips through ``CheckpointManager`` bit for bit, and the dense and
Kimi families are the trees and step programs they were.
"""

import dataclasses
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchsnapshot_tpu as ts
from test_kimi_linear_reference import KIMI, _kimi_tokens
from test_lora_reference import TODAYS_TREE, TOY, _bits, _by_path, _todays_train_step, _tokens
from torchsnapshot_tpu.models import TransformerConfig, init_train_state, make_train_step
from torchsnapshot_tpu.models import glm_dsa as glm

_REF = os.path.join(os.path.dirname(__file__), os.pardir, "chipbench", "glm_dsa_reference.py")
_spec = importlib.util.spec_from_file_location("glm_dsa_reference", _REF)
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

# One dense layer, then two expert layers; 2 of 4 heads held (tp 2), half of
# the dense and shared SwiGLU columns, 4 of 32 experts; each query keeps 12
# of its causal keys, so queries from position 12 on drop keys.
GLM = TransformerConfig(
    vocab_size=64, d_model=32, n_heads=2, n_layers=3, d_ff=48, family="glm_dsa",
    n_experts=4, n_routed_experts=32, first_expert=4, experts_per_token=4, n_shared_experts=1,
    expert_d_ff=16, n_dense_layers=1, kv_lora_rank=16, q_lora_rank=24, qk_nope_head_dim=8,
    qk_rope_head_dim=4, v_head_dim=8, index_n_heads=4, index_head_dim=8, index_topk=12, tp_size=2,
    routed_scaling_factor=2.5, router_bias_rate=1e-3, dtype=jnp.float32)
SEQ = 32

# float32 against float32: what is left is the order of the sums (the query
# blocks and the chunked loss against whole matrices, XLA's fused
# reductions), some ulp of 2**-24 through three layers. Measured 5e-7
# relative on the logits and at most 1.5e-6 on a leaf's gradient; bfloat16
# (2**-8) misses these by four orders.
F32_TOL = dict(rtol=1e-5, atol=2e-5)
F32_GRAD_TOL = 1e-4
# The model in bfloat16 against the reference in float32 on the same
# bf16-rounded weights, by the norm of the difference over the norm of the
# reference. Every matmul output and residual is rounded to 8 bits (2**-8 =
# 0.4 %); the two latents, their norms and three layers add roundings, and a
# rounding that reorders a token's top 4 of 32 expert scores swaps an
# expert. Measured: logits 0.052, loss 0.13 %, gradients 0.054 over all
# trained leaves (one expert layer's w_down 0.27 alone, where a swapped
# expert lands).
BF16_LOGITS_TOL = 0.15
BF16_LOSS_RTOL = 1e-2
BF16_GRAD_TOL = 0.25


@functools.lru_cache(maxsize=None)
def _step(cfg):
    """One compiled step a configuration, shared by the tests."""
    return make_train_step(cfg)


def _glm_tokens(cfg, batch=2, seed=0):
    toks = np.random.default_rng(seed).integers(0, cfg.vocab_size, (batch, SEQ))
    return jnp.asarray(toks.astype(np.int32))


def _seeded(cfg, seed=3):
    """A state whose router biases and LayerNorm biases are drawn: with zeros
    they would take no part, and a restore that lost them would pass."""
    state = init_train_state(cfg, seed=seed)
    key = jax.random.PRNGKey(seed + 100)
    for block in state.params["layers"]:
        key, a, b = jax.random.split(key, 3)
        block["indexer"]["k_norm_bias"] = (jax.random.normal(
            a, block["indexer"]["k_norm_bias"].shape, jnp.float32) * 0.1).astype(cfg.dtype)
        if "moe" in block:
            block["moe"]["router_bias"] = jax.random.normal(
                b, block["moe"]["router_bias"].shape, jnp.float32) * 0.05
    return state


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def _x(cfg, seed=7):
    return jax.random.normal(jax.random.PRNGKey(seed), (2, SEQ, cfg.d_model))


# -- the attention half --------------------------------------------------------


def test_rope_is_the_references():
    x = jax.random.normal(jax.random.PRNGKey(1), (2, SEQ, 3, 8))
    np.testing.assert_allclose(np.asarray(glm.rope(x)), np.asarray(ref.rope(x)),
                               rtol=1e-5, atol=1e-6)
    # A rotation: norms kept.
    np.testing.assert_allclose(np.linalg.norm(glm.rope(x), axis=-1), np.linalg.norm(x, axis=-1),
                               rtol=1e-5)


def test_in_float32_the_indexer_selects_the_references_keys_exactly():
    state = _seeded(GLM)
    block = state.params["layers"][1]
    x = _x(GLM)
    with jax.default_matmul_precision("highest"):
        q, k, v, qi, ki, w = glm._projections(GLM, block["mla"], block["indexer"], x)
        scores = glm.index_scores(qi, ki, w)
        keep = glm.selection(scores, jnp.arange(SEQ), GLM.index_topk)
        c_q = ref.rmsnorm(x @ block["mla"]["wq_a"], block["mla"]["q_norm"])
        want_scores = ref.index_scores(GLM, block["indexer"], x, c_q)
        want_keep = ref.select(GLM, want_scores)
    causal = np.tril(np.ones((SEQ, SEQ), bool))
    np.testing.assert_allclose(np.where(causal, scores, 0), np.where(causal, want_scores, 0),
                               **F32_TOL)
    np.testing.assert_array_equal(np.asarray(keep), np.asarray(want_keep))
    kept = np.asarray(keep).sum(-1)
    np.testing.assert_array_equal(kept, np.broadcast_to(np.minimum(np.arange(SEQ) + 1,
                                                                    GLM.index_topk), kept.shape))


@pytest.mark.parametrize("query_block", [8, SEQ])
def test_the_attention_half_is_the_reference_given_the_same_selection(query_block):
    state = _seeded(GLM)
    block = state.params["layers"][2]
    x = _x(GLM, seed=8)
    with jax.default_matmul_precision("highest"):
        got, kl = glm.attention(GLM, block, x, query_block=query_block)
        want, want_kl, _, _ = ref.attention(GLM, block, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **F32_TOL)
    np.testing.assert_allclose(float(kl), float(want_kl), **F32_TOL)
    assert float(kl) > 0


# -- the model -----------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_logits_loss_kls_and_loads_are_the_references(dtype):
    cfg = dataclasses.replace(GLM, dtype=dtype)
    state, tokens = _seeded(cfg), _glm_tokens(cfg)
    with jax.default_matmul_precision("highest"):
        got, loads, kls = glm.forward(cfg, state.params, tokens)
        want, want_loads, want_kls = ref.forward(cfg, state.params, tokens)
        want_loss = ref.loss_of_logits(want, tokens) + sum(want_kls)
        _, loss = _step(cfg)(state, tokens)
    assert len(loads) == len(want_loads) == 2 and len(kls) == 3
    for a in loads:
        assert float(jnp.sum(a)) == tokens.size * cfg.experts_per_token
    if dtype == jnp.float32:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **F32_TOL)
        np.testing.assert_allclose(float(loss), float(want_loss), **F32_TOL)
        np.testing.assert_allclose(np.asarray(kls), np.asarray(want_kls), **F32_TOL)
        for a, b in zip(loads, want_loads):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    else:
        assert _rel(got, want) < BF16_LOGITS_TOL
        np.testing.assert_allclose(float(loss), float(want_loss), rtol=BF16_LOSS_RTOL)
        # bfloat16 does not pass the float32 tolerance.
        assert not np.allclose(np.asarray(got), np.asarray(want), **F32_TOL)


def _step_gradients(cfg, state, tokens):
    """adamw's first step leaves ``mu = (1 - b1) g`` with b1 = 0.9: the
    gradient the step took is ten times its first moment."""
    with jax.default_matmul_precision("highest"):
        new, _ = _step(cfg)(state, tokens)
    return {k: np.asarray(v, np.float32) * 10.0 for k, v in _by_path(new.opt_state[0].mu).items()}


def test_every_trained_leafs_gradient_is_the_references_float32():
    """Each leaf held to the reference by the norm of the difference over the
    norm of its gradient."""
    state, tokens = _seeded(GLM), _glm_tokens(GLM)
    _, want = ref.loss_and_grads(GLM, state.params, tokens)
    want = _by_path(want)
    got = _step_gradients(GLM, state, tokens)
    assert got.keys() == want.keys()
    assert sum("indexer" in k for k in want) == 5 * GLM.n_layers
    for key, g in want.items():
        if float(jnp.max(jnp.abs(g))) == 0.0:
            # A held expert no token chose: both sides exactly zero.
            assert not got[key].any(), key
            continue
        assert _rel(got[key], g) < F32_GRAD_TOL, key


def test_the_bfloat16_gradient_is_the_references_and_misses_the_float32_tolerance():
    cfg = dataclasses.replace(GLM, dtype=jnp.bfloat16)
    state, tokens = _seeded(cfg), _glm_tokens(cfg)
    _, want = ref.loss_and_grads(cfg, state.params, tokens)
    want = _by_path(want)
    got = _step_gradients(cfg, state, tokens)
    keys = sorted(want)
    whole = _rel(np.concatenate([got[k].ravel() for k in keys]),
                 np.concatenate([np.asarray(want[k]).ravel() for k in keys]))
    assert F32_GRAD_TOL < whole < BF16_GRAD_TOL


def test_the_kl_reaches_the_indexer_alone_and_the_lm_loss_never_does():
    state, tokens = _seeded(GLM), _glm_tokens(GLM)
    trainable = glm.experts.trainable(state.params)
    biases = glm.experts.biases(state.params)

    def part(which):
        def f(t):
            ce, kls, _ = glm.losses(GLM, t, biases, tokens)
            return ce if which == "ce" else sum(kls)
        return _by_path(jax.jit(jax.grad(f))(trainable))

    ce, kl = part("ce"), part("kl")
    for key in ce:
        in_indexer = "['indexer']" in key
        assert (float(np.max(np.abs(kl[key]))) > 0) == in_indexer, key
        if in_indexer:
            assert not np.asarray(ce[key]).any(), key
    # Every indexer leaf learns from the KL.
    assert all(float(np.max(np.abs(kl[k]))) > 0 for k in kl if "['indexer']" in k)


def test_the_shares_add_up_to_the_uncut_layer():
    """tp 2 x ep 8 chips, each holding 2 of 4 heads, half of the SwiGLU
    columns and 4 of the 32 experts, each compute their share of a layer;
    with what every chip holds whole counted once (the residual, and the
    indexer, whose selection every chip makes alike), the shares add up to
    the reference layer with everything held."""
    tp, h = 2, GLM.n_heads
    whole = dataclasses.replace(GLM, n_heads=h * tp, tp_size=1, n_experts=GLM.n_routed_experts,
                                first_expert=0)
    params = _seeded(whole).params
    dense, expert = params["layers"][0], params["layers"][1]
    x = _x(GLM, seed=9)
    dn, dr, dv = GLM.qk_nope_head_dim, GLM.qk_rope_head_dim, GLM.v_head_dim
    f_dense, f_shared = GLM.d_ff // tp, GLM.expert_d_ff // tp

    def heads(block, rank):
        """The attention leaves of tp rank ``rank``: its heads' columns of
        W_qb and W_kvb and rows of W_o; the latents and the indexer whole."""
        mla, i = dict(block["mla"]), slice(rank * h, (rank + 1) * h)

        def cols(w, width):
            return w.reshape(w.shape[0], h * tp, width)[:, i].reshape(w.shape[0], -1)

        mla["wq_b"] = cols(mla["wq_b"], dn + dr)
        mla["wkv_b"] = cols(mla["wkv_b"], dn + dv)
        mla["wo"] = mla["wo"].reshape(h * tp, dv, -1)[i].reshape(h * dv, -1)
        return {**block, "mla": mla}

    def columns(w_gate, w_up, w_down, rank, f):
        c = slice(rank * f, (rank + 1) * f)
        return w_gate[:, c], w_up[:, c], w_down[c]

    with jax.default_matmul_precision("highest"):
        want_attn, _, _, _ = ref.attention(whole, dense, x)
        want_dense = ref.swiglu(x, **dense["mlp"])
        want_moe, _ = ref.moe(whole, expert["moe"], x)
        attn = sum(glm.attention(GLM, heads(dense, r), x)[0] for r in range(tp))
        mlp = sum(ref.swiglu(x, *columns(dense["mlp"]["w_gate"], dense["mlp"]["w_up"],
                                         dense["mlp"]["w_down"], r, f_dense))
                  for r in range(tp))
        routed = 0
        gate, up, down = columns(expert["moe"]["shared_gate"], expert["moe"]["shared_up"],
                                 expert["moe"]["shared_down"], 0, f_shared)
        for first in range(0, GLM.n_routed_experts, GLM.n_experts):
            share = dataclasses.replace(GLM, first_expert=first)
            mine = {**expert["moe"], "shared_gate": gate, "shared_up": up, "shared_down": down,
                    **{w: expert["moe"][w][first:first + GLM.n_experts]
                       for w in ("w_gate", "w_up", "w_down")}}
            part, _ = glm.experts.moe(share, mine, x, "glm")
            routed = routed + part - glm.experts.swiglu(x, gate, up, down)
        # The shared expert's columns are the tp ranks', once each.
        shared = sum(glm.experts.swiglu(x, *columns(
            expert["moe"]["shared_gate"], expert["moe"]["shared_up"],
            expert["moe"]["shared_down"], r, f_shared)) for r in range(tp))
        moe = routed + shared
        one = glm.attention(GLM, heads(dense, 0), x)[0]
    assert _rel(one, want_attn) > 0.1  # a share alone is not the layer
    np.testing.assert_allclose(np.asarray(attn), np.asarray(want_attn), **F32_TOL)
    np.testing.assert_allclose(np.asarray(mlp), np.asarray(want_dense), **F32_TOL)
    np.testing.assert_allclose(np.asarray(moe), np.asarray(want_moe), **F32_TOL)


def test_the_bias_moves_by_gamma_a_step_and_is_no_optimizer_leaf():
    state, tokens = _seeded(GLM), _glm_tokens(GLM)
    before = [np.asarray(b["moe"]["router_bias"]).copy() for b in state.params["layers"]
              if "moe" in b]
    _, loads, _ = ref.forward(GLM, state.params, tokens)
    opt_paths = list(_by_path(state.opt_state))
    assert not any("router_bias" in p for p in opt_paths)
    assert sum("router'" in p for p in opt_paths) == 2 * 2  # mu and nu of each router
    new, _ = _step(GLM)(state, tokens)
    after = [b["moe"]["router_bias"] for b in new.params["layers"] if "moe" in b]
    for b0, b1, load in zip(before, after, loads):
        assert b1.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(b1), np.asarray(ref.bias_step(GLM, b0, load)))
        step = np.abs(np.asarray(b1, np.float64) - b0)
        assert set(np.round(step / GLM.router_bias_rate, 3)) <= {0.0, 1.0}
        assert step.max() > 0


# -- the other families, a checkpoint ---------------------------------------------


GLM_ONLY = ("q_lora_rank", "index_n_heads", "index_head_dim", "index_topk", "tp_size")


def test_the_dense_and_kimi_families_keep_their_trees_and_step_programs():
    """Whatever the glm_dsa fields hold, the dense family's tree and step
    program are today's, and the Kimi family's step program is the one it
    lowers to with them at their defaults."""
    assert sorted((k, v.shape) for k, v in _by_path(
        init_train_state(TOY, seed=0).as_pytree()).items()) == TODAYS_TREE
    busy = dataclasses.replace(TOY, **{f: getattr(GLM, f) for f in GLM_ONLY})
    tokens = _tokens(TOY)
    state = init_train_state(busy, seed=2)
    want = _todays_train_step(TOY).lower(state, tokens).as_text()
    assert make_train_step(busy).lower(state, tokens).as_text() == want

    kimi_busy = dataclasses.replace(KIMI, **{f: getattr(GLM, f) for f in GLM_ONLY})
    kimi_state, kimi_tokens = init_train_state(KIMI, seed=2), _kimi_tokens(KIMI)
    assert (make_train_step(kimi_busy).lower(kimi_state, kimi_tokens).as_text()
            == make_train_step(KIMI).lower(kimi_state, kimi_tokens).as_text())


@pytest.mark.parametrize("change,match", [
    (dict(family="glm"), "unknown family"),
    (dict(first_expert=30), "held"),
    (dict(tp_size=5), "tp_size"),
    (dict(index_topk=0), "index_topk"),
    (dict(lora_rank=8), "dense family"),
    (dict(attn_impl="flash"), "dense family"),
])
def test_a_config_the_family_cannot_build_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        init_train_state(dataclasses.replace(GLM, **change), seed=0)


def _app_state(state, step):
    return {"params": ts.PyTreeState(state.params), "opt": ts.PyTreeState(state.opt_state),
            "progress": ts.StateDict(step=step), "rng": ts.RngState(state.rng)}


def test_the_state_round_trips_through_the_manager_bit_for_bit(tmp_path):
    """async_save of a trained state, three more steps, then restore_latest
    into a state of another seed: every leaf (3-d expert stacks, float32
    biases, the indexer's LayerNorm) is the saved bits, and the three steps
    replayed from it give the losses the uninterrupted run gave."""
    cfg = dataclasses.replace(GLM, dtype=jnp.bfloat16)
    step_fn = _step(cfg)
    state = _seeded(cfg)
    for i in range(2):
        state, _ = step_fn(state, _glm_tokens(cfg, seed=i))
    saved = {k: _bits(v).copy() for k, v in _by_path(
        {"params": state.params, "opt": state.opt_state, "rng": state.rng}).items()}
    kinds = {(v.ndim, v.dtype.name) for v in _by_path(state.params).values()}
    assert {(3, "bfloat16"), (1, "float32")} <= kinds
    mgr = ts.CheckpointManager(str(tmp_path / "ckpt"), keep_last_n=1)
    mgr.async_save(2, _app_state(state, 2)).wait()
    uninterrupted = []
    for i in range(3):
        state, loss = step_fn(state, _glm_tokens(cfg, seed=10 + i))
        uninterrupted.append(float(loss))

    dest = _app_state(init_train_state(cfg, seed=99), 0)
    assert mgr.restore_latest(dest) == 2
    got = {k: _bits(v) for k, v in _by_path(
        {"params": dest["params"].tree, "opt": dest["opt"].tree, "rng": dest["rng"].keys}).items()}
    assert got.keys() == saved.keys()
    for key in saved:
        assert np.array_equal(got[key], saved[key]), key
    restored = glm.TrainState(dest["params"].tree, dest["opt"].tree,
                              jnp.asarray(dest["progress"]["step"], jnp.int32), dest["rng"].keys)
    replayed = []
    for i in range(3):
        restored, loss = step_fn(restored, _glm_tokens(cfg, seed=10 + i))
        replayed.append(float(loss))
    assert replayed == uninterrupted


def test_the_cells_state_is_the_configuration_files_arithmetic():
    """The benchmark's GLM configuration, sized as `chipbench/workload.py`
    sizes it (five top-level keys and the `model` block), builds the state
    its `arithmetic` counts: jax.eval_shape, nothing allocated."""
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), os.pardir, "chipbench", "configs",
                        "glm-5-l5-ep32-tp8.json")
    with open(path) as f:
        config = json.load(f)
    cfg = TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"], d_ff=config["intermediate_size"],
        n_layers=config["num_hidden_layers"],
        **{k: getattr(jnp, v) if k == "dtype" else v for k, v in config["model"].items()})
    shapes = jax.eval_shape(lambda: init_train_state(cfg, seed=0))
    leaves = jax.tree_util.tree_leaves(shapes)
    assert len(leaves) == 319
    assert sum(x.size * x.dtype.itemsize for x in leaves) == 10_319_661_584
    trained = sum(x.size for k, x in _by_path(shapes.params).items() if "router_bias" not in k)
    assert trained == 1_719_942_912
    assert "10,319,661,584 B" in config["arithmetic"] and "1,719,942,912" in config["arithmetic"]

"""The ``kimi_linear`` family (``models/kimi_linear.py``, ``ops/kda.py``)
against the plain reference beside this file (``kimi_linear_reference.py``),
at toy widths on seeded random weights with the routers' biases drawn
nonzero; and what a checkpoint of such a state counts on: the bias moves
outside the optimizer, the state round-trips through ``CheckpointManager``
bit for bit, a mesh computes what one device does, and the dense family is
the tree and step program it was.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import kimi_linear_reference as ref
import torchsnapshot_tpu as ts
from test_lora_reference import TODAYS_TREE, TOY, _bits, _by_path, _todays_train_step, _tokens
from torchsnapshot_tpu.models import (
    TransformerConfig,
    init_train_state,
    make_train_step,
    param_shardings,
)
from torchsnapshot_tpu.models import kimi_linear as kl
from torchsnapshot_tpu.ops.kda import chunk_kda

# One period of the published pattern (KDA, KDA, KDA, MLA) after the dense
# layer, as the benchmark's cut has it; 32 experts of which 4 are held here.
KIMI = TransformerConfig(
    vocab_size=64, d_model=32, n_heads=2, n_layers=5, d_ff=48, family="kimi_linear",
    layer_kinds="KKKMK", n_experts=4, n_routed_experts=32, first_expert=4,
    experts_per_token=4, n_shared_experts=1, expert_d_ff=16, n_dense_layers=1,
    kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
    kda_head_dim=8, conv_kernel=4, routed_scaling_factor=2.446, router_bias_rate=1e-3,
    dtype=jnp.float32)
SEQ = 32

# float32 against float32: what is left is the order of the sums (the
# chunked solve against the token-by-token recurrence, XLA's fused
# reductions), some ulp of 2**-24 through five layers. Measured at most 2e-6
# relative on the logits, 4e-6 on a gradient's norm; bfloat16 (2**-8) misses
# these by three orders.
F32_TOL = dict(rtol=1e-5, atol=2e-5)
F32_GRAD_TOL = 1e-4
# The model in bfloat16 against the reference in float32 on the same
# bf16-rounded weights, by the norm of the difference over the norm of the
# reference. Every matmul output, residual and gate is rounded to 8 bits
# (2**-8 = 0.4 %); a layer alone moves 0.5 % (MLA, experts) to 1.3 % (KDA,
# whose conv, SiLU and L2 norm add roundings). Two things make the whole
# model move more at these toy widths: a rounding that reorders a token's top
# 4 of 32 scores swaps an expert (a few tokens a layer), and KDA carries the
# change to every later position; and the KDA stack's gradient is ill
# conditioned: with its layers computed in float32 and only the residual
# stream in bf16 it still moves 11 %. Measured: logits 0.17, loss 0.4 %,
# gradients 0.29 over all trained leaves; bf16 does not fail the float32
# tolerances by luck, it misses them by four orders.
BF16_LOGITS_TOL = 0.25
BF16_LOSS_RTOL = 2e-2
BF16_GRAD_TOL = 0.5
# The op against the recurrence, float32 both (measured 3e-7 on outputs of
# |o| <= 0.3); a bfloat16 state between chunks measured 7e-5.
KDA_ATOL = 5e-6


def _kimi_tokens(cfg, batch=2, seed=0):
    toks = np.random.default_rng(seed).integers(0, cfg.vocab_size, (batch, SEQ))
    return jnp.asarray(toks.astype(np.int32))


def _seeded(cfg, seed=3):
    """A state whose router biases are drawn: with zeros the bias would take
    no part in the routing, and a restore that lost it would pass."""
    state = init_train_state(cfg, seed=seed)
    key = jax.random.PRNGKey(seed + 100)
    for block in state.params["layers"]:
        if "moe" in block:
            key, sub = jax.random.split(key)
            block["moe"]["router_bias"] = jax.random.normal(
                sub, block["moe"]["router_bias"].shape, jnp.float32) * 0.05
    return state


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


# -- the chunked op ------------------------------------------------------------


def _kda_inputs(b=2, s=64, h=3, dk=16, dv=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = ref.l2norm(jax.random.normal(ks[0], (b, s, h, dk)))
    k = ref.l2norm(jax.random.normal(ks[1], (b, s, h, dk)))
    v = jax.random.normal(ks[2], (b, s, h, dv))
    # Decays up to e^-7 a token: within a chunk of 64, exp(-G) would overflow.
    g = -jnp.exp(jax.random.normal(ks[3], (b, s, h, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    return q, k, v, g, beta


@pytest.mark.parametrize("chunk", [16, 64])
def test_chunked_kda_is_the_recurrence_forward_and_backward(chunk):
    args = _kda_inputs()
    scale = args[0].shape[-1] ** -0.5
    probe = jnp.cos(jnp.arange(args[2].shape[-1]))

    def summed(fn):
        return lambda *a: jnp.sum(fn(*a) * probe)

    with jax.default_matmul_precision("highest"):
        want = ref.kda_recurrence(*args, scale)
        got = chunk_kda(*args, scale=scale, chunk=chunk)
        want_g = jax.grad(summed(lambda *a: ref.kda_recurrence(*a, scale)), range(5))(*args)
        got_g = jax.grad(summed(lambda *a: chunk_kda(*a, scale=scale, chunk=chunk)),
                         range(5))(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=KDA_ATOL)
    for name, a, b in zip("q k v g beta".split(), got_g, want_g):
        assert float(jnp.max(jnp.abs(b))) > 1e-2, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=4 * KDA_ATOL, err_msg=name)


def test_a_bfloat16_state_fails_the_float32_tolerance():
    args = _kda_inputs()
    scale = args[0].shape[-1] ** -0.5
    with jax.default_matmul_precision("highest"):
        want = ref.kda_recurrence(*args, scale)
        got = chunk_kda(*args, scale=scale, chunk=16, state_dtype=jnp.bfloat16)
    assert float(jnp.max(jnp.abs(got - want))) > 5 * KDA_ATOL


def test_a_sequence_that_is_no_whole_number_of_chunks_is_refused():
    q, k, v, g, beta = _kda_inputs(s=48)
    with pytest.raises(ValueError, match="not a multiple of the chunk"):
        chunk_kda(q, k, v, g, beta, scale=1.0, chunk=32)


# -- the layers and the model --------------------------------------------------


def test_mla_is_the_reference():
    state = _seeded(KIMI)
    block = state.params["layers"][3]["mla"]
    x = jax.random.normal(jax.random.PRNGKey(7), (2, SEQ, KIMI.d_model))
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(np.asarray(kl._mla(KIMI, block, x)),
                                   np.asarray(ref.mla(KIMI, block, x)), **F32_TOL)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_logits_loss_and_loads_are_the_references(dtype):
    cfg = dataclasses.replace(KIMI, dtype=dtype)
    state, tokens = _seeded(cfg), _kimi_tokens(cfg)
    with jax.default_matmul_precision("highest"):
        got, loads = kl.forward(cfg, state.params, tokens)
        want, want_loads = ref.logits_and_loads(cfg, state.params, tokens)
        want_loss = ref.loss_of_logits(want, tokens)
        _, loss = make_train_step(cfg)(state, tokens)
    assert len(loads) == len(want_loads) == 4
    for a in loads:
        assert float(jnp.sum(a)) == tokens.size * cfg.experts_per_token
    if dtype == jnp.float32:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **F32_TOL)
        np.testing.assert_allclose(float(loss), float(want_loss), **F32_TOL)
        for a, b in zip(loads, want_loads):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    else:
        assert _rel(got, want) < BF16_LOGITS_TOL
        np.testing.assert_allclose(float(loss), float(want_loss), rtol=BF16_LOSS_RTOL)


def _step_gradients(cfg, state, tokens):
    """adamw's first step leaves ``mu = (1 - b1) g`` with b1 = 0.9: the
    gradient the step took is ten times its first moment."""
    with jax.default_matmul_precision("highest"):
        new, _ = make_train_step(cfg)(state, tokens)
    return {k: np.asarray(v, np.float32) * 10.0 for k, v in _by_path(new.opt_state[0].mu).items()}


def test_every_trained_leafs_gradient_is_the_references_float32():
    """Each leaf held to the reference by the norm of the difference over the
    norm of its gradient."""
    state, tokens = _seeded(KIMI), _kimi_tokens(KIMI)
    _, want = ref.loss_and_grads(KIMI, state.params, tokens)
    want = _by_path(want)
    got = _step_gradients(KIMI, state, tokens)
    assert got.keys() == want.keys()
    for key, g in want.items():
        if float(jnp.max(jnp.abs(g))) == 0.0:
            # A held expert no token chose: both sides exactly zero.
            assert not got[key].any(), key
            continue
        assert _rel(got[key], g) < F32_GRAD_TOL, key


def test_the_bfloat16_gradient_is_the_references():
    cfg = dataclasses.replace(KIMI, dtype=jnp.bfloat16)
    state, tokens = _seeded(cfg), _kimi_tokens(cfg)
    _, want = ref.loss_and_grads(cfg, state.params, tokens)
    want = _by_path(want)
    got = _step_gradients(cfg, state, tokens)
    keys = sorted(want)
    assert _rel(np.concatenate([got[k].ravel() for k in keys]),
                np.concatenate([np.asarray(want[k]).ravel() for k in keys])) < BF16_GRAD_TOL


@pytest.mark.parametrize("crowd", [0.0, 10.0], ids=["as-routed", "every-token-held"])
def test_the_expert_layer_is_the_reference_however_many_assignments_it_holds(crowd):
    """With the held experts' biases raised by `crowd` every token picks
    them: every assignment is in a group, none past them."""
    state = _seeded(KIMI)
    block = dict(state.params["layers"][1]["moe"])
    held = slice(KIMI.first_expert, KIMI.first_expert + KIMI.n_experts)
    block["router_bias"] = block["router_bias"].at[held].add(crowd)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, SEQ, KIMI.d_model))
    with jax.default_matmul_precision("highest"):
        got, load = kl._moe(KIMI, block, x)
        want, want_load = ref.moe(KIMI, block, x)
    n_held = float(jnp.sum(load[held]))
    every = x.shape[0] * SEQ * KIMI.experts_per_token
    assert (n_held == every) == bool(crowd) and n_held > 0
    np.testing.assert_array_equal(np.asarray(load), np.asarray(want_load))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **F32_TOL)


@pytest.mark.parametrize("crowd", [-10.0, 0.0, 10.0],
                         ids=["none-held", "as-routed", "every-token-held"])
def test_the_groups_cover_every_assignment_whatever_the_routing(crowd):
    """The grouped matmuls are handed every (token, slot) row inside some
    group, so they do the same work wherever the routing lands: the held
    assignments first, by expert, then the others in the last group as rows
    of zeros, which add nothing to the layer or to its gradient."""
    state = _seeded(KIMI)
    block = dict(state.params["layers"][1]["moe"])
    first, n = KIMI.first_expert, KIMI.n_experts
    block["router_bias"] = block["router_bias"].at[first:first + n].add(crowd)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, SEQ, KIMI.d_model))
    chosen, _, load = kl._route(KIMI, block, x.reshape(-1, KIMI.d_model))
    order, mine, sizes = kl._dispatch(KIMI, chosen)
    n_held = int(jnp.sum(load[first:first + n]))
    assert int(jnp.sum(sizes)) == chosen.size
    assert (n_held == 0) == (crowd < 0) and (n_held == chosen.size) == (crowd > 0)
    np.testing.assert_array_equal(np.asarray(sizes[:-1]), np.asarray(load[first:first + n - 1]))
    assert np.asarray(mine[:n_held]).all() and not np.asarray(mine[n_held:]).any()
    assert (np.diff(np.asarray(chosen.reshape(-1)[order][:n_held])) >= 0).all()

    def grads(moe):
        return _by_path(jax.grad(lambda b: jnp.sum(jnp.sin(moe(KIMI, b, x)[0])))(block))

    with jax.default_matmul_precision("highest"):
        got, want = grads(kl._moe), grads(ref.moe)
    for key, g in want.items():
        if float(jnp.max(jnp.abs(g))) == 0.0:
            assert not np.asarray(got[key]).any(), key
            continue
        assert _rel(got[key], g) < F32_GRAD_TOL, key


def test_the_shares_add_up_to_the_uncut_layer():
    """Sixteen devices, each holding 2 of the 32 experts, each compute their
    share of one expert layer; with the shared expert, which every device
    computes alike, counted once, the shares add up to the reference layer
    with every expert held."""
    cfg = dataclasses.replace(KIMI, n_experts=2)
    n_routed, held = cfg.n_routed_experts, cfg.n_experts
    whole = dataclasses.replace(cfg, n_experts=n_routed, first_expert=0)
    block = _seeded(whole).params["layers"][1]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(9), (2, SEQ, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        want, _ = ref.moe(whole, block, x)
        shared = ref.swiglu(x, block["shared_gate"], block["shared_up"], block["shared_down"])
        total = -(n_routed // held - 1) * shared
        for first in range(0, n_routed, held):
            share = dataclasses.replace(cfg, first_expert=first)
            mine = {**block, **{w: block[w][first:first + held]
                                for w in ("w_gate", "w_up", "w_down")}}
            part, _ = kl._moe(share, mine, x)
            total = total + part
            # A share alone is not the layer.
            assert _rel(part, want) > 0.1
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), **F32_TOL)


def test_the_bias_moves_by_gamma_a_step_and_is_no_optimizer_leaf():
    state, tokens = _seeded(KIMI), _kimi_tokens(KIMI)
    before = [np.asarray(b["moe"]["router_bias"]).copy() for b in state.params["layers"]
              if "moe" in b]
    _, loads = ref.logits_and_loads(KIMI, state.params, tokens)
    opt_paths = list(_by_path(state.opt_state))
    assert not any("router_bias" in p for p in opt_paths)
    assert sum("router" in p for p in opt_paths) == 2 * 4  # mu and nu of each router
    new, _ = make_train_step(KIMI)(state, tokens)
    after = [b["moe"]["router_bias"] for b in new.params["layers"] if "moe" in b]
    for b0, b1, load in zip(before, after, loads):
        assert b1.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(b1), np.asarray(ref.bias_step(KIMI, b0, load)))
        step = np.abs(np.asarray(b1, np.float64) - b0)
        assert set(np.round(step / KIMI.router_bias_rate, 3)) <= {0.0, 1.0}
        assert step.max() > 0


# -- a mesh, the dense family, a checkpoint -----------------------------------


@pytest.fixture(scope="module")
def mesh4():
    return Mesh(np.asarray(jax.devices()[:4]).reshape(1, 2, 2), ("dp", "sp", "tp"))


def test_a_four_device_step_is_the_one_device_step(mesh4):
    cfg = dataclasses.replace(KIMI, d_ff=64)
    tokens = _kimi_tokens(cfg)
    sharded = init_train_state(cfg, seed=4, mesh=mesh4)
    want = _by_path(param_shardings(cfg, mesh4))
    assert want["['layers'][1]['moe']['w_gate']"].spec == P("sp", "dp", "tp")
    for key, leaf in _by_path(sharded.params).items():
        assert leaf.sharding.is_equivalent_to(want[key], leaf.ndim), key
    single = init_train_state(cfg, seed=4)
    step, single_step = make_train_step(cfg, mesh=mesh4), make_train_step(cfg)
    placed = jax.device_put(tokens, NamedSharding(mesh4, P("dp", None)))
    with jax.default_matmul_precision("highest"):
        for i in range(2):
            sharded, loss = step(sharded, placed)
            single, single_loss = single_step(single, tokens)
            np.testing.assert_allclose(float(loss), float(single_loss), **F32_TOL)
            if i == 0:
                # The first step's gradients (adamw's first moments): after
                # that an update of +-lr for a gradient near zero is its sign.
                # The step's loads, so the biases, are the same counts.
                got = _by_path(sharded.opt_state[0].mu)
                for key, leaf in _by_path(single.opt_state[0].mu).items():
                    assert _rel(got[key], leaf) < F32_GRAD_TOL, key
                for a, b in zip(kl._biases(sharded.params), kl._biases(single.params)):
                    if a is not None:
                        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_dense_family_reads_no_kimi_field():
    """The default family is the dense one, its tree is today's, and its
    step program is today's whatever the Kimi fields hold."""
    assert TransformerConfig().family == TOY.family == "dense"
    assert sorted((k, v.shape) for k, v in _by_path(
        init_train_state(TOY, seed=0).as_pytree()).items()) == TODAYS_TREE
    busy = dataclasses.replace(TOY, **{
        f.name: getattr(KIMI, f.name) for f in dataclasses.fields(TransformerConfig)
        if f.name not in {"family", "vocab_size", "d_model", "n_heads", "n_layers", "d_ff",
                          "n_experts", "dtype"}})
    tokens = _tokens(TOY)
    state = init_train_state(busy, seed=2)
    want = _todays_train_step(TOY).lower(state, tokens).as_text()
    assert make_train_step(busy).lower(state, tokens).as_text() == want
    assert make_train_step(TOY).lower(state, tokens).as_text() == want


@pytest.mark.parametrize("change,match", [
    (dict(family="kimi"), "unknown family"),
    (dict(layer_kinds="KKX"), "layer_kinds"),
    (dict(first_expert=30), "held"),
    (dict(lora_rank=8), "dense family"),
    (dict(attn_impl="flash"), "dense family"),
    (dict(moe_every=1), "dense family"),
])
def test_a_config_the_family_cannot_build_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        init_train_state(dataclasses.replace(KIMI, **change), seed=0)


def _app_state(state, step):
    return {"params": ts.PyTreeState(state.params), "opt": ts.PyTreeState(state.opt_state),
            "progress": ts.StateDict(step=step), "rng": ts.RngState(state.rng)}


def test_the_state_round_trips_through_the_manager_bit_for_bit(tmp_path):
    """async_save of a trained state, three more steps, then restore_latest
    into a state of another seed: every leaf (3-d expert stacks, float32
    biases, 64-byte leaves) is the saved bits, and the three steps replayed
    from it give the losses the uninterrupted run gave."""
    cfg = dataclasses.replace(KIMI, dtype=jnp.bfloat16)
    step_fn = make_train_step(cfg)
    state = _seeded(cfg)
    for i in range(2):
        state, _ = step_fn(state, _kimi_tokens(cfg, seed=i))
    saved = {k: _bits(v).copy() for k, v in _by_path(
        {"params": state.params, "opt": state.opt_state, "rng": state.rng}).items()}
    kinds = {(v.ndim, v.dtype.name) for v in _by_path(state.params).values()}
    assert {(3, "bfloat16"), (1, "float32")} <= kinds
    mgr = ts.CheckpointManager(str(tmp_path / "ckpt"), keep_last_n=1)
    mgr.async_save(2, _app_state(state, 2)).wait()
    uninterrupted = []
    for i in range(3):
        state, loss = step_fn(state, _kimi_tokens(cfg, seed=10 + i))
        uninterrupted.append(float(loss))

    dest = _app_state(init_train_state(cfg, seed=99), 0)
    assert mgr.restore_latest(dest) == 2
    got = {k: _bits(v) for k, v in _by_path(
        {"params": dest["params"].tree, "opt": dest["opt"].tree, "rng": dest["rng"].keys}).items()}
    assert got.keys() == saved.keys()
    for key in saved:
        assert np.array_equal(got[key], saved[key]), key
    restored = kl.TrainState(dest["params"].tree, dest["opt"].tree,
                             jnp.asarray(dest["progress"]["step"], jnp.int32), dest["rng"].keys)
    replayed = []
    for i in range(3):
        restored, loss = step_fn(restored, _kimi_tokens(cfg, seed=10 + i))
        replayed.append(float(loss))
    assert replayed == uninterrupted

"""A fine-tune over a frozen base (``TransformerConfig.lora_rank``) against
a plain reference, independent of ``models/transformer.py``.

The reference is the block's equations in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")`` (LoRA, arXiv:2106.09685, section
4.1: ``h = W0 x + B A x``, here on the fused qkv projection with
``alpha / r = 1``): logits, the loss and the adapters' gradients. Beside it
what a checkpoint of such a state counts on: a frozen leaf leaves a donated
step as the bits it entered with, the optimizer state holds moments for the
adapters alone, and ``lora_rank=0`` is the state and the step program the
repo had before the field.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torchsnapshot_tpu.models import (
    TransformerConfig,
    forward,
    init_train_state,
    make_train_step,
    param_shardings,
)
from torchsnapshot_tpu.models.transformer import TrainState, _optimizer

TOY = TransformerConfig(vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64)
ADAPTERS = ("lora_a", "lora_b")
# float32 against float32: what is left is the order of the sums (the
# model's fused einsums, XLA's reductions), a few ulp of 2**-24 through two
# blocks; 2e-5 is two hundred of them, and bf16 (2**-8) would miss it by 1e2.
F32_TOL = dict(rtol=2e-5, atol=2e-6)
# The model in bfloat16 against the reference in float32 on the same
# bf16-rounded weights: every matmul output and residual is rounded to 8 bits
# (2**-8 = 0.4 %), through two blocks and the unembedding.
BF16_TOL = dict(rtol=5e-2, atol=5e-2)


def _tokens(cfg, batch=4, seq=16, seed=0):
    toks = np.random.default_rng(seed).integers(0, cfg.vocab_size, (batch, seq))
    return jnp.asarray(toks.astype(np.int32))


def _seeded(cfg, seed=5):
    """A state whose ``lora_b`` is drawn, not zeros: with B = 0 the adapter
    adds nothing and A's gradient is zero, and neither would be tested."""
    state = init_train_state(cfg, seed=seed)
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), cfg.n_layers)
    for block, key in zip(state.params["layers"], keys):
        block["lora_b"] = (
            jax.random.normal(key, block["lora_b"].shape, jnp.float32) * 0.05
        ).astype(cfg.dtype)
    return state


def _bits(x):
    a = np.ascontiguousarray(np.asarray(x))
    return a.view(np.dtype(f"u{a.dtype.itemsize}"))


def _by_path(tree):
    return {jax.tree_util.keystr(p): x for p, x in jax.tree_util.tree_leaves_with_path(tree)}


# -- the reference -----------------------------------------------------------


def _ref_rmsnorm(x, scale):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6) * scale


def _ref_logits(params, tokens, n_heads):
    """The model's forward pass, written down plainly in float32."""
    p = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    x = p["embed"][tokens]
    b, s, d = x.shape
    hd = d // n_heads
    mask = jnp.tril(jnp.ones((s, s), bool))
    for block in p["layers"]:
        h = _ref_rmsnorm(x, block["ln1_scale"])
        qkv = h @ block["wqkv"]
        if "lora_a" in block:
            qkv = qkv + (h @ block["lora_a"]) @ block["lora_b"]
        q, k, v = (qkv.reshape(b, s, 3, n_heads, hd)[:, :, i] for i in range(3))
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(mask, logits, -jnp.inf), axis=-1)
        attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, d)
        x = x + attn @ block["wo"]
        h = _ref_rmsnorm(x, block["ln2_scale"])
        x = x + jax.nn.gelu(h @ block["w_in"]) @ block["w_out"]
    return _ref_rmsnorm(x, p["ln_f_scale"]) @ p["unembed"]


def _ref_loss(params, tokens, n_heads):
    logp = jax.nn.log_softmax(_ref_logits(params, tokens, n_heads)[:, :-1], axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked)


def _ref_adapter_grads(params, tokens, n_heads):
    def of_adapters(adapters):
        layers = [{**block, **ab} for block, ab in zip(params["layers"], adapters)]
        return _ref_loss({**params, "layers": layers}, tokens, n_heads)

    adapters = [{k: block[k] for k in ADAPTERS} for block in params["layers"]]
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(of_adapters)(
            jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), adapters))


# -- the equations -----------------------------------------------------------


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, F32_TOL), (jnp.bfloat16, BF16_TOL)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("rank", [1, 8])
def test_forward_is_the_reference_with_the_adapted_projection(rank, dtype, tol):
    cfg = dataclasses.replace(TOY, lora_rank=rank, dtype=dtype)
    state, tokens = _seeded(cfg), _tokens(cfg)
    with jax.default_matmul_precision("highest"):
        want = _ref_logits(state.params, tokens, cfg.n_heads)
        got = forward(cfg, state.params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **tol)
    # The adapter is in the result: without it the logits differ by more.
    bare = {**state.params,
            "layers": [{k: v for k, v in block.items() if k not in ADAPTERS}
                       for block in state.params["layers"]]}
    with jax.default_matmul_precision("highest"):
        without = _ref_logits(bare, tokens, cfg.n_heads)
    assert float(jnp.max(jnp.abs(without - want))) > 10 * tol["atol"]


@pytest.mark.parametrize("rank", [1, 8])
def test_one_blocks_loss_and_adapter_gradients_are_the_references(rank):
    """adamw's first step leaves ``mu = (1 - b1) * g`` with b1 = 0.9: the
    gradient the step took is ten times its first moment."""
    cfg = dataclasses.replace(TOY, lora_rank=rank, dtype=jnp.float32, n_layers=1)
    state, tokens = _seeded(cfg), _tokens(cfg)
    want_loss, want_grads = _ref_adapter_grads(state.params, tokens, cfg.n_heads)
    with jax.default_matmul_precision("highest"):
        new_state, loss = make_train_step(cfg)(state, tokens)
    np.testing.assert_allclose(float(loss), float(want_loss), **F32_TOL)
    mu = new_state.opt_state[0].mu["layers"]
    for block, want in zip(mu, want_grads):
        for name in ADAPTERS:
            assert float(jnp.max(jnp.abs(want[name]))) > 1e-4, name
            np.testing.assert_allclose(np.asarray(block[name]) * 10.0, np.asarray(want[name]),
                                       rtol=1e-3, atol=1e-6, err_msg=name)


# -- what a checkpoint of the state counts on --------------------------------


@pytest.fixture(scope="module")
def mesh4():
    return Mesh(np.asarray(jax.devices()[:4]).reshape(1, 2, 2), ("dp", "sp", "tp"))


@pytest.mark.parametrize("on_mesh", [False, True], ids=["one-device", "mesh-1x2x2"])
def test_frozen_leaves_keep_their_bits_through_donated_steps(on_mesh, mesh4):
    mesh = mesh4 if on_mesh else None
    cfg = dataclasses.replace(TOY, lora_rank=8)
    state = init_train_state(cfg, seed=11, mesh=mesh)
    # A negative zero among the frozen: `p + 0.0` would give +0.0 back.
    wqkv = state.params["layers"][0]["wqkv"]
    state.params["layers"][0]["wqkv"] = jax.device_put(wqkv.at[0, 0].set(-0.0), wqkv.sharding)
    assert _bits(state.params["layers"][0]["wqkv"])[0, 0] == 0x8000
    before = {k: _bits(v).copy() for k, v in _by_path(state.as_pytree()).items()}
    step = make_train_step(cfg, mesh=mesh)
    tokens = _tokens(cfg)
    if mesh is not None:
        tokens = jax.device_put(tokens, NamedSharding(mesh, P("dp", None)))
    for _ in range(3):
        state, loss = step(state, tokens)
    assert np.isfinite(float(loss))
    after = {k: _bits(v) for k, v in _by_path(state.as_pytree()).items()}
    assert after.keys() == before.keys()
    trained = {k for k in before if any(name in k for name in ADAPTERS)}
    assert len(trained) == 3 * 2 * cfg.n_layers  # parameter, mu, nu
    for key in before:
        same = np.array_equal(before[key], after[key])
        if key in trained or key in ("['step']", "['rng']") or key.endswith(".count"):
            assert not same, f"{key} did not change in three steps"
        else:
            assert same, f"frozen leaf {key} changed"
    assert after["['params']['layers'][0]['wqkv']"][0, 0] == 0x8000


def test_opt_state_has_no_leaf_for_a_frozen_parameter():
    cfg = dataclasses.replace(TOY, lora_rank=8)
    state = init_train_state(cfg, seed=0)
    paths = list(_by_path(state.opt_state))
    moments = [p for p in paths if not p.endswith(".count")]
    assert len(moments) == 2 * 2 * cfg.n_layers
    assert all(any(name in p for name in ADAPTERS) for p in moments), moments
    # The base is the dense state's, to the bit: the adapters draw from a
    # key stream of their own.
    dense = init_train_state(TOY, seed=0)
    for key, leaf in _by_path(dense.params).items():
        assert np.array_equal(_bits(leaf), _bits(_by_path(state.params)[key])), key
    shapes = {k: v.shape for k, v in _by_path(state.params["layers"][0]).items()}
    assert shapes["['lora_a']"] == (32, 8) and shapes["['lora_b']"] == (8, 96)
    assert not np.asarray(state.params["layers"][0]["lora_b"]).any()


# -- lora_rank = 0 is what the repo had ---------------------------------------

_TODAYS_PARAMS = (
    [("['embed']", (64, 32)), ("['unembed']", (32, 64)), ("['ln_f_scale']", (32,))]
    + [(f"['layers'][{i}]['{name}']", shape) for i in range(2)
       for name, shape in (("ln1_scale", (32,)), ("ln2_scale", (32,)), ("wqkv", (32, 96)),
                           ("wo", (32, 32)), ("w_in", (32, 64)), ("w_out", (64, 32)))])
# Parameters, adamw's two moments of each, its count, the step and the key.
TODAYS_TREE = sorted(
    [(prefix + path, shape) for path, shape in _TODAYS_PARAMS
     for prefix in ("['params']", "['opt_state'][0].mu", "['opt_state'][0].nu")]
    + [("['opt_state'][0].count", ()), ("['step']", ()), ("['rng']", (2,))])


def _todays_train_step(cfg):
    """`make_train_step` as it was before `lora_rank`, for the comparison."""
    opt = _optimizer(cfg)

    def loss_fn(params, tokens):
        logits = forward(cfg, params, tokens, mesh=None)
        targets = tokens[:, 1:]
        logits = logits[:, :-1]
        losses = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
        return jnp.mean(losses)

    def train_step(state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(state.params, tokens)
        updates, new_opt_state = opt.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        new_rng = jax.random.fold_in(state.rng, state.step)
        return TrainState(params=new_params, opt_state=new_opt_state,
                          step=state.step + 1, rng=new_rng), loss

    return jax.jit(train_step, donate_argnums=(0,))


def test_rank_zero_is_todays_tree():
    assert TOY.lora_rank == 0
    state = init_train_state(TOY, seed=0)
    got = sorted((k, v.shape) for k, v in _by_path(state.as_pytree()).items())
    assert got == TODAYS_TREE
    assert all(v.dtype == jnp.bfloat16 for k, v in _by_path(state.as_pytree()).items()
               if k.startswith(("['params']", "['opt_state'][0].mu", "['opt_state'][0].nu")))


def test_rank_zero_is_todays_step_program_and_todays_bits():
    tokens = _tokens(TOY)
    state = init_train_state(TOY, seed=2)
    now = make_train_step(TOY).lower(state, tokens).as_text()
    then = _todays_train_step(TOY).lower(state, tokens).as_text()
    assert now == then
    other = init_train_state(TOY, seed=2)
    a, b = make_train_step(TOY), _todays_train_step(TOY)
    for _ in range(2):
        state, loss_a = a(state, tokens)
        other, loss_b = b(other, tokens)
    assert float(loss_a) == float(loss_b)
    got, want = _by_path(state.as_pytree()), _by_path(other.as_pytree())
    for key in want:
        assert np.array_equal(_bits(got[key]), _bits(want[key])), key


# -- shardings ------------------------------------------------------------------


def test_shardings_hold_on_four_devices(mesh4):
    cfg = dataclasses.replace(TOY, lora_rank=8)
    state = init_train_state(cfg, seed=1, mesh=mesh4)
    want = _by_path(param_shardings(cfg, mesh4))
    assert want["['layers'][0]['lora_a']"].spec == P("dp", None)
    assert want["['layers'][0]['lora_b']"].spec == P(None, "tp")

    def check(state):
        for key, leaf in _by_path(state.params).items():
            assert leaf.sharding.is_equivalent_to(want[key], leaf.ndim), key
        for moments in (state.opt_state[0].mu, state.opt_state[0].nu):
            for key, leaf in _by_path(moments).items():
                assert leaf.sharding.is_equivalent_to(want[key], leaf.ndim), key
        assert state.opt_state[0].count.sharding.is_fully_replicated

    check(state)
    # lora_b (8, 96) over tp = 2: each device holds half the columns.
    shard = state.params["layers"][0]["lora_b"].addressable_shards[0]
    assert shard.data.shape == (8, 48)
    tokens = jax.device_put(_tokens(cfg), NamedSharding(mesh4, P("dp", None)))
    step = make_train_step(cfg, mesh=mesh4)
    for _ in range(2):
        state, loss = step(state, tokens)
    check(state)
    # The sharded step computes what one device computes.
    single = init_train_state(cfg, seed=1)
    single_step = make_train_step(cfg)
    for _ in range(2):
        single, single_loss = single_step(single, _tokens(cfg))
    np.testing.assert_allclose(float(loss), float(single_loss), rtol=2e-2)

"""GPipe schedule over a pp mesh axis (parallel/pipeline.py): correctness
vs unpipelined sequential application, differentiability, and the
checkpoint round-trip of stacked per-stage state — the one state layout
the GSPMD flagship model never produces (SURVEY.md §2.12)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import torchsnapshot_tpu as ts
from torchsnapshot_tpu.parallel import (
    pipeline_stage_shardings,
    pipelined_apply,
    stack_stage_params,
)


def _pp_mesh(n: int) -> Mesh:
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices")
    return Mesh(np.asarray(jax.devices()[:n]).reshape(n), ("pp",))


def _stage_fn(params, x):
    h = jnp.tanh(x @ params["w"] + params["b"])
    return h + x  # residual keeps the hopping shape


def _make_stages(n_stages: int, d: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    return [
        {
            "w": jnp.asarray(rng.standard_normal((d, d)) * 0.1, jnp.float32),
            "b": jnp.asarray(rng.standard_normal((d,)) * 0.1, jnp.float32),
        }
        for _ in range(n_stages)
    ]


def test_pipeline_matches_sequential():
    n_stages, d = 4, 16
    mesh = _pp_mesh(n_stages)
    per_stage = _make_stages(n_stages, d)
    stacked = stack_stage_params(per_stage, mesh=mesh)
    x = jnp.asarray(
        np.random.default_rng(1).standard_normal((8, d)), jnp.float32
    )
    out = pipelined_apply(
        _stage_fn, stacked, x, mesh=mesh, n_microbatches=4
    )
    ref = x
    for p in per_stage:
        ref = _stage_fn(p, ref)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_pipeline_bubble_only_schedule():
    """n_microbatches == 1 (pure bubble) still yields the right answer."""
    n_stages, d = 2, 8
    mesh = _pp_mesh(n_stages)
    per_stage = _make_stages(n_stages, d, seed=3)
    stacked = stack_stage_params(per_stage, mesh=mesh)
    x = jnp.ones((2, d), jnp.float32)
    out = pipelined_apply(_stage_fn, stacked, x, mesh=mesh, n_microbatches=1)
    ref = x
    for p in per_stage:
        ref = _stage_fn(p, ref)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_pipeline_grad():
    """Reverse-mode through the schedule (the backward pipeline) matches
    the unpipelined gradient."""
    n_stages, d = 2, 8
    mesh = _pp_mesh(n_stages)
    per_stage = _make_stages(n_stages, d, seed=5)
    stacked = stack_stage_params(per_stage, mesh=mesh)
    x = jnp.asarray(
        np.random.default_rng(2).standard_normal((4, d)), jnp.float32
    )

    def loss_pipe(params):
        return jnp.sum(
            pipelined_apply(_stage_fn, params, x, mesh=mesh, n_microbatches=2)
            ** 2
        )

    def loss_seq(per_stage_params):
        y = x
        for p in per_stage_params:
            y = _stage_fn(p, y)
        return jnp.sum(y**2)

    g_pipe = jax.grad(loss_pipe)(stacked)
    g_seq = jax.grad(loss_seq)(per_stage)
    g_seq_stacked = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *g_seq
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
        ),
        g_pipe,
        g_seq_stacked,
    )


def test_pipeline_state_checkpoint_roundtrip(tmp_path):
    """Per-stage state through the checkpointer: stacked pp-sharded params
    save and restore byte-identically, including into a DIFFERENT pp
    degree (elastic resharding of the stage dim)."""
    n_stages, d = 4, 16
    mesh = _pp_mesh(n_stages)
    stacked = stack_stage_params(_make_stages(n_stages, d, seed=7), mesh=mesh)
    path = str(tmp_path / "snap")
    ts.Snapshot.take(path, {"pp": ts.PyTreeState(stacked)})

    # Same pp degree.
    dest = jax.tree_util.tree_map(
        lambda leaf: jax.device_put(
            jnp.zeros_like(leaf), leaf.sharding
        ),
        stacked,
    )
    wrapped = ts.PyTreeState(dest)
    ts.Snapshot(path).restore({"pp": wrapped})
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        wrapped.tree,
        stacked,
    )

    # Elastic: restore into pp=2 (stage dim resharded via overlap math).
    mesh2 = _pp_mesh(2)
    sh2 = pipeline_stage_shardings(stacked, mesh2)
    dest2 = jax.tree_util.tree_map(
        lambda leaf, s: jax.device_put(jnp.zeros_like(leaf), s),
        stacked,
        sh2,
    )
    wrapped2 = ts.PyTreeState(dest2)
    ts.Snapshot(path).restore({"pp": wrapped2})
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        wrapped2.tree,
        stacked,
    )


def test_pipeline_rejects_stage_mesh_mismatch():
    mesh = _pp_mesh(2)
    stacked = stack_stage_params(_make_stages(4, 8), mesh=None)
    with pytest.raises(ValueError, match="4 stages.*2 devices"):
        pipelined_apply(
            _stage_fn, stacked, jnp.ones((4, 8)), mesh=mesh, n_microbatches=2
        )

"""Pipeline parallelism: a GPipe schedule over a ``pp`` mesh axis.

Reference parity: none — the reference (torchsnapshot) recognizes PP state
only as generic per-rank entries (SURVEY.md §2.12: "TP / PP / EP as such:
only insofar as their state is ShardedTensor or per-rank"). This module
exists because the checkpointer claims to cover any layout a parallel
workload produces, and pipeline stages are the one layout a GSPMD-sharded
flagship model alone never exercises.

TPU-first design — a pipeline is a *schedule*, not a sharding, so it is
expressed as an explicit per-device program:

- Stage parameters are ONE stacked pytree: every leaf gains a leading
  ``n_stages`` dim sharded ``P('pp', ...)`` (``stack_stage_params``).
  For the checkpointer this is just another NamedSharding array — the
  sharded preparer persists each stage's slice from the device that owns
  it, and elastic restore across different pp degrees falls out of the
  existing overlap-based resharding.
- ``pipelined_apply`` runs the schedule under ``jax.shard_map``: at tick
  ``t`` device ``r`` computes microbatch ``t - r``; activations hop to the
  next stage with ``lax.ppermute`` inside a ``lax.scan`` (static trip
  count ``n_micro + n_stages - 1`` — the classic GPipe trapezoid with
  ``n_stages - 1`` bubble ticks).
- The whole schedule is differentiable: reverse-mode through the scan
  IS the backward pipeline (activations of all ticks are saved — GPipe
  memory semantics; swap in ``jax.checkpoint`` on the stage fn to trade
  recompute for memory).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

StageFn = Callable[[Any, jax.Array], jax.Array]


def stack_stage_params(per_stage: list, mesh: Optional[Mesh] = None) -> Any:
    """Stack per-stage parameter pytrees into one pytree whose leaves have
    a leading ``n_stages`` dim, sharded over ``pp`` when a mesh is given.

    The stacked form is what trains, pipelines, and checkpoints: one
    ``jax.Array`` per leaf, stage ``i``'s slice resident on the devices of
    mesh row ``pp=i``.
    """
    stacked = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *per_stage
    )
    if mesh is None:
        return stacked
    return jax.tree_util.tree_map(
        jax.device_put, stacked, pipeline_stage_shardings(stacked, mesh)
    )


def pipelined_apply(
    stage_fn: StageFn,
    stage_params: Any,
    x: jax.Array,
    *,
    mesh: Mesh,
    n_microbatches: int,
    axis_name: str = "pp",
) -> jax.Array:
    """Run ``x`` through ``n_stages`` copies of ``stage_fn`` as a GPipe
    pipeline over the mesh's ``axis_name`` axis.

    Args:
        stage_fn: ``(params_for_one_stage, activation) -> activation`` with
            activation shape preserved (embed before / readout after the
            pipeline — the hopping tensor must have one static shape).
        stage_params: stacked pytree from :func:`stack_stage_params`
            (leaves ``(n_stages, ...)`` sharded over ``axis_name``).
        x: ``(batch, ...)`` activations entering stage 0; ``batch`` must
            divide by ``n_microbatches``.

    Returns:
        ``(batch, ...)`` output of the last stage, replicated over the
        ``pp`` axis.
    """
    n_stages = mesh.shape[axis_name]
    leaves = jax.tree_util.tree_leaves(stage_params)
    if leaves and leaves[0].shape[0] != n_stages:
        # per_device keeps only its slice's first stage — a mismatched
        # stacking would silently drop stages, not error.
        raise ValueError(
            f"stage_params are stacked for {leaves[0].shape[0]} stages but "
            f"mesh axis {axis_name!r} has {n_stages} devices"
        )
    batch = x.shape[0]
    if batch % n_microbatches:
        raise ValueError(
            f"batch {batch} must divide by n_microbatches={n_microbatches}"
        )
    mb = batch // n_microbatches
    xs = x.reshape(n_microbatches, mb, *x.shape[1:])
    n_ticks = n_microbatches + n_stages - 1

    def per_device(params: Any, xs_local: jax.Array) -> jax.Array:
        # (1, ...) stage slice → this device's stage params.
        params = jax.tree_util.tree_map(lambda p: p[0], params)
        r = lax.axis_index(axis_name)
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            act, out_buf = carry
            # Stage 0 ingests microbatch t (while any remain); deeper
            # stages consume the activation that just hopped in.
            inp = jnp.where(
                r == 0,
                xs_local[jnp.clip(t, 0, n_microbatches - 1)],
                act,
            )
            y = stage_fn(params, inp)
            # The last stage finishes microbatch t - (n_stages - 1).
            done = t - (n_stages - 1)
            write = jnp.logical_and(
                r == n_stages - 1,
                jnp.logical_and(done >= 0, done < n_microbatches),
            )
            slot = jnp.clip(done, 0, n_microbatches - 1)
            updated = lax.dynamic_update_slice(
                out_buf,
                y[None].astype(out_buf.dtype),
                (slot,) + (0,) * y.ndim,
            )
            out_buf = jnp.where(write, updated, out_buf)
            act = lax.ppermute(y, axis_name, perm)
            return (act, out_buf), None

        zero_act = jnp.zeros_like(xs_local[0])
        out0 = jnp.zeros_like(xs_local)
        (_, out_buf), _ = lax.scan(
            tick, (zero_act, out0), jnp.arange(n_ticks)
        )
        # Only the last stage holds real outputs; psum replicates them
        # (every other stage contributes zeros).
        out_buf = lax.psum(
            jnp.where(r == n_stages - 1, out_buf, jnp.zeros_like(out_buf)),
            axis_name,
        )
        return out_buf

    spec_params = jax.tree_util.tree_map(
        lambda leaf: P(axis_name, *([None] * (leaf.ndim - 1))), stage_params
    )
    out = jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(spec_params, P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, xs)
    return out.reshape(batch, *x.shape[1:])


def pipeline_stage_shardings(
    stage_params: Any, mesh: Mesh, axis_name: str = "pp"
) -> Any:
    """NamedSharding pytree for stacked stage params (checkpoint restore
    destinations)."""
    return jax.tree_util.tree_map(
        lambda leaf: NamedSharding(
            mesh, P(axis_name, *([None] * (leaf.ndim - 1)))
        ),
        stage_params,
    )

"""Driver `restore_loop`: `restore_latest` into the live state, back to back.

Set-up trains two steps, commits one snapshot with the blocking `save`, and
trains on for the losses an uninterrupted run gives. The destination is that
trained-on state with every bit flipped (or, where the configuration names a
`restore_mesh`, a state built from `seed + 1` on it), so nothing of the saved
values is resident; one restore is the warm-up.
Before each restore of the window the harness flips every bit of the
destinations, so a restore that leaves a leaf alone cannot pass; after each
it takes the fingerprint that `verify` reads once the window is closed. Both
are on-device passes of a few milliseconds and are inside the window's
seconds. The window ends at the first completion after `seconds`.
"""

import time
from typing import Any, Dict, List

import numpy as np

import reference

SAVED_STEP = 2
REPLAY_STEPS = 3


def setup(ctx) -> None:
    ctx.mgr = ctx.manager()
    state = ctx.init_state(ctx.seed, ctx.mesh)
    ctx.jax.block_until_ready(state)
    ctx.stage("init_state")
    step_fn = ctx.step_fn(ctx.mesh)
    for step in range(1, SAVED_STEP + 1):
        state, loss = step_fn(state, ctx.tokens(step, ctx.mesh))
    loss.block_until_ready()
    ctx.stage("first_steps")
    app_state = ctx.app_state(state, SAVED_STEP)
    ctx.expected = np.asarray(ctx.fingerprint(ctx.saved_tree(app_state)))
    ctx.mgr.save(SAVED_STEP, app_state)
    del app_state
    ctx.stage("save")
    ctx.losses_uninterrupted = []
    for step in range(SAVED_STEP + 1, SAVED_STEP + 1 + REPLAY_STEPS):
        state, loss = step_fn(state, ctx.tokens(step, ctx.mesh))
        ctx.losses_uninterrupted.append(float(loss))
    if ctx.restore_mesh is ctx.mesh:
        # The trained-on state is the destination: `_restore` flips its every
        # bit first, which leaves nothing of the saved values resident and
        # spares a second init (11 s at these sizes).
        ctx.app = ctx.app_state(state, 0)
        del state
    else:
        del state
        ctx.app = ctx.app_state(ctx.init_state(ctx.seed + 1, ctx.restore_mesh), 0)
    ctx.stage("uninterrupted_steps")
    _restore(ctx, {})
    ctx.stage("warmup_restore")
    # The steps `verify` replays run on the restore's layout.
    if ctx.restore_mesh is not ctx.mesh:
        _replay(ctx)
        _restore(ctx, {})


def _restore(ctx, op: Dict[str, Any]) -> None:
    app = ctx.app
    for tree in ("params", "opt"):
        app[tree].tree = ctx.scramble(app[tree].tree)
    app["progress"]["step"] = -1
    t = time.monotonic()
    with ctx.annotate("restore"):
        op["returned_step"] = ctx.mgr.restore_latest(app)
        ctx.block(app)
    op["restore_s"] = time.monotonic() - t
    op["progress_step"] = app["progress"]["step"]
    op["fingerprint"] = ctx.fingerprint(ctx.saved_tree(app))
    op["critical_path"] = ctx.report("restore", ctx.mgr, SAVED_STEP)


def _replay(ctx) -> List[float]:
    """The steps after the saved one, from the restored state (which they consume)."""
    state = ctx.state_of(ctx.app, SAVED_STEP, ctx.restore_mesh)
    step_fn, losses = ctx.step_fn(ctx.restore_mesh), []
    for step in range(SAVED_STEP + 1, SAVED_STEP + 1 + REPLAY_STEPS):
        state, loss = step_fn(state, ctx.tokens(step, ctx.restore_mesh))
        losses.append(float(loss))
    ctx.app = ctx.app_state(state, SAVED_STEP + REPLAY_STEPS)
    return losses


def window(ctx, seconds: float) -> Dict[str, Any]:
    ops: List[Dict[str, Any]] = []
    t_start = time.monotonic()
    while True:
        op: Dict[str, Any] = {}
        _restore(ctx, op)
        ops.append(op)
        if time.monotonic() - t_start >= seconds:
            break
    window_s = time.monotonic() - t_start
    ctx.ops = ops
    return {
        "window_s": window_s,
        "attempted": len(ops),
        "failed": sum(op["returned_step"] != SAVED_STEP for op in ops),
        "end_to_end": {"restore_s": window_s / len(ops)},
        "ops": [{k: v for k, v in op.items() if k != "fingerprint"} for op in ops],
    }


def verify(ctx) -> List[Dict[str, Any]]:
    """Every restore of the window gave back the saved step and, in every
    leaf, the bits the save was handed; the steps after the last restore give
    the losses the uninterrupted run gave."""
    differing = sum(
        reference.leaves_differing(ctx.expected, np.asarray(op["fingerprint"])) for op in ctx.ops)
    step_gap = sum(
        abs((op["returned_step"] if op["returned_step"] is not None else -1) - SAVED_STEP)
        + abs(op["progress_step"] - SAVED_STEP) for op in ctx.ops)
    return [
        reference.check("leaves_differing", differing, 0),
        reference.check("restored_step_gap", step_gap, 0),
        reference.check("loss_gap", reference.loss_gap(ctx.losses_uninterrupted, _replay(ctx)),
                        ctx.config.get("restore_loss_rtol", 0)),
    ]

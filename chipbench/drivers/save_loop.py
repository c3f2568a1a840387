"""Driver `save_loop`: train steps with an asynchronous save of the whole
state every `save_every_steps`, at most one in flight.

The loop is a job's: it dispatches steps and never waits for a loss, so the
runtime has some tens of steps queued when a save is due, and `async_save`
is called, and timed, right there. What the call spends waiting for the
device to reach the state it clones is stall as a job's clock reads it; the
traced run says how much of it that is (`capture_queue_wait_s`).

The window is whole cycles. A cycle opens at a step where a save is due:
wait for the save before it if that is still in flight (a forced wait: stall,
as in a job), start the next, train `save_every_steps` steps and call
`wait()` from the loop at the first step boundary after `done()`. The window
closes where the next cycle would have opened, once the last step is done on
the device: the one `block_until_ready` on a loss. Every second and every
step up to there is in the numbers. A cycle is 10 to 25 s at the cells'
sizes, so the window holds the whole number of cycles nearest to `seconds`
(at least one): a cycle opens only while half a cycle, at the mean length of
those before it, still fits.
"""

import time
from typing import Any, Dict, List

import numpy as np

import reference

REPLAY_STEPS = 3
# More than the runtime lets a loop queue (16 to 35 at these sizes).
PRIME_STEPS = 48


def _mean(values: List[float]) -> float:
    return sum(values) / len(values) if values else 0.0


def setup(ctx) -> None:
    """State from the seed, one compiled step, one save: every shape the
    window uses."""
    jax = ctx.jax
    ctx.mgr = ctx.manager()
    ctx.state = ctx.init_state(ctx.seed, ctx.mesh)
    ctx.step = 0
    jax.block_until_ready(ctx.state)
    ctx.stage("init_state")
    step_fn = ctx.step_fn(ctx.mesh)
    for _ in range(2):
        ctx.step += 1
        ctx.state, loss = step_fn(ctx.state, ctx.tokens(ctx.step, ctx.mesh))
    loss.block_until_ready()
    ctx.stage("first_steps")
    app_state = ctx.app_state(ctx.state, ctx.step)
    pending = ctx.mgr.async_save(ctx.step, app_state)
    ctx.fingerprint(ctx.saved_tree(app_state)).block_until_ready()
    del app_state
    ctx.step += 1
    ctx.state, loss = step_fn(ctx.state, ctx.tokens(ctx.step, ctx.mesh))
    pending.wait()
    loss.block_until_ready()
    ctx.stage("warmup_save")


def prime(ctx) -> None:
    """The last of set-up: the loop as a save finds it, with steps dispatched
    and not waited for, so that the window's first save is like its others."""
    step_fn = ctx.step_fn(ctx.mesh)
    ctx.primed = []
    for _ in range(min(PRIME_STEPS, 8) if ctx.rehearse else PRIME_STEPS):
        ctx.step += 1
        ctx.state, loss = step_fn(ctx.state, ctx.tokens(ctx.step, ctx.mesh))
        ctx.primed.append(loss)


def window(ctx, seconds: float) -> Dict[str, Any]:
    every = int(ctx.traffic["save_every_steps"])
    if ctx.rehearse:
        every = min(every, 8)  # the CPU's toy steps are no faster than the chip's real ones
    step_fn, mgr, mesh = ctx.step_fn(ctx.mesh), ctx.mgr, ctx.mesh
    saves: List[Dict[str, Any]] = []
    losses: Dict[int, Any] = {}
    pending, save = None, None

    def finish(forced: bool) -> None:
        nonlocal pending
        t = time.monotonic()
        with ctx.annotate("wait"):
            pending.wait()
        save["wait_s"] = time.monotonic() - t
        save["forced"] = forced
        save["commit_s"] = ctx.marker_age(mgr, save["step"], save["called_at"])
        save["marker"] = save["commit_s"] is not None
        save["critical_path"] = ctx.report("async_take", mgr, save["step"])
        pending = None

    first_step = ctx.step
    t_start = time.monotonic()
    # Steps of `prime` that the device has yet to run: the window's work too.
    queued = sum(not loss.is_ready() for loss in ctx.primed)
    while True:
        if pending is not None:
            finish(forced=True)
        elapsed = time.monotonic() - t_start
        if saves and elapsed + 0.5 * elapsed / len(saves) >= seconds:
            break
        app_state = ctx.app_state(ctx.state, ctx.step)
        save = {"step": ctx.step, "called_at": time.time(), "opened_s": time.monotonic() - t_start}
        if not saves:
            save["steps_queued_at_start"] = queued
        t = time.monotonic()
        with ctx.annotate("async_save"):
            pending = mgr.async_save(ctx.step, app_state)
        save["call_s"] = time.monotonic() - t
        save["fingerprint"] = ctx.fingerprint(ctx.saved_tree(app_state))
        del app_state
        saves.append(save)
        for _ in range(every):
            ctx.step += 1
            with ctx.annotate("train_step"):
                ctx.state, losses[ctx.step] = step_fn(ctx.state, ctx.tokens(ctx.step, mesh))
            if pending is not None and pending.done():
                finish(forced=False)
    with ctx.annotate("last_step"):
        losses[ctx.step].block_until_ready()
    window_s = time.monotonic() - t_start

    steps = ctx.step - first_step + queued
    last = saves[-1]["step"]
    ctx.saves = saves
    ctx.losses_after_last = [float(losses[s]) for s in range(last + 1, last + 1 + REPLAY_STEPS)]
    return {
        "window_s": window_s,
        "attempted": len(saves),
        "failed": sum(not s["marker"] for s in saves),
        "end_to_end": {
            "save_stall_s": _mean([s["call_s"] + s["wait_s"] for s in saves]),
            "save_commit_s": _mean([s["commit_s"] for s in saves if s["commit_s"] is not None]),
            "train_steps_per_s": steps / window_s,
        },
        "steps": steps,
        "ops": [{k: v for k, v in s.items() if k != "fingerprint"} for s in saves],
    }


def verify(ctx) -> List[Dict[str, Any]]:
    """Once the window is closed: every save had its marker when `wait()`
    returned; the last one, the one retention kept, restores into the live
    state bit-identically to what the save was handed, and the steps after it
    give the losses the uninterrupted run gave."""
    saves, mgr = ctx.saves, ctx.mgr
    last = saves[-1]
    expected = np.asarray(last["fingerprint"])
    app_state = ctx.app_state(ctx.state, ctx.step)
    del ctx.state
    for tree in ("params", "opt"):
        app_state[tree].tree = ctx.scramble(app_state[tree].tree)
    restored = mgr.restore_latest(app_state)
    got = np.asarray(ctx.fingerprint(ctx.saved_tree(app_state)))
    progress = app_state["progress"]["step"]
    step_gap = (abs((restored if restored is not None else -1) - last["step"])
                + abs(progress - last["step"]))
    state = ctx.state_of(app_state, last["step"], ctx.mesh)
    del app_state
    step_fn, replayed = ctx.step_fn(ctx.mesh), []
    for step in range(last["step"] + 1, last["step"] + 1 + REPLAY_STEPS):
        state, loss = step_fn(state, ctx.tokens(step, ctx.mesh))
        replayed.append(float(loss))
    return [
        reference.check("saves_without_marker", sum(not s["marker"] for s in saves), 0),
        reference.check("leaves_differing", reference.leaves_differing(expected, got), 0),
        reference.check("restored_step_gap", step_gap, 0),
        reference.check("loss_gap", reference.loss_gap(ctx.losses_after_last, replayed),
                        ctx.config.get("restore_loss_rtol", 0)),
    ]

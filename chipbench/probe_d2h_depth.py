"""How many device-to-host transfers the link wants in flight, with a cell's
train loop running beside them.

    python chipbench/probe_d2h_depth.py --workload neox-6.9b-l2.async-full [--out <file>]

One process. A thread dispatches the cell's train steps and never waits for a
loss, as `drivers/save_loop.py` does. Beside it, for each row of the table, the
state's leaves are cloned on the device as a save's capture clones them (under
the loop's lock, so the clones queue behind the runtime's steps as in the cell),
waited for, and moved to the host:

- `async`: at most `depth` transfers started by `copy_to_host_async()` and not
  yet collected; four threads collect them in order with `np.asarray` (the
  library's `staging_threads`), and each collection starts the next transfer;
- `blocking`: `depth` threads, each in a blocking `np.asarray` of one clone
  that nothing started before: what the save pipeline does at `depth` 4.

Then the same over 2 GiB of equal pieces (32 and 128 MiB) in `async` mode: is
it the count in flight or the piece size that carries the rate. A row is the
bytes, wall seconds, GiB/s and the steps the loop dispatched meanwhile per
second. Nothing here is compared or part of any run of a cell: `PERF.md`
quotes it as a probe, by the platform its lines name.
"""

import argparse
import json
import os
import queue
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import cells
from workload import Context

DEPTHS = (1, 4, 8, 16, 32, 64)
COLLECTORS = 4
UNIFORM_BYTES = 2 << 30
UNIFORM_PIECES = (32 << 20, 128 << 20)


class Loop:
    """The train loop beside the transfers: steps dispatched back to back, the
    state donated from one to the next. `lock` is held across a dispatch, and
    by whoever reads `state`."""

    def __init__(self, ctx) -> None:
        self.ctx, self.lock, self.steps = ctx, threading.Lock(), 0
        self.state = ctx.init_state(ctx.seed, ctx.mesh)
        self._step_fn = ctx.step_fn(ctx.mesh)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, name="probe-train", daemon=True)

    def _run(self) -> None:
        loss = None
        while not self._stop.is_set():
            with self.lock:
                self.steps += 1
                self.state, loss = self._step_fn(
                    self.state, self.ctx.tokens(self.steps, self.ctx.mesh))
        loss.block_until_ready()

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._thread.join()


def move_async(arrays: List[Any], depth: int) -> None:
    admitted = threading.Semaphore(depth)
    started: "queue.Queue[Any]" = queue.Queue()

    def collect() -> None:
        while True:
            x = started.get()
            if x is None:
                return
            np.asarray(x)
            admitted.release()

    with ThreadPoolExecutor(COLLECTORS) as pool:
        for _ in range(COLLECTORS):
            pool.submit(collect)
        for x in arrays:
            admitted.acquire()
            x.copy_to_host_async()
            started.put(x)
        for _ in range(COLLECTORS):
            started.put(None)


def move_blocking(arrays: List[Any], depth: int) -> None:
    with ThreadPoolExecutor(depth) as pool:
        list(pool.map(np.asarray, arrays))


MOVES = {"async": move_async, "blocking": move_blocking}


def timed(jax, loop: Loop, arrays: List[Any], mode: str, depth: int) -> Dict[str, Any]:
    jax.block_until_ready(arrays)
    nbytes = sum(x.nbytes for x in arrays)
    steps, t = loop.steps, time.monotonic()
    MOVES[mode](arrays, depth)
    wall = time.monotonic() - t
    return {"mode": mode, "depth": depth, "pieces": len(arrays), "bytes": nbytes,
            "wall_s": wall, "GiB_per_s": nbytes / 2**30 / wall,
            "loop_steps_per_s": (loop.steps - steps) / wall}


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out")
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, cells.ROOT)
    import jax
    import jax.numpy as jnp

    import torchsnapshot_tpu as ts
    from torchsnapshot_tpu.io_preparer import _capture_clone_jit

    bench = cells.benchmark()
    cell = cells.cell(bench, args.workload)
    ctx = Context(jax, ts, cell, cells.config(bench, cell["config"]),
                  cells.traffic(cell["traffic"]), args.seed, "", args.rehearse, None)
    device = jax.devices()[0]
    where = f"platform={device.platform} device_kind={device.device_kind}"
    loop = Loop(ctx)
    clone = _capture_clone_jit()

    def clones() -> List[Any]:
        with loop.lock:
            leaves = [x for x in jax.tree_util.tree_leaves(
                (loop.state.params, loop.state.opt_state)) if x.ndim]
            return [clone(x) for x in leaves]

    jax.block_until_ready(clones())  # every clone shape compiled before the loop starts
    loop.start()
    time.sleep(2.0)
    rows = []

    def row(what: str, arrays: List[Any], mode: str, depth: int) -> None:
        r = dict(timed(jax, loop, arrays, mode, depth), what=what)
        rows.append(r)
        print(f"probe_d2h_depth: {what} {mode} depth {depth}: {r['pieces']} pieces, "
              f"{r['bytes'] / 2**30:.2f} GiB in {r['wall_s']:.3f} s = {r['GiB_per_s']:.3f} GiB/s, "
              f"loop {r['loop_steps_per_s']:.2f} steps/s", flush=True)

    for mode in MOVES:
        for depth in DEPTHS:
            row("state", clones(), mode, depth)
    total = (1 << 24) if args.rehearse else UNIFORM_BYTES
    for piece in UNIFORM_PIECES:
        piece = piece >> 7 if args.rehearse else piece
        base = jnp.zeros((piece,), jnp.uint8)
        for n, depth in enumerate(d for d in DEPTHS if d <= total // piece):
            # New arrays each time: jax keeps the host copy of one it has given out.
            with loop.lock:
                arrays = [base + np.uint8(n + 1) for _ in range(total // piece)]
            row(f"uniform_{piece >> 20}MiB", arrays, "async", depth)
            del arrays
    loop.stop()
    print(f"probe_d2h_depth: {where} cell={cell['name']} loop steps {loop.steps}", flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"where": where, "cell": cell["name"], "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()

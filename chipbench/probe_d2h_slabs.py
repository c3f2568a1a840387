"""Where a save's device-to-host copies land, with a cell's train loop beside
them: in memory the process has never touched, or in recycled slabs of
`dest_pool` (`torchsnapshot_tpu.d2h_slabs`).

    python chipbench/probe_d2h_slabs.py --workload neox-6.9b-l2.async-full [--out <file>]

One process. `probe_d2h_depth.Loop` dispatches the cell's train steps and never
waits for a loss. Beside it, for each pass, the state's leaves are cloned on the
device as a save's capture clones them and waited for, and four threads (the
library's `staging_threads`) take the clones one at a time, each in a blocking
`np.asarray`, and let each go once it is on the host, with its host copy, as the
save pipeline does:

- `today`: numpy's own allocator;
- `fresh`: under the slab handler, the pool emptied first (slabs made now; a
  slab the pass gave back may serve a later leaf of the same size);
- `recycled`: under the slab handler, on the slabs the pass before left.

A row is the bytes, wall seconds, GiB/s, the share of the bytes that landed in
a slab (`leased_share`: what the handler caught), of those in a slab used
before (`recycled_share`), the process's system CPU seconds per GiB
(`getrusage`), the steps the loop dispatched per second, and what the pool
retains after the pass. Nothing here is compared or part of any run of a cell:
`PERF.md` quotes it as a probe, by the platform its lines name.
"""

import argparse
import collections
import json
import os
import resource
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import cells
from probe_d2h_depth import COLLECTORS, Loop
from workload import Context

PASSES = ("today", "fresh", "recycled", "today", "recycled")


def move(jax, loop: Loop, arrays: List[Any], what: str) -> Dict[str, Any]:
    from torchsnapshot_tpu import d2h_slabs, dest_pool
    from torchsnapshot_tpu.scheduler import derived_staging_window_bytes

    jax.block_until_ready(arrays)
    sizes = [int(x.nbytes) for x in arrays]
    window = derived_staging_window_bytes(sizes, 1 << 40)
    if what == "fresh":
        dest_pool.process_pool().clear()
    todo = collections.deque(arrays)
    del arrays
    counts = {"leased": 0, "recycled": 0}
    lock = threading.Lock()

    def collect() -> None:
        with d2h_slabs.staging_window(sizes, window):
            while True:
                try:
                    x = todo.popleft()
                except IndexError:
                    return
                if what == "today":
                    np.asarray(x)
                    continue
                with d2h_slabs.lease(int(x.nbytes)) as lease:
                    host = np.asarray(x)
                x = host = None
                d2h_slabs.drop_runtime_refs()
                if lease is not None:
                    with lock:
                        counts["leased"] += lease.leased_bytes
                        counts["recycled"] += lease.recycled_bytes

    nbytes = sum(sizes)
    steps, t = loop.steps, time.monotonic()
    sys0 = resource.getrusage(resource.RUSAGE_SELF).ru_stime
    with ThreadPoolExecutor(COLLECTORS) as pool:
        for f in [pool.submit(collect) for _ in range(COLLECTORS)]:
            f.result()
    wall = time.monotonic() - t
    sys_s = resource.getrusage(resource.RUSAGE_SELF).ru_stime - sys0
    return {"what": what, "pieces": len(sizes), "bytes": nbytes, "wall_s": wall,
            "GiB_per_s": nbytes / 2**30 / wall,
            "leased_share": counts["leased"] / nbytes,
            "recycled_share": counts["recycled"] / nbytes,
            "sys_s_per_GiB": sys_s / (nbytes / 2**30),
            "loop_steps_per_s": (loop.steps - steps) / wall,
            "pool_retained_bytes": dest_pool.process_pool().retained_bytes(),
            "window_bytes": window}


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
    for what in PASSES:
        r = move(jax, loop, clones(), what)
        rows.append(r)
        print(f"probe_d2h_slabs: {what}: {r['bytes'] / 2**30:.2f} GiB in {r['wall_s']:.3f} s = "
              f"{r['GiB_per_s']:.3f} GiB/s, leased {100 * r['leased_share']:.3f} %, recycled "
              f"{100 * r['recycled_share']:.3f} %, system {r['sys_s_per_GiB']:.3f} s/GiB, "
              f"loop {r['loop_steps_per_s']:.2f} steps/s", flush=True)
    loop.stop()
    print(f"probe_d2h_slabs: {where} cell={cell['name']} loop steps {loop.steps}", flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"where": where, "cell": cell["name"], "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()

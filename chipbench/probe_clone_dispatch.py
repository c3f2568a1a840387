"""What a save's clone dispatches cost the caller while the runtime's queue
is full of train steps: one program over the whole state against one a leaf.

    python chipbench/probe_clone_dispatch.py --workload neox-6.9b-l2.async-full [--out <file>]

One process, the cell's state and train step. Each reading primes the loop as
`drivers/save_loop.prime` leaves it (48 steps dispatched, none waited for) and
then times, by the host's clock and with nothing waited for inside it,

- `tree`: the dispatch of one jitted copy of every leaf a save clones;
- `per_leaf`: the dispatch of one jitted copy a leaf, each timed by itself
  (what `ArrayBufferStager.capture` did until PR 35);

and after the dispatch how long until the copies are ready. Then, with
nothing queued and the compile cache off, the cold compile of the tree copy
over the cell's own leaves and over 47, 299, 512, 1,024 and 4,096 leaves of
128 KiB, and a dispatch of each into the empty queue. Nothing here is compared or
part of any run of a cell: `PERF.md` quotes it as a probe, by the platform its
lines name.
"""

import argparse
import json
import os
import sys
import time
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import cells
from workload import Context

PRIME_STEPS = 48  # drivers/save_loop.PRIME_STEPS
READINGS = 3
SYNTHETIC_LEAVES = (47, 299, 512, 1024, 4096)
SYNTHETIC_SHAPE = (256, 256)  # bf16: 128 KiB a leaf


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

    bench = cells.benchmark()
    cell = cells.cell(bench, args.workload)
    ctx = Context(jax, ts, cell, cells.config(bench, cell["config"]),
                  cells.traffic(cell["traffic"]), args.seed, "", args.rehearse, None)
    device = jax.devices()[0]
    where = f"platform={device.platform} device_kind={device.device_kind}"
    out: Dict[str, Any] = {"where": where, "cell": cell["name"]}

    def say(msg: str) -> None:
        print(f"probe_clone_dispatch: {msg}", flush=True)

    def clone_leaf(x):
        return jnp.copy(x)

    def clone_tree(xs):
        return [jnp.copy(x) for x in xs]

    per_leaf, tree = jax.jit(clone_leaf), jax.jit(clone_tree)

    state = ctx.init_state(ctx.seed, ctx.mesh)
    step_fn = ctx.step_fn(ctx.mesh)
    step = 0

    def train(n: int) -> List[Any]:
        nonlocal state, step
        losses = []
        for _ in range(n):
            step += 1
            state, loss = step_fn(state, ctx.tokens(step, ctx.mesh))
            losses.append(loss)
        return losses

    def leaves() -> List[Any]:
        tree_ = ctx.saved_tree(ctx.app_state(state, step))
        return [x for x in jax.tree_util.tree_leaves(tree_) if isinstance(x, jax.Array)]

    train(2)[-1].block_until_ready()
    # From here on every compile is cold: the clone programs are what is timed.
    # (`reset_cache`: a cache already in use goes on serving hits without it.)
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    n_steps = 8 if args.rehearse else PRIME_STEPS
    t = time.monotonic()
    train(n_steps)[-1].block_until_ready()
    step_s = (time.monotonic() - t) / n_steps
    src = leaves()
    nbytes = sum(x.nbytes for x in src)
    out.update(leaves=len(src), bytes=nbytes, step_s=step_s)
    say(f"{where} cell={cell['name']} leaves {len(src)} bytes {nbytes} step {step_s:.4f} s")

    t = time.monotonic()
    lowered = tree.lower(src)
    t_lower = time.monotonic() - t
    lowered.compile()
    t_compile = time.monotonic() - t - t_lower
    out["state_compile"] = {"leaves": len(src), "lower_s": t_lower, "compile_s": t_compile}
    say(f"cold compile of the tree copy over the state's {len(src)} leaves: "
        f"lower {t_lower:.3f} s, compile {t_compile:.3f} s")
    t = time.monotonic()
    jax.block_until_ready([per_leaf(x) for x in src])
    say(f"per-leaf clones compiled and run once: {time.monotonic() - t:.3f} s")
    jax.block_until_ready(tree(src))
    del src

    rows: List[Dict[str, Any]] = []
    for reading in range(READINGS):
        for mode in ("tree", "per_leaf"):
            primed = train(n_steps)
            src = leaves()
            queued = sum(not loss.is_ready() for loss in primed)
            t = time.monotonic()
            if mode == "tree":
                copies = tree(src)
                each = [time.monotonic() - t]
            else:
                copies, each = [], []
                for x in src:
                    t1 = time.monotonic()
                    copies.append(per_leaf(x))
                    each.append(time.monotonic() - t1)
            dispatch_s = time.monotonic() - t
            still = sum(not loss.is_ready() for loss in primed)
            jax.block_until_ready(copies)
            ready_s = time.monotonic() - t
            del copies, src
            primed[-1].block_until_ready()
            slow = sorted(each, reverse=True)
            row = {"mode": mode, "reading": reading, "steps_queued_before": queued,
                   "steps_queued_after": still, "dispatch_s": dispatch_s, "ready_s": ready_s,
                   "dispatches": len(each), "dispatches_over_10ms": sum(d > 0.010 for d in each),
                   "slowest_s": slow[0], "median_s": slow[len(slow) // 2]}
            rows.append(row)
            say(f"{mode} reading {reading}: {queued} steps queued before, {still} after; "
                f"dispatch {dispatch_s:.4f} s in {len(each)} dispatches "
                f"({row['dispatches_over_10ms']} over 10 ms, slowest {slow[0]:.4f} s, "
                f"median {row['median_s'] * 1e3:.3f} ms); copies ready after {ready_s:.4f} s")
    out["rows"] = rows

    compiles = []
    for n in SYNTHETIC_LEAVES:
        n = min(n, 64) if args.rehearse else n
        xs = [jnp.full(SYNTHETIC_SHAPE, i, jnp.bfloat16) for i in range(n)]
        jax.block_until_ready(xs)
        t = time.monotonic()
        lowered = tree.lower(xs)
        t_lower = time.monotonic() - t
        lowered.compile()
        t_compile = time.monotonic() - t - t_lower
        t = time.monotonic()
        first = tree(xs)  # traces again; no second compile (0.07 s at 1,024 leaves)
        t_first = time.monotonic() - t
        jax.block_until_ready(first)
        t = time.monotonic()
        again = tree(xs)
        t_dispatch = time.monotonic() - t
        jax.block_until_ready(again)
        t_run = time.monotonic() - t
        compiles.append({"leaves": n, "lower_s": t_lower, "compile_s": t_compile,
                         "first_call_s": t_first, "warm_dispatch_s": t_dispatch,
                         "warm_ready_s": t_run})
        say(f"tree copy of {n} leaves of 128 KiB, empty queue: lower {t_lower:.3f} s, "
            f"compile {t_compile:.3f} s, first call {t_first:.3f} s, warm dispatch "
            f"{t_dispatch * 1e3:.3f} ms, ready {t_run * 1e3:.3f} ms")
        del xs, first, again
    out["synthetic_compiles"] = compiles
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()

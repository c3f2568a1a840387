"""The `loss_gap` of a cell with a `restore_mesh`, over many seeds in one
process: what `restore_loss_rtol` in its configuration file is set from.

    python chipbench/probe_loss_gap.py --workload <cell> --seeds 1,2,3 [--out <file>]

For each seed the harness's own state, tokens and step functions
(`workload.Context`) run as `drivers/restore_loop.py` runs them: two steps on
the saved mesh, then three more there and the same three on the restore mesh.
The save and the restore between them are replaced by `jax.device_put` onto
the restore mesh's shardings: a restore gives back every bit (`correct` holds
it to that), so the replayed losses are the harness's, digit for digit, at a
fifth of a run's seconds a seed. Nothing here is compared: `PERF.md` quotes it
as a probe, by the platform its lines name. `--rehearse` runs toy sizes on the
CPU backend.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import cells
import reference
from drivers.restore_loop import REPLAY_STEPS, SAVED_STEP
from workload import Context


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True, help="comma-separated")
    p.add_argument("--out")
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, cells.ROOT)
    import jax

    import torchsnapshot_tpu as ts

    bench = cells.benchmark()
    cell = cells.cell(bench, args.workload)
    config = cells.config(bench, cell["config"])
    seeds = [int(s) for s in args.seeds.split(",")]
    ctx = Context(jax, ts, cell, config, cells.traffic(cell["traffic"]), seeds[0], "",
                  args.rehearse, None)
    if ctx.restore_mesh is ctx.mesh:
        raise cells.BenchError(f"{cell['config']} names no restore_mesh: its loss_gap is 0")
    template = ctx.init_state(0, ctx.restore_mesh)
    shardings = jax.tree_util.tree_map(lambda x: x.sharding, (template.params, template.opt_state))
    del template
    here, there = ctx.step_fn(ctx.mesh), ctx.step_fn(ctx.restore_mesh)
    replay = range(SAVED_STEP + 1, SAVED_STEP + 1 + REPLAY_STEPS)
    rows = []
    for seed in seeds:
        t = time.monotonic()
        ctx.seed = seed
        state = ctx.init_state(seed, ctx.mesh)
        for step in range(1, SAVED_STEP + 1):
            state, _ = here(state, ctx.tokens(step, ctx.mesh))
        # The steps donate their state: the other layout gets copies.
        params, opt = jax.device_put((state.params, state.opt_state), shardings, may_alias=False)
        rng = jax.device_put(state.rng, state.rng.sharding, may_alias=False)
        other = ctx.state_of({"params": ts.PyTreeState(params), "opt": ts.PyTreeState(opt),
                              "rng": ts.RngState(rng)}, SAVED_STEP, ctx.restore_mesh)
        del params, opt, rng
        uninterrupted, replayed = [], []
        for step in replay:
            state, loss = here(state, ctx.tokens(step, ctx.mesh))
            uninterrupted.append(float(loss))
        del state
        for step in replay:
            other, loss = there(other, ctx.tokens(step, ctx.restore_mesh))
            replayed.append(float(loss))
        del other
        rows.append({"seed": seed, "loss_gap": reference.loss_gap(uninterrupted, replayed),
                     "uninterrupted": uninterrupted, "replayed": replayed,
                     "seconds": time.monotonic() - t})
        print(json.dumps(rows[-1]), flush=True)
    device = jax.devices()[0]
    summary = {"cell": cell["name"], "platform": device.platform, "device_kind": device.device_kind,
               "device_count": cell["chips"], "seeds": len(rows),
               "widest_loss_gap": max(r["loss_gap"] for r in rows),
               "restore_loss_rtol": config.get("restore_loss_rtol", 0)}
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({**summary, "rows": rows}, f, indent=1)
    print("probe_loss_gap: " + json.dumps(summary), flush=True)


if __name__ == "__main__":
    main()

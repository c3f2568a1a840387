"""Run one cell of BENCHMARK.json on the machine this is started on.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process. Set-up (everything from process start to the window's start)
builds the state on the device from the seed and warms the cell's own shapes;
the window is driven by the traffic file's driver; once it is closed the
memory peak is read and the driver's `verify` decides `correct`. The last
line of standard output is the result. See README.md beside this file.
"""

import time

T_PROCESS_START = time.monotonic()

import argparse
import faulthandler
import glob
import json
import logging
import os
import sys
from typing import Any, Dict, List, NoReturn, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import cells
import device_trace as trace
import probes
import reference
import storage
from cells import ROOT, BenchError
from workload import Context

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# The SLO engine ships on and judges checkpoint overhead against a budget a
# benchmark's cadence breaks by construction; its verdicts are not fallbacks.
VERDICT_LOGGERS = ("torchsnapshot_tpu.telemetry.slo", "torchsnapshot_tpu.telemetry.bundle")
# What the interpreter's teardown may take once the result is out, with the
# TPU runtime and the library's threads alive, before the run fails.
TEARDOWN_LIMIT_S = 30


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def fail(msg: str) -> NoReturn:
    log(f"chipbench: FAIL: {msg}")
    faulthandler.dump_traceback_later(TEARDOWN_LIMIT_S, exit=True)
    sys.exit(1)


class WarningTrap(logging.Handler):
    """Every fallback on the library's data path warns and carries on; in a
    run of the benchmark carrying on is a failure."""

    def __init__(self) -> None:
        super().__init__(level=logging.WARNING)
        self.records: List[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        if record.name not in VERDICT_LOGGERS:
            self.records.append(f"{record.name}: {record.getMessage()}")


def place_compile_cache(jax) -> str:
    """JAX's persistent cache at a fixed path inside the checkout, unless
    JAX_COMPILATION_CACHE_DIR names one (copy of benchmarks/common.py)."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def cache_entries(path: str) -> int:
    return len(glob.glob(os.path.join(path, "*-cache")))


def parse(argv: Optional[List[str]]) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), required=True)
    p.add_argument("--rehearse", action="store_true",
                   help="toy sizes on the CPU backend; names platform=cpu and is never a result")
    p.add_argument("--fault", help="plant a fault under the timed path (faults.py); never a result")
    return p.parse_args(argv)


def main(argv: Optional[List[str]] = None) -> None:
    args = parse(argv)
    knobs = sorted(k for k in os.environ if k.startswith("TORCHSNAPSHOT_TPU_"))
    if knobs:
        fail(f"the benchmark runs the shipped configuration; unset {knobs}")
    if not os.path.isdir(os.path.join(ROOT, "torchsnapshot_tpu")):
        fail(f"no torchsnapshot_tpu package in {ROOT}")
    try:
        run(args)
    except BenchError as e:
        fail(str(e))
    # The result is out; what is left is the exit a job makes too. It hung
    # once in some 75 runs (PERF.md, PR 26): a teardown that does not end
    # writes every thread's stack to standard error and exits 1.
    sys.stdout.flush()
    faulthandler.dump_traceback_later(TEARDOWN_LIMIT_S, exit=True)


def run(args: argparse.Namespace) -> None:
    bench = cells.benchmark()
    cell = cells.cell(bench, args.workload)
    config = cells.config(bench, cell["config"])
    traffic = cells.traffic(cell["traffic"])
    driver = cells.driver(traffic["driver"])
    end_to_end = cells.metrics_of(bench, "end_to_end", cell["name"])
    per_layer = cells.metrics_of(bench, "per_layer", cell["name"])
    readers = {m["name"]: cells.layer_reader(m["name"]) for m in per_layer} if args.trace else {}

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, ROOT)
    import jax

    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
    cache_dir = place_compile_cache(jax)
    devices = jax.devices()
    want = "cpu" if args.rehearse else "tpu"
    if devices[0].platform != want:
        fail(f"jax.devices()[0].platform is {devices[0].platform!r}, need {want!r}: "
             "nothing here falls back to another backend")
    if len(devices) < cell["chips"]:
        fail(f"{len(devices)} device(s), the cell asks for {cell['chips']}")
    devices = devices[:cell["chips"]]
    if not args.rehearse:
        cells.peaks(devices[0].device_kind)  # a device the table does not know is an error
    log(f"chipbench: platform={devices[0].platform} device_kind={devices[0].device_kind} "
        f"device_count={len(devices)} cell={cell['name']} seed={args.seed} "
        f"compile cache {cache_dir}: {cache_entries(cache_dir)} entries"
        + (" -- REHEARSAL at toy sizes, not a result" if args.rehearse else ""))

    trap = WarningTrap()
    logging.getLogger("torchsnapshot_tpu").addHandler(trap)
    import torchsnapshot_tpu as ts
    from torchsnapshot_tpu import _native

    if _native.lib() is None:
        fail("the native I/O library did not build or load; pure-Python I/O is not the shipped path")

    compiles: List[str] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *a, **kw: compiles.append(name) if name == COMPILE_EVENT else None)

    root = storage.claim(traffic["storage"])
    ctx = Context(jax, ts, cell, config, traffic, args.seed, root, args.rehearse, args.fault)
    storage.check_room(root, ctx.nbytes)
    ctx.stages["start_to_context"] = time.monotonic() - T_PROCESS_START
    driver.setup(ctx)
    link = probes.link(jax, devices[0], (1 << 24) if args.rehearse else probes.PROBE_BYTES) \
        if args.trace else None
    if link:
        log(f"chipbench: link probe {link}")
        ctx.stage("link_probe")

    seconds = args.seconds
    if args.trace:
        seconds = min(seconds, traffic.get("trace_seconds", seconds))
    entries_before, compiles_before = cache_entries(cache_dir), len(compiles)
    trace_dir = trace.start(jax) if args.trace else None
    if args.trace:
        ctx.stage("trace_start")
    if hasattr(driver, "prime"):
        driver.prime(ctx)
        ctx.stage("prime")
    setup_s = time.monotonic() - T_PROCESS_START
    with ctx.annotate("window"):
        window = driver.window(ctx, seconds)
    planes = trace.stop(jax, trace_dir, ctx.stages) if args.trace else None
    new_compiles = len(compiles) - compiles_before
    new_entries = cache_entries(cache_dir) - entries_before
    if new_compiles or new_entries:
        fail(f"{new_compiles} compilation(s) and {new_entries} new compile-cache entries inside "
             "the window: a shape was not warmed up")

    stats = [d.memory_stats() or {} for d in devices]
    memory_peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}

    t = time.monotonic()
    try:
        checks = driver.verify(ctx)
    except Exception as e:  # noqa: BLE001 - a restore that raises is a wrong answer
        log(f"chipbench: verify raised {type(e).__name__}: {e}")
        checks = [reference.check("verify_raised", 1, 0)]
    verify_s = time.monotonic() - t
    if trap.records:
        for line in trap.records:
            log(f"chipbench: package warning: {line}")
        fail(f"{len(trap.records)} package log record(s) at WARNING or above: a fallback was taken")

    measured = dict(window["end_to_end"], setup_s=setup_s)
    result: Dict[str, Any] = {
        "correct": reference.correct(checks),
        "attempted": window["attempted"],
        "failed": window["failed"],
    }
    if args.trace:
        reduced = trace.reduce(planes, cell["chips"])
        run_data = {
            "cell": cell, "config": config, "traffic": traffic, "device": device,
            "state_bytes": ctx.nbytes, "window": window, "end_to_end": measured,
            "link": link, "memory": stats, "trace": reduced,
        }
        metrics = {}
        for m in per_layer:
            value = readers[m["name"]](run_data)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if reduced:
            device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
            result["breakdown"] = reduced["breakdown"]
    else:
        missing = [m["name"] for m in end_to_end if m["name"] not in measured]
        if missing:
            raise BenchError(f"driver {traffic['driver']!r} does not measure {missing}")
        metrics = {m["name"]: {"value": measured[m["name"]], "unit": m["unit"]}
                   for m in end_to_end}
    result.update(metrics=metrics, device=device)
    if args.rehearse:
        result["rehearsal"] = "toy sizes on platform=cpu: not a result"
    if args.fault:
        result["fault"] = args.fault
    result.update(window_s=window["window_s"], verify_s=verify_s, state_bytes=ctx.nbytes,
                  setup_stages=ctx.stages, ops=window["ops"],
                  tuner_decisions=ctx.tuner_decisions(), checks=checks)
    log(f"chipbench: window {window['window_s']:.2f} s, {window['attempted']} operations, "
        f"set-up {setup_s:.2f} s, verify {verify_s:.2f} s, "
        f"peak {memory_peak / 2**30:.2f} GiB, state {ctx.nbytes / 2**30:.2f} GiB")
    log("chipbench: set-up and trace stages (s): "
        + ", ".join(f"{k} {v:.2f}" for k, v in ctx.stages.items()))
    log(f"chipbench: autotuner [step, action, tunable, from, to]: {result['tuner_decisions']}")
    for c in checks:
        log(f"chipbench: check {c['name']}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()

"""What the kernel's account costs a span, and whether this kernel keeps it.

    python chipbench/probe_usage.py --out <file> [--threads <n>] [--rehearse]

Since PR 40 the library samples `resource.getrusage` around the spans that
`telemetry/names.py` lists and puts CPU microseconds (user, system) and bytes
faulted in on them. Two questions no run of a cell answers, in one process:

1. **Does this kernel keep the account?** A 64 MiB anonymous mapping touched
   for the first time, then again, and a thread that spins 50 ms, each between
   two samples of `RUSAGE_THREAD`, `RUSAGE_SELF` and `/proc/self/stat`: what
   each says moved. A kernel that counts no minor faults reads 0 through the
   first touch, and `fault_bytes` then says nothing on it.
2. **What does it cost?** One call of each clock, and one span of each kind
   (`probe_spans.py` prices one name, `stage:d2h`, a sampled one since PR 40):
   outside the sets (`stage:leaf`), with its thread's account (`stage:d2h`),
   an envelope with the process's (`snapshot:restore`, through `tracing.begin`
   / `end` as the library opens it); with the profiler off and on as `run.py
   --trace 1` starts it. `RUSAGE_SELF` walks the process's threads, so
   `--threads` idle threads stand beside the runtime's own (a benchmark
   process has some two hundred). A library without the sets (a parent of
   PR 40) prices all three as what they were there.

Nothing here is compared: `PERF.md` quotes it as a probe, by the platform its
last line names."""

import argparse
import json
import mmap
import os
import resource
import shutil
import statistics
import sys
import threading
import time
from typing import Any, Callable, Dict

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

from probe_spans import SPAN_COST_BATCH as BATCH, SPAN_COST_BATCHES as BATCHES

TOUCH_BYTES, SPIN_S = 64 << 20, 0.05


def cost_us(one: Callable[[], Any]) -> Dict[str, float]:
    """Microseconds a call: the median and the slowest of twenty batches of
    a hundred, as `probe_spans.span_cost_us` takes them."""
    batches = []
    for _ in range(BATCHES):
        t = time.perf_counter()
        for _ in range(BATCH):
            one()
        batches.append((time.perf_counter() - t) / BATCH * 1e6)
    return {"median": statistics.median(batches), "slowest_batch": max(batches)}


def _sample() -> Dict[str, float]:
    thread, process = (resource.getrusage(who) for who in
                       (resource.RUSAGE_THREAD, resource.RUSAGE_SELF))
    with open("/proc/self/stat") as f:
        stat = f.read().rsplit(")", 1)[1].split()
    return {"thread_fault_bytes": thread.ru_minflt * resource.getpagesize(),
            "process_fault_bytes": process.ru_minflt * resource.getpagesize(),
            "proc_stat_fault_bytes": int(stat[7]) * resource.getpagesize(),
            "thread_cpu_s": thread.ru_utime + thread.ru_stime,
            "process_cpu_s": process.ru_utime + process.ru_stime,
            "thread_time_s": time.thread_time()}


def account(work: Callable[[], None]) -> Dict[str, float]:
    before = _sample()
    work()
    after = _sample()
    return {k: after[k] - before[k] for k in before}


def kernel_account() -> Dict[str, Any]:
    fresh = mmap.mmap(-1, TOUCH_BYTES)
    view = memoryview(fresh)

    def touch() -> None:
        for at in range(0, TOUCH_BYTES, resource.getpagesize()):
            view[at] = 1

    def spin() -> None:
        end = time.perf_counter() + SPIN_S
        while time.perf_counter() < end:
            pass

    try:
        out = {"touch_bytes": TOUCH_BYTES, "first_touch": account(touch),
               "second_touch": account(touch), "spin_s": SPIN_S, "spin": account(spin)}
    finally:
        view.release()
        fresh.close()
    out["counts_faults"] = out["first_touch"]["process_fault_bytes"] >= TOUCH_BYTES // 2
    return out


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--out", required=True)
    p.add_argument("--threads", type=int, default=200)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    import device_trace
    from torchsnapshot_tpu.telemetry import names
    from torchsnapshot_tpu.utils import tracing

    device = jax.devices()[0]
    if device.platform != ("cpu" if args.rehearse else "tpu"):
        sys.exit(f"probe_usage: platform {device.platform!r}: nothing here falls back")

    def plain(name: str) -> Callable[[], None]:
        def one() -> None:
            with tracing.trace_annotation(name, bytes=1):
                pass
        return one

    def envelope() -> None:
        tracing.end(tracing.begin(names.SPAN_RESTORE, path="/probe"))

    spans = {"outside_the_sets": plain(names.SPAN_LEAF_STAGE),
             "thread_account": plain(names.SPAN_STAGE_D2H),
             "process_account": envelope}
    calls = {"getrusage_thread": lambda: resource.getrusage(resource.RUSAGE_THREAD),
             "getrusage_self": lambda: resource.getrusage(resource.RUSAGE_SELF),
             "thread_time_ns": time.thread_time_ns, "perf_counter": time.perf_counter}
    with open("/proc/version") as f:
        version = f.read().strip()
    out: Dict[str, Any] = {"platform": device.platform, "device_kind": device.device_kind,
                           "proc_version": version, "kernel_account": kernel_account()}
    release = threading.Event()
    for _ in range(args.threads):
        threading.Thread(target=release.wait, daemon=True).start()
    out.update(
        threads=threading.active_count(),
        sampled_names=sorted(getattr(names, "SPANS_WITH_THREAD_USAGE", ())) + sorted(
            getattr(names, "SPANS_WITH_PROCESS_USAGE", ())),
        call_us={name: cost_us(one) for name, one in calls.items()},
        span_us_profiler_off={kind: cost_us(one) for kind, one in spans.items()})
    directory = device_trace.start(jax)
    try:
        out["span_us_profiler_on"] = {kind: cost_us(one) for kind, one in spans.items()}
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(directory, ignore_errors=True)
    release.set()
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    first = out["kernel_account"]["first_touch"]
    line = ", ".join(f"{kind} {out['span_us_profiler_off'][kind]['median']:.2f} "
                     f"({out['span_us_profiler_on'][kind]['median']:.2f})" for kind in spans)
    print(f"probe_usage: platform={device.platform} device_kind={device.device_kind} "
          f"threads={out['threads']} sampled={len(out['sampled_names'])}: a first touch of "
          f"{TOUCH_BYTES >> 20} MiB reads {first['process_fault_bytes'] / 2**20:.1f} MiB of faults "
          f"(thread {first['thread_fault_bytes'] / 2**20:.1f}, /proc {first['proc_stat_fault_bytes'] / 2**20:.1f}); "
          f"getrusage {out['call_us']['getrusage_thread']['median']:.2f} us a call; us a span, "
          f"profiler off (on): {line} -> {args.out}", flush=True)


if __name__ == "__main__":
    main()

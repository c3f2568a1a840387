"""What the per-layer metrics do not carry: one traced run of a cell, read span
by span.

    python chipbench/probe_spans.py --workload <cell> --seed <n> --seconds <s> --out <file>

This is `run.py --trace 1` and nothing else drives the cell: the same set-up,
window and `verify`, the same refusals (a platform that is not the TPU, a
native library that did not load, any package warning, a compilation inside
the window), the same result line on standard output. Three hooks keep what
that run throws away: the profile before it is deleted, the recorder's mark
before the profiler starts, and the `run` the per-layer readers are given.
From them the JSON written to `--out` holds, for every operation of the
window, its whole stage table; the `capture:*`, `stage:d2h` and
`restore:place` spans of the window's first operation one by one; the longest
stretches of it no span covers; the offset and drift between the flight
recorder's clock and the profile's (`trace.xplane_offset_us`) with the
library's spans found on the profile's host planes; and what one span costs
with the profiler off and on. Nothing here is compared: `PERF.md` quotes it as
a probe, by the platform and device its last line names.
"""

import argparse
import faulthandler
import json
import os
import statistics
import sys
import time
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import cells
import device_trace
import run as harness
import stage_table

SPAN_COST_BATCHES, SPAN_COST_BATCH = 20, 100
FIRST_OP_SPANS = {
    "capture": ("SPAN_CAPTURE_CLONE", "SPAN_CAPTURE_HOST_COPY", "SPAN_CAPTURE_OBJECT"),
    "d2h": ("SPAN_STAGE_D2H",), "place": ("SPAN_RESTORE_PLACE",)}


def span_cost_us() -> Dict[str, float]:
    """Microseconds per dual-emitted span: the median and the slowest of
    twenty batches of a hundred (a collection or a thread start inside one
    batch is a one-off, not the span's cost)."""
    from torchsnapshot_tpu.telemetry import names
    from torchsnapshot_tpu.utils.tracing import trace_annotation

    batches = []
    for _ in range(SPAN_COST_BATCHES):
        t = time.perf_counter()
        for _ in range(SPAN_COST_BATCH):
            with trace_annotation(names.SPAN_STAGE_D2H, bytes=1):
                pass
        batches.append((time.perf_counter() - t) / SPAN_COST_BATCH * 1e6)
    return {"median": statistics.median(batches), "slowest_batch": max(batches)}


def one_by_one(op: Dict[str, Any], names: List[str]) -> List[Dict[str, Any]]:
    t0 = min(e["ts"] for e in op["events"])
    return [{"name": e["name"], "at_s": (e["ts"] - t0) / 1e6, "dur_s": e["dur"] / 1e6,
             **{k: e["args"].get(k) for k in ("bytes", "leaf", "kind", "arrays")}}
            for e in sorted(op["events"], key=lambda e: e["bseq"]) if e["name"] in names]


def gaps(op: Dict[str, Any], top: int = 12) -> List[Dict[str, Any]]:
    """The longest stretches of the envelopes with no span of the op open,
    each with the span that ended before it and the one that began after."""
    # A stage table's rows are the op's spans but its envelopes.
    envelopes = [e for e in op["events"] if e["name"] not in op["table"]["stages"]]
    t0 = min(e["ts"] for e in envelopes)
    spans = sorted((e for e in op["events"] if e not in envelopes), key=lambda e: e["ts"])
    found, last = [], "<envelope begins>"
    for env in sorted(envelopes, key=lambda e: e["ts"]):
        covered, end = env["ts"], env["ts"] + env["dur"]
        for e in spans:
            if e["ts"] + e["dur"] <= covered or e["ts"] >= end:
                continue
            if e["ts"] > covered:
                found.append({"at_s": (covered - t0) / 1e6, "dur_s": (e["ts"] - covered) / 1e6,
                              "after": last, "before": e["name"]})
            if e["ts"] + e["dur"] > covered:
                covered, last = e["ts"] + e["dur"], e["name"]
        if end > covered:
            found.append({"at_s": (covered - t0) / 1e6, "dur_s": (end - covered) / 1e6,
                          "after": last, "before": f"<{env['name']} ends>"})
    return sorted(found, key=lambda g: -g["dur_s"])[:top]


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--out", required=True)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args()
    kept: Dict[str, Any] = {}

    start, planes, layer_reader = device_trace.start, device_trace.planes, cells.layer_reader

    def start_marked(jax) -> str:
        # After run.py's refusals, inside set-up: the library is imported.
        from torchsnapshot_tpu.telemetry import trace as recorder_trace

        kept["cost_quiet"] = span_cost_us()
        kept["mark"] = recorder_trace.get_recorder().mark()
        directory = start(jax)
        kept["cost_traced"] = span_cost_us()
        return directory

    def planes_and_clock(data) -> List[Dict[str, Any]]:
        from torchsnapshot_tpu.telemetry import names, trace as recorder_trace

        library = {v for k, v in vars(names).items() if k.startswith("SPAN_")}
        events = [e for e in recorder_trace.get_recorder().events_since(kept["mark"])
                  if e.get("ph") == "X"
                  and not (e["name"] == names.SPAN_STAGE_D2H and e["args"].get("bytes") == 1)]
        on_profile: Dict[str, int] = {}
        for plane in data.planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name in library:
                            on_profile[e.name] = on_profile.get(e.name, 0) + 1
        kept.update(clock=recorder_trace.xplane_offset_us(data, events),
                    library_spans_on_profile=on_profile, spans_in_window=len(events))
        return planes(data)

    def keeping(name: str):
        read = layer_reader(name)

        def reader(run: Dict[str, Any]):
            kept["run"] = run
            return read(run)

        return reader

    device_trace.start, device_trace.planes, cells.layer_reader = \
        start_marked, planes_and_clock, keeping
    harness.main(["--workload", args.workload, "--seed", str(args.seed),
                  "--seconds", str(args.seconds), "--trace", "1"]
                 + (["--rehearse"] if args.rehearse else []))
    # The result line is out and run.py has armed its teardown limit.
    faulthandler.cancel_dump_traceback_later()

    from torchsnapshot_tpu.telemetry import critpath, trace as recorder_trace

    run = kept["run"]
    ops = stage_table.ops(run) or []
    out: Dict[str, Any] = {
        "cell": run["cell"]["name"], "device": run["device"], "state_bytes": run["state_bytes"],
        "end_to_end": run["end_to_end"], "ops": run["window"]["ops"],
        "span_cost_us": {"profiler_off": kept["cost_quiet"], "profiler_on": kept["cost_traced"]},
        "recorder": {"spans_in_window": kept.get("spans_in_window"),
                     "dropped": recorder_trace.get_recorder().dropped,
                     "events_per_op": [len(op["events"]) for op in ops]},
        "clock": kept.get("clock"),
        "library_spans_on_profile": kept.get("library_spans_on_profile"),
        "stage_tables": [op["table"] for op in ops],
    }
    if ops:
        out["first_op_unattributed_gaps"] = gaps(ops[0])
        for key, constants in FIRST_OP_SPANS.items():
            out[f"first_op_{key}_spans"] = one_by_one(ops[0], stage_table.span_names(*constants))
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1, default=str)
    device = run["device"]
    print(f"probe_spans: platform={device['platform']} device_kind={device['kind']} "
          f"cell={out['cell']}: {len(ops)} operations, {kept.get('spans_in_window')} spans, "
          f"clock {out['clock']}, span cost {kept['cost_quiet']['median']:.2f} us "
          f"(profiler on {kept['cost_traced']['median']:.2f}) -> {args.out}", flush=True)
    for table in out["stage_tables"]:
        harness.log(critpath.format_stage_table(table))
    faulthandler.dump_traceback_later(harness.TEARDOWN_LIMIT_S, exit=True)


if __name__ == "__main__":
    main()

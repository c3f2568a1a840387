"""The profiler's trace of the window, and its reduction to what the result
line carries: device busy seconds, the busiest device operations, and the
idle gaps by what the host was inside (`chipbench:` annotations).

The reduction works on plain data, `planes()`'s output, so that it can be
checked on a small recorded trace (tests/data/small_trace.json).
"""

import bisect
import glob
import itertools
import os
import shutil
import tempfile
import time
from typing import Any, Dict, List, Optional, Tuple

PREFIX = "chipbench:"
WINDOW = PREFIX + "window"
UNANNOTATED = "_no_chipbench_annotation_open_"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[float, float]


def start(jax) -> str:
    directory = tempfile.mkdtemp(prefix="chipbench_trace_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the Python tracer slows the host and fills the trace
    jax.profiler.start_trace(directory, profiler_options=options)
    return directory


def stop(jax, directory: str, seconds: Dict[str, float]) -> List[Dict[str, Any]]:
    """Stop the profiler, read the trace into plain data and delete it; what
    each part took goes into `seconds`."""
    t = time.monotonic()
    jax.profiler.stop_trace()
    seconds["trace_stop"] = time.monotonic() - t
    try:
        found = glob.glob(os.path.join(directory, "plugins", "profile", "*", "*.xplane.pb"))
        if not found:
            return []
        t = time.monotonic()
        out = planes(jax.profiler.ProfileData.from_file(found[0]))
        seconds["trace_read"] = time.monotonic() - t
        seconds["trace_MiB"] = os.path.getsize(found[0]) / 2**20
        return out
    finally:
        shutil.rmtree(directory, ignore_errors=True)


def planes(data) -> List[Dict[str, Any]]:
    """Device operations and `chipbench:` annotations as plain lists; the
    rest of the trace (the library's own host spans, XLA's threads) is left."""
    out = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PLANE)
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [[e.name, e.start_ns, e.duration_ns] for e in line.events
                      if device or e.name.startswith(PREFIX)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            out.append({"name": plane.name, "lines": lines})
    return out


def _union(intervals: List[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def reduce(plane_list: List[Dict[str, Any]], chips: int) -> Optional[Dict[str, Any]]:
    """None where the trace has no window annotation or no device plane."""
    annotations: List[Tuple[str, float, float]] = []
    window: Optional[Interval] = None
    devices: List[List[Tuple[str, float, float]]] = []
    modules: List[Tuple[str, float, float]] = []
    for plane in plane_list:
        for line in plane["lines"]:
            events = [(n, float(s), float(s) + float(d)) for n, s, d in line["events"]]
            if plane["name"].startswith(DEVICE_PLANE):
                if line["name"] == OPS_LINE:
                    devices.append(events)
                elif not modules:
                    modules = events
            else:
                for name, a, b in events:
                    if name == WINDOW:
                        window = (a, b)
                    elif name.startswith(PREFIX):
                        annotations.append((name, a, b))
    if window is None or not devices:
        return None
    devices = devices[:chips]
    annotations.sort(key=lambda e: e[1])
    starts = [a for _, a, _ in annotations]
    latest_end = list(itertools.accumulate((b for _, _, b in annotations), max))
    busy_ns, by_op, gaps = 0.0, {}, {}
    for events in devices:
        inside = [(n, max(a, window[0]), min(b, window[1])) for n, a, b in events
                  if b > window[0] and a < window[1]]
        busy = _union([(a, b) for _, a, b in inside])
        busy_ns += sum(b - a for a, b in busy)
        for name, a, b in inside:
            # "%fusion.13 = (bf16[...]) fusion(...)": the result's name is enough.
            name = name.split(" = ")[0].lstrip("%")
            by_op[name] = by_op.get(name, 0.0) + (b - a)
        edges = [window[0]] + [x for ab in busy for x in ab] + [window[1]]
        for gap in zip(edges[0::2], edges[1::2]):
            if gap[1] <= gap[0]:
                continue
            covered = 0.0
            # Only annotations that can reach into the gap: a traced window
            # has 1e5 gaps and hundreds of annotations.
            i = bisect.bisect_left(starts, gap[1]) - 1
            while i >= 0 and latest_end[i] > gap[0]:
                name, a, b = annotations[i]
                i -= 1
                part = _overlap(gap, (a, b))
                if part:
                    gaps[name] = gaps.get(name, 0.0) + part
                    covered += part
            if gap[1] - gap[0] - covered > 0:
                gaps[UNANNOTATED] = gaps.get(UNANNOTATED, 0.0) + gap[1] - gap[0] - covered
    n = len(devices)

    def top(table: Dict[str, float]) -> List[List[Any]]:
        ranked = sorted(table.items(), key=lambda kv: -kv[1])[:10]
        return [[name, ns / n / 1e9] for name, ns in ranked]

    return {
        "busy_s": busy_ns / n / 1e9,
        "window_s": (window[1] - window[0]) / 1e9,
        "breakdown": {"device_ops": top(by_op), "idle_gaps": top(gaps)},
        # Programs run on the first device and the host's annotations, in
        # seconds from the window's start, for readers that need the order.
        "modules": [[name, (a - window[0]) / 1e9, (b - window[0]) / 1e9]
                    for name, a, b in modules if b > window[0] and a < window[1]],
        "annotations": [[name, (a - window[0]) / 1e9, (b - window[0]) / 1e9]
                        for name, a, b in annotations],
    }

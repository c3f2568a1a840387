"""Reduction from the library's per-operation stage tables to per-operation
means: for every stage of a save or a restore the wall seconds at least one
such span was open (busy), the spans' summed seconds (thread-seconds) and the
ratio of the two (parallelism), beside the seconds no span covers.

`critpath.stage_tables` makes the tables from the flight recorder's events;
the window's operations are the last of the cell's kind in the ring. A library
without stage tables (a parent of PR 27) and a ring that dropped events of the
window both read as nothing: every reader built on this returns None.

A reader names a stage by the constant `telemetry/names.py` declares it under
(`"SPAN_STAGE_D2H"`): the span's name is spelled there alone, and a library
that lacks the constant has no such stage to read.
"""

from typing import Any, Dict, List, Optional

from device_trace import _union

KIND_BY_DRIVER = {"save_loop": "async_take", "restore_loop": "restore"}

CACHE_KEY = "_stage_table_ops"


def ops(run: Dict[str, Any]) -> Optional[List[Dict[str, Any]]]:
    """The window's operations, oldest first, each `{"table", "events",
    "caller_tid"}`: its stage table, its spans, and the recorder track of the
    thread that called it (its first envelope's). Made once a run and kept
    in `run`: seventeen readers ask."""
    if CACHE_KEY not in run:
        run[CACHE_KEY] = _ops(run)
    return run[CACHE_KEY]


def _ops(run: Dict[str, Any]) -> Optional[List[Dict[str, Any]]]:
    from torchsnapshot_tpu.telemetry import critpath, trace

    kind = KIND_BY_DRIVER.get(run["traffic"]["driver"])
    count = len(run["window"]["ops"])
    if not hasattr(critpath, "stage_tables") or kind is None or not count:
        return None
    recorder = trace.get_recorder()
    events = [e for e in recorder.events_since(0) if e.get("ph") == "X"]
    tables = critpath.stage_tables(events)
    mine = sorted(op for op, table in tables.items() if table["kind"] == kind)[-count:]
    if len(mine) < count:
        return None
    # An op's id is the begin order of its first envelope: an event of it
    # that the ring evicted completed after that.
    if recorder.dropped and min(e["seq"] for e in events) > mine[0]:
        return None
    out = []
    for op in mine:
        own = [e for e in events if e.get("op") == op]
        first = next(e for e in own if e["bseq"] == op)
        out.append({"table": tables[op], "events": own, "caller_tid": first["tid"]})
    return out


def span_names(*constants: str) -> List[str]:
    """The span names `telemetry.names` declares under these constants."""
    from torchsnapshot_tpu.telemetry import names

    return [getattr(names, c) for c in constants if hasattr(names, c)]


def _mean(values: List[float]) -> Optional[float]:
    return sum(values) / len(values) if values else None


def busy_s(run: Dict[str, Any], *constants: str) -> Optional[float]:
    """Mean per operation of the seconds at least one span of these names was
    open; None where no operation of the window has one."""
    window = ops(run)
    if window is None:
        return None
    names = span_names(*constants)
    values = []
    for op in window:
        stages = op["table"]["stages"]
        if len(names) == 1:
            if names[0] in stages:
                values.append(stages[names[0]]["busy_s"])
            continue
        spans = [(e["ts"], e["ts"] + e["dur"]) for e in op["events"] if e["name"] in names]
        if spans:
            values.append(sum(b - a for a, b in _union(spans)) / 1e6)
    return _mean(values)


def thread_s(run: Dict[str, Any], *constants: str,
             caller_only: bool = False) -> Optional[float]:
    """Mean per operation of the summed seconds of the spans of these names,
    with `caller_only` of those on the thread that called the operation."""
    window = ops(run)
    if window is None:
        return None
    names = span_names(*constants)
    values = []
    for op in window:
        spans = [e for e in op["events"] if e["name"] in names
                 and (not caller_only or e["tid"] == op["caller_tid"])]
        if spans:
            values.append(sum(e["dur"] for e in spans) / 1e6)
    return _mean(values)


def parallelism(run: Dict[str, Any], constant: str) -> Optional[float]:
    """Thread-seconds over busy seconds of one stage, over the whole window."""
    window = ops(run)
    if window is None or not span_names(constant):
        return None
    (name,) = span_names(constant)
    rows = [op["table"]["stages"][name] for op in window if name in op["table"]["stages"]]
    busy = sum(row["busy_s"] for row in rows)
    return sum(row["thread_s"] for row in rows) / busy if busy else None


def unattributed_s(run: Dict[str, Any]) -> Optional[float]:
    """Mean per operation of the envelope's seconds with no span of the
    operation open on any thread."""
    window = ops(run)
    if window is None:
        return None
    return _mean([op["table"]["unattributed_s"] for op in window])

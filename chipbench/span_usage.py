"""Reduction from the kernel's account a span carries in its `args` to a ratio
per operation, beside `span_args.py` (byte counts) and `stage_table.py`
(seconds). The library samples `getrusage` where a span of a listed name opens
and where it closes (`telemetry/names.py`: `SPANS_WITH_THREAD_USAGE` on the
span's own thread, `SPANS_WITH_PROCESS_USAGE` over the whole process) and puts
the differences on the span: `cpu_user_us`, `cpu_sys_us` and, where the kernel
counts minor faults, `fault_bytes`. The machine the benchmark runs on does not
(gVisor: `ru_minflt` reads 0 through a first touch, `probe_usage.py`), so
nothing here reads the third: it is the stage table's column on a kernel that
keeps it. A reader names its spans by their constants; a library that lacks a
constant or the args (a parent of PR 40) reads as nothing."""

from typing import Any, Callable, Dict, Optional

from stage_table import ops, span_names

USER, SYS = "cpu_user_us", "cpu_sys_us"


def _per_op(run: Dict[str, Any], constants: tuple,
            ratio: Callable[[int, int, int], Optional[float]]) -> Optional[float]:
    """Mean over the window's operations of `ratio(cpu, system, wall)`, each
    in microseconds and summed over the operation's spans of these names
    that carry the account; None where none does."""
    window = ops(run)
    names = span_names(*constants)
    if window is None or not names:
        return None
    values = []
    for op in window:
        spans = [e for e in op["events"] if e["name"] in names and USER in e["args"]]
        if spans:
            system = sum(e["args"][SYS] for e in spans)
            cpu = system + sum(e["args"][USER] for e in spans)
            value = ratio(cpu, system, sum(e["dur"] for e in spans))
            if value is not None:
                values.append(value)
    return sum(values) / len(values) if values else None


def cpu_over_wall(run: Dict[str, Any], *constants: str) -> Optional[float]:
    """CPU seconds, user and system, over the spans' summed wall: of a
    thread's spans the share of their time the thread computed (under 1 it
    waited, for another thread, a page or a core); of an envelope, the cores
    the process kept busy while it was open."""
    return _per_op(run, constants, lambda cpu, system, wall: cpu / wall)


def sys_over_cpu(run: Dict[str, Any], *constants: str) -> Optional[float]:
    """The system part of the spans' CPU seconds: the kernel's work (page
    zeroing, tmpfs copies) against user code's."""
    return _per_op(run, constants, lambda cpu, system, wall: system / cpu if cpu else None)

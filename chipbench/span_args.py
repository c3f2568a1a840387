"""Reduction from the byte counts a span carries in its `args` to a ratio per
operation, beside `stage_table.py`, which reduces spans to seconds."""

from typing import Any, Dict, List, Optional, Tuple

from stage_table import ops, span_names


def _total(op: Dict[str, Any], names: List[str], arg: str) -> int:
    return sum(e["args"].get(arg, 0) for e in op["events"] if e["name"] in names)


def ratio(run: Dict[str, Any], over: Tuple[str, str], under: Tuple[str, str]) -> Optional[float]:
    """Mean per operation of one arg summed over one span name's spans, over
    another's. `over` and `under` are each (the constant `telemetry/names.py`
    declares the span under, the arg). None where the library lacks a
    constant, and where no operation of the window has spans of `under` whose
    arg sums above zero."""
    window = ops(run)
    top, bottom = span_names(over[0]), span_names(under[0])
    if window is None or not top or not bottom:
        return None
    values = []
    for op in window:
        denominator = _total(op, bottom, under[1])
        if denominator:
            values.append(_total(op, top, over[1]) / denominator)
    return sum(values) / len(values) if values else None

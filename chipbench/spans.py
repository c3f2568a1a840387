"""Reduction from the library's critical-path segments to per-operation means."""

from typing import Any, Dict, Optional


def segment_mean(run: Dict[str, Any], segment: str) -> Optional[float]:
    """Mean over the window's operations of one segment of
    `telemetry.last_report(...).critical_path`; None where no operation has it."""
    paths = [op.get("critical_path") or {} for op in run["window"]["ops"]]
    values = [p[segment] for p in paths if segment in p]
    return sum(values) / len(values) if values else None

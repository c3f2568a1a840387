"""Seconds per save with at least one staging request waiting for room in the
host staging pool (`pipeline:budget_acquire` open). The sweep's `budget_wait`
segment reads a hundredth of this: a request that waits is seldom the span
begun last, and the pool is what keeps D2H and the write apart (PERF.md 5.2)."""

from typing import Any, Dict, Optional

from stage_table import busy_s


def read(run: Dict[str, Any]) -> Optional[float]:
    return busy_s(run, "SPAN_PIPELINE_BUDGET_ACQUIRE")

"""`reshard:plan` per restore: the seconds the sharded preparer spent, leaf by
leaf inside `restore:plan`, on destination boxes, box overlaps and row bands.
A library without the span (a parent of PR 29) reads as nothing."""

from typing import Any, Dict, Optional

from stage_table import busy_s


def read(run: Dict[str, Any]) -> Optional[float]:
    return busy_s(run, "SPAN_RESHARD_PLAN")

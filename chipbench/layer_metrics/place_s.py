"""Seconds per restore inside `restore:place` (one batched `device_put` and its
deferred conversions, on the event-loop thread) or `restore:apply`."""

from typing import Any, Dict, Optional

from stage_table import busy_s


def read(run: Dict[str, Any]) -> Optional[float]:
    return busy_s(run, "SPAN_RESTORE_PLACE", "SPAN_RESTORE_APPLY")

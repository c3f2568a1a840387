"""Seconds per restore during which at least one `storage:read` was open."""

from typing import Any, Dict, Optional

from stage_table import busy_s


def read(run: Dict[str, Any]) -> Optional[float]:
    return busy_s(run, "SPAN_STORAGE_READ")

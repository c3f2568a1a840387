"""`restore:plan` per restore: metadata and checksum-table reads, then per
stateful the destination allocation and the read requests."""

from typing import Any, Dict, Optional

from stage_table import busy_s


def read(run: Dict[str, Any]) -> Optional[float]:
    return busy_s(run, "SPAN_RESTORE_PLAN")

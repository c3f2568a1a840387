"""`telemetry:report` per restore: the report emission (critical path, stage
table, sinks) and the manager's history row, both inside `restore_latest`."""

from typing import Any, Dict, Optional

from stage_table import thread_s


def read(run: Dict[str, Any]) -> Optional[float]:
    return thread_s(run, "SPAN_TELEMETRY_REPORT")

"""The manager's own work in `wait()` per save, on the loop's thread: index and
retention (`manager:index`, retention nested in it), history / ledger / SLOs
(`telemetry:report`) and the tuner's move (`manager:tune`)."""

from typing import Any, Dict, Optional

from stage_table import thread_s


def read(run: Dict[str, Any]) -> Optional[float]:
    return thread_s(run, "SPAN_MANAGER_INDEX", "SPAN_MANAGER_TUNE", "SPAN_TELEMETRY_REPORT",
                    caller_only=True)

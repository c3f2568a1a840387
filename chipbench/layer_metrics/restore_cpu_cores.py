"""Cores the process kept busy during a restore: user and system CPU seconds of
the restore envelope (`RUSAGE_SELF`) over its wall, mean per restore."""

from typing import Any, Dict, Optional

from span_usage import cpu_over_wall


def read(run: Dict[str, Any]) -> Optional[float]:
    return cpu_over_wall(run, "SPAN_RESTORE")

"""Cores the process kept busy while a save committed: user and system CPU
seconds of the commit envelope (`RUSAGE_SELF`: the save's threads, the
runtime's and the train loop) over its wall, mean per save."""

from typing import Any, Dict, Optional

from span_usage import cpu_over_wall


def read(run: Dict[str, Any]) -> Optional[float]:
    return cpu_over_wall(run, "SPAN_ASYNC_TAKE_COMMIT")

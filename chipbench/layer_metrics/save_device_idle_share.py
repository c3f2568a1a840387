"""Share of the traced window in which no operation ran on the device, save cells."""

from typing import Any, Dict, Optional


def read(run: Dict[str, Any]) -> Optional[float]:
    trace = run["trace"]
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])

"""Device seconds of the library's clone programs (`jit_ts_capture_clone`, one
per captured leaf) per save of the traced window."""

from typing import Any, Dict, Optional

CLONE_PROGRAM = "jit_ts_capture_clone"


def read(run: Dict[str, Any]) -> Optional[float]:
    trace, saves = run["trace"], len(run["window"]["ops"])
    if not trace or not saves:
        return None
    clones = [b - a for name, a, b in trace["modules"] if name.startswith(CLONE_PROGRAM)]
    return sum(clones) / saves if clones else None

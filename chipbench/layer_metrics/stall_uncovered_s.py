"""Stall per save (seconds inside `async_save` and `wait()` calls) that the `device_capture` segment does not cover."""

from typing import Any, Dict, Optional

from spans import segment_mean


def read(run: Dict[str, Any]) -> Optional[float]:
    ops = run["window"]["ops"]
    capture = segment_mean(run, "device_capture")
    if not ops or capture is None:
        return None
    return sum(op["call_s"] + op["wait_s"] for op in ops) / len(ops) - capture

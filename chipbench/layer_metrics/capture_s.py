"""The library's `device_capture` critical-path segment (the device clone), mean per save."""

from typing import Any, Dict, Optional

from spans import segment_mean


def read(run: Dict[str, Any]) -> Optional[float]:
    return segment_mean(run, "device_capture")

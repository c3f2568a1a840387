"""The library's `read_drain` critical-path segment (read + verify), mean per restore."""

from typing import Any, Dict, Optional

from spans import segment_mean


def read(run: Dict[str, Any]) -> Optional[float]:
    return segment_mean(run, "read_drain")

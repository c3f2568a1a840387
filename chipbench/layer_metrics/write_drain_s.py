"""The library's `write_drain` critical-path segment (storage writes), mean per save."""

from typing import Any, Dict, Optional

from spans import segment_mean


def read(run: Dict[str, Any]) -> Optional[float]:
    return segment_mean(run, "write_drain")

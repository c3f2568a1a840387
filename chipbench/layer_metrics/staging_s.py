"""The library's `staging` critical-path segment (D2H + checksum/serialize), mean per save."""

from typing import Any, Dict, Optional

from spans import segment_mean


def read(run: Dict[str, Any]) -> Optional[float]:
    return segment_mean(run, "staging")

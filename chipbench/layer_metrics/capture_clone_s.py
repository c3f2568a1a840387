"""Seconds of the per-leaf `capture:clone` spans (the dispatch of each on-device
clone, which waits for nothing itself) per save: what the runtime makes the
dispatches wait."""

from typing import Any, Dict, Optional

from stage_table import thread_s


def read(run: Dict[str, Any]) -> Optional[float]:
    return thread_s(run, "SPAN_CAPTURE_CLONE")

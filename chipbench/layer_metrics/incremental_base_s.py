"""Seconds of a save spent reading the incremental base's metadata
(`incremental:base`, inside `take:plan`: the manifest of the save before,
through the storage plugin, on the caller's thread). Mean per save; nothing on
a library without the span (a parent of PR 34)."""

from typing import Any, Dict, Optional

from stage_table import busy_s


def read(run: Dict[str, Any]) -> Optional[float]:
    return busy_s(run, "SPAN_INCREMENTAL_BASE")

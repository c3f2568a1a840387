"""Seconds of a restore's envelope with no span of the restore open on any
thread: the library's uninstrumented time."""

from typing import Any, Dict, Optional

from stage_table import unattributed_s


def read(run: Dict[str, Any]) -> Optional[float]:
    return unattributed_s(run)

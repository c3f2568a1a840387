"""Seconds of a save's envelopes (call and commit) with no span of the save open
on any thread: the library's uninstrumented time."""

from typing import Any, Dict, Optional

from stage_table import unattributed_s


def read(run: Dict[str, Any]) -> Optional[float]:
    return unattributed_s(run)

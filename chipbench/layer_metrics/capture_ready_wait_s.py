"""Seconds of a save in which its drain thread waited for the device to reach
the save's clones (`capture:ready`, before the first staging request is
admitted): `async_save` returns with the clone program queued behind the
runtime's steps, and the transfers cannot start before the device has run
them. Mean per save; nothing on a library without the span (a parent of
PR 35, whose caller waited for the queue inside `async_save` instead)."""

from typing import Any, Dict, Optional

from stage_table import busy_s


def read(run: Dict[str, Any]) -> Optional[float]:
    return busy_s(run, "SPAN_CAPTURE_READY")

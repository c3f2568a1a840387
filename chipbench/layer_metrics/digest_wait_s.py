"""Seconds of a save in which the caller was blocked until the device had
answered with the digests (`incremental:digest_wait`, inside `take:plan`): the
device runs the digest program behind whatever the runtime had queued, so a
job's queued steps are in it. Mean per save; nothing on a library without the
span (a parent of PR 34) and in a cell whose saves record no digests."""

from typing import Any, Dict, Optional

from stage_table import busy_s


def read(run: Dict[str, Any]) -> Optional[float]:
    return busy_s(run, "SPAN_INCREMENTAL_DIGEST_WAIT")

"""Share of a restore's resharded bytes that took the overlap copy: the
`bytes` of its `reshard:copy` spans over the `bytes_needed` of its
`reshard:plan` spans (the bytes of the destination boxes), in per cent, mean
per restore of the window. The rest was read straight into its box. A library
without the spans (a parent of PR 29) reads as nothing."""

from typing import Any, Dict, Optional

from span_args import ratio


def read(run: Dict[str, Any]) -> Optional[float]:
    share = ratio(run, ("SPAN_RESHARD_COPY", "bytes"), ("SPAN_RESHARD_PLAN", "bytes_needed"))
    return None if share is None else 100.0 * share

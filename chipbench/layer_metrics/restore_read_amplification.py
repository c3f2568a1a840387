"""Bytes a restore planned to read per byte its destinations need:
`bytes_to_read` over `bytes_needed`, both summed over the restore's
`reshard:plan` spans (the read spans carry `blob`, not bytes), mean per
restore of the window. 1 where every saved shard is read once and wholly
needed. A library without the span (a parent of PR 29) reads as nothing."""

from typing import Any, Dict, Optional

from span_args import ratio


def read(run: Dict[str, Any]) -> Optional[float]:
    return ratio(run, ("SPAN_RESHARD_PLAN", "bytes_to_read"), ("SPAN_RESHARD_PLAN", "bytes_needed"))

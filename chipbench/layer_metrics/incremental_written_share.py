"""Share of the digested bytes that a save staged and wrote, in per cent:
`bytes_written` over `bytes_written + bytes_referenced`, the skip decisions
the library puts on the end of `take:plan` where a take records digests. Read
as `bytes_referenced` over `bytes_written` a save (`span_args.ratio`: a save
always writes something, the optimizer's count and the key move every step)
and turned into the share of the mean; a save that referenced nothing reads
100. Nothing on a library without the counters (a parent of PR 34) and in a
cell whose saves record no digests."""

from typing import Any, Dict, Optional

from span_args import ratio


def read(run: Dict[str, Any]) -> Optional[float]:
    referenced = ratio(run, ("SPAN_TAKE_PLAN", "bytes_referenced"),
                       ("SPAN_TAKE_PLAN", "bytes_written"))
    return None if referenced is None else 100.0 / (1.0 + referenced)

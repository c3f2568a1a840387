"""`reshard:copy` per restore: the seconds at least one executor thread was
inside the `np.copyto` loop that carries a read buffer's overlaps into their
destination boxes. A read that landed in its box directly opens none. A
library without the span (a parent of PR 29) reads as nothing."""

from typing import Any, Dict, Optional

from stage_table import busy_s


def read(run: Dict[str, Any]) -> Optional[float]:
    return busy_s(run, "SPAN_RESHARD_COPY")

"""Seconds per save during which at least one `stage:d2h` (the `np.asarray` of a
device array: PJRT transfer + host untiling) was open."""

from typing import Any, Dict, Optional

from stage_table import busy_s


def read(run: Dict[str, Any]) -> Optional[float]:
    return busy_s(run, "SPAN_STAGE_D2H")

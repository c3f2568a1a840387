"""`stage:d2h` thread-seconds over busy seconds: how many transfers ran at once
while any ran."""

from typing import Any, Dict, Optional

from stage_table import parallelism


def read(run: Dict[str, Any]) -> Optional[float]:
    return parallelism(run, "SPAN_STAGE_D2H")

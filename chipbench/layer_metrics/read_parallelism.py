"""`storage:read` thread-seconds over busy seconds: how many reads were open at
once while any was (1 to 2: serialized behind the event loop)."""

from typing import Any, Dict, Optional

from stage_table import parallelism


def read(run: Dict[str, Any]) -> Optional[float]:
    return parallelism(run, "SPAN_STORAGE_READ")

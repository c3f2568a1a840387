"""Planning of a save (`take:plan`: state dicts, flatten, prepare / partition /
batch the write requests, manifest gather), inside the `async_save` call; mean per save."""

from typing import Any, Dict, Optional

from stage_table import busy_s


def read(run: Dict[str, Any]) -> Optional[float]:
    return busy_s(run, "SPAN_TAKE_PLAN")

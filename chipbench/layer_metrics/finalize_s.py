"""`commit:finalize` per save: checksum table, manifest and commit marker, on the
commit thread once the last blob is written."""

from typing import Any, Dict, Optional

from stage_table import busy_s


def read(run: Dict[str, Any]) -> Optional[float]:
    return busy_s(run, "SPAN_COMMIT_FINALIZE")

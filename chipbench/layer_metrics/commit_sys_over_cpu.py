"""The system part of the process's CPU seconds while a save committed: the
kernel (page zeroing, tmpfs copies) against user code (the untiling, the
CRC). The commit envelope's `cpu_sys_us` over user + system, mean per save."""

from typing import Any, Dict, Optional

from span_usage import sys_over_cpu


def read(run: Dict[str, Any]) -> Optional[float]:
    return sys_over_cpu(run, "SPAN_ASYNC_TAKE_COMMIT")

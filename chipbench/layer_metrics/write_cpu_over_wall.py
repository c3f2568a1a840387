"""Share of the native write spans (write fused with the CRC32-C, to tmpfs all
CPU) that their threads computed: CPU seconds (`RUSAGE_THREAD`) over
thread-seconds of `storage:fs_native_write`, `_pwritev` and `_direct_write`,
mean per save. Under 1 a writing thread was descheduled or blocked."""

from typing import Any, Dict, Optional

from span_usage import cpu_over_wall


def read(run: Dict[str, Any]) -> Optional[float]:
    return cpu_over_wall(run, "SPAN_FS_NATIVE_WRITE", "SPAN_FS_NATIVE_PWRITEV",
                         "SPAN_FS_NATIVE_DIRECT_WRITE")

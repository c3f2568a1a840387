"""Share of its `stage:d2h` spans (the `np.asarray` of a device array) that a
staging thread computed: its own CPU seconds (`RUSAGE_THREAD`) over the spans'
thread-seconds, mean per save. Near 0 the thread waits for the runtime's
threads, which do the transfer and the untiling."""

from typing import Any, Dict, Optional

from span_usage import cpu_over_wall


def read(run: Dict[str, Any]) -> Optional[float]:
    return cpu_over_wall(run, "SPAN_STAGE_D2H")

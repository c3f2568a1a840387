"""Peak device memory of the run so far (fullest chip) over that chip's share of the state's bytes."""

from typing import Any, Dict, Optional


def read(run: Dict[str, Any]) -> Optional[float]:
    peaks = [s.get("peak_bytes_in_use") for s in run["memory"]]
    if not peaks or None in peaks:
        return None
    return max(peaks) / (run["state_bytes"] / len(peaks))

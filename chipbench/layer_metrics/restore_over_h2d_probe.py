"""State bytes over `restore_s`, as a share of the harness's own H2D probe rate."""

from typing import Any, Dict, Optional


def read(run: Dict[str, Any]) -> Optional[float]:
    if not run["link"]:
        return None
    rate = run["state_bytes"] / 2**30 / run["end_to_end"]["restore_s"]
    return rate / run["link"]["h2d_GiB_per_s"]

"""State bytes over `save_commit_s`, as a share of the harness's own D2H probe rate."""

from typing import Any, Dict, Optional


def read(run: Dict[str, Any]) -> Optional[float]:
    if not run["link"]:
        return None
    rate = run["state_bytes"] / 2**30 / run["end_to_end"]["save_commit_s"]
    return rate / run["link"]["d2h_GiB_per_s"]

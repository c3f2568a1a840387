"""Share of a save's jax-source bytes its capture pass cloned on the device:
`clone_bytes` over `clone_bytes` + `host_copy_bytes`, the counters the
library puts on the end of `stage:device_capture`, in %. Mean
per save. 100 where the state leaves room for a whole clone; below it where
the capture copied what did not fit to the host before returning. Nothing on
a library without the counters (one that clones every source whatever the
room), and where no save had a jax source."""

from typing import Any, Dict, Optional

from stage_table import ops, span_names


def read(run: Dict[str, Any]) -> Optional[float]:
    window, names = ops(run), span_names("SPAN_DEVICE_CAPTURE")
    if window is None or not names:
        return None
    shares = []
    for op in window:
        spans = [e["args"] for e in op["events"] if e["name"] in names]
        cloned = sum(a.get("clone_bytes", 0) for a in spans)
        total = cloned + sum(a.get("host_copy_bytes", 0) for a in spans)
        if total:
            shares.append(100.0 * cloned / total)
    return sum(shares) / len(shares) if shares else None

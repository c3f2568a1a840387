"""Seconds a save's call spent copying to the host the jax sources its device
had no room to clone: the wall seconds at least one `capture:host_copy` span
of kind `no_room` was open (several copies run at once), mean per save.
Nothing on a library without such spans (one that clones every source
whatever the room), and where no save of the window lacked room."""

from typing import Any, Dict, Optional

from device_trace import _union
from stage_table import ops, span_names


def read(run: Dict[str, Any]) -> Optional[float]:
    window, names = ops(run), span_names("SPAN_CAPTURE_HOST_COPY")
    if window is None or not names:
        return None
    values = []
    for op in window:
        spans = [(e["ts"], e["ts"] + e["dur"]) for e in op["events"]
                 if e["name"] in names and e["args"].get("kind") == "no_room"]
        if spans:
            values.append(sum(b - a for a, b in _union(spans)) / 1e6)
    return sum(values) / len(values) if values else None

"""Share of a save's device-to-host bytes whose transfer first had to slice a
device array: over the `stage:d2h` spans of a save (`args` `bytes`, and
`sliced_bytes`, the bytes of a transfer that waited for a slice program the
staging thread dispatched behind the runtime's queue), the sum of
`sliced_bytes` over the sum of `bytes`, in per cent, mean per save of the
window. A save none of whose spans carries `sliced_bytes` (a library that does
not count it) reads as nothing."""

from typing import Any, Dict, Optional

from stage_table import ops, span_names


def read(run: Dict[str, Any]) -> Optional[float]:
    from torchsnapshot_tpu.telemetry import names as library_names

    window = ops(run)
    names = span_names("SPAN_STAGE_D2H")
    arg = getattr(library_names, "ARG_SLICED_BYTES", None)
    if window is None or not names or arg is None:
        return None
    shares = []
    for op in window:
        spans = [e["args"] for e in op["events"] if e["name"] in names]
        total = sum(a.get("bytes", 0) for a in spans)
        if total and any(arg in a for a in spans):
            shares.append(100.0 * sum(a.get(arg, 0) for a in spans) / total)
    return sum(shares) / len(shares) if shares else None

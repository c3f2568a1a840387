"""Share of a save's device-to-host bytes that landed in a recycled slab: over
the `stage:d2h` spans of a save (`args` `bytes`, and inside the staging window
`recycled_bytes`, the bytes whose slab of `dest_pool` a copy had written
before), the sum of `recycled_bytes` over the sum of `bytes`, in per cent,
mean per save of the window. A save none of whose spans carries
`recycled_bytes` (a library without the slab handler; leaves all under the
floor) reads as nothing."""

from typing import Any, Dict, Optional

from stage_table import ops, span_names


def read(run: Dict[str, Any]) -> Optional[float]:
    from torchsnapshot_tpu.telemetry import names as library_names

    window = ops(run)
    names = span_names("SPAN_STAGE_D2H")
    arg = getattr(library_names, "ARG_RECYCLED_BYTES", None)
    if window is None or not names or arg is None:
        return None
    shares = []
    for op in window:
        spans = [e["args"] for e in op["events"] if e["name"] in names]
        total = sum(a.get("bytes", 0) for a in spans)
        if total and any(arg in a for a in spans):
            shares.append(100.0 * sum(a.get(arg, 0) for a in spans) / total)
    return sum(shares) / len(shares) if shares else None

"""Share of a restore's destination bytes that landed in a recycled slab:
over the `restore:dest_acquire` spans of a restore (one per admitted read,
`args` `bytes` and `recycled`), the bytes with `recycled` over all bytes, in
per cent, mean per restore of the window. A library without the span (a
parent of PR 28) reads as nothing."""

from typing import Any, Dict, Optional

from stage_table import ops, span_names


def read(run: Dict[str, Any]) -> Optional[float]:
    window = ops(run)
    names = span_names("SPAN_RESTORE_DEST_ACQUIRE")
    if window is None or not names:
        return None
    shares = []
    for op in window:
        spans = [e["args"] for e in op["events"] if e["name"] in names]
        total = sum(a.get("bytes", 0) for a in spans)
        if total:
            shares.append(100.0 * sum(a.get("bytes", 0) for a in spans if a.get("recycled")) / total)
    return sum(shares) / len(shares) if shares else None

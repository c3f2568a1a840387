"""Seconds of a restore, by the host's clock around the call, that the `read_drain` segment does not cover: H2D, placement and whatever no span covers."""

from typing import Any, Dict, Optional

from spans import segment_mean


def read(run: Dict[str, Any]) -> Optional[float]:
    ops = run["window"]["ops"]
    drain = segment_mean(run, "read_drain")
    if not ops or drain is None:
        return None
    return sum(op["restore_s"] for op in ops) / len(ops) - drain

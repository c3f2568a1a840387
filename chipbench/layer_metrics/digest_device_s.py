"""Device seconds of the library's digest programs (`jit_ts_device_digest`,
one a device group a save) per save of the traced window, as `clone_device_s`
reads the clone's."""

from typing import Any, Dict, Optional

DIGEST_PROGRAM = "jit_ts_device_digest"


def read(run: Dict[str, Any]) -> Optional[float]:
    trace, saves = run["trace"], len(run["window"]["ops"])
    if not trace or not saves:
        return None
    digests = [b - a for name, a, b in trace["modules"] if name.startswith(DIGEST_PROGRAM)]
    return sum(digests) / saves if digests else None

"""Leaves a clone program of a save's capture pass clones: `clone_leaves` over
`clone_programs`, the counters the library puts on the end of
`stage:device_capture` (one program a device group a save since PR 35: the
save's written jax leaves on one chip). Mean per save. A dispatch costs the
caller one of the runtime's slots, so this is how many leaves share one.
Nothing on a library without the counters (a parent of PR 35), which
dispatched one program a leaf, and where every program of a save failed and
its leaves were cloned one by one (`fallback_leaves`)."""

from typing import Any, Dict, Optional

from span_args import ratio


def read(run: Dict[str, Any]) -> Optional[float]:
    return ratio(run, ("SPAN_DEVICE_CAPTURE", "clone_leaves"),
                 ("SPAN_DEVICE_CAPTURE", "clone_programs"))

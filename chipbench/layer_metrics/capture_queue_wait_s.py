"""Seconds of an `async_save` call in which the device was still running
train steps dispatched before it: the call waits for the device to reach the
state it clones. Per save: from the `chipbench:async_save` annotation's start
to the end of the last train-step program that ran inside it."""

from typing import Any, Dict, Optional

STEP_PROGRAM = "jit_train_step"


def read(run: Dict[str, Any]) -> Optional[float]:
    trace = run["trace"]
    if not trace:
        return None
    calls = [(a, b) for name, a, b in trace["annotations"] if name == "chipbench:async_save"]
    steps = [(a, b) for name, a, b in trace["modules"] if name.startswith(STEP_PROGRAM)]
    if not calls or not steps:
        return None
    waits = []
    for a, b in calls:
        inside = [min(end, b) for start, end in steps if start < b and end > a]
        waits.append(max(inside) - a if inside else 0.0)
    return sum(waits) / len(waits)

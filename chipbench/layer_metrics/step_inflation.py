"""Mean device seconds of a train-step program that started while a save was in
flight (from the `chipbench:async_save` call to the `chipbench:wait` that
followed its commit) over the mean of those that started while none was, in
the same traced window."""

from typing import Any, Dict, Optional

STEP_PROGRAM = "jit_train_step"


def read(run: Dict[str, Any]) -> Optional[float]:
    trace = run["trace"]
    if not trace:
        return None
    in_flight, opened = [], None
    for name, start, _ in trace["annotations"]:
        if name == "chipbench:async_save":
            opened = start
        elif name == "chipbench:wait" and opened is not None:
            in_flight.append((opened, start))
            opened = None
    busy, quiet = [], []
    for name, start, end in trace["modules"]:
        if name.startswith(STEP_PROGRAM):
            during = any(a <= start < b for a, b in in_flight)
            (busy if during else quiet).append(end - start)
    if not busy or not quiet:
        return None
    return (sum(busy) / len(busy)) / (sum(quiet) / len(quiet))

"""The train step's share of the chip's bf16 peak: the model FLOPs of one step
(`step_counts.py`, from the configuration's shapes) over the mean device
seconds of the `jit_train_step` programs that started while no save was in
flight (outside every `chipbench:async_save` .. `chipbench:wait` span) times
`peaks.json`'s bf16 FLOP/s, in per cent. The counts go to standard error.
Nothing where the trace has no such program, or `step_counts` finds no shapes
to count in the configuration. Above 100 the operations are counted too high
or the time leaves out a program."""

import sys
from typing import Any, Dict, Optional

import cells
import step_counts

STEP_PROGRAM = "jit_train_step"


def quiet_step_seconds(trace: Dict[str, Any]) -> Optional[float]:
    in_flight, opened = [], None
    for name, start, _ in trace["annotations"]:
        if name == "chipbench:async_save":
            opened = start
        elif name == "chipbench:wait" and opened is not None:
            in_flight.append((opened, start))
            opened = None
    if opened is not None:
        in_flight.append((opened, float("inf")))
    quiet = [end - start for name, start, end in trace["modules"]
             if name.startswith(STEP_PROGRAM) and not any(a <= start < b for a, b in in_flight)]
    return sum(quiet) / len(quiet) if quiet else None


def read(run: Dict[str, Any]) -> Optional[float]:
    if not run["trace"]:
        return None
    seconds = quiet_step_seconds(run["trace"])
    need = step_counts.counts(run["config"])
    if not seconds or need is None:
        return None
    peak = cells.peaks(run["device"]["kind"])["bf16_flops_per_s"]
    print(f"chipbench: step_counts {need}: {need['flops'] / peak * 1e3:.2f} ms at the bf16 peak, "
          f"{seconds * 1e3:.2f} ms measured", file=sys.stderr, flush=True)
    return 100.0 * need["flops"] / (seconds * peak)

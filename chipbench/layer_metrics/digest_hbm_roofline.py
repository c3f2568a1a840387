"""The digest program's share of the HBM roofline: the bytes it has to read
for one save (every leaf of the saved state once, `digest_counts.py`, from the
configuration's leaf shapes and not from the library's span) over its device
seconds a save (`digest_device_s`) times the chip's published HBM rate
(`peaks.json`), in per cent. The program is bound by its uint32 arithmetic on
the VPU (25 operations a lane), for which the table has no peak: the count
goes to standard error beside the bytes. Above 100 the bytes are counted too
high or the time leaves out a program."""

import sys
from typing import Any, Dict, Optional

import cells
import digest_counts


def read(run: Dict[str, Any]) -> Optional[float]:
    seconds = cells.layer_reader("digest_device_s")(run)
    if not seconds:
        return None
    import jax

    # A traced run with a device plane is a run on the chip: run.py has
    # refused a device the table does not know, and a rehearsal's sizes.
    need = digest_counts.counts(
        digest_counts.saved_leaves(jax, run["cell"], run["config"], rehearse=False))
    peak = cells.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    print(f"chipbench: digest_counts {need}: {need['bytes'] / peak * 1e3:.2f} ms at the HBM "
          f"peak, {seconds * 1e3:.2f} ms measured, {need['uint32_ops'] / seconds / 1e12:.3f} "
          "T uint32 op/s", file=sys.stderr, flush=True)
    return 100.0 * need["bytes"] / (seconds * peak)

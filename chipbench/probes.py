"""The harness's own reading of the host-device link, as a ceiling: 1 GiB each
way, as one array, as 8 pieces and as 32 pieces all in flight at once, three times
each, the best rate of all taken. Run in set-up of a traced run only."""

import time
from typing import Dict, List

import numpy as np

PROBE_BYTES = 1 << 30
IN_FLIGHT = (1, 8, 32)
REPEATS = 3


def link(jax, device, nbytes: int = PROBE_BYTES) -> Dict[str, float]:
    jnp = jax.numpy
    gib = nbytes / 2**30
    out: Dict[str, float] = {}
    for pieces in IN_FLIGHT:
        on_device = [jax.device_put(jnp.ones((nbytes // pieces,), jnp.uint8), device)
                     for _ in range(pieces)]
        d2h: List[float] = []
        h2d: List[float] = []
        for _ in range(REPEATS):
            # New arrays each time: jax keeps the host copy of one it has given out.
            on_device = [x + np.uint8(1) for x in on_device]
            jax.block_until_ready(on_device)
            t = time.monotonic()
            for x in on_device:
                x.copy_to_host_async()
            on_host = [np.asarray(x) for x in on_device]
            d2h.append(time.monotonic() - t)
            t = time.monotonic()
            back = jax.device_put(on_host, device)
            jax.block_until_ready(back)
            h2d.append(time.monotonic() - t)
            del back
        out[f"d2h_GiB_per_s_{pieces}_in_flight"] = gib / min(d2h)
        out[f"h2d_GiB_per_s_{pieces}_in_flight"] = gib / min(h2d)
        del on_device, on_host
    out["d2h_GiB_per_s"] = max(v for k, v in out.items() if k.startswith("d2h"))
    out["h2d_GiB_per_s"] = max(v for k, v in out.items() if k.startswith("h2d"))
    return out

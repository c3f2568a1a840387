"""What the library's device digest (`jit_ts_device_digest`,
torchsnapshot_tpu/ops/device_digest.py) has to read and compute for one
save of a configuration's state, reckoned from the leaf shapes alone:
`digest_hbm_roofline` divides these bytes by the program's device time, and a
test holds them to the `bytes` the library's `incremental:digest_launch` span
counts.

The digest is a multilinear hash over a leaf's memory image as unsigned
lanes (uint32 where the itemsize is a multiple of 4, else uint16 or uint8),
each lane widened to uint32. A lane costs: its index (1), the index times the
golden ratio (1), the widening (1), and for each of the two accumulators the
seed added (1), the mixer (three shifts, three xors, two multiplies: 8), the
lane times its weight (1) and the sum (1): 3 + 2 x 11 = 25 uint32 operations.
Every byte is read once; 8 bytes a chunk are written.

    python chipbench/digest_counts.py chipbench/configs/neox-6.9b-l12-lora.json
"""

import json
import os
import sys
from typing import Any, Dict, List

OPS_PER_LANE = 3 + 2 * 11
DIGEST_BYTES = 8


def lane_bytes(itemsize: int) -> int:
    return 4 if itemsize % 4 == 0 else min(itemsize, 2)


def saved_leaves(jax, cell: Dict[str, Any], config: Dict[str, Any], rehearse: bool) -> List[Any]:
    """Shapes and dtypes of the leaves a snapshot of the cell's state holds
    (`workload.Context.saved_tree`: parameters, optimizer state, the key), from
    `jax.eval_shape`: nothing is built."""
    import workload
    from torchsnapshot_tpu.models import TransformerConfig, init_train_state

    _, _, cfg = workload.model_config(jax, TransformerConfig, cell, config, rehearse)
    state = jax.eval_shape(lambda: init_train_state(cfg, seed=0))
    return jax.tree_util.tree_leaves(
        {"params": state.params, "opt": state.opt_state, "rng": state.rng})


def counts(leaves: List[Any]) -> Dict[str, int]:
    """Bytes the digest programs read, lanes they hash and uint32 operations
    they do for one save of these leaves, every leaf on the device."""
    nbytes = lanes = 0
    for leaf in leaves:
        size = 1
        for d in leaf.shape:
            size *= int(d)
        nbytes += size * leaf.dtype.itemsize
        lanes += size * leaf.dtype.itemsize // lane_bytes(leaf.dtype.itemsize)
    return {"leaves": len(leaves), "bytes": nbytes, "lanes": lanes,
            "uint32_ops": lanes * OPS_PER_LANE}


def main(argv: List[str]) -> None:
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path[:0] = [here, os.path.dirname(here)]
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    with open(argv[1]) as f:
        config = json.load(f)
    cell = {"name": config["name"], "config": config["name"]}
    print(json.dumps(counts(saved_leaves(jax, cell, config, rehearse=False))))


if __name__ == "__main__":
    main(sys.argv)

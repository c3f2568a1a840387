"""Cold restore: timed restore in a FRESH process whose transfer path has
never run a device→host copy — the restore-after-restart scenario
(BASELINE.md "restore-to-step0"; the reference's load benchmark is
likewise a standalone read-only process,
``/root/reference/benchmarks/load_tensor/main.py:24-61``).

An in-process restore timed after a take (bench.py's ``restore_gbps``)
shares its process with whatever the takes left behind; this leg is the
number a restarted job sees. A chip belongs to one process at a time,
so bench.py spawns this script only while its own process is not
holding a TPU; on the chip, ``chip_smoke.py``'s phase B is the cold
restore.

Usage (spawned by bench.py; runs on the default platform — the real
chip when present):

    python benchmarks/cold_restore.py --snap DIR --trials 2 --json

The destination tree is rebuilt from the snapshot manifest (device-side
``jnp.zeros`` — no H2D before the timed restore). Each timed restore is
bracketed by pattern-matched H2D probes of RANDOM content (zeros can be
transparently compressed by transport layers).
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--snap", required=True)
    p.add_argument("--trials", type=int, default=2)
    p.add_argument("--json", action="store_true")
    args = p.parse_args()

    import jax.numpy as jnp
    import numpy as np

    # Pins the CPU platform only under JAX_PLATFORMS=cpu; otherwise this
    # leg runs on the default platform (the chip when present).
    from benchmarks.common import jax, place_compile_cache

    place_compile_cache()

    import torchsnapshot_tpu as ts
    from torchsnapshot_tpu.manifest import (
        ArrayEntry,
        ChunkedArrayEntry,
        ShardedArrayEntry,
    )

    snap = ts.Snapshot(args.snap)
    manifest = snap.get_manifest()
    leaves = {}
    for path, entry in manifest.items():
        if not isinstance(
            entry, (ArrayEntry, ChunkedArrayEntry, ShardedArrayEntry)
        ):
            continue
        # bench's tree: "0/state/<leaf>"
        parts = path.split("/")
        leaves["/".join(parts[2:])] = (tuple(entry.shape), entry.dtype)
    if not leaves:
        raise SystemExit("no array entries found in manifest")
    dev = jax.devices()[0]
    nbytes = sum(
        int(np.prod(s)) * np.dtype(jnp.bfloat16 if d == "bfloat16" else d).itemsize
        for s, d in leaves.values()
    )
    gib = nbytes / (1 << 30)
    n_streams = min(4, max(1, len(leaves) - 1))

    rng = np.random.default_rng(0)
    max_leaf_mib = max(
        int(np.prod(s))
        * np.dtype(jnp.bfloat16 if d_ == "bfloat16" else d_).itemsize
        for s, d_ in leaves.values()
    ) >> 20

    # Pattern matching: probe chunks scale to a quick link estimate
    # (~4 s of probe wall) but never exceed the snapshot's largest leaf
    # — the restore's actual per-placement transfer size.
    quick = np.ascontiguousarray(
        rng.integers(0, 255, (4096, 4096), dtype=np.uint8)
    )
    t0 = time.perf_counter()
    jax.block_until_ready(jax.device_put(quick, dev))
    est = quick.nbytes / (1 << 30) / (time.perf_counter() - t0)
    chunk_mib = int(
        min(max(32, max_leaf_mib), max(32, est * 4.0 * 1024 / n_streams))
    )
    side = int((chunk_mib * (1 << 20)) ** 0.5)

    def probe(tag: str) -> float:
        # Random content: transport-layer compression of zeros would
        # fake the ceiling.
        hosts = [
            rng.integers(0, 255, (side, side), dtype=np.uint8)
            for _ in range(n_streams)
        ]
        t0 = time.perf_counter()
        d = jax.device_put(hosts, [dev] * n_streams)
        jax.block_until_ready(d)
        r = sum(h.nbytes for h in hosts) / (1 << 30) / (time.perf_counter() - t0)
        del d, hosts
        log(
            f"cold-restore: H2D probe {tag} ({n_streams}x{chunk_mib} MiB): "
            f"{r:.3f} GB/s"
        )
        return r

    def make_dest():
        tree = {}
        for key, (shape, dtype) in leaves.items():
            jdt = jnp.bfloat16 if dtype == "bfloat16" else dtype
            tree[key] = jnp.zeros(shape, jdt)
        d = ts.PyTreeState(tree)
        jax.block_until_ready(d.tree)
        return d

    probes = [probe("before restore 0")]
    times = []
    for i in range(args.trials):
        dest = make_dest()
        # Writeback guard (repo methodology): the parent's take loop may
        # still be flushing ~GiBs of dirty pages; on the one-core box
        # that inflated timed restores up to 10x.
        os.sync()
        t0 = time.perf_counter()
        snap.restore({"state": dest})
        jax.block_until_ready(dest.tree)
        times.append(time.perf_counter() - t0)
        log(f"cold-restore: restore {i}: {times[-1]:.2f} s "
            f"({gib / times[-1]:.3f} GB/s)")
        del dest
        probes.append(probe(f"after restore {i}"))

    brackets = [max(probes[i], probes[i + 1]) for i in range(len(times))]
    ratios = [(gib / t) / b for t, b in zip(times, brackets) if b > 0]
    out = {
        "size_gib": round(gib, 2),
        # A silent CPU fallback (e.g. an exclusively-held device) must be
        # visible in the record: multi-GB/s page-cache "restores" are not
        # hardware-limit figures.
        "cold_restore_backend": (
            f"{jax.default_backend()}:{dev.device_kind}"
        ),
        "cold_restore_gbps": round(
            statistics.median(gib / t for t in times), 3
        ),
        "cold_restore_times_s": [round(t, 2) for t in times],
        "cold_restore_h2d_probes": [round(r, 3) for r in probes],
        "cold_restore_efficiency": (
            round(statistics.median(ratios), 3) if ratios else 0.0
        ),
        "cold_restore_link_unstable": any(
            max(a, b) / min(a, b) > 1.5
            for a, b in zip(probes, probes[1:])
            if min(a, b) > 0
        ),
    }
    if args.json:
        print(json.dumps(out))


if __name__ == "__main__":
    main()

"""Incremental-checkpoint benchmark: fine-tune-shaped state, full takes
vs digest-gated incremental takes.

No reference counterpart (the reference rewrites all bytes every take).
The workload models the states where incremental checkpointing pays:

- ``base``: a large frozen sharded tower (LoRA/adapter fine-tunes, EMA
  copies, frozen embedding stacks) — never changes after step 0.
- ``adapter``: small trainable weights + their optimizer moments —
  change every step, always rewritten.
- ``table``: a row-sharded embedding table whose updates hit a *hot
  region* (clustered rows) — chunk-level skipping keeps the cold chunks.

An adversarial case is also reported: ``--uniform-table`` scatters the
table updates uniformly, which dirties every skip-unit chunk and shows
incremental degrading gracefully to ~full cost plus digest overhead
(wall-time numbers below include that overhead; nothing is hidden).

Measured per save: wall time, bytes written to storage, and — the number
that matters on TPU — bytes *staged* across the device→host link.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python benchmarks/incremental/main.py

On the real chip drop JAX_PLATFORMS.
"""

import argparse
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))
from benchmarks.common import jax  # noqa: E402

import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

import torchsnapshot_tpu as ts  # noqa: E402


def tree_bytes(tree) -> int:
    return sum(
        l.size * l.dtype.itemsize for l in jax.tree_util.tree_leaves(tree)
    )


def dir_bytes(path: str) -> int:
    total = 0
    for dirpath, _, files in os.walk(path):
        for f in files:
            total += os.path.getsize(os.path.join(dirpath, f))
    return total


def make_state(mesh, base_mib: int, table_rows: int, dim: int, seed: int):
    sharding = NamedSharding(mesh, P("x", None))
    key = jax.random.PRNGKey(seed)
    n_base = max(1, base_mib // 16)
    base = {}
    for i in range(n_base):
        key, k = jax.random.split(key)
        base[f"layer_{i}"] = jax.device_put(
            jax.random.normal(k, (4096 * 1024 // dim, dim), jax.numpy.float32),
            sharding,
        )
    key, k1, k2, k3 = jax.random.split(key, 4)
    state = {
        "base": base,
        "adapter": {
            "w": jax.random.normal(k1, (512, 512), jax.numpy.float32),
            "m": jax.random.normal(k2, (512, 512), jax.numpy.float32),
        },
        "table": jax.device_put(
            jax.random.normal(k3, (table_rows, dim), jax.numpy.float32),
            sharding,
        ),
    }
    jax.block_until_ready(state)
    return state


def train_interval(state, step: int, frac: float, uniform: bool):
    """One save interval's worth of updates: adapter fully, table rows
    either clustered (hot region) or uniform (adversarial)."""
    table = state["table"]
    rows = table.shape[0]
    n = max(1, int(rows * frac))
    rng = np.random.default_rng(step)
    if uniform:
        idx = jax.numpy.asarray(rng.choice(rows, size=n, replace=False))
    else:
        start = int(rng.integers(0, max(1, rows - n)))
        idx = jax.numpy.arange(start, start + n)
    new_state = {
        "base": state["base"],  # frozen
        "adapter": {
            "w": state["adapter"]["w"] + 0.01,
            "m": state["adapter"]["m"] * 0.9,
        },
        "table": table.at[idx].add(0.01),
    }
    jax.block_until_ready(new_state)
    return new_state


class StagedBytesCounter:
    """Counts bytes through ArrayBufferStager._stage_sync — the actual
    device→host traffic a take causes."""

    def __init__(self) -> None:
        self.bytes = 0

    def __enter__(self):
        from torchsnapshot_tpu import io_preparer

        self._orig = io_preparer.ArrayBufferStager._stage_sync
        counter = self

        def counting(stager):
            buf = counter._orig(stager)
            counter.bytes += memoryview(buf).nbytes
            return buf

        io_preparer.ArrayBufferStager._stage_sync = counting
        return self

    def __exit__(self, *exc):
        from torchsnapshot_tpu import io_preparer

        io_preparer.ArrayBufferStager._stage_sync = self._orig
        return False


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--base-mib", type=int, default=64)
    p.add_argument("--table-rows", type=int, default=65536)
    p.add_argument("--dim", type=int, default=64)
    p.add_argument("--update-frac", type=float, default=0.01)
    p.add_argument("--uniform-table", action="store_true")
    p.add_argument("--steps", type=int, default=4)
    p.add_argument(
        "--incremental-chunk-kib",
        type=int,
        default=512,
        help="skip-unit granularity (INCREMENTAL_CHUNK_BYTES knob)",
    )
    p.add_argument("--root", type=str, default=None)
    args = p.parse_args()

    devices = jax.devices()
    mesh = Mesh(np.array(devices), ("x",))
    state = make_state(mesh, args.base_mib, args.table_rows, args.dim, seed=0)
    state_gib = tree_bytes(state) / (1 << 30)
    print(
        f"state: {state_gib:.3f} GiB ({args.base_mib} MiB frozen base, "
        f"{args.table_rows}x{args.dim} table with "
        f"{'uniform' if args.uniform_table else 'clustered'} "
        f"{args.update_frac:.1%} row updates, 2 MiB trainable adapter) "
        f"on {len(devices)} {devices[0].platform} devices; "
        f"skip unit {args.incremental_chunk_kib} KiB"
    )

    root = args.root or tempfile.mkdtemp(prefix="ts-incremental-bench-")
    shutil.rmtree(root, ignore_errors=True)

    from torchsnapshot_tpu.knobs import override_incremental_chunk_size_bytes

    mgr_full = ts.CheckpointManager(root + "/full")
    mgr_incr = ts.CheckpointManager(root + "/incr", incremental=True)

    rows = []
    with override_incremental_chunk_size_bytes(
        args.incremental_chunk_kib * 1024
    ):
        for step in range(args.steps):
            if step > 0:
                state = train_interval(
                    state, step, args.update_frac, args.uniform_table
                )

            with StagedBytesCounter() as cf:
                t0 = time.perf_counter()
                mgr_full.save(step, {"m": ts.PyTreeState(state)})
                t_full = time.perf_counter() - t0
            b_full = dir_bytes(os.path.join(root, "full", f"step_{step:010d}"))

            with StagedBytesCounter() as ci:
                t0 = time.perf_counter()
                mgr_incr.save(step, {"m": ts.PyTreeState(state)})
                t_incr = time.perf_counter() - t0
            b_incr = dir_bytes(os.path.join(root, "incr", f"step_{step:010d}"))

            rows.append(
                (step, t_full, b_full, cf.bytes, t_incr, b_incr, ci.bytes)
            )
            print(
                f"step {step}: full {t_full:6.2f}s {b_full / 1e6:8.1f} MB "
                f"written {cf.bytes / 1e6:8.1f} MB staged | incremental "
                f"{t_incr:6.2f}s {b_incr / 1e6:8.1f} MB written "
                f"{ci.bytes / 1e6:8.1f} MB staged"
            )

        # Steady-state = mean over the sparse-update steps (step 0 is the
        # unavoidable full base for both modes).
        if len(rows) > 1:
            ss = rows[1:]
            f_t = sum(r[1] for r in ss) / len(ss)
            i_t = sum(r[4] for r in ss) / len(ss)
            f_b = sum(r[2] for r in ss) / len(ss)
            i_b = sum(r[5] for r in ss) / len(ss)
            f_s = sum(r[3] for r in ss) / len(ss)
            i_s = sum(r[6] for r in ss) / len(ss)
            print(
                f"steady-state means: save time {f_t:.2f}s -> {i_t:.2f}s "
                f"({f_t / max(i_t, 1e-9):.1f}x), bytes written "
                f"{f_b / 1e6:.1f} -> {i_b / 1e6:.1f} MB "
                f"({f_b / max(i_b, 1):.1f}x), bytes staged (D2H) "
                f"{f_s / 1e6:.1f} -> {i_s / 1e6:.1f} MB "
                f"({f_s / max(i_s, 1):.1f}x)"
            )

        # Correctness: restore the newest incremental step and compare.
        dest_state = make_state(
            mesh, args.base_mib, args.table_rows, args.dim, seed=1
        )
        dest = {"m": ts.PyTreeState(dest_state)}
        t0 = time.perf_counter()
        mgr_incr.restore_latest(dest)
        t_restore = time.perf_counter() - t0
        got = jax.tree_util.tree_leaves(dest["m"].tree)
        want = jax.tree_util.tree_leaves(state)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        print(f"restore(latest incremental): {t_restore:.2f}s, byte-identical")

    if args.root is None:
        shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    main()

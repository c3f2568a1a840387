"""Which memory a restore's reads land in, beside the cells' numbers.

    python chipbench/probe_read_memory.py chipbench/configs/neox-6.9b-l2.json /dev/shm/chipbench_probe_rm

No jax and no chip: the library's two native read kernels alone, on files of
the sizes a snapshot of that configuration's train state holds (every 2-d leaf
and every scale, parameters and both Adam moments), written once to the given
directory. For each kernel (`pread_into_crc`: the fused read + CRC32-C of every
4 MiB page that a restore uses; `pread_into`: the plain read) and 1, 8 and 16
threads it reads every file twice: into a fresh `np.empty` per file, as a
restore's destinations are allocated, and into the same arrays again. Then
once more through a small pool of `_native.aligned_buffer` slabs of the files'
sizes, each zero-filled when it is made and handed from file to file. A row
is wall seconds, thread-seconds (the sum of the files' own seconds) and GiB/s
in all and a thread. Not part of any run of a cell.
"""

import json
import os
import shutil
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List

import numpy as np

from cells import ROOT
from storage import fs_type

sys.path.insert(0, ROOT)

PAGE = 4 << 20
THREADS = (1, 8, 16)
POOL_BYTES = 3 << 29  # 1.5 GiB of slabs: a third of the neox state


def leaf_sizes(config: Dict[str, int]) -> List[int]:
    """Bytes of every array leaf of the bf16 train state, as `workload.py`
    makes it: per layer w_in, w_out, wo, wqkv and two scales, then embed,
    unembed and the final scale; parameters, mu and nu."""
    d, ff, v = config["hidden_size"], config["intermediate_size"], config["vocab_size"]
    layer = [d * ff, ff * d, d * d, d * 3 * d, d, d]
    elems = layer * config["num_hidden_layers"] + [v * d, d * v, d]
    return [2 * n for n in elems] * 3


def write_files(directory: str, sizes: List[int]) -> List[str]:
    piece = os.urandom(1 << 20) * 64
    paths = []
    for i, size in enumerate(sizes):
        path = os.path.join(directory, f"leaf_{i}")
        with open(path, "wb") as f:
            for start in range(0, size, len(piece)):
                f.write(piece[: min(len(piece), size - start)])
        paths.append(path)
    return paths


def timed_reads(one: Callable[[int], None], count: int, threads: int) -> Dict[str, float]:
    """`one(i)` for every file on `threads` threads, in the files' order."""
    def timed(i: int) -> float:
        t0 = time.monotonic()
        one(i)
        return time.monotonic() - t0

    t0 = time.monotonic()
    with ThreadPoolExecutor(threads) as pool:
        each = list(pool.map(timed, range(count)))
    return {"wall_s": time.monotonic() - t0, "thread_s": sum(each)}


def row(label: Dict[str, object], took: Dict[str, float], total: int) -> Dict[str, object]:
    gib = total / 2**30
    out = dict(label, **took)
    out["GiB_per_s"] = gib / took["wall_s"]
    out["GiB_per_s_a_thread"] = gib / took["thread_s"]
    print(json.dumps(out), flush=True)
    return out


class SlabPool:
    """Exact-size free lists under one cap, as a restore's pool would keep
    them: a slab is zero-filled when made and blocks its taker while the cap
    is reached and none of its size is free."""

    def __init__(self, cap: int) -> None:
        from torchsnapshot_tpu import _native

        self._make = _native.aligned_buffer
        self._cap, self._held = cap, 0
        self._free: Dict[int, List[memoryview]] = {}
        self._cond = threading.Condition()
        self.made_bytes = 0
        self.make_s = 0.0

    def take(self, size: int) -> memoryview:
        with self._cond:
            while not self._free.get(size):
                # Free slabs of other sizes make room before anyone waits.
                for other, views in self._free.items():
                    while views and self._held + size > self._cap:
                        views.pop()
                        self._held -= other
                if self._held + size <= self._cap or self._held == 0:
                    self._held += size
                    break
                self._cond.wait()
            else:
                return self._free[size].pop()
        t0 = time.monotonic()
        slab = self._make(size)
        with self._cond:
            self.make_s += time.monotonic() - t0
            self.made_bytes += size
        return slab

    def give(self, slab: memoryview) -> None:
        with self._cond:
            self._free.setdefault(slab.nbytes, []).append(slab)
            self._cond.notify_all()


def main() -> None:
    from torchsnapshot_tpu import _native

    with open(sys.argv[1]) as f:
        config = json.load(f)
    directory = sys.argv[2]
    if _native.lib() is None:
        raise SystemExit("the native I/O library did not build: nothing to probe")
    with open("/proc/meminfo") as f:
        mem = dict(line.split(":") for line in f)
    thp = {}
    for knob in ("enabled", "shmem_enabled", "defrag"):
        try:
            with open(f"/sys/kernel/mm/transparent_hugepage/{knob}") as f:
                thp[knob] = f.read().strip()
        except OSError:
            thp[knob] = None
    sizes = leaf_sizes(config)
    total = sum(sizes)
    os.makedirs(directory, exist_ok=True)
    print(json.dumps({"cpus": os.cpu_count(), "MemAvailable": mem["MemAvailable"].strip(),
                      "transparent_hugepage": thp, "dir": directory, "fs": fs_type(directory),
                      "files": len(sizes), "bytes": total}), flush=True)
    kernels = {
        "pread_into_crc": lambda path, out: _native.pread_into_crc(path, out, PAGE),
        "pread_into": lambda path, out: _native.pread_into(path, out),
    }
    rows = []
    try:
        t0 = time.monotonic()
        paths = write_files(directory, sizes)
        print(json.dumps({"write_s": time.monotonic() - t0}), flush=True)
        count = len(paths)
        for threads in THREADS:
            for kernel, read in kernels.items():
                arrays: Dict[int, np.ndarray] = {}

                def fresh(i: int) -> None:
                    arrays[i] = np.empty(sizes[i], np.uint8)
                    read(paths[i], arrays[i])

                def again(i: int) -> None:
                    read(paths[i], arrays[i])

                for memory, one in (("fresh", fresh), ("again", again)):
                    rows.append(row({"kernel": kernel, "threads": threads, "memory": memory},
                                    timed_reads(one, count, threads), total))
                del arrays
        # The pool a restore would keep: a process's first pass through it
        # (slabs made and zero-filled as they are asked for) and its second.
        for threads in (8, 16):
            pool = SlabPool(POOL_BYTES)

            def pooled(i: int) -> None:
                slab = pool.take(sizes[i])
                try:
                    _native.pread_into_crc(paths[i], slab, PAGE)
                finally:
                    pool.give(slab)

            for memory in ("pool_first", "pool_again"):
                took = timed_reads(pooled, count, threads)
                rows.append(row({"kernel": "pread_into_crc", "threads": threads, "memory": memory,
                                 "pool_bytes": POOL_BYTES, "slab_bytes_made": pool.made_bytes,
                                 "slab_make_s": pool.make_s}, took, total))
            del pool
    finally:
        shutil.rmtree(directory, ignore_errors=True)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "probe_read_memory.json"), "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()

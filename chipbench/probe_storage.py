"""Plain sequential write + read of a few GiB, beside the cells' numbers.

    python chipbench/probe_storage.py /dev/shm/chipbench_probe /tmp/chipbench_probe

No jax, no library: `os.write` / `os.readinto` of 128 MiB pieces into one
file, three passes per directory, no fsync (the library calls none either).
It says what the mount under a cell's `storage.root` can take, so that a
cell's seconds can be read against it. Not part of any run of a cell.
"""

import json
import os
import shutil
import sys
import time

from storage import fs_type

PIECE = 128 << 20
TOTAL = 4 << 30
PASSES = 3


def probe(directory: str) -> dict:
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, "probe.bin")
    piece = bytearray(os.urandom(1 << 20) * (PIECE >> 20))
    into = bytearray(PIECE)
    out = {"dir": directory, "fs": fs_type(directory), "write_s": [], "read_s": []}
    try:
        for _ in range(PASSES):
            t0 = time.monotonic()
            fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o600)
            try:
                for _ in range(TOTAL // PIECE):
                    os.write(fd, piece)
            finally:
                os.close(fd)
            out["write_s"].append(time.monotonic() - t0)
            t0 = time.monotonic()
            fd = os.open(path, os.O_RDONLY)
            try:
                while os.readv(fd, [into]):
                    pass
            finally:
                os.close(fd)
            out["read_s"].append(time.monotonic() - t0)
    finally:
        shutil.rmtree(directory, ignore_errors=True)
    gib = TOTAL / 2**30
    out["write_GiB_per_s"] = [gib / s for s in out["write_s"]]
    out["read_GiB_per_s"] = [gib / s for s in out["read_s"]]
    return out


def main() -> None:
    with open("/proc/meminfo") as f:
        mem = dict(line.split(":") for line in f)
    print(json.dumps({"MemTotal": mem["MemTotal"].strip(),
                      "MemAvailable": mem["MemAvailable"].strip(),
                      "cpus": os.cpu_count()}))
    for directory in sys.argv[1:]:
        parent = os.path.dirname(directory.rstrip("/")) or "/"
        vfs = os.statvfs(parent)
        print(json.dumps({"mount_of": parent, "fs": fs_type(parent),
                          "size_GiB": vfs.f_blocks * vfs.f_frsize / 2**30,
                          "free_GiB": vfs.f_bavail * vfs.f_frsize / 2**30}))
        print(json.dumps(probe(directory)), flush=True)


if __name__ == "__main__":
    main()

"""The storage root of one run: a directory of this checkout's own under the
mount the traffic file names, checked, emptied, and removed again."""

import atexit
import hashlib
import os
import shutil
import signal
import sys
from typing import Any, Dict

from cells import ROOT, BenchError

# One retained step, one in flight, slack: `workload.KEEP_LAST_N` is 1.
FREE_OVER_STATE = 2.5
OWNER = ".chipbench_owner_pid"


def fs_type(path: str) -> str:
    """Filesystem type of the mount that holds `path` (copy of chip_smoke.fs_type)."""
    path = os.path.realpath(path)
    best, kind = "", "unknown"
    with open("/proc/mounts") as f:
        for line in f:
            _, mount, fstype = line.split()[:3]
            if (path == mount or path.startswith(mount.rstrip("/") + "/")) and len(
                mount
            ) >= len(best):
                best, kind = mount, fstype
    return kind


def root_of(storage: Dict[str, Any], checkout: str = ROOT) -> str:
    """`<root>-<hash of the checkout's path>`: two checkouts on one machine
    (the driver's parent and change, a test's copy) never meet, and the same
    checkout finds, and clears, what a killed run of its own left."""
    tag = hashlib.sha1(os.path.realpath(checkout).encode()).hexdigest()[:12]
    return f"{storage['root'].rstrip('/')}-{tag}"


def _owner_alive(root: str) -> int:
    """The pid that holds `root`, where that process still runs; else 0."""
    try:
        with open(os.path.join(root, OWNER)) as f:
            pid = int(f.read())
        os.kill(pid, 0)
    except (OSError, ValueError):
        return 0
    return 0 if pid == os.getpid() else pid


def claim(storage: Dict[str, Any]) -> str:
    """Check the mount is of the kind the traffic file says, empty this
    checkout's root and arrange its removal at exit and on SIGTERM: /dev/shm
    outlives a process, and a killed run's leftovers would take the next
    run's RAM. A root that a live run of this checkout holds is left alone."""
    root = root_of(storage)
    parent = os.path.dirname(root) or "/"
    if not os.path.isdir(parent):
        raise BenchError(f"storage root {root}: {parent} does not exist")
    kind = fs_type(parent)
    if kind != storage["kind"]:
        raise BenchError(f"storage root {root} is on {kind!r}, the traffic file says "
                         f"{storage['kind']!r}; no other path is tried")
    holder = _owner_alive(root)
    if holder:
        raise BenchError(f"storage root {root} is held by process {holder}: "
                         "one run to a checkout at a time")
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    with open(os.path.join(root, OWNER), "w") as f:
        f.write(str(os.getpid()))

    def remove() -> None:
        shutil.rmtree(root, ignore_errors=True)

    def on_term(signum, frame) -> None:
        remove()
        sys.exit(128 + signum)

    atexit.register(remove)
    signal.signal(signal.SIGTERM, on_term)
    return root


def check_room(root: str, state_bytes: int) -> None:
    vfs = os.statvfs(root)
    free = vfs.f_bavail * vfs.f_frsize
    if free < FREE_OVER_STATE * state_bytes:
        raise BenchError(f"storage root {root} has {free / 2**30:.2f} GiB free, the cell needs "
                         f"{FREE_OVER_STATE} x {state_bytes / 2**30:.2f} GiB")

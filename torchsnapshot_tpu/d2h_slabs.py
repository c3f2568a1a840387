"""Recycled host destinations for device-to-host staging.

A device-snapshot async take stages every jax leaf on its staging threads
with ``np.asarray(clone)``, and the runtime's copy lands in a host array
that jaxlib allocates right then. Memory the process has never touched
costs a page fault per 4 KiB inside that copy, and those faults are served
one at a time for the whole process (under 1 GiB/s on the v5e host, on any
number of threads; PERF.md section 6), the same problem ``dest_pool`` solves
for restores. jaxlib allocates that array through numpy's allocator
handler (NEP 49), on the calling thread, so :func:`lease` sets a handler
(``native/ts_slab_alloc.cpp``) around the one call: an allocation of at
least :data:`FLOOR_BYTES` takes a slab of exactly its size from
``dest_pool.process_pool()``, and the slab goes back to the pool when numpy
frees the array, on whatever thread drops it last (the write that took
the staged bytes). Anything smaller, and a request the pool cannot serve
under the window's cap, goes to numpy's default allocator.

The cap is the staging window plus its largest request
(:func:`staging_window`): the window already bounds what is in flight, so
that is what the pool keeps between saves, and never more. Only the
staging pipeline of a device-snapshot async take opens a window; a
blocking take started its copies at prepare time, outside any lease, and
restores and ``verify`` never stage.
"""

import contextlib
import contextvars
import gc
import threading
from typing import Dict, Iterator, NamedTuple, Optional, Sequence

from . import _native, dest_pool

# Below this a leaf's copy is too small for its faults to matter (the
# inline-staging threshold of io_preparer).
FLOOR_BYTES = 1 << 20


class _Window(NamedTuple):
    cap_bytes: int
    sizes: frozenset


_WINDOW: "contextvars.ContextVar[Optional[_Window]]" = contextvars.ContextVar(
    "torchsnapshot_tpu_d2h_window", default=None
)


class Lease:
    """One staged copy's account: bytes that landed in a slab of the
    pool (``leased_bytes``), and of those, bytes in a slab used before
    (``recycled_bytes``)."""

    __slots__ = ("leased_bytes", "recycled_bytes")

    def __init__(self) -> None:
        self.leased_bytes = 0
        self.recycled_bytes = 0


_local = threading.local()
# Slabs under a numpy array, by address: kept alive here until numpy
# frees the array.
_out: Dict[int, dest_pool.Slab] = {}
_init_lock = threading.Lock()
_handler = None
_handler_tried = False


def _take(nbytes: int) -> int:
    """The handler's malloc: a slab's address, or 0 for numpy's own."""
    lease = getattr(_local, "lease", None)
    window = _WINDOW.get()
    if lease is None or window is None:
        return 0
    slab = dest_pool.process_pool().try_take(
        nbytes, window.cap_bytes, keep_sizes=window.sizes
    )
    if slab is None:
        return 0
    address = slab.array.ctypes.data
    _out[address] = slab
    lease.leased_bytes += nbytes
    if slab.recycled:
        lease.recycled_bytes += nbytes
    return address


def _give(address: int) -> None:
    """The handler's free of a slab."""
    slab = _out.pop(address, None)
    if slab is not None:
        # Faulted in by the copy now, whether or not it was before.
        dest_pool.process_pool().give_back(slab)


def _load():
    global _handler, _handler_tried
    if not _handler_tried:
        with _init_lock:
            if not _handler_tried:
                lib = _native.slab_alloc()
                if lib is not None and lib.ts_slabs_init(_take, _give, FLOOR_BYTES) == 0:
                    _handler = lib
                _handler_tried = True
    return _handler


def drop_runtime_refs() -> None:
    """A jax array that dies hands the host value it cached (the array
    under a slab) to jaxlib's deferred garbage, which jax empties on
    every pass of Python's collector (``gc.callbacks``): run a pass of
    the youngest generation, so that the slab comes back when its last
    Python reference goes and not at some later collection."""
    gc.collect(0)


def available() -> bool:
    """The handler is built and numpy takes it."""
    return _load() is not None


def slabs_out() -> int:
    """Slabs under a numpy array now."""
    return len(_out)


@contextlib.contextmanager
def staging_window(request_bytes: Sequence[int], window_bytes: int) -> Iterator[None]:
    """The staging pipeline run inside this block (its tasks and executor
    hops inherit the context) leases slabs for requests of
    ``request_bytes``, under a cap of ``window_bytes`` plus the largest
    of them."""
    sizes = frozenset(n for n in request_bytes if n >= FLOOR_BYTES)
    if not sizes:
        yield
        return
    token = _WINDOW.set(_Window(window_bytes + max(sizes), sizes))
    try:
        yield
    finally:
        _WINDOW.reset(token)


@contextlib.contextmanager
def lease(nbytes: int) -> Iterator[Optional[Lease]]:
    """Host arrays numpy allocates on this thread inside the block take
    slabs: the ``np.asarray`` of a jax array of ``nbytes``. Yields None,
    and sets nothing, outside a staging window, below the floor, or where
    the handler is not available."""
    handler = _load() if nbytes >= FLOOR_BYTES and _WINDOW.get() is not None else None
    if handler is None or getattr(_local, "lease", None) is not None:
        yield None
        return
    current = _local.lease = Lease()
    if handler.ts_slabs_enter() != 0:
        _local.lease = None
        yield None
        return
    try:
        yield current
    finally:
        handler.ts_slabs_exit()
        _local.lease = None

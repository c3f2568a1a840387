"""Recycled host destinations for restore reads.

A restore reads every dense leaf into host memory and places it on a device
from there. Memory the process has never touched costs a page fault per
4 KiB inside the read, and those faults are served one at a time for the
whole process: on the v5e host a read into fresh memory moves under 1 GiB/s
on any number of threads, the same read into memory touched before 22-28
(PERF.md, PR 28). So the destinations of leaves that go back to an
accelerator come from this pool: page-aligned slabs in exact-size free lists
(a train state repeats every shape in params, mu and nu), faulted in by the
first read into them and handed out again once the device array placed from
them is ready (``is_ready()``, asked when a slab is next wanted; a restore
waits for its last placements before it returns, because an array the
application has deleted since can no longer be asked).

:class:`DestinationPool` is the process's slabs; :func:`process_pool` is the
one instance restores share. It keeps its free slabs between restores, up to
the largest cap a restore gave it (``retained_bytes``), until the process ends
or ``clear()`` is called. :class:`DestinationLeases` is one read pipeline's
side of it: the slabs that pipeline has out, its cap, and its reads waiting
for a slab.

Only a placement that copies may take a slab: the CPU backend's
``device_put`` can alias an aligned numpy buffer, and a recycled slab would
then rewrite an array the application holds (``snapshot._restore_destination``
decides; nothing here knows about devices).
"""

import asyncio
import collections
import threading
from typing import Any, Callable, Collection, Counter, Deque, Dict, List, Optional, Tuple

import numpy as np

from .utils.tracing import run_in_executor

_ALIGN = 4096
# A pipeline's cap: this many of its largest destination, or half its
# pooled bytes where that is more (half a plan holds every shape of a
# state whose second half repeats its first, as mu and nu do).
_SLABS_OF_LARGEST = 4
# How long a starved pipeline sleeps on the pool before it looks again
# at its own finished reads (slabs another restore holds change nothing
# it can see).
_STARVED_RECHECK_S = 0.1


class Slab:
    """``nbytes`` of page-aligned host memory. ``recycled`` says a read
    landed in it before: its pages are faulted in."""

    __slots__ = ("array", "recycled")

    def __init__(self, nbytes: int) -> None:
        # Untouched: the first read into it faults the pages in, on the
        # reading threads, instead of one thread zero-filling them here.
        raw = np.empty(nbytes + _ALIGN, np.uint8)
        start = (-raw.ctypes.data) % _ALIGN
        self.array = raw[start : start + nbytes]
        self.recycled = False


class DestinationPool:
    """Exact-size free lists of slabs. Thread-safe: restores on different
    threads share it."""

    def __init__(self) -> None:
        self._cond = threading.Condition()
        self._free: Dict[int, List[Slab]] = {}
        # Slabs whose leaf is placed, with the placed value: oldest first.
        self._placed: Deque[Tuple[Slab, Any]] = collections.deque()
        self._held_bytes = 0
        # The largest cap a pipeline has asked for: a restore's small
        # statefuls (a step count, an RNG key) run under caps of bytes,
        # and must not evict what its large ones recycle.
        self._cap_bytes = 0
        # Slabs out (under a read, or placed and not yet ready), by size.
        self._out_sizes: Counter[int] = collections.Counter()

    def retained_bytes(self) -> int:
        """Bytes of every slab the pool accounts for: free, under a read,
        or placed and not yet ready."""
        return self._held_bytes

    def try_take(
        self,
        nbytes: int,
        cap_bytes: int,
        may_grow: bool = True,
        keep_sizes: Collection[int] = (),
    ) -> Optional[Slab]:
        """A free slab of exactly ``nbytes``; else, with ``may_grow``, a
        new one if it fits under the pool's cap (``cap_bytes``, or a
        larger one asked for earlier); else None. Free slabs of
        sizes not in ``keep_sizes`` (the sizes the caller will ask for)
        give way to a new one. Those in it give way only where no slab of
        ``nbytes`` is out: one that is out comes back, and waiting for it
        costs less than faulting a new one in and, later, the evicted
        ones again. An empty pool admits any size (a cap below one
        destination must not stop a restore)."""
        with self._cond:
            free = self._free.get(nbytes)
            if free:
                slab = free.pop()
                slab.recycled = True
                self._out_sizes[nbytes] += 1
                return slab
            if not may_grow:
                return None
            cap_bytes = self._cap_bytes = max(self._cap_bytes, cap_bytes)
            self._evict_locked(nbytes, cap_bytes, keep_sizes)
            if not self._out_sizes[nbytes]:
                self._evict_locked(nbytes, cap_bytes, ())
            if self._held_bytes and self._held_bytes + nbytes > cap_bytes:
                return None
            self._held_bytes += nbytes
            self._out_sizes[nbytes] += 1
        return Slab(nbytes)

    def _evict_locked(
        self, nbytes: int, cap_bytes: int, keep_sizes: Collection[int]
    ) -> None:
        for size, slabs in self._free.items():
            if size in keep_sizes:
                continue
            while slabs and self._held_bytes + nbytes > cap_bytes:
                slabs.pop()
                self._held_bytes -= size

    def placed(self, slab: Slab, value: Any) -> None:
        """``slab``'s bytes were handed to ``device_put`` and ``value`` is
        the array it returned: the transfer may read the slab until
        ``value`` is ready, so it is free again only then (``sweep``)."""
        with self._cond:
            self._placed.append((slab, value))
            self._cond.notify_all()

    def discard(self, slab: Slab) -> None:
        """Forget a slab that is out (an executor thread of a failed
        restore may still be reading into it)."""
        with self._cond:
            self._forget_locked(slab)

    def _forget_locked(self, slab: Slab) -> None:
        self._held_bytes -= slab.array.nbytes
        self._out_sizes[slab.array.nbytes] -= 1
        self._cond.notify_all()

    def sweep(self) -> None:
        """Take back the slabs whose placed value is ready by now. A value
        that no longer says (the application deleted it, or donated it to
        a computation) may still be in transfer: its slab is dropped, not
        reused."""
        with self._cond:
            waiting: Deque[Tuple[Slab, Any]] = collections.deque()
            for slab, value in self._placed:
                try:
                    ready = value.is_ready()
                except Exception:  # noqa: BLE001 - deleted: unknowable
                    self._forget_locked(slab)
                    continue
                if ready:
                    self._free.setdefault(slab.array.nbytes, []).append(slab)
                    self._out_sizes[slab.array.nbytes] -= 1
                    self._cond.notify_all()
                else:
                    waiting.append((slab, value))
            self._placed = waiting

    def wait_for_change(self, timeout_s: float) -> None:
        """Block until the oldest placement is ready; with none, until
        one is made or a slab comes back or is dropped, at most
        ``timeout_s``. Off the event loop."""
        with self._cond:
            if not self._placed:
                self._cond.wait(timeout_s)
                return
            value = self._placed[0][1]
        _wait_ready(value)

    def unsettled(self) -> int:
        """Placements whose slab is not back yet."""
        return len(self._placed)

    def settle(self) -> None:
        """Wait for every placement and take its slab back. A restore does
        so before it returns: from then on the application may delete the
        placed arrays, and ``sweep`` has to drop what it cannot ask."""
        with self._cond:
            values = [value for _, value in self._placed]
        for value in values:
            _wait_ready(value)
        self.sweep()

    def clear(self) -> None:
        """Drop the free slabs (those out come back as always) and
        forget the cap."""
        with self._cond:
            self._cap_bytes = 0
            for size, slabs in self._free.items():
                self._held_bytes -= size * len(slabs)
            self._free.clear()


def _wait_ready(value: Any) -> None:
    try:
        value.block_until_ready()
    except Exception:  # noqa: BLE001 - deleted: the next sweep drops its slab
        pass


_PROCESS_POOL = DestinationPool()


def process_pool() -> DestinationPool:
    """The pool every restore of this process shares."""
    return _PROCESS_POOL


def pipeline_cap_bytes(destination_sizes: List[int], memory_budget_bytes: int) -> int:
    """The pool's cap for a pipeline with these pooled destinations: from
    what the plan shows, never below its largest destination, never above
    the restore's memory budget otherwise."""
    largest = max(destination_sizes)
    cap = max(_SLABS_OF_LARGEST * largest, sum(destination_sizes) // 2)
    return max(largest, min(cap, memory_budget_bytes))


class DestinationLeases:
    """One read pipeline's slabs. Everything runs on the pipeline's event
    loop, but ``placed`` and ``abandon``, which may also run on that
    thread once the loop has returned.

    ``flush`` is the pipeline's way to place what it has read
    (``_StreamingPlacer.flush``): a read that finds the pool at its cap
    waits for slabs, slabs come back only through placements, and a
    placement batch that waits for more bytes would wait for ever.
    """

    def __init__(
        self,
        pool: DestinationPool,
        sizes: List[int],
        memory_budget_bytes: int,
        flush: Callable[[], None],
    ) -> None:
        """``sizes``: the bytes of every destination the pipeline's reads
        will ask for."""
        self._pool = pool
        self._sizes = frozenset(sizes)
        self._cap = pipeline_cap_bytes(sizes, memory_budget_bytes)
        self._flush = flush
        self._out: Dict[int, Slab] = {}
        self._waiters: List[Tuple[int, "asyncio.Future[Slab]"]] = []
        self._server: Optional["asyncio.Task[None]"] = None
        self.bytes_recycled = 0
        self.bytes_fresh = 0

    @property
    def starved(self) -> bool:
        """A read of this pipeline is waiting for a slab."""
        return bool(self._waiters)

    async def bind(self, consumer: Any) -> bool:
        """Give ``consumer`` (a ``BufferConsumer``) its destination if it
        still needs one; True where that is a slab used before."""
        nbytes = consumer.unbound_destination_bytes()
        if not nbytes:
            return False
        slab = await self._take(nbytes)
        consumer.bind_destination(
            slab.array, lambda value: self.placed(slab, value)
        )
        if slab.recycled:
            self.bytes_recycled += nbytes
        else:
            self.bytes_fresh += nbytes
        return slab.recycled

    async def _take(self, nbytes: int) -> Slab:
        if not self._waiters:
            self._pool.sweep()
            # Free slabs of the plan's other sizes stay for its reads.
            slab = self._pool.try_take(
                nbytes, self._cap, keep_sizes=self._sizes
            )
            if slab is not None:
                self._out[id(slab)] = slab
                return slab
        waiter = asyncio.get_running_loop().create_future()
        self._waiters.append((nbytes, waiter))
        if self._server is None:
            self._server = asyncio.ensure_future(self._serve_waiters())
        return await waiter

    async def _serve_waiters(self) -> None:
        try:
            while self._waiters:
                self._flush()
                self._hand_out()
                if self._waiters:
                    await run_in_executor(
                        None, self._pool.wait_for_change, _STARVED_RECHECK_S
                    )
        except asyncio.CancelledError:
            for _, waiter in self._waiters:
                waiter.cancel()
            self._waiters.clear()
            raise
        except Exception as e:  # noqa: BLE001 - a failed placement fails the reads that wait on it
            for _, waiter in self._waiters:
                if not waiter.done():
                    waiter.set_exception(e)
            self._waiters.clear()
        finally:
            self._server = None

    def _hand_out(self) -> None:
        """Every waiting read whose size is free gets its slab, in order;
        then the first still in line may have a new one made. What came
        back for a read further down stays for that read, and a large
        leaf is not starved of room by the small ones behind it."""
        self._pool.sweep()
        waiting = []
        for nbytes, waiter in self._waiters:
            if waiter.done():
                continue
            slab = self._pool.try_take(nbytes, self._cap, may_grow=False)
            if slab is None:
                waiting.append((nbytes, waiter))
            else:
                self._give(slab, waiter)
        if waiting:
            nbytes, waiter = waiting[0]
            slab = self._pool.try_take(
                nbytes, self._cap, keep_sizes=self._sizes
            )
            if slab is not None:
                self._give(slab, waiter)
                del waiting[0]
        self._waiters = waiting

    def _give(self, slab: Slab, waiter: "asyncio.Future[Slab]") -> None:
        # Recorded here, not by the woken read: a read cancelled before
        # it runs again must not lose the slab.
        self._out[id(slab)] = slab
        waiter.set_result(slab)

    def placed(self, slab: Slab, value: Any) -> None:
        if self._out.pop(id(slab), None) is not None:
            self._pool.placed(slab, value)

    def abandon(self) -> None:
        """The restore failed: every slab still out is dropped, not
        returned (a cancelled read's thread may still be writing)."""
        for slab in self._out.values():
            self._pool.discard(slab)
        self._out.clear()

    async def aclose(self) -> None:
        """End of the pipeline's reads, failed or not: nothing of it may
        stay scheduled on a loop that is about to stop."""
        server = self._server
        if server is not None:
            server.cancel()
            try:
                await server
            except asyncio.CancelledError:
                pass

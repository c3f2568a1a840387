"""Recycled host destinations for restore reads.

A restore reads every leaf into host memory and places it on a device
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
application has deleted since can no longer be asked). A leaf saved in
shards takes all its destination boxes at once, one slab a box, and each
of its reads that has to be copied into them takes one more slab as its
read buffer, which comes back when the copy returns, with no device to ask.

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
import functools
import threading
from typing import (
    Any,
    Callable,
    Collection,
    Counter,
    Deque,
    Dict,
    List,
    Optional,
    Sequence,
    Tuple,
)

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
        """One slab of exactly ``nbytes`` (``try_take_all`` of one)."""
        slabs = self.try_take_all((nbytes,), cap_bytes, may_grow, keep_sizes)
        return None if slabs is None else slabs[0]

    def try_take_all(
        self,
        sizes: Sequence[int],
        cap_bytes: int,
        may_grow: bool = True,
        keep_sizes: Collection[int] = (),
        over_cap: bool = False,
    ) -> Optional[List[Slab]]:
        """A slab of exactly each of ``sizes``, all of them or None: free
        ones first; for the rest, with ``may_grow``, new ones if they fit
        under the pool's cap (``cap_bytes``, or a larger one asked for
        earlier). Free slabs of sizes not in ``keep_sizes`` (the sizes
        the caller will ask for) give way to new ones. Those in it give
        way only where fewer slabs of a wanted size are out than are
        missing: one that is out comes back, and waiting for it costs
        less than faulting a new one in and, later, the evicted ones
        again. An empty pool admits any sizes (a cap below one
        destination must not stop a restore), and ``over_cap`` takes
        them in any case, once every free slab has given way (for the
        caller whom nothing that is out will help)."""
        need = collections.Counter(sizes)
        with self._cond:
            missing = {
                size: n - len(self._free.get(size, ()))
                for size, n in need.items()
                if n > len(self._free.get(size, ()))
            }
            if missing:
                if not may_grow:
                    return None
                cap_bytes = self._cap_bytes = max(self._cap_bytes, cap_bytes)
                new_bytes = sum(size * n for size, n in missing.items())
                self._evict_locked(new_bytes, cap_bytes, set(keep_sizes) | set(need))
                if over_cap or any(
                    self._out_sizes[size] < n for size, n in missing.items()
                ):
                    self._evict_locked(new_bytes, cap_bytes, need)
                if (
                    not over_cap
                    and self._held_bytes
                    and self._held_bytes + new_bytes > cap_bytes
                ):
                    return None
                self._held_bytes += new_bytes
            slabs: List[Optional[Slab]] = []
            for size in sizes:
                free = self._free.get(size)
                slab = free.pop() if free else None
                if slab is not None:
                    slab.recycled = True
                slabs.append(slab)
                self._out_sizes[size] += 1
        return [
            Slab(size) if slab is None else slab
            for size, slab in zip(sizes, slabs)
        ]

    def _evict_locked(
        self, nbytes: int, cap_bytes: int, keep_sizes: Collection[int]
    ) -> None:
        for size, slabs in self._free.items():
            if size in keep_sizes:
                continue
            while slabs and self._held_bytes + nbytes > cap_bytes:
                slabs.pop()
                self._held_bytes -= size

    def give_back(self, slab: Slab) -> None:
        """``slab`` is out and nothing reads or writes it any more (a
        read buffer whose copy returned): it is free again."""
        with self._cond:
            self._free.setdefault(slab.array.nbytes, []).append(slab)
            self._out_sizes[slab.array.nbytes] -= 1
            self._cond.notify_all()

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


class PlacedTogether:
    """Several arrays placed from one slab (a box of a sharded leaf that
    more than one device holds), as one value for ``placed``: ready when
    all are."""

    def __init__(self, values: Sequence[Any]) -> None:
        self._values = list(values)

    def is_ready(self) -> bool:
        return all(value.is_ready() for value in self._values)

    def block_until_ready(self) -> None:
        for value in self._values:
            value.block_until_ready()


_PROCESS_POOL = DestinationPool()


def process_pool() -> DestinationPool:
    """The pool every restore of this process shares."""
    return _PROCESS_POOL


def pipeline_cap_bytes(
    destination_sizes: List[int],
    memory_budget_bytes: int,
    buffer_sizes: Sequence[int] = (),
    reads_in_flight: int = 0,
) -> int:
    """The pool's cap for a pipeline with these pooled destinations (a
    sharded leaf counts as one, with the bytes of all its boxes: it takes
    them together): from what the plan shows, never below its largest
    destination, never above the restore's memory budget otherwise. Read
    buffers add what ``reads_in_flight`` of the largest take, not their
    sum over the plan, and one to the floor."""
    largest = max(destination_sizes)
    cap = max(_SLABS_OF_LARGEST * largest, sum(destination_sizes) // 2)
    if buffer_sizes:
        largest_buffer = max(buffer_sizes)
        cap += min(sum(buffer_sizes), reads_in_flight * largest_buffer)
        largest += largest_buffer
    return max(largest, min(cap, memory_budget_bytes))


class Lease:
    """What one read took from the pool, for its ``restore:dest_acquire``
    span. ``recycled``: every slab it took was used before (for a read
    that took none, the box it lands in). ``buffer``: the slab its bytes
    land in before they are copied, to be given back (``release``)."""

    __slots__ = ("recycled", "box_bytes", "box_bytes_recycled", "buffer")

    def __init__(self) -> None:
        self.recycled = False
        self.box_bytes = 0
        self.box_bytes_recycled = 0
        self.buffer: Optional[Slab] = None


class _Ask:
    """A read waiting for slabs of ``sizes``, all at once."""

    __slots__ = ("sizes", "buffer", "granted")

    def __init__(
        self, sizes: Tuple[int, ...], buffer: bool, granted: "asyncio.Future[List[Slab]]"
    ) -> None:
        self.sizes = sizes
        self.buffer = buffer
        self.granted = granted


class DestinationLeases:
    """One read pipeline's slabs. Everything runs on the pipeline's event
    loop, but ``placed`` and ``abandon``, which may also run on that
    thread once the loop has returned.

    ``flush`` is the pipeline's way to place what it has read
    (``_StreamingPlacer.flush``): a read that finds the pool at its cap
    waits for slabs, slabs come back only through placements, and a
    placement batch that waits for more bytes would wait for ever.

    A leaf saved in shards has several reads and several boxes. Its boxes
    are taken together by whichever of its reads comes first, all or none:
    two leaves each holding half their boxes at the cap would wait on each
    other for ever. A leaf that holds its boxes gives them back only after
    every one of its reads has had a buffer, so a waiting buffer is served
    before a waiting destination, and where nothing is on its way back
    (none of the pipeline's buffers is out, no placement has yet to land)
    the first in line is made even over the cap.
    """

    def __init__(
        self,
        pool: DestinationPool,
        sizes: List[int],
        memory_budget_bytes: int,
        flush: Callable[[], None],
        groups: Sequence[Sequence[int]] = (),
        buffers: Sequence[int] = (),
        reads_in_flight: int = 1,
    ) -> None:
        """``sizes``: the bytes of every destination the pipeline's reads
        will ask for one at a time; ``groups``: for every leaf whose
        destinations are taken together, their bytes; ``buffers``: the
        bytes of every read buffer that will be asked for, of which
        ``reads_in_flight`` are wanted at once."""
        self._pool = pool
        self._sizes = frozenset(sizes).union(buffers, *groups)
        self._cap = pipeline_cap_bytes(
            list(sizes) + [sum(group) for group in groups],
            memory_budget_bytes,
            buffers,
            reads_in_flight,
        )
        self._flush = flush
        self._out: Dict[int, Slab] = {}
        self._buffers_out = 0
        self._waiters: List[_Ask] = []
        self._server: Optional["asyncio.Task[None]"] = None
        # Leaves whose destinations some read is taking or has taken.
        self._shared: Dict[int, "asyncio.Future[None]"] = {}
        self.bytes_recycled = 0
        self.bytes_fresh = 0

    @classmethod
    def for_reads(
        cls,
        pool: DestinationPool,
        consumers: Sequence[Any],
        memory_budget_bytes: int,
        flush: Callable[[], None],
        reads_in_flight: int,
    ) -> Optional["DestinationLeases"]:
        """The leases of a pipeline with these ``BufferConsumer``s, from
        what they still need; None where none needs anything."""
        shared = {}
        for consumer in consumers:
            leaf = consumer.shared_destination()
            if leaf is not None:
                shared[id(leaf)] = leaf
        sizes = [c.unbound_destination_bytes() for c in consumers]
        groups = [leaf.unbound_sizes() for leaf in shared.values()]
        buffers = [c.read_buffer_bytes() for c in consumers]
        sizes, groups = [n for n in sizes if n], [g for g in groups if g]
        if not sizes and not groups:
            return None
        return cls(
            pool,
            sizes,
            memory_budget_bytes,
            flush,
            groups,
            [n for n in buffers if n],
            reads_in_flight,
        )

    @property
    def starved(self) -> bool:
        """A read of this pipeline is waiting for a slab."""
        return bool(self._waiters)

    async def bind_shared(self, consumer: Any, lease: Lease) -> None:
        """Give the leaf of ``consumer`` its destinations if no read of it
        has yet, or wait for the read that is taking them. Before the
        read is charged to the memory budget: a read that waits here then
        holds nothing that the leaves holding the slabs wait for."""
        shared = consumer.shared_destination()
        if shared is None:
            return
        bound = self._shared.get(id(shared))
        if bound is not None:
            await bound
            lease.recycled = shared.recycled
            return
        sizes = shared.unbound_sizes()
        if not sizes:
            return
        bound = self._shared[id(shared)] = asyncio.get_running_loop().create_future()
        try:
            slabs = await self._take(tuple(sizes), buffer=False)
        except BaseException:
            bound.cancel()
            raise
        shared.bind(
            [slab.array for slab in slabs],
            [functools.partial(self.placed, slab) for slab in slabs],
            all(slab.recycled for slab in slabs),
        )
        bound.set_result(None)
        lease.recycled = shared.recycled
        lease.box_bytes = sum(sizes)
        lease.box_bytes_recycled = sum(
            slab.array.nbytes for slab in slabs if slab.recycled
        )

    async def bind(self, consumer: Any, lease: Lease) -> None:
        """Give ``consumer`` (a ``BufferConsumer``) what it still needs of
        its own: its destination, or the buffer its read lands in."""
        nbytes = consumer.unbound_destination_bytes()
        if nbytes:
            (slab,) = await self._take((nbytes,), buffer=False)
            consumer.bind_destination(
                slab.array, functools.partial(self.placed, slab)
            )
            lease.recycled = slab.recycled
        nbytes = consumer.read_buffer_bytes()
        if nbytes:
            (lease.buffer,) = await self._take((nbytes,), buffer=True)
            # Of what this read took itself: the leaf's boxes count
            # where it was the read that bound them.
            lease.recycled = lease.buffer.recycled and (
                lease.box_bytes == lease.box_bytes_recycled
            )

    def release(self, lease: Lease) -> None:
        """The read's bytes are out of its buffer: the slab is free for
        the next read, with no device to ask."""
        slab, lease.buffer = lease.buffer, None
        if slab is not None and self._out.pop(id(slab), None) is not None:
            self._buffers_out -= 1
            self._pool.give_back(slab)
            if self._waiters:
                self._hand_out()

    async def _take(self, sizes: Tuple[int, ...], buffer: bool) -> List[Slab]:
        ask = _Ask(sizes, buffer, asyncio.get_running_loop().create_future())
        self._waiters.append(ask)
        self._hand_out()
        if not ask.granted.done() and self._server is None:
            self._server = asyncio.ensure_future(self._serve_waiters())
        return await ask.granted

    async def _serve_waiters(self) -> None:
        try:
            while self._waiters:
                self._flush()
                self._hand_out(stuck=not self._buffers_out and not self._pool.unsettled())
                if self._waiters:
                    await run_in_executor(
                        None, self._pool.wait_for_change, _STARVED_RECHECK_S
                    )
        except asyncio.CancelledError:
            for ask in self._waiters:
                ask.granted.cancel()
            self._waiters.clear()
            raise
        except Exception as e:  # noqa: BLE001 - a failed placement fails the reads that wait on it
            for ask in self._waiters:
                if not ask.granted.done():
                    ask.granted.set_exception(e)
            self._waiters.clear()
        finally:
            self._server = None

    def _hand_out(self, stuck: bool = False) -> None:
        """Every waiting read whose sizes are free gets its slabs, in
        order; then the first buffer still in line may have a new one
        made, and the first destination. What came back for a read
        further down stays for that read, and a large leaf is not starved
        of room by the small ones behind it. ``stuck``: nothing is on its
        way back (no buffer is out, what was read is placed and has
        landed), so the first buffer in line is made even over the cap:
        the leaves that hold their boxes wait for it."""
        self._pool.sweep()
        waiting = []
        # Sizes the free lists could not serve in this pass: they cannot
        # serve them to a read further down either.
        not_free = set()
        for ask in self._waiters:
            if ask.granted.done():
                continue
            slabs = None
            if ask.sizes not in not_free:
                slabs = self._pool.try_take_all(ask.sizes, self._cap, may_grow=False)
            if slabs is None:
                not_free.add(ask.sizes)
                waiting.append(ask)
            else:
                self._give(slabs, ask)
        for buffer in (True, False):
            ask = next((a for a in waiting if a.buffer == buffer), None)
            if ask is None:
                continue
            slabs = self._pool.try_take_all(
                ask.sizes,
                self._cap,
                keep_sizes=self._sizes,
                over_cap=buffer and stuck,
            )
            if slabs is not None:
                self._give(slabs, ask)
                waiting.remove(ask)
        self._waiters = waiting

    def _give(self, slabs: List[Slab], ask: _Ask) -> None:
        # Recorded here, not by the woken read: a read cancelled before
        # it runs again must not lose the slabs.
        self._buffers_out += ask.buffer
        for slab in slabs:
            self._out[id(slab)] = slab
            if slab.recycled:
                self.bytes_recycled += slab.array.nbytes
            else:
                self.bytes_fresh += slab.array.nbytes
        ask.granted.set_result(slabs)

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

"""Small-write coalescing into slab blobs.

Reference parity: torchsnapshot/batcher.py (482 LoC). Buffer-protocol write
requests under the slab threshold (knob, 128 MiB default) are packed into
``batched/{uuid}`` slabs; every affected ``ArrayEntry`` — standalone or
nested inside Chunked/Sharded entries — has its ``location``/``byte_range``
rewritten to point into the slab (reference batcher.py:202-352). On the read
side, multiple ranged reads of one location merge into a single spanning
read whose consumer hands each member its sub-slice (reference
batcher.py:355-474).

TPU-native simplifications vs the reference:

- Slab member sizes are computed exactly at *planning* time from
  dtype × shape arithmetic (buffer-protocol arrays have no serialization
  framing), so byte ranges are assigned before any staging happens — no
  placeholder rewriting pass.
- The device-slab path (reference GPUBatchedBufferStager,
  batcher.py:102-160) is a fused XLA program (ops/device_pack.py): slab
  members resident on device are bitcast+concatenated on device and leave
  via ONE D2H transfer. It is knob-gated off by default
  (``TORCHSNAPSHOT_TPU_DEVICE_PACK``): per-member ``copy_to_host_async``
  prefetches pipeline well on links that handle small async copies
  efficiently, while the pack wins where per-transfer overhead
  dominates (10⁴⁺ tiny leaves, high-latency hosts). Neither has been
  timed on the v5e chip. Both paths are bit-identical.
"""

from __future__ import annotations

import asyncio
import logging
import uuid
from concurrent.futures import Executor
from typing import Dict, List, Optional, Tuple

import numpy as np

from . import _native, knobs, telemetry
from .telemetry import names as metric_names
from .telemetry.trace import get_recorder as _trace_recorder
from .utils.tracing import run_in_executor
from .io_types import (
    BufferConsumer,
    BufferList,
    BufferStager,
    BufferType,
    ReadReq,
    as_bytes_view,
    WriteReq,
)
from .manifest import (
    ArrayEntry,
    ChunkedArrayEntry,
    Entry,
    ShardedArrayEntry,
)


logger: logging.Logger = logging.getLogger(__name__)


def _is_batchable(req: WriteReq) -> bool:
    """Buffer-protocol array stagers without a custom prepare hook produce
    exactly ``get_staging_cost_bytes()`` bytes (reference is_batchable,
    batcher.py:477-482)."""
    from .io_preparer import ArrayBufferStager

    stager = req.buffer_stager
    return (
        isinstance(stager, ArrayBufferStager)
        and stager.array_prepare_func is None
    )


def _array_entries_by_location(entries: List[Entry]) -> Dict[str, List[ArrayEntry]]:
    """Every ArrayEntry in the manifest, keyed by storage location —
    including those nested in chunked/sharded entries."""
    out: Dict[str, List[ArrayEntry]] = {}

    def add(ae: ArrayEntry) -> None:
        out.setdefault(ae.location, []).append(ae)

    for entry in entries:
        if isinstance(entry, ArrayEntry):
            add(entry)
        elif isinstance(entry, (ChunkedArrayEntry, ShardedArrayEntry)):
            shards = entry.chunks if isinstance(entry, ChunkedArrayEntry) else entry.shards
            for shard in shards:
                add(shard.array)
    return out


class BatchedBufferStager(BufferStager):
    """Stages member buffers into one slab bytearray.

    Device-resident members pack **on device** first: a fused jitted
    program bitcasts each to its uint8 memory image and concatenates, so
    a device group's members cost one dispatch + one D2H transfer instead
    of one per member — the TPU answer to the reference's
    GPUBatchedBufferStager (batcher.py:102-160), replacing its
    storage-level GPU copies with an XLA program. Host members (and any
    device member the pack cannot handle) are materialized sequentially
    on the executor, costing only the memcpy.
    """

    def __init__(self, members: List[Tuple[WriteReq, int, int]]) -> None:
        # (req, offset, size) triples; offsets pre-assigned at planning.
        self.members = members
        self.total = sum(size for _, _, size in members)
        # The group split (and the staging cost derived from it) is fixed
        # here: it depends on knob state and on stager.arr fields that
        # staging itself mutates, so admission and any later budget
        # arithmetic must see one consistent value. The vectorized-write
        # decision is pinned for the same reason: a knob flip between
        # admission and staging must not change what this stager costs
        # or returns.
        self._vectorized = knobs.is_write_vectorized_enabled()
        self._packed, self._rest = self._split_device_groups()
        pack_bytes = sum(size for items in self._packed for _, _, size in items)
        peak_member = max(
            (
                req.buffer_stager.get_staging_cost_bytes()
                for req, _, _ in self._rest
            ),
            default=0,
        )
        if self._vectorized:
            # Zero-pack: the members' own staged buffers ARE the output
            # (handed to the plugin as a BufferList) — no slab
            # allocation, no transient pack copies alongside it.
            self._staging_cost = self.total
        else:
            self._staging_cost = self.total + pack_bytes + peak_member

    def jax_sources(self) -> list:
        return [
            arr
            for req, _, _ in self.members
            for arr in req.buffer_stager.jax_sources()
        ]

    def capture(self, cache: dict, leaf: str = "") -> None:
        """Device-snapshot capture recurses into the slab's members:
        each member stager pins its own source (shared ``cache``, so a
        leaf split across slabs still snapshots once). The group split
        computed at construction still holds — jax members clone to jax
        arrays on the same devices, so pack eligibility is unchanged
        (and the pack path degrades to sequential staging on any
        surprise, as it always has)."""
        for req, _, _ in self.members:
            req.buffer_stager.capture(cache, leaf=req.path)

    # Per-dispatch member cap: an N-ary concat program's trace/compile
    # time grows with N, and one compile per distinct slab layout must
    # stay cheap.
    _PACK_GROUP_MAX = 128

    def _split_device_groups(self):
        """Partition members into device-pack groups (>= 2 jax members on
        one device set, knob-gated) and the remainder staged
        member-by-member."""
        if not knobs.is_device_pack_enabled():
            return [], list(self.members)
        from .io_preparer import ArrayBufferStager, is_jax_array
        from .ops.device_pack import device_group_key, pack_supported

        groups: Dict[Tuple[int, ...], List[Tuple[WriteReq, int, int]]] = {}
        rest: List[Tuple[WriteReq, int, int]] = []
        for item in self.members:
            stager = item[0].buffer_stager
            arr = getattr(stager, "arr", None)
            if (
                isinstance(stager, ArrayBufferStager)
                and is_jax_array(arr)
                and pack_supported(arr.dtype)
            ):
                groups.setdefault(device_group_key(arr), []).append(item)
            else:
                rest.append(item)
        packed: List[List[Tuple[WriteReq, int, int]]] = []
        for key, items in groups.items():
            if len(items) < 2:
                rest.extend(items)
                continue
            for i in range(0, len(items), self._PACK_GROUP_MAX):
                chunk = items[i : i + self._PACK_GROUP_MAX]
                if len(chunk) >= 2:
                    packed.append(chunk)
                else:
                    rest.extend(chunk)
        return packed, rest

    def _pack_group_sync(
        self, items: List[Tuple[WriteReq, int, int]], view: memoryview
    ) -> None:
        """One dispatch + one D2H for a whole device group, scattered into
        the slab at the planned offsets. Falls back to per-member staging
        on any failure (pack is an optimization, never a requirement)."""
        from .ops.device_pack import pack_async

        try:
            specs = []
            for req, _, _ in items:
                stager = req.buffer_stager
                slc = stager.slc
                specs.append(
                    (
                        stager.arr,
                        (slc.start, slc.stop) if slc is not None else None,
                    )
                )
            host = np.asarray(pack_async(specs))  # the single D2H
            expected = sum(size for _, _, size in items)
            if host.nbytes != expected:
                raise RuntimeError(
                    f"device pack produced {host.nbytes} bytes, "
                    f"planned {expected}"
                )
            src = 0
            for req, offset, size in items:
                view[offset : offset + size] = host[src : src + size].data
                src += size
                req.buffer_stager.arr = None  # release HBM promptly
        except Exception as e:  # noqa: BLE001
            logger.warning(
                "Device slab pack failed (%r); staging %d members "
                "individually",
                e,
                len(items),
            )
            for req, offset, size in items:
                # arr is cleared only after a member's bytes landed in the
                # slab; a mid-scatter failure must not re-stage those.
                if req.buffer_stager.arr is None:
                    continue
                buf = req.buffer_stager._stage_sync()
                self._copy_member(view, buf, req, offset, size)

    def _copy_member(
        self, view: memoryview, buf: BufferType, req: WriteReq, offset: int, size: int
    ) -> None:
        mv = as_bytes_view(buf)
        self._check_member_size(len(mv), req, size)
        view[offset : offset + size] = mv

    async def stage_buffer(self, executor: Optional[Executor] = None) -> BufferType:
        if self._vectorized:
            # Zero-pack path: no slab buffer exists, so no pack span is
            # emitted — the distinct span name is the observable pin
            # that the pack pass did not run.
            with _trace_recorder().span(
                metric_names.SPAN_BATCHER_STAGE_SLAB_VECTORIZED,
                members=len(self.members),
                bytes=self.total,
            ):
                return await self._stage_vectorized_impl(executor)
        # Recorder-only span (awaits inside): the slab's whole
        # pack+memcpy assembly as one timeline block.
        with _trace_recorder().span(
            metric_names.SPAN_BATCHER_STAGE_SLAB,
            members=len(self.members),
            bytes=self.total,
        ):
            return await self._stage_buffer_impl(executor)

    def _pack_group_vectorized(
        self, items: List[Tuple[WriteReq, int, int]]
    ) -> List[Tuple[int, memoryview]]:
        """Device-pack a group for the zero-pack path: one dispatch + one
        D2H yields a host buffer whose per-member slices become BufferList
        parts directly — no scatter into a slab. Falls back to per-member
        staging on any failure, like the packed path."""
        from .ops.device_pack import pack_async

        out: List[Tuple[int, memoryview]] = []
        try:
            specs = []
            for req, _, _ in items:
                stager = req.buffer_stager
                slc = stager.slc
                specs.append(
                    (
                        stager.arr,
                        (slc.start, slc.stop) if slc is not None else None,
                    )
                )
            host = np.asarray(pack_async(specs))  # the single D2H
            expected = sum(size for _, _, size in items)
            if host.nbytes != expected:
                raise RuntimeError(
                    f"device pack produced {host.nbytes} bytes, "
                    f"planned {expected}"
                )
            hostview = memoryview(host).cast("B")
            src = 0
            for req, offset, size in items:
                out.append((offset, hostview[src : src + size]))
                src += size
                req.buffer_stager.arr = None  # release HBM promptly
            return out
        except Exception as e:  # noqa: BLE001
            logger.warning(
                "Device slab pack failed (%r); staging %d members "
                "individually",
                e,
                len(items),
            )
            for req, offset, size in items:
                if req.buffer_stager.arr is None:
                    # This member's bytes already landed in ``out``.
                    continue
                buf = req.buffer_stager._stage_sync()
                mv = as_bytes_view(buf)
                self._check_member_size(len(mv), req, size)
                out.append((offset, mv))
            return out

    async def _stage_vectorized_impl(
        self, executor: Optional[Executor] = None
    ) -> BufferList:
        """Zero-pack slab staging: stage every member, hand the staged
        buffers to the write path as a :class:`BufferList` in planned
        offset order. The plugin's vectorized kernel (pwritev + fused
        CRC) writes them without the gather_memcpy pack pass ever
        running — the one-full-memory-pass-per-staged-byte elimination
        this path exists for."""
        parts: List[Tuple[int, memoryview]] = []
        pack_futures = [
            run_in_executor(executor, self._pack_group_vectorized, items)
            for items in self._packed
        ]
        first_exc: Optional[BaseException] = None
        try:
            for req, offset, size in self._rest:
                buf = await req.buffer_stager.stage_buffer(executor)
                mv = as_bytes_view(buf)
                self._check_member_size(len(mv), req, size)
                parts.append((offset, mv))
        except BaseException as e:  # noqa: BLE001 - settle packs first
            first_exc = e
        for fut in pack_futures:
            try:
                parts.extend(await fut)
            except BaseException as pack_exc:  # noqa: BLE001
                if first_exc is None:
                    first_exc = pack_exc
                else:
                    logger.warning(
                        "Device pack failed while aborting slab staging: %r",
                        pack_exc,
                    )
        if first_exc is not None:
            raise first_exc
        parts.sort(key=lambda item: item[0])
        expect = 0
        for offset, mv in parts:
            if offset != expect:
                raise RuntimeError(
                    f"vectorized slab has a hole at byte {expect} "
                    f"(next member starts at {offset}); manifest byte "
                    f"ranges would be wrong"
                )
            expect = offset + mv.nbytes
        if expect != self.total:
            raise RuntimeError(
                f"vectorized slab staged {expect} bytes, planned "
                f"{self.total}"
            )
        telemetry.metrics().counter_inc(
            metric_names.BATCHER_PACK_BYTES_AVOIDED_TOTAL, self.total
        )
        return BufferList([mv for _, mv in parts])

    def _check_member_size(self, staged: int, req: WriteReq, size: int) -> None:
        if staged != size:
            raise RuntimeError(
                f"Slab member {req.path!r} staged {staged} bytes but "
                f"was planned at {size}; byte ranges in the manifest "
                f"would be wrong"
            )

    async def _stage_buffer_impl(
        self, executor: Optional[Executor] = None
    ) -> BufferType:
        # 4096-aligned allocation: a packed slab qualifies for the fs
        # plugin's O_DIRECT write path (alignment is the eligibility
        # gate; see docs/storage.md "Native write path").
        slab = _native.aligned_buffer(self.total)
        view = memoryview(slab)
        packed, rest = self._packed, self._rest
        pack_futures = [
            run_in_executor(executor, self._pack_group_sync, items, view)
            for items in packed
        ]
        # Every pack future MUST settle before this method returns or
        # raises, no matter which one fails first: the executor threads
        # hold the slab's exported memoryview and may still be writing
        # into it (bytearray deallocation with exported views aborts the
        # interpreter). Collect the first failure — from the rest loop or
        # any pack — settle everything, then raise it.
        first_exc: Optional[BaseException] = None
        try:
            for req, offset, size in rest:
                buf = await req.buffer_stager.stage_buffer(executor)
                # Large members copy with the multithreaded native memcpy;
                # small ones aren't worth the thread spawn.
                if size >= (8 << 20):
                    mv = as_bytes_view(buf)
                    if len(mv) == size and _native.gather_memcpy(
                        slab, [(mv, offset)], n_threads=4
                    ):
                        continue
                self._copy_member(view, buf, req, offset, size)
        except BaseException as e:  # noqa: BLE001 - settle packs first
            first_exc = e
        for fut in pack_futures:
            try:
                await fut
            except BaseException as pack_exc:  # noqa: BLE001
                if first_exc is None:
                    first_exc = pack_exc
                else:
                    logger.warning(
                        "Device pack failed while aborting slab staging: %r",
                        pack_exc,
                    )
        if first_exc is not None:
            raise first_exc
        return slab

    def get_staging_cost_bytes(self) -> int:
        # The pack path transiently holds each group's packed host buffer
        # alongside the slab before the scatter, groups run concurrently,
        # AND the rest loop stages one member at the same time — admit at
        # the sum so the scheduler's budget bounds the true peak. The
        # member term counts only non-packed members (a packed member's
        # bytes are already inside pack_bytes). A slab with no
        # pack-eligible members costs the same as with the knob off.
        # Computed once in __init__: staging mutates the fields it
        # depends on.
        return self._staging_cost


def batch_write_requests(
    entries: List[Entry], write_reqs: List[WriteReq]
) -> Tuple[List[Entry], List[WriteReq]]:
    """Coalesce sub-threshold buffer-protocol writes into slabs, rewriting
    the affected manifest entries in place."""
    threshold = knobs.get_slab_size_threshold_bytes()
    by_location = _array_entries_by_location(entries)

    small: List[Tuple[WriteReq, int]] = []
    kept: List[WriteReq] = []
    for req in write_reqs:
        size = req.buffer_stager.get_staging_cost_bytes()
        # Only coalesce writes whose manifest entry we can rewrite.
        if _is_batchable(req) and size < threshold and req.path in by_location:
            small.append((req, size))
        else:
            kept.append(req)

    if len(small) < 2:
        return entries, write_reqs

    # Greedy fill: pack in plan order until the slab would overflow.
    slabs: List[List[Tuple[WriteReq, int, int]]] = []
    current: List[Tuple[WriteReq, int, int]] = []
    offset = 0
    for req, size in small:
        if current and offset + size > threshold:
            slabs.append(current)
            current, offset = [], 0
        current.append((req, offset, size))
        offset += size
    if current:
        slabs.append(current)

    for members in slabs:
        if len(members) == 1:
            # A lone member gains nothing from slab indirection.
            kept.append(members[0][0])
            continue
        location = f"batched/{uuid.uuid4().hex}"
        for req, off, size in members:
            for ae in by_location[req.path]:
                ae.location = location
                ae.byte_range = [off, off + size]
        kept.append(
            WriteReq(path=location, buffer_stager=BatchedBufferStager(members))
        )
    return entries, kept


# ----------------------------------------------------------------------
# read side
# ----------------------------------------------------------------------


class BatchedBufferConsumer(BufferConsumer):
    """Feeds each member consumer its sub-slice of a spanning read
    (reference BatchedBufferConsumer, batcher.py:355-474)."""

    def __init__(self, members: List[ReadReq], base: int, span_bytes: int) -> None:
        self.members = members
        self.base = base
        self.span_bytes = span_bytes

    async def consume_buffer(
        self, buf: BufferType, executor: Optional[Executor] = None
    ) -> None:
        mv = as_bytes_view(buf)
        # Recorder-only span: the spanning read's fan-out to member
        # consumers, previously invisible on any timeline.
        with _trace_recorder().span(
            metric_names.SPAN_BATCHER_CONSUME_SPANNING,
            members=len(self.members),
            bytes=self.span_bytes,
        ):
            await asyncio.gather(
                *(
                    member.buffer_consumer.consume_buffer(
                        mv[member.byte_range[0] - self.base : member.byte_range[1] - self.base],
                        executor,
                    )
                    for member in self.members
                )
            )

    def get_consuming_cost_bytes(self) -> int:
        # The spanning buffer itself (gap bytes included) dominates; the
        # member copies consume into destinations already accounted for.
        return max(
            self.span_bytes,
            sum(m.buffer_consumer.get_consuming_cost_bytes() for m in self.members),
        )


def batch_read_requests(read_reqs: List[ReadReq]) -> List[ReadReq]:
    """Merge ranged reads of one *slab* into one spanning read.

    Only ``batched/`` locations are merged: other multi-read paths are
    budget-bounded chunk splits (io_preparer / sharded_io_preparer ranged
    reads), and re-merging those would reintroduce exactly the unbounded
    buffer the splitting exists to prevent.
    """
    groups: Dict[str, List[ReadReq]] = {}
    order: List[str] = []
    out: List[ReadReq] = []
    for req in read_reqs:
        if not req.path.startswith("batched/") or req.byte_range is None:
            out.append(req)
            continue
        if req.path not in groups:
            order.append(req.path)
        groups.setdefault(req.path, []).append(req)

    for path in order:
        members = groups[path]
        if len(members) == 1:
            out.append(members[0])
            continue
        base = min(m.byte_range[0] for m in members)
        end = max(m.byte_range[1] for m in members)
        out.append(
            ReadReq(
                path=path,
                buffer_consumer=BatchedBufferConsumer(members, base, end - base),
                byte_range=(base, end),
            )
        )
    return out

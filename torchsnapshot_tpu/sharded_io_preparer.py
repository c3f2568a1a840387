"""Sharded-array preparer: GSPMD-partitioned ``jax.Array`` checkpointing
with elastic resharding on restore.

Reference parity: ShardedTensorIOPreparer (io_preparer.py:167-391) — but
where the reference walks torch ``ShardedTensor`` chunk specs on one
dimension, a single ``NamedSharding``-driven preparer covers every GSPMD
layout uniformly (FSDP, TP, row/column-wise embedding sharding, sequence-dim
sharding, replicated × sharded mixes, uneven remainders): the analysis in
SURVEY.md §2.12.

Write side:
- ``addressable_shards`` yields this process's device shards; exactly one
  *global* copy of each distinct shard box is written, elected by
  ``replica_id == 0`` (each box's replica-0 device lives on exactly one
  process, so no coordination round is needed for deduplication — the
  write-once analog of the reference's replicated partitioning).
- Boxes larger than the shard-size knob subdivide along dim 0 (reference
  subdivide_shard, io_preparer.py:168-198).
- The device→host DMA is started asynchronously at prepare time
  (``copy_to_host_async``), so all shards' transfers overlap each other and
  storage I/O.

Read side (resharding):
- The destination layout comes from the *current* leaf's sharding (or a
  host array for ``read_object``); every persisted shard that overlaps a
  locally-addressable destination box is read once and its overlap regions
  copied out (reference groups reads the same way, io_preparer.py:317-391).
- When an overlap is a contiguous row range of the saved shard, a ranged
  read fetches only those bytes.
- ``finalize`` assembles the restored host boxes into a ``jax.Array`` via
  ``jax.make_array_from_single_device_arrays`` — one H2D per addressable
  device, no full-array host materialization.
"""

from __future__ import annotations

from concurrent.futures import Executor
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from .io_types import BufferConsumer, BufferType, ReadReq, WriteReq
from .manifest import ArrayEntry, Shard, ShardedArrayEntry
from .resharding import (
    Box,
    Overlap,
    box_overlap,
    plan_row_slab_reads,
    subdivide_box,
    target_boxes_for_sharding,
)
from .serialization import (
    Serializer,
    array_from_memoryview,
    array_size_bytes,
    dtype_to_string,
)
from .telemetry import names as metric_names
from .utils.tracing import run_in_executor, trace_annotation


# Sentinel: assembly was registered into a placement batch and will land
# via its deferred callback, not the immediate return value.
_DEFERRED = object()


def _shard_location(logical_path: str, box: Box) -> str:
    """Storage path for one shard box: ``sharded/{path}_{offsets}``
    (reference uses a ``sharded/`` prefix too, io_preparer.py:849-855)."""
    suffix = "_".join(str(o) for o in box.offsets) or "scalar"
    return f"sharded/{logical_path}_{suffix}"


class _OverlapConsumer(BufferConsumer):
    """Deserializes one saved shard (or a row range of it) and copies every
    overlap region into its destination view (reference
    ShardedTensorBufferConsumer, io_preparer.py:460-492)."""

    def __init__(
        self,
        dtype: str,
        buf_shape: Tuple[int, ...],
        copies: List[Tuple[np.ndarray, Tuple[slice, ...]]],
        dest_owned: bool = False,
    ) -> None:
        self.dtype = dtype
        self.buf_shape = buf_shape
        self.copies = copies  # (dst_view, src_slices into the read buffer)
        self.dest_owned = dest_owned

    async def consume_buffer(
        self, buf: BufferType, executor: Optional[Executor] = None
    ) -> None:
        await run_in_executor(executor, self._consume_sync, buf)

    def _consume_sync(self, buf: BufferType) -> None:
        with trace_annotation(metric_names.SPAN_LEAF_CONSUME):
            src = array_from_memoryview(buf, self.dtype, self.buf_shape)
            with trace_annotation(
                metric_names.SPAN_RESHARD_COPY,
                bytes=self.destination_nbytes(),
                buf_bytes=int(src.nbytes),
            ):
                for dst_view, src_slices in self.copies:
                    np.copyto(dst_view, src[src_slices], casting="no")

    def get_consuming_cost_bytes(self) -> int:
        return array_size_bytes(self.buf_shape, self.dtype)

    def destination_nbytes(self) -> int:
        """Bytes of destination this consumer actually fills — the
        read-amplification denominator (``bytes_needed``). Distinct
        from the consuming cost: a whole-shard read serving a partial
        destination has a buffer larger than the bytes it delivers,
        and that gap is exactly what the doctor's
        ``restore-read-amplified`` rule exists to see."""
        return sum(int(v.nbytes) for v, _ in self.copies)

    def direct_destination(self) -> Optional[memoryview]:
        # Direct read only when this is a straight whole-buffer copy into
        # one framework-owned destination view (the no-resharding fast
        # path); user-owned in-place arrays keep copy-on-success semantics.
        if not self.dest_owned:
            return None
        if len(self.copies) != 1:
            return None
        dst_view, src_slices = self.copies[0]
        if tuple(dst_view.shape) != self.buf_shape or src_slices != tuple(
            slice(0, s) for s in self.buf_shape
        ):
            return None
        from .serialization import try_writable_byte_view

        if dtype_to_string(dst_view.dtype) != self.dtype:
            return None
        return try_writable_byte_view(dst_view)


class ShardedArrayIOPreparer:
    # ------------------------------------------------------------------
    # write
    # ------------------------------------------------------------------

    @staticmethod
    def prepare_write(
        obj: Any,
        logical_path: str,
        is_async_snapshot: bool,
        array_prepare_func: Optional[Callable[..., Any]] = None,
        incremental: Optional[Any] = None,
    ) -> Tuple[ShardedArrayEntry, List[WriteReq]]:
        dtype_str = dtype_to_string(obj.dtype)
        itemsize = np.dtype(obj.dtype).itemsize
        shards: List[Shard] = []
        write_reqs: List[WriteReq] = []

        from .io_preparer import (
            ArrayBufferStager,
            effective_max_shard_size_bytes,
        )

        max_shard = effective_max_shard_size_bytes(incremental)

        for dev_shard in obj.addressable_shards:
            # Write-once election: the replica-0 copy of each box exists on
            # exactly one device globally.
            if dev_shard.replica_id != 0:
                continue
            box = Box.from_index(dev_shard.index, obj.shape)
            for piece in subdivide_box(box, max_shard, itemsize):
                if incremental is not None:
                    # Unchanged since the incremental base: reference its
                    # blob; no stager, no D2H for this piece.
                    ref = incremental.ref_entry(
                        piece.offsets, piece.sizes, False
                    )
                    if ref is not None:
                        shards.append(
                            Shard(
                                offsets=list(piece.offsets),
                                sizes=list(piece.sizes),
                                array=ref,
                            )
                        )
                        continue
                location = _shard_location(logical_path, piece)
                slc: Optional[slice] = None
                if piece != box:
                    row0 = piece.offsets[0] - box.offsets[0]
                    slc = slice(row0, row0 + piece.sizes[0])
                shards.append(
                    Shard(
                        offsets=list(piece.offsets),
                        sizes=list(piece.sizes),
                        array=ArrayEntry(
                            location=location,
                            serializer=Serializer.BUFFER_PROTOCOL.value,
                            dtype=dtype_str,
                            shape=list(piece.sizes),
                            replicated=False,
                            digest=(
                                incremental.digest_for(
                                    piece.offsets, piece.sizes
                                )
                                if incremental is not None
                                else None
                            ),
                        ),
                    )
                )
                # ArrayBufferStager prefetches D2H only for whole-shard
                # writes (slc None); subdivided pieces transfer lazily so
                # the shard-size knob's memory bound holds.
                write_reqs.append(
                    WriteReq(
                        path=location,
                        buffer_stager=ArrayBufferStager(
                            dev_shard.data,
                            is_async_snapshot,
                            slc=slc,
                            array_prepare_func=array_prepare_func,
                        ),
                    )
                )

        entry = ShardedArrayEntry(
            dtype=dtype_str,
            shape=[int(d) for d in obj.shape],
            shards=shards,
        )
        return entry, write_reqs

    # ------------------------------------------------------------------
    # read
    # ------------------------------------------------------------------

    @staticmethod
    def _sharding_destination(
        sharding: Any, shape: Tuple[int, ...], np_dtype: Any
    ) -> Tuple[
        Dict[Box, np.ndarray],
        Callable[..., Any],
        bool,
    ]:
        """Destination boxes + assembler for an arbitrary target
        ``Sharding`` over ``shape`` — the elastic core: the sharding
        need not match the one the array was saved under, nor the saved
        world size (each process only allocates/assembles the boxes its
        addressable devices cover)."""
        import jax

        groups = target_boxes_for_sharding(sharding, shape)
        boxes: Dict[Box, np.ndarray] = {
            box: np.empty(box.sizes, dtype=np_dtype) for box in groups
        }
        device_to_box: Dict[Any, Box] = {
            device: box for box, devices in groups.items() for device in devices
        }

        def assemble(
            filled: Dict[Box, np.ndarray], batch=None, on_done=None
        ) -> Any:
            # One batched H2D dispatch for all shards (a per-device
            # device_put loop pays per-call dispatch latency 8x over);
            # with a shared ``batch`` the shards ride the restore-wide
            # dispatch instead, and assembly defers until it runs.
            devices = list(device_to_box)
            per_device = [filled[device_to_box[d]] for d in devices]
            span = trace_annotation(
                metric_names.SPAN_RESHARD_ASSEMBLE,
                devices=len(devices),
                bytes=sum(int(box.nbytes) for box in per_device),
            )
            if batch is not None and on_done is not None:
                slots = [batch.put(box, d) for box, d in zip(per_device, devices)]

                def make() -> None:
                    with span:
                        on_done(
                            jax.make_array_from_single_device_arrays(
                                shape, sharding, [s.value for s in slots]
                            )
                        )

                batch.defer(make)
                return _DEFERRED
            with span:
                arrays = jax.device_put(per_device, devices)
                return jax.make_array_from_single_device_arrays(
                    shape, sharding, arrays
                )

        return boxes, assemble, True

    @staticmethod
    def _destination_boxes(
        entry: ShardedArrayEntry,
        current_leaf: Any,
        target_sharding: Optional[Any] = None,
    ) -> Tuple[
        Dict[Box, np.ndarray],
        Optional[Callable[[Dict[Box, np.ndarray]], Any]],
        bool,
    ]:
        """Host buffers to read into, keyed by destination box, plus an
        assembler back to the application's leaf flavor, plus whether the
        buffers are framework-allocated (owned) — only owned buffers may be
        direct-read targets; a user's in-place array must keep
        copy-on-success semantics so a failed restore never tears it.
        An explicit ``target_sharding`` wins over the current leaf's
        layout (restore-into-a-new-topology without a template leaf)."""
        from .serialization import string_to_dtype

        np_dtype = string_to_dtype(entry.dtype)
        shape = tuple(entry.shape)

        from .io_preparer import is_jax_array

        if target_sharding is not None:
            return ShardedArrayIOPreparer._sharding_destination(
                target_sharding, shape, np_dtype
            )

        if is_jax_array(current_leaf):
            sharding = current_leaf.sharding
            target_shape = tuple(current_leaf.shape)
            if target_shape != shape:
                raise ValueError(
                    f"Cannot reshard a saved array of shape {list(shape)} "
                    f"into a leaf of shape {list(target_shape)}"
                )

            # Uncommitted destination leaves (e.g. optax step counters
            # created by plain jnp ops) must stay uncommitted — the same
            # rule as snapshot._restore_destination: committing them to a
            # concrete device makes the restored state unusable in a jit
            # alongside differently-placed arrays. An uncommitted array is
            # single-device by construction, so it has exactly one box.
            if not getattr(current_leaf, "_committed", True):
                groups = target_boxes_for_sharding(sharding, shape)
                if len(groups) == 1:
                    boxes = {
                        box: np.empty(box.sizes, dtype=np_dtype)
                        for box in groups
                    }

                    def assemble_uncommitted(
                        filled: Dict[Box, np.ndarray], batch=None, on_done=None
                    ) -> Any:
                        import jax.numpy as jnp

                        return jnp.asarray(next(iter(filled.values())))

                    return boxes, assemble_uncommitted, True

            return ShardedArrayIOPreparer._sharding_destination(
                sharding, shape, np_dtype
            )

        # Host destination (np.ndarray in-place, or fresh allocation).
        if isinstance(current_leaf, np.ndarray):
            if tuple(current_leaf.shape) != shape or current_leaf.dtype != np_dtype:
                raise ValueError(
                    f"Destination array (shape {current_leaf.shape}, dtype "
                    f"{current_leaf.dtype}) does not match saved sharded "
                    f"array (shape {list(shape)}, dtype {entry.dtype})"
                )
            full = current_leaf
            owned = False
        else:
            full = np.empty(shape, dtype=np_dtype)
            owned = True
        full_box = Box(tuple(0 for _ in shape), shape)
        return (
            {full_box: full},
            (lambda filled, batch=None, on_done=None: filled[full_box]),
            owned,
        )

    @staticmethod
    def prepare_read_into(
        entry: ShardedArrayEntry,
        current_leaf: Any,
        restored: Dict[str, Any],
        path: str,
        buffer_size_limit_bytes: Optional[int] = None,
        dest_owned: Optional[bool] = None,
        target_sharding: Optional[Any] = None,
    ) -> Tuple[List[ReadReq], Optional[Callable[[], None]]]:
        """Build resharding reads into ``restored[path]``; the returned
        finalize callback must run after the reads complete. ``dest_owned``
        overrides the derived ownership (a caller reading into a buffer it
        allocated itself may declare it framework-owned to keep direct
        reads). ``target_sharding`` restores under an arbitrary jax
        ``Sharding`` — any layout, any world size — regardless of what
        ``current_leaf`` is (the template-free elastic entry point)."""
        with trace_annotation(
            metric_names.SPAN_RESHARD_PLAN, saved_shards=len(entry.shards)
        ) as span:
            boxes, assemble, derived_owned = (
                ShardedArrayIOPreparer._destination_boxes(
                    entry, current_leaf, target_sharding=target_sharding
                )
            )
            if dest_owned is None:
                dest_owned = derived_owned
            read_reqs: List[ReadReq] = []

            for saved in entry.shards:
                saved_box = Box(tuple(saved.offsets), tuple(saved.sizes))
                overlaps: List[Tuple[np.ndarray, Overlap]] = []
                for dst_box, dst_buf in boxes.items():
                    ov = box_overlap(saved_box, dst_box)
                    if ov is not None:
                        overlaps.append((dst_buf[ov.dst_slices], ov))
                if not overlaps:
                    continue
                read_reqs.extend(
                    ShardedArrayIOPreparer._reqs_for_saved_shard(
                        saved, saved_box, overlaps, buffer_size_limit_bytes,
                        dest_owned=dest_owned,
                    )
                )
            span.annotate(
                dest_boxes=len(boxes),
                reads=len(read_reqs),
                bytes_needed=sum(int(b.nbytes) for b in boxes.values()),
                bytes_to_read=sum(
                    r.buffer_consumer.get_consuming_cost_bytes()
                    for r in read_reqs
                ),
            )

        def finalize(batch=None) -> None:
            def on_done(arr: Any) -> None:
                restored[path] = arr

            out = assemble(boxes, batch, on_done)
            if out is not _DEFERRED:
                restored[path] = out

        return read_reqs, finalize

    @staticmethod
    def _reqs_for_saved_shard(
        saved: Shard,
        saved_box: Box,
        overlaps: List[Tuple[np.ndarray, Overlap]],
        buffer_size_limit_bytes: Optional[int] = None,
        dest_owned: bool = False,
    ) -> List[ReadReq]:
        """Reads for one saved shard feeding all its overlap regions.

        The read shrinks to the smallest row band covering every overlap
        (``resharding.plan_row_slab_reads`` — the shared geometry the
        compat bridge ranges with too) and — under a buffer size limit —
        splits into multiple ranged reads so host memory stays bounded.
        Overlaps that slice *trailing* dims still ride the row band: the
        band's bytes contain the needed columns and the consumer slices
        them out, so a partial destination never pays a whole-shard read
        just because it is column-partial (read amplification stays near
        1.0 for the dominant dim-0 resharding pattern, and at one row
        band otherwise). A band spanning the whole shard degenerates to
        the single whole-blob read it always was."""
        entry = saved.array
        shard_shape = tuple(saved_box.sizes)

        plan = None
        if shard_shape and entry.serializer == Serializer.BUFFER_PROTOCOL.value:
            plan = plan_row_slab_reads(
                shard_shape,
                [ov for _, ov in overlaps],
                row_nbytes=array_size_bytes(shard_shape[1:], entry.dtype),
                base=entry.byte_range_tuple[0] if entry.byte_range_tuple else 0,
                buffer_limit_bytes=buffer_size_limit_bytes,
            )
        if plan is not None:
            views = [dst_view for dst_view, _ in overlaps]
            return [
                ReadReq(
                    path=entry.location,
                    buffer_consumer=_OverlapConsumer(
                        entry.dtype,
                        read.buf_shape,
                        [
                            (views[c.overlap_index][c.dst_rows], c.src_slices)
                            for c in read.copies
                        ],
                        dest_owned=dest_owned,
                    ),
                    byte_range=read.byte_range,
                )
                for read in plan
            ]

        copies = [(dst_view, ov.src_slices) for dst_view, ov in overlaps]
        return [
            ReadReq(
                path=entry.location,
                buffer_consumer=_OverlapConsumer(
                    entry.dtype, shard_shape, copies, dest_owned=dest_owned
                ),
                byte_range=entry.byte_range_tuple,
            )
        ]

    @staticmethod
    def prepare_read(
        entry: ShardedArrayEntry,
        obj_out: Optional[Any],
        buffer_size_limit_bytes: Optional[int] = None,
        dest_owned: bool = False,
    ) -> List[ReadReq]:
        """Reference-shaped API: reads in place into an ``np.ndarray``.
        Callers needing jax assembly must use :meth:`prepare_read_into`
        (whose finalize callback this entry point cannot run)."""
        if not isinstance(obj_out, np.ndarray):
            raise ValueError(
                f"Reading a sharded entry through prepare_read requires an "
                f"np.ndarray destination (got {type(obj_out)}); use "
                f"prepare_read_into for jax.Array assembly"
            )
        restored: Dict[str, Any] = {}
        reqs, _ = ShardedArrayIOPreparer.prepare_read_into(
            entry,
            obj_out,
            restored,
            "__out__",
            buffer_size_limit_bytes,
            dest_owned=dest_owned or None,
        )
        return reqs

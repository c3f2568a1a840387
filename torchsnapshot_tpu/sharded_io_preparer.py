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

import threading
from concurrent.futures import Executor
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from .dest_pool import PlacedTogether
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


Slices = Tuple[slice, ...]


def _view(arr: np.ndarray, slices: Slices) -> np.ndarray:
    # A 0-d array indexed with () gives a scalar, not a view.
    return arr[slices] if slices else arr


def _contiguous_in(sizes: Sequence[int], slices: Slices) -> bool:
    """Whether ``slices`` of a C-contiguous array of shape ``sizes`` is one
    run of its bytes: full in every dimension after the first it cuts,
    one wide in every dimension before that."""
    full_so_far = True
    for size, slc in zip(reversed(sizes), reversed(slices)):
        width = slc.stop - slc.start
        if not full_so_far and width != 1:
            return False
        if width != size:
            full_so_far = False
    return True


class _LeafBoxes:
    """One sharded leaf's destination boxes as host arrays, by box.

    Made with the plan (``np.empty``, or the application's own array), or,
    ``late``, when the leaf's first read comes: the read pipeline binds a
    slab of ``dest_pool`` to each (``BufferConsumer.shared_destination``),
    and a leaf nobody bound makes its own then. Late boxes are those of a
    leaf bound for an accelerator, whose host bytes nobody sees after
    placement (``snapshot._bound_for_accelerator``)."""

    def __init__(
        self,
        np_dtype: Any,
        boxes: Iterable[Box],
        late: bool = False,
        arrays: Optional[Dict[Box, np.ndarray]] = None,
    ) -> None:
        self.dtype = np.dtype(np_dtype)
        self.sizes: Dict[Box, int] = {
            box: box.numel() * self.dtype.itemsize for box in boxes
        }
        self.late = late and all(self.sizes.values())
        # Every box is a slab a read landed in before (once bound).
        self.recycled = False
        self._arrays = arrays
        self._on_placed: Dict[Box, Callable[[Any], None]] = {}
        self._lock = threading.Lock()
        if arrays is None and not self.late:
            self._arrays = self._fresh()

    def _fresh(self) -> Dict[Box, np.ndarray]:
        return {box: np.empty(box.sizes, dtype=self.dtype) for box in self.sizes}

    def nbytes(self) -> int:
        return sum(self.sizes.values())

    def unbound_sizes(self) -> List[int]:
        return list(self.sizes.values()) if self._arrays is None else []

    def bind(
        self,
        bufs: Sequence[np.ndarray],
        on_placed: Sequence[Callable[[Any], None]],
        recycled: bool,
    ) -> None:
        self._arrays = {
            box: buf.view(self.dtype).reshape(box.sizes)
            for box, buf in zip(self.sizes, bufs)
        }
        self._on_placed = dict(zip(self.sizes, on_placed))
        self.recycled = recycled

    def arrays(self) -> Dict[Box, np.ndarray]:
        if self._arrays is None:
            # Nobody bound them; consumers of one leaf run on several
            # threads.
            with self._lock:
                if self._arrays is None:
                    self._arrays = self._fresh()
        return self._arrays

    def placed(self, box: Box, values: Sequence[Any]) -> None:
        """``values`` are on their devices from ``box``'s array: whoever
        bound it has it back once all of them are ready."""
        on_placed = self._on_placed.pop(box, None)
        if on_placed is not None:
            on_placed(values[0] if len(values) == 1 else PlacedTogether(values))


class _OverlapConsumer(BufferConsumer):
    """Deserializes one saved shard (or a row range of it) and copies every
    overlap region into its destination box (reference
    ShardedTensorBufferConsumer, io_preparer.py:460-492)."""

    def __init__(
        self,
        dtype: str,
        buf_shape: Tuple[int, ...],
        boxes: _LeafBoxes,
        copies: List[Tuple[Box, Slices, Slices]],
        dest_owned: bool = False,
    ) -> None:
        self.dtype = dtype
        self.buf_shape = buf_shape
        self.boxes = boxes
        # (box, slices of the box's array, slices of the read buffer)
        self.copies = copies
        self.dest_owned = dest_owned

    async def consume_buffer(
        self, buf: BufferType, executor: Optional[Executor] = None
    ) -> None:
        await run_in_executor(executor, self._consume_sync, buf)

    def _consume_sync(self, buf: BufferType) -> None:
        with trace_annotation(metric_names.SPAN_LEAF_CONSUME):
            src = array_from_memoryview(buf, self.dtype, self.buf_shape)
            arrays = self.boxes.arrays()
            with trace_annotation(
                metric_names.SPAN_RESHARD_COPY,
                bytes=self.destination_nbytes(),
                buf_bytes=int(src.nbytes),
            ):
                for box, dst_slices, src_slices in self.copies:
                    np.copyto(
                        _view(arrays[box], dst_slices),
                        src[src_slices],
                        casting="no",
                    )

    def get_consuming_cost_bytes(self) -> int:
        return array_size_bytes(self.buf_shape, self.dtype)

    def destination_nbytes(self) -> int:
        """Bytes of destination this consumer actually fills — the
        read-amplification denominator (``bytes_needed``). Distinct
        from the consuming cost: a whole-shard read serving a partial
        destination has a buffer larger than the bytes it delivers,
        and that gap is exactly what the doctor's
        ``restore-read-amplified`` rule exists to see."""
        return sum(
            array_size_bytes([s.stop - s.start for s in dst_slices], self.dtype)
            for _, dst_slices, _ in self.copies
        )

    def _lands_in_its_box(self) -> Optional[Tuple[Box, Slices]]:
        """Where this read is a straight whole-buffer copy into one run
        of bytes of one framework-owned box (the no-resharding fast
        path), that box and the slices of it; user-owned in-place arrays
        keep copy-on-success semantics."""
        if not self.dest_owned or len(self.copies) != 1:
            return None
        box, dst_slices, src_slices = self.copies[0]
        if tuple(
            s.stop - s.start for s in dst_slices
        ) != self.buf_shape or src_slices != tuple(
            slice(0, s) for s in self.buf_shape
        ):
            return None
        if dtype_to_string(self.boxes.dtype) != self.dtype:
            return None
        if not _contiguous_in(box.sizes, dst_slices):
            return None
        return box, dst_slices

    def direct_destination(self) -> Optional[memoryview]:
        lands = self._lands_in_its_box()
        if lands is None:
            return None
        from .serialization import try_writable_byte_view

        box, dst_slices = lands
        return try_writable_byte_view(_view(self.boxes.arrays()[box], dst_slices))

    def shared_destination(self) -> Optional[_LeafBoxes]:
        return self.boxes if self.boxes.late else None

    def read_buffer_bytes(self) -> int:
        # Only beside pooled boxes: any other read leaves the allocation
        # to the storage plugin, as ever.
        if not self.boxes.late or self._lands_in_its_box() is not None:
            return 0
        return self.get_consuming_cost_bytes()


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
        sharding: Any, shape: Tuple[int, ...], np_dtype: Any, late: bool = False
    ) -> Tuple[_LeafBoxes, Callable[..., Any], bool]:
        """Destination boxes + assembler for an arbitrary target
        ``Sharding`` over ``shape`` — the elastic core: the sharding
        need not match the one the array was saved under, nor the saved
        world size (each process only allocates/assembles the boxes its
        addressable devices cover)."""
        import jax

        groups = target_boxes_for_sharding(sharding, shape)
        boxes = _LeafBoxes(np_dtype, groups, late=late)
        device_to_box: Dict[Any, Box] = {
            device: box for box, devices in groups.items() for device in devices
        }

        def assemble(
            boxes: _LeafBoxes, batch=None, on_done=None
        ) -> Any:
            # One batched H2D dispatch for all shards (a per-device
            # device_put loop pays per-call dispatch latency 8x over);
            # with a shared ``batch`` the shards ride the restore-wide
            # dispatch instead, and assembly defers until it runs.
            filled = boxes.arrays()
            devices = list(device_to_box)
            per_device = [filled[device_to_box[d]] for d in devices]
            span = trace_annotation(
                metric_names.SPAN_RESHARD_ASSEMBLE,
                devices=len(devices),
                bytes=sum(int(box.nbytes) for box in per_device),
            )

            def placed(arrays: List[Any]) -> Any:
                # A box's memory is its binder's again when every device
                # that got it has it.
                on_device = dict(zip(devices, arrays))
                for box, its_devices in groups.items():
                    boxes.placed(box, [on_device[d] for d in its_devices])
                return jax.make_array_from_single_device_arrays(
                    shape, sharding, arrays
                )

            if batch is not None and on_done is not None:
                slots = [batch.put(box, d) for box, d in zip(per_device, devices)]

                def make() -> None:
                    with span:
                        on_done(placed([s.value for s in slots]))

                batch.defer(make)
                return _DEFERRED
            with span:
                return placed(jax.device_put(per_device, devices))

        return boxes, assemble, True

    @staticmethod
    def _destination_boxes(
        entry: ShardedArrayEntry,
        current_leaf: Any,
        target_sharding: Optional[Any] = None,
        late: bool = False,
    ) -> Tuple[_LeafBoxes, Callable[..., Any], bool]:
        """Host buffers to read into, keyed by destination box, plus an
        assembler back to the application's leaf flavor, plus whether the
        buffers are framework-allocated (owned) — only owned buffers may be
        direct-read targets; a user's in-place array must keep
        copy-on-success semantics so a failed restore never tears it.
        An explicit ``target_sharding`` wins over the current leaf's
        layout (restore-into-a-new-topology without a template leaf).
        ``late``: the boxes of a committed ``jax.Array`` leaf may wait for
        the leaf's first read (``_LeafBoxes``)."""
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
            committed = getattr(current_leaf, "_committed", True)
            if not committed:
                groups = target_boxes_for_sharding(sharding, shape)
                if len(groups) == 1:

                    def assemble_uncommitted(
                        boxes: _LeafBoxes, batch=None, on_done=None
                    ) -> Any:
                        import jax.numpy as jnp

                        return jnp.asarray(next(iter(boxes.arrays().values())))

                    return _LeafBoxes(np_dtype, groups), assemble_uncommitted, True

            return ShardedArrayIOPreparer._sharding_destination(
                sharding, shape, np_dtype, late=late and committed
            )

        # Host destination (np.ndarray in-place, or fresh allocation).
        full_box = Box(tuple(0 for _ in shape), shape)
        if isinstance(current_leaf, np.ndarray):
            if tuple(current_leaf.shape) != shape or current_leaf.dtype != np_dtype:
                raise ValueError(
                    f"Destination array (shape {current_leaf.shape}, dtype "
                    f"{current_leaf.dtype}) does not match saved sharded "
                    f"array (shape {list(shape)}, dtype {entry.dtype})"
                )
            boxes = _LeafBoxes(np_dtype, [full_box], arrays={full_box: current_leaf})
            owned = False
        else:
            boxes = _LeafBoxes(np_dtype, [full_box])
            owned = True
        return (
            boxes,
            (lambda boxes, batch=None, on_done=None: boxes.arrays()[full_box]),
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
        late: bool = False,
    ) -> Tuple[List[ReadReq], Optional[Callable[[], None]]]:
        """Build resharding reads into ``restored[path]``; the returned
        finalize callback must run after the reads complete. ``dest_owned``
        overrides the derived ownership (a caller reading into a buffer it
        allocated itself may declare it framework-owned to keep direct
        reads). ``target_sharding`` restores under an arbitrary jax
        ``Sharding`` — any layout, any world size — regardless of what
        ``current_leaf`` is (the template-free elastic entry point).
        ``late``: the caller's read pipeline may bind the host boxes of a
        committed ``jax.Array`` leaf when its first read comes
        (``dest_pool``); they are then not made here."""
        with trace_annotation(
            metric_names.SPAN_RESHARD_PLAN, saved_shards=len(entry.shards)
        ) as span:
            boxes, assemble, derived_owned = (
                ShardedArrayIOPreparer._destination_boxes(
                    entry, current_leaf, target_sharding=target_sharding, late=late
                )
            )
            if dest_owned is None:
                dest_owned = derived_owned
            read_reqs: List[ReadReq] = []

            for saved in entry.shards:
                saved_box = Box(tuple(saved.offsets), tuple(saved.sizes))
                overlaps: List[Tuple[Box, Overlap]] = []
                for dst_box in boxes.sizes:
                    ov = box_overlap(saved_box, dst_box)
                    if ov is not None:
                        overlaps.append((dst_box, ov))
                if not overlaps:
                    continue
                read_reqs.extend(
                    ShardedArrayIOPreparer._reqs_for_saved_shard(
                        saved, saved_box, boxes, overlaps,
                        buffer_size_limit_bytes, dest_owned=dest_owned,
                    )
                )
            span.annotate(
                dest_boxes=len(boxes.sizes),
                reads=len(read_reqs),
                bytes_needed=boxes.nbytes(),
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
        boxes: _LeafBoxes,
        overlaps: List[Tuple[Box, Overlap]],
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

            def rows_of(overlap_index: int, rows: slice) -> Tuple[Box, Slices]:
                # ``rows`` of the overlap's own rows, as rows of its box.
                box, ov = overlaps[overlap_index]
                row0 = ov.dst_slices[0].start
                return box, (
                    slice(row0 + rows.start, row0 + rows.stop),
                ) + ov.dst_slices[1:]

            return [
                ReadReq(
                    path=entry.location,
                    buffer_consumer=_OverlapConsumer(
                        entry.dtype,
                        read.buf_shape,
                        boxes,
                        [
                            rows_of(c.overlap_index, c.dst_rows) + (c.src_slices,)
                            for c in read.copies
                        ],
                        dest_owned=dest_owned,
                    ),
                    byte_range=read.byte_range,
                )
                for read in plan
            ]

        copies = [(box, ov.dst_slices, ov.src_slices) for box, ov in overlaps]
        return [
            ReadReq(
                path=entry.location,
                buffer_consumer=_OverlapConsumer(
                    entry.dtype, shard_shape, boxes, copies, dest_owned=dest_owned
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

"""IO preparers: turn pytree leaves into write/read requests + manifest entries.

Reference parity: torchsnapshot/io_preparer.py (the type-dispatch core).
``prepare_write`` dispatch order (reference :872-927): primitive-inline →
sharded array → dense array (chunked when larger than the chunk knob) →
opaque object pickle. ``prepare_read`` mirrors it.

TPU-native design points (vs the reference's CUDA/torch machinery):

- **Immutability replaces defensive copies.** ``jax.Array`` values never
  mutate, so async snapshots need no consistency copy of device state — the
  reference must copy CPU tensors for async takes (io_preparer.py:555-579);
  here only mutable ``np.ndarray`` leaves get that treatment.
- **Async D2H DMA replaces the thread-pool ``.to("cpu")``.** Staging calls
  ``copy_to_host_async()`` at prepare time so the TPU→host transfer overlaps
  other requests' serialization and storage I/O (the overlap the reference
  forgoes, io_preparer.py:522-526).
- **One dtype path.** Every JAX dtype (incl. bf16/fp8) is buffer-protocol
  serializable (serialization.py), so there is no ``TORCH_SAVE`` fallback for
  arrays and no quantized-tensor special case — fp8 is a first-class dtype,
  not a (scale, zero_point) codec.

The sharded-array preparer (``NamedSharding`` shards, elastic resharding)
lives in ``sharded_io_preparer.py``; it subsumes the reference's
ShardedTensorIOPreparer.
"""

from __future__ import annotations

import asyncio
import functools
import logging
import sys
from concurrent.futures import Executor
from typing import Any, Callable, List, NamedTuple, Optional, Tuple

import numpy as np

from . import knobs
from .io_types import BufferConsumer, BufferStager, BufferType, ReadReq, WriteReq
from .manifest import (
    ArrayEntry,
    ChunkedArrayEntry,
    Entry,
    ObjectEntry,
    PrimitiveEntry,
    Shard,
)
from .serialization import (
    SUPPORTED_DTYPES,
    Serializer,
    array_as_memoryview,
    array_from_memoryview,
    array_size_bytes,
    dtype_to_string,
    obj_type_name,
    pickle_load_from_bytes,
    pickle_save_as_bytes,
    string_to_dtype,
)

from .telemetry import names as metric_names
from .utils.tracing import run_in_executor, trace_annotation

logger: logging.Logger = logging.getLogger(__name__)

ArrayPrepareFunc = Callable[[Any, bool], Any]


def _jax():
    import jax

    return jax


@functools.lru_cache(maxsize=1)
def _capture_clone_jit():
    """The on-device clone of a captured leaf as one named program
    (``jit_ts_capture_clone`` on a profile's Modules line): the copy
    ``jnp.copy`` dispatches, one compile per leaf shape as before."""
    import jax
    import jax.numpy as jnp

    def ts_capture_clone(x):
        with jax.named_scope("ts_capture_clone"):
            return jnp.copy(x)

    return jax.jit(ts_capture_clone)


# Members of one clone program at the most: what a program costs to
# compile grows faster than its parameters (on a v5e 0.9 s cold at 299
# leaves, 1.3 s at 512, 2.7-3.9 s at 1,024, 29 s at 4,096; a warm
# dispatch 45 us a parameter: PERF.md section 6, PR 35), so a state of
# 10^5 small leaves is cloned by ceil(n / cap) programs and not by one.
_CLONE_GROUP_MAX = 512


@functools.lru_cache(maxsize=1)
def _capture_clone_group_jit():
    """:func:`_capture_clone_jit` over a list: every member of one
    device group cloned by one program, under the same name on a
    profile's Modules line. One dispatch takes one of the runtime's
    slots however many leaves it clones; one compile per distinct list
    of shapes."""
    import jax
    import jax.numpy as jnp

    def ts_capture_clone(xs):
        with jax.named_scope("ts_capture_clone"):
            return [jnp.copy(x) for x in xs]

    return jax.jit(ts_capture_clone)


def is_jax_array(obj: Any) -> bool:
    if "jax" not in sys.modules:
        return False
    import jax

    return isinstance(obj, jax.Array)


def is_sharded_array(obj: Any) -> bool:
    """True when ``obj`` is a jax.Array actually partitioned over devices
    (not merely replicated). Replicated multi-device arrays are dense:
    every process holds the full value."""
    if not is_jax_array(obj):
        return False
    sharding = obj.sharding
    if sharding.is_fully_replicated:
        return False
    return len(sharding.device_set) > 1 or not obj.is_fully_addressable


def get_storage_path(logical_path: str, rank: int, replicated: bool) -> str:
    """Reference parity: io_preparer.py:849-855 (sharded paths are chosen by
    the sharded preparer)."""
    if replicated:
        return f"replicated/{logical_path}"
    return f"{rank}/{logical_path}"


# ---------------------------------------------------------------------------
# Dense arrays
# ---------------------------------------------------------------------------


# Below this size a host-resident buffer is staged inline on the event
# loop instead of a ThreadPoolExecutor round-trip (GIL release buys
# nothing for a sub-millisecond memcpy; the future machinery costs more).
_INLINE_STAGE_MAX_BYTES = 1 << 20


class ArrayBufferStager(BufferStager):
    """Stages a dense array (np.ndarray or unsharded jax.Array) to a host
    byte buffer.

    For jax arrays the D2H DMA is kicked off asynchronously at construction
    (prepare time); ``stage_buffer`` then materializes the (already
    in-flight) host copy on the executor. ``slc`` selects a row range for
    chunked writes — sliced on-device so only the chunk's bytes transfer.
    """

    def __init__(
        self,
        arr: Any,
        is_async_snapshot: bool,
        slc: Optional[slice] = None,
        array_prepare_func: Optional[ArrayPrepareFunc] = None,
    ) -> None:
        self.arr = arr
        self.is_async_snapshot = is_async_snapshot
        self.slc = slc
        self.array_prepare_func = array_prepare_func
        # Whether capture() already pinned a consistent copy of a
        # mutable (numpy) source — staging must not copy it again.
        self._captured = False
        # Device-snapshot async takes skip the D2H prefetch on purpose:
        # capture() pins an ON-DEVICE clone instead, and the background
        # drain's staging pool is what bounds host memory — an eager
        # whole-state prefetch here would fill jax's host-copy cache
        # with the entire checkpoint outside the pool's accounting.
        if (
            is_jax_array(arr)
            and slc is None
            and not (is_async_snapshot and knobs.is_async_device_snapshot_enabled())
            and not self._may_device_pack()
        ):
            try:
                arr.copy_to_host_async()
            except Exception:
                pass  # prefetch is best-effort; np.asarray below still works

    def _may_device_pack(self) -> bool:
        """True when this array will likely land in a device-packed slab
        (batching + device-pack on, pack-capable dtype, below the slab
        threshold): its bytes then leave the device inside the slab's
        single packed transfer, and a per-member prefetch here would pay
        that D2H twice. (Residual: an array that ends up *alone* in its
        device group still stages individually without the prefetch —
        unknowable at prepare time, and bounded at one cold transfer per
        device.)"""
        if (
            self.array_prepare_func is not None
            or not knobs.is_batching_enabled()
            or not knobs.is_device_pack_enabled()
        ):
            return False
        from .ops.device_pack import pack_supported

        if not pack_supported(self.arr.dtype):
            return False
        return (
            self.get_staging_cost_bytes() < knobs.get_slab_size_threshold_bytes()
        )

    def jax_sources(self) -> List[Any]:
        return [self.arr] if is_jax_array(self.arr) else []

    def capture(self, cache: dict, leaf: str = "") -> None:
        """Device-snapshot capture (the deferred-staging async take's
        pre-return consistency point):

        - jax leaves get an on-device clone — dispatched asynchronously,
          so the visible cost is the dispatch, not the copy — making the
          snapshot immune to the application donating (or deleting) the
          live buffers after ``async_take`` returns;
        - mutable numpy leaves get the defensive host copy that staging
          would otherwise have made (staging now runs after control
          returned to training, too late to be a consistency point);
        - either way the copy is made once per underlying array
          (``cache``), however many chunk/shard stagers slice it.

        A jax clone that fails (e.g. a multi-process array this process
        cannot re-materialize on device) falls back to an eager HOST
        snapshot of the bytes — slower (it pays the D2H in the visible
        span, for that leaf only) but never inconsistent.

        A jax source that :func:`capture_write_reqs` already cloned
        with its device group is in ``cache`` and adopted from there;
        what follows is the path of one it could not clone that way.

        One span per distinct source (``leaf`` is the write request's
        path): ``capture:clone`` is the dispatch alone — nothing here
        waits for the device — so one that is long is the runtime
        holding the dispatch back."""
        arr = self.arr
        if arr is None:
            return
        key = id(arr)
        if key in cache:
            self.arr = cache[key]
            self._captured = True
            return
        snap = None
        if is_jax_array(arr):
            try:
                with trace_annotation(
                    metric_names.SPAN_CAPTURE_CLONE,
                    kind="device",
                    bytes=int(arr.nbytes),
                    leaf=leaf,
                ):
                    snap = _capture_clone_jit()(arr)
            except Exception as e:  # noqa: BLE001 - host fallback, never torn
                logger.warning(
                    "Device clone of a %d-byte leaf failed (%r); copying it "
                    "to the host inside the visible span instead",
                    arr.nbytes,
                    e,
                )
        if snap is None:
            kind = (
                "clone_fallback"
                if is_jax_array(arr)
                else "numpy" if isinstance(arr, np.ndarray) else "array_like"
            )
            with trace_annotation(
                metric_names.SPAN_CAPTURE_HOST_COPY,
                kind=kind,
                bytes=int(getattr(arr, "nbytes", 0)),
                leaf=leaf,
            ):
                if is_jax_array(arr):
                    # Immutable source: its host image needs no second copy.
                    snap = np.ascontiguousarray(np.asarray(arr))
                else:
                    # Exotic array-likes materialize through numpy too:
                    # the generic consistency fallback.
                    snap = np.array(np.asarray(arr), order="C", copy=True)
        cache[key] = snap
        self.arr = snap
        self._captured = True

    async def stage_buffer(self, executor: Optional[Executor] = None) -> BufferType:
        # Tiny host-resident leaves (torchrec-style 1e5-leaf manifests are
        # mostly these) aren't worth an executor hop: the future/queue
        # machinery costs ~100x the memcpy. Device arrays always go to the
        # executor — np.asarray would block the event loop on D2H — and so
        # do prepare-func stagers: the hook is arbitrary user code and may
        # return a device array or something larger than the pre-prepare
        # size gate saw (same exclusion batcher._is_batchable applies).
        if (
            self.array_prepare_func is None
            and not is_jax_array(self.arr)
            and self.get_staging_cost_bytes() <= _INLINE_STAGE_MAX_BYTES
        ):
            return self._stage_sync()
        return await run_in_executor(executor, self._stage_sync)

    def _stage_sync(self) -> BufferType:
        with trace_annotation(metric_names.SPAN_LEAF_STAGE):
            return self._stage_sync_impl()

    def _stage_sync_impl(self) -> BufferType:
        arr = self.arr
        if self.array_prepare_func is not None:
            arr = self.array_prepare_func(arr, self.is_async_snapshot)
        if self.slc is not None:
            arr = arr[self.slc]
        if is_jax_array(arr):
            # jax.Array is immutable: the host copy is consistent even for
            # async snapshots, with no defensive copy.
            with trace_annotation(
                metric_names.SPAN_STAGE_D2H, bytes=int(arr.nbytes)
            ):
                host = np.asarray(arr)
            host = np.ascontiguousarray(host)
        else:
            host = np.asarray(arr)
            if self.is_async_snapshot and not self._captured:
                # Mutable leaf: snapshot a consistent copy before returning
                # control to training (reference io_preparer.py:555-565).
                # A captured source was already copied at async_take time
                # (device-snapshot mode) and nothing mutates it now.
                host = np.array(host, order="C", copy=True)
            else:
                host = np.ascontiguousarray(host)
        # Drop the device reference promptly so HBM isn't pinned by the
        # pending storage write.
        self.arr = None
        return array_as_memoryview(host)

    def get_staging_cost_bytes(self) -> int:
        # Pure arithmetic — slicing a jax array here would run a device op
        # (and allocate HBM) just to read a shape.
        shape = tuple(self.arr.shape)
        if self.slc is not None and shape:
            shape = (len(range(*self.slc.indices(shape[0]))),) + shape[1:]
        return int(np.dtype(self.arr.dtype).itemsize * np.prod(shape, dtype=np.int64))


class ArrayBufferConsumer(BufferConsumer):
    """Deserializes bytes and copies them into a destination view.

    The destination is an ``np.ndarray`` view (possibly a narrowed slice of
    a larger restore target); the copy runs on the executor since it is
    pure-numpy and GIL-releasing for large blocks.

    ``dst`` None: the destination exists from the moment the read is
    admitted. The read pipeline binds one (``bind_destination``: a slab of
    ``dest_pool``); a consumer nobody bound makes its own.
    """

    def __init__(
        self,
        dst: Optional[np.ndarray],
        dtype: str,
        shape: Tuple[int, ...],
        dest_owned: bool = False,
    ) -> None:
        self.dst = dst
        self.dtype = dtype
        self.shape = tuple(shape)
        # Only framework-allocated destinations may be read into directly:
        # a failed direct read leaves partial bytes, which is harmless in a
        # fresh buffer but would tear a user-owned in-place array that the
        # caller might keep using after catching the restore error.
        self.dest_owned = dest_owned
        self._on_placed: Optional[Callable[[Any], None]] = None

    async def consume_buffer(
        self, buf: BufferType, executor: Optional[Executor] = None
    ) -> None:
        # Mirror of ArrayBufferStager.stage_buffer: a tiny copy is cheaper
        # than the future/queue round-trip it would ride.
        if self.get_consuming_cost_bytes() <= _INLINE_STAGE_MAX_BYTES:
            self._consume_sync(buf)
            return
        await run_in_executor(executor, self._consume_sync, buf)

    def _consume_sync(self, buf: BufferType) -> None:
        with trace_annotation(metric_names.SPAN_LEAF_CONSUME):
            src = array_from_memoryview(buf, self.dtype, self.shape)
            np.copyto(self.destination(), src, casting="no")

    def get_consuming_cost_bytes(self) -> int:
        return array_size_bytes(self.shape, self.dtype)

    def unbound_destination_bytes(self) -> int:
        return self.get_consuming_cost_bytes() if self.dst is None else 0

    def bind_destination(
        self, buf: np.ndarray, on_placed: Callable[[Any], None]
    ) -> None:
        self.dst = buf.view(string_to_dtype(self.dtype)).reshape(self.shape)
        self._on_placed = on_placed

    def destination(self) -> np.ndarray:
        if self.dst is None:
            self.dst = np.empty(self.shape, dtype=string_to_dtype(self.dtype))
        return self.dst

    def placed(self, value: Any) -> None:
        """``value`` is on its device from ``dst``: whoever bound ``dst``
        has it back once ``value`` is ready."""
        on_placed, self._on_placed = self._on_placed, None
        if on_placed is not None:
            on_placed(value)

    def direct_destination(self) -> Optional[memoryview]:
        from .serialization import try_writable_byte_view

        if not self.dest_owned:
            return None
        dst = self.destination()
        if dtype_to_string(dst.dtype) != self.dtype or tuple(
            dst.shape
        ) != self.shape:
            return None
        return try_writable_byte_view(dst)


class ArrayIOPreparer:
    """Dense-array preparer (reference TensorIOPreparer, io_preparer.py:631-782)."""

    @staticmethod
    def prepare_write(
        obj: Any,
        logical_path: str,
        rank: int,
        replicated: bool,
        is_async_snapshot: bool,
        array_prepare_func: Optional[ArrayPrepareFunc] = None,
        incremental: Optional[Any] = None,
    ) -> Tuple[Entry, List[WriteReq]]:
        location = get_storage_path(logical_path, rank, replicated)
        dtype_str = dtype_to_string(obj.dtype)
        shape = [int(d) for d in obj.shape]
        if incremental is not None:
            # Unchanged since the incremental base: reference its blob and
            # construct no stager (so no D2H prefetch fires).
            ref = incremental.ref_entry(
                tuple(0 for _ in shape), tuple(shape), replicated
            )
            if ref is not None:
                return ref, []
        entry = ArrayEntry(
            location=location,
            serializer=Serializer.BUFFER_PROTOCOL.value,
            dtype=dtype_str,
            shape=shape,
            replicated=replicated,
            digest=(
                incremental.digest_for(tuple(0 for _ in shape), tuple(shape))
                if incremental is not None
                else None
            ),
        )
        req = WriteReq(
            path=location,
            buffer_stager=ArrayBufferStager(
                obj, is_async_snapshot, array_prepare_func=array_prepare_func
            ),
        )
        return entry, [req]

    @staticmethod
    def can_load_inplace(entry: ArrayEntry, obj: Any) -> bool:
        if not isinstance(obj, np.ndarray):
            return False
        return (
            list(obj.shape) == list(entry.shape)
            and dtype_to_string(obj.dtype) == entry.dtype
            and obj.flags.writeable
        )

    @staticmethod
    def empty_array_from_entry(entry: "ArrayEntry | ChunkedArrayEntry") -> np.ndarray:
        from .serialization import string_to_dtype

        return np.empty(tuple(entry.shape), dtype=string_to_dtype(entry.dtype))

    @staticmethod
    def prepare_read(
        entry: ArrayEntry,
        arr_out: Optional[np.ndarray],
        buffer_size_limit_bytes: Optional[int] = None,
        dest_owned: bool = False,
    ) -> List[ReadReq]:
        """Build read request(s) for a dense entry into ``arr_out``, or,
        with ``arr_out`` None, one whole read whose consumer gets its
        destination when the read is admitted.

        With a buffer size limit, large entries become multiple *ranged*
        reads, each consuming directly into a flat slice of the destination
        so peak memory stays bounded (reference io_preparer.py:706-752).
        Falls back to one whole read when the destination can't be viewed
        flat (non-contiguous narrow).
        """
        if arr_out is not None and list(arr_out.shape) != list(entry.shape):
            raise ValueError(
                f"Destination shape {list(arr_out.shape)} != entry shape "
                f"{entry.shape} for {entry.location}"
            )
        total_bytes = array_size_bytes(entry.shape, entry.dtype)
        base = entry.byte_range_tuple[0] if entry.byte_range_tuple else 0

        flat: Optional[np.ndarray] = None
        if (
            arr_out is not None
            and buffer_size_limit_bytes is not None
            and total_bytes > buffer_size_limit_bytes
            and arr_out.flags.c_contiguous
        ):
            flat = arr_out.reshape(-1)

        if flat is None:
            byte_range = (
                (base, base + total_bytes) if entry.byte_range_tuple else None
            )
            return [
                ReadReq(
                    path=entry.location,
                    buffer_consumer=ArrayBufferConsumer(
                        dst=arr_out,
                        dtype=entry.dtype,
                        shape=tuple(entry.shape),
                        dest_owned=dest_owned,
                    ),
                    byte_range=byte_range,
                )
            ]

        itemsize = total_bytes // max(1, flat.size)
        elems_per_read = max(1, buffer_size_limit_bytes // itemsize)
        reqs = []
        for begin in range(0, flat.size, elems_per_read):
            end = min(begin + elems_per_read, flat.size)
            reqs.append(
                ReadReq(
                    path=entry.location,
                    buffer_consumer=ArrayBufferConsumer(
                        dst=flat[begin:end],
                        dtype=entry.dtype,
                        shape=(end - begin,),
                        dest_owned=dest_owned,
                    ),
                    byte_range=(base + begin * itemsize, base + end * itemsize),
                )
            )
        return reqs


# ---------------------------------------------------------------------------
# Chunked arrays (large dense arrays written as multiple blobs)
# ---------------------------------------------------------------------------


def chunk_shapes(
    shape: List[int], dtype: str, max_chunk_size_bytes: int
) -> List[Tuple[int, int]]:
    """Split dim 0 into ``[start, stop)`` row ranges of at most the chunk
    budget (rows larger than the budget stay whole — reference
    chunk_tensor, io_preparer.py:72-100). Delegates to the shared
    dim-0 box-splitting in parallel/overlap.py so dense chunking and
    sharded-shard subdivision cannot drift apart."""
    from .parallel.overlap import Box, subdivide_box
    from .serialization import string_to_dtype

    if not shape or shape[0] <= 1:
        return [(0, shape[0] if shape else 0)]
    pieces = subdivide_box(
        Box(tuple(0 for _ in shape), tuple(shape)),
        max_chunk_size_bytes,
        string_to_dtype(dtype).itemsize,
    )
    return [(p.offsets[0], p.offsets[0] + p.sizes[0]) for p in pieces]


def effective_max_chunk_size_bytes(incremental: Optional[Any]) -> int:
    """Digest-enabled takes chunk tighter (the incremental-chunk knob) so
    the skip unit is fine enough for sparse updates; plain takes use the
    chunk knob alone. Applied identically on every step of a base chain,
    keeping chunk boundaries (the digest keys) stable."""
    size = knobs.get_max_chunk_size_bytes()
    if incremental is not None:
        size = min(size, knobs.get_incremental_chunk_size_bytes())
    return size


def effective_max_shard_size_bytes(incremental: Optional[Any]) -> int:
    """Shard-piece analog of :func:`effective_max_chunk_size_bytes`."""
    size = knobs.get_max_shard_size_bytes()
    if incremental is not None:
        size = min(size, knobs.get_incremental_chunk_size_bytes())
    return size


class ChunkedArrayIOPreparer:
    """Reference parity: ChunkedTensorIOPreparer (io_preparer.py:71-164)."""

    @staticmethod
    def should_chunk(obj: Any, incremental: Optional[Any] = None) -> bool:
        nbytes = int(
            np.dtype(obj.dtype).itemsize * np.prod(obj.shape, dtype=np.int64)
        )
        return (
            nbytes > effective_max_chunk_size_bytes(incremental)
            and len(obj.shape) >= 1
            and int(obj.shape[0]) > 1
        )

    @staticmethod
    def prepare_write(
        obj: Any,
        logical_path: str,
        rank: int,
        replicated: bool,
        is_async_snapshot: bool,
        array_prepare_func: Optional[ArrayPrepareFunc] = None,
        incremental: Optional[Any] = None,
    ) -> Tuple[ChunkedArrayEntry, List[WriteReq]]:
        location = get_storage_path(logical_path, rank, replicated)
        dtype_str = dtype_to_string(obj.dtype)
        shape = [int(d) for d in obj.shape]
        chunks: List[Shard] = []
        write_reqs: List[WriteReq] = []
        for start, stop in chunk_shapes(
            shape, dtype_str, effective_max_chunk_size_bytes(incremental)
        ):
            chunk_location = f"{location}_{start}"
            chunk_shape = [stop - start] + shape[1:]
            offsets = [start] + [0] * (len(shape) - 1)
            if incremental is not None:
                ref = incremental.ref_entry(offsets, chunk_shape, replicated)
                if ref is not None:
                    chunks.append(
                        Shard(offsets=offsets, sizes=chunk_shape, array=ref)
                    )
                    continue
            chunks.append(
                Shard(
                    offsets=offsets,
                    sizes=chunk_shape,
                    array=ArrayEntry(
                        location=chunk_location,
                        serializer=Serializer.BUFFER_PROTOCOL.value,
                        dtype=dtype_str,
                        shape=chunk_shape,
                        replicated=replicated,
                        digest=(
                            incremental.digest_for(offsets, chunk_shape)
                            if incremental is not None
                            else None
                        ),
                    ),
                )
            )
            write_reqs.append(
                WriteReq(
                    path=chunk_location,
                    buffer_stager=ArrayBufferStager(
                        obj,
                        is_async_snapshot,
                        slc=slice(start, stop),
                        array_prepare_func=array_prepare_func,
                    ),
                )
            )
        entry = ChunkedArrayEntry(
            dtype=dtype_str, shape=shape, chunks=chunks, replicated=replicated
        )
        return entry, write_reqs

    @staticmethod
    def prepare_read(
        entry: ChunkedArrayEntry,
        arr_out: np.ndarray,
        buffer_size_limit_bytes: Optional[int] = None,
        dest_owned: bool = False,
    ) -> List[ReadReq]:
        reqs: List[ReadReq] = []
        for chunk in entry.chunks:
            view = arr_out[
                tuple(
                    slice(o, o + s) for o, s in zip(chunk.offsets, chunk.sizes)
                )
            ]
            reqs.extend(
                ArrayIOPreparer.prepare_read(
                    chunk.array, view, buffer_size_limit_bytes, dest_owned
                )
            )
        return reqs


# ---------------------------------------------------------------------------
# Opaque objects
# ---------------------------------------------------------------------------


class ObjectBufferStager(BufferStager):
    def __init__(self, obj: Any) -> None:
        self.obj = obj
        self._buf: Optional[bytes] = None

    def capture(self, cache: dict, leaf: str = "") -> None:
        """Objects are snapshotted by pickling them NOW: deferred
        staging would otherwise serialize a mutable object (a metrics
        dict, a dataloader state) after training resumed mutating it.
        Objects are metadata-sized in practice; the pickle cost sits in
        the visible span by design — consistency over latency here. An
        object that holds a device array waits for the device here."""
        if self._buf is None:
            with trace_annotation(
                metric_names.SPAN_CAPTURE_OBJECT,
                kind=type(self.obj).__name__,
                leaf=leaf,
            ) as span:
                self._buf = pickle_save_as_bytes(self.obj)
                span.annotate(bytes=len(self._buf))
            self.obj = None

    async def stage_buffer(self, executor: Optional[Executor] = None) -> BufferType:
        if self._buf is not None:
            return self._buf
        return await run_in_executor(executor, pickle_save_as_bytes, self.obj)

    def get_staging_cost_bytes(self) -> int:
        if self._buf is not None:
            return len(self._buf)
        return sys.getsizeof(self.obj)


class ObjectBufferConsumer(BufferConsumer):
    """Objects can't be filled in place; the deserialized value is routed to
    a callback (the reference's "box" pattern, snapshot.py:582-591)."""

    def __init__(self, callback: Callable[[Any], None], size_hint: int = 1024) -> None:
        self.callback = callback
        self.size_hint = size_hint

    async def consume_buffer(
        self, buf: BufferType, executor: Optional[Executor] = None
    ) -> None:
        obj = await run_in_executor(
            executor, pickle_load_from_bytes, bytes(buf)
        )
        self.callback(obj)

    def get_consuming_cost_bytes(self) -> int:
        return self.size_hint


class ObjectIOPreparer:
    @staticmethod
    def prepare_write(
        obj: Any,
        logical_path: str,
        rank: int,
        replicated: bool,
    ) -> Tuple[ObjectEntry, List[WriteReq]]:
        location = get_storage_path(logical_path, rank, replicated)
        entry = ObjectEntry(
            location=location,
            serializer=Serializer.PICKLE.value,
            obj_type=obj_type_name(obj),
            replicated=replicated,
        )
        return entry, [WriteReq(path=location, buffer_stager=ObjectBufferStager(obj))]

    @staticmethod
    def prepare_read(
        entry: ObjectEntry, callback: Callable[[Any], None]
    ) -> List[ReadReq]:
        return [
            ReadReq(
                path=entry.location,
                buffer_consumer=ObjectBufferConsumer(callback),
            )
        ]


# ---------------------------------------------------------------------------
# Primitives
# ---------------------------------------------------------------------------


class PrimitivePreparer:
    """Inline-able builtins (reference io_preparer.py:858-869). Note
    ``bool`` resolves before ``int`` because ``PrimitiveEntry.from_object``
    dispatches on the exact type name."""

    @staticmethod
    def should_inline(obj: Any) -> bool:
        return type(obj) in (int, float, str, bool, bytes)

    @staticmethod
    def prepare_write(obj: Any, replicated: bool) -> PrimitiveEntry:
        return PrimitiveEntry.from_object(obj, replicated=replicated)


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------


def _is_dense_array(obj: Any) -> bool:
    if is_jax_array(obj):
        return not is_sharded_array(obj)
    return isinstance(obj, np.ndarray) and obj.dtype in SUPPORTED_DTYPES


def prepare_write(
    obj: Any,
    logical_path: str,
    rank: int,
    replicated: bool = False,
    is_async_snapshot: bool = False,
    array_prepare_func: Optional[ArrayPrepareFunc] = None,
    incremental: Optional[Any] = None,
) -> Tuple[Entry, List[WriteReq]]:
    """Reference parity: io_preparer.py:872-927 (dispatch order preserved).

    ``incremental`` is a per-leaf :class:`incremental.LeafIncrementalPlan`
    consulted chunk-by-chunk: unchanged chunks become base-referencing
    entries with no write request (and no stager, hence no D2H)."""
    if PrimitivePreparer.should_inline(obj):
        return PrimitivePreparer.prepare_write(obj, replicated), []
    if is_sharded_array(obj):
        from .sharded_io_preparer import ShardedArrayIOPreparer

        return ShardedArrayIOPreparer.prepare_write(
            obj, logical_path, is_async_snapshot, array_prepare_func,
            incremental=incremental,
        )
    if _is_dense_array(obj):
        if ChunkedArrayIOPreparer.should_chunk(obj, incremental=incremental):
            return ChunkedArrayIOPreparer.prepare_write(
                obj, logical_path, rank, replicated, is_async_snapshot,
                array_prepare_func, incremental=incremental,
            )
        return ArrayIOPreparer.prepare_write(
            obj, logical_path, rank, replicated, is_async_snapshot,
            array_prepare_func, incremental=incremental,
        )
    return ObjectIOPreparer.prepare_write(obj, logical_path, rank, replicated)


class CapturedSources(NamedTuple):
    """What :func:`capture_write_reqs` pinned: the number of distinct
    sources, the on-device clones among them (not ready yet: the drain
    waits for them, ``scheduler.DeferredIOWork``), and how the jax
    sources were cloned: by how many group programs, how many leaves in
    those, and how many leaves one by one."""

    sources: int
    device_clones: List[Any]
    clone_programs: int
    clone_leaves: int
    fallback_leaves: int

    @property
    def device_programs(self) -> int:
        """Programs the device clones come from: a clone that no group
        program made is one of its own."""
        return self.clone_programs + len(self.device_clones) - self.clone_leaves


def _clone_device_groups(
    sources: List[Any], cache: dict, group_max: int
) -> Tuple[int, int]:
    """Clone a take's jax sources on the device, one program a device
    group (and a ``group_max`` members), into ``cache``. The runtime
    makes a dispatch wait for one of its slots, which a training loop
    keeps full of steps: one dispatch a group waits for one step where
    one a leaf waits for as many steps as there are slots. A group
    whose program raises at dispatch (no room for all its outputs at
    once, a member this process cannot address) leaves the cache as it
    was, and its members to ``capture``'s path for one leaf. Returns the
    programs dispatched and the leaves they clone."""
    from .ops.device_pack import device_group_key

    groups: dict = {}
    for arr in sources:
        groups.setdefault(device_group_key(arr), []).append(arr)
    programs = leaves = 0
    for group in groups.values():
        # A program is compiled once a list of shapes. Equal shapes side
        # by side make the programs of a group larger than the cap alike
        # (a state of 10^5 leaves has a few dozen shapes), and make a
        # plan's program the same whatever order its leaves came in.
        group.sort(key=lambda arr: (str(arr.dtype), arr.shape))
        for i in range(0, len(group), group_max):
            members = group[i : i + group_max]
            nbytes = sum(int(arr.nbytes) for arr in members)
            try:
                with trace_annotation(
                    metric_names.SPAN_CAPTURE_CLONE,
                    kind="device",
                    bytes=nbytes,
                    leaves=len(members),
                ):
                    clones = _capture_clone_group_jit()(members)
            except Exception as e:  # noqa: BLE001 - leaf by leaf instead
                logger.warning(
                    "Device clone of %d leaves (%d bytes) as one program "
                    "failed (%r); cloning them one by one",
                    len(members),
                    nbytes,
                    e,
                )
                continue
            cache.update((id(arr), c) for arr, c in zip(members, clones))
            programs += 1
            leaves += len(members)
    return programs, leaves


def capture_write_reqs(
    write_reqs: List[WriteReq], queue_drained: bool = False
) -> CapturedSources:
    """Device-snapshot capture pass over a take's write plan: every
    stager pins a consistent copy of its source (``BufferStager.capture``
    — on-device clones for jax leaves, host copies for mutable numpy
    leaves, eager pickles for objects) so ``async_take`` may return
    before any staging ran. One shared cache keyed by the source
    object: a leaf sliced into many chunk/shard stagers is snapshotted
    once. The jax sources are cloned first, together
    (:func:`_clone_device_groups`), so each stager finds its clone in
    the cache.

    ``queue_drained``: the take's plan has just waited for the device
    (it recorded digests, ``IncrementalTakeContext.waited_for_device``).
    The runtime's slots are then free and a dispatch costs the host's
    work alone, while the set of leaves such a take writes is what its
    skip decisions leave, another in every save: a program over all of
    them would be compiled anew nearly every time. Each source is then a
    program of its own, compiled once a shape."""
    cache: dict = {}
    # A leaf sliced into chunk or shard stagers, or held by a slab's
    # member, is one source. The dict keeps every source alive, and so
    # its ``id`` its own, until the pass is over.
    jax_sources = {
        id(arr): arr
        for req in write_reqs
        for arr in req.buffer_stager.jax_sources()
    }
    programs, leaves = _clone_device_groups(
        list(jax_sources.values()),
        cache,
        group_max=1 if queue_drained else _CLONE_GROUP_MAX,
    )
    for req in write_reqs:
        req.buffer_stager.capture(cache, leaf=req.path)
    return CapturedSources(
        sources=len(cache),
        device_clones=[v for v in cache.values() if is_jax_array(v)],
        clone_programs=programs,
        clone_leaves=leaves,
        fallback_leaves=len(jax_sources) - leaves,
    )


def prepare_read(
    entry: Entry,
    obj_out: Optional[Any] = None,
    buffer_size_limit_bytes: Optional[int] = None,
    callback: Optional[Callable[[Any], None]] = None,
    dest_owned: bool = False,
) -> List[ReadReq]:
    """Reference parity: io_preparer.py:930-966.

    Dense/chunked entries require an ``np.ndarray`` destination (callers
    allocate via :meth:`ArrayIOPreparer.empty_array_from_entry`), but an
    owned dense entry may pass none: its consumer then gets one when the
    read is admitted. Object entries require a ``callback``; primitives
    produce no reads.
    ``dest_owned`` declares the destination framework-allocated, enabling
    direct (zero-copy) storage reads into it; destinations owned by the
    application must keep copy-on-success semantics.
    """
    if isinstance(entry, PrimitiveEntry):
        return []
    if isinstance(entry, ArrayEntry):
        if not isinstance(obj_out, np.ndarray) and not (
            obj_out is None and dest_owned
        ):
            raise ValueError(
                f"Reading {entry.location} requires an np.ndarray destination "
                f"(got {type(obj_out)})"
            )
        return ArrayIOPreparer.prepare_read(
            entry, obj_out, buffer_size_limit_bytes, dest_owned
        )
    if isinstance(entry, ChunkedArrayEntry):
        if not isinstance(obj_out, np.ndarray):
            raise ValueError(
                f"Reading a chunked entry requires an np.ndarray destination "
                f"(got {type(obj_out)})"
            )
        return ChunkedArrayIOPreparer.prepare_read(
            entry, obj_out, buffer_size_limit_bytes, dest_owned
        )
    if isinstance(entry, ObjectEntry):
        if callback is None:
            raise ValueError("Reading an object entry requires a callback")
        return ObjectIOPreparer.prepare_read(entry, callback)
    from .manifest import ShardedArrayEntry

    if isinstance(entry, ShardedArrayEntry):
        from .sharded_io_preparer import ShardedArrayIOPreparer

        return ShardedArrayIOPreparer.prepare_read(
            entry, obj_out, buffer_size_limit_bytes, dest_owned
        )
    raise TypeError(f"prepare_read does not handle entry type {type(entry)}")

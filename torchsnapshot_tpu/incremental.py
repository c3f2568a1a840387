"""Incremental takes: skip unchanged chunks using on-device digests.

No counterpart exists in the reference — its every take rewrites all
bytes. On TPU the dominant cost of a checkpoint is the device→host copy
followed by storage writes; for real training states much of that traffic
is redundant (embedding tables with sparse updates, frozen towers, EMA
copies, optimizer moments of frozen params). This module detects
unchanged chunks *on device* — a jitted 64-bit digest per chunk
(ops/device_digest.py), so only 8 bytes cross the link per unchanged
chunk — and rewrites neither their D2H nor their storage bytes. The new
manifest instead carries entries whose ``location`` points into the base
snapshot (``../step_.../...``), which every storage plugin resolves
lexically.

Granularity is exactly the write granularity the preparers already use
(whole dense arrays, dim-0 chunks of large dense arrays, replica-0
subdivided shard boxes of sharded arrays), so a skipped chunk references
a blob whose bytes are byte-identical to what a full take would have
written. Digest equality is probabilistic (~2^-64 false-skip per chunk
comparison — far below memory error rates); restore-side CRC
verification (integrity.py) is unaffected because the referenced blob's
checksum entries are inherited into the new snapshot's table.

Interplay with the rest of the take pipeline:

- The skip decision happens *before* anything is staged, so no D2H
  fires for skipped chunks.
- Digest computations for every leaf are launched in one pass before any
  comparison blocks (JAX async dispatch pipelines them); the comparison
  pass then materializes results.
- Where an ``async_take`` on one process has room on its devices for a
  clone of its jax sources, the decisions run behind the return
  (``behind_return``): the caller plans every chunk written, the capture
  pass clones every source, and the commit thread digests the clones,
  waits for them and drops the chunks (and the clones) the base already
  holds (:meth:`IncrementalTakeContext.decide`). Otherwise the caller
  waits for the digests itself (docs/incremental.md).
- Replicated entries skip identically on every rank (digests are
  functions of bytes only), so partitioning and replicated-entry
  consolidation see consistent manifests.
- If chunking/shard knobs or shardings changed between steps, chunk keys
  (offsets, sizes) stop matching and the affected leaves are simply
  rewritten in full — never incorrect, just not incremental.
"""

from __future__ import annotations

import logging
import os
import posixpath
from typing import Any, Callable, Dict, List, Optional, Set, Tuple

import numpy as np

from . import knobs
from .manifest import (
    ArrayEntry,
    ChunkedArrayEntry,
    Entry,
    Manifest,
    ShardedArrayEntry,
    get_manifest_for_rank,
)
from .ops import device_digest as dd
from .serialization import Serializer, dtype_to_string
from .telemetry import names as metric_names
from .utils.tracing import trace_annotation

logger: logging.Logger = logging.getLogger(__name__)

ChunkKey = Tuple[Tuple[int, ...], Tuple[int, ...]]  # (offsets, sizes)


# Schemes whose plugins resolve parent-relative (``../``) locations: the
# filesystem natively, s3/gs by lexical key normalization. ``memory://``
# stores are flat per-name dicts with no cross-snapshot namespace, and
# unknown entry-point schemes can't be assumed to normalize — refs to
# either would take fine and then fail to restore.
_REF_CAPABLE_SCHEMES = ("fs", "s3", "gs")


def relative_ref_prefix(new_path: str, base_path: str) -> Optional[str]:
    """Relative prefix from the new snapshot root to the base snapshot
    root, or None when no resolvable lexical relation exists (different
    storage scheme, different s3/gs bucket, or a scheme whose plugin
    can't resolve parent refs). ``../step_0000000005``-style prefixes
    compose with base locations via ``posixpath.normpath``; chained refs
    collapse to the originating snapshot."""
    from .storage_plugin import _parse_url

    new_scheme, new_root = _parse_url(new_path)
    base_scheme, base_root = _parse_url(base_path)
    if new_scheme != base_scheme or new_scheme not in _REF_CAPABLE_SCHEMES:
        return None
    new_root = new_root.rstrip("/")
    base_root = base_root.rstrip("/")
    if not new_root or not base_root:
        return None
    if new_scheme == "fs":
        # relpath between a relative and an absolute fs path resolves
        # through the process cwd at *take* time; the resulting ref would
        # not survive a restore from a different cwd. Anchor both.
        new_root = os.path.abspath(new_root)
        base_root = os.path.abspath(base_root)
    if new_root == base_root:
        return None
    if new_scheme in ("s3", "gs"):
        # Object keys resolve lexically within one bucket only: a ref
        # must never climb past it.
        if new_root.split("/", 1)[0] != base_root.split("/", 1)[0]:
            return None
    rel = posixpath.relpath(base_root, new_root)
    if rel.startswith(("/", "./")) or rel == ".":
        return None
    return rel


class LeafIncrementalPlan:
    """Digest-comparison results for one leaf, consumed by the array
    preparers chunk-by-chunk: ``ref_entry`` returns a base-referencing
    entry for an unchanged chunk (the preparer then constructs no
    stager), ``digest_for`` the digest to record on a written chunk."""

    def __init__(
        self,
        refs: Dict[ChunkKey, Tuple[ArrayEntry, str]],
        digests: Dict[ChunkKey, str],
        on_ref_used: Callable[[str, str], None],
        on_decision: Callable[[bool, Tuple[int, ...]], None],
    ) -> None:
        # refs: chunk key -> (ref entry template, base-manifest location)
        self._refs = refs
        self._digests = digests
        self._on_ref_used = on_ref_used
        # (referenced, the chunk's sizes): a preparer asks ``ref_entry``
        # of every chunk and ``digest_for`` of those it goes on to write.
        self._on_decision = on_decision

    def ref_entry(
        self,
        offsets: Tuple[int, ...] | List[int],
        sizes: Tuple[int, ...] | List[int],
        replicated: bool,
    ) -> Optional[ArrayEntry]:
        hit = self._refs.get((tuple(offsets), tuple(sizes)))
        if hit is None:
            return None
        template, base_location = hit
        clone = ArrayEntry(
            location=template.location,
            serializer=template.serializer,
            dtype=template.dtype,
            shape=list(template.shape),
            replicated=replicated,
            byte_range=template.byte_range,
            digest=template.digest,
        )
        self._on_ref_used(clone.location, base_location)
        self._on_decision(True, tuple(sizes))
        return clone

    def digest_for(
        self,
        offsets: Tuple[int, ...] | List[int],
        sizes: Tuple[int, ...] | List[int],
    ) -> Optional[str]:
        self._on_decision(False, tuple(sizes))
        return self._digests.get((tuple(offsets), tuple(sizes)))


class _DigestBatch:
    """Digest work for one device group, dispatched as a single fused
    program (device_digest.digest_many_async): per-chunk dispatch
    round-trips dominate digest cost on real accelerators, so a take
    issues O(device groups) dispatches, not O(chunks)."""

    def __init__(self) -> None:
        self.specs: List[Tuple[Any, Optional[Tuple[Tuple[int, int], ...]]]] = []
        # Each spec's leaf and chunk keys, for a batch remade over the
        # capture's clones (IncrementalTakeContext.adopt_clones).
        self.members: List[Tuple[str, List[ChunkKey]]] = []
        # Output-row mapping: one (logical_path, chunk_key) per digest row.
        self.rows: List[Tuple[str, ChunkKey]] = []
        # Bytes of the rows' chunks: what the program reads.
        self.nbytes = 0

    def add(
        self,
        arr: Any,
        ranges: Optional[Tuple[Tuple[int, int], ...]],
        logical_path: str,
        keys: List[ChunkKey],
    ) -> None:
        self.specs.append((arr, ranges))
        self.members.append((logical_path, keys))
        self.rows.extend((logical_path, k) for k in keys)
        self.nbytes += sum(_chunk_nbytes(sizes, arr.dtype) for _, sizes in keys)


def _chunk_nbytes(sizes: Tuple[int, ...], dtype: Any) -> int:
    return int(np.prod(sizes, dtype=np.int64)) * np.dtype(dtype).itemsize


def _base_chunk_map(entry: Entry) -> Dict[ChunkKey, ArrayEntry]:
    """Every (offsets, sizes) box the base snapshot holds bytes for, with
    its dense entry — uniform across the three array flavors, so a leaf
    may change flavor between steps (dense → sharded, resharded meshes)
    and still match boxes that survived identically."""
    out: Dict[ChunkKey, ArrayEntry] = {}
    if isinstance(entry, ArrayEntry):
        shape = tuple(entry.shape)
        out[(tuple(0 for _ in shape), shape)] = entry
    elif isinstance(entry, ChunkedArrayEntry):
        for chunk in entry.chunks:
            out[(tuple(chunk.offsets), tuple(chunk.sizes))] = chunk.array
    elif isinstance(entry, ShardedArrayEntry):
        for shard in entry.shards:
            out[(tuple(shard.offsets), tuple(shard.sizes))] = shard.array
    return out


def _ignore(*args: Any) -> None:
    pass


# The plan of a leaf prepared with every chunk written and no digest yet,
# in an incremental take's chunk layout (``write_all_plan``).
_WRITE_ALL = LeafIncrementalPlan({}, {}, _ignore, _ignore)


def _decide_leaf(
    entry: Entry, reqs: List[Any], plan: LeafIncrementalPlan
) -> Tuple[Entry, List[Any]]:
    """What the preparers do with a leaf's plan chunk by chunk, applied to
    the entry and the write requests they made of it with every chunk
    written (``write_all_plan``): one request a chunk, in the entry's
    order, for a dense, a chunked and a sharded entry alike. A chunk the
    base holds becomes its reference and loses its request; a written
    one gets its digest."""
    if isinstance(entry, ArrayEntry):
        zeros = tuple(0 for _ in entry.shape)
        shape = tuple(entry.shape)
        ref = plan.ref_entry(zeros, shape, entry.replicated)
        if ref is not None:
            return ref, []
        entry.digest = plan.digest_for(zeros, shape)
        return entry, reqs
    if isinstance(entry, ChunkedArrayEntry):
        chunks, replicated = entry.chunks, entry.replicated
    else:
        chunks, replicated = entry.shards, False
    kept = []
    for chunk, req in zip(chunks, reqs):
        ref = plan.ref_entry(chunk.offsets, chunk.sizes, replicated)
        if ref is None:
            chunk.array.digest = plan.digest_for(chunk.offsets, chunk.sizes)
            kept.append(req)
        else:
            chunk.array = ref
    return entry, kept


# What a device must have free beyond the clone of a take's jax sources
# for the take to decide behind its return: a sixteenth of the clone, and
# 64 MiB at the least, for what the runtime allocates between the probe
# and the clone's dispatch and for the allocator's fragmentation.
_ROOM_MARGIN_SHARE = 16
_ROOM_MARGIN_MIN_BYTES = 64 << 20


def clone_bytes_by_device(flattened: Dict[str, Any]) -> Dict[Any, int]:
    """The bytes the capture pass clones on each device: every
    addressable copy of a dense jax leaf, the replica-0 shards of a
    sharded one (the sources of ``prepare_write``'s stagers)."""
    from .io_preparer import is_jax_array, is_sharded_array

    need: Dict[Any, int] = {}
    for leaf in flattened.values():
        if not is_jax_array(leaf):
            continue
        sharded = is_sharded_array(leaf)
        for shard in leaf.addressable_shards:
            if not sharded or shard.replica_id == 0:
                need[shard.device] = need.get(shard.device, 0) + int(
                    shard.data.nbytes
                )
    return need


def room_margin(nbytes: int) -> int:
    """What a device must keep free beside a clone of ``nbytes``."""
    return max(nbytes // _ROOM_MARGIN_SHARE, _ROOM_MARGIN_MIN_BYTES)


def memory_stats(device: Any) -> Optional[Dict[str, int]]:
    """``device.memory_stats()``: the one place the capture's account of
    room reads it."""
    return device.memory_stats()


def room_for_clone(flattened: Dict[str, Any]) -> bool:
    """Whether every device's ``memory_stats()`` shows room for the clone
    of the take's jax sources: ``bytes_limit - bytes_in_use`` at least its
    bytes there and the margin. A device that keeps no such account (the
    CPU backend) sets no limit this can see."""
    for device, nbytes in clone_bytes_by_device(flattened).items():
        stats = memory_stats(device)
        if not stats or "bytes_limit" not in stats:
            continue
        if stats["bytes_limit"] - stats.get("bytes_in_use", 0) < nbytes + room_margin(nbytes):
            return False
    return True


class IncrementalTakeContext:
    """Take-scoped digest state: launched futures, the base snapshot's
    chunk map, and the refs actually used (for checksum inheritance and
    the manager's retention bookkeeping)."""

    def __init__(
        self,
        base_available: Optional[Manifest],
        ref_prefix: Optional[str],
        base_path: Optional[str],
        base_world_size: int,
    ) -> None:
        self._base_available = base_available or {}
        self._ref_prefix = ref_prefix
        self._base_path = base_path
        self._base_world_size = base_world_size
        # logical_path -> ordered chunk keys (the leaf's digest layout);
        # presence of a path means its digests were (or are being)
        # computed — the analog of a "launch" having happened.
        self._layouts: Dict[str, List[ChunkKey]] = {}
        # (logical_path, chunk_key) -> (d1, d2); host digests land here at
        # launch, device digests at first plan_for (materialization).
        self._results: Dict[Tuple[str, ChunkKey], Tuple[int, int]] = {}
        # In-flight device groups: (future, output-row mapping, bytes of
        # the rows' chunks).
        self._group_futs: List[
            Tuple[Any, List[Tuple[str, ChunkKey]], int]
        ] = []
        self._materialized = False
        # Whether this take's plan blocked on a digest program: the
        # device has then run everything queued before it, and the
        # capture pass finds the runtime's queue empty.
        self.waited_for_device = False
        # Bytes of the leaves digested on the host at launch.
        self._host_bytes = 0
        # What the preparers decided chunk by chunk, on this rank and
        # before partitioning: a chunk of a digested leaf is referenced
        # into the base or written (docs/incremental.md).
        self.decisions: Dict[str, int] = {
            "chunks_referenced": 0,
            "bytes_referenced": 0,
            "chunks_written": 0,
            "bytes_written": 0,
        }
        # The dtype of each collected leaf, and which of them are sharded:
        # what the decisions read of a leaf, so that no array is kept.
        self._dtypes: Dict[str, Any] = {}
        self._sharded: Set[str] = set()
        self._replicated_paths: Set[str] = set()
        # new (normalized) ref location -> base-manifest location
        self.used_refs: Dict[str, str] = {}
        # Whether the skip decisions run on the commit thread (collect,
        # hold, adopt_clones, then digest_clones and decide there), and what
        # the caller hands it: device digests noted and not dispatched,
        # the rank's manifest and each leaf's write requests, planned
        # with every chunk written, and a stager a jax source.
        self.behind_return = False
        self._batches: Dict[Tuple[int, ...], _DigestBatch] = {}
        self._manifest: Dict[str, Entry] = {}
        self._planned: Dict[str, List[Any]] = {}
        self._holders: Dict[int, Any] = {}

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------

    @classmethod
    def build(
        cls,
        path: str,
        incremental_base: Optional[Any],
        rank: int,
    ) -> "IncrementalTakeContext":
        """``incremental_base`` is a snapshot path or Snapshot; None (or a
        base whose location can't be referenced relatively) yields a
        digest-record-only context — the take writes everything but its
        manifest can serve as a base for the next one."""
        with trace_annotation(metric_names.SPAN_INCREMENTAL_BASE) as span:
            ctx = cls._build(path, incremental_base, rank)
            span.annotate(
                entries=len(ctx._base_available),
                usable=int(ctx._ref_prefix is not None),
            )
        return ctx

    @classmethod
    def _build(
        cls,
        path: str,
        incremental_base: Optional[Any],
        rank: int,
    ) -> "IncrementalTakeContext":
        if incremental_base is None:
            return cls(None, None, None, 0)
        from .snapshot import Snapshot

        base = (
            incremental_base
            if isinstance(incremental_base, Snapshot)
            else Snapshot(str(incremental_base))
        )
        try:
            metadata = base.metadata
        except Exception as e:  # noqa: BLE001 - base gone: full take
            logger.warning(
                "Incremental base %s unreadable (%r); taking a full snapshot",
                base.path,
                e,
            )
            return cls(None, None, None, 0)
        ref_prefix = relative_ref_prefix(path, base.path)
        if ref_prefix is None:
            logger.warning(
                "Incremental base %s is not relatively addressable from %s; "
                "taking a full snapshot (digests still recorded)",
                base.path,
                path,
            )
            return cls(None, None, None, 0)
        return cls(
            get_manifest_for_rank(metadata, rank),
            ref_prefix,
            base.path,
            metadata.world_size,
        )

    # ------------------------------------------------------------------
    # pass 1: launch digests
    # ------------------------------------------------------------------

    def launch(
        self,
        flattened: Dict[str, Any],
        array_prepare_func: Optional[Callable[..., Any]],
    ) -> None:
        """Kick off digest computation for every eligible array leaf.
        Device digests dispatch asynchronously; host digests compute
        inline. Must run before any stager construction so skip decisions
        precede D2H prefetches."""
        if array_prepare_func is not None:
            # Written bytes are a function of the hook, not the leaf;
            # digests of the leaf would lie.
            return
        with trace_annotation(metric_names.SPAN_INCREMENTAL_DIGEST_LAUNCH) as span:
            self._dispatch(self._collect(flattened))
            self._annotate_launch(span)

    def collect(
        self,
        flattened: Dict[str, Any],
        array_prepare_func: Optional[Callable[..., Any]],
    ) -> None:
        """The caller's pass of a take that decides behind its return:
        every eligible leaf's chunk layout, host leaves digested here
        (this is their consistency point), device digests noted over the
        live arrays and not dispatched (:meth:`adopt_clones`,
        :meth:`digest_clones`). Their programs are compiled here, before the
        clone exists, the first time the state's shapes are seen (a
        job's first save: 8.6 s on the caller at the benchmark's
        incremental cell, 17.3 s when the commit thread compiled and
        loaded it, PERF.md section 6, PR 41), and never dispatched."""
        self.behind_return = True
        if array_prepare_func is None:
            self._batches = self._collect(flattened)
            for batch in self._batches.values():
                if batch.specs:
                    dd.prepare_many(batch.specs)

    def write_all_plan(self, logical_path: str) -> Optional[LeafIncrementalPlan]:
        """The plan the caller of a take that decides behind its return
        prepares a leaf with: an incremental take's chunk layout, every
        chunk written, no digest yet. None for a leaf that is not
        digested, which the preparers lay out as a plain take does."""
        return _WRITE_ALL if logical_path in self._layouts else None

    def hold(self, manifest: Dict[str, Entry], planned: Dict[str, List[Any]]) -> None:
        """Keep what the commit thread decides over: the rank's manifest
        and each leaf's write requests as the caller planned them, with
        every chunk written; and, until :meth:`adopt_clones`, a stager
        for each jax source."""
        self._manifest, self._planned = manifest, planned
        self._holders = {
            id(source): req.buffer_stager
            for reqs in planned.values()
            for req in reqs
            for source in req.buffer_stager.jax_sources()
        }

    def adopt_clones(self) -> None:
        """After the capture pass, on the caller's thread: the noted
        device digests take each live array's clone instead, which its
        stager now holds, so that nothing behind the return reads the
        live state, and nothing here keeps it. A source captured as a
        host copy (its clone failed) leaves its leaf undigested: written
        in full, with no digest."""
        holders, self._holders = self._holders, {}
        adopted: Dict[Tuple[int, ...], _DigestBatch] = {}
        for group, batch in self._batches.items():
            remade = adopted[group] = _DigestBatch()
            for (arr, ranges), (path, keys) in zip(batch.specs, batch.members):
                holder = holders.get(id(arr))
                clones = holder.jax_sources() if holder is not None else []
                if clones:
                    remade.add(clones[0], ranges, path, keys)
                else:
                    self._layouts.pop(path, None)
        self._batches = adopted

    def digest_clones(self) -> None:
        """The commit thread's digests: dispatch them over the clones, one
        program a device group (``incremental:digest_launch``), and wait
        for them (``incremental:digest_wait``), before the decisions."""
        batches, self._batches = self._batches, {}
        with trace_annotation(metric_names.SPAN_INCREMENTAL_DIGEST_LAUNCH) as span:
            self._dispatch(batches)
            self._annotate_launch(span)
        self._materialize_all()

    def _annotate_launch(self, span: Any) -> None:
        span.annotate(
            leaves=len(self._layouts),
            chunks=sum(len(keys) for keys in self._layouts.values()),
            bytes=sum(nbytes for _, _, nbytes in self._group_futs),
            host_bytes=self._host_bytes,
            programs=len(self._group_futs),
        )

    def _collect(
        self, flattened: Dict[str, Any]
    ) -> Dict[Tuple[int, ...], _DigestBatch]:
        # Device digest work batches per device group — one fused dispatch
        # per group instead of one round-trip per chunk.
        batches: Dict[Tuple[int, ...], _DigestBatch] = {}
        for logical_path, leaf in flattened.items():
            try:
                self._collect_leaf(logical_path, leaf, batches)
            except Exception as e:  # noqa: BLE001 - digest is an optimization
                logger.warning(
                    "Digest launch failed for %r (%r); leaf will be "
                    "written in full",
                    logical_path,
                    e,
                )
                self._layouts.pop(logical_path, None)
        return batches

    def _dispatch(self, batches: Dict[Tuple[int, ...], _DigestBatch]) -> None:
        for batch in batches.values():
            if not batch.specs:
                continue
            try:
                fut = dd.digest_many_async(batch.specs)
            except Exception as e:  # noqa: BLE001
                logger.warning(
                    "Batched digest dispatch failed (%r); %d leaves will "
                    "be written in full",
                    e,
                    len({p for p, _ in batch.rows}),
                )
                for p, _ in batch.rows:
                    self._layouts.pop(p, None)
                continue
            self._group_futs.append((fut, batch.rows, batch.nbytes))

    @staticmethod
    def _device_group(arr: Any) -> Tuple[int, ...]:
        from .ops.device_pack import device_group_key

        return device_group_key(arr)

    def _collect_leaf(
        self,
        logical_path: str,
        leaf: Any,
        batches: Dict[Tuple[int, ...], _DigestBatch],
    ) -> None:
        from .io_preparer import (
            ChunkedArrayIOPreparer,
            PrimitivePreparer,
            _is_dense_array,
            chunk_shapes,
            effective_max_chunk_size_bytes,
            is_jax_array,
            is_sharded_array,
        )

        if PrimitivePreparer.should_inline(leaf):
            return
        if is_sharded_array(leaf):
            if dd.digest_supported(leaf.dtype):
                self._dtypes[logical_path] = leaf.dtype
                self._sharded.add(logical_path)
                self._collect_sharded(logical_path, leaf, batches)
            return
        if not _is_dense_array(leaf) or not dd.digest_supported(leaf.dtype):
            return
        self._dtypes[logical_path] = leaf.dtype

        shape = tuple(int(d) for d in leaf.shape)
        keys: List[ChunkKey] = []
        # ``incremental=True`` sentinel: the collected chunk layout must
        # equal what the preparers will use when handed a non-None plan.
        if ChunkedArrayIOPreparer.should_chunk(leaf, incremental=True):
            ranges = chunk_shapes(
                list(shape),
                dtype_to_string(leaf.dtype),
                effective_max_chunk_size_bytes(True),
            )
            for start, stop in ranges:
                keys.append(
                    (
                        (start,) + tuple(0 for _ in shape[1:]),
                        (stop - start,) + shape[1:],
                    )
                )
            if is_jax_array(leaf):
                batch = batches.setdefault(
                    self._device_group(leaf), _DigestBatch()
                )
                batch.add(leaf, tuple(ranges), logical_path, keys)
            else:
                host = np.asarray(leaf)
                self._host_bytes += host.nbytes
                for (start, stop), key in zip(ranges, keys):
                    self._results[(logical_path, key)] = dd.digest_host(
                        host[start:stop]
                    )
        else:
            key = (tuple(0 for _ in shape), shape)
            keys.append(key)
            if is_jax_array(leaf):
                batch = batches.setdefault(
                    self._device_group(leaf), _DigestBatch()
                )
                batch.add(leaf, None, logical_path, [key])
            else:
                host = np.asarray(leaf)
                self._host_bytes += host.nbytes
                self._results[(logical_path, key)] = dd.digest_host(host)
        self._layouts[logical_path] = keys

    def _collect_sharded(
        self,
        logical_path: str,
        leaf: Any,
        batches: Dict[Tuple[int, ...], _DigestBatch],
    ) -> None:
        from .io_preparer import effective_max_shard_size_bytes
        from .parallel.overlap import Box, subdivide_box

        itemsize = np.dtype(leaf.dtype).itemsize
        max_shard = effective_max_shard_size_bytes(True)
        keys: List[ChunkKey] = []
        for dev_shard in leaf.addressable_shards:
            if dev_shard.replica_id != 0:
                continue
            box = Box.from_index(dev_shard.index, leaf.shape)
            shard_keys: List[ChunkKey] = []
            shard_ranges: List[Tuple[int, int]] = []
            whole = True
            for piece in subdivide_box(box, max_shard, itemsize):
                key = (tuple(piece.offsets), tuple(piece.sizes))
                shard_keys.append(key)
                row0 = piece.offsets[0] - box.offsets[0]
                shard_ranges.append((row0, row0 + piece.sizes[0]))
                whole = whole and piece == box
            batch = batches.setdefault(
                self._device_group(dev_shard.data), _DigestBatch()
            )
            batch.add(
                dev_shard.data,
                None if whole else tuple(shard_ranges),
                logical_path,
                shard_keys,
            )
            keys.extend(shard_keys)
        if keys:
            self._layouts[logical_path] = keys

    def _materialize_all(self) -> None:
        """Block on every device group's digest future (first plan_for
        call). A failed group degrades its leaves to full writes."""
        if self._materialized:
            return
        self._materialized = True
        self.waited_for_device = bool(self._group_futs)
        with trace_annotation(
            metric_names.SPAN_INCREMENTAL_DIGEST_WAIT,
            chunks=sum(len(rows) for _, rows, _ in self._group_futs),
        ):
            self._materialize_groups()

    def _materialize_groups(self) -> None:
        for fut, rows, _ in self._group_futs:
            try:
                values = dd.materialize_many(fut)
            except Exception as e:  # noqa: BLE001 - digest is an optimization
                logger.warning(
                    "Digest materialization failed (%r); %d leaves will "
                    "be written in full",
                    e,
                    len({p for p, _ in rows}),
                )
                for p, _ in rows:
                    self._layouts.pop(p, None)
                continue
            for (path, key), row in zip(rows, values):
                self._results[(path, key)] = (int(row[0]), int(row[1]))
        self._group_futs = []

    # ------------------------------------------------------------------
    # cross-rank agreement
    # ------------------------------------------------------------------

    def synchronize(self, pg_wrapper: Any, replicated_paths: Set[str]) -> None:
        """Align skip decisions across ranks for replicated leaves.

        Replicated manifest entries are asserted equal at consolidation
        (partitioner.consolidate_replicated_entries), so any per-rank
        divergence — a rank whose base metadata read failed, or whose
        digest launch errored for one leaf — must degrade *all* ranks to
        the same full-write (or digest-less) treatment, not crash the
        take. Two collective facts settle it: whether every rank has a
        usable base, and which replicated leaves every rank managed to
        launch digests for."""
        self._replicated_paths = set(replicated_paths)
        if pg_wrapper.get_world_size() == 1:
            return
        # Materialize before gathering so late (materialize-time) digest
        # failures are part of the agreement, not a divergence after it.
        self._materialize_all()
        local = (
            self._ref_prefix is not None,
            sorted(p for p in self._layouts if p in replicated_paths),
        )
        # Gather-to-leader + broadcast of the two decided facts: every
        # rank applies the same decision without pulling every rank's
        # launched-leaf list (O(world x leaves) per rank at torchrec
        # scale) through the coordinator.
        gathered = pg_wrapper.gather_object(local)
        decision = None
        if gathered is not None:
            all_have_base = all(has_base for has_base, _ in gathered)
            common_set = set(gathered[0][1])
            for _, launched in gathered[1:]:
                common_set &= set(launched)
            decision = (all_have_base, sorted(common_set))
        all_have_base, common_list = pg_wrapper.broadcast_object(decision)
        common = set(common_list)
        if not all_have_base:
            # Some rank can't reference the base: nobody may.
            self._base_available = {}
            self._ref_prefix = None
        for path in list(self._layouts):
            if path in replicated_paths and path not in common:
                del self._layouts[path]

    # ------------------------------------------------------------------
    # pass 2: materialize + compare
    # ------------------------------------------------------------------

    def plan_for(self, logical_path: str) -> Optional[LeafIncrementalPlan]:
        if logical_path not in self._layouts:
            return None
        self._materialize_all()
        keys = self._layouts.get(logical_path)
        if keys is None:  # group failed during materialization
            return None
        digests: Dict[ChunkKey, str] = {}
        for key in keys:
            value = self._results.get((logical_path, key))
            if value is None:
                return None
            digests[key] = dd.format_digest(value)

        refs: Dict[ChunkKey, Tuple[ArrayEntry, str]] = {}
        base_entry = self._base_available.get(logical_path)
        current_dtype = self._current_dtype(logical_path)
        current_replicated = self._is_replicated_dense(logical_path)
        if (
            base_entry is not None
            and self._ref_prefix is not None
            and current_dtype is not None
        ):
            for key, base_chunk in _base_chunk_map(base_entry).items():
                # The digest covers bytes, not the type tag — require the
                # base chunk to match the current leaf's dtype and the
                # box's shape exactly, on top of digest equality. The
                # base's replicated *placement* must also match the
                # current take's: a leaf promoted to (or demoted from)
                # replicated between steps would otherwise produce
                # rank-divergent refs into per-rank base locations, which
                # the replicated-entry consolidation assert rejects.
                if (
                    key in digests
                    and base_chunk.digest == digests[key]
                    and base_chunk.dtype == current_dtype
                    and base_chunk.serializer == Serializer.BUFFER_PROTOCOL.value
                    and list(base_chunk.shape) == list(key[1])
                    and base_chunk.replicated == current_replicated
                ):
                    template = ArrayEntry(
                        location=posixpath.normpath(
                            posixpath.join(self._ref_prefix, base_chunk.location)
                        ),
                        serializer=base_chunk.serializer,
                        dtype=base_chunk.dtype,
                        shape=list(base_chunk.shape),
                        replicated=base_chunk.replicated,
                        byte_range=base_chunk.byte_range,
                        digest=base_chunk.digest,
                    )
                    # Second element: the location as the *base manifest*
                    # spells it — the key its checksum table uses.
                    refs[key] = (template, base_chunk.location)
        if not refs and not digests:
            return None

        def on_ref_used(ref_location: str, base_location: str) -> None:
            self.used_refs[ref_location] = base_location

        dtype = self._dtypes[logical_path]

        def on_decision(referenced: bool, sizes: Tuple[int, ...]) -> None:
            kind = "referenced" if referenced else "written"
            self.decisions[f"chunks_{kind}"] += 1
            self.decisions[f"bytes_{kind}"] += _chunk_nbytes(sizes, dtype)

        return LeafIncrementalPlan(refs, digests, on_ref_used, on_decision)

    def _is_replicated_dense(self, logical_path: str) -> bool:
        """The replicated flag the preparers will stamp on this leaf's
        dense entries: True only for non-sharded leaves matched by the
        verified replication set (sharded entries always carry False)."""
        return (
            logical_path in self._replicated_paths
            and logical_path not in self._sharded
        )

    def _current_dtype(self, logical_path: str) -> Optional[str]:
        dtype = self._dtypes.get(logical_path)
        if dtype is None:
            return None
        try:
            return dtype_to_string(dtype)
        except Exception:  # noqa: BLE001
            return None

    # ------------------------------------------------------------------
    # pass 2 behind the return (the commit thread)
    # ------------------------------------------------------------------

    def decide(self) -> Tuple[Dict[str, Entry], List[Any]]:
        """The skip decisions of a take that decides behind its return,
        over what :meth:`hold` kept: each digested leaf's entry and write
        requests become what ``prepare_write`` makes with the leaf's plan
        (:func:`_decide_leaf`), the others stay as planned. Returns the
        rank's manifest and the write requests, in the plan's order;
        nothing here keeps a request, so the clones that only dropped
        requests held go with them."""
        manifest, planned = self._manifest, self._planned
        self._manifest, self._planned = {}, {}
        write_reqs: List[Any] = []
        for logical_path, reqs in planned.items():
            plan = self.plan_for(logical_path)
            if plan is not None:
                manifest[logical_path], reqs = _decide_leaf(
                    manifest[logical_path], reqs, plan
                )
            write_reqs.extend(reqs)
        return manifest, write_reqs

    # ------------------------------------------------------------------
    # checksum inheritance
    # ------------------------------------------------------------------

    def inherit_checksums(self, checksums: Dict[str, tuple]) -> None:
        """Copy the base snapshot's checksum entries for every referenced
        blob into this take's table (keyed by the new ref location), so
        restore-time verification covers unwritten bytes too."""
        if not self.used_refs or self._base_path is None:
            return
        if knobs.is_checksums_disabled():
            return
        import asyncio

        from .integrity import load_checksum_tables
        from .storage_plugin import url_to_storage_plugin

        # Fail-soft: every data blob and the manifest are already durable
        # by the time this runs; a transient error reading the base's
        # tables must degrade the referenced blobs to UNVERIFIED restores
        # (with a warning), not fail the whole checkpoint.
        base_table = None
        event_loop = asyncio.new_event_loop()
        try:
            try:
                storage = url_to_storage_plugin(self._base_path)
                try:
                    base_table = load_checksum_tables(
                        self._base_world_size, storage, event_loop
                    )
                finally:
                    try:
                        event_loop.run_until_complete(storage.close())
                    except Exception as close_exc:  # noqa: BLE001
                        # Close failures don't affect the already-loaded
                        # tables — inheritance proceeds normally.
                        logger.warning(
                            "Error closing base storage plugin after "
                            "checksum inheritance: %r",
                            close_exc,
                        )
            except Exception as e:  # noqa: BLE001
                logger.warning(
                    "Could not inherit checksum tables from base %s (%r); "
                    "referenced blobs will restore UNVERIFIED",
                    self._base_path,
                    e,
                )
        finally:
            event_loop.close()
        if not base_table:
            return
        for ref_loc, base_loc in self.used_refs.items():
            entry = base_table.get(base_loc)
            if entry is not None:
                checksums[ref_loc] = entry

"""CAS write interception and the rank-0 manifest rewrite.

``CASStoragePlugin`` wraps a take's storage plugin. Data-blob writes
(``{rank}/...``, ``sharded/...``, ``replicated/...``, ``batched/...``)
are *diverted*: the integrity entry is computed first (one pass over the
bytes — the same entry the checksum table records, so nothing is hashed
twice), the digest key derived, and the bytes written to
``../chunks/<key>`` **only if the store does not already hold that
key** — dedup across steps, across replicated ranks (identical bytes
from any writer resolve to one stored blob; concurrent same-key writers
are idempotent because the content is the key), and across consumers
(the mirror and peer tier see the chunk once). Control blobs
(``.snapshot_metadata``, ``checksums/``, telemetry dotfiles) pass
through untouched.

The manifest fix-up happens once, on rank 0, at commit time: every
writing rank persists its ``path -> digest`` map as ``cas/{rank}``
before the commit barrier (next to its checksum table), and rank 0's
metadata write reads the maps back and rewrites entry locations to
``../chunks/<key>`` — after which the snapshot is indistinguishable
from any other parent-ref-bearing snapshot to every reader. A rank
whose knob/skew kept CAS off simply contributes no map, and its paths
stay step-local: the two layouts compose per blob.

Crash safety of the chunk write itself: the digest key embeds the byte
length, and the existence check requires an exact on-disk size match —
a partial chunk left by a kill mid-write can never satisfy dedup and is
simply overwritten by the next writer of the same content. Dedup hits
*touch* the chunk's mtime, which is what the manager GC's grace window
keys off (an in-flight step's reused chunks are always fresh).
"""

from __future__ import annotations

import json
import logging
import os
from typing import Dict, Optional, Tuple

from .. import telemetry
from ..integrity import compute_checksum_entry
from ..io_types import ReadIO, StoragePlugin, WriteIO, payload_nbytes
from ..telemetry import names as metric_names
from ..utils.tracing import run_in_executor
from .store import (
    CAS_MAP_DIR,
    CHUNKS_DIRNAME,
    chunk_location,
    digest_key,
    local_chunks_dir,
    root_url_of_snapshot,
)

logger: logging.Logger = logging.getLogger(__name__)

# Entries at or under this size are hashed inline on the event loop;
# larger ones hop to an executor (same threshold rationale as the
# scheduler's checksum_off_slot).
_INLINE_DIGEST_BYTES = 1 * 1024 * 1024

_CONTROL_TOP_SEGMENTS = frozenset(("checksums", CAS_MAP_DIR, CHUNKS_DIRNAME))


def is_data_path(path: str) -> bool:
    """Paths whose bytes belong in the chunk store: everything a take's
    write pipeline emits except control/metadata blobs (dotfiles,
    checksum tables, the cas maps themselves)."""
    if path.startswith("../"):
        return False
    first = path.split("/", 1)[0]
    if not first or first.startswith("."):
        return False
    return first not in _CONTROL_TOP_SEGMENTS


def chunk_map_path(rank: int) -> str:
    return f"{CAS_MAP_DIR}/{rank}"


class CASStoragePlugin(StoragePlugin):
    """Write-side CAS interception for one take. Reads, deletes and
    control writes delegate to the inner plugin unchanged."""

    def __init__(self, inner: StoragePlugin, snapshot_url: str) -> None:
        self.inner = inner
        self.snapshot_url = snapshot_url
        root_url = root_url_of_snapshot(snapshot_url)
        local = local_chunks_dir(root_url)
        assert local is not None  # gated by cas_eligible at install
        self._local_dir = local
        # original write path -> (digest key, nbytes, newly written?)
        self.records: Dict[str, Tuple[str, int, bool]] = {}
        self._written_keys: set = set()

    # -- capability passthrough -----------------------------------------

    @property
    def supports_multibuffer(self) -> bool:  # type: ignore[override]
        return getattr(self.inner, "supports_multibuffer", False)

    # -- writes ----------------------------------------------------------

    async def _entry_of(self, buf) -> Tuple:
        if payload_nbytes(buf) <= _INLINE_DIGEST_BYTES:
            return compute_checksum_entry(buf)
        return await run_in_executor(
            None, compute_checksum_entry, buf
        )

    def _has(self, key: str, nbytes: int) -> bool:
        """Exact-size local existence check; a hit touches the chunk's
        mtime (the GC grace window's liveness signal)."""
        if key in self._written_keys:
            return True
        path = os.path.join(self._local_dir, key)
        try:
            if os.path.getsize(path) != nbytes:
                return False
        except OSError:
            return False
        try:
            os.utime(path)
        except OSError:
            pass  # touch is best-effort; grace default dwarfs a take
        return True

    async def _divert(self, write_io: WriteIO, entry: Tuple) -> None:
        key = digest_key(entry)
        nbytes = payload_nbytes(write_io.buf)
        registry = telemetry.metrics()
        if self._has(key, nbytes):
            write_io.variant = "deduped"
            self.records[write_io.path] = (key, nbytes, False)
            # Tiered roots: a dedup hit writes nothing, but this step's
            # durability still covers the chunk — if its original
            # writer crashed before mirroring, no other job would ever
            # ship it. Record it for mirror enqueue; the durable-side
            # probe skips already-held chunks at one ranged byte each.
            note = getattr(self.inner, "note_written", None)
            if note is not None:
                note(chunk_location(key), nbytes)
            registry.counter_inc(metric_names.CAS_CHUNKS_DEDUPED_TOTAL)
            registry.counter_inc(
                metric_names.CAS_BYTES_DEDUPED_TOTAL, nbytes
            )
            return
        inner_io = WriteIO(path=chunk_location(key), buf=write_io.buf)
        await self.inner.write(inner_io)
        write_io.variant = inner_io.variant
        self._written_keys.add(key)
        self.records[write_io.path] = (key, nbytes, True)
        registry.counter_inc(metric_names.CAS_CHUNKS_WRITTEN_TOTAL)
        registry.counter_inc(metric_names.CAS_BYTES_WRITTEN_TOTAL, nbytes)
        # Kill point: the chunk's bytes exist, but no map/manifest/pin
        # references them yet — the stray-sweep + grace-window case the
        # crash matrix must prove safe.
        from ..chaos import crashpoint

        crashpoint(metric_names.CRASH_CAS_CHUNK_WRITTEN)

    async def write(self, write_io: WriteIO) -> None:
        if not is_data_path(write_io.path):
            await self.inner.write(write_io)
            return
        # Checksums may be globally disabled, but content addressing IS
        # a digest: compute the entry regardless (it just stays out of
        # the table).
        entry = await self._entry_of(write_io.buf)
        await self._divert(write_io, entry)

    async def write_with_checksum(self, write_io: WriteIO):
        if not is_data_path(write_io.path):
            return await self.inner.write_with_checksum(write_io)
        # The digest must exist BEFORE the bytes can be addressed, so
        # the fused single-pass kernel cannot serve CAS writes; the
        # entry computed here doubles as the table entry, so the total
        # hash work is unchanged (one pass).
        entry = await self._entry_of(write_io.buf)
        await self._divert(write_io, entry)
        return entry

    # -- reads / deletes / close: delegate -------------------------------

    async def read(self, read_io: ReadIO) -> None:
        await self.inner.read(read_io)

    async def read_with_checksum(self, read_io: ReadIO):
        return await self.inner.read_with_checksum(read_io)

    async def read_degraded(self, read_io: ReadIO) -> bool:
        return await self.inner.read_degraded(read_io)

    async def delete(self, path: str) -> None:
        await self.inner.delete(path)

    async def close(self) -> None:
        await self.inner.close()

    # -- take-commit plumbing --------------------------------------------

    def rekey_checksums(self, checksums: Dict[str, Tuple]) -> None:
        """Re-home this rank's checksum-table entries from the original
        write paths to the chunk locations the manifest will name, so
        restore-time verification keys match read paths. Runs in the
        checksum finalizer, before the table is persisted."""
        for orig, (key, _nbytes, _new) in self.records.items():
            entry = checksums.pop(orig, None)
            if entry is not None:
                checksums[chunk_location(key)] = entry

    async def write_chunk_map(self, rank: int) -> None:
        """Persist this rank's ``path -> digest`` map (``cas/{rank}``)
        — the input of rank 0's manifest rewrite; committed alongside
        the checksum table, before the commit barrier."""
        if not self.records:
            return
        doc = {
            "paths": {
                path: {"k": key, "n": nbytes, "new": new}
                for path, (key, nbytes, new) in sorted(self.records.items())
            }
        }
        await self.inner.write(
            WriteIO(
                path=chunk_map_path(rank),
                buf=json.dumps(doc, sort_keys=True).encode(),
            )
        )


async def load_chunk_maps(
    storage: StoragePlugin, world_size: int
) -> Dict[str, Tuple[str, int, bool]]:
    """Merge every rank's committed ``cas/{rank}`` map:
    ``original path -> (digest key, nbytes, newly written)``. Ranks
    without a map (nothing diverted — empty rank, or CAS off there)
    contribute nothing; the rewrite is per-blob."""
    merged: Dict[str, Tuple[str, int, bool]] = {}
    for rank in range(world_size):
        read_io = ReadIO(path=chunk_map_path(rank))
        try:
            await storage.read(read_io)
        except FileNotFoundError:
            continue
        try:
            doc = json.loads(bytes(read_io.buf))
        except ValueError as e:
            # A corrupt map would leave this rank's manifest entries
            # pointing at step-local paths holding no bytes — fail the
            # commit loudly rather than commit a broken snapshot.
            raise RuntimeError(
                f"CAS chunk map {chunk_map_path(rank)} is unparseable"
            ) from e
        for path, rec in doc.get("paths", {}).items():
            prev = merged.get(path)
            new = bool(rec.get("new")) or bool(prev and prev[2])
            merged[path] = (str(rec["k"]), int(rec["n"]), new)
    return merged


def rewrite_manifest_locations(
    manifest, merged: Dict[str, Tuple[str, int, bool]]
) -> int:
    """Point every manifest entry whose original location appears in
    ``merged`` at its chunk (``../chunks/<key>``), preserving byte
    ranges (batched-slab members share one chunk and keep their
    windows). Returns the number of locations rewritten."""
    from ..manifest import ChunkedArrayEntry, ShardedArrayEntry

    rewritten = 0

    def _fix(dense) -> None:
        nonlocal rewritten
        hit = merged.get(dense.location)
        if hit is not None:
            dense.location = chunk_location(hit[0])
            rewritten += 1

    for entry in manifest.values():
        if isinstance(entry, ShardedArrayEntry):
            for shard in entry.shards:
                _fix(shard.array)
        elif isinstance(entry, ChunkedArrayEntry):
            for chunk in entry.chunks:
                _fix(chunk.array)
        elif getattr(entry, "location", None) is not None:
            _fix(entry)
    return rewritten


async def maybe_rewrite_manifest(metadata, storage: StoragePlugin) -> None:
    """Rank-0 commit hook: when the take ran through a CAS wrapper,
    fold every rank's chunk map into the global manifest before the
    metadata blob is written. No-op for legacy takes."""
    if not isinstance(storage, CASStoragePlugin):
        return
    merged = await load_chunk_maps(storage, metadata.world_size)
    if merged:
        n = rewrite_manifest_locations(metadata.manifest, merged)
        logger.debug(
            "CAS commit: rewrote %d manifest locations onto %d chunks",
            n,
            len({k for k, _, _ in merged.values()}),
        )

"""CheckpointManager: step-numbered snapshots with retention and
latest-step resume.

The reference exposes only the single-snapshot primitives
(snapshot.py:175-243) and its examples hand-roll the loop around them
(examples/simple_example.py:59-76: restore-if-exists, then periodic
takes). This module packages that loop the way TPU training jobs use it:

    mgr = CheckpointManager(root, keep_last_n=3)
    start = mgr.restore_latest(app_state)          # None on a fresh run
    for step in range(start or 0, total_steps):
        ...
        if step % save_every == 0:
            mgr.save(step, app_state)              # or async_save

Storage-agnostic: steps live at ``{root}/step_{step:010d}`` and the
committed-step list is a rank-0-maintained ``.manager_index`` JSON blob
(storage plugins have no directory listing, so the index is the source
of truth; a step whose take crashed before commit never enters it and is
invisible to restore). Retention deletes every blob named by the dropped
step's manifest — the commit marker first, so a half-deleted step can
never be mistaken for a valid one.

Incremental mode (``incremental=True``, or per-save): each save records
on-device digests and references the previous committed step's unchanged
chunks instead of rewriting them (incremental.py). The index additionally
tracks which origin steps each step's manifest references; retention
*pins* a dropped step whose blobs are still referenced by a retained step
(blobs stay, step leaves the visible list) and deletes it as soon as no
retained step references it — so incremental chains never dangle and
storage is reclaimed exactly when safe.
"""

from __future__ import annotations

import asyncio
import json
import logging
import re
import threading
from typing import Any, Dict, List, Optional, Set

from . import knobs, telemetry
from .chaos import crashpoint as _crashpoint
from .event_loop import run_in_fresh_event_loop
from .telemetry import names as metric_names
from .telemetry.trace import get_recorder as _trace_recorder
from .telemetry.trace import op_scope as _op_scope
from .io_types import ReadIO, StoragePlugin, WriteIO
from .manifest import (
    ChunkedArrayEntry,
    Entry,
    Manifest,
    ShardedArrayEntry,
    SnapshotMetadata,
    entry_locations,
)
from .pg_wrapper import PGWrapper
from .snapshot import SNAPSHOT_METADATA_FNAME, PendingSnapshot, Snapshot
from .stateful import AppState
from .storage_plugin import join_path, split_tiered_url, url_to_storage_plugin
from .utils.tracing import trace_annotation

logger: logging.Logger = logging.getLogger(__name__)

INDEX_BLOB = ".manager_index"
INDEX_BACKUP_BLOB = ".manager_index.backup"


def _step_dirname(step: int) -> str:
    return f"step_{step:010d}"


_REF_LOCATION_RE = re.compile(r"^\.\./step_(\d+)/")


def referenced_steps(manifest: Manifest) -> Set[int]:
    """Origin steps an (incremental) snapshot's manifest references.
    Chained refs collapse at take time (incremental.py), so locations
    always name the originating step directly."""
    out: Set[int] = set()
    for entry in manifest.values():
        for location in _entry_locations(entry):
            m = _REF_LOCATION_RE.match(location)
            if m:
                out.add(int(m.group(1)))
    return out


# Re-exported for existing importers; the implementation moved to
# manifest.entry_locations (the CAS refcount derivation needs it below
# the manager layer).
_entry_locations = entry_locations


def _manifest_chunk_refs(manifest: Manifest) -> Dict[str, int]:
    """The content-addressed chunks a manifest references (digest key ->
    nbytes); empty for legacy-layout snapshots."""
    from .cas import chunk_refs

    return chunk_refs(manifest)


def _manifest_digest_map(manifest: Manifest) -> Dict[Any, Any]:
    """Every on-device digest a manifest records, keyed by structural
    position ``(manifest path, offsets, sizes)`` with the covered byte
    count — comparing two consecutive steps' maps measures how much of
    the state the digests say was unchanged (ledger evidence for the
    dedup-ineffective doctor rule). Empty for digest-less takes."""
    from .serialization import array_size_bytes

    out: Dict[Any, Any] = {}
    for path, entry in manifest.items():
        if isinstance(entry, (ShardedArrayEntry, ChunkedArrayEntry)):
            pieces = (
                entry.shards
                if isinstance(entry, ShardedArrayEntry)
                else entry.chunks
            )
            for piece in pieces:
                if piece.array.digest:
                    out[(path, tuple(piece.offsets), tuple(piece.sizes))] = (
                        piece.array.digest,
                        array_size_bytes(
                            piece.array.shape, piece.array.dtype
                        ),
                    )
        else:
            digest = getattr(entry, "digest", None)
            if digest:
                out[(path, (), ())] = (
                    digest,
                    array_size_bytes(entry.shape, entry.dtype),
                )
    return out


async def read_index_full_async(storage: StoragePlugin) -> Dict[str, Any]:
    """Primary slot, falling back to the backup slot: the index is
    rewritten on every save (backup slot first), so a crash mid-write
    must not brick the manager — whichever slot survives is valid,
    at worst one save stale. Returns ``{"steps": [...], "refs":
    {step: [origin steps]}, "pinned": [...]}``; the latter two default
    empty for pre-incremental indexes. Module-level so read-only
    consumers (``fsck --cas``) share the exact recovery semantics."""
    io_failed: List[str] = []
    corrupt: List[str] = []
    absent: List[str] = []
    for slot in (INDEX_BLOB, INDEX_BACKUP_BLOB):
        read_io = ReadIO(path=slot)
        try:
            await storage.read(read_io)
        except FileNotFoundError:
            absent.append(slot)
            continue
        except Exception as e:  # noqa: BLE001
            logger.warning("Could not read index slot %s: %r", slot, e)
            io_failed.append(slot)
            continue
        if read_io.buf is None:
            absent.append(slot)
            continue
        try:
            raw = json.loads(bytes(read_io.buf))
            return {
                "steps": sorted(int(s) for s in raw["steps"]),
                "refs": {
                    str(int(k)): sorted(int(v) for v in vs)
                    for k, vs in raw.get("refs", {}).items()
                },
                "pinned": sorted(int(p) for p in raw.get("pinned", [])),
                "metrics": {
                    str(int(k)): float(v)
                    for k, v in raw.get("metrics", {}).items()
                },
                "evicted": sorted(
                    int(s) for s in raw.get("evicted", [])
                ),
                # Pre-marker indexes with committed steps may predate
                # incremental-ref recording entirely: a missing refs
                # entry there means "unknown" and GC must verify before
                # deleting. A fresh (empty) index is trivially complete.
                "refs_complete": bool(
                    raw.get("refs_complete", not raw["steps"])
                ),
            }
        except (ValueError, KeyError, TypeError) as e:
            logger.warning(
                "Index slot %s is corrupt (%r); trying %s",
                slot,
                e,
                INDEX_BACKUP_BLOB,
            )
            corrupt.append(slot)
    # "Slots absent" (fresh directory) yields []. One corrupt slot with
    # the OTHER slot absent is the same thing: writes go backup-then-
    # primary (_write_index_async), so that state can only be a torn
    # FIRST-ever index write — no step list was ever readable; self-
    # recover.  Everything else ("slots unreadable": transient I/O
    # errors, or BOTH slots corrupt) must NOT be treated as empty — a
    # subsequent index rewrite would silently orphan every previously
    # committed step.  Fail the operation loudly instead; a transient
    # storage error heals on retry.
    if io_failed or len(corrupt) > 1:
        raise RuntimeError(
            "checkpoint index unreadable "
            f"(io_failed={io_failed!r}, corrupt={corrupt!r}); "
            "refusing to treat the step list as empty"
        )
    return {
        "steps": [], "refs": {}, "pinned": [], "metrics": {},
        "evicted": [], "refs_complete": True,
    }


class _PendingManagedSnapshot:
    """Wraps a PendingSnapshot so that the manager's after-commit work
    for the step (``CheckpointManager._after_commit``: index, retention,
    history, ledger, the tuner's decision) runs exactly once, as the
    tail of the take's commit thread: it is done when ``done()`` reads
    true, whether or not anyone calls ``wait()``. ``wait()`` joins,
    installs what the tuner decided (knob overrides land between takes,
    on the thread that drives them) and, where that work raised on the
    commit thread, runs it once more and raises what that raises."""

    def __init__(
        self,
        manager: "CheckpointManager",
        step: int,
        metric: Optional[float] = None,
    ):
        self._manager = manager
        self._step = step
        self._metric = metric
        # Set by CheckpointManager.async_save once the take is staged;
        # the commit thread never reads it.
        self._pending: PendingSnapshot
        self._committed = False
        # Exactly once a step: the commit thread and every wait() pass
        # through here (a duplicate history record widens the trend
        # baseline).
        self._commit_lock = threading.Lock()
        # The tuner's decided vector, until wait() installs it.
        self._decided: Optional[Dict[str, Any]] = None

    def _after_commit(self, snapshot: Snapshot, on: str) -> None:
        with self._commit_lock:
            if self._committed:
                return
            self._decided = self._manager._after_commit(
                self._step, snapshot, self._metric, on
            )
            self._committed = True

    def wait(self, phase: str = "committed") -> Optional[Snapshot]:
        """Passes ``phase`` through to :meth:`PendingSnapshot.wait`. A
        ``"staged"`` wait observes D2H completion and indexes nothing
        itself; the step becomes visible to ``restore_latest`` when its
        commit succeeded, never before its marker exists (the drain
        paths that flush checkpoints before teardown must wait for
        ``"committed"``, and this wrapper's default does). When a
        committed wait returns, the marker exists, the index lists the
        step, retention and chunk GC for it have run, and history and
        ledger hold its row."""
        if phase not in ("staged", "committed"):
            # Same contract as PendingSnapshot.wait: a typo'd phase must
            # not silently become a committed wait.
            raise ValueError(
                f'phase must be "staged" or "committed", got {phase!r}'
            )
        if phase == "staged":
            self._pending.wait(phase="staged")
            return None
        try:
            snapshot = self._pending.wait()
        except BaseException:
            # A failed take: no index entry, and nothing of it in flight.
            self._manager._bases_in_flight.pop(self._step, None)
            raise
        # wait() may be called from more than one place (progress loop +
        # shutdown path, possibly on different threads): a no-op unless
        # the commit thread's pass raised.
        self._after_commit(snapshot, "caller")
        with self._commit_lock:
            decided, self._decided = self._decided, None
        self._manager._install_tuned(self._step, snapshot, decided)
        return snapshot

    def done(self) -> bool:
        return self._pending.done()

    def staged(self) -> bool:
        return self._pending.staged()


class _ManagedPendingRestore:
    """Wraps a PendingRestore so the restore's telemetry summary lands
    in the manager's step history once the apply succeeds — the
    async-restore report is only emitted at ``wait()`` time (the apply
    runs on the calling thread), so the recording must ride the same
    call. Delegates everything else to the wrapped handle."""

    def __init__(self, manager: "CheckpointManager", step: int, pending: Any):
        self._manager = manager
        self._step = step
        self._pending = pending
        self._recorded = False

    def wait(self) -> None:
        out = self._pending.wait()
        if not self._recorded:
            self._recorded = True
            self._manager._record_restore_history(
                self._step, self._pending.trace_op
            )
        return out

    def done(self) -> bool:
        return self._pending.done()

    def __getattr__(self, name: str) -> Any:
        return getattr(self._pending, name)


class CheckpointManager:
    def __init__(
        self,
        root: str,
        keep_last_n: Optional[int] = None,
        pg: Optional[Any] = None,
        incremental: bool = False,
        keep_best_n: Optional[int] = None,
        best_mode: str = "min",
        keep_fast_last_n: Optional[int] = None,
        keep_peer_last_n: Optional[int] = None,
        cdn_topic: Optional[str] = None,
        cdn_store: Optional[Any] = None,
    ) -> None:
        if keep_last_n is not None and keep_last_n < 1:
            raise ValueError(f"keep_last_n must be >= 1, got {keep_last_n}")
        if keep_best_n is not None and keep_best_n < 1:
            raise ValueError(f"keep_best_n must be >= 1, got {keep_best_n}")
        if keep_peer_last_n is not None and keep_peer_last_n < 1:
            raise ValueError(
                f"keep_peer_last_n must be >= 1, got {keep_peer_last_n}"
            )
        if keep_fast_last_n is not None and keep_fast_last_n < 1:
            raise ValueError(
                f"keep_fast_last_n must be >= 1, got {keep_fast_last_n}"
            )
        if keep_fast_last_n is not None and split_tiered_url(root) is None:
            raise ValueError(
                "keep_fast_last_n requires a tiered:// root (fast-tier "
                "eviction needs a durable tier to fall back to)"
            )
        if best_mode not in ("min", "max"):
            raise ValueError(f"best_mode must be 'min' or 'max', got {best_mode}")
        self.root = root
        self.keep_last_n = keep_last_n
        # Metric-driven retention: steps saved with a ``metric=`` keep the
        # best ``keep_best_n`` scores (``best_mode``: lower- or
        # higher-is-better) IN ADDITION to the newest ``keep_last_n`` —
        # the "checkpoint the best eval loss" loop without hand-rolled GC.
        # When only keep_best_n is set, unscored steps are never GC'd
        # (see _retained).
        self.keep_best_n = keep_best_n
        self.best_mode = best_mode
        # Tier-aware retention (tiered:// roots only): retained steps
        # older than the newest ``keep_fast_last_n`` are dropped from the
        # FAST tier once durable-complete — they stay committed and
        # restorable through the per-blob durable fallback. A step is
        # never evicted before its durable commit marker exists, and the
        # durable tier is only ever touched by the normal retention GC
        # (which the index's pin logic already guards for incremental
        # refs).
        self.keep_fast_last_n = keep_fast_last_n
        # Peer-RAM retention (docs/peer.md): each rank's neighbor keeps
        # the newest N committed steps' shards in its host-RAM cache.
        # Default None = no count bound — the cache's byte budget (LRU
        # with the newest committed step pinned) is then the only
        # limit; set N=1 to keep exactly the step restore_latest would
        # pick and nothing older.
        self.keep_peer_last_n = keep_peer_last_n
        # Default for save()/async_save(): digest-enabled takes that
        # reference the previous committed step's unchanged chunks.
        self.incremental = incremental
        # One wrapper for the manager's own collectives; Snapshot calls get
        # the raw pg and build their own wrappers — safe because the op
        # sequence is shared across wrappers of the same pg (pg_wrapper).
        self._pg_arg = pg
        self._pg = PGWrapper(pg)
        # Peer-tier bring-up (tiered/peer.py): start this process's
        # cache server and advertise its endpoint through the
        # coordination store. Inert for single-process jobs, under the
        # TORCHSNAPSHOT_TPU_PEER_TIER=0 kill switch, or when pg carries
        # no store; failures degrade (the tier is recovery insurance,
        # never a reason a manager cannot construct).
        try:
            from .tiered import peer as peer_tier

            peer_tier.maybe_configure(
                self._pg, keep_last_n=keep_peer_last_n
            )
        except Exception as e:  # noqa: BLE001 - peer tier is best-effort
            logger.warning("peer tier: configure failed: %r", e)
        # Content-addressed chunk store (docs/cas.md): lazily-resolved
        # rank-0 handle over the root's ``chunks/`` refcount journal.
        # False = unresolved; None = root has no local tier (no CAS).
        # Resolution is evidence-driven, not knob-driven: a root holding
        # CAS steps from an earlier run keeps refcounted GC even with
        # the knob now off.
        self._cas_store: Any = False
        # Checkpoint CDN publish side (docs/cdn.md): with the CDN knob
        # on and a topic named, rank 0 announces every committed step's
        # chunk set to the coordination store so a serving fleet can
        # track the run. ``cdn_store`` overrides the pg's store (tests,
        # cross-job stores). Publisher is built lazily on first commit
        # — constructing a manager must not touch the store.
        self.cdn_topic = cdn_topic
        self._cdn_store_arg = cdn_store
        self._cdn_publisher: Any = None
        # Exact per-step storage accounting computed at commit time
        # (chunks newly materialized vs. reused), read back by
        # _post_step_ledger; and the previous committed manifest's
        # digest map, for the ledger's bytes_digest_unchanged signal.
        self._last_cas_accounting: Optional[Dict[str, Any]] = None
        self._prev_digest_map: Dict[str, Any] = {}
        # One step's after-commit work at a time (_after_commit): the
        # index is a read-modify-write and the accounting above is the
        # manager's, while two async saves' commit threads, or one and a
        # blocking save, may get there together. They index and retain
        # in the order their commits end.
        self._after_commit_lock = threading.Lock()
        # {step being saved: the step it diffs against} for this
        # manager's incremental takes that are not indexed yet (rank 0
        # resolved the base). Retention keeps such a base's blobs: the
        # take reads its manifest now and will reference its chunks,
        # and the index cannot say so before the take commits.
        self._bases_in_flight: Dict[int, int] = {}
        if self._pg.get_rank() == 0:
            try:
                self._reconcile_cas()
            except Exception as e:  # noqa: BLE001 - healing is best-effort
                logger.warning("CAS refcount reconcile failed: %r", e)
        # Lazily-constructed write-path autotuner (tuner/autotuner.py);
        # stays None while TORCHSNAPSHOT_TPU_AUTOTUNE=0 — the kill
        # switch means no tuner object, no state file, no broadcast.
        self._autotuner: Optional[Any] = None
        # Run-level goodput ledger (telemetry/ledger.py): rank 0 opens
        # (or, after a restart/preemption, resumes) the run — the
        # run-start event anchors every segment's wall-time attribution
        # and registers this process as the root's only ledger writer.
        # None while TORCHSNAPSHOT_TPU_LEDGER=0 (no file appears).
        self._ledger_run_id: Optional[str] = None
        if knobs.is_ledger_enabled() and self._pg.get_rank() == 0:
            try:
                from .telemetry import ledger as run_ledger

                self._ledger_run_id = run_ledger.open_run(
                    self.root, world_size=self._pg.get_world_size()
                )
            except Exception as e:  # noqa: BLE001 - ledger is best-effort
                logger.warning("could not open the run ledger: %r", e)

    # ------------------------------------------------------------------
    # saving
    # ------------------------------------------------------------------

    def step_path(self, step: int) -> str:
        # join_path is tiered-aware: with a tiered:// root, the step
        # segment lands on BOTH tiers' roots.
        return join_path(self.root, _step_dirname(step))

    def _incremental_take_kwargs(
        self,
        step: int,
        incremental: Optional[bool],
        take_kwargs: Dict[str, Any],
    ) -> Dict[str, Any]:
        """Resolve the per-save incremental setting and, when on, point the
        take at the latest committed step. Rank 0 resolves the base and
        everyone follows — ranks must never diff against different bases.
        The base is held against retention (``_bases_in_flight``) until
        ``step`` is indexed or its take has failed."""
        if incremental is None:
            incremental = self.incremental
        if not incremental:
            return take_kwargs
        if "incremental_base" in take_kwargs:
            return {**take_kwargs, "record_digests": True}
        base_step = None
        if self._pg.get_rank() == 0:
            # Under the lock, and not over the broadcast below (a peer's
            # commit thread may hold its own lock waiting for ours): an
            # after-commit pass that has written the index may still be
            # deleting the steps it dropped.
            with self._after_commit_lock:
                base_step = self.latest_step()
                if base_step is not None:
                    self._bases_in_flight[step] = base_step
        base_step = self._pg.broadcast_object(base_step)
        out = {**take_kwargs, "record_digests": True}
        if base_step is not None:
            out["incremental_base"] = self.step_path(base_step)
        return out

    def save(
        self,
        step: int,
        app_state: AppState,
        incremental: Optional[bool] = None,
        metric: Optional[float] = None,
        **take_kwargs: Any,
    ) -> Snapshot:
        """Synchronous checkpoint of ``step``; updates the index and
        applies retention after the commit. ``incremental`` overrides the
        manager-level default for this save; ``metric`` records this
        step's score for ``keep_best_n`` retention and ``best_step()``
        (rank 0's value is authoritative)."""
        self._validate_metric(metric)
        take_kwargs = self._incremental_take_kwargs(
            step, incremental, take_kwargs
        )
        try:
            snapshot = Snapshot.take(
                self.step_path(step), app_state, pg=self._pg_arg, **take_kwargs
            )
        except BaseException:
            self._bases_in_flight.pop(step, None)
            raise
        self._install_tuned(
            step, snapshot, self._after_commit(step, snapshot, metric, "caller")
        )
        return snapshot

    def _after_commit(
        self,
        step: int,
        snapshot: Snapshot,
        metric: Optional[float],
        on: str,
    ) -> Optional[Dict[str, Any]]:
        """What the manager does for a step once its snapshot is
        committed: index + retention, history / ledger / SLOs, the CDN
        announce, the tuner's decision. One function with two callers:
        ``save()`` on the caller's thread (``on="caller"``, inside its
        stall) and, for ``async_save``, the take's commit thread
        (``on="commit"``) after the marker is written and before
        ``done()`` turns true, so the step is visible to
        ``restore_latest`` from then on and none of this is inside
        ``wait()``. Recorded as stages of the take that made the step
        (``snapshot.trace_op``). Returns the knob vector the tuner
        decided, for ``_install_tuned`` on the thread that drives the
        takes, or None. Everything but the tuner's pass runs under the
        manager's lock: its exchange waits for peers, whose own locks
        may be held for another step."""
        with _op_scope(snapshot.trace_op):
            with self._after_commit_lock:
                with trace_annotation(
                    metric_names.SPAN_MANAGER_INDEX, step=step, on=on
                ):
                    self._commit_step(
                        step,
                        refs=lambda: referenced_steps(
                            snapshot.metadata.manifest
                        ),
                        metric=metric,
                        chunk_refs=lambda: _manifest_chunk_refs(
                            snapshot.metadata.manifest
                        ),
                    )
                # Indexed with its references: the index holds the base.
                self._bases_in_flight.pop(step, None)
                telemetry.metrics().counter_inc(
                    metric_names.MANAGER_SAVES_TOTAL
                )
                with trace_annotation(
                    metric_names.SPAN_TELEMETRY_REPORT, kind="step", step=step
                ):
                    self._record_step_history(step)
                    self._post_step_ledger(step, snapshot)
                    self._evaluate_slos(step)
                self._publish_cdn_step(step, snapshot)
            with trace_annotation(metric_names.SPAN_MANAGER_TUNE, step=step):
                return self._autotune_step(step, snapshot)

    def _install_tuned(
        self,
        step: int,
        snapshot: Snapshot,
        decided: Optional[Dict[str, Any]],
    ) -> None:
        """Install the vector the tuner decided after ``step`` committed
        (None: nothing to install), on the thread that called ``save()``
        / ``wait()``: overrides change a take's geometry, so they land
        between two takes in program order on every rank, which a commit
        thread cannot promise. A handle that is never waited for
        installs nothing."""
        if decided is None:
            return
        with _op_scope(snapshot.trace_op), trace_annotation(
            metric_names.SPAN_MANAGER_TUNE, step=step
        ):
            try:
                from .tuner import tunables

                tunables.apply_vector(decided)
            except Exception as e:  # noqa: BLE001 - tuning is best-effort
                logger.warning(
                    "autotuner: could not install the vector decided "
                    "after step %d: %r",
                    step,
                    e,
                )

    @staticmethod
    def _validate_metric(metric: Optional[float]) -> None:
        """NaN/inf poison min()/sort comparisons, silently selecting a
        diverged checkpoint as 'best' — reject them at the API boundary."""
        if metric is None:
            return
        import math

        if not math.isfinite(float(metric)):
            raise ValueError(
                f"metric must be finite, got {metric!r} (a diverged "
                f"eval score must not enter best-checkpoint retention)"
            )

    def async_save(
        self,
        step: int,
        app_state: AppState,
        incremental: Optional[bool] = None,
        metric: Optional[float] = None,
        **take_kwargs: Any,
    ) -> _PendingManagedSnapshot:
        """Pipelined checkpoint. The index entry, the retention pass and
        the step's history and ledger rows follow the commit marker on
        the take's commit thread (``_after_commit``): a committed step
        is visible to ``restore_latest`` once ``done()`` reads true,
        and ``wait()`` only joins and installs the tuner's move."""
        self._validate_metric(metric)
        take_kwargs = self._incremental_take_kwargs(
            step, incremental, take_kwargs
        )
        handle = _PendingManagedSnapshot(self, step, metric=metric)
        try:
            handle._pending = Snapshot.async_take(
                self.step_path(step),
                app_state,
                pg=self._pg_arg,
                _after_commit=lambda snapshot: handle._after_commit(
                    snapshot, "commit"
                ),
                **take_kwargs,
            )
        except BaseException:
            self._bases_in_flight.pop(step, None)
            raise
        return handle

    def _record_step_history(self, step: int) -> None:
        """Append the just-committed step's telemetry summary to the
        manager root's rolling history (``.telemetry-history.jsonl``),
        the input ``doctor --trend`` baselines against. Rank 0 only;
        best-effort (history must never fail a save); knob-bounded
        (TORCHSNAPSHOT_TPU_HISTORY_MAX_RECORDS, <= 0 disables)."""
        if self._pg.get_rank() != 0:
            return
        try:
            from .telemetry import history, last_report

            # Path-keyed lookup: overlapping async saves each find their
            # own step's report, never whichever commit thread emitted
            # last.
            report = last_report(
                "take", "async_take", path=self.step_path(step)
            )
            if report is None:
                return
            history.append_summary(
                self.root, history.summarize_report(report, step=step)
            )
        except Exception as e:  # noqa: BLE001 - history is best-effort
            logger.warning(
                "could not record step %d telemetry history: %r", step, e
            )

    def _post_step_ledger(self, step: int, snapshot: Snapshot) -> None:
        """Post the just-committed step to the run ledger: the
        retention-visible moment, with the step's storage accounting —
        bytes newly written vs. referenced from an incremental base
        (the reuse ratio the goodput engine's storage-cost curve
        reports) — then refresh the run-so-far ``goodput_*`` gauges.
        Rank 0 only; best-effort (the ledger must never fail a save)."""
        if self._pg.get_rank() != 0 or not knobs.is_ledger_enabled():
            return
        try:
            from .fsck import blob_requirements
            from .telemetry import last_report
            from .telemetry import ledger as run_ledger
            from .telemetry import names as event_names
            from .telemetry.goodput import publish_gauges

            need = blob_requirements(snapshot.metadata.manifest)
            bytes_new = sum(
                n for loc, n in need.items() if not loc.startswith("../")
            )
            bytes_reused = sum(
                n for loc, n in need.items() if loc.startswith("../")
            )
            fields: Dict[str, Any] = {
                "step": step,
                "bytes_new": int(bytes_new),
                "bytes_reused": int(bytes_reused),
                "bytes_total": int(bytes_new + bytes_reused),
                "blobs": len(need),
            }
            # CAS steps: every data location is a ``../chunks/`` ref, so
            # the prefix split above cannot see new vs. reused — replace
            # it with the EXACT per-chunk accounting the commit's
            # refcount pin computed (chunks already pinned = reused).
            acct = self._last_cas_accounting
            if acct is not None and acct.get("step") == step:
                fields.update(
                    cas=True,
                    bytes_new=acct["bytes_new"],
                    bytes_reused=acct["bytes_reused"],
                    bytes_total=acct["bytes_total"],
                    chunks_new=acct["chunks_new"],
                    chunks_reused=acct["chunks_reused"],
                )
            # How much of the state the on-device digests say was
            # UNCHANGED since the previous committed step — the
            # ``dedup-ineffective`` doctor rule compares this against
            # the realized reuse ratio (unchanged bytes that were
            # nevertheless re-stored mean the dedup path is broken).
            cur_digests = _manifest_digest_map(snapshot.metadata.manifest)
            if cur_digests:
                prev = self._prev_digest_map
                unchanged = sum(
                    n
                    for k, (d, n) in cur_digests.items()
                    if prev.get(k, (None, 0))[0] == d
                )
                fields["bytes_digest_unchanged"] = int(unchanged)
                fields["bytes_digest_covered"] = int(
                    sum(n for _, n in cur_digests.values())
                )
            self._prev_digest_map = cur_digests
            report = last_report(
                "take", "async_take", path=self.step_path(step)
            )
            if report is not None:
                fields["kind"] = report.kind
                fields["take_s"] = round(
                    max(report.phases.values(), default=0.0), 6
                )
            run_ledger.post_event(
                self.root, event_names.EVENT_STEP_COMMITTED, **fields
            )
            publish_gauges(self.root)
        except Exception as e:  # noqa: BLE001 - ledger is best-effort
            logger.warning(
                "could not post step %d to the run ledger: %r", step, e
            )

    def _evaluate_slos(self, step: int) -> None:
        """Re-judge the declared SLOs against the run's recorded
        evidence at the retention-visible moment (telemetry/slo.py):
        refreshes the burn-rate gauges, posts an edge-triggered
        ``slo-breach`` ledger event per objective episode, and captures
        one incident bundle per evaluation that saw a fresh breach.
        Rank 0 only — the evidence it judges is rank-0-recorded;
        best-effort (a judgment must never fail a save)."""
        if (
            self._pg.get_rank() != 0
            or not knobs.is_slo_enabled()
            or not knobs.is_ledger_enabled()
        ):
            return
        try:
            from .telemetry import slo

            slo.evaluate_step(self.root, step)
        except Exception as e:  # noqa: BLE001 - the SLO engine is best-effort
            logger.warning(
                "could not evaluate SLOs at step %d: %r", step, e
            )

    def _publish_cdn_step(self, step: int, snapshot: Snapshot) -> None:
        """Announce the just-committed step's chunk set on the CDN
        topic (docs/cdn.md). Rank 0 only, post-commit only — the
        announce's chunks are already durable by construction. Steps
        without content-addressed chunks (CAS off) have nothing a
        fleet can dedup-pull, so they are skipped, not half-announced.
        Best-effort: a publish failure degrades serving freshness,
        never the save."""
        if (
            self.cdn_topic is None
            or self._pg.get_rank() != 0
            or not knobs.is_cdn_enabled()
        ):
            return
        try:
            chunks = _manifest_chunk_refs(snapshot.metadata.manifest)
            if not chunks:
                logger.debug(
                    "cdn: step %d carries no CAS chunks; not published",
                    step,
                )
                return
            if self._cdn_publisher is None:
                store = (
                    self._cdn_store_arg
                    if self._cdn_store_arg is not None
                    else self._pg.store
                )
                if store is None:
                    logger.warning(
                        "cdn: topic %r configured but no coordination "
                        "store is reachable; steps will not be published",
                        self.cdn_topic,
                    )
                    self.cdn_topic = None
                    return
                from .cdn import CdnPublisher

                self._cdn_publisher = CdnPublisher(
                    store,
                    self.cdn_topic,
                    publisher_id=f"rank0@{self.root}",
                    root=self.root,
                )
            self._cdn_publisher.publish(step, chunks)
        except Exception as e:  # noqa: BLE001 - publishing is best-effort
            logger.warning("cdn: could not publish step %d: %r", step, e)

    def _autotune_step(
        self, step: int, snapshot: Snapshot
    ) -> Optional[Dict[str, Any]]:
        """One closed-loop tuning pass after ``step`` committed: rank 0
        reads the step's report and decides the next knob vector, every
        rank receives it (tuner/autotuner.py) and returns it for
        ``_install_tuned``. The exchange is keyed by the take's nonce,
        not by a place in the process group's op sequence: on a commit
        thread it falls between the caller's collectives in an order
        the ranks do not share. The TORCHSNAPSHOT_TPU_AUTOTUNE=0 kill
        switch must be set uniformly across ranks (like every
        geometry-affecting knob) — with it, this is a pure no-op.
        Best-effort: tuning must never fail a save."""
        if not knobs.is_autotune_enabled():
            return None
        try:
            if self._autotuner is None:
                with self._after_commit_lock:  # two commit threads, one tuner
                    if self._autotuner is None:
                        from .tuner import Autotuner

                        self._autotuner = Autotuner(self.root)
            report = None
            if self._pg.get_rank() == 0:
                from .telemetry import last_report

                report = last_report(
                    "take", "async_take", path=self.step_path(step)
                )
            return self._autotuner.decide_after_step(
                step,
                report,
                self._pg.keyed(f"tuner/{snapshot.commit_nonce}"),
            )
        except Exception as e:  # noqa: BLE001 - tuning is best-effort
            logger.warning(
                "autotuner: skipped tuning after step %d: %r", step, e
            )
            return None

    # ------------------------------------------------------------------
    # resuming
    # ------------------------------------------------------------------

    def all_steps(self) -> List[int]:
        """Committed steps, ascending. Every rank may call this; the index
        blob is tiny."""
        return self._read_index()

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def best_step(self) -> Optional[int]:
        """The committed step with the best recorded metric (``best_mode``
        ordering; newest wins ties), or None when no step has one."""
        index = self._with_root_storage(self._read_index_full_async)
        scored = [s for s in index["steps"] if str(s) in index["metrics"]]
        if not scored:
            return None
        return min(
            scored, key=lambda s: self._metric_sort_key(s, index["metrics"])
        )

    def restore_best(self, app_state: AppState) -> Optional[int]:
        """Restore the best-metric committed step; returns it, or None if
        no step carries a metric. Rank 0 resolves, everyone follows."""
        step = self.best_step() if self._pg.get_rank() == 0 else None
        step = self._pg.broadcast_object(step)
        if step is None:
            return None
        self.restore(step, app_state)
        return step

    def restore(self, step: int, app_state: AppState) -> None:
        snapshot = Snapshot(self.step_path(step), pg=self._pg_arg)
        snapshot.restore(app_state)
        telemetry.metrics().counter_inc(metric_names.MANAGER_RESTORES_TOTAL)
        self._record_restore_history(step, snapshot.trace_op)

    def _record_restore_history(self, step: int, trace_op: int = 0) -> None:
        """Append the just-served restore's telemetry summary to the
        same rolling history takes feed — recovery time is a trend
        metric too (``doctor --trend`` baselines per kind, so restore
        rows never pollute take baselines). Rank 0 only; best-effort.
        Inside the caller's restore time, so a stage of that restore
        (``trace_op``)."""
        if self._pg.get_rank() != 0:
            return
        try:
            from .telemetry import history, last_report

            with _op_scope(trace_op), trace_annotation(
                metric_names.SPAN_TELEMETRY_REPORT, kind="step", step=step
            ):
                report = last_report(
                    "restore", "async_restore", path=self.step_path(step)
                )
                if report is None:
                    return
                history.append_summary(
                    self.root, history.summarize_report(report, step=step)
                )
        except Exception as e:  # noqa: BLE001 - history is best-effort
            logger.warning(
                "could not record step %d restore history: %r", step, e
            )

    def restore_latest(self, app_state: AppState) -> Optional[int]:
        """Restore the newest committed step into ``app_state``; returns
        its step number, or None when no checkpoint exists (fresh run).
        Rank 0 resolves the step and everyone follows — ranks must never
        resume from different steps."""
        step = self.latest_step() if self._pg.get_rank() == 0 else None
        step = self._pg.broadcast_object(step)
        if step is None:
            return None
        self.restore(step, app_state)
        return step

    def async_restore(self, step: int, app_state: AppState):
        """Pipelined restore of ``step`` (Snapshot.async_restore): reads
        run in the background; call ``.wait()`` to apply."""
        pending = Snapshot(self.step_path(step), pg=self._pg_arg).async_restore(
            app_state
        )
        # Counted at initiation (the wait handle is Snapshot-level):
        # async resumes must move the same counter sync ones do.
        telemetry.metrics().counter_inc(metric_names.MANAGER_RESTORES_TOTAL)
        return _ManagedPendingRestore(self, step, pending)

    def async_restore_latest(self, app_state: AppState):
        """Kick off a pipelined restore of the newest committed step;
        returns ``(step, PendingRestore)`` or ``None`` on a fresh run.
        Overlap jit compilation with the reads, then ``wait()``."""
        step = self.latest_step() if self._pg.get_rank() == 0 else None
        step = self._pg.broadcast_object(step)
        if step is None:
            return None
        return step, self.async_restore(step, app_state)

    # ------------------------------------------------------------------
    # index + retention (rank 0 only; peers observe via the index blob)
    # ------------------------------------------------------------------

    def _with_root_storage(self, coro_fn):
        """Run ``coro_fn(storage)`` against the manager root in a fresh
        event loop, closing the plugin on every path."""

        async def body():
            storage = url_to_storage_plugin(self.root)
            try:
                return await coro_fn(storage)
            finally:
                await storage.close()

        return run_in_fresh_event_loop(body())

    def _commit_step(
        self,
        step: int,
        refs: Optional[Any] = None,
        metric: Optional[float] = None,
        chunk_refs: Optional[Any] = None,
    ) -> None:
        """``refs``/``chunk_refs`` may be values or zero-arg callables.
        Pass callables when computing them requires the snapshot
        manifest: they are evaluated only on rank 0, after the early
        return — non-leader ranks hold no in-memory metadata and must
        not pull the global manifest from storage just to drop it."""
        if self._pg.get_rank() != 0:
            return
        if callable(refs):
            refs = refs()
        if callable(chunk_refs):
            chunk_refs = chunk_refs()
        self._with_root_storage(
            lambda storage: self._commit_step_async(
                step, storage, refs or set(), metric, chunk_refs or {}
            )
        )

    def _retained(
        self, steps: List[int], just_saved: int, metrics: Dict[str, float]
    ) -> List[int]:
        """Retention policy: newest ``keep_last_n`` ∪ best ``keep_best_n``
        (by recorded metric) ∪ the just-saved step (never GC'd in its own
        commit — a rollback may produce a numerically-old step).

        With ``keep_best_n`` alone (``keep_last_n=None``), only *scored*
        steps compete for deletion: unscored steps are all retained, so
        enabling metric retention never silently GCs checkpoints that
        were saved without a metric."""
        if self.keep_last_n is None and self.keep_best_n is None:
            return list(steps)
        keep: Set[int] = set()
        if self.keep_last_n is not None:
            keep.update(steps[-self.keep_last_n :])
        if self.keep_best_n is not None:
            scored = [s for s in steps if str(s) in metrics]
            scored.sort(key=lambda s: self._metric_sort_key(s, metrics))
            keep.update(scored[: self.keep_best_n])
            if self.keep_last_n is None:
                keep.update(s for s in steps if str(s) not in metrics)
        if just_saved not in keep:
            # A step-counter reset/rollback produced a numerically-old (or
            # metric-poor) step: keep it anyway, loudly — operators need
            # the signal that the index now mixes numbering epochs.
            logger.warning(
                "Just-saved step %d falls outside the retention policy "
                "(retained: %s); keeping it anyway — the just-saved "
                "checkpoint is never deleted",
                just_saved,
                sorted(keep),
            )
            keep.add(just_saved)
        return [s for s in steps if s in keep]

    def _metric_sort_key(self, step: int, metrics: Dict[str, float]):
        """One ordering for retention AND best_step()/restore_best(), so
        they can never disagree about which step is 'best': best metric
        first (mode-signed), newest step wins ties."""
        sign = 1.0 if self.best_mode == "min" else -1.0
        return (sign * metrics[str(step)], -step)

    async def _commit_step_async(
        self,
        step: int,
        storage: StoragePlugin,
        refs: Set[int],
        metric: Optional[float] = None,
        chunk_refs: Optional[Dict[str, int]] = None,
    ) -> None:
        index = await self._read_index_full_async(storage)
        steps = [s for s in index["steps"] if s != step]
        steps.append(step)
        steps.sort()
        refs_map: Dict[str, List[int]] = dict(index["refs"])
        if refs:
            refs_map[str(step)] = sorted(refs)
        else:
            refs_map.pop(str(step), None)
        # CAS refcounts: pin the step's chunks BEFORE the index write —
        # a crash between the two leaves a pinned-but-uncommitted step
        # (garbage retained until reconcile), never an indexed step
        # whose chunks a racing GC could reclaim. The pin also yields
        # the step's exact storage accounting (chunks already live =
        # reused bytes) for the run ledger.
        self._last_cas_accounting = self._cas_pin_step(
            step, chunk_refs or {}
        )
        metrics: Dict[str, float] = dict(index["metrics"])
        if metric is not None:
            metrics[str(step)] = float(metric)
        else:
            metrics.pop(str(step), None)
        pinned: Set[int] = set(index["pinned"])
        evicted: Set[int] = set(index["evicted"])
        evicted.discard(step)  # a re-saved step is fast-resident again

        retained = self._retained(steps, step, metrics)
        dropped = [s for s in steps if s not in retained]
        steps = retained

        # Explicit retention check (the orphaned-base guard): in an
        # index NOT marked ``refs_complete`` — written before
        # incremental refs existed — a retained step's missing refs
        # entry means "unknown", not "none": presuming it ref-free
        # while GC deletes bases would leave its ``../step_*``
        # locations dangling, with fsck the only thing that would ever
        # notice. Re-derive refs from each such step's own manifest,
        # exactly once: every index this version writes carries the
        # marker, under which absence soundly means verified-empty.
        if not index["refs_complete"]:
            for s in steps:
                if str(s) not in refs_map:
                    derived = await self._derive_refs_async(storage, s)
                    if derived:
                        refs_map[str(s)] = sorted(derived)

        # Pin-or-delete: a dropped (or previously pinned) step whose blobs
        # a *retained* step's manifest still references must keep its
        # blobs. Refs name origin steps directly (chained refs collapse at
        # take time), so one pass over retained steps' ref lists is the
        # full liveness set — pins don't propagate.
        needed: Set[int] = set()
        for s in steps:
            needed.update(refs_map.get(str(s), ()))
        # And the base of a take of this manager still in flight, with
        # the origins its base references: its manifest will name them,
        # the index cannot yet. It is pinned here like any referenced
        # step, and dropped by a later pass if the take referenced
        # nothing of it or failed.
        for saving, base in list(self._bases_in_flight.items()):
            if saving != step:
                needed.add(base)
                needed.update(refs_map.get(str(base), ()))
        to_delete: List[int] = []
        for old in dropped:
            if old in needed:
                pinned.add(old)
            else:
                to_delete.append(old)
        for p in sorted(pinned):
            if p not in needed:
                pinned.discard(p)
                to_delete.append(p)
        for gone in to_delete:
            refs_map.pop(str(gone), None)
            metrics.pop(str(gone), None)
            evicted.discard(gone)

        # Fast-tier eviction pass (tiered roots with keep_fast_last_n):
        # surviving steps beyond the newest N — pinned incremental origins
        # included — lose their fast-tier copies once durable-complete.
        # Eviction is attempted before the index write so the recorded
        # evicted set never claims a step this pass failed to evict.
        if self.keep_fast_last_n is not None:
            hot = set(steps[-self.keep_fast_last_n :])
            hot.add(step)
            candidates = [
                s
                for s in sorted(set(steps) | pinned)
                if s not in hot and s not in evicted
            ]
            for old in candidates:
                try:
                    if await self._evict_fast_async(old):
                        evicted.add(old)
                except Exception as e:  # noqa: BLE001 - must not fail a save
                    logger.warning(
                        "Failed to evict step %d from the fast tier: %r",
                        old,
                        e,
                    )

        await self._write_index_async(
            steps, storage, refs=refs_map, pinned=sorted(pinned),
            metrics=metrics, evicted=sorted(evicted),
        )
        registry = telemetry.metrics()
        registry.gauge_set(metric_names.MANAGER_RETAINED_STEPS, len(steps))
        if to_delete:
            registry.counter_inc(
                metric_names.MANAGER_GC_STEPS_TOTAL, len(to_delete)
            )
        # Recorder-only, like ``pipeline:*``: the span crosses awaits
        # (utils/tracing.py's rule for the dual annotation).
        with _trace_recorder().span(
            metric_names.SPAN_MANAGER_RETENTION, step=step, steps=len(to_delete)
        ):
            for old in to_delete:
                try:
                    await self._delete_step_async(old)
                except Exception as e:  # noqa: BLE001 - GC must not fail a save
                    logger.warning("Failed to GC step %d: %r", old, e)
            # Chunk-store GC: unpin the deleted steps and reclaim chunks
            # no pinned step references (grace-window + orphan deferral
            # inside). Runs AFTER the step deletes so an interrupted pass
            # errs toward retaining chunks, never toward dangling refs.
            # Runs on EVERY commit, not only ones that dropped steps —
            # grace-deferred orphans and crashed takes' strays must age
            # out even in runs whose retention never deletes anything
            # (keep-everything, or still inside the first keep_last_n
            # saves).
            try:
                await self._cas_collect_async(storage, step, to_delete)
            except Exception as e:  # noqa: BLE001 - GC must not fail a save
                logger.warning("CAS chunk GC failed: %r", e)

    async def _derive_refs_async(
        self, storage: StoragePlugin, step: int
    ) -> Set[int]:
        """Re-derive a step's origin-step refs from its committed
        manifest (the explicit retention check for refs-less index
        entries). An unreadable manifest conservatively pins nothing
        AND nothing referencing it is deleted this pass — the read
        error propagates to the caller's warning path."""
        read_io = ReadIO(
            path=f"{_step_dirname(step)}/{SNAPSHOT_METADATA_FNAME}"
        )
        try:
            await storage.read(read_io)
        except FileNotFoundError:
            return set()
        metadata = SnapshotMetadata.from_yaml(bytes(read_io.buf).decode())
        return referenced_steps(metadata.manifest)

    # ------------------------------------------------------------------
    # content-addressed chunk store (docs/cas.md; rank 0 only)
    # ------------------------------------------------------------------

    def _get_cas_store(self):
        """The root's chunk store handle, or None for roots without a
        local filesystem tier. Resolved once; cheap for legacy roots
        (the journal load of a nonexistent file is one failed open)."""
        if self._cas_store is not False:
            return self._cas_store
        from .cas import CASStore, local_chunks_dir

        if local_chunks_dir(self.root) is None:
            self._cas_store = None
        else:
            self._cas_store = CASStore(self.root)
        return self._cas_store

    def _cas_pin_step(
        self, step: int, chunk_refs: Dict[str, int]
    ) -> Optional[Dict[str, Any]]:
        """Pin a committing step's chunks in the refcount journal and
        return its exact storage accounting (bytes newly materialized
        vs. reused from already-pinned chunks). None for legacy steps
        (no chunk refs) — the journal is never created for them."""
        store = self._get_cas_store()
        if store is None or not chunk_refs:
            return None
        pins, orphans = store.load()
        pinned_before: Set[str] = set()
        for s, chunks in pins.items():
            if s != step:
                pinned_before.update(chunks)
        reused = {
            k: n for k, n in chunk_refs.items() if k in pinned_before
        }
        new = {
            k: n for k, n in chunk_refs.items() if k not in pinned_before
        }
        store.pin(step, chunk_refs)
        # Kill point: pinned-but-unindexed (the index write is still
        # pending) — construction-time reconcile must unpin on reload.
        _crashpoint(metric_names.CRASH_REFCOUNT_PINNED)
        # Chunks resurrected from the orphan (grace-deferred) list are
        # live again: drop them from it so GC stops considering them.
        revived = set(chunk_refs) & set(orphans)
        if revived:
            store.clear_orphans(revived)
        return {
            "step": step,
            "chunks_new": len(new),
            "chunks_reused": len(reused),
            "bytes_new": int(sum(new.values())),
            "bytes_reused": int(sum(reused.values())),
            "bytes_total": int(sum(chunk_refs.values())),
        }

    async def _cas_collect_async(
        self,
        storage: StoragePlugin,
        trigger_step: int,
        deleted_steps: List[int],
    ) -> None:
        """Unpin GC'd steps and reclaim refcount-dead chunks. A dead
        chunk younger than the grace window is deferred as a journaled
        orphan (a concurrent not-yet-pinned take may have just deduped
        against it — its touch keeps the mtime fresh) and retried on a
        later pass. Reclaimed bytes are posted to the run ledger so the
        goodput storage curve tracks what retention actually keeps."""
        from .cas import CHUNKS_DIRNAME

        store = self._get_cas_store()
        if store is None:
            return
        pins, orphans, leases = store.load_full()
        candidates: Dict[str, int] = dict(orphans)
        unpinned = False
        for old in deleted_steps:
            chunks = pins.pop(old, None)
            if chunks is not None:
                store.unpin(old)
                unpinned = True
                candidates.update(chunks)
        if unpinned:
            # Kill point: steps unpinned, reclaim deletes still pending
            # (dead chunks must age out via grace/stray sweeps, never
            # dangle).
            _crashpoint(metric_names.CRASH_GC_UNPINNED)
        # Leases (CDN subscriber pins) count as live: a serving fleet's
        # durable copy source must survive step retention until the
        # fleet re-leases without it.
        live = store.live_chunks(pins, leases)
        # Stray sweep: on-disk chunks in NO pin and NO orphan record —
        # a take that crashed before its commit pinned them, or pins
        # reconcile dropped. Without this they would never become GC
        # candidates (candidates are otherwise journal-derived only)
        # and leak forever. Folding them into this pass is safe for a
        # concurrent in-flight take: its fresh chunks defer through the
        # grace window below, and its commit's pin revives them from
        # the orphan list.
        for key, nbytes in store.list_chunks().items():
            if key not in live and key not in candidates:
                candidates[key] = nbytes
        if not candidates:
            if unpinned:
                store.maybe_compact()
            return
        grace = knobs.get_cas_gc_grace_seconds()
        reclaimed: Dict[str, int] = {}
        cleared: Set[str] = set()
        deferred: Dict[str, int] = {}
        for key, nbytes in candidates.items():
            if key in live:
                cleared.add(key)  # re-pinned since it was orphaned
                continue
            age = store.chunk_age_seconds(key)
            if age is None:
                cleared.add(key)  # already gone (fsck/manual cleanup)
                continue
            if grace > 0 and age < grace:
                deferred[key] = nbytes
                continue
            try:
                await storage.delete(f"{CHUNKS_DIRNAME}/{key}")
            except FileNotFoundError:
                pass
            reclaimed[key] = nbytes
        store.clear_orphans((cleared | set(reclaimed)) & set(orphans))
        store.record_orphans(
            {k: n for k, n in deferred.items() if k not in orphans}
        )
        store.maybe_compact()
        if reclaimed:
            registry = telemetry.metrics()
            registry.counter_inc(
                metric_names.CAS_CHUNKS_RECLAIMED_TOTAL, len(reclaimed)
            )
            registry.counter_inc(
                metric_names.CAS_BYTES_RECLAIMED_TOTAL,
                sum(reclaimed.values()),
            )
            if knobs.is_ledger_enabled():
                try:
                    from .telemetry import ledger as run_ledger
                    from .telemetry import names as event_names

                    run_ledger.post_event(
                        self.root,
                        event_names.EVENT_GC_RECLAIMED,
                        step=trigger_step,
                        bytes_reclaimed=int(sum(reclaimed.values())),
                        blobs=len(reclaimed),
                        chunks=True,
                    )
                except Exception as e:  # noqa: BLE001 - best-effort
                    logger.warning(
                        "could not post chunk GC to the run ledger: %r", e
                    )
        if deferred:
            logger.info(
                "CAS GC deferred %d dead-but-fresh chunk(s) inside the "
                "%.0fs grace window (a concurrent take may hold them); "
                "a later pass reclaims them",
                len(deferred),
                grace,
            )

    def _reconcile_cas(self) -> None:
        """Construction-time healing (rank 0): bring the refcount
        journal in line with the index + manifests. Covers a crash that
        lost or tore the journal after steps committed (chunks written,
        refcount append missing — wholesale OR one step's pin lost
        while other pins survived), and stale pins of steps that left
        the index. No-op — zero manifest reads — when the root has no
        chunk store, the store is empty, or every indexed step's pin
        state already matches the journal."""
        import os as _os

        store = self._get_cas_store()
        if store is None or not _os.path.isdir(store.local_dir):
            return
        from .cas import chunk_refs as _chunk_refs

        pins, _ = store.load()
        if not pins and not store.list_chunks():
            return  # empty store: nothing pinned, nothing on disk
        index = self._with_root_storage(self._read_index_full_async)
        expected = set(index["steps"]) | set(index["pinned"])
        stale_pins = set(pins) - expected
        # Indexed steps with NO pin record: a legacy-layout step (no
        # chunk refs — absence from the journal IS canonical) or a
        # committed CAS step whose pin append was lost or torn while
        # OTHER pins survived (partial journal damage). Only the
        # manifest can tell them apart, and guessing wrong would let
        # the stray sweep reclaim a committed step's chunks — so read
        # exactly these manifests and re-derive. Steps whose pin record
        # survived are trusted as-is (the pin was derived from the same
        # manifest at commit time).
        missing_pins = expected - set(pins)
        if not stale_pins and not missing_pins:
            return

        async def _refs_of_missing(storage: StoragePlugin):
            mapping: Dict[int, Dict[str, int]] = {}
            for s in sorted(missing_pins):
                read_io = ReadIO(
                    path=f"{_step_dirname(s)}/{SNAPSHOT_METADATA_FNAME}"
                )
                try:
                    await storage.read(read_io)
                except FileNotFoundError:
                    mapping[s] = {}
                    continue
                metadata = SnapshotMetadata.from_yaml(
                    bytes(read_io.buf).decode()
                )
                mapping[s] = _chunk_refs(metadata.manifest)
            return mapping

        mapping = self._with_root_storage(_refs_of_missing)
        for s in expected & set(pins):
            mapping[s] = pins[s]
        if store.reconcile(mapping):
            logger.info(
                "CAS refcount journal reconciled against the index "
                "(%d committed/pinned steps)",
                len(expected),
            )

    async def _read_index_async(self, storage: StoragePlugin) -> List[int]:
        return (await self._read_index_full_async(storage))["steps"]

    async def _read_index_full_async(
        self, storage: StoragePlugin
    ) -> Dict[str, Any]:
        return await read_index_full_async(storage)

    async def _write_index_async(
        self,
        steps: List[int],
        storage: StoragePlugin,
        refs: Optional[Dict[str, List[int]]] = None,
        pinned: Optional[List[int]] = None,
        metrics: Optional[Dict[str, float]] = None,
        evicted: Optional[List[int]] = None,
    ) -> None:
        payload_obj: Dict[str, Any] = {"steps": steps}
        if steps:
            # Under this marker, a step's ABSENT refs entry soundly
            # means verified-empty — the GC retention check re-derives
            # refs from manifests only for unmarked (older) indexes.
            payload_obj["refs_complete"] = True
        if refs:
            payload_obj["refs"] = refs
        if pinned:
            payload_obj["pinned"] = pinned
        if metrics:
            payload_obj["metrics"] = metrics
        if evicted:
            payload_obj["evicted"] = evicted
        payload = json.dumps(payload_obj).encode()
        # Backup FIRST, primary second. With this order a torn *primary*
        # write always leaves a valid new backup behind it, and a torn
        # backup write leaves the previous (valid, one-save-stale) primary
        # — consistent with the caller's view, since the save never
        # returned. It also makes "corrupt primary + absent backup"
        # impossible except for a torn first-ever index write, which is
        # what _read_index_async's recovery rule assumes.
        await storage.write(WriteIO(path=INDEX_BACKUP_BLOB, buf=payload))
        # Kill point: the torn pair — a valid NEW backup behind a stale
        # primary, the exact state the read-side recovery rule assumes.
        _crashpoint(metric_names.CRASH_INDEX_BACKUP_WRITTEN)
        await storage.write(WriteIO(path=INDEX_BLOB, buf=payload))
        _crashpoint(metric_names.CRASH_INDEX_WRITTEN)

    def _read_index(self) -> List[int]:
        return self._with_root_storage(self._read_index_async)

    async def _evict_fast_async(self, step: int) -> bool:
        """Drop one step's FAST-tier copy (tiered roots only); the step
        stays committed and restorable via the per-blob durable fallback.
        Returns True when evicted, False when the step is not yet safe to
        evict (durable commit marker absent — the mirror is still
        working, or failed and will resume)."""
        from .integrity import table_path
        from .tiered.journal import MirrorJournal
        from .tiered.mirror import is_durable_async
        from .tiered.plugin import TieredStoragePlugin

        path = self.step_path(step)
        if not await is_durable_async(path):
            return False
        storage = url_to_storage_plugin(path)
        try:
            if not isinstance(storage, TieredStoragePlugin):
                return False
            # The durable manifest is authoritative for what to remove
            # (the fast copy may already be partial).
            read_io = ReadIO(path=SNAPSHOT_METADATA_FNAME)
            await storage.durable.read(read_io)
            metadata = SnapshotMetadata.from_yaml(bytes(read_io.buf).decode())
            locations: Set[str] = set()
            for entry in metadata.manifest.values():
                locations.update(_entry_locations(entry))
            locations = {l for l in locations if not l.startswith("../")}
            from .cas import chunk_map_path

            for rank in range(metadata.world_size):
                locations.add(table_path(rank))
                locations.add(chunk_map_path(rank))

            async def _drop(location: str) -> None:
                try:
                    await storage.fast.delete(location)
                except FileNotFoundError:
                    pass

            # Commit marker first (deletion discipline shared with
            # _delete_step_async), then data, then the journal. The
            # telemetry event log and progress heartbeats are not
            # manifest-named; drop them explicitly or every evicted
            # step leaks files.
            from .telemetry.progress import SNAPSHOT_PROGRESS_PREFIX
            from .telemetry.sink import SNAPSHOT_EVENTS_BASENAME

            from .tiered.peer import placement_doc_path

            await _drop(SNAPSHOT_METADATA_FNAME)
            await _drop(SNAPSHOT_EVENTS_BASENAME)
            for rank in range(metadata.world_size):
                await _drop(f"{SNAPSHOT_PROGRESS_PREFIX}{rank}.json")
                await _drop(placement_doc_path(rank))
            slots = asyncio.Semaphore(knobs.get_per_rank_io_concurrency())

            async def _drop_slotted(location: str) -> None:
                async with slots:
                    await _drop(location)

            results = await asyncio.gather(
                *(_drop_slotted(l) for l in sorted(locations)),
                return_exceptions=True,
            )
            for r in results:
                if isinstance(r, BaseException):
                    raise r
            await MirrorJournal(blobs={}).delete(storage.fast)
        finally:
            await storage.close()
        logger.info("Evicted step %d from the fast tier", step)
        return True

    def wait_durable(
        self, step: int, timeout: Optional[float] = None
    ) -> None:
        """Durability barrier: block until ``step`` is fully mirrored to
        the durable tier AND the durable tier's index names it — i.e.
        until the durable tier alone could serve ``restore_latest``.
        Immediate no-op for non-tiered roots (their commit was the
        durable write). Raises ``TimeoutError`` on deadline, and
        re-raises a failed mirror's error (the fast tier remains
        restorable; the journal resumes the upload).

        ``timeout=None`` (the default) is NOT unbounded: it resolves to
        the ``TORCHSNAPSHOT_TPU_WAIT_DURABLE_TIMEOUT_SECONDS`` knob
        (default 30 min) so a wedged durable tier surfaces as a clear
        ``TimeoutError`` instead of a silent poll loop the stall
        watchdog is the only escape from. A non-positive knob value
        restores the unbounded wait, explicitly."""
        import time as _time

        from .tiered.mirror import wait_durable as _wait_durable

        if timeout is None:
            default_timeout = knobs.get_wait_durable_timeout_seconds()
            timeout = default_timeout if default_timeout > 0 else None
        tiers = split_tiered_url(self.root)
        deadline = (
            _time.monotonic() + timeout if timeout is not None else None
        )
        _wait_durable(self.step_path(step), timeout=timeout)
        if tiers is None:
            return
        fast_root, durable_root = tiers
        from .tiered.mirror import get_mirror

        mirror = get_mirror()
        resumed_root = False
        while True:

            async def _read_durable_index(_url=durable_root):
                storage = url_to_storage_plugin(_url)
                try:
                    return await self._read_index_full_async(storage)
                finally:
                    await storage.close()

            try:
                index = run_in_fresh_event_loop(_read_durable_index())
                if step in index["steps"]:
                    return
            except (FileNotFoundError, RuntimeError):
                pass  # index not mirrored yet
            # The index trails through the ROOT's own mirror jobs: if the
            # newest one failed and nothing is in flight, polling would
            # never progress — resume it once, then surface its error.
            root_jobs = mirror.jobs_for(fast_root)
            if root_jobs and all(j.done_evt.is_set() for j in root_jobs):
                if root_jobs[-1].error is not None:
                    if not resumed_root:
                        resumed_root = True
                        mirror.resume(self.root)
                    else:
                        raise RuntimeError(
                            f"step {step} is durable, but mirroring the "
                            f"manager index keeps failing; the fast tier "
                            f"remains authoritative and resume_mirrors() "
                            f"retries the upload"
                        ) from root_jobs[-1].error
            if deadline is not None and _time.monotonic() >= deadline:
                raise TimeoutError(
                    f"step {step} durable, but the durable index does not "
                    f"name it within {timeout}s"
                )
            _time.sleep(0.05)

    def resume_mirrors(self) -> List[int]:
        """Re-enqueue interrupted durable-tier mirrors after a restart:
        every committed step whose durable commit marker is absent
        resumes from its journal (completed blobs are skipped) or, when
        no journal survived, from its fast-tier manifest. Returns the
        resumed steps. Rank 0 only (peers no-op); no-op for non-tiered
        roots."""
        if split_tiered_url(self.root) is None or self._pg.get_rank() != 0:
            return []
        from .tiered.mirror import get_mirror, is_durable

        mirror = get_mirror()
        resumed: List[int] = []
        for step in self.all_steps():
            path = self.step_path(step)
            if not is_durable(path) and mirror.resume(path) is not None:
                resumed.append(step)
        # The root's own control blobs (index slots) may also have an
        # interrupted mirror journaled.
        mirror.resume(self.root)
        return resumed

    async def _delete_step_async(self, step: int) -> None:
        """Delete a step's blobs, manifest-driven (plugins cannot list).
        The commit marker goes first: once it is gone the step is simply
        uncommitted, so a crash mid-deletion leaves garbage bytes but
        never a corrupt-looking valid snapshot."""
        from .integrity import table_path

        storage = url_to_storage_plugin(self.step_path(step))
        try:
            from .tiered.plugin import TieredStoragePlugin

            if isinstance(storage, TieredStoragePlugin) and storage.fast_url:
                # The step is leaving BOTH tiers: stop any in-flight
                # mirror first (its fast-tier source blobs are about to
                # vanish; letting it run would only fail noisily).
                from .tiered.mirror import get_mirror

                get_mirror().cancel_path(storage.fast_url)
            read_io = ReadIO(path=SNAPSHOT_METADATA_FNAME)
            try:
                await storage.read(read_io)
            except FileNotFoundError:
                return  # never committed; nothing authoritative to walk
            metadata = SnapshotMetadata.from_yaml(bytes(read_io.buf).decode())
            await storage.delete(SNAPSHOT_METADATA_FNAME)
            # Kill point: the dropped step is uncommitted but its data
            # blobs remain — garbage, never a valid-looking snapshot.
            _crashpoint(metric_names.CRASH_GC_MARKER_DELETED)
            if isinstance(storage, TieredStoragePlugin):
                from .tiered.journal import MirrorJournal

                await MirrorJournal(blobs={}).delete(storage.fast)
            # The snapshot-adjacent telemetry log and any progress
            # heartbeats (a crashed take leaves one behind) are not
            # named by the manifest; remove them with the step or GC
            # leaks files per dropped step. Shared-dir heartbeats have
            # no other reaper at all.
            from .telemetry.progress import (
                SNAPSHOT_PROGRESS_PREFIX,
                remove_dir_heartbeats,
            )
            from .telemetry.sink import SNAPSHOT_EVENTS_BASENAME

            remove_dir_heartbeats(self.step_path(step))

            try:
                await storage.delete(SNAPSHOT_EVENTS_BASENAME)
            except FileNotFoundError:
                pass  # sink was never enabled for this step
            from .tiered.peer import placement_doc_path

            for rank in range(metadata.world_size):
                try:
                    await storage.delete(
                        f"{SNAPSHOT_PROGRESS_PREFIX}{rank}.json"
                    )
                except FileNotFoundError:
                    pass  # no heartbeat recorded / already settled
                try:
                    await storage.delete(placement_doc_path(rank))
                except FileNotFoundError:
                    pass  # no peer push ever recorded placement

            locations: Set[str] = set()
            manifest: Manifest = metadata.manifest
            for entry in manifest.values():
                locations.update(_entry_locations(entry))
            # Parent-relative locations are another step's blobs (this
            # step was incremental): never delete outside the step dir.
            locations = {l for l in locations if not l.startswith("../")}
            from .cas import chunk_map_path

            for rank in range(metadata.world_size):
                locations.add(table_path(rank))
                # CAS chunk maps are step blobs too (absent for legacy
                # steps; _delete_one tolerates the miss).
                locations.add(chunk_map_path(rank))
            # Bounded-concurrent deletes: a dropped step of a large sharded
            # model has thousands of blobs, and serial object-store
            # round-trips would stall rank 0's save() for minutes.
            slots = asyncio.Semaphore(knobs.get_per_rank_io_concurrency())

            async def _delete_one(location: str) -> None:
                async with slots:
                    try:
                        await storage.delete(location)
                    except FileNotFoundError:
                        pass  # checksum tables are optional; slabs dedupe

            # return_exceptions: let every delete settle before the plugin
            # closes (a bare gather would abandon in-flight siblings to die
            # against a closing plugin), then surface the first failure.
            results = await asyncio.gather(
                *(_delete_one(l) for l in sorted(locations)),
                return_exceptions=True,
            )
            for r in results:
                if isinstance(r, BaseException):
                    raise r
        finally:
            await storage.close()
        # Peer-RAM copies of the dropped step: best-effort eviction
        # from every advertised peer cache (they self-bound via budget
        # LRU + keep_peer_last_n regardless; this reclaims promptly).
        try:
            from .tiered.peer import maybe_evict_step

            maybe_evict_step(self.step_path(step))
        except Exception as e:  # noqa: BLE001 - GC must not fail a save
            logger.warning(
                "peer tier: evicting step %d peer copies failed: %r",
                step,
                e,
            )
        self._post_gc_ledger(step, metadata.manifest)
        logger.info("Retention dropped step %d", step)

    def _post_gc_ledger(self, step: int, manifest: Manifest) -> None:
        """Record the GC'd step in the run ledger (bytes reclaimed —
        base-referenced locations belong to other steps and are not
        counted) and prune its ``step-committed`` storage records so
        the goodput storage curve tracks what retention actually
        keeps. Runs on rank 0 only (GC is rank-0 work); best-effort."""
        if not knobs.is_ledger_enabled():
            return
        try:
            from .fsck import blob_requirements
            from .telemetry import ledger as run_ledger
            from .telemetry import names as event_names

            need = blob_requirements(manifest)
            own = {
                loc: n
                for loc, n in need.items()
                if not loc.startswith("../")
            }
            run_ledger.post_event(
                self.root,
                event_names.EVENT_GC_RECLAIMED,
                step=step,
                bytes_reclaimed=int(sum(own.values())),
                blobs=len(own),
            )
            run_ledger.prune_steps(self.root, {step})
        except Exception as e:  # noqa: BLE001 - GC must not fail a save
            logger.warning(
                "could not record GC of step %d in the run ledger: %r",
                step,
                e,
            )

"""The Snapshot user API: take / async_take / restore / read_object.

Reference parity: torchsnapshot/snapshot.py (991 LoC). Same protocol shape:

- ``take``: plan → partition → execute → barrier → rank-0 commits the
  ``.snapshot_metadata`` manifest (commit-after-barrier invariant,
  reference snapshot.py:230-237 — a snapshot without the metadata file never
  happened, which is what makes interrupted takes safe).
- ``async_take``: returns a :class:`PendingSnapshot` in
  checkpoint-size-independent time — the plan collectives run, a
  consistent device snapshot is pinned (on-device clones, dispatched),
  and staging (D2H + serialization), storage I/O and the commit all run
  on a background thread coordinated by a store-based
  store barrier (never collectives — reference
  snapshot.py:948). ``wait(phase=)`` exposes the staged/committed
  boundaries; docs/async.md has the full phase model.
- ``restore``: per-stateful memory-frugal load — current leaves are reused
  as restore destinations so footprint stays ~1x (reference
  snapshot.py:682-692); JAX arrays are restored host-side then
  ``device_put`` back onto their original sharding/device.
- ``read_object``: random access to one manifest path with an optional
  memory budget for chunked ranged reads.

TPU-native notes: app state is pytree-friendly (``PyTreeState``), RNG state
is explicit ``jax.random`` keys (no hidden global to guard, but the
save-first/restore-after ordering is preserved — reference
snapshot.py:340-346), and replication is declared via globs and/or detected
from fully-replicated shardings rather than inferred from DDP modules.
"""

from __future__ import annotations

import asyncio
import contextlib
import fnmatch
import logging
import time
from typing import Any, Callable, Dict, List, Optional, Set, Tuple

import numpy as np

from . import dest_pool, knobs, telemetry
from .dest_pool import DestinationLeases
from .telemetry import progress as _progress
from .telemetry.trace import (
    TraceMark,
    current_op as _current_op,
    export_op_trace,
    get_recorder as _trace_recorder,
    op_scope as _op_scope,
)
from .dist_store import StoreBarrier, make_barrier
from .flatten import flatten, inflate
from .io_preparer import (
    ArrayIOPreparer,
    capture_write_reqs,
    is_jax_array,
    prepare_read,
    prepare_write,
)
from .io_types import StoragePlugin, WriteIO, WriteReq
from .manifest import (
    ArrayEntry,
    ChunkedArrayEntry,
    Manifest,
    ObjectEntry,
    PrimitiveEntry,
    ShardedArrayEntry,
    SnapshotMetadata,
    get_manifest_for_rank,
    is_container_entry,
)
from .pg_wrapper import PGWrapper
from .rng_state import RngState
from .scheduler import (
    DeferredIOWork,
    PendingIOWork,
    get_process_memory_budget_bytes,
    sync_execute_read_reqs,
    sync_execute_write_reqs,
)
from .stateful import AppState, Stateful
from .storage_plugin import url_to_storage_plugin
from .utils import tracing as _tracing
from .utils.tracing import trace_annotation
from .version import __version__

logger: logging.Logger = logging.getLogger(__name__)

SNAPSHOT_METADATA_FNAME = ".snapshot_metadata"


def _nonce_barrier(prefix: str, pg_wrapper: "PGWrapper") -> Optional[StoreBarrier]:
    """The error-propagating rendezvous used by every distributed phase
    (take commit, restore keys, async plan/apply), built one way so the
    phases can never diverge in barrier wiring. None single-process.
    ``make_barrier`` resolves the topology: the O(log world)
    :class:`~torchsnapshot_tpu.dist_store.TreeBarrier` by default,
    ``LinearBarrier`` behind the ``TORCHSNAPSHOT_TPU_TREE_BARRIER=0``
    kill switch — the contract (``report_error`` poison,
    ``BarrierError`` on every pending wait) is identical, so the phases
    swap topologies without caring."""
    if pg_wrapper.get_world_size() <= 1:
        return None
    assert pg_wrapper.store is not None
    return make_barrier(
        prefix,
        pg_wrapper.store,
        pg_wrapper.get_rank(),
        pg_wrapper.get_world_size(),
    )


@contextlib.contextmanager
def _reporting_to(barrier: Optional["StoreBarrier"], what: str):
    """Fail-fast discipline shared by every distributed phase: an error
    raised inside the block is reported into ``barrier`` (best-effort)
    before propagating, so peers waiting there abandon within seconds
    instead of blocking out the store timeout."""
    try:
        yield
    except BaseException as e:
        if barrier is not None:
            try:
                barrier.report_error(e)
            except Exception as report_exc:  # noqa: BLE001 - already failing
                logger.error(
                    "failed to report %s error to peers (%r); they will "
                    "abandon at the barrier timeout",
                    what,
                    report_exc,
                )
        raise


def _req_needed_bytes(req: Any) -> int:
    """One read request's contribution to ``bytes_needed`` — the bytes
    of destination it fills. Consumers that may read more than they
    deliver (a whole-shard read feeding a partial destination) expose
    ``destination_nbytes``; for everything else the consuming cost IS
    the destination size."""
    consumer = req.buffer_consumer
    fn = getattr(consumer, "destination_nbytes", None)
    return int(fn()) if fn is not None else int(
        consumer.get_consuming_cost_bytes()
    )


def _merge_fanout_telemetry(pipeline: Optional[dict], fanout_ctx) -> None:
    """Fold a fan-out context's byte accounting into a restore's merged
    pipeline telemetry: the owner-side union-window fetches (which ran
    in the exchange, outside any pipeline) add to ``bytes_fetched``, and
    peer-shipped bytes become ``bytes_received``."""
    if fanout_ctx is None or pipeline is None:
        return
    pipeline["bytes_fetched"] = (
        int(pipeline.get("bytes_fetched", 0)) + fanout_ctx.bytes_fetched
    )
    pipeline["bytes_received"] = (
        int(pipeline.get("bytes_received", 0)) + fanout_ctx.bytes_received
    )


def _merge_peer_telemetry(pipeline: Optional[dict], peer_ctx) -> None:
    """Fold a peer-tier restore context's per-tier byte accounting into
    the restore's merged pipeline telemetry: ``tier_split`` (bytes
    served per tier of the peer RAM -> fast -> durable ladder) and the
    ``peer`` degradation evidence the ``peer-tier-degraded`` doctor
    rule cites. The ladder's split supersedes any scheduler-recorded
    one (its ``read_degraded`` already counted corruption reroutes into
    ``tier_bytes`` — summing would double-count); the scheduler's
    ``degraded_reads`` summary rides alongside untouched."""
    if peer_ctx is None or pipeline is None:
        return
    pipeline.update(peer_ctx.pipeline_fields())


def _crashpoint(name: str) -> None:
    """Chaos kill point (chaos/crashpoints.py): production no-op."""
    from .chaos import crashpoint

    crashpoint(name)


def _maybe_push_to_peer(path: str, pending_io_work) -> None:
    """Post-commit peer-tier hook (every rank): queue this rank's
    written blobs — with the integrity entries the pipeline already
    computed — for replication into the ring neighbor's host RAM
    (tiered/peer.py). Inert unless the tier is configured; failures
    degrade (WARN + metrics), never fail the take."""
    try:
        from .tiered import peer as peer_tier

        peer_tier.maybe_enqueue_push(path, pending_io_work.checksums)
    except Exception as e:  # noqa: BLE001 - the peer tier must never fail a take
        logger.warning("peer tier: post-commit push hook failed: %r", e)
    # Kill point: the post-commit peer hook ran (enqueue, not settle).
    _crashpoint(telemetry.names.CRASH_PEER_ENQUEUED)


def _maybe_cas_storage(
    storage: StoragePlugin, path: str, cas_on: bool
) -> StoragePlugin:
    """Wrap a take's storage plugin with the content-addressed write
    interceptor (docs/cas.md) when the broadcast-agreed decision says
    so. The decision rides the existing path broadcast (rank 0 decides;
    env skew can never mix layouts *within* one blob — and even a
    per-rank mix composes, since the rank-0 rewrite is per-blob)."""
    if not cas_on:
        return storage
    from .cas import CASStoragePlugin

    return CASStoragePlugin(storage, path)


def _maybe_write_cas_map(
    storage: StoragePlugin,
    rank: int,
    event_loop: asyncio.AbstractEventLoop,
) -> None:
    """Persist this rank's CAS ``path -> digest`` map (``cas/{rank}``)
    before the commit barrier — the input of rank 0's manifest rewrite,
    committed with the same always-before-barrier discipline as the
    checksum table. No-op for legacy takes."""
    from .cas import CASStoragePlugin

    if isinstance(storage, CASStoragePlugin):
        event_loop.run_until_complete(storage.write_chunk_map(rank))


def _mirror_state_for(path: str) -> Dict[str, Any]:
    """The process mirror's queue/lag state, for reports about tiered
    paths ({} otherwise): at take-report time the step's upload job was
    just enqueued, so this is the durability backlog the take added to."""
    from .tiered.mirror import mirror_state_for_path

    return dict(mirror_state_for_path(path) or {})


def _emit_snapshot_report(kind: str, trace_op: int = 0, **report: Any) -> None:
    """:func:`_build_and_emit_report` under a ``telemetry:report`` span
    of operation ``trace_op``: the emission runs after the envelope
    closed and, for a take or a restore, inside the caller's timed
    call, so what it costs is a stage of the op like any other."""
    with _op_scope(trace_op), trace_annotation(
        telemetry.names.SPAN_TELEMETRY_REPORT, kind=kind
    ):
        _build_and_emit_report(kind=kind, trace_op=trace_op, **report)


def _build_and_emit_report(
    kind: str,
    path: str,
    pg_wrapper: "PGWrapper",
    pipeline: Optional[dict],
    counter_baseline: Dict[str, float],
    nonce: Optional[str],
    error: Optional[BaseException] = None,
    trace_mark: Optional[TraceMark] = None,
    tunables: Optional[Dict[str, Any]] = None,
    trace_op: int = 0,
) -> None:
    """Assemble this rank's SnapshotReport, aggregate across ranks, and
    hand it to the sinks. Best-effort — telemetry must never fail a
    checkpoint — EXCEPT that the cross-rank gather is unconditionally
    symmetric: every rank that reaches this function participates
    (whether or not a sink is configured locally), so a sink knob set on
    rank 0 only can never strand the gather. Store-based, not a
    collective: safe on the async-take commit thread.

    With ``trace_mark`` (the flight-recorder cursor captured at op
    start), the operation's span window is also exported as a Chrome
    trace file when the trace sink knob is on; the cross-rank gather
    doubles as the clock-offset measurement the trace merge uses to
    align per-rank timelines."""
    try:
        registry = telemetry.metrics()
        report = telemetry.build_report(
            kind=kind,
            path=path,
            rank=pg_wrapper.get_rank(),
            world_size=pg_wrapper.get_world_size(),
            pipeline=pipeline,
            counter_deltas=registry.counters_delta_since(counter_baseline),
            mirror=_mirror_state_for(path),
            error=repr(error) if error is not None else None,
            # The knob values the op actually ran under. Callers capture
            # the snapshot at op START: an async take's commit thread
            # emits after the drain, by which time the autotuner may
            # already have moved the vector for the next step.
            tunables=(
                tunables if tunables is not None else knobs.tunable_snapshot()
            ),
        )
        # Blocking-chain attribution over the op's recorder window
        # (telemetry/critpath.py). Computed BEFORE the gather so every
        # rank's dict carries its segments into the cross-rank fold.
        # The envelope span closed before this call (callers end it
        # before emitting), so the window holds the op's full extent.
        # The op's stage table (who was busy) rides beside the
        # partition; this emission's own span is still open, so the
        # table a report carries is without it.
        if trace_mark is not None:
            try:
                from .telemetry import critpath as _critpath

                events = _trace_recorder().events_since(trace_mark)
                cp = _critpath.critical_path_from_events(
                    events, kind, op=trace_op
                )
                # Unstamped spans (work outside any context) go in too:
                # stage_tables counts them by overlap, and the report's
                # table must be the one any other reader of it gets.
                table = _critpath.stage_tables(
                    [e for e in events if e.get("op", 0) in (0, trace_op)]
                ).get(trace_op)
                if cp is not None and table is not None:
                    cp["stages"] = table["stages"]
                    cp["unattributed_s"] = table["unattributed_s"]
                    if "process" in table:
                        cp["process"] = table["process"]
                report.critical_path = cp
            except Exception as e:  # noqa: BLE001 - attribution is best-effort
                logger.warning(
                    "telemetry: critical-path attribution failed: %r", e
                )
        gathered = None
        if (
            nonce
            and pg_wrapper.get_world_size() > 1
            and pg_wrapper.store is not None
        ):
            # Separately guarded with a bounded timeout: every rank that
            # commits reaches this gather, but a rank dying in the tiny
            # window after the commit barrier must cost rank 0 seconds
            # (and only the aggregation), never the 300 s store timeout
            # or the local report.
            try:
                # Every rank stamps its wall clock at gather entry —
                # moments after the same commit barrier on every rank —
                # which is what makes the per-rank deltas usable as
                # clock offsets for the trace merge.
                own = report.to_dict()
                own["gather_unix_ts"] = time.time()
                gathered = pg_wrapper.store.gather(
                    f"__telemetry/{kind}/{nonce}",
                    pg_wrapper.get_rank(),
                    pg_wrapper.get_world_size(),
                    own,
                    timeout=60.0,
                )
            except Exception as e:  # noqa: BLE001 - emit unaggregated
                logger.warning(
                    "telemetry: cross-rank gather for %s failed (%r); "
                    "emitting the unaggregated rank-local report",
                    kind,
                    e,
                )
                gathered = None
            if gathered is not None:
                report.aggregated = telemetry.aggregate_across_ranks(gathered)
                report.clock_offsets_s = telemetry.clock_offsets_from_gather(
                    gathered
                )
                for metric, spread in sorted(report.aggregated.items()):
                    logger.info(
                        "telemetry %s %s: min=%s median=%s max=%s "
                        "straggler=rank %s",
                        kind,
                        metric,
                        spread["min"],
                        spread["median"],
                        spread["max"],
                        spread["straggler"],
                    )
        telemetry.emit_report(report, registry)
        # Run-ledger events (rank 0 only; the owned-root gate inside
        # post_op_event additionally restricts posting to the process
        # whose manager opened the run — ad-hoc snapshots never post):
        # takes record their training-visible stall + overlapped drain,
        # restores the recovery time served. Failed ops post nothing —
        # their cost lands in the segment's lost-work bucket instead.
        if error is None and pg_wrapper.get_rank() == 0:
            from .telemetry import ledger as run_ledger

            # Restores carry a tier split (which tier of the peer ->
            # fast -> durable ladder served the bytes); when the gather
            # ran, sum it across ranks so the ledger records the
            # WORLD's recovery economics, not just rank 0's.
            world_tier_split = None
            if gathered:
                splits = [
                    r.get("tier_split")
                    for r in gathered
                    if isinstance(r, dict) and r.get("tier_split")
                ]
                if splits:
                    world_tier_split = {}
                    for s in splits:
                        for t, b in s.items():
                            world_tier_split[t] = (
                                world_tier_split.get(t, 0) + int(b)
                            )
            run_ledger.post_op_event(
                kind, path, report, world_tier_split=world_tier_split
            )
        if trace_mark is not None:
            export_op_trace(kind, path, pg_wrapper.get_rank(), trace_mark)
    except Exception as e:  # noqa: BLE001 - telemetry must not fail the op
        logger.warning("telemetry: %s report emission failed: %r", kind, e)


class Snapshot:
    """A reference to an existing or to-be-created snapshot at ``path``."""

    def __init__(
        self,
        path: str,
        pg: Optional[Any] = None,
    ) -> None:
        self.path = path
        self._pg_arg = pg
        self._metadata: Optional[SnapshotMetadata] = None
        # The flight recorder's id of the take that made this snapshot or
        # of the last restore from it (0: neither, in this process): work
        # done for that operation after it returned (the manager's index,
        # retention, history) is recorded under it (trace.op_scope).
        self.trace_op = 0
        # The nonce the ranks of the take that made this snapshot agreed
        # on ("" for a single process, or no take in this process): what
        # the manager keys its own cross-rank exchange for the step by,
        # so that it needs no place in the process group's op sequence.
        self.commit_nonce = ""
        # Merged checksum tables, loaded at most once per Snapshot instance
        # (False = not loaded yet; None = no tables / verification disabled).
        self._checksum_table_cache: Any = False

    # ------------------------------------------------------------------
    # take
    # ------------------------------------------------------------------

    @classmethod
    def take(
        cls,
        path: str,
        app_state: AppState,
        pg: Optional[Any] = None,
        replicated: Optional[List[str]] = None,
        incremental_base: Optional[Any] = None,
        record_digests: bool = False,
        _custom_array_prepare_func=None,
    ) -> "Snapshot":
        """Synchronous distributed checkpoint (reference snapshot.py:175-243).

        ``incremental_base`` (a snapshot path or Snapshot, consistent
        across ranks) enables the incremental take: chunks whose on-device
        digest matches the base's recorded digest are not staged or
        written — the manifest references the base's blob instead
        (incremental.py). ``record_digests`` records digests without a
        base, making this snapshot usable as a future base."""
        op = _TakeOp("take", path, pg)
        take_span = _tracing.begin(
            telemetry.names.SPAN_TAKE, path=op.path, rank=op.rank
        )
        op_error: Optional[BaseException] = None
        try:
            op.stage(
                app_state,
                replicated or [],
                incremental_base,
                record_digests,
                _custom_array_prepare_func,
            )
            op.commit(take_span)
        except BaseException as e:
            op_error = e
            raise
        finally:
            _tracing.end(take_span)  # no-op if already closed
            op.settle(op_error)
        return op.snapshot()

    @classmethod
    def async_take(
        cls,
        path: str,
        app_state: AppState,
        pg: Optional[Any] = None,
        replicated: Optional[List[str]] = None,
        incremental_base: Optional[Any] = None,
        record_digests: bool = False,
        _custom_array_prepare_func=None,
        _after_commit: Optional[Callable[["Snapshot"], None]] = None,
    ) -> "PendingSnapshot":
        """Pipelined checkpoint whose training-visible span is independent
        of checkpoint size (docs/async.md): by default the call returns as
        soon as the manifest/plan collectives finish and a consistent
        device snapshot is pinned — on-device clones of the leaves the
        write plan needs (dispatched, not awaited), host copies of mutable
        numpy leaves — and the ENTIRE staging (D2H + serialize) plus
        storage drain and commit run on a background thread through a
        host staging pool bounded by a window sized from the plan
        (``scheduler.StagingPool``). The application may mutate, donate,
        or delete the live arrays freely once this returns. ``PendingSnapshot.wait(phase=)`` distinguishes
        the ``"staged"`` point (D2H done; host buffers hold the bytes)
        from the default ``"committed"`` barrier.

        ``TORCHSNAPSHOT_TPU_ASYNC_DEVICE_SNAPSHOT=0`` restores the
        pre-deferral behavior (staging completes before this returns —
        reference snapshot.py:245-314 — costing no transient HBM copy).
        ``incremental_base``/``record_digests`` as in :meth:`take`."""
        op_begin = time.monotonic()
        op = _TakeOp("async_take", path, pg)
        try:
            # The commit envelope, on its own thread, joins this op.
            with _tracing.op_annotation(
                telemetry.names.SPAN_ASYNC_TAKE_STAGE,
                path=op.path,
                rank=op.rank,
            ):
                op.stage(
                    app_state,
                    replicated or [],
                    incremental_base,
                    record_digests,
                    _custom_array_prepare_func,
                    defer_staging=knobs.is_async_device_snapshot_enabled(),
                )
        except BaseException as e:
            # The failure path owns the loop/storage (no PendingSnapshot
            # thread will ever run to close them).
            try:
                op.event_loop.run_until_complete(op.storage.close())
            except Exception:  # noqa: BLE001 - already failing
                pass
            op.settle(e)
            raise
        return PendingSnapshot(op, op_begin, _after_commit)

    @classmethod
    def _plan_take(
        cls,
        path: str,
        app_state: AppState,
        pg_wrapper: PGWrapper,
        replicated: List[str],
        is_async_snapshot: bool,
        incremental_base: Optional[Any],
        record_digests: bool,
        _custom_array_prepare_func,
    ) -> Tuple[List[WriteReq], Optional[SnapshotMetadata], int, Optional[Any]]:
        """A take's plan, before any byte moves: capture the state dicts,
        flatten, prepare / partition / batch the write requests, agree on
        the budget and gather the manifest. Returns the write requests,
        the metadata (None off rank 0), the memory budget and the
        incremental context (None for a full take)."""
        _validate_app_state(app_state)
        rank = pg_wrapper.get_rank()
        world_size = pg_wrapper.get_world_size()
        replicated_patterns = _coalesce_replicated(replicated, pg_wrapper)

        # RNG first: capturing other statefuls must not perturb what gets
        # saved as the RNG state (reference invariant snapshot.py:340-346).
        # With explicit jax keys nothing mutates behind our back, but
        # .state_dict() of arbitrary statefuls may consume entropy. The
        # capture is collective-free and happens HERE, out of band; the
        # RNG key keeps its *sorted* slot in the barriered loop below —
        # which key is the RNG one is rank-local knowledge, so reordering
        # the loop by it would diverge the barrier/collective schedule on
        # ranks that lack (or name differently) the RngState.
        rng_key_and_state = _pop_rng_state(app_state)
        rng_capture = None
        if rng_key_and_state is not None:
            rng_key, rng_stateful = rng_key_and_state
            rng_capture = flatten(rng_stateful.state_dict(), prefix=rng_key)
        flattened_global: Dict[str, Any] = {}
        rank_manifest: Manifest = {}

        keys = _gather_keys(app_state, pg_wrapper)
        for key in keys:
            if rng_key_and_state is not None and key == rng_key_and_state[0]:
                container_entries, flattened = rng_capture
                pg_wrapper.barrier()
                rank_manifest.update(container_entries)
                flattened_global.update(flattened)
                continue
            stateful = app_state.get(key)
            if stateful is None:
                pg_wrapper.barrier()
                continue
            state_dict = stateful.state_dict()
            # Statefuls are captured in globally-sorted key order with a
            # barrier in between: .state_dict() may itself run collectives
            # (reference snapshot.py:353-370).
            pg_wrapper.barrier()
            container_entries, flattened = flatten(state_dict, prefix=key)
            rank_manifest.update(container_entries)
            flattened_global.update(flattened)

        replicated_paths = _calculate_replicated_entries(
            flattened_global,
            replicated_patterns,
            pg_wrapper,
            inferred=_infer_replicated_paths(flattened_global, world_size),
        )

        incr_ctx = None
        if incremental_base is not None or record_digests:
            from .incremental import IncrementalTakeContext

            incr_ctx = IncrementalTakeContext.build(
                path, incremental_base, rank
            )
            # One launch pass before any stager exists: device digests
            # dispatch asynchronously and overlap each other; skip
            # decisions must precede D2H prefetches.
            incr_ctx.launch(flattened_global, _custom_array_prepare_func)
            # Replicated entries are asserted equal at consolidation, so
            # per-rank degradation (unreadable base, failed digest launch)
            # must degrade every rank identically.
            incr_ctx.synchronize(pg_wrapper, replicated_paths)

        write_reqs: List[WriteReq] = []
        for logical_path, leaf in flattened_global.items():
            entry, reqs = prepare_write(
                obj=leaf,
                logical_path=logical_path,
                rank=rank,
                replicated=logical_path in replicated_paths,
                is_async_snapshot=is_async_snapshot,
                array_prepare_func=_custom_array_prepare_func,
                incremental=(
                    incr_ctx.plan_for(logical_path) if incr_ctx else None
                ),
            )
            rank_manifest[logical_path] = entry
            write_reqs.extend(reqs)

        if world_size > 1:
            from .partitioner import partition_write_reqs

            rank_manifest, write_reqs = partition_write_reqs(
                entries=rank_manifest, write_reqs=write_reqs, pg_wrapper=pg_wrapper
            )

        if knobs.is_batching_enabled():
            from .batcher import batch_write_requests

            entry_list = list(rank_manifest.values())
            entry_list, write_reqs = batch_write_requests(entry_list, write_reqs)
            rank_manifest = dict(zip(rank_manifest.keys(), entry_list))

        # Budget agreement runs BEFORE the manifest gather on purpose: the
        # gather's consolidation/validation is the last rank-0-only
        # failure point of staging, and it must also be the last wrapped
        # collective — a peer must have nothing left between its
        # (non-blocking) gather send and the error-propagating commit
        # barrier, or a rank-0 failure strands it inside an op-seq
        # collective poll (a 300 s store timeout) where the reported
        # error is invisible.
        memory_budget_bytes = get_process_memory_budget_bytes(pg_wrapper)

        global_manifest = _gather_manifest(rank_manifest, pg_wrapper)
        # Non-leader ranks carry no metadata object: the snapshot they
        # return lazy-loads the committed global manifest from storage
        # (Snapshot.metadata), which is both cheaper than shipping it
        # through the coordinator and guaranteed consistent with what
        # rank 0 committed.
        metadata = (
            SnapshotMetadata(
                version=__version__,
                world_size=world_size,
                manifest=global_manifest,
            )
            if global_manifest is not None
            else None
        )
        return write_reqs, metadata, memory_budget_bytes, incr_ctx

    @classmethod
    def _take_impl(
        cls,
        path: str,
        app_state: AppState,
        pg_wrapper: PGWrapper,
        replicated: List[str],
        storage: StoragePlugin,
        event_loop: asyncio.AbstractEventLoop,
        is_async_snapshot: bool,
        incremental_base: Optional[Any] = None,
        record_digests: bool = False,
        _custom_array_prepare_func=None,
        progress_tracker: Optional[_progress.ProgressTracker] = None,
        defer_staging: bool = False,
    ) -> Tuple[
        "PendingIOWork | DeferredIOWork",
        Optional[SnapshotMetadata],
        Optional[Dict[str, int]],
    ]:
        """Shared take core (reference snapshot.py:316-440). The returned
        metadata is None on non-leader ranks (manifests gather to rank 0
        only; see :func:`_gather_manifest`); the third value is the skip
        decisions of a take that records digests (chunks and bytes
        referenced into the base and written), None for a plain take.

        With ``defer_staging`` (device-snapshot async takes), no staging
        runs here: the write plan's sources are captured (on-device
        clones / host copies) and the returned :class:`DeferredIOWork`
        runs the whole pool-bounded pipeline on the background commit
        thread. Collectives still all happen on this (the calling)
        thread either way."""
        rank = pg_wrapper.get_rank()
        with trace_annotation(
            telemetry.names.SPAN_TAKE_PLAN, rank=rank
        ) as plan_span:
            write_reqs, metadata, memory_budget_bytes, incr_ctx = (
                cls._plan_take(
                    path=path,
                    app_state=app_state,
                    pg_wrapper=pg_wrapper,
                    replicated=replicated,
                    is_async_snapshot=is_async_snapshot,
                    incremental_base=incremental_base,
                    record_digests=record_digests,
                    _custom_array_prepare_func=_custom_array_prepare_func,
                )
            )
            decisions = None
            if incr_ctx is not None:
                decisions = dict(incr_ctx.decisions)
                plan_span.annotate(**decisions)

        if defer_staging:
            # Device-snapshot point: pin every write source (on-device
            # clone dispatch for jax leaves — cheap; host copies for
            # mutable numpy leaves; eager pickles for objects), then
            # hand the un-staged plan to the background drain. From the
            # caller's return onward the live arrays are free to be
            # mutated, donated, or deleted.
            with trace_annotation(
                telemetry.names.SPAN_DEVICE_CAPTURE,
                rank=rank,
                reqs=len(write_reqs),
            ) as capture_span:
                captured = capture_write_reqs(
                    write_reqs,
                    queue_drained=incr_ctx is not None
                    and incr_ctx.waited_for_device,
                )
                capture_span.annotate(
                    clone_programs=captured.clone_programs,
                    clone_leaves=captured.clone_leaves,
                    fallback_leaves=captured.fallback_leaves,
                )
            logger.debug(
                "async take captured %d device/host sources for %d "
                "deferred write requests",
                captured.sources,
                len(write_reqs),
            )
            if progress_tracker is not None:
                progress_tracker.set_phase("captured")
            pending_io_work: "PendingIOWork | DeferredIOWork" = (
                DeferredIOWork(
                    write_reqs=write_reqs,
                    storage=storage,
                    memory_budget_bytes=memory_budget_bytes,
                    rank=rank,
                    progress=progress_tracker,
                    device_clones=captured.device_clones,
                    clone_programs=captured.device_programs,
                )
            )
        else:
            pending_io_work = sync_execute_write_reqs(
                write_reqs=write_reqs,
                storage=storage,
                memory_budget_bytes=memory_budget_bytes,
                rank=rank,
                event_loop=event_loop,
                progress=progress_tracker,
            )
        if incr_ctx is not None:
            # Referenced blobs were not rewritten, so their checksums come
            # from the base snapshot's tables (keyed by the ref location):
            # restore-time verification must cover unwritten bytes too.
            # Deferred to finalize_checksums (the background commit thread
            # for async takes) — it reads base tables from storage, which
            # must not delay the staging-done return.
            pending_io_work.checksum_finalizer = (
                lambda: incr_ctx.inherit_checksums(pending_io_work.checksums)
            )
        from .cas import CASStoragePlugin

        if isinstance(storage, CASStoragePlugin):
            # CAS takes additionally re-home the table entries from the
            # original write paths to the chunk locations the rewritten
            # manifest will name — composed AFTER the incremental
            # inherit (whose entries already carry chunk-ref keys).
            prev_finalizer = pending_io_work.checksum_finalizer

            def _cas_finalize(prev=prev_finalizer) -> None:
                if prev is not None:
                    prev()
                storage.rekey_checksums(pending_io_work.checksums)

            pending_io_work.checksum_finalizer = _cas_finalize
        return pending_io_work, metadata, decisions

    @staticmethod
    def _write_snapshot_metadata(
        metadata: SnapshotMetadata,
        storage: StoragePlugin,
        event_loop: asyncio.AbstractEventLoop,
    ) -> None:
        # CAS takes: fold every rank's committed ``cas/{rank}`` chunk
        # map into the manifest first — entry locations become
        # ``../chunks/<key>`` parent refs, after which the snapshot
        # reads like any other to every consumer. No-op for legacy
        # takes (the wrapper's absence is the signal).
        from .cas import maybe_rewrite_manifest

        event_loop.run_until_complete(
            maybe_rewrite_manifest(metadata, storage)
        )
        # Kill points bracketing the commit write: before, the step
        # must read as never-happened; after, as committed (whether or
        # not anything downstream — index, mirror, peer — ever ran).
        _crashpoint(telemetry.names.CRASH_PRE_COMMIT_MARKER)
        # Committed as JSON — a YAML subset (reference manifest.py:19-22
        # invariant), so any YAML tooling still reads it, and loading takes
        # the fast json.loads path instead of a YAML parse.
        metadata_bytes = metadata.to_json().encode("utf-8")
        event_loop.run_until_complete(
            storage.write(WriteIO(path=SNAPSHOT_METADATA_FNAME, buf=metadata_bytes))
        )
        _crashpoint(telemetry.names.CRASH_COMMIT_MARKER)

    # ------------------------------------------------------------------
    # metadata / manifest
    # ------------------------------------------------------------------

    @property
    def metadata(self) -> SnapshotMetadata:
        if self._metadata is None:
            event_loop = asyncio.new_event_loop()
            try:
                storage = url_to_storage_plugin(self.path)
                from .io_types import ReadIO

                read_io = ReadIO(path=SNAPSHOT_METADATA_FNAME)
                event_loop.run_until_complete(storage.read(read_io))
                assert read_io.buf is not None
                self._metadata = SnapshotMetadata.from_yaml(
                    bytes(read_io.buf).decode("utf-8")
                )
                event_loop.run_until_complete(storage.close())
            finally:
                event_loop.close()
        return self._metadata

    def get_manifest(self) -> Manifest:
        import copy

        return copy.deepcopy(self.metadata.manifest)

    def _get_checksum_table(
        self, storage: StoragePlugin, event_loop: asyncio.AbstractEventLoop
    ):
        """Merged blob digests, fetched at most once per Snapshot instance
        (repeated read_object calls must not re-read every rank's table)."""
        if self._checksum_table_cache is False:
            self._checksum_table_cache = _get_checksum_table_impl(
                self.metadata.world_size, storage, event_loop
            )
        return self._checksum_table_cache

    # ------------------------------------------------------------------
    # restore
    # ------------------------------------------------------------------

    def restore(self, app_state: AppState) -> None:
        """In-place restore (reference snapshot.py:442-491): a key at a
        time — plan, exchange, read, apply, barrier — on the caller's
        thread, so that only one stateful's new arrays stand beside its
        old ones and each stateful is a read pipeline of its own. The
        steps are :class:`_RestoreOp`'s, shared with
        :meth:`async_restore`."""
        _validate_app_state(app_state)
        pg_wrapper = PGWrapper(self._pg_arg)
        op = _RestoreOp(
            self, "restore", pg_wrapper, _agree_restore(pg_wrapper)
        )
        restore_span = _tracing.begin(
            telemetry.names.SPAN_RESTORE, path=self.path, rank=op.rank
        )
        self.trace_op = op.trace_op = _current_op()
        op_error: Optional[BaseException] = None
        try:
            # Collectives FIRST, storage reads second (round 5; same
            # principle as _take_impl's budget-before-gather order): the
            # metadata and checksum-table reads are the restore's
            # pre-coordination failure points, and a rank failing there
            # must not leave peers inside an op-seq collective poll —
            # where a reported error is invisible. Only local work sits
            # between a rank's set-up reads and the first error-aware key
            # barrier, so set-up failures reported into key barrier 0
            # abandon peers in seconds.
            rng_key_and_state = _pop_rng_state(app_state)
            rng_key = rng_key_and_state[0] if rng_key_and_state else None
            keys = _gather_keys(app_state, pg_wrapper)
            memory_budget_bytes = get_process_memory_budget_bytes(pg_wrapper)
            op.set_up(op.barrier(0) if keys else None, memory_budget_bytes)
            for i, key in enumerate(keys):
                # The RNG stateful keeps its slot and is restored last.
                stateful = None if key == rng_key else app_state.get(key)
                barrier = op.barrier(i)
                with _reporting_to(barrier, "restore"):
                    # Plan first so the fan-out exchange (a round every
                    # rank runs in the same order, plan or no plan)
                    # knows this rank's needed byte windows. The
                    # exchange's waits poll THIS round's barrier error
                    # key, so a peer failing anywhere in this block
                    # aborts the round in seconds (_reporting_to writes
                    # that key on the way out).
                    plans = self._plan_key(key, stateful, op)
                    round_locs = op.exchange(plans, i)
                    try:
                        if plans:
                            op.read_plans(plans, apply=True)
                    finally:
                        if op.fanout_ctx is not None:
                            op.fanout_ctx.drop(round_locs)
                if barrier is not None:
                    barrier.arrive()
                    barrier.depart()
            # RNG state is restored last so that load_state_dict side
            # effects of other statefuls cannot disturb it (reference
            # snapshot.py:478-489): rank-locally, outside the shared
            # barrier schedule, in no exchange.
            if rng_key_and_state is not None:
                plans = self._plan_key(*rng_key_and_state, op)
                if plans:
                    op.read_plans(plans, apply=True)
            _settle_destinations()
            op.close_storage()
            _tracing.end(restore_span)
            op.report(nonce=op.nonce)
        except BaseException as e:
            op_error = e
            raise
        finally:
            _tracing.end(restore_span)  # no-op if already closed
            op.settle(op_error)

    def async_restore(self, app_state: AppState) -> "PendingRestore":
        """Pipelined restore: storage reads (and H2D placement) run on a
        background thread; ``wait()`` applies the restored state dicts.

        No reference counterpart (its restore is synchronous only). The
        use case is TPU cold-start: restore I/O overlaps the train-step
        compilation that dominates restore-to-step0, e.g.::

            pending = snapshot.async_restore(app_state)
            compiled = train_step.lower(state, batch).compile()  # overlaps
            pending.wait()                                        # applies

        State capture (``state_dict()``) and the read *planning* happen on
        the calling thread before this returns — collectives stay on the
        main thread, mirroring async_take's discipline (reference
        snapshot.py:948) — so until ``wait()`` returns, the application's
        jax leaves are untouched (fresh host buffers absorb the reads;
        ``wait()`` re-raises background failures before applying anything,
        leaving app state unmodified on error). In-place numpy leaves are
        the exception: they are read into directly and must not be used
        until ``wait()`` returns.

        The steps are :class:`_RestoreOp`'s, shared with :meth:`restore`;
        this driver plans every key, exchanges once and reads all plans
        in one pipeline, so every stateful's new arrays are held until
        ``wait()``."""
        _validate_app_state(app_state)
        pg_wrapper = PGWrapper(self._pg_arg)
        op = _RestoreOp(self, "async_restore", pg_wrapper)
        try:
            # The op's first envelope: capture, planning and the exchange
            # run on the calling thread; the read thread's envelope joins
            # it, and wait() applies and reports under it.
            with _tracing.op_annotation(
                telemetry.names.SPAN_ASYNC_RESTORE_PLAN,
                path=self.path,
                rank=op.rank,
            ):
                op.trace_op = _current_op()
                return self._start_async_restore(app_state, op)
        except BaseException as e:
            op.settle(e)  # no read thread will ever run to do it
            raise

    def _start_async_restore(
        self, app_state: AppState, op: "_RestoreOp"
    ) -> "PendingRestore":
        memory_budget_bytes = get_process_memory_budget_bytes(op.pg)
        rng_key_and_state = _pop_rng_state(app_state)
        # The key list (and hence the barrier schedule) must be identical
        # on every rank; the RNG key is rank-local knowledge, so it keeps
        # its sorted slot here and only its *apply* is deferred (to last,
        # after all barriers — RngState application is collective-free).
        keys = _gather_keys(app_state, op.pg)
        # Agreed BEFORE any storage read or planning (round 5), so the
        # whole set-up runs with an error-aware rendezvous in place: the
        # metadata read and per-key planning report failures into the
        # plan barriers below, and peers abandon there in seconds instead
        # of stranding inside a plain op-seq barrier (where a reported
        # error is invisible) for the full store timeout.
        op.nonce, op.fanout_agreed = _agree_restore(op.pg)
        op.set_up(op.barrier("plan0") if keys else None, memory_budget_bytes)

        plans: Dict[str, _StatefulLoadPlan] = {}
        for i, key in enumerate(keys):
            barrier = op.barrier(f"plan{i}")
            with _reporting_to(barrier, "async restore planning"):
                for plan in self._plan_key(key, app_state.get(key), op):
                    plans[key] = plan
            # state_dict() may itself run collectives: keep the capture
            # globally ordered (reference snapshot.py:353-370). The
            # barrier is error-aware: a peer's planning failure abandons
            # this rank here instead of at a store timeout.
            if barrier is not None:
                barrier.arrive()
                barrier.depart()

        # The exchange is a cross-rank rendezvous, so it runs HERE — on
        # the calling thread, after every plan exists — covering all
        # plans in one round; the owner-side unique-shard fetches land in
        # this (visible) span and the background pipeline then reads them
        # from the cache (no rendezvous off the main thread). The round's
        # error-aware barrier keeps a failing rank from stranding its
        # peers in the exchange.
        if op.fanout_ctx is not None:
            with _reporting_to(op.barrier("fanout"), "fan-out exchange"):
                op.exchange(list(plans.values()), "fanout")
        return PendingRestore(
            op, keys, plans, rng_key_and_state[0] if rng_key_and_state else None
        )

    def _plan_key(
        self, key: str, stateful: Optional[Stateful], op: "_RestoreOp"
    ) -> List["_StatefulLoadPlan"]:
        """The plans of one key on this rank: none where the rank holds
        no such stateful or the snapshot no entry for it."""
        plan = None
        if stateful is not None:
            plan = self._plan_stateful_load(
                key, stateful, op.available, op.memory_budget_bytes
            )
        return [] if plan is None else [plan]

    def _plan_stateful_load(
        self,
        key: str,
        stateful: Stateful,
        available: Manifest,
        memory_budget_bytes: int,
    ) -> Optional["_StatefulLoadPlan"]:
        """Pure planning for one stateful's restore: captures its current
        state dict, picks/allocates read destinations, builds read
        requests + deferred conversions. No storage I/O happens here."""
        with trace_annotation(
            telemetry.names.SPAN_RESTORE_PLAN, stateful=key
        ):
            return self._plan_stateful_load_impl(
                key, stateful, available, memory_budget_bytes
            )

    def _plan_stateful_load_impl(
        self,
        key: str,
        stateful: Stateful,
        available: Manifest,
        memory_budget_bytes: int,
    ) -> Optional["_StatefulLoadPlan"]:
        from .flatten import _encode

        encoded_key = _encode(key)
        entries = {
            path: entry
            for path, entry in available.items()
            if path == encoded_key or path.startswith(encoded_key + "/")
        }
        if not entries:
            logger.warning("No entries found for stateful %r; skipping", key)
            return None

        current_container_entries, current_flattened = flatten(
            stateful.state_dict(), prefix=key
        )
        del current_container_entries

        read_reqs = []
        restored: Dict[str, Any] = {}
        container_entries: Manifest = {}
        # Per-leaf groups of (reads, deferred conversion): the conversion
        # (np buffer -> the leaf flavor the application currently holds,
        # e.g. a jax device array) may run as soon as the group's reads
        # complete — streaming placement — or all together after.
        groups: List[_LeafGroup] = []

        for path, entry in entries.items():
            if is_container_entry(entry):
                container_entries[path] = entry
                continue
            if isinstance(entry, PrimitiveEntry):
                restored[path] = entry.get_value()
                continue
            current_leaf = current_flattened.get(path)
            if isinstance(entry, ObjectEntry):

                def _cb(obj: Any, path: str = path) -> None:
                    restored[path] = obj

                read_reqs.extend(prepare_read(entry, callback=_cb))
                continue
            if isinstance(entry, ShardedArrayEntry):
                from .sharded_io_preparer import ShardedArrayIOPreparer

                reqs, finalize = ShardedArrayIOPreparer.prepare_read_into(
                    entry,
                    current_leaf,
                    restored,
                    path,
                    buffer_size_limit_bytes=memory_budget_bytes,
                    late=_bound_for_accelerator(current_leaf),
                )
                read_reqs.extend(reqs)
                if finalize is not None:
                    groups.append(_LeafGroup(reqs, finalize))
                continue
            assert isinstance(entry, (ArrayEntry, ChunkedArrayEntry))
            dst, convert, owned = _restore_destination(
                entry, current_leaf, late=True
            )
            reqs = prepare_read(entry, obj_out=dst, dest_owned=owned)
            read_reqs.extend(reqs)
            if convert is None:
                restored[path] = dst
            else:

                def _pp(
                    batch: Optional["_PlacementBatch"],
                    path: str = path,
                    dst: Optional[np.ndarray] = dst,
                    consumer: Any = reqs[0].buffer_consumer,
                    convert: Callable[..., Any] = convert,
                ) -> None:
                    # A late destination is the one read's consumer's.
                    late = dst is None

                    def placed(value: Any) -> None:
                        restored[path] = value
                        if late:
                            consumer.placed(value)

                    out = convert(consumer.dst if late else dst, batch)
                    if isinstance(out, _PlacementSlot):
                        assert batch is not None
                        batch.defer(lambda: placed(out.value))
                    else:
                        placed(out)

                groups.append(_LeafGroup(reqs, _pp))

        return _StatefulLoadPlan(
            key=key,
            stateful=stateful,
            container_entries=container_entries,
            restored=restored,
            groups=groups,
            read_reqs=read_reqs,
        )

    # ------------------------------------------------------------------
    # read_object
    # ------------------------------------------------------------------

    def read_object(
        self,
        path: str,
        obj_out: Optional[Any] = None,
        memory_budget_bytes: Optional[int] = None,
        sharding: Optional[Any] = None,
    ) -> Any:
        """Random access to a single object by manifest path
        ``"RANK/STATEFUL/KEY..."`` (reference snapshot.py:507-612).

        ``sharding`` places a ShardedArray entry directly under an
        arbitrary jax ``Sharding`` — any layout, any world size,
        no template leaf needed (reshard-on-read, docs/restore.md);
        only the byte windows overlapping this process's addressable
        devices are read. Mutually exclusive with ``obj_out`` — an
        in-place destination defines its own layout, and silently
        preferring one would leave the other untouched."""
        if sharding is not None and obj_out is not None:
            raise ValueError(
                "read_object: pass either obj_out (in-place restore into "
                "your array) or sharding (fresh placement under a target "
                "Sharding), not both"
            )
        rank_str, _, logical_path = path.partition("/")
        try:
            rank = int(rank_str)
        except ValueError:
            raise ValueError(
                f"read_object path must start with a rank (got {path!r})"
            ) from None
        available = get_manifest_for_rank(self.metadata, rank)
        if logical_path not in available:
            raise ValueError(
                f"{logical_path!r} is not a valid entry for rank {rank} "
                f"(candidates: {sorted(available)[:20]}...)"
            )
        entry = available[logical_path]
        if isinstance(entry, PrimitiveEntry):
            return entry.get_value()
        if is_container_entry(entry):
            raise ValueError(
                f"{logical_path!r} is a container; read leaf paths instead"
            )

        event_loop = asyncio.new_event_loop()
        try:
            storage = url_to_storage_plugin(self.path)
            restored: Dict[str, Any] = {}
            result_path = "__read_object__"
            finalize: Optional[Callable[[], None]] = None

            if isinstance(entry, ObjectEntry):
                read_reqs = prepare_read(
                    entry, callback=lambda o: restored.__setitem__(result_path, o)
                )
            elif isinstance(entry, ShardedArrayEntry):
                from .sharded_io_preparer import ShardedArrayIOPreparer

                read_reqs, finalize = ShardedArrayIOPreparer.prepare_read_into(
                    entry,
                    obj_out,
                    restored,
                    result_path,
                    buffer_size_limit_bytes=memory_budget_bytes,
                    target_sharding=sharding,
                )
            else:
                assert isinstance(entry, (ArrayEntry, ChunkedArrayEntry))
                dst, convert, owned = _restore_destination(entry, obj_out)
                if sharding is not None and obj_out is None:
                    import jax

                    target = sharding

                    def convert(
                        host: np.ndarray, batch=None, _t=target
                    ) -> Any:
                        return jax.device_put(host, _t)

                read_reqs = prepare_read(
                    entry,
                    obj_out=dst,
                    buffer_size_limit_bytes=memory_budget_bytes,
                    dest_owned=owned,
                )
                if convert is None:
                    restored[result_path] = dst
                else:
                    finalize = lambda: restored.__setitem__(  # noqa: E731
                        result_path, convert(dst)
                    )

            if knobs.is_batching_enabled():
                from .batcher import batch_read_requests

                read_reqs = batch_read_requests(read_reqs)

            sync_execute_read_reqs(
                read_reqs=read_reqs,
                storage=storage,
                memory_budget_bytes=memory_budget_bytes
                or get_process_memory_budget_bytes(None),
                rank=rank,
                event_loop=event_loop,
                checksum_table=self._get_checksum_table(storage, event_loop),
            )
            if finalize is not None:
                finalize()
            event_loop.run_until_complete(storage.close())
            return restored[result_path]
        finally:
            event_loop.close()


class _PlacementSlot:
    """Future for one array's device placement inside a _PlacementBatch."""

    __slots__ = ("_batch", "_idx")

    def __init__(self, batch: "_PlacementBatch", idx: int) -> None:
        self._batch = batch
        self._idx = idx

    @property
    def value(self) -> Any:
        return self._batch._results[self._idx]


class _PlacementBatch:
    """Batches every restore-time H2D placement into ONE ``jax.device_put``
    dispatch. Per-leaf device_put calls pay per-dispatch latency once per
    leaf (hundreds of calls for a real model's cold restore); jax's
    batched device_put moves the same bytes in a single dispatch.
    ``put`` registers (host array, target sharding/device) and returns a
    slot; ``defer`` registers work that reads slots; ``run`` executes the
    batched transfer then the deferred work."""

    def __init__(self) -> None:
        self._values: List[Any] = []
        self._targets: List[Any] = []
        self._deferred: List[Callable[[], None]] = []
        self._results: List[Any] = []

    def put(self, value: Any, target: Any) -> _PlacementSlot:
        self._values.append(value)
        self._targets.append(target)
        return _PlacementSlot(self, len(self._values) - 1)

    def defer(self, fn: Callable[[], None]) -> None:
        self._deferred.append(fn)

    def _bytes_by_device(self) -> Dict[str, int]:
        """Host bytes this batch sends to each device, by device id: a
        target is one device (a sharded leaf's box) or a sharding, every
        device of which gets its shard of the value."""
        out: Dict[str, int] = {}
        for value, target in zip(self._values, self._targets):
            nbytes = int(getattr(value, "nbytes", 0))
            devices = [target]
            if hasattr(target, "shard_shape"):
                devices = list(target.addressable_devices)
                shard = target.shard_shape(value.shape)
                nbytes = int(np.prod(shard, dtype=np.int64)) * value.dtype.itemsize
            for device in devices:
                key = str(device.id)
                out[key] = out.get(key, 0) + nbytes
        return out

    def run(self) -> None:
        if not self._values and not self._deferred:
            return
        # On the thread that calls it: the scheduler's event loop for a
        # streamed flush (reads wait behind it), else the restore's own.
        by_device = self._bytes_by_device()
        with trace_annotation(
            telemetry.names.SPAN_RESTORE_PLACE,
            arrays=len(self._values),
            bytes=sum(int(getattr(v, "nbytes", 0)) for v in self._values),
            **({"bytes_by_device": by_device} if len(by_device) > 1 else {}),
        ):
            if self._values:
                import jax

                self._results = jax.device_put(self._values, self._targets)
            for fn in self._deferred:
                fn()
        self._values, self._targets, self._deferred = [], [], []


class _LeafGroup:
    """One leaf's read requests plus the deferred conversion that turns
    their completed buffers into the application's leaf flavor. ``done``
    flips once the conversion ran (streamed or final batch) so it can
    never run twice."""

    __slots__ = ("reqs", "fn", "nbytes", "remaining", "done")

    def __init__(
        self,
        reqs: List[Any],
        fn: Callable[[Optional["_PlacementBatch"]], None],
    ) -> None:
        self.reqs = reqs
        self.fn = fn
        self.nbytes = sum(
            r.buffer_consumer.get_consuming_cost_bytes() for r in reqs
        )
        self.remaining = len(reqs)
        self.done = False


class _StreamingPlacer:
    """Rolling restore-time H2D placement: a leaf's conversion runs as
    soon as ALL of its reads complete, batched into one ``jax.device_put``
    dispatch per ~``flush_bytes`` of restored data. Storage reads and
    device transfers then overlap instead of serializing (all reads
    first, one placement after) — the transfer of early leaves hides
    behind the remaining reads. ``flush_bytes <= 0`` disables streaming
    (everything places in the caller's final batch).

    Single-threaded by construction: completion callbacks, flushes, and
    ``finalize`` all run on the scheduler's event-loop thread.
    """

    def __init__(self, flush_bytes: Optional[int] = None) -> None:
        self.flush_bytes = (
            knobs.get_restore_placement_flush_bytes()
            if flush_bytes is None
            else flush_bytes
        )
        self._by_req: Dict[int, _LeafGroup] = {}
        self._pending: List[_LeafGroup] = []
        self._pending_bytes = 0
        # The pipeline's slabs of the destination pool, where it has
        # reads that take one (lease_destinations).
        self.leases: Optional[DestinationLeases] = None

    def lease_destinations(
        self, read_reqs: List[Any], memory_budget_bytes: int
    ) -> None:
        """Reads that came without a destination (a dense leaf's, or a
        sharded leaf's boxes and the buffers its reads are copied out of)
        take slabs of the process's pool, under a cap from what this
        pipeline shows. A destination's slab comes back through a
        placement, so without streaming (all placements after all reads)
        nothing is leased: such reads make their own destination, as one
        nobody binds always does."""
        if self.flush_bytes > 0:
            self.leases = DestinationLeases.for_reads(
                dest_pool.process_pool(),
                [r.buffer_consumer for r in read_reqs],
                memory_budget_bytes,
                self.flush,
                knobs.get_per_rank_io_concurrency(),
            )

    def report_destinations(self, pipeline_telemetry: dict) -> None:
        if self.leases is not None:
            pipeline_telemetry["dest_bytes_recycled"] = self.leases.bytes_recycled
            pipeline_telemetry["dest_bytes_fresh"] = self.leases.bytes_fresh

    def abandon_destinations(self) -> None:
        if self.leases is not None:
            self.leases.abandon()

    def register_plan(self, plan: "_StatefulLoadPlan") -> None:
        if self.flush_bytes <= 0:
            return
        for group in plan.groups:
            if group.remaining == 0:
                self._ready(group)
            else:
                for req in group.reqs:
                    self._by_req[id(req)] = group

    def on_req_complete(self, req: Any) -> None:
        """Scheduler hook. Batched spanning reads complete their member
        requests (the planned objects live inside the merged consumer)."""
        from .batcher import BatchedBufferConsumer

        consumer = req.buffer_consumer
        if isinstance(consumer, BatchedBufferConsumer):
            for member in consumer.members:
                self.on_req_complete(member)
            return
        group = self._by_req.pop(id(req), None)
        if group is None:
            return
        group.remaining -= 1
        if group.remaining == 0:
            self._ready(group)

    def _ready(self, group: _LeafGroup) -> None:
        self._pending.append(group)
        self._pending_bytes += group.nbytes
        # A read waiting for a slab waits for a placement: none may sit
        # here until more bytes arrive that cannot.
        starved = self.leases is not None and self.leases.starved
        if self._pending_bytes >= self.flush_bytes or starved:
            self.flush()

    def flush(self) -> None:
        if not self._pending:
            return
        batch = _PlacementBatch()
        for group in self._pending:
            group.fn(batch)
            group.done = True
        self._pending = []
        self._pending_bytes = 0
        batch.run()


class _StatefulLoadPlan:
    """Planned restore of one stateful: read requests plus the deferred
    work that turns completed reads into application state."""

    def __init__(
        self,
        key: str,
        stateful: Stateful,
        container_entries: Manifest,
        restored: Dict[str, Any],
        groups: List[_LeafGroup],
        read_reqs: List[Any],
    ) -> None:
        self.key = key
        self.stateful = stateful
        self.container_entries = container_entries
        self.restored = restored
        self.groups = groups
        self.read_reqs = read_reqs

    def finish_reads(self, batch: _PlacementBatch) -> None:
        """Register into ``batch`` the deferred conversions (np buffers
        -> device arrays on their original shardings) not already
        streamed; the caller runs the batch (one dispatch spanning many
        plans). Safe off the main thread: conversions only
        ``device_put`` addressable data — no collectives."""
        for group in self.groups:
            if not group.done:
                group.fn(batch)
                group.done = True

    def apply(self, finish: bool = False) -> None:
        """Hand the restored state dict to the application, under the
        plan's ``restore:apply`` span. May run arbitrary user code
        (collectives included) — main thread only. ``finish``: the
        plan's last placements have not run, and run under the span
        first."""
        with trace_annotation(
            telemetry.names.SPAN_RESTORE_APPLY, stateful=self.key
        ):
            if finish:
                _finish_plans([self])
            state_dict = inflate(
                {**self.container_entries}, self.restored, prefix=self.key
            )
            self.stateful.load_state_dict(state_dict)


def _finish_plans(plans: List[_StatefulLoadPlan]) -> None:
    """Whatever did not stream (streaming off, leaves without reads)
    places in ONE batched ``device_put`` spanning ``plans``: per-leaf
    dispatch latency x hundreds of leaves is real cold-start time."""
    batch = _PlacementBatch()
    for plan in plans:
        plan.finish_reads(batch)
    batch.run()


# ---------------------------------------------------------------------------
# take: one prologue, one commit sequence, two drivers
# ---------------------------------------------------------------------------


class _TakeOp:
    """One take from its prologue to its report: what
    :meth:`Snapshot.take` and the :class:`PendingSnapshot` of
    :meth:`Snapshot.async_take` both hold, and the commit sequence both
    run — ``take`` on the caller's thread, the handle on its
    ``snapshot-commit`` thread. Collectives all happen in the prologue
    and in :meth:`stage`, on the calling thread."""

    def __init__(self, kind: str, path: str, pg: Optional[Any]) -> None:
        from .cas import cas_eligible

        self.kind = kind
        self.pg = pg_wrapper = PGWrapper(pg)
        self.rank = pg_wrapper.get_rank()
        # Rank-0 path wins; the CAS layout decision rides the same
        # broadcast (one agreement, no extra collective) so ranks can
        # never diverge on where data bytes land.
        self.path, cas_on = pg_wrapper.broadcast_object(
            (path, cas_eligible(path))
        )
        # Error-propagating commit barrier: a rank whose staging or
        # writes fail must not strand its peers for the full store
        # timeout — they observe the reported error at arrive() and
        # abandon (no commit marker anywhere). Staging includes
        # rank-0-only work such as replication verification, and peers
        # whose staging succeeded may already be waiting at arrive().
        # The nonce keeps barrier keys from aliasing any earlier take to
        # the same path (failed ones included); a world of one has no
        # barrier and agrees none.
        self.nonce = ""
        if pg_wrapper.get_world_size() > 1:
            import uuid

            self.nonce = pg_wrapper.broadcast_object(uuid.uuid4().hex)
        self.barrier = _nonce_barrier(
            f"__snapshot_commit/{self.nonce}", pg_wrapper
        )
        self.counter_baseline = telemetry.metrics().counters_snapshot()
        self.tunables = knobs.tunable_snapshot()
        self.trace_mark = _trace_recorder().mark()
        self.storage = _maybe_cas_storage(
            url_to_storage_plugin(self.path), self.path, cas_on
        )
        # Live-progress heartbeat for the whole op: external pollers see
        # a stuck rank from outside the process (telemetry/progress.py).
        self.tracker = _progress.track(kind, self.path, self.rank)
        self.event_loop = asyncio.new_event_loop()
        # The flight recorder's id of this take: read in stage(), while
        # the caller's envelope is open.
        self.trace_op = 0
        self.pending_io_work: "PendingIOWork | DeferredIOWork | None" = None
        self.metadata: Optional[SnapshotMetadata] = None
        # Extra pipeline fields of the report: the async handle's
        # visible / staged phase split.
        self.phases: Dict[str, float] = {}
        # The skip decisions of a take that records digests, for the
        # report (None for a plain take).
        self.incremental: Optional[Dict[str, int]] = None

    def stage(
        self,
        app_state: AppState,
        replicated: List[str],
        incremental_base: Optional[Any],
        record_digests: bool,
        _custom_array_prepare_func,
        defer_staging: bool = False,
    ) -> None:
        """Plan and stage (``defer_staging``: capture) inside the
        caller's envelope; a failure poisons the commit barrier before
        it raises."""
        with _reporting_to(self.barrier, f"{self.kind} staging"):
            self.trace_op = _current_op()
            (
                self.pending_io_work,
                self.metadata,
                self.incremental,
            ) = Snapshot._take_impl(
                path=self.path,
                app_state=app_state,
                pg_wrapper=self.pg,
                replicated=replicated,
                storage=self.storage,
                event_loop=self.event_loop,
                is_async_snapshot=self.kind == "async_take",
                incremental_base=incremental_base,
                record_digests=record_digests,
                _custom_array_prepare_func=_custom_array_prepare_func,
                progress_tracker=self.tracker,
                defer_staging=defer_staging,
            )

    def commit(self, span: Any) -> None:
        """The commit sequence, spelled here alone: drain the writes,
        make the checksum and CAS tables durable, rendezvous, rank 0
        writes the marker, hand the blobs to the peer tier, close the
        storage, close ``span`` (the caller's envelope) and report. A
        failure anywhere in it is reported to the barrier before it
        propagates, so peers polling at arrive() or depart() abandon in
        seconds instead of blocking out the store timeout; ``take``
        raises it, the commit thread records it for ``wait()``."""
        work, loop = self.pending_io_work, self.event_loop
        with _reporting_to(self.barrier, self.kind):
            work.sync_complete(loop)
            _crashpoint(telemetry.names.CRASH_TAKE_WRITES_DONE)
            # All writes are durable on every rank before the commit
            # marker exists anywhere (commit-after-barrier invariant).
            with trace_annotation(
                telemetry.names.SPAN_COMMIT_FINALIZE, rank=self.rank
            ):
                _write_checksum_and_cas_tables(
                    work, self.rank, self.storage, loop
                )
                if self.barrier is not None:
                    self.barrier.arrive()
                if self.rank == 0:
                    Snapshot._write_snapshot_metadata(
                        self.metadata, self.storage, loop
                    )
                if self.barrier is not None:
                    self.barrier.depart()
            # Post-commit: hand this rank's blobs to the peer tier (the
            # committed step is what a replacement rank would restore).
            # The enqueue is queue-put cheap; the job runs on the peer
            # replicator's own worker.
            _maybe_push_to_peer(self.path, work)
            loop.run_until_complete(self.storage.close())
            # The envelope closes before the report/trace emission so
            # the exported timeline carries the take's full extent.
            _tracing.end(span)
            # Post-close on purpose: a tiered plugin enqueues its mirror
            # job at close, so the report's mirror state reflects the
            # durability backlog this take just created. Store-based
            # gather + local file append only: safe on the commit thread
            # (no collectives), the rule the commit barrier follows.
            _emit_snapshot_report(
                kind=self.kind,
                path=self.path,
                pg_wrapper=self.pg,
                pipeline={
                    **work.pipeline_telemetry(),
                    **self.phases,
                    "incremental": self.incremental,
                },
                counter_baseline=self.counter_baseline,
                nonce=self.nonce,
                trace_mark=self.trace_mark,
                tunables=self.tunables,
                trace_op=self.trace_op,
            )

    def snapshot(self) -> "Snapshot":
        """The committed snapshot, for the caller and for what the
        manager does for the step (recorded under ``trace_op``, its
        cross-rank exchange keyed by ``commit_nonce``). Carries the
        take's process group: ``restore()`` on it keeps per-rank
        availability and coordination semantics."""
        snapshot = Snapshot(path=self.path, pg=self.pg)
        snapshot._metadata = self.metadata
        snapshot.trace_op = self.trace_op
        snapshot.commit_nonce = self.nonce
        return snapshot

    def settle(self, error: Optional[BaseException]) -> None:
        """The end of the take on the thread that ran it, failed or not.
        Success removes the heartbeat file; failure leaves a terminal
        document (doctor evidence the op *ended*)."""
        self.tracker.finish(error)
        self.event_loop.close()


class PendingSnapshot:
    """Handle on an in-flight async snapshot (reference snapshot.py:904-991).

    A background thread drains staging (for device-snapshot takes) and
    storage I/O, synchronizes through a store-based
    :class:`StoreBarrier` (collectives are not thread-safe to issue off
    the main thread — reference comment snapshot.py:948), and rank 0
    writes the commit marker only if every rank succeeded. Errors
    propagate to every rank through the barrier and re-raise in
    ``wait()``.

    The snapshot moves through three phases (docs/async.md):

    - **visible** — over by the time the caller holds this handle: the
      plan collectives ran and a consistent snapshot is pinned (device
      clones / host copies); the live state is free.
    - **staged** — background D2H + serialization finished; the bytes
      sit in host buffers (and, for tiered paths, partly in the fast
      tier). ``wait(phase="staged")``.
    - **committed** — every rank's writes are durable and the commit
      marker exists. ``wait()`` / ``wait(phase="committed")``.

    ``after_commit`` (``CheckpointManager.async_save``'s index,
    retention and history for the step) runs on the commit thread once
    the commit succeeded, handed the committed snapshot, before
    ``done()`` turns true; a failed take never reaches it, and what it
    raises is logged and never becomes the take's error.
    """

    def __init__(
        self,
        op: _TakeOp,
        op_begin: float,
        after_commit: Optional[Callable[["Snapshot"], None]] = None,
    ) -> None:
        import threading

        self._op = op
        # Handed in, not set from outside, for the reason on_staged is
        # wired below: a tiny state commits before the caller could.
        self._after_commit = after_commit
        self.path = op.path
        # The flight recorder's id of this take (the stage envelope's):
        # the commit envelope joins it, and so does what the manager
        # does for the step behind it.
        self.trace_op = op.trace_op
        self.commit_nonce = op.nonce
        self.pg = op.pg
        self._exc_info: Optional[BaseException] = None
        self._done = threading.Event()
        self._staged = threading.Event()
        # Phase-split telemetry, relative to async_take's entry, for the
        # doctor's async-visible-stall rule: the visible span is over by
        # construction time (this handle IS the return value); staged_s
        # is stamped by the drain callback.
        op.phases["visible_s"] = round(time.monotonic() - op_begin, 6)
        if isinstance(op.pending_io_work, DeferredIOWork):
            # Wired BEFORE the thread starts: the drain may reach the
            # staged boundary arbitrarily fast.
            def _mark_staged() -> None:
                op.phases["staged_s"] = round(time.monotonic() - op_begin, 6)
                self._staged.set()

            op.pending_io_work.on_staged = _mark_staged
        else:
            # Non-deferred takes staged before this handle existed.
            op.phases["staged_s"] = op.phases["visible_s"]
            self._staged.set()
        self._thread = threading.Thread(
            target=self._complete_snapshot, name="snapshot-commit", daemon=True
        )
        self._thread.start()

    def _complete_snapshot(self) -> None:
        # Taken, not kept: the manager's hook refers to the handle that
        # holds this one, and a cycle would keep the take's requests and
        # buffers until the collector finds them.
        after_commit, self._after_commit = self._after_commit, None
        commit_span = _tracing.begin(
            telemetry.names.SPAN_ASYNC_TAKE_COMMIT,
            op=self.trace_op,
            path=self.path,
            rank=self._op.rank,
        )
        try:
            self._op.commit(commit_span)
        except BaseException as e:  # noqa: BLE001 - must propagate via wait()
            self._exc_info = e
            logger.error("Async snapshot failed: %r", e)
        else:
            if after_commit is not None:
                try:
                    after_commit(self._op.snapshot())
                except Exception as e:  # noqa: BLE001 - the take succeeded
                    logger.warning(
                        "after-commit work for %s failed on the commit "
                        "thread (the snapshot is committed): %r",
                        self.path,
                        e,
                    )
        finally:
            # Ordering matters on the failure path: the error is recorded
            # and the heartbeat settled TERMINAL ("failed", never a
            # crash-shaped non-terminal leftover) before the staged/done
            # events release any waiter — a woken wait() must observe the
            # final state, exactly once, not a half-settled one.
            _tracing.end(commit_span)  # no-op if already closed
            self._op.settle(self._exc_info)
            self._staged.set()  # no-op if staging completed normally
            self._done.set()

    def wait(self, phase: str = "committed") -> Optional[Snapshot]:
        """Block until the snapshot reaches ``phase``:

        - ``"staged"`` — background staging (D2H + serialize) finished;
          returns None (there is no committed snapshot yet). The legacy
          unblock point: everything the pre-deferral ``async_take``
          guaranteed at return time holds here.
        - ``"committed"`` (default) — storage drain + commit barrier
          done on every rank; returns the committed :class:`Snapshot`.

        A background failure re-raises here — on the first ``wait()``
        that observes it and on every later one (callers polling
        ``wait(phase="staged")`` then ``wait()`` see it at both, rather
        than a success after an error). The progress heartbeat is
        settled terminal by the drain thread before any waiter wakes."""
        if phase not in ("staged", "committed"):
            raise ValueError(
                f'phase must be "staged" or "committed", got {phase!r}'
            )
        if phase == "staged":
            self._staged.wait()
            if self._exc_info is not None:
                raise self._exc_info
            return None
        self._thread.join()
        if self._exc_info is not None:
            raise self._exc_info
        return self._op.snapshot()

    def done(self) -> bool:
        return self._done.is_set()

    def staged(self) -> bool:
        """True once background staging finished (``wait(phase="staged")``
        will not block). Also true after a failed drain — ``wait`` then
        raises instead of blocking."""
        return self._staged.is_set()


# ---------------------------------------------------------------------------
# restore: one set-up, one read pipeline, one report, two drivers
# ---------------------------------------------------------------------------


def _agree_restore(pg_wrapper: PGWrapper) -> Tuple[Optional[str], bool]:
    """A restore's ONE agreement collective, before any failure point:
    the nonce of its error-propagating barriers and, riding the same
    broadcast, whether shard blobs fan out — rank 0's knob reading
    decides for the whole job (env skew can never diverge the schedule)
    and a later set-up failure can never leave the shared op-seq counter
    half-advanced. ``(None, False)`` in a world of one."""
    if pg_wrapper.get_world_size() <= 1:
        return None, False
    import uuid

    return pg_wrapper.broadcast_object(
        (uuid.uuid4().hex, knobs.is_fanout_restore_enabled())
    )


class _RestoreOp:
    """One restore from its agreement to its report: the steps that
    :meth:`Snapshot.restore` and :meth:`Snapshot.async_restore` share,
    each written once — set-up, fan-out exchange, the read pipeline, the
    report. What differs stays with the drivers: which plans go through
    one pipeline, on which thread, and when they are applied.

    ``restore`` is not ``async_restore().wait()``: it plans, reads and
    applies one stateful at a time, so only that stateful's new arrays
    stand beside its old ones in HBM and its pipeline takes its own cap
    of the destination pool; the async driver holds every stateful's new
    arrays until ``wait()`` and reads on another thread."""

    def __init__(
        self,
        snapshot: Snapshot,
        kind: str,
        pg_wrapper: PGWrapper,
        agreement: Tuple[Optional[str], bool] = (None, False),
    ) -> None:
        self.snapshot = snapshot
        self.kind = kind
        self.path = snapshot.path
        self.pg = pg_wrapper
        self.rank = pg_wrapper.get_rank()
        self.nonce, self.fanout_agreed = agreement
        self.counter_baseline = telemetry.metrics().counters_snapshot()
        self.tunables = knobs.tunable_snapshot()
        self.trace_mark = _trace_recorder().mark()
        self.trace_op = 0
        self.tracker = _progress.track(kind, self.path, self.rank)
        self.memory_budget_bytes = 0
        self.event_loop: Optional[asyncio.AbstractEventLoop] = None
        self.storage: Optional[StoragePlugin] = None
        self.available: Manifest = {}
        self.checksum_table: Any = None
        self.peer_ctx: Any = None
        self.fanout_ctx: Any = None
        self.cold_start: Dict[str, float] = {}
        # One entry a read pipeline, merged by the report.
        self.pipelines: List[dict] = []

    def barrier(self, tag: Any) -> Optional[StoreBarrier]:
        """The error-propagating barrier ``tag`` of this restore (same
        design as the take commit barrier): a rank whose reads fail —
        bit rot, a CRC mismatch — reports before raising, so peers
        waiting there abandon instead of blocking out the full store
        timeout. All of a restore's barriers hang off its one nonce."""
        if self.nonce is None:
            return None
        return _nonce_barrier(f"__restore/{self.nonce}/{tag}", self.pg)

    def set_up(
        self, barrier: Optional[StoreBarrier], memory_budget_bytes: int
    ) -> None:
        """Open what the reads need, on the calling thread, after the
        driver's collectives: the event loop, the storage plugin behind
        the peer ladder, the rank's manifest, the checksum table and the
        fan-out owner table. A failure is reported to ``barrier``, the
        first one peers wait at."""
        self.memory_budget_bytes = memory_budget_bytes
        with _reporting_to(barrier, f"{self.kind} setup"):
            # Cold-start attribution: the envelope work before the first
            # storage byte can move — event-loop spin-up, plugin open,
            # and the native digest library's first load — timed
            # separately so a first-trial restore that dwarfs warm trials
            # convicts its cause in the report (``cold_start`` /
            # ``cold_start_s``) instead of leaving the gap a guess.
            t = time.monotonic()
            self.event_loop = asyncio.new_event_loop()
            self.cold_start["event_loop_s"] = time.monotonic() - t
            t = time.monotonic()
            self.storage = url_to_storage_plugin(self.path)
            self.cold_start["plugin_open_s"] = time.monotonic() - t
            t = time.monotonic()
            from .integrity import _alg_available

            _alg_available("crc32c")  # first call loads the native lib
            self.cold_start["native_load_s"] = time.monotonic() - t
            # Peer-tier ladder (docs/peer.md): when surviving peers hold
            # this step's shards in RAM, reads resolve peer -> fast ->
            # durable per blob, digest-verified. Build is rank-local
            # (inventory RPCs, no collectives), so peers building or
            # not building the ladder independently can never diverge
            # the restore schedule; every failure degrades to None. The
            # pulls are point-to-point socket reads, safe on a read
            # thread.
            from .tiered import peer as _peer_tier

            self.peer_ctx = _peer_tier.build_restore_context(self.path)
            if self.peer_ctx is not None:
                self.storage = self.peer_ctx.wrap(self.storage)
            with trace_annotation(telemetry.names.SPAN_RESTORE_PLAN):
                self.available = get_manifest_for_rank(
                    self.snapshot.metadata, self.rank
                )
                self.checksum_table = self.snapshot._get_checksum_table(
                    self.storage, self.event_loop
                )
            # Single-reader fan-out (docs/restore.md): enablement was
            # broadcast-agreed; the owner table is derived
            # deterministically from the committed manifest (same bytes
            # on every rank), inside the error-aware set-up window like
            # every other failure-prone set-up read.
            if self.fanout_agreed:
                from .fanout import FanoutRestoreContext

                fanout_ctx = FanoutRestoreContext.build(
                    self.snapshot.metadata.manifest, self.pg
                )
                if fanout_ctx.owners:  # else nothing shard-shaped to fan out
                    self.fanout_ctx = fanout_ctx

    def exchange(
        self, plans: List["_StatefulLoadPlan"], tag: Any
    ) -> List[str]:
        """One fan-out round over ``plans`` (none: this rank loads
        nothing this round, and still takes part) under barrier ``tag``,
        whose error key the round's waits poll. On the thread that owns
        collective ordering. Returns the locations it cached."""
        if self.fanout_ctx is None:
            return []
        return self.fanout_ctx.exchange(
            [r for plan in plans for r in plan.read_reqs],
            self.storage,
            self.event_loop,
            rendezvous_prefix=f"__restore/{self.nonce}/{tag}",
        )

    def read_plans(
        self, plans: List["_StatefulLoadPlan"], apply: bool
    ) -> None:
        """The read pipeline, spelled here alone: one pipeline over the
        reads of ``plans``, with streaming placement and destinations
        leased from the process's pool, the exchanged shard blobs served
        from the fan-out cache and the rest from the plugin. On the
        thread that calls it. ``apply``: each plan is handed to the
        application as soon as the pipeline is through (the sync driver,
        a key at a time); otherwise its last placements are dispatched
        and ``wait()`` applies it."""
        read_reqs = [r for plan in plans for r in plan.read_reqs]
        # The rank's pre-batching destination bytes — the denominator of
        # the read-amplification metric restore reports carry.
        bytes_needed = sum(_req_needed_bytes(r) for r in read_reqs)
        if knobs.is_batching_enabled():
            from .batcher import batch_read_requests

            read_reqs = batch_read_requests(read_reqs)
        # Streaming placement: completed leaves device_put in rolling
        # batches while the remaining reads are still in flight.
        placer = _StreamingPlacer()
        for plan in plans:
            placer.register_plan(plan)
        placer.lease_destinations(read_reqs, self.memory_budget_bytes)
        fanout_ctx = self.fanout_ctx
        try:
            pipeline = sync_execute_read_reqs(
                read_reqs=read_reqs,
                storage=(
                    fanout_ctx.wrap(self.storage)
                    if fanout_ctx is not None
                    else self.storage
                ),
                memory_budget_bytes=self.memory_budget_bytes,
                rank=self.rank,
                event_loop=self.event_loop,
                checksum_table=self.checksum_table,
                on_req_complete=placer.on_req_complete,
                progress=self.tracker,
                classify_read=(
                    fanout_ctx.classify_read
                    if fanout_ctx is not None
                    else None
                ),
                destinations=placer.leases,
            )
            pipeline["bytes_needed"] = bytes_needed
            placer.report_destinations(pipeline)
            self.pipelines.append(pipeline)
            placer.flush()
            if apply:
                for plan in plans:
                    plan.apply(finish=True)
            else:
                _finish_plans(plans)
        except BaseException:
            placer.abandon_destinations()
            raise

    def close_storage(self) -> None:
        self.event_loop.run_until_complete(self.storage.close())

    def report(self, nonce: Optional[str]) -> None:
        """Emit the restore's report: the pipelines merged, the fan-out
        and peer-tier byte accounting, the cold-start split. ``nonce``
        None keeps it rank-local (no cross-rank gather)."""
        pipeline = telemetry.merge_pipeline_telemetry(self.pipelines)
        _merge_fanout_telemetry(pipeline, self.fanout_ctx)
        _merge_peer_telemetry(pipeline, self.peer_ctx)
        # Round the parts BEFORE summing: the report layer rounds each
        # part to 6dp on serialization, so deriving the total from the
        # raw values can disagree with the serialized parts by 1e-06 for
        # unlucky timings.
        cold_start = {k: round(v, 6) for k, v in self.cold_start.items()}
        pipeline["cold_start"] = cold_start
        pipeline["cold_start_s"] = round(sum(cold_start.values()), 6)
        _emit_snapshot_report(
            kind=self.kind,
            path=self.path,
            pg_wrapper=self.pg,
            pipeline=pipeline,
            counter_baseline=self.counter_baseline,
            nonce=nonce,
            trace_mark=self.trace_mark,
            tunables=self.tunables,
            trace_op=self.trace_op,
        )

    def settle(self, error: Optional[BaseException]) -> None:
        """The end of the restore's reads on the thread that ran them,
        failed or not."""
        self.tracker.finish(error)
        if self.event_loop is not None:
            self.event_loop.close()


class PendingRestore:
    """Handle on an in-flight async restore (see Snapshot.async_restore).

    The background thread runs only storage reads, deserialization, and
    device placement of addressable data — never collectives (the same
    rule the async-take commit thread follows, reference snapshot.py:948).
    ``wait()`` joins it, re-raises any failure *before* touching app
    state, then applies the restored state dicts on the calling thread in
    globally-sorted key order with barriers in between (load_state_dict
    may run collectives)."""

    def __init__(
        self,
        op: _RestoreOp,
        keys: List[str],
        plans: Dict[str, _StatefulLoadPlan],
        rng_key: Optional[str],
    ) -> None:
        import threading

        self._op = op
        self.path = op.path
        self._keys = keys
        self._plans = plans
        self._rng_key = rng_key
        # The op async_restore's planning envelope opened; the reads
        # envelope joins it, and wait() applies and reports under it.
        self.trace_op = op.trace_op
        self._exc_info: Optional[BaseException] = None
        self._applied = False
        self._done = threading.Event()
        self._thread = threading.Thread(
            target=self._run_reads, name="restore-reads", daemon=True
        )
        self._thread.start()

    def _run_reads(self) -> None:
        op = self._op
        reads_span = _tracing.begin(
            telemetry.names.SPAN_ASYNC_RESTORE_READS,
            op=self.trace_op,
            path=self.path,
            rank=op.rank,
        )
        try:
            op.read_plans(list(self._plans.values()), apply=False)
            _settle_destinations()
            op.close_storage()
        except BaseException as e:  # noqa: BLE001 - must propagate via wait()
            self._exc_info = e
            logger.error("Async restore failed: %r", e)
        finally:
            # Release the exchanged shard bytes whether or not the reads
            # succeeded; the handle may outlive the restore.
            if op.fanout_ctx is not None:
                op.fanout_ctx.clear()
            _tracing.end(reads_span)
            op.settle(self._exc_info)
            self._done.set()

    def wait(self) -> None:
        """Block until reads finish, then apply the state dicts. Must be
        called from the thread that owns collective ordering (the one
        that called async_restore).

        Failure semantics match the sync restore: a rank whose reads (or
        applies) failed reports the error into the barrier its peers are
        waiting at and raises; the peers observe it and abandon within
        seconds (no commit-style retry — a failed distributed restore is
        fatal to the job, not recoverable per-rank)."""
        self._thread.join()
        op = self._op
        if self._exc_info is not None:
            # State was never applied; the read buffers are useless.
            # Release them before raising (the handle may be kept for
            # diagnostics, and a retry will allocate its own). Peers whose
            # reads succeeded are waiting at the FIRST apply barrier —
            # tell them before raising.
            self._plans = {}
            first = op.barrier(0) if self._keys else None
            with _reporting_to(first, "restore-read"):
                raise self._exc_info
        if self._applied:
            return
        # One barrier per gathered KEY, plan or no plan, as in restore():
        # different ranks may hold plans for different keys (per-rank
        # statefuls, elastic world-size changes), and a per-plan barrier
        # count would diverge and deadlock. The RNG plan is skipped here
        # — its key is rank-local knowledge, so it must not perturb the
        # shared schedule — and applied after all barriers (RngState
        # application is collective-free): the restore-RNG-last invariant.
        for i, key in enumerate(self._keys):
            barrier = op.barrier(i)
            with _reporting_to(barrier, "restore-apply"):
                plan = self._plans.get(key)
                if plan is not None and key != self._rng_key:
                    self._apply(plan)
            # load_state_dict may run collectives; keep global order
            # (reference snapshot.py:466-476 barrier discipline).
            if barrier is not None:
                barrier.arrive()
                barrier.depart()
        rng_plan = self._plans.get(self._rng_key) if self._rng_key else None
        if rng_plan is not None:
            self._apply(rng_plan)
        # Applied only if every plan succeeded: a raised apply leaves the
        # handle un-applied, so a retried wait() re-applies from the start
        # (deterministic) instead of silently succeeding half-restored.
        self._applied = True
        # Local report only (no cross-rank gather): wait() call times are
        # application-controlled, and the emission must not add a
        # rendezvous of its own to the apply schedule.
        op.report(nonce=None)
        # Release the checkpoint-sized host buffers the plans hold; the
        # handle itself may outlive the restore (done()-polling callers).
        self._plans = {}

    def _apply(self, plan: _StatefulLoadPlan) -> None:
        with _op_scope(self.trace_op):
            plan.apply()

    def done(self) -> bool:
        """True once background reads finished (wait() will not block)."""
        return self._done.is_set()


# ---------------------------------------------------------------------------
# Helpers
# ---------------------------------------------------------------------------


def _validate_app_state(app_state: AppState) -> None:
    """Reference parity: snapshot.py:658-666."""
    if not isinstance(app_state, dict):
        raise TypeError(
            f"app_state must be a Dict[str, Stateful], got {type(app_state)}"
        )
    for key, value in app_state.items():
        if not isinstance(key, str):
            raise TypeError(f"app_state keys must be str, got {type(key)}")
        if not (hasattr(value, "state_dict") and hasattr(value, "load_state_dict")):
            raise TypeError(
                f"app_state[{key!r}] ({type(value)}) does not implement the "
                f"Stateful protocol (state_dict/load_state_dict). Wrap pure "
                f"pytrees in PyTreeState."
            )


def _pop_rng_state(app_state: AppState) -> Optional[Tuple[str, RngState]]:
    """At most one RngState is allowed (reference snapshot.py:858-877)."""
    rng_items = [(k, v) for k, v in app_state.items() if isinstance(v, RngState)]
    if len(rng_items) > 1:
        raise RuntimeError(
            f"At most one RngState is allowed in app_state "
            f"(found {[k for k, _ in rng_items]})"
        )
    if not rng_items:
        return None
    key, stateful = rng_items[0]
    del app_state[key]
    # Caller re-inserts after processing so the dict is left intact.
    app_state[key] = stateful
    return key, stateful


def _gather_keys(
    app_state: AppState,
    pg_wrapper: PGWrapper,
) -> List[str]:
    """Sorted union of app-state keys across ranks (reference
    snapshot.py:851-856). Deliberately *never* reordered by rank-local
    facts (e.g. which key holds the RngState): the list defines the
    barrier/collective schedule and must be identical on every rank."""
    local_keys = list(app_state.keys())
    gathered = pg_wrapper.all_gather_object(local_keys)
    return sorted({k for ks in gathered for k in ks})


def _coalesce_replicated(
    replicated: List[str], pg_wrapper: PGWrapper
) -> List[str]:
    """Intersection of replication globs across ranks (reference
    snapshot.py:789-849): a path is treated as replicated only if every rank
    declared it."""
    if pg_wrapper.get_world_size() == 1:
        return list(replicated)
    gathered = pg_wrapper.all_gather_object(sorted(replicated))
    common = set(gathered[0])
    for patterns in gathered[1:]:
        common &= set(patterns)
    return sorted(common)


def _infer_replicated_paths(
    flattened: Dict[str, Any], world_size: int
) -> Set[str]:
    """Auto-detect replicated leaves from their GSPMD sharding — the
    TPU-native analog of the reference's DDP-module introspection
    (reference snapshot.py:828-844).

    A ``jax.Array`` fully replicated over more than one device is inferred
    replicated only when that is a *global* declaration:

    - world size 1: trivially global — the snapshot holds exactly one
      value, so marking it replicated only widens restore-time
      availability (any future world size reads it).
    - world size > 1: only when the sharding's devices span more than one
      process — under SPMD a multi-process ``jax.Array`` holds one
      consistent global value, so every participating process has the
      same bytes. An array replicated over a rank's *local* devices only
      (e.g. per-host statistics) carries no cross-rank guarantee and is
      never inferred; per-rank state must stay per-rank.

    Single-device arrays carry no declaration at all and are never
    inferred (the reference likewise only infers from the explicit DDP
    wrapper, not from plain tensors).
    """
    inferred: Set[str] = set()
    for path, leaf in flattened.items():
        if not is_jax_array(leaf):
            continue
        sharding = getattr(leaf, "sharding", None)
        if (
            sharding is None
            or not sharding.is_fully_replicated
            or len(sharding.device_set) <= 1
        ):
            continue
        if world_size > 1:
            processes = {d.process_index for d in sharding.device_set}
            if len(processes) <= 1:
                continue
        inferred.add(path)
    return inferred


def _calculate_replicated_entries(
    flattened: Dict[str, Any],
    patterns: List[str],
    pg_wrapper: PGWrapper,
    inferred: Optional[Set[str]] = None,
) -> Set[str]:
    """Glob-match replication patterns and verify matched paths exist on
    every rank; rank 0 decides, everyone follows (reference
    snapshot.py:623-656)."""
    matched = {
        path
        for path in flattened
        if any(fnmatch.fnmatch(path, p) for p in patterns)
    }
    if inferred:
        matched |= inferred & set(flattened)
    if pg_wrapper.get_world_size() == 1:
        return matched
    # Gather-to-leader + broadcast of the decision: "rank 0 decides,
    # everyone follows" never needed every rank to hold every rank's
    # matched list — non-leaders send O(own list) and receive O(common).
    all_matched = pg_wrapper.gather_object(sorted(matched))
    common: List[str] = []
    if all_matched is not None:
        common_set: Set[str] = set(all_matched[0])
        for paths in all_matched[1:]:
            common_set &= set(paths)
        common = sorted(common_set)
    verified = pg_wrapper.broadcast_object(common)
    return set(verified)


def _gather_manifest(
    rank_manifest: Manifest, pg_wrapper: PGWrapper
) -> Optional[Manifest]:
    """Gather per-rank manifests TO RANK 0 and merge into the global
    ``{rank}/{path}``-keyed manifest there; returns None on every other
    rank (reference snapshot.py:879-901 all_gathers over c10d, which
    spreads the world² bytes peer-to-peer; over a KV store the leader is
    the only socket, so the non-leaders — which don't need the global
    manifest: rank 0 alone writes metadata, and restore lazy-loads it
    from storage post-commit — must not each pull O(world x manifest)
    bytes through it)."""
    from .manifest import is_replicated

    gathered = pg_wrapper.gather_object(rank_manifest)
    if gathered is None:
        return None
    merged_replicated: Manifest = {}
    if pg_wrapper.get_world_size() > 1:
        from .partitioner import consolidate_replicated_entries

        merged_replicated = consolidate_replicated_entries(gathered)

    global_manifest: Manifest = {}
    for rnk, manifest in enumerate(gathered):
        for logical_path, entry in manifest.items():
            if is_replicated(entry) and not is_container_entry(entry):
                if rnk > 0:
                    continue  # replicated entries live under rank 0 only
                entry = merged_replicated.get(logical_path, entry)
            global_manifest[f"{rnk}/{logical_path}"] = entry
    return global_manifest


def _get_checksum_table_impl(
    world_size: int,
    storage: StoragePlugin,
    event_loop: asyncio.AbstractEventLoop,
):
    """Merged digests of every writer rank, or None (no tables written,
    or verification disabled)."""
    if knobs.is_checksums_disabled():
        return None
    from .integrity import load_checksum_tables

    return load_checksum_tables(world_size, storage, event_loop)


def _maybe_write_checksum_table(
    pending_io_work: PendingIOWork,
    rank: int,
    storage: StoragePlugin,
    event_loop: asyncio.AbstractEventLoop,
) -> None:
    """Persist this rank's blob digests (recorded during the write
    pipeline) before the commit barrier: a committed snapshot always has
    complete tables. No-ops when checksums are disabled (the pipeline
    recorded nothing)."""
    if not pending_io_work.checksums:
        return
    from .integrity import sync_write_checksum_table

    sync_write_checksum_table(
        pending_io_work.checksums, rank, storage, event_loop
    )


def _write_checksum_and_cas_tables(
    pending_io_work: "PendingIOWork | DeferredIOWork",
    rank: int,
    storage: StoragePlugin,
    event_loop: asyncio.AbstractEventLoop,
) -> None:
    """What a rank makes durable between its last blob and the commit
    barrier, sync take and async commit thread alike: the (finalized)
    checksum table, then the CAS chunk map, a kill point after each."""
    pending_io_work.finalize_checksums()
    _maybe_write_checksum_table(pending_io_work, rank, storage, event_loop)
    _crashpoint(telemetry.names.CRASH_CHECKSUM_TABLE_WRITTEN)
    _maybe_write_cas_map(storage, rank, event_loop)
    _crashpoint(telemetry.names.CRASH_CAS_MAP_WRITTEN)


def _restore_destination(
    entry: "ArrayEntry | ChunkedArrayEntry",
    current_leaf: Any,
    late: bool = False,
) -> Tuple[
    Optional[np.ndarray], Optional[Callable[[np.ndarray], Any]], bool
]:
    """Pick/allocate the host read destination for a dense entry and, when
    the application's current leaf is a device array, a converter that puts
    the restored bytes back on its device/sharding. The third element says
    whether the destination is framework-allocated (owned): only owned
    buffers may be direct-read targets — the application's own in-place
    array keeps copy-on-success semantics so a failed restore can't tear
    it.

    ``late``: the caller can do without the destination until the read
    is admitted. It is then None for a leaf whose host bytes nobody sees
    after placement: one blob that ``device_put`` copies to devices none
    of which is a CPU. Such a read gets a recycled slab of ``dest_pool``.
    On the CPU backend ``device_put`` may alias an aligned numpy buffer,
    so a recycled slab would rewrite an array the application holds;
    a host ``np.ndarray`` leaf and an uncommitted leaf (``jnp.asarray``)
    hand their buffer on as is. Those keep a fresh ``np.empty``."""
    if isinstance(current_leaf, np.ndarray) and ArrayIOPreparer.can_load_inplace(
        _as_array_entry(entry), current_leaf
    ):
        return current_leaf, None, False
    if (
        hasattr(current_leaf, "shape")
        and list(getattr(current_leaf, "shape")) != list(entry.shape)
    ):
        # JAX state is replaced, not mutated, so the checkpointed shape wins;
        # but a silent shape change usually means the wrong checkpoint.
        logger.warning(
            "Restoring shape %s over a current leaf of shape %s; the "
            "checkpointed value replaces the leaf",
            list(entry.shape),
            list(current_leaf.shape),
        )
    if is_jax_array(current_leaf):
        import jax

        sharding = current_leaf.sharding
        # Uncommitted leaves (e.g. optax step counters created by plain
        # jnp ops) must stay uncommitted: committing them to a concrete
        # device makes the restored state unusable in a jit alongside
        # differently-placed arrays.
        committed = getattr(current_leaf, "_committed", True)
        dst = None
        if not (
            late
            and isinstance(entry, ArrayEntry)
            and _bound_for_accelerator(current_leaf)
        ):
            dst = ArrayIOPreparer.empty_array_from_entry(entry)

        def convert(
            host: np.ndarray, batch: Optional["_PlacementBatch"] = None
        ) -> Any:
            if not committed:
                import jax.numpy as jnp

                return jnp.asarray(host)
            if batch is None:
                return jax.device_put(host, sharding)
            # Registered into the restore-wide batched device_put; the
            # caller resolves the slot after batch.run().
            return batch.put(host, sharding)

        return dst, convert, True
    return ArrayIOPreparer.empty_array_from_entry(entry), None, True


def _settle_destinations() -> None:
    """Before a restore hands its arrays to the application: wait until
    those placed from pooled slabs are on their devices, so that every
    slab is back while its array can still be asked (``dest_pool``). The
    wait is the tail of the last placements' transfers."""
    pool = dest_pool.process_pool()
    waiting = pool.unsettled()
    if waiting:
        with trace_annotation(
            telemetry.names.SPAN_RESTORE_PLACE, arrays=waiting, bytes=0
        ):
            pool.settle()


def _bound_for_accelerator(leaf: Any) -> bool:
    """Whether nobody sees the host bytes a restore reads for ``leaf``
    once they are placed, so that they may land in recycled memory of
    ``dest_pool``: a committed ``jax.Array`` whose placement copies. A
    host ``np.ndarray`` and an uncommitted leaf (``jnp.asarray``) hand
    their buffer on as it is."""
    return (
        is_jax_array(leaf)
        and getattr(leaf, "_committed", True)
        and _placement_copies(leaf.sharding)
    )


def _placement_copies(sharding: Any) -> bool:
    """Whether ``device_put`` under ``sharding`` copies the host buffer:
    no target device is a CPU (whose arrays may alias host memory)."""
    return all(d.platform != "cpu" for d in sharding.device_set)


def _as_array_entry(entry: "ArrayEntry | ChunkedArrayEntry") -> ArrayEntry:
    if isinstance(entry, ArrayEntry):
        return entry
    from .serialization import Serializer

    return ArrayEntry(
        location="",
        serializer=Serializer.BUFFER_PROTOCOL.value,
        dtype=entry.dtype,
        shape=entry.shape,
        replicated=entry.replicated,
    )

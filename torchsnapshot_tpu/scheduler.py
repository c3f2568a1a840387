"""Pipelined write/read execution under a host-memory budget.

Reference parity: torchsnapshot/scheduler.py. Same contract, different
machinery: instead of explicit state-set juggling (scheduler.py:237-330),
each request runs as its own coroutine —

    write:  acquire budget -> stage (device->host + serialize, on a thread
            pool) -> re-price budget to actual buffer size -> acquire an I/O
            slot -> storage.write -> release budget
    read:   acquire budget -> acquire I/O slot -> storage.read -> release
            slot -> consume (deserialize + copy, on a thread pool) -> release

Admission control lives in :class:`MemoryBudget`: a request larger than the
whole budget is admitted only when nothing else is in flight (reference rule,
scheduler.py:266-271), so huge buffers serialize instead of deadlocking.

``execute_write_reqs`` returns a :class:`PendingIOWork` as soon as *staging*
has finished for every request (scheduler.py:224-234): from then on the
application may mutate/free device arrays while storage I/O drains in the
background. Device-snapshot async takes go further: :class:`DeferredIOWork`
defers the WHOLE pipeline to the background commit thread, running it
through a :class:`StagingPool` (an admission controller whose window is
sized from the plan, or pinned by the operator) so host staging memory is
bounded by that window, not by the checkpoint's size — the training-
visible span ends at capture, before any staging ran (docs/async.md).
"""

from __future__ import annotations

import asyncio
import heapq
import logging
import math
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import TYPE_CHECKING, Any, Callable, List, Optional, Sequence

import psutil

if TYPE_CHECKING:  # pragma: no cover - typing only
    from .dest_pool import DestinationLeases
    from .telemetry.progress import ProgressTracker

from . import knobs, telemetry
from .dest_pool import Lease
from .telemetry.trace import get_recorder as _trace_recorder
from .utils.tracing import run_in_executor, trace_annotation
from .integrity import (
    ChecksumError,
    ChecksumTable,
    compute_checksum_entry,
    verify_checksum,
    verify_page_crcs,
    verify_range_checksum,
)
from .io_types import (
    BufferList,
    ReadIO,
    ReadReq,
    StoragePlugin,
    WriteIO,
    WriteReq,
)

logger: logging.Logger = logging.getLogger(__name__)

# Observability: wall-clock phase completions (seconds since the
# pipeline's reporter started) of the most recent write/read pipeline run
# in this process, keyed by phase name ("staging"/"writing"/"loading").
# Historically a module-level dict here; now a compatibility shim over
# the telemetry registry's phase-timing channel (telemetry/registry.py),
# which also feeds the snapshot_phase_seconds histogram. Semantics are
# unchanged: last-writer-wins across concurrent pipelines — callers that
# care (bench.py's in-take stall diagnosis) run one pipeline at a time.


def reset_phase_timings() -> None:
    telemetry.metrics().reset_phase_timings()


def last_phase_timings() -> dict:
    return telemetry.metrics().last_phase_timings()


def record_phase_timing(phase: str, elapsed_s: float) -> None:
    """Publish a phase completion into the machine-readable channel from
    outside the pipeline (the tiered mirror records its "mirroring" phase
    here, next to the pipeline's staging/writing/loading entries)."""
    telemetry.record_phase(phase, elapsed_s)


# Near-zero-elapsed throughput guard (div-by-~0 would print inf MB/s);
# one shared threshold with the snapshot-stats renderer.
safe_rate_mb_s = telemetry.safe_rate_mb_s

_MAX_PER_RANK_MEMORY_BUDGET_BYTES: int = 32 * 1024 * 1024 * 1024
_LOG_LINE_LIMIT = 8
# Non-fused checksum compute runs inline (on the event loop) below this
# size: even on the slicing-by-8 software CRC (~0.4 GB/s) 64 KiB stalls
# the loop well under a millisecond, while an executor round-trip costs
# ~0.1 ms per request regardless of size.
_INLINE_CHECKSUM_BYTES = 64 * 1024


def _verify_blob(mode: str, blob: str, nbytes: int, verify, *args):
    """One checksum verification of read bytes under its ``verify:blob``
    span, on whichever thread runs it (inline or the executor). ``mode``:
    ``whole`` re-hashes the blob, ``range`` the pages a ranged read
    covers, ``pages`` folds the CRCs the fused read already computed."""
    with trace_annotation(
        telemetry.names.SPAN_VERIFY_BLOB, bytes=nbytes, mode=mode, blob=blob
    ):
        return verify(*args)


def get_process_memory_budget_bytes(pg=None) -> int:
    """Per-process host-memory budget for staging/consuming buffers.

    ``min(available_host_memory * fraction / local_world_size, 32 GiB)``
    with an env-var override (reference: scheduler.py:45-65). The
    fraction defaults to the historical 0.6 and is a tunable knob
    (TORCHSNAPSHOT_TPU_MEMORY_BUDGET_FRACTION — the autotuner's
    budget-starved lever). ``local_world_size`` counts co-hosted
    processes via a hostname all-gather on ``pg`` — on TPU pods this is
    processes per host, not chips per host.
    """
    override = knobs.get_per_rank_memory_budget_bytes_override()
    if override is not None:
        logger.info("Memory budget manually set to %d bytes", override)
        return override
    available = int(
        psutil.virtual_memory().available * knobs.get_memory_budget_fraction()
    )
    local_world_size = 1
    if pg is not None and pg.get_world_size() > 1:
        import socket

        hostnames = pg.all_gather_object(socket.gethostname())
        local_world_size = sum(1 for h in hostnames if h == socket.gethostname())
    budget = min(available // local_world_size, _MAX_PER_RANK_MEMORY_BUDGET_BYTES)
    logger.info("Memory budget set to %d bytes", budget)
    return budget


class MemoryBudget:
    """Async counting budget with an idle-admission escape hatch.

    ``acquire(cost)`` waits until ``cost`` fits, or until the pipeline is
    completely idle (in which case an oversized request is admitted alone).
    ``adjust(delta)`` re-prices a held reservation (staging cost vs actual
    buffer size can differ, e.g. non-contiguous arrays); ``release`` returns
    the final amount.
    """

    def __init__(self, total_bytes: int) -> None:
        self.total_bytes = total_bytes
        self.available_bytes = total_bytes
        self.inflight = 0
        self._cond: asyncio.Condition = asyncio.Condition()
        # Telemetry: cumulative admission-wait seconds (how long requests
        # sat blocked on the budget — the FastPersist-style signal for
        # "the budget, not the storage, is the bottleneck") and the peak
        # concurrently-reserved bytes this budget ever carried.
        self.wait_s = 0.0
        self.peak_reserved_bytes = 0

    def _note_reserved(self) -> None:
        reserved = self.total_bytes - self.available_bytes
        if reserved > self.peak_reserved_bytes:
            self.peak_reserved_bytes = reserved

    async def acquire(self, cost_bytes: int) -> None:
        t0 = time.monotonic()
        async with self._cond:
            await self._cond.wait_for(
                lambda: cost_bytes <= self.available_bytes or self.inflight == 0
            )
            self.available_bytes -= cost_bytes
            self.inflight += 1
            self._note_reserved()
        waited = time.monotonic() - t0
        self.wait_s += waited
        telemetry.metrics().histogram_observe(
            telemetry.names.MEMORY_BUDGET_WAIT_SECONDS, waited
        )

    async def adjust(self, delta_bytes: int) -> None:
        async with self._cond:
            self.available_bytes -= delta_bytes
            self._note_reserved()
            if delta_bytes < 0:
                self._cond.notify_all()

    async def release(self, cost_bytes: int) -> None:
        async with self._cond:
            self.available_bytes += cost_bytes
            self.inflight -= 1
            self._cond.notify_all()


# Device-to-host transfers the window leaves room for: with a train loop
# beside it the host side of the link stops gaining between 8 and 16 in
# flight (chipbench/probe_d2h_depth.py; PERF.md section 6, PR 32), which
# is as far as raising ``staging_threads`` can pay.
LINK_TRANSFERS_IN_FLIGHT = 16


def derived_staging_window_bytes(
    request_bytes: Sequence[int], memory_budget_bytes: int
) -> int:
    """The staging window a plan asks for. A request holds its bytes from
    admission until its write has finished, so the window is the
    transfers the link can use in flight plus the writes that may run
    at once (the I/O slots), counted in requests of the plan's own mean
    size: few large leaves and many small ones get the same depth, not
    the same bytes. Never below the two largest requests together (a
    leaf's write overlaps the next leaf's transfer), never above the
    plan's bytes or the process budget."""
    if not request_bytes:
        return 0
    total = sum(request_bytes)
    depth = LINK_TRANSFERS_IN_FLIGHT + knobs.get_per_rank_io_concurrency()
    window = max(
        depth * total // len(request_bytes),
        sum(heapq.nlargest(2, request_bytes)),
    )
    return min(window, total, memory_budget_bytes)


class StagingPool(MemoryBudget):
    """Host staging window of a background D2H drain.

    A device-snapshot async take runs its whole staging pipeline on the
    background commit thread; this pool is that pipeline's admission
    controller. What it admits is what is in flight between the device
    and storage: a request's bytes are reserved from admission, before
    its device-to-host transfer, until its write has finished, so the
    window has to hold the transfers and the writes that run at once or
    the two take turns. ``chosen`` says where the capacity came from:

    - ``derived``: from the plan's ``request_bytes``
      (:func:`derived_staging_window_bytes`), the default;
    - ``env``: ``TORCHSNAPSHOT_TPU_STAGING_POOL_SLAB_BYTES`` / ``_SLABS``
      set by the operator pin ``slabs x slab_bytes`` exactly (the way to
      cap host memory);
    - ``tuner``: the autotuner's override of the two asks for more than
      the plan does; it can raise the window, never shrink it;
    - ``caller``: ``slab_bytes`` / ``slabs`` passed in, or no plan given.

    Always clamped to the process memory budget it is accounted against.
    Inherits the idle-admission escape hatch: a single request larger
    than the whole pool is admitted alone (it serializes instead of
    deadlocking), and all of MemoryBudget's wait/peak telemetry.
    """

    def __init__(
        self,
        memory_budget_bytes: int,
        slab_bytes: Optional[int] = None,
        slabs: Optional[int] = None,
        request_bytes: Optional[Sequence[int]] = None,
    ) -> None:
        by_hand = (
            slab_bytes is not None or slabs is not None or request_bytes is None
        )
        self.slab_bytes = (
            slab_bytes
            if slab_bytes is not None
            else knobs.get_staging_pool_slab_bytes()
        )
        self.slabs = (
            slabs if slabs is not None else knobs.get_staging_pool_slabs()
        )
        self.memory_budget_bytes = memory_budget_bytes
        capacity = self.slab_bytes * self.slabs
        chosen = "caller" if by_hand else knobs.staging_pool_geometry_source()
        if chosen in (None, "tuner"):
            window = derived_staging_window_bytes(
                request_bytes, memory_budget_bytes
            )
            if chosen is None or window >= capacity:
                chosen, capacity = "derived", window
        self.chosen: str = chosen
        super().__init__(min(memory_budget_bytes, max(1, capacity)))

    def geometry(self) -> dict:
        return {
            "capacity_bytes": self.total_bytes,
            "slab_bytes": self.slab_bytes,
            "slabs": self.slabs,
            "chosen": self.chosen,
        }


class PeerCacheBudget:
    """Synchronous counting budget for the peer-RAM checkpoint cache
    (tiered/peer.py) — :class:`MemoryBudget`'s accounting model
    (total/available/peak) without the event-loop coupling: the peer
    server's handler threads reserve and release under a plain lock,
    and an oversized reservation is *refused* rather than queued — a
    push that does not fit (even after the cache's LRU eviction) must
    degrade to storage-only durability, never block the pusher or grow
    the cache past its bound."""

    def __init__(self, total_bytes: int) -> None:
        self.total_bytes = max(0, int(total_bytes))
        self.available_bytes = self.total_bytes
        self.peak_reserved_bytes = 0
        self._lock = threading.Lock()

    def try_reserve(self, cost_bytes: int) -> bool:
        """Reserve ``cost_bytes`` if they fit; False otherwise (the
        caller evicts and retries, or refuses the push)."""
        cost = int(cost_bytes)
        with self._lock:
            if cost > self.available_bytes:
                return False
            self.available_bytes -= cost
            reserved = self.total_bytes - self.available_bytes
            if reserved > self.peak_reserved_bytes:
                self.peak_reserved_bytes = reserved
            return True

    def release(self, cost_bytes: int) -> None:
        with self._lock:
            self.available_bytes = min(
                self.total_bytes, self.available_bytes + int(cost_bytes)
            )

    def reserved_bytes(self) -> int:
        with self._lock:
            return self.total_bytes - self.available_bytes


class _PipelineStats:
    """Live counters backing the progress reporter."""

    def __init__(self) -> None:
        self.pending = 0
        self.staging = 0
        self.waiting_io = 0
        self.io = 0
        self.done = 0
        self.bytes_moved = 0
        self.bytes_staged = 0
        # Write pipelines: bytes served per write-path variant
        # ("vectorized" | "direct" | "fused" | "buffered"), as stamped
        # by the storage plugin on each WriteIO — the per-take record
        # that lets doctor --trend correlate a write-path knob flip
        # with an efficiency move.
        self.write_variant_bytes: dict = {}
        # Read pipelines only: how many of the moved bytes were pulled
        # from the storage plugin itself ("fetched") versus served from
        # a peer-exchanged cache (fan-out restore; those bytes were
        # accounted as fetched/received by the exchange that shipped
        # them, not here). bytes_moved - bytes_fetched = locally-served.
        self.bytes_fetched = 0
        # Self-healing reads (docs/chaos.md): requests whose first copy
        # failed digest verification and were re-served from an
        # alternate tier — count/bytes totals plus bytes by the tier
        # that finally vouched (folded into the report's tier_split).
        self.degraded_reads = 0
        self.degraded_bytes = 0
        self.degraded_tier_bytes: dict = {}


# report_phase_done -> the phase the op is IN once that one completed,
# published to the live-progress heartbeat (telemetry/progress.py).
_NEXT_PHASE = {"staging": "writing", "writing": "committing", "loading": "applying"}


class _ProgressReporter:
    """Rank-0 header + per-rank progress rows with RSS delta, budget and GB
    moved (reference _WriteReporter, scheduler.py:96-175)."""

    _ROW = (
        "{rank:>4} {pending:>9} {staging:>9} {waiting:>9} {io:>9} "
        "{rss_delta:>15} {budget:>19} {moved:>15}"
    )

    def __init__(
        self,
        stats: _PipelineStats,
        budget: MemoryBudget,
        rank: int,
        total: int,
        progress: Optional["ProgressTracker"] = None,
    ) -> None:
        self.stats = stats
        self.budget = budget
        self.rank = rank
        # Live-progress tracker for the enclosing operation (None when
        # the caller runs no heartbeat, e.g. read_object).
        self.progress = progress
        # Per-pipeline phase completions (phase -> seconds since start):
        # unlike the process-global last_phase_timings channel this can
        # never leak a previous run's phases into this run's report.
        self.phase_s: dict = {}
        self.begin_ts = time.monotonic()
        self._process = psutil.Process()
        self.baseline_rss = self._process.memory_info().rss
        self.report_every = max(1, math.ceil(total / _LOG_LINE_LIMIT))
        self._header = self._ROW.format(
            rank="Rank",
            pending="Pending",
            staging="Staging",
            waiting="Writable",
            io="I/O",
            rss_delta="RSS Delta (GB)",
            budget="Budget (GB)",
            moved="Moved (GB)",
        )

    def print_header(self) -> None:
        if self.rank == 0:
            logger.info(self._header)
            logger.info("-" * len(self._header))

    def report(self) -> None:
        rss_delta_gb = (self._process.memory_info().rss - self.baseline_rss) / 1024**3
        logger.info(
            self._ROW.format(
                rank=self.rank,
                pending=self.stats.pending,
                staging=self.stats.staging,
                waiting=self.stats.waiting_io,
                io=self.stats.io,
                rss_delta=f"{rss_delta_gb:.2f}",
                budget=(
                    f"{self.budget.available_bytes / 1024**3:.2f}/"
                    f"{self.budget.total_bytes / 1024**3:.2f}"
                ),
                moved=f"{self.stats.bytes_moved / 1024**3:.2f}",
            )
        )

    def maybe_report(self) -> None:
        if self.stats.done % self.report_every == 0:
            self.report()

    def publish_progress(self) -> None:
        """Feed the op's live-progress tracker from this pipeline's
        counters. Called on every request completion and phase
        transition; the tracker's file writes are interval-gated, the
        in-memory view updates every time."""
        if self.progress is None:
            return
        self.progress.update_pipeline(
            pending=self.stats.pending,
            staging=self.stats.staging,
            inflight=self.stats.waiting_io + self.stats.io,
            done=self.stats.done,
            staged_bytes=self.stats.bytes_staged,
            done_bytes=self.stats.bytes_moved,
            budget_wait_s=self.budget.wait_s,
        )

    def report_phase_done(self, phase: str) -> None:
        elapsed = time.monotonic() - self.begin_ts
        self.phase_s[phase] = round(elapsed, 3)
        telemetry.record_phase(phase, elapsed)
        self.publish_progress()
        if self.progress is not None and phase in _NEXT_PHASE:
            self.progress.set_phase(_NEXT_PHASE[phase])
        mbps = safe_rate_mb_s(self.stats.bytes_moved, elapsed)
        msg = (
            f"Rank {self.rank} completed {phase} in {elapsed:.2f}s "
            f"(throughput {mbps:.2f} MB/s)"
        )
        pad = max(0, len(self._header) - len(msg) - 2) / 2
        logger.info(f"{'-' * math.ceil(pad)} {msg} {'-' * math.floor(pad)}")

    def pipeline_telemetry(self) -> dict:
        """This run's exact numbers for SnapshotReport assembly."""
        out = {
            "phases": dict(self.phase_s),
            "bytes_moved": self.stats.bytes_moved,
            "blobs": self.stats.done,
            "budget_wait_s": round(self.budget.wait_s, 6),
            "peak_staged_bytes": self.budget.peak_reserved_bytes,
        }
        if isinstance(self.budget, StagingPool):
            out["staging_pool"] = self.budget.geometry()
        if self.stats.write_variant_bytes:
            out["write_path"] = dict(self.stats.write_variant_bytes)
        return out


class PendingIOWork:
    """Handle over storage I/O still draining after staging completed
    (reference scheduler.py:178-217). ``complete`` re-raises the first
    failure; the commit marker must not be written in that case."""

    def __init__(
        self,
        io_tasks: List["asyncio.Task[None]"],
        reporter: _ProgressReporter,
        executor: ThreadPoolExecutor,
        checksums: Optional[ChecksumTable] = None,
    ) -> None:
        self.io_tasks = io_tasks
        self.reporter = reporter
        self._executor = executor
        # Filled in as writes complete; stable only after complete().
        self.checksums: ChecksumTable = checksums if checksums is not None else {}
        # Optional hook run after complete() and before the checksum table
        # is persisted (incremental takes inherit base-table entries here —
        # storage reads that must stay off the staging-critical path so
        # async_take returns at staging-done as promised).
        self.checksum_finalizer: Optional[Callable[[], None]] = None

    def finalize_checksums(self) -> None:
        if self.checksum_finalizer is not None:
            try:
                self.checksum_finalizer()
            finally:
                self.checksum_finalizer = None

    def pipeline_telemetry(self) -> dict:
        """The write pipeline's exact per-run numbers (phases, bytes,
        blob count, budget wait, peak staged); stable after complete()."""
        return self.reporter.pipeline_telemetry()

    async def complete(self) -> None:
        # Recorder-only span (not trace_annotation): this coroutine
        # awaits across the whole I/O drain and a thread-local jax
        # annotation would mis-nest with interleaved tasks.
        drain_span = _trace_recorder().begin(
            telemetry.names.SPAN_PIPELINE_WRITE_DRAIN,
            tasks=len(self.io_tasks),
        )
        try:
            if self.io_tasks:
                try:
                    await asyncio.gather(*self.io_tasks)
                except BaseException:
                    # Settle the sibling writes before re-raising: gather
                    # propagates on the FIRST failure while the rest keep
                    # running, and the caller's failure path closes the
                    # event loop — leaving tasks to die mid-write with
                    # "Task was destroyed but it is pending" noise (and
                    # buffers whose budget releases never ran).
                    for t in self.io_tasks:
                        t.cancel()
                    await asyncio.gather(*self.io_tasks, return_exceptions=True)
                    raise
        finally:
            _trace_recorder().end(drain_span)
            self._executor.shutdown(wait=False)
        self.reporter.report_phase_done("writing")
        telemetry.metrics().gauge_set(
            telemetry.names.MEMORY_BUDGET_PEAK_STAGED_BYTES,
            self.reporter.budget.peak_reserved_bytes,
        )

    def sync_complete(self, event_loop: asyncio.AbstractEventLoop) -> None:
        event_loop.run_until_complete(self.complete())


async def execute_write_reqs(
    write_reqs: List[WriteReq],
    storage: StoragePlugin,
    memory_budget_bytes: int,
    rank: int,
    progress: Optional["ProgressTracker"] = None,
    staging_pool: Optional[MemoryBudget] = None,
) -> PendingIOWork:
    """Run the staged write pipeline; returns once every request is past
    staging, with storage I/O continuing inside the returned handle.
    ``progress`` (the enclosing op's live-progress tracker) receives the
    pipeline's plan and per-request counter updates. ``staging_pool``
    substitutes a (typically much tighter) admission controller for the
    raw budget — the background-drain path of device-snapshot async
    takes, whose host staging footprint must be pool-bounded, not
    checkpoint-sized."""
    budget = (
        staging_pool
        if staging_pool is not None
        else MemoryBudget(memory_budget_bytes)
    )
    stats = _PipelineStats()
    stats.pending = len(write_reqs)
    reporter = _ProgressReporter(stats, budget, rank, len(write_reqs), progress)
    reporter.print_header()
    if progress is not None:
        progress.begin_pipeline(
            len(write_reqs),
            sum(r.buffer_stager.get_staging_cost_bytes() for r in write_reqs),
            phase="staging",
        )

    executor = ThreadPoolExecutor(
        max_workers=knobs.get_staging_threads(), thread_name_prefix="ts-stage"
    )
    io_slots = asyncio.Semaphore(knobs.get_per_rank_io_concurrency())
    io_tasks: List[asyncio.Task] = []
    record_checksums = not knobs.is_checksums_disabled()
    checksums: ChecksumTable = {}
    # Sticky runtime-decline: a plugin that overrides write_with_checksum
    # but declines (native runtime unavailable) declines for the whole
    # run — remember it so later writes keep checksum compute OFF the
    # bounded I/O slots.
    fused_declined = False

    async def checksum_off_slot(buf):
        """Checksum compute for the non-fused path. Small buffers run
        inline: the executor round-trip costs ~0.1 ms, an order of
        magnitude more than hashing the bytes themselves — at torchrec
        scale (1e5 tiny leaves, batching off) the hop, not the CRC, was
        the per-request floor. Large buffers keep the hop so a multi-MiB
        CRC never stalls the event loop."""
        if len(buf) <= _INLINE_CHECKSUM_BYTES:
            return compute_checksum_entry(buf)
        return await run_in_executor(executor, compute_checksum_entry, buf)

    async def write_one(req: WriteReq, buf) -> None:
        nonlocal fused_declined
        buf_len = len(buf)
        try:
            # Zero-pack payloads only reach plugins that can vector-write
            # them; for the rest, consolidate here — paying exactly the
            # pack pass the old path always paid, never more. The copy
            # transiently holds parts + contiguous buffer, so re-price
            # the reservation for its duration (adjust never blocks —
            # bounded overshoot now, later admissions wait it out), and
            # run the full-slab memcpy in the executor like the pack
            # pass it replaces.
            if isinstance(buf, BufferList) and not getattr(
                storage, "supports_multibuffer", False
            ):
                await budget.adjust(buf_len)
                try:
                    buf = await run_in_executor(
                        executor, buf.consolidate
                    )
                finally:
                    await budget.adjust(-buf_len)
            # Fused write+checksum (one cache-hot memory pass) when the
            # plugin overrides it; otherwise checksum first (off the I/O
            # slot), then write.
            fused = (
                record_checksums
                and not fused_declined
                and type(storage).write_with_checksum
                is not StoragePlugin.write_with_checksum
            )
            if record_checksums and not fused:
                checksums[req.path] = await checksum_off_slot(buf)
            declined = False
            # One WriteIO for the whole request: the plugin stamps the
            # write-path variant that actually served it (vectorized /
            # direct / fused / buffered) onto this object.
            write_io = WriteIO(path=req.path, buf=buf)
            async with io_slots:
                stats.waiting_io -= 1
                stats.io += 1
                try:
                    # I/O spans are emitted inside the storage plugin's
                    # executor work (fs.py): wrapping the await here would
                    # record suspension time of interleaved tasks, not I/O.
                    if fused:
                        entry = await storage.write_with_checksum(write_io)
                        if entry is not None:
                            checksums[req.path] = entry
                        else:
                            # Plugin declined at runtime (native lib
                            # unavailable; nothing written): fall back
                            # OUTSIDE the slot — checksum compute must
                            # not serialize the bounded I/O streams.
                            declined = True
                    else:
                        await storage.write(write_io)
                finally:
                    stats.io -= 1
            if declined:
                # Two-step fallback for this and (sticky) all later
                # writes: checksum off the I/O slots, then re-acquire a
                # slot for the plain write.
                fused_declined = True
                checksums[req.path] = await checksum_off_slot(buf)
                stats.waiting_io += 1
                async with io_slots:
                    stats.waiting_io -= 1
                    stats.io += 1
                    try:
                        await storage.write(write_io)
                    finally:
                        stats.io -= 1
            variant = write_io.variant or "buffered"
            stats.write_variant_bytes[variant] = (
                stats.write_variant_bytes.get(variant, 0) + buf_len
            )
        finally:
            del buf
            await budget.release(buf_len)
        stats.done += 1
        stats.bytes_moved += buf_len
        reporter.maybe_report()
        reporter.publish_progress()

    async def stage_one(req: WriteReq) -> None:
        """Budget-admitted staging; hands the staged buffer straight to a
        background write task so I/O overlaps other requests' staging.
        Recorder spans per phase (budget wait, then the D2H/serialize
        stage itself): the per-request timeline the flight recorder
        exports. Recorder-only — these spans cross awaits."""
        recorder = _trace_recorder()
        cost = req.buffer_stager.get_staging_cost_bytes()
        with recorder.span(
            telemetry.names.SPAN_PIPELINE_BUDGET_ACQUIRE,
            blob=req.path,
            bytes=cost,
        ):
            await budget.acquire(cost)
        stats.pending -= 1
        stats.staging += 1
        stage_span = recorder.begin(
            telemetry.names.SPAN_PIPELINE_STAGE, blob=req.path, bytes=cost
        )
        try:
            buf = await req.buffer_stager.stage_buffer(executor)
        except BaseException:
            recorder.end(stage_span)
            stats.staging -= 1
            await budget.release(cost)
            raise
        recorder.end(stage_span, staged_bytes=len(buf))
        stats.staging -= 1
        stats.waiting_io += 1
        stats.bytes_staged += len(buf)
        reporter.publish_progress()
        # Re-price the reservation: actual buffer size can differ from the
        # staging cost (e.g. pickled objects).
        await budget.adjust(len(buf) - cost)
        io_tasks.append(asyncio.create_task(write_one(req, buf)))
        del buf

    staging_tasks = [asyncio.create_task(stage_one(r)) for r in write_reqs]
    try:
        if staging_tasks:
            await asyncio.gather(*staging_tasks)
    except BaseException:
        for t in staging_tasks + io_tasks:
            t.cancel()
        await asyncio.gather(*staging_tasks, *io_tasks, return_exceptions=True)
        executor.shutdown(wait=False)
        raise

    reporter.report_phase_done("staging")
    return PendingIOWork(
        io_tasks=io_tasks,
        reporter=reporter,
        executor=executor,
        checksums=checksums,
    )


def sync_execute_write_reqs(
    write_reqs: List[WriteReq],
    storage: StoragePlugin,
    memory_budget_bytes: int,
    rank: int,
    event_loop: asyncio.AbstractEventLoop,
    progress: Optional["ProgressTracker"] = None,
) -> PendingIOWork:
    return event_loop.run_until_complete(
        execute_write_reqs(
            write_reqs=write_reqs,
            storage=storage,
            memory_budget_bytes=memory_budget_bytes,
            rank=rank,
            progress=progress,
        )
    )


class DeferredIOWork:
    """Write work whose staging has NOT run yet — the device-snapshot
    async take's handle. ``async_take`` constructs one right after the
    capture pass (on-device clones dispatched, mutable host leaves
    copied) and returns; the background commit thread then calls
    ``sync_complete``, which runs the WHOLE pipeline: staging (D2H +
    serialize) through a :class:`StagingPool` sized from these write
    requests so host memory stays bounded, overlapped with the storage
    writes by the ordinary stage/write machinery of
    :func:`execute_write_reqs`.

    Mirrors :class:`PendingIOWork`'s surface (``sync_complete`` /
    ``finalize_checksums`` / ``checksums`` / ``checksum_finalizer`` /
    ``pipeline_telemetry``) so ``PendingSnapshot`` drives either handle
    identically. ``on_staged`` fires on the drain thread the moment
    staging finished — the take's ``staged`` phase boundary
    (``PendingSnapshot.wait(phase="staged")``).

    ``device_clones`` are the capture pass's on-device clones, made by
    ``clone_programs`` programs that the device may not have reached
    yet: ``async_take`` dispatched them behind whatever the runtime had
    queued and returned. The drain waits for them under a span of its
    own (``capture:ready``) before it admits a staging request, so that
    no ``stage:d2h`` holds the runtime's queue.
    """

    def __init__(
        self,
        write_reqs: List[WriteReq],
        storage: StoragePlugin,
        memory_budget_bytes: int,
        rank: int,
        progress: Optional["ProgressTracker"] = None,
        device_clones: Optional[List[Any]] = None,
        clone_programs: int = 0,
    ) -> None:
        self.write_reqs = write_reqs
        self._storage = storage
        self._memory_budget_bytes = memory_budget_bytes
        self._rank = rank
        self._progress = progress
        # Same contract as PendingIOWork: filled as writes complete
        # (rebound to the live pipeline's table once staging starts),
        # stable only after sync_complete() returns.
        self.checksums: ChecksumTable = {}
        self.checksum_finalizer: Optional[Callable[[], None]] = None
        self.on_staged: Optional[Callable[[], None]] = None
        self._inner: Optional[PendingIOWork] = None
        self._device_clones = device_clones
        self._clone_programs = clone_programs

    def _await_device_clones(self) -> None:
        # The stagers hold the clones; this list must not keep them (and
        # their HBM) alive past the wait.
        clones, self._device_clones = self._device_clones, None
        if not clones:
            return
        import jax

        with trace_annotation(
            telemetry.names.SPAN_CAPTURE_READY,
            bytes=sum(int(c.nbytes) for c in clones),
            programs=self._clone_programs,
        ):
            jax.block_until_ready(clones)

    def sync_complete(self, event_loop: asyncio.AbstractEventLoop) -> None:
        self._await_device_clones()
        pool = StagingPool(
            self._memory_budget_bytes,
            request_bytes=[
                r.buffer_stager.get_staging_cost_bytes()
                for r in self.write_reqs
            ],
        )
        inner = event_loop.run_until_complete(
            execute_write_reqs(
                write_reqs=self.write_reqs,
                storage=self._storage,
                memory_budget_bytes=self._memory_budget_bytes,
                rank=self._rank,
                progress=self._progress,
                staging_pool=pool,
            )
        )
        self._inner = inner
        # The inner pipeline's table is the live one; expose it so the
        # caller's checksum-table write (and an incremental take's
        # inherit closure, which reads ``self.checksums`` at call time)
        # see every recorded digest.
        self.checksums = inner.checksums
        self.write_reqs = []
        if self.on_staged is not None:
            self.on_staged()
        inner.sync_complete(event_loop)

    def finalize_checksums(self) -> None:
        if self.checksum_finalizer is not None:
            try:
                self.checksum_finalizer()
            finally:
                self.checksum_finalizer = None

    def pipeline_telemetry(self) -> dict:
        return (
            self._inner.pipeline_telemetry() if self._inner is not None else {}
        )


async def execute_read_reqs(
    read_reqs: List[ReadReq],
    storage: StoragePlugin,
    memory_budget_bytes: int,
    rank: int,
    checksum_table: Optional[ChecksumTable] = None,
    on_req_complete: Optional[Callable[[ReadReq], None]] = None,
    progress: Optional["ProgressTracker"] = None,
    classify_read: Optional[Callable[[ReadReq], Optional[str]]] = None,
    destinations: Optional["DestinationLeases"] = None,
) -> dict:
    """Read pipeline: storage read -> deserialize/copy, budgeted by each
    request's consuming cost (reference scheduler.py:357-444). Returns
    the run's pipeline-telemetry dict (phases, bytes, budget wait) for
    SnapshotReport assembly.

    ``on_req_complete`` fires on the event loop after a request's bytes
    are verified and consumed — the hook streaming restore placement
    hangs device_put flushes on while other reads are still in flight.

    ``classify_read`` attributes each completed request's bytes for the
    fetched-vs-received accounting restore reports carry: return
    ``"fetched"`` (the default for every request when no classifier is
    given) to count the bytes as pulled from the storage plugin, or
    ``None`` for bytes served from a local cache (fan-out restore's
    exchanged shards — the exchange already accounted those). The
    telemetry dict reports the sum as ``bytes_fetched``.

    ``destinations`` gives a read whose consumer came without a
    destination a slab of the process's pool (``dest_pool``), waiting for
    one where the pool is at its cap: a dense leaf's when the read is
    admitted, a sharded leaf's boxes (all of them, with its first read)
    before that, and a read that is copied into its boxes a buffer to land
    in, which is the pool's again when the copy returns."""
    budget = MemoryBudget(memory_budget_bytes)
    stats = _PipelineStats()
    stats.pending = len(read_reqs)
    reporter = _ProgressReporter(stats, budget, rank, len(read_reqs), progress)
    if progress is not None:
        progress.begin_pipeline(
            len(read_reqs),
            sum(
                r.buffer_consumer.get_consuming_cost_bytes() for r in read_reqs
            ),
            phase="loading",
        )

    executor = ThreadPoolExecutor(
        max_workers=knobs.get_staging_threads(), thread_name_prefix="ts-consume"
    )
    io_slots = asyncio.Semaphore(knobs.get_per_rank_io_concurrency())
    verify_skipped = [0]
    # Sticky runtime-decline for the fused read+CRC path (mirrors the
    # write pipeline's flag): once a plugin declines, later reads skip
    # the attempt. Plugins that never overrode the hook start declined.
    fused_read_declined = (
        type(storage).read_with_checksum
        is StoragePlugin.read_with_checksum
    )

    recorder = _trace_recorder()

    def begin_dest_span(req: ReadReq, cost: int) -> int:
        # Recorder-only: the span crosses the wait for a slab.
        return recorder.begin(
            telemetry.names.SPAN_RESTORE_DEST_ACQUIRE, blob=req.path, bytes=cost
        )

    def end_dest_span(span: int, lease: Lease, direct: bool) -> None:
        recorder.end(
            span,
            recycled=int(lease.recycled),
            direct=int(direct),
            box_bytes=lease.box_bytes,
            box_bytes_recycled=lease.box_bytes_recycled,
        )

    async def read_one(req: ReadReq) -> None:
        nonlocal fused_read_declined
        consumer = req.buffer_consumer
        cost = consumer.get_consuming_cost_bytes()
        lease = Lease()
        dest_span = None
        if destinations is not None and consumer.shared_destination() is not None:
            # A leaf's boxes come before the budget: a read that waits
            # for them must not hold budget that the reads of the leaves
            # holding them wait for. Its span then covers that wait too.
            dest_span = begin_dest_span(req, cost)
            try:
                await destinations.bind_shared(consumer, lease)
            except BaseException:
                end_dest_span(dest_span, lease, False)
                raise
        await budget.acquire(cost)
        stats.pending -= 1
        try:
            entry = (
                checksum_table.get(req.path)
                if checksum_table is not None
                else None
            )
            fused_pages = None
            if dest_span is None:
                dest_span = begin_dest_span(req, cost)
            dest = None
            try:
                if destinations is not None:
                    await destinations.bind(consumer, lease)
                dest = consumer.direct_destination()
            finally:
                end_dest_span(dest_span, lease, dest is not None)
            if dest is None and lease.buffer is not None:
                # Not the destination: the consumer copies out of it.
                dest = memoryview(lease.buffer.array)
            async with io_slots:
                stats.io += 1
                read_io = ReadIO(
                    path=req.path, byte_range=req.byte_range, dest=dest
                )
                try:
                    # Fused read+verify source: one cache-hot pass
                    # computes the page digests during the disk read.
                    if (
                        entry is not None
                        and entry[0] == "crc32c"
                        and req.byte_range is None
                        and not fused_read_declined
                    ):
                        fused_pages = await storage.read_with_checksum(read_io)
                        if fused_pages is None:
                            fused_read_declined = True
                    if fused_pages is None:
                        await storage.read(read_io)
                finally:
                    stats.io -= 1
            buf = read_io.buf
            if buf is None:
                raise AssertionError(
                    f"Storage plugin did not populate buffer for {req.path}"
                )
            # Whole-blob reads verify against the blob digest; ranged reads
            # verify every page their range fully covers (recorded for
            # blobs larger than one page). Reads that end up with no
            # verification at all are counted and reported below so
            # 'checksums on' is never silently hollow. Runs before the
            # value is handed to the application either way (direct reads
            # land in framework-owned buffers only).
            if entry is not None:

                async def _verify_current(
                    cur_buf, use_fused_pages=None
                ) -> None:
                    verified_from_pages = False
                    nbytes = memoryview(cur_buf).nbytes
                    if use_fused_pages is not None:
                        # Pure GF(2) fold over the pages read — O(pages),
                        # no second pass over the bytes, no executor hop.
                        # False = this entry needs the bytes (foreign alg
                        # / mismatched interim granularity): verify below.
                        verified_from_pages = _verify_blob(
                            "pages",
                            req.path,
                            nbytes,
                            verify_page_crcs,
                            use_fused_pages,
                            nbytes,
                            entry,
                            req.path,
                        )
                    # Small buffers verify inline: the executor
                    # round-trip costs ~0.1 ms against sub-microsecond
                    # hashing (same rationale as checksum_off_slot).
                    small = nbytes <= _INLINE_CHECKSUM_BYTES
                    if verified_from_pages:
                        pass
                    elif req.byte_range is None:
                        whole = ("whole", req.path, nbytes, verify_checksum,
                                 cur_buf, entry, req.path)
                        if small:
                            _verify_blob(*whole)
                        else:
                            await run_in_executor(
                                executor, _verify_blob, *whole
                            )
                    else:
                        ranged = ("range", req.path, nbytes,
                                  verify_range_checksum, cur_buf, entry,
                                  req.byte_range, req.path)
                        if small:
                            page_verified = _verify_blob(*ranged)
                        else:
                            page_verified = await run_in_executor(
                                executor, _verify_blob, *ranged
                            )
                        if not page_verified:
                            verify_skipped[0] += 1

                try:
                    await _verify_current(buf, use_fused_pages=fused_pages)
                except ChecksumError as first_err:
                    # Self-healing ladder (docs/chaos.md): a corrupt
                    # tier copy must not fail a restore the OTHER tiers
                    # could serve. Multi-source plugins re-read from
                    # alternates (tiered: the other tier; the peer
                    # ladder: durable/fast) until one verifies;
                    # single-source plugins have none and the original
                    # error stands — corruption is never served
                    # silently either way.
                    healed = False
                    async with io_slots:
                        while await storage.read_degraded(read_io):
                            buf = read_io.buf
                            try:
                                await _verify_current(buf)
                            except ChecksumError:
                                continue
                            healed = True
                            break
                    if not healed:
                        raise
                    tier = read_io.served_by or "unknown"
                    nbytes = memoryview(buf).nbytes
                    stats.degraded_reads += 1
                    stats.degraded_bytes += nbytes
                    stats.degraded_tier_bytes[tier] = (
                        stats.degraded_tier_bytes.get(tier, 0) + nbytes
                    )
                    registry = telemetry.metrics()
                    registry.counter_inc(
                        telemetry.names.STORAGE_DEGRADED_READS_TOTAL,
                        tier=tier,
                    )
                    registry.counter_inc(
                        telemetry.names.STORAGE_DEGRADED_READ_BYTES_TOTAL,
                        nbytes,
                        tier=tier,
                    )
                    logger.warning(
                        "read of %s failed verification (%s); healed "
                        "from the %r tier copy",
                        req.path,
                        first_err,
                        tier,
                    )
            if (
                read_io.dest is not None
                and buf is read_io.dest
                and lease.buffer is None
            ):
                # The plugin read straight into the destination; nothing
                # left to deserialize or copy.
                pass
            else:
                stats.staging += 1
                try:
                    with _trace_recorder().span(
                        telemetry.names.SPAN_PIPELINE_CONSUME,
                        blob=req.path,
                        bytes=memoryview(buf).nbytes,
                    ):
                        await req.buffer_consumer.consume_buffer(buf, executor)
                finally:
                    stats.staging -= 1
            if destinations is not None:
                # Not where the read failed: a thread may still write it.
                destinations.release(lease)
            stats.done += 1
            stats.bytes_moved += buf.nbytes
            kind = (
                classify_read(req) if classify_read is not None else "fetched"
            )
            if kind == "fetched":
                stats.bytes_fetched += buf.nbytes
            del buf, read_io, dest
            if on_req_complete is not None:
                on_req_complete(req)
            reporter.maybe_report()
            reporter.publish_progress()
        finally:
            await budget.release(cost)

    tasks = [asyncio.create_task(read_one(r)) for r in read_reqs]
    try:
        await asyncio.gather(*tasks)
    except BaseException:
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        raise
    finally:
        executor.shutdown(wait=False)
        if destinations is not None:
            await destinations.aclose()
    if verify_skipped[0]:
        logger.info(
            "%d of %d reads were ranged with no fully-covered pages and "
            "skipped checksum verification",
            verify_skipped[0],
            len(read_reqs),
        )
    reporter.report_phase_done("loading")
    out = reporter.pipeline_telemetry()
    # Read pipelines always report their plugin-fetched bytes: the
    # fallback (no classifier) counts every request, so a plain restore's
    # bytes_fetched equals bytes_moved and the read-amplification math
    # works whether or not fan-out ran.
    out["bytes_fetched"] = stats.bytes_fetched
    if stats.degraded_reads:
        # Corruption-rerouted reads: the count/bytes summary the
        # storage-corruption doctor rule cites, plus the serving tiers
        # folded into the report's tier_split so the reroute is visible
        # in the same split the peer ladder reports.
        out["degraded_reads"] = {
            "blobs": stats.degraded_reads,
            "bytes": stats.degraded_bytes,
        }
        out["tier_split"] = dict(stats.degraded_tier_bytes)
    return out


def sync_execute_read_reqs(
    read_reqs: List[ReadReq],
    storage: StoragePlugin,
    memory_budget_bytes: int,
    rank: int,
    event_loop: asyncio.AbstractEventLoop,
    checksum_table: Optional[ChecksumTable] = None,
    on_req_complete: Optional[Callable[[ReadReq], None]] = None,
    progress: Optional["ProgressTracker"] = None,
    classify_read: Optional[Callable[[ReadReq], Optional[str]]] = None,
    destinations: Optional["DestinationLeases"] = None,
) -> dict:
    return event_loop.run_until_complete(
        execute_read_reqs(
            read_reqs=read_reqs,
            storage=storage,
            memory_budget_bytes=memory_budget_bytes,
            rank=rank,
            checksum_table=checksum_table,
            on_req_complete=on_req_complete,
            progress=progress,
            classify_read=classify_read,
            destinations=destinations,
        )
    )

"""Peer-RAM checkpoint tier: survive preemption at host-RAM speed.

The tiered subsystem's third tier (docs/peer.md). The mirror (mirror.py)
buys *durability* off the take's critical path; this module buys cheap
*recovery*: every rank pushes the shards it committed into a neighbor
rank's host-RAM cache (ring placement, ``(rank + offset) % world``), so
after a single-host preemption the replacement rank pulls its shards
from the surviving peer's RAM instead of paying a durable-storage
restore. The in-memory redundant checkpointing pattern the LLM
checkpoint I/O study (arXiv:2512.24511) and ByteCheckpoint
(arXiv:2407.20143) identify as the gap between checkpoint *interval*
and checkpoint *cost*.

Topology and transport:

- Each participating process runs one peer cache server (daemon
  threads, length-prefixed frames shared with the TCP store —
  ``dist_store.send_frame``) over a :class:`PeerCache` bounded by a
  :class:`~torchsnapshot_tpu.scheduler.PeerCacheBudget` (LRU by step,
  the newest committed step pinned).
- Endpoints ride the coordination store's endpoint registry
  (``dist_store.publish_endpoint`` — overwritten on re-publish, so a
  replacement rank re-announces itself under the same rank id).
- Pushes run on a background worker (mirror-shaped job queue) with a
  per-transfer timeout and the shared collective-progress retry
  strategy; a dead peer costs the pusher a bounded number of timeouts
  and then *degrades* — WARN + ``peer_tier_degraded`` gauge — never a
  wedged push. Each push job records a placement journal entry
  (``.peer_placement-rank<r>.json``) next to the snapshot (fast tier
  for tiered paths) so ``fsck --tier peer`` can audit coverage offline.

Restore ladder (per shard): **peer RAM → local fast tier → durable**
in *availability* order — with one optimization: a blob already resident
on the LOCAL fast tier is read from local disk directly (free) instead
of shipped over the interconnect; only bytes this host actually lost
pull from peers. :func:`build_restore_context` assembles a fanout-style
owner table over the *surviving* peers (one inventory RPC per endpoint,
issued concurrently; dead peers are skipped with a WARN), and
:meth:`PeerRestoreContext.wrap` hands the read pipeline a plugin view
that pulls table-resident blobs from peer RAM — every pulled byte
digest-verified through the integrity layer before it is trusted, and
ranged reads of paged blobs sliced server-side so only the window
crosses the socket — and falls through per blob on ANY failure
(dead peer, stale step, checksum mismatch, budget-refused partial
push). Every peer failure mode resolves to a correct-if-slower
restore, never a wrong or hung one.

Kill switch: ``TORCHSNAPSHOT_TPU_PEER_TIER=0`` (no server, no pushes,
no pulls). Knobs: ring offset, cache budget bytes, transfer timeout
(knobs.py).
"""

from __future__ import annotations

import json
import logging
import pickle
import queue
import socket
import socketserver
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from .. import knobs, telemetry
from ..dist_store import (
    Store,
    lookup_endpoint,
    lookup_endpoints,
    publish_endpoint,
    recv_frame,
    send_frame,
)
from ..event_loop import run_in_fresh_event_loop
from ..integrity import ChecksumError, verify_checksum
from ..io_types import ReadIO, StoragePlugin, WriteIO
from ..scheduler import PeerCacheBudget
from ..storage_plugin import split_tiered_url, url_to_storage_plugin
from ..storage_plugins.retry import (
    CollectiveProgressRetryStrategy,
    RetriesExhausted,
)
from ..telemetry import names as metric_names
from ..telemetry import wire
from ..telemetry.trace import get_recorder as _trace_recorder
from ..utils.tracing import run_in_executor

logger: logging.Logger = logging.getLogger(__name__)

# Endpoint-registry service name (dist_store.publish_endpoint).
PEER_SERVICE = "peer-tier"

# Placement-journal basename prefix: one doc per pushing rank per step
# dir, written to the local/fast tier after each push job settles.
PEER_PLACEMENT_PREFIX = ".peer_placement-rank"

# A pulling endpoint is declared dead for the rest of one restore after
# this many consecutive transport failures (checksum mismatches do NOT
# count — the transport is fine, the bytes are not).
_PULL_DEAD_AFTER_FAILURES = 2


def peer_step_key(path_url: str) -> str:
    """The cache key for one snapshot path: the fast-tier URL for
    tiered paths (identical string on every rank), the path itself
    otherwise. Pushers and pullers must derive the same key from the
    same manager step path."""
    tiers = split_tiered_url(path_url)
    base = tiers[0] if tiers is not None else path_url
    return base.rstrip("/")


def placement_doc_path(rank: int) -> str:
    return f"{PEER_PLACEMENT_PREFIX}{rank}.json"


class PeerTransferError(RuntimeError):
    """A peer transport operation failed (connect/timeout/protocol)."""


# ---------------------------------------------------------------------------
# The cache (the receiving side's host RAM)
# ---------------------------------------------------------------------------


class _StepSlot:
    __slots__ = ("blobs", "committed", "step", "chunk_refs")

    def __init__(self, step: Optional[int]) -> None:
        # path -> (checksum-table entry, bytes)
        self.blobs: Dict[str, Tuple[tuple, bytes]] = {}
        # Content-addressed blobs this step references in the cache's
        # shared chunk pool (bytes stored once across steps).
        self.chunk_refs: set = set()
        self.committed = False
        self.step = step

    @property
    def holds_bytes(self) -> bool:
        return bool(self.blobs or self.chunk_refs)


class PeerCache:
    """Host-RAM store of peer-pushed checkpoint blobs.

    Steps evict LRU (arrival/commit order) under the byte budget, with
    the newest *committed* step pinned — the one copy that must survive
    arbitrary pressure, because it is the one a replacement rank will
    ask for. A push that cannot fit even after evicting every unpinned
    step is refused (``("refused", "budget")``) — the pusher records
    the degradation; restores simply miss and fall through."""

    def __init__(
        self,
        budget: Optional[PeerCacheBudget] = None,
        keep_last_n: Optional[int] = None,
    ) -> None:
        self._budget = (
            budget
            if budget is not None
            else PeerCacheBudget(knobs.get_peer_cache_budget_bytes())
        )
        self.keep_last_n = keep_last_n
        self._lock = threading.Lock()
        # Insertion/commit order doubles as LRU order: Python dicts
        # preserve it and `move_to_end`-style refreshes re-insert.
        self._steps: Dict[str, _StepSlot] = {}
        self._pinned: Optional[str] = None
        # Shared chunk pool (docs/cas.md): content-addressed blobs are
        # stored ONCE across steps — path -> (entry, bytes) plus a
        # per-chunk refcount of the step slots referencing it. Budget
        # bytes are reserved at first insert and released when the last
        # referencing step drops.
        self._chunks: Dict[str, Tuple[tuple, bytes]] = {}
        self._chunk_rc: Dict[str, int] = {}

    # -- mutation (server handler threads) ------------------------------

    def _is_chunk(self, path: str) -> bool:
        from ..cas import is_chunk_location

        return is_chunk_location(path)

    def _ref_chunk_locked(self, slot: _StepSlot, path: str) -> None:
        if path not in slot.chunk_refs:
            slot.chunk_refs.add(path)
            self._chunk_rc[path] = self._chunk_rc.get(path, 0) + 1

    def reference_chunks(
        self, step_key: str, step: Optional[int], paths: List[str]
    ) -> List[str]:
        """Inventory-by-digest dedup: of ``paths`` (chunk locations),
        reference the ones already pooled under ``step_key`` and return
        them — the pusher then ships bytes only for the misses."""
        with self._lock:
            hits = [p for p in paths if p in self._chunks]
            if hits:
                slot = self._steps.get(step_key)
                if slot is None:
                    slot = _StepSlot(step)
                    self._steps[step_key] = slot
                for p in hits:
                    self._ref_chunk_locked(slot, p)
            self._publish_gauges_locked()
            return hits

    def put(
        self,
        step_key: str,
        step: Optional[int],
        path: str,
        entry: tuple,
        data: bytes,
    ) -> Tuple[bool, str]:
        nbytes = len(data)
        with self._lock:
            if nbytes > self._budget.total_bytes:
                # Doomed from the start: a blob larger than the whole
                # budget must be refused WITHOUT collateral eviction —
                # destroying older steps' copies cannot make it fit.
                self._publish_gauges_locked()
                return False, "budget"
            slot = self._steps.get(step_key)
            if slot is None:
                slot = _StepSlot(step)
                self._steps[step_key] = slot
            if self._is_chunk(path):
                # Content-addressed: the path IS the content, so a
                # pooled copy serves every step — reference it (no new
                # bytes) or insert it once.
                if path in self._chunks:
                    self._ref_chunk_locked(slot, path)
                    self._publish_gauges_locked()
                    return True, "ok"
                while not self._budget.try_reserve(nbytes):
                    if not self._evict_one_locked(exclude=step_key):
                        self._publish_gauges_locked()
                        return False, "budget"
                self._chunks[path] = (tuple(entry), data)
                self._ref_chunk_locked(slot, path)
                self._publish_gauges_locked()
                return True, "ok"
            prior = slot.blobs.pop(path, None)
            if prior is not None:
                self._budget.release(len(prior[1]))
            while not self._budget.try_reserve(nbytes):
                if not self._evict_one_locked(exclude=step_key):
                    self._publish_gauges_locked()
                    return False, "budget"
            slot.blobs[path] = (tuple(entry), data)
            self._publish_gauges_locked()
            return True, "ok"

    def commit(self, step_key: str, step: Optional[int]) -> None:
        with self._lock:
            slot = self._steps.pop(step_key, None)
            if slot is None:
                slot = _StepSlot(step)
            slot.committed = True
            if step is not None:
                slot.step = step
            self._steps[step_key] = slot  # LRU refresh: newest position
            if slot.holds_bytes:
                self._pinned = step_key
            # An EMPTY committed step (every push refused/raced away)
            # must not steal the pin: the previous pinned step is still
            # the newest copy a replacement rank could actually use.
            if self.keep_last_n is not None:
                # Only steps that actually HOLD bytes compete for the
                # retention window: an empty committed slot must not
                # push a usable copy out of it.
                committed = [
                    k
                    for k, s in self._steps.items()
                    if s.committed and s.holds_bytes
                ]
                for old in committed[: -max(1, self.keep_last_n)]:
                    self._drop_locked(old)
            self._publish_gauges_locked()

    def evict_step(self, step_key: str) -> bool:
        with self._lock:
            if step_key not in self._steps:
                return False
            self._drop_locked(step_key)
            self._publish_gauges_locked()
            return True

    def _drop_locked(self, step_key: str) -> None:
        slot = self._steps.pop(step_key, None)
        if slot is None:
            return
        for _, data in slot.blobs.values():
            self._budget.release(len(data))
        for path in slot.chunk_refs:
            rc = self._chunk_rc.get(path, 0) - 1
            if rc <= 0:
                self._chunk_rc.pop(path, None)
                pooled = self._chunks.pop(path, None)
                if pooled is not None:
                    self._budget.release(len(pooled[1]))
            else:
                self._chunk_rc[path] = rc
        if self._pinned == step_key:
            self._pinned = None

    def _evict_one_locked(self, exclude: str) -> bool:
        for key in self._steps:
            if key == exclude or key == self._pinned:
                continue
            self._drop_locked(key)
            return True
        return False

    # -- reads ----------------------------------------------------------

    def get(self, step_key: str, path: str) -> Optional[Tuple[tuple, bytes]]:
        with self._lock:
            if self._is_chunk(path):
                # Content-addressed: a pooled chunk serves ANY step —
                # the path names the bytes, not their provenance.
                pooled = self._chunks.get(path)
                if pooled is not None:
                    return pooled
            slot = self._steps.get(step_key)
            if slot is None:
                return None
            return slot.blobs.get(path)

    def inventory(self, step_key: str) -> Dict[str, tuple]:
        with self._lock:
            slot = self._steps.get(step_key)
            if slot is None:
                return {}
            out = {p: e for p, (e, _) in slot.blobs.items()}
            for p in slot.chunk_refs:
                pooled = self._chunks.get(p)
                if pooled is not None:
                    out[p] = pooled[0]
            return out

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "steps": len(self._steps),
                "blobs": sum(len(s.blobs) for s in self._steps.values()),
                "chunks": len(self._chunks),
                "chunk_bytes": sum(
                    len(d) for _, d in self._chunks.values()
                ),
                "bytes": self._budget.reserved_bytes(),
                "budget_bytes": self._budget.total_bytes,
                "pinned": self._pinned,
                "committed_steps": sorted(
                    k for k, s in self._steps.items() if s.committed
                ),
            }

    def _publish_gauges_locked(self) -> None:
        try:
            registry = telemetry.metrics()
            registry.gauge_set(
                metric_names.PEER_CACHE_BYTES,
                self._budget.reserved_bytes(),
            )
            registry.gauge_set(
                metric_names.PEER_CACHE_STEPS, len(self._steps)
            )
        except Exception:  # noqa: BLE001 - telemetry is best-effort
            pass


# ---------------------------------------------------------------------------
# Transport: server + client (length-prefixed frames, pickled tuples)
# ---------------------------------------------------------------------------


class _PeerServer(socketserver.ThreadingTCPServer):
    daemon_threads = True
    allow_reuse_address = True
    # The stock backlog of 5 drops connects when a fleet syncs at once
    # (every non-owner dials the owner within the same announce poll
    # interval); the kernel's SYN retransmit then stalls the dropped
    # dialers for whole seconds. Queue a fleet's worth instead.
    request_queue_size = 128

    def __init__(self, addr, cache: PeerCache) -> None:
        super().__init__(addr, _PeerRequestHandler)
        self.cache = cache
        # Concurrent-handler count: the wire observatory's userspace
        # proxy for accept pressure on this cache server.
        self.active_handlers = 0
        self.active_lock = threading.Lock()


class _PeerRequestHandler(socketserver.BaseRequestHandler):
    def _dispatch(self, cmd: str, args: tuple, cache: PeerCache) -> Any:
        registry = telemetry.metrics()
        if cmd == metric_names.RPC_PEER_PUSH:
            step_key, step, path, entry, data = args
            return cache.put(step_key, step, path, entry, data)
        if cmd == metric_names.RPC_PEER_COMMIT:
            step_key, step = args
            cache.commit(step_key, step)
            return (True, "ok")
        if cmd == metric_names.RPC_PEER_PULL:
            if len(args) == 3:
                step_key, path, rng = args
            else:
                step_key, path = args
                rng = None
            found = cache.get(step_key, path)
            if found is not None and rng is not None:
                # Server-side slice: a ranged read of a cached
                # blob ships only the requested window, not the
                # whole blob, over the socket.
                entry, data = found
                found = (
                    entry,
                    data[int(rng[0]) : int(rng[1])],
                )
            if found is not None:
                registry.counter_inc(metric_names.PEER_PULL_HITS_TOTAL)
                registry.counter_inc(
                    metric_names.PEER_PULL_BYTES_TOTAL,
                    len(found[1]),
                )
            else:
                registry.counter_inc(metric_names.PEER_PULL_MISSES_TOTAL)
            return found
        if cmd == metric_names.RPC_PEER_REFCHUNKS:
            step_key, step, paths = args
            return cache.reference_chunks(step_key, step, list(paths))
        if cmd == metric_names.RPC_PEER_LIST:
            (step_key,) = args
            return cache.inventory(step_key)
        if cmd == metric_names.RPC_PEER_EVICT:
            (step_key,) = args
            return cache.evict_step(step_key)
        if cmd == metric_names.RPC_PEER_STATS:
            return cache.stats()
        if cmd == metric_names.RPC_PEER_PING:
            return "pong"
        return None

    def handle(self) -> None:
        server: _PeerServer = self.server  # type: ignore[assignment]
        cache = server.cache
        with server.active_lock:
            server.active_handlers += 1
            depth = server.active_handlers
        try:
            wire.observe_accept_depth("peer", depth)
        except Exception:  # noqa: BLE001 - observability is best-effort
            pass
        try:
            while True:
                cmd, args = pickle.loads(
                    recv_frame(self.request, endpoint="peer")
                )
                # Stitch the sender's context into this side's trace:
                # the handler span carries the CLIENT's span id as
                # parent, so the merged cross-rank timeline links the
                # subscriber's pull to the serving peer's work.
                ctx = wire.last_received_context()
                if ctx is not None:
                    with _trace_recorder().span(
                        metric_names.SPAN_WIRE_HANDLER,
                        op=ctx.op,
                        trace_id=ctx.trace_id,
                        parent_span_id=ctx.span_id,
                    ):
                        reply = self._dispatch(cmd, args, cache)
                else:
                    reply = self._dispatch(cmd, args, cache)
                send_frame(self.request, pickle.dumps(reply), endpoint="peer")
        except (ConnectionError, EOFError, OSError):
            return
        finally:
            with server.active_lock:
                server.active_handlers -= 1


class PeerClient:
    """One connection to a peer's cache server; every operation is
    bounded by the transfer-timeout knob (connect and per-frame socket
    ops alike) and any failure raises :class:`PeerTransferError` with
    the connection torn down — the next call redials."""

    def __init__(
        self, host: str, port: int, timeout: Optional[float] = None
    ) -> None:
        self.host = host
        self.port = port
        self.timeout = (
            timeout
            if timeout is not None
            else knobs.get_peer_transfer_timeout_seconds()
        )
        self._sock: Optional[socket.socket] = None
        self._lock = threading.Lock()

    def _connect(self) -> socket.socket:
        if self._sock is None:
            t_dial = time.monotonic()
            try:
                sock = socket.create_connection(
                    (self.host, self.port), timeout=self.timeout
                )
            except OSError:
                try:
                    wire.observe_dial("peer", 0.0, ok=False)
                except Exception:  # noqa: BLE001 - best-effort
                    pass
                raise
            try:
                # Dial latency per successful connect: a full listen
                # backlog on the serving peer shows up here as whole-
                # second SYN-retransmit quanta (wire-dial-stalled).
                wire.observe_dial("peer", time.monotonic() - t_dial)
            except Exception:  # noqa: BLE001 - best-effort
                pass
            sock.settimeout(self.timeout)
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            self._sock = sock
        return self._sock

    def request(self, cmd: str, *args: Any) -> Any:
        t0 = time.monotonic()
        with self._lock:
            try:
                # Propagate (or extend) this thread's wire context so
                # the request frame carries trace/span/op — the serving
                # peer's handler span links back to it in the merged
                # trace. ``cmd`` IS the declared RPC id (names.RPC_*).
                with wire.propagate(cmd) as ctx, _trace_recorder().span(
                    metric_names.SPAN_WIRE_RPC,
                    op=cmd,
                    trace_id=ctx.trace_id,
                    span_id=ctx.span_id,
                ):
                    sock = self._connect()
                    send_frame(sock, pickle.dumps((cmd, args)), endpoint="peer")
                    reply = pickle.loads(recv_frame(sock, endpoint="peer"))
            except (OSError, EOFError, pickle.PickleError) as e:
                self._teardown_locked()
                raise PeerTransferError(
                    f"peer {self.host}:{self.port} {cmd} failed: {e!r}"
                ) from e
        try:
            wire.observe_rpc("peer", cmd, time.monotonic() - t0)
        except Exception:  # noqa: BLE001 - telemetry is best-effort
            pass
        return reply

    def _teardown_locked(self) -> None:
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
            self._sock = None

    def close(self) -> None:
        with self._lock:
            self._teardown_locked()

    # Typed convenience wrappers. The op ids are the declared RPC
    # registry constants (names.RPC_PEER_*) — snaplint's rpc-op-ids
    # rule keeps literal op strings out of request call sites.

    def push(
        self,
        step_key: str,
        step: Optional[int],
        path: str,
        entry: tuple,
        data: bytes,
    ) -> Tuple[bool, str]:
        return tuple(
            self.request(
                metric_names.RPC_PEER_PUSH, step_key, step, path, entry, data
            )
        )

    def commit(self, step_key: str, step: Optional[int]) -> None:
        self.request(metric_names.RPC_PEER_COMMIT, step_key, step)

    def reference_chunks(
        self, step_key: str, step: Optional[int], paths: List[str]
    ) -> List[str]:
        """Dedup probe: which of these content-addressed chunk paths the
        peer already pools (now referenced under ``step_key``). The
        pusher ships bytes only for the rest."""
        return list(
            self.request(
                metric_names.RPC_PEER_REFCHUNKS, step_key, step, list(paths)
            )
        )

    def pull(
        self,
        step_key: str,
        path: str,
        byte_range: Optional[Tuple[int, int]] = None,
    ) -> Optional[Tuple[tuple, bytes]]:
        return self.request(
            metric_names.RPC_PEER_PULL, step_key, path, byte_range
        )

    def list_step(self, step_key: str) -> Dict[str, tuple]:
        return dict(self.request(metric_names.RPC_PEER_LIST, step_key))

    def evict(self, step_key: str) -> bool:
        return bool(self.request(metric_names.RPC_PEER_EVICT, step_key))

    def stats(self) -> Dict[str, Any]:
        return dict(self.request(metric_names.RPC_PEER_STATS))

    def ping(self) -> bool:
        """Liveness probe: a full request/response round trip through
        the peer's dispatch loop (not just a TCP connect), so a hung
        server reads as dead. True iff the peer answered."""
        try:
            return self.request(metric_names.RPC_PEER_PING) == "pong"
        except (OSError, RuntimeError):
            return False


# ---------------------------------------------------------------------------
# The replicator (the pushing side's background worker)
# ---------------------------------------------------------------------------


class PeerPushJob:
    """One step's push work: blob inventory + completion handle."""

    def __init__(
        self,
        path_url: str,
        step_key: str,
        step: Optional[int],
        blobs: Dict[str, Optional[tuple]],
        committed: bool,
    ) -> None:
        self.path_url = path_url
        self.step_key = step_key
        self.step = step
        self.blobs = dict(blobs)
        self.committed = committed
        self.done_evt = threading.Event()
        self.error: Optional[BaseException] = None
        self.blobs_pushed = 0
        self.bytes_pushed = 0
        self.pushed: List[str] = []
        self.blobs_refused = 0
        self.blobs_skipped = 0
        self.blobs_failed = 0
        # Content-addressed chunks the peer already held (inventory-by-
        # digest dedup): placed without crossing the wire.
        self.blobs_deduped = 0
        self.bytes_deduped = 0
        self.target_rank: Optional[int] = None
        self.endpoint: Optional[Tuple[str, int]] = None

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self.done_evt.wait(timeout)


class PeerReplicator:
    """Process-wide peer-tier runtime: the local cache server plus the
    background push worker. Inert until :meth:`configure` runs (which
    needs a coordination store and rank/world coordinates); every
    public method is a no-op-shaped fallback before then."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._configured = False
        self._store: Optional[Store] = None
        self._rank = 0
        self._world = 1
        self._server: Optional[_PeerServer] = None
        self._server_thread: Optional[threading.Thread] = None
        self.cache = PeerCache()
        self.host: Optional[str] = None
        self.port: Optional[int] = None
        self._queue: "queue.Queue[Optional[PeerPushJob]]" = queue.Queue()
        self._jobs: List[PeerPushJob] = []
        self._worker: Optional[threading.Thread] = None
        self._stopped = False
        self.degraded = False
        self._failures = 0

    # -- setup -----------------------------------------------------------

    def configure(
        self,
        store: Store,
        rank: int,
        world_size: int,
        keep_last_n: Optional[int] = None,
    ) -> bool:
        """Start the cache server (once) and advertise its endpoint.
        Idempotent; re-configuring refreshes ``keep_last_n`` and
        re-publishes the endpoint (the replacement-rank re-announce)."""
        with self._lock:
            if self._stopped:
                return False
            self._store = store
            self._rank = int(rank)
            self._world = int(world_size)
            if keep_last_n is not None:
                self.cache.keep_last_n = keep_last_n
            if self._server is None:
                server = _PeerServer(("0.0.0.0", 0), self.cache)
                self._server = server
                self.port = server.server_address[1]
                self.host = _advertise_host()
                self._server_thread = threading.Thread(
                    target=server.serve_forever,
                    name="peer-tier-server",
                    daemon=True,
                )
                self._server_thread.start()
            self._configured = True
        try:
            publish_endpoint(
                store, PEER_SERVICE, self._rank, self.host, self.port
            )
        except Exception as e:  # noqa: BLE001 - degraded, not fatal
            logger.warning("peer tier: endpoint publish failed: %r", e)
            self._note_degraded()
        return True

    @property
    def configured(self) -> bool:
        return self._configured

    @property
    def rank(self) -> int:
        return self._rank

    @property
    def world_size(self) -> int:
        return self._world

    def endpoint_for(self, rank: int) -> Optional[Tuple[str, int]]:
        if self._store is None:
            return None
        return lookup_endpoint(self._store, PEER_SERVICE, rank)

    def resolve_endpoints(self, ranks) -> Dict[int, Tuple[str, int]]:
        """Every advertised endpoint for ``ranks`` in ONE batched store
        round trip (``dist_store.lookup_endpoints``); {} before
        configure or on a failed registry read."""
        if self._store is None:
            return {}
        return lookup_endpoints(self._store, PEER_SERVICE, ranks)

    def target_rank(self) -> int:
        return (self._rank + knobs.get_peer_ring_offset()) % max(
            1, self._world
        )

    # -- pushing ---------------------------------------------------------

    def enqueue_push(
        self,
        path_url: str,
        blobs: Dict[str, Optional[tuple]],
        committed: bool = True,
        step: Optional[int] = None,
    ) -> Optional[PeerPushJob]:
        """Queue one step's blobs for replication to the ring neighbor;
        returns a handle, or None when the tier cannot run (not
        configured, single-process world, or a ring offset that maps
        the rank onto itself)."""
        with self._lock:
            if (
                not self._configured
                or self._stopped
                or self._world <= 1
                or not blobs
            ):
                return None
            if self.target_rank() == self._rank:
                return None
            job = PeerPushJob(
                path_url, peer_step_key(path_url), step, blobs, committed
            )
            # Settled jobs carry no state restores need (the cache is
            # the truth): keep EVERY unsettled job (drain() — the
            # preemption-grace flush — must wait on all of them) plus
            # the newest few failures for state().
            unsettled = [
                j for j in self._jobs if not j.done_evt.is_set()
            ]
            failed = [
                j
                for j in self._jobs
                if j.done_evt.is_set() and j.error is not None
            ][-8:]
            self._jobs = failed + unsettled
            self._jobs.append(job)
            self._ensure_worker_locked()
        self._queue.put(job)
        return job

    def _ensure_worker_locked(self) -> None:
        if self._worker is None or not self._worker.is_alive():
            self._worker = threading.Thread(
                target=self._worker_main, name="peer-tier-push", daemon=True
            )
            self._worker.start()

    def _worker_main(self) -> None:
        while True:
            job = self._queue.get()
            if job is None:
                return
            recorder = _trace_recorder()
            # The job is an operation of its own: its spans carry its id.
            job_span = recorder.begin_op(
                metric_names.SPAN_PEER_JOB,
                0,
                step=job.step_key,
                blobs=len(job.blobs),
            )
            try:
                run_in_fresh_event_loop(self._run_job(job))
                if job.blobs_failed == 0 and job.blobs_refused == 0:
                    self._clear_degraded()
            except BaseException as e:  # noqa: BLE001 - degrade, never raise
                job.error = e
                self._note_degraded()
                logger.warning(
                    "peer tier: push of %s to rank %s degraded (%r); the "
                    "restore ladder falls through to storage",
                    job.step_key,
                    job.target_rank,
                    e,
                )
            finally:
                recorder.end(job_span)
                self._settle_telemetry(job)
                job.done_evt.set()
                self._queue.task_done()

    async def _run_job(self, job: PeerPushJob) -> None:
        job.target_rank = self.target_rank()
        endpoint = self.endpoint_for(job.target_rank)
        job.endpoint = endpoint
        if endpoint is None:
            raise PeerTransferError(
                f"rank {job.target_rank} published no peer endpoint"
            )
        timeout = knobs.get_peer_transfer_timeout_seconds()
        storage = url_to_storage_plugin(job.path_url)
        client = PeerClient(endpoint[0], endpoint[1], timeout=timeout)
        retry = CollectiveProgressRetryStrategy(
            progress_window_seconds=timeout, scope="peer"
        )
        try:
            # Inventory-by-digest dedup, one RPC: content-addressed
            # chunk paths the neighbor already pools are *referenced*
            # under this step (no bytes cross the wire) — a dense-
            # retention run pushes one full step plus deltas.
            from ..cas import is_chunk_location

            deduped: set = set()
            chunk_paths = sorted(
                p for p in job.blobs if is_chunk_location(p)
            )
            if chunk_paths:

                async def _ref_once():
                    return await run_in_executor(
                        None,
                        client.reference_chunks,
                        job.step_key,
                        job.step,
                        chunk_paths,
                    )

                hits = await retry.run(
                    _ref_once, retriable_exceptions=(PeerTransferError,)
                )
                for p in hits:
                    deduped.add(p)
                    job.blobs_deduped += 1
                    entry = job.blobs.get(p)
                    if entry is not None and len(entry) >= 3:
                        job.bytes_deduped += int(entry[2])
                    job.pushed.append(p)
            for path in sorted(job.blobs):
                if path in deduped:
                    continue
                entry = job.blobs[path]
                read_io = ReadIO(path=path)
                try:
                    await storage.read(read_io)
                except FileNotFoundError:
                    # Eviction/GC raced the push: the blob is gone
                    # locally, so there is nothing to replicate.
                    job.blobs_skipped += 1
                    continue
                data = bytes(read_io.buf)
                if entry is None:
                    from ..integrity import compute_checksum_entry

                    entry = compute_checksum_entry(data)

                def _push_sync(
                    p: str = path, e: tuple = entry, d: bytes = data
                ):
                    return client.push(job.step_key, job.step, p, e, d)

                async def _push_once():
                    return await run_in_executor(None, _push_sync)

                with _trace_recorder().span(
                    metric_names.SPAN_PEER_PUSH, blob=path
                ):
                    accepted, reason = await retry.run(
                        _push_once,
                        retriable_exceptions=(PeerTransferError,),
                    )
                if accepted:
                    job.blobs_pushed += 1
                    job.bytes_pushed += len(data)
                    job.pushed.append(path)
                else:
                    # The peer's budget refused the blob: permanent for
                    # this step (the cache is full of pinned bytes) —
                    # count it and move on, the ladder falls through.
                    job.blobs_refused += 1
            if job.committed:
                async def _commit_once():
                    return await run_in_executor(
                        None, client.commit, job.step_key, job.step
                    )

                await retry.run(
                    _commit_once, retriable_exceptions=(PeerTransferError,)
                )
            await self._write_placement(storage, job)
        except (PeerTransferError, RetriesExhausted) as e:
            # Only blobs neither pushed, budget-refused, nor GC-skipped
            # actually FAILED on the transport — refusals/skips are
            # already counted and must not be double-reported to the
            # doctor/fsck evidence.
            job.blobs_failed = max(
                0,
                len(job.blobs)
                - job.blobs_pushed
                - job.blobs_refused
                - job.blobs_skipped
                - job.blobs_deduped,
            )
            try:
                await self._write_placement(storage, job, error=repr(e))
            except Exception:  # noqa: BLE001 - already degrading
                pass
            raise
        finally:
            client.close()
            await storage.close()

    async def _write_placement(
        self,
        storage: StoragePlugin,
        job: PeerPushJob,
        error: Optional[str] = None,
    ) -> None:
        """Placement journal entry for this push (fast/local tier): the
        offline record of which blobs have peer copies where —
        ``fsck --tier peer``'s evidence."""
        from .plugin import TieredStoragePlugin

        doc = {
            "step_key": job.step_key,
            "step": job.step,
            "pusher_rank": self._rank,
            "target_rank": job.target_rank,
            "endpoint": (
                f"{job.endpoint[0]}:{job.endpoint[1]}"
                if job.endpoint
                else None
            ),
            "committed": job.committed,
            "blobs_pushed": job.blobs_pushed,
            "blobs_refused": job.blobs_refused,
            "blobs_skipped": job.blobs_skipped,
            "blobs_failed": job.blobs_failed,
            "blobs_deduped": job.blobs_deduped,
            "bytes_pushed": job.bytes_pushed,
            "bytes_deduped": job.bytes_deduped,
            # Only the blobs that actually LANDED in the peer's RAM —
            # the placement claim fsck audits against requirements.
            "blobs": sorted(job.pushed),
            "blobs_total": len(job.blobs),
            "error": error,
            "unix_ts": round(time.time(), 3),
        }
        payload = json.dumps(doc, sort_keys=True).encode()
        target = (
            storage.fast
            if isinstance(storage, TieredStoragePlugin)
            else storage
        )
        await target.write(
            WriteIO(path=placement_doc_path(self._rank), buf=payload)
        )

    def _settle_telemetry(self, job: PeerPushJob) -> None:
        try:
            registry = telemetry.metrics()
            registry.counter_inc(
                metric_names.PEER_PUSH_BLOBS_TOTAL, job.blobs_pushed
            )
            registry.counter_inc(
                metric_names.PEER_PUSH_BYTES_TOTAL, job.bytes_pushed
            )
            if job.blobs_deduped:
                registry.counter_inc(
                    metric_names.PEER_PUSH_CHUNKS_DEDUPED_TOTAL,
                    job.blobs_deduped,
                )
                registry.counter_inc(
                    metric_names.PEER_PUSH_BYTES_DEDUPED_TOTAL,
                    job.bytes_deduped,
                )
            failures = job.blobs_failed + job.blobs_refused
            if failures or job.error is not None:
                registry.counter_inc(
                    metric_names.PEER_PUSH_FAILURES_TOTAL, max(1, failures)
                )
        except Exception:  # noqa: BLE001 - telemetry is best-effort
            pass

    def _note_degraded(self) -> None:
        self.degraded = True
        self._failures += 1
        try:
            telemetry.metrics().gauge_set(
                metric_names.PEER_TIER_DEGRADED_STATE, 1
            )
        except Exception:  # noqa: BLE001
            pass

    def _clear_degraded(self) -> None:
        if not self.degraded:
            return
        self.degraded = False
        try:
            telemetry.metrics().gauge_set(
                metric_names.PEER_TIER_DEGRADED_STATE, 0
            )
        except Exception:  # noqa: BLE001
            pass

    # -- completion / lifecycle -----------------------------------------

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Block until every queued push settles (True) or the timeout
        lapses (False). The preemption drain hook: inside the eviction
        grace window this ships the last committed step's delta into
        the surviving peer's RAM — host-RAM bandwidth, not a durable
        commit — so the replacement's restore has a hot copy."""
        deadline = (
            time.monotonic() + timeout if timeout is not None else None
        )
        with self._lock:
            jobs = list(self._jobs)
        for job in jobs:
            remaining = None
            if deadline is not None:
                remaining = max(0.0, deadline - time.monotonic())
            if not job.wait(remaining):
                return False
        return True

    def state(self) -> Dict[str, Any]:
        with self._lock:
            pending = [j for j in self._jobs if not j.done_evt.is_set()]
            return {
                "configured": self._configured,
                "rank": self._rank,
                "world_size": self._world,
                "endpoint": (
                    f"{self.host}:{self.port}" if self.port else None
                ),
                "degraded": self.degraded,
                "failures": self._failures,
                "jobs_pending": len(pending),
                "cache": self.cache.stats(),
            }

    def stop(self) -> None:
        with self._lock:
            if self._stopped:
                return
            self._stopped = True
            self._configured = False
            worker = self._worker
            server = self._server
            server_thread = self._server_thread
        self._queue.put(None)
        if worker is not None:
            worker.join(timeout=10)
        if server is not None:
            server.shutdown()
            server.server_close()
        if server_thread is not None:
            server_thread.join(timeout=10)


def _advertise_host() -> str:
    """The address peers dial for THIS process's cache server: this
    machine's own interface, never the coordinator's."""
    from ..dist_store import _local_advertise_host

    try:
        return _local_advertise_host()
    except Exception:  # noqa: BLE001 - last resort
        return socket.gethostname()


# ---------------------------------------------------------------------------
# Process-wide replicator + integration hooks
# ---------------------------------------------------------------------------

_replicator: Optional[PeerReplicator] = None
_replicator_lock = threading.Lock()
# One-shot warning latch: peer tier enabled but inert (checksums off).
_WARNED_NO_CHECKSUMS = False


def get_replicator() -> PeerReplicator:
    global _replicator
    with _replicator_lock:
        if _replicator is None:
            _replicator = PeerReplicator()
        return _replicator


def reset_peer_tier() -> None:
    """Stop and discard the process replicator (tests simulating a
    restarted — or preempted — process)."""
    global _replicator
    with _replicator_lock:
        rep, _replicator = _replicator, None
    if rep is not None:
        rep.stop()


def maybe_configure(pg: Any, keep_last_n: Optional[int] = None) -> bool:
    """Configure the peer tier for this process if the knob is on and a
    multi-rank coordination store exists; False otherwise. Safe to call
    repeatedly (manager construction, replacement-rank restart)."""
    if not knobs.is_peer_tier_enabled():
        return False
    from ..pg_wrapper import PGWrapper

    wrapper = pg if isinstance(pg, PGWrapper) else PGWrapper(pg)
    store = wrapper.store
    if store is None or wrapper.get_world_size() <= 1:
        return False
    return get_replicator().configure(
        store,
        wrapper.get_rank(),
        wrapper.get_world_size(),
        keep_last_n=keep_last_n,
    )


def maybe_enqueue_push(
    path: str, written: Dict[str, tuple], committed: bool = True
) -> Optional[PeerPushJob]:
    """Snapshot-commit hook (every rank): queue this rank's written
    blobs for replication to its ring neighbor. ``written`` is the
    rank's checksum table (path -> integrity entry) — the digests the
    puller will verify against. No-op unless the tier is configured;
    base-referenced (``../``) locations belong to other steps and are
    skipped. Never raises."""
    if not knobs.is_peer_tier_enabled():
        return None
    with _replicator_lock:
        rep = _replicator
    if rep is None or not rep.configured:
        return None
    try:
        # Base-referenced (``../step_*``) locations belong to other
        # steps and are skipped — but content-addressed chunk refs ARE
        # this step's payload (stored once, referenced by many): they
        # push (or dedup against the neighbor's pool) like any blob.
        from ..cas import is_chunk_location

        blobs: Dict[str, Optional[tuple]] = {
            p: tuple(e)
            for p, e in written.items()
            if not p.startswith("../") or is_chunk_location(p)
        }
        if not blobs:
            if knobs.is_checksums_disabled():
                # The blob inventory IS the checksum table: with
                # checksums off there is nothing to push (and nothing
                # a puller could verify). Say so ONCE — a run with the
                # peer tier nominally on but silently inert would
                # otherwise only be discovered at the preemption it
                # failed to insure.
                global _WARNED_NO_CHECKSUMS
                if not _WARNED_NO_CHECKSUMS:
                    _WARNED_NO_CHECKSUMS = True
                    logger.warning(
                        "peer tier: checksums are disabled "
                        "(TORCHSNAPSHOT_TPU_DISABLE_CHECKSUMS), so no "
                        "blob inventory exists to push — the peer tier "
                        "is inert and preemption recovery will pay a "
                        "full storage restore"
                    )
            return None
        from ..telemetry.ledger import step_from_path

        step = step_from_path(peer_step_key(path))
        return rep.enqueue_push(
            path, blobs, committed=committed, step=step
        )
    except Exception as e:  # noqa: BLE001 - the tier degrades, never fails ops
        logger.warning("peer tier: push enqueue failed: %r", e)
        return None


def maybe_drain(timeout: Optional[float] = None) -> bool:
    """Flush pending peer pushes (preemption grace window / teardown);
    True when everything settled or the tier is inert."""
    with _replicator_lock:
        rep = _replicator
    if rep is None or not rep.configured:
        return True
    return rep.drain(timeout)


def maybe_evict_step(path: str) -> None:
    """Manager-GC hook (rank 0): best-effort eviction of a dropped
    step's peer copies from EVERY advertised endpoint — the caches
    self-bound regardless (budget LRU + keep_last_n), this just
    reclaims the RAM promptly. Runs on a detached daemon thread: GC
    sits on rank 0's save path, and a dead peer's connect timeouts
    must never stretch a save."""
    with _replicator_lock:
        rep = _replicator
    if rep is None or not rep.configured:
        return
    step_key = peer_step_key(path)
    timeout = min(5.0, knobs.get_peer_transfer_timeout_seconds())
    world = rep.world_size

    def _evict_all() -> None:
        # One batched registry resolve for the whole ring, then the
        # per-endpoint evict RPCs.
        endpoints = rep.resolve_endpoints(range(world))
        for rank in range(world):
            endpoint = endpoints.get(rank)
            if endpoint is None:
                continue
            client = PeerClient(endpoint[0], endpoint[1], timeout=timeout)
            try:
                client.evict(step_key)
            except PeerTransferError:
                pass  # dead peer: its cache died with it
            finally:
                client.close()

    threading.Thread(
        target=_evict_all, name="peer-tier-evict", daemon=True
    ).start()


def peer_state_for_path(path: str) -> Optional[Dict[str, Any]]:
    """The process replicator's state when the tier is configured, else
    None — the one state read shared by snapshot reports and the
    doctor (mirror_state_for_path's shape)."""
    with _replicator_lock:
        rep = _replicator
    if rep is None or not rep.configured:
        return None
    return rep.state()


# ---------------------------------------------------------------------------
# Restore side: the tier ladder
# ---------------------------------------------------------------------------


class PeerRestoreContext:
    """One restore's peer-tier state: the owner table over surviving
    peers (blob path -> endpoint + integrity entry) and the per-tier
    byte accounting the restore report carries as ``tier_split``."""

    def __init__(
        self,
        table: Dict[str, Tuple[int, Tuple[str, int], tuple]],
        step_key: str,
        timeout: Optional[float] = None,
    ) -> None:
        self.table = table
        self.step_key = step_key
        self.timeout = (
            timeout
            if timeout is not None
            else knobs.get_peer_transfer_timeout_seconds()
        )
        self._lock = threading.Lock()
        # Per-endpoint free-connection pool: concurrent pulls each
        # borrow a connection (creating one when none is free) and
        # return it on success, so restore reads are NOT serialized
        # onto one TCP stream per surviving peer — concurrency is
        # bounded by the read pipeline's executor, not by a shared
        # client lock. A connection that errored is closed, not
        # returned.
        self._free_clients: Dict[Tuple[str, int], List[PeerClient]] = {}
        self._endpoint_failures: Dict[Tuple[str, int], int] = {}
        self.tier_bytes: Dict[str, int] = {
            "peer": 0,
            "fast": 0,
            "durable": 0,
        }
        self.peer_failures = 0
        self.fallthrough_bytes = 0
        self.served_blobs = 0

    @property
    def eligible_blobs(self) -> int:
        return len(self.table)

    def _borrow(self, endpoint: Tuple[str, int]) -> Optional[PeerClient]:
        with self._lock:
            if (
                self._endpoint_failures.get(endpoint, 0)
                >= _PULL_DEAD_AFTER_FAILURES
            ):
                outcome = "dead"
                client = None
            else:
                free = self._free_clients.get(endpoint)
                if free:
                    outcome = "reused"
                    client = free.pop()
                else:
                    outcome = "new"
                    client = PeerClient(
                        endpoint[0], endpoint[1], timeout=self.timeout
                    )
        try:
            wire.observe_pool_checkout("peer", outcome)
        except Exception:  # noqa: BLE001 - telemetry is best-effort
            pass
        return client

    def _give_back(
        self, endpoint: Tuple[str, int], client: PeerClient
    ) -> None:
        with self._lock:
            self._free_clients.setdefault(endpoint, []).append(client)

    def pull(
        self,
        path: str,
        byte_range: Optional[Tuple[int, int]] = None,
    ) -> Optional[bytes]:
        """Digest-verified pull of ``path`` (the whole blob, or exactly
        the ``byte_range`` window) from the owning peer, or None on ANY
        failure (the caller falls through a tier).

        Ranged reads of blobs with per-page digests are sliced on the
        SERVER — only the window crosses the socket — and verified via
        the page digests the range fully covers; a window covering no
        full page (or a blob with only a whole-blob digest) falls back
        to one whole-blob transfer verified end-to-end and sliced
        client-side, so no byte is ever trusted unverified."""
        owner = self.table.get(path)
        if owner is None:
            return None
        _, endpoint, entry = owner
        client = self._borrow(endpoint)
        if client is None:
            return None
        entry = tuple(entry)
        rng = None
        if byte_range is not None:
            rng = (int(byte_range[0]), int(byte_range[1]))
        # Server-side slicing only when the window is verifiable on its
        # own (paged entry, integrity.verify_range_checksum).
        ranged = rng is not None and len(entry) >= 5
        try:
            with _trace_recorder().span(
                metric_names.SPAN_PEER_PULL, blob=path
            ):
                found = client.pull(
                    self.step_key, path, rng if ranged else None
                )
                if found is not None and ranged:
                    from ..integrity import verify_range_checksum

                    if not verify_range_checksum(
                        found[1], entry, rng, path
                    ):
                        # The window fully covers no page: re-pull the
                        # whole blob so the full digest can vouch.
                        found = client.pull(self.step_key, path)
                        ranged = False
            if found is None:
                # Stale step / evicted blob: a correct miss.
                self._give_back(endpoint, client)
                with self._lock:
                    self.peer_failures += 1
                return None
            pulled_entry, data = found
            # Trust NOTHING before the integrity layer passes: verify
            # against the entry recorded at *write* time (the inventory
            # the table was built from), so a corrupted cache — or a
            # peer echoing a different step's bytes — can never reach
            # the destination buffers. (Ranged pulls were verified
            # against the covered page digests above.)
            if not ranged:
                verify_checksum(data, entry, path)
            self._give_back(endpoint, client)
            with self._lock:
                self._endpoint_failures.pop(endpoint, None)
            if rng is not None and not ranged:
                return data[rng[0] : rng[1]]
            return data
        except ChecksumError as e:
            logger.warning(
                "peer tier: checksum mismatch pulling %s (%r); falling "
                "through to the next tier",
                path,
                e,
            )
            # The transport is fine — only the bytes are wrong: the
            # connection goes back to the pool, the failure count does
            # NOT advance the endpoint toward dead.
            self._give_back(endpoint, client)
            with self._lock:
                self.peer_failures += 1
            return None
        except PeerTransferError as e:
            client.close()
            with self._lock:
                self.peer_failures += 1
                n = self._endpoint_failures.get(endpoint, 0) + 1
                self._endpoint_failures[endpoint] = n
            logger.warning(
                "peer tier: pull of %s from %s failed (%r, failure %d); "
                "falling through to the next tier",
                path,
                endpoint,
                e,
                n,
            )
            return None

    def count(self, tier: str, nbytes: int) -> None:
        with self._lock:
            self.tier_bytes[tier] = self.tier_bytes.get(tier, 0) + int(
                nbytes
            )
            if tier == "peer":
                self.served_blobs += 1

    def discount(self, tier: str, nbytes: int) -> None:
        """Take back a serve that verification later rejected (the
        corruption ladder re-served the blob from another tier): the
        split must sum to the bytes actually restored, not restored
        plus every corrupt attempt."""
        with self._lock:
            self.tier_bytes[tier] = max(
                0, self.tier_bytes.get(tier, 0) - int(nbytes)
            )

    def note_fallthrough(self, nbytes: int) -> None:
        with self._lock:
            self.fallthrough_bytes += int(nbytes)

    def pipeline_fields(self) -> Dict[str, Any]:
        """The restore report's peer-tier fields (report.py maps them
        through build_report): per-tier byte split + degradation
        evidence for the ``peer-tier-degraded`` doctor rule."""
        with self._lock:
            return {
                "tier_split": dict(self.tier_bytes),
                "peer": {
                    "eligible_blobs": self.eligible_blobs,
                    "served_blobs": self.served_blobs,
                    "failures": self.peer_failures,
                    "fallthrough_bytes": self.fallthrough_bytes,
                    "degraded": bool(
                        self.peer_failures or self.fallthrough_bytes
                    ),
                },
            }

    def wrap(self, storage: StoragePlugin) -> "StoragePlugin":
        return _PeerLadderPlugin(storage, self)

    def close(self) -> None:
        with self._lock:
            pools, self._free_clients = dict(self._free_clients), {}
        for clients in pools.values():
            for client in clients:
                client.close()


class _PeerLadderPlugin(StoragePlugin):
    """The per-shard tier ladder as a plugin view: peer RAM first for
    table-resident blobs, then the local fast tier, then durable —
    with per-tier byte accounting. Substituted for the restore's
    storage plugin wholesale, so close() DOES delegate (the ladder owns
    the inner plugin's lifecycle for the op)."""

    def __init__(self, inner: StoragePlugin, ctx: PeerRestoreContext) -> None:
        from .plugin import TieredStoragePlugin

        self.inner = inner
        self.ctx = ctx
        self._tiered = (
            inner if isinstance(inner, TieredStoragePlugin) else None
        )

    async def read(self, read_io: ReadIO) -> None:
        path = read_io.path
        eligible = path in self.ctx.table
        # A LOCAL fast-tier hit short-circuits the peer pull: the ladder
        # exists for bytes the host lost, and a surviving rank's local
        # copy is free — shipping it over the interconnect would
        # multiply restore traffic by ~world for no availability gain.
        # (The replacement rank's fast tier is empty, so its shards
        # still resolve peer-first in effect.)
        if self._tiered is not None:
            try:
                await self._tiered.fast.read(read_io)
                read_io.served_by = "fast"
                self.ctx.count(
                    "fast",
                    memoryview(read_io.buf).nbytes
                    if read_io.buf is not None
                    else 0,
                )
                return
            except FileNotFoundError:
                pass
        if eligible:
            rng = read_io.byte_range
            chunk = await run_in_executor(
                None, self.ctx.pull, path, rng
            )
            if chunk is not None:
                if read_io.dest is not None and len(read_io.dest) == len(
                    chunk
                ):
                    read_io.dest[:] = chunk
                    read_io.buf = read_io.dest
                else:
                    read_io.buf = memoryview(bytes(chunk))
                read_io.served_by = "peer"
                self.ctx.count("peer", len(chunk))
                return
        # Bottom of the ladder: durable storage (a non-tiered inner
        # plugin IS the durable tier).
        if self._tiered is not None:
            await self._tiered.durable.read(read_io)
        else:
            await self.inner.read(read_io)
        read_io.served_by = "durable"
        nbytes = (
            memoryview(read_io.buf).nbytes if read_io.buf is not None else 0
        )
        self.ctx.count("durable", nbytes)
        if eligible:
            # A peer copy existed for this blob but durable storage
            # served it: the degradation the doctor rule cites.
            self.ctx.note_fallthrough(nbytes)

    async def read_degraded(self, read_io: ReadIO) -> bool:
        """Corruption fallthrough, ladder flavor: peer pulls are
        digest-verified inside :meth:`PeerRestoreContext.pull` (corrupt
        peer bytes never escape it), so the storage tiers are the only
        sources whose bytes can reach verification corrupt — retry
        whichever of durable/fast has not served this request yet."""
        tried = getattr(read_io, "_tiers_tried", None)
        if tried is None:
            tried = {read_io.served_by} if read_io.served_by else set()
            read_io._tiers_tried = tried
        # The rejected serve was already counted by read() (or by a
        # previous healing round): take it back so tier_split sums to
        # the bytes actually restored.
        if read_io.served_by and read_io.buf is not None:
            self.ctx.discount(
                read_io.served_by, memoryview(read_io.buf).nbytes
            )
        tiers = []
        if self._tiered is not None:
            tiers = [
                ("durable", self._tiered.durable),
                ("fast", self._tiered.fast),
            ]
        else:
            tiers = [("durable", self.inner)]
        for tier, plugin in tiers:
            if tier in tried:
                continue
            tried.add(tier)
            try:
                await plugin.read(read_io)
            except (FileNotFoundError, OSError):
                continue
            read_io.served_by = tier
            self.ctx.count(
                tier,
                memoryview(read_io.buf).nbytes
                if read_io.buf is not None
                else 0,
            )
            return True
        return False

    async def read_with_checksum(self, read_io: ReadIO):
        # Decline (sticky, per the interface contract): the ladder must
        # route every read through the tier logic above.
        return None

    async def write(self, write_io: WriteIO) -> None:
        await self.inner.write(write_io)

    async def write_with_checksum(self, write_io: WriteIO):
        return await self.inner.write_with_checksum(write_io)

    async def delete(self, path: str) -> None:
        await self.inner.delete(path)

    async def close(self) -> None:
        self.ctx.close()
        await self.inner.close()


def build_restore_context(path: str) -> Optional[PeerRestoreContext]:
    """Assemble the restore-side owner table for one snapshot path by
    asking every advertised peer endpoint for its inventory of the
    step (one LIST RPC each; a dead peer is skipped with a WARN).
    Endpoint resolution is ONE batched ``multi_get`` against the
    registry (``dist_store.lookup_endpoints``) — restore setup on a
    thousand-rank world costs one store round trip, not world
    sequential lookups. Returns None when the tier is off/inert or no
    peer holds anything for the step — the restore then runs exactly
    the pre-peer path. Never raises: every failure mode degrades to
    "no peer tier"."""
    if not knobs.is_peer_tier_enabled():
        return None
    with _replicator_lock:
        rep = _replicator
    if rep is None or not rep.configured:
        return None
    try:
        from concurrent.futures import ThreadPoolExecutor

        step_key = peer_step_key(path)
        timeout = knobs.get_peer_transfer_timeout_seconds()
        endpoints = rep.resolve_endpoints(range(rep.world_size))

        def _inventory_of(rank: int):
            endpoint = endpoints.get(rank)
            if endpoint is None:
                return rank, None, {}
            client = PeerClient(endpoint[0], endpoint[1], timeout=timeout)
            try:
                return rank, endpoint, client.list_step(step_key)
            except PeerTransferError as e:
                logger.warning(
                    "peer tier: rank %d endpoint %s unreachable during "
                    "restore setup (%r); its cached shards fall through "
                    "to storage",
                    rank,
                    endpoint,
                    e,
                )
                return rank, endpoint, {}
            finally:
                client.close()

        # CONCURRENT inventory RPCs: setup cost is one timeout, not
        # world x timeout, when stale endpoints of preempted hosts
        # linger in the registry.
        with ThreadPoolExecutor(
            max_workers=min(8, max(1, rep.world_size)),
            thread_name_prefix="peer-tier-inv",
        ) as pool:
            results = list(pool.map(_inventory_of, range(rep.world_size)))
        table: Dict[str, Tuple[int, Tuple[str, int], tuple]] = {}
        for rank, endpoint, inventory in results:
            if endpoint is None:
                continue
            for blob_path, entry in inventory.items():
                table.setdefault(
                    blob_path, (rank, endpoint, tuple(entry))
                )
        if not table:
            return None
        return PeerRestoreContext(table, step_key, timeout=timeout)
    except Exception as e:  # noqa: BLE001 - degrade to storage-only restore
        logger.warning("peer tier: restore-context build failed: %r", e)
        return None

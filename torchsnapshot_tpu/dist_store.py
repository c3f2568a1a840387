"""Process-coordination store: KV primitives, object collectives, barriers.

Reference parity: torchsnapshot/dist_store.py (TCPStore bootstrap +
``LinearBarrier``). The TPU-native stack has no torch ``c10d`` store, so this
module provides:

- :class:`Store` — the primitive interface (set/get/add/delete) plus object
  collectives built on it. All snapshot coordination traffic is metadata
  (manifests, plans, error reports) — array bytes never travel here.
- :class:`TCPStore` — a self-contained socket KV server hosted by rank 0,
  used by tests and by multi-process CPU/TPU runs without a JAX coordinator.
- :class:`JaxCoordinationStore` — adapter over the JAX distributed runtime's
  coordination-service KV (``jax.distributed``), for real pods.
- :class:`LinearBarrier` — two-phase (arrive/depart) barrier with error
  propagation, safe to use off the main thread; the async-commit primitive
  (reference dist_store.py:91-196, used at snapshot.py:948-969 because the
  background commit thread must not issue collectives).
- :class:`TreeBarrier` — the default production barrier (same contract,
  built by :func:`make_barrier`): arrive/depart aggregate through a
  fanout-``k`` rank tree, so no single key ever has more than ``k``
  writers or readers and the critical path is O(log_k world) instead of
  every rank rendezvousing on the leader's counter.
- :class:`ShardedStore` — N member stores behind deterministic
  key->shard hashing, so a thousand-rank world's key traffic spreads
  over N server sockets instead of serializing through one hub.

Scaling disciplines (docs/scaling.md; measured by
``benchmarks/coordination_scaling.py`` over the scalemodel harness):
every wait loop backs off exponentially (``_PollPacer``, cap ~100 ms)
so an idle 1000-rank barrier doesn't hammer the store at O(world/5ms)
QPS, and multi-key traffic rides the batched ``multi_set`` /
``multi_get`` / ``multi_delete`` primitives — one wire round trip per
*batch*, not per key. Store requests and barrier waits feed the
coordination telemetry (``coordination_*`` counters, ``barrier:*``
spans) that the ``coordination-bound`` doctor rule reads.

Collective keys are transient: the last participant to finish an operation
deletes its keys, so long-lived stores don't leak.
"""

from __future__ import annotations

import abc
from dataclasses import dataclass
import pickle
import socket
import socketserver
import struct
import threading
import time
import zlib
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from . import knobs

_DEFAULT_TIMEOUT_S = 300.0
_POLL_INTERVAL_S = 0.005
_POLL_CAP_S = 0.1
_CONNECT_TIMEOUT_S = 30.0

# Process-wide (initial, cap) the pacer binds per instance. Not an
# operator knob: the one consumer is the scale-model harness's legacy
# baseline (initial == cap reproduces the pre-backoff fixed-interval
# polling so its O(world) QPS wall stays measurable after the fix).
_POLL_PROFILE: Tuple[float, float] = (_POLL_INTERVAL_S, _POLL_CAP_S)


def _set_poll_profile(initial: float, cap: float) -> Tuple[float, float]:
    """Swap the process-wide poll profile; returns the previous one.
    Scale-model harness use only — production always runs the backoff
    defaults. Affects pacers constructed AFTER the call."""
    global _POLL_PROFILE
    prev = _POLL_PROFILE
    _POLL_PROFILE = (float(initial), float(cap))
    return prev


# Aggregate idle-poll budget a wait loop sizes its backoff cap against:
# cap ≈ world / _POLL_QPS_BUDGET, clamped to [initial, _POLL_CAP_S]. A
# 2-proc barrier keeps ~5 ms detection latency (the cap would only cost
# it latency — two pollers cannot hammer anything), a 256-rank one backs
# off to ~50 ms, a 1000-rank one to the 100 ms ceiling (~10k QPS fleet-
# wide either way). World-aware call sites (barriers, fan-out rounds)
# pass the scaled cap; plain key waits keep the defaults.
_POLL_QPS_BUDGET = 5000.0


def scaled_poll_cap(world_size: int) -> float:
    profile_initial, profile_cap = _POLL_PROFILE
    return min(
        profile_cap,
        max(profile_initial, world_size / _POLL_QPS_BUDGET),
    )


class _PollPacer:
    """Deadline-aware exponential poll backoff for store wait loops.

    Fixed-interval polling is an O(world) QPS multiplier: a 1000-rank
    barrier polling one key every 5 ms lands 200k requests/s on the
    store while *nothing changes*. Backoff doubles the interval per
    miss up to ~100 ms (late enough that a long wait costs each rank
    ~10 QPS, early enough that release latency stays bounded by the
    cap), never sleeping past the caller's deadline, and resets on
    observation so a busy exchange keeps its low first-poll latency."""

    def __init__(
        self,
        initial: Optional[float] = None,
        cap: Optional[float] = None,
    ) -> None:
        self._initial = _POLL_PROFILE[0] if initial is None else initial
        self._cap = _POLL_PROFILE[1] if cap is None else cap
        self._delay = self._initial

    def reset(self) -> None:
        self._delay = self._initial

    def sleep(self, deadline: Optional[float] = None) -> None:
        delay = self._delay
        if deadline is not None:
            delay = min(delay, max(0.0, deadline - time.monotonic()))
        if delay > 0:
            time.sleep(delay)
        self._delay = min(self._delay * 2.0, self._cap)


# ---------------------------------------------------------------------------
# Coordination telemetry (best-effort; never fails a collective)
# ---------------------------------------------------------------------------

_TELE_MODULES = None


def _tele_modules():
    """(telemetry pkg, names, trace) lazily resolved: dist_store sits
    below the telemetry package in the import graph, so the binding
    happens on first use, never at import time."""
    global _TELE_MODULES
    if _TELE_MODULES is None:
        from . import telemetry as _telemetry
        from .telemetry import names as _names
        from .telemetry import trace as _trace

        _TELE_MODULES = (_telemetry, _names, _trace)
    return _TELE_MODULES


def _observe_store_requests(op: str, seconds: float, requests: int = 1) -> None:
    """One store round trip's worth of coordination accounting. The
    per-op deltas land in SnapshotReport.coordination (report.py), which
    is what the scale-model harness and the ``coordination-bound``
    doctor rule attribute against wall time."""
    try:
        telemetry, n, _ = _tele_modules()
        reg = telemetry.metrics()
        reg.counter_inc(n.COORD_STORE_REQUESTS_TOTAL, float(requests), op=op)
        reg.counter_inc(n.COORD_STORE_SECONDS_TOTAL, seconds, op=op)
    except Exception:  # noqa: BLE001 - telemetry must never break the store
        pass


_WIRE_MODULE = None


def _wire():
    """telemetry.wire lazily resolved (same discipline as
    :func:`_tele_modules`): the wire observatory instruments this
    module's framing layer, but dist_store must stay importable below
    the telemetry package."""
    global _WIRE_MODULE
    if _WIRE_MODULE is None:
        from .telemetry import wire as _wire_mod

        _WIRE_MODULE = _wire_mod
    return _WIRE_MODULE


@dataclass
class ProcessGroup:
    """What :class:`~torchsnapshot_tpu.pg_wrapper.PGWrapper` consumes: a
    store plus this process's coordinates."""

    store: "Store"
    rank: int
    world_size: int


class StoreTimeoutError(TimeoutError):
    pass


class BarrierError(RuntimeError):
    """A peer reported an error into the barrier (reference
    dist_store.py:177-193)."""


_READ_GRACE_S = 5.0


class _TransientReads:
    """Tolerance tracker for deadline-bounded poll loops.

    ``try_get`` raises on transport/service failures (None strictly means
    "key definitively absent"). A poll loop should read a *brief* failure
    as "not yet" — the deadline machinery exists to ride out hiccups —
    but a store failing continuously must re-raise rather than be polled
    until the full deadline: on a TCPStore, a dead socket means the
    leader is gone, and 300 s of retries would mask a peer death."""

    def __init__(self, grace: float = _READ_GRACE_S) -> None:
        self._grace = grace
        self._first_failure: Optional[float] = None

    def read(self, fn):
        """Run ``fn`` (a store read); None if it failed within grace."""
        try:
            out = fn()
        except Exception:
            now = time.monotonic()
            if self._first_failure is None:
                self._first_failure = now
            if now - self._first_failure > self._grace:
                raise
            return None
        self._first_failure = None
        return out


class Store(abc.ABC):
    """KV primitives + derived object collectives."""

    # -- primitives -------------------------------------------------------

    @abc.abstractmethod
    def set(self, key: str, value: bytes) -> None: ...

    @abc.abstractmethod
    def try_get(self, key: str) -> Optional[bytes]:
        """The value, or None when the key is *definitively absent*.
        Raises on transport/service failures — callers distinguishing
        "peer did not signal" from "could not observe" depend on it."""

    @abc.abstractmethod
    def add(self, key: str, amount: int) -> int:
        """Atomically add to an integer key (created at 0); returns the new
        value."""

    @abc.abstractmethod
    def delete(self, key: str) -> None: ...

    # -- batched primitives ----------------------------------------------
    #
    # Default implementations degrade to per-key loops so every Store
    # (including the JAX coordination-service adapter) supports them;
    # stores with a wire protocol (TCPStore, and ShardedStore per
    # member) override with ONE round trip per batch — the difference
    # between a fan-out round's setup costing O(world) sequential
    # requests and O(1).

    def multi_set(self, items: Dict[str, bytes]) -> None:
        for key, value in items.items():
            self.set(key, value)

    def multi_get(self, keys: Sequence[str]) -> Dict[str, Optional[bytes]]:
        """Value per key (None where definitively absent), same failure
        semantics as :meth:`try_get`."""
        return {key: self.try_get(key) for key in keys}

    def multi_delete(self, keys: Iterable[str]) -> None:
        for key in keys:
            self.delete(key)

    def scan(self, prefix: str) -> List[str]:
        """All present keys starting with ``prefix`` (sorted). Registry
        consumers only (the fleet plane enumerating ``__obs/``) — not
        every backing store can enumerate, so the default refuses
        rather than silently returning nothing."""
        raise NotImplementedError(
            f"{type(self).__name__} does not support prefix scans"
        )

    # -- blocking helpers -------------------------------------------------

    def get(
        self,
        key: str,
        timeout: float = _DEFAULT_TIMEOUT_S,
        poll_cap: Optional[float] = None,
    ) -> bytes:
        """Blocking read with exponential poll backoff. ``poll_cap``
        bounds the backoff (callers that know the world size pass
        :func:`scaled_poll_cap` so a 2-proc collective keeps ~5 ms
        detection latency; the default cap is the 100 ms ceiling)."""
        deadline = time.monotonic() + timeout
        reads = _TransientReads()
        pacer = _PollPacer(cap=poll_cap)
        while True:
            val = reads.read(lambda: self.try_get(key))
            if val is not None:
                return val
            if time.monotonic() > deadline:
                raise StoreTimeoutError(f"Timed out waiting for store key {key!r}")
            pacer.sleep(deadline)

    def wait_any(
        self, keys: Sequence[str], timeout: float = _DEFAULT_TIMEOUT_S
    ) -> Dict[str, bytes]:
        """Block until at least one of ``keys`` exists; returns all present.
        Polls the whole key set in one batched round trip per tick."""
        deadline = time.monotonic() + timeout
        reads = _TransientReads()
        pacer = _PollPacer()
        while True:
            got = reads.read(lambda: self.multi_get(list(keys)))
            present = {
                k: v for k, v in (got or {}).items() if v is not None
            }
            if present:
                return present
            if time.monotonic() > deadline:
                raise StoreTimeoutError(f"Timed out waiting for any of {keys!r}")
            pacer.sleep(deadline)

    # -- object collectives ----------------------------------------------

    def _cleanup(self, prefix: str, world_size: int, keys: List[str]) -> None:
        if self.add(f"{prefix}/__done", 1) == world_size:
            self.multi_delete(keys + [f"{prefix}/__done"])

    def exchange(
        self,
        prefix: str,
        rank: int,
        world_size: int,
        obj: Any,
        timeout: float = _DEFAULT_TIMEOUT_S,
    ) -> List[Any]:
        """All-gather of picklable objects.

        Rank 0 aggregates the per-rank blobs into ONE combined value that
        everyone else fetches with a single get: O(1) store round-trips
        per non-leader rank instead of O(world), so a v4-32-pod manifest
        gather doesn't issue world² sequential requests through the
        leader's socket (the bytes are inherently O(world²) for an
        all-gather; the round-trips need not be).
        """
        cap = scaled_poll_cap(world_size)
        self.set(f"{prefix}/{rank}", pickle.dumps(obj))
        if rank == 0:
            blobs = [
                self.get(f"{prefix}/{i}", timeout, poll_cap=cap)
                for i in range(world_size)
            ]
            out = [pickle.loads(b) for b in blobs]
            self.set(f"{prefix}/__all", pickle.dumps(blobs))
        else:
            out = [
                pickle.loads(b)
                for b in pickle.loads(
                    self.get(f"{prefix}/__all", timeout, poll_cap=cap)
                )
            ]
        self._cleanup(
            prefix,
            world_size,
            [f"{prefix}/{i}" for i in range(world_size)] + [f"{prefix}/__all"],
        )
        return out

    def gather(
        self,
        prefix: str,
        rank: int,
        world_size: int,
        obj: Any,
        dst: int = 0,
        timeout: float = _DEFAULT_TIMEOUT_S,
    ) -> Optional[List[Any]]:
        """Gather picklable objects to ``dst`` (rank order); None elsewhere.

        Unlike :meth:`exchange`, non-destination ranks publish their own
        blob and do NOT fetch the combined value: per non-dst rank the
        store traffic is O(own blob) + one counter bump, not
        O(world x blob) — the difference between a manifest gather that
        funnels world² bytes through the leader's socket and one that
        moves each manifest once (reference analog: the c10d gather the
        reference's snapshot.py:879-901 all_gather spreads peer-to-peer;
        here non-leaders don't need the global manifest at all — rank 0
        alone writes metadata, and restore reads it from storage).
        """
        blob = pickle.dumps(obj)
        out = None
        if rank == dst:
            # The destination's own blob never touches the store (nobody
            # else reads it); the loads() keeps all-gather's copy
            # semantics for the local entry.
            cap = scaled_poll_cap(world_size)
            out = [
                pickle.loads(blob)
                if i == rank
                else pickle.loads(
                    self.get(f"{prefix}/{i}", timeout, poll_cap=cap)
                )
                for i in range(world_size)
            ]
        else:
            self.set(f"{prefix}/{rank}", blob)
        # Keys survive until every rank (dst included, which increments
        # only after reading all blobs) has passed through _cleanup;
        # deleting dst's never-set key is a no-op.
        self._cleanup(
            prefix, world_size, [f"{prefix}/{i}" for i in range(world_size)]
        )
        return out

    def broadcast(
        self,
        prefix: str,
        rank: int,
        world_size: int,
        obj: Any,
        src: int = 0,
        timeout: float = _DEFAULT_TIMEOUT_S,
    ) -> Any:
        if rank == src:
            self.set(f"{prefix}/obj", pickle.dumps(obj))
            out = obj
        else:
            out = pickle.loads(
                self.get(
                    f"{prefix}/obj",
                    timeout,
                    poll_cap=scaled_poll_cap(world_size),
                )
            )
        self._cleanup(prefix, world_size, [f"{prefix}/obj"])
        return out

    def scatter(
        self,
        prefix: str,
        rank: int,
        world_size: int,
        objs: Optional[Sequence[Any]],
        src: int = 0,
        timeout: float = _DEFAULT_TIMEOUT_S,
    ) -> Any:
        if rank == src:
            assert objs is not None and len(objs) == world_size
            for i, o in enumerate(objs):
                self.set(f"{prefix}/{i}", pickle.dumps(o))
        out = pickle.loads(
            self.get(
                f"{prefix}/{rank}", timeout, poll_cap=scaled_poll_cap(world_size)
            )
        )
        self._cleanup(prefix, world_size, [f"{prefix}/{i}" for i in range(world_size)])
        return out

    def barrier(
        self,
        prefix: str,
        rank: int,
        world_size: int,
        timeout: float = _DEFAULT_TIMEOUT_S,
    ) -> None:
        if self.add(f"{prefix}/arrive", 1) == world_size:
            self.set(f"{prefix}/go", b"1")
        else:
            self.get(
                f"{prefix}/go", timeout, poll_cap=scaled_poll_cap(world_size)
            )
        if self.add(f"{prefix}/depart", 1) == world_size:
            for k in (f"{prefix}/arrive", f"{prefix}/go", f"{prefix}/depart"):
                self.delete(k)


# ---------------------------------------------------------------------------
# TCP store
# ---------------------------------------------------------------------------

_CMD_SET, _CMD_TRY_GET, _CMD_ADD, _CMD_DELETE = 0, 1, 2, 3
# Batched commands: one frame each way per BATCH. arg carries the
# key->value dict (multi_set) or key list (multi_get / multi_delete);
# the scalar ``key`` slot of the request tuple is unused ("").
_CMD_MULTI_SET, _CMD_MULTI_GET, _CMD_MULTI_DELETE = 4, 5, 6
# Prefix scan (key enumeration): the fleet metrics plane's reader
# (telemetry/wire.py collect_fleet) discovers `__obs/` publishers with
# it. ``key`` carries the prefix; arg is unused.
_CMD_SCAN = 7

_CMD_OP_NAMES = {
    _CMD_SET: "set",
    _CMD_TRY_GET: "try_get",
    _CMD_ADD: "add",
    _CMD_DELETE: "delete",
    _CMD_MULTI_SET: "multi_set",
    _CMD_MULTI_GET: "multi_get",
    _CMD_MULTI_DELETE: "multi_delete",
    _CMD_SCAN: "scan",
}


def _store_rpc_ids():
    """cmd int -> declared RPC op id (names.RPC_STORE_*), resolved
    lazily so the registry stays the single source of op-id strings."""
    _, n, _ = _tele_modules()
    return {
        _CMD_SET: n.RPC_STORE_SET,
        _CMD_TRY_GET: n.RPC_STORE_TRY_GET,
        _CMD_ADD: n.RPC_STORE_ADD,
        _CMD_DELETE: n.RPC_STORE_DELETE,
        _CMD_MULTI_SET: n.RPC_STORE_MULTI_SET,
        _CMD_MULTI_GET: n.RPC_STORE_MULTI_GET,
        _CMD_MULTI_DELETE: n.RPC_STORE_MULTI_DELETE,
        _CMD_SCAN: n.RPC_STORE_SCAN,
    }


# Chaos-engineering seam (chaos/engine.py install_wire_chaos): when
# set, every frame in BOTH directions passes through the hook — fail /
# delay / corrupt injection over the one framing the TCP store and the
# peer transport share. None in production; reads cost one global load.
_WIRE_CHAOS = None


def send_frame(
    sock: socket.socket, payload: bytes, endpoint: str = "store"
) -> None:
    """Length-prefixed frame write — the one wire framing shared by the
    TCP store and the peer-tier transport (tiered/peer.py), so the two
    socket protocols cannot drift in how they delimit messages.

    Wire observatory (telemetry/wire.py): when the sending thread has
    an active :func:`~torchsnapshot_tpu.telemetry.wire.propagate`
    context, the payload is prefixed with the compact trace header
    BEFORE the chaos hook sees it — chaos corrupts the header exactly
    like real wire damage would, and the receiver degrades it to a
    context-free frame. Frame/byte counts land per ``endpoint``."""
    try:
        w = _wire()
        ctx = w.current_context()
        if ctx is not None:
            payload = w.encode_frame(ctx, payload)
    except Exception:  # noqa: BLE001 - observability never breaks the wire
        pass
    hook = _WIRE_CHAOS
    if hook is not None:
        payload = hook("wire-send", payload)
        if payload is None:
            return  # dropped frame: the receiver waits it out
    try:
        _wire().observe_frame(endpoint, "send", len(payload) + 4)
    except Exception:  # noqa: BLE001 - observability never breaks the wire
        pass
    sock.sendall(struct.pack("<I", len(payload)) + payload)


def recv_frame(sock: socket.socket, endpoint: str = "store") -> bytes:
    header = _recv_exact(sock, 4)
    (length,) = struct.unpack("<I", header)
    payload = _recv_exact(sock, length)
    hook = _WIRE_CHAOS
    if hook is not None:
        payload = hook("wire-recv", payload)
    try:
        w = _wire()
        w.observe_frame(endpoint, "recv", len(payload) + 4)
        ctx, payload = w.decode_frame(payload)
        # Stash (or clear) the inbound context so the handler that
        # processes this frame can link its span to the sender's.
        w.set_received_context(ctx)
    except Exception:  # noqa: BLE001 - observability never breaks the wire
        pass
    return payload


# Internal aliases kept for the store's own call sites.
_send_msg = send_frame
_recv_msg = recv_frame


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    chunks = []
    while n:
        chunk = sock.recv(n)
        if not chunk:
            raise ConnectionError("store connection closed")
        chunks.append(chunk)
        n -= len(chunk)
    return b"".join(chunks)


class _StoreServer(socketserver.ThreadingTCPServer):
    daemon_threads = True
    allow_reuse_address = True
    # socketserver's default listen backlog is 5: a thousand-rank world
    # connecting at once overflows the SYN queue and rides kernel
    # connect retries for seconds. Size the backlog for the fleet.
    request_queue_size = 1024

    def __init__(self, addr) -> None:
        super().__init__(addr, _StoreRequestHandler)
        self.kv: Dict[str, bytes] = {}
        self.kv_lock = threading.Lock()
        # Concurrent-handler count: the wire observatory's userspace
        # proxy for accept pressure (the kernel accept queue itself is
        # not portably readable).
        self.active_handlers = 0
        self.active_lock = threading.Lock()


class _StoreRequestHandler(socketserver.BaseRequestHandler):
    def handle(self) -> None:
        server: _StoreServer = self.server  # type: ignore[assignment]
        with server.active_lock:
            server.active_handlers += 1
            depth = server.active_handlers
        try:
            _wire().observe_accept_depth("store", depth)
        except Exception:  # noqa: BLE001 - observability is best-effort
            pass
        try:
            while True:
                msg = pickle.loads(_recv_msg(self.request))
                cmd, key, arg = msg
                with server.kv_lock:
                    if cmd == _CMD_SET:
                        server.kv[key] = arg
                        reply = None
                    elif cmd == _CMD_TRY_GET:
                        reply = server.kv.get(key)
                    elif cmd == _CMD_ADD:
                        new = int(server.kv.get(key, b"0")) + arg
                        server.kv[key] = str(new).encode()
                        reply = new
                    elif cmd == _CMD_DELETE:
                        server.kv.pop(key, None)
                        reply = None
                    elif cmd == _CMD_MULTI_SET:
                        server.kv.update(arg)
                        reply = None
                    elif cmd == _CMD_MULTI_GET:
                        reply = {k: server.kv.get(k) for k in arg}
                    elif cmd == _CMD_MULTI_DELETE:
                        for k in arg:
                            server.kv.pop(k, None)
                        reply = None
                    elif cmd == _CMD_SCAN:
                        reply = sorted(
                            k for k in server.kv if k.startswith(key)
                        )
                    else:  # pragma: no cover
                        raise ValueError(f"bad store command {cmd}")
                _send_msg(self.request, pickle.dumps(reply))
        except (ConnectionError, EOFError):
            return
        finally:
            with server.active_lock:
                server.active_handlers -= 1


class TCPStore(Store):
    """Socket KV store; rank 0 hosts the server in a daemon thread
    (reference analog: ``get_or_create_store`` bootstrapping a c10d
    TCPStore, dist_store.py:22-88)."""

    def __init__(
        self,
        host: str,
        port: int,
        is_server: bool,
        connect_timeout: float = _CONNECT_TIMEOUT_S,
    ) -> None:
        self._server: Optional[_StoreServer] = None
        self._connect_timeout = connect_timeout
        if is_server:
            self._server = _StoreServer((host, port))
            self.port = self._server.server_address[1]
            self._server_thread = threading.Thread(
                target=self._server.serve_forever, daemon=True
            )
            self._server_thread.start()
        else:
            self.port = port
        self.host = host
        self._sock: Optional[socket.socket] = None
        self._sock_lock = threading.Lock()

    def _connect(self) -> socket.socket:
        if self._sock is None:
            deadline = time.monotonic() + self._connect_timeout
            while True:
                # Per-attempt timeout bounded by the remaining deadline:
                # without it, an unreachable host (firewall DROP, dead
                # VM) sits in the kernel's SYN-retry cycle for minutes
                # and the deadline below never gets a chance to fire.
                remaining = deadline - time.monotonic()
                try:
                    t_dial = time.monotonic()
                    sock = socket.create_connection(
                        (self.host, self.port),
                        timeout=max(0.05, min(5.0, remaining)),
                    )
                    try:
                        # Dial latency per successful attempt: a full
                        # listen backlog shows up here as whole-second
                        # SYN-retransmit quanta (wire-dial-stalled).
                        _wire().observe_dial(
                            "store", time.monotonic() - t_dial
                        )
                    except Exception:  # noqa: BLE001 - best-effort
                        pass
                    # Back to blocking mode: the per-attempt timeout
                    # must not leak into request/response recv calls.
                    sock.settimeout(None)
                    sock.setsockopt(
                        socket.IPPROTO_TCP, socket.TCP_NODELAY, 1
                    )
                    self._sock = sock
                    break
                except socket.gaierror:
                    # Name resolution failing is a misconfiguration
                    # (typo'd host), not a leader that hasn't bound
                    # yet: fail fast instead of burning the deadline.
                    raise
                except OSError as e:
                    try:
                        _wire().observe_dial("store", 0.0, ok=False)
                    except Exception:  # noqa: BLE001 - best-effort
                        pass
                    # Deadline-bounded with a clear timeout error: a
                    # leader that never comes up must read as "store
                    # unreachable", not as a raw ECONNREFUSED (or a
                    # minutes-late EHOSTUNREACH) from deep inside a
                    # collective.
                    if time.monotonic() > deadline:
                        raise StoreTimeoutError(
                            f"Timed out connecting to store at "
                            f"{self.host}:{self.port} after "
                            f"{self._connect_timeout:.1f}s (is the rank-0 "
                            f"store server up?)"
                        ) from e
                    time.sleep(0.05)
        return self._sock

    def _request(self, cmd: int, key: str, arg: Any = None) -> Any:
        t0 = time.monotonic()
        with self._sock_lock:
            sock = self._connect()
            _send_msg(sock, pickle.dumps((cmd, key, arg)))
            reply = pickle.loads(_recv_msg(sock))
        elapsed = time.monotonic() - t0
        _observe_store_requests(_CMD_OP_NAMES.get(cmd, "other"), elapsed)
        try:
            w = _wire()
            w.observe_rpc("store", _store_rpc_ids()[cmd], elapsed)
        except Exception:  # noqa: BLE001 - telemetry is best-effort
            pass
        return reply

    def set(self, key: str, value: bytes) -> None:
        self._request(_CMD_SET, key, value)

    def try_get(self, key: str) -> Optional[bytes]:
        return self._request(_CMD_TRY_GET, key)

    def add(self, key: str, amount: int) -> int:
        return self._request(_CMD_ADD, key, amount)

    def delete(self, key: str) -> None:
        self._request(_CMD_DELETE, key)

    def multi_set(self, items: Dict[str, bytes]) -> None:
        self._request(_CMD_MULTI_SET, "", dict(items))

    def multi_get(self, keys: Sequence[str]) -> Dict[str, Optional[bytes]]:
        return self._request(_CMD_MULTI_GET, "", list(keys))

    def multi_delete(self, keys: Iterable[str]) -> None:
        self._request(_CMD_MULTI_DELETE, "", list(keys))

    def scan(self, prefix: str) -> List[str]:
        return self._request(_CMD_SCAN, prefix)

    def close(self) -> None:
        with self._sock_lock:
            if self._sock is not None:
                self._sock.close()
                self._sock = None
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
            self._server = None


class InProcessStore(Store):
    """Thread-shared store for single-process/multi-thread tests."""

    def __init__(self) -> None:
        self._kv: Dict[str, bytes] = {}
        self._lock = threading.Lock()

    def set(self, key: str, value: bytes) -> None:
        with self._lock:
            self._kv[key] = value

    def try_get(self, key: str) -> Optional[bytes]:
        with self._lock:
            return self._kv.get(key)

    def add(self, key: str, amount: int) -> int:
        with self._lock:
            new = int(self._kv.get(key, b"0")) + amount
            self._kv[key] = str(new).encode()
            return new

    def delete(self, key: str) -> None:
        with self._lock:
            self._kv.pop(key, None)

    def multi_set(self, items: Dict[str, bytes]) -> None:
        with self._lock:
            self._kv.update(items)

    def multi_get(self, keys: Sequence[str]) -> Dict[str, Optional[bytes]]:
        with self._lock:
            return {k: self._kv.get(k) for k in keys}

    def multi_delete(self, keys: Iterable[str]) -> None:
        with self._lock:
            for k in keys:
                self._kv.pop(k, None)

    def scan(self, prefix: str) -> List[str]:
        with self._lock:
            return sorted(k for k in self._kv if k.startswith(prefix))


# ---------------------------------------------------------------------------
# Sharded store
# ---------------------------------------------------------------------------


def shard_for_key(key: str, num_shards: int) -> int:
    """Deterministic key->shard routing (crc32, like the fan-out owner
    table — ``hash()`` is process-randomized and MUST NOT be used here:
    every rank has to route a key to the same shard)."""
    return zlib.crc32(key.encode("utf-8", "surrogatepass")) % num_shards


class ShardedStore(Store):
    """N member stores behind deterministic key->shard hashing.

    A single TCPStore hub serializes world x keys traffic through one
    socket's accept/handler path; sharding spreads the key space over N
    independent servers so coordination throughput scales with N. Every
    primitive routes by :func:`shard_for_key`; per-key atomicity (``add``,
    the collectives' cleanup counters) holds because a key always lands
    on the same member. Batched ops are grouped per shard — one round
    trip per *touched shard*, not per key. Collectives/barriers from the
    base class work unchanged: they are built on the primitives.
    """

    def __init__(self, stores: Sequence[Store]) -> None:
        if not stores:
            raise ValueError("ShardedStore needs at least one member store")
        self._stores: List[Store] = list(stores)

    @property
    def num_shards(self) -> int:
        return len(self._stores)

    def _count_shard(self, shard: int, requests: int = 1) -> None:
        """Per-shard request accounting: the skew evidence behind the
        ``store-hot-shard`` doctor rule and the fleet snapshot's
        ``store_shards`` split."""
        try:
            telemetry, n, _ = _tele_modules()
            telemetry.metrics().counter_inc(
                n.COORD_STORE_SHARD_REQUESTS_TOTAL,
                float(requests),
                shard=str(shard),
            )
        except Exception:  # noqa: BLE001 - telemetry is best-effort
            pass

    def _member(self, key: str) -> Store:
        shard = shard_for_key(key, len(self._stores))
        self._count_shard(shard)
        return self._stores[shard]

    def _group(self, keys: Iterable[str]) -> Dict[int, List[str]]:
        grouped: Dict[int, List[str]] = {}
        for key in keys:
            grouped.setdefault(
                shard_for_key(key, len(self._stores)), []
            ).append(key)
        for shard in grouped:
            self._count_shard(shard)
        return grouped

    def set(self, key: str, value: bytes) -> None:
        self._member(key).set(key, value)

    def try_get(self, key: str) -> Optional[bytes]:
        return self._member(key).try_get(key)

    def add(self, key: str, amount: int) -> int:
        return self._member(key).add(key, amount)

    def delete(self, key: str) -> None:
        self._member(key).delete(key)

    def multi_set(self, items: Dict[str, bytes]) -> None:
        for shard, keys in self._group(items).items():
            self._stores[shard].multi_set({k: items[k] for k in keys})

    def multi_get(self, keys: Sequence[str]) -> Dict[str, Optional[bytes]]:
        out: Dict[str, Optional[bytes]] = {}
        for shard, shard_keys in self._group(keys).items():
            out.update(self._stores[shard].multi_get(shard_keys))
        return out

    def multi_delete(self, keys: Iterable[str]) -> None:
        for shard, shard_keys in self._group(keys).items():
            self._stores[shard].multi_delete(shard_keys)

    def scan(self, prefix: str) -> List[str]:
        out: List[str] = []
        for member in self._stores:
            out.extend(member.scan(prefix))
        return sorted(set(out))

    def close(self) -> None:
        for member in self._stores:
            close = getattr(member, "close", None)
            if close is not None:
                try:
                    close()
                except Exception:  # noqa: BLE001 - best-effort teardown
                    pass


_SHARD_STORE_PREFIX = "__ts/shard_store"


def bootstrap_sharded_store(
    base: Store,
    rank: int,
    world_size: int,
    num_shards: Optional[int] = None,
    timeout: float = _DEFAULT_TIMEOUT_S,
) -> Store:
    """Stand up a :class:`ShardedStore` of TCPStore members over an
    existing coordination store (which only needs ``set``/``get``).

    Rank 0's knob reading decides the shard count for the whole job —
    published through ``base`` (the same agreement-by-broadcast
    discipline as the fan-out nonce): env skew across ranks can never
    split the key space two ways. Shard ``i`` is hosted by rank
    ``i % world_size``, so on a multi-host pod the server sockets spread
    across hosts instead of stacking on the leader. ``num_shards <= 1``
    returns ``base`` unchanged (the packaged default)."""
    if rank == 0:
        if num_shards is None:
            num_shards = knobs.get_store_shards()
        num_shards = max(1, min(int(num_shards), world_size * 8))
        base.set(f"{_SHARD_STORE_PREFIX}/n", str(num_shards).encode())
    else:
        num_shards = int(base.get(f"{_SHARD_STORE_PREFIX}/n", timeout))
    if num_shards <= 1:
        return base
    members: List[Optional[Store]] = [None] * num_shards
    for i in range(num_shards):
        if i % world_size != rank:
            continue
        host = _local_advertise_host()
        tcp = TCPStore(host="0.0.0.0", port=0, is_server=True)
        tcp.host = host
        base.set(f"{_SHARD_STORE_PREFIX}/{i}", f"{host}:{tcp.port}".encode())
        members[i] = tcp
    for i in range(num_shards):
        if members[i] is not None:
            continue
        addr = base.get(f"{_SHARD_STORE_PREFIX}/{i}", timeout).decode()
        host, port = addr.rsplit(":", 1)
        members[i] = TCPStore(host=host, port=int(port), is_server=False)
    return ShardedStore([m for m in members if m is not None])


class JaxCoordinationStore(Store):
    """KV store over the JAX distributed coordination service.

    Usable once ``jax.distributed.initialize`` has run; rides DCN like the
    rest of JAX's control plane.
    """

    def __init__(self) -> None:
        import uuid

        from jax._src import distributed

        client = distributed.global_state.client
        if client is None:
            raise RuntimeError(
                "jax.distributed is not initialized; "
                "JaxCoordinationStore requires a coordinator"
            )
        self._client = client
        # Self-check the absent-key classification NOW: try_get maps the
        # coordination service's NOT_FOUND status to None by matching the
        # status token in the raised exception. A jaxlib that words the
        # absent-key status differently would otherwise turn EVERY
        # absent-key poll into a raise — after the _TransientReads grace,
        # all barriers and preemption polls on real pods would fail, a
        # silent total-breakage mode whose cause (message wording) sits
        # far from its symptom. Probing a key that provably was never set
        # makes the mismatch loud at construction instead.
        probe = f"__ts_absent_probe/{uuid.uuid4().hex}"
        try:
            val = self.try_get(probe)
        except Exception as e:
            raise RuntimeError(
                "JaxCoordinationStore: absent-key probe failed — either "
                "this jaxlib reports an absent key in a way try_get does "
                "not classify as NOT_FOUND, or the coordination service "
                "is unreachable. Use TCPStore coordination instead "
                f"(probe raised {e!r})."
            ) from e
        if val is not None:
            raise RuntimeError(
                "JaxCoordinationStore: absent-key probe returned a value "
                f"({val!r}) for a key that was never set; refusing to use "
                "a store with broken get semantics"
            )

    def set(self, key: str, value: bytes) -> None:
        self._client.key_value_set_bytes(key, value)

    def try_get(self, key: str) -> Optional[bytes]:
        try:
            return bytes(self._client.key_value_try_get_bytes(key))
        except Exception as e:
            # Only "key absent" maps to None (the coordination service
            # reports it as a NOT_FOUND status; match the status token or
            # a NotFound exception type so a jaxlib that re-words the
            # message still classifies correctly). A transport/service
            # failure must raise: callers read None as "peer did not
            # signal", and conflating the two turns an unhealthy
            # coordinator into a false all-clear exactly where the signal
            # matters (e.g. the preemption grace check before a lone save).
            msg = str(e).lower()
            if (
                "not_found" in msg
                or "not found" in msg
                or "notfound" in type(e).__name__.lower()
            ):
                return None
            raise

    def add(self, key: str, amount: int) -> int:
        return int(self._client.key_value_increment(key, amount))

    def delete(self, key: str) -> None:
        try:
            self._client.key_value_delete(key)
        except Exception:
            pass


def jax_process_group():
    """The process group for a ``jax.distributed``-initialized job: rank
    and world from the JAX runtime, coordination over its KV service —
    no address side-channel to plumb. This is how multi-host TPU pods
    hand ``pg=`` to ``Snapshot.take``/``CheckpointManager``::

        jax.distributed.initialize()
        pg = jax_process_group()
        ts.Snapshot.take(path, app_state, pg=pg)

    (Reference analog: get_or_create_store reusing the c10d default
    TCPStore, dist_store.py:22-88.)

    The result is cached per process: repeated calls return the SAME
    ProcessGroup (hence the same store object), which keeps the
    ``__pg/*`` op-seq namespace shared across call sites.
    """
    global _JAX_PG
    with _JAX_PG_LOCK:
        if _JAX_PG is not None:
            return _JAX_PG
        import jax

        rank = jax.process_index()
        world = jax.process_count()
        store: Store = JaxCoordinationStore()
        # Store sharding (docs/scaling.md): rank 0's knob decides the
        # shard count for the whole job; the members bootstrap through
        # the KV service. Default 1 = no-op.
        if world > 1:
            store = bootstrap_sharded_store(store, rank, world)
        _JAX_PG = ProcessGroup(
            store=store,
            rank=rank,
            world_size=world,
        )
        return _JAX_PG


_JAX_PG: Optional[ProcessGroup] = None
_JAX_PG_LOCK = threading.Lock()


def _local_advertise_host() -> str:
    """An address peers on other hosts can dial for THIS machine —
    correct on any rank, so right for a per-rank server (shard store
    member, peer-tier cache): outbound-interface IP first (the UDP
    connect sends no traffic), hostname last."""
    try:
        probe = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        try:
            probe.connect(("8.8.8.8", 80))
            return probe.getsockname()[0]
        finally:
            probe.close()
    except Exception:
        return socket.gethostname()


# ---------------------------------------------------------------------------
# Endpoint registry (peer-tier transport bootstrap)
# ---------------------------------------------------------------------------

_ENDPOINT_PREFIX = "__endpoint"


def publish_endpoint(
    store: Store, service: str, rank: int, host: str, port: int
) -> None:
    """Advertise a per-rank network endpoint through the coordination
    store. Unlike collective keys, endpoint keys are a *registry*: they
    are overwritten on re-publish (a replacement rank re-announces
    itself after a preemption under the same rank id) and never
    cleaned up by a counter — a surviving peer must stay discoverable
    for the whole run. Nonce-free by design: the rank id IS the
    identity the ring placement keys on."""
    store.set(f"{_ENDPOINT_PREFIX}/{service}/{rank}", f"{host}:{port}".encode())


def lookup_endpoint(
    store: Store, service: str, rank: int
) -> Optional[Tuple[str, int]]:
    """The advertised ``(host, port)`` for ``rank``, or None when the
    rank never published (or the store read failed — an unreachable
    registry must read as "no endpoint", never raise into a restore
    that can correctly proceed without peers)."""
    try:
        raw = store.try_get(f"{_ENDPOINT_PREFIX}/{service}/{rank}")
    except Exception:
        return None
    if raw is None:
        return None
    return _parse_endpoint(raw)


def _parse_endpoint(raw: bytes) -> Optional[Tuple[str, int]]:
    try:
        host, port = raw.decode().rsplit(":", 1)
        return host, int(port)
    except (ValueError, UnicodeDecodeError):
        return None


def lookup_endpoints(
    store: Store, service: str, ranks: Iterable[int]
) -> Dict[int, Tuple[str, int]]:
    """Batched registry resolve: every advertised ``(host, port)`` for
    ``ranks``, in ONE ``multi_get`` round trip — restore setup resolving
    a thousand surviving peers costs one store request, not a thousand
    sequential lookups. Ranks that never published (or whose entries are
    garbage) are simply absent from the result; a failed store read
    returns ``{}`` (same "no endpoint, never raise" contract as
    :func:`lookup_endpoint`). The resolve wall time feeds the
    ``coordination_endpoint_seconds_total`` counter."""
    rank_list = list(ranks)
    keys = [f"{_ENDPOINT_PREFIX}/{service}/{r}" for r in rank_list]
    t0 = time.monotonic()
    try:
        got = store.multi_get(keys)
    except Exception:
        return {}
    finally:
        try:
            telemetry, n, _ = _tele_modules()
            telemetry.metrics().counter_inc(
                n.COORD_ENDPOINT_SECONDS_TOTAL,
                time.monotonic() - t0,
                service=service,
            )
        except Exception:  # noqa: BLE001 - telemetry is best-effort
            pass
    out: Dict[int, Tuple[str, int]] = {}
    for rank, key in zip(rank_list, keys):
        raw = got.get(key)
        if raw is None:
            continue
        parsed = _parse_endpoint(raw)
        if parsed is not None:
            out[rank] = parsed
    return out


# ---------------------------------------------------------------------------
# Barriers
# ---------------------------------------------------------------------------


class StoreBarrier:
    """Shared two-phase (arrive/depart) barrier machinery with error
    propagation. Subclasses implement ``_phase`` (the rendezvous
    topology) and ``_cleanup`` (post-depart key removal); the contract —
    usable off the main thread, ``report_error`` poisons every peer's
    pending/future wait with :class:`BarrierError`, ``depart`` before
    ``arrive`` raises — is identical across topologies, so call sites
    (snapshot.py's ``_nonce_barrier``, fanout rounds) swap
    transparently via :func:`make_barrier`. Every phase is traced
    (``barrier:arrive``/``barrier:depart`` spans) and its wall time
    feeds ``coordination_barrier_wait_seconds_total`` — the evidence the
    ``coordination-bound`` doctor rule cites.
    """

    _IMPL = "base"

    def __init__(
        self, prefix: str, store: Store, rank: int, world_size: int
    ) -> None:
        self.prefix = prefix
        self.store = store
        self.rank = rank
        self.world_size = world_size
        self._arrived = False

    def _key(self, name: str) -> str:
        return f"{self.prefix}/{name}"

    def _check_error(self, reads: Optional[_TransientReads] = None) -> None:
        # One-shot call sites (no shared tracker) still get single-hiccup
        # tolerance from a fresh tracker: the first failed read returns
        # None ("no error seen"), matching the pre-strict-try_get
        # semantics; only a shared tracker accumulating failures past the
        # grace re-raises.
        if reads is None:
            reads = _TransientReads()
        err = reads.read(lambda: self.store.try_get(self._key("error")))
        if err is not None:
            exc = pickle.loads(err)
            raise BarrierError(
                f"Rank {self.rank}: a peer reported an error into barrier "
                f"{self.prefix!r}"
            ) from exc

    def _wait_for(self, key: str, timeout: float) -> None:
        """Deadline-aware wait with exponential poll backoff (see
        ``_PollPacer``): a 1000-rank barrier parked here must idle at
        ~10 QPS per rank, not 200/s."""
        deadline = time.monotonic() + timeout
        reads = _TransientReads()
        pacer = _PollPacer(cap=scaled_poll_cap(self.world_size))
        while True:
            got = reads.read(
                lambda: self.store.multi_get([self._key("error"), key])
            )
            if got is not None:
                err = got.get(self._key("error"))
                if err is not None:
                    self._raise_peer_error(err)
                if got.get(key) is not None:
                    return
            if time.monotonic() > deadline:
                raise StoreTimeoutError(
                    f"Rank {self.rank} timed out in barrier {self.prefix!r} "
                    f"waiting for {key!r}"
                )
            pacer.sleep(deadline)

    def _raise_peer_error(self, payload: bytes) -> None:
        exc = pickle.loads(payload)
        raise BarrierError(
            f"Rank {self.rank}: a peer reported an error into barrier "
            f"{self.prefix!r}"
        ) from exc

    def _wait_count(self, key: str, target: int, timeout: float) -> None:
        """Poll ONE counter key until it reaches ``target``: the waiter's
        cost is O(1) store requests per poll regardless of world size
        (a per-rank-key scan would be world−1 sequential requests per
        tick — minutes of pure polling on a large pod). Error key and
        counter ride one batched round trip."""
        if target <= 0:
            self._check_error()
            return
        deadline = time.monotonic() + timeout
        reads = _TransientReads()
        pacer = _PollPacer(cap=scaled_poll_cap(self.world_size))
        while True:
            got = reads.read(
                lambda: self.store.multi_get([self._key("error"), key])
            )
            if got is not None:
                err = got.get(self._key("error"))
                if err is not None:
                    self._raise_peer_error(err)
                val = got.get(key)
                if val is not None and int(val) >= target:
                    return
            if time.monotonic() > deadline:
                raise StoreTimeoutError(
                    f"Rank {self.rank} timed out in barrier {self.prefix!r} "
                    f"waiting for {key!r} to reach {target}"
                )
            pacer.sleep(deadline)

    def _phase(self, phase: str, timeout: float) -> None:
        raise NotImplementedError

    def _cleanup(self, timeout: float) -> None:
        raise NotImplementedError

    def _observed_phase(self, phase: str, timeout: float) -> None:
        t0 = time.monotonic()
        token = None
        tele = n = trace = None
        try:
            tele, n, trace = _tele_modules()
            token = trace.get_recorder().begin(
                n.SPAN_BARRIER_ARRIVE
                if phase == "arrive"
                else n.SPAN_BARRIER_DEPART,
                prefix=self.prefix,
                rank=self.rank,
                world=self.world_size,
                impl=self._IMPL,
            )
        except Exception:  # noqa: BLE001 - telemetry is best-effort
            token = None
        try:
            self._phase(phase, timeout)
        finally:
            try:
                if token is not None:
                    trace.get_recorder().end(token)
                if tele is not None:
                    tele.metrics().counter_inc(
                        n.COORD_BARRIER_WAIT_SECONDS_TOTAL,
                        time.monotonic() - t0,
                        phase=phase,
                        impl=self._IMPL,
                    )
            except Exception:  # noqa: BLE001 - telemetry is best-effort
                pass

    def arrive(self, timeout: float = _DEFAULT_TIMEOUT_S) -> None:
        self._observed_phase("arrive", timeout)
        self._arrived = True

    def depart(self, timeout: float = _DEFAULT_TIMEOUT_S) -> None:
        if not self._arrived:
            raise RuntimeError("depart() called before arrive()")
        self._observed_phase("depart", timeout)
        self._cleanup(timeout)

    def report_error(self, exc: BaseException) -> None:
        try:
            payload = pickle.dumps(exc)
        except Exception:
            payload = pickle.dumps(RuntimeError(repr(exc)))
        self.store.set(self._key("error"), payload)


class LinearBarrier(StoreBarrier):
    """Two-phase leader-centric barrier with error propagation.

    Reference parity: dist_store.py:91-196. Phase one (``arrive``):
    followers deposit into one counter, the leader observes all deposits
    then releases one ``go`` key. Phase two (``depart``): mirrored. Kept
    behind the ``TORCHSNAPSHOT_TPU_TREE_BARRIER=0`` kill switch (see
    :func:`make_barrier`): per-rank round trips are O(1), but every rank
    rendezvouses on the leader's two keys, so at large world sizes the
    hub store serializes world waiters per phase — the wall the
    scale-model bench convicts (docs/scaling.md).
    """

    _IMPL = "linear"

    def _phase(self, phase: str, timeout: float) -> None:
        if self.rank == 0:
            self._wait_count(
                self._key(f"{phase}/count"), self.world_size - 1, timeout
            )
            self.store.set(self._key(f"{phase}/go"), b"1")
        else:
            self._check_error()
            self.store.add(self._key(f"{phase}/count"), 1)
            self._wait_for(self._key(f"{phase}/go"), timeout)

    def _cleanup(self, timeout: float) -> None:
        """Best-effort removal of this barrier's keys after a successful
        depart so a long-lived store doesn't accumulate them. Followers ack
        that they are past the depart release before the leader deletes."""
        try:
            if self.rank != 0:
                self.store.add(self._key("done/count"), 1)
                return
            self._wait_count(
                self._key("done/count"), self.world_size - 1, timeout
            )
            self.store.multi_delete(
                [
                    self._key(f"{phase}/{part}")
                    for phase in ("arrive", "depart", "done")
                    for part in ("count", "go")
                ]
                + [self._key("error")]
            )
        except Exception:  # pragma: no cover - cleanup must never fail a commit
            pass


class TreeBarrier(StoreBarrier):
    """Tree-structured two-phase barrier: O(log_k world) critical path,
    no key with more than ``fanout`` writers or readers.

    Ranks form an implicit ``fanout``-ary tree (children of ``r`` are
    ``r*k+1 .. r*k+k``). Per phase, a rank (1) waits for its own counter
    to reach its child count — each child increments it only after its
    whole subtree arrived — (2) increments its parent's counter, (3)
    waits for its release key, then (4) releases its children with one
    batched ``multi_set``. The aggregate store load stays O(world) per
    phase (it must — every rank signals once), but it spreads over
    world/k distinct keys (shardable via :class:`ShardedStore`) instead
    of rendezvousing on the leader's one counter, and the release wave
    is a k-way broadcast tree instead of world ranks polling one key.

    Same contract as :class:`LinearBarrier` (``report_error`` poisons
    every pending wait via the shared ``{prefix}/error`` key, which is
    also the error channel fan-out rounds poll).
    """

    _IMPL = "tree"

    def __init__(
        self,
        prefix: str,
        store: Store,
        rank: int,
        world_size: int,
        fanout: Optional[int] = None,
    ) -> None:
        super().__init__(prefix, store, rank, world_size)
        if fanout is None:
            fanout = knobs.get_barrier_fanout()
        self.fanout = max(2, int(fanout))

    def _children(self) -> List[int]:
        base = self.rank * self.fanout
        return [
            child
            for child in range(base + 1, base + self.fanout + 1)
            if child < self.world_size
        ]

    def _phase(self, phase: str, timeout: float) -> None:
        children = self._children()
        if children:
            self._wait_count(
                self._key(f"{phase}/c/{self.rank}"), len(children), timeout
            )
        if self.rank != 0:
            self._check_error()
            parent = (self.rank - 1) // self.fanout
            self.store.add(self._key(f"{phase}/c/{parent}"), 1)
            self._wait_for(self._key(f"{phase}/go/{self.rank}"), timeout)
        if children:
            self.store.multi_set(
                {self._key(f"{phase}/go/{child}"): b"1" for child in children}
            )

    def _cleanup(self, timeout: float) -> None:
        """Each rank deletes ITS OWN keys — no done-counter rendezvous
        needed: a rank's counter was last written before it observed the
        target (children increment before waiting for release), and its
        release key was last written before it returned from the wait,
        so after this rank's depart nobody touches them again."""
        try:
            keys = [
                self._key(f"{phase}/{part}/{self.rank}")
                for phase in ("arrive", "depart")
                for part in ("c", "go")
            ]
            if self.rank == 0:
                keys.append(self._key("error"))
            self.store.multi_delete(keys)
        except Exception:  # pragma: no cover - cleanup must never fail a commit
            pass


def make_barrier(
    prefix: str, store: Store, rank: int, world_size: int
) -> StoreBarrier:
    """The blessed barrier constructor for every coordination phase:
    :class:`TreeBarrier` (default; fanout from
    ``TORCHSNAPSHOT_TPU_BARRIER_FANOUT``) unless the
    ``TORCHSNAPSHOT_TPU_TREE_BARRIER=0`` kill switch selects the
    leader-centric :class:`LinearBarrier`. Rank-uniform inputs only —
    both knobs are tunables the autotuner moves through the broadcast
    vector, so geometries can't mix mid-run."""
    if knobs.is_tree_barrier_enabled():
        return TreeBarrier(prefix, store, rank, world_size)
    return LinearBarrier(prefix, store, rank, world_size)

"""Distributed test harness, shipped as part of the package.

Reference parity: torchsnapshot/test_utils.py (290 LoC). The load-bearing
trick there is ``@run_with_pet(nproc=N)`` relaunching a test under
torchelastic with a gloo rendezvous so N-rank semantics run on one CPU box
(test_utils.py:205-238). The TPU-native equivalent fans out plain
``multiprocessing`` spawn workers that rendezvous on a :class:`TCPStore`
hosted by rank 0 — no cluster, no torch. Workers run on the CPU backend (the
coordination layer never touches devices; array content tests pair this with
the 8-device virtual mesh).

Also exports the equality/rand helpers the reference ships
(assert_state_dict_eq / rand_tensor analogs, test_utils.py:72-144).
"""

from __future__ import annotations

import asyncio
import multiprocessing as mp
import os
import pickle
import socket
import threading
import traceback
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from .dist_store import ProcessGroup, Store, TCPStore  # noqa: F401 - re-export


def get_free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def faulty_fs_plugin(
    should_fail: Callable[[str], bool],
    ops: Sequence[str] = ("write",),
    exc_msg: str = "injected storage failure",
    delay_s: float = 0.0,
    mode: str = "fail",
    seed: int = 0,
):
    """An ``FSStoragePlugin`` subclass whose listed ``ops`` ("write",
    "read", "delete" — "write"/"read" covering their fused
    ``*_with_checksum`` variants too, which the chaos wrapper declines
    so every op funnels through the injected path) misbehave when
    ``should_fail(io.path)`` is truthy.

    Since the chaos engine landed this is a thin shim over ONE fault
    plan (chaos/plan.py): each listed op becomes a predicate-triggered
    :class:`~torchsnapshot_tpu.chaos.FaultSpec`, so the crash tests and
    the declarative fault plans replay through the same mechanism.
    ``mode`` extends the legacy raise-only behavior:

    - ``"fail"`` (default): raise ``OSError(exc_msg)``, after
      ``delay_s`` if set — byte-compatible with the legacy shim.
    - ``"corrupt"``: size-preserving bit damage (written bytes or read
      buffer) — only digest verification catches it.
    - ``"delay"``: sleep ``delay_s``, then proceed normally.
    - plus any other chaos mode (``"torn"``, ``"drop"``, ``"crash"``).

    ``should_fail`` may filter by path (data blobs only) or close over
    a counter (fault at the N-th storage op). Pair with
    :func:`patch_storage_plugin`. Returns the subclass; its
    ``chaos_engine`` attribute exposes the backing engine (the
    ``fired`` log pins replay determinism)."""
    from .chaos import ChaosEngine, FaultPlan, FaultSpec, chaotic_plugin_type
    from .storage_plugins.fs import FSStoragePlugin

    point_of = {
        "write": "storage-write",
        "read": "storage-read",
        "delete": "storage-delete",
    }
    # "fail" keeps the legacy shape: an optional sleep and then the
    # raise. Chaos-wise that is mode="delay"+raise, which plain "fail"
    # specs don't model — so a failing spec with delay keeps delay_s
    # and the engine path sleeps before raising via the "fail" arm
    # below (asyncio.sleep lives in the injectors).
    plan = FaultPlan(
        seed=seed,
        faults=[
            FaultSpec(
                point=point_of[op],
                mode=mode,
                times=None,
                predicate=should_fail,
                exc_msg=exc_msg,
                delay_s=delay_s,
            )
            for op in ops
        ],
    )
    engine = ChaosEngine(plan)
    cls = chaotic_plugin_type(FSStoragePlugin, engine)
    cls.chaos_engine = engine
    return cls


def patch_storage_plugin(cls):
    """Route ``Snapshot``'s plugin resolution to ``cls`` for the scope of
    the returned context manager."""
    from unittest import mock

    return mock.patch(
        "torchsnapshot_tpu.snapshot.url_to_storage_plugin",
        side_effect=lambda url: cls(root=url.split("://")[-1]),
    )


class MarkerWrites:
    """The fs plugin's commit-marker writes, held open on an event or made
    to fail for chosen snapshots, under a ``pytest.MonkeyPatch``: an async
    take's commit thread stands where a slow or a broken storage would
    leave it, with every blob durable and the snapshot uncommitted.
    ``where`` is a piece of the snapshot's path (a manager's step
    directory name)."""

    TIMEOUT_S = 60.0

    def __init__(self, patch: Any) -> None:
        from .snapshot import SNAPSHOT_METADATA_FNAME
        from .storage_plugins.fs import FSStoragePlugin

        self.held: Dict[str, threading.Event] = {}
        self.broken: List[str] = []
        write = FSStoragePlugin.write

        async def gated(plugin, write_io):
            if write_io.path.endswith(SNAPSHOT_METADATA_FNAME):
                for where, gate in list(self.held.items()):
                    if where in plugin.root:
                        assert await asyncio.get_running_loop().run_in_executor(
                            None, gate.wait, self.TIMEOUT_S
                        )
                for where in self.broken:
                    if where in plugin.root:
                        raise OSError(f"planted: marker of {where}")
            return await write(plugin, write_io)

        patch.setattr(FSStoragePlugin, "write", gated)

    def hold(self, where: str) -> threading.Event:
        """Hold the marker of snapshots under ``where`` until the
        returned event is set."""
        self.held[where] = threading.Event()
        return self.held[where]


class ByteCountingStore(Store):
    """Delegating store wrapper that meters this rank's coordination
    traffic: payload bytes sent (``set`` values) and received (``try_get``
    results). Used by the manifest-gather scale test and the
    protocol-traffic benchmark to prove non-leader ranks pay O(own
    manifest), not O(world x manifest)."""

    def __init__(self, inner: Store) -> None:
        self.inner = inner
        self.sent_bytes = 0
        self.received_bytes = 0

    def set(self, key: str, value: bytes) -> None:
        self.sent_bytes += len(value)
        self.inner.set(key, value)

    def try_get(self, key: str):
        out = self.inner.try_get(key)
        if out is not None:
            self.received_bytes += len(out)
        return out

    def add(self, key: str, amount: int) -> int:
        return self.inner.add(key, amount)

    def delete(self, key: str) -> None:
        self.inner.delete(key)


def _worker_main(
    conn,
    fn_module: str,
    fn_qualname: str,
    fn_file: Optional[str],
    rank: int,
    world_size: int,
    port: int,
    args: bytes,
) -> None:
    try:
        # Workers must not grab the chip (it belongs to one process at a
        # time, and the parent may hold it): pin them to the CPU backend,
        # in the env for their own children and in jax.config in case the
        # module under test imported jax before this ran.
        os.environ["JAX_PLATFORMS"] = "cpu"
        import importlib
        import sys

        import jax

        jax.config.update("jax_platforms", "cpu")

        if fn_file is not None:
            sys.path.insert(0, os.path.dirname(os.path.abspath(fn_file)))
        module = importlib.import_module(fn_module)
        fn = module
        for part in fn_qualname.split("."):
            fn = getattr(fn, part)
        fn = getattr(fn, "_ts_inner_fn", fn)

        store = TCPStore("127.0.0.1", port, is_server=(rank == 0))
        pg = ProcessGroup(store=store, rank=rank, world_size=world_size)
        extra_args, extra_kwargs = pickle.loads(args)
        result = fn(pg, *extra_args, **extra_kwargs)
        conn.send(("ok", pickle.dumps(result)))
        # Rank 0 hosts the store server: no worker may exit until every
        # worker reported, or stragglers' store ops hit a dead socket. The
        # parent acks once all results are in.
        conn.recv()
    except BaseException as e:  # noqa: BLE001 - reported to the parent
        conn.send(("error", f"rank {rank}: {e!r}\n{traceback.format_exc()}"))
    finally:
        conn.close()


def run_multiprocess(
    fn: Callable[..., Any],
    nproc: int,
    args: Sequence[Any] = (),
    kwargs: Optional[Dict[str, Any]] = None,
    timeout: float = 180.0,
    port: Optional[int] = None,
) -> List[Any]:
    """Run ``fn(pg, *args, **kwargs)`` in ``nproc`` spawned processes with a
    shared TCP store; returns per-rank results, raises on any rank failure.

    ``fn`` must be a module-level callable (spawned workers re-import it by
    qualified name, the same constraint as the reference's launch pad,
    test_utils.py:221-224). Callers juggling additional listeners should
    pass an explicit ``port`` allocated alongside theirs (two sequential
    get_free_port calls can return the same just-released port).
    """
    if port is None:
        port = get_free_port()
    ctx = mp.get_context("spawn")
    payload = pickle.dumps((tuple(args), kwargs or {}))
    import importlib

    fn_file = getattr(
        importlib.import_module(fn.__module__), "__file__", None
    )
    procs = []
    conns = []
    for rank in range(nproc):
        parent_conn, child_conn = ctx.Pipe()
        p = ctx.Process(
            target=_worker_main,
            args=(
                child_conn,
                fn.__module__,
                fn.__qualname__,
                fn_file,
                rank,
                nproc,
                port,
                payload,
            ),
            daemon=True,
        )
        p.start()
        procs.append(p)
        conns.append(parent_conn)

    results: List[Any] = [None] * nproc
    errors: List[str] = []
    for rank, conn in enumerate(conns):
        if conn.poll(timeout):
            status, payload_out = conn.recv()
            if status == "ok":
                results[rank] = pickle.loads(payload_out)
            else:
                errors.append(payload_out)
        else:
            errors.append(f"rank {rank}: timed out after {timeout}s")
    # Release the workers only after every rank reported (the rank-0 worker
    # hosts the store server for the others).
    for conn in conns:
        try:
            conn.send("exit")
        except (BrokenPipeError, OSError):
            pass
    for p in procs:
        p.join(timeout=10)
        if p.is_alive():
            p.terminate()
    if errors:
        raise AssertionError(
            "Multiprocess run failed:\n" + "\n".join(errors)
        )
    return results


def multiprocess_test(nproc: int):
    """Decorator: ``@multiprocess_test(nproc=2)`` turns
    ``def test_x(pg): ...`` into a fan-out test (reference ``run_with_pet``,
    test_utils.py:227-265)."""

    def deco(fn: Callable[..., Any]) -> Callable[..., Any]:
        # No functools.wraps: pytest would follow __wrapped__ and treat the
        # inner function's ``pg`` parameter as a fixture. The inner function
        # is re-imported by workers via the _ts_inner_fn attribute instead.
        def wrapper() -> None:
            # Per-rank return values are discarded: pytest warns on tests
            # returning non-None. Use run_multiprocess directly when the
            # rank results matter.
            run_multiprocess(wrapper, nproc=nproc)

        wrapper.__name__ = fn.__name__
        wrapper.__qualname__ = fn.__qualname__
        wrapper.__module__ = fn.__module__
        wrapper.__doc__ = fn.__doc__
        wrapper._ts_inner_fn = fn
        return wrapper

    return deco


def drive_preemption_loop(
    pg,
    saver,
    save_fn: Callable[[int], None],
    evict_rank: int,
    evict_step: int = 2,
    steps: int = 200,
    pace_s: float = 0.02,
) -> Optional[int]:
    """Shared preemption-agreement exercise: run a paced step loop, inject
    an eviction notice on one rank, save via ``save_fn(step)`` at the
    agreed step; returns it (None if no agreement fired). The pacing is
    load-bearing — real steps take wall time on every rank; without it an
    unflagged rank exhausts its loop before the flag even lands."""
    import time

    saved_at: Optional[int] = None
    for step in range(steps):
        time.sleep(pace_s)
        if pg.rank == evict_rank and step == evict_step:
            saver.request_save()
        if saver.should_save(step):
            save_fn(step)
            saved_at = step
            break
    saver.close()
    return saved_at


# ---------------------------------------------------------------------------
# Equality / random-data helpers
# ---------------------------------------------------------------------------


def _to_comparable(x: Any) -> Any:
    if hasattr(x, "__array__"):
        return np.asarray(x)
    return x


def tree_eq(a: Any, b: Any) -> bool:
    """Deep equality over nested dict/list structures with array leaves
    (reference check_state_dict_eq, test_utils.py:95-101)."""
    if isinstance(a, dict) and isinstance(b, dict):
        return set(a) == set(b) and all(tree_eq(a[k], b[k]) for k in a)
    if isinstance(a, (list, tuple)) and isinstance(b, (list, tuple)):
        return len(a) == len(b) and all(tree_eq(x, y) for x, y in zip(a, b))
    ca, cb = _to_comparable(a), _to_comparable(b)
    if isinstance(ca, np.ndarray) or isinstance(cb, np.ndarray):
        ca, cb = np.asarray(ca), np.asarray(cb)
        return (
            ca.shape == cb.shape
            and ca.dtype == cb.dtype
            and bool(np.array_equal(ca, cb))
        )
    return bool(ca == cb)


def assert_tree_eq(a: Any, b: Any) -> None:
    if not tree_eq(a, b):
        raise AssertionError(f"Trees differ:\n{a!r}\n---\n{b!r}")


def rand_array(shape: Sequence[int], dtype: Any = "float32", seed: int = 0):
    """Random array covering the full supported dtype table (reference
    rand_tensor, test_utils.py:104-144)."""
    rng = np.random.default_rng(seed)
    dt = np.dtype(dtype)
    if dt.kind == "b":
        return rng.integers(0, 2, shape).astype(bool)
    if dt.kind in "iu" or dt.name in ("int4", "uint4"):
        return rng.integers(0, 8, shape).astype(dt)
    if dt.kind == "c":
        return (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)).astype(dt)
    return rng.standard_normal(shape).astype(dt)

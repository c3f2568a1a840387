"""JaxCoordinationStore + jax_process_group over a real (single-process)
jax.distributed runtime.

Reference analog: tests/test_dist_store.py's TCPStore coverage — here the
store rides the JAX coordination service instead, the path multi-host TPU
pods use (SURVEY.md §2.11 TPU-equivalent). jax.distributed.initialize is
process-global and irreversible, so the exercise runs in a spawned worker
(the harness pins workers to the CPU backend).
"""

from torchsnapshot_tpu.test_utils import run_multiprocess


def _jax_coordination_worker(pg, port: int):
    import jax

    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}", num_processes=1, process_id=0
    )
    import numpy as np

    import torchsnapshot_tpu as ts
    from torchsnapshot_tpu.dist_store import (
        JaxCoordinationStore,
        LinearBarrier,
        jax_process_group,
    )

    store = JaxCoordinationStore()
    # KV primitives.
    store.set("k1", b"value-1")
    assert store.try_get("k1") == b"value-1"
    assert store.try_get("missing") is None
    store.delete("k1")
    assert store.try_get("k1") is None

    assert store.add("ctr", 2) == 2
    assert store.add("ctr", 3) == 5

    # Object collectives (world 1 semantics still run real KV traffic).
    assert store.exchange("ex", 0, 1, {"x": 1}) == [{"x": 1}]
    assert store.broadcast("bc", 0, 1, "hello") == "hello"
    barrier = LinearBarrier("b", store, rank=0, world_size=1)
    barrier.arrive()
    barrier.depart()

    # The convenience pg threads through the Snapshot API (world size 1
    # short-circuits collectives, so KV coverage comes from the block
    # above; this asserts construction + end-to-end compatibility).
    jpg = jax_process_group()
    assert jpg.rank == 0 and jpg.world_size == 1
    import tempfile

    path = tempfile.mkdtemp(prefix="ts_jaxcoord_")
    arr = np.arange(16.0)
    ts.Snapshot.take(path, {"s": ts.PyTreeState({"w": arr})}, pg=jpg)
    dst = {"s": ts.PyTreeState({"w": np.zeros(16)})}
    ts.Snapshot(path, pg=jpg).restore(dst)
    np.testing.assert_array_equal(dst["s"].tree["w"], arr)


def test_jax_coordination_store() -> None:
    # Allocate the coordinator port and the harness TCPStore port from two
    # simultaneously-bound sockets: sequential get_free_port() calls can
    # return the same just-released port.
    import socket

    with socket.socket() as s1, socket.socket() as s2:
        s1.bind(("127.0.0.1", 0))
        s2.bind(("127.0.0.1", 0))
        coord_port = s1.getsockname()[1]
        store_port = s2.getsockname()[1]

    run_multiprocess(
        _jax_coordination_worker, nproc=1, args=(coord_port,), port=store_port
    )


def _jax_dist2_worker(pg, coord_port: int, root: str):
    """A genuine 2-process jax.distributed job: the coordination service
    carries ALL snapshot metadata traffic (key gathers, replication
    verification, partitioning, manifest gather, commit barrier)."""
    import jax

    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{coord_port}",
        num_processes=2,
        process_id=pg.rank,
    )
    import numpy as np

    import torchsnapshot_tpu as ts
    from torchsnapshot_tpu.dist_store import jax_process_group

    jpg = jax_process_group()
    assert jpg.world_size == 2 and jpg.rank == pg.rank

    state = {
        "shared": ts.PyTreeState({"w": np.full((64, 4), 2.5, np.float32)}),
        "mine": ts.StateDict(rank_val=40 + pg.rank),
    }
    snap = ts.Snapshot.take(
        root, state, pg=jpg, replicated=["shared/**"]
    )
    md = snap.metadata
    assert md.world_size == 2
    assert md.manifest["0/shared/w"].replicated
    assert "1/shared/w" not in md.manifest

    dst = {
        "shared": ts.PyTreeState({"w": np.zeros((64, 4), np.float32)}),
        "mine": ts.StateDict(rank_val=-1),
    }
    ts.Snapshot(root, pg=jpg).restore(dst)
    assert float(dst["shared"].tree["w"][3, 3]) == 2.5
    assert dst["mine"]["rank_val"] == 40 + pg.rank

    # Preemption agreement over the SAME coordination service (the pod
    # path): an eviction notice on rank 1 only; both ranks must save the
    # same step through the manager.
    from torchsnapshot_tpu.test_utils import drive_preemption_loop

    mgr = ts.CheckpointManager(root + "_mgr", pg=jpg)
    saver = ts.PreemptionSaver(jpg, signals=(), poll_interval=0.1)
    saved_at = drive_preemption_loop(
        jpg,
        saver,
        lambda step: mgr.save(step, {"s": ts.StateDict(step=step)}),
        evict_rank=1,
    )
    assert saved_at is not None
    return saved_at


def test_two_process_jax_distributed_snapshot(tmp_path) -> None:
    import socket

    with socket.socket() as s1, socket.socket() as s2:
        s1.bind(("127.0.0.1", 0))
        s2.bind(("127.0.0.1", 0))
        coord_port = s1.getsockname()[1]
        store_port = s2.getsockname()[1]

    results = run_multiprocess(
        _jax_dist2_worker,
        nproc=2,
        args=(coord_port, str(tmp_path / "snap")),
        port=store_port,
    )
    # Both ranks agreed on one preemption-save step over the
    # coordination service.
    assert results[0] == results[1] and results[0] is not None, results


def test_constructor_probe_rejects_misclassifying_client() -> None:
    """The absent-key self-check (round 5): a jaxlib whose coordination
    client words the absent-key status in a way try_get cannot classify
    as NOT_FOUND must be rejected loudly AT CONSTRUCTION — otherwise
    every absent-key poll raises and, past the transient-read grace, all
    barriers and preemption polls fail on real pods with the cause
    (message wording) nowhere near the symptom."""
    from unittest import mock

    import pytest

    class WeirdClient:
        def key_value_try_get_bytes(self, key):
            raise ValueError("no such entry exists")  # not a NOT_FOUND token

    class _State:
        client = WeirdClient()

    with mock.patch("jax._src.distributed.global_state", _State()):
        from torchsnapshot_tpu.dist_store import JaxCoordinationStore

        with pytest.raises(RuntimeError, match="absent-key probe"):
            JaxCoordinationStore()


def test_constructor_probe_rejects_phantom_values() -> None:
    """A store returning a value for a never-set key has broken get
    semantics (e.g. a client echoing defaults); refuse it."""
    from unittest import mock

    import pytest

    class EchoClient:
        def key_value_try_get_bytes(self, key):
            return b"phantom"

    class _State:
        client = EchoClient()

    with mock.patch("jax._src.distributed.global_state", _State()):
        from torchsnapshot_tpu.dist_store import JaxCoordinationStore

        with pytest.raises(RuntimeError, match="never set"):
            JaxCoordinationStore()

"""CheckpointManager: retention, latest-step resume, async saves, and the
uncommitted-step invisibility invariant.

The reference ships only the single-snapshot primitives and its examples
hand-roll this loop (examples/simple_example.py:59-76); the manager is
the packaged version, so the tests assert the loop's guarantees rather
than reference parity.
"""

import os

import numpy as np
import pytest

import torchsnapshot_tpu as ts
from torchsnapshot_tpu.manager import INDEX_BLOB, _step_dirname
from torchsnapshot_tpu.snapshot import SNAPSHOT_METADATA_FNAME


def _state(value: float):
    return {"s": ts.PyTreeState({"w": np.full((8,), value)})}


def test_save_restore_latest_roundtrip(tmp_path) -> None:
    mgr = ts.CheckpointManager(str(tmp_path))
    assert mgr.latest_step() is None
    assert mgr.restore_latest(_state(0.0)) is None  # fresh run

    mgr.save(10, _state(10.0))
    mgr.save(20, _state(20.0))
    assert mgr.all_steps() == [10, 20]

    dst = _state(0.0)
    assert mgr.restore_latest(dst) == 20
    np.testing.assert_array_equal(dst["s"].tree["w"], np.full((8,), 20.0))

    dst = _state(0.0)
    mgr.restore(10, dst)
    np.testing.assert_array_equal(dst["s"].tree["w"], np.full((8,), 10.0))


def test_retention_deletes_old_steps(tmp_path) -> None:
    mgr = ts.CheckpointManager(str(tmp_path), keep_last_n=2)
    for step in (1, 2, 3, 4):
        mgr.save(step, _state(float(step)))
    assert mgr.all_steps() == [3, 4]

    # Dropped steps lose their commit marker AND their blobs.
    for dropped in (1, 2):
        step_dir = tmp_path / _step_dirname(dropped)
        assert not (step_dir / SNAPSHOT_METADATA_FNAME).exists()
        assert not (step_dir / "0" / "s" / "w").exists()
    # Retained steps restore.
    dst = _state(0.0)
    mgr.restore(3, dst)
    np.testing.assert_array_equal(dst["s"].tree["w"], np.full((8,), 3.0))


def test_async_save_commits_on_wait(tmp_path) -> None:
    mgr = ts.CheckpointManager(str(tmp_path), keep_last_n=1)
    pending = mgr.async_save(5, _state(5.0))
    pending.wait()
    pending2 = mgr.async_save(6, _state(6.0))
    pending2.wait()
    assert mgr.all_steps() == [6]
    dst = _state(0.0)
    assert mgr.restore_latest(dst) == 6


def test_async_save_staged_wait_does_not_index(tmp_path, monkeypatch) -> None:
    """wait(phase="staged") observes D2H completion only and indexes
    nothing itself: a half-drained step must never be visible to
    restore_latest. With the commit held open at the marker's write the
    step is absent; once the marker exists the commit thread indexes it,
    exactly once, and it is present when done() reads true."""
    from torchsnapshot_tpu.test_utils import MarkerWrites

    marker_may_land = MarkerWrites(monkeypatch).hold(_step_dirname(3))
    mgr = ts.CheckpointManager(str(tmp_path))
    pending = mgr.async_save(3, _state(3.0))
    assert pending.wait(phase="staged") is None
    assert pending.staged() and not pending.done()
    assert 3 not in mgr.all_steps()
    # A typo'd phase must not silently become a committed wait (same
    # contract as PendingSnapshot).
    with pytest.raises(ValueError, match="staged"):
        pending.wait(phase="stagd")
    marker_may_land.set()
    snapshot = pending.wait()
    assert snapshot is not None and pending.done()
    assert mgr.all_steps() == [3]


def test_uncommitted_step_invisible(tmp_path) -> None:
    """A step directory without a commit marker (crashed take) must never
    appear in the index or be restored."""
    mgr = ts.CheckpointManager(str(tmp_path))
    mgr.save(1, _state(1.0))
    # Simulate a crash mid-take of step 2: files exist, no marker, no index
    # update (the index is only written after Snapshot.take returns).
    fake = tmp_path / _step_dirname(2) / "0" / "s"
    fake.mkdir(parents=True)
    (fake / "w").write_bytes(b"\x00" * 64)
    assert mgr.all_steps() == [1]
    dst = _state(0.0)
    assert mgr.restore_latest(dst) == 1


def test_sharded_and_checksums_gced(tmp_path) -> None:
    """Retention walks every manifest entry kind: sharded shard blobs and
    checksum tables go too."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >=2 devices")
    mesh = Mesh(np.array(devs), ("x",))

    def sharded_state(v: float):
        arr = jax.device_put(
            jnp.full((8 * len(devs), 4), v), NamedSharding(mesh, P("x", None))
        )
        return {"s": ts.PyTreeState({"emb": arr})}

    mgr = ts.CheckpointManager(str(tmp_path), keep_last_n=1)
    mgr.save(1, sharded_state(1.0))
    step1 = tmp_path / _step_dirname(1)
    assert (step1 / "checksums" / "0").exists()
    shard_blobs = list((step1 / "sharded").rglob("*")) if (step1 / "sharded").exists() else []
    assert shard_blobs

    mgr.save(2, sharded_state(2.0))
    assert mgr.all_steps() == [2]
    assert not (step1 / SNAPSHOT_METADATA_FNAME).exists()
    assert not (step1 / "checksums" / "0").exists()
    remaining = [
        p for p in (step1 / "sharded").rglob("*") if p.is_file()
    ] if (step1 / "sharded").exists() else []
    assert remaining == []


def test_index_blob_location(tmp_path) -> None:
    mgr = ts.CheckpointManager(str(tmp_path))
    mgr.save(7, _state(7.0))
    assert (tmp_path / INDEX_BLOB).exists()


def test_memory_backend(tmp_path) -> None:
    from torchsnapshot_tpu.storage_plugins.memory import MemoryStoragePlugin

    try:
        mgr = ts.CheckpointManager("memory://mgrtest", keep_last_n=1)
        mgr.save(1, _state(1.0))
        mgr.save(2, _state(2.0))
        assert mgr.all_steps() == [2]
        dst = _state(0.0)
        assert mgr.restore_latest(dst) == 2
        np.testing.assert_array_equal(dst["s"].tree["w"], np.full((8,), 2.0))
    finally:
        for name in list(
            n for n in __import__(
                "torchsnapshot_tpu.storage_plugins.memory",
                fromlist=["_STORES"],
            )._STORES
            if n.startswith("mgrtest")
        ):
            MemoryStoragePlugin.drop_store(name)


def test_corrupt_index_falls_back_to_backup(tmp_path) -> None:
    """A crash mid-index-write must not brick the manager: the backup slot
    (written after the primary) still lists the previous steps."""
    from torchsnapshot_tpu.manager import INDEX_BACKUP_BLOB

    mgr = ts.CheckpointManager(str(tmp_path))
    mgr.save(1, _state(1.0))
    mgr.save(2, _state(2.0))
    assert (tmp_path / INDEX_BACKUP_BLOB).exists()
    (tmp_path / INDEX_BLOB).write_text("{trunc")  # torn primary write
    assert mgr.all_steps() == [1, 2]
    dst = _state(0.0)
    assert mgr.restore_latest(dst) == 2


def test_saving_older_step_is_never_deleted(tmp_path) -> None:
    """Retention keeps the newest N numerically, but the just-saved
    checkpoint survives even when its number is older (step-counter
    rollback) — save() must never return a dangling snapshot."""
    mgr = ts.CheckpointManager(str(tmp_path), keep_last_n=2)
    mgr.save(9, _state(9.0))
    mgr.save(10, _state(10.0))
    mgr.save(5, _state(5.0))
    assert 5 in mgr.all_steps()
    dst = _state(0.0)
    mgr.restore(5, dst)
    np.testing.assert_array_equal(dst["s"].tree["w"], np.full((8,), 5.0))


def test_multiprocess_fresh_restore_then_save(tmp_path) -> None:
    """The aliasing regression: restore_latest on a fresh run (broadcast,
    early return, NO trailing barrier) immediately followed by save's
    internal broadcasts — shared op sequencing must keep every store key
    unique, or a slow rank reads the wrong object."""
    import os
    import tempfile

    from torchsnapshot_tpu.test_utils import run_multiprocess

    path = os.path.join(tempfile.gettempdir(), "mgr-mp-test")
    results = run_multiprocess(_mgr_worker, nproc=2, args=(path,))
    assert results == [3, 3]


def _mgr_worker(pg, root: str):
    import shutil

    import numpy as np

    import torchsnapshot_tpu as ts

    if pg.rank == 0:
        shutil.rmtree(root, ignore_errors=True)
    from torchsnapshot_tpu.pg_wrapper import PGWrapper

    PGWrapper(pg).barrier()  # both ranks see the clean root
    mgr = ts.CheckpointManager(root, keep_last_n=2, pg=pg)
    state = {"s": ts.PyTreeState({"w": np.full((4,), float(pg.rank))})}
    assert mgr.restore_latest(state) is None  # fresh: broadcast + early return
    mgr.save(3, state)
    PGWrapper(pg).barrier()  # rank 0's index write is durable
    dst = {"s": ts.PyTreeState({"w": np.zeros(4)})}
    resumed = mgr.restore_latest(dst)
    assert float(dst["s"].tree["w"][0]) == float(pg.rank)  # per-rank state
    return resumed


def test_multiprocess_async_save_and_retention(tmp_path) -> None:
    """async_save in a multiprocess world: the background commits of both
    ranks coordinate through the store barrier, retention runs on rank 0
    inside wait(), and the next resume sees exactly the retained steps."""
    from torchsnapshot_tpu.test_utils import run_multiprocess

    results = run_multiprocess(
        _mgr_async_worker, nproc=2, args=(str(tmp_path / "root"),)
    )
    assert results == [[2, 3], [2, 3]]


def _mgr_async_worker(pg, root: str):
    import shutil

    import numpy as np

    import torchsnapshot_tpu as ts
    from torchsnapshot_tpu.pg_wrapper import PGWrapper

    if pg.rank == 0:
        shutil.rmtree(root, ignore_errors=True)
    PGWrapper(pg).barrier()
    mgr = ts.CheckpointManager(root, keep_last_n=2, pg=pg)
    for step in (1, 2, 3):
        state = {
            "s": ts.PyTreeState({"w": np.full((4,), float(step))}),
            "progress": ts.StateDict(rank=pg.rank),
        }
        pending = mgr.async_save(step, state)
        pending.wait()
    PGWrapper(pg).barrier()  # rank 0's index write is durable everywhere
    steps = sorted(mgr.all_steps())
    dst = {
        "s": ts.PyTreeState({"w": np.zeros(4)}),
        "progress": ts.StateDict(rank=-1),
    }
    resumed = mgr.restore_latest(dst)
    assert resumed == 3
    assert float(dst["s"].tree["w"][0]) == 3.0
    assert dst["progress"]["rank"] == pg.rank  # per-rank state stayed per-rank
    return steps


def test_unreadable_index_fails_save_instead_of_orphaning(tmp_path) -> None:
    """Transiently unreadable index slots must not be treated as an empty
    step list: a save in that state would rewrite the index as just the new
    step, silently orphaning every previously committed step."""
    import unittest.mock as mock

    from torchsnapshot_tpu.storage_plugins.fs import FSStoragePlugin

    mgr = ts.CheckpointManager(str(tmp_path))
    mgr.save(1, _state(1.0))
    mgr.save(2, _state(2.0))

    real_read = FSStoragePlugin.read

    async def flaky_read(self, read_io):
        if read_io.path.endswith(".index") or "index" in read_io.path:
            raise OSError("transient storage blip")
        return await real_read(self, read_io)

    with mock.patch.object(FSStoragePlugin, "read", flaky_read):
        with pytest.raises(Exception, match="index unreadable|transient"):
            mgr.save(3, _state(3.0))
    # The blip healed: the earlier steps are still indexed and restorable.
    assert mgr.all_steps() == [1, 2]
    dst = _state(0.0)
    assert mgr.restore_latest(dst) == 2


def test_torn_first_index_write_self_recovers(tmp_path) -> None:
    """Corrupt primary + absent backup = the very first index write tore
    before the backup slot existed; nothing was ever committed to the
    index, so the manager must self-recover, not brick."""
    (tmp_path / INDEX_BLOB).write_text("{torn")
    mgr = ts.CheckpointManager(str(tmp_path))
    assert mgr.all_steps() == []
    mgr.save(1, _state(1.0))
    assert mgr.all_steps() == [1]


def test_both_index_slots_corrupt_raises(tmp_path) -> None:
    from torchsnapshot_tpu.manager import INDEX_BACKUP_BLOB

    mgr = ts.CheckpointManager(str(tmp_path))
    mgr.save(1, _state(1.0))
    (tmp_path / INDEX_BLOB).write_text("{torn")
    (tmp_path / INDEX_BACKUP_BLOB).write_text("{torn")
    with pytest.raises(RuntimeError, match="index unreadable"):
        mgr.all_steps()


# ---------------------------------------------------------------------------
# metric-based retention (keep_best_n)
# ---------------------------------------------------------------------------


def _mstate(v: float):
    import jax.numpy as jnp

    return {"m": ts.PyTreeState({"w": jnp.full((8,), float(v))})}


def test_keep_best_n_retains_best_and_last(tmp_path):
    mgr = ts.CheckpointManager(
        str(tmp_path), keep_last_n=1, keep_best_n=2, best_mode="min"
    )
    losses = {0: 5.0, 1: 1.0, 2: 4.0, 3: 0.5, 4: 9.0}
    for step, loss in losses.items():
        mgr.save(step, _mstate(step), metric=loss)
    # best two: steps 3 (0.5) and 1 (1.0); last one: step 4.
    assert mgr.all_steps() == [1, 3, 4]
    assert mgr.best_step() == 3

    dest = _mstate(-1)
    assert mgr.restore_best(dest) == 3
    import numpy as np

    assert float(np.asarray(dest["m"].tree["w"])[0]) == 3.0


def test_keep_best_max_mode_and_ties(tmp_path):
    mgr = ts.CheckpointManager(
        str(tmp_path), keep_best_n=1, best_mode="max"
    )
    mgr.save(0, _mstate(0), metric=0.9)
    mgr.save(1, _mstate(1), metric=0.9)  # tie: newest wins
    mgr.save(2, _mstate(2), metric=0.1)
    # step 2 survives only as the just-saved step of its own commit; the
    # next save drops it.
    mgr.save(3, _mstate(3), metric=0.2)
    assert mgr.best_step() == 1
    assert 1 in mgr.all_steps()
    assert 0 not in mgr.all_steps()
    assert 2 not in mgr.all_steps()


def test_metricless_steps_protected_only_by_last_n(tmp_path):
    mgr = ts.CheckpointManager(str(tmp_path), keep_last_n=2, keep_best_n=1)
    mgr.save(0, _mstate(0), metric=1.0)
    mgr.save(1, _mstate(1))  # no metric
    mgr.save(2, _mstate(2))  # no metric
    mgr.save(3, _mstate(3))  # no metric
    # best: 0; last two: 2, 3; step 1 dropped.
    assert mgr.all_steps() == [0, 2, 3]
    assert mgr.best_step() == 0


def test_keep_best_alone_never_gcs_unscored_steps(tmp_path):
    """With keep_best_n and no keep_last_n, only scored steps compete for
    deletion — enabling metric retention must not GC metric-less saves."""
    mgr = ts.CheckpointManager(str(tmp_path), keep_best_n=1)
    mgr.save(0, _mstate(0))  # unscored
    mgr.save(1, _mstate(1), metric=2.0)
    mgr.save(2, _mstate(2))  # unscored
    mgr.save(3, _mstate(3), metric=1.0)  # new best: step 1 drops
    assert mgr.all_steps() == [0, 2, 3]
    assert mgr.best_step() == 3


def test_best_step_none_without_metrics(tmp_path):
    mgr = ts.CheckpointManager(str(tmp_path))
    mgr.save(0, _mstate(0))
    assert mgr.best_step() is None
    assert mgr.restore_best(_mstate(-1)) is None


def test_async_save_metric_recorded(tmp_path):
    mgr = ts.CheckpointManager(str(tmp_path), keep_best_n=1)
    mgr.async_save(0, _mstate(0), metric=3.0).wait()
    mgr.async_save(1, _mstate(1), metric=2.0).wait()
    assert mgr.best_step() == 1


def test_best_retention_composes_with_incremental_pins(tmp_path):
    """A best-kept step referencing an origin keeps the origin pinned."""
    import jax.numpy as jnp

    def st(t):
        return {
            "m": ts.PyTreeState(
                {"frozen": jnp.arange(32.0), "t": jnp.full((4,), float(t))}
            )
        }

    mgr = ts.CheckpointManager(
        str(tmp_path), keep_last_n=1, keep_best_n=1, incremental=True
    )
    mgr.save(0, st(0), metric=5.0)
    mgr.save(1, st(1), metric=0.1)  # the best; refs step 0's frozen blob
    mgr.save(2, st(2), metric=7.0)
    mgr.save(3, st(3), metric=8.0)
    steps = mgr.all_steps()
    assert 1 in steps and 3 in steps and 2 not in steps
    # Restoring the best still works through the pinned origin.
    dest = st(-1)
    assert mgr.restore_best(dest) == 1
    import numpy as np

    np.testing.assert_array_equal(
        np.asarray(dest["m"].tree["frozen"]), np.arange(32.0)
    )


def test_nonfinite_metric_rejected(tmp_path):
    mgr = ts.CheckpointManager(str(tmp_path), keep_best_n=1)
    with pytest.raises(ValueError, match="finite"):
        mgr.save(0, _mstate(0), metric=float("nan"))
    with pytest.raises(ValueError, match="finite"):
        mgr.async_save(0, _mstate(0), metric=float("inf"))
    assert mgr.all_steps() == []  # nothing committed


@pytest.mark.parametrize("seed", range(3))
def test_retention_gc_fuzz_every_indexed_step_restores(tmp_path, seed):
    """Randomized save sequences (incremental on/off, random metrics,
    random keep_last_n/keep_best_n): after EVERY save, every step still
    in the index must restore byte-exact and deep-fsck clean — retention
    with ref-pinning GC must never delete blobs a live step references.
    A 10-run sweep of this generator passed during round 4."""
    from torchsnapshot_tpu.fsck import verify_snapshot
    from torchsnapshot_tpu.knobs import override_incremental_chunk_size_bytes

    rng = np.random.default_rng(6000 + seed)
    keep_last = int(rng.integers(1, 4)) if rng.random() < 0.7 else None
    keep_best = int(rng.integers(1, 3)) if rng.random() < 0.5 else None
    incremental = bool(rng.random() < 0.6)
    mgr = ts.CheckpointManager(
        str(tmp_path / "root"),
        keep_last_n=keep_last,
        keep_best_n=keep_best,
        incremental=incremental,
    )
    base = rng.standard_normal(3000).astype(np.float32)
    states = {}
    with override_incremental_chunk_size_bytes(256):
        for step in range(8):
            arr = base.copy()
            idx = rng.integers(0, arr.size, 20)  # sparse: refs chain
            arr[idx] = rng.standard_normal(20)
            base = arr
            states[step] = arr.copy()
            metric = (
                float(rng.standard_normal()) if rng.random() < 0.7 else None
            )
            mgr.save(step, {"m": ts.PyTreeState({"w": arr})}, metric=metric)

            for s in mgr.all_steps():
                dst = ts.PyTreeState({"w": np.zeros(3000, np.float32)})
                ts.Snapshot(mgr.step_path(s)).restore({"m": dst})
                np.testing.assert_array_equal(dst.tree["w"], states[s])
                report = verify_snapshot(mgr.step_path(s), deep=True)
                assert report.ok, (s, report)

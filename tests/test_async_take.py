"""Async take: staging-unblock semantics, background commit, and the
no-commit-marker-on-failure invariant.

Structural model: reference tests/test_async_take.py:25-115 — subclassed
slow/faulty FS plugins patched in, asserting a failed async take leaves no
``.snapshot_metadata``.
"""

import asyncio
import contextlib
import os
import tempfile
import time
from unittest import mock

import jax.numpy as jnp
import numpy as np
import pytest

import torchsnapshot_tpu as ts
from torchsnapshot_tpu.io_types import WriteIO
from torchsnapshot_tpu.snapshot import SNAPSHOT_METADATA_FNAME
from torchsnapshot_tpu.storage_plugins.fs import FSStoragePlugin
from torchsnapshot_tpu.test_utils import multiprocess_test


class SlowFSStoragePlugin(FSStoragePlugin):
    DELAY_S = 0.3

    async def write(self, write_io: WriteIO) -> None:
        if write_io.path != SNAPSHOT_METADATA_FNAME:
            await asyncio.sleep(self.DELAY_S)
        await super().write(write_io)


from torchsnapshot_tpu.test_utils import faulty_fs_plugin
from torchsnapshot_tpu.test_utils import patch_storage_plugin as _patch_plugin

FaultyFSStoragePlugin = faulty_fs_plugin(
    lambda path: path != SNAPSHOT_METADATA_FNAME, delay_s=0.05
)


def test_async_take_roundtrip(tmp_path) -> None:
    app_state = {
        "p": ts.PyTreeState({"w": jnp.arange(128.0)}),
        "prog": ts.StateDict(step=9),
    }
    pending = ts.Snapshot.async_take(str(tmp_path), app_state)
    snapshot = pending.wait()
    assert pending.done()
    fresh = {"p": ts.PyTreeState({"w": jnp.zeros(128)}), "prog": ts.StateDict(step=0)}
    snapshot.restore(fresh)
    np.testing.assert_array_equal(np.asarray(fresh["p"].tree["w"]), np.arange(128.0))
    assert fresh["prog"]["step"] == 9


def test_async_take_unblocks_before_io(tmp_path) -> None:
    with _patch_plugin(SlowFSStoragePlugin):
        app_state = {"p": ts.PyTreeState({"w": jnp.ones(64)})}
        t0 = time.monotonic()
        pending = ts.Snapshot.async_take(str(tmp_path), app_state)
        returned_at = time.monotonic() - t0
        # Returned before the (deliberately slow) storage write finished...
        assert returned_at < SlowFSStoragePlugin.DELAY_S
        assert not os.path.exists(tmp_path / SNAPSHOT_METADATA_FNAME)
        # ...and the commit marker appears only after wait().
        pending.wait()
    assert os.path.exists(tmp_path / SNAPSHOT_METADATA_FNAME)


def test_failed_async_take_leaves_no_commit_marker(tmp_path) -> None:
    with _patch_plugin(FaultyFSStoragePlugin):
        app_state = {"p": ts.PyTreeState({"w": jnp.ones(64)})}
        pending = ts.Snapshot.async_take(str(tmp_path), app_state)
        with pytest.raises(OSError, match="injected storage failure"):
            pending.wait()
    assert not os.path.exists(tmp_path / SNAPSHOT_METADATA_FNAME)
    # The failed location is restorable-from never: metadata access fails.
    with pytest.raises(FileNotFoundError):
        _ = ts.Snapshot(str(tmp_path)).metadata


def test_async_take_numpy_mutation_consistency(tmp_path) -> None:
    """Mutable (numpy) leaves must be snapshotted at async_take time even if
    the application mutates them before I/O completes (reference defensive
    copy semantics, io_preparer.py:555-565)."""
    arr = np.full((32,), 1.0)
    app_state = {"s": ts.StateDict(arr=arr)}
    with _patch_plugin(SlowFSStoragePlugin):
        pending = ts.Snapshot.async_take(str(tmp_path), app_state)
        arr[:] = -1.0  # mutate after staging returned
        snapshot = pending.wait()
    fresh = {"s": ts.StateDict(arr=np.zeros(32))}
    snapshot.restore(fresh)
    np.testing.assert_array_equal(fresh["s"]["arr"], np.full((32,), 1.0))


@multiprocess_test(nproc=2)
def test_async_take_peer_failure_no_commit(pg) -> None:
    """Rank 1's storage fails; the store-barrier propagates the error so
    rank 0 must not write the commit marker."""
    import jax.numpy as jnp

    path = os.path.join(tempfile.gettempdir(), "async-fail-test")
    if pg.rank == 0:
        import shutil

        shutil.rmtree(path, ignore_errors=True)
    from torchsnapshot_tpu.pg_wrapper import PGWrapper

    PGWrapper(pg).barrier()

    plugin_cls = FaultyFSStoragePlugin if pg.rank == 1 else FSStoragePlugin
    app_state = {"prog": ts.StateDict(rank=pg.rank), "p": ts.PyTreeState({"w": jnp.ones(8)})}
    with _patch_plugin(plugin_cls):
        pending = ts.Snapshot.async_take(path, app_state, pg=pg)
        with pytest.raises(Exception):
            pending.wait()
    assert not os.path.exists(os.path.join(path, SNAPSHOT_METADATA_FNAME))


@multiprocess_test(nproc=2)
def test_async_take_rank0_staging_failure_fails_fast(pg) -> None:
    """Rank 0 fails during STAGING in a rank-0-only step (replication
    consolidation, after the non-leader manifest gather): its error must
    reach rank 1's commit thread through the commit-nonce barrier, so
    rank 1's wait() raises in seconds instead of stranding for the 300 s
    store timeout. Pins two round-5 changes together: async_take
    constructs the error-reporting barrier handle BEFORE _take_impl, and
    the memory-budget all-gather runs BEFORE the manifest gather (a peer
    must have no wrapped collective left between its gather send and the
    commit barrier — it cannot see the reported error from inside an
    op-seq poll loop)."""
    import time

    import jax.numpy as jnp

    path = os.path.join(tempfile.gettempdir(), "async-rank0-staging-fail")
    if pg.rank == 0:
        import shutil

        shutil.rmtree(path, ignore_errors=True)
    from torchsnapshot_tpu.pg_wrapper import PGWrapper

    PGWrapper(pg).barrier()
    app_state = {"p": ts.PyTreeState({"w": jnp.ones(4096)})}
    t0 = time.monotonic()
    if pg.rank == 0:
        with mock.patch(
            "torchsnapshot_tpu.partitioner.consolidate_replicated_entries",
            side_effect=RuntimeError("injected staging failure"),
        ), pytest.raises(RuntimeError, match="injected staging failure"):
            ts.Snapshot.async_take(path, app_state, pg=pg, replicated=["p/**"])
    else:
        pending = ts.Snapshot.async_take(
            path, app_state, pg=pg, replicated=["p/**"]
        )
        with pytest.raises(Exception):
            pending.wait()
        assert time.monotonic() - t0 < 60.0, (
            "peer blocked to store timeout despite reported staging error"
        )
    assert not os.path.exists(os.path.join(path, SNAPSHOT_METADATA_FNAME))


@multiprocess_test(nproc=2)
def test_sync_take_commit_window_failure_fails_fast(pg) -> None:
    """Rank 0's metadata write fails INSIDE the commit window (between
    barrier arrive and depart): the round-5 _reporting_to wrap means
    peers polling at depart() observe the error and abandon in seconds
    (they used to block out the full store timeout), and no commit
    marker exists."""
    import time

    import jax.numpy as jnp

    from torchsnapshot_tpu.snapshot import Snapshot

    path = os.path.join(tempfile.gettempdir(), "sync-commit-window-fail")
    if pg.rank == 0:
        import shutil

        shutil.rmtree(path, ignore_errors=True)
    from torchsnapshot_tpu.pg_wrapper import PGWrapper

    PGWrapper(pg).barrier()
    app_state = {"p": ts.PyTreeState({"w": jnp.ones(1024) * pg.rank})}
    ctx = (
        mock.patch.object(
            Snapshot,
            "_write_snapshot_metadata",
            side_effect=RuntimeError("injected metadata-write failure"),
        )
        if pg.rank == 0
        else contextlib.nullcontext()
    )
    t0 = time.monotonic()
    with ctx, pytest.raises(Exception):
        ts.Snapshot.take(path, app_state, pg=pg)
    assert time.monotonic() - t0 < 60.0, "peer blocked to store timeout"
    assert not os.path.exists(os.path.join(path, SNAPSHOT_METADATA_FNAME))


@multiprocess_test(nproc=2)
def test_sync_take_peer_failure_fails_fast_no_commit(pg) -> None:
    """SYNC take symmetry of the async case above: rank 1's storage
    fails; rank 0 must observe the reported error at the commit barrier
    and raise well before the store timeout (it used to block the full
    300 s), and no commit marker may exist on either rank."""
    import time

    import jax.numpy as jnp

    path = os.path.join(tempfile.gettempdir(), "sync-fail-test")
    if pg.rank == 0:
        import shutil

        shutil.rmtree(path, ignore_errors=True)
    from torchsnapshot_tpu.pg_wrapper import PGWrapper

    PGWrapper(pg).barrier()

    plugin_cls = FaultyFSStoragePlugin if pg.rank == 1 else FSStoragePlugin
    app_state = {
        "prog": ts.StateDict(rank=pg.rank),
        "p": ts.PyTreeState({"w": jnp.ones(8) * pg.rank}),
    }
    t0 = time.monotonic()
    with _patch_plugin(plugin_cls), pytest.raises(Exception):
        ts.Snapshot.take(path, app_state, pg=pg)
    assert time.monotonic() - t0 < 60.0, "survivor blocked to store timeout"
    assert not os.path.exists(os.path.join(path, SNAPSHOT_METADATA_FNAME))


# ---------------------------------------------------------------------------
# Device-snapshot deferral (round 6): size-independent visible span,
# wait(phase=), mutation-after-return, drain-failure semantics.
# ---------------------------------------------------------------------------


def _sleepy_stage(delay_s: float):
    """Patch ArrayBufferStager's staging kernel to sleep first — makes
    'did staging run inside async_take?' observable on a fast CPU."""
    from torchsnapshot_tpu.io_preparer import ArrayBufferStager

    orig = ArrayBufferStager._stage_sync_impl

    def slow(self):
        time.sleep(delay_s)
        return orig(self)

    return mock.patch.object(ArrayBufferStager, "_stage_sync_impl", slow)


def test_async_take_returns_before_staging(tmp_path) -> None:
    """The device-snapshot default: async_take returns after capture
    dispatch; the (deliberately slow) staging runs on the background
    drain, observable at wait(phase="staged")."""
    app_state = {"p": ts.PyTreeState({"w": jnp.arange(512.0)})}
    with _sleepy_stage(0.4):
        t0 = time.monotonic()
        pending = ts.Snapshot.async_take(str(tmp_path), app_state)
        returned_at = time.monotonic() - t0
        assert returned_at < 0.4, "staging ran inside the visible span"
        assert pending.wait(phase="staged") is None
        assert pending.staged()
        snapshot = pending.wait()
    fresh = {"p": ts.PyTreeState({"w": jnp.zeros(512)})}
    snapshot.restore(fresh)
    np.testing.assert_array_equal(
        np.asarray(fresh["p"].tree["w"]), np.arange(512.0)
    )


def test_async_take_device_snapshot_disabled_stages_before_return(
    tmp_path,
) -> None:
    """The kill-switch restores the pre-deferral contract: staging
    completes before async_take returns."""
    from torchsnapshot_tpu import knobs

    app_state = {"p": ts.PyTreeState({"w": jnp.arange(64.0)})}
    with knobs.disable_async_device_snapshot(), _sleepy_stage(0.3):
        t0 = time.monotonic()
        pending = ts.Snapshot.async_take(str(tmp_path), app_state)
        returned_at = time.monotonic() - t0
        assert returned_at >= 0.3, "staging was deferred despite the knob"
        assert pending.staged()  # staged at construction
        pending.wait()


def test_async_take_wait_phase_validation_and_ordering(tmp_path) -> None:
    """wait(phase="staged") precedes the commit marker (storage writes
    still draining); wait() produces it; bogus phases are rejected."""
    with _patch_plugin(SlowFSStoragePlugin):
        app_state = {"p": ts.PyTreeState({"w": jnp.ones(64)})}
        pending = ts.Snapshot.async_take(str(tmp_path), app_state)
        with pytest.raises(ValueError, match="staged"):
            pending.wait(phase="flushed")
        assert pending.wait(phase="staged") is None
        # Staged is the D2H boundary, not the commit: the slow writes
        # (>= DELAY_S each) are still draining behind it.
        assert not os.path.exists(tmp_path / SNAPSHOT_METADATA_FNAME)
        snapshot = pending.wait(phase="committed")
        assert snapshot is not None
    assert os.path.exists(tmp_path / SNAPSHOT_METADATA_FNAME)


@pytest.mark.parametrize(
    "shape", [(64,), (513, 257), (128, 1024)], ids=["tiny", "odd", "wide"]
)
def test_async_take_mutation_after_return_roundtrip(tmp_path, shape) -> None:
    """Train-step-style in-place donation/update of the live arrays
    immediately after async_take returns must not corrupt the restored
    bytes (the on-device clone is the consistency point)."""
    import jax

    key = jax.random.PRNGKey(0)
    original = jax.random.normal(key, shape, dtype=jnp.float32)
    expected = np.array(np.asarray(original))  # pre-mutation truth
    counter = np.arange(8.0)  # mutable host leaf
    app_state = {
        "p": ts.PyTreeState({"w": original}),
        "s": ts.StateDict(counter=counter),
    }
    pending = ts.Snapshot.async_take(str(tmp_path), app_state)
    # Donation-shaped mutation the moment control returns: the donated
    # buffer may be reused by XLA for the output; the numpy leaf is
    # overwritten in place.
    donate = jax.jit(lambda x: x * -2.0 + 1.0, donate_argnums=0)
    clobbered = donate(original)
    jax.block_until_ready(clobbered)
    del original
    counter[:] = -1.0
    snapshot = pending.wait()
    fresh = {
        "p": ts.PyTreeState({"w": jnp.zeros(shape, jnp.float32)}),
        "s": ts.StateDict(counter=np.zeros(8)),
    }
    snapshot.restore(fresh)
    np.testing.assert_array_equal(np.asarray(fresh["p"].tree["w"]), expected)
    np.testing.assert_array_equal(fresh["s"]["counter"], np.arange(8.0))


def test_async_take_mutation_after_return_incremental(tmp_path) -> None:
    """The incremental variant: unchanged chunks reference the base (no
    clone, no write), changed chunks are captured — mutation after
    return corrupts neither."""
    import jax

    from torchsnapshot_tpu import knobs

    base_w = jnp.arange(4096.0)
    base_path = str(tmp_path / "base")
    with knobs.override_incremental_chunk_size_bytes(4096):
        ts.Snapshot.take(
            base_path,
            {"p": ts.PyTreeState({"w": base_w})},
            record_digests=True,
        )
        # Change one region; the rest of the chunks match the base.
        changed = base_w.at[:512].set(-3.0)
        expected = np.array(np.asarray(changed))
        pending = ts.Snapshot.async_take(
            str(tmp_path / "incr"),
            {"p": ts.PyTreeState({"w": changed})},
            incremental_base=base_path,
        )
        donate = jax.jit(lambda x: x * 0.0, donate_argnums=0)
        jax.block_until_ready(donate(changed))
        del changed
        snapshot = pending.wait()
    fresh = {"p": ts.PyTreeState({"w": jnp.zeros(4096)})}
    snapshot.restore(fresh)
    np.testing.assert_array_equal(np.asarray(fresh["p"].tree["w"]), expected)


def test_async_take_drain_failure_surfaces_on_wait_heartbeat_terminal(
    tmp_path,
) -> None:
    """A background-drain failure AFTER async_take returned must (a)
    surface on wait() — once recorded, every wait observes the same
    error, staged and committed alike — and (b) settle the progress
    heartbeat TERMINAL ("failed"), never a crash-shaped non-terminal
    leftover the doctor would misread as interrupted-take."""
    import json

    from torchsnapshot_tpu import knobs

    fail_after = [0]

    def should_fail(path: str) -> bool:
        # Let a couple of writes through so the failure lands mid-drain.
        if path == SNAPSHOT_METADATA_FNAME:
            return False
        fail_after[0] += 1
        return fail_after[0] > 2

    plugin_cls = faulty_fs_plugin(should_fail, delay_s=0.02)
    state = {
        f"w{i}": jnp.full((256,), float(i)) for i in range(8)
    }
    with knobs.override_progress_interval_seconds(0.01), _patch_plugin(
        plugin_cls
    ):
        pending = ts.Snapshot.async_take(
            str(tmp_path), {"p": ts.PyTreeState(state)}
        )
        with pytest.raises(OSError, match="injected storage failure") as e1:
            pending.wait()
        # Idempotent re-raise: the SAME recorded failure, both phases.
        with pytest.raises(OSError) as e2:
            pending.wait()
        with pytest.raises(OSError):
            pending.wait(phase="staged")
        assert e2.value is e1.value
    assert not os.path.exists(tmp_path / SNAPSHOT_METADATA_FNAME)
    heartbeat = tmp_path / ".progress-rank0.json"
    assert heartbeat.exists(), "failed op must leave a terminal heartbeat"
    doc = json.loads(heartbeat.read_text())
    assert doc["terminal"] == "failed"
    assert "injected storage failure" in (doc["error"] or "")


def test_async_take_staging_failure_unblocks_staged_wait(tmp_path) -> None:
    """A failure BEFORE the staged boundary must not strand
    wait(phase="staged"): the drain settles and the wait raises."""
    from torchsnapshot_tpu.io_preparer import ArrayBufferStager

    def boom(self):
        raise RuntimeError("injected staging failure")

    app_state = {"p": ts.PyTreeState({"w": jnp.ones(256)})}
    with mock.patch.object(ArrayBufferStager, "_stage_sync_impl", boom):
        pending = ts.Snapshot.async_take(str(tmp_path), app_state)
        with pytest.raises(RuntimeError, match="injected staging failure"):
            pending.wait(phase="staged")
        with pytest.raises(RuntimeError, match="injected staging failure"):
            pending.wait()
    assert not os.path.exists(tmp_path / SNAPSHOT_METADATA_FNAME)


def test_async_take_visible_staged_split_in_report(tmp_path) -> None:
    """The emitted async_take SnapshotReport carries the visible/staged
    phase split (the doctor's async-visible-stall evidence)."""
    from torchsnapshot_tpu import knobs, telemetry

    with knobs.enable_telemetry():
        pending = ts.Snapshot.async_take(
            str(tmp_path), {"p": ts.PyTreeState({"w": jnp.ones(512)})}
        )
        pending.wait()
        events_path = telemetry.events_path_for(str(tmp_path))
    events = telemetry.load_events(events_path)
    reports = [e for e in events if e.get("kind") == "async_take"]
    assert reports, "async_take must emit a report"
    report = reports[-1]
    assert report["visible_s"] is not None and report["visible_s"] >= 0
    assert report["staged_s"] is not None
    assert report["staged_s"] >= report["visible_s"]
    # The pool geometry that bounded the drain rides along (the context
    # for reading peak_staged_bytes on a pool-bounded pipeline).
    assert report["staging_pool"]["slabs"] >= 1
    assert report["staging_pool"]["capacity_bytes"] >= 1


@multiprocess_test(nproc=2)
def test_async_take_distributed_commit(pg) -> None:
    import jax.numpy as jnp

    path = os.path.join(tempfile.gettempdir(), "async-ok-test")
    if pg.rank == 0:
        import shutil

        shutil.rmtree(path, ignore_errors=True)
    from torchsnapshot_tpu.pg_wrapper import PGWrapper

    PGWrapper(pg).barrier()
    app_state = {"prog": ts.StateDict(rank=pg.rank)}
    pending = ts.Snapshot.async_take(path, app_state, pg=pg)
    snapshot = pending.wait()
    assert os.path.exists(os.path.join(path, SNAPSHOT_METADATA_FNAME))
    fresh = {"prog": ts.StateDict(rank=-1)}
    snapshot.restore(fresh)
    assert fresh["prog"]["rank"] == pg.rank



# ---------------------------------------------------------------------------
# The staging window of a device-snapshot drain comes from the plan (PR 32)
# ---------------------------------------------------------------------------


def _last_async_report(path: str) -> dict:
    import dataclasses

    from torchsnapshot_tpu import telemetry

    return dataclasses.asdict(telemetry.last_report("async_take", path=path))


def _roundtrip_ok(path: str, tree: dict) -> None:
    restored = {"p": ts.PyTreeState({k: jnp.zeros_like(v) for k, v in tree.items()})}
    ts.Snapshot(path).restore(restored)
    for k, v in tree.items():
        np.testing.assert_array_equal(np.asarray(restored["p"].tree[k]), np.asarray(v))


def test_async_take_drains_through_a_window_derived_from_its_plan(tmp_path) -> None:
    from torchsnapshot_tpu import knobs

    tree = {f"w{i}": jnp.full((64, 64), i, jnp.float32) for i in range(6)}
    with knobs.enable_telemetry():
        ts.Snapshot.async_take(str(tmp_path), {"p": ts.PyTreeState(tree)}).wait()
    report = _last_async_report(str(tmp_path))
    pool = report["staging_pool"]
    assert pool["chosen"] == "derived"
    # Six equal leaves: 32 requests of the plan's mean size is more than
    # the plan, so the window is the plan's bytes.
    assert pool["capacity_bytes"] == 6 * 64 * 64 * 4
    assert report["peak_staged_bytes"] <= pool["capacity_bytes"]
    _roundtrip_ok(str(tmp_path), tree)


@pytest.mark.parametrize("slab_bytes, slabs", [(20000, 2), (4096, 3)])
def test_async_take_env_pinned_pool_is_honoured_to_the_byte(
    tmp_path, slab_bytes, slabs
) -> None:
    """The operator's two variables give the pool they gave before: the
    drain never holds more than slabs x slab_bytes (a leaf larger than
    that is admitted alone), and the take commits bit-identically."""
    from torchsnapshot_tpu import knobs

    tree = {f"w{i}": jnp.full((64, 64), i, jnp.float32) for i in range(6)}
    with knobs.enable_telemetry(), knobs.override_staging_pool_slab_bytes(
        slab_bytes
    ), knobs.override_staging_pool_slabs(slabs):
        ts.Snapshot.async_take(str(tmp_path), {"p": ts.PyTreeState(tree)}).wait()
    report = _last_async_report(str(tmp_path))
    assert report["staging_pool"] == {
        "capacity_bytes": slab_bytes * slabs,
        "slab_bytes": slab_bytes,
        "slabs": slabs,
        "chosen": "env",
    }
    leaf = 64 * 64 * 4
    assert report["peak_staged_bytes"] <= max(slab_bytes * slabs, leaf)
    _roundtrip_ok(str(tmp_path), tree)


def test_async_take_tuner_override_cannot_shrink_the_window(tmp_path) -> None:
    from torchsnapshot_tpu import knobs

    tree = {f"w{i}": jnp.full((64, 64), i, jnp.float32) for i in range(6)}
    try:
        knobs.set_tuner_override(knobs._STAGING_POOL_SLAB_BYTES_ENV, 1024)
        knobs.set_tuner_override(knobs._STAGING_POOL_SLABS_ENV, 2)
        with knobs.enable_telemetry():
            ts.Snapshot.async_take(str(tmp_path), {"p": ts.PyTreeState(tree)}).wait()
    finally:
        knobs.clear_tuner_override(knobs._STAGING_POOL_SLAB_BYTES_ENV)
        knobs.clear_tuner_override(knobs._STAGING_POOL_SLABS_ENV)
    pool = _last_async_report(str(tmp_path))["staging_pool"]
    assert (pool["chosen"], pool["capacity_bytes"]) == ("derived", 6 * 64 * 64 * 4)
    _roundtrip_ok(str(tmp_path), tree)

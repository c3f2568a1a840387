"""Streaming restore placement: leaves device_put while later reads are
still in flight (rolling batches), with the flush knob controlling
granularity. No reference counterpart (its restore consumes directly into
torch tensors); this is the TPU H2D-overlap path."""

import asyncio
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchsnapshot_tpu as ts
from torchsnapshot_tpu import snapshot as snapshot_mod
from torchsnapshot_tpu.knobs import override_restore_placement_flush_bytes
from torchsnapshot_tpu.storage_plugins.fs import FSStoragePlugin
from torchsnapshot_tpu.test_utils import assert_tree_eq


EVENTS = []


class RecordingFSStoragePlugin(FSStoragePlugin):
    async def _record(self, path):
        if path.startswith("0/"):
            EVENTS.append(("read", path))
        await asyncio.sleep(0.02)  # keep later reads in flight past flushes

    async def read(self, read_io):
        await super().read(read_io)
        await self._record(read_io.path)

    async def read_with_checksum(self, read_io):
        # Whole-blob reads take the fused read+CRC path; record those too.
        pages = await super().read_with_checksum(read_io)
        if pages is not None:
            await self._record(read_io.path)
        return pages


def _patch_plugin(cls):
    return mock.patch(
        "torchsnapshot_tpu.snapshot.url_to_storage_plugin",
        side_effect=lambda url: cls(root=url.split("://")[-1]),
    )


def _recording_run(monkeypatch):
    orig = snapshot_mod._PlacementBatch.run

    def run(self):
        if self._values:
            EVENTS.append(("flush", len(self._values)))
        return orig(self)

    monkeypatch.setattr(snapshot_mod._PlacementBatch, "run", run)


def _tree(seed: float):
    return {
        f"w{i}": jnp.full((64, 8), seed + i, jnp.float32) for i in range(6)
    }


def _committed_zeros_like(tree):
    """Device-committed destinations: uncommitted leaves (plain jnp ops)
    convert via jnp.asarray and never enter a placement batch."""
    dev = jax.devices()[0]
    return {
        k: jax.device_put(np.zeros(v.shape, v.dtype), dev)
        for k, v in tree.items()
    }


def test_streaming_placement_overlaps_reads(tmp_path, monkeypatch):
    """With a tiny flush threshold, placements run between read
    completions — not one batch after all reads."""
    EVENTS.clear()
    _recording_run(monkeypatch)
    monkeypatch.setenv("TORCHSNAPSHOT_TPU_PER_RANK_IO_CONCURRENCY", "1")
    src = _tree(2.0)
    p = str(tmp_path / "snap")
    ts.Snapshot.take(p, {"m": ts.PyTreeState(src)})

    dest = {"m": ts.PyTreeState(_committed_zeros_like(src))}
    with override_restore_placement_flush_bytes(1), _patch_plugin(
        RecordingFSStoragePlugin
    ):
        ts.Snapshot(p).restore(dest)
    assert_tree_eq(dest["m"].tree, src)

    flushes = [i for i, (kind, _) in enumerate(EVENTS) if kind == "flush"]
    reads = [i for i, (kind, _) in enumerate(EVENTS) if kind == "read"]
    assert len(flushes) >= 2, EVENTS
    # At least one placement flushed before the last read completed.
    assert flushes[0] < reads[-1], EVENTS


def test_flush_disabled_places_in_one_batch(tmp_path, monkeypatch):
    """flush_bytes=0 restores the pre-streaming behavior: exactly one
    batched device_put after all reads."""
    EVENTS.clear()
    _recording_run(monkeypatch)
    src = _tree(4.0)
    p = str(tmp_path / "snap")
    ts.Snapshot.take(p, {"m": ts.PyTreeState(src)})

    dest = {"m": ts.PyTreeState(_committed_zeros_like(src))}
    with override_restore_placement_flush_bytes(0):
        ts.Snapshot(p).restore(dest)
    assert_tree_eq(dest["m"].tree, src)
    assert [e for e in EVENTS if e[0] == "flush"] == [("flush", len(src))]


def test_streaming_async_restore_roundtrip(tmp_path):
    """Async restore with per-leaf streaming matches the source."""
    src = _tree(7.0)
    p = str(tmp_path / "snap")
    ts.Snapshot.take(p, {"m": ts.PyTreeState(src)})
    dest = {"m": ts.PyTreeState(_committed_zeros_like(src))}
    with override_restore_placement_flush_bytes(1):
        pending = ts.Snapshot(p).async_restore(dest)
        pending.wait()
    assert_tree_eq(dest["m"].tree, src)


def test_streaming_with_batched_reads(tmp_path):
    """Spanning slab reads complete their member requests: streaming and
    read batching compose (merged-req completion fans out to leaves)."""
    from torchsnapshot_tpu.knobs import (
        enable_batching,
        override_slab_size_threshold_bytes,
    )

    src = _tree(9.0)
    p = str(tmp_path / "snap")
    with enable_batching(), override_slab_size_threshold_bytes(1 << 20):
        ts.Snapshot.take(p, {"m": ts.PyTreeState(src)})
        dest = {"m": ts.PyTreeState(_committed_zeros_like(src))}
        with override_restore_placement_flush_bytes(1):
            ts.Snapshot(p).restore(dest)
    assert_tree_eq(dest["m"].tree, src)


def test_streaming_sharded_restore(tmp_path):
    """Sharded-array finalizers stream too: a resharded restore under a
    tiny flush threshold stays byte-exact."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("needs a multi-device mesh")
    full = np.arange(32 * 8, dtype=np.float32).reshape(32, 8)
    mesh4 = Mesh(np.array(devs[:4]), ("x",))
    arr = jax.device_put(full, NamedSharding(mesh4, P("x")))
    p = str(tmp_path / "snap")
    ts.Snapshot.take(p, {"m": ts.PyTreeState({"w": arr})})

    mesh2 = Mesh(np.array(devs[:2]), ("x",))
    target = jax.device_put(np.zeros_like(full), NamedSharding(mesh2, P("x")))
    dest = {"m": ts.PyTreeState({"w": target})}
    with override_restore_placement_flush_bytes(1):
        ts.Snapshot(p).restore(dest)
    np.testing.assert_array_equal(np.asarray(dest["m"].tree["w"]), full)


# ---------------------------------------------------------------------------
# Pooled read destinations (dest_pool.py)
# ---------------------------------------------------------------------------


def _bits(tree):
    return {
        k: np.array(np.asarray(v).reshape(-1).view(np.uint8), copy=True)
        for k, v in tree.items()
    }


def _assert_bits(tree, bits):
    for k, want in bits.items():
        np.testing.assert_array_equal(
            np.asarray(tree[k]).reshape(-1).view(np.uint8), want, err_msg=k
        )


def _slabs(pool):
    return [s.array for slabs in pool._free.values() for s in slabs]


@pytest.fixture
def accelerator_path(monkeypatch):
    """Take the path of an accelerator on the CPU backend: say that
    placements copy, and make them copy (``device_put`` of an aligned
    numpy array may alias it here, which is why this backend never pools).
    Yields the process's pool, emptied before and after."""
    from torchsnapshot_tpu import dest_pool

    real_put = jax.device_put

    def copying_put(x, *args, **kwargs):
        copied = jax.tree_util.tree_map(
            lambda v: np.array(v) if isinstance(v, np.ndarray) else v, x
        )
        return real_put(copied, *args, **kwargs)

    monkeypatch.setattr(snapshot_mod, "_placement_copies", lambda s: True)
    monkeypatch.setattr(jax, "device_put", copying_put)
    pool = dest_pool.process_pool()
    pool.clear()
    yield pool
    pool.settle()
    pool.clear()


def test_cpu_backend_restores_into_fresh_memory(tmp_path):
    """Two restores of different checkpoints in one process on the CPU
    backend leave the first restore's arrays bit-identical: here a placed
    array may alias its host buffer, so no destination is recycled."""
    from torchsnapshot_tpu import dest_pool, telemetry

    first, second = _tree(1.0), _tree(50.0)
    ts.Snapshot.take(str(tmp_path / "a"), {"m": ts.PyTreeState(first)})
    ts.Snapshot.take(str(tmp_path / "b"), {"m": ts.PyTreeState(second)})
    dest_a = {"m": ts.PyTreeState(_committed_zeros_like(first))}
    dest_b = {"m": ts.PyTreeState(_committed_zeros_like(first))}
    with override_restore_placement_flush_bytes(1):
        ts.Snapshot(str(tmp_path / "a")).restore(dest_a)
        bits_a = _bits(dest_a["m"].tree)
        ts.Snapshot(str(tmp_path / "b")).restore(dest_b)
    _assert_bits(dest_a["m"].tree, bits_a)
    assert_tree_eq(dest_a["m"].tree, first)
    assert_tree_eq(dest_b["m"].tree, second)
    assert dest_pool.process_pool().retained_bytes() == 0
    report = telemetry.last_report("restore")
    assert report.dest_bytes_recycled is None and report.dest_bytes_fresh is None


@pytest.mark.parametrize("restores", ["sync", "async"])
def test_pooled_destinations_are_recycled(tmp_path, accelerator_path, restores):
    """Where placements copy, a second restore reads into the first one's
    slabs, every leaf stays bit-identical, and the report and the
    ``restore:dest_acquire`` spans say what was recycled."""
    from torchsnapshot_tpu import telemetry
    from torchsnapshot_tpu.telemetry import names, trace

    pool = accelerator_path
    first, second = _tree(1.0), _tree(50.0)
    nbytes = sum(v.nbytes for v in first.values())
    ts.Snapshot.take(str(tmp_path / "a"), {"m": ts.PyTreeState(first)})
    ts.Snapshot.take(str(tmp_path / "b"), {"m": ts.PyTreeState(second)})

    def restore(name):
        dest = {"m": ts.PyTreeState(_committed_zeros_like(first))}
        snap = ts.Snapshot(str(tmp_path / name))
        if restores == "async":
            snap.async_restore(dest).wait()
            return dest, telemetry.last_report("async_restore")
        snap.restore(dest)
        return dest, telemetry.last_report("restore")

    # Six leaves of one size: the cap is four of them, made new by the
    # first restore, which already reads its last two leaves into them.
    cap = 4 * nbytes // 6
    dest_a, report_a = restore("a")
    assert report_a.dest_bytes_fresh == cap
    assert report_a.dest_bytes_recycled == nbytes - cap
    assert pool.unsettled() == 0  # the restore waited for its placements
    mark = trace.get_recorder().mark()
    dest_b, report_b = restore("b")
    assert (report_b.dest_bytes_recycled, report_b.dest_bytes_fresh) == (nbytes, 0)
    assert_tree_eq(dest_a["m"].tree, first)
    assert_tree_eq(dest_b["m"].tree, second)
    assert pool.retained_bytes() == cap
    spans = [
        e["args"]
        for e in trace.get_recorder().events_since(mark)
        if e.get("name") == names.SPAN_RESTORE_DEST_ACQUIRE
    ]
    assert sum(a["bytes"] for a in spans if a["recycled"]) == nbytes
    assert all(a["recycled"] in (0, 1) and a["blob"] for a in spans)


def test_host_and_uncommitted_leaves_are_never_pooled(tmp_path, accelerator_path):
    """A host ``np.ndarray`` leaf and an uncommitted leaf (``jnp.asarray``)
    hand their buffer to the application as is: neither may be a slab."""
    pool = accelerator_path
    src = {
        "committed": jax.device_put(np.arange(512, dtype=np.float32), jax.devices()[0]),
        "uncommitted": jnp.arange(512, dtype=jnp.float32) + 1,
        "host_inplace": np.arange(512, dtype=np.float32) + 2,
        "host_reshaped": np.arange(512, dtype=np.float32) + 3,
    }
    p = str(tmp_path / "snap")
    ts.Snapshot.take(p, {"m": ts.PyTreeState(src)})
    for _ in range(2):
        dest_tree = {
            "committed": jax.device_put(np.zeros(512, np.float32), jax.devices()[0]),
            "uncommitted": jnp.zeros(512, jnp.float32),
            "host_inplace": np.zeros(512, np.float32),
            "host_reshaped": np.zeros(7, np.float32),  # replaced, not filled
        }
        dest = {"m": ts.PyTreeState(dest_tree)}
        ts.Snapshot(p).restore(dest)
        out = dest["m"].tree
        assert_tree_eq(out, src)
        assert out["host_inplace"] is dest_tree["host_inplace"]
        slabs = _slabs(pool)
        assert [s.nbytes for s in slabs] == [2048]  # the committed leaf's
        for key in ("uncommitted", "host_inplace", "host_reshaped"):
            assert not any(np.shares_memory(np.asarray(out[key]), s) for s in slabs), key


class FailsOnceFSStoragePlugin(FSStoragePlugin):
    fail_on = "w4"

    async def read_with_checksum(self, read_io):
        if read_io.path.endswith(self.fail_on):
            await asyncio.sleep(0.05)  # other reads have their slabs by now
            raise OSError("injected read failure")
        return await super().read_with_checksum(read_io)


def test_failed_restore_leaves_the_pool_usable(tmp_path, accelerator_path):
    """A read that raises mid-restore: the restore raises, its slabs are
    dropped rather than returned, and the next restore is correct."""
    pool = accelerator_path
    src = _tree(3.0)
    nbytes = sum(v.nbytes for v in src.values())
    p = str(tmp_path / "snap")
    ts.Snapshot.take(p, {"m": ts.PyTreeState(src)})
    dest = {"m": ts.PyTreeState(_committed_zeros_like(src))}
    ts.Snapshot(p).restore(dest)  # fills the pool
    cap = pool.retained_bytes()
    assert cap == 4 * nbytes // 6

    dest = {"m": ts.PyTreeState(_committed_zeros_like(src))}
    with _patch_plugin(FailsOnceFSStoragePlugin), pytest.raises(
        OSError, match="injected read failure"
    ):
        ts.Snapshot(p).restore(dest)
    pool.settle()
    assert sum(pool._out_sizes.values()) == 0
    assert pool.retained_bytes() < cap  # what the failed restore had out

    dest = {"m": ts.PyTreeState(_committed_zeros_like(src))}
    ts.Snapshot(p).restore(dest)
    assert_tree_eq(dest["m"].tree, src)
    assert pool.retained_bytes() == cap


@pytest.mark.parametrize("flush_bytes", [1, 1 << 30], ids=["flush-1B", "flush-1GiB"])
def test_one_slab_pool_does_not_deadlock(tmp_path, accelerator_path, flush_bytes):
    """A pool that holds one slab of the plan's size, under a placement
    batch that would wait for more bytes than a leaf has (or flushes every
    leaf): a read waiting for the slab makes the placer flush what it
    holds, so the slab comes back and the restore completes."""
    from torchsnapshot_tpu import telemetry
    from torchsnapshot_tpu.knobs import override_per_rank_memory_budget_bytes

    pool = accelerator_path
    src = _tree(5.0)
    leaf = next(iter(src.values())).nbytes
    p = str(tmp_path / "snap")
    ts.Snapshot.take(p, {"m": ts.PyTreeState(src)})
    dest = {"m": ts.PyTreeState(_committed_zeros_like(src))}
    # The budget clamps the cap to one destination (and admits one read
    # at a time: each finds the only slab under the leaf before it).
    with override_per_rank_memory_budget_bytes(leaf), override_restore_placement_flush_bytes(flush_bytes):
        ts.Snapshot(p).restore(dest)
    assert_tree_eq(dest["m"].tree, src)
    assert pool.retained_bytes() == leaf
    report = telemetry.last_report("restore")
    assert report.dest_bytes_fresh == leaf
    assert report.dest_bytes_recycled == (len(src) - 1) * leaf

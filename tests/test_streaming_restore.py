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


# ---------------------------------------------------------------------------
# Pooled boxes and read buffers of sharded leaves (sharded_io_preparer.py)
# ---------------------------------------------------------------------------

N_SHARDED = 6
SHARDED_SHAPE = (64, 8)
LEAF = 64 * 8 * 4


def _mesh(shape, names):
    n = int(np.prod(shape))
    return jax.sharding.Mesh(np.asarray(jax.devices()[:n]).reshape(shape), names)


def _sharded_tree(seed, sharding):
    """Half the leaves split by rows, half by columns where the layout
    has both; every element distinct within its leaf and between seeds."""
    rng = np.random.default_rng(seed)
    full = {
        f"w{i}": rng.standard_normal(SHARDED_SHAPE).astype(np.float32)
        for i in range(N_SHARDED)
    }
    return full, {k: jax.device_put(v, sharding(i)) for i, (k, v) in enumerate(full.items())}


def _sharding(mesh, row_spec, col_spec):
    from jax.sharding import NamedSharding

    return lambda i: NamedSharding(mesh, row_spec if i % 2 else col_spec)


def _layouts(name):
    """(saved, restored) shardings by leaf number."""
    from jax.sharding import PartitionSpec as P

    if name == "tp2->4":
        rows, cols = P("tp"), P(None, "tp")
        return (_sharding(_mesh((2,), ("tp",)), rows, cols),
                _sharding(_mesh((4,), ("tp",)), rows, cols))
    if name == "rows->cols-on-two-devices-each":
        # Every restored box lives on two devices (replicated over "sp").
        return (_sharding(_mesh((2,), ("tp",)), P("tp"), P("tp")),
                _sharding(_mesh((2, 2), ("sp", "tp")), P(None, "tp"), P(None, "tp")))
    assert name == "same"
    both = _sharding(_mesh((4,), ("tp",)), P("tp"), P(None, "tp"))
    return both, both


def _assert_every_shard_is_the_saved_one(tree, full):
    for key, leaf in tree.items():
        for shard in leaf.addressable_shards:
            got, want = np.asarray(shard.data), full[key][shard.index]
            assert got.shape == want.shape, (key, shard.index)
            np.testing.assert_array_equal(
                got.view(np.uint32), want.view(np.uint32), err_msg=f"{key} {shard.device}"
            )


def _restore_sharded(path, restored_sharding, seed=99):
    from torchsnapshot_tpu import telemetry

    _, live = _sharded_tree(seed, restored_sharding)
    dest = {"m": ts.PyTreeState(live)}
    ts.Snapshot(path).restore(dest)
    return dest["m"].tree, telemetry.last_report("restore")


def _dest_spans(mark):
    from torchsnapshot_tpu.telemetry import names, trace

    events = trace.get_recorder().events_since(mark)
    return (
        [e["args"] for e in events if e.get("name") == names.SPAN_RESTORE_DEST_ACQUIRE],
        [e["args"] for e in events if e.get("name") == names.SPAN_RESHARD_COPY],
    )


@pytest.mark.parametrize("layout", ["tp2->4", "rows->cols-on-two-devices-each"])
def test_resharded_restores_recycle_boxes_and_buffers(tmp_path, accelerator_path, layout):
    """Two resharding restores of different snapshots in one process: the
    second takes every box and every read buffer from what the first
    faulted in, every shard of both is the saved one at its own index bit
    for bit, and the first restore's arrays do not change under the
    second."""
    from torchsnapshot_tpu.telemetry import trace

    pool = accelerator_path
    saved, restored = _layouts(layout)
    full_a, tree_a = _sharded_tree(1, saved)
    full_b, tree_b = _sharded_tree(2, saved)
    ts.Snapshot.take(str(tmp_path / "a"), {"m": ts.PyTreeState(tree_a)})
    ts.Snapshot.take(str(tmp_path / "b"), {"m": ts.PyTreeState(tree_b)})

    out_a, report_a = _restore_sharded(str(tmp_path / "a"), restored)
    _assert_every_shard_is_the_saved_one(out_a, full_a)
    bits_a = _bits(out_a)
    # Boxes and buffers: every leaf's bytes twice (each saved shard is read
    # whole into a buffer and copied into two boxes).
    assert report_a.dest_bytes_fresh + report_a.dest_bytes_recycled == 2 * N_SHARDED * LEAF
    assert 0 < report_a.dest_bytes_fresh == pool.retained_bytes()
    assert pool.unsettled() == 0 and sum(pool._out_sizes.values()) == 0

    mark = trace.get_recorder().mark()
    out_b, report_b = _restore_sharded(str(tmp_path / "b"), restored)
    assert (report_b.dest_bytes_recycled, report_b.dest_bytes_fresh) == (2 * N_SHARDED * LEAF, 0)
    _assert_every_shard_is_the_saved_one(out_b, full_b)
    _assert_bits(out_a, bits_a)
    _assert_every_shard_is_the_saved_one(out_a, full_a)
    assert pool.retained_bytes() == report_a.dest_bytes_fresh

    acquired, copied = _dest_spans(mark)
    assert len(acquired) == 2 * N_SHARDED and not any(a["direct"] for a in acquired)
    assert all(a["recycled"] == 1 for a in acquired)
    # One read a leaf bound its boxes; every read was copied out of a buffer.
    assert sorted(a["box_bytes"] for a in acquired) == [0] * N_SHARDED + [LEAF] * N_SHARDED
    assert all(a["box_bytes_recycled"] == a["box_bytes"] for a in acquired)
    assert sum(c["bytes"] for c in copied) == sum(c["buf_bytes"] for c in copied) == N_SHARDED * LEAF


@pytest.mark.parametrize("flush_bytes", [1, 1 << 30], ids=["flush-1B", "flush-1GiB"])
def test_a_pool_of_one_leafs_boxes_and_one_buffer_does_not_deadlock(
    tmp_path, accelerator_path, flush_bytes
):
    """A cap that holds one leaf's boxes and one read buffer: every other
    leaf waits for all its boxes at once (never for half of them), the
    leaf that has its boxes always finds the room of a buffer, and its
    placement is flushed for the leaves that wait."""
    from torchsnapshot_tpu.knobs import override_per_rank_memory_budget_bytes

    pool = accelerator_path
    saved, restored = _layouts("tp2->4")
    full, tree = _sharded_tree(3, saved)
    ts.Snapshot.take(str(tmp_path / "snap"), {"m": ts.PyTreeState(tree)})
    cap = LEAF + LEAF // 2
    with override_per_rank_memory_budget_bytes(cap), override_restore_placement_flush_bytes(flush_bytes):
        out, report = _restore_sharded(str(tmp_path / "snap"), restored)
    _assert_every_shard_is_the_saved_one(out, full)
    assert pool.retained_bytes() == cap
    assert report.dest_bytes_fresh == cap
    assert report.dest_bytes_recycled == 2 * N_SHARDED * LEAF - cap


def test_a_corrupt_shard_fails_the_restore_and_leaves_the_pool_usable(tmp_path, accelerator_path):
    """A saved shard that fails its checksum: the restore raises, the boxes
    and buffers it had out are dropped rather than reused (a thread may
    still write into them), and the next restore is correct."""
    import glob

    from torchsnapshot_tpu.integrity import ChecksumError

    pool = accelerator_path
    saved, restored = _layouts("tp2->4")
    full, tree = _sharded_tree(4, saved)
    good, bad = str(tmp_path / "good"), str(tmp_path / "bad")
    ts.Snapshot.take(good, {"m": ts.PyTreeState(tree)})
    ts.Snapshot.take(bad, {"m": ts.PyTreeState(tree)})
    _restore_sharded(good, restored)  # fills the pool
    held = pool.retained_bytes()
    slabs_before = {id(s) for s in _slabs(pool)}

    (victim,) = glob.glob(f"{bad}/sharded/m/w4_0_0")
    with open(victim, "r+b") as f:
        f.seek(100)
        f.write(b"\xff\x00\xff\x00")
    with pytest.raises(ChecksumError):
        _restore_sharded(bad, restored)
    pool.settle()
    assert sum(pool._out_sizes.values()) == 0
    dropped = held - pool.retained_bytes()  # what the failed restore had out
    assert dropped > 0
    assert {id(s) for s in _slabs(pool)} < slabs_before

    out, report = _restore_sharded(good, restored)
    _assert_every_shard_is_the_saved_one(out, full)
    assert report.dest_bytes_fresh == dropped
    assert pool.retained_bytes() == held


def test_a_same_layout_sharded_restore_leases_boxes_and_no_buffer(tmp_path, accelerator_path):
    """Saved and restored under one layout: every read lands in its box,
    which is a slab of the pool; no buffer is leased, nothing is copied."""
    from torchsnapshot_tpu.telemetry import trace

    pool = accelerator_path
    saved, restored = _layouts("same")
    full, tree = _sharded_tree(5, saved)
    ts.Snapshot.take(str(tmp_path / "snap"), {"m": ts.PyTreeState(tree)})
    _, report = _restore_sharded(str(tmp_path / "snap"), restored)
    assert report.dest_bytes_fresh + report.dest_bytes_recycled == N_SHARDED * LEAF
    mark = trace.get_recorder().mark()
    out, report = _restore_sharded(str(tmp_path / "snap"), restored)
    _assert_every_shard_is_the_saved_one(out, full)
    assert (report.dest_bytes_recycled, report.dest_bytes_fresh) == (N_SHARDED * LEAF, 0)
    acquired, copied = _dest_spans(mark)
    assert not copied
    assert len(acquired) == 4 * N_SHARDED and all(a["direct"] and a["recycled"] for a in acquired)
    assert sum(a["box_bytes"] for a in acquired) == N_SHARDED * LEAF
    assert {s.nbytes for s in _slabs(pool)} == {LEAF // 4}


@pytest.mark.parametrize("target", ["cpu-backend", "host-array", "uncommitted"])
def test_sharded_leaves_nobody_may_pool_allocate_as_ever(tmp_path, request, target):
    """On the real CPU backend (no fixture: a placed array may alias its
    host buffer), and on the accelerator's path for a host ``np.ndarray``
    target and an uncommitted leaf, a sharded entry's boxes and read
    buffers are fresh allocations: the pool stays empty and the report
    counts nothing."""
    from torchsnapshot_tpu import dest_pool, telemetry

    saved, restored = _layouts("tp2->4")
    full, tree = _sharded_tree(6, saved)
    path = str(tmp_path / "snap")
    ts.Snapshot.take(path, {"m": ts.PyTreeState(tree)})
    if target == "cpu-backend":
        pool = dest_pool.process_pool()
        pool.clear()
        _, live = _sharded_tree(7, restored)
    else:
        pool = request.getfixturevalue("accelerator_path")
        make = np.zeros if target == "host-array" else jnp.zeros
        live = {k: make(SHARDED_SHAPE, np.float32) for k in full}
    for _ in range(2):
        dest = {"m": ts.PyTreeState(dict(live))}
        ts.Snapshot(path).restore(dest)
        for key, leaf in dest["m"].tree.items():
            np.testing.assert_array_equal(np.asarray(leaf), full[key], err_msg=key)
            if target == "host-array":
                assert leaf is live[key]
            if target == "uncommitted":
                assert not leaf._committed
        assert pool.retained_bytes() == 0
        report = telemetry.last_report("restore")
        assert report.dest_bytes_recycled is None and report.dest_bytes_fresh is None

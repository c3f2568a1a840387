"""Sharded-array checkpointing: write dedup, shard subdivision, and the
elastic resharding matrix.

Structural model: reference tests/test_sharded_tensor_resharding.py — write
with one spec, restore into another, compare the full array; crossed over a
matrix of source × destination shardings on the 8-device virtual mesh.
"""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import torchsnapshot_tpu as ts
from torchsnapshot_tpu.knobs import override_max_shard_size_bytes
from torchsnapshot_tpu.resharding import (
    Box,
    box_overlap,
    plan_row_slab_reads,
    row_slab_byte_window,
    subdivide_box,
    target_boxes_for_sharding,
)


def _mesh(shape, names):
    devs = jax.devices()
    needed = int(np.prod(shape))
    if len(devs) < needed:
        pytest.skip(
            f"needs {needed} devices, backend has {len(devs)} "
            f"(CPU runs force an 8-device virtual mesh via conftest)"
        )
    return Mesh(np.array(devs[:needed]).reshape(shape), names)


def _shardings():
    """A spread of GSPMD layouts over 8 devices: 1-d, 2-d, replicated mixes,
    and uneven divisions."""
    m8 = _mesh((8,), ("x",))
    m42 = _mesh((4, 2), ("a", "b"))
    m24 = _mesh((2, 4), ("a", "b"))
    return {
        "row8": NamedSharding(m8, P("x")),
        "col8": NamedSharding(m8, P(None, "x")),
        "grid42": NamedSharding(m42, P("a", "b")),
        "grid24": NamedSharding(m24, P("a", "b")),
        "rowrep": NamedSharding(m42, P("a")),  # replicated over b
        "colrep": NamedSharding(m42, P(None, "b")),  # replicated over a
        "full_replicated_grid": NamedSharding(m42, P()),
    }


_MATRIX = list(itertools.permutations(["row8", "grid42", "colrep"], 2)) + [
    ("row8", "row8"),
    ("grid42", "grid24"),
    ("col8", "rowrep"),
    ("rowrep", "col8"),
    ("grid24", "full_replicated_grid"),
]


@pytest.mark.parametrize("src_name,dst_name", _MATRIX)
def test_resharding_matrix(tmp_path, src_name, dst_name) -> None:
    shardings = _shardings()
    x = jnp.arange(32 * 24, dtype=jnp.float32).reshape(32, 24)
    xs = jax.device_put(x, shardings[src_name])
    ts.Snapshot.take(str(tmp_path), {"m": ts.PyTreeState({"w": xs})})

    target = jax.device_put(jnp.zeros((32, 24)), shardings[dst_name])
    fresh = {"m": ts.PyTreeState({"w": target})}
    ts.Snapshot(str(tmp_path)).restore(fresh)
    w = fresh["m"].tree["w"]
    assert w.sharding.is_equivalent_to(shardings[dst_name], 2)
    np.testing.assert_array_equal(np.asarray(w), np.asarray(x))


_FUZZ_MESH_SHAPES = [(8,), (4, 2), (2, 4), (2, 2, 2), (4,), (2,), (1,)]


def _rand_mesh(rng):
    shape = _FUZZ_MESH_SHAPES[rng.integers(0, len(_FUZZ_MESH_SHAPES))]
    devs = jax.devices()
    n = int(np.prod(shape))
    names = tuple(f"ax{i}" for i in range(len(shape)))
    return Mesh(np.array(devs[:n]).reshape(shape), names)


def _rand_valid_spec(rng, mesh, shape):
    """A random PartitionSpec each of whose sharded dims is divisible by
    its mesh axis (device_put's constraint — the framework itself also
    handles misaligned boundaries; see the dedicated test above)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    names = list(mesh.axis_names)
    rng.shuffle(names)
    spec = []
    for dim in shape:
        picked = None
        if rng.random() < 0.6:
            for i, n in enumerate(names):
                if dim % sizes[n] == 0:
                    picked = names.pop(i)
                    break
        spec.append(picked)
    return P(*spec)


@pytest.mark.parametrize("seed", range(16))
def test_resharding_fuzz(tmp_path, seed) -> None:
    """Property widening of the hand-picked matrix: random array shape,
    random source mesh/spec, restored under an independently random
    destination mesh/spec (different device counts included — elastic
    up and down), byte-compared. A 100-case sweep of this generator
    passed during round 4; these 16 deterministic seeds pin it."""
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    rng = np.random.default_rng(9000 + seed)
    src_mesh = _rand_mesh(rng)
    dst_mesh = _rand_mesh(rng)
    ndim = int(rng.integers(1, 4))
    shape = tuple(
        int(rng.choice([1, 2, 3, 4, 6, 8, 16, 24, 40])) for _ in range(ndim)
    )
    src_spec = _rand_valid_spec(rng, src_mesh, shape)
    dst_spec = _rand_valid_spec(rng, dst_mesh, shape)
    data = np.arange(np.prod(shape), dtype=np.float32).reshape(shape) + seed

    x = jax.device_put(jnp.asarray(data), NamedSharding(src_mesh, src_spec))
    ts.Snapshot.take(str(tmp_path), {"m": ts.PyTreeState({"w": x})})
    dest = jax.device_put(
        jnp.zeros(shape, jnp.float32), NamedSharding(dst_mesh, dst_spec)
    )
    dp = ts.PyTreeState({"w": dest})
    ts.Snapshot(str(tmp_path)).restore({"m": dp})
    np.testing.assert_array_equal(
        np.asarray(dp.tree["w"]),
        data,
        err_msg=f"{shape} {src_spec} -> {dst_spec}",
    )


def test_misaligned_shard_boundaries(tmp_path) -> None:
    """Save 5-way, restore 3-way: 6-row saved shards vs 10-row destination
    boxes — every destination draws from two saved shards with non-aligned
    boundaries (the general-overlap case the reference's 1-d chunk walk
    cannot express)."""
    devs = jax.devices()
    src = NamedSharding(Mesh(np.array(devs[:5]), ("x",)), P("x"))
    dst = NamedSharding(Mesh(np.array(devs[:3]), ("x",)), P("x"))
    x = jnp.arange(30 * 3, dtype=jnp.float32).reshape(30, 3)
    xs = jax.device_put(x, src)
    ts.Snapshot.take(str(tmp_path), {"m": ts.PyTreeState({"w": xs})})
    fresh = {"m": ts.PyTreeState({"w": jax.device_put(jnp.zeros((30, 3)), dst)})}
    ts.Snapshot(str(tmp_path)).restore(fresh)
    w = fresh["m"].tree["w"]
    assert w.sharding.is_equivalent_to(dst, 2)
    np.testing.assert_array_equal(np.asarray(w), np.asarray(x))


def test_replica_dedup_writes_each_box_once(tmp_path) -> None:
    sharding = NamedSharding(_mesh((4, 2), ("a", "b")), P(None, "b"))
    x = jnp.ones((16, 8), jnp.float32)
    xs = jax.device_put(x, sharding)
    snap = ts.Snapshot.take(str(tmp_path), {"m": ts.PyTreeState({"w": xs})})
    entry = snap.get_manifest()["0/m/w"]
    # 2-way column sharding replicated 4x: exactly 2 boxes on disk.
    assert len(entry.shards) == 2
    offsets = sorted(tuple(s.offsets) for s in entry.shards)
    assert offsets == [(0, 0), (0, 4)]


def test_shard_subdivision_knob(tmp_path) -> None:
    sharding = NamedSharding(_mesh((2, 4), ("a", "b")), P("a"))
    x = jnp.arange(64 * 16, dtype=jnp.float32).reshape(64, 16)
    xs = jax.device_put(x, sharding)
    with override_max_shard_size_bytes(1024):
        snap = ts.Snapshot.take(str(tmp_path), {"m": ts.PyTreeState({"w": xs})})
    entry = snap.get_manifest()["0/m/w"]
    # Each 32x16 f32 box is 2 KiB -> split into 2x 16-row pieces.
    assert len(entry.shards) == 4
    for shard in entry.shards:
        assert shard.sizes[0] <= 16
    fresh = {"m": ts.PyTreeState({"w": jax.device_put(jnp.zeros((64, 16)), sharding)})}
    ts.Snapshot(str(tmp_path)).restore(fresh)
    np.testing.assert_array_equal(np.asarray(fresh["m"].tree["w"]), np.asarray(x))


def test_sharded_read_object_full_assembly(tmp_path) -> None:
    sharding = NamedSharding(_mesh((8,), ("x",)), P("x", None))
    x = jnp.arange(16 * 6, dtype=jnp.bfloat16).reshape(16, 6)
    xs = jax.device_put(x, sharding)
    ts.Snapshot.take(str(tmp_path), {"m": ts.PyTreeState({"w": xs})})
    out = ts.Snapshot(str(tmp_path)).read_object("0/m/w")
    assert isinstance(out, np.ndarray)
    assert out.dtype == np.dtype("bfloat16")
    np.testing.assert_array_equal(
        np.asarray(out, np.float32), np.asarray(x, np.float32)
    )


def test_sharded_restore_shape_mismatch_raises(tmp_path) -> None:
    sharding = NamedSharding(_mesh((8,), ("x",)), P("x"))
    xs = jax.device_put(jnp.ones((16, 4)), sharding)
    ts.Snapshot.take(str(tmp_path), {"m": ts.PyTreeState({"w": xs})})
    bad_target = jax.device_put(jnp.zeros((8, 4)), sharding)
    with pytest.raises(ValueError, match="reshard"):
        ts.Snapshot(str(tmp_path)).restore(
            {"m": ts.PyTreeState({"w": bad_target})}
        )


def test_box_overlap_math() -> None:
    a = Box((0, 0), (4, 4))
    b = Box((2, 2), (4, 4))
    ov = box_overlap(a, b)
    assert ov.src_slices == (slice(2, 4), slice(2, 4))
    assert ov.dst_slices == (slice(0, 2), slice(0, 2))
    assert box_overlap(Box((0,), (4,)), Box((4,), (4,))) is None
    with pytest.raises(ValueError, match="Rank mismatch"):
        box_overlap(Box((0,), (4,)), Box((0, 0), (4, 4)))


def test_subdivide_box() -> None:
    box = Box((8, 0), (10, 4))
    pieces = subdivide_box(box, max_bytes=4 * 4 * 4, itemsize=4)  # 4 rows/piece
    assert [p.offsets[0] for p in pieces] == [8, 12, 16]
    assert sum(p.sizes[0] for p in pieces) == 10
    # 0-d / tiny boxes stay whole.
    assert subdivide_box(Box((), ()), 10, 4) == [Box((), ())]


def test_plan_row_slab_reads_geometry() -> None:
    """The shared row-band planner: trailing-sliced overlaps still ride
    a banded ranged read (the amplification fix), buffer limits split
    the band, and whole-shard bands return None (caller's whole read)."""
    shard = (32, 24)
    itemsize = 4
    row_nbytes = 24 * itemsize
    # A column-partial overlap of rows [8, 16): the band is those rows.
    ov = box_overlap(Box((0, 0), shard), Box((8, 12), (8, 12)))
    plan = plan_row_slab_reads(shard, [ov], row_nbytes)
    assert plan is not None and len(plan) == 1
    (read,) = plan
    assert read.rows == (8, 16)
    assert read.byte_range == (8 * row_nbytes, 16 * row_nbytes)
    assert read.buf_shape == (8, 24)
    (copy,) = read.copies
    assert copy.dst_rows == slice(0, 8)
    assert copy.src_slices == (slice(0, 8), slice(12, 24))
    # The strict-slab window helper refuses a trailing-sliced overlap
    # (the compat bridge's per-piece loads cannot column-slice)...
    assert row_slab_byte_window(shard, ov, row_nbytes) is None
    # ...but accepts a full-trailing one, composing with a base offset.
    full = box_overlap(Box((0, 0), shard), Box((8, 0), (8, 24)))
    assert row_slab_byte_window(shard, full, row_nbytes, base=100) == (
        100 + 8 * row_nbytes,
        100 + 16 * row_nbytes,
    )
    # Whole-shard band with no limit: None (one whole read is optimal).
    whole = box_overlap(Box((0, 0), shard), Box((0, 0), shard))
    assert plan_row_slab_reads(shard, [whole], row_nbytes) is None
    # ...unless a buffer limit forces splitting.
    split = plan_row_slab_reads(
        shard, [whole], row_nbytes, buffer_limit_bytes=8 * row_nbytes
    )
    assert split is not None
    assert [r.rows for r in split] == [(0, 8), (8, 16), (16, 24), (24, 32)]
    # 0-d shards never range.
    assert plan_row_slab_reads((), [whole], itemsize) is None


def test_plan_row_slab_reads_roundtrip_matches_direct_copy() -> None:
    """Property pin: executing a plan's copies against a fake blob
    reproduces exactly what direct whole-shard slicing would."""
    rng = np.random.default_rng(7)
    for _ in range(24):
        ndim = int(rng.integers(1, 4))
        shard = tuple(int(rng.integers(1, 9)) for _ in range(ndim))
        src = rng.standard_normal(shard).astype(np.float32)
        overlaps = []
        views = []
        for _ in range(int(rng.integers(1, 4))):
            offs = tuple(int(rng.integers(0, s)) for s in shard)
            sizes = tuple(
                int(rng.integers(1, s - o + 1)) for s, o in zip(shard, offs)
            )
            ov = box_overlap(Box(tuple(0 for _ in shard), shard), Box(offs, sizes))
            overlaps.append(ov)
            views.append(np.zeros(sizes, np.float32))
        row_nbytes = int(np.prod(shard[1:], dtype=np.int64)) * 4
        plan = plan_row_slab_reads(
            shard,
            overlaps,
            row_nbytes,
            buffer_limit_bytes=int(rng.integers(1, 5)) * row_nbytes,
        )
        if plan is None:
            for view, ov in zip(views, overlaps):
                view[...] = src[ov.src_slices]
        else:
            blob = src.tobytes()
            for read in plan:
                a, b = read.byte_range
                buf = np.frombuffer(blob[a:b], np.float32).reshape(
                    read.buf_shape
                )
                for copy in read.copies:
                    views[copy.overlap_index][copy.dst_rows] = buf[
                        copy.src_slices
                    ]
        for view, ov in zip(views, overlaps):
            np.testing.assert_array_equal(view, src[ov.src_slices])


def test_column_partial_destinations_use_ranged_reads(tmp_path) -> None:
    """A partial destination that slices a saved shard's rows AND
    columns (the per-rank view of an elastic multi-process restore)
    must pay a row-banded ranged read, not the whole shard — the read
    amplification the fan-out path's needed-window math rides on.
    Before the shared planner, any trailing-sliced overlap fell back to
    a whole-shard read."""
    from torchsnapshot_tpu.manifest import ShardedArrayEntry
    from torchsnapshot_tpu.sharded_io_preparer import (
        ShardedArrayIOPreparer,
        _LeafBoxes,
    )
    from torchsnapshot_tpu.serialization import array_size_bytes

    sharding = NamedSharding(_mesh((2,), ("x",)), P(None, "x"))  # 2 col shards
    x = jnp.arange(32 * 24, dtype=jnp.float32).reshape(32, 24)
    xs = jax.device_put(x, sharding)
    snap = ts.Snapshot.take(str(tmp_path), {"m": ts.PyTreeState({"w": xs})})
    entry = snap.get_manifest()["0/m/w"]
    assert isinstance(entry, ShardedArrayEntry)

    # One rank's destination box: rows [8, 16) of columns [0, 6) — a
    # row- and column-partial window of the first 32x12 saved shard.
    saved = entry.shards[0]
    saved_box = Box(tuple(saved.offsets), tuple(saved.sizes))
    dst_box = Box((8, 0), (8, 6))
    ov = box_overlap(saved_box, dst_box)
    view = np.zeros((8, 6), np.float32)
    boxes = _LeafBoxes(np.float32, [dst_box], arrays={dst_box: view})
    reqs = ShardedArrayIOPreparer._reqs_for_saved_shard(
        saved, saved_box, boxes, [(dst_box, ov)]
    )
    assert reqs and all(r.byte_range is not None for r in reqs)
    fetched = sum(r.byte_range[1] - r.byte_range[0] for r in reqs)
    whole = array_size_bytes(saved.sizes, saved.array.dtype)
    # 8 of 32 rows: a quarter of the shard's bytes, not all of them.
    assert fetched == whole // 4
    # And the ranged read reconstructs the exact window.
    import asyncio

    from torchsnapshot_tpu.scheduler import sync_execute_read_reqs
    from torchsnapshot_tpu.storage_plugin import url_to_storage_plugin

    loop = asyncio.new_event_loop()
    sync_execute_read_reqs(
        reqs, url_to_storage_plugin(str(tmp_path)), 10**7, 0, loop
    )
    loop.close()
    np.testing.assert_array_equal(view, np.asarray(x)[8:16, 0:6])


def test_target_boxes_for_sharding_groups_replicas() -> None:
    sharding = NamedSharding(_mesh((4, 2), ("a", "b")), P(None, "b"))
    groups = target_boxes_for_sharding(sharding, (16, 8))
    assert len(groups) == 2  # 2-way column split, replicated 4x
    assert all(len(devs) == 4 for devs in groups.values())


def test_sharded_read_respects_buffer_limit(tmp_path) -> None:
    """Regression (review finding): a memory budget must split sharded
    reads into ranged row reads rather than admitting whole-shard buffers."""
    from torchsnapshot_tpu.manifest import ShardedArrayEntry
    from torchsnapshot_tpu.sharded_io_preparer import ShardedArrayIOPreparer

    sharding = NamedSharding(_mesh((2, 4), ("a", "b")), P("a"))
    x = jnp.arange(64 * 16, dtype=jnp.float32).reshape(64, 16)
    xs = jax.device_put(x, sharding)
    snap = ts.Snapshot.take(str(tmp_path), {"m": ts.PyTreeState({"w": xs})})
    entry = snap.get_manifest()["0/m/w"]
    assert isinstance(entry, ShardedArrayEntry)

    out = np.zeros((64, 16), np.float32)
    # Each saved shard is 32x16x4B = 2 KiB; a 512B limit must split reads.
    reqs = ShardedArrayIOPreparer.prepare_read(
        entry, out, buffer_size_limit_bytes=512
    )
    assert len(reqs) > len(entry.shards)
    for req in reqs:
        assert req.byte_range is not None
        assert req.byte_range[1] - req.byte_range[0] <= 512
    # And the reads actually reconstruct the array.
    import asyncio

    from torchsnapshot_tpu.scheduler import sync_execute_read_reqs
    from torchsnapshot_tpu.storage_plugin import url_to_storage_plugin

    loop = asyncio.new_event_loop()
    sync_execute_read_reqs(
        reqs, url_to_storage_plugin(str(tmp_path)), 10**6, 0, loop
    )
    loop.close()
    np.testing.assert_array_equal(out, np.asarray(x))


def test_sharded_prepare_read_requires_np_destination(tmp_path) -> None:
    from torchsnapshot_tpu.io_preparer import prepare_read
    from torchsnapshot_tpu.manifest import ShardedArrayEntry

    sharding = NamedSharding(_mesh((8,), ("x",)), P("x"))
    xs = jax.device_put(jnp.ones((16, 4)), sharding)
    snap = ts.Snapshot.take(str(tmp_path), {"m": ts.PyTreeState({"w": xs})})
    entry = snap.get_manifest()["0/m/w"]
    with pytest.raises(ValueError, match="np.ndarray destination"):
        prepare_read(entry, obj_out=None)


def test_contiguous_in_agrees_with_numpy() -> None:
    """Whether a read lands in its box is decided before the box exists
    (a pooled box is bound when its leaf's first read comes): the geometry
    alone has to say what numpy says of the view."""
    from torchsnapshot_tpu.sharded_io_preparer import _contiguous_in

    rng = np.random.default_rng(11)
    seen = set()
    for _ in range(400):
        ndim = int(rng.integers(1, 4))
        sizes = tuple(int(rng.integers(1, 5)) for _ in range(ndim))
        starts = [int(rng.integers(0, s)) for s in sizes]
        slices = tuple(
            slice(a, int(rng.integers(a + 1, s + 1))) for a, s in zip(starts, sizes)
        )
        want = np.empty(sizes, np.float32)[slices].flags.c_contiguous
        assert _contiguous_in(sizes, slices) == want, (sizes, slices)
        seen.add(want)
    assert seen == {True, False}
    assert _contiguous_in((), ())


def test_late_boxes_nobody_bound_are_made_once_under_racing_consumers() -> None:
    """A leaf's consumers run on several executor threads; where no read
    pipeline bound the leaf's late boxes, whichever thread comes first
    makes them, and every other thread copies into the same arrays."""
    import sys
    import threading

    from torchsnapshot_tpu.sharded_io_preparer import _LeafBoxes

    boxes = [Box((0, 0), (4, 8)), Box((4, 0), (4, 8))]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for _ in range(50):
            leaf = _LeafBoxes(np.float32, boxes, late=True)
            assert leaf.unbound_sizes() == [128, 128]
            start, seen = threading.Barrier(16), []

            def consume() -> None:
                start.wait(10)
                seen.append(leaf.arrays())

            threads = [threading.Thread(target=consume) for _ in range(16)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(10)
            assert not any(t.is_alive() for t in threads)
            assert len(seen) == 16 and all(arrays is seen[0] for arrays in seen)
            assert leaf.unbound_sizes() == []
    finally:
        sys.setswitchinterval(interval)

"""A device array whose chunks are all written leaves the device whole.

A deferred-staging async take clones its jax sources on the device, one
program a device group (`io_preparer.capture_write_reqs`). A leaf written as
dim-0 chunks (the incremental layout cuts every leaf over 16 MiB) used to
have each chunk's stager slice the clone at staging: an eager device program
of its own, which queued behind the training loop's steps before its
transfer could begin. Where the chunk requests over one array tile it, the
drain now transfers the array once and writes each chunk from the host copy
(`io_preparer.stage_sources_whole`, `WriteReq.parts`). The capture, its
clone program and the digests are what they were.
"""

import asyncio
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchsnapshot_tpu as ts
from torchsnapshot_tpu import incremental, io_preparer, knobs, scheduler
from torchsnapshot_tpu.io_preparer import (
    ArrayBufferStager,
    ChunkedArrayIOPreparer,
    capture_write_reqs,
    stage_sources_whole,
)
from torchsnapshot_tpu.io_types import StoragePlugin, WriteReq
from torchsnapshot_tpu.telemetry import names, trace

SHAPE = (64, 32)  # float32: 8 KiB
CHUNK = 2048  # four chunks of 16 rows
ROW = SHAPE[1] * 4


def _leaf(i=0, shape=SHAPE):
    return jnp.arange(math.prod(shape), dtype=jnp.float32).reshape(shape) * 3 + i


def _chunk_reqs(leaf, path="m/w"):
    """The write requests of `leaf` as an incremental take's caller plans
    them behind the return: every chunk written."""
    with knobs.override_incremental_chunk_size_bytes(CHUNK):
        _, reqs = ChunkedArrayIOPreparer.prepare_write(
            leaf, path, 0, False, True, incremental=incremental._WRITE_ALL)
    return reqs


def _rows_req(arr, start, stop, path=None):
    return WriteReq(path=path or f"0/m/w_{start}",
                    buffer_stager=ArrayBufferStager(arr, True, slc=slice(start, stop)))


class _MemoryStorage:
    """Just enough of a storage plugin for `execute_write_reqs`: blobs by
    path, held as bytes."""

    def __init__(self):
        self.blobs = {}

    async def write(self, write_io):
        self.blobs[write_io.path] = bytes(write_io.buf)

    # Not fused: the pipeline checksums each blob, then writes it.
    write_with_checksum = StoragePlugin.write_with_checksum


def _execute(reqs, budget_bytes=1 << 30):
    """Run the staging and write pipeline over `reqs`; returns the blobs,
    the checksums, the `stage:d2h` spans' args, the pipeline's telemetry
    and the budget as it ended."""
    storage = _MemoryStorage()
    budget = scheduler.MemoryBudget(budget_bytes)
    rec = trace.get_recorder()
    mark = rec.mark()
    loop = asyncio.new_event_loop()
    try:
        pending = loop.run_until_complete(scheduler.execute_write_reqs(
            reqs, storage, budget_bytes, rank=0, staging_pool=budget))
        pending.sync_complete(loop)
    finally:
        loop.close()
    d2h = [e["args"] for e in rec.events_since(mark)
           if e.get("ph") == "X" and e["name"] == names.SPAN_STAGE_D2H]
    return storage.blobs, pending.checksums, d2h, pending.pipeline_telemetry(), budget


def test_the_chunk_requests_of_a_clone_become_one_request_in_the_first_ones_place():
    x, y = _leaf(0), _leaf(1, (8, 8))
    reqs = _chunk_reqs(x)
    other = WriteReq(path="0/m/y", buffer_stager=ArrayBufferStager(y, True))
    plan = [other] + reqs
    captured = capture_write_reqs(plan)
    assert (captured.clone_programs, captured.clone_leaves) == (1, 2)
    (clone,) = [c for c in captured.device_clones if c.shape == SHAPE]
    staged = stage_sources_whole(plan)
    assert len(staged) == 2 and staged[0] is other
    whole = staged[1]
    assert whole.path == "0/m/w_0" and whole.buffer_stager.slc is None
    assert whole.buffer_stager.arr is clone
    assert whole.parts == [(f"0/m/w_{16 * i}", 16 * i * ROW, 16 * (i + 1) * ROW)
                           for i in range(4)]
    assert whole.buffer_stager.get_staging_cost_bytes() == x.nbytes


@pytest.mark.parametrize("rows,whole", [
    ([(0, 16), (16, 32), (32, 48), (48, 64)], True),
    ([(48, 64), (0, 16), (32, 48), (16, 32)], True),   # any order
    ([(0, 30), (30, 64)], True),                       # uneven
    ([(0, 16), (32, 48), (48, 64)], False),            # a gap: a chunk skipped
    ([(0, 16), (16, 32), (32, 48)], False),            # short of the end
    ([(0, 20), (16, 40), (40, 64)], False),            # overlapping
    ([(0, 32), (0, 32), (32, 64)], False),             # a chunk twice
    ([(0, 64)], False),                                # one request: nothing to gain
])
def test_an_array_is_staged_whole_only_where_its_requests_tile_it(rows, whole):
    x = _leaf(2)
    reqs = [_rows_req(x, a, b) for a, b in rows]
    staged = stage_sources_whole(reqs)
    if whole:
        (req,) = staged
        assert sorted(req.parts) == sorted(
            (f"0/m/w_{a}", a * ROW, b * ROW) for a, b in rows)
    else:
        assert staged == reqs


def test_a_whole_array_request_beside_its_chunks_keeps_its_place():
    x = _leaf(3)
    whole = WriteReq(path="0/m/all", buffer_stager=ArrayBufferStager(x, True))
    reqs = _chunk_reqs(x)
    staged = stage_sources_whole([whole] + reqs)
    assert len(staged) == 2 and staged[0] is whole
    assert [p for p, _, _ in staged[1].parts] == [r.path for r in reqs]


@pytest.mark.parametrize("keep", ["over_the_plain_size", "prepare_func", "host_source"])
def test_these_chunk_requests_keep_their_own_staging(keep):
    x = _leaf(4)
    if keep == "prepare_func":
        reqs = [WriteReq(path=f"0/m/w_{a}", buffer_stager=ArrayBufferStager(
            x, True, slc=slice(a, a + 16), array_prepare_func=lambda a, _: a))
            for a in range(0, 64, 16)]
    elif keep == "host_source":
        reqs = _chunk_reqs(np.asarray(x).copy())
    else:
        reqs = _chunk_reqs(x)
    if keep == "over_the_plain_size":
        # A plain take stages an array this large a chunk at a time.
        with knobs.override_max_chunk_size_bytes(x.nbytes - 1):
            assert stage_sources_whole(reqs) == reqs
    else:
        assert stage_sources_whole(reqs) == reqs


def test_the_whole_transfer_writes_each_chunk_byte_for_byte_and_slices_nothing():
    x = _leaf(5)
    reqs = _chunk_reqs(x)
    capture_write_reqs(reqs)
    host = np.asarray(x)
    x.delete()  # every stager reads the clone
    blobs, checksums, d2h, telemetry, budget = _execute(stage_sources_whole(reqs))
    assert blobs == {f"0/m/w_{16 * i}": host[16 * i:16 * (i + 1)].tobytes() for i in range(4)}
    assert sorted(checksums) == sorted(blobs)
    assert [(a["bytes"], a[names.ARG_SLICED_BYTES]) for a in d2h] == [(host.nbytes, 0)]
    assert telemetry["blobs"] == 4 and telemetry["bytes_moved"] == host.nbytes
    assert budget.available_bytes == budget.total_bytes


def test_chunks_staged_one_by_one_slice_the_array_first():
    x = _leaf(6)
    reqs = _chunk_reqs(x)
    host = np.asarray(x)
    blobs, _, d2h, telemetry, budget = _execute(reqs)
    assert blobs == {f"0/m/w_{16 * i}": host[16 * i:16 * (i + 1)].tobytes() for i in range(4)}
    assert [a[names.ARG_SLICED_BYTES] for a in d2h] == [16 * ROW] * 4
    assert telemetry["blobs"] == 4
    assert budget.available_bytes == budget.total_bytes


def test_the_parts_give_their_reservation_back_with_the_last_of_them():
    budget = scheduler.MemoryBudget(100)
    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(budget.acquire(60))
        release = scheduler._release_together(budget, 60)
        seen = []
        for part in (20, 30, 10):
            loop.run_until_complete(release(part))
            seen.append(budget.available_bytes)
    finally:
        loop.close()
    assert seen == [40, 40, 100]


def test_the_clone_program_clones_a_chunked_source_whole():
    x = _leaf(7)
    cache = {}
    programs, leaves = io_preparer._clone_device_groups([x], cache, group_max=512)
    assert (programs, leaves) == (1, 1)
    assert cache[id(x)].shape == SHAPE


def test_the_drained_queue_path_stays_one_source_a_program():
    xs = [_leaf(8), _leaf(9), _leaf(10, (8, 8))]
    reqs = [r for i, x in enumerate(xs) for r in _chunk_reqs(x, f"m/x{i}")]
    captured = capture_write_reqs(reqs, queue_drained=True)
    assert (captured.clone_programs, captured.clone_leaves) == (3, 3)


def test_a_plain_async_take_of_a_leaf_over_the_chunk_size_keeps_its_chunk_slices(tmp_path):
    tree = {"w": _leaf(11), "v": _leaf(12, (8, 8))}
    want = {k: np.asarray(v).tobytes() for k, v in tree.items()}
    rec = trace.get_recorder()
    mark = rec.mark()
    with knobs.override_max_chunk_size_bytes(CHUNK):
        pending = ts.Snapshot.async_take(str(tmp_path / "s"), {"m": ts.PyTreeState(tree)})
        for v in tree.values():
            v.delete()
        snapshot = pending.wait()
    d2h = [e["args"] for e in rec.events_since(mark)
           if e.get("ph") == "X" and e["name"] == names.SPAN_STAGE_D2H]
    assert sorted(a[names.ARG_SLICED_BYTES] for a in d2h) == [0] + [16 * ROW] * 4
    out = {"m": ts.PyTreeState({"w": jnp.zeros(SHAPE, jnp.float32),
                                "v": jnp.zeros((8, 8), jnp.float32)})}
    snapshot.restore(out)
    assert {k: np.asarray(v).tobytes() for k, v in out["m"].tree.items()} == want


@pytest.mark.parametrize("behind", [True, False], ids=["behind", "waiting"])
def test_an_incremental_take_of_a_changed_chunked_leaf_stages_it_whole(
        tmp_path, monkeypatch, behind):
    """Every chunk of `w` changed: its requests tile it, on the path that
    decides behind the return and on the one that decides first, and one
    transfer writes its four chunks; `b`, unchanged, is referenced."""
    monkeypatch.setattr(incremental, "room_for_clone", lambda flattened: behind)
    w, b = _leaf(13), jnp.arange(5, dtype=jnp.int32)
    rec = trace.get_recorder()
    with knobs.override_incremental_chunk_size_bytes(CHUNK):
        base = str(tmp_path / "s0")
        ts.Snapshot.async_take(base, {"m": ts.PyTreeState({"w": w, "b": b})},
                               record_digests=True).wait()
        live = {"m": ts.PyTreeState({"w": w + 1.0, "b": b + 0})}
        want = np.asarray(live["m"].tree["w"]).tobytes()
        mark = rec.mark()
        pending = ts.Snapshot.async_take(str(tmp_path / "s1"), live, incremental_base=base)
        for leaf in live["m"].tree.values():
            leaf.delete()
        snapshot = pending.wait()
    d2h = [e["args"] for e in rec.events_since(mark)
           if e.get("ph") == "X" and e["name"] == names.SPAN_STAGE_D2H]
    assert [(a["bytes"], a[names.ARG_SLICED_BYTES]) for a in d2h] == [(64 * ROW, 0)]
    manifest = snapshot.get_manifest()
    assert [c.array.location for c in manifest["0/m/w"].chunks] == [
        f"0/m/w_{16 * i}" for i in range(4)]
    assert manifest["0/m/b"].location == "../s0/0/m/b"
    fresh = {"m": ts.PyTreeState({"w": jnp.zeros(SHAPE, jnp.float32),
                                  "b": jnp.zeros(5, jnp.int32)})}
    snapshot.restore(fresh)
    assert np.asarray(fresh["m"].tree["w"]).tobytes() == want
    assert np.asarray(fresh["m"].tree["b"]).tolist() == list(range(5))


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs 2 devices")
def test_the_pieces_of_a_device_shard_stage_the_shard_whole(tmp_path):
    """An incremental take writes a shard over the chunk size as row pieces
    of it; where every piece is written, the shard leaves whole."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:2]), ("x",))
    sharding = NamedSharding(mesh, P("x", None))
    x = jax.device_put(_leaf(14), sharding)
    want = np.asarray(x).tobytes()
    rec = trace.get_recorder()
    mark = rec.mark()
    with knobs.override_incremental_chunk_size_bytes(CHUNK // 2):  # two pieces a shard
        pending = ts.Snapshot.async_take(str(tmp_path / "s"), {"m": ts.PyTreeState({"x": x})},
                                         record_digests=True)
        x.delete()
        snapshot = pending.wait()
    d2h = [e["args"] for e in rec.events_since(mark)
           if e.get("ph") == "X" and e["name"] == names.SPAN_STAGE_D2H]
    assert sorted((a["bytes"], a[names.ARG_SLICED_BYTES]) for a in d2h) == [(32 * ROW, 0)] * 2
    out = {"m": ts.PyTreeState({"x": jax.device_put(jnp.zeros(SHAPE, jnp.float32), sharding)})}
    snapshot.restore(out)
    assert np.asarray(out["m"].tree["x"]).tobytes() == want


def test_the_drain_stages_a_tiling_clone_whole():
    """The deferred drain waits for the clones, then gives a clone whose
    chunk requests tile it one transfer."""
    x = _leaf(15)
    reqs = _chunk_reqs(x)
    captured = capture_write_reqs(reqs)
    host = np.asarray(x)
    x.delete()
    storage = _MemoryStorage()
    work = scheduler.DeferredIOWork(
        write_reqs=reqs, storage=storage, memory_budget_bytes=1 << 30, rank=0,
        device_clones=captured.device_clones, clone_programs=captured.clone_programs)
    rec = trace.get_recorder()
    mark = rec.mark()
    loop = asyncio.new_event_loop()
    try:
        work.sync_complete(loop)
    finally:
        loop.close()
    d2h = [(e["args"]["bytes"], e["args"][names.ARG_SLICED_BYTES]) for e in rec.events_since(mark)
           if e.get("ph") == "X" and e["name"] == names.SPAN_STAGE_D2H]
    assert d2h == [(host.nbytes, 0)]
    assert storage.blobs == {
        f"0/m/w_{16 * i}": host[16 * i:16 * (i + 1)].tobytes() for i in range(4)}


"""Where each element goes when a train state is restored under another
layout: a plain numpy reference, independent of ``resharding.py``.

Before the save ``full = np.asarray(leaf)``; after ``restore_latest`` into a
state built on another mesh, every addressable shard of every leaf must be
``full[shard.index]``, bit for bit. The whole train state goes through
``CheckpointManager.save`` / ``restore_latest`` as chipbench's
``restore_loop`` calls them, at its toy widths. Beside it the arithmetic of
the counters on the reshard spans (``reshard:plan``, ``reshard:copy``,
``reshard:assemble``, ``direct`` on ``restore:dest_acquire``,
``bytes_by_device`` on ``restore:place``) and their place in the restore's
stage table.
"""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

import torchsnapshot_tpu as ts
from torchsnapshot_tpu.flatten import flatten
from torchsnapshot_tpu.manifest import ShardedArrayEntry
from torchsnapshot_tpu.models import TransformerConfig, init_train_state
from torchsnapshot_tpu.telemetry import critpath, names, trace

# chipbench/workload.py's REHEARSAL_SIZES and REHEARSAL_LAYERS.
TOY = TransformerConfig(vocab_size=512, d_model=256, n_heads=4, n_layers=2, d_ff=1024)
SAVED_STEP = 2
# (dp, sp, tp) saved -> restored. The first is the benchmark's cell; the
# fourth saves from one device, so no leaf is saved in shards.
LAYOUTS = [
    ((1, 2, 2), (1, 1, 4)),
    ((1, 1, 4), (1, 2, 2)),
    ((1, 2, 2), (1, 1, 1)),
    ((1, 1, 1), (1, 2, 2)),
    ((2, 1, 2), (1, 2, 2)),
]
RESHARD_SPANS = (names.SPAN_RESHARD_PLAN, names.SPAN_RESHARD_COPY, names.SPAN_RESHARD_ASSEMBLE)


def _mesh(shape):
    n = int(np.prod(shape))
    return Mesh(np.asarray(jax.devices()[:n]).reshape(shape), ("dp", "sp", "tp"))


def _app_state(state, step):
    return {
        "params": ts.PyTreeState(state.params),
        "opt": ts.PyTreeState(state.opt_state),
        "progress": ts.StateDict(step=step),
        "rng": ts.RngState(state.rng),
    }


def _leaves(app):
    """The two trees' leaves by the path the manifest knows them under."""
    out = {}
    for key in ("params", "opt"):
        out.update(flatten(app[key].state_dict(), prefix=key)[1])
    return out


def _bits(a):
    """The array's elements as unsigned integers of their own width."""
    a = np.ascontiguousarray(a)
    return a.view(np.dtype(f"u{a.dtype.itemsize}"))


@pytest.fixture(scope="module", params=LAYOUTS, ids=lambda p: f"{p[0]}->{p[1]}".replace(" ", ""))
def resharded(request, tmp_path_factory):
    saved_mesh, restored_mesh = request.param
    saved = _app_state(init_train_state(TOY, seed=7, mesh=_mesh(saved_mesh)), SAVED_STEP)
    full = {k: np.asarray(v) for k, v in _leaves(saved).items()}
    mgr = ts.CheckpointManager(str(tmp_path_factory.mktemp("reshard")), keep_last_n=1)
    mgr.save(SAVED_STEP, saved)
    live = _app_state(init_train_state(TOY, seed=8, mesh=_mesh(restored_mesh)), 0)
    shardings = {k: v.sharding for k, v in _leaves(live).items()}
    recorder = trace.get_recorder()
    mark = recorder.mark()
    assert mgr.restore_latest(live) == SAVED_STEP
    events = [e for e in recorder.events_since(mark) if e.get("ph") == "X"]
    (op,) = [e["op"] for e in events if e["name"] == names.SPAN_RESTORE]
    manifest = ts.Snapshot(mgr.step_path(SAVED_STEP)).get_manifest()
    return {
        "full": full, "restored": _leaves(live), "shardings": shardings,
        "events": [e for e in events if e["op"] == op], "op": op,
        "sharded_entries": {k.split("/", 1)[1]: e for k, e in manifest.items()
                            if isinstance(e, ShardedArrayEntry)},
        "live": live,
    }


def _sum(events, name, arg):
    return sum(e["args"][arg] for e in events if e["name"] == name)


def test_every_addressable_shard_is_the_saved_one_at_its_own_index(resharded):
    full, restored = resharded["full"], resharded["restored"]
    assert restored.keys() == full.keys() and len(full) == 3 * 15 + 1
    assert resharded["live"]["progress"]["step"] == SAVED_STEP
    for key, leaf in restored.items():
        assert leaf.sharding == resharded["shardings"][key], key
        assert leaf.shape == full[key].shape and leaf.dtype == full[key].dtype, key
        for shard in leaf.addressable_shards:
            got, want = np.asarray(shard.data), full[key][shard.index]
            assert got.shape == want.shape, (key, shard.index)
            assert np.array_equal(_bits(got), _bits(want)), (key, shard.device, shard.index)


def test_the_reshard_counters_add_up(resharded):
    events, entries = resharded["events"], resharded["sharded_entries"]
    plans = [e for e in events if e["name"] == names.SPAN_RESHARD_PLAN]
    assert len(plans) == len(entries)
    # Every distinct box of every leaf saved in shards, once: what the host
    # has to fill before placement (a box two devices share is filled once).
    boxes = 0
    for path in entries:
        distinct = {tuple((s.start, s.stop) for s in shard.index): shard.data.nbytes
                    for shard in resharded["restored"][path].addressable_shards}
        boxes += sum(distinct.values())
    needed = _sum(events, names.SPAN_RESHARD_PLAN, "bytes_needed")
    assert needed == boxes
    assert _sum(events, names.SPAN_RESHARD_PLAN, "dest_boxes") >= len(entries)
    # Each needed byte arrives one way: copied out of a read buffer, or read
    # straight into its box.
    copied = _sum(events, names.SPAN_RESHARD_COPY, "bytes")
    direct = sum(e["args"]["bytes"] for e in events
                 if e["name"] == names.SPAN_RESTORE_DEST_ACQUIRE and e["args"]["direct"]
                 and e["args"]["blob"].startswith("sharded/"))
    assert copied + direct == needed
    # Every saved shard overlaps some box of a whole restore, and is read once.
    saved = sum(int(np.prod(s.sizes)) * np.dtype(s.array.dtype).itemsize
                for entry in entries.values() for s in entry.shards)
    to_read = _sum(events, names.SPAN_RESHARD_PLAN, "bytes_to_read")
    assert to_read >= saved
    assert _sum(events, names.SPAN_RESHARD_COPY, "buf_bytes") + direct == to_read
    assert _sum(events, names.SPAN_RESHARD_PLAN, "saved_shards") == sum(
        len(entry.shards) for entry in entries.values())
    assert _sum(events, names.SPAN_RESHARD_PLAN, "reads") == sum(
        e["name"] == names.SPAN_RESTORE_DEST_ACQUIRE and e["args"]["blob"].startswith("sharded/")
        for e in events)
    assert _sum(events, names.SPAN_RESHARD_ASSEMBLE, "bytes") >= needed


def test_the_reshard_spans_are_in_the_stage_table_with_an_op_id(resharded):
    events, op = resharded["events"], resharded["op"]
    table = critpath.stage_tables(events)[op]
    assert table["kind"] == "restore"
    if not resharded["sharded_entries"]:
        # Saved from one device: dense entries, placed under the new sharding
        # by `device_put`; the reshard path has nothing to do.
        assert not set(RESHARD_SPANS) & set(table["stages"])
        return
    for name in (names.SPAN_RESHARD_PLAN, names.SPAN_RESHARD_ASSEMBLE):
        assert table["stages"][name]["count"] == len(resharded["sharded_entries"]), name
    copies = [e for e in events if e["name"] == names.SPAN_RESHARD_COPY]
    assert len(copies) == table["stages"].get(names.SPAN_RESHARD_COPY, {"count": 0})["count"]
    parents = {e["bseq"]: e["name"] for e in events}
    for e in events:
        if e["name"] in RESHARD_SPANS:
            assert e["op"] == op and e["parent"]
    assert {parents[e["parent"]] for e in copies} <= {names.SPAN_LEAF_CONSUME}
    assert table["stages"][names.SPAN_RESHARD_ASSEMBLE]["bytes"] == _sum(
        events, names.SPAN_RESHARD_ASSEMBLE, "bytes")


def test_a_placement_onto_several_devices_says_what_each_device_got(resharded):
    places = [e["args"] for e in resharded["events"]
              if e["name"] == names.SPAN_RESTORE_PLACE and e["args"]["bytes"]]
    by_device = {}
    for args in places:
        for device, nbytes in args.get("bytes_by_device", {}).items():
            by_device[device] = by_device.get(device, 0) + nbytes
    want = {}
    for leaf in resharded["restored"].values():
        if not getattr(leaf, "_committed", True):
            continue
        for shard in leaf.addressable_shards:
            want[str(shard.device.id)] = want.get(str(shard.device.id), 0) + shard.data.nbytes
    if len(want) == 1:
        assert not by_device
        return
    rng = resharded["live"]["rng"].keys
    extra = {d: n - want[d] for d, n in by_device.items()}
    # Beside the two trees: the RNG key, the same few bytes on every device.
    assert set(by_device) == set(want) and len(set(extra.values())) == 1
    assert 0 <= next(iter(extra.values())) <= 2 * np.asarray(jax.random.key_data(rng)).nbytes


def test_the_reshard_spans_are_charged_to_segments_that_exist():
    assert critpath.segment_for(names.SPAN_RESHARD_PLAN) == critpath.SEG_PLAN
    assert critpath.segment_for(names.SPAN_RESHARD_COPY) == critpath.segment_for(
        names.SPAN_LEAF_CONSUME) == critpath.SEG_READ_DRAIN
    assert critpath.segment_for(names.SPAN_RESHARD_ASSEMBLE) == critpath.SEG_PLACEMENT

"""A capture sized to the device's room.

A deferred-staging async take clones its jax sources on the device before it
returns. Where the state leaves no room for a whole clone beside what the
process needs above it (a train step's gradients and activations), the take
clones the sources that fit and copies the others to the host before it
returns (`io_preparer.capture_write_reqs`). The CPU backend keeps no account
of its memory, so every case here fakes `memory_stats`; each deletes the
sources the moment `async_take` returns, as a donating step does, and holds
the restore to the bits.
"""

import logging
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchsnapshot_tpu as ts
from torchsnapshot_tpu import incremental
from torchsnapshot_tpu.telemetry import names, trace

N = 6
SHAPE = (64, 32)
LEAF = 4 * math.prod(SHAPE)  # float32
MARGIN = incremental.room_margin(LEAF)
GIB = 1 << 30
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _tree(seed=0):
    return {f"w{i}": jnp.arange(math.prod(SHAPE), dtype=jnp.float32).reshape(SHAPE) + i + seed
            for i in range(N)}


LIMIT = 8 * GIB


class FakeStats:
    """`memory_stats` of every device of `LIMIT` bytes: what the process's
    programs have reserved at most (`reserve`, `peak_bytes_reserved`), and
    `room` what clones may use beyond that and what is in use."""

    def __init__(self, reserve=2 * GIB):
        self.reserve, self.calls = reserve, 0
        self.room = 0

    def __call__(self, device):
        self.calls += 1
        return {"bytes_limit": LIMIT, "bytes_in_use": LIMIT - self.reserve - self.room,
                "peak_bytes_in_use": LIMIT - self.reserve - self.room,
                "peak_bytes_reserved": self.reserve}


@pytest.fixture
def stats(monkeypatch):
    fake = FakeStats()
    monkeypatch.setattr(incremental, "memory_stats", fake)
    return fake


def _take_delete_restore(path, tree, **take_kwargs):
    """An async take of `tree` whose leaves are deleted right after the call
    returns; the restore is compared with the bits the leaves held. Returns
    the take's spans by name."""
    want = {k: np.array(np.asarray(v)) for k, v in tree.items()}
    rec = trace.get_recorder()
    mark = rec.mark()
    pending = ts.Snapshot.async_take(str(path), {"m": ts.PyTreeState(tree)}, **take_kwargs)
    for v in tree.values():
        v.delete()
    snapshot = pending.wait()
    events = [e for e in rec.events_since(mark) if e["ph"] == "X"]
    (stage,) = [e for e in events if e["name"] == names.SPAN_ASYNC_TAKE_STAGE]
    spans = {}
    for e in events:
        if e["op"] == stage["op"]:
            spans.setdefault(e["name"], []).append(e)
    out = {"m": ts.PyTreeState({k: jnp.zeros(SHAPE, jnp.float32) for k in want})}
    snapshot.restore(out)
    for k, v in want.items():
        got = np.asarray(out["m"].tree[k])
        assert got.dtype == v.dtype and got.tobytes() == v.tobytes(), k
    return spans


def _capture_args(spans):
    (capture,) = spans[names.SPAN_DEVICE_CAPTURE]
    return capture["args"]


@pytest.mark.parametrize("fit", [0, 1, 3, N - 1])
def test_a_state_over_the_room_clones_what_fits_and_copies_the_rest(tmp_path, stats, caplog,
                                                                     fit):
    stats.room = fit * LEAF + MARGIN
    with caplog.at_level(logging.INFO, logger="torchsnapshot_tpu"):
        spans = _take_delete_restore(tmp_path / "s", _tree())
    assert not [r for r in caplog.records if r.levelno >= logging.WARNING]
    assert any("no device room for a whole clone" in r.getMessage() for r in caplog.records)
    args = _capture_args(spans)
    assert args["clone_bytes"] == fit * LEAF
    assert args["host_copy_bytes"] == (N - fit) * LEAF
    assert args["clone_room_bytes"] == stats.room
    assert args["clone_leaves"] == fit and args["fallback_leaves"] == 0
    assert len(spans.get(names.SPAN_CAPTURE_CLONE, [])) == (1 if fit else 0)
    copies = spans[names.SPAN_CAPTURE_HOST_COPY]
    assert [e["args"] for e in copies] == [{"kind": "no_room", "bytes": LEAF}] * (N - fit)
    # Inside the call: the copies end before async_take returns.
    (stage,) = spans[names.SPAN_ASYNC_TAKE_STAGE]
    assert max(e["ts"] + e["dur"] for e in copies) <= stage["ts"] + stage["dur"]


def test_chunked_sources_leave_whole_where_cloned_and_are_cut_on_the_host_where_not(
        tmp_path, stats):
    """A state of chunked leaves over the room: a cloned leaf leaves the
    device in one transfer that writes its four chunks; a leaf copied to
    the host inside the call is written a chunk at a time from that copy.
    No transfer slices a device array."""
    from torchsnapshot_tpu import knobs

    stats.room = 2 * LEAF + MARGIN
    with knobs.override_incremental_chunk_size_bytes(LEAF // 4):
        spans = _take_delete_restore(tmp_path / "s", _tree(), record_digests=True)
    args = _capture_args(spans)
    assert (args["clone_bytes"], args["host_copy_bytes"]) == (2 * LEAF, (N - 2) * LEAF)
    assert (args["clone_programs"], args["clone_leaves"]) == (1, 2)
    copies = spans[names.SPAN_CAPTURE_HOST_COPY]
    assert [e["args"] for e in copies] == [{"kind": "no_room", "bytes": LEAF}] * (N - 2)
    d2h = [e["args"] for e in spans[names.SPAN_STAGE_D2H]]
    assert [(a["bytes"], a[names.ARG_SLICED_BYTES]) for a in d2h] == [(LEAF, 0)] * 2


def test_the_reserve_is_the_programs_reservation_read_at_every_take(tmp_path, stats):
    """The reserve is `peak_bytes_reserved` as each take reads it: a program
    that reserved more since (a larger step) leaves the clone less room,
    whatever the first take saw."""
    stats.room = 2 * LEAF + MARGIN
    spans = _take_delete_restore(tmp_path / "a", _tree())
    assert _capture_args(spans)["clone_bytes"] == 2 * LEAF
    stats.reserve += LEAF
    stats.room -= LEAF
    spans = _take_delete_restore(tmp_path / "b", _tree(1))
    assert _capture_args(spans)["clone_room_bytes"] == LEAF + MARGIN
    assert _capture_args(spans)["clone_bytes"] == LEAF


def test_a_save_before_any_program_reserved_copies_every_source_and_warns(tmp_path, stats,
                                                                         caplog):
    """No program has reserved memory yet (a save before the first step):
    what the step will need is unknown, so a state over the room clones
    nothing, and says so at WARNING; one with room is cloned whole."""
    stats.reserve, stats.room = 0, 3 * LEAF + MARGIN
    with caplog.at_level(logging.INFO, logger="torchsnapshot_tpu"):
        args = _capture_args(_take_delete_restore(tmp_path / "a", _tree()))
    assert (args["clone_bytes"], args["host_copy_bytes"]) == (0, N * LEAF)
    (warning,) = [r for r in caplog.records if r.levelno >= logging.WARNING]
    assert "no program has reserved memory" in warning.getMessage()
    stats.room = N * LEAF + MARGIN
    args = _capture_args(_take_delete_restore(tmp_path / "b", _tree(1)))
    assert (args["clone_bytes"], args["host_copy_bytes"]) == (N * LEAF, 0)


@pytest.mark.parametrize("later_room", [0, LEAF // 2], ids=["same-room", "half-a-leaf-more"])
def test_the_split_is_the_same_from_save_to_save_and_compiles_nothing_new(
        tmp_path, stats, later_room):
    """The split is the longest prefix of the take's sources that fits the
    room, so a state saved again and again beside the same room clones the
    same sources through the same program, compiled once."""
    stats.room = 2 * LEAF + MARGIN
    first = _capture_args(_take_delete_restore(tmp_path / "a", _tree()))
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *a, **kw: compiles.append(name) if name == COMPILE_EVENT else None)
    stats.room += later_room
    tree = _tree(2)
    jax.block_until_ready(tree)
    mark = len(compiles)
    second = _capture_args(_take_delete_restore(tmp_path / "b", tree))
    keys = ("clone_bytes", "host_copy_bytes", "clone_leaves", "clone_programs")
    assert {k: second[k] for k in keys} == {k: first[k] for k in keys}
    # Restoring compiles nothing either: no program came in anywhere.
    assert compiles[mark:] == []


def test_a_plan_that_no_longer_fits_is_made_anew(tmp_path, stats):
    stats.room = 3 * LEAF + MARGIN
    assert _capture_args(_take_delete_restore(tmp_path / "a", _tree()))["clone_leaves"] == 3
    stats.room = LEAF + MARGIN
    assert _capture_args(_take_delete_restore(tmp_path / "b", _tree(1)))["clone_leaves"] == 1


def test_a_state_with_room_is_cloned_whole_through_one_program(tmp_path, stats):
    stats.room = N * LEAF + incremental.room_margin(N * LEAF)
    spans = _take_delete_restore(tmp_path / "s", _tree())
    args = _capture_args(spans)
    assert (args["clone_programs"], args["clone_leaves"], args["fallback_leaves"]) == (1, N, 0)
    assert (args["clone_bytes"], args["host_copy_bytes"]) == (N * LEAF, 0)
    assert names.SPAN_CAPTURE_HOST_COPY not in spans
    (clone,) = spans[names.SPAN_CAPTURE_CLONE]
    assert clone["args"] == {"kind": "device", "bytes": N * LEAF, "leaves": N}
    # One memory_stats a device is what the decision costs, a take's and
    # the restore's (a key).
    assert stats.calls == 2 * len({d for v in _tree().values() for d in v.devices()})


def test_without_an_account_every_source_is_cloned(tmp_path, monkeypatch):
    """The CPU backend keeps no account: nothing limits the clone, and the
    take carries no room."""
    spans = _take_delete_restore(tmp_path / "s", _tree())
    args = _capture_args(spans)
    assert (args["clone_bytes"], args["host_copy_bytes"]) == (N * LEAF, 0)
    assert "clone_room_bytes" not in args


def test_a_manager_save_of_a_state_over_the_room_restores_bit_for_bit(tmp_path, stats):
    stats.room = 2 * LEAF + MARGIN
    tree = _tree(5)
    want = {k: np.array(np.asarray(v)) for k, v in tree.items()}
    mgr = ts.CheckpointManager(str(tmp_path / "ckpt"), keep_last_n=1)
    pending = mgr.async_save(7, {"m": ts.PyTreeState(tree)})
    for v in tree.values():
        v.delete()
    pending.wait()
    out = {"m": ts.PyTreeState({k: jnp.zeros(SHAPE, jnp.float32) for k in want})}
    assert mgr.restore_latest(out) == 7
    for k, v in want.items():
        assert np.asarray(out["m"].tree[k]).tobytes() == v.tobytes(), k


def _live_over_room(stats, tmp_path, room):
    """A snapshot of one tree, and another live tree to restore it into,
    with (or without) room on the device for both."""
    stats.room = 10 * N * LEAF
    tree = _tree(6)
    want = {k: np.array(np.asarray(v)) for k, v in tree.items()}
    snapshot = ts.Snapshot.take(str(tmp_path / "s"), {"m": ts.PyTreeState(tree)})
    live = _tree(7)
    # What a restore sees free is bytes_limit - bytes_in_use alone.
    stats.reserve = 0
    stats.room = N * LEAF + incremental.room_margin(N * LEAF) if room else N * LEAF
    return snapshot, want, live


@pytest.mark.parametrize("room", [True, False], ids=["room", "no-room"])
def test_a_restore_without_room_frees_the_arrays_it_replaces_after_its_reads(
        tmp_path, stats, caplog, room):
    """A state that fills the chip is restored in place of itself: where
    the device lacks room for the restored arrays beside the ones they
    replace, every read lands on the host and is verified first, and only
    then are those freed and the new ones placed; with room they are left
    alone and the placement streams, as before."""
    snapshot, want, live = _live_over_room(stats, tmp_path, room)
    out = {"m": ts.PyTreeState(dict(live))}
    with caplog.at_level(logging.INFO, logger="torchsnapshot_tpu"):
        snapshot.restore(out)
    assert not [r for r in caplog.records if r.levelno >= logging.WARNING]
    for k, v in want.items():
        assert np.asarray(out["m"].tree[k]).tobytes() == v.tobytes(), k
    assert all(v.is_deleted() for v in live.values()) == (not room)
    assert not any(v.is_deleted() for v in live.values()) == room


def test_a_restore_without_room_that_fails_its_reads_leaves_the_state_whole(tmp_path, stats):
    """A blob that fails its checksum fails the restore before any of the
    application's arrays is freed: it holds the arrays it held."""
    from torchsnapshot_tpu.integrity import ChecksumError

    snapshot, _, live = _live_over_room(stats, tmp_path, room=False)
    held = {k: np.array(np.asarray(v)) for k, v in live.items()}
    blobs = [p for p in (tmp_path / "s").rglob("*") if p.is_file() and p.stat().st_size >= LEAF]
    assert blobs
    for blob in blobs:
        with open(blob, "r+b") as f:
            f.seek(100)
            f.write(b"\xff\x00\xff\x00")
    out = {"m": ts.PyTreeState(dict(live))}
    with pytest.raises(ChecksumError):
        snapshot.restore(out)
    for k, v in held.items():
        assert not live[k].is_deleted(), k
        assert np.asarray(out["m"].tree[k]).tobytes() == v.tobytes(), k

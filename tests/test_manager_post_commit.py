"""The manager's after-commit work (index, retention, history, the tuner's
decision) for an ``async_save`` runs as the tail of the take's commit thread,
after the marker is written and before ``done()`` turns true; ``wait()``
joins, installs the tuner's vector, and retries what raised.

What must hold: a loop that only polls ``done()`` gets its steps indexed and
retained; the index never lists a step whose marker does not exist; exactly
one history row a step whoever waits, however often; two saves in flight
index in the order their commits end; the base of an incremental take in
flight survives the retention of the save before it.
"""

import json
import threading
import time

import numpy as np
import pytest

import torchsnapshot_tpu as ts
from torchsnapshot_tpu import knobs
from torchsnapshot_tpu.manager import INDEX_BACKUP_BLOB, INDEX_BLOB, _step_dirname
from torchsnapshot_tpu.snapshot import SNAPSHOT_METADATA_FNAME
from torchsnapshot_tpu.telemetry import history, names, trace
from torchsnapshot_tpu.test_utils import MarkerWrites, run_multiprocess

COMMIT_THREAD = "snapshot-commit"
DEADLINE_S = 60.0


def _state(value: float, frozen: float = 1.0, n: int = 1 << 12):
    return {"s": ts.PyTreeState({"frozen": np.full((n,), frozen, np.float32),
                                 "w": np.full((n,), value, np.float32)})}


def _poll_done(pending) -> None:
    """What a loop that never calls ``wait()`` does."""
    deadline = time.monotonic() + DEADLINE_S
    while not pending.done():
        assert time.monotonic() < deadline, "the commit thread never finished"
        time.sleep(0.002)


def _rows(root) -> list:
    return history.load_history(history.history_path_for(str(root)))


@pytest.fixture
def markers(monkeypatch):
    return MarkerWrites(monkeypatch)


def _index_spans(mark: int) -> list:
    events = [e for e in trace.get_recorder().events_since(mark) if e.get("ph") == "X"]
    commit_tids = {e["tid"] for e in events if e["name"] == names.SPAN_ASYNC_TAKE_COMMIT}
    return [{"on": e["args"].get("on"), "step": e["args"]["step"],
             "on_commit_thread": e["tid"] in commit_tids, "op": e["op"]}
            for e in events if e["name"] == names.SPAN_MANAGER_INDEX]


def test_done_alone_indexes_retains_and_records(tmp_path) -> None:
    """Never a ``wait()``: the index lists the step, retention under
    ``keep_last_n=1`` has removed the step before, history holds one row a
    step."""
    with knobs.override_history_max_records(16):
        mgr = ts.CheckpointManager(str(tmp_path), keep_last_n=1)
        for step in (1, 2, 3):
            _poll_done(mgr.async_save(step, _state(float(step))))
            assert mgr.all_steps() == [step]
            gone = tmp_path / _step_dirname(step - 1)
            assert not [f for f in gone.rglob("*") if f.is_file()]
            assert [r["step"] for r in _rows(tmp_path)] == list(range(1, step + 1))
    dst = _state(0.0)
    assert mgr.restore_latest(dst) == 3
    np.testing.assert_array_equal(dst["s"].tree["w"], _state(3.0)["s"].tree["w"])


@pytest.mark.parametrize("entry", ["async_save", "save"])
def test_index_span_says_where_it_ran(tmp_path, monkeypatch, entry) -> None:
    """``manager:index`` carries ``on="commit"`` and runs on the
    ``snapshot-commit`` thread for ``async_save``; ``on="caller"``, on the
    calling thread, under ``save()``. One function, two callers."""
    threads = []
    mgr = ts.CheckpointManager(str(tmp_path))
    commit_step = mgr._commit_step

    def watched(*args, **kwargs):
        threads.append(threading.current_thread().name)
        return commit_step(*args, **kwargs)

    monkeypatch.setattr(mgr, "_commit_step", watched)
    with knobs.enable_telemetry():
        mark = trace.get_recorder().mark()
        if entry == "async_save":
            pending = mgr.async_save(1, _state(1.0))
            _poll_done(pending)
            spans = _index_spans(mark)  # before any wait(): it has run
            pending.wait()
        else:
            mgr.save(1, _state(1.0))
            spans = _index_spans(mark)
        assert _index_spans(mark) == spans  # wait() indexed nothing again
    (span,) = spans
    assert span["step"] == 1 and span["op"]
    if entry == "async_save":
        assert span["on"] == "commit" and span["on_commit_thread"]
        assert threads == [COMMIT_THREAD]
    else:
        assert span["on"] == "caller" and not span["on_commit_thread"]
        assert threads == [threading.current_thread().name]


def test_step_enters_the_index_between_marker_and_done(tmp_path, markers) -> None:
    """With the commit held open at the marker's write the step is in no
    index slot; it is in the index once ``done()`` reads true: the index
    never lists a step whose marker does not exist."""
    mgr = ts.CheckpointManager(str(tmp_path))
    mgr.save(1, _state(1.0))
    gate = markers.hold(_step_dirname(2))
    pending = mgr.async_save(2, _state(2.0))
    pending.wait(phase="staged")
    time.sleep(0.05)
    assert not pending.done()
    assert not (tmp_path / _step_dirname(2) / SNAPSHOT_METADATA_FNAME).exists()
    assert mgr.all_steps() == [1]
    for slot in (INDEX_BLOB, INDEX_BACKUP_BLOB):
        assert json.loads((tmp_path / slot).read_text())["steps"] == [1]
    gate.set()
    _poll_done(pending)
    assert (tmp_path / _step_dirname(2) / SNAPSHOT_METADATA_FNAME).exists()
    assert mgr.all_steps() == [1, 2]


def test_two_in_flight_index_in_the_order_their_commits_end(tmp_path, markers) -> None:
    """Commits that end in reverse order leave an index that lists both,
    one retention pass and one history row a step."""
    with knobs.enable_telemetry(), knobs.override_history_max_records(16):
        mgr = ts.CheckpointManager(str(tmp_path), keep_last_n=2)
        first, second = markers.hold(_step_dirname(1)), markers.hold(_step_dirname(2))
        mark = trace.get_recorder().mark()
        p1 = mgr.async_save(1, _state(1.0))
        p2 = mgr.async_save(2, _state(2.0))
        second.set()
        _poll_done(p2)
        assert mgr.all_steps() == [2] and not p1.done()
        first.set()
        _poll_done(p1)
        assert mgr.all_steps() == [1, 2]
        p1.wait(), p2.wait()
        events = trace.get_recorder().events_since(mark)
        retention = [e["args"]["step"] for e in events
                     if e.get("ph") == "X" and e["name"] == names.SPAN_MANAGER_RETENTION]
        assert retention == [2, 1]
        assert [r["step"] for r in _rows(tmp_path)] == [2, 1]
    for step in (1, 2):
        dst = _state(0.0)
        mgr.restore(step, dst)
        np.testing.assert_array_equal(dst["s"].tree["w"], _state(float(step))["s"].tree["w"])


def test_failed_commit_indexes_nothing(tmp_path, markers) -> None:
    """A failed take never reaches the hook: no index entry, no history
    row, and ``wait()`` raises the take's error, every time."""
    with knobs.override_history_max_records(16):
        mgr = ts.CheckpointManager(str(tmp_path), keep_last_n=1)
        mgr.save(1, _state(1.0))
        markers.broken.append(_step_dirname(2))
        pending = mgr.async_save(2, _state(2.0))
        _poll_done(pending)
        for _ in range(2):
            with pytest.raises(OSError, match="planted: marker of step_0000000002"):
                pending.wait()
        assert mgr.all_steps() == [1]
        assert (tmp_path / _step_dirname(1) / SNAPSHOT_METADATA_FNAME).exists()
        assert [r["step"] for r in _rows(tmp_path)] == [1]
    assert mgr.restore_latest(_state(0.0)) == 1


def test_after_commit_error_leaves_the_take_committed_and_wait_retries(
    tmp_path, monkeypatch
) -> None:
    """An ``_after_commit`` that raises on the commit thread does not fail
    the take: ``done()`` turns true, ``wait()`` runs it once more on its own
    thread and raises what that raises, and the ``wait()`` after that
    indexes exactly once (one history row)."""
    with knobs.override_history_max_records(16):
        mgr = ts.CheckpointManager(str(tmp_path))
        commit_step = mgr._commit_step
        calls = []

        def unreadable_twice(*args, **kwargs):
            calls.append(threading.current_thread().name)
            if len(calls) <= 2:
                raise RuntimeError("checkpoint index unreadable (planted)")
            return commit_step(*args, **kwargs)

        monkeypatch.setattr(mgr, "_commit_step", unreadable_twice)
        pending = mgr.async_save(1, _state(1.0))
        _poll_done(pending)
        assert (tmp_path / _step_dirname(1) / SNAPSHOT_METADATA_FNAME).exists()
        assert mgr.all_steps() == [] and _rows(tmp_path) == []
        with pytest.raises(RuntimeError, match="index unreadable"):
            pending.wait()
        assert mgr.all_steps() == []
        assert pending.wait() is not None
        assert pending.wait() is not None
        me = threading.current_thread().name
        assert calls == [COMMIT_THREAD, me, me]
        assert mgr.all_steps() == [1]
        assert [r["step"] for r in _rows(tmp_path)] == [1]


def test_tuner_decides_behind_the_return_and_installs_in_wait(tmp_path) -> None:
    """The decision is on the state file once ``done()`` reads true; the
    overrides, which change a take's geometry, are not visible before
    ``wait()`` and are after it; a blocking ``save()`` installs before it
    returns."""
    with knobs.enable_autotune():
        mgr = ts.CheckpointManager(str(tmp_path))
        pending = mgr.async_save(1, _state(1.0))
        _poll_done(pending)
        decided = json.loads((tmp_path / ".tuner-state.json").read_text())
        assert [d["step"] for d in decided["decisions"]] == [1]
        assert knobs.get_tuner_overrides() == {}
        pending.wait()
        installed = knobs.get_tuner_overrides()
        assert installed
        pending.wait()  # installs nothing again
        assert knobs.get_tuner_overrides() == installed
        knobs.clear_tuner_overrides()
        mgr.save(2, _state(2.0))
        assert knobs.get_tuner_overrides()


def test_two_waits_from_two_threads_record_one_row(tmp_path, monkeypatch) -> None:
    """Also when the commit thread's pass raised and both waiters find the
    work still to do."""
    with knobs.override_history_max_records(16):
        mgr = ts.CheckpointManager(str(tmp_path))
        commit_step = mgr._commit_step

        def fails_on_the_commit_thread(*args, **kwargs):
            if threading.current_thread().name == COMMIT_THREAD:
                raise RuntimeError("planted")
            time.sleep(0.05)  # the other waiter arrives meanwhile
            return commit_step(*args, **kwargs)

        monkeypatch.setattr(mgr, "_commit_step", fails_on_the_commit_thread)
        pending = mgr.async_save(1, _state(1.0))
        _poll_done(pending)
        results = []
        waiters = [threading.Thread(target=lambda: results.append(pending.wait()))
                   for _ in range(2)]
        for t in waiters:
            t.start()
        for t in waiters:
            t.join(DEADLINE_S)
        assert len(results) == 2 and all(r is not None for r in results)
        assert mgr.all_steps() == [1]
        assert [r["step"] for r in _rows(tmp_path)] == [1]


def test_incremental_take_in_flight_keeps_its_base(tmp_path, markers) -> None:
    """``keep_last_n=1``: save 3 resolved step 1 as its base before step 2
    was indexed; step 2 references nothing of step 1, so its retention
    would delete step 1 under the take in flight. The manager knows the
    bases of its own handles: step 1 is pinned, step 3 restores bit for
    bit, and step 2 goes when step 3 commits."""
    mgr = ts.CheckpointManager(str(tmp_path), keep_last_n=1)
    mgr.save(1, _state(1.0), incremental=True)
    second, third = markers.hold(_step_dirname(2)), markers.hold(_step_dirname(3))
    p2 = mgr.async_save(2, _state(2.0, frozen=5.0), incremental=False)
    p3 = mgr.async_save(3, _state(3.0), incremental=True)
    assert mgr._bases_in_flight == {3: 1}
    second.set()
    _poll_done(p2)
    assert mgr.all_steps() == [2]
    assert (tmp_path / _step_dirname(1) / SNAPSHOT_METADATA_FNAME).exists()
    third.set()
    _poll_done(p3)
    assert mgr.all_steps() == [3] and mgr._bases_in_flight == {}
    assert not (tmp_path / _step_dirname(2) / SNAPSHOT_METADATA_FNAME).exists()
    dst = _state(0.0, frozen=0.0)
    assert mgr.restore_latest(dst) == 3
    for leaf, want in (("w", 3.0), ("frozen", 1.0)):
        np.testing.assert_array_equal(dst["s"].tree[leaf], _state(want, want)["s"].tree[leaf])
    # The base that a later take references nothing of goes with the next pass.
    mgr.save(4, _state(4.0, frozen=4.0), incremental=False)
    assert mgr.all_steps() == [4]
    assert not (tmp_path / _step_dirname(1) / SNAPSHOT_METADATA_FNAME).exists()


def _overlapping_saves_worker(pg, root: str):
    from torchsnapshot_tpu import knobs as _knobs
    from torchsnapshot_tpu.tuner import state as _tuner_state, tunables as _tunables

    with _knobs.enable_autotune():
        mgr = ts.CheckpointManager(root, pg=pg)
        state = {"s": ts.PyTreeState({"w": np.full((2048,), float(pg.rank), np.float32)})}
        # No wait() between them: each commit thread's exchange of the
        # tuner's vector falls among the next save's collectives, in an
        # order the two ranks do not share.
        handles = [mgr.async_save(step, state) for step in range(4)]
        applied = []
        for pending in handles:
            pending.wait()
            applied.append(dict(_tunables.current_vector()))
        steps = mgr.all_steps()
        st = _tuner_state.load_state(root) if pg.rank == 0 else None
        return applied, steps, sorted(d["step"] for d in st.decisions) if st else None


def test_commit_threads_exchange_the_tuner_vector_off_the_op_sequence(tmp_path) -> None:
    """Two ranks, saves that overlap: the tuner's store exchange runs on
    commit threads, keyed by the take's nonce (``PGWrapper.keyed``), so it
    never takes a place in the op sequence that the callers' collectives
    count on; both ranks install the same vectors."""
    results = run_multiprocess(
        _overlapping_saves_worker, nproc=2, args=(str(tmp_path / "ckpt"),)
    )
    (applied0, steps0, decided), (applied1, steps1, _) = results
    assert applied0 == applied1
    assert steps0 == steps1 == [0, 1, 2, 3]
    assert decided == [0, 1, 2, 3]

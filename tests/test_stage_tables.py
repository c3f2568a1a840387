"""Op ids, parents and the per-op stage table.

The flight recorder stamps every span with the operation it belongs to
(``op``) and the span that caused it (``parent``); ``critpath`` selects
an operation's spans by that id and, beside the critical-path partition,
says per stage who was busy (``stage_tables``). A real async save and a
restore on the CPU backend must yield every stage span with both stamps,
across the executor hops, and leave next to none of the wall unattributed;
under a profiler session the same spans are on the XPlane and the two
clocks can be aligned (``trace.xplane_offset_us``).
"""

import asyncio
import glob
import mmap
import os
import resource
import statistics
import threading
import time
import types
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchsnapshot_tpu as ts
from torchsnapshot_tpu import knobs, telemetry
from torchsnapshot_tpu.telemetry import critpath, history, names, trace
from torchsnapshot_tpu.utils import tracing

TAKE, RESTORE = names.SPAN_TAKE, names.SPAN_RESTORE
STAGE, COMMIT = names.SPAN_ASYNC_TAKE_STAGE, names.SPAN_ASYNC_TAKE_COMMIT

# Every span of the issue's table A, by the operation that emits it.
SAVE_SPANS = {
    names.SPAN_TAKE_PLAN, names.SPAN_DEVICE_CAPTURE, names.SPAN_CAPTURE_CLONE,
    names.SPAN_CAPTURE_HOST_COPY, names.SPAN_CAPTURE_OBJECT, names.SPAN_CAPTURE_READY,
    names.SPAN_STAGE_D2H, names.SPAN_COMMIT_FINALIZE, names.SPAN_MANAGER_INDEX,
    names.SPAN_MANAGER_RETENTION, names.SPAN_MANAGER_TUNE,
    names.SPAN_TELEMETRY_REPORT,
}
RESTORE_SPANS = {
    names.SPAN_RESTORE_PLAN, names.SPAN_VERIFY_BLOB, names.SPAN_RESTORE_PLACE,
    names.SPAN_RESTORE_APPLY, names.SPAN_TELEMETRY_REPORT,
}
# Spans opened first thing on an executor thread: their stamps cross a hop.
EXECUTOR_ONLY = {
    names.SPAN_LEAF_STAGE, names.SPAN_FS_NATIVE_WRITE, names.SPAN_FS_NATIVE_READ,
}


def ev(name, ts_us, dur_us, bseq, op=0, tid=0, **args):
    return {"ph": "X", "name": name, "ts": ts_us, "dur": dur_us, "bseq": bseq,
            "seq": bseq, "tid": tid, "op": op, "parent": op, "args": args}


def unstamped(event):
    return {k: v for k, v in event.items() if k not in ("op", "parent")}


# ---------------------------------------------------------------------------
# stage_tables on synthetic events
# ---------------------------------------------------------------------------


def test_busy_is_the_union_and_thread_seconds_the_sum():
    events = [
        ev(RESTORE, 0, 1_000_000, 1, op=1, path="/s"),
        # Three reads: two overlap, one follows a gap.
        ev(names.SPAN_STORAGE_READ, 100_000, 300_000, 2, op=1, tid=1, bytes=10),
        ev(names.SPAN_STORAGE_READ, 200_000, 300_000, 3, op=1, tid=2, bytes=20),
        ev(names.SPAN_STORAGE_READ, 700_000, 100_000, 4, op=1, tid=1, bytes=30),
        ev(names.SPAN_RESTORE_PLACE, 450_000, 100_000, 5, op=1, bytes=60),
    ]
    table = critpath.stage_tables(events)[1]
    assert table["kind"] == "restore" and table["wall_s"] == pytest.approx(1.0)
    reads = table["stages"][names.SPAN_STORAGE_READ]
    assert reads == {"count": 3, "busy_s": pytest.approx(0.5),
                     "thread_s": pytest.approx(0.7), "bytes": 60, "max_open": 2}
    place = table["stages"][names.SPAN_RESTORE_PLACE]
    assert place["busy_s"] == place["thread_s"] == pytest.approx(0.1)
    assert place["max_open"] == 1
    # Covered: [0.1, 0.55) and [0.7, 0.8).
    assert table["unattributed_s"] == pytest.approx(1.0 - 0.45 - 0.1)
    # Busiest stage first: that is the order an operator reads.
    assert list(table["stages"])[0] == names.SPAN_STORAGE_READ


def test_back_to_back_spans_are_never_open_together():
    events = [
        ev(TAKE, 0, 100, 1, op=1),
        ev(names.SPAN_STORAGE_WRITE, 10, 20, 2, op=1),
        ev(names.SPAN_STORAGE_WRITE, 30, 20, 3, op=1),
    ]
    row = critpath.stage_tables(events)[1]["stages"][names.SPAN_STORAGE_WRITE]
    assert row["max_open"] == 1 and row["busy_s"] == row["thread_s"]


def test_spans_after_the_envelope_belong_to_the_op_and_do_not_cover_it():
    events = [
        ev(RESTORE, 0, 1_000, 1, op=1),
        ev(names.SPAN_STORAGE_READ, 0, 600, 2, op=1),
        ev(names.SPAN_TELEMETRY_REPORT, 1_000, 500, 3, op=1),
    ]
    table = critpath.stage_tables(events)[1]
    assert table["stages"][names.SPAN_TELEMETRY_REPORT]["thread_s"] == pytest.approx(0.0005)
    assert table["wall_s"] == pytest.approx(0.001)
    assert table["unattributed_s"] == pytest.approx(0.0004)


def test_two_overlapping_ops_are_told_apart_by_op():
    """An async commit still draining while the next take stages: by time
    the second take's window holds the first one's writes."""
    events = [
        ev(STAGE, 0, 100_000, 1, op=1, path="/a"),
        ev(COMMIT, 100_000, 900_000, 2, op=1, path="/a"),
        ev(names.SPAN_STORAGE_WRITE, 200_000, 700_000, 3, op=1, tid=1, bytes=7),
        ev(STAGE, 500_000, 100_000, 10, op=10, path="/b"),
        ev(names.SPAN_CAPTURE_CLONE, 510_000, 80_000, 11, op=10, bytes=3),
        ev(COMMIT, 600_000, 900_000, 12, op=10, path="/b"),
        ev(names.SPAN_STORAGE_WRITE, 1_000_000, 400_000, 13, op=10, tid=2, bytes=5),
    ]
    tables = critpath.stage_tables(events)
    assert set(tables) == {1, 10}
    first, second = tables[1], tables[10]
    assert first["kind"] == second["kind"] == "async_take"
    assert first["wall_s"] == pytest.approx(1.0) and second["wall_s"] == pytest.approx(1.0)
    assert first["stages"][names.SPAN_STORAGE_WRITE]["bytes"] == 7
    assert names.SPAN_CAPTURE_CLONE not in first["stages"]
    assert second["stages"][names.SPAN_STORAGE_WRITE] == {
        "count": 1, "busy_s": pytest.approx(0.4), "thread_s": pytest.approx(0.4),
        "bytes": 5, "max_open": 1}
    assert second["stages"][names.SPAN_CAPTURE_CLONE]["bytes"] == 3
    # The critical path of the second take is not gated by the first's write.
    cp = critpath.critical_path_from_events(events, "async_take", op=10)
    assert cp["wall_s"] == pytest.approx(1.0)
    assert cp["segments"]["write_drain"] == pytest.approx(0.4)
    assert cp["segments"]["device_capture"] == pytest.approx(0.08)


def test_events_without_op_fall_back_to_the_window():
    """Files written before the recorder stamped ids: an envelope is an op,
    an async commit joins the stage envelope of its path, spans belong by
    overlap."""
    stamped = [
        ev(STAGE, 0, 100, 1, path="/a"),
        ev(names.SPAN_CAPTURE_CLONE, 10, 50, 2),
        ev(COMMIT, 100, 400, 3, path="/a"),
        ev(names.SPAN_STORAGE_WRITE, 150, 200, 4, tid=1),
        ev(RESTORE, 1_000, 300, 5, path="/a"),
        ev(names.SPAN_STORAGE_READ, 1_050, 100, 6, tid=1),
        ev(names.SPAN_STORAGE_READ, 5_000, 100, 7, tid=1),  # in no window
    ]
    tables = critpath.stage_tables([unstamped(e) for e in stamped])
    assert sorted(t["kind"] for t in tables.values()) == ["async_take", "restore"]
    take = next(t for t in tables.values() if t["kind"] == "async_take")
    restore = next(t for t in tables.values() if t["kind"] == "restore")
    assert take["wall_s"] == pytest.approx(0.0005)
    assert set(take["stages"]) == {names.SPAN_CAPTURE_CLONE, names.SPAN_STORAGE_WRITE}
    assert restore["stages"][names.SPAN_STORAGE_READ]["count"] == 1
    # The same holds for spans rebuilt from a Chrome trace file.
    doc = trace.chrome_trace([unstamped(e) for e in stamped], {})
    from_doc = critpath.stage_tables(trace.spans_from_chrome(doc))
    assert sorted(t["kind"] for t in from_doc.values()) == ["async_take", "restore"]
    # ... and the critical path, as it did before ids existed.
    cp = critpath.critical_path_from_events([unstamped(e) for e in stamped], "restore")
    assert cp["segments"]["read_drain"] == pytest.approx(0.0001)


def test_chrome_export_round_trips_the_ids():
    rec = trace.SpanRecorder(capacity=64)
    envelope = rec.begin_op(TAKE, path="/x")
    with rec.span(names.SPAN_TAKE_PLAN):
        pass
    rec.end(envelope)
    events = rec.events_since(0)
    spans = trace.spans_from_chrome(trace.chrome_trace(events, rec.tid_names()))
    by_name = {s["name"]: s for s in spans}
    take, plan = by_name[TAKE], by_name[names.SPAN_TAKE_PLAN]
    assert take["op"] == take["bseq"] == plan["op"] == plan["parent"] != 0
    assert critpath.stage_tables(spans)[take["op"]]["stages"][names.SPAN_TAKE_PLAN]["count"] == 1


# ---------------------------------------------------------------------------
# The recorder's op id and parent
# ---------------------------------------------------------------------------


def test_spans_carry_the_envelopes_op_and_their_callers_parent():
    rec = trace.SpanRecorder(capacity=64)
    outside = rec.begin(names.SPAN_STORAGE_READ)
    rec.end(outside)
    envelope = rec.begin_op(RESTORE, path="/x")
    op = trace.current_op()
    outer = rec.begin(names.SPAN_RESTORE_PLAN)
    inner = rec.begin(names.SPAN_STORAGE_READ)
    rec.end(inner)
    rec.end(outer)
    sibling = rec.begin(names.SPAN_RESTORE_APPLY)
    rec.instant(names.INSTANT_STORAGE_RETRY)
    rec.end(sibling)
    rec.end(envelope)
    assert trace.current_op() == 0
    by_bseq = {e["bseq"]: e for e in rec.events_since(0)}
    events = sorted(by_bseq.values(), key=lambda e: e["bseq"])
    before, env, plan, read, apply_, retry = events
    assert (before["op"], before["parent"]) == (0, 0)
    assert env["op"] == env["bseq"] == op and env["parent"] == 0
    assert (plan["op"], plan["parent"]) == (op, env["bseq"])
    assert (read["op"], read["parent"]) == (op, plan["bseq"])
    assert (apply_["op"], apply_["parent"]) == (op, env["bseq"])
    assert (retry["op"], retry["parent"]) == (op, apply_["bseq"])


def test_a_later_envelope_joins_the_op_on_another_thread():
    rec = trace.SpanRecorder(capacity=64)
    stage = rec.begin_op(STAGE, path="/x")
    op = trace.current_op()
    rec.end(stage)
    seen = {}

    def commit():
        seen["before"] = trace.current_op()
        envelope = rec.begin_op(COMMIT, op, path="/x")
        with rec.span(names.SPAN_STORAGE_WRITE):
            pass
        rec.end(envelope)
        seen["after"] = trace.current_op()

    t = threading.Thread(target=commit)
    t.start()
    t.join()
    assert seen == {"before": 0, "after": 0}
    by_name = {e["name"]: e for e in rec.events_since(0)}
    assert by_name[COMMIT]["op"] == op and by_name[COMMIT]["parent"] == op
    assert by_name[names.SPAN_STORAGE_WRITE]["op"] == op
    assert by_name[names.SPAN_STORAGE_WRITE]["parent"] == by_name[COMMIT]["bseq"]
    table = critpath.stage_tables(rec.events_since(0))
    assert list(table) == [op] and table[op]["kind"] == "async_take"


def test_tasks_inherit_and_run_in_executor_carries_the_context():
    rec = trace.get_recorder()
    mark = rec.mark()
    pool = ThreadPoolExecutor(max_workers=2)

    def work(name):
        with tracing.trace_annotation(name):
            return trace.current_op()

    async def child():
        with rec.span(names.SPAN_PIPELINE_STAGE):
            carried = await tracing.run_in_executor(pool, work, names.SPAN_LEAF_STAGE)
            loop = asyncio.get_running_loop()
            dropped = await loop.run_in_executor(pool, work, names.SPAN_LEAF_CONSUME)
        return carried, dropped

    async def main():
        return await asyncio.gather(child(), child())

    with tracing.op_annotation(TAKE, path="/x"):
        op = trace.current_op()
        loop = asyncio.new_event_loop()
        try:
            results = loop.run_until_complete(main())
        finally:
            loop.close()
    pool.shutdown()
    assert results == [(op, 0), (op, 0)]
    events = rec.events_since(mark)
    stages = {e["bseq"]: e for e in events if e["name"] == names.SPAN_PIPELINE_STAGE}
    assert len(stages) == 2 and all(e["op"] == op for e in stages.values())
    carried = [e for e in events if e["name"] == names.SPAN_LEAF_STAGE]
    # Each executor span's parent is the pipeline span of the task that sent it.
    assert sorted(e["parent"] for e in carried) == sorted(stages)
    assert all(e["op"] == op and e["tid"] not in {s["tid"] for s in stages.values()}
               for e in carried)
    assert all((e["op"], e["parent"]) == (0, 0)
               for e in events if e["name"] == names.SPAN_LEAF_CONSUME)


def test_op_scope_attributes_later_work_and_ends_are_idempotent():
    rec = trace.get_recorder()
    mark = rec.mark()
    envelope = tracing.begin(RESTORE, path="/x")
    op = trace.current_op()
    tracing.end(envelope)
    tracing.end(envelope)  # the ``finally`` after an early close
    assert trace.current_op() == 0
    with trace.op_scope(op):
        with tracing.trace_annotation(names.SPAN_TELEMETRY_REPORT, kind="restore"):
            pass
    with trace.op_scope(0):
        with tracing.trace_annotation(names.SPAN_MANAGER_TUNE):
            pass
    assert trace.current_op() == 0
    events = {e["name"]: e for e in rec.events_since(mark)}
    assert len([e for e in rec.events_since(mark) if e["name"] == RESTORE]) == 1
    assert (events[names.SPAN_TELEMETRY_REPORT]["op"],
            events[names.SPAN_TELEMETRY_REPORT]["parent"]) == (op, op)
    assert events[names.SPAN_MANAGER_TUNE]["op"] == 0


def test_annotate_adds_args_known_after_the_work():
    rec = trace.get_recorder()
    mark = rec.mark()
    with tracing.trace_annotation(names.SPAN_CAPTURE_OBJECT, kind="dict") as span:
        span.annotate(bytes=12)
    (event,) = rec.events_since(mark)
    assert event["args"] == {"kind": "dict", "bytes": 12}


# ---------------------------------------------------------------------------
# The kernel's account on the spans that move bytes and on the envelopes
# ---------------------------------------------------------------------------

USER, SYS, FAULTS = names.USAGE_ARGS
THP = "/sys/kernel/mm/transparent_hugepage/enabled"
# A process that has never faulted is a kernel that keeps no count (gVisor,
# which the chip's machine runs): ``fault_bytes`` is then absent everywhere.
COUNTS_FAULTS = resource.getrusage(resource.RUSAGE_SELF).ru_minflt > 0


def _one_span(name, work, envelope=False):
    """The args of one span of ``name`` around ``work()``, as
    ``trace_annotation`` records it (an envelope through begin / end)."""
    rec = trace.get_recorder()
    mark = rec.mark()
    if envelope:
        span = tracing.begin(name, path="/x")
        try:
            work()
        finally:
            tracing.end(span)
            tracing.end(span)  # the ``finally`` after an early close
    else:
        with tracing.trace_annotation(name, bytes=1):
            work()
    (event,) = [e for e in rec.events_since(mark) if e["name"] == name]
    return event["args"]


def _burn(seconds):
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


def test_a_sampled_span_counts_the_pages_it_touches_first():
    if os.path.exists(THP) and "[always]" in open(THP).read():
        pytest.skip("transparent huge pages are 'always': a fault is 2 MiB, not a page")
    if not COUNTS_FAULTS:
        pytest.skip("this kernel counts no minor faults: ru_minflt is 0 for the whole process")
    size = 64 << 20
    fresh = mmap.mmap(-1, size)
    try:
        def touch():
            np.frombuffer(fresh, np.uint8)[:: resource.getpagesize()] = 1

        first = _one_span(names.SPAN_STAGE_D2H, touch)
        again = _one_span(names.SPAN_STAGE_D2H, touch)
    finally:
        fresh.close()
    assert size // 2 <= first[FAULTS] <= size + (8 << 20), first
    assert again[FAULTS] < size // 16, again


@pytest.mark.parametrize("name,envelope", [
    (names.SPAN_FS_NATIVE_WRITE, False), (names.SPAN_STAGE_D2H, False),
    (RESTORE, True), (COMMIT, True)])
def test_a_sampled_span_tells_computing_from_waiting(name, envelope):
    spun = _one_span(name, lambda: _burn(0.05), envelope)
    assert spun[USER] + spun[SYS] >= 40_000, spun
    # A sleeping thread spends nothing; a process's other threads may (an
    # envelope reads all of them), so only the thread's account is held low.
    slept = _one_span(name, lambda: time.sleep(0.05), envelope)
    assert {USER, SYS} <= set(slept) and (FAULTS in slept) == COUNTS_FAULTS
    if not envelope:
        assert slept[USER] + slept[SYS] < 5_000, slept


def _counting_resource(monkeypatch, counts_faults=True, **without):
    """``resource`` as utils/tracing.py sees it, its ``getrusage`` calls
    counted by target; ``without`` names attributes this platform lacks,
    and a kernel that does not count faults reports none, ever."""
    calls = []

    def getrusage(who):
        calls.append(who)
        usage = resource.getrusage(who)
        if counts_faults:
            return usage
        return types.SimpleNamespace(
            ru_utime=usage.ru_utime, ru_stime=usage.ru_stime, ru_minflt=0)

    fake = types.SimpleNamespace(
        getrusage=getrusage, getpagesize=resource.getpagesize,
        **{k: getattr(resource, k) for k in ("RUSAGE_THREAD", "RUSAGE_SELF")
           if k not in without})
    monkeypatch.setattr(tracing, "resource", fake)
    return calls


@pytest.mark.parametrize("name,envelope,who", [
    (names.SPAN_STAGE_D2H, False, "RUSAGE_THREAD"),
    (names.SPAN_FS_NATIVE_PWRITEV, False, "RUSAGE_THREAD"),
    (names.SPAN_FS_NATIVE_DIRECT_WRITE, False, "RUSAGE_THREAD"),
    (names.SPAN_FS_NATIVE_WRITE, False, "RUSAGE_THREAD"),
    (COMMIT, True, "RUSAGE_SELF"), (RESTORE, True, "RUSAGE_SELF"),
    # Outside the two sets: the stall's envelope, and the spans around
    # the sampled ones.
    (STAGE, True, None), (TAKE, True, None), (names.SPAN_LEAF_STAGE, False, None),
    (names.SPAN_STORAGE_WRITE, False, None), (names.SPAN_CAPTURE_CLONE, False, None),
    # Sampled once, measured, and out again: a restore's byte-moving spans.
    (names.SPAN_FS_NATIVE_READ, False, None), (names.SPAN_RESTORE_PLACE, False, None),
    (names.SPAN_RESHARD_COPY, False, None)])
def test_only_the_listed_names_pay_for_the_account(monkeypatch, name, envelope, who):
    calls = _counting_resource(monkeypatch)
    args = _one_span(name, lambda: None, envelope)
    if who is None:
        assert calls == [] and not set(names.USAGE_ARGS) & set(args)
    else:
        # One sample each side, the second end of an envelope none.
        assert calls == [getattr(resource, who)] * 2
        assert {USER, SYS} <= set(args)


def test_a_span_that_may_cross_an_await_is_never_sampled(monkeypatch):
    calls = _counting_resource(monkeypatch)
    rec = trace.get_recorder()
    mark = rec.mark()
    with rec.span(names.SPAN_STAGE_D2H, bytes=1):
        pass
    (event,) = rec.events_since(mark)
    assert calls == [] and event["args"] == {"bytes": 1}


@pytest.mark.parametrize("name,envelope,missing", [
    (names.SPAN_STAGE_D2H, False, "RUSAGE_THREAD"), (RESTORE, True, "RUSAGE_SELF")])
def test_a_platform_without_the_account_records_nothing(monkeypatch, name, envelope, missing):
    calls = _counting_resource(monkeypatch, **{missing: True})
    args = _one_span(name, lambda: None, envelope)
    assert calls == [] and not set(names.USAGE_ARGS) & set(args)
    # Every reader of the table finds no such key, not a zero.
    op = ev(RESTORE, 0, 1000, 1, op=1, **(args if envelope else {}))
    span = ev(names.SPAN_STAGE_D2H, 10, 100, 2, op=1, **(args if not envelope else {}))
    table = critpath.stage_tables([op, span])[1]
    assert "process" not in table and "cpu_s" not in table["stages"][names.SPAN_STAGE_D2H]
    assert "cpu_s" not in critpath.format_stage_table(table).splitlines()[2]


@pytest.mark.parametrize("name,envelope", [(names.SPAN_STAGE_D2H, False), (COMMIT, True)])
def test_a_kernel_that_counts_no_faults_leaves_them_out(monkeypatch, name, envelope):
    """gVisor answers ``getrusage`` with CPU seconds and ``ru_minflt`` 0:
    the span keeps its CPU and says nothing of faults, not zero."""
    _counting_resource(monkeypatch, counts_faults=False)
    args = _one_span(name, lambda: _burn(0.02), envelope)
    assert args[USER] + args[SYS] >= 10_000 and FAULTS not in args
    events = [ev(STAGE, 0, 1000, 1, op=1), ev(COMMIT, 1000, 9000, 2, op=1, **(args if envelope else {})),
              ev(names.SPAN_STAGE_D2H, 2000, 500, 3, op=1, **({} if envelope else args))]
    table = critpath.stage_tables(events)[1]
    row = table["process"] if envelope else table["stages"][names.SPAN_STAGE_D2H]
    assert row["cpu_s"] > 0 and "fault_bytes" not in row
    printed = critpath.format_stage_table(table).splitlines()[-1 if envelope else 2]
    assert printed.split()[-1] == "-"


def test_the_stage_table_sums_the_account_and_prints_it():
    def usage(user, system, faults):
        return {USER: user, SYS: system, FAULTS: faults}

    events = [
        ev(STAGE, 0, 100_000, 1, op=1, path="/s"),
        ev(COMMIT, 100_000, 2_000_000, 2, op=1, tid=1, path="/s",
           **usage(3_000_000, 1_000_000, 96 << 20)),
        ev(names.SPAN_STAGE_D2H, 200_000, 500_000, 3, op=1, tid=2, bytes=1 << 20,
           **usage(100_000, 50_000, 0)),
        ev(names.SPAN_STAGE_D2H, 300_000, 500_000, 4, op=1, tid=3, bytes=1 << 20,
           **usage(200_000, 150_000, 2 << 20)),
        # A span of the name from a library without the account beside them.
        ev(names.SPAN_STAGE_D2H, 900_000, 100_000, 5, op=1, tid=2, bytes=1 << 20),
        ev(names.SPAN_STORAGE_WRITE, 900_000, 400_000, 6, op=1, tid=4, bytes=3 << 20),
        # Another op's envelope is another op's account.
        ev(RESTORE, 5_000_000, 1_000_000, 7, op=7, path="/s", **usage(9, 9, 4096)),
    ]
    tables = critpath.stage_tables(events)
    table = tables[1]
    d2h = table["stages"][names.SPAN_STAGE_D2H]
    assert (d2h["count"], d2h["cpu_s"], d2h["sys_s"], d2h["fault_bytes"]) == (
        3, pytest.approx(0.5), pytest.approx(0.2), 2 << 20)
    assert set(table["stages"][names.SPAN_STORAGE_WRITE]) == {
        "count", "busy_s", "thread_s", "bytes", "max_open"}
    assert table["process"] == {"cpu_s": pytest.approx(4.0), "sys_s": pytest.approx(1.0),
                                "fault_bytes": 96 << 20, "wall_s": pytest.approx(2.0)}
    assert tables[7]["process"]["fault_bytes"] == 4096
    text = critpath.format_stage_table(table).splitlines()
    assert text[1].split()[-4:] == ["cpu_s", "sys_s", "fault", "MiB"]
    (row,) = [line.split() for line in text if line.split()[0] == names.SPAN_STAGE_D2H]
    assert row[-3:] == ["0.500", "0.200", "2.0"]
    (write,) = [line.split() for line in text if line.split()[0] == names.SPAN_STORAGE_WRITE]
    assert len(write) == len(row) - 3
    assert text[-1].split() == ["(process)", "2.000", "4.000", "1.000", "96.0"]
    # Through a Chrome file, as `telemetry trace` reads it, the same table.
    doc = trace.chrome_trace(events, {})
    assert critpath.stage_tables(trace.spans_from_chrome(doc))[1]["process"] == table["process"]


# ---------------------------------------------------------------------------
# Names, segments, device programs
# ---------------------------------------------------------------------------


def test_new_names_are_registered_mapped_and_lint_clean():
    import sys
    from pathlib import Path

    root = str(Path(__file__).resolve().parent.parent)
    if root not in sys.path:
        sys.path.insert(0, root)
    from tools import check_metric_names, check_span_names

    assert check_span_names.check() == []
    assert check_metric_names.check() == []
    want = {
        names.SPAN_TAKE_PLAN: "plan", names.SPAN_RESTORE_PLAN: "plan",
        names.SPAN_CAPTURE_CLONE: "device_capture",
        names.SPAN_CAPTURE_HOST_COPY: "device_capture",
        names.SPAN_CAPTURE_OBJECT: "device_capture",
        # The drain's wait for the device: not the caller's seconds
        # (`device_capture`) and not the staging's.
        names.SPAN_CAPTURE_READY: "capture_ready",
        names.SPAN_STAGE_D2H: "staging", names.SPAN_VERIFY_BLOB: "read_drain",
        names.SPAN_COMMIT_FINALIZE: "commit", names.SPAN_MANAGER_INDEX: "commit",
        names.SPAN_MANAGER_RETENTION: "commit", names.SPAN_MANAGER_TUNE: "commit",
        names.SPAN_RESTORE_PLACE: "placement", names.SPAN_RESTORE_APPLY: "placement",
        # Runs after the envelope: charged to nothing the sweep partitions.
        names.SPAN_TELEMETRY_REPORT: "other",
    }
    for span, segment in want.items():
        assert critpath.segment_for(span) == segment, span


def test_the_librarys_device_programs_have_stable_names():
    from torchsnapshot_tpu.io_preparer import _capture_clone_jit
    from torchsnapshot_tpu.ops import device_digest

    x = jnp.arange(4096, dtype=jnp.float32).reshape(64, 64)
    clone = _capture_clone_jit()
    lowered = clone.lower(x)
    assert "jit_ts_capture_clone" in lowered.as_text()
    y = clone(x)
    assert y.unsafe_buffer_pointer() != x.unsafe_buffer_pointer()
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    assert "jit_ts_device_digest" in device_digest._digest_jit().lower(x).as_text()
    many = device_digest._digest_many_jit(1, (None,))
    assert "jit_ts_device_digest" in many.lower([x]).as_text()
    assert device_digest.materialize(device_digest.digest_device_async(x)) == (
        device_digest.digest_host(np.asarray(x)))


# ---------------------------------------------------------------------------
# A real save and restore
# ---------------------------------------------------------------------------

LEAVES, LEAF_SHAPE = 24, (1024, 1024)  # 96 MiB of float32
# The test box's storage is its page cache: 96 MiB move in 30 ms. The
# "slowed" pass gives a blob what a disk would, so that an op's wall is
# its I/O; the "shipped" pass runs the kernels as they are.
BLOB_LATENCY_S = 0.05
# Seconds of an op no span covers: the bounds leave a loaded box four to
# eight times what an idle one reads.
UNATTRIBUTED_MAX_S, UNATTRIBUTED_MEDIAN_S = 0.030, 0.012
LEAF_BYTES = 4 * LEAF_SHAPE[0] * LEAF_SHAPE[1]


def _app_state():
    device = jax.devices()[0]
    tree = {f"w{i}": jax.device_put(jnp.full(LEAF_SHAPE, i, jnp.float32), device)
            for i in range(LEAVES)}
    tree["host"] = np.arange(1000, dtype=np.int64)
    return {"model": ts.PyTreeState(tree),
            "progress": ts.StateDict(step=0, seen={1, 2, 3})}


def _ops(events, kind):
    tables = critpath.stage_tables(events)
    return {op: t for op, t in tables.items() if t["kind"] == kind}


@pytest.fixture(scope="module", params=["shipped", "slowed"])
def saved(request, tmp_path_factory):
    """Three async saves and three restores through the manager, telemetry
    on: the recorder's events of all of it, and the reports. Once on the
    shipped path and once with every blob slowed to a disk's latency."""
    from torchsnapshot_tpu import _native

    root = str(tmp_path_factory.mktemp("stage_tables") / "ckpt")
    app = _app_state()
    rec = trace.get_recorder()

    def slowed(kernel):
        def run(*args, **kwargs):
            time.sleep(BLOB_LATENCY_S)
            return kernel(*args, **kwargs)

        return run

    with pytest.MonkeyPatch.context() as patch, knobs.enable_telemetry(), \
            knobs.override_history_max_records(16):
        if request.param == "slowed":
            for kernel in ("write_file_crc", "pread_into_crc"):
                patch.setattr(_native, kernel, slowed(getattr(_native, kernel)))
        mgr = ts.CheckpointManager(root, keep_last_n=1)
        mark = rec.mark()
        for step in range(3):
            mgr.async_save(step, app).wait()
        for _ in range(3):
            assert mgr.restore_latest(app) == 2
        events = rec.events_since(mark)
        reports = {kind: telemetry.last_report(kind, path=mgr.step_path(2))
                   for kind in ("async_take", "restore")}
    assert rec.dropped == 0
    np.testing.assert_array_equal(np.asarray(app["model"].tree["w3"][0, :4]), [3.0] * 4)
    return {"root": root, "events": events, "reports": reports, "mgr": mgr,
            "path": request.param}


def test_every_stage_span_is_emitted_with_op_and_parent(saved):
    events = [e for e in saved["events"] if e["ph"] == "X"]
    saves, restores = _ops(events, "async_take"), _ops(events, "restore")
    assert len(saves) == 3 and len(restores) == 3
    for ops, wanted in ((saves, SAVE_SPANS), (restores, RESTORE_SPANS)):
        for op, table in ops.items():
            assert wanted <= set(table["stages"]), wanted - set(table["stages"])
            mine = [e for e in events if e["op"] == op]
            envelopes = {e["bseq"] for e in mine if e["name"] in (STAGE, COMMIT, RESTORE)}
            assert op in envelopes
            ids = {e["bseq"] for e in mine}
            for e in mine:
                if e["bseq"] == op:
                    assert e["parent"] == 0
                else:
                    # The parent is a span of the same op (the envelope for
                    # top-level work), never a guess.
                    assert e["parent"] in ids, e
    # One clone program for the device's jax leaves, with their bytes.
    op, table = next(iter(saves.items()))
    (clone,) = [e for e in events if e["op"] == op and e["name"] == names.SPAN_CAPTURE_CLONE]
    assert clone["args"] == {"kind": "device", "bytes": LEAVES * LEAF_BYTES, "leaves": LEAVES}
    (capture,) = [e for e in events if e["op"] == op and e["name"] == names.SPAN_DEVICE_CAPTURE]
    assert (capture["args"]["clone_programs"], capture["args"]["clone_leaves"],
            capture["args"]["fallback_leaves"]) == (1, LEAVES, 0)
    assert table["stages"][names.SPAN_STAGE_D2H]["bytes"] == LEAVES * LEAF_BYTES
    assert table["stages"][names.SPAN_STORAGE_WRITE]["bytes"] >= LEAVES * LEAF_BYTES
    # Nothing that ran for an op is left outside one.
    stray = {e["name"] for e in events if not e["op"]} - {
        names.SPAN_STORAGE_READ, names.SPAN_FS_NATIVE_READ}  # restore_latest's index read
    assert stray == set(), stray


def test_the_drain_waits_for_the_clones_on_its_own_thread(saved):
    """`capture:ready` is the commit thread's, inside the commit envelope and
    before the first transfer: the caller's capture span does not hold it."""
    events = [e for e in saved["events"] if e["ph"] == "X"]
    for op, table in _ops(events, "async_take").items():
        mine = {e["name"]: e for e in events if e["op"] == op
                and e["name"] in (STAGE, COMMIT, names.SPAN_CAPTURE_READY,
                                  names.SPAN_DEVICE_CAPTURE)}
        ready, commit = mine[names.SPAN_CAPTURE_READY], mine[COMMIT]
        assert ready["tid"] == commit["tid"] != mine[STAGE]["tid"]
        assert ready["parent"] == commit["bseq"]
        assert mine[names.SPAN_DEVICE_CAPTURE]["tid"] == mine[STAGE]["tid"]
        assert ready["args"] == {"bytes": LEAVES * LEAF_BYTES, "programs": 1}
        row = table["stages"][names.SPAN_CAPTURE_READY]
        assert row["count"] == 1 and row["bytes"] == LEAVES * LEAF_BYTES
        first_d2h = min(e["ts"] for e in events
                        if e["op"] == op and e["name"] == names.SPAN_STAGE_D2H)
        assert commit["ts"] <= ready["ts"] and ready["ts"] + ready["dur"] <= first_d2h
    assert critpath.segment_for(names.SPAN_CAPTURE_READY) != critpath.SEG_DEVICE_CAPTURE


def test_the_envelopes_carry_the_processs_account_and_the_stall_pays_nothing(saved):
    """A real save and restore: the commit and restore envelopes hold what
    the process spent between their ends, the byte-moving spans what their
    threads did, and none of it was sampled on the thread that called
    ``async_save``: the stall's envelope has no account and no sampled
    span of the take is on its track."""
    events = [e for e in saved["events"] if e["ph"] == "X"]
    sampled = names.SPANS_WITH_THREAD_USAGE | names.SPANS_WITH_PROCESS_USAGE
    for op, table in _ops(events, "async_take").items():
        mine = [e for e in events if e["op"] == op]
        (stage,) = [e for e in mine if e["name"] == STAGE]
        (commit,) = [e for e in mine if e["name"] == COMMIT]
        assert not set(names.USAGE_ARGS) & set(stage["args"])
        assert commit["args"][USER] + commit["args"][SYS] > 0
        on_caller = [e["name"] for e in mine if e["tid"] == stage["tid"]]
        assert not sampled & set(on_caller), on_caller
        moved = [e for e in mine if e["name"] in names.SPANS_WITH_THREAD_USAGE]
        assert {names.SPAN_STAGE_D2H, names.SPAN_FS_NATIVE_WRITE} <= {e["name"] for e in moved}
        assert all({USER, SYS} <= set(e["args"]) for e in moved)
        process = table["process"]
        assert process["wall_s"] == pytest.approx(commit["dur"] / 1e6, abs=1e-5)
        assert process["cpu_s"] == pytest.approx(
            (commit["args"][USER] + commit["args"][SYS]) / 1e6, abs=1e-5)
        # The write's threads computed inside the envelope, so their CPU
        # is part of the process's (a tick of the kernel's clock apart).
        write = table["stages"][names.SPAN_FS_NATIVE_WRITE]
        assert 0 < write["cpu_s"] <= process["cpu_s"] + 0.02
        assert ("fault_bytes" in write) == ("fault_bytes" in process) == COUNTS_FAULTS
        assert write.get("fault_bytes", 0) <= process.get("fault_bytes", 0) + (1 << 20)
    for op, table in _ops(events, "restore").items():
        (envelope,) = [e for e in events if e["op"] == op and e["name"] == RESTORE]
        assert {USER, SYS} <= set(envelope["args"])
        assert table["process"]["wall_s"] == pytest.approx(table["wall_s"], abs=1e-5)
        # The envelope alone: no span of a restore pays for a sample.
        assert not [name for name, row in table["stages"].items() if "cpu_s" in row]
    assert saved["reports"]["async_take"].critical_path["process"]["cpu_s"] > 0
    assert "(process)" in critpath.format_stage_table(table)


@pytest.mark.parametrize("entry", ["async_save", "save"])
def test_the_managers_spans_run_where_the_commit_ended(saved, tmp_path, entry):
    """`manager:index` (with `manager:retention` inside it), the step's
    `telemetry:report` and `manager:tune` are stages of the take either way:
    on the commit thread, behind the commit envelope, for an `async_save`
    (`on="commit"`), on the caller's, behind the take's envelope, for a
    blocking `save` (`on="caller"`)."""
    if entry == "async_save":
        events = [e for e in saved["events"] if e["ph"] == "X"]
        ops, envelope, on = _ops(events, "async_take"), COMMIT, "commit"
    else:
        rec = trace.get_recorder()
        with knobs.enable_telemetry():
            mark = rec.mark()
            ts.CheckpointManager(str(tmp_path), keep_last_n=1).save(0, _app_state())
            events = [e for e in rec.events_since(mark) if e["ph"] == "X"]
        ops, envelope, on = _ops(events, "take"), TAKE, "caller"
    assert ops
    for op in ops:
        mine = [e for e in events if e["op"] == op]
        (ended,) = [e for e in mine if e["name"] == envelope]
        (stage,) = [e for e in mine if e["name"] in (STAGE, TAKE)]
        (index,) = [e for e in mine if e["name"] == names.SPAN_MANAGER_INDEX]
        (retention,) = [e for e in mine if e["name"] == names.SPAN_MANAGER_RETENTION]
        (report,) = [e for e in mine if e["name"] == names.SPAN_TELEMETRY_REPORT
                     and e["args"].get("kind") == "step"]
        (tune,) = [e for e in mine if e["name"] == names.SPAN_MANAGER_TUNE]
        assert index["args"]["on"] == on
        assert retention["parent"] == index["bseq"]
        for span in (index, report, tune):
            assert span["tid"] == ended["tid"], span["name"]
            assert (span["tid"] == stage["tid"]) == (entry == "save"), span["name"]
            assert span["ts"] >= ended["ts"] + ended["dur"], span["name"]


def test_stamps_cross_the_executor_hops(saved):
    events = [e for e in saved["events"] if e["ph"] == "X"]
    by_bseq = {e["bseq"]: e for e in events}
    crossed = set()
    for e in events:
        if e["name"] in EXECUTOR_ONLY and e["op"]:
            parent = by_bseq[e["parent"]]
            assert parent["op"] == e["op"], (e, parent)
            # A leaf of a few KiB is staged inline, on its parent's track.
            if parent["tid"] != e["tid"]:
                crossed.add(e["name"])
    assert crossed == EXECUTOR_ONLY
    d2h = next(e for e in events if e["name"] == names.SPAN_STAGE_D2H)
    leaf = by_bseq[d2h["parent"]]
    assert leaf["name"] == names.SPAN_LEAF_STAGE
    assert by_bseq[leaf["parent"]]["name"] == names.SPAN_PIPELINE_STAGE
    verify = [e for e in events if e["name"] == names.SPAN_VERIFY_BLOB]
    assert verify and all(e["args"]["mode"] in ("whole", "range", "pages") for e in verify)


def test_next_to_none_of_the_wall_is_unattributed(saved):
    """Every op, in seconds: what no span covers is a few milliseconds
    around the pipelines whatever the op's wall (2-4 ms on an idle box).
    As a share it is judged where an op's wall is its I/O, as on a real
    mount: the median of three under the 5 % the benchmark holds the chip to."""
    events = saved["events"]
    for kind in ("async_take", "restore"):
        tables = list(_ops(events, kind).values())
        seconds = [t["unattributed_s"] for t in tables]
        assert max(seconds) < UNATTRIBUTED_MAX_S, (kind, seconds)
        assert statistics.median(seconds) < UNATTRIBUTED_MEDIAN_S, (kind, seconds)
        if saved["path"] == "slowed":
            shares = [t["unattributed_s"] / t["wall_s"] for t in tables]
            assert statistics.median(shares) < 0.05, (kind, shares)


def test_segments_still_cover_the_wall_with_the_new_ones_present(saved):
    for kind, new in (("async_take", {"plan", "commit"}), ("restore", {"plan", "placement"})):
        cp = saved["reports"][kind].critical_path
        assert new <= set(cp["segments"]), cp["segments"]
        assert sum(cp["segments"].values()) >= critpath.MIN_COVERAGE * cp["wall_s"]
        assert cp["coverage"] >= critpath.MIN_COVERAGE
        assert cp["segments"].get("other", 0.0) == pytest.approx(
            cp["unattributed_s"], abs=2e-3)


def test_the_report_and_the_history_row_carry_the_table(saved):
    cp = saved["reports"]["restore"].critical_path
    stages = cp["stages"]
    assert {names.SPAN_STORAGE_READ, names.SPAN_RESTORE_PLACE, names.SPAN_VERIFY_BLOB} <= set(stages)
    row = stages[names.SPAN_STORAGE_READ]
    assert set(row) == {"count", "busy_s", "thread_s", "bytes", "max_open"}
    assert 0 < row["busy_s"] <= row["thread_s"] and row["busy_s"] <= cp["wall_s"]
    assert saved["reports"]["restore"].to_dict()["critical_path"]["stages"] == stages
    rows = history.load_history(history.history_path_for(saved["root"]))
    kinds = [r["kind"] for r in rows]
    assert kinds.count("async_take") == 3 and kinds.count("restore") == 3
    for r in rows:
        assert r["critpath"]["stages"] and r["critpath"]["unattributed_s"] is not None
    assert rows[-1]["critpath"]["stages"][names.SPAN_RESTORE_PLACE]["count"] >= 1


def test_the_trace_cli_prints_the_stage_table(tmp_path, capsys):
    path = str(tmp_path / "snap")
    with knobs.enable_trace():
        ts.Snapshot.take(path, {"m": ts.PyTreeState({"w": jnp.ones((256, 256))})})
    assert trace.main([path]) == 0
    out = capsys.readouterr().out
    assert "longest spans" in out
    table = out[out.index("take: wall"):]
    assert "unattributed" in table and "busy_s" in table and "thread_s" in table
    for name in (names.SPAN_TAKE_PLAN, names.SPAN_STORAGE_WRITE, names.SPAN_COMMIT_FINALIZE):
        assert name in table


def test_the_reports_table_counts_unstamped_spans_as_stage_tables_does(saved):
    """One function, one answer: the table a report carries is what
    ``stage_tables`` gives any other reader of the same events, spans
    without an op id (counted by overlap) included."""
    events = [e for e in saved["events"] if e["ph"] == "X"]
    cp = saved["reports"]["restore"].critical_path
    op, whole = list(_ops(events, "restore").items())[-1]
    # restore_latest's own index read runs before the envelope; plant a
    # span of no op inside it, as a thread outside any context would.
    envelope = next(e for e in events if e["bseq"] == op)
    stray = ev(names.SPAN_WIRE_RPC, envelope["ts"] + 10, envelope["dur"] - 20, 10**9)
    stray.update(seq=10**9, op=0, parent=0)
    with_stray = critpath.stage_tables(events + [stray])[op]
    assert with_stray["stages"][names.SPAN_WIRE_RPC]["count"] == 1
    assert with_stray["unattributed_s"] == pytest.approx(0.0, abs=1e-4)
    # The report was built while its own emission span was open and
    # before the manager's history row; every other stage is the same.
    later = {names.SPAN_TELEMETRY_REPORT}
    assert {k: v for k, v in whole["stages"].items() if k not in later} == {
        k: v for k, v in cp["stages"].items() if k not in later}
    assert whole["unattributed_s"] == pytest.approx(cp["unattributed_s"], abs=1e-6)


def test_async_restore_plans_inside_its_op(tmp_path):
    app = _app_state()
    path = str(tmp_path / "snap")
    ts.Snapshot.take(path, app)
    rec = trace.get_recorder()
    mark = rec.mark()
    with knobs.enable_telemetry():
        pending = ts.Snapshot(path).async_restore(app)
        pending.wait()
        report = telemetry.last_report("async_restore", path=path)
    events = [e for e in rec.events_since(mark) if e["ph"] == "X"]
    ((op, table),) = _ops(events, "async_restore").items()
    assert op == pending.trace_op
    mine = [e for e in events if e["op"] == op]
    envelopes = {e["name"]: e for e in mine if e["name"].startswith("snapshot:")}
    assert set(envelopes) == {names.SPAN_ASYNC_RESTORE_PLAN, names.SPAN_ASYNC_RESTORE_READS}
    plan, reads = envelopes[names.SPAN_ASYNC_RESTORE_PLAN], envelopes[names.SPAN_ASYNC_RESTORE_READS]
    assert plan["bseq"] == op and plan["parent"] == 0
    assert reads["parent"] == op and reads["tid"] != plan["tid"]
    # Planning runs on the caller's thread under the first envelope; the
    # reads, placement and apply follow under the same op.
    plans = [e for e in mine if e["name"] == names.SPAN_RESTORE_PLAN]
    assert plans and all(e["parent"] == op and e["tid"] == plan["tid"] for e in plans)
    assert {names.SPAN_RESTORE_PLAN, names.SPAN_STORAGE_READ, names.SPAN_VERIFY_BLOB,
            names.SPAN_RESTORE_PLACE, names.SPAN_RESTORE_APPLY,
            names.SPAN_TELEMETRY_REPORT} <= set(table["stages"])
    assert not [e["name"] for e in events if not e["op"]]
    cp = report.critical_path
    assert cp["segments"]["plan"] > 0 and cp["coverage"] >= critpath.MIN_COVERAGE
    assert cp["stages"][names.SPAN_RESTORE_PLAN]["count"] == len(plans)


# What one read pipeline leaves behind whichever driver ran it: the spans of
# its stages in the op's table, and these keys in the report.
PIPELINE_SPANS = {
    names.SPAN_RESTORE_PLAN, names.SPAN_STORAGE_READ, names.SPAN_VERIFY_BLOB,
    names.SPAN_RESTORE_DEST_ACQUIRE, names.SPAN_RESTORE_PLACE,
    names.SPAN_RESTORE_APPLY,
}
PIPELINE_KEYS = ("bytes_needed", "dest_bytes_recycled", "dest_bytes_fresh", "cold_start_s")


@pytest.mark.parametrize("driver", ["restore", "async_restore"])
def test_both_restore_drivers_run_the_one_read_pipeline(tmp_path, accelerator_path, driver):
    app = _app_state()
    path = str(tmp_path / "snap")
    ts.Snapshot.take(path, app)
    rec = trace.get_recorder()
    with knobs.enable_telemetry():
        mark = rec.mark()
        if driver == "restore":
            ts.Snapshot(path).restore(app)
        else:
            ts.Snapshot(path).async_restore(app).wait()
        report = telemetry.last_report(driver, path=path)
    events = [e for e in rec.events_since(mark) if e["ph"] == "X"]
    ((op, table),) = _ops(events, driver).items()
    assert PIPELINE_SPANS <= set(table["stages"]), PIPELINE_SPANS - set(table["stages"])
    # Set-up reads the manifest and the checksum table under a span of its
    # own, before any stateful's plan.
    plans = [e for e in events if e["op"] == op and e["name"] == names.SPAN_RESTORE_PLAN]
    assert "stateful" not in plans[0]["args"]
    assert {e["args"]["stateful"] for e in plans[1:]} == {"model", "progress"}
    applied = [e["args"]["stateful"] for e in events
               if e["op"] == op and e["name"] == names.SPAN_RESTORE_APPLY]
    assert applied == ["model", "progress"]
    carried = report.to_dict()
    assert [k for k in PIPELINE_KEYS if carried.get(k) is None] == []
    assert carried["bytes_needed"] >= LEAVES * LEAF_BYTES
    assert (carried["dest_bytes_recycled"] + carried["dest_bytes_fresh"]
            == LEAVES * LEAF_BYTES)
    assert set(carried["cold_start"]) == {"event_loop_s", "plugin_open_s", "native_load_s"}
    assert accelerator_path.unsettled() == 0  # the restore waited for its placements


@pytest.mark.parametrize("driver", ["take", "async_take"])
def test_both_take_drivers_run_the_one_commit_sequence(tmp_path, driver):
    app = _app_state()
    path = str(tmp_path / "snap")
    rec = trace.get_recorder()
    with knobs.enable_telemetry():
        mark = rec.mark()
        if driver == "take":
            op = ts.Snapshot.take(path, app).trace_op
        else:
            pending = ts.Snapshot.async_take(path, app)
            pending.wait()
            op = pending.trace_op
        assert telemetry.last_report(driver, path=path) is not None
    mine = sorted((e for e in rec.events_since(mark) if e["ph"] == "X" and e["op"] == op),
                  key=lambda e: e["bseq"])
    (finalize,) = [e for e in mine if e["name"] == names.SPAN_COMMIT_FINALIZE]
    envelope = [e for e in mine if e["name"] in (TAKE, COMMIT)][-1]
    # The writes drain, then the finalize window opens inside the envelope:
    # the checksum table and the marker are its two writes, nothing else is.
    drained = [e for e in mine if e["name"] == names.SPAN_PIPELINE_WRITE_DRAIN]
    assert drained and all(e["ts"] + e["dur"] <= finalize["ts"] for e in drained)
    assert finalize["parent"] == envelope["bseq"]
    commit = [e["name"] for e in mine if e["bseq"] >= finalize["bseq"]
              and e["name"] != names.SPAN_FS_NATIVE_WRITE]
    assert commit == [names.SPAN_COMMIT_FINALIZE, names.SPAN_STORAGE_WRITE,
                      names.SPAN_STORAGE_WRITE, names.SPAN_TELEMETRY_REPORT]
    writes = [e for e in mine if e["parent"] == finalize["bseq"]]
    assert [e["name"] for e in writes] == [names.SPAN_STORAGE_WRITE] * 2
    # The envelope closes before the report is emitted, so the report's
    # window holds the take's full extent.
    (reported,) = [e for e in mine if e["name"] == names.SPAN_TELEMETRY_REPORT]
    assert envelope["ts"] + envelope["dur"] <= reported["ts"]
    assert os.path.exists(os.path.join(path, ".snapshot_metadata"))


def test_a_mirror_job_is_an_operation_of_its_own(tmp_path):
    from torchsnapshot_tpu.tiered import reset_mirror, wait_durable

    reset_mirror()
    rec = trace.get_recorder()
    mark = rec.mark()
    url = f"tiered://{tmp_path / 'fast'}|{tmp_path / 'durable'}"
    try:
        ts.Snapshot.take(url, {"m": ts.PyTreeState({"w": np.arange(4096, dtype=np.float32)})})
        wait_durable(url, timeout=60)
    finally:
        reset_mirror()
    events = [e for e in rec.events_since(mark) if e["ph"] == "X"]
    (job,) = [e for e in events if e["name"] == names.SPAN_MIRROR_JOB]
    (take,) = [e for e in events if e["name"] == TAKE]
    assert job["op"] == job["bseq"] != take["op"] and job["parent"] == 0
    blobs = [e for e in events if e["name"] == names.SPAN_MIRROR_BLOB]
    assert blobs and all(e["op"] == job["op"] for e in blobs)
    tables = critpath.stage_tables(events)
    assert tables[job["op"]]["kind"] == "mirror"
    assert names.SPAN_MIRROR_BLOB in tables[job["op"]]["stages"]
    # The take's table holds none of the job's spans, though they overlap it or not.
    assert names.SPAN_MIRROR_BLOB not in tables[take["op"]]["stages"]


# ---------------------------------------------------------------------------
# One clock
# ---------------------------------------------------------------------------


def test_stage_spans_reach_the_xplane_and_the_clocks_align(tmp_path):
    app = _app_state()
    mgr = ts.CheckpointManager(str(tmp_path / "ckpt"), keep_last_n=1)
    mgr.async_save(0, app).wait()  # compiles the clone outside the session
    rec = trace.get_recorder()
    mark = rec.mark()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "profile"), profiler_options=options)
    try:
        mgr.async_save(1, app).wait()
        assert mgr.restore_latest(app) == 1
    finally:
        jax.profiler.stop_trace()
    events = rec.events_since(mark)
    (pb,) = glob.glob(os.path.join(str(tmp_path / "profile"), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(pb)
    on_xplane = {e.name for plane in data.planes if plane.name.startswith("/host:")
                 for line in plane.lines for e in line.events}
    # Spans that cross an await stay recorder-only (utils/tracing.py).
    crosses_await = {names.SPAN_PIPELINE_STAGE, names.SPAN_MANAGER_RETENTION}
    dual = (SAVE_SPANS | RESTORE_SPANS | {STAGE, COMMIT, RESTORE}) - crosses_await
    assert dual <= on_xplane, dual - on_xplane
    assert not crosses_await & on_xplane
    assert any("ts_capture_clone" in name for name in on_xplane)
    offset = trace.xplane_offset_us(data, events)
    assert offset is not None and offset["n"] >= len(dual)
    assert offset["spread_us"] < 1000.0
    # The profile counts from its session's start, the recorder from 1970.
    assert offset["median_us"] > 1e15
    assert abs(offset["drift_us_per_s"]) < 1e4
    assert trace.xplane_offset_us(data, []) is None

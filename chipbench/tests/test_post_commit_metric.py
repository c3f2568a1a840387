"""PR 39's per-layer metric of the entry points: the entry, what the reader
counts (the manager's spans on any thread, the step's report and no other),
a traced rehearsal in which the work ran on the commit thread, and a library
without the span constants reads as nothing."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import cells
import harness
from test_capture_metrics import SAVES, _ev, _run

NEW = "post_commit_work_s"


def test_benchmark_json_ends_with_the_entry():
    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["per_layer"][-1] == {
        "name": NEW, "unit": "s", "better": "lower", "source": "program_span",
        "layer": "entry points", "moves": "save_stall_s", "workloads": SAVES}
    assert os.path.isfile(os.path.join(harness.REPO, "chipbench", "layer_metrics", NEW + ".py"))


def _save(op, manager_tid):
    """One async save's envelopes and what follows its commit: the commit
    thread's report, then the manager's spans on `manager_tid`, the install
    on the caller's (track 0)."""
    from torchsnapshot_tpu.telemetry import names

    t = op * 10_000_000
    return [
        _ev(names.SPAN_ASYNC_TAKE_STAGE, t, 60_000, op, op),
        _ev(names.SPAN_ASYNC_TAKE_COMMIT, t + 60_000, 8_000_000, op + 1, op, tid=1),
        _ev(names.SPAN_TELEMETRY_REPORT, t + 8_060_000, 9_000, op + 2, op, tid=1,
            kind="async_take"),
        _ev(names.SPAN_MANAGER_INDEX, t + 8_070_000, 50_000, op + 3, op, tid=manager_tid, step=op),
        _ev(names.SPAN_MANAGER_RETENTION, t + 8_075_000, 44_000, op + 4, op, tid=7, step=op),
        _ev(names.SPAN_TELEMETRY_REPORT, t + 8_120_000, 12_000, op + 5, op, tid=manager_tid,
            kind="step", step=op),
        _ev(names.SPAN_MANAGER_TUNE, t + 8_132_000, 6_000, op + 6, op, tid=manager_tid, step=op),
        _ev(names.SPAN_MANAGER_TUNE, t + 8_200_000, 200, op + 7, op, tid=0, step=op),
    ]


@pytest.mark.parametrize("manager_tid,caller_s", [(0, 0.0682), (1, 0.0002)])
def test_the_reader_counts_the_work_wherever_it_ran(manager_tid, caller_s):
    """In `wait()` (the parent) or on the commit thread: the same seconds,
    retention once, the commit thread's own report never; `post_commit_s`
    beside it reads the caller's thread."""
    run = _run(_save(1, manager_tid) + _save(100, manager_tid))
    assert cells.layer_reader(NEW)(run) == pytest.approx(0.0682)
    assert cells.layer_reader("post_commit_s")(run) == pytest.approx(caller_s)


def test_a_traced_rehearsal_reads_the_work_off_the_callers_thread(tmp_path):
    checkout = harness.copy_benchmark(tmp_path)
    rc, result, err = harness.run_cell(checkout, SAVES[0], trace=1, seed=3900000013)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True and result["failed"] == 0
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert 0 < metrics["post_commit_s"] < 0.005 and metrics["post_commit_s"] < metrics[NEW]


def test_the_reader_finds_nothing_on_a_library_without_the_spans(monkeypatch):
    from torchsnapshot_tpu.telemetry import names

    import stage_table

    run = _run(_save(1, 1))
    for constant in ("SPAN_MANAGER_INDEX", "SPAN_MANAGER_TUNE"):
        monkeypatch.delattr(names, constant)
    assert cells.layer_reader(NEW)(run) is None
    assert cells.layer_reader(NEW)(dict(run, **{stage_table.CACHE_KEY: None})) is None

"""PR 35's two per-layer metrics of the device-capture layer: the entries, a
traced rehearsal of each save cell reads both, and a library without the
counters and the span (the parent of PR 35) reads as nothing."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import cells
import harness

NEW = ("capture_leaves_per_program", "capture_ready_wait_s")
SAVES = ["neox-6.9b-l2.async-full", "pythia-1b.async-full",
         "neox-6.9b-l12-lora.async-incremental"]


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return harness.copy_benchmark(tmp_path_factory.mktemp("capture_metrics"))


def test_benchmark_json_ends_with_the_two_entries():
    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    leaves, ready = bench["per_layer"][-2:]
    assert (leaves["name"], ready["name"]) == NEW
    assert leaves == {"name": NEW[0], "unit": "ratio", "better": "higher",
                      "source": "program_counter", "layer": "device capture",
                      "moves": "save_stall_s", "workloads": SAVES}
    assert ready == {"name": NEW[1], "unit": "s", "better": "lower", "source": "program_span",
                     "layer": "device capture", "moves": "save_commit_s", "workloads": SAVES}
    for name in NEW:
        assert os.path.isfile(os.path.join(harness.REPO, "chipbench", "layer_metrics",
                                           name + ".py"))


# Toy sizes keep the real trees: neox's 47 written jax leaves in one program.
# An incremental save has waited for its digests, so the queue is empty and
# each of the leaves it writes is a program of its own, compiled once a shape
# by set-up's full save: nothing compiles inside the window.
@pytest.mark.parametrize("workload,leaves", [
    ("neox-6.9b-l2.async-full", 47),
    ("neox-6.9b-l12-lora.async-incremental", 1),
])
def test_a_traced_rehearsal_reads_both(checkout, workload, leaves):
    rc, result, err = harness.run_cell(checkout, workload, trace=1, seed=3500000011)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True and result["failed"] == 0
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW) <= set(metrics)
    assert metrics[NEW[0]] == leaves
    assert 0 <= metrics[NEW[1]] < metrics["staging_s"] + metrics["write_drain_s"] + 1.0
    # The caller's capture still holds the dispatch, and nothing else.
    assert 0 < metrics["capture_clone_s"] <= metrics["capture_s"] * 1.001


def _run(events):
    import stage_table
    from torchsnapshot_tpu.telemetry import critpath

    tables = critpath.stage_tables(events)
    ops = [{"table": tables[op], "events": [e for e in events if e["op"] == op],
            "caller_tid": 0} for op in sorted(tables)]
    return {stage_table.CACHE_KEY: ops, "window": {"ops": [{}] * len(ops)},
            "traffic": cells.traffic("async-full")}


def _ev(name, ts, dur, bseq, op, tid=0, **args):
    return {"ph": "X", "name": name, "ts": ts, "dur": dur, "bseq": bseq, "seq": bseq,
            "tid": tid, "op": op, "parent": op, "args": args}


def test_the_readers_reduce_the_counters_and_the_span():
    from torchsnapshot_tpu.telemetry import names

    events = []
    for op, (programs, leaves, wait_us) in ((1, (1, 47, 1_700_000)), (100, (4, 52, 300_000))):
        events += [
            _ev(names.SPAN_ASYNC_TAKE_STAGE, op * 10_000_000, 100_000, op, op),
            _ev(names.SPAN_DEVICE_CAPTURE, op * 10_000_000 + 10, 60_000, op + 1, op,
                clone_programs=programs, clone_leaves=leaves, fallback_leaves=0),
            _ev(names.SPAN_ASYNC_TAKE_COMMIT, op * 10_000_000 + 100_000, 5_000_000, op + 2, op,
                tid=1),
            _ev(names.SPAN_CAPTURE_READY, op * 10_000_000 + 100_100, wait_us, op + 3, op, tid=1,
                bytes=7, programs=programs),
        ]
    run = _run(events)
    assert cells.layer_reader(NEW[0])(run) == pytest.approx((47 / 1 + 52 / 4) / 2)
    assert cells.layer_reader(NEW[1])(run) == pytest.approx((1.7 + 0.3) / 2)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_on_a_library_without_the_counters(monkeypatch, name):
    """The parent of PR 35: `stage:device_capture` ends without the three
    counters, there is no `SPAN_CAPTURE_READY`, and one clone a leaf."""
    from torchsnapshot_tpu.telemetry import names

    import stage_table

    ready = names.SPAN_CAPTURE_READY
    monkeypatch.delattr(names, "SPAN_CAPTURE_READY")
    events = [
        _ev(names.SPAN_ASYNC_TAKE_STAGE, 0, 100_000, 1, 1),
        _ev(names.SPAN_DEVICE_CAPTURE, 10, 60_000, 2, 1, rank=0, reqs=47),
        _ev(names.SPAN_CAPTURE_CLONE, 20, 50_000, 3, 1, kind="device", bytes=3, leaf="0/p/w"),
        _ev(names.SPAN_ASYNC_TAKE_COMMIT, 100_000, 5_000_000, 4, 1, tid=1),
    ]
    run = _run(events)
    assert ready not in run[stage_table.CACHE_KEY][0]["table"]["stages"]
    assert cells.layer_reader(name)(run) is None
    # And where the ring dropped the window's events.
    assert cells.layer_reader(name)(dict(run, **{stage_table.CACHE_KEY: None})) is None

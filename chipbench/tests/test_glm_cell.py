"""The GLM and dense incremental cells' entries and the two per-layer metrics
of the sized capture: the cells and metrics come last in their lists, and the
readers read the counters and spans a sized capture leaves (a library
without them reads as nothing)."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import cells
import harness
import stage_table

GLM = "glm-5-l5-ep32-tp8.async-full-64"
NEOX = "neox-6.9b-l2.async-incremental"
SAVES = ["neox-6.9b-l2.async-full", "pythia-1b.async-full",
         "neox-6.9b-l12-lora.async-incremental", "kimi-linear-48b-l5-ep16.async-full", NEOX, GLM]


def _bench():
    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_ends_with_the_entries():
    bench = _bench()
    assert [w["name"] for w in bench["workloads"][-2:]] == [NEOX, GLM]
    assert bench["configs"][-1]["name"] == "glm-5-l5-ep32-tp8"
    share, seconds = bench["per_layer"][-2:]
    assert share == {"name": "capture_cloned_share", "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "device capture",
                     "moves": "save_stall_s", "workloads": SAVES}
    assert seconds["name"] == "capture_host_copy_s" and seconds["workloads"] == [GLM]
    # The GLM cell reports what the Kimi cell reports, but the MFU whose
    # counter knows Kimi's shapes alone.
    kimi = {m["name"] for m in cells.metrics_of(bench, "per_layer", SAVES[3])}
    glm = {m["name"] for m in cells.metrics_of(bench, "per_layer", GLM)}
    assert glm == kimi - {"train_step_mfu"} | {"capture_host_copy_s"}


def _run(*captures, copies=()):
    """A run whose window's operations are given: each a capture span's args
    and the `(ts, dur, kind)` of its host copies."""
    names = dict(capture=stage_table.span_names("SPAN_DEVICE_CAPTURE")[0],
                 copy=stage_table.span_names("SPAN_CAPTURE_HOST_COPY")[0])
    ops = []
    for i, args in enumerate(captures):
        events = [{"name": names["capture"], "args": args, "ts": 0, "dur": 1}]
        events += [{"name": names["copy"], "args": {"kind": kind}, "ts": ts, "dur": dur}
                   for ts, dur, kind in (copies[i] if i < len(copies) else ())]
        ops.append({"events": events, "table": {}, "caller_tid": 0})
    return {stage_table.CACHE_KEY: ops}


def test_the_cloned_share_is_a_mean_per_save():
    read = cells.layer_reader("capture_cloned_share")
    whole = {"clone_bytes": 300, "host_copy_bytes": 0}
    assert read(_run(whole, whole)) == 100.0
    assert read(_run(whole, {"clone_bytes": 100, "host_copy_bytes": 300})) == 62.5
    assert read(_run({"clone_bytes": 0, "host_copy_bytes": 5})) == 0.0
    # A library that clones every source writes neither counter; a save
    # with no jax source has nothing to share.
    assert read(_run({"clone_programs": 1})) is None
    assert read({stage_table.CACHE_KEY: None}) is None


def test_the_host_copy_seconds_are_the_union_of_no_room_copies():
    read = cells.layer_reader("capture_host_copy_s")
    args = {"clone_bytes": 1, "host_copy_bytes": 1}
    copies = [[(0, 2_000_000, "no_room"), (1_000_000, 2_000_000, "no_room"),
               (9_000_000, 5_000_000, "numpy")]]
    assert read(_run(args, copies=copies)) == pytest.approx(3.0)
    assert read(_run(args, copies=[[(0, 1, "numpy")]])) is None

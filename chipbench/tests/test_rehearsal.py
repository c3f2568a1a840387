"""The command at toy size on the CPU: both drivers, one and four virtual
devices, what the last line holds, and that everything found by name fails
by name. A rehearsal names platform=cpu and cannot be read as a result."""

import json
import os
import shutil
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import harness
import device_trace as chipbench_trace
import storage as chipbench_storage

HERE = os.path.dirname(os.path.abspath(__file__))
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}

# What a later PR adds for the four-chip cell: one configuration file and one
# entry under `configs` and under `workloads`. Sizes are toy under --rehearse.
FOUR_CHIP_CONFIG = {
    "name": "toy-4chip", "source": "test", "hidden_size": 4096, "num_attention_heads": 32,
    "intermediate_size": 16384, "vocab_size": 50432, "num_hidden_layers": 6,
    "batch": 4, "seq": 512, "chips": 4, "mesh": [1, 2, 2], "restore_mesh": [1, 1, 4],
    "restore_loss_rtol": 0.01,
}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = harness.copy_benchmark(tmp_path_factory.mktemp("rehearsal"))
    with open(os.path.join(root, "chipbench", "configs", "toy-4chip.json"), "w") as f:
        json.dump(FOUR_CHIP_CONFIG, f)

    def add(bench):
        bench["configs"].append({"name": "toy-4chip", "source": "test", "reduced": [],
                                 "file": "chipbench/configs/toy-4chip.json", "why": "test"})
        for traffic, metric in (("resume", "restore_s"), ("async-full", "save_commit_s")):
            name = f"toy-4chip.{traffic}"
            bench["workloads"].append({"name": name, "config": "toy-4chip", "traffic": traffic,
                                       "chips": 4, "why": "test"})
            for group in ("end_to_end", "per_layer"):
                for m in bench[group]:
                    if m["name"] == metric or m.get("moves") == metric:
                        m["workloads"].append(name)
        bench["configs"].append({"name": "ghost", "source": "test", "reduced": [],
                                 "file": "chipbench/configs/ghost.json", "why": "test"})
        bench["workloads"].append({"name": "ghost.resume", "config": "ghost", "traffic": "resume",
                                   "chips": 1, "why": "test"})
        bench["workloads"].append({"name": "neox-6.9b-l2.ghost", "config": "neox-6.9b-l2",
                                   "traffic": "ghost", "chips": 1, "why": "test"})
        bench["per_layer"].append({"name": "ghost_metric", "unit": "s", "better": "lower",
                                   "source": "host_clock", "layer": "test", "moves": "restore_s",
                                   "workloads": ["toy-4chip.resume"]})

    harness.edit_benchmark(root, add)
    return root


@pytest.mark.parametrize("workload,devices,metric", [
    ("neox-6.9b-l2.async-full", 1, "save_commit_s"),
    ("neox-6.9b-l2.resume", 1, "restore_s"),
    ("toy-4chip.resume", 4, "restore_s"),
    ("toy-4chip.async-full", 4, "save_commit_s"),
])
def test_rehearsal_runs_and_cannot_be_read_as_a_result(checkout, workload, devices, metric):
    rc, result, err = harness.run_cell(checkout, workload, devices=devices)
    assert rc == 0, err[-3000:]
    assert RESULT_KEYS <= set(result)
    assert list(result)[-1] == "checks" and result["checks"]
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu" and result["device"]["count"] == devices
    assert "not a result" in result["rehearsal"]
    assert "platform=cpu" in err and "REHEARSAL" in err
    assert result["metrics"][metric]["value"] > 0 and result["metrics"]["setup_s"]["value"] > 0
    for check in result["checks"]:
        assert f"check {check['name']}: " in err
    assert set(result["metrics"]) == set(harness.end_to_end_of(checkout, workload))
    assert not os.path.exists(harness.storage_root(checkout))


def test_traced_rehearsal_reports_per_layer_metrics(checkout):
    rc, result, err = harness.run_cell(checkout, "neox-6.9b-l2.resume", trace=1)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True
    assert {"read_drain_s", "restore_uncovered_s", "restore_over_h2d_probe"} <= set(result["metrics"])
    # No device plane on the CPU: a reader that finds nothing returns nothing.
    assert "restore_device_idle_share" not in result["metrics"]
    assert "restore_s" not in result["metrics"]


def test_without_a_chip_there_is_no_result(checkout):
    rc, result, err = harness.run_cell(checkout, "neox-6.9b-l2.resume", rehearse=False)
    assert rc != 0 and result is None
    assert "need 'tpu'" in err


@pytest.mark.parametrize("workload,trace,devices,named", [
    ("nope.resume", 0, 1, "unknown workload 'nope.resume'"),
    ("ghost.resume", 0, 1, "unknown config 'ghost'"),
    ("neox-6.9b-l2.ghost", 0, 1, "unknown traffic 'ghost'"),
    ("toy-4chip.resume", 1, 4, "unknown per-layer metric 'ghost_metric'"),
])
def test_what_is_found_by_name_fails_by_name(checkout, workload, trace, devices, named):
    rc, result, err = harness.run_cell(checkout, workload, trace=trace, devices=devices)
    assert rc != 0 and result is None
    assert named in err


def test_a_set_library_knob_fails_the_run(checkout):
    rc, result, err = harness.run_cell(checkout, "neox-6.9b-l2.resume",
                                       env={"TORCHSNAPSHOT_TPU_DIRECT_IO": "1"})
    assert rc != 0 and result is None
    assert "TORCHSNAPSHOT_TPU_DIRECT_IO" in err


def test_storage_of_another_kind_fails_the_run(checkout, tmp_path):
    with open(os.path.join(checkout, "chipbench", "traffic", "on-disk.json"), "w") as f:
        json.dump({"driver": "restore_loop",
                   "storage": {"kind": "tmpfs", "root": str(tmp_path / "not_tmpfs")}}, f)
    harness.edit_benchmark(checkout, lambda bench: bench["workloads"].append(
        {"name": "neox-6.9b-l2.on-disk", "config": "neox-6.9b-l2", "traffic": "on-disk",
         "chips": 1, "why": "test"}))
    rc, result, err = harness.run_cell(checkout, "neox-6.9b-l2.on-disk")
    assert rc != 0 and result is None
    assert "the traffic file says 'tmpfs'" in err


def test_two_checkouts_never_share_a_storage_root(checkout, tmp_path):
    """The driver runs parent and change on one machine: each checkout claims
    a root of its own under the traffic file's, clears only that, and leaves
    one alone that a live run of the same checkout holds."""
    mine, theirs = harness.storage_root(checkout), harness.storage_root(str(tmp_path))
    assert mine != theirs and mine.startswith("/dev/shm/chipbench-")
    owner = os.path.join(mine, chipbench_storage.OWNER)
    os.makedirs(theirs)
    try:
        rc, result, err = harness.run_cell(checkout, "neox-6.9b-l2.resume")
        assert rc == 0 and result["correct"] is True, err[-3000:]
        assert os.path.isdir(theirs) and not os.path.exists(mine)
        os.makedirs(mine)
        with open(owner, "w") as f:
            f.write(str(os.getpid()))  # a live holder: this test's own process
        rc, result, err = harness.run_cell(checkout, "neox-6.9b-l2.resume")
        assert rc != 0 and result is None and f"held by process {os.getpid()}" in err
        assert os.path.isfile(owner)
        with open(owner, "w") as f:
            f.write("4194000")  # a holder that is gone: a killed run's leftovers
        rc, result, err = harness.run_cell(checkout, "neox-6.9b-l2.resume")
        assert rc == 0 and result["correct"] is True, err[-3000:]
        assert not os.path.exists(mine)
    finally:
        shutil.rmtree(theirs, ignore_errors=True)
        shutil.rmtree(mine, ignore_errors=True)


def test_a_mount_without_room_fails_with_the_reason():
    with pytest.raises(chipbench_storage.BenchError, match="GiB free, the cell needs 2.5 x"):
        chipbench_storage.check_room("/dev/shm", 1 << 60)


def test_trace_reduction_on_a_recorded_trace():
    """data/small_trace.json: three train steps, one async save and the start
    of its drain, cut from a trace of the neox state on a v5e chip (PR 26,
    chip call 1) in `trace.planes()` form. The expected numbers come from a
    rasterised count over the same events, not from trace.reduce."""
    with open(os.path.join(HERE, "data", "small_trace.json")) as f:
        recorded = json.load(f)
    reduced = chipbench_trace.reduce(recorded["planes"], chips=1)
    assert reduced is not None
    assert reduced["window_s"] == pytest.approx(recorded["expected"]["window_s"], rel=1e-9)
    assert reduced["busy_s"] == pytest.approx(recorded["expected"]["busy_s"], rel=1e-3)
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    gaps = dict(reduced["breakdown"]["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(reduced["window_s"] - reduced["busy_s"], rel=1e-6)
    assert gaps["chipbench:async_save"] == pytest.approx(
        recorded["expected"]["idle_inside_async_save_s"], rel=1e-3)
    ops = reduced["breakdown"]["device_ops"]
    assert len(ops) == 10 and ops == sorted(ops, key=lambda kv: -kv[1])
    assert "%" + ops[0][0] == recorded["expected"]["busiest_op"]
    steps = [m for m in reduced["modules"] if m[0].startswith("jit_train_step")]
    assert len(steps) == recorded["expected"]["train_step_programs"]
    # The save was called with three steps still to run: the call waits for them.
    call = next(a for a in reduced["annotations"] if a[0] == "chipbench:async_save")
    wait = harness.layer_reader("capture_queue_wait_s")({"trace": reduced})
    assert wait == pytest.approx(steps[-1][2] - call[1], rel=1e-9) and 0.2 < wait < 0.23
    assert chipbench_trace.reduce([p for p in recorded["planes"] if "TPU" not in p["name"]], 1) is None

"""The per-layer metrics that read the library's stage tables: a traced
rehearsal of each cell reports every one of the cell's, the helper reads
nothing where the recorder dropped events or the library has no tables, and
the clone's device seconds come from the Modules line."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import harness

SAVE = {"plan_s", "capture_clone_s", "post_commit_s", "budget_wait_s", "d2h_busy_s",
        "d2h_parallelism", "write_busy_s", "finalize_s", "save_unattributed_s"}
RESTORE = {"restore_plan_s", "read_busy_s", "read_parallelism", "verify_busy_s", "place_s",
           "restore_report_s", "restore_unattributed_s"}
# Read from the device plane, which the CPU backend's profile does not have.
DEVICE_ONLY = {"clone_device_s"}
# The metrics the cells had before, where a CPU run can read them.
OLD = {"async-full": {"capture_s", "stall_uncovered_s", "staging_s", "write_drain_s",
                      "commit_over_d2h_probe"},
       "resume": {"read_drain_s", "restore_uncovered_s", "restore_over_h2d_probe"}}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return harness.copy_benchmark(tmp_path_factory.mktemp("stage_metrics"))


def test_benchmark_json_gains_seventeen_entries_with_their_cells():
    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    new = {m["name"]: m for m in bench["per_layer"][13:]}
    assert set(new) == SAVE | RESTORE | DEVICE_ONLY and len(bench["per_layer"]) == 30
    e2e = {m["name"] for m in bench["end_to_end"]}
    saves = ["neox-6.9b-l2.async-full", "pythia-1b.async-full"]
    for name, m in new.items():
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in e2e
        assert m["workloads"] == (["neox-6.9b-l2.resume"] if name in RESTORE else saves)
        assert os.path.isfile(os.path.join(harness.REPO, "chipbench", "layer_metrics",
                                           name + ".py"))


@pytest.mark.parametrize("workload,new", [
    ("neox-6.9b-l2.async-full", SAVE),
    ("pythia-1b.async-full", SAVE),
    ("neox-6.9b-l2.resume", RESTORE),
])
def test_traced_rehearsal_reports_every_new_metric_of_the_cell(checkout, workload, new):
    rc, result, err = harness.run_cell(checkout, workload, trace=1)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True and result["failed"] == 0
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert new | OLD[workload.split(".")[-1]] <= set(metrics), (new - set(metrics))
    assert not DEVICE_ONLY & set(metrics)
    for name in new:
        assert metrics[name] is not None and metrics[name] >= 0, name
    parallel = "d2h_parallelism" if new is SAVE else "read_parallelism"
    assert metrics[parallel] >= 1.0
    if new is SAVE:
        # The per-leaf clones are what the capture span holds.
        assert 0 < metrics["capture_clone_s"] <= metrics["capture_s"] * 1.001
        assert 0 < metrics["d2h_busy_s"] and 0 < metrics["write_busy_s"]
        assert metrics["post_commit_s"] > 0 and metrics["plan_s"] > 0
    else:
        # What left read_drain went to placement: the partition still holds.
        op = result["ops"][-1]["critical_path"]
        assert {"plan", "placement", "read_drain"} <= set(op)
        assert metrics["place_s"] > 0 and metrics["read_busy_s"] > 0
        assert metrics["restore_report_s"] > 0


def test_clone_device_seconds_come_from_the_modules_line():
    read = harness.layer_reader("clone_device_s")
    modules = [["jit_train_step(1)", 0.0, 0.05], ["jit_ts_capture_clone(7)", 0.05, 0.06],
               ["jit_ts_capture_clone(9)", 0.06, 0.08], ["jit_copy(3)", 0.08, 0.09]]
    run = {"trace": {"modules": modules}, "window": {"ops": [{}, {}]}}
    assert read(run) == pytest.approx(0.015)
    # A parent's clone is the anonymous jit_copy: nothing to read, not zero.
    assert read({"trace": {"modules": modules[:1] + modules[3:]}, "window": {"ops": [{}]}}) is None
    assert read({"trace": None, "window": {"ops": [{}]}}) is None


@pytest.fixture()
def library(tmp_path):
    """The library in this process, on the CPU backend, and one save + restore
    through its manager."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, harness.REPO)
    import jax
    import jax.numpy as jnp
    import torchsnapshot_tpu as ts
    from torchsnapshot_tpu.telemetry import trace

    def run_ops():
        device = jax.devices()[0]
        app = {"m": ts.PyTreeState(
            {f"w{i}": jax.device_put(jnp.full((64, 64), i, jnp.float32), device)
             for i in range(8)})}
        mgr = ts.CheckpointManager(str(tmp_path / "ckpt"), keep_last_n=1)
        mgr.async_save(1, app).wait()
        mgr.restore_latest(app)

    return ts, trace, run_ops


def _run(driver, ops=1):
    return {"traffic": {"driver": driver}, "window": {"ops": [{}] * ops}}


def test_the_helper_reads_the_windows_operations(library):
    import stage_table

    ts, trace, run_ops = library
    trace.get_recorder().reset()
    run_ops()
    save, restore = _run("save_loop"), _run("restore_loop")
    (op,) = stage_table.ops(save)
    assert op["table"]["kind"] == "async_take" and op["events"]
    assert stage_table.busy_s(save, "SPAN_STAGE_D2H") > 0
    assert stage_table.thread_s(save, "SPAN_CAPTURE_CLONE") > 0
    assert stage_table.parallelism(save, "SPAN_STORAGE_WRITE") >= 1.0
    assert stage_table.unattributed_s(restore) >= 0
    # The manager's report is on the caller's thread, the commit thread's is not.
    both = stage_table.thread_s(save, "SPAN_TELEMETRY_REPORT")
    mine = stage_table.thread_s(save, "SPAN_TELEMETRY_REPORT", caller_only=True)
    assert 0 < mine < both
    union = stage_table.busy_s(restore, "SPAN_RESTORE_PLACE", "SPAN_RESTORE_APPLY")
    assert union <= (stage_table.busy_s(restore, "SPAN_RESTORE_PLACE")
                     + stage_table.busy_s(restore, "SPAN_RESTORE_APPLY"))
    # A constant the library does not declare is a stage it does not have.
    assert stage_table.busy_s(save, "SPAN_NO_SUCH_STAGE") is None
    assert stage_table.parallelism(save, "SPAN_NO_SUCH_STAGE") is None
    # The pool's wait is read from its own span, not from the sweep's segment.
    assert harness.layer_reader("budget_wait_s")(save) == stage_table.busy_s(
        save, "SPAN_PIPELINE_BUDGET_ACQUIRE") > 0
    # More operations than the ring holds of the kind: not the window's.
    assert stage_table.ops(_run("save_loop", ops=2)) is None
    assert stage_table.ops(_run("some_other_loop")) is None


def test_a_ring_smaller_than_one_operation_reads_as_nothing(library):
    import stage_table

    ts, trace, run_ops = library
    with ts.knobs.override_trace_buffer_events(16):
        trace.get_recorder().reset()
        run_ops()
        assert trace.get_recorder().dropped > 0
        for driver in ("save_loop", "restore_loop"):
            run = _run(driver)
            assert stage_table.ops(run) is None
            assert stage_table.busy_s(run, "SPAN_STORAGE_WRITE") is None
            assert stage_table.parallelism(run, "SPAN_STORAGE_READ") is None
            assert stage_table.unattributed_s(run) is None
        for name in sorted(SAVE):
            assert harness.layer_reader(name)(_run("save_loop")) is None, name
    trace.get_recorder().reset()


def test_a_library_without_stage_tables_reads_as_nothing(library, monkeypatch):
    import stage_table

    ts, trace, run_ops = library
    from torchsnapshot_tpu.telemetry import critpath

    trace.get_recorder().reset()
    run_ops()
    monkeypatch.delattr(critpath, "stage_tables")
    run = _run("restore_loop")
    assert stage_table.ops(run) is None
    for name in sorted(RESTORE):
        assert harness.layer_reader(name)(run) is None, name


def _probe(checkout, tmp_path, *extra):
    import subprocess

    out = str(tmp_path / "probe.json")
    env = {k: v for k, v in os.environ.items() if not k.startswith("TORCHSNAPSHOT_TPU_")}
    env.update(JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.path.join(harness.REPO, ".jax_cache"))
    proc = subprocess.run(
        [sys.executable, os.path.join(checkout, "chipbench", "probe_spans.py"), "--workload",
         "neox-6.9b-l2.resume", "--seed", "2147483659", "--seconds", "1", "--out", out, *extra],
        cwd=checkout, env=env, capture_output=True, text=True, timeout=600)
    return proc, out


def test_the_probe_refuses_what_run_refuses(checkout, tmp_path):
    """It is run.py's flow: on a backend that is not the TPU it fails before
    any set-up and writes nothing."""
    proc, out = _probe(checkout, tmp_path)
    assert proc.returncode == 1 and "need 'tpu'" in proc.stderr, proc.stderr[-2000:]
    assert not os.path.exists(out) and "probe_spans:" not in proc.stdout


def test_the_probe_rehearsal_names_its_platform_and_reads_the_window(checkout, tmp_path):
    proc, out = _probe(checkout, tmp_path, "--rehearse")
    assert proc.returncode == 0, proc.stderr[-3000:]
    result_line, probe_line = proc.stdout.strip().splitlines()[-2:]
    result = json.loads(result_line)
    assert result["correct"] is True and result["rehearsal"]
    assert probe_line.startswith(
        "probe_spans: platform=cpu device_kind=cpu cell=neox-6.9b-l2.resume")
    with open(out) as f:
        probe = json.load(f)
    assert probe["device"]["platform"] == "cpu"
    assert len(probe["stage_tables"]) == len(probe["ops"]) == result["attempted"]
    assert probe["clock"]["n"] > 0 and probe["recorder"]["dropped"] == 0
    assert probe["library_spans_on_profile"]["restore:place"] >= 1
    assert probe["first_op_place_spans"] and probe["first_op_unattributed_gaps"]
    cost = probe["span_cost_us"]["profiler_on"]
    assert 0 < cost["median"] <= cost["slowest_batch"]

"""The four-chip cell `neox-6.9b-l6-4chip.resume-reshard` itself, rehearsed
on four virtual devices at toy widths: its line, the four metrics that read
the reshard spans, its control, and the arithmetic of its configuration."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import harness

CELL = "neox-6.9b-l6-4chip.resume-reshard"
RESHARD = {"reshard_plan_s", "reshard_copy_busy_s", "reshard_copied_share",
           "restore_read_amplification"}
# The restore metrics the one-chip resume cell had, where a CPU run can read
# them (the device plane is the chip's).
RESTORE = {"read_drain_s", "restore_uncovered_s", "restore_over_h2d_probe", "restore_plan_s",
           "read_busy_s", "read_parallelism", "verify_busy_s", "place_s", "restore_report_s",
           "restore_unattributed_s", "restore_dest_reuse_share"}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return harness.copy_benchmark(tmp_path_factory.mktemp("reshard_cell"))


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traced(checkout):
    rc, result, err = harness.run_cell(checkout, CELL, devices=4, trace=1)
    assert rc == 0, err[-3000:]
    return result


def test_traced_rehearsal_finds_the_four_reshard_metrics(traced):
    result = traced
    assert result["correct"] is True and result["failed"] == 0, result["checks"]
    assert result["device"]["platform"] == "cpu" and result["device"]["count"] == 4
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert RESHARD | RESTORE <= set(metrics), (RESHARD | RESTORE) - set(metrics)
    assert metrics["reshard_plan_s"] > 0 and metrics["reshard_copy_busy_s"] > 0
    # tp 2 -> 4: every saved shard feeds two boxes, so every byte is copied,
    # and every saved shard is needed whole, so nothing is read twice.
    assert metrics["reshard_copied_share"] == 100.0
    assert metrics["restore_read_amplification"] == 1.0
    # Nothing on this path takes a slab of the destination pool.
    assert metrics["restore_dest_reuse_share"] == 0.0
    assert metrics["restore_unattributed_s"] < metrics["read_drain_s"]
    assert {c["name"] for c in result["checks"]} == {
        "leaves_differing", "restored_step_gap", "loss_gap"}


def test_the_loss_gap_probe_reads_what_the_run_reads(checkout, traced):
    """`probe_loss_gap.py` replaces the save and the restore by `device_put`:
    for the run's seed it gives the run's `loss_gap`, digit for digit."""
    env = {k: v for k, v in os.environ.items() if not k.startswith("TORCHSNAPSHOT_TPU_")}
    env.update(JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=os.path.join(harness.REPO, ".jax_cache"))
    proc = subprocess.run(
        [sys.executable, os.path.join(checkout, "chipbench", "probe_loss_gap.py"), "--workload", CELL,
         "--seeds", "2147483659", "--rehearse"], cwd=checkout, env=env, capture_output=True,
        text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = proc.stdout.strip().splitlines()[-1]
    assert last.startswith("probe_loss_gap: ")
    summary = json.loads(last.split(": ", 1)[1])
    (check,) = [c for c in traced["checks"] if c["name"] == "loss_gap"]
    assert summary["platform"] == "cpu" and summary["seeds"] == 1
    assert summary["widest_loss_gap"] == check["value"] > 0
    assert summary["restore_loss_rtol"] == check["limit"]


def test_untraced_rehearsal_reports_the_end_to_end_metrics(checkout):
    rc, result, err = harness.run_cell(checkout, CELL, devices=4, seed=2147483661)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True, result["checks"]
    assert set(result["metrics"]) == {"restore_s", "setup_s"}
    assert result["attempted"] >= 1 and result["metrics"]["restore_s"]["value"] > 0
    assert not os.path.exists(harness.storage_root(checkout))


def test_one_altered_bit_on_the_other_layout_comes_out_not_correct(checkout):
    rc, result, err = harness.run_cell(checkout, CELL, "--fault", "alter_answer", devices=4,
                                       seed=2147483662)
    assert rc == 0, err[-3000:]
    assert result["fault"] == "alter_answer" and result["correct"] is False
    checks = {c["name"]: c["value"] for c in result["checks"]}
    assert checks["leaves_differing"] >= 1 and checks["restored_step_gap"] == 0


def test_fewer_devices_than_the_cell_asks_for_is_no_result(checkout):
    rc, result, err = harness.run_cell(checkout, CELL, devices=2)
    assert rc != 0 and result is None
    assert "the cell asks for 4" in err


def test_the_entries_of_the_cell(bench):
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == {"name": CELL, "config": "neox-6.9b-l6-4chip", "traffic": "resume", "chips": 4,
                    "why": cell["why"]} and len(cell["why"]) <= 200
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] == [CELL]
    (config,) = [c for c in bench["configs"] if c["name"] == "neox-6.9b-l6-4chip"]
    assert config["reduced"] == ["num_hidden_layers"] and len(config["source"]) <= 200
    # the deployment's own source first: one that starts with neox-6.9b-l2's reads
    # to the driver as that configuration again (refused once, PR 29)
    assert config["source"].startswith("https://arxiv.org/abs/2407.20143 ")
    assert "https://huggingface.co/EleutherAI/pythia-6.9b/" in config["source"]
    assert not [c["name"] for c in bench["configs"] if c is not config
                and (c["source"] in config["source"] or config["source"] in c["source"])]
    reported = {m["name"] for group in ("end_to_end", "per_layer") for m in bench[group]
                if CELL in m.get("workloads", [CELL])}
    assert reported == RESHARD | RESTORE | {
        "restore_s", "setup_s", "restore_device_idle_share"}
    for m in bench["per_layer"]:
        if m["name"] in RESHARD:
            assert (m["layer"], m["moves"], m["workloads"]) == (
                "restore: reshard", "restore_s", [CELL])
            assert os.path.isfile(os.path.join(harness.REPO, "chipbench", "layer_metrics",
                                               m["name"] + ".py"))


def test_the_configuration_is_the_one_chip_one_at_six_layers_on_two_meshes():
    def load(name):
        with open(os.path.join(harness.REPO, "chipbench", "configs", name + ".json")) as f:
            return json.load(f)

    four, one = load("neox-6.9b-l6-4chip"), load("neox-6.9b-l2")
    widths = ("hidden_size", "num_attention_heads", "intermediate_size", "vocab_size", "batch",
              "seq", "published", "reduced")
    assert {k: four[k] for k in widths} == {k: one[k] for k in widths}
    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        (entry,) = [c for c in json.load(f)["configs"] if c["name"] == four["name"]]
    assert four["source"] == entry["source"] != one["source"]
    assert four["guarantees"][:3] == one["guarantees"] and len(four["guarantees"]) == 5
    assert (four["num_hidden_layers"], four["chips"], four["mesh"], four["restore_mesh"]) == (
        6, 4, [1, 2, 2], [1, 1, 4])
    assert four["architecture"] is None and 0 < four["restore_loss_rtol"] < 0.01


def test_the_arithmetic_of_the_configuration_is_the_states():
    """`state_bytes` of the run is `jax.eval_shape` of the same init at the
    same widths: no memory is touched here."""
    import jax

    sys.path.insert(0, harness.REPO)
    from torchsnapshot_tpu.models import TransformerConfig, init_train_state

    with open(os.path.join(harness.REPO, "chipbench", "configs", "neox-6.9b-l6-4chip.json")) as f:
        c = json.load(f)
    d, layers = c["hidden_size"], c["num_hidden_layers"]
    assert c["intermediate_size"] == 4 * d
    params = layers * (12 * d * d + 2 * d) + 2 * c["vocab_size"] * d + d
    assert params == 1_621_151_744 and "1,621,151,744" in c["arithmetic"]
    assert 6 * params == 9_726_910_464 and "9,726,910,464" in c["arithmetic"]
    cfg = TransformerConfig(vocab_size=c["vocab_size"], d_model=d, n_heads=c["num_attention_heads"],
                            n_layers=layers, d_ff=c["intermediate_size"])
    state = jax.eval_shape(lambda: init_train_state(cfg, seed=0, mesh=None))
    leaves = jax.tree_util.tree_leaves(state)
    # Beside the parameters and the two moments: step, adam's count, the key.
    assert sum(x.size * x.dtype.itemsize for x in leaves) == 6 * params + 4 + 4 + 8
    assert len(leaves) - 1 == 119 and "119 leaves" in c["arithmetic"]


def test_a_ratio_of_span_args_and_a_library_without_the_span(monkeypatch):
    sys.path.insert(0, harness.REPO)
    import span_args
    import stage_table
    from torchsnapshot_tpu.telemetry import names

    def op(*spans):
        return {"table": {"stages": {}}, "caller_tid": 1,
                "events": [{"name": name, "args": args} for name, args in spans]}

    plan, copy = names.SPAN_RESHARD_PLAN, names.SPAN_RESHARD_COPY
    run = {stage_table.CACHE_KEY: [
        op((plan, {"bytes_needed": 100, "bytes_to_read": 150}), (copy, {"bytes": 40, "buf_bytes": 50}),
           (plan, {"bytes_needed": 100, "bytes_to_read": 100}), (copy, {"bytes": 10, "buf_bytes": 10})),
        op((plan, {"bytes_needed": 50, "bytes_to_read": 50})),
        op((names.SPAN_RESTORE_PLAN, {})),  # a restore of dense leaves only: left out
    ]}
    assert harness.layer_reader("reshard_copied_share")(run) == pytest.approx(100 * (0.25 + 0) / 2)
    assert harness.layer_reader("restore_read_amplification")(run) == pytest.approx((1.25 + 1) / 2)
    assert span_args.ratio({stage_table.CACHE_KEY: None}, ("SPAN_RESHARD_COPY", "bytes"),
                           ("SPAN_RESHARD_PLAN", "bytes_needed")) is None
    monkeypatch.delattr(names, "SPAN_RESHARD_COPY")
    assert harness.layer_reader("reshard_copied_share")(run) is None
    assert harness.layer_reader("reshard_copy_busy_s")(run) is None
    assert harness.layer_reader("restore_read_amplification")(run) == pytest.approx(1.125)
    monkeypatch.delattr(names, "SPAN_RESHARD_PLAN")
    assert harness.layer_reader("restore_read_amplification")(run) is None
    assert harness.layer_reader("reshard_plan_s")(run) is None

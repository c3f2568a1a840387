"""The cell `neox-6.9b-l12-lora.async-incremental` (PR 34): its files and
entries, its arithmetic against `jax.eval_shape`, a rehearsal traced and
untraced at toy size, its control, `digest_counts.py` against the bytes the
library's launch span counts, and each of its readers on a library without
the spans."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import harness
import cells
import digest_counts
import workload

CONFIG = "neox-6.9b-l12-lora"
TRAFFIC = "async-incremental"
CELL = f"{CONFIG}.{TRAFFIC}"
LAYER = "incremental: digest + skip"
NEW_METRICS = {"digest_wait_s": "save_stall_s", "digest_device_s": "save_stall_s",
               "digest_hbm_roofline": "save_stall_s", "incremental_written_share": "save_commit_s",
               "incremental_base_s": "save_stall_s"}
# What a CPU rehearsal can read of them: the device plane of a profile is the chip's.
ON_THE_HOST = {"digest_wait_s", "incremental_written_share", "incremental_base_s"}


@pytest.fixture(scope="module")
def jax():
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, harness.REPO)
    import jax

    return jax


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """The benchmark as it is, and beside the cell what `harness.add_cell`
    adds: the dense two-layer state under the same traffic, in which every
    leaf is trained and next to nothing can be skipped."""
    root = harness.copy_benchmark(tmp_path_factory.mktemp("incremental_cell"))

    def add(bench):
        name = harness.add_cell(bench, "neox-6.9b-l2", TRAFFIC, "save_commit_s")
        for m in bench["per_layer"]:
            if m["name"] in ON_THE_HOST and name not in m["workloads"]:
                m["workloads"].append(name)

    harness.edit_benchmark(root, add)
    return root


# -- files and entries ------------------------------------------------------


def test_the_entries_are_the_ones_the_cell_reports_under():
    bench = cells.benchmark()
    cell = cells.cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, TRAFFIC, 1)
    assert bench["workloads"][-1] is cell and bench["configs"][-1]["name"] == CONFIG
    assert bench["configs"][-1]["reduced"] == ["num_hidden_layers"]
    assert bench["configs"][-1]["source"] == cells.config(bench, CONFIG)["source"]
    assert len(bench["configs"][-1]["source"]) <= 200
    assert [m["name"] for m in cells.metrics_of(bench, "end_to_end", CELL)] == [
        "save_stall_s", "save_commit_s", "train_steps_per_s", "setup_s"]
    per_layer = {m["name"]: m for m in cells.metrics_of(bench, "per_layer", CELL)}
    # The five new ones last, of one layer, the new cell alone.
    assert [m["name"] for m in bench["per_layer"][-5:]] == list(NEW_METRICS)
    for name, moves in NEW_METRICS.items():
        m = per_layer[name]
        assert (m["layer"], m["moves"], m["workloads"]) == (LAYER, moves, [CELL])
        cells.layer_reader(name)
    # Every save-side metric of the dense cells but the link share, whose
    # numerator would be state bytes that never cross the link here.
    dense = {m["name"] for m in cells.metrics_of(bench, "per_layer", "neox-6.9b-l2.async-full")}
    assert set(per_layer) - set(NEW_METRICS) == dense - {"commit_over_d2h_probe"}
    assert bench["run_seconds"] == 51 and len(bench["workloads"]) == 6
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_the_traffic_file_saves_incrementally_every_128_steps():
    traffic = cells.traffic(TRAFFIC)
    assert traffic["driver"] == "save_loop" and traffic["save_every_steps"] == 128
    assert traffic["save_kwargs"] == {"incremental": True} and traffic["trace_seconds"] == 28
    assert traffic["storage"] == cells.traffic("async-full")["storage"]


def test_the_configuration_states_its_source_its_cut_and_its_guarantees():
    config = cells.config(cells.benchmark(), CONFIG)
    for key in ("source", "published", "reduced", "assumed", "deployment", "guarantees",
                "arithmetic"):
        assert config[key], key
    assert config["model"] == {"lora_rank": 8} and config["architecture"] is None
    assert config["published"] == {"num_hidden_layers": 32} and config["num_hidden_layers"] == 12
    # No width is cut: the five sized keys but the depth are neox-6.9b-l2's.
    dense = cells.config(cells.benchmark(), "neox-6.9b-l2")
    for key in ("hidden_size", "num_attention_heads", "intermediate_size", "vocab_size",
                "batch", "seq", "chips", "mesh"):
        assert config[key] == dense[key], key
    assert config["restore_loss_rtol"] == 0
    assert any("references an earlier save" in g for g in config["guarantees"])
    assert any("Retention never deletes" in g for g in config["guarantees"])


def test_the_arithmetic_is_what_eval_shape_gives(jax):
    import torchsnapshot_tpu as ts

    bench = cells.benchmark()
    config = cells.config(bench, CONFIG)
    ctx = workload.Context(jax, ts, cells.cell(bench, CELL), config, cells.traffic(TRAFFIC),
                           0, "", False, None)
    leaves = digest_counts.saved_leaves(jax, ctx.cell, config, rehearse=False)
    nbytes = [x.size * x.dtype.itemsize for x in leaves]
    state = jax.eval_shape(lambda: ctx.init_state(0, None))
    paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(
        {"params": state.params, "opt": state.opt_state})]
    adapters = [n for p, n in zip(paths, nbytes) if "lora_" in p]
    frozen = [n for p, n in zip(paths, nbytes) if "lora_" not in p and "count" not in p]
    assert (len(frozen), sum(frozen)) == (75, 2 * 2_829_160_448) == (75, 5_658_320_896)
    assert (min(frozen), max(frozen)) == (8 * 1024, 50432 * 4096 * 2)
    assert (len(adapters), sum(adapters)) == (72, 6 * 1_572_864) == (72, 9_437_184)
    assert sorted(set(adapters)) == [64 * 1024, 192 * 1024]
    assert (len(leaves), sum(nbytes)) == (149, 5_667_758_092)
    assert ctx.nbytes == sum(nbytes) + 4  # the step
    text = config["arithmetic"]
    for number in ("201,334,784", "413,138,944", "2,829,160,448", "5,658,320,896", "1,572,864",
                   "9,437,184", "149 leaves", "5,667,758,092", "5,667,758,096", "9,437,196"):
        assert number in text, number
    # The chunks of a digest-recording take, by the library's own rule.
    from torchsnapshot_tpu.io_preparer import chunk_shapes, effective_max_chunk_size_bytes

    limit = effective_max_chunk_size_bytes(True)
    chunks = [len(chunk_shapes(list(x.shape), x.dtype.name, limit)) if n > limit else 1
              for x, n in zip(leaves, nbytes)]
    assert (sum(chunks), sum(c > 1 for c in chunks), sum(c for c in chunks if c > 1)) == (
        449, 50, 350)
    assert "350 chunks of the 50 chunked leaves, 449 chunks in all" in text
    # What the digest has to do for one save.
    need = digest_counts.counts(leaves)
    assert need == {"leaves": 149, "bytes": 5_667_758_092, "lanes": 2_833_879_043,
                    "uint32_ops": 25 * 2_833_879_043}


# -- the rehearsal ------------------------------------------------------------


@pytest.fixture(scope="module")
def traced(checkout):
    rc, result, err = harness.run_cell(checkout, CELL, trace=1, seed=3400000001)
    assert rc == 0, err[-3000:]
    return result, err


def test_the_cell_rehearses_correct_untraced(checkout):
    rc, result, err = harness.run_cell(checkout, CELL, seed=3400000003)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 1
    assert result["model"] == {"lora_rank": 8} and result["save_kwargs"] == {"incremental": True}
    assert set(result["metrics"]) == set(harness.end_to_end_of(checkout, CELL))
    assert {c["name"]: c["value"] for c in result["checks"]} == {
        "saves_without_marker": 0, "leaves_differing": 0, "restored_step_gap": 0, "loss_gap": 0.0}
    assert "plan" in result["ops"][-1]["critical_path"]
    assert not [k for k in result["ops"][-1]["critical_path"] if "incremental" in k]


def test_the_cell_rehearses_correct_traced_and_reads_its_host_side_metrics(traced):
    result, err = traced
    assert result["correct"] is True and result["failed"] == 0
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert ON_THE_HOST <= set(metrics)
    # Toy sizes: 2 layers, so 2 x 2 adapters with two moments each, the count and the key.
    written = 3 * 2 * (256 * 8 + 8 * 768) * 2 + 4 + 8
    eligible = result["state_bytes"] - 4
    assert metrics["incremental_written_share"] == pytest.approx(100.0 * written / eligible)
    assert 0 < metrics["incremental_base_s"] < metrics["plan_s"]
    assert 0 < metrics["digest_wait_s"] < metrics["plan_s"]
    # The host-side readers of the dense save cells find their spans here too.
    for name in ("capture_s", "capture_clone_s", "staging_s", "d2h_busy_s", "d2h_parallelism",
                 "write_drain_s", "write_busy_s", "budget_wait_s", "finalize_s", "post_commit_s",
                 "stall_uncovered_s", "save_unattributed_s"):
        assert metrics[name] is not None and metrics[name] >= 0, name
    assert "commit_over_d2h_probe" not in metrics


def test_a_dense_state_writes_all_but_its_norm_scales(checkout):
    """Every leaf of a dense state is trained, and every leaf moves but the
    five norm scales: bfloat16 holds them at 1.0 against updates of 1e-3."""
    rc, result, err = harness.run_cell(checkout, f"neox-6.9b-l2.{TRAFFIC}", trace=1,
                                       seed=3400000005)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True and result["model"] == {}
    scales = 5 * 256 * 2
    assert result["metrics"]["incremental_written_share"]["value"] == pytest.approx(
        100.0 * (1 - scales / (result["state_bytes"] - 4)))


def test_the_control_comes_out_not_correct(checkout):
    rc, result, err = harness.run_cell(checkout, CELL, "--fault", "alter_answer", seed=3400000007)
    assert rc == 0, err[-3000:]
    assert result["fault"] == "alter_answer" and result["correct"] is False
    checks = {c["name"]: c["value"] for c in result["checks"]}
    assert checks["leaves_differing"] == 1 and checks["saves_without_marker"] == 0


# -- digest_counts.py against the library's own count -------------------------


def test_digest_counts_is_the_bytes_of_the_launch_span(jax, tmp_path):
    import torchsnapshot_tpu as ts
    from torchsnapshot_tpu.telemetry import names, trace

    bench = cells.benchmark()
    config, cell = cells.config(bench, CONFIG), cells.cell(bench, CELL)
    ctx = workload.Context(jax, ts, cell, config, cells.traffic(TRAFFIC), 0, str(tmp_path),
                           True, None)
    state = ctx.init_state(0, ctx.mesh)
    recorder = trace.get_recorder()
    mark = recorder.mark()
    ctx.save(ctx.manager().async_save, 1, ctx.app_state(state, 1)).wait()
    (launch,) = [e["args"] for e in recorder.events_since(mark)
                 if e.get("ph") == "X" and e["name"] == names.SPAN_INCREMENTAL_DIGEST_LAUNCH]
    need = digest_counts.counts(digest_counts.saved_leaves(jax, cell, config, rehearse=True))
    assert need["bytes"] == launch["bytes"] == ctx.nbytes - 4
    assert need["leaves"] == launch["leaves"] and launch["host_bytes"] == 0
    assert need["uint32_ops"] == digest_counts.OPS_PER_LANE * need["lanes"]
    # bf16 leaves hash 2-byte lanes, the int32 count and the uint32 key 4-byte ones.
    assert need["lanes"] == (need["bytes"] - 12) // 2 + 3
    assert [digest_counts.lane_bytes(n) for n in (1, 2, 4, 8)] == [1, 2, 4, 4]


# -- a library without the spans ----------------------------------------------


@pytest.mark.parametrize("name", list(NEW_METRICS))
def test_a_reader_finds_nothing_on_a_library_without_the_spans(jax, monkeypatch, name):
    """The parent of PR 34: no `SPAN_INCREMENTAL_*` constant, no skip counters
    on `take:plan`, no digest program in the window of a dense cell."""
    from torchsnapshot_tpu.telemetry import names

    import stage_table

    for constant in ("SPAN_INCREMENTAL_BASE", "SPAN_INCREMENTAL_DIGEST_LAUNCH",
                     "SPAN_INCREMENTAL_DIGEST_WAIT"):
        monkeypatch.delattr(names, constant)
    plan = {"name": names.SPAN_TAKE_PLAN, "ts": 0, "dur": 10, "args": {"rank": 0}}
    op = {"table": {"stages": {names.SPAN_TAKE_PLAN: {"busy_s": 1e-5, "thread_s": 1e-5}},
                    "unattributed_s": 0.0},
          "events": [plan], "caller_tid": 1}
    bench = cells.benchmark()
    run = {stage_table.CACHE_KEY: [op], "window": {"ops": [{}]},
           "traffic": cells.traffic(TRAFFIC), "cell": cells.cell(bench, CELL),
           "config": cells.config(bench, CONFIG), "device": {"kind": "TPU v5 lite"},
           "trace": {"modules": [["jit_train_step", 0.0, 0.1], ["jit_ts_capture_clone", 0.1, 0.2]],
                     "annotations": [], "busy_s": 0.2, "window_s": 1.0}}
    assert cells.layer_reader(name)(run) is None
    # And where the ring dropped the window's events, or nothing was traced.
    assert cells.layer_reader(name)(dict(run, **{stage_table.CACHE_KEY: None}, trace=None)) is None


def test_the_roofline_reader_divides_the_counted_bytes_by_the_measured_time(jax, capsys):
    bench = cells.benchmark()
    run = {"window": {"ops": [{}, {}]}, "cell": cells.cell(bench, CELL),
           "config": cells.config(bench, CONFIG), "device": {"kind": "TPU v5 lite"},
           "trace": {"modules": [["jit_ts_device_digest", 1.0, 1.1], ["jit_train_step", 1.1, 1.2],
                                 ["jit_ts_device_digest(1)", 9.0, 9.3]]}}
    assert cells.layer_reader("digest_device_s")(run) == pytest.approx(0.2)
    share = cells.layer_reader("digest_hbm_roofline")(run)
    assert share == pytest.approx(100.0 * 5_667_758_092 / (0.2 * 819e9))
    assert "uint32_ops" in capsys.readouterr().err
    with pytest.raises(cells.BenchError):
        cells.layer_reader("digest_hbm_roofline")(dict(run, device={"kind": "cpu"}))

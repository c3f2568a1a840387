"""The Kimi Linear cell (`kimi-linear-48b-l5-ep16.async-full`): its entries,
found by name; a rehearsal that is `correct`; the state `jax.eval_shape`
gives against the file's `arithmetic`; `step_counts.py` against a count by
hand; and `train_step_mfu`'s reader on a recorded trace."""

import json
import os
import re
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import cells
import harness
import step_counts

CONFIG = "kimi-linear-48b-l5-ep16"
CELL = CONFIG + ".async-full"
SOURCE = "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json"
DENSE_SAVE_CELL = "pythia-1b.async-full"


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config(bench):
    return cells.config(bench, CONFIG)


def test_the_configuration_and_the_cell_are_entered(bench, config):
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert entry["source"] == SOURCE == config["source"]
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers", "num_experts",
                                                     "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 27, "num_experts": 256,
                                   "vocab_size": 163840}
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "async-full", 1)
    assert config["mesh"] == [1, 1, 1] and (config["batch"], config["seq"]) == (4, 512)


def test_the_cell_reports_what_the_dense_save_cells_report_and_the_mfu(bench):
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if DENSE_SAVE_CELL in m.get("workloads", []):
                assert CELL in m["workloads"], m["name"]
    (mfu,) = [m for m in bench["per_layer"] if m["name"] == "train_step_mfu"]
    assert mfu == {"name": "train_step_mfu", "unit": "%", "better": "higher",
                   "source": "device_trace", "layer": "workload: model step",
                   "moves": "train_steps_per_s", "workloads": [CELL]}
    assert os.path.isfile(os.path.join(harness.REPO, "chipbench", "layer_metrics",
                                       "train_step_mfu.py"))


def test_the_model_block_holds_the_published_widths(config):
    m = config["model"]
    assert m["family"] == "kimi_linear"
    assert m["layer_kinds"][:config["num_hidden_layers"]] == "KKKMK"
    # Full attention at the published layers, 1-indexed.
    assert [i + 1 for i, k in enumerate(m["layer_kinds"]) if k == "M"] == \
        config["linear_attn_config"]["full_attn_layers"]
    assert (m["n_experts"], m["n_routed_experts"]) == (config["num_experts"], 256)
    assert m["experts_per_token"] == config["num_experts_per_token"] == 8
    assert m["expert_d_ff"] == config["moe_intermediate_size"] == 1024
    assert m["n_shared_experts"] == config["num_shared_experts"] == 1
    assert m["n_dense_layers"] == config["first_k_dense_replace"] == 1
    assert m["kv_lora_rank"] == config["kv_lora_rank"] == 512
    assert (m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]) == (128, 64, 128)
    assert m["kda_head_dim"] == config["linear_attn_config"]["head_dim"] == 128
    assert m["conv_kernel"] == config["linear_attn_config"]["short_conv_kernel_size"] == 4
    assert m["routed_scaling_factor"] == config["routed_scaling_factor"] == 2.446


def test_the_state_is_the_files_arithmetic(bench, config):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, harness.REPO)
    import jax
    import workload
    from torchsnapshot_tpu.models import TransformerConfig, init_train_state

    cell = cells.cell(bench, CELL)
    _, _, cfg = workload.model_config(jax, TransformerConfig, cell, config, rehearse=False)
    state = jax.eval_shape(lambda: init_train_state(cfg, seed=0))
    saved = jax.tree_util.tree_leaves(
        {"params": state.params, "opt": state.opt_state, "rng": state.rng})
    nbytes = sum(x.size * x.dtype.itemsize for x in saved)
    text = config["arithmetic"]
    assert f"{len(saved)} leaves, {nbytes:,} B" in text
    trained = sum(x.size for x in jax.tree_util.tree_leaves(state.params)
                  if x.dtype == jax.numpy.bfloat16)
    assert f"Trained: {trained:,} parameters" in text
    assert sum(x.ndim == 3 for x in saved) == 36
    assert sum(x.dtype == jax.numpy.float32 for x in saved) == 4
    assert int(re.search(r"(\d+) leaves under 4 KiB", text).group(1)) == sum(
        x.size * x.dtype.itemsize < 4096 for x in saved)


# A toy configuration counted by hand: d 8, 2 heads of 4 (KDA), MLA 2 x (3 +
# 1) and v 2 from a latent of 4, 4 of 8 experts of width 6 held, 2 a token.
TOY = {"hidden_size": 8, "num_attention_heads": 2, "vocab_size": 10, "batch": 1, "seq": 4,
       "num_hidden_layers": 3, "intermediate_size": 16,
       "model": {"layer_kinds": "KMK", "kda_head_dim": 4, "qk_nope_head_dim": 3,
                 "qk_rope_head_dim": 1, "v_head_dim": 2, "kv_lora_rank": 4, "expert_d_ff": 6,
                 "conv_kernel": 2, "n_dense_layers": 1, "n_shared_experts": 1,
                 "experts_per_token": 2, "n_experts": 4, "n_routed_experts": 8}}


def test_step_counts_is_a_count_by_hand():
    got = step_counts.counts(TOY)
    t = 4
    # KDA: q/k/v 3 x 8 x 8, convs 3 x 2 x 8, f and g 2 x (8 x 4 + 4 x 8), b 8 x 2, o 8 x 8.
    kda = 192 + 48 + 128 + 16 + 64
    # MLA: q 8 x 8, kv_a 8 x 5, kv_b 4 x 10, o 4 x 8.
    mla = 64 + 40 + 40 + 32
    assert got["kda_projections"] == 3 * 2 * t * 2 * kda
    assert got["mla_projections"] == 3 * 2 * t * mla
    assert got["dense_mlp"] == 3 * 2 * t * 3 * 8 * 16
    assert got["shared_experts"] == 3 * 2 * t * 2 * 3 * 8 * 6
    assert got["router"] == 3 * 2 * t * 2 * 8 * 8
    # 2 of 8 a token, 4 held: 1 local assignment a token.
    assert got["routed_experts"] == 3 * 2 * t * 2 * 1 * 3 * 8 * 6
    assert got["head"] == 3 * 2 * t * 8 * 10
    # 10 causal pairs, 2 heads, 2 x (4 + 2) a pair.
    assert got["mla_attention"] == 3 * 10 * 2 * 12
    # One chunk of 4: P 2K x 2.5 pairs, A 2K x 1.5, solve 4 x 8, P delta 2V x 2.5, states 6KV.
    per = 20 + 12 + 32 + 20 + 96
    assert step_counts.kda_chunk_flops(4, 4, 4) == per
    assert got["kda_chunk"] == 3 * 2 * t * 2 * per
    assert got["flops"] == sum(v for k, v in got.items() if k not in ("flops", "tokens"))


def _run(config, modules, annotations):
    return {"trace": {"modules": modules, "annotations": annotations}, "config": config,
            "device": {"kind": "TPU v5 lite"}}


def test_train_step_mfu_reads_the_steps_outside_a_save(config):
    read = harness.layer_reader("train_step_mfu")
    annotations = [["chipbench:async_save", 1.0, 1.1], ["chipbench:wait", 5.0, 5.2],
                   ["chipbench:async_save", 9.0, 9.1]]
    modules = [["jit_train_step(123)", 0.0, 0.05], ["jit_train_step(123)", 2.0, 2.09],
               ["jit_train_step(123)", 6.0, 6.07], ["jit_train_step(123)", 10.0, 10.2],
               ["jit_fingerprint", 6.5, 6.6]]
    want = 100.0 * step_counts.counts(config)["flops"] / (0.06 * 197e12)
    assert read(_run(config, modules, annotations)) == pytest.approx(want)
    assert 0 < want <= 100


def test_train_step_mfu_reads_nothing_without_a_step_program_or_shapes_to_count(config):
    read = harness.layer_reader("train_step_mfu")
    assert read(_run(config, [["jit_fingerprint", 0.0, 0.1]], [])) is None
    assert read({**_run(config, [], []), "trace": None}) is None
    dense = cells.config(cells.benchmark(), "neox-6.9b-l2")
    assert step_counts.counts(dense) is None
    assert read(_run(dense, [["jit_train_step(1)", 0.0, 0.1]], [])) is None


def test_the_cell_rehearses_and_is_correct(tmp_path):
    root = harness.copy_benchmark(tmp_path)
    rc, result, err = harness.run_cell(root, CELL)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True, result["checks"]
    assert result["model"]["family"] == "kimi_linear"
    assert set(result["metrics"]) == set(harness.end_to_end_of(root, CELL))

"""Model FLOPs of one train step of a configuration with GLM-5's layers
(`glm_dsa`), by part, and the operations and bytes of its DSA op, reckoned
from the configuration file's shapes alone (no library, no jax):
`probe_glm_kernels.py` divides them by device seconds. `step_counts.py`
counts Kimi's shapes and returns None for these (PERF.md §7: which reader
should count this family is a benchmark PR's to wire).

A step is forward and backward: three times the forward's operations.
Recomputed work (every layer's two halves, the attention's query blocks and
the loss's chunks are rematerialised in the backward pass) does not count.
The forward, a token:

- every matmul on the token's path, 2 operations a weight: the query and KV
  latents and their up-projections at the held heads, the output
  projection, the indexer's three projections, the dense SwiGLU and the
  shared expert at their held columns, the router, the head;
- the routed experts at their expected local assignments: a token makes
  `num_experts_per_tok` of them over the router's `n_routed_experts`
  (`model.n_routed_experts`), `model.n_experts` of which are held, 3
  matmuls of `hidden x moe_intermediate_size` each;
- DSA as its definition asks, whatever the kernel skips or does not:
  - the indexer over every causal pair, `seq (seq + 1) / 2` a sequence:
    `2 index_head_dim` for each head's dot product and 2 for its weighted
    ReLU, `index_n_heads` heads;
  - the attention over the selected pairs alone, `min(t + 1, index_topk)`
    for query `t`: `2 (qk_nope + qk_rope) + 2 v_head_dim` a pair and held
    head;
- the embedding gather, norms, rotary, activations, the selection, the
  indexer's KL and the loss: 0.

    python chipbench/glm_counts.py chipbench/configs/glm-5-l5-ep32-tp8.json
"""

import json
import sys
from typing import Any, Dict

BACKWARD_OVER_FORWARD = 2


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def selected_pairs(seq: int, topk: int) -> int:
    """Sum over queries t < seq of min(t + 1, topk)."""
    k = min(topk, seq)
    return k * (k + 1) // 2 + (seq - k) * k


def dsa_op(config: Dict[str, Any]) -> Dict[str, float]:
    """One layer's DSA forward over a batch, from its inputs (q, k, v at the
    held heads in bf16; the indexer's queries, key and head weights in
    float32) to its output: the operations its definition requires and the
    bytes an ideal kernel moves (each input read once, the output written
    once)."""
    m = config["model"]
    b, s = config["batch"], config["seq"]
    h, hi, di = config["num_attention_heads"], m["index_n_heads"], m["index_head_dim"]
    dqk, dv = m["qk_nope_head_dim"] + m["qk_rope_head_dim"], m["v_head_dim"]
    indexer = b * causal_pairs(s) * hi * (2 * di + 2)
    attention = b * selected_pairs(s, m["index_topk"]) * h * (2 * dqk + 2 * dv)
    bf16, f32 = 2, 4
    nbytes = b * s * (bf16 * h * (2 * dqk + 2 * dv)  # q, k, v in; out
                      + f32 * (hi * di + di + hi))  # q^I, k^I, w in
    return {"flops": float(indexer + attention), "bytes": float(nbytes),
            "indexer_flops": float(indexer), "attention_flops": float(attention)}


def counts(config: Dict[str, Any]) -> Dict[str, float]:
    """Operations of one train step, by part, and their sum `flops`."""
    m = config["model"]
    b, s = config["batch"], config["seq"]
    tokens = b * s
    d, h, v = config["hidden_size"], config["num_attention_heads"], config["vocab_size"]
    rq, r = m["q_lora_rank"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    hi, di = m["index_n_heads"], m["index_head_dim"]
    tp = m["tp_size"]
    f_dense = config["intermediate_size"] // tp
    f_shared = m["expert_d_ff"] * m["n_shared_experts"] // tp
    n_layers, n_dense = config["num_hidden_layers"], m["n_dense_layers"]
    n_moe = n_layers - n_dense
    mla = 2 * (d * rq + rq * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) + h * dv * d)
    index_proj = 2 * (rq * hi * di + d * di + d * hi)
    dsa = dsa_op(config)
    per_assignment = m["experts_per_token"] * m["n_experts"] / m["n_routed_experts"]
    forward = {
        "mla_projections": n_layers * tokens * mla,
        "indexer_projections": n_layers * tokens * index_proj,
        "indexer_scores": n_layers * dsa["indexer_flops"],
        "sparse_attention": n_layers * dsa["attention_flops"],
        "dense_mlp": n_dense * tokens * 2 * 3 * d * f_dense,
        "shared_expert": n_moe * tokens * 2 * 3 * d * f_shared,
        "router": n_moe * tokens * 2 * d * m["n_routed_experts"],
        "experts": n_moe * tokens * per_assignment * 2 * 3 * d * m["expert_d_ff"],
        "head": tokens * 2 * d * v,
    }
    step = {k: float((1 + BACKWARD_OVER_FORWARD) * x) for k, x in forward.items()}
    step["flops"] = sum(step.values())
    return step


if __name__ == "__main__":
    with open(sys.argv[1]) as f:
        cfg = json.load(f)
    print(json.dumps({"step": counts(cfg), "dsa_op": dsa_op(cfg)}, indent=1))

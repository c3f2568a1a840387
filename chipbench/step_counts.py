"""Model FLOPs of one train step of a configuration with Kimi Linear's
layers, reckoned from the configuration file's shapes alone (no library, no
jax):
`train_step_mfu` divides them by the step program's device seconds, and
`probe_kimi_kernels.py` takes the KDA op's and the grouped expert matmul's
operations and bytes from here.

A step is forward and backward: three times the forward's operations (the
backward takes two matmuls for each of the forward's). Recomputed work (the
KDA chunk body is rematerialised in the backward pass) does not count. The
forward, a token:

- every matmul on the token's path, 2 operations a weight: the projections
  of each layer, the depthwise convolutions (a multiply-add a tap), the dense
  SwiGLU, the shared expert, the router, the head;
- the routed experts at their expected local assignments: a token makes
  `experts_per_token` of them over `n_routed_experts`, `n_experts` of which
  are held here, so `experts_per_token * n_experts / n_routed_experts` a
  token, 3 matmuls of `hidden x expert_d_ff` each;
- MLA's score and value products over the causal pairs, `seq (seq + 1) / 2`
  a sequence and head, `2 (qk_nope + qk_rope + v)` each;
- the KDA chunk's own arithmetic (ops/kda.py), with `C` tokens a chunk, for
  a head: the pair products of A and P over the causal pairs of the chunk
  (`2 K` each), the triangular solve for `[U, W]` (`C (K + V)` a token on
  average), `P delta` (`2 V` a pair), and three state products (`W S`, `Q S`,
  `K^T delta`: `2 K V` a token each);
- the embedding gather, norms, activations and the loss: 0.

    python chipbench/step_counts.py chipbench/configs/kimi-linear-48b-l5-ep16.json
"""

import json
import sys
from typing import Any, Dict, Optional

KDA_CHUNK = 64
BACKWARD_OVER_FORWARD = 2


def kda_chunk_flops(seq: int, k: int, v: int) -> float:
    """Forward operations a token and head of the chunked KDA recurrence."""
    c = min(KDA_CHUNK, seq)
    pairs = (c + 1) / 2  # causal pairs a token, on average, diagonal included
    strict = (c - 1) / 2
    return (2 * k * pairs  # P
            + 2 * k * strict  # A
            + c * (k + v)  # solve (I + A) [U, W] = beta [V, K_G]: C^2 / 2 (K + V) a chunk
            + 2 * v * pairs  # P delta
            + 3 * 2 * k * v)  # W S0, Q_G S0, K_end^T delta


def counts(config: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """Operations of one train step, by part, and their sum `flops`; None
    where the configuration lacks a shape the count needs (a `model` block
    without the fields above: a dense configuration)."""
    try:
        return _by_part(config)
    except KeyError:
        return None


def _by_part(config: Dict[str, Any]) -> Dict[str, float]:
    m = config["model"]
    d, h = config["hidden_size"], config["num_attention_heads"]
    vocab, batch, seq = config["vocab_size"], config["batch"], config["seq"]
    tokens = batch * seq
    dk = m["kda_head_dim"]
    hk = h * dk
    dqk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    dv, r = m["v_head_dim"], m["kv_lora_rank"]
    fe = m["expert_d_ff"]
    kinds = m["layer_kinds"][: config["num_hidden_layers"]]
    kda_weights = (3 * d * hk + 3 * m["conv_kernel"] * hk + 2 * (d * dk + dk * hk) + d * h
                   + hk * d)
    mla_weights = d * h * dqk + d * (r + m["qk_rope_head_dim"]) + r * h * (m["qk_nope_head_dim"]
                                                                        + dv) + h * dv * d
    n_kda, n_mla = kinds.count("K"), kinds.count("M")
    n_dense = m["n_dense_layers"]
    n_moe = len(kinds) - n_dense
    per_assignment = 3 * d * fe
    local_share = m["experts_per_token"] * m["n_experts"] / m["n_routed_experts"]
    fwd = {
        "kda_projections": 2 * tokens * n_kda * kda_weights,
        "mla_projections": 2 * tokens * n_mla * mla_weights,
        "dense_mlp": 2 * tokens * n_dense * 3 * d * config["intermediate_size"],
        "shared_experts": 2 * tokens * n_moe * m["n_shared_experts"] * 3 * d * fe,
        "router": 2 * tokens * n_moe * d * m["n_routed_experts"],
        "routed_experts": 2 * tokens * n_moe * local_share * per_assignment,
        "head": 2 * tokens * d * vocab,
        "mla_attention": n_mla * batch * h * seq * (seq + 1) / 2 * 2 * (dqk + dv),
        "kda_chunk": n_kda * tokens * h * kda_chunk_flops(seq, dk, dk),
    }
    out = {name: (1 + BACKWARD_OVER_FORWARD) * value for name, value in fwd.items()}
    out["flops"] = sum(out.values())
    out["tokens"] = tokens
    return out


def kda_op(config: Dict[str, Any]) -> Dict[str, float]:
    """One KDA op's forward at the configuration's shapes: operations, and
    the bytes it has to move (q, k, v and the gate in, o out, in the dtypes
    the model hands them: bf16 q/k/v/o, float32 g and beta)."""
    h, seq, batch = config["num_attention_heads"], config["seq"], config["batch"]
    dk = config["model"]["kda_head_dim"]
    tokens = batch * seq
    return {"flops": tokens * h * kda_chunk_flops(seq, dk, dk),
            "bytes": tokens * h * (4 * dk * 2 + dk * 4 + 4)}


def grouped_matmul(config: Dict[str, Any], rows: int) -> Dict[str, float]:
    """One grouped expert matmul (`hidden -> expert_d_ff`) of `rows` routed
    rows: operations, and the bytes of the rows, the held experts' weights
    and the output in bf16."""
    d, fe = config["hidden_size"], config["model"]["expert_d_ff"]
    e = config["model"]["n_experts"]
    return {"flops": 2 * rows * d * fe, "bytes": 2 * (rows * d + e * d * fe + rows * fe)}


def main(argv) -> None:
    with open(argv[1]) as f:
        config = json.load(f)
    print(json.dumps(counts(config)))


if __name__ == "__main__":
    main(sys.argv)

"""The DSA op of `glm-5-l5-ep32-tp8` jitted alone at the cell's shapes and
timed on the chip, with its operations and bytes from `glm_counts.py` and
its share of the roofline; and the cell's step by named scope.

    python chipbench/probe_glm_kernels.py --out <file> [--rehearse]

- **the DSA op** (`models.glm_dsa.sparse_attention`: the indexer's scores,
  the top-k selection and the attention masked to it, a block of queries at
  a time): the forward, and forward plus backward (the gradient of a
  projection of the output to q, k, v and the indexer's inputs; three times
  the forward's operations by `glm_counts`' rule), at batch x 4,096 with the
  held heads. Its operations are those DSA's definition requires (the
  indexer over the causal pairs, the attention over the selected pairs), so
  the share reads what masking the unselected pairs costs. Timed as
  `probe_kimi_kernels.py` times: the median of its `REPS` calls, each
  waited for, after two warm calls.
- **the step by part**: ten steps of the cell's `jit_train_step` under the
  profiler, the device seconds of its operations summed by the step's named
  scope (`glm:mla`, `glm:indexer`, `glm:select`, `glm:sparse_attn`,
  `glm:router`, `glm:experts`, `glm:shared`; the rest by what XLA's
  metadata names), forward and backward apart, a step's mean; beside it the
  step's model FLOPs (`glm_counts.counts`) over its device seconds and the
  bf16 peak.

A share is the larger of operations over the bf16 peak and bytes over the
HBM peak (`peaks.json`), over the time; the bound is the one that is larger.
The last line is the JSON also written to `--out`."""

import argparse
import json
import os
import sys
from typing import Any, Dict

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import cells
import glm_counts
import probe_kimi_kernels as kernels
import workload

CELL = "glm-5-l5-ep32-tp8.async-full-64"
SCOPES = ("glm:mla", "glm:indexer", "glm:select", "glm:sparse_attn", "glm:router",
          "glm:experts", "glm:shared")


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--out", required=True)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp

    from torchsnapshot_tpu.models import TransformerConfig, init_train_state, make_train_step
    from torchsnapshot_tpu.models import glm_dsa as glm

    bench = cells.benchmark()
    cell = cells.cell(bench, CELL)
    config = cells.config(bench, cell["config"])
    sizes, _, cfg = workload.model_config(jax, TransformerConfig, cell, config, args.rehearse)
    # glm_counts reads the file's keys: lay the run's sizes over them.
    shaped = dict(config, hidden_size=cfg.d_model, num_attention_heads=cfg.n_heads,
                  intermediate_size=cfg.d_ff, num_hidden_layers=cfg.n_layers,
                  vocab_size=cfg.vocab_size, batch=sizes["batch"], seq=sizes["seq"])
    dev = jax.devices()[0]
    peaks = (cells.peaks(dev.device_kind) if not args.rehearse
             else {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0})
    b, s, h = sizes["batch"], sizes["seq"], cfg.n_heads
    dqk, dv = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim, cfg.v_head_dim
    hi, di = cfg.index_n_heads, cfg.index_head_dim
    keys = jax.random.split(jax.random.PRNGKey(0), 7)
    q = jax.random.normal(keys[0], (b, s, h, dqk), jnp.float32).astype(cfg.dtype)
    k = jax.random.normal(keys[1], (b, s, h, dqk), jnp.float32).astype(cfg.dtype)
    v = jax.random.normal(keys[2], (b, s, h, dv), jnp.float32).astype(cfg.dtype)
    qi = jax.random.normal(keys[3], (b, s, hi, di), jnp.float32)
    ki = jax.random.normal(keys[4], (b, s, di), jnp.float32)
    w = jax.random.normal(keys[5], (b, s, hi), jnp.float32) * (hi * di) ** -0.5
    probe = jnp.cos(jnp.arange(dv, dtype=jnp.float32))

    def op(*xs):
        out, kl = glm.sparse_attention(cfg, *xs)
        return jnp.sum(out.astype(jnp.float32) * probe) + kl

    fwd = jax.jit(lambda *xs: glm.sparse_attention(cfg, *xs))
    fwd_bwd = jax.jit(jax.grad(op, argnums=(0, 1, 2, 3, 4, 5)))
    need = glm_counts.dsa_op(shaped)
    out: Dict[str, Any] = {
        "dsa_forward": kernels.share(need, kernels.timed(jax, fwd, q, k, v, qi, ki, w), peaks),
        "dsa_forward_backward": kernels.share(
            {n: 3 * x for n, x in need.items()},
            kernels.timed(jax, fwd_bwd, q, k, v, qi, ki, w), peaks),
    }
    del q, k, v, qi, ki, w

    state = init_train_state(cfg, seed=0)
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, sizes["vocab_size"], (b, s), dtype=np.int32))
    kernels.SCOPES = SCOPES
    out["step"] = kernels.step_by_part(jax, make_train_step(cfg), state, tokens)
    flops = glm_counts.counts(shaped)
    out["step"]["flops"] = flops
    if out["step"]["step_s"]:  # no device plane on the CPU
        out["step"]["model_flops_share_pct"] = (
            100.0 * flops["flops"] / out["step"]["step_s"] / peaks["bf16_flops_per_s"])
    out.update(platform=dev.platform, device_kind=dev.device_kind, cell=CELL,
               rehearsal=bool(args.rehearse))
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()

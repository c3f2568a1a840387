"""The KDA op and the grouped expert matmul of `kimi-linear-48b-l5-ep16`,
each jitted alone at the cell's shapes and timed on the chip, with its
operations and bytes from `step_counts.py` and its share of the roofline.

    python chipbench/probe_kimi_kernels.py --out <file> [--rehearse]

Both run inside the one `jit_train_step` program in the cell, where the
trace's reduction keeps programs and the ten longest operations only; so
their own shares come from here. Timed: the median of `REPS` calls, each
waited for (`block_until_ready`), after two warm calls.

- **KDA** (`ops/kda.py`): the forward, and forward plus backward (the
  gradient of a projection of `o` to every input; three times the forward's
  operations by `step_counts`' rule), at batch x seq x heads x 128 x 128.
- **grouped matmul** (`jax.lax.ragged_dot`, hidden -> expert width): as
  the model hands it every (token, slot) row, batch x seq x experts a token,
  sorted by held expert, with the group sizes that a top-8 of 256 sigmoid
  routing of random scores gives the 16 held experts and the other rows in
  the last group (`models.kimi_linear._dispatch`). Its operations are those
  of every row it is handed. Beside it, the same kernel with the held rows
  alone in groups (the rest past them), and a dense matmul of the held rows:
  what the kernel costs for the rows in its groups and what the held rows
  cost without it.

A share is the larger of operations over the bf16 peak and bytes over the
HBM peak (`peaks.json`), over the time; the bound is the one that is larger.

Beside them, what the choices inside cost: the KDA op's forward plus
backward by sub-chunk (`ops.kda.SUB`; a sub-chunk of the whole chunk forms
every pair channel by channel). And
**the step by part**: ten steps of the cell's `jit_train_step` under the
profiler, the device seconds of its operations summed by the step's named
scope (`kimi:kda`, `kimi:mla`, `kimi:router`, `kimi:experts`,
`kimi:shared`; the rest by what XLA's metadata names), forward and backward
apart, a step's mean. The last line is the JSON also written to `--out`."""

import argparse
import glob
import json
import os
import re
import shutil
import statistics
import tempfile
import sys
import time
from typing import Any, Callable, Dict

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import cells
import device_trace
import step_counts
import workload

CELL = "kimi-linear-48b-l5-ep16.async-full"
REPS = 20
PROFILED_STEPS = 10
SCOPES = ("kimi:kda", "kimi:mla", "kimi:router", "kimi:experts", "kimi:shared")
_META = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name="([^"]*)"')


def timed(jax, fn: Callable, *args) -> float:
    for _ in range(2):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(REPS):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t)
    return statistics.median(times)


def part_of(op_name: str) -> str:
    """What of the step an operation belongs to, by its metadata."""
    side = "backward" if "transpose(" in op_name else "forward"
    for scope in SCOPES:
        if scope in op_name:
            return f"{scope} {side}"
    if "optax" in op_name or "adam" in op_name or "apply_updates" in op_name:
        return "optimizer"
    if "softmax_cross_entropy" in op_name or "log_softmax" in op_name:
        return f"loss {side}"
    return f"other {side}"


def step_by_part(jax, step, state, tokens) -> Dict[str, Any]:
    """Device seconds a step of each part, from the profiler's trace of
    PROFILED_STEPS steps of the compiled step (loops and conditionals are
    left out: the operations of their bodies are counted themselves)."""
    hlo = step.lower(state, tokens).compile().as_text()
    scope_of = {}
    for line in hlo.splitlines():
        found = _META.match(line)
        if found:
            scope_of[found.group(1)] = found.group(2)
    for _ in range(2):
        state, loss = step(state, tokens)
    loss.block_until_ready()
    directory = tempfile.mkdtemp(prefix="kimi_step_")
    try:
        jax.profiler.start_trace(directory)
        for _ in range(PROFILED_STEPS):
            state, loss = step(state, tokens)
        loss.block_until_ready()
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(directory, "plugins", "profile", "*", "*.xplane.pb"))
        planes = device_trace.planes(jax.profiler.ProfileData.from_file(path))
    finally:
        shutil.rmtree(directory, ignore_errors=True)
    parts: Dict[str, float] = {}
    programs = 0.0
    for plane in planes:
        if not plane["name"].startswith(device_trace.DEVICE_PLANE):
            continue
        for line in plane["lines"]:
            for name, _, duration in line["events"]:
                if line["name"] == device_trace.MODULES_LINE:
                    if name.startswith("jit_train_step"):
                        programs += duration / 1e9
                    continue
                op = name.split(" = ")[0].lstrip("%")
                if op.startswith(("while", "conditional")) or op not in scope_of:
                    continue
                part = part_of(scope_of[op])
                parts[part] = parts.get(part, 0.0) + duration / 1e9
        break  # the first device
    per_step = {k: v / PROFILED_STEPS for k, v in sorted(parts.items(), key=lambda kv: -kv[1])}
    return {"step_s": programs / PROFILED_STEPS, "ops_s": sum(per_step.values()),
            "by_part_s": per_step}


def share(need: Dict[str, float], seconds: float, peaks: Dict[str, float]) -> Dict[str, Any]:
    compute = need["flops"] / peaks["bf16_flops_per_s"]
    memory = need["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": seconds, **need, "roofline_pct": 100.0 * max(compute, memory) / seconds,
            "bound": "compute" if compute >= memory else "memory"}


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--out", required=True)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp

    from torchsnapshot_tpu.models import TransformerConfig
    from torchsnapshot_tpu.ops.kda import chunk_kda

    bench = cells.benchmark()
    cell = cells.cell(bench, CELL)
    config = cells.config(bench, cell["config"])
    sizes, _, cfg = workload.model_config(jax, TransformerConfig, cell, config, args.rehearse)
    # step_counts reads the file's keys: lay the run's sizes over them.
    shaped = dict(config, hidden_size=cfg.d_model, num_attention_heads=cfg.n_heads,
                  batch=sizes["batch"], seq=sizes["seq"])
    dev = jax.devices()[0]
    peaks = (cells.peaks(dev.device_kind) if not args.rehearse
             else {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0})
    b, s, h, dk = sizes["batch"], sizes["seq"], cfg.n_heads, cfg.kda_head_dim
    keys = jax.random.split(jax.random.PRNGKey(0), 8)

    def unit(key):
        x = jax.random.normal(key, (b, s, h, dk), jnp.float32)
        return (x / jnp.linalg.norm(x, axis=-1, keepdims=True)).astype(cfg.dtype)

    q, k = unit(keys[0]), unit(keys[1])
    v = jax.random.normal(keys[2], (b, s, h, dk), jnp.float32).astype(cfg.dtype)
    a = jax.random.uniform(keys[3], (h, 1), jnp.float32, 1.0, 16.0)
    g = -a * jax.nn.softplus(jax.random.normal(keys[4], (b, s, h, dk), jnp.float32) - 4.0)
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], (b, s, h), jnp.float32))
    probe = jnp.cos(jnp.arange(dk, dtype=jnp.float32))
    fwd = jax.jit(lambda *xs: chunk_kda(*xs, scale=dk ** -0.5))
    fwd_bwd = jax.jit(jax.grad(
        lambda *xs: jnp.sum(chunk_kda(*xs, scale=dk ** -0.5).astype(jnp.float32) * probe),
        argnums=(0, 1, 2, 3, 4)))
    kda_need = step_counts.kda_op(shaped)
    out: Dict[str, Any] = {
        "kda_forward": share(kda_need, timed(jax, fwd, q, k, v, g, beta), peaks),
        "kda_forward_backward": share({n: 3 * x for n, x in kda_need.items()},
                                      timed(jax, fwd_bwd, q, k, v, g, beta), peaks),
    }

    tokens, d, fe = b * s, cfg.d_model, cfg.expert_d_ff
    rows = tokens * cfg.experts_per_token
    scores = jax.random.uniform(keys[6], (tokens, cfg.n_routed_experts))
    _, chosen = jax.lax.top_k(scores, cfg.experts_per_token)
    counts = np.bincount(np.asarray(chosen).ravel(), minlength=cfg.n_routed_experts)
    held = counts[cfg.first_expert:cfg.first_expert + cfg.n_experts].astype(np.int32)
    lhs = jax.random.normal(keys[7], (rows, d), jnp.float32).astype(cfg.dtype)
    rhs = (jax.random.normal(keys[7], (cfg.n_experts, d, fe), jnp.float32) / d ** 0.5).astype(
        cfg.dtype)
    grouped = jax.jit(jax.lax.ragged_dot)
    every = held.copy()
    every[-1] += rows - every.sum()
    n_held = int(held.sum())
    dense = jax.jit(lambda x, w: x @ w)
    out.update({
        "held_rows": n_held,
        "rows_handed": rows,
        "group_sizes": every.tolist(),
        "grouped_matmul": share(step_counts.grouped_matmul(shaped, rows),
                                timed(jax, grouped, lhs, rhs, jnp.asarray(every)), peaks),
        "grouped_matmul_held_rows_alone_in_groups": share(
            step_counts.grouped_matmul(shaped, n_held),
            timed(jax, grouped, lhs, rhs, jnp.asarray(held)), peaks),
        "dense_matmul_of_held_rows": share(
            step_counts.grouped_matmul(dict(shaped, model=dict(shaped["model"], n_experts=1)),
                                       n_held),
            timed(jax, dense, lhs[:n_held], rhs[0]), peaks),
    })
    from torchsnapshot_tpu.models import init_train_state, make_train_step
    from torchsnapshot_tpu.ops import kda

    sweep = {}
    for sub in (kda.SUB, 8, 32, 64):
        default, kda.SUB = kda.SUB, sub
        try:
            variant = jax.jit(jax.grad(
                lambda *xs: jnp.sum(chunk_kda(*xs, scale=dk ** -0.5).astype(jnp.float32) * probe),
                argnums=(0, 1, 2, 3, 4)))
            sweep[f"sub_{sub}"] = timed(jax, variant, q, k, v, g, beta)
        finally:
            kda.SUB = default
    out["kda_forward_backward_by_sub_chunk_s"] = sweep

    del lhs, rhs, q, k, v, g

    state = init_train_state(cfg, seed=0)
    step_tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, sizes["vocab_size"], (b, s), dtype=np.int32))
    need = step_counts.counts(dict(shaped, vocab_size=sizes["vocab_size"]))["flops"]
    out["step"] = step_by_part(jax, make_train_step(cfg), state, step_tokens)
    out["step"]["flops"] = need
    out.update(platform=dev.platform, device_kind=dev.device_kind, cell=CELL,
               rehearsal=bool(args.rehearse))
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()

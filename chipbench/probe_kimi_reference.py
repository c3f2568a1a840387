"""The cell's model against the plain reference at the published widths.

    python chipbench/probe_kimi_reference.py --seeds 0 1 2 --out <file> [--rehearse]

For each seed, the state `kimi-linear-48b-l5-ep16.async-full` builds from it
(`workload.Context.init_state`) and the tokens of its first step (`seed + 1`):

- **logits and loss of the batch**: the model (`kimi_linear.forward`, bf16
  as the cell runs it) against `kimi_linear_reference.py` in float32 under
  `jax.default_matmul_precision("highest")`, computed a layer at a time so
  that one layer's float32 weights are live at a time;
- **the gradient of every trained leaf for the batch's first sequence**: the
  model's (`jax.grad` of the step's loss) against the reference's, chained
  back a layer at a time through `jax.vjp` (a KDA layer's token-by-token
  states, 1 GiB a sequence, are live for one layer only);
- **the next precision below the configuration's**: the same reference on
  the weights rounded to float8_e4m3fn against the float32 reference, which a
  limit has to refuse.

Each is the norm of the difference over the norm of the reference (per leaf
for gradients, and over all of them); the loss as a relative gap. Nothing
here decides `correct`: PERF.md records the readings and the limits set from
them. The last line is the JSON also written to `--out`."""

import argparse
import json
import os
import sys
import time
from typing import Any, Dict, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import cells
import workload

CELL = "kimi-linear-48b-l5-ep16.async-full"


def rel(jnp, got, want) -> float:
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seeds", type=int, nargs="+", default=[0])
    p.add_argument("--out", required=True)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import optax

    import kimi_linear_reference as ref
    from torchsnapshot_tpu.models import TransformerConfig, init_train_state
    from torchsnapshot_tpu.models import kimi_linear as kl

    bench = cells.benchmark()
    cell = cells.cell(bench, CELL)
    config = cells.config(bench, cell["config"])
    sizes, _, cfg = workload.model_config(jax, TransformerConfig, cell, config, args.rehearse)
    kinds = cfg.layer_kinds[:cfg.n_layers]
    f32 = ref.f32

    layer = {kind: jax.jit(lambda block, x, kind=kind: ref.layer(cfg, kind, block, x)[0])
             for kind in set(kinds)}
    layer_vjp = {kind: jax.jit(lambda block, x, dx, kind=kind: jax.vjp(
        lambda b, y: ref.layer(cfg, kind, b, y)[0], block, x)[1](dx)) for kind in set(kinds)}

    @jax.jit
    def head_logits(params, x):
        return ref.rmsnorm(x, params["ln_f_scale"]) @ params["unembed"]

    def ref_logits(params, tokens, cast):
        x = cast(params["embed"])[tokens]
        for kind, block in zip(kinds, params["layers"]):
            x = layer[kind](cast(block), x)
        return head_logits(cast({k: params[k] for k in ("ln_f_scale", "unembed")}), x)

    def ref_grads(params, tokens):
        xs = [f32(params["embed"])[tokens]]
        for kind, block in zip(kinds, params["layers"]):
            xs.append(layer[kind](f32(block), xs[-1]))
        top = f32({k: params[k] for k in ("ln_f_scale", "unembed")})
        (g_top, dx) = jax.jit(jax.grad(
            lambda t, x: ref.loss_of_logits(head_logits(t, x), tokens), (0, 1)))(top, xs[-1])
        grads = {**g_top, "layers": [None] * len(kinds)}
        for i in reversed(range(len(kinds))):
            g_block, dx = layer_vjp[kinds[i]](f32(params["layers"][i]), xs[i], dx)
            grads["layers"][i] = g_block
        grads["embed"] = jnp.zeros(params["embed"].shape, jnp.float32).at[tokens[0]].add(dx[0])
        return ref.without_biases(grads)

    @jax.jit
    def model_logits(params, tokens):
        return kl.forward(cfg, params, tokens)[0]

    @jax.jit
    def model_grads(params, tokens):
        biases = kl._biases(params)

        def loss(trainable):
            logits, _ = kl.forward(cfg, kl._with_biases(trainable, biases), tokens)
            return jnp.mean(optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], tokens[:, 1:]))

        return jax.grad(loss)(kl._trainable(params))

    def to_fp8(x):
        return x if x.dtype == jnp.float32 else x.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    results: List[Dict[str, Any]] = []
    for seed in args.seeds:
        t = time.monotonic()
        params = init_train_state(cfg, seed=seed).params
        tokens = jnp.asarray(np.random.default_rng(seed + 1).integers(
            0, sizes["vocab_size"], (sizes["batch"], sizes["seq"]), dtype=np.int32))
        with jax.default_matmul_precision("highest"):
            want = ref_logits(params, tokens, f32)
            low = ref_logits(params, tokens, lambda tree: jax.tree_util.tree_map(to_fp8, tree))
            want_grads = ref_grads(params, tokens[:1])
        got = model_logits(params, tokens)
        got_grads = model_grads(params, tokens[:1])
        loss = {name: float(ref.loss_of_logits(x, tokens)) for name, x in
                (("model", got), ("reference", want), ("reference_fp8", low))}
        flat_got = {jax.tree_util.keystr(k): v for k, v in
                    jax.tree_util.tree_leaves_with_path(got_grads)}
        flat_want = {jax.tree_util.keystr(k): v for k, v in
                     jax.tree_util.tree_leaves_with_path(want_grads)}
        assert flat_got.keys() == flat_want.keys()
        per_leaf = {k: rel(jnp, flat_got[k], flat_want[k]) for k in flat_want
                    if float(jnp.max(jnp.abs(flat_want[k]))) > 0}
        # Over all leaves without concatenating them (3 GiB in float32).
        diff = sum(float(jnp.sum(jnp.square(flat_got[k].astype(jnp.float32) - flat_want[k])))
                   for k in flat_want)
        norm = sum(float(jnp.sum(jnp.square(flat_want[k]))) for k in flat_want)
        whole = (diff / norm) ** 0.5
        worst = sorted(per_leaf.items(), key=lambda kv: -kv[1])[:8]
        results.append({
            "seed": seed,
            "logits_rel": rel(jnp, got, want),
            "logits_rel_fp8_reference": rel(jnp, low, want),
            "loss": loss,
            "loss_gap": abs(loss["model"] - loss["reference"]) / abs(loss["reference"]),
            "loss_gap_fp8_reference": abs(loss["reference_fp8"] - loss["reference"])
            / abs(loss["reference"]),
            "grad_rel_all_leaves": whole,
            "grad_rel_worst_leaves": worst,
            "grad_leaves_compared": len(per_leaf),
            "seconds": time.monotonic() - t,
        })
        print(json.dumps(results[-1]), file=sys.stderr, flush=True)
        del params, got, want, low, got_grads, want_grads
    dev = jax.devices()[0]
    out = {"platform": dev.platform, "device_kind": dev.device_kind, "cell": CELL,
           "rehearsal": bool(args.rehearse), "results": results}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()

"""The GLM cell's model against the plain reference at the cell's widths and
sizes.

    python chipbench/probe_glm_reference.py --seeds 0 1 2 --out <file> [--rehearse]

For each seed, the parameters `glm-5-l5-ep32-tp8.async-full-64` builds from
it (`workload.Context.init_state`) and the tokens of its first step (`seed +
1`), batch 1 x 4,096:

- **logits and loss** (cross-entropy plus the indexers' KL): the model
  (`glm_dsa.forward`, bf16 as the cell runs it) against
  `glm_dsa_reference.py` in float32 under
  `jax.default_matmul_precision("highest")`, a layer at a time, one layer's
  float32 weights on the device at a time;
- **the gradient of every trained leaf**: the model's (`jax.grad` of the
  step's loss) against the reference's, chained back a layer at a time
  through `jax.vjp`, each layer's KL given a cotangent of 1;
- **the indexer, layer by layer, on the model's own layer inputs**: its
  scores (the norm of the difference over the norm, causal pairs) and the
  overlap of the selected sets (the share of the reference's selected pairs
  the model selected too), and the attention half's output with the
  reference attending to the model's own selection;
- **the next precision below the configuration's**: the reference on the
  weights rounded to float8_e4m3fn against the float32 reference, which a
  limit has to refuse.

Each reading is the norm of the difference over the norm of the reference
(per leaf for gradients, and over all of them); the loss as a relative gap.
Nothing here decides `correct`: PERF.md records the readings and the limits
set from them. The last line is the JSON also written to `--out`."""

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

CELL = "glm-5-l5-ep32-tp8.async-full-64"
QUERY_BLOCK = 512


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

    import glm_dsa_reference as ref
    from torchsnapshot_tpu.models import TransformerConfig, init_train_state
    from torchsnapshot_tpu.models import experts
    from torchsnapshot_tpu.models import glm_dsa as glm

    bench = cells.benchmark()
    cell = cells.cell(bench, CELL)
    config = cells.config(bench, cell["config"])
    sizes, _, cfg = workload.model_config(jax, TransformerConfig, cell, config, args.rehearse)
    f32 = ref.f32
    cpu = jax.devices("cpu")[0]

    def host(tree):
        return jax.device_put(tree, cpu)

    def device(tree, cast=f32):
        return cast(jax.device_put(tree, jax.devices()[0]))

    def rel(got, want) -> float:
        got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
        return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))

    layer = jax.jit(lambda block, x: ref.layer(cfg, block, x))
    layer_vjp = jax.jit(lambda block, x, dx: jax.vjp(
        lambda b, y: ref.layer(cfg, b, y), block, x)[1](
            (dx, None if "moe" not in block else jnp.zeros(cfg.n_routed_experts), 1.0)))

    @jax.jit
    def head_logits(top, x):
        return ref.rmsnorm(x, top["ln_f_scale"]) @ top["unembed"]

    def ref_forward(params, tokens, cast):
        """The reference a layer at a time: (logits, each layer's input, the
        KLs)."""
        x = device(params["embed"], cast)[tokens]
        xs, kls = [], []
        for block in params["layers"]:
            xs.append(x)
            x, _, kl = layer(device(block, cast), x)
            kls.append(float(kl))
        top = device({k: params[k] for k in ("ln_f_scale", "unembed")}, cast)
        return head_logits(top, x), xs + [x], kls

    def ref_grads(params, tokens, xs):
        top = device({k: params[k] for k in ("ln_f_scale", "unembed")})
        g_top, dx = jax.jit(jax.grad(
            lambda t, x: ref.loss_of_logits(head_logits(t, x), tokens), (0, 1)))(top, xs[-1])
        grads = {k: host(v) for k, v in g_top.items()}
        grads["layers"] = [None] * len(params["layers"])
        for i in reversed(range(len(params["layers"]))):
            g_block, dx = layer_vjp(device(params["layers"][i]), xs[i], dx)
            grads["layers"][i] = host(g_block)
        grads["embed"] = host(jnp.zeros(params["embed"].shape, jnp.float32).at[
            tokens.reshape(-1)].add(dx.reshape(-1, dx.shape[-1])))
        return ref.without_biases(grads)

    @jax.jit
    def model_logits(params, tokens):
        logits, _, kls = glm.forward(cfg, params, tokens)
        return logits, kls

    @jax.jit
    def model_grads(params, tokens):
        return jax.grad(lambda t: glm.loss_fn(cfg, t, experts.biases(params), tokens)[0])(
            experts.trainable(params))

    @jax.jit
    def model_layer(block, x):
        """The model's attention half on a layer's input: (its normed input,
        its output, the indexer's scores and selection, a block of queries at
        a time)."""
        h = glm._rmsnorm(x, block["ln1_scale"])
        out, _ = glm.attention(cfg, block, h)
        _, _, _, qi, ki, w = glm._projections(cfg, block["mla"], block["indexer"], h)
        s = x.shape[1]
        bq = min(QUERY_BLOCK, s)
        scores, keep = [], []
        for start in range(0, s, bq):
            blk = glm.index_scores(qi[:, start:start + bq], ki, w[:, start:start + bq])
            scores.append(blk)
            keep.append(glm.selection(blk, jnp.arange(start, start + bq), cfg.index_topk))
        return h, out, jnp.concatenate(scores, 1), jnp.concatenate(keep, 1)

    @jax.jit
    def ref_attention(block, h, keep):
        out, _, _, scores = ref.attention(cfg, block, h, keep=keep)
        return out, scores, ref.select(cfg, scores)

    def to_fp8(x):
        return x if x.dtype == jnp.float32 else x.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    results: List[Dict[str, Any]] = []
    for seed in args.seeds:
        t = time.monotonic()
        params = init_train_state(cfg, seed=seed).params
        tokens = jnp.asarray(np.random.default_rng(seed + 1).integers(
            0, sizes["vocab_size"], (sizes["batch"], sizes["seq"]), dtype=np.int32))
        got, got_kls = model_logits(params, tokens)
        got_kls = [float(k) for k in got_kls]
        got_grads = host(model_grads(params, tokens))
        params_host = host(params)
        del params
        with jax.default_matmul_precision("highest"):
            want, xs, want_kls = ref_forward(params_host, tokens, f32)
            low, _, low_kls = ref_forward(params_host, tokens,
                                          lambda tree: jax.tree_util.tree_map(to_fp8, tree))
            indexer = []
            for i, block in enumerate(params_host["layers"]):
                # The model's layer input, in the model's dtype: the
                # reference's input rounded as the model's residual is.
                x = xs[i].astype(cfg.dtype)
                h, out, scores, keep = model_layer(device(block, lambda b: b), x)
                want_out, want_scores, want_keep = ref_attention(device(block), f32(h), keep)
                causal = jnp.tril(jnp.ones(scores.shape[-2:], bool))
                overlap = float(jnp.sum(keep & want_keep) / jnp.sum(want_keep))
                indexer.append({
                    "layer": i,
                    "scores_rel": rel(jnp.where(causal, scores, 0), jnp.where(causal, want_scores, 0)),
                    "selected_overlap": overlap,
                    "attention_rel_given_selection": rel(out, want_out),
                })
                del h, out, scores, keep, want_out, want_scores, want_keep
            want_grads = ref_grads(params_host, tokens, xs)
        ce = {name: float(ref.loss_of_logits(x, tokens)) for name, x in
              (("model", got), ("reference", want), ("reference_fp8", low))}
        loss = {"model": ce["model"] + sum(got_kls), "reference": ce["reference"] + sum(want_kls),
                "reference_fp8": ce["reference_fp8"] + sum(low_kls)}
        flat_got = {jax.tree_util.keystr(k): v for k, v in
                    jax.tree_util.tree_leaves_with_path(got_grads)}
        flat_want = {jax.tree_util.keystr(k): v for k, v in
                     jax.tree_util.tree_leaves_with_path(want_grads)}
        assert flat_got.keys() == flat_want.keys()
        diff = norm = 0.0
        per_leaf = {}
        for k in flat_want:
            a = np.asarray(flat_got[k], np.float32)
            b = np.asarray(flat_want[k], np.float32)
            d2, n2 = float(np.sum(np.square(a - b), dtype=np.float64)), float(
                np.sum(np.square(b), dtype=np.float64))
            diff, norm = diff + d2, norm + n2
            if n2 > 0:
                per_leaf[k] = (d2 / n2) ** 0.5
        worst = sorted(per_leaf.items(), key=lambda kv: -kv[1])[:8]
        results.append({
            "seed": seed,
            "logits_rel": rel(got, want),
            "logits_rel_fp8_reference": rel(low, want),
            "loss": loss,
            "loss_gap": abs(loss["model"] - loss["reference"]) / abs(loss["reference"]),
            "loss_gap_fp8_reference": abs(loss["reference_fp8"] - loss["reference"])
            / abs(loss["reference"]),
            "kl_by_layer": {"model": got_kls, "reference": want_kls, "reference_fp8": low_kls},
            "grad_rel_all_leaves": (diff / norm) ** 0.5,
            "grad_rel_worst_leaves": worst,
            "grad_leaves_compared": len(per_leaf),
            "indexer_by_layer": indexer,
            "seconds": time.monotonic() - t,
        })
        print(json.dumps(results[-1]), file=sys.stderr, flush=True)
        del got, want, low, got_grads, want_grads, xs, params_host
    dev = jax.devices()[0]
    out = {"platform": dev.platform, "device_kind": dev.device_kind, "cell": CELL,
           "rehearsal": bool(args.rehearse), "results": results}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()

"""Microbench: Pallas flash attention vs the dense einsum op on one chip.

Reference anchor: the reference has no attention at all (it is a
checkpointing library); this benchmarks the flagship workload's hot op on
the hardware it was written for, reporting achieved attention FLOP/s and
the flash/dense speedup across sequence lengths.

Run: python benchmarks/flash_attention/main.py          (real TPU)
     JAX_PLATFORMS=cpu python ... --interpret           (smoke test)
"""

import argparse
import sys
import time

sys.path.append(__import__("os").path.join(__import__("os").path.dirname(__file__), ".."))
import common  # noqa: F401  (path + platform pinning)

import jax
import jax.numpy as jnp
import numpy as np

from torchsnapshot_tpu.ops.attention import causal_attention
from torchsnapshot_tpu.ops.flash_attention import flash_causal_attention


def timeit(fn, q, k, v, iters=10):
    """One-dispatch chained timing: a single jitted ``fori_loop`` runs
    ``iters`` data-dependent kernels, and a scalar fetch forces
    completion, so per-call dispatch latency does not floor the
    per-kernel figure."""

    def chained(n):
        @jax.jit
        def run(q, k, v):
            body = lambda _, x: fn(x, k, v).astype(q.dtype)
            return jnp.sum(jax.lax.fori_loop(0, n, body, q))

        return run

    # Pilot: estimate per-iteration time, then size the real run so fused
    # compute (>= 0.5 s) dwarfs dispatch jitter.
    pilot = chained(iters)
    float(pilot(q, k, v))  # compile + warm
    t0 = time.perf_counter()
    float(pilot(q, k, v))
    t_est = max((time.perf_counter() - t0) / iters, 1e-6)
    n = min(max(iters, int(0.5 / t_est)), 4096)
    run = chained(n)
    float(run(q, k, v))  # compile + warm
    best = float("inf")
    for _ in range(3):  # min-of-3: the dev chip is shared and noisy
        t0 = time.perf_counter()
        float(run(q, k, v))
        best = min(best, time.perf_counter() - t0)
    return best / n


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--interpret", action="store_true")
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()

    b, h, d = 4, 8, 128
    print(f"device: {jax.devices()[0]}  b={b} h={h} d={d}")
    print(f"{'seq':>6} {'dense ms':>9} {'flash ms':>9} {'speedup':>8} "
          f"{'flash TFLOP/s':>13}")
    for s in (1024, 2048, 4096, 8192):
        rng = np.random.default_rng(s)
        q, k, v = (
            jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.bfloat16)
            for _ in range(3)
        )
        dense = jax.jit(causal_attention)
        flash = jax.jit(
            lambda q, k, v: flash_causal_attention(
                q, k, v, interpret=args.interpret
            )
        )
        t_flash = timeit(flash, q, k, v, iters=args.iters)
        try:
            dense_out = np.asarray(dense(q, k, v), np.float32)
        except Exception:
            # The s^2 logits tensor no longer fits in HBM — the reason the
            # flash kernel exists. Flash keeps going. (Only the dense
            # computation is guarded: a flash-vs-dense MISMATCH must
            # propagate, never masquerade as a capacity limit.)
            dense_ms, speedup = f"{'OOM':>9}", f"{'—':>8}"
        else:
            np.testing.assert_allclose(
                np.asarray(flash(q, k, v), np.float32),
                dense_out,
                atol=0.06, rtol=0.06,
            )
            t_dense = timeit(dense, q, k, v, iters=args.iters)
            dense_ms, speedup = f"{t_dense*1e3:9.2f}", f"{t_dense/t_flash:8.2f}"
        # causal attention FLOPs: 2 matmuls * 2*b*h*s^2*d, halved by causality
        flops = 2 * 2 * b * h * s * s * d / 2
        print(
            f"{s:>6} {dense_ms} {t_flash*1e3:>9.2f} "
            f"{speedup} {flops/t_flash/1e12:>13.2f}"
        )


if __name__ == "__main__":
    main()

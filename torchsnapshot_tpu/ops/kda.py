"""Kimi Delta Attention (KDA, arXiv:2510.26692 §3): the gated delta rule with
a decay per key channel, in the chunked form that training needs.

Per head, with a key-by-value state ``S`` (``K x V``)::

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t * scale

A scan over tokens would keep one state a token for the backward pass (at
batch 4 x 512 tokens x 32 heads x 128 x 128 that is 4 GiB a layer). The
chunked form (WY / UT transform, as FLA's ``chunk_kda``) keeps one state a
chunk. Within a chunk of ``C`` tokens that starts from ``S_0``, with the
inclusive cumulative log-decay ``G_t = g_1 + ... + g_t``::

    delta = (I + A)^{-1} diag(beta) (V - K_G S_0)       A[t, i] = beta_t sum_d k_t[d] k_i[d] exp(G_t[d] - G_i[d]),  i < t
    O     = Q_G S_0 + P delta                           P[t, i] = sum_d q_t[d] k_i[d] exp(G_t[d] - G_i[d]),  i <= t
    S_C   = Diag(exp G_C) S_0 + (K * exp(G_C - G))^T delta

where ``K_G``, ``Q_G`` are the rows ``k_t * exp(G_t)``, ``q_t * exp(G_t)``.
Every exponent is of a later cumulative decay less an earlier one, so never
positive: nothing overflows however strong the decay is (factoring
``exp(G_t - G_i)`` into ``exp(G_t) exp(-G_i)`` would, within one chunk).

``A`` and ``P`` are built a sub-chunk of ``SUB`` tokens at a time. Where
``i`` lies in an earlier sub-chunk than ``t``, the decay factors through the
cumulative decay ``G_r`` of the token before ``t``'s sub-chunk,
``exp(G_t - G_r) exp(G_r - G_i)``, both exponents again never positive, and
the pairs are two matmuls. Only the pairs within one sub-chunk are formed
channel by channel (a ``SUB x SUB x K`` product): a chunk's per-channel pair
tensor is what bounded the op by HBM traffic.

Everything inside runs in float32: the decays, their sums, the solve and
the state. The chunk body is rematerialised in the backward pass, so what a
layer keeps is its inputs and one state a chunk. (Computing ``A``, ``P`` and
the solve for every chunk at once, outside the scan, and rematerialising
the whole op made the op alone 11 % faster and the train step 10 % slower on
a v5e: PERF.md.)
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

CHUNK = 64
SUB = 16

_HIGHEST = jax.lax.Precision.HIGHEST


def _mm(spec: str, *operands: jax.Array) -> jax.Array:
    return jnp.einsum(spec, *operands, precision=_HIGHEST)


def chunk_kda(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    g: jax.Array,
    beta: jax.Array,
    *,
    scale: float,
    chunk: int = CHUNK,
    state_dtype: Any = jnp.float32,
) -> jax.Array:
    """KDA over whole sequences from a zero state.

    Args:
        q, k: ``(batch, seq, heads, K)``, each already L2-normalised per head.
        v: ``(batch, seq, heads, V)``.
        g: ``(batch, seq, heads, K)``, the per-channel log-decay (``<= 0``).
        beta: ``(batch, seq, heads)``, the write strength in ``(0, 1)``.
        scale: what ``o_t`` is multiplied by (``K ** -0.5`` in the model).
        chunk: tokens a chunk; ``seq`` is cut into ``min(chunk, seq)``.
        state_dtype: the dtype the state is carried in from chunk to chunk.

    Returns:
        ``o``: ``(batch, seq, heads, V)`` in ``v.dtype``.
    """
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    c = min(chunk, s)
    if s % c:
        raise ValueError(f"chunk_kda: seq {s} is not a multiple of the chunk {c}")
    n = s // c
    f32 = jnp.float32

    def chunks(x: jax.Array) -> jax.Array:
        # (b, s, h, ...) -> (n, b, h, c, ...)
        x = x.astype(f32).reshape(b, n, c, h, *x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 3, 2)

    sub = min(SUB, c)
    if c % sub:
        raise ValueError(f"chunk_kda: chunk {c} is not a multiple of the sub-chunk {sub}")
    m = c // sub
    causal = jnp.tril(jnp.ones((c, c), bool))
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    within = jnp.tril(jnp.ones((sub, sub), bool))
    eye = jnp.eye(c, dtype=f32)
    # before[a, i]: token i lies in a sub-chunk before sub-chunk a.
    before = jnp.arange(c)[None, :] < (jnp.arange(m) * sub)[:, None]

    def pairs(left: jax.Array, kc: jax.Array, cum: jax.Array) -> jax.Array:
        """sum_d left_t[d] k_i[d] exp(G_t[d] - G_i[d]) for i <= t, 0 above."""
        ls, ks, gs = (x.reshape(*x.shape[:-2], m, sub, dk) for x in (left, kc, cum))
        # G_r: the cumulative decay of the token before each sub-chunk (the
        # first sub-chunk has no earlier token; its row of `before` is empty).
        ref = jnp.concatenate([gs[..., :1, :1, :], gs[..., :-1, -1:, :]], axis=-3)
        lft = ls * jnp.exp(gs - ref)  # (..., m, sub, dk)
        rgt = jnp.where(before[:, :, None], ref - cum[..., None, :, :], -jnp.inf)
        rgt = kc[..., None, :, :] * jnp.exp(rgt)  # (..., m, c, dk)
        off = _mm("...msd,...mid->...msi", lft, rgt).reshape(*left.shape[:-2], c, c)
        diff = jnp.where(within[:, :, None], gs[..., :, None, :] - gs[..., None, :, :], -jnp.inf)
        diag = jnp.sum(ls[..., :, None, :] * ks[..., None, :, :] * jnp.exp(diff), axis=-1)
        diag = jnp.einsum("...msi,mn->...msni", diag, jnp.eye(m, dtype=f32))
        return off + diag.reshape(off.shape)

    @jax.checkpoint
    def body(state: jax.Array, xs):
        qc, kc, vc, gc, bc = xs  # (b, h, c, dk) x 2, (b, h, c, dv), (b, h, c, dk), (b, h, c)
        cum = jnp.cumsum(gc, axis=-2)
        a = jnp.where(strict, pairs(kc, kc, cum), 0.0) * bc[..., None]
        p = jnp.where(causal, pairs(qc, kc, cum), 0.0)
        decay = jnp.exp(cum)
        rhs = bc[..., None] * jnp.concatenate([vc, kc * decay], axis=-1)
        uw = solve_triangular(eye + a, rhs, lower=True, unit_diagonal=True)
        u, w = uw[..., :dv], uw[..., dv:]
        s0 = state.astype(f32)
        delta = u - _mm("bhtk,bhkv->bhtv", w, s0)
        out = _mm("bhtk,bhkv->bhtv", qc * decay, s0) + _mm("bhti,bhiv->bhtv", p, delta)
        last = cum[..., -1:, :]  # G_C
        k_end = kc * jnp.exp(last - cum)
        new = jnp.exp(last[..., 0, :])[..., None] * s0 + _mm("bhik,bhiv->bhkv", k_end, delta)
        return new.astype(state_dtype), out

    xs = (chunks(q) * scale, chunks(k), chunks(v), chunks(g), chunks(beta[..., None])[..., 0])
    state = jnp.zeros((b, h, dk, dv), state_dtype)
    _, out = jax.lax.scan(body, state, xs)  # (n, b, h, c, dv)
    out = jnp.moveaxis(jnp.moveaxis(out, 2, 3), 0, 1).reshape(b, s, h, dv)
    return out.astype(v.dtype)

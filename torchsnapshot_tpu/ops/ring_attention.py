"""Ring attention: exact causal attention over a sequence-sharded mesh axis.

Long-context scaling is first-class in this framework: activations stay
sequence-sharded across the ``sp`` mesh axis end to end, and attention —
the one op that mixes positions — is computed by rotating key/value blocks
around the ``sp`` ring with ``jax.lax.ppermute`` while each device keeps
its resident query block. Per-step partial results merge with the online
(flash-style) softmax recurrence, so the full ``(seq, seq)`` score matrix
never materializes anywhere: memory per device is O(seq_local^2) and the
KV transfers ride the ICI ring, overlapping with each step's einsums.

This is the RingAttention construction (Liu et al., 2023; see PAPERS.md)
expressed in idiomatic JAX: ``shard_map`` makes the per-device program
explicit, the ring step is an ``lax.scan`` (static trip count → reverse-mode
differentiable, compiler-schedulable), and the blockwise math is einsums
that tile onto the MXU with f32 accumulation.

The reference framework (torchsnapshot) has no sequence-parallel support at
all (SURVEY.md §2.12: absent); this op is part of the flagship workload
that produces the sequence-sharded training state the checkpointer must
persist, and makes multi-million-token contexts reachable without the
all-to-all resharding the Ulysses path in ``ops.attention`` needs.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .attention import causal_attention

_NEG_INF = -1e30  # finite "masked" value: keeps exp() exact-zero-free and
# the running max finite even for fully-masked (future) blocks.


def _ring_attention_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    axis_size: int,
    use_flash: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Per-device program: local blocks ``(b, s_local, h, d)``.

    Device ``r`` holds query block ``r``; at ring step ``t`` it holds the
    KV block originally owned by device ``(r - t) mod n`` and merges that
    block's contribution into the (max, sum, acc) online-softmax carry.

    With ``use_flash`` each step's blockwise attention runs in the Pallas
    kernel (ops/flash_attention.py ``flash_attention_chunk``) instead of
    einsums that materialize ``(b, h, s_local, s_local)`` logits in HBM:
    per-step memory drops to O(block·d) VMEM, which is what makes
    s_local in the tens of thousands (multi-million-token global context)
    fit. The step's mask mode depends on where the wandering KV block sits
    relative to the resident queries: fully behind → no mask, the diagonal
    step → local causal mask, fully ahead → skipped.
    """
    r = jax.lax.axis_index(axis_name)
    b, s, h, d = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, dtype=jnp.float32))
    qf = q.astype(jnp.float32) * scale

    local_pos = jnp.arange(s)
    q_pos = r * s + local_pos  # global positions of resident queries

    def _contrib_einsum(k_t, v_t, src):
        k_pos = src * s + local_pos
        # (b, h, s_q, s_k) logits on the MXU, f32 accumulation.
        logits = jnp.einsum(
            "bqhd,bkhd->bhqk",
            qf,
            k_t.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        mask = q_pos[:, None] >= k_pos[None, :]
        logits = jnp.where(mask, logits, _NEG_INF)
        m_c = jnp.max(logits, axis=-1)
        p = jnp.exp(logits - m_c[..., None])
        # A fully-masked block contributes p == exp(_NEG_INF - m) == 0.
        p = jnp.where(mask, p, 0.0)
        l_c = jnp.sum(p, axis=-1)
        o_c = jnp.einsum(
            "bhqk,bkhd->bhqd",
            p,
            v_t.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        return o_c, m_c, l_c

    def _contrib_flash(k_t, v_t, src):
        from .flash_attention import flash_attention_chunk

        def _full(_):
            return flash_attention_chunk(
                q, k_t, v_t, causal=False, interpret=interpret
            )

        def _diag(_):
            return flash_attention_chunk(
                q, k_t, v_t, causal=True, interpret=interpret
            )

        def _skip(_):
            return (
                jnp.zeros((b, h, s, d), jnp.float32),
                jnp.full((b, h, s), _NEG_INF, jnp.float32),
                jnp.zeros((b, h, s), jnp.float32),
            )

        branch = jnp.where(src < r, 0, jnp.where(src == r, 1, 2))
        return jax.lax.switch(branch, [_full, _diag, _skip], None)

    def ring_step(carry, t):
        o, m, l, k_t, v_t = carry
        src = (r - t) % axis_size
        contrib = _contrib_flash if use_flash else _contrib_einsum
        o_c, m_c, l_c = contrib(k_t, v_t, src)
        # Merge the chunk's (unnormalized acc, max, normalizer) into the
        # carry with the two-way online-softmax recurrence.
        m_new = jnp.maximum(m, m_c)
        corr = jnp.exp(m - m_new)
        corr_c = jnp.exp(m_c - m_new)
        l_new = l * corr + l_c * corr_c
        o_new = o * corr[..., None] + o_c * corr_c[..., None]
        # Rotate KV around the ring: i → i+1, so next step holds src-1's
        # block. XLA overlaps this ppermute with the next step's einsums.
        perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
        k_nxt = jax.lax.ppermute(k_t, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_t, axis_name, perm)
        return (o_new, m_new, l_new, k_nxt, v_nxt), None

    o0 = jnp.zeros((b, h, s, d), dtype=jnp.float32)
    m0 = jnp.full((b, h, s), _NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((b, h, s), dtype=jnp.float32)
    (o, _, l, _, _), _ = jax.lax.scan(
        ring_step, (o0, m0, l0, k, v), jnp.arange(axis_size)
    )
    out = o / l[..., None]  # every query sees ≥ its own position ⇒ l > 0
    return jnp.einsum("bhqd->bqhd", out).astype(q.dtype)


@functools.partial(
    jax.jit, static_argnames=("mesh", "axis_name", "use_flash", "interpret")
)
def ring_causal_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Optional[Mesh] = None,
    axis_name: str = "sp",
    use_flash: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Exact causal attention with sequence sharded over ``axis_name``.

    Args:
        q, k, v: ``(batch, seq, n_heads, head_dim)``; ``seq`` must divide
            evenly over ``mesh.shape[axis_name]``.
        mesh: mesh containing ``axis_name`` (and optionally ``dp``/``tp``
            for batch/head parallelism — those partitions need no
            collectives here). ``None`` falls back to the dense op.
        use_flash: run each ring step's blockwise attention in the Pallas
            flash kernel instead of HBM-materializing einsums (long local
            sequences). ``interpret`` runs that kernel in the Pallas
            interpreter (CPU tests).

    Returns:
        ``(batch, seq, n_heads, head_dim)``, numerically equal (up to f32
        roundoff) to :func:`~torchsnapshot_tpu.ops.attention.causal_attention`.
    """
    if mesh is None:
        return causal_attention(q, k, v)
    axis_size = mesh.shape[axis_name]
    has_dp = "dp" in mesh.axis_names
    has_tp = "tp" in mesh.axis_names
    spec = P("dp" if has_dp else None, axis_name, "tp" if has_tp else None, None)

    def mapped(flash: bool):
        return jax.shard_map(
            functools.partial(
                _ring_attention_local,
                axis_name=axis_name,
                axis_size=axis_size,
                use_flash=flash,
                interpret=interpret,
            ),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )

    if not use_flash:
        return mapped(False)(q, k, v)

    # The Pallas chunk kernel has no autodiff rule; the einsum ring
    # computes the same function, so its vjp IS this function's vjp.
    # Forward runs the kernel (no s_local² HBM intermediate); backward
    # rematerializes through the einsum ring — the same backward cost the
    # non-flash ring path pays.
    @jax.custom_vjp
    def rca(q, k, v):
        return mapped(True)(q, k, v)

    def fwd(q, k, v):
        return mapped(True)(q, k, v), (q, k, v)

    def bwd(res, g):
        _, vjp = jax.vjp(mapped(False), *res)
        return vjp(g)

    rca.defvjp(fwd, bwd)
    return rca(q, k, v)


def ring_attention_block_specs(
    mesh: Mesh, axis_name: str = "sp"
) -> Tuple[P, P]:
    """(activation, qkv) PartitionSpecs a model should constrain to so the
    ring path sees sequence-sharded inputs without resharding."""
    del mesh
    return P("dp", axis_name, None), P("dp", axis_name, None, None)

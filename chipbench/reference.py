"""The plain reference and the comparison that decides `correct`.

This system runs no model: it stores a train state and gives it back. The
reference of that is the identity: what a save was handed is what a restore
returns, bit for bit, in every leaf. So the expected answer is a fingerprint
of each leaf, taken by this file from the data before the library sees it,
and the answer is the same fingerprint of what the library gave back. The
loss of the steps after a restore is held to the loss the uninterrupted run
computed on the same tokens. Nothing here imports the library.

A fingerprint is two 32-bit sums over the leaf's elements read as unsigned
integers of their own width: the plain sum, and the sum weighted by a hash of
the element's flat index. One altered element always changes the first; two
elements swapped change the second. All arithmetic wraps modulo 2**32.
"""

from typing import Any, Dict, List

import numpy as np

_GOLDEN = 2654435761  # odd, so multiplying by it is a bijection modulo 2**32
_UINT_OF_WIDTH = {1: np.uint8, 2: np.uint16, 4: np.uint32}


def fingerprint_numpy(leaf: np.ndarray) -> np.ndarray:
    """The fingerprint in plain numpy; the device version below is held to it."""
    flat = np.ascontiguousarray(leaf).reshape(-1)
    bits = flat.view(_UINT_OF_WIDTH[flat.dtype.itemsize]).astype(np.uint64)
    weight = (np.arange(bits.size, dtype=np.uint64) * _GOLDEN + 1) % (1 << 32)
    return np.array([bits.sum() % (1 << 32), (bits * weight % (1 << 32)).sum() % (1 << 32)],
                    dtype=np.uint32)


def as_bits(jax, x):
    """A device array read as unsigned integers of its elements' own width."""
    jnp = jax.numpy
    return jax.lax.bitcast_convert_type(
        x, {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize])


def make_fingerprint(jax):
    """One jitted program over a whole tree: (leaves, 2) uint32."""
    jnp, lax = jax.numpy, jax.lax

    def leaf_fp(x):
        if jax.dtypes.issubdtype(x.dtype, jax.dtypes.prng_key):
            x = jax.random.key_data(x)
        bits = as_bits(jax, x).astype(jnp.uint32)
        if bits.ndim == 0:
            bits = bits.reshape(1)
        index = jnp.zeros(bits.shape, jnp.uint32)
        stride = 1
        for axis in reversed(range(bits.ndim)):
            index = index + lax.broadcasted_iota(jnp.uint32, bits.shape, axis) * np.uint32(
                stride % (1 << 32))
            stride *= bits.shape[axis]
        weight = index * np.uint32(_GOLDEN) + np.uint32(1)
        return jnp.stack([jnp.sum(bits, dtype=jnp.uint32),
                          jnp.sum(bits * weight, dtype=jnp.uint32)])

    @jax.jit
    def fingerprint(tree):
        return jnp.stack([leaf_fp(x) for x in jax.tree_util.tree_leaves(tree)])

    return fingerprint


def leaves_differing(expected: np.ndarray, got: np.ndarray) -> int:
    expected, got = np.asarray(expected), np.asarray(got)
    if expected.shape != got.shape:
        return max(len(expected), len(got))
    return int(np.any(expected != got, axis=1).sum())


def loss_gap(expected: List[float], got: List[float]) -> float:
    """Widest relative gap between the losses of the steps after a restore and
    those the uninterrupted run computed; a loss that is missing or not finite
    is a gap of 1."""
    if len(expected) != len(got) or not expected:
        return 1.0
    gaps = []
    for want, have in zip(expected, got):
        if not (np.isfinite(want) and np.isfinite(have)):
            return 1.0
        gaps.append(abs(have - want) / abs(want))
    return float(max(gaps))


def check(name: str, value: float, limit: float) -> Dict[str, Any]:
    return {"name": name, "value": value, "limit": limit}


def correct(checks: List[Dict[str, Any]]) -> bool:
    return bool(checks) and all(c["value"] <= c["limit"] for c in checks)

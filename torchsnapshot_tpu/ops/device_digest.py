"""On-device content digests for change detection.

The incremental checkpointer (incremental.py) must answer "did this
chunk's bytes change since the base snapshot?" *without* moving the chunk
to the host — on a TPU the device→host link is exactly the resource a
checkpoint skip is trying to save. So the digest is computed **on
device** by a jitted reduction and only the 8-byte result crosses the
link.

There is no counterpart in the reference (its integrity story is
host-side only); the closest analog is the content-addressing some
checkpoint stores do after staging, which pays the full D2H first.

Digest: a 64-bit multilinear hash over the array's bytes viewed as a
vector of unsigned *lanes* (uint32 when the itemsize is a multiple of 4,
else uint16/uint8), with position-dependent weights derived from a
splitmix32-style mixer:

    w(i, seed) = mix32(i * GOLDEN + seed)
    d_seed     = mix32( (Σ_i lane_i · w(i, seed)) mod 2^32  ^  nbytes )
    digest     = "mlh64:" + hex(d_SEED1 ‖ d_SEED2)

Two independent 32-bit accumulators give a 64-bit digest; the chance a
*changed* chunk collides is ~2^-64 per comparison — far below memory
soft-error rates. (The hash is content-addressing for change detection,
not an adversarial MAC; CRC-based integrity verification on restore is a
separate subsystem, integrity.py.)

The numpy implementation is bit-identical to the jitted one (pinned by
tests/test_device_digest.py across every supported dtype), so a leaf may
move between host and device across steps without spurious rewrites.
All math is uint32 with wraparound, vectorizable on the TPU's VPU; XLA
fuses iota → mix → multiply → reduce without materializing the weights.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import numpy as np

_GOLDEN = np.uint32(0x9E3779B9)
_SEED1 = np.uint32(0x243F6A88)  # pi fractional bits
_SEED2 = np.uint32(0xB7E15162)  # e fractional bits

# numpy block size (lanes) for the host implementation: bounds the weight
# array materialization to ~16 MiB while keeping per-block overhead noise.
_HOST_BLOCK_LANES = 1 << 22

DIGEST_PREFIX = "mlh64:"


# ---------------------------------------------------------------------------
# dtype support / lane views
# ---------------------------------------------------------------------------


# Sub-byte dtypes report itemsize 1 through np.dtype but cannot be
# bitcast to uint8 lanes on device. One list, shared with
# ops/device_pack.py so pack and digest can never disagree on
# device-eligibility.
SUB_BYTE_DTYPE_NAMES: Tuple[str, ...] = (
    "int4",
    "uint4",
    "int2",
    "uint2",
    "float4_e2m1fn",
)


def bitcastable_dtype(dtype: Any) -> bool:
    """True when the dtype's memory image has a uint8-lane view usable on
    device: fixed-width, byte-aligned, non-complex. Complex dtypes are
    excluded (device bitcast of interleaved re/im pairs is not uniformly
    available); sub-byte dtypes because their lane view is
    framework-specific."""
    try:
        dt = np.dtype(dtype)
    except TypeError:
        # jax-only dtypes (bfloat16, fp8) reach here as ml_dtypes dtypes,
        # which np.dtype understands; anything else is unsupported.
        return False
    if dt.kind == "c" or dt.hasobject:
        return False
    return dt.name not in SUB_BYTE_DTYPE_NAMES


def digest_supported(dtype: Any) -> bool:
    """Digestable = bitcastable with a power-of-two lane-splittable
    itemsize."""
    if not bitcastable_dtype(dtype):
        return False
    return np.dtype(dtype).itemsize in (1, 2, 4, 8)


def _lane_dtype(itemsize: int) -> np.dtype:
    if itemsize % 4 == 0:
        return np.dtype(np.uint32)
    if itemsize == 2:
        return np.dtype(np.uint16)
    return np.dtype(np.uint8)


# ---------------------------------------------------------------------------
# numpy implementation
# ---------------------------------------------------------------------------


def _mix32_np(x: np.ndarray) -> np.ndarray:
    """splitmix32-style finalizer; input/output uint32 arrays."""
    x = x.astype(np.uint32, copy=True)
    x ^= x >> np.uint32(16)
    x *= np.uint32(0x7FEB352D)
    x ^= x >> np.uint32(15)
    x *= np.uint32(0x846CA68B)
    x ^= x >> np.uint32(16)
    return x


def digest_host(arr: np.ndarray) -> Tuple[int, int]:
    """Digest of a host array's memory image. Blockwise so the weight
    arrays stay small; block sums are exact because uint32 addition is
    associative under wraparound."""
    arr = np.ascontiguousarray(arr)
    if not digest_supported(arr.dtype):
        raise TypeError(f"digest does not support dtype {arr.dtype}")
    nbytes = arr.nbytes & 0xFFFFFFFF
    lanes = arr.reshape(-1).view(_lane_dtype(arr.dtype.itemsize))
    # Accumulators are plain ints masked to 32 bits: numpy *scalar* uint32
    # arithmetic warns on overflow even though array ops wrap silently.
    acc1 = 0
    acc2 = 0
    for start in range(0, lanes.size, _HOST_BLOCK_LANES):
        block = lanes[start : start + _HOST_BLOCK_LANES].astype(
            np.uint32, copy=False
        )
        idx = np.arange(
            start, start + block.size, dtype=np.uint64
        ).astype(np.uint32)
        base = idx * _GOLDEN
        w1 = _mix32_np(base + _SEED1)
        w2 = _mix32_np(base + _SEED2)
        # Array sums wrap in uint32, matching the device reduction.
        acc1 = (acc1 + int(np.sum(block * w1, dtype=np.uint32))) & 0xFFFFFFFF
        acc2 = (acc2 + int(np.sum(block * w2, dtype=np.uint32))) & 0xFFFFFFFF
    d1 = int(_mix32_np(np.asarray(acc1 ^ nbytes, dtype=np.uint32))[()])
    d2 = int(_mix32_np(np.asarray(acc2 ^ nbytes, dtype=np.uint32))[()])
    return d1, d2


# ---------------------------------------------------------------------------
# jax implementation
# ---------------------------------------------------------------------------


def _mix32_jnp(x):
    import jax.numpy as jnp

    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def _lanes_jnp(x):
    """Reinterpret a device array's memory image as a flat lane vector,
    mirroring the numpy ``.view`` in :func:`digest_host` (both platforms
    are little-endian; serialization.py guards the host side)."""
    import jax.numpy as jnp
    from jax import lax

    if x.dtype == jnp.bool_:
        # bool memory is one 0/1 byte per element; astype equals the view.
        return x.reshape(-1).astype(jnp.uint8)
    itemsize = np.dtype(x.dtype).itemsize
    lane = _lane_dtype(itemsize)
    if itemsize == lane.itemsize:
        if x.dtype != jnp.dtype(lane):
            x = lax.bitcast_convert_type(x, jnp.dtype(lane))
        return x.reshape(-1)
    # Wider element than lane: bitcast appends a minor dim of
    # itemsize/lane.itemsize lanes, minor-to-major == memory order.
    return lax.bitcast_convert_type(x, jnp.dtype(lane)).reshape(-1)


def _digest_jax_impl(x):
    import jax.numpy as jnp

    lanes = _lanes_jnp(x).astype(jnp.uint32)
    nbytes = jnp.uint32((x.size * np.dtype(x.dtype).itemsize) & 0xFFFFFFFF)
    idx = jnp.arange(lanes.size, dtype=jnp.uint32)
    base = idx * _GOLDEN
    acc1 = jnp.sum(lanes * _mix32_jnp(base + _SEED1), dtype=jnp.uint32)
    acc2 = jnp.sum(lanes * _mix32_jnp(base + _SEED2), dtype=jnp.uint32)
    return jnp.stack(
        [_mix32_jnp(acc1 ^ nbytes), _mix32_jnp(acc2 ^ nbytes)]
    )


# The name of both digest programs on a profile's Modules line
# (``jit_ts_device_digest``) and of their ops' scope.
_PROGRAM_NAME = "ts_device_digest"


@functools.lru_cache(maxsize=1)
def _digest_jit():
    import jax

    def ts_device_digest(x):
        with jax.named_scope(_PROGRAM_NAME):
            return _digest_jax_impl(x)

    # jit caches per (shape, dtype) signature; one wrapper suffices.
    return jax.jit(ts_device_digest)


def digest_device_async(arr: Any, row_range: Optional[Tuple[int, int]] = None):
    """Launch the digest of a device array (or a dim-0 row range of it) on
    its own device; returns a ``jax.Array`` of shape (2,) uint32 — a
    future under JAX's async dispatch. Call :func:`materialize` (or
    ``np.asarray``) to block."""
    if row_range is not None:
        start, stop = row_range
        arr = arr[start:stop]
    return _digest_jit()(arr)


def materialize(digest_future: Any) -> Tuple[int, int]:
    host = np.asarray(digest_future)
    return int(host[0]), int(host[1])


# ---------------------------------------------------------------------------
# batched digests: one dispatch for many arrays/chunks
# ---------------------------------------------------------------------------

# (row_ranges or None) per array; None = digest the whole array.
RangeSpec = Optional[Tuple[Tuple[int, int], ...]]


@functools.lru_cache(maxsize=256)
def _digest_many_jit(n_arrays: int, range_specs: Tuple[RangeSpec, ...]):
    """Compiled program digesting every (array, row-range) pair in one
    dispatch. Per-dispatch latency is what dominates digest cost on real
    accelerators (a checkpoint's worth of chunks is hundreds of tiny
    reductions); fusing them into one XLA program pays one dispatch + one
    (n, 2) transfer per device group instead of one round-trip per chunk.
    jit retraces per input shapes/dtypes, so one cache entry per chunk
    *layout* serves every step of a training run."""
    import jax
    import jax.numpy as jnp

    def ts_device_digest(arrays):
        outs = []
        with jax.named_scope(_PROGRAM_NAME):
            for x, ranges in zip(arrays, range_specs):
                if ranges is None:
                    outs.append(_digest_jax_impl(x))
                else:
                    for a, b in ranges:
                        outs.append(_digest_jax_impl(x[a:b]))
            return jnp.stack(outs)

    return jax.jit(ts_device_digest)


def digest_many_async(specs: list):
    """Digest many device arrays (each whole, or per row-range) in ONE
    dispatch. ``specs`` is ``[(arr, row_ranges|None), ...]``; all arrays
    should live on the same device (group by device set — the caller's
    job). Returns a future of shape ``(total_chunks, 2)`` uint32, rows in
    spec order (ranges expanded in order)."""
    arrays = [arr for arr, _ in specs]
    range_specs = tuple(
        tuple(r) if r is not None else None for _, r in specs
    )
    fn = _digest_many_jit(len(arrays), range_specs)
    return fn(arrays)


def materialize_many(digest_future: Any) -> np.ndarray:
    """Block on a :func:`digest_many_async` future; returns (n, 2) uint32."""
    return np.asarray(digest_future)


# ---------------------------------------------------------------------------
# string form (what manifests carry)
# ---------------------------------------------------------------------------


def format_digest(d: Tuple[int, int]) -> str:
    return f"{DIGEST_PREFIX}{d[0]:08x}{d[1]:08x}"


def digest_host_str(arr: np.ndarray) -> str:
    return format_digest(digest_host(arr))

"""Device-slab packing: bit-exactness against the serialization path and
end-to-end batched snapshots staging device members through one packed
transfer (the reference's GPUBatchedBufferStager analog, as an XLA
program)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import torchsnapshot_tpu as ts  # noqa: E402
from torchsnapshot_tpu.knobs import (  # noqa: E402
    enable_batching,
    enable_device_pack,
    override_slab_size_threshold_bytes,
)
from torchsnapshot_tpu.ops import device_pack as dp  # noqa: E402
from torchsnapshot_tpu.serialization import array_as_memoryview  # noqa: E402
from torchsnapshot_tpu.test_utils import assert_tree_eq, rand_array  # noqa: E402

DTYPES = [
    "float32",
    "float16",
    "bfloat16",
    "int8",
    "uint8",
    "int32",
    "bool",
    "float8_e4m3fn",
]


def _np_array(shape, dtype, seed=0):
    if dtype in ("bfloat16", "float8_e4m3fn"):
        import ml_dtypes

        return rand_array(shape, "float32", seed).astype(
            np.dtype(getattr(ml_dtypes, dtype))
        )
    return rand_array(shape, dtype, seed)


@pytest.mark.parametrize("dtype", DTYPES)
def test_pack_matches_serialization_bytes(dtype):
    hosts = [_np_array((5, 3), dtype, seed=i) for i in range(3)]
    devs = [jnp.asarray(h) for h in hosts]
    packed = np.asarray(dp.pack_async([(d, None) for d in devs]))
    expect = b"".join(bytes(array_as_memoryview(h)) for h in hosts)
    assert packed.tobytes() == expect


def test_pack_row_slices():
    host = _np_array((16, 4), "float32", seed=1)
    dev = jnp.asarray(host)
    packed = np.asarray(dp.pack_async([(dev, (2, 7)), (dev, (10, 12))]))
    expect = host[2:7].tobytes() + host[10:12].tobytes()
    assert packed.tobytes() == expect


def test_pack_supported_excludes_subbyte_and_complex():
    assert not dp.pack_supported(np.complex64)
    try:
        import ml_dtypes

        assert not dp.pack_supported(ml_dtypes.int4)
    except ImportError:
        pass
    assert dp.pack_supported(np.float32)


def test_batched_snapshot_uses_device_pack(tmp_path, monkeypatch):
    """With batching on, device members of a slab must stage through ONE
    pack call (not per-member np.asarray), and the snapshot must restore
    bit-exactly."""
    from torchsnapshot_tpu.ops import device_pack

    calls = []
    orig = device_pack.pack_async

    def counting(specs):
        calls.append(len(specs))
        return orig(specs)

    monkeypatch.setattr(device_pack, "pack_async", counting)

    tree = {
        f"leaf_{i}": jnp.asarray(_np_array((32, 8), "float32", seed=i))
        for i in range(6)
    }
    tree["host_leaf"] = _np_array((16,), "float32", seed=99)
    p = str(tmp_path / "snap")
    with enable_batching(), enable_device_pack(), \
            override_slab_size_threshold_bytes(1 << 20):
        ts.Snapshot.take(p, {"m": ts.PyTreeState(tree)})
    # All 6 device leaves are below the threshold and on one device group:
    # exactly one pack call with 6 members.
    assert calls == [6]

    dest = {
        "m": ts.PyTreeState(
            {
                **{
                    f"leaf_{i}": jnp.zeros((32, 8), jnp.float32)
                    for i in range(6)
                },
                "host_leaf": np.zeros(16, np.float32),
            }
        )
    }
    ts.Snapshot(p).restore(dest)
    assert_tree_eq(dest["m"].tree, tree)


def test_batched_snapshot_mixed_dtypes_roundtrip(tmp_path):
    tree = {}
    for i, dtype in enumerate(DTYPES):
        tree[f"a_{dtype}"] = jnp.asarray(_np_array((7, 3), dtype, seed=i))
    p = str(tmp_path / "snap")
    with enable_batching(), enable_device_pack(), \
            override_slab_size_threshold_bytes(1 << 20):
        ts.Snapshot.take(p, {"m": ts.PyTreeState(tree)})
    dest = {
        "m": ts.PyTreeState(
            {k: jnp.zeros_like(v) for k, v in tree.items()}
        )
    }
    ts.Snapshot(p).restore(dest)
    for k, v in tree.items():
        got = np.asarray(dest["m"].tree[k])
        want = np.asarray(v)
        assert got.tobytes() == want.tobytes(), k


def test_pack_failure_falls_back(tmp_path, monkeypatch):
    """A failing pack degrades to per-member staging, not a failed take."""
    from torchsnapshot_tpu.ops import device_pack

    def boom(specs):
        raise RuntimeError("injected pack failure")

    monkeypatch.setattr(device_pack, "pack_async", boom)
    tree = {
        f"leaf_{i}": jnp.asarray(_np_array((8, 8), "float32", seed=i))
        for i in range(4)
    }
    p = str(tmp_path / "snap")
    with enable_batching(), enable_device_pack(), \
            override_slab_size_threshold_bytes(1 << 20):
        ts.Snapshot.take(p, {"m": ts.PyTreeState(tree)})
    dest = {
        "m": ts.PyTreeState(
            {f"leaf_{i}": jnp.zeros((8, 8), jnp.float32) for i in range(4)}
        )
    }
    ts.Snapshot(p).restore(dest)
    assert_tree_eq(dest["m"].tree, tree)


def test_pack_fallback_skips_already_scattered_members(monkeypatch):
    """A mid-scatter failure falls back per-member but must skip members
    whose bytes already landed in the slab (their arr was cleared) —
    re-staging them would hit np.asarray(None)."""
    from torchsnapshot_tpu import batcher
    from torchsnapshot_tpu.io_preparer import ArrayBufferStager
    from torchsnapshot_tpu.io_types import WriteReq
    from torchsnapshot_tpu.ops import device_pack

    def boom(specs):
        raise RuntimeError("injected pack failure")

    monkeypatch.setattr(device_pack, "pack_async", boom)

    a = jnp.asarray(_np_array((4, 4), "float32", seed=0))
    b = jnp.asarray(_np_array((4, 4), "float32", seed=1))
    sa = ArrayBufferStager(a, is_async_snapshot=False)
    sb = ArrayBufferStager(b, is_async_snapshot=False)
    size = a.nbytes
    items = [
        (WriteReq(path="x", buffer_stager=sa), 0, size),
        (WriteReq(path="y", buffer_stager=sb), size, size),
    ]
    stager = batcher.BatchedBufferStager(items)
    # Simulate a scatter that already copied member 'a' into the slab.
    sa.arr = None
    slab = bytearray(2 * size)
    stager._pack_group_sync(items, memoryview(slab))
    assert bytes(slab[size:]) == np.asarray(b).tobytes()
    assert bytes(slab[:size]) == bytes(size)  # a's region left alone


def test_batched_stager_cost_stable_across_staging():
    """The staging cost is fixed at construction: staging clears
    stager.arr, and a post-staging re-read (budget release/adjust paths)
    must see the admission-time value, not a recomputation over mutated
    state."""
    import asyncio

    from torchsnapshot_tpu import batcher
    from torchsnapshot_tpu.io_preparer import ArrayBufferStager
    from torchsnapshot_tpu.io_types import WriteReq

    arrs = [jnp.asarray(_np_array((8, 8), "float32", seed=i)) for i in range(2)]
    size = arrs[0].nbytes
    items = [
        (
            WriteReq(path=f"p{i}", buffer_stager=ArrayBufferStager(a, False)),
            i * size,
            size,
        )
        for i, a in enumerate(arrs)
    ]
    stager = batcher.BatchedBufferStager(items)
    cost_before = stager.get_staging_cost_bytes()
    buf = asyncio.run(stager.stage_buffer())
    assert len(buf) == 2 * size
    assert stager.get_staging_cost_bytes() == cost_before


def test_device_pack_off_by_default(tmp_path, monkeypatch):
    """Without the knob, batching stages members individually (no pack)."""
    from torchsnapshot_tpu.ops import device_pack

    calls = []
    orig = device_pack.pack_async

    def counting(specs):
        calls.append(len(specs))
        return orig(specs)

    monkeypatch.setattr(device_pack, "pack_async", counting)
    tree = {
        f"leaf_{i}": jnp.asarray(_np_array((8, 8), "float32", seed=i))
        for i in range(4)
    }
    p = str(tmp_path / "snap")
    with enable_batching(), override_slab_size_threshold_bytes(1 << 20):
        ts.Snapshot.take(p, {"m": ts.PyTreeState(tree)})
    assert calls == []


def test_pack_group_cap_splits_dispatches(tmp_path, monkeypatch):
    from torchsnapshot_tpu import batcher
    from torchsnapshot_tpu.ops import device_pack

    monkeypatch.setattr(batcher.BatchedBufferStager, "_PACK_GROUP_MAX", 3)
    calls = []
    orig = device_pack.pack_async

    def counting(specs):
        calls.append(len(specs))
        return orig(specs)

    monkeypatch.setattr(device_pack, "pack_async", counting)
    tree = {
        f"leaf_{i}": jnp.asarray(_np_array((8, 8), "float32", seed=i))
        for i in range(7)
    }
    p = str(tmp_path / "snap")
    with enable_batching(), enable_device_pack(), \
            override_slab_size_threshold_bytes(1 << 20):
        ts.Snapshot.take(p, {"m": ts.PyTreeState(tree)})
    assert sorted(calls) == [3, 3]  # 7 -> [3, 3] + 1 individually
    dest = {
        "m": ts.PyTreeState(
            {f"leaf_{i}": jnp.zeros((8, 8), jnp.float32) for i in range(7)}
        )
    }
    ts.Snapshot(p).restore(dest)
    assert_tree_eq(dest["m"].tree, tree)

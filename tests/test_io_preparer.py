"""Pure-unit preparer tests: read reqs fulfilled directly from write reqs
in memory, no storage plugin involved.

Reference parity: tests/test_tensor_io_preparer.py:32-56
(``_fulfill_read_reqs_with_write_reqs``) and
tests/test_chunked_tensor_io_preparer.py.
"""

from __future__ import annotations

import asyncio
from typing import Any, Dict, List

import numpy as np
import pytest

from torchsnapshot_tpu import knobs
from torchsnapshot_tpu.io_preparer import (
    ArrayIOPreparer,
    ChunkedArrayIOPreparer,
    chunk_shapes,
    prepare_read,
    prepare_write,
)
from torchsnapshot_tpu.io_types import ReadReq, WriteReq
from torchsnapshot_tpu.manifest import (
    ArrayEntry,
    ChunkedArrayEntry,
    ObjectEntry,
    PrimitiveEntry,
)
from torchsnapshot_tpu.test_utils import rand_array


def fulfill_read_reqs_with_write_reqs(
    read_reqs: List[ReadReq], write_reqs: List[WriteReq]
) -> None:
    """Stage every write request's buffer, then feed each read request's
    consumer from the staged bytes (honoring byte ranges)."""
    loop = asyncio.new_event_loop()
    try:
        staged: Dict[str, bytes] = {}
        for wr in write_reqs:
            staged[wr.path] = bytes(
                loop.run_until_complete(wr.buffer_stager.stage_buffer())
            )
        for rr in read_reqs:
            buf = staged[rr.path]
            if rr.byte_range is not None:
                begin, end = rr.byte_range
                buf = buf[begin:end]
            loop.run_until_complete(rr.buffer_consumer.consume_buffer(buf))
    finally:
        loop.close()


@pytest.mark.parametrize(
    "dtype",
    ["float32", "float64", "float16", "bfloat16", "int8", "uint8", "int16",
     "int32", "int64", "bool", "complex64", "complex128"],
)
def test_array_write_read_roundtrip(dtype: str) -> None:
    import jax.numpy as jnp

    if dtype == "bfloat16":
        src = jnp.asarray(rand_array((13, 7), "float32", seed=3), dtype=jnp.bfloat16)
        src = np.asarray(src)
    else:
        src = rand_array((13, 7), dtype, seed=3)
    entry, write_reqs = prepare_write(src, "foo/bar", rank=0, replicated=False)
    assert isinstance(entry, ArrayEntry)
    assert entry.location == "0/foo/bar"
    dst = ArrayIOPreparer.empty_array_from_entry(entry)
    read_reqs = prepare_read(entry, obj_out=dst)
    fulfill_read_reqs_with_write_reqs(read_reqs, write_reqs)
    np.testing.assert_array_equal(dst, src)


def test_jax_array_roundtrip() -> None:
    import jax.numpy as jnp

    src = jnp.arange(64, dtype=jnp.float32).reshape(8, 8) * 0.5
    entry, write_reqs = prepare_write(src, "w", rank=2, replicated=False)
    assert isinstance(entry, ArrayEntry)
    assert entry.location == "2/w"
    dst = ArrayIOPreparer.empty_array_from_entry(entry)
    read_reqs = prepare_read(entry, obj_out=dst)
    fulfill_read_reqs_with_write_reqs(read_reqs, write_reqs)
    np.testing.assert_array_equal(dst, np.asarray(src))


def test_replicated_storage_path() -> None:
    src = rand_array((4,), "float32")
    entry, write_reqs = prepare_write(src, "p/q", rank=1, replicated=True)
    assert entry.location == "replicated/p/q"
    assert entry.replicated
    assert write_reqs[0].path == "replicated/p/q"


@pytest.mark.parametrize("limit", [16, 64, 1000])
def test_ranged_reads_under_buffer_limit(limit: int) -> None:
    """With a buffer size limit, a large entry becomes multiple ranged reads
    whose byte ranges tile the payload (reference io_preparer.py:706-752)."""
    src = rand_array((32, 8), "float32", seed=9)
    entry, write_reqs = prepare_write(src, "big", rank=0)
    dst = ArrayIOPreparer.empty_array_from_entry(entry)
    read_reqs = prepare_read(entry, obj_out=dst, buffer_size_limit_bytes=limit)
    if limit < src.nbytes:
        assert len(read_reqs) > 1
        for rr in read_reqs:
            begin, end = rr.byte_range
            assert end - begin <= max(limit, src.itemsize)
        # Ranges tile [0, nbytes) exactly.
        spans = sorted(rr.byte_range for rr in read_reqs)
        assert spans[0][0] == 0 and spans[-1][1] == src.nbytes
        for (b0, e0), (b1, e1) in zip(spans, spans[1:]):
            assert e0 == b1
    fulfill_read_reqs_with_write_reqs(read_reqs, write_reqs)
    np.testing.assert_array_equal(dst, src)


def test_noncontiguous_dest_falls_back_to_whole_read() -> None:
    src = rand_array((8, 8), "float32", seed=1)
    entry, write_reqs = prepare_write(src, "x", rank=0)
    backing = np.zeros((8, 16), dtype=np.float32)
    dst = backing[:, ::2]  # non-contiguous view
    assert not dst.flags.c_contiguous
    read_reqs = prepare_read(entry, obj_out=dst, buffer_size_limit_bytes=16)
    assert len(read_reqs) == 1
    fulfill_read_reqs_with_write_reqs(read_reqs, write_reqs)
    np.testing.assert_array_equal(dst, src)


def test_can_load_inplace() -> None:
    src = rand_array((4, 4), "float32")
    entry, _ = prepare_write(src, "x", rank=0)
    ok = np.empty((4, 4), dtype=np.float32)
    assert ArrayIOPreparer.can_load_inplace(entry, ok)
    wrong_shape = np.empty((4, 5), dtype=np.float32)
    assert not ArrayIOPreparer.can_load_inplace(entry, wrong_shape)
    wrong_dtype = np.empty((4, 4), dtype=np.float64)
    assert not ArrayIOPreparer.can_load_inplace(entry, wrong_dtype)
    ro = np.empty((4, 4), dtype=np.float32)
    ro.flags.writeable = False
    assert not ArrayIOPreparer.can_load_inplace(entry, ro)
    assert not ArrayIOPreparer.can_load_inplace(entry, [[0.0] * 4] * 4)


# ---------------------------------------------------------------------------
# Chunked arrays
# ---------------------------------------------------------------------------


def test_chunk_shapes_tile_dim0() -> None:
    shapes = chunk_shapes([100, 16], "float32", max_chunk_size_bytes=1024)
    # 16 fp32 per row = 64 bytes; 1024 bytes => 16 rows per chunk.
    assert shapes[0] == (0, 16)
    assert shapes[-1][1] == 100
    covered = []
    for start, stop in shapes:
        assert stop > start
        covered.extend(range(start, stop))
    assert covered == list(range(100))


def test_chunk_shapes_row_larger_than_budget_stays_whole() -> None:
    shapes = chunk_shapes([4, 1024], "float64", max_chunk_size_bytes=16)
    assert shapes == [(0, 1), (1, 2), (2, 3), (3, 4)]


def test_chunked_roundtrip_and_entry_layout() -> None:
    src = rand_array((64, 4), "float32", seed=5)
    with knobs.override_max_chunk_size_bytes(256):
        entry, write_reqs = prepare_write(src, "big", rank=0)
    assert isinstance(entry, ChunkedArrayEntry)
    assert len(entry.chunks) == len(write_reqs) > 1
    for chunk, wr in zip(entry.chunks, write_reqs):
        assert chunk.array.location == wr.path
        assert chunk.array.location.startswith("0/big_")
        assert chunk.sizes[1:] == [4]
    dst = ArrayIOPreparer.empty_array_from_entry(entry)
    read_reqs = prepare_read(entry, obj_out=dst)
    fulfill_read_reqs_with_write_reqs(read_reqs, write_reqs)
    np.testing.assert_array_equal(dst, src)


def test_chunked_roundtrip_with_buffer_limit() -> None:
    src = rand_array((64, 4), "float32", seed=6)
    with knobs.override_max_chunk_size_bytes(512):
        entry, write_reqs = prepare_write(src, "big", rank=0)
    dst = ArrayIOPreparer.empty_array_from_entry(entry)
    read_reqs = prepare_read(entry, obj_out=dst, buffer_size_limit_bytes=128)
    assert len(read_reqs) > len(entry.chunks)
    fulfill_read_reqs_with_write_reqs(read_reqs, write_reqs)
    np.testing.assert_array_equal(dst, src)


def test_should_chunk_respects_knob() -> None:
    arr = rand_array((1024,), "float32")
    assert not ChunkedArrayIOPreparer.should_chunk(arr)
    with knobs.override_max_chunk_size_bytes(64):
        assert ChunkedArrayIOPreparer.should_chunk(arr)
        # 0-d and single-row arrays are never chunked.
        assert not ChunkedArrayIOPreparer.should_chunk(np.float32(1.0))
        assert not ChunkedArrayIOPreparer.should_chunk(
            rand_array((1, 1024), "float32")
        )


# ---------------------------------------------------------------------------
# Objects & primitives
# ---------------------------------------------------------------------------


def test_object_roundtrip_via_callback() -> None:
    src = {"a": [1, 2, 3], "b": ("x", 4.5)}
    entry, write_reqs = prepare_write(src, "obj", rank=0)
    assert isinstance(entry, ObjectEntry)
    assert entry.obj_type == "dict"
    box: List[Any] = []
    read_reqs = prepare_read(entry, callback=box.append)
    fulfill_read_reqs_with_write_reqs(read_reqs, write_reqs)
    assert box == [src]


def test_primitives_inline_no_write_reqs() -> None:
    for val in (3, 3.25, "s", True, b"\x00\x01"):
        entry, write_reqs = prepare_write(val, "p", rank=0)
        assert isinstance(entry, PrimitiveEntry)
        assert write_reqs == []
        assert entry.get_value() == val
        assert type(entry.get_value()) is type(val)
        assert prepare_read(entry) == []


def test_prepare_read_requires_destination_or_callback() -> None:
    arr_entry, _ = prepare_write(rand_array((2,), "float32"), "a", rank=0)
    with pytest.raises(ValueError, match="destination"):
        prepare_read(arr_entry)
    obj_entry, _ = prepare_write(object(), "o", rank=0)
    with pytest.raises(ValueError, match="callback"):
        prepare_read(obj_entry)


def test_staging_cost_matches_payload() -> None:
    src = rand_array((16, 16), "float64")
    _, write_reqs = prepare_write(src, "c", rank=0)
    assert write_reqs[0].buffer_stager.get_staging_cost_bytes() == src.nbytes
    with knobs.override_max_chunk_size_bytes(512):
        _, chunked_reqs = prepare_write(src, "c", rank=0)
    assert (
        sum(wr.buffer_stager.get_staging_cost_bytes() for wr in chunked_reqs)
        == src.nbytes
    )


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("bound", [True, False], ids=["bound", "self-made"])
def test_late_destination(dtype: str, bound: bool) -> None:
    """An owned dense entry planned without a destination: its consumer
    takes the buffer the read pipeline binds when it admits the read (a
    slab of dest_pool), hands it back through ``placed`` exactly once,
    and makes its own where nobody bound one (a batched read's member, a
    pipeline without a pool)."""
    src = rand_array((24, 16), dtype)
    entry, write_reqs = prepare_write(src, "late", rank=0)
    (req,) = prepare_read(entry, obj_out=None, dest_owned=True)
    consumer = req.buffer_consumer
    assert consumer.dst is None
    assert consumer.unbound_destination_bytes() == src.nbytes

    slab = np.zeros(src.nbytes, np.uint8)
    handed_back: List[Any] = []
    if bound:
        consumer.bind_destination(slab, handed_back.append)
    assert consumer.unbound_destination_bytes() == 0 or not bound

    direct = consumer.direct_destination()
    assert direct is not None and direct.nbytes == src.nbytes
    assert np.shares_memory(np.frombuffer(direct, np.uint8), slab) == bound
    fulfill_read_reqs_with_write_reqs([req], write_reqs)
    assert consumer.dst.shape == src.shape and consumer.dst.dtype == src.dtype
    np.testing.assert_array_equal(
        consumer.dst.view(np.uint8), src.view(np.uint8)
    )
    assert np.shares_memory(consumer.dst, slab) == bound

    consumer.placed("on-device")
    consumer.placed("on-device")
    assert handed_back == (["on-device"] if bound else [])


def test_late_destination_is_for_owned_whole_reads() -> None:
    src = rand_array((8, 8), "float32")
    entry, _ = prepare_write(src, "late", rank=0)
    with pytest.raises(ValueError, match="destination"):
        prepare_read(entry, obj_out=None, dest_owned=False)
    # A buffer limit cannot split what has no destination yet.
    assert len(prepare_read(entry, None, buffer_size_limit_bytes=16, dest_owned=True)) == 1
    with knobs.override_max_chunk_size_bytes(64):
        chunked, _ = prepare_write(src, "late", rank=0)
    assert isinstance(chunked, ChunkedArrayEntry)
    with pytest.raises(ValueError, match="destination"):
        prepare_read(chunked, obj_out=None, dest_owned=True)

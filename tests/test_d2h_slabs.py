"""Recycled host destinations for device-to-host staging (``d2h_slabs``).

The CPU backend aliases a device array's host value for most dtypes and
allocates nothing; it copies ``int4``, so the leaves here are ``int4`` (one
byte an element on the host) of at least ``FLOOR_BYTES``.
"""

import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchsnapshot_tpu as ts
from torchsnapshot_tpu import d2h_slabs, dest_pool
from torchsnapshot_tpu.scheduler import derived_staging_window_bytes
from torchsnapshot_tpu.telemetry import names, trace

try:
    from numpy._core.multiarray import get_handler_name
except ImportError:  # numpy < 2
    from numpy.core.multiarray import get_handler_name

MIB = 1 << 20
HANDLER = "torchsnapshot_tpu_d2h_slabs"

pytestmark = pytest.mark.skipif(
    not d2h_slabs.available(), reason="the slab handler needs a C++ toolchain"
)


@pytest.fixture(autouse=True)
def empty_pool():
    dest_pool.process_pool().clear()
    yield
    dest_pool.process_pool().clear()


def _state(seed=0, shapes=((2048, 1024), (1024, 3072))):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes) + 1)
    state = {
        f"w{i}": jax.random.randint(k, shape, -8, 8).astype(jnp.int4)
        for i, (k, shape) in enumerate(zip(keys, shapes))
    }
    # Under the floor: numpy's own allocator.
    state["small"] = jax.random.randint(keys[-1], (64, 64), -8, 8).astype(jnp.int4)
    return state


def _d2h_spans(mark):
    return [
        e["args"]
        for e in trace.get_recorder().events_since(mark)
        if e.get("ph") == "X" and e["name"] == names.SPAN_STAGE_D2H
    ]


def test_the_handler_is_scoped_to_the_lease_and_its_thread():
    other = {}

    def on_another_thread():
        other["name"] = get_handler_name()
        other["array"] = get_handler_name(np.empty(4 * MIB, np.uint8))

    with d2h_slabs.staging_window([4 * MIB], 8 * MIB):
        assert get_handler_name() == "default_allocator"
        with d2h_slabs.lease(4 * MIB) as lease:
            inside = np.empty(4 * MIB, np.uint8)
            assert get_handler_name() == HANDLER
            assert get_handler_name(inside) == HANDLER
            thread = threading.Thread(target=on_another_thread)
            thread.start()
            thread.join()
        after = np.empty(4 * MIB, np.uint8)
    assert other == {"name": "default_allocator", "array": "default_allocator"}
    assert get_handler_name() == "default_allocator"
    assert get_handler_name(after) == "default_allocator"
    assert (lease.leased_bytes, lease.recycled_bytes) == (4 * MIB, 0)
    assert d2h_slabs.slabs_out() == 1
    del inside
    assert d2h_slabs.slabs_out() == 0


def test_outside_a_window_or_under_the_floor_nothing_is_set():
    with d2h_slabs.lease(4 * MIB) as lease:
        assert lease is None and get_handler_name() == "default_allocator"
    with d2h_slabs.staging_window([4 * MIB], 8 * MIB):
        with d2h_slabs.lease(d2h_slabs.FLOOR_BYTES - 1) as lease:
            assert lease is None and get_handler_name() == "default_allocator"


def test_an_array_freed_on_another_thread_gives_its_slab_back():
    pool = dest_pool.process_pool()
    with d2h_slabs.staging_window([2 * MIB], 8 * MIB):
        with d2h_slabs.lease(2 * MIB):
            arrays = [np.empty(2 * MIB, np.uint8)]
    assert d2h_slabs.slabs_out() == 1
    thread = threading.Thread(target=arrays.clear)
    thread.start()
    thread.join()
    assert d2h_slabs.slabs_out() == 0
    assert pool.retained_bytes() == 2 * MIB
    with d2h_slabs.staging_window([2 * MIB], 8 * MIB):
        with d2h_slabs.lease(2 * MIB) as lease:
            again = np.empty(2 * MIB, np.uint8)
    assert lease.recycled_bytes == 2 * MIB
    del again


def test_exact_sizes_are_recycled():
    with d2h_slabs.staging_window([2 * MIB, 3 * MIB], 8 * MIB):
        for expect_recycled in (0, 5 * MIB):
            with d2h_slabs.lease(3 * MIB) as lease:
                a = np.empty(2 * MIB, np.uint8)
                b = np.empty(3 * MIB, np.uint8)
                a[:] = 1
                b[:] = 2
            assert lease.leased_bytes == 5 * MIB
            assert lease.recycled_bytes == expect_recycled
            del a, b
        with d2h_slabs.lease(3 * MIB) as lease:
            c = np.empty(3 * MIB + 1, np.uint8)  # no slab of this size
        assert (lease.leased_bytes, lease.recycled_bytes) == (3 * MIB + 1, 0)
        del c


@pytest.mark.parametrize(
    "nbytes,leased",
    [(d2h_slabs.FLOOR_BYTES - 1, False), (d2h_slabs.FLOOR_BYTES, True), (9 * MIB, False)],
    ids=["under_floor", "at_floor", "over_cap"],
)
def test_a_request_under_the_floor_or_over_the_cap_goes_to_numpy(nbytes, leased):
    # The window's cap: 4 MiB + its largest request, 4 MiB.
    with d2h_slabs.staging_window([4 * MIB], 4 * MIB):
        # A slab that stays out, so that the pool is not empty (an empty
        # pool admits any size).
        with d2h_slabs.lease(4 * MIB):
            held = np.empty(4 * MIB, np.uint8)
        with d2h_slabs.lease(4 * MIB) as lease:
            arr = np.empty(nbytes, np.uint8)
            arr[:] = 7
    assert get_handler_name(arr) == HANDLER
    assert lease.leased_bytes == (nbytes if leased else 0)
    assert d2h_slabs.slabs_out() == 1 + leased
    del arr, held
    assert d2h_slabs.slabs_out() == 0


def test_a_second_async_take_lands_in_recycled_slabs(tmp_path):
    state = _state()
    large = {k: int(v.nbytes) for k, v in state.items() if v.nbytes >= d2h_slabs.FLOOR_BYTES}
    readings = []
    for i in range(2):
        mark = trace.get_recorder().mark()
        ts.Snapshot.async_take(str(tmp_path / str(i)), {"s": ts.PyTreeState(state)}).wait()
        spans = _d2h_spans(mark)
        assert sorted(a["bytes"] for a in spans) == sorted(int(v.nbytes) for v in state.values())
        readings.append(
            {
                "leased": sum(a.get(names.ARG_LEASED_BYTES, 0) for a in spans),
                "recycled": sum(a.get(names.ARG_RECYCLED_BYTES, 0) for a in spans),
                "small_args": [
                    set(a) & {names.ARG_LEASED_BYTES, names.ARG_RECYCLED_BYTES}
                    for a in spans
                    if a["bytes"] < d2h_slabs.FLOOR_BYTES
                ],
            }
        )
        # Every slab is back once the take has committed.
        assert d2h_slabs.slabs_out() == 0
    total = sum(large.values())
    assert readings[0]["leased"] == total and readings[0]["recycled"] == 0
    assert readings[1]["leased"] == total and readings[1]["recycled"] == total
    # A leaf under the floor takes no lease: its span has no slab args.
    for reading in readings:
        assert reading["small_args"] == [set()]


def test_the_pool_keeps_at_most_the_window_and_its_largest_request(tmp_path):
    state = _state(shapes=((2048, 1024),) * 3 + ((1024, 3072),) * 2)
    sizes = [int(v.nbytes) for v in state.values()]
    ts.Snapshot.async_take(str(tmp_path / "s"), {"s": ts.PyTreeState(state)}).wait()
    pool = dest_pool.process_pool()
    cap = derived_staging_window_bytes(sizes, 1 << 40) + max(sizes)
    assert 0 < pool.retained_bytes() <= cap
    pool.clear()
    assert pool.retained_bytes() == 0


def test_a_copied_leaf_saved_twice_has_the_same_bytes_and_restores_bit_identical(tmp_path):
    state = _state(seed=3)
    paths = [str(tmp_path / str(i)) for i in range(2)]
    for path in paths:
        ts.Snapshot.async_take(path, {"s": ts.PyTreeState(state)}).wait()
    files = {}
    for path in paths:
        found = {}
        for root, _, names_ in os.walk(path):
            for name in names_:
                full = os.path.join(root, name)
                if name != ".snapshot_metadata":
                    with open(full, "rb") as f:
                        found[os.path.relpath(full, path)] = f.read()
        files[path] = found
    first, second = files.values()
    assert any(k.startswith("checksums") for k in first)
    assert first == second
    target = {"s": ts.PyTreeState(jax.tree_util.tree_map(jnp.zeros_like, state))}
    ts.Snapshot(paths[1]).restore(target)
    for key, value in state.items():
        restored = target["s"].tree[key]
        assert restored.dtype == value.dtype
        assert np.array_equal(np.asarray(restored), np.asarray(value)), key


def test_a_blocking_take_and_a_restore_take_no_slab(tmp_path):
    state = _state(seed=5)
    path = str(tmp_path / "s")
    mark = trace.get_recorder().mark()
    ts.Snapshot.take(path, {"s": ts.PyTreeState(state)})
    target = {"s": ts.PyTreeState(jax.tree_util.tree_map(jnp.zeros_like, state))}
    ts.Snapshot(path).restore(target)
    events = [e for e in trace.get_recorder().events_since(mark) if e.get("ph") == "X"]
    assert any(e["name"] == names.SPAN_STAGE_D2H for e in events)
    for e in events:
        assert names.ARG_RECYCLED_BYTES not in e.get("args", {}), e["name"]
        assert names.ARG_LEASED_BYTES not in e.get("args", {}), e["name"]
    assert d2h_slabs.slabs_out() == 0
    for key, value in state.items():
        assert np.array_equal(np.asarray(target["s"].tree[key]), np.asarray(value))

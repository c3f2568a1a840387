"""One clone program a device group a take.

A deferred-staging async take clones its jax sources on the device before it
returns. The runtime makes every dispatch wait for one of its slots, which a
training loop keeps full of steps, so the capture pass dispatches one program
over all the sources of a device group (`io_preparer.capture_write_reqs`) and
not one a leaf. Every case here deletes the sources the moment `async_take`
returns, as a donating step does, and holds the restore to the bits.
"""

import contextlib
import logging
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import torchsnapshot_tpu as ts
from torchsnapshot_tpu import io_preparer, knobs
from torchsnapshot_tpu.telemetry import names, trace

N = 6
SHAPE = (64, 32)


def _dense(n=N, shape=SHAPE, device=None):
    device = device or jax.devices()[0]
    return {f"w{i}": jax.device_put(
        jnp.arange(math.prod(shape), dtype=jnp.float32).reshape(shape) + i, device)
        for i in range(n)}


def _zeros_like(tree):
    return {k: (jax.device_put(jnp.zeros(v.shape, v.dtype), v.sharding)
                if isinstance(v, jax.Array) else np.zeros_like(v))
            for k, v in tree.items()}


def _take_delete_restore(path, tree):
    """An async take of `tree` whose jax leaves are deleted right after the
    call returns; the restore is compared with the bits the leaves held.
    Returns the take's spans by name."""
    want = {k: np.array(np.asarray(v)) for k, v in tree.items()}
    fresh = _zeros_like(tree)
    rec = trace.get_recorder()
    mark = rec.mark()
    pending = ts.Snapshot.async_take(str(path), {"m": ts.PyTreeState(tree)})
    for v in tree.values():
        if isinstance(v, jax.Array):
            v.delete()
    snapshot = pending.wait()
    events = [e for e in rec.events_since(mark) if e["ph"] == "X"]
    (stage,) = [e for e in events if e["name"] == names.SPAN_ASYNC_TAKE_STAGE]
    spans = {}
    for e in events:
        if e["op"] == stage["op"]:
            spans.setdefault(e["name"], []).append(e)
    out = {"m": ts.PyTreeState(fresh)}
    snapshot.restore(out)
    for k, v in want.items():
        got = np.asarray(out["m"].tree[k])
        assert got.dtype == v.dtype and got.tobytes() == v.tobytes(), k
    return spans


def _counters(spans):
    (capture,) = spans[names.SPAN_DEVICE_CAPTURE]
    return {k: capture["args"][k]
            for k in ("clone_programs", "clone_leaves", "fallback_leaves")}


def _chunked():
    return knobs.override_max_chunk_size_bytes(2048)  # SHAPE in float32 is 8 KiB


@pytest.mark.parametrize("layout,stagers_per_leaf", [
    (contextlib.nullcontext, 1), (_chunked, 4), (knobs.enable_batching, 1)],
    ids=["dense", "chunked", "slab"])
def test_n_leaves_on_one_device_are_one_program(tmp_path, layout, stagers_per_leaf):
    tree = _dense()
    tree["host"] = np.arange(12, dtype=np.int64)
    with layout():
        spans = _take_delete_restore(tmp_path / "s", tree)
    (clone,) = spans[names.SPAN_CAPTURE_CLONE]
    nbytes = N * 4 * math.prod(SHAPE)
    assert clone["args"] == {"kind": "device", "bytes": nbytes, "leaves": N}
    assert _counters(spans) == {"clone_programs": 1, "clone_leaves": N, "fallback_leaves": 0}
    # However many stagers slice a leaf, or wherever a slab holds it, its
    # bytes leave the device once each.
    d2h = spans[names.SPAN_STAGE_D2H]
    assert sum(e["args"]["bytes"] for e in d2h) == nbytes
    if layout is _chunked:
        assert len(d2h) == N * stagers_per_leaf
    if layout is knobs.enable_batching:
        assert names.SPAN_BATCHER_STAGE_SLAB_VECTORIZED in spans or (
            names.SPAN_BATCHER_STAGE_SLAB in spans)
    (host,) = spans[names.SPAN_CAPTURE_HOST_COPY]
    assert host["args"]["leaf"].endswith("host")
    # The drain waits for the clones once, before it stages anything.
    (ready,) = spans[names.SPAN_CAPTURE_READY]
    assert ready["args"] == {"bytes": nbytes, "programs": 1}
    assert ready["tid"] != clone["tid"]
    assert ready["ts"] + ready["dur"] <= min(e["ts"] for e in d2h)


def test_a_sharded_array_is_one_program_a_device(tmp_path):
    devices = jax.devices()[:4]
    mesh = Mesh(np.asarray(devices), ("x",))
    rows = NamedSharding(mesh, P("x"))
    tree = {
        "a": jax.device_put(jnp.arange(8 * 16, dtype=jnp.float32).reshape(8, 16), rows),
        "b": jax.device_put(jnp.arange(4 * 8, dtype=jnp.int32).reshape(4, 8) * 3, rows),
        "dense": jax.device_put(jnp.arange(32, dtype=jnp.bfloat16), devices[0]),
    }
    spans = _take_delete_restore(tmp_path / "s", tree)
    clones = spans[names.SPAN_CAPTURE_CLONE]
    assert len(clones) == len(devices)
    # Two shards a device, and the dense leaf with device 0's.
    assert sorted(e["args"]["leaves"] for e in clones) == [2, 2, 2, 3]
    assert _counters(spans) == {"clone_programs": 4, "clone_leaves": 9, "fallback_leaves": 0}
    (ready,) = spans[names.SPAN_CAPTURE_READY]
    assert ready["args"]["programs"] == 4
    assert ready["args"]["bytes"] == sum(e["args"]["bytes"] for e in clones)


@pytest.mark.parametrize("n,cap", [(10, 4), (8, 4), (3, 1)])
def test_above_the_member_cap_a_group_is_several_programs(tmp_path, monkeypatch, n, cap):
    monkeypatch.setattr(io_preparer, "_CLONE_GROUP_MAX", cap)
    spans = _take_delete_restore(tmp_path / "s", _dense(n))
    clones = spans[names.SPAN_CAPTURE_CLONE]
    assert len(clones) == math.ceil(n / cap)
    assert sum(e["args"]["leaves"] for e in clones) == n
    assert max(e["args"]["leaves"] for e in clones) <= cap
    assert _counters(spans) == {
        "clone_programs": math.ceil(n / cap), "clone_leaves": n, "fallback_leaves": 0}


def test_the_shipped_cap_holds_a_few_hundred_leaves_in_one_program():
    # pythia-1b's 299 leaves must be one program.
    assert io_preparer._CLONE_GROUP_MAX >= 512


def _failing_group_program(fails_for):
    real = io_preparer._capture_clone_group_jit()

    def program(members):
        if fails_for(members):
            raise RuntimeError("RESOURCE_EXHAUSTED: planted")
        return real(members)

    return lambda: program


def test_a_group_program_that_raises_leaves_every_leaf_to_the_per_leaf_path(
        tmp_path, monkeypatch, caplog):
    monkeypatch.setattr(io_preparer, "_capture_clone_group_jit",
                        _failing_group_program(lambda members: True))
    with caplog.at_level(logging.WARNING, logger=io_preparer.__name__):
        spans = _take_delete_restore(tmp_path / "s", _dense())
    clones = spans[names.SPAN_CAPTURE_CLONE]
    # The failed program's span, then one a leaf as before this mechanism.
    assert [e["args"].get("leaves") for e in clones] == [N] + [None] * N
    assert sorted(e["args"]["leaf"] for e in clones[1:]) == [f"0/m/w{i}" for i in range(N)]
    assert _counters(spans) == {"clone_programs": 0, "clone_leaves": 0, "fallback_leaves": N}
    assert names.SPAN_CAPTURE_HOST_COPY not in spans
    # One warning a group, with its byte count; none a leaf.
    warnings = [r for r in caplog.records if r.levelno >= logging.WARNING]
    assert len(warnings) == 1
    assert str(N * 4 * math.prod(SHAPE)) in warnings[0].getMessage()
    # Each per-leaf clone is a program of its own for the drain to wait for.
    (ready,) = spans[names.SPAN_CAPTURE_READY]
    assert ready["args"] == {"bytes": N * 4 * math.prod(SHAPE), "programs": N}


def test_one_failing_group_does_not_touch_the_others(tmp_path, monkeypatch):
    first, second = jax.devices()[:2]
    on_second = lambda members: any(second in m.devices() for m in members)  # noqa: E731
    monkeypatch.setattr(io_preparer, "_capture_clone_group_jit",
                        _failing_group_program(on_second))
    tree = _dense(3, device=first)
    tree.update({f"v{i}": v for i, v in enumerate(_dense(2, device=second).values())})
    spans = _take_delete_restore(tmp_path / "s", tree)
    assert _counters(spans) == {"clone_programs": 1, "clone_leaves": 3, "fallback_leaves": 2}
    per_leaf = [e for e in spans[names.SPAN_CAPTURE_CLONE] if "leaf" in e["args"]]
    assert sorted(e["args"]["leaf"] for e in per_leaf) == ["0/m/v0", "0/m/v1"]
    (ready,) = spans[names.SPAN_CAPTURE_READY]
    assert ready["args"]["programs"] == 3


def test_a_take_that_waited_for_its_digests_clones_leaf_by_leaf(tmp_path):
    """Its plan drained the runtime's queue, and what it writes changes from
    save to save: a program a leaf, compiled once a shape, and none over the
    set (the benchmark's incremental cell may compile nothing in its window)."""
    rec = trace.get_recorder()
    tree = _dense(4)
    want = {k: np.array(np.asarray(v)) for k, v in tree.items()}
    base = str(tmp_path / "base")
    mark = rec.mark()
    ts.Snapshot.async_take(base, {"m": ts.PyTreeState(tree)}, record_digests=True).wait()
    changed = dict(tree, w1=tree["w1"] + 1, w3=tree["w3"] * 2)
    want.update({k: np.array(np.asarray(changed[k])) for k in ("w1", "w3")})
    compiled = []

    def listener(name, *args, **kwargs):
        compiled.append(name)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        pending = ts.Snapshot.async_take(str(tmp_path / "incr"),
                                         {"m": ts.PyTreeState(changed)}, incremental_base=base)
        for v in changed.values():
            v.delete()
        snapshot = pending.wait()
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    by_take = {}
    for e in rec.events_since(mark):
        if e["ph"] == "X" and e["name"] in (names.SPAN_CAPTURE_CLONE, names.SPAN_DEVICE_CAPTURE):
            by_take.setdefault(e["op"], {}).setdefault(e["name"], []).append(e["args"])
    full, incr = (by_take[op] for op in sorted(by_take))
    assert [a["leaves"] for a in full[names.SPAN_CAPTURE_CLONE]] == [1] * 4
    assert [a["leaves"] for a in incr[names.SPAN_CAPTURE_CLONE]] == [1] * 2
    (counters,) = incr[names.SPAN_DEVICE_CAPTURE]
    assert (counters["clone_programs"], counters["clone_leaves"],
            counters["fallback_leaves"]) == (2, 2, 0)
    # The two written leaves have the shape the full save's had.
    assert not [name for name in compiled if "backend_compile" in name]
    fresh = {"m": ts.PyTreeState(_zeros_like(want))}
    snapshot.restore(fresh)
    for k, v in want.items():
        assert np.asarray(fresh["m"].tree[k]).tobytes() == v.tobytes(), k


def test_a_take_without_jax_leaves_dispatches_and_waits_for_nothing(tmp_path):
    spans = _take_delete_restore(tmp_path / "s", {"host": np.arange(100.0)})
    assert names.SPAN_CAPTURE_CLONE not in spans and names.SPAN_CAPTURE_READY not in spans
    assert _counters(spans) == {"clone_programs": 0, "clone_leaves": 0, "fallback_leaves": 0}


def test_the_group_program_keeps_the_clones_name_and_copies():
    x = jnp.arange(4096, dtype=jnp.float32).reshape(64, 64)
    y = jnp.arange(128, dtype=jnp.bfloat16)
    program = io_preparer._capture_clone_group_jit()
    # `clone_device_s` finds the library's clones on a profile's Modules line
    # by this prefix.
    assert "jit_ts_capture_clone" in program.lower([x, y]).as_text()
    cx, cy = program([x, y])
    assert cx.unsafe_buffer_pointer() != x.unsafe_buffer_pointer()
    assert cy.unsafe_buffer_pointer() != y.unsafe_buffer_pointer()
    np.testing.assert_array_equal(np.asarray(cx), np.asarray(x))
    assert np.asarray(cy).tobytes() == np.asarray(y).tobytes()


def test_blocking_take_captures_nothing(tmp_path):
    rec = trace.get_recorder()
    mark = rec.mark()
    ts.Snapshot.take(str(tmp_path / "s"), {"m": ts.PyTreeState(_dense(2))})
    seen = {e["name"] for e in rec.events_since(mark)}
    assert not seen & {names.SPAN_DEVICE_CAPTURE, names.SPAN_CAPTURE_CLONE,
                       names.SPAN_CAPTURE_READY}


def test_the_capture_pass_does_not_import_the_attention_kernels():
    """`device_group_key` lives in `ops`: a job that never builds the models
    must not pay for the Pallas imports inside its first `async_take`."""
    code = ("import sys, torchsnapshot_tpu.ops.device_pack as dp; "
            "assert not [m for m in sys.modules if m.endswith('attention')], sys.modules; "
            "from torchsnapshot_tpu.ops import causal_attention, flash_causal_attention, "
            "ring_causal_attention; assert callable(flash_causal_attention)")
    done = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]

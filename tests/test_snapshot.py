"""Single-process Snapshot take/restore/read_object round-trips.

Structural model: reference tests/test_snapshot.py:25-145 — property-matrix
round-trips verified by exact equality, plus chunked-path coverage via
shrunken knobs.
"""

import math
import os

import chex
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import torchsnapshot_tpu as ts
from torchsnapshot_tpu.knobs import override_max_chunk_size_bytes
from torchsnapshot_tpu.snapshot import SNAPSHOT_METADATA_FNAME


def _make_app_state():
    params = {
        "dense": {"w": jnp.ones((8, 16), jnp.bfloat16) * 0.5, "b": jnp.zeros(16)},
        "emb": jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
    }
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)
    return {
        "params": ts.PyTreeState(params),
        "opt": ts.PyTreeState(opt_state),
        "progress": ts.StateDict(epoch=3, step=1234, lr=0.125, name="run", done=False),
        "rng": ts.RngState(jax.random.key(7)),
        "extra": ts.StateDict(
            blob={"nested": [1, 2, {"x": np.arange(5)}]}, opaque={10, 20}
        ),
    }, params, opt_state


def _fresh_app_state():
    params = {
        "dense": {
            "w": jnp.zeros((8, 16), jnp.bfloat16),
            "b": jnp.full((16,), -1.0),
        },
        "emb": jnp.zeros((8, 8), jnp.float32),
    }
    opt = optax.adam(1e-3)
    opt_state = opt.init(jax.tree_util.tree_map(lambda x: x * 0, params))
    return {
        "params": ts.PyTreeState(params),
        "opt": ts.PyTreeState(opt_state),
        "progress": ts.StateDict(epoch=0, step=0, lr=0.0, name="", done=True),
        "rng": ts.RngState(jax.random.key(0)),
        "extra": ts.StateDict(blob=None, opaque=None),
    }


def test_take_restore_roundtrip(tmp_path) -> None:
    app_state, params, opt_state = _make_app_state()
    snapshot = ts.Snapshot.take(str(tmp_path), app_state)
    assert os.path.exists(tmp_path / SNAPSHOT_METADATA_FNAME)

    fresh = _fresh_app_state()
    ts.Snapshot(str(tmp_path)).restore(fresh)

    chex.assert_trees_all_equal(fresh["params"].tree, params)
    chex.assert_trees_all_equal(fresh["opt"].tree, opt_state)
    assert dict(fresh["progress"]) == {
        "epoch": 3,
        "step": 1234,
        "lr": 0.125,
        "name": "run",
        "done": False,
    }
    # Restored leaves keep their flavor: jax stays jax, numpy stays numpy.
    assert isinstance(fresh["params"].tree["dense"]["w"], jax.Array)
    assert fresh["params"].tree["dense"]["w"].dtype == jnp.bfloat16
    restored_blob = fresh["extra"]["blob"]
    np.testing.assert_array_equal(restored_blob["nested"][2]["x"], np.arange(5))
    # RNG restored: same key -> same draw.
    expected = jax.random.normal(jax.random.key(7), (3,))
    actual = jax.random.normal(fresh["rng"].keys, (3,))
    np.testing.assert_array_equal(np.asarray(expected), np.asarray(actual))
    assert snapshot.metadata.world_size == 1


def test_take_restore_chunked(tmp_path) -> None:
    """Shrunken chunk knob forces the chunked path on small arrays
    (reference fixture pattern: tests/test_ddp.py:35-59)."""
    arr = jnp.arange(4096, dtype=jnp.float32).reshape(64, 64)
    app_state = {"s": ts.PyTreeState({"big": arr})}
    with override_max_chunk_size_bytes(1024):
        snap = ts.Snapshot.take(str(tmp_path), app_state)
    manifest = snap.get_manifest()
    entry = manifest["0/s/big"]
    assert entry.type == "ChunkedArray"
    assert len(entry.chunks) == math.ceil(4096 * 4 / 1024)

    fresh = {"s": ts.PyTreeState({"big": jnp.zeros((64, 64), jnp.float32)})}
    ts.Snapshot(str(tmp_path)).restore(fresh)
    np.testing.assert_array_equal(np.asarray(fresh["s"].tree["big"]), np.asarray(arr))


@pytest.mark.parametrize(
    "dtype",
    [
        "float32",
        "bfloat16",
        "float16",
        "int8",
        "int32",
        "uint8",
        "bool",
        "complex64",
        "float8_e4m3fn",
        "float8_e5m2",
    ],
)
def test_roundtrip_dtypes(tmp_path, dtype) -> None:
    rng = np.random.default_rng(0)
    if dtype.startswith("float8"):
        import ml_dtypes

        arr = rng.standard_normal((16, 4)).astype(getattr(ml_dtypes, dtype))
    elif dtype == "bool":
        arr = rng.integers(0, 2, (16, 4)).astype(bool)
    elif dtype == "complex64":
        arr = (rng.standard_normal((16, 4)) + 1j * rng.standard_normal((16, 4))).astype(
            np.complex64
        )
    elif np.dtype(dtype).kind in "iu":
        arr = rng.integers(0, 100, (16, 4)).astype(dtype)
    else:
        arr = rng.standard_normal((16, 4)).astype(dtype)
    x = jnp.asarray(arr)
    app_state = {"t": ts.PyTreeState({"x": x})}
    ts.Snapshot.take(str(tmp_path), app_state)
    fresh = {"t": ts.PyTreeState({"x": jnp.zeros_like(x)})}
    ts.Snapshot(str(tmp_path)).restore(fresh)
    np.testing.assert_array_equal(
        np.ascontiguousarray(np.asarray(fresh["t"].tree["x"])).view(np.uint8),
        np.ascontiguousarray(np.asarray(x)).view(np.uint8),
    )


def test_read_object(tmp_path) -> None:
    app_state, params, _ = _make_app_state()
    ts.Snapshot.take(str(tmp_path), app_state)
    snap = ts.Snapshot(str(tmp_path))

    # Primitive: inline value, no I/O.
    assert snap.read_object("0/progress/step") == 1234
    assert snap.read_object("0/progress/lr") == 0.125

    # Array.
    emb = snap.read_object("0/params/emb")
    np.testing.assert_array_equal(np.asarray(emb), np.asarray(params["emb"]))

    # Array with memory budget -> chunked ranged reads.
    emb2 = snap.read_object("0/params/emb", memory_budget_bytes=64)
    np.testing.assert_array_equal(np.asarray(emb2), np.asarray(params["emb"]))

    # In-place destination.
    out = np.zeros((8, 8), np.float32)
    got = snap.read_object("0/params/emb", obj_out=out)
    assert got is out
    np.testing.assert_array_equal(out, np.asarray(params["emb"]))

    # Leaf inside a nested container.
    x = snap.read_object("0/extra/blob/nested/2/x")
    np.testing.assert_array_equal(x, np.arange(5))

    # Object entry (sets are not flattenable -> pickled whole).
    opaque = snap.read_object("0/extra/opaque")
    assert opaque == {10, 20}

    # Errors.
    with pytest.raises(ValueError, match="not a valid entry"):
        snap.read_object("0/nope")
    with pytest.raises(ValueError, match="rank"):
        snap.read_object("progress/step")
    with pytest.raises(ValueError, match="container"):
        snap.read_object("0/progress")


def test_restore_into_missing_keys_warns_not_crashes(tmp_path) -> None:
    app_state = {"a": ts.StateDict(x=1)}
    ts.Snapshot.take(str(tmp_path), app_state)
    fresh = {"a": ts.StateDict(x=0), "b": ts.StateDict(y=9)}
    ts.Snapshot(str(tmp_path)).restore(fresh)
    assert fresh["a"]["x"] == 1
    assert fresh["b"]["y"] == 9  # untouched


def test_no_commit_marker_means_no_snapshot(tmp_path) -> None:
    with pytest.raises(FileNotFoundError):
        _ = ts.Snapshot(str(tmp_path / "nothing")).metadata


def test_take_validates_app_state(tmp_path) -> None:
    with pytest.raises(TypeError, match="Stateful"):
        ts.Snapshot.take(str(tmp_path), {"bad": {"plain": "dict"}})
    with pytest.raises(TypeError, match="app_state keys"):
        ts.Snapshot.take(str(tmp_path), {7: ts.StateDict(x=1)})


def test_memory_url_roundtrip() -> None:
    from torchsnapshot_tpu.storage_plugins.memory import MemoryStoragePlugin

    try:
        app_state = {"p": ts.PyTreeState({"w": jnp.ones(4)})}
        ts.Snapshot.take("memory://snaptest", app_state)
        fresh = {"p": ts.PyTreeState({"w": jnp.zeros(4)})}
        ts.Snapshot("memory://snaptest").restore(fresh)
        np.testing.assert_array_equal(np.asarray(fresh["p"].tree["w"]), np.ones(4))
    finally:
        MemoryStoragePlugin.drop_store("snaptest")


def test_manifest_yaml_on_disk_is_loadable(tmp_path) -> None:
    app_state, _, _ = _make_app_state()
    ts.Snapshot.take(str(tmp_path), app_state)
    from torchsnapshot_tpu.manifest import SnapshotMetadata

    text = (tmp_path / SNAPSHOT_METADATA_FNAME).read_text()
    md = SnapshotMetadata.from_yaml(text)
    assert "0/params/dense/w" in md.manifest
    assert md.manifest["0/params/dense/w"].dtype == "bfloat16"

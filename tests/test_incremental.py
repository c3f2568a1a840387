"""Incremental takes: unchanged chunks become base refs (no bytes
written), changed chunks rewrite, restores stay byte-exact — across
dense, chunked, and sharded leaves, with checksum inheritance and chained
bases. No reference counterpart (the reference rewrites all bytes every
take); see incremental.py."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import torchsnapshot_tpu as ts
from torchsnapshot_tpu.incremental import relative_ref_prefix
from torchsnapshot_tpu.knobs import (
    override_max_chunk_size_bytes,
    override_max_shard_size_bytes,
)
from torchsnapshot_tpu.manifest import (
    ArrayEntry,
    ChunkedArrayEntry,
    ShardedArrayEntry,
)
from torchsnapshot_tpu.test_utils import assert_tree_eq


def _blob_files(root: str):
    """Relative paths of all data blobs under a snapshot dir (metadata,
    checksums excluded)."""
    out = set()
    for dirpath, _, files in os.walk(root):
        for f in files:
            rel = os.path.relpath(os.path.join(dirpath, f), root)
            if rel.startswith((".snapshot_metadata", "checksums")):
                continue
            out.add(rel)
    return out


def _take_pair(tmp_path, state0, state1, **take1_kwargs):
    """Full take of state0 at step0; incremental take of state1 at step1."""
    p0 = str(tmp_path / "step_0")
    p1 = str(tmp_path / "step_1")
    ts.Snapshot.take(p0, state0, record_digests=True)
    ts.Snapshot.take(p1, state1, incremental_base=p0, **take1_kwargs)
    return p0, p1


def test_relative_ref_prefix():
    assert relative_ref_prefix("/r/step_1", "/r/step_0") == "../step_0"
    assert relative_ref_prefix("s3://b/r/step_1", "s3://b/r/step_0") == "../step_0"
    assert relative_ref_prefix("/r/step_1", "s3://b/r/step_0") is None
    assert relative_ref_prefix("/r/a", "/r/a") is None


def test_relative_ref_prefix_mixed_relative_absolute(tmp_path, monkeypatch):
    """fs roots are anchored to absolute form before relpath: mixing a
    relative take path with an absolute base (or vice versa) must yield
    the same prefix as the all-absolute spelling, not one that depends on
    the cwd at take time."""
    monkeypatch.chdir(tmp_path)
    want = relative_ref_prefix(
        str(tmp_path / "r" / "step_1"), str(tmp_path / "r" / "step_0")
    )
    assert want == "../step_0"
    assert relative_ref_prefix("r/step_1", str(tmp_path / "r" / "step_0")) == want
    assert relative_ref_prefix(str(tmp_path / "r" / "step_1"), "r/step_0") == want
    assert relative_ref_prefix("r/step_1", "r/step_0") == want
    # Same-root detection also survives mixed spellings.
    assert relative_ref_prefix("r/a", str(tmp_path / "r" / "a")) is None
    # A bare '/' root rstrips to empty: still declined (never cwd-anchored).
    assert relative_ref_prefix("/", str(tmp_path / "r" / "step_0")) is None
    assert relative_ref_prefix(str(tmp_path / "r" / "step_1"), "/") is None


def test_dense_unchanged_is_not_rewritten(tmp_path):
    w = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
    b = jnp.ones((8,), jnp.float32)
    state0 = {"m": ts.PyTreeState({"w": w, "b": b})}
    state1 = {"m": ts.PyTreeState({"w": w, "b": b + 1})}  # only b changes
    p0, p1 = _take_pair(tmp_path, state0, state1)

    files1 = _blob_files(p1)
    assert any("b" in f for f in files1)
    assert not any("/w" in f or f.endswith("w") for f in files1), files1

    manifest = ts.Snapshot(p1).get_manifest()
    w_entry = manifest["0/m/w"]
    assert isinstance(w_entry, ArrayEntry)
    assert w_entry.location == "../step_0/0/m/w"
    assert w_entry.digest is not None

    dest = {"m": ts.PyTreeState({"w": jnp.zeros_like(w), "b": jnp.zeros_like(b)})}
    ts.Snapshot(p1).restore(dest)
    assert_tree_eq(dest["m"].tree, {"w": w, "b": b + 1})


def test_unchanged_leaf_skips_d2h(tmp_path, monkeypatch):
    """The whole point: an unchanged leaf's bytes never cross to the host.
    Patch the stager's staging entry point and count invocations."""
    from torchsnapshot_tpu import io_preparer

    w = jnp.arange(1024, dtype=jnp.float32)
    state = {"m": ts.PyTreeState({"w": w})}
    p0 = str(tmp_path / "s0")
    ts.Snapshot.take(p0, state, record_digests=True)

    calls = []
    orig = io_preparer.ArrayBufferStager.__init__

    def counting_init(self, arr, *a, **k):
        calls.append(1)
        return orig(self, arr, *a, **k)

    monkeypatch.setattr(io_preparer.ArrayBufferStager, "__init__", counting_init)
    ts.Snapshot.take(str(tmp_path / "s1"), state, incremental_base=p0)
    assert calls == []  # no stager was even constructed


def test_chunked_partial_change(tmp_path):
    """A large dense array chunked at dim 0: mutate one chunk's rows; the
    other chunks must be refs."""
    with override_max_chunk_size_bytes(256):  # 8x8 f32 rows = 32B/row
        base = np.arange(32 * 8, dtype=np.float32).reshape(32, 8)
        changed = base.copy()
        changed[20, 3] += 1.0  # touches exactly one chunk
        state0 = {"m": ts.PyTreeState({"big": jnp.asarray(base)})}
        state1 = {"m": ts.PyTreeState({"big": jnp.asarray(changed)})}
        p0, p1 = _take_pair(tmp_path, state0, state1)

        manifest = ts.Snapshot(p1).get_manifest()
        entry = manifest["0/m/big"]
        assert isinstance(entry, ChunkedArrayEntry)
        ref_chunks = [
            c for c in entry.chunks if c.array.location.startswith("../")
        ]
        new_chunks = [
            c for c in entry.chunks if not c.array.location.startswith("../")
        ]
        assert len(new_chunks) == 1
        assert len(ref_chunks) == len(entry.chunks) - 1
        assert new_chunks[0].offsets[0] <= 20 < new_chunks[0].offsets[0] + new_chunks[0].sizes[0]

        dest = {"m": ts.PyTreeState({"big": jnp.zeros((32, 8), jnp.float32)})}
        ts.Snapshot(p1).restore(dest)
        np.testing.assert_array_equal(np.asarray(dest["m"].tree["big"]), changed)


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 devices")
def test_sharded_partial_change(tmp_path):
    mesh = Mesh(np.array(jax.devices()[:4]), ("x",))
    sharding = NamedSharding(mesh, P("x", None))
    base = np.arange(16 * 4, dtype=np.float32).reshape(16, 4)
    changed = base.copy()
    changed[9, 1] = -5.0  # third shard (rows 8..12)
    arr0 = jax.device_put(base, sharding)
    arr1 = jax.device_put(changed, sharding)
    p0, p1 = _take_pair(
        tmp_path, {"m": ts.PyTreeState({"t": arr0})}, {"m": ts.PyTreeState({"t": arr1})}
    )

    manifest = ts.Snapshot(p1).get_manifest()
    entry = manifest["0/m/t"]
    assert isinstance(entry, ShardedArrayEntry)
    refs = [s for s in entry.shards if s.array.location.startswith("../")]
    news = [s for s in entry.shards if not s.array.location.startswith("../")]
    assert len(news) == 1 and news[0].offsets == [8, 0]
    assert len(refs) == 3

    dest_arr = jax.device_put(np.zeros((16, 4), np.float32), sharding)
    dest = {"m": ts.PyTreeState({"t": dest_arr})}
    ts.Snapshot(p1).restore(dest)
    np.testing.assert_array_equal(np.asarray(dest["m"].tree["t"]), changed)


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 devices")
def test_sharded_subdivided_pieces(tmp_path):
    """Shards above the shard-size knob subdivide; piece-level skipping
    must work at that granularity too."""
    mesh = Mesh(np.array(jax.devices()[:2]), ("x",))
    sharding = NamedSharding(mesh, P("x", None))
    with override_max_shard_size_bytes(64):  # 4 f32 per row -> 4 rows/piece... 16B/row
        base = np.arange(16 * 4, dtype=np.float32).reshape(16, 4)
        changed = base.copy()
        changed[0, 0] = 99.0  # first piece of first shard
        arr0 = jax.device_put(base, sharding)
        arr1 = jax.device_put(changed, sharding)
        p0, p1 = _take_pair(
            tmp_path,
            {"m": ts.PyTreeState({"t": arr0})},
            {"m": ts.PyTreeState({"t": arr1})},
        )
        manifest = ts.Snapshot(p1).get_manifest()
        entry = manifest["0/m/t"]
        news = [s for s in entry.shards if not s.array.location.startswith("../")]
        refs = [s for s in entry.shards if s.array.location.startswith("../")]
        assert len(news) == 1 and news[0].offsets == [0, 0]
        assert len(refs) == len(entry.shards) - 1

        dest = {
            "m": ts.PyTreeState(
                {"t": jax.device_put(np.zeros((16, 4), np.float32), sharding)}
            )
        }
        ts.Snapshot(p1).restore(dest)
        np.testing.assert_array_equal(np.asarray(dest["m"].tree["t"]), changed)


def test_chained_refs_collapse_to_origin(tmp_path):
    """step2 references an unchanged blob written at step0 *directly*,
    through the chain step2 -> step1 -> step0."""
    w = jnp.arange(32, dtype=jnp.float32)
    state = {"m": ts.PyTreeState({"w": w})}
    p0 = str(tmp_path / "step_0")
    p1 = str(tmp_path / "step_1")
    p2 = str(tmp_path / "step_2")
    ts.Snapshot.take(p0, state, record_digests=True)
    ts.Snapshot.take(p1, state, incremental_base=p0)
    ts.Snapshot.take(p2, state, incremental_base=p1)

    entry = ts.Snapshot(p2).get_manifest()["0/m/w"]
    assert entry.location == "../step_0/0/m/w"  # not ../step_1/...

    dest = {"m": ts.PyTreeState({"w": jnp.zeros_like(w)})}
    ts.Snapshot(p2).restore(dest)
    assert_tree_eq(dest["m"].tree, {"w": w})


def test_checksum_inheritance_detects_base_corruption(tmp_path):
    """Refs inherit the base's CRC entries: corrupting the base blob makes
    the *incremental* snapshot's restore fail loudly."""
    from torchsnapshot_tpu.integrity import ChecksumError

    w = jnp.arange(64, dtype=jnp.float32)
    state = {"m": ts.PyTreeState({"w": w})}
    p0, p1 = _take_pair(
        tmp_path, state, {"m": ts.PyTreeState({"w": w})}
    )
    blob = os.path.join(p0, "0", "m", "w")
    with open(blob, "r+b") as f:
        f.seek(8)
        f.write(b"\xff\xff\xff\xff")

    dest = {"m": ts.PyTreeState({"w": jnp.zeros_like(w)})}
    with pytest.raises(ChecksumError):
        ts.Snapshot(p1).restore(dest)


def test_digest_recorded_on_full_take(tmp_path):
    p0 = str(tmp_path / "s")
    ts.Snapshot.take(
        p0, {"m": ts.PyTreeState({"w": jnp.ones(8)})}, record_digests=True
    )
    entry = ts.Snapshot(p0).get_manifest()["0/m/w"]
    assert entry.digest and entry.digest.startswith("mlh64:")


def test_no_digests_without_flag(tmp_path):
    p0 = str(tmp_path / "s")
    ts.Snapshot.take(p0, {"m": ts.PyTreeState({"w": jnp.ones(8)})})
    entry = ts.Snapshot(p0).get_manifest()["0/m/w"]
    assert entry.digest is None


def test_base_without_digests_falls_back_to_full(tmp_path):
    w = jnp.arange(16, dtype=jnp.float32)
    state = {"m": ts.PyTreeState({"w": w})}
    p0 = str(tmp_path / "s0")
    ts.Snapshot.take(p0, state)  # no digests recorded
    p1 = str(tmp_path / "s1")
    ts.Snapshot.take(p1, state, incremental_base=p0)
    entry = ts.Snapshot(p1).get_manifest()["0/m/w"]
    assert not entry.location.startswith("../")  # full write
    dest = {"m": ts.PyTreeState({"w": jnp.zeros_like(w)})}
    ts.Snapshot(p1).restore(dest)
    assert_tree_eq(dest["m"].tree, {"w": w})


def test_missing_base_falls_back_to_full(tmp_path):
    w = jnp.arange(16, dtype=jnp.float32)
    state = {"m": ts.PyTreeState({"w": w})}
    p1 = str(tmp_path / "s1")
    ts.Snapshot.take(
        p1, state, incremental_base=str(tmp_path / "never_existed")
    )
    dest = {"m": ts.PyTreeState({"w": jnp.zeros_like(w)})}
    ts.Snapshot(p1).restore(dest)
    assert_tree_eq(dest["m"].tree, {"w": w})


def test_dtype_change_forces_rewrite(tmp_path):
    """Same byte pattern, different dtype: must not ref."""
    a32 = jnp.asarray(np.zeros(16, np.float32))
    ai32 = jnp.asarray(np.zeros(16, np.int32))
    p0, p1 = _take_pair(
        tmp_path,
        {"m": ts.PyTreeState({"x": a32})},
        {"m": ts.PyTreeState({"x": ai32})},
    )
    entry = ts.Snapshot(p1).get_manifest()["0/m/x"]
    assert not entry.location.startswith("../")


def test_chunk_knob_change_forces_rewrite(tmp_path):
    base = np.arange(32 * 8, dtype=np.float32).reshape(32, 8)
    state = {"m": ts.PyTreeState({"big": jnp.asarray(base)})}
    p0 = str(tmp_path / "s0")
    with override_max_chunk_size_bytes(256):
        ts.Snapshot.take(p0, state, record_digests=True)
    p1 = str(tmp_path / "s1")
    with override_max_chunk_size_bytes(512):  # different chunk boundaries
        ts.Snapshot.take(p1, state, incremental_base=p0)
        entry = ts.Snapshot(p1).get_manifest()["0/m/big"]
        for chunk in entry.chunks:
            assert not chunk.array.location.startswith("../")
    dest = {"m": ts.PyTreeState({"big": jnp.zeros((32, 8), jnp.float32)})}
    ts.Snapshot(p1).restore(dest)
    np.testing.assert_array_equal(np.asarray(dest["m"].tree["big"]), base)


def test_incremental_async_take(tmp_path):
    w = jnp.arange(64, dtype=jnp.float32)
    b = jnp.ones((8,), jnp.float32)
    state0 = {"m": ts.PyTreeState({"w": w, "b": b})}
    p0 = str(tmp_path / "s0")
    ts.Snapshot.take(p0, state0, record_digests=True)

    state1 = {"m": ts.PyTreeState({"w": w, "b": b * 3})}
    pending = ts.Snapshot.async_take(
        str(tmp_path / "s1"), state1, incremental_base=p0
    )
    snap = pending.wait()
    entry = snap.get_manifest()["0/m/w"]
    assert entry.location == "../s0/0/m/w"
    dest = {"m": ts.PyTreeState({"w": jnp.zeros_like(w), "b": jnp.zeros_like(b)})}
    snap.restore(dest)
    assert_tree_eq(dest["m"].tree, {"w": w, "b": b * 3})


def test_read_object_through_ref(tmp_path):
    w = jnp.arange(16, dtype=jnp.float32)
    state = {"m": ts.PyTreeState({"w": w})}
    p0, p1 = _take_pair(tmp_path, state, {"m": ts.PyTreeState({"w": w})})
    out = ts.Snapshot(p1).read_object("0/m/w")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(w))


def test_np_host_leaves_incremental(tmp_path):
    """Host numpy leaves participate via the bit-identical host digest."""
    w = np.arange(24, dtype=np.float32)
    state0 = {"m": ts.StateDict(w=w.copy(), v=np.zeros(4, np.int32))}
    state1 = {"m": ts.StateDict(w=w.copy(), v=np.ones(4, np.int32))}
    p0, p1 = _take_pair(tmp_path, state0, state1)
    manifest = ts.Snapshot(p1).get_manifest()
    assert manifest["0/m/w"].location.startswith("../")
    assert not manifest["0/m/v"].location.startswith("../")
    dest = {"m": ts.StateDict(w=np.zeros_like(w), v=np.zeros(4, np.int32))}
    ts.Snapshot(p1).restore(dest)
    np.testing.assert_array_equal(dest["m"]["w"], w)
    np.testing.assert_array_equal(dest["m"]["v"], np.ones(4, np.int32))


# ---------------------------------------------------------------------------
# distributed
# ---------------------------------------------------------------------------

from torchsnapshot_tpu.test_utils import multiprocess_test  # noqa: E402


@multiprocess_test(nproc=2)
def test_distributed_incremental_replicated_and_per_rank(pg) -> None:
    """World-2 incremental take: replicated refs agree across ranks (the
    consolidation assert would trip otherwise), changed per-rank state
    rewrites, unchanged replicated state refs the base."""
    import shutil
    import tempfile

    import jax.numpy as jnp

    root = os.path.join(tempfile.gettempdir(), "dist-incr-test")
    if pg.rank == 0:
        shutil.rmtree(root, ignore_errors=True)
    p0 = os.path.join(root, "step_0")
    p1 = os.path.join(root, "step_1")

    w = jnp.full((64, 8), 7.5, jnp.float32)
    state0 = {
        "params": ts.PyTreeState({"w": w, "b": jnp.arange(8.0)}),
        "progress": ts.StateDict(rank_steps=100 + pg.rank),
    }
    ts.Snapshot.take(p0, state0, pg=pg, replicated=["params/**"],
                     record_digests=True)

    state1 = {
        "params": ts.PyTreeState({"w": w, "b": jnp.arange(8.0) + 1}),
        "progress": ts.StateDict(rank_steps=200 + pg.rank),
    }
    snap = ts.Snapshot.take(
        p1, state1, pg=pg, replicated=["params/**"], incremental_base=p0
    )
    md = snap.metadata
    # Unchanged replicated leaf refs the base; changed one was rewritten.
    assert md.manifest["0/params/w"].location == "../step_0/replicated/params/w"
    assert md.manifest["0/params/b"].location == "replicated/params/b"
    assert not os.path.exists(os.path.join(p1, "replicated", "params", "w"))

    fresh = {
        "params": ts.PyTreeState({"w": jnp.zeros((64, 8)), "b": jnp.zeros(8)}),
        "progress": ts.StateDict(rank_steps=-1),
    }
    ts.Snapshot(p1, pg=pg).restore(fresh)
    assert float(fresh["params"].tree["w"][0, 0]) == 7.5
    assert float(fresh["params"].tree["b"][5]) == 6.0
    assert fresh["progress"]["rank_steps"] == 200 + pg.rank


def test_incremental_chunk_knob_refines_skip_unit(tmp_path):
    """Digest-enabled takes chunk at the incremental-chunk knob, so a
    sparse row update skips the untouched fine chunks even when the array
    is below the plain chunk threshold."""
    from torchsnapshot_tpu.knobs import override_incremental_chunk_size_bytes

    base = np.random.default_rng(0).standard_normal((256, 16)).astype(np.float32)
    changed = base.copy()
    changed[100] += 1.0
    with override_incremental_chunk_size_bytes(1024):  # 16 rows/chunk
        p0, p1 = _take_pair(
            tmp_path,
            {"m": ts.PyTreeState({"t": jnp.asarray(base)})},
            {"m": ts.PyTreeState({"t": jnp.asarray(changed)})},
        )
        entry = ts.Snapshot(p1).get_manifest()["0/m/t"]
        assert isinstance(entry, ChunkedArrayEntry)
        news = [c for c in entry.chunks if not c.array.location.startswith("../")]
        refs = [c for c in entry.chunks if c.array.location.startswith("../")]
        assert len(news) == 1 and len(refs) == len(entry.chunks) - 1
    dest = {"m": ts.PyTreeState({"t": jnp.zeros((256, 16), jnp.float32)})}
    ts.Snapshot(p1).restore(dest)
    np.testing.assert_array_equal(np.asarray(dest["m"].tree["t"]), changed)


def test_plain_take_chunking_unaffected_by_incremental_knob(tmp_path):
    """Without digests, the incremental-chunk knob must not change blob
    layout (a plain take of a 1 MiB array stays one blob)."""
    from torchsnapshot_tpu.knobs import override_incremental_chunk_size_bytes

    arr = jnp.asarray(np.zeros((256, 16), np.float32))
    with override_incremental_chunk_size_bytes(1024):
        p = str(tmp_path / "s")
        ts.Snapshot.take(p, {"m": ts.PyTreeState({"t": arr})})
        entry = ts.Snapshot(p).get_manifest()["0/m/t"]
        assert isinstance(entry, ArrayEntry)  # not chunked


def test_memory_scheme_refuses_refs(tmp_path):
    """memory:// stores are flat per-name namespaces: refs must be
    refused (full take) rather than written and then unrestorable."""
    assert relative_ref_prefix("memory://s1", "memory://s0") is None

    w = jnp.arange(16, dtype=jnp.float32)
    state = {"m": ts.PyTreeState({"w": w})}
    ts.Snapshot.take("memory://incr-s0", state, record_digests=True)
    ts.Snapshot.take(
        "memory://incr-s1", state, incremental_base="memory://incr-s0"
    )
    entry = ts.Snapshot("memory://incr-s1").get_manifest()["0/m/w"]
    assert not entry.location.startswith("../")
    dest = {"m": ts.PyTreeState({"w": jnp.zeros_like(w)})}
    ts.Snapshot("memory://incr-s1").restore(dest)
    assert_tree_eq(dest["m"].tree, {"w": w})


def test_cross_bucket_refuses_refs():
    assert relative_ref_prefix("s3://b1/r/s1", "s3://b2/r/s0") is None
    assert relative_ref_prefix("gs://b/x/s1", "gs://b/y/s0") == "../../y/s0"


@multiprocess_test(nproc=2)
def test_distributed_degraded_base_agrees(pg) -> None:
    """If only one rank can read the base, no rank may emit refs for
    replicated leaves — the take degrades to full on every rank instead
    of tripping the consolidation assert."""
    import shutil
    import tempfile

    import jax.numpy as jnp

    root = os.path.join(tempfile.gettempdir(), "dist-incr-degraded")
    if pg.rank == 0:
        shutil.rmtree(root, ignore_errors=True)
    p0 = os.path.join(root, "step_0")
    p1 = os.path.join(root, "step_1")
    w = jnp.full((16, 4), 2.0, jnp.float32)
    state = {"params": ts.PyTreeState({"w": w})}
    ts.Snapshot.take(p0, state, pg=pg, replicated=["params/**"],
                     record_digests=True)

    # Rank 1 is handed a nonexistent base: its build() falls back.
    base = p0 if pg.rank == 0 else os.path.join(root, "no_such_step")
    snap = ts.Snapshot.take(
        p1, state, pg=pg, replicated=["params/**"], incremental_base=base
    )
    entry = snap.metadata.manifest["0/params/w"]
    assert not entry.location.startswith("../")  # degraded to full everywhere

    fresh = {"params": ts.PyTreeState({"w": jnp.zeros((16, 4))})}
    ts.Snapshot(p1, pg=pg).restore(fresh)
    assert float(fresh["params"].tree["w"][3, 3]) == 2.0


@multiprocess_test(nproc=2)
def test_replication_promotion_forces_rewrite(pg) -> None:
    """A leaf saved per-rank at the base and replicated now must rewrite
    (per-rank base locations would diverge across ranks)."""
    import shutil
    import tempfile

    import jax.numpy as jnp

    root = os.path.join(tempfile.gettempdir(), "dist-incr-promote")
    if pg.rank == 0:
        shutil.rmtree(root, ignore_errors=True)
    p0 = os.path.join(root, "step_0")
    p1 = os.path.join(root, "step_1")
    w = jnp.full((8,), 4.0, jnp.float32)
    state = {"params": ts.PyTreeState({"w": w})}
    ts.Snapshot.take(p0, state, pg=pg, record_digests=True)  # per-rank
    snap = ts.Snapshot.take(
        p1, state, pg=pg, replicated=["params/**"], incremental_base=p0
    )
    entry = snap.metadata.manifest["0/params/w"]
    assert entry.replicated and not entry.location.startswith("../")


# ---------------------------------------------------------------------------
# CheckpointManager integration: chained saves, pinning, cascade GC
# ---------------------------------------------------------------------------


def _mgr_state(v_w, v_t):
    return {
        "m": ts.PyTreeState(
            {
                "w": jnp.full((64,), float(v_w), jnp.float32),
                "t": jnp.full((32,), float(v_t), jnp.float32),
            }
        )
    }


def test_manager_incremental_chain_and_restore(tmp_path):
    root = str(tmp_path / "ckpts")
    mgr = ts.CheckpointManager(root, incremental=True)
    mgr.save(0, _mgr_state(1, 1))
    mgr.save(1, _mgr_state(1, 2))  # only t changes
    mgr.save(2, _mgr_state(1, 3))

    man2 = ts.Snapshot(mgr.step_path(2)).get_manifest()
    assert man2["0/m/w"].location == "../step_0000000000/0/m/w"
    assert not man2["0/m/t"].location.startswith("../")

    dest = _mgr_state(0, 0)
    assert mgr.restore_latest(dest) == 2
    assert float(dest["m"].tree["w"][0]) == 1.0
    assert float(dest["m"].tree["t"][0]) == 3.0


def test_manager_retention_pins_referenced_base(tmp_path):
    root = str(tmp_path / "ckpts")
    mgr = ts.CheckpointManager(root, keep_last_n=2, incremental=True)
    for step in range(4):
        mgr.save(step, _mgr_state(1, step))  # w never changes -> refs step 0

    index = json.loads(
        (tmp_path / "ckpts" / ".manager_index").read_text()
    )
    assert index["steps"] == [2, 3]
    assert index["pinned"] == [0]  # w blob origin, still referenced
    # Pinned step's blobs survive; its commit marker too (blobs readable).
    assert os.path.exists(os.path.join(mgr.step_path(0), "0", "m", "w"))
    # Step 1 was dropped and not referenced (its only novel blob was t).
    assert not os.path.exists(os.path.join(mgr.step_path(1), "0", "m", "t"))

    # Restore still works through the pin.
    dest = _mgr_state(0, 0)
    assert mgr.restore_latest(dest) == 3
    assert float(dest["m"].tree["w"][0]) == 1.0
    assert float(dest["m"].tree["t"][0]) == 3.0


def test_manager_cascade_deletes_unreferenced_pin(tmp_path):
    root = str(tmp_path / "ckpts")
    mgr = ts.CheckpointManager(root, keep_last_n=2, incremental=True)
    for step in range(4):
        mgr.save(step, _mgr_state(1, step))  # pins step 0 (w origin)
    assert os.path.exists(os.path.join(mgr.step_path(0), "0", "m", "w"))

    # Change w: new steps stop referencing step 0; once no retained step
    # refs it, the pin is released and its blobs deleted.
    mgr.save(4, _mgr_state(2, 4))
    mgr.save(5, _mgr_state(2, 5))
    index = json.loads((tmp_path / "ckpts" / ".manager_index").read_text())
    assert index["steps"] == [4, 5]
    assert index.get("pinned", []) == [4] or index.get("pinned", []) == []
    assert not os.path.exists(os.path.join(mgr.step_path(0), "0", "m", "w"))

    dest = _mgr_state(0, 0)
    assert mgr.restore_latest(dest) == 5
    assert float(dest["m"].tree["w"][0]) == 2.0
    assert float(dest["m"].tree["t"][0]) == 5.0


def test_manager_async_incremental(tmp_path):
    root = str(tmp_path / "ckpts")
    mgr = ts.CheckpointManager(root, incremental=True)
    mgr.async_save(0, _mgr_state(1, 1)).wait()
    pending = mgr.async_save(1, _mgr_state(1, 2))
    pending.wait()
    man1 = ts.Snapshot(mgr.step_path(1)).get_manifest()
    assert man1["0/m/w"].location.startswith("../step_0000000000")
    index = json.loads((tmp_path / "ckpts" / ".manager_index").read_text())
    assert index["refs"]["1"] == [0]


def test_manager_old_index_format_still_reads(tmp_path):
    root = tmp_path / "ckpts"
    mgr = ts.CheckpointManager(str(root))
    mgr.save(0, _mgr_state(1, 1))
    # Rewrite the index in the pre-incremental format.
    (root / ".manager_index").write_text(json.dumps({"steps": [0]}))
    (root / ".manager_index.backup").write_text(json.dumps({"steps": [0]}))
    assert mgr.all_steps() == [0]
    mgr.save(1, _mgr_state(1, 2))
    assert mgr.all_steps() == [0, 1]


def test_manager_non_incremental_unaffected(tmp_path):
    root = str(tmp_path / "ckpts")
    mgr = ts.CheckpointManager(root)  # incremental off
    mgr.save(0, _mgr_state(1, 1))
    mgr.save(1, _mgr_state(1, 2))
    man1 = ts.Snapshot(mgr.step_path(1)).get_manifest()
    assert not man1["0/m/w"].location.startswith("../")
    index = json.loads((tmp_path / "ckpts" / ".manager_index").read_text())
    assert "refs" not in index and "pinned" not in index


# ---------------------------------------------------------------------------
# skip decisions behind the return of an async_take
# ---------------------------------------------------------------------------

from torchsnapshot_tpu import incremental, knobs  # noqa: E402
from torchsnapshot_tpu.manifest import entry_to_yaml_obj  # noqa: E402
from torchsnapshot_tpu.ops import device_digest  # noqa: E402
from torchsnapshot_tpu.scheduler import DecidedIOWork, DeferredIOWork  # noqa: E402
from torchsnapshot_tpu.telemetry import names, trace  # noqa: E402

PATHS = {"behind": True, "waiting": False}


def _behind_state(step):
    """Every kind of leaf a take plans, changed in part from take to take:
    a chunked leaf (one row-chunk moves at take 2), a small dense leaf, a
    sharded leaf with replicas (one shard moves at take 3) whose shards
    subdivide, a frozen one, host numpy, an object and a primitive."""
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("x", "y"))
    big = jnp.arange(64 * 32, dtype=jnp.float32).reshape(64, 32)
    sharded = jnp.arange(32 * 16, dtype=jnp.float32).reshape(32, 16)
    if step >= 2:
        big = big.at[5].add(1.0)
    if step >= 3:
        sharded = sharded.at[30].add(1.0)
    return {
        "m": ts.PyTreeState({
            "big": big,
            "small": jnp.arange(8.0) + step,
            "frozen": jnp.ones((16, 4), jnp.bfloat16),
            "sharded": jax.device_put(sharded, NamedSharding(mesh, P("x", None))),
        }),
        "host": ts.StateDict(v=np.arange(10) * (2 if step >= 3 else 1), note={"step": step},
                             n=step),
    }


def _files(root):
    out = {}
    for dirpath, _, files in os.walk(root):
        for f in files:
            full = os.path.join(dirpath, f)
            with open(full, "rb") as fh:
                out[os.path.relpath(full, root)] = fh.read()
    return out


def _three_takes(root, behind, monkeypatch, on_return=None):
    """Three incremental async_takes, each against the one before, the
    first recording digests with no base. Returns each take's manifest
    and files, the restore of the last, and each take's flag."""
    monkeypatch.setattr(incremental, "room_for_clone", lambda flattened: behind)
    rec = trace.get_recorder()
    out, flags, base = [], [], None
    with knobs.override_incremental_chunk_size_bytes(1024):
        for step in (1, 2, 3):
            path = os.path.join(root, f"step_{step}")
            mark = rec.mark()
            app = _behind_state(step)
            pending = ts.Snapshot.async_take(path, app, incremental_base=base,
                                             record_digests=True)
            if on_return is not None:
                on_return(app)
            pending.wait()
            flags += [e["args"][names.ARG_DECIDED_BEHIND_RETURN]
                      for e in rec.events_since(mark)
                      if e.get("ph") == "X" and e["name"] == names.SPAN_TAKE_PLAN
                      and names.ARG_DECIDED_BEHIND_RETURN in e["args"]]
            manifest = ts.Snapshot(path).get_manifest()
            out.append(({k: entry_to_yaml_obj(v) for k, v in manifest.items()}, _files(path)))
            base = path
    fresh = _behind_state(0)
    for leaf in ("big", "small", "frozen", "sharded"):
        old = fresh["m"].tree[leaf]
        fresh["m"].tree[leaf] = jax.device_put(jnp.zeros(old.shape, old.dtype), old.sharding)
    ts.Snapshot(base).restore(fresh)
    restored = {k: np.asarray(v).tobytes() for k, v in fresh["m"].tree.items()}
    restored.update({k: repr(fresh["host"][k]) for k in ("v", "note", "n")})
    return out, restored, flags


def _want():
    state = _behind_state(3)
    want = {k: np.asarray(v).tobytes() for k, v in state["m"].tree.items()}
    want.update({k: repr(state["host"][k]) for k in ("v", "note", "n")})
    return want


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")
def test_both_paths_commit_the_same_bytes(tmp_path, monkeypatch):
    """Every entry, reference, digest, written chunk and checksum of three
    takes, byte for byte, whichever thread made the skip decisions; both
    restore the last take's state."""
    behind, restored_b, flags_b = _three_takes(str(tmp_path / "b"), True, monkeypatch)
    waiting, restored_w, flags_w = _three_takes(str(tmp_path / "w"), False, monkeypatch)
    assert (flags_b, flags_w) == ([1, 1, 1], [0, 0, 0])
    for step, ((man_b, files_b), (man_w, files_w)) in enumerate(zip(behind, waiting), 1):
        assert man_b == man_w, step
        assert files_b == files_w, step
    last = behind[2][0]
    refs = [k for k, e in last.items() if "../step_" in json.dumps(e)]
    assert {"0/m/big", "0/m/frozen", "0/m/sharded"} <= set(refs)
    assert not last["0/m/small"]["location"].startswith("../")
    assert restored_b == restored_w == _want()


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")
@pytest.mark.parametrize("path", PATHS)
def test_deleting_the_live_state_after_the_return_changes_nothing(tmp_path, monkeypatch, path):
    """The commit thread reads clones: the live arrays deleted (as a
    donating step frees them) and the host leaf overwritten right after
    async_take returns."""

    def clobber(app):
        for leaf in app["m"].tree.values():
            leaf.delete()
        app["host"]["v"][:] = -1

    _, restored, flags = _three_takes(str(tmp_path), PATHS[path], monkeypatch, clobber)
    assert flags == [int(PATHS[path])] * 3
    assert restored == _want()


def _one_take(tmp_path, monkeypatch, case):
    """One take of a small state; returns its take:plan spans, the digest
    dispatches, the room probes and the async work's type."""
    handed, probed = [], []
    real_digest, real_room = device_digest.digest_many_async, incremental.room_for_clone
    monkeypatch.setattr(device_digest, "digest_many_async",
                        lambda specs: handed.append(len(specs)) or real_digest(specs))
    monkeypatch.setattr(incremental, "room_for_clone",
                        lambda flattened: probed.append(1) or (case != "no_room"
                                                               and real_room(flattened)))
    app = {"m": ts.PyTreeState({"w": jnp.arange(32.0), "b": jnp.ones(4)})}
    base = str(tmp_path / "base")
    if case in ("no_room", "blocking"):
        ts.Snapshot.take(base, app, record_digests=True)
        handed.clear(), probed.clear()
    kwargs = {} if case == "plain" else {
        "record_digests": True, "incremental_base": None if case == "first" else base}
    rec = trace.get_recorder()
    mark = rec.mark()
    path = str(tmp_path / "take")
    work = None
    if case == "blocking":
        ts.Snapshot.take(path, app, **kwargs)
    else:
        pending = ts.Snapshot.async_take(path, app, **kwargs)
        pending.wait()
        work = type(pending._op.pending_io_work)
    events = [e for e in rec.events_since(mark) if e.get("ph") == "X"]
    return events, handed, probed, work


@pytest.mark.parametrize("case,flag,work", [
    ("plain", None, DeferredIOWork),
    ("first", 1, DecidedIOWork),
    ("no_room", 0, DeferredIOWork),
    ("blocking", 0, None),
])
def test_which_path_a_take_runs(tmp_path, monkeypatch, case, flag, work):
    """A plain async_take never asks for room, builds no deferred decision
    and dispatches no digest; a job's first incremental take (digests, no
    base) decides behind the return; without room, and in a blocking take,
    the caller waits for its digests, on its own thread."""
    events, handed, probed, got = _one_take(tmp_path, monkeypatch, case)
    plans = [e for e in events if e["name"] == names.SPAN_TAKE_PLAN]
    assert [e["args"].get(names.ARG_DECIDED_BEHIND_RETURN) for e in plans][:1] == [flag]
    assert got is work
    if case == "plain":
        assert handed == probed == [] and len(plans) == 1
        assert "bytes_written" not in plans[0]["args"]
        return
    # One digest program over both leaves.
    assert handed == [2] and probed == ([] if case == "blocking" else [1])
    (wait,) = [e for e in events if e["name"] == names.SPAN_INCREMENTAL_DIGEST_WAIT]
    assert (wait["tid"] == plans[0]["tid"]) == (flag == 0)
    assert len(plans) == 1 + flag


class _Device:
    def __init__(self, stats):
        self.stats = stats

    def memory_stats(self):
        return self.stats


@pytest.mark.parametrize("need,free,room", [
    (1 << 30, (1 << 30) + (1 << 26), True),  # a sixteenth of the clone, exactly
    (1 << 30, (1 << 30) + (1 << 26) - 1, False),
    (1 << 20, (1 << 20) + (64 << 20), True),  # 64 MiB at the least
    (1 << 20, (1 << 20) + (64 << 20) - 1, False),
    (1 << 30, None, True),  # a device that keeps no account (the CPU backend)
])
def test_the_room_probe_counts_the_clone_and_its_margin(monkeypatch, need, free, room):
    limit = 16 << 30
    device = _Device(None if free is None else
                     {"bytes_limit": limit, "bytes_in_use": limit - free})
    monkeypatch.setattr(incremental, "clone_bytes_by_device", lambda flattened: {device: need})
    assert incremental.room_for_clone({}) is room


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")
def test_the_clone_is_counted_where_it_lands():
    """A dense leaf's bytes on its device, a replicated one's on each, a
    sharded one's replica-0 shards alone; host leaves not at all."""
    devices = jax.devices()[:8]
    mesh = Mesh(np.array(devices).reshape(4, 2), ("x", "y"))
    flattened = {
        "a": jax.device_put(jnp.zeros(256, jnp.float32), devices[3]),
        "r": jax.device_put(jnp.zeros(64, jnp.float32), NamedSharding(mesh, P())),
        "s": jax.device_put(jnp.zeros((8, 16), jnp.float32), NamedSharding(mesh, P("x", None))),
        "h": np.zeros(1000),
    }
    need = incremental.clone_bytes_by_device(flattened)
    want = {d: 256 for d in devices}
    want[devices[3]] += 1024
    for d in mesh.devices[:, 0]:
        want[d] += 2 * 16 * 4
    assert need == want


@multiprocess_test(nproc=2)
def test_distributed_incremental_async_take_waits_on_the_caller(pg) -> None:
    """World 2: the manifest gather and the agreement are collectives on
    the caller's thread, so the caller waits for its digests as before."""
    import shutil
    import tempfile

    import jax.numpy as jnp

    from torchsnapshot_tpu.telemetry import names, trace

    root = os.path.join(tempfile.gettempdir(), "dist-incr-async-test")
    if pg.rank == 0:
        shutil.rmtree(root, ignore_errors=True)
    p0, p1 = os.path.join(root, "step_0"), os.path.join(root, "step_1")
    w = jnp.full((64, 8), 2.5, jnp.float32)
    ts.Snapshot.take(p0, {"m": ts.PyTreeState({"w": w})}, pg=pg, record_digests=True)
    rec = trace.get_recorder()
    mark = rec.mark()
    state = {"m": ts.PyTreeState({"w": w, "b": jnp.arange(4.0) + pg.rank})}
    snap = ts.Snapshot.async_take(p1, state, pg=pg, incremental_base=p0).wait()
    (plan,) = [e for e in rec.events_since(mark)
               if e.get("ph") == "X" and e["name"] == names.SPAN_TAKE_PLAN]
    assert plan["args"][names.ARG_DECIDED_BEHIND_RETURN] == 0
    assert snap.metadata.manifest[f"{pg.rank}/m/w"].location == f"../step_0/{pg.rank}/m/w"


def test_the_commit_thread_compiles_no_digest_program(tmp_path, monkeypatch):
    """A job's first take compiles its digest program on the caller,
    before the clone exists, and dispatches nothing there; the commit
    thread's dispatch runs what was compiled, and so does the next take's."""
    import threading

    monkeypatch.setattr(incremental, "room_for_clone", lambda flattened: True)
    compiled = []

    def listener(name, *args, **kwargs):
        if name == "/jax/core/compile/backend_compile_duration":
            compiled.append(threading.current_thread().name)

    state = {"m": ts.PyTreeState({"odd": jnp.arange(3 * 41 * 7.0).reshape(41, 21),
                                  "b": jnp.ones(13)})}
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        base = str(tmp_path / "s1")
        ts.Snapshot.async_take(base, state, record_digests=True).wait()
        first = list(compiled)
        ts.Snapshot.async_take(str(tmp_path / "s2"), state, incremental_base=base).wait()
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert "MainThread" in first and "snapshot-commit" not in compiled


def test_a_source_captured_on_the_host_is_written_without_its_digest(tmp_path, monkeypatch):
    """Behind the return the digests read the clones. A leaf whose clone
    failed and fell back to a host copy has none: it is written in full
    with no digest, and the others are decided as ever."""
    from torchsnapshot_tpu import io_preparer

    monkeypatch.setattr(incremental, "room_for_clone", lambda flattened: True)
    w, b = jnp.arange(48.0).reshape(6, 8), jnp.ones(5)
    base = str(tmp_path / "s0")
    ts.Snapshot.take(base, {"m": ts.PyTreeState({"w": w, "b": b})}, record_digests=True)

    def no_group():
        def program(members):
            raise RuntimeError("no room for the group")
        return program

    real = io_preparer._capture_clone_jit()

    def per_leaf(x):
        if x.shape == w.shape:
            raise RuntimeError("no room for this leaf")
        return real(x)

    monkeypatch.setattr(io_preparer, "_capture_clone_group_jit", no_group)
    monkeypatch.setattr(io_preparer, "_capture_clone_jit", lambda: per_leaf)
    live = {"m": ts.PyTreeState({"w": w + 0, "b": b + 0})}
    snap = ts.Snapshot.async_take(str(tmp_path / "s1"), live, incremental_base=base).wait()
    manifest = snap.get_manifest()
    assert manifest["0/m/b"].location == "../s0/0/m/b"
    assert manifest["0/m/w"].location == "0/m/w" and manifest["0/m/w"].digest is None
    fresh = {"m": ts.PyTreeState({"w": jnp.zeros_like(w), "b": jnp.zeros_like(b)})}
    ts.Snapshot(str(tmp_path / "s1")).restore(fresh)
    assert_tree_eq(fresh["m"].tree, {"w": w, "b": b})


# ---------------------------------------------------------------------------
# chunked leaves at staging: a written subset slices, a whole set tiles
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8])
def test_the_digests_of_row_arrays_are_the_digests_over_the_leafs_rows(dtype):
    """The digest of `x[a:b]` as an array of its own is the digest of those
    rows of `x`: a chunk digests the same however its bytes are held."""
    x = (jnp.arange(40 * 24) % 251).astype(dtype).reshape(40, 24)
    ranges = ((0, 16), (16, 32), (32, 40))
    over_rows = device_digest.materialize_many(device_digest.digest_many_async([(x, ranges)]))
    over_arrays = device_digest.materialize_many(
        device_digest.digest_many_async([(x[a:b], None) for a, b in ranges]))
    assert over_arrays.tolist() == over_rows.tolist()
    host = np.asarray(x)
    assert [tuple(r) for r in over_rows.tolist()] == [
        device_digest.digest_host(host[a:b]) for a, b in ranges]


def _partly_changed_pair(root, behind, monkeypatch, rows=(19,)):
    """A full take and an incremental one of a dense state whose chunked
    leaf changed in `rows` between them; returns the second take's spans
    by name, its manifest as YAML objects, and the restore of it."""
    monkeypatch.setattr(incremental, "room_for_clone", lambda flattened: behind)
    w = jnp.arange(64 * 32, dtype=jnp.float32).reshape(64, 32)
    b = jnp.arange(5, dtype=jnp.int32)
    changed = w
    for row in rows:
        changed = changed.at[row].add(1.0)
    want = {"w": np.asarray(changed).tobytes(), "b": np.arange(5, dtype=np.int32).tobytes()}
    rec = trace.get_recorder()
    with knobs.override_incremental_chunk_size_bytes(1024):  # 8 chunks of 8 rows
        base = os.path.join(root, "s0")
        ts.Snapshot.async_take(base, {"m": ts.PyTreeState({"w": w, "b": b})},
                               record_digests=True).wait()
        mark = rec.mark()
        live = {"m": ts.PyTreeState({"w": changed, "b": b + 0})}
        pending = ts.Snapshot.async_take(os.path.join(root, "s1"), live, incremental_base=base)
        for leaf in live["m"].tree.values():
            leaf.delete()
        snapshot = pending.wait()
    spans = {}
    for e in rec.events_since(mark):
        if e.get("ph") == "X":
            spans.setdefault(e["name"], []).append(e["args"])
    manifest = {k: entry_to_yaml_obj(v) for k, v in snapshot.get_manifest().items()}
    fresh = {"m": ts.PyTreeState({"w": jnp.zeros_like(w), "b": jnp.zeros_like(b)})}
    snapshot.restore(fresh)
    restored = {k: np.asarray(v).tobytes() for k, v in fresh["m"].tree.items()}
    return spans, manifest, restored, want


@pytest.mark.parametrize("path", PATHS)
def test_a_partly_changed_chunked_leaf_writes_its_one_chunk_on_both_paths(
        tmp_path, monkeypatch, path):
    """One chunk of eight changed: on both paths it is the one written, its
    requests do not tile the leaf, and its transfer slices the clone first.
    Behind the return the skipped chunks' requests go before the drain
    waits, so only their clone's bytes are waited for."""
    spans, manifest, restored, want = _partly_changed_pair(
        str(tmp_path), PATHS[path], monkeypatch)
    (plan,) = [a for a in spans[names.SPAN_TAKE_PLAN] if "bytes_written" in a]
    chunk = 8 * 32 * 4
    assert (plan["chunks_written"], plan["bytes_written"]) == (1, chunk)
    assert (plan["chunks_referenced"], plan["bytes_referenced"]) == (8, 7 * chunk + 20)
    w = manifest["0/m/w"]
    locations = [c["array"]["location"] for c in w["chunks"]]
    assert locations[2] == "0/m/w_16"
    assert all(loc.startswith("../s0/") for i, loc in enumerate(locations) if i != 2)
    assert manifest["0/m/b"]["location"] == "../s0/0/m/b"
    (d2h,) = spans[names.SPAN_STAGE_D2H]
    assert (d2h["bytes"], d2h[names.ARG_SLICED_BYTES]) == (chunk, chunk)
    assert restored == want


@pytest.mark.parametrize("path", PATHS)
def test_a_wholly_changed_chunked_leaf_leaves_the_device_in_one_transfer(
        tmp_path, monkeypatch, path):
    """Every chunk changed: on both paths the written requests tile the
    leaf, and one transfer of its clone, slicing nothing, writes them all."""
    spans, manifest, restored, want = _partly_changed_pair(
        str(tmp_path), PATHS[path], monkeypatch, rows=range(0, 64, 8))
    (plan,) = [a for a in spans[names.SPAN_TAKE_PLAN] if "bytes_written" in a]
    assert (plan["chunks_written"], plan["chunks_referenced"]) == (8, 1)
    assert [c["array"]["location"] for c in manifest["0/m/w"]["chunks"]] == [
        f"0/m/w_{8 * i}" for i in range(8)]
    (d2h,) = spans[names.SPAN_STAGE_D2H]
    assert (d2h["bytes"], d2h[names.ARG_SLICED_BYTES]) == (64 * 32 * 4, 0)
    assert restored == want


@pytest.mark.parametrize("rows", [(19,), tuple(range(0, 64, 8))], ids=["one", "every"])
def test_both_paths_give_a_changed_chunked_leaf_the_same_manifest(tmp_path, monkeypatch, rows):
    behind = _partly_changed_pair(str(tmp_path / "b"), True, monkeypatch, rows)
    waiting = _partly_changed_pair(str(tmp_path / "w"), False, monkeypatch, rows)
    assert behind[1] == waiting[1]
    assert behind[2] == waiting[2] == behind[3]


def test_the_commit_thread_compiles_no_digest_or_clone_program(tmp_path, monkeypatch):
    """The caller of a job's first take compiles the digest program before
    the clone exists; the commit thread dispatches it, stages a tiling
    leaf whole, and compiles nothing; a second take compiles nothing
    anywhere."""
    import threading

    monkeypatch.setattr(incremental, "room_for_clone", lambda flattened: True)
    compiled = []

    def listener(name, *args, **kwargs):
        if name == "/jax/core/compile/backend_compile_duration":
            compiled.append(threading.current_thread().name)

    state = {"m": ts.PyTreeState({"odd": jnp.arange(3 * 41 * 7.0).reshape(41, 21),
                                  "b": jnp.ones(13)})}
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        with knobs.override_incremental_chunk_size_bytes(21 * 4 * 8):  # 6 chunks
            base = str(tmp_path / "s1")
            ts.Snapshot.async_take(base, state, record_digests=True).wait()
            first = list(compiled)
            changed = {"m": ts.PyTreeState({k: v + 1 for k, v in state["m"].tree.items()})}
            jax.block_until_ready(changed["m"].tree)
            compiled.clear()
            ts.Snapshot.async_take(str(tmp_path / "s2"), changed, incremental_base=base).wait()
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert "MainThread" in first and "snapshot-commit" not in first
    assert compiled == []

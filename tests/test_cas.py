"""Content-addressed chunk store (torchsnapshot_tpu/cas, docs/cas.md).

Covers the ISSUE-12 satellite matrix: digest-key derivation, dedup'd
take/restore round trips bit-identical to the legacy layout, refcounted
GC (shared chunks survive, dead chunks reclaim, grace-window deferral
protects in-flight takes), crash healing (torn journal tail, lost
journal rebuilt from manifests), legacy<->CAS mixed roots, incremental
refs collapsing onto chunks (base-step GC structurally safe), the
legacy-mode orphaned-base retention guard, 2-process replicated-rank
dedup (exactly one stored copy, pinned via a counting plugin), the
whole-store fsck audit, chunk-level mirror shipping, the peer cache's
chunk pool, and the dedup-ineffective doctor rule.
"""

import json
import os
import threading

import numpy as np
import pytest

import torchsnapshot_tpu as ts
from torchsnapshot_tpu import cas, knobs
from torchsnapshot_tpu.cas import (
    CASStore,
    chunk_location,
    chunk_refs,
    digest_key,
    is_chunk_location,
    key_of_location,
    nbytes_of_key,
    parse_key,
)
from torchsnapshot_tpu.integrity import ChecksumError, compute_checksum_entry
from torchsnapshot_tpu.manager import referenced_steps
from torchsnapshot_tpu.storage_plugins.fs import FSStoragePlugin
from torchsnapshot_tpu.test_utils import patch_storage_plugin, run_multiprocess


def _state(n=4096, offset=0.0, extra=None):
    tree = {
        "w": np.arange(n, dtype=np.float32) + offset,
        "frozen": np.ones(n // 4, dtype=np.float32),
    }
    if extra is not None:
        tree.update(extra)
    return {"m": ts.PyTreeState(tree)}


def _chunk_files(root):
    cdir = os.path.join(root, "chunks")
    if not os.path.isdir(cdir):
        return {}
    return {
        name: os.path.getsize(os.path.join(cdir, name))
        for name in os.listdir(cdir)
        if name.startswith("cas-")
    }


def _journal_records(root):
    path = os.path.join(root, "chunks", ".refcounts.jsonl")
    if not os.path.exists(path):
        return []
    return [
        json.loads(line)
        for line in open(path).read().splitlines()
        if line.strip()
    ]


# ---------------------------------------------------------------------------
# digest keys
# ---------------------------------------------------------------------------


def test_digest_key_derivation_and_parse():
    entry = compute_checksum_entry(b"hello chunk store")
    key = digest_key(entry)
    assert key.startswith("cas-")
    assert nbytes_of_key(key) == len(b"hello chunk store")
    alg, nbytes, crc = parse_key(key)
    assert alg == entry[0] and nbytes == entry[2] and crc == entry[1]
    # Same bytes -> same key; different bytes -> different key.
    assert key == digest_key(compute_checksum_entry(b"hello chunk store"))
    assert key != digest_key(compute_checksum_entry(b"hello chunk steve"))
    loc = chunk_location(key)
    assert is_chunk_location(loc) and key_of_location(loc) == key
    # Legacy refs and step-local paths are never chunk locations.
    assert not is_chunk_location("../step_0000000001/0/m/w")
    assert not is_chunk_location("0/m/w")
    assert key_of_location("../chunks/not-a-key") is None


def test_digest_key_paged_entries_fold_pages():
    from torchsnapshot_tpu.integrity import PAGE_SIZE

    big = np.arange(PAGE_SIZE // 4 * 2 + 999, dtype=np.int32).tobytes()
    entry = compute_checksum_entry(big)
    assert len(entry) >= 5  # paged
    key = digest_key(entry)
    assert "-p" in key
    assert nbytes_of_key(key) == len(big)
    # parse_key still exposes the whole-blob CRC (pages are an extension).
    assert parse_key(key)[2] == entry[1]


# ---------------------------------------------------------------------------
# take / restore round trip + dedup
# ---------------------------------------------------------------------------


def test_take_restore_roundtrip_bit_identical_to_legacy(tmp_path):
    legacy_root = str(tmp_path / "legacy")
    cas_root = str(tmp_path / "cas")
    state = _state(offset=3.0)
    ts.Snapshot.take(os.path.join(legacy_root, "step_0000000001"), state)
    with knobs.enable_cas():
        snap = ts.Snapshot.take(
            os.path.join(cas_root, "step_0000000001"), state
        )
    manifest = snap.metadata.manifest
    locs = {
        p: e.location
        for p, e in manifest.items()
        if getattr(e, "location", None)
    }
    assert locs and all(is_chunk_location(l) for l in locs.values())
    # The stored chunk bytes ARE the legacy blob bytes (same
    # serialization, different address): restore is bit-identical by
    # construction, pinned here at the byte level.
    legacy_w = open(
        os.path.join(legacy_root, "step_0000000001", "0", "m", "w"), "rb"
    ).read()
    w_chunk = key_of_location(locs["0/m/w"])
    cas_w = open(os.path.join(cas_root, "chunks", w_chunk), "rb").read()
    assert cas_w == legacy_w
    # And end-to-end through restore (checksum-verified: the rekeyed
    # table's keys match the chunk read paths).
    dest = _state(offset=0.0)
    ts.Snapshot(os.path.join(cas_root, "step_0000000001")).restore(dest)
    np.testing.assert_array_equal(
        dest["m"].tree["w"], state["m"].tree["w"]
    )


def test_second_identical_take_stores_nothing_new(tmp_path):
    root = str(tmp_path / "ckpt")
    state = _state()
    with knobs.enable_cas():
        ts.Snapshot.take(os.path.join(root, "step_0000000001"), state)
        before = _chunk_files(root)
        ts.Snapshot.take(os.path.join(root, "step_0000000002"), state)
        after = _chunk_files(root)
    assert before == after  # dedup across steps: zero new chunk bytes
    # Both manifests reference the same chunks.
    m1 = ts.Snapshot(os.path.join(root, "step_0000000001")).metadata.manifest
    m2 = ts.Snapshot(os.path.join(root, "step_0000000002")).metadata.manifest
    assert chunk_refs(m1) == chunk_refs(m2)


def test_restore_verifies_chunk_bytes(tmp_path):
    root = str(tmp_path / "ckpt")
    state = _state()
    with knobs.enable_cas():
        snap = ts.Snapshot.take(os.path.join(root, "step_0000000001"), state)
    key = key_of_location(snap.metadata.manifest["0/m/w"].location)
    with open(os.path.join(root, "chunks", key), "r+b") as f:
        f.seek(16)
        f.write(b"\xde\xad")
    with pytest.raises(ChecksumError):
        ts.Snapshot(os.path.join(root, "step_0000000001")).restore(_state())


def test_async_take_cas_roundtrip(tmp_path):
    root = str(tmp_path / "ckpt")
    state = _state(offset=11.0)
    with knobs.enable_cas():
        pending = ts.Snapshot.async_take(
            os.path.join(root, "step_0000000001"), state
        )
        snap = pending.wait()
    assert all(
        is_chunk_location(e.location)
        for e in snap.metadata.manifest.values()
        if getattr(e, "location", None)
    )
    dest = _state()
    snap.restore(dest)
    np.testing.assert_array_equal(
        dest["m"].tree["w"], state["m"].tree["w"]
    )


def test_ineligible_scheme_falls_back_to_legacy(tmp_path):
    with knobs.enable_cas():
        snap = ts.Snapshot.take("memory://casless/step_0000000001", _state())
    assert not any(
        is_chunk_location(e.location)
        for e in snap.metadata.manifest.values()
        if getattr(e, "location", None)
    )


# ---------------------------------------------------------------------------
# manager: refcounted GC
# ---------------------------------------------------------------------------


def test_manager_retention_refcount_gc(tmp_path):
    root = str(tmp_path / "ckpt")
    with knobs.enable_cas(), knobs.override_cas_gc_grace_seconds(0):
        mgr = ts.CheckpointManager(root, keep_last_n=2)
        for i in range(5):
            mgr.save(i, _state(offset=float(i)))
        files = _chunk_files(root)
        # Two live 'w' variants (steps 3, 4) + ONE shared 'frozen'
        # chunk: dense retention at ~1 step + deltas.
        assert len(files) == 3
        dest = _state()
        assert mgr.restore_latest(dest) == 4
        np.testing.assert_array_equal(
            dest["m"].tree["w"], _state(offset=4.0)["m"].tree["w"]
        )
        # The journal records pins for exactly the retained steps.
        store = CASStore(root)
        pins, orphans = store.load()
        assert sorted(pins) == [3, 4]
        assert not orphans


def test_gc_grace_defers_then_reclaims(tmp_path):
    root = str(tmp_path / "ckpt")
    with knobs.enable_cas():
        with knobs.override_cas_gc_grace_seconds(3600):
            mgr = ts.CheckpointManager(root, keep_last_n=1)
            mgr.save(0, _state(offset=0.0))
            mgr.save(1, _state(offset=1.0))  # drops step 0
            files = _chunk_files(root)
            # Step 0's unique chunk is dead but FRESH: deferred as a
            # journaled orphan, not reclaimed (an in-flight take may
            # have just deduped against it).
            store = CASStore(root)
            pins, orphans = store.load()
            assert sorted(pins) == [1]
            assert len(orphans) == 1
            assert set(orphans) <= set(files)
        with knobs.override_cas_gc_grace_seconds(0):
            mgr.save(2, _state(offset=2.0))  # next pass reclaims
            store = CASStore(root)
            pins, orphans = store.load()
            assert not orphans
            dead = set(_chunk_files(root))
            assert not any(k in dead for k in orphans)
        dest = _state()
        assert mgr.restore_latest(dest) == 2


def test_concurrent_take_dedup_survives_gc_of_its_source(tmp_path, monkeypatch):
    """The ISSUE's concurrent take + GC pin: an in-flight (not yet
    committed) async take dedups against step 0's chunks; a sync save
    then GCs step 0 — the grace window keeps the shared chunks on disk,
    and the async step commits restorable."""
    from torchsnapshot_tpu.test_utils import MarkerWrites

    # Step 1's commit is held open at its marker: its commit thread pins
    # and indexes the step as soon as the marker exists.
    marker_may_land = MarkerWrites(monkeypatch).hold("step_0000000001")
    root = str(tmp_path / "ckpt")
    with knobs.enable_cas(), knobs.override_cas_gc_grace_seconds(3600):
        mgr = ts.CheckpointManager(root, keep_last_n=1)
        state_a = _state(offset=7.0)
        mgr.save(0, state_a)
        # In-flight take of the SAME state: its writes dedup against
        # step 0's chunks (touching them) but nothing is pinned until
        # it commits.
        pending = mgr.async_save(1, state_a)
        pending._pending.wait(phase="staged")
        # A competing commit drops step 0 while step 1 is un-pinned.
        mgr.save(2, _state(offset=9.0))
        assert not pending.done()
        marker_may_land.set()
        assert pending.wait() is not None  # committed + pinned step 1
        dest = _state()
        mgr.restore(1, dest)
        np.testing.assert_array_equal(
            dest["m"].tree["w"], state_a["m"].tree["w"]
        )


def test_crash_between_chunk_write_and_refcount_append_heals(tmp_path):
    root = str(tmp_path / "ckpt")
    with knobs.enable_cas():
        mgr = ts.CheckpointManager(root, keep_last_n=3)
        mgr.save(0, _state(offset=0.0))
        mgr.save(1, _state(offset=1.0))
        journal = os.path.join(root, "chunks", ".refcounts.jsonl")
        # Simulated crash: the chunks + index landed, the journal did
        # not survive at all.
        os.remove(journal)
        mgr2 = ts.CheckpointManager(root, keep_last_n=3)
        pins, _ = CASStore(root).load()
        assert sorted(pins) == [0, 1]
        assert pins[1] == chunk_refs(
            ts.Snapshot(mgr2.step_path(1)).metadata.manifest
        )
        dest = _state()
        assert mgr2.restore_latest(dest) == 1


def test_torn_journal_tail_is_skipped_and_healed(tmp_path):
    root = str(tmp_path / "ckpt")
    with knobs.enable_cas():
        mgr = ts.CheckpointManager(root, keep_last_n=3)
        mgr.save(0, _state())
        store = CASStore(root)
        pins_before, _ = store.load()
        with open(store.journal_path, "a") as f:
            f.write('{"op": "pin", "step": 99, "chu')  # kill mid-append
        pins, _ = store.load()
        assert pins == pins_before  # torn tail skipped
        store.pin(42, {"cas-crc32c-1-00000000": 1})  # heals with newline
        pins, _ = store.load()
        assert 42 in pins and 99 not in pins and 0 in pins


# ---------------------------------------------------------------------------
# mixed layouts + incremental interplay
# ---------------------------------------------------------------------------


def test_mixed_legacy_and_cas_root_restores_both(tmp_path):
    root = str(tmp_path / "ckpt")
    mgr = ts.CheckpointManager(root, keep_last_n=10)
    mgr.save(0, _state(offset=0.0))  # legacy layout
    with knobs.enable_cas():
        mgr.save(1, _state(offset=1.0))  # CAS layout, same root
        dest = _state()
        mgr.restore(0, dest)
        np.testing.assert_array_equal(
            dest["m"].tree["w"], _state(offset=0.0)["m"].tree["w"]
        )
        mgr.restore(1, dest)
        np.testing.assert_array_equal(
            dest["m"].tree["w"], _state(offset=1.0)["m"].tree["w"]
        )
    # And with the knob back off (restore is layout-agnostic).
    dest = _state()
    mgr.restore(1, dest)
    np.testing.assert_array_equal(
        dest["m"].tree["w"], _state(offset=1.0)["m"].tree["w"]
    )


def test_incremental_refs_collapse_onto_chunks(tmp_path):
    """CAS supersedes the lexical ``../step_*`` base references: an
    incremental take over a CAS base lands every unchanged chunk at its
    ``../chunks/<key>`` address directly (normpath collapses the
    step-relative composition), so manifests carry NO step refs and
    base-step GC can never dangle a reference — the structural
    impossibility the ISSUE names."""
    root = str(tmp_path / "ckpt")
    with knobs.enable_cas(), knobs.override_cas_gc_grace_seconds(0):
        mgr = ts.CheckpointManager(root, keep_last_n=1, incremental=True)
        mgr.save(0, _state(offset=5.0))
        mgr.save(1, _state(offset=5.0))  # unchanged: all refs
        man1 = ts.Snapshot(mgr.step_path(1)).metadata.manifest
        assert referenced_steps(man1) == set()  # no ../step_* anywhere
        assert chunk_refs(man1)
        # keep_last_n=1 deleted step 0's blobs outright (GC leaves only
        # empty directories behind, as for any legacy step) — nothing
        # pins it, because nothing references it.
        step0 = os.path.join(root, "step_0000000000")
        leftover = [
            os.path.join(dp, f)
            for dp, _, fs in os.walk(step0)
            for f in fs
        ]
        assert leftover == []
        index = json.loads(
            open(os.path.join(root, ".manager_index")).read()
        )
        assert "pinned" not in index
        dest = _state()
        assert mgr.restore_latest(dest) == 1
        np.testing.assert_array_equal(
            dest["m"].tree["w"], _state(offset=5.0)["m"].tree["w"]
        )


def test_incremental_skip_avoids_chunk_rewrites(tmp_path):
    root = str(tmp_path / "ckpt")
    with knobs.enable_cas():
        mgr = ts.CheckpointManager(root, keep_last_n=5, incremental=True)
        mgr.save(0, _state(offset=2.0))
        before = _chunk_files(root)
        mgr.save(1, _state(offset=2.0))
        assert _chunk_files(root) == before


# ---------------------------------------------------------------------------
# legacy-mode retention guard (the orphaned-base bugfix)
# ---------------------------------------------------------------------------


def test_legacy_gc_rederives_refs_for_unmarked_index(tmp_path):
    """An index written before refs recording (no ``refs`` map, no
    ``refs_complete`` marker) holds an incremental step whose base a
    keep_last_n GC would drop: the explicit retention check re-derives
    refs from the retained manifests and PINS the base instead of
    orphaning the ``../step_*`` references."""
    root = str(tmp_path / "ckpt")
    mgr = ts.CheckpointManager(root, keep_last_n=2, incremental=True)
    mgr.save(0, _state(offset=1.0))
    mgr.save(1, _state(offset=1.0))  # references step 0's blobs
    man1 = ts.Snapshot(mgr.step_path(1)).metadata.manifest
    assert referenced_steps(man1) == {0}
    # Strip the refs bookkeeping: the pre-incremental index format.
    for slot in (".manager_index", ".manager_index.backup"):
        path = os.path.join(root, slot)
        index = json.loads(open(path).read())
        index.pop("refs", None)
        index.pop("refs_complete", None)
        open(path, "w").write(json.dumps(index))
    # keep_last_n=2: committing step 2 drops step 0 from the visible
    # list — WITHOUT the guard its blobs would be deleted while step
    # 1 still references them.
    mgr.save(2, _state(offset=3.0))
    index = json.loads(open(os.path.join(root, ".manager_index")).read())
    assert index.get("pinned") == [0]  # healed: base pinned, not orphaned
    assert index.get("refs", {}).get("1") == [0]
    assert index.get("refs_complete") is True
    from torchsnapshot_tpu.fsck import verify_snapshot

    report = verify_snapshot(mgr.step_path(1))
    assert report.ok, [p.__dict__ for p in report.problems]
    dest = _state()
    mgr.restore(1, dest)
    np.testing.assert_array_equal(
        dest["m"].tree["w"], _state(offset=1.0)["m"].tree["w"]
    )


# ---------------------------------------------------------------------------
# 2-process replicated-rank dedup
# ---------------------------------------------------------------------------

_CHUNK_WRITES = []


class _ChunkCountingFS(FSStoragePlugin):
    """Accumulates every chunk-blob write this process issues."""

    async def write(self, write_io):
        if is_chunk_location(write_io.path):
            _CHUNK_WRITES.append(write_io.path)
        await super().write(write_io)


def _replicated_dedup_worker(pg, root: str):
    os.environ["TORCHSNAPSHOT_TPU_CAS"] = "1"
    state = {
        "m": ts.PyTreeState(
            {
                # Identical bytes on BOTH ranks, saved per-rank (not
                # declared replicated): the partitioner keeps two
                # entries, the chunk store keeps one blob.
                "same": np.arange(8192, dtype=np.float32),
                "own": np.full(1024, float(pg.rank), dtype=np.float32),
            }
        )
    }
    with patch_storage_plugin(_ChunkCountingFS):
        ts.Snapshot.take(
            os.path.join(root, "step_0000000001"), state, pg=pg
        )
        first = list(_CHUNK_WRITES)
        ts.Snapshot.take(
            os.path.join(root, "step_0000000002"), state, pg=pg
        )        # dedup across steps: nothing new anywhere
        second = [p for p in _CHUNK_WRITES if p not in first]
    return {"rank": pg.rank, "first": first, "second": second}


@pytest.mark.slow
def test_two_proc_replicated_rank_dedup(tmp_path):
    root = str(tmp_path / "ckpt")
    rows = run_multiprocess(_replicated_dedup_worker, nproc=2, args=(root,))
    files = _chunk_files(root)
    snap = ts.Snapshot(os.path.join(root, "step_0000000001"))
    manifest = snap.metadata.manifest
    same_locs = {
        manifest["0/m/same"].location,
        manifest["1/m/same"].location,
    }
    # Replica dedup: both ranks' identical leaves resolve to ONE stored
    # blob (one location, one file).
    assert len(same_locs) == 1
    key = key_of_location(next(iter(same_locs)))
    assert key in files
    # Exactly one stored copy per unique digest overall: 'same' (x1) +
    # per-rank 'own' (x2) = 3 chunk files.
    assert len(files) == 3
    # Step 2 (identical state) wrote NOTHING on either rank.
    for row in rows:
        assert row["second"] == []


# ---------------------------------------------------------------------------
# fsck --cas
# ---------------------------------------------------------------------------


def test_fsck_cas_store_audit(tmp_path):
    from torchsnapshot_tpu.fsck import main as fsck_main, verify_cas_store

    root = str(tmp_path / "ckpt")
    with knobs.enable_cas():
        mgr = ts.CheckpointManager(root, keep_last_n=5)
        for i in range(3):
            mgr.save(i, _state(offset=float(i)))
    report = verify_cas_store(root, deep=True)
    assert report.ok
    assert report.steps == [0, 1, 2]
    assert report.crcs_verified == report.chunks_referenced
    # 3 'w' variants + 1 shared 'frozen': 4 stored, logical = 3 steps
    # x 2 leaves -> dedup ratio > 1.
    assert report.chunks_present == 4
    assert report.dedup_ratio > 1.1
    assert report.bytes_per_retained_step > 0
    assert fsck_main([root, "--cas", "--deep"]) == 0

    cdir = os.path.join(root, "chunks")
    victim = sorted(k for k in _chunk_files(root))[0]
    # Corruption -> deep audit checksum problem.
    with open(os.path.join(cdir, victim), "r+b") as f:
        f.seek(3)
        f.write(b"\x99")
    deep = verify_cas_store(root, deep=True)
    assert any(p.kind == "checksum" for p in deep.problems)
    # Dangling ref -> missing problem (shallow sees it too).
    os.remove(os.path.join(cdir, victim))
    shallow = verify_cas_store(root)
    assert any(
        p.kind == "missing" and victim in p.location
        for p in shallow.problems
    )
    assert fsck_main([root, "--cas"]) == 1
    # A stray (unreferenced) chunk is informational, never a failure.
    stray = digest_key(compute_checksum_entry(b"stray bytes"))
    open(os.path.join(cdir, stray), "wb").write(b"stray bytes")
    report = verify_cas_store(root)
    assert stray in report.unreferenced
    assert not any(stray in p.location for p in report.problems)


# ---------------------------------------------------------------------------
# mirror: chunk-level shipping
# ---------------------------------------------------------------------------


def test_mirror_ships_only_novel_chunks(tmp_path):
    from torchsnapshot_tpu.tiered.mirror import get_mirror, reset_mirror

    fast = str(tmp_path / "fast")
    dur = str(tmp_path / "dur")
    root = f"tiered://{fast}/ckpt|{dur}/ckpt"
    reset_mirror()
    try:
        with knobs.enable_cas():
            mgr = ts.CheckpointManager(root, keep_last_n=4)
            mgr.save(0, _state(offset=6.0))
            mgr.wait_durable(0)
            shipped_first = get_mirror().metrics()["bytes_mirrored"]
            mgr.save(1, _state(offset=6.0))  # identical: chunks all held
            mgr.wait_durable(1)
            shipped_second = (
                get_mirror().metrics()["bytes_mirrored"] - shipped_first
            )
        state_bytes = 4096 * 4 + 1024 * 4
        assert shipped_first > state_bytes  # data + metadata shipped
        # Step 1 ships only control blobs (manifest, tables, maps) —
        # every data chunk is skipped by the durable existence probe.
        assert shipped_second < state_bytes / 4
        # Durable tier holds the chunks once.
        assert sorted(_chunk_files(os.path.join(dur, "ckpt"))) == sorted(
            _chunk_files(os.path.join(fast, "ckpt"))
        )
        # And a fast-tier loss restores from durable alone.
        import shutil

        shutil.rmtree(fast)
        dest = _state()
        mgr2 = ts.CheckpointManager(root, keep_last_n=4)
        assert mgr2.restore_latest(dest) == 1
        np.testing.assert_array_equal(
            dest["m"].tree["w"], _state(offset=6.0)["m"].tree["w"]
        )
    finally:
        reset_mirror()


# ---------------------------------------------------------------------------
# peer tier: chunk pool + inventory-by-digest
# ---------------------------------------------------------------------------


def test_peer_cache_chunk_pool_refcounts():
    from torchsnapshot_tpu.scheduler import PeerCacheBudget
    from torchsnapshot_tpu.tiered.peer import PeerCache

    cache = PeerCache(budget=PeerCacheBudget(1 << 20))
    data = b"c" * 1000
    entry = compute_checksum_entry(data)
    loc = chunk_location(digest_key(entry))
    ok, _ = cache.put("stepA", 1, loc, entry, data)
    assert ok
    bytes_after_one = cache.stats()["bytes"]
    # A second step referencing the same chunk adds NO bytes.
    assert cache.reference_chunks("stepB", 2, [loc, "../chunks/cas-x"]) == [
        loc
    ]
    assert cache.stats()["bytes"] == bytes_after_one
    assert loc in cache.inventory("stepB")
    # Served for any step key: content-addressed.
    assert cache.get("stepB", loc)[1] == data
    assert cache.get("stepC", loc)[1] == data
    # Dropping ONE referencing step keeps the pooled chunk.
    assert cache.evict_step("stepA")
    assert cache.get("stepB", loc)[1] == data
    assert cache.stats()["bytes"] == bytes_after_one
    # Dropping the last reference frees the bytes.
    assert cache.evict_step("stepB")
    assert cache.get("stepB", loc) is None
    assert cache.stats()["bytes"] == 0


def test_peer_transport_refchunks_roundtrip():
    from torchsnapshot_tpu.scheduler import PeerCacheBudget
    from torchsnapshot_tpu.tiered.peer import (
        PeerCache,
        PeerClient,
        _PeerServer,
    )

    cache = PeerCache(budget=PeerCacheBudget(1 << 20))
    server = _PeerServer(("127.0.0.1", 0), cache)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        host, port = server.server_address
        client = PeerClient(host, port, timeout=10.0)
        data = b"z" * 512
        entry = compute_checksum_entry(data)
        loc = chunk_location(digest_key(entry))
        assert client.push("s1", 1, loc, entry, data) == (True, "ok")
        client.commit("s1", 1)
        # Inventory-by-digest: the next step's pusher learns the chunk
        # is already held and ships nothing.
        assert client.reference_chunks("s2", 2, [loc]) == [loc]
        assert client.reference_chunks("s2", 2, ["../chunks/cas-nope"]) == []
        got = client.pull("s2", loc)
        assert got is not None and bytes(got[1]) == data
        assert loc in client.list_step("s2")
        client.close()
    finally:
        server.shutdown()
        server.server_close()


# ---------------------------------------------------------------------------
# ledger accounting + the dedup-ineffective doctor rule
# ---------------------------------------------------------------------------


def test_ledger_step_committed_cas_accounting(tmp_path):
    from torchsnapshot_tpu.telemetry import names as tn
    from torchsnapshot_tpu.telemetry.ledger import load_ledger

    root = str(tmp_path / "ckpt")
    with knobs.enable_cas(), knobs.enable_ledger():
        mgr = ts.CheckpointManager(root, keep_last_n=5)
        mgr.save(0, _state(offset=4.0), record_digests=True)
        mgr.save(1, _state(offset=4.0), record_digests=True)
    records = load_ledger(os.path.join(root, ".ledger.jsonl"))
    committed = [
        r for r in records if r.get("event") == tn.EVENT_STEP_COMMITTED
    ]
    assert len(committed) == 2
    first, second = committed
    assert first["cas"] and second["cas"]
    assert first["bytes_reused"] == 0
    assert first["bytes_new"] == first["bytes_total"] > 0
    # The identical second step is pure reuse — the EXACT accounting
    # the prefix heuristic could never produce for chunk refs.
    assert second["bytes_new"] == 0
    assert second["bytes_reused"] == second["bytes_total"] > 0
    assert second["chunks_new"] == 0 and second["chunks_reused"] > 0
    # Digest evidence: the unchanged state is fully digest-covered.
    assert second["bytes_digest_unchanged"] > 0
    assert (
        second["bytes_digest_unchanged"] == second["bytes_digest_covered"]
    )


def _step_record(step, total, reused, unchanged, covered, cas=True):
    from torchsnapshot_tpu.telemetry import names as tn

    return {
        "event": tn.EVENT_STEP_COMMITTED,
        "step": step,
        "cas": cas,
        "bytes_total": total,
        "bytes_new": total - reused,
        "bytes_reused": reused,
        "bytes_digest_unchanged": unchanged,
        "bytes_digest_covered": covered,
    }


def test_dedup_ineffective_rule_fires_and_stays_quiet():
    from torchsnapshot_tpu.telemetry import names as tn
    from torchsnapshot_tpu.telemetry.doctor import (
        Evidence,
        diagnose_evidence,
    )

    def verdicts(records):
        ev = Evidence(
            path="/r", ledger_records=records, ledger_file="/r/.ledger.jsonl"
        )
        return [
            v
            for v in diagnose_evidence(ev)
            if v.rule == tn.RULE_DEDUP_INEFFECTIVE
        ]

    # Broken dedup: digests say ~90% unchanged, reuse ~0 across the
    # window -> fires, citing the records.
    bad = [
        _step_record(i, 1000, 0, 900, 1000) for i in range(4)
    ]
    out = verdicts(bad)
    assert len(out) == 1
    assert out[0].evidence["reuse_fraction"] == 0.0
    assert out[0].evidence["digest_unchanged_fraction"] == 0.9
    # Healthy dedup (unchanged bytes ARE reused) -> quiet.
    assert verdicts(
        [_step_record(i, 1000, 900, 900, 1000) for i in range(4)]
    ) == []
    # Genuinely-changing state (digests agree nothing holds) -> quiet.
    assert verdicts(
        [_step_record(i, 1000, 0, 50, 1000) for i in range(4)]
    ) == []
    # No digest coverage -> cannot claim the state was static -> quiet.
    assert verdicts(
        [_step_record(i, 1000, 0, 0, 0) for i in range(4)]
    ) == []
    # Too few CAS records -> quiet.
    assert verdicts([_step_record(0, 1000, 0, 900, 1000)]) == []
    # Legacy records never trigger it.
    assert verdicts(
        [_step_record(i, 1000, 0, 900, 1000, cas=False) for i in range(4)]
    ) == []


# ---------------------------------------------------------------------------
# review-hardening regressions: durable-side repair + stray GC + tier audit
# ---------------------------------------------------------------------------


def test_mirror_reships_deduped_chunk_missing_from_durable(tmp_path):
    """A dedup hit writes nothing, but the step's durability claim
    still covers the chunk: if the original writer's mirror never
    landed it (crash before commit, manual durable-tier damage), the
    next referencing step's mirror job must ship it — the deduped
    chunk rides the job and the durable probe decides."""
    from torchsnapshot_tpu.tiered.mirror import reset_mirror

    fast = str(tmp_path / "fast")
    dur = str(tmp_path / "dur")
    root = f"tiered://{fast}/ckpt|{dur}/ckpt"
    reset_mirror()
    try:
        with knobs.enable_cas():
            mgr = ts.CheckpointManager(root, keep_last_n=4)
            mgr.save(0, _state(offset=8.0))
            mgr.wait_durable(0)
            dchunks = os.path.join(dur, "ckpt", "chunks")
            victim = sorted(_chunk_files(os.path.join(dur, "ckpt")))[0]
            os.remove(os.path.join(dchunks, victim))
            mgr.save(1, _state(offset=8.0))  # identical: pure dedup
            mgr.wait_durable(1)
            assert victim in _chunk_files(os.path.join(dur, "ckpt"))
        # The repaired durable tier alone restores the step.
        import shutil

        shutil.rmtree(fast)
        dest = _state()
        mgr2 = ts.CheckpointManager(root, keep_last_n=4)
        assert mgr2.restore_latest(dest) == 1
        np.testing.assert_array_equal(
            dest["m"].tree["w"], _state(offset=8.0)["m"].tree["w"]
        )
    finally:
        reset_mirror()


def test_mirror_reships_torn_durable_chunk(tmp_path):
    """The durable existence probe is size-verified (the key embeds
    nbytes, the probe reads the LAST byte): a truncated durable copy —
    a crash mid-upload; fs writes have no temp+rename — misses the
    probe and is overwritten whole instead of being trusted forever."""
    from torchsnapshot_tpu.tiered.mirror import reset_mirror

    fast = str(tmp_path / "fast")
    dur = str(tmp_path / "dur")
    root = f"tiered://{fast}/ckpt|{dur}/ckpt"
    reset_mirror()
    try:
        with knobs.enable_cas():
            mgr = ts.CheckpointManager(root, keep_last_n=4)
            mgr.save(0, _state(offset=9.0))
            mgr.wait_durable(0)
            dchunks = os.path.join(dur, "ckpt", "chunks")
            victim = sorted(_chunk_files(os.path.join(dur, "ckpt")))[0]
            want = nbytes_of_key(victim)
            with open(os.path.join(dchunks, victim), "r+b") as f:
                f.truncate(want // 2)  # torn upload
            mgr.save(1, _state(offset=9.0))
            mgr.wait_durable(1)
            assert (
                os.path.getsize(os.path.join(dchunks, victim)) == want
            )
    finally:
        reset_mirror()


def test_gc_sweeps_stray_unpinned_chunks(tmp_path):
    """Chunks in NO pin and NO orphan record (a take that crashed
    before its commit pinned them) still become GC candidates via the
    on-disk stray sweep — they age through the grace window like any
    orphan instead of leaking forever."""
    root = str(tmp_path / "ckpt")
    with knobs.enable_cas(), knobs.override_cas_gc_grace_seconds(0):
        mgr = ts.CheckpointManager(root, keep_last_n=1)
        mgr.save(0, _state(offset=0.0))
        # Simulate a crashed take: chunk bytes on disk, never pinned.
        stray = digest_key(compute_checksum_entry(b"crashed take bytes"))
        stray_path = os.path.join(root, "chunks", stray)
        open(stray_path, "wb").write(b"crashed take bytes")
        mgr.save(1, _state(offset=1.0))  # retention GC pass runs
        assert not os.path.exists(stray_path)
        # Live chunks were untouched.
        dest = _state()
        assert mgr.restore_latest(dest) == 1


def test_gc_stray_sweep_defers_fresh_chunks(tmp_path):
    """The stray sweep must not reclaim a concurrent in-flight take's
    freshly-written (not yet pinned) chunks: inside the grace window a
    stray is deferred as a journaled orphan; the take's commit pin
    revives it."""
    root = str(tmp_path / "ckpt")
    with knobs.enable_cas(), knobs.override_cas_gc_grace_seconds(3600):
        mgr = ts.CheckpointManager(root, keep_last_n=1)
        mgr.save(0, _state(offset=0.0))
        inflight = digest_key(compute_checksum_entry(b"in-flight bytes"))
        inflight_path = os.path.join(root, "chunks", inflight)
        open(inflight_path, "wb").write(b"in-flight bytes")
        mgr.save(1, _state(offset=1.0))
        assert os.path.exists(inflight_path)  # deferred, not reclaimed
        store = CASStore(root)
        _pins, orphans = store.load()
        assert inflight in orphans
        # The "in-flight take" commits: its pin revives the chunk.
        store.pin(99, {inflight: len(b"in-flight bytes")})
        store.clear_orphans([inflight])
        _pins, orphans = store.load()
        assert inflight not in orphans


def test_fsck_cas_flags_torn_copy_in_one_tier(tmp_path):
    """Per-tier size audit: a truncated chunk copy on ONE tier is a
    finding even when the other tier holds the full bytes — collapsing
    sizes with max() would pass a root whose durable tier alone is
    unrestorable."""
    from torchsnapshot_tpu.fsck import verify_cas_store
    from torchsnapshot_tpu.tiered.mirror import reset_mirror

    fast = str(tmp_path / "fast")
    dur = str(tmp_path / "dur")
    root = f"tiered://{fast}/ckpt|{dur}/ckpt"
    reset_mirror()
    try:
        with knobs.enable_cas():
            mgr = ts.CheckpointManager(root, keep_last_n=4)
            mgr.save(0, _state(offset=11.0))
            mgr.wait_durable(0)
    finally:
        reset_mirror()
    assert verify_cas_store(root).ok
    victim = sorted(_chunk_files(os.path.join(dur, "ckpt")))[0]
    dcopy = os.path.join(dur, "ckpt", "chunks", victim)
    with open(dcopy, "r+b") as f:
        f.truncate(nbytes_of_key(victim) // 2)
    report = verify_cas_store(root)
    assert any(
        p.kind == "truncated"
        and victim in p.location
        and os.path.join(dur, "ckpt", "chunks") in p.detail
        for p in report.problems
    )


def test_reconcile_heals_partially_lost_pin(tmp_path):
    """Partial journal damage: one committed step's pin record lost
    while OTHER pins survive. Reconcile must re-derive the missing pin
    from that step's manifest — otherwise the stray sweep would reclaim
    a committed step's chunks once they aged past the grace window."""
    root = str(tmp_path / "ckpt")
    with knobs.enable_cas(), knobs.override_cas_gc_grace_seconds(0):
        mgr = ts.CheckpointManager(root, keep_last_n=3)
        mgr.save(0, _state(offset=0.0))
        mgr.save(1, _state(offset=1.0))
        del mgr
        # Drop ONLY step 0's pin (rewrite the journal without it).
        store = CASStore(root)
        pins, orphans = store.load()
        assert sorted(pins) == [0, 1]
        step0_chunks = set(pins.pop(0))
        store.compact(pins, orphans)
        # Next construction heals the missing pin from the manifest...
        mgr2 = ts.CheckpointManager(root, keep_last_n=3)
        pins, _ = CASStore(root).load()
        assert sorted(pins) == [0, 1]
        assert set(pins[0]) == step0_chunks
        # ...so a GC pass (runs on every commit) cannot touch step 0.
        mgr2.save(2, _state(offset=2.0))
        assert step0_chunks <= set(_chunk_files(root))
        dest = _state()
        ts.Snapshot(mgr2.step_path(0)).restore(dest)
        np.testing.assert_array_equal(
            dest["m"].tree["w"], _state(offset=0.0)["m"].tree["w"]
        )


def test_gc_runs_without_retention_deletes(tmp_path):
    """Chunk GC rides EVERY commit, not only ones that dropped steps:
    a keep-everything manager still reclaims crashed takes' strays and
    aged-out orphans."""
    root = str(tmp_path / "ckpt")
    with knobs.enable_cas(), knobs.override_cas_gc_grace_seconds(0):
        mgr = ts.CheckpointManager(root)  # no retention: never deletes
        mgr.save(0, _state(offset=0.0))
        stray = digest_key(compute_checksum_entry(b"crashed take bytes"))
        stray_path = os.path.join(root, "chunks", stray)
        open(stray_path, "wb").write(b"crashed take bytes")
        mgr.save(1, _state(offset=1.0))  # drops nothing
        assert not os.path.exists(stray_path)
        dest = _state()
        assert mgr.restore_latest(dest) == 1

"""Which bytes an incremental save may leave unwritten: a reference that is
independent of ``incremental.py`` and ``ops/device_digest.py``.

The reference keeps a host copy (``np.asarray``) of every leaf at each of
three saves; a chunk "changed" when its bytes differ from the save before.
The state is the toy fine-tune over a frozen base (``lora_rank=8``): steps
between the saves move the adapters, their moments, adamw's count and the
key, and nothing else; between saves 2 and 3 one bit of one frozen leaf is
flipped on the device. The saves go through ``CheckpointManager(keep_last_n=1)``
as chipbench's ``save_loop`` calls it, ``incremental=True`` on each, with the
incremental chunk size cut so that the larger leaves are written in chunks as
the real widths' are. Beside it the arithmetic of the counters on the digest
path's spans and their place in the take's stage table.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchsnapshot_tpu as ts
from torchsnapshot_tpu import knobs
from torchsnapshot_tpu.flatten import flatten
from torchsnapshot_tpu.manifest import ArrayEntry, ChunkedArrayEntry
from torchsnapshot_tpu.models import TransformerConfig, init_train_state, make_train_step
from torchsnapshot_tpu.ops import device_digest
from torchsnapshot_tpu.telemetry import critpath, names, trace

# chipbench/workload.py's REHEARSAL_SIZES and REHEARSAL_LAYERS, adapted.
TOY = TransformerConfig(vocab_size=512, d_model=256, n_heads=4, n_layers=2, d_ff=1024,
                        lora_rank=8)
CHUNK_BYTES = 64 * 1024  # embed (256 KiB) in 4 chunks, w_in / w_out (512 KiB) in 8
STEPS = (1, 2, 3)
FLIPPED = "params/layers/1/w_out"
INCREMENTAL_SPANS = (names.SPAN_INCREMENTAL_BASE, names.SPAN_INCREMENTAL_DIGEST_LAUNCH,
                     names.SPAN_INCREMENTAL_DIGEST_WAIT)


def _app_state(state, step):
    return {
        "params": ts.PyTreeState(state.params),
        "opt": ts.PyTreeState(state.opt_state),
        "progress": ts.StateDict(step=step),
        "rng": ts.RngState(state.rng),
    }


def _leaves(app):
    """Every array leaf by the path the manifest knows it under."""
    out = {}
    for key in ("params", "opt", "rng"):
        out.update(flatten(app[key].state_dict(), prefix=key)[1])
    return out


def _bits(a):
    a = np.ascontiguousarray(a)
    return a.view(np.dtype(f"u{a.dtype.itemsize}"))


def _flip_one_bit(x):
    """The lowest bit of one element, on the device."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint16)
    return jax.lax.bitcast_convert_type(bits.at[3, 5].set(bits[3, 5] ^ 1), x.dtype)


def _chunks(entry):
    """An array entry's chunks as (row range, the dense entry of its bytes)."""
    if isinstance(entry, ArrayEntry):
        return [((0, entry.shape[0] if entry.shape else 1), entry)]
    assert isinstance(entry, ChunkedArrayEntry)
    return [((c.offsets[0], c.offsets[0] + c.sizes[0]), c.array) for c in entry.chunks]


def _rows(host, rows):
    return host.reshape(-1)[:] if not host.ndim else host[rows[0]:rows[1]]


@pytest.fixture(scope="module", params=["async_save", "save"])
def saved(request, tmp_path_factory):
    handed = []
    real = device_digest.digest_many_async

    def spy(specs):
        handed.append(sum(arr.nbytes for arr, _ in specs))
        return real(specs)

    patch = pytest.MonkeyPatch()
    patch.setattr(device_digest, "digest_many_async", spy)
    root = str(tmp_path_factory.mktemp("incremental"))
    mgr = ts.CheckpointManager(root, keep_last_n=1)
    state = init_train_state(TOY, seed=7)
    step_fn = make_train_step(TOY)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 512, (4, 128), dtype=np.int32))
    recorder = trace.get_recorder()
    host, events, reports, launched, manifests = {}, {}, {}, {}, {}
    try:
        with knobs.override_incremental_chunk_size_bytes(CHUNK_BYTES):
            for step in STEPS:
                for _ in range(2):
                    state, _ = step_fn(state, tokens)
                if step == 3:
                    block = state.params["layers"][1]
                    block["w_out"] = _flip_one_bit(block["w_out"])
                app = _app_state(state, step)
                host[step] = {k: np.array(v) for k, v in _leaves(app).items()}
                mark, before = recorder.mark(), len(handed)
                if request.param == "async_save":
                    mgr.async_save(step, app, incremental=True).wait()
                else:
                    mgr.save(step, app, incremental=True)
                events[step] = [e for e in recorder.events_since(mark) if e.get("ph") == "X"]
                launched[step] = handed[before:]
                reports[step] = ts.telemetry.last_report(
                    "take", "async_take", path=mgr.step_path(step))
                # Read while it is the latest: retention drops it with the next.
                manifests[step] = {
                    k.split("/", 1)[1]: e
                    for k, e in ts.Snapshot(mgr.step_path(step)).get_manifest().items()
                    if isinstance(e, (ArrayEntry, ChunkedArrayEntry))}
            full_root = str(tmp_path_factory.mktemp("full"))
            ts.CheckpointManager(full_root, keep_last_n=1).save(3, _app_state(state, 3))
    finally:
        patch.undo()
    return {"mgr": mgr, "root": root, "full_root": full_root, "host": host, "events": events,
            "reports": reports, "launched": launched, "manifests": manifests,
            "kind": "async_take" if request.param == "async_save" else "take"}


def _reference(saved, step):
    """{(leaf, row range): (changed since the save before, bytes)} over the
    chunks save `step` laid the leaves out in."""
    now, then = saved["host"][step], saved["host"].get(step - 1)
    out = {}
    for path, entry in saved["manifests"][step].items():
        for rows, _ in _chunks(entry):
            a = _rows(now[path], rows)
            changed = then is None or not np.array_equal(_bits(a), _bits(_rows(then[path], rows)))
            out[(path, rows)] = (changed, a.nbytes)
    return out


def _span(saved, step, name):
    (event,) = [e for e in saved["events"][step] if e["name"] == name]
    return event


# (a) --------------------------------------------------------------------


@pytest.mark.parametrize("step", [2, 3])
def test_referenced_entries_are_exactly_the_chunks_the_reference_calls_unchanged(saved, step):
    reference = _reference(saved, step)
    assert len(reference) > len(saved["host"][step])  # some leaves are in chunks
    referenced = {(path, rows) for path, entry in saved["manifests"][step].items()
                  for rows, dense in _chunks(entry) if dense.location.startswith("../")}
    unchanged = {key for key, (changed, _) in reference.items() if not changed}
    assert referenced == unchanged
    # What moved between two saves: the adapters, their moments, the count, the key.
    moved = {path for (path, _), (changed, _) in reference.items() if changed}
    trained = {p for p in saved["host"][step] if "lora_" in p}
    assert len(trained) == 3 * 2 * TOY.n_layers
    assert moved - {FLIPPED} == trained | {"opt/0/count", "rng/keys"}


def test_the_first_save_has_no_base_and_writes_everything(saved):
    assert all(not dense.location.startswith("../")
               for entry in saved["manifests"][1].values() for _, dense in _chunks(entry))
    assert _span(saved, 1, names.SPAN_INCREMENTAL_BASE)["args"] == {"entries": 0, "usable": 0}
    base = _span(saved, 2, names.SPAN_INCREMENTAL_BASE)["args"]
    assert base["usable"] == 1 and base["entries"] >= len(saved["host"][1])


# (b) --------------------------------------------------------------------


@pytest.fixture(scope="module")
def restored(saved):
    out = {}
    for name, root in (("incremental", saved["root"]), ("full", saved["full_root"])):
        live = _app_state(init_train_state(TOY, seed=8), 0)
        assert ts.CheckpointManager(root, keep_last_n=1).restore_latest(live) == 3
        assert live["progress"]["step"] == 3
        out[name] = {k: np.asarray(v) for k, v in _leaves(live).items()}
    return out


def test_the_restore_of_save_3_is_the_host_copy_and_what_a_full_save_restores(saved, restored):
    want = saved["host"][3]
    assert restored["incremental"].keys() == restored["full"].keys() == want.keys()
    for path, leaf in want.items():
        for name in ("incremental", "full"):
            got = restored[name][path]
            assert got.dtype == leaf.dtype and got.shape == leaf.shape, (name, path)
            assert np.array_equal(_bits(got), _bits(leaf)), (name, path)


# (c) --------------------------------------------------------------------


def test_one_flipped_bit_has_exactly_its_chunk_rewritten(saved):
    then, now = saved["host"][2][FLIPPED], saved["host"][3][FLIPPED]
    differing = np.argwhere(_bits(then) != _bits(now))
    assert differing.tolist() == [[3, 5]]
    assert int(_bits(then)[3, 5] ^ _bits(now)[3, 5]) == 1
    chunks = _chunks(saved["manifests"][3][FLIPPED])
    assert len(chunks) == 8
    written = [rows for rows, dense in chunks if not dense.location.startswith("../")]
    assert written == [rows for rows, _ in chunks if rows[0] <= 3 < rows[1]] and len(written) == 1
    # Every other frozen chunk of save 3 is a reference.
    for path, entry in saved["manifests"][3].items():
        if "lora_" in path or path in ("opt/0/count", "rng/keys", FLIPPED):
            continue
        assert all(dense.location.startswith("../") for _, dense in _chunks(entry)), path


# (d) --------------------------------------------------------------------


def test_retention_keeps_every_blob_the_kept_snapshot_references(saved, restored):
    mgr = saved["mgr"]
    assert mgr.all_steps() == [3]
    locations = [dense.location for entry in saved["manifests"][3].values()
                 for _, dense in _chunks(entry)]
    climbing = [loc for loc in locations if loc.startswith("../")]
    assert climbing and len(climbing) < len(locations)
    for loc in locations:
        assert os.path.isfile(os.path.normpath(os.path.join(mgr.step_path(3), loc))), loc
    # Chained references collapse to the save that wrote the bytes: step 1.
    assert {loc.split("/")[1] for loc in climbing} == {os.path.basename(mgr.step_path(1))}
    # Step 2 wrote only what step 3 wrote again: no file of it is left.
    assert not [f for _, _, files in os.walk(mgr.step_path(2)) for f in files]
    assert restored["incremental"].keys() == saved["host"][3].keys()


# (e) --------------------------------------------------------------------


@pytest.mark.parametrize("step", STEPS)
def test_the_counters_add_up(saved, step):
    reference = _reference(saved, step)
    plan = _span(saved, step, names.SPAN_TAKE_PLAN)["args"]
    launch = _span(saved, step, names.SPAN_INCREMENTAL_DIGEST_LAUNCH)["args"]
    wait = _span(saved, step, names.SPAN_INCREMENTAL_DIGEST_WAIT)["args"]
    eligible = sum(leaf.nbytes for leaf in saved["host"][step].values())
    assert plan["bytes_referenced"] + plan["bytes_written"] == eligible
    assert plan["bytes_written"] == sum(n for changed, n in reference.values() if changed)
    assert plan["chunks_written"] == sum(changed for changed, _ in reference.values())
    assert plan["chunks_referenced"] + plan["chunks_written"] == len(reference)
    # Every leaf is on the device: one program, and it was handed every byte.
    assert launch["programs"] == len(saved["launched"][step]) == 1
    assert launch["bytes"] == saved["launched"][step][0] == eligible
    assert launch["host_bytes"] == 0
    assert launch["leaves"] == len(saved["host"][step])
    assert launch["chunks"] == wait["chunks"] == len(reference)
    assert saved["reports"][step].incremental == {
        k: plan[k] for k in ("chunks_referenced", "bytes_referenced",
                             "chunks_written", "bytes_written")}


# (f) --------------------------------------------------------------------


@pytest.mark.parametrize("step", STEPS)
def test_the_spans_are_in_the_stage_table_with_an_op_id_under_take_plan(saved, step):
    events = saved["events"][step]
    plan = _span(saved, step, names.SPAN_TAKE_PLAN)
    op = plan["op"]
    assert op
    table = critpath.stage_tables([e for e in events if e.get("op") == op])[op]
    assert table["kind"] == saved["kind"]
    for name in INCREMENTAL_SPANS:
        event = _span(saved, step, name)
        # One recorder event a take, not one a leaf.
        assert table["stages"][name]["count"] == 1, name
        assert event["op"] == op and event["parent"] == plan["bseq"], name
        assert plan["ts"] <= event["ts"] and event["ts"] + event["dur"] <= plan["ts"] + plan["dur"]
        assert critpath.segment_for(name) == critpath.SEG_PLAN
    segments = saved["reports"][step].critical_path["segments"]
    assert not [k for k in segments if "incremental" in k or "digest" in k]


def test_a_plain_save_has_none_of_it(tmp_path):
    recorder = trace.get_recorder()
    mark = recorder.mark()
    mgr = ts.CheckpointManager(str(tmp_path), keep_last_n=1)
    mgr.save(1, _app_state(init_train_state(TOY, seed=0), 1))
    events = [e for e in recorder.events_since(mark) if e.get("ph") == "X"]
    assert not [e for e in events if e["name"] in INCREMENTAL_SPANS]
    (plan,) = [e for e in events if e["name"] == names.SPAN_TAKE_PLAN]
    assert "bytes_written" not in plan["args"]
    assert ts.telemetry.last_report("take", path=mgr.step_path(1)).incremental is None

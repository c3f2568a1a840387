"""The per-layer metric `d2h_dest_reuse_share`: the share of a save's
`stage:d2h` bytes whose host copy landed in a slab of `dest_pool` that a copy
had written before. The entry is found by name (no position in `per_layer`);
the reduction runs on recorded events; a library without the span arg (a
parent's) and a save whose leaves all sit under the floor read as nothing."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import cells
import harness
from test_capture_metrics import _ev, _run

NAME = "d2h_dest_reuse_share"
CELLS = ["neox-6.9b-l2.async-full", "pythia-1b.async-full", "kimi-linear-48b-l5-ep16.async-full"]


def test_benchmark_json_has_the_entry():
    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "%", "better": "higher", "source": "program_span",
                     "layer": "D2H staging + checksum/serialize", "moves": "save_commit_s",
                     "workloads": CELLS}
    assert os.path.isfile(os.path.join(harness.REPO, "chipbench", "layer_metrics", NAME + ".py"))


def _save(op, leaves, slab_args=True):
    """One async save: its commit envelope and one `stage:d2h` a leaf, each
    `(bytes, recycled_bytes)`; `recycled_bytes` None is a leaf under the floor,
    whose span carries no slab args."""
    from torchsnapshot_tpu.telemetry import names

    t = op * 100_000_000
    events = [
        _ev(names.SPAN_ASYNC_TAKE_STAGE, t, 60_000, op, op),
        _ev(names.SPAN_ASYNC_TAKE_COMMIT, t + 60_000, 8_000_000, op + 1, op, tid=1),
    ]
    for i, (nbytes, recycled) in enumerate(leaves):
        args = {"bytes": nbytes}
        if slab_args and recycled is not None:
            args.update({names.ARG_LEASED_BYTES: nbytes, names.ARG_RECYCLED_BYTES: recycled})
        events.append(_ev(names.SPAN_STAGE_D2H, t + 100_000 + i * 1_000, 500, op + 2 + i, op,
                          tid=2 + i % 4, **args))
    return events


def test_the_reader_takes_recycled_over_all_bytes_a_save_and_the_mean_of_saves():
    # The first save: 3 of 4 large bytes recycled, a small leaf beside them;
    # the second: every large byte recycled.
    run = _run(_save(1, [(300, 300), (100, 0), (8, None)])
               + _save(100, [(300, 300), (100, 100), (8, None)]))
    assert cells.layer_reader(NAME)(run) == pytest.approx((100 * 300 / 408 + 100 * 400 / 408) / 2)


def test_the_reader_finds_nothing_without_the_arg(monkeypatch):
    """A library without the slab handler has the span and not the args; a save of small
    leaves alone takes no slab; a ring that dropped the window; a library
    that lacks the span's constant or the arg's."""
    from torchsnapshot_tpu.telemetry import names

    import stage_table

    assert cells.layer_reader(NAME)(_run(_save(1, [(300, 300)], slab_args=False))) is None
    assert cells.layer_reader(NAME)(_run(_save(1, [(8, None), (4, None)]))) is None
    run = _run(_save(1, [(300, 150)]))
    assert cells.layer_reader(NAME)(run) == pytest.approx(50.0)
    assert cells.layer_reader(NAME)(dict(run, **{stage_table.CACHE_KEY: None})) is None
    monkeypatch.delattr(names, "ARG_RECYCLED_BYTES")
    assert cells.layer_reader(NAME)(run) is None
    monkeypatch.undo()
    monkeypatch.delattr(names, "SPAN_STAGE_D2H")
    assert cells.layer_reader(NAME)(run) is None

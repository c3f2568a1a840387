"""The per-layer metric `d2h_device_slice_share`: the share of a save's
`stage:d2h` bytes whose transfer first had to slice a device array. The entry
is found by name (no position in `per_layer`); the reduction runs on recorded
events; a library without the span arg (a parent's) reads as nothing."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import cells
import harness
from test_capture_metrics import _ev, _run

NAME = "d2h_device_slice_share"
CELLS = ["neox-6.9b-l12-lora.async-incremental", "neox-6.9b-l2.async-incremental"]


def test_benchmark_json_has_the_entry():
    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "%", "better": "lower", "source": "program_span",
                     "layer": "D2H staging + checksum/serialize", "moves": "save_commit_s",
                     "workloads": CELLS}
    assert os.path.isfile(os.path.join(harness.REPO, "chipbench", "layer_metrics", NAME + ".py"))


def _save(op, leaves, sliced_arg=True):
    """One async save: its commit envelope and one `stage:d2h` a leaf, each
    `(bytes, sliced_bytes)`."""
    from torchsnapshot_tpu.telemetry import names

    t = op * 100_000_000
    events = [
        _ev(names.SPAN_ASYNC_TAKE_STAGE, t, 60_000, op, op),
        _ev(names.SPAN_ASYNC_TAKE_COMMIT, t + 60_000, 8_000_000, op + 1, op, tid=1),
    ]
    for i, (nbytes, sliced) in enumerate(leaves):
        args = {"bytes": nbytes}
        if sliced_arg:
            args[names.ARG_SLICED_BYTES] = sliced
        events.append(_ev(names.SPAN_STAGE_D2H, t + 100_000 + i * 1_000, 500, op + 2 + i, op,
                          tid=2 + i % 4, **args))
    return events


def test_the_reader_takes_sliced_over_all_bytes_a_save_and_the_mean_of_saves():
    # The first save sliced every chunk of one leaf; the second sliced nothing.
    run = _run(_save(1, [(300, 300), (100, 0)]) + _save(100, [(300, 0), (100, 0)]))
    assert cells.layer_reader(NAME)(run) == pytest.approx((100 * 300 / 400 + 0) / 2)


def test_the_reader_finds_nothing_without_the_arg(monkeypatch):
    from torchsnapshot_tpu.telemetry import names

    import stage_table

    assert cells.layer_reader(NAME)(_run(_save(1, [(300, 0)], sliced_arg=False))) is None
    run = _run(_save(1, [(300, 0)]))
    assert cells.layer_reader(NAME)(run) == 0.0
    assert cells.layer_reader(NAME)(dict(run, **{stage_table.CACHE_KEY: None})) is None
    monkeypatch.delattr(names, "ARG_SLICED_BYTES")
    assert cells.layer_reader(NAME)(run) is None
    monkeypatch.undo()
    monkeypatch.delattr(names, "SPAN_STAGE_D2H")
    assert cells.layer_reader(NAME)(run) is None

"""PR 40's five per-layer metrics of the kernel's account (CPU seconds, user
and system, which the library puts on the spans that move bytes and on the
commit and restore envelopes): the entries, found by name; each reduction on
recorded events; a traced rehearsal of a save cell and of a resume cell
reports all of its four / one; events without the args (a parent's library)
read as nothing. `fault_bytes`, the third arg, has no metric: the machine the
benchmark runs on counts no faults (`probe_usage.py`), and the two entries
ISSUE 40 asked for it would read nothing in any cell. `read_cpu_over_wall` was
built, read on the chip and taken out with the sampling of a restore's spans,
which cost a restore of 299 leaves 1.7 % (PERF.md §6)."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import cells
import harness
from test_capture_metrics import SAVES, _ev, _run

RESUMES = ["neox-6.9b-l2.resume", "neox-6.9b-l6-4chip.resume-reshard", "pythia-1b.resume"]
ENTRY, D2H, WRITE = "entry points", "D2H staging + checksum/serialize", "storage write"
# name: (better, layer, moves, cells)
NEW = {
    "commit_cpu_cores": ("lower", ENTRY, "save_commit_s", SAVES),
    "commit_sys_over_cpu": ("lower", ENTRY, "save_commit_s", SAVES),
    "d2h_cpu_over_wall": ("higher", D2H, "save_commit_s", SAVES),
    "write_cpu_over_wall": ("higher", WRITE, "save_commit_s", SAVES),
    "restore_cpu_cores": ("lower", ENTRY, "restore_s", RESUMES),
}
SAVE_SIDE = [name for name, entry in NEW.items() if entry[3] is SAVES]
RESTORE_SIDE = [name for name, entry in NEW.items() if entry[3] is RESUMES]
FAULTS = 1 << 29  # recorded where the kernel counts them; no reader here


@pytest.mark.parametrize("name", NEW)
def test_benchmark_json_has_the_entry(name):
    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    better, layer, moves, workloads = NEW[name]
    assert entry == {"name": name, "unit": "ratio", "better": better,
                     "source": "program_counter", "layer": layer, "moves": moves,
                     "workloads": workloads}
    assert os.path.isfile(os.path.join(harness.REPO, "chipbench", "layer_metrics", name + ".py"))


def _usage(user, system, faults):
    return {"cpu_user_us": user, "cpu_sys_us": system, "fault_bytes": faults}


def _save(op, scale, account=True):
    """One async save: the commit envelope with the process's account, two
    transfers and two writes with their threads'. `scale` stretches the
    second save's CPU, so that a mean per save is not a ratio of sums."""
    from torchsnapshot_tpu.telemetry import names

    def usage(user, system, faults):
        return _usage(user * scale, system * scale, faults * scale) if account else {}

    t = op * 100_000_000
    return [
        _ev(names.SPAN_ASYNC_TAKE_STAGE, t, 60_000, op, op),
        _ev(names.SPAN_ASYNC_TAKE_COMMIT, t + 60_000, 8_000_000, op + 1, op, tid=1,
            **usage(18_000_000, 6_000_000, FAULTS)),
        _ev(names.SPAN_STAGE_D2H, t + 100_000, 2_000_000, op + 2, op, tid=2, bytes=5,
            **usage(150_000, 50_000, 4096)),
        _ev(names.SPAN_STAGE_D2H, t + 100_000, 2_000_000, op + 3, op, tid=3, bytes=5,
            **usage(300_000, 300_000, 0)),
        _ev(names.SPAN_FS_NATIVE_WRITE, t + 3_000_000, 1_000_000, op + 4, op, tid=4, blob="a",
            **usage(400_000, 500_000, 0)),
        _ev(names.SPAN_FS_NATIVE_PWRITEV, t + 3_000_000, 3_000_000, op + 5, op, tid=5, blob="b",
            **usage(1_100_000, 400_000, 0)),
        # Not a write kernel: the request's span around it is never read.
        _ev(names.SPAN_STORAGE_WRITE, t + 2_900_000, 3_200_000, op + 6, op, tid=0, bytes=9),
    ]


def _restore(op, account=True):
    from torchsnapshot_tpu.telemetry import names

    usage = _usage if account else (lambda *a: {})
    t = op * 100_000_000
    return [
        _ev(names.SPAN_RESTORE, t, 1_000_000, op, op, **usage(1_500_000, 500_000, FAULTS)),
        # A restore's own spans are not sampled: the envelope alone is.
        _ev(names.SPAN_FS_NATIVE_READ, t + 1_000, 400_000, op + 1, op, tid=1, blob="a"),
        _ev(names.SPAN_RESTORE_PLACE, t + 500_000, 100_000, op + 2, op, arrays=1, bytes=7),
    ]


def test_the_save_side_readers_reduce_the_account_per_save():
    run = _run(_save(1, 1) + _save(100, 2))
    read = {name: cells.layer_reader(name)(run) for name in SAVE_SIDE}
    assert read["commit_cpu_cores"] == pytest.approx((3.0 + 6.0) / 2)
    assert read["commit_sys_over_cpu"] == pytest.approx(0.25)
    assert read["d2h_cpu_over_wall"] == pytest.approx((0.2 + 0.4) / 2)
    assert read["write_cpu_over_wall"] == pytest.approx((0.6 + 1.2) / 2)


def test_the_restore_side_readers_reduce_the_account_per_restore():
    run = _run(_restore(1) + _restore(100))
    read = {name: cells.layer_reader(name)(run) for name in RESTORE_SIDE}
    assert read == {"restore_cpu_cores": pytest.approx(2.0)}


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_where_the_spans_carry_no_account(monkeypatch, name):
    """A parent of PR 40 has every span and none of the three args; a
    library older still lacks a constant; a ring that dropped the window."""
    from torchsnapshot_tpu.telemetry import names

    import stage_table

    events = _save(1, 1, account=False) if name in SAVE_SIDE else _restore(1, account=False)
    run = _run(events)
    assert cells.layer_reader(name)(run) is None
    assert cells.layer_reader(name)(dict(run, **{stage_table.CACHE_KEY: None})) is None
    with_account = _run(_save(1, 1) if name in SAVE_SIDE else _restore(1))
    assert cells.layer_reader(name)(with_account) is not None
    for constant in ("SPAN_ASYNC_TAKE_COMMIT", "SPAN_RESTORE", "SPAN_STAGE_D2H",
                     "SPAN_FS_NATIVE_WRITE", "SPAN_FS_NATIVE_PWRITEV",
                     "SPAN_FS_NATIVE_DIRECT_WRITE"):
        monkeypatch.delattr(names, constant)
    assert cells.layer_reader(name)(with_account) is None


@pytest.mark.parametrize("workload,mine,others", [
    (SAVES[0], SAVE_SIDE, RESTORE_SIDE), (RESUMES[0], RESTORE_SIDE, SAVE_SIDE)])
def test_a_traced_rehearsal_reports_all_of_its_cells_metrics(tmp_path, workload, mine, others):
    checkout = harness.copy_benchmark(tmp_path)
    rc, result, err = harness.run_cell(checkout, workload, trace=1, seed=4000000019)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True and result["failed"] == 0
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(mine) <= set(metrics) and not set(others) & set(metrics)
    assert all(result["metrics"][name]["unit"] == "ratio" for name in mine)
    cores = os.cpu_count()
    for name in mine:
        assert metrics[name] >= 0, name
        if name.endswith("_cpu_over_wall"):
            assert metrics[name] <= 1.02, name
        if name.endswith("_cpu_cores"):
            assert 0 < metrics[name] <= cores, name
    if mine is SAVE_SIDE:
        assert 0 <= metrics["commit_sys_over_cpu"] <= 1

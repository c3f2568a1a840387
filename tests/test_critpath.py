"""Critical-path engine + differential analysis (telemetry/critpath.py).

Sweep-line attribution unit coverage (innermost-frame gating, envelope
unions, exhaustive partition), the acceptance bar end-to-end (every
SnapshotReport's ``critical_path`` segments sum to >= 95% of op wall on
real single- and 2-process takes/restores, including the peer-served
path), the stitched-wire descent over a merged Chrome doc, the diff CLI
(injected storage slowdown attributed to write-drain with span
citations; bench-record mode quiet on real rounds and firing on a
doctored pair), and the trend integrations (``critical-path-shifted``,
``bench-regression``, ``critpath_<segment>_s`` series).
"""

import asyncio
import json
import os
import re

import numpy as np
import pytest

import torchsnapshot_tpu as ts
from torchsnapshot_tpu import knobs, telemetry
from torchsnapshot_tpu.telemetry import critpath, names
from torchsnapshot_tpu.telemetry.doctor import (
    diagnose_trend,
    registered_rule_ids,
)
from torchsnapshot_tpu.telemetry.history import detect_trend_regressions
from torchsnapshot_tpu.telemetry.stats import main as stats_main
from torchsnapshot_tpu.test_utils import run_multiprocess


# ---------------------------------------------------------------------------
# Sweep-line attribution (unit, synthetic recorder windows)
# ---------------------------------------------------------------------------


def _ev(name, ts_us, dur_us, bseq, args=None):
    return {
        "ph": "X",
        "name": name,
        "ts": ts_us,
        "dur": dur_us,
        "bseq": bseq,
        "args": args or {},
    }


def test_sweep_charges_innermost_frame_and_partitions_exactly():
    """Nested spans: each elementary interval goes to the most recently
    begun open span; envelope-only time lands in ``other``; the
    partition sums to the wall exactly (coverage 1.0)."""
    events = [
        _ev(names.SPAN_TAKE, 0, 1_000_000, 0),
        _ev(names.SPAN_PIPELINE_STAGE, 0, 400_000, 1),
        _ev(names.SPAN_STORAGE_WRITE, 100_000, 200_000, 2, {"blob": "0/w"}),
    ]
    cp = critpath.critical_path_from_events(events, "take")
    assert cp is not None
    assert cp["wall_s"] == pytest.approx(1.0)
    assert cp["coverage"] == pytest.approx(1.0)
    # [0,100ms) + [300,400ms) staging; [100,300ms) write inside stage
    # gates (innermost); [400ms,1s) envelope-only -> other.
    assert cp["segments"]["staging"] == pytest.approx(0.2, abs=1e-6)
    assert cp["segments"]["write_drain"] == pytest.approx(0.2, abs=1e-6)
    assert cp["segments"]["other"] == pytest.approx(0.6, abs=1e-6)
    assert sum(cp["segments"].values()) == pytest.approx(cp["wall_s"])
    assert cp["dominant"] == "other"
    write = [c for c in cp["chain"] if c["span"] == names.SPAN_STORAGE_WRITE]
    assert write and write[0]["blob"] == "0/w"
    assert write[0]["gated_s"] == pytest.approx(0.2, abs=1e-6)


def test_async_take_attributes_over_envelope_union():
    """Async takes have two envelopes (visible stage + background
    commit); the sweep partitions their union and ignores span time
    outside both windows."""
    events = [
        _ev(names.SPAN_ASYNC_TAKE_STAGE, 0, 100_000, 0),
        _ev(names.SPAN_ASYNC_TAKE_COMMIT, 200_000, 300_000, 1),
        _ev(names.SPAN_PIPELINE_STAGE, 0, 100_000, 2),
        # Straddles the inter-envelope gap: only the in-window part
        # (200ms..250ms) may be charged.
        _ev(names.SPAN_STORAGE_WRITE, 150_000, 100_000, 3),
    ]
    cp = critpath.critical_path_from_events(events, "async_take")
    assert cp["wall_s"] == pytest.approx(0.4)
    assert cp["segments"]["staging"] == pytest.approx(0.1, abs=1e-6)
    assert cp["segments"]["write_drain"] == pytest.approx(0.05, abs=1e-6)
    assert sum(cp["segments"].values()) == pytest.approx(0.4)


def test_no_envelope_yields_none():
    assert critpath.critical_path_from_events([], "take") is None
    assert critpath.critical_path_from_events(
        [_ev(names.SPAN_STORAGE_WRITE, 0, 10, 0)], "take"
    ) is None
    assert critpath.critical_path_from_events(
        [_ev(names.SPAN_TAKE, 0, 100, 0)], "no_such_kind"
    ) is None


def test_foreign_envelope_bounds_but_never_gates():
    """Another op's envelope overlapping the window (async commit
    draining into the next take) must not absorb attribution."""
    events = [
        _ev(names.SPAN_TAKE, 0, 100_000, 0),
        _ev(names.SPAN_ASYNC_TAKE_COMMIT, 0, 100_000, 1),
    ]
    cp = critpath.critical_path_from_events(events, "take")
    assert cp["segments"] == {"other": pytest.approx(0.1)}


# ---------------------------------------------------------------------------
# End-to-end: reports carry critical_path meeting the coverage bar
# ---------------------------------------------------------------------------


def _assert_coverage(ev):
    cp = ev.get("critical_path")
    assert cp, f"{ev.get('kind')} report carries no critical_path"
    assert cp["coverage"] >= critpath.MIN_COVERAGE
    assert sum(cp["segments"].values()) >= 0.95 * cp["wall_s"]
    assert cp["dominant"] in cp["segments"]
    return cp


def test_single_process_take_and_restore_meet_coverage_bar(tmp_path):
    path = str(tmp_path / "snap")
    with knobs.enable_telemetry():
        state = {
            "m": ts.PyTreeState(
                {"w": np.arange(1 << 20, dtype=np.float32)}
            )
        }
        ts.Snapshot.take(path, state)
        dest = {
            "m": ts.PyTreeState(
                {"w": np.zeros(1 << 20, dtype=np.float32)}
            )
        }
        ts.Snapshot(path).restore(dest)
    events = telemetry.load_events(os.path.join(path, ".telemetry.jsonl"))
    by_kind = {e["kind"]: e for e in events}
    take_cp = _assert_coverage(by_kind["take"])
    restore_cp = _assert_coverage(by_kind["restore"])
    # The chains cite real storage spans, not just envelope residue.
    assert any(
        c["segment"] == "write_drain" for c in take_cp["chain"]
    )
    assert any(
        c["segment"] == "read_drain" for c in restore_cp["chain"]
    )


def _worker_take_restore_critpath(pg, path):
    import os

    import numpy as np

    import torchsnapshot_tpu as ts
    from torchsnapshot_tpu import knobs, telemetry
    from torchsnapshot_tpu.pg_wrapper import PGWrapper

    os.environ["TORCHSNAPSHOT_TPU_FANOUT_RESTORE"] = "1"
    with knobs.enable_telemetry():
        state = {
            "m": ts.PyTreeState(
                {"w": np.arange(200_000, dtype=np.float32)}
            )
        }
        ts.Snapshot.take(path, state, pg=pg, replicated=["**"])
        PGWrapper(pg).barrier()
        dest = {
            "m": ts.PyTreeState(
                {"w": np.zeros(200_000, dtype=np.float32)}
            )
        }
        ts.Snapshot(path, pg=pg).restore(dest)
        np.testing.assert_array_equal(
            dest["m"].tree["w"], np.arange(200_000, dtype=np.float32)
        )
    if pg.rank != 0:
        return
    events = telemetry.load_events(os.path.join(path, ".telemetry.jsonl"))
    takes = [e for e in events if e.get("kind") == "take"]
    restores = [e for e in events if e.get("kind") == "restore"]
    assert takes and restores
    for ev in takes + restores:
        cp = ev.get("critical_path")
        assert cp, f"rank {ev.get('rank')} {ev['kind']} lacks critical_path"
        assert cp["coverage"] >= 0.95
        assert sum(cp["segments"].values()) >= 0.95 * cp["wall_s"]
    # A coordinated 2-proc take spends wall in the commit barrier: the
    # coordination segment must be attributed somewhere in the window.
    agg = [e for e in takes if e.get("aggregated")]
    assert agg, "rank 0's take report carries no cross-rank aggregate"
    folded = agg[-1]["aggregated"]
    critpath_keys = [k for k in folded if k.startswith("critpath_")]
    assert critpath_keys, f"no critpath fold in {sorted(folded)}"
    spread = folded[critpath_keys[0]]
    assert {"min", "median", "max", "straggler"} <= set(spread)


@pytest.mark.slow
def test_two_process_take_and_fanout_restore_meet_coverage_bar(tmp_path):
    run_multiprocess(
        _worker_take_restore_critpath, nproc=2, args=(str(tmp_path / "s"),)
    )


def test_peer_served_restore_attributes_peer_segment(tmp_path):
    """The peer -> fast -> durable ladder, peer-served: blob reads gated
    by ``peer:pull`` must attribute to the ``peer`` segment (and still
    meet the coverage bar)."""
    import glob as _glob
    import threading

    from torchsnapshot_tpu.dist_store import (
        InProcessStore,
        publish_endpoint,
    )
    from torchsnapshot_tpu.scheduler import PeerCacheBudget
    from torchsnapshot_tpu.tiered import peer

    path = str(tmp_path / "snap")
    with knobs.enable_peer_tier(), knobs.enable_telemetry():
        store = InProcessStore()
        rep = peer.get_replicator()
        assert rep.configure(store, rank=0, world_size=2)
        rank1_cache = peer.PeerCache(budget=PeerCacheBudget(1 << 30))
        server = peer._PeerServer(("127.0.0.1", 0), rank1_cache)
        threading.Thread(target=server.serve_forever, daemon=True).start()
        try:
            publish_endpoint(
                store,
                peer.PEER_SERVICE,
                1,
                "127.0.0.1",
                server.server_address[1],
            )
            state = {
                "m": ts.PyTreeState(
                    {"w": np.arange(50_000, dtype=np.float32)}
                )
            }
            ts.Snapshot.take(path, state)
            assert rep.drain(timeout=60)
            for blob in _glob.glob(os.path.join(path, "m", "*")):
                os.remove(blob)
            dest = {
                "m": ts.PyTreeState(
                    {"w": np.zeros(50_000, dtype=np.float32)}
                )
            }
            ts.Snapshot(path).restore(dest)
            np.testing.assert_array_equal(
                dest["m"].tree["w"], np.arange(50_000, dtype=np.float32)
            )
        finally:
            peer.reset_peer_tier()
            server.shutdown()
            server.server_close()
    events = telemetry.load_events(os.path.join(path, ".telemetry.jsonl"))
    restore = [e for e in events if e.get("kind") == "restore"][-1]
    cp = _assert_coverage(restore)
    assert cp["segments"].get("peer", 0.0) > 0.0
    assert any(c["segment"] == "peer" for c in cp["chain"])


# ---------------------------------------------------------------------------
# Merged-doc attribution: stitched wire descent
# ---------------------------------------------------------------------------


def test_doc_attribution_descends_stitched_wire_to_peer_frames():
    """An interval gated by ``wire:rpc`` resolves to whatever the
    serving peer's handler was inside (here its disk read) — a 'slow
    RPC' names the peer's storage, not the socket."""

    def B(pid, tid, name, ts_us, args=None):
        return {
            "ph": "B",
            "pid": pid,
            "tid": tid,
            "name": name,
            "ts": ts_us,
            "args": args or {},
        }

    def E(pid, tid, ts_us):
        return {"ph": "E", "pid": pid, "tid": tid, "ts": ts_us}

    rpc_args = {"span_id": "s1", "trace_id": "t1", "op": "fetch"}
    handler_args = {"parent_span_id": "s1", "trace_id": "t1"}
    doc = {
        "traceEvents": [
            B(0, 1, names.SPAN_TAKE, 0),
            B(0, 1, names.SPAN_WIRE_RPC, 1_000, rpc_args),
            B(1, 7, names.SPAN_WIRE_HANDLER, 1_500, handler_args),
            B(1, 7, names.SPAN_STORAGE_READ, 2_000, {"blob": "0/w"}),
            E(1, 7, 8_000),
            E(1, 7, 8_500),
            E(0, 1, 9_000),
            E(0, 1, 10_000),
        ]
    }
    cp = critpath.critical_path_from_doc(doc, "take")
    assert cp is not None
    assert cp["dominant"] == "read_drain"
    assert cp["segments"]["read_drain"] > 0.0
    assert "wire" not in cp["segments"] or (
        cp["segments"]["wire"] < cp["segments"]["read_drain"]
    )
    cited = [c for c in cp["chain"] if c["span"] == names.SPAN_STORAGE_READ]
    assert cited and cited[0]["blob"] == "0/w"


def test_doc_attribution_without_stitch_keeps_wire_segment():
    doc = {
        "traceEvents": [
            {"ph": "B", "pid": 0, "tid": 1, "name": names.SPAN_TAKE, "ts": 0},
            {
                "ph": "B",
                "pid": 0,
                "tid": 1,
                "name": names.SPAN_WIRE_RPC,
                "ts": 100,
                "args": {"span_id": "sX", "trace_id": "tX"},
            },
            {"ph": "E", "pid": 0, "tid": 1, "ts": 900},
            {"ph": "E", "pid": 0, "tid": 1, "ts": 1_000},
        ]
    }
    cp = critpath.critical_path_from_doc(doc, "take")
    assert cp["segments"]["wire"] == pytest.approx(0.0008)


# ---------------------------------------------------------------------------
# Self-time (trace summary satellite)
# ---------------------------------------------------------------------------


def test_spans_from_chrome_reports_self_time():
    from torchsnapshot_tpu.telemetry.trace import (
        longest_spans_from_doc,
        spans_from_chrome,
        summarize_merged,
    )

    doc = {
        "traceEvents": [
            {"ph": "B", "pid": 0, "tid": 1, "name": "parent", "ts": 0},
            {"ph": "B", "pid": 0, "tid": 1, "name": "child", "ts": 10_000},
            {"ph": "E", "pid": 0, "tid": 1, "ts": 90_000},
            {"ph": "E", "pid": 0, "tid": 1, "ts": 100_000},
        ]
    }
    by = {s["name"]: s for s in spans_from_chrome(doc)}
    assert by["parent"]["dur_us"] == 100_000
    assert by["parent"]["self_us"] == 20_000
    assert by["child"]["self_us"] == 80_000
    tops = longest_spans_from_doc(doc, 2)
    assert tops[0]["name"] == "parent"
    assert tops[0]["dur_ms"] == 100.0
    assert tops[0]["self_ms"] == 20.0
    summary = summarize_merged(doc)
    assert "self" in summary
    # The self-time listing surfaces the real culprit (child), not the
    # envelope that merely contains it.
    assert "top self-time spans" in summary


# ---------------------------------------------------------------------------
# Diff CLI: injected slow plugin -> write_drain, with span citations
# ---------------------------------------------------------------------------


async def _none_coro():
    # Stands in for write_with_checksum: None routes the scheduler to
    # the two-step fallback, which lands in write() -> _write_impl.
    return None


def test_diff_cli_attributes_injected_storage_slowdown(
    tmp_path, monkeypatch, capsys
):
    from torchsnapshot_tpu.storage_plugins.fs import FSStoragePlugin

    before = str(tmp_path / "before")
    after = str(tmp_path / "after")
    state = {
        "m": ts.PyTreeState({"w": np.arange(100_000, dtype=np.float32)})
    }
    with knobs.enable_telemetry():
        ts.Snapshot.take(before, state)
        # Patch below the accounting boundary: write() opens the
        # storage:write span and delegates to _write_impl, so a sleep
        # here is a slowdown *inside* the instrumented storage layer —
        # exactly what the diff CLI must pin on write_drain.
        orig_write = FSStoragePlugin._write_impl

        async def slow_write(self, write_io):
            await asyncio.sleep(0.1)
            await orig_write(self, write_io)

        monkeypatch.setattr(FSStoragePlugin, "_write_impl", slow_write)
        monkeypatch.setattr(
            FSStoragePlugin,
            "write_with_checksum",
            lambda self, write_io: _none_coro(),
        )
        ts.Snapshot.take(after, state)
    rc = stats_main(["diff", before, after, "--kind", "take"])
    out = capsys.readouterr().out
    assert rc == 2
    assert "write_drain" in out
    assert "REGRESSED" in out
    # Span-level evidence citation for the regressed segment.
    assert "gating spans" in out
    assert "storage:" in out
    # JSON mode carries the same verdict machine-readably.
    rc = stats_main(["diff", before, after, "--kind", "take", "--json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 2
    assert doc["regressed"][0]["segment"] == "write_drain"
    assert doc["evidence"]


def test_diff_cli_unusable_operand_exits_1(tmp_path, capsys):
    assert stats_main(["diff", str(tmp_path), str(tmp_path)]) == 1
    assert "no report found" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# Bench differential: quiet on round-to-round drift, fires on a doctored pair
# ---------------------------------------------------------------------------


def _bench_pair():
    """Two synthetic parsed records carrying every signal leg: the second
    is the first with each leg drifted by 15-30 % in alternating
    directions, the size of round-to-round noise between two runs of
    the same code."""
    before = {
        "value": 0.24,
        "restore_gbps": 0.15,
        "cold_restore_gbps": 0.6,
        "async_visible_s": 24.0,
        "cold_start_sync_s": 1.3,
        "fanout_restore_s": 2.3,
        "fallback_restore_s": 0.8,
        "peer_recovery_wall_s": 0.4,
        "pipeline_efficiency": 0.6,
        "steady_state_final_efficiency": 0.5,
        "write_path_zero_pack_speedup": 2.7,
        "incremental_speedup": 2.2,
    }
    assert set(before) == set(critpath.BENCH_LEGS)
    drift = (1.3, 0.75, 1.15, 0.85)
    after = {
        leg: round(v * drift[i % len(drift)], 4)
        for i, (leg, v) in enumerate(before.items())
    }
    return before, after


def test_bench_regressions_quiet_on_drift():
    """Drift with no code change behind it must stay quiet under the
    declared tolerances, whichever way each leg moved."""
    before, after = _bench_pair()
    assert critpath.bench_regressions([("a", before), ("b", after)]) == []
    assert critpath.bench_regressions([("b", after), ("a", before)]) == []


def test_bench_regression_fires_on_doctored_pair(tmp_path, capsys):
    before, after = _bench_pair()
    doctored = dict(after)
    doctored["value"] = round(after["value"] * 0.2, 4)  # 5x slowdown
    rows = critpath.bench_regressions([("before", before), ("doctored", doctored)])
    assert [r["leg"] for r in rows] == ["value"]
    assert rows[0]["baseline_records"] == ["before"]
    verdicts = critpath.bench_verdicts(rows)
    assert verdicts[0].rule == names.RULE_BENCH_REGRESSION

    # CLI bench mode end-to-end on temp records.
    a = tmp_path / "BENCH_r90.json"
    b = tmp_path / "BENCH_r91.json"
    ok = tmp_path / "BENCH_r92.json"
    a.write_text(json.dumps({"parsed": before}))
    b.write_text(json.dumps({"parsed": doctored}))
    ok.write_text(json.dumps({"parsed": after}))
    assert stats_main(["diff", str(a), str(b)]) == 2
    out = capsys.readouterr().out
    assert "REGRESSED" in out and names.RULE_BENCH_REGRESSION in out
    assert stats_main(["diff", str(a), str(ok)]) == 0


def test_bench_skipped_leg_zero_is_not_a_regression():
    """A leg recorded 0.0 (budget-gated / failed leg) is absent, not a
    collapse to zero — in the newest record AND in baselines."""
    base = {"value": 0.2, "pipeline_efficiency": 0.6}
    rows = critpath.bench_regressions(
        [("a", base), ("b", {"value": 0.2, "pipeline_efficiency": 0.0})]
    )
    assert rows == []
    rows = critpath.bench_regressions(
        [
            ("a", {"value": 0.0}),
            ("b", {"value": 0.2}),
            ("c", {"value": 0.21}),
        ]
    )
    assert rows == []


# ---------------------------------------------------------------------------
# Trend integration: shifted dominants, critpath series, doctor rules
# ---------------------------------------------------------------------------


def _hist_row(kind, dominant, step, seconds=1.0):
    return {
        "kind": kind,
        "step": step,
        "path": f"/root/step_{step}",
        "critpath": {
            "dominant": dominant,
            "coverage": 1.0,
            "segments": {dominant: seconds},
        },
    }


def test_detect_critical_path_shifts_flags_moved_dominant():
    records = [_hist_row("take", "write_drain", i) for i in range(4)]
    records.append(_hist_row("take", "coordination", 4, seconds=2.5))
    rows = critpath.detect_critical_path_shifts(records)
    assert len(rows) == 1
    row = rows[0]
    assert row["dominant"] == "coordination"
    assert row["previous_dominant"] == "write_drain"
    assert row["baseline_share"] == 1.0
    assert row["dominant_s"] == 2.5
    # Stable history: quiet.
    stable = [_hist_row("take", "write_drain", i) for i in range(6)]
    assert critpath.detect_critical_path_shifts(stable) == []
    # Kinds are separate populations: a restore dominated by read_drain
    # must not count against the take baseline.
    mixed = [_hist_row("take", "write_drain", i) for i in range(4)]
    mixed.append(_hist_row("restore", "read_drain", 4))
    assert critpath.detect_critical_path_shifts(mixed) == []


def test_doctor_trend_emits_critical_path_shifted_verdict():
    records = [_hist_row("take", "write_drain", i) for i in range(4)]
    records.append(_hist_row("take", "coordination", 4))
    verdicts = diagnose_trend(records)
    shifted = [
        v for v in verdicts if v.rule == names.RULE_CRITICAL_PATH_SHIFTED
    ]
    assert len(shifted) == 1
    assert "coordination" in shifted[0].summary
    assert shifted[0].evidence["previous_dominant"] == "write_drain"


def test_trend_series_cover_critpath_segments():
    """History rows' critical-path segments feed ``critpath_<seg>_s``
    trend series — a segment that balloons regresses even when the
    total wall is absorbed elsewhere."""
    records = [
        {
            "kind": "take",
            "step": i,
            "take_s": 2.0,
            "critpath": {
                "dominant": "write_drain",
                "segments": {"write_drain": 1.0, "staging": 0.5},
            },
        }
        for i in range(4)
    ]
    records.append(
        {
            "kind": "take",
            "step": 4,
            "take_s": 2.0,
            "critpath": {
                "dominant": "write_drain",
                "segments": {"write_drain": 1.9, "staging": 0.5},
            },
        }
    )
    rows = detect_trend_regressions(records)
    metrics = {r["metric"] for r in rows}
    assert "critpath_write_drain_s" in metrics
    assert "critpath_staging_s" not in metrics


def test_new_rule_ids_are_registered_and_kebab_case():
    ids = registered_rule_ids()
    for rid in (
        names.RULE_CRITICAL_PATH_SHIFTED,
        names.RULE_BENCH_REGRESSION,
    ):
        assert rid in ids
        assert re.fullmatch(r"[a-z0-9]+(-[a-z0-9]+)*", rid)


def test_history_rows_carry_critpath_summary(tmp_path):
    """summarize_report folds the report's critical_path into the
    history row (dominant + coverage + rounded segments)."""
    from torchsnapshot_tpu.telemetry.history import summarize_report
    from torchsnapshot_tpu.telemetry.report import SnapshotReport

    report = SnapshotReport(kind="take", path=str(tmp_path), rank=0)
    report.critical_path = {
        "wall_s": 1.0,
        "coverage": 1.0,
        "segments": {"write_drain": 0.75, "other": 0.25},
        "dominant": "write_drain",
        "chain": [],
    }
    row = summarize_report(report, step=7)
    assert row["critpath"]["dominant"] == "write_drain"
    assert row["critpath"]["segments"]["write_drain"] == 0.75
    none_report = SnapshotReport(kind="take", path=str(tmp_path), rank=0)
    assert summarize_report(none_report, step=8)["critpath"] is None

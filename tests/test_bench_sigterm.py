"""The bench record must survive a driver kill.

Round 4's signal of record died as ``rc: 124, parsed: null``: the driver
SIGTERMed ``bench.py`` before its single end-of-run emission point. The
round-5 redesign promises that ANY termination still yields a parsed
final JSON line (``complete: false``, ``terminated_by``) plus rolling
``bench-partial:`` lines. This test pins that contract end-to-end: it
launches the real ``bench.py`` (tiny state, CPU backend), waits for the
first partial emission, SIGTERMs the process mid-run — exactly what
``timeout(1)`` does — and asserts the record came out anyway.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
BENCH = REPO / "bench.py"


def test_sigterm_mid_run_still_emits_parsed_record(tmp_path):
    bench_md_before = (REPO / "BENCH.md").read_bytes()
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        # Overrides set => bench must NOT rewrite BENCH.md's block.
        TS_BENCH_GB="0.001",
        TS_BENCH_SKIP_PROTOCOL="1",
        TS_BENCH_PARTIAL_PATH=str(tmp_path / "BENCH_partial.json"),
        TMPDIR=str(tmp_path),
        # Not the checkout's .jax_cache: test_chip_smoke.py, on another
        # worker, holds that directory to what it was.
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
    )
    proc = subprocess.Popen(
        [sys.executable, str(BENCH)],
        cwd=str(tmp_path),
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL,
        text=True,
    )
    # Hard watchdog: readline() below blocks, so a wedged bench.py (no
    # stdout at all) would otherwise hang the whole test session.
    watchdog = threading.Timer(120, proc.kill)
    watchdog.start()
    lines = []
    saw_partial = False
    deadline = time.monotonic() + 120
    try:
        while time.monotonic() < deadline:
            line = proc.stdout.readline()
            if not line:
                break
            lines.append(line.rstrip("\n"))
            if line.startswith("bench-partial: "):
                saw_partial = True
                proc.send_signal(signal.SIGTERM)
                break
        assert saw_partial, f"no bench-partial line before timeout: {lines}"
        # Drain remaining stdout; the handler writes the bare record line.
        rest, _ = proc.communicate(timeout=60)
        lines += rest.splitlines()
    finally:
        watchdog.cancel()
        if proc.poll() is None:
            proc.kill()
            proc.wait()

    assert proc.returncode == 128 + signal.SIGTERM, lines[-3:]

    bare = [
        ln for ln in lines if ln.startswith("{") and not ln.startswith("bench-partial")
    ]
    assert bare, f"no final bare JSON line emitted: {lines[-5:]}"
    record = json.loads(bare[-1])
    assert record["metric"] == "checkpoint_save_throughput"
    assert record["complete"] is False
    assert record["terminated_by"] == "SIGTERM"
    # The partial line that triggered the kill parses too, and the two
    # agree on the leg structure.
    partial = json.loads(
        next(ln for ln in lines if ln.startswith("bench-partial: ")).split(
            "bench-partial: ", 1
        )[1]
    )
    assert partial["metric"] == "checkpoint_save_throughput"
    assert "last_leg" in partial

    # Non-default run (TS_BENCH_* overrides): the committed doc block is
    # untouched even on the termination path.
    assert (REPO / "BENCH.md").read_bytes() == bench_md_before

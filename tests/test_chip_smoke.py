"""chip_smoke.py's contract, as far as a machine without a chip can check
it: the explicit CPU rehearsal drives both child processes through the
shipped configuration (conftest's ``TORCHSNAPSHOT_TPU_*`` pins are
stripped), and without the flag — or with a knob set — the script
refuses instead of passing on the CPU."""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
SMOKE = str(REPO / "chip_smoke.py")


def _run(tmp_path, *args, **extra_env):
    env = {
        k: v
        for k, v in os.environ.items()
        if not k.startswith("TORCHSNAPSHOT_TPU_")
    }
    # Four virtual devices: phase A saves on (dp, sp, tp) = (1, 2, 2) and
    # phase B resumes on (1, 1, 4), so the reshard path is rehearsed too.
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
        TMPDIR=str(tmp_path),
        **extra_env,
    )
    return subprocess.run(
        [sys.executable, SMOKE, *args],
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
    )


def test_cpu_rehearsal_passes_and_cannot_be_read_as_a_pass(tmp_path):
    default_cache = REPO / ".jax_cache"
    before = sorted(os.listdir(default_cache)) if default_cache.exists() else None
    proc = _run(tmp_path, "--cpu-rehearsal")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    out = proc.stdout
    last = json.loads(out.strip().splitlines()[-1])
    assert "ok" not in last
    assert last["rehearsal"] == "passed"
    assert last["device"] == {"platform": "cpu", "kind": "cpu", "count": 4}
    assert "[a] platform=cpu" in out and "[b] platform=cpu" in out
    assert "resuming on {'dp': 1, 'sp': 1, 'tp': 4}" in out
    assert "leaves bitwise equal to phase A's step" in out
    assert "attn_impl='flash'" in out
    # The cache went where the environment said and nowhere else, and
    # the second process compiled less than the first.
    new = dict(
        re.findall(r"\[([ab])\] compile cache: \d+ -> \d+ entries \((\d+) new\)", out)
    )
    assert 0 < int(new["b"]) < int(new["a"])
    assert any(n.endswith("-cache") for n in os.listdir(tmp_path / "cache"))
    after = sorted(os.listdir(default_cache)) if default_cache.exists() else None
    assert after == before
    # The snapshot scratch directory is removed at exit.
    assert not list(tmp_path.glob("ts_chip_smoke_*"))


def test_refuses_to_pass_on_cpu_without_the_flag(tmp_path):
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert "jax.devices()[0].platform is 'cpu', need 'tpu'" in proc.stdout
    assert '"ok"' not in proc.stdout
    assert not list(tmp_path.glob("ts_chip_smoke_*"))


def test_refuses_a_set_knob(tmp_path):
    proc = _run(tmp_path, "--cpu-rehearsal", TORCHSNAPSHOT_TPU_CAS="1")
    assert proc.returncode != 0
    assert "TORCHSNAPSHOT_TPU_CAS" in proc.stdout
    assert "[a]" not in proc.stdout  # no phase was started

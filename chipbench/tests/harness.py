"""Shared by the tests beside it: a throwaway copy of the benchmark that a
test may add files and entries to, and the command run on it at toy size."""

import json
import os
import shutil
import subprocess
import sys
from typing import Any, Dict, List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def copy_benchmark(tmp_path) -> str:
    """BENCHMARK.json and chipbench/ copied under `tmp_path`, the package
    linked beside them: what a later PR's checkout looks like to run.py."""
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(REPO, "chipbench"), os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(REPO, "torchsnapshot_tpu"), os.path.join(root, "torchsnapshot_tpu"))
    return root


def edit_benchmark(root: str, edit) -> None:
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    edit(bench)
    with open(path, "w") as f:
        json.dump(bench, f)


def run_cell(root: str, workload: str, *extra: str, devices: int = 1, seed: int = 2147483659,
             trace: int = 0, env: Optional[Dict[str, str]] = None,
             rehearse: bool = True) -> Tuple[int, Optional[Dict[str, Any]], str]:
    """(exit code, the last stdout line as JSON or None, stderr)."""
    full_env = {k: v for k, v in os.environ.items() if not k.startswith("TORCHSNAPSHOT_TPU_")}
    full_env.update(JAX_PLATFORMS="cpu",
                    XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
                    JAX_COMPILATION_CACHE_DIR=os.path.join(REPO, ".jax_cache"))
    full_env.update(env or {})
    cmd: List[str] = [sys.executable, os.path.join(root, "chipbench", "run.py"),
                      "--workload", workload, "--seed", str(seed), "--seconds", "1",
                      "--trace", str(trace), *extra]
    if rehearse:
        cmd.append("--rehearse")
    proc = subprocess.run(cmd, cwd=root, env=full_env, capture_output=True, text=True, timeout=600)
    lines = proc.stdout.strip().splitlines()
    result = None
    if lines:
        try:
            result = json.loads(lines[-1])
        except ValueError:
            result = None
    return proc.returncode, result, proc.stderr


def storage_root(root: str, traffic: str = "resume") -> str:
    """The storage root that the checkout at `root` claims for a traffic mix."""
    sys.path.insert(0, os.path.join(REPO, "chipbench"))
    import cells
    import storage

    tag = storage.root_of(cells.traffic(traffic)["storage"], checkout=root)
    return tag


def end_to_end_of(root: str, workload: str) -> List[str]:
    """Names of the end-to-end metrics BENCHMARK.json at `root` gives the cell."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return [m["name"] for m in bench["end_to_end"]
            if "workloads" not in m or workload in m["workloads"]]


def layer_reader(name: str):
    sys.path.insert(0, os.path.join(REPO, "chipbench"))
    import cells

    return cells.layer_reader(name)

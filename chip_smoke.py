"""Chip smoke: train -> async save -> kill -> resume, on the TPU.

The quickest proof that the library's main path still starts on the chip,
through the entry points a user calls (README.md): the flagship model at
the pod recipe's width (benchmarks/pod/README.md: ``d_model=4096``), depth
cut to fit one 16 GB chip, random weights from a seed.

    python chip_smoke.py                 # needs a TPU; exits non-zero without
    python chip_smoke.py --cpu-rehearsal # tiny model on CPU; never a pass

This parent process imports neither jax nor the package (a process that
has touched JAX holds the chip) and runs two children in sequence:

- phase A (train + save): train, sync save with digests, save the same
  state again (device digests must agree with themselves: ~0 bytes
  rewritten), train on, ``CheckpointManager.async_save`` while training
  continues on donated buffers, wait, commit marker present. Records a
  per-leaf sha256 of the saved step and the losses of the steps after it.
- phase B (resume): a fresh process, a different seed, one mesh axis laid
  out differently when there are several devices, ``restore_latest``,
  sha256 equal to phase A's, the same steps replayed with equal losses,
  then one step through the Pallas flash kernel compiled natively.

It runs the shipped configuration (no ``TORCHSNAPSHOT_TPU_*`` variable may
be set) and nothing on its path may hide a missing chip: any package log
record at WARNING or above, a missing native I/O library, or a failed
phase fails the run. The last stdout line of a pass is one JSON object
naming the device as JAX reports it.
"""

import argparse
import glob
import hashlib
import json
import logging
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time
from typing import NoReturn

HERE = os.path.dirname(os.path.abspath(__file__))
PHASE_TIMEOUT_S = 540  # two phases + start-up stay inside the 1200 s contract
SEED = 20260926

# Width is the pod recipe's and is not cut; depth is. Per layer 12*d^2 =
# 201.3 M params, embed + unembed 268.4 M; params, mu and nu are bf16, so
# 6 B/param: 4 layers = 1073.7 M params = 6.00 GiB of train state, 12.0 GiB
# while the async save's on-device clone lives. Measured peak on a v5e chip
# (15.75 GiB usable): 12.11 GiB, so 4 layers fill the chip as far as a job
# that saves through the device clone can fill it; 5 would not fit.
CONFIG = dict(
    vocab_size=32768, d_model=4096, n_heads=64, d_ff=16384, n_layers=4,
    batch=4, seq=512,
)
# The rehearsal keeps head_dim 64, a seq the flash kernel accepts and
# heads that divide over four (virtual) devices.
REHEARSAL_CONFIG = dict(
    vocab_size=512, d_model=256, n_heads=4, d_ff=1024, n_layers=2,
    batch=4, seq=128,
)
BASE_STEP = 2  # sync save + unchanged-state save here
SAVE_STEP = 4  # async save here, training continues
REPLAY_STEPS = 3  # steps after SAVE_STEP that phase B replays
# One more step after those: phase B runs it through attn_impl="flash" and
# compares with phase A's default-attention loss.
LOSS_RTOL = 1e-2  # bf16 model: changed layout / other attention kernel


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> NoReturn:
    log(f"chip_smoke: FAIL: {msg}")
    sys.exit(1)


def fs_type(path: str) -> str:
    path = os.path.realpath(path)
    best, kind = "", "unknown"
    with open("/proc/mounts") as f:
        for line in f:
            _, mount, fstype = line.split()[:3]
            if (path == mount or path.startswith(mount.rstrip("/") + "/")) and len(
                mount
            ) >= len(best):
                best, kind = mount, fstype
    return kind


def tree_bytes(path: str) -> int:
    total = 0
    for root, _, files in os.walk(path):
        total += sum(os.path.getsize(os.path.join(root, f)) for f in files)
    return total


# ----------------------------------------------------------------------
# parent
# ----------------------------------------------------------------------


def run_phase(phase: str, workdir: str, rehearsal: bool) -> dict:
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--workdir", workdir]
    env = dict(os.environ)
    if rehearsal:
        cmd.append("--cpu-rehearsal")
        env["JAX_PLATFORMS"] = "cpu"
    sys.stdout.flush()
    # Own session: a timeout or an interrupt takes the whole group down,
    # g++ and anything else the child started included.
    proc = subprocess.Popen(cmd, env=env, cwd=HERE, start_new_session=True)
    try:
        rc = proc.wait(timeout=PHASE_TIMEOUT_S)
    except subprocess.TimeoutExpired:
        fail(f"phase {phase} exceeded {PHASE_TIMEOUT_S} s")
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    if rc != 0:
        fail(f"phase {phase} exited {rc}")
    with open(os.path.join(workdir, f"phase_{phase}.json")) as f:
        return json.load(f)


def parent(rehearsal: bool) -> None:
    knobs = sorted(k for k in os.environ if k.startswith("TORCHSNAPSHOT_TPU_"))
    if knobs:
        fail(f"the smoke runs the shipped configuration; unset {knobs}")
    if not os.path.isdir(os.path.join(HERE, "torchsnapshot_tpu")):
        fail(f"no torchsnapshot_tpu package beside {__file__}")
    workdir = tempfile.mkdtemp(prefix="ts_chip_smoke_")
    kind = fs_type(workdir)
    log(f"chip_smoke: snapshots under {workdir} (filesystem: {kind})"
        + (" -- tmpfs: 'storage' seconds below are memcpy seconds"
           if kind in ("tmpfs", "ramfs") else ""))
    t0 = time.monotonic()
    try:
        a = run_phase("a", workdir, rehearsal)
        b = run_phase("b", workdir, rehearsal)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    if a["device"] != b["device"]:
        fail(f"phases saw different devices: {a['device']} vs {b['device']}")
    log(f"chip_smoke: both phases passed in {time.monotonic() - t0:.1f} s "
        f"(A {a['phase_s']:.1f} s, B {b['phase_s']:.1f} s)")
    if rehearsal:
        # No "ok" key: a rehearsal is not a pass.
        log(json.dumps({"rehearsal": "passed", "device": a["device"]}))
    else:
        log(json.dumps({"ok": True, "device": a["device"]}))


# ----------------------------------------------------------------------
# children (the only code here that imports jax or the package)
# ----------------------------------------------------------------------


# The SLO engine ships on and judges checkpoint overhead per commit
# interval against a 10 % budget. A smoke that saves 4 GB every two steps
# breaches it by construction, and the breach captures an incident bundle;
# both log at WARNING. They are verdicts on the cadence, not fallbacks, so
# they are printed and do not fail the run. Nothing else is exempt.
VERDICT_LOGGERS = (
    "torchsnapshot_tpu.telemetry.slo",
    "torchsnapshot_tpu.telemetry.bundle",
)


class _WarningTrap(logging.Handler):
    """Every fallback on the data path warns and carries on (host copy
    for a failed device clone, host digests, pure-Python I/O). On the
    smoke path carrying on is a failure."""

    def __init__(self) -> None:
        super().__init__(level=logging.WARNING)
        self.records = []

    def emit(self, record: logging.LogRecord) -> None:
        line = f"{record.name}: {record.getMessage()}"
        if record.name in VERDICT_LOGGERS:
            log(f"chip_smoke: verdict (not a fallback): {line}")
        else:
            self.records.append(line)


class Child:
    def __init__(self, phase: str, workdir: str, rehearsal: bool) -> None:
        self.t_start = time.monotonic()
        self.phase, self.workdir = phase, workdir
        self.root = os.path.join(workdir, "ckpt")
        self.cfg_dict = REHEARSAL_CONFIG if rehearsal else CONFIG
        self.trap = _WarningTrap()
        logging.getLogger("torchsnapshot_tpu").addHandler(self.trap)

        from benchmarks.common import jax, place_compile_cache

        self.jax = jax
        self.cache_dir = place_compile_cache()
        self.cache_before = self.cache_entries()
        dev = jax.devices()[0]
        self.device = {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())}
        want = "cpu" if rehearsal else "tpu"
        if dev.platform != want:
            fail(f"jax.devices()[0].platform is {dev.platform!r}, need {want!r}"
                 + ("" if rehearsal else
                    " (no accelerator: nothing here may run on the CPU, in "
                    "interpret mode or through pure-Python I/O and pass)"))
        import importlib.metadata as md

        import jaxlib

        try:
            libtpu = md.version("libtpu")
        except md.PackageNotFoundError:
            libtpu = "absent"
        log(f"[{phase}] platform={dev.platform} device_kind={dev.device_kind} "
            f"device_count={len(jax.devices())} jax={jax.__version__} "
            f"jaxlib={jaxlib.__version__} libtpu={libtpu}")
        log(f"[{phase}] compile cache {self.cache_dir}: "
            f"{self.cache_before} entries at start")

        from torchsnapshot_tpu import _native

        so_glob = os.path.join(os.path.dirname(_native._SRC_PATH), "_ts_io_*.so")
        had_so = bool(glob.glob(so_glob))
        if _native.lib() is None:
            fail("native I/O library did not build or load; the pure-Python "
                 "I/O path is not the shipped path")
        log(f"[{phase}] native_io: {'loaded' if had_so else 'built'}")

        from torchsnapshot_tpu.models import TransformerConfig

        c = self.cfg_dict
        self.cfg = TransformerConfig(
            vocab_size=c["vocab_size"], d_model=c["d_model"],
            n_heads=c["n_heads"], n_layers=c["n_layers"], d_ff=c["d_ff"],
        )

    def cache_entries(self) -> int:
        return len(glob.glob(os.path.join(self.cache_dir, "*-cache")))

    def tokens(self, step: int, mesh):
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P

        c = self.cfg_dict
        host = np.random.default_rng(SEED + step).integers(
            0, c["vocab_size"], (c["batch"], c["seq"]), dtype=np.int32
        )
        return self.jax.device_put(host, NamedSharding(mesh, P("dp", None)))

    def app_state(self, state) -> dict:
        import torchsnapshot_tpu as ts

        return {
            "params": ts.PyTreeState(state.params),
            "opt": ts.PyTreeState(state.opt_state),
            "progress": ts.StateDict(step=int(state.step)),
            "rng": ts.RngState(state.rng),
        }

    def leaf_sha256(self, state) -> dict:
        import numpy as np

        tree = {"params": state.params, "opt": state.opt_state, "rng": state.rng}
        flat, _ = self.jax.tree_util.tree_flatten_with_path(tree)
        # uint8 view: ml_dtypes (bf16) arrays refuse the buffer protocol.
        return {
            self.jax.tree_util.keystr(path): hashlib.sha256(
                np.ascontiguousarray(np.asarray(leaf)).reshape(-1).view(np.uint8)
            ).hexdigest()
            for path, leaf in flat
        }

    def critical_path(self, kind: str, path: str) -> str:
        """Where the library's own telemetry says an op's wall went."""
        import torchsnapshot_tpu as ts

        cp = ts.telemetry.last_report(kind, path=path).critical_path
        split = ", ".join(f"{k} {v:.2f}" for k, v in cp["segments"].items() if v >= 0.005)
        return f"[{self.phase}]   its critical path: {cp['wall_s']:.2f} s = {split}"

    def hbm(self, tag: str) -> None:
        stats = [d.memory_stats() or {} for d in self.jax.devices()]
        peaks = [s.get("peak_bytes_in_use") for s in stats]
        shown = ["not reported" if p is None else f"{p / 2**30:.2f}" for p in peaks]
        limit = stats[0].get("bytes_limit")
        log(f"[{self.phase}] {tag}: peak_bytes_in_use per device (GiB): {shown}"
            + (f" of {limit / 2**30:.2f}" if limit else ""))

    def finish(self, result: dict) -> None:
        if self.trap.records:
            for r in self.trap.records:
                log(f"[{self.phase}] package warning: {r}")
            fail(f"{len(self.trap.records)} package log record(s) at WARNING or "
                 "above: a fallback was taken")
        after = self.cache_entries()
        log(f"[{self.phase}] compile cache: {self.cache_before} -> {after} entries "
            f"({after - self.cache_before} new)")
        result.update(device=self.device, phase_s=time.monotonic() - self.t_start)
        with open(os.path.join(self.workdir, f"phase_{self.phase}.json"), "w") as f:
            json.dump(result, f)
        log(f"[{self.phase}] phase passed in {result['phase_s']:.1f} s")


def phase_a(ch: Child) -> None:
    import numpy as np

    import torchsnapshot_tpu as ts
    from torchsnapshot_tpu.models import (
        init_train_state, make_mesh, make_train_step,
    )

    jax, cfg = ch.jax, ch.cfg
    mesh = make_mesh()
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    t0 = time.monotonic()
    state = init_train_state(cfg, seed=SEED, mesh=mesh)
    jax.block_until_ready(state)
    init_s = time.monotonic() - t0
    leaves = jax.tree_util.tree_leaves(state)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state.params))
    state_bytes = sum(x.nbytes for x in leaves)
    log(f"[a] config {ch.cfg_dict} mesh {mesh_shape}: {n_params / 1e6:.1f} M params, "
        f"train state {state_bytes / 2**30:.2f} GiB "
        f"({state_bytes / n_params:.1f} B/param), x2 while the async save's "
        f"device clone lives, + {2 * n_params / 2**30:.2f} GiB gradients; "
        f"init {init_s:.1f} s")
    per_dev = {}
    for leaf in leaves:
        for shard in leaf.addressable_shards:
            per_dev[shard.device.id] = per_dev.get(shard.device.id, 0) + shard.data.nbytes
    log(f"[a] state bytes held per device (GiB): "
        f"{ {d: round(b / 2**30, 2) for d, b in sorted(per_dev.items())} }")
    if len(per_dev) != len(jax.devices()) or max(per_dev.values()) > 1.25 * min(
        per_dev.values()
    ):
        fail(f"state is not spread evenly over {len(jax.devices())} devices: {per_dev}")

    step_fn = make_train_step(cfg, mesh=mesh)
    step_s, losses = [], {}

    def train_to(state, last_step: int):
        for step in range(int(state.step) + 1, last_step + 1):
            t0 = time.monotonic()
            state, loss = step_fn(state, ch.tokens(step, mesh))
            losses[step] = float(loss)  # blocks until the step is done
            step_s.append(time.monotonic() - t0)
            if not np.isfinite(losses[step]):
                fail(f"loss at step {step} is {losses[step]}")
        return state

    state = train_to(state, BASE_STEP)
    log(f"[a] first step (compile + run) {step_s[0]:.1f} s, "
        f"next {step_s[1]:.2f} s, losses {losses}")
    ch.hbm("after first steps")

    # Sync save with digests recorded, then the same state again: every
    # device digest must equal the one just recorded, so nothing is
    # rewritten. (The async save below is the other half: every leaf
    # changed, so every byte must be rewritten.)
    mgr = ts.CheckpointManager(ch.root, keep_last_n=3, incremental=True)
    t0 = time.monotonic()
    mgr.save(BASE_STEP, ch.app_state(state))
    sync_save_s = time.monotonic() - t0
    base_bytes = tree_bytes(mgr.step_path(BASE_STEP))
    unchanged_path = os.path.join(ch.workdir, "unchanged")
    t0 = time.monotonic()
    ts.Snapshot.take(
        unchanged_path, ch.app_state(state), record_digests=True,
        incremental_base=mgr.step_path(BASE_STEP),
    )
    unchanged_save_s = time.monotonic() - t0
    unchanged_bytes = tree_bytes(unchanged_path)
    log(f"[a] sync save of step {BASE_STEP}: {sync_save_s:.2f} s, "
        f"{base_bytes / 2**30:.3f} GiB on storage "
        f"({base_bytes / state_bytes:.3f} of state bytes); unchanged-state "
        f"incremental save: {unchanged_save_s:.2f} s, {unchanged_bytes} bytes")
    log(ch.critical_path("take", mgr.step_path(BASE_STEP)))
    if not 0.99 <= base_bytes / state_bytes <= 1.05:
        fail("bytes written are not ~1x the state bytes (replicas written twice, "
             "or bytes missing)")
    # Metadata only: a manifest of references and a checksum table.
    if unchanged_bytes > max(0.01 * base_bytes, 1 << 18):
        fail("unchanged state was rewritten: device digests disagree with themselves")

    state = train_to(state, SAVE_STEP)
    sha = ch.leaf_sha256(state)  # D2H of the whole state, outside any timed span

    # The save under test: returns after the on-device clone, training
    # continues on donated buffers while the clone drains to storage.
    t0 = time.monotonic()
    pending = mgr.async_save(SAVE_STEP, ch.app_state(state))
    visible_s = time.monotonic() - t0
    overlapped = 0
    for step in range(SAVE_STEP + 1, SAVE_STEP + REPLAY_STEPS + 2):
        state = train_to(state, step)
        overlapped += not pending.done()
    pending.wait()
    total_s = time.monotonic() - t0
    marker = os.path.join(mgr.step_path(SAVE_STEP), ".snapshot_metadata")
    if not os.path.exists(marker):
        fail(f"no commit marker at {marker}")
    save_bytes = tree_bytes(mgr.step_path(SAVE_STEP))
    log(f"[a] async save of step {SAVE_STEP}: visible {visible_s:.2f} s, total "
        f"{total_s:.2f} s, {overlapped} of {REPLAY_STEPS + 1} train steps finished "
        f"while it drained, {save_bytes / 2**30:.3f} GiB on storage "
        f"({save_bytes / state_bytes:.3f} of state bytes, every leaf changed)")
    log(ch.critical_path("async_take", mgr.step_path(SAVE_STEP)))
    if mgr.latest_step() != SAVE_STEP:
        fail(f"latest step is {mgr.latest_step()}, expected {SAVE_STEP}")
    if not 0.99 <= save_bytes / state_bytes <= 1.05:
        fail("changed state was not rewritten in full: a device digest "
             "missed a change, or replicas were written twice")
    log(f"[a] steady step {sorted(step_s[1:])[len(step_s[1:]) // 2]:.3f} s (median "
        f"of {len(step_s) - 1}), losses after the save "
        f"{[losses[s] for s in range(SAVE_STEP + 1, SAVE_STEP + REPLAY_STEPS + 2)]}")
    ch.hbm("end of phase")
    ch.finish(dict(
        mesh=mesh_shape, state_bytes=state_bytes, sha256=sha,
        losses={str(k): v for k, v in losses.items()},
    ))


def phase_b(ch: Child) -> None:
    import dataclasses

    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import torchsnapshot_tpu as ts
    from torchsnapshot_tpu.models import (
        TrainState, init_train_state, make_train_step,
    )

    jax, cfg = ch.jax, ch.cfg
    with open(os.path.join(ch.workdir, "phase_a.json")) as f:
        a = json.load(f)
    # Resume with every device on the tp axis: with four devices phase A
    # saved on (1, 2, 2), so every dense leaf is re-boxed on the way in.
    mesh = Mesh(np.asarray(jax.devices()).reshape(1, 1, -1), ("dp", "sp", "tp"))
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    same_layout = mesh_shape == a["mesh"]
    log(f"[b] saved on mesh {a['mesh']}, resuming on {mesh_shape}")

    state = init_train_state(cfg, seed=SEED + 1, mesh=mesh)
    app_state = ch.app_state(state)
    del state  # app_state alone owns the destinations the restore replaces
    mgr = ts.CheckpointManager(ch.root, keep_last_n=3, incremental=True)
    t0 = time.monotonic()
    step = mgr.restore_latest(app_state)
    jax.block_until_ready(app_state["params"].tree)
    restore_s = time.monotonic() - t0
    if step != SAVE_STEP or app_state["progress"]["step"] != SAVE_STEP:
        fail(f"restored step {step} / progress {dict(app_state['progress'])}, "
             f"expected {SAVE_STEP}")
    step_rng = jax.device_put(
        (jnp.asarray(step, jnp.int32), jnp.asarray(app_state["rng"].keys)),
        NamedSharding(mesh, P()),
    )
    state = TrainState(app_state["params"].tree, app_state["opt"].tree, *step_rng)
    log(f"[b] restore_latest -> step {step} in {restore_s:.2f} s "
        f"({a['state_bytes'] / 2**30 / restore_s:.2f} GiB/s)")
    log(ch.critical_path("restore", mgr.step_path(step)))
    t0 = time.monotonic()
    sha = ch.leaf_sha256(state)
    verify_s = time.monotonic() - t0
    bad = sorted(k for k in a["sha256"] if sha.get(k) != a["sha256"][k])
    if bad or len(sha) != len(a["sha256"]):
        fail(f"{len(bad)} of {len(a['sha256'])} leaves differ from the saved step: "
             f"{bad[:5]}")
    log(f"[b] all {len(sha)} leaves bitwise equal to phase A's step {SAVE_STEP} (sha256)")

    step_fn = make_train_step(cfg, mesh=mesh)
    for i in range(1, REPLAY_STEPS + 1):
        t0 = time.monotonic()
        state, loss = step_fn(state, ch.tokens(step + i, mesh))
        got, want = float(loss), a["losses"][str(step + i)]
        if i == 1:
            log(f"[b] first step after resume {time.monotonic() - t0:.1f} s; "
                f"process start to that step {time.monotonic() - ch.t_start:.1f} s, "
                f"{verify_s:.1f} s of it this script's sha256 pass")
        ok = got == want if same_layout else abs(got - want) <= LOSS_RTOL * abs(want)
        log(f"[b] step {step + i}: loss {got!r}, uninterrupted run {want!r}")
        if not ok:
            fail("replayed loss differs from the uninterrupted run's"
                 + (" (same layout: must be identical)" if same_layout else ""))

    # One step through the Pallas flash kernel at this head_dim, compiled
    # natively (the platform check in Child.__init__ came first, so
    # transformer._pallas_interpret() is False on the smoke path).
    last = step + REPLAY_STEPS + 1
    flash_fn = make_train_step(dataclasses.replace(cfg, attn_impl="flash"), mesh=mesh)
    t0 = time.monotonic()
    state, loss = flash_fn(state, ch.tokens(last, mesh))
    got, want = float(loss), a["losses"][str(last)]
    log(f"[b] step {last} with attn_impl='flash' (head_dim {cfg.head_dim}): loss "
        f"{got!r} vs default attention {want!r}, {time.monotonic() - t0:.1f} s "
        "with compile")
    if not abs(got - want) <= LOSS_RTOL * abs(want):
        fail("flash attention loss is outside bf16 tolerance of the default's")
    ch.hbm("end of phase")
    ch.finish({})


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--cpu-rehearsal", action="store_true",
                   help="tiny model on the CPU backend; prints platform=cpu and "
                   "is never a pass")
    p.add_argument("--phase", choices=("a", "b"), help=argparse.SUPPRESS)
    p.add_argument("--workdir", help=argparse.SUPPRESS)
    args = p.parse_args()
    if args.phase is None:
        parent(args.cpu_rehearsal)
        return
    sys.path.insert(0, HERE)
    child = Child(args.phase, args.workdir, args.cpu_rehearsal)
    (phase_a if args.phase == "a" else phase_b)(child)


if __name__ == "__main__":
    main()

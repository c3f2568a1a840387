"""What every driver needs of the workload: the mesh, the train state made
on the device from the seed, tokens by step, the app state the README shows,
and the harness's own device programs (fingerprint, scramble)."""

import json
import os
import time
from typing import Any, Dict, List, Optional

import numpy as np

import reference
from cells import BenchError

# --rehearse: the CPU cannot hold the source's widths; keeps head_dim 64 and
# heads that divide over four virtual devices.
REHEARSAL_SIZES = dict(vocab_size=512, d_model=256, n_heads=4, d_ff=1024, batch=4, seq=128)
REHEARSAL_LAYERS = 2
MARKER = ".snapshot_metadata"
# One retained step: `storage.FREE_OVER_STATE` counts on it.
KEEP_LAST_N = 1


class Context:
    """One run's workload. `jax` and the library are imported by run.py once
    the platform is known, and handed in."""

    def __init__(self, jax, ts, cell: Dict[str, Any], config: Dict[str, Any],
                 traffic: Dict[str, Any], seed: int, root: str, rehearse: bool,
                 fault: Optional[str]) -> None:
        from torchsnapshot_tpu.models import TransformerConfig

        self.jax, self.ts = jax, ts
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.root, self.fault, self.rehearse = seed, root, fault, rehearse
        sizes = dict(
            vocab_size=config["vocab_size"], d_model=config["hidden_size"],
            n_heads=config["num_attention_heads"], d_ff=config["intermediate_size"],
            n_layers=config["num_hidden_layers"], batch=config["batch"], seq=config["seq"])
        if rehearse:
            sizes.update(REHEARSAL_SIZES, n_layers=min(sizes["n_layers"], REHEARSAL_LAYERS))
        self.sizes = sizes
        self.cfg = TransformerConfig(
            vocab_size=sizes["vocab_size"], d_model=sizes["d_model"], n_heads=sizes["n_heads"],
            n_layers=sizes["n_layers"], d_ff=sizes["d_ff"],
        )
        self.nbytes = self.state_bytes(jax.eval_shape(lambda: self.init_state(0, None)))
        self.mesh = self.make_mesh(config["mesh"])
        self.restore_mesh = (self.make_mesh(config["restore_mesh"])
                             if config.get("restore_mesh") else self.mesh)
        self.fingerprint = reference.make_fingerprint(jax)
        self.scramble = jax.jit(
            lambda tree: jax.tree_util.tree_map(_flip_bits(jax), tree), donate_argnums=0)
        self._step_fns: Dict[Any, Any] = {}
        self.stages: Dict[str, float] = {}
        self._stage_t = time.monotonic()

    def stage(self, name: str) -> None:
        """Close one stage of set-up: its seconds go to standard error with the
        result, so that a set-up that grew can be read."""
        now = time.monotonic()
        self.stages[name] = self.stages.get(name, 0.0) + now - self._stage_t
        self._stage_t = now

    def make_mesh(self, shape: List[int]):
        from jax.sharding import Mesh

        n = int(np.prod(shape))
        if n != self.cell["chips"]:
            raise BenchError(f"mesh {shape} has {n} devices, the cell asks for "
                             f"{self.cell['chips']} chips")
        devices = self.jax.devices()[:n]
        return Mesh(np.asarray(devices).reshape(shape), ("dp", "sp", "tp"))

    # -- the workload ---------------------------------------------------

    def init_state(self, seed: int, mesh):
        from torchsnapshot_tpu.models import init_train_state

        return init_train_state(self.cfg, seed=seed, mesh=mesh)

    def step_fn(self, mesh):
        from torchsnapshot_tpu.models import make_train_step

        if mesh not in self._step_fns:
            self._step_fns[mesh] = make_train_step(self.cfg, mesh=mesh)
        return self._step_fns[mesh]

    def tokens(self, step: int, mesh):
        from jax.sharding import NamedSharding, PartitionSpec as P

        host = np.random.default_rng(self.seed + step).integers(
            0, self.sizes["vocab_size"], (self.sizes["batch"], self.sizes["seq"]), dtype=np.int32)
        return self.jax.device_put(host, NamedSharding(mesh, P("dp", None)))

    def app_state(self, state, step: int) -> Dict[str, Any]:
        """The README's app state. `step` is the host's count: reading
        `state.step` would wait for every step still queued."""
        ts = self.ts
        return {
            "params": ts.PyTreeState(state.params),
            "opt": ts.PyTreeState(state.opt_state),
            "progress": ts.StateDict(step=step),
            "rng": ts.RngState(state.rng),
        }

    def state_of(self, app_state: Dict[str, Any], step: int, mesh):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from torchsnapshot_tpu.models import TrainState

        jnp = self.jax.numpy
        step_rng = self.jax.device_put(
            (jnp.asarray(step, jnp.int32), jnp.asarray(app_state["rng"].keys)),
            NamedSharding(mesh, P()))
        return TrainState(app_state["params"].tree, app_state["opt"].tree, *step_rng)

    @staticmethod
    def saved_tree(app_state: Dict[str, Any]) -> Dict[str, Any]:
        """The leaves a snapshot holds, in the order the fingerprints go by."""
        return {"params": app_state["params"].tree, "opt": app_state["opt"].tree,
                "rng": app_state["rng"].keys}

    def state_bytes(self, state) -> int:
        """Bytes of a state, or of its shapes (`jax.eval_shape`)."""
        return sum(x.size * x.dtype.itemsize for x in self.jax.tree_util.tree_leaves(state))

    def block(self, app_state: Dict[str, Any]) -> None:
        self.jax.block_until_ready(self.saved_tree(app_state))

    # -- the library, as the README calls it ------------------------------

    def manager(self):
        import faults

        mgr = self.ts.CheckpointManager(os.path.join(self.root, "snapshots"),
                                        keep_last_n=KEEP_LAST_N)
        return faults.plant(self.fault, mgr, self) if self.fault else mgr

    def marker_age(self, mgr, step: int, called_at: float) -> Optional[float]:
        """Seconds from `called_at` (time.time()) to the commit marker's change
        time on storage; None where there is no marker."""
        try:
            st = os.stat(os.path.join(mgr.step_path(step), MARKER))
        except FileNotFoundError:
            return None
        return max(st.st_mtime, st.st_ctime) - called_at

    def report(self, kind: str, mgr, step: int) -> Dict[str, float]:
        """The library's own critical-path segments of one operation."""
        rep = self.ts.telemetry.last_report(kind, path=mgr.step_path(step))
        if rep is None or not rep.critical_path:
            return {}
        cp = rep.critical_path
        return dict(cp["segments"], wall_s=cp["wall_s"])

    def tuner_decisions(self) -> List[List[Any]]:
        """The moves the library's write-path autotuner (on as shipped) made
        after each committed step of this run: [step, action, tunable, from,
        to]. A window's saves are the first of a process, so they are taken
        while the tuner still climbs; a commit time is read beside this."""
        try:
            with open(os.path.join(self.root, "snapshots", ".tuner-state.json")) as f:
                decisions = json.load(f).get("decisions", [])
        except (OSError, ValueError):
            return []
        return [[d.get("step"), d["decision"].get("action"), d["decision"].get("tunable"),
                 d["decision"].get("from_value"), d["decision"].get("to_value")]
                for d in decisions if "decision" in d]

    def annotate(self, name: str):
        return self.jax.profiler.TraceAnnotation(f"chipbench:{name}")


def _flip_bits(jax):
    def flip(x):
        if jax.dtypes.issubdtype(x.dtype, jax.dtypes.prng_key) or not x.ndim:
            return x
        return jax.lax.bitcast_convert_type(~reference.as_bits(jax, x), x.dtype)

    return flip

"""Faults planted under the timed path, to show that `correct` comes out false.

`run.py --fault <name>` puts one of these between the driver and the
library's `CheckpointManager`. A run with a fault is never a result: its line
carries `"fault"`, and the driver of the benchmark never passes the flag. The
control of these cells is `alter_answer`: it breaks the guarantee the
configurations state, a restore that is bit-identical in every leaf, by one
bit of one element of one leaf.
"""

from typing import Any, Dict

import numpy as np

import reference
from cells import BenchError


class _Planted:
    def __init__(self, mgr, ctx) -> None:
        self._mgr, self._ctx = mgr, ctx

    def __getattr__(self, name: str) -> Any:
        return getattr(self._mgr, name)


def _flip_one_bit(ctx, tree):
    """`tree` with the lowest bit of one element of one leaf flipped; leaf and
    element are drawn from the seed."""
    jax = ctx.jax
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    rng = np.random.default_rng(ctx.seed)
    which = int(rng.choice([i for i, x in enumerate(leaves) if x.ndim]))
    x = leaves[which]
    at = tuple(int(rng.integers(0, n)) for n in x.shape)
    bits = reference.as_bits(jax, x)
    bits = bits.at[at].set(bits[at] ^ 1)
    leaves[which] = jax.device_put(jax.lax.bitcast_convert_type(bits, x.dtype), x.sharding)
    return jax.tree_util.tree_unflatten(treedef, leaves)


class _Done:
    """What a save that did nothing hands back."""

    def done(self) -> bool:
        return True

    def wait(self) -> None:
        return None


class AlterAnswer(_Planted):
    """A save writes the altered leaf, a restore gives it back altered; where
    a save was altered the restore that checks it is left alone, or the same
    bit would flip back."""

    _saved_altered = False

    def async_save(self, step: int, app_state: Dict[str, Any]):
        self._saved_altered = True
        altered = dict(app_state)
        altered["params"] = self._ctx.ts.PyTreeState(_flip_one_bit(self._ctx, app_state["params"].tree))
        return self._mgr.async_save(step, altered)

    def restore_latest(self, app_state: Dict[str, Any]):
        step = self._mgr.restore_latest(app_state)
        if not self._saved_altered:
            app_state["params"].tree = _flip_one_bit(self._ctx, app_state["params"].tree)
        return step


class UnchangedState(_Planted):
    def async_save(self, step: int, app_state: Dict[str, Any]):
        return _Done()

    def restore_latest(self, app_state: Dict[str, Any]):
        return self._mgr.latest_step()


class HalfLeftOut(_Planted):
    def async_save(self, step: int, app_state: Dict[str, Any]):
        return self._mgr.async_save(step, {k: v for k, v in app_state.items() if k != "opt"})

    def restore_latest(self, app_state: Dict[str, Any]):
        return self._mgr.restore_latest({k: v for k, v in app_state.items() if k != "opt"})


FAULTS = {"alter_answer": AlterAnswer, "unchanged_state": UnchangedState,
          "half_left_out": HalfLeftOut}


def plant(name: str, mgr, ctx):
    if name not in FAULTS:
        raise BenchError(f"unknown fault {name!r}; there are: {', '.join(FAULTS)}")
    return FAULTS[name](mgr, ctx)

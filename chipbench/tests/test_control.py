"""The comparison that decides `correct` has been shown to fail.

The control of these cells breaks the guarantee the configurations state, a
restore bit-identical in every leaf, by one bit (`alter_answer`); it runs on
the chip at the cells' own sizes through `run.py --fault alter_answer` and
here at toy size. The other faults drive a whole run with the timed path
broken underneath and see `correct` come out false: a step that leaves its
state unchanged (a save that writes nothing, a restore that places nothing)
and half of the work left out (the optimizer's leaves neither saved nor
restored). The exchange between chips does not exist on these cells.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import harness
import reference


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return harness.copy_benchmark(tmp_path_factory.mktemp("control"))


def failing(result):
    return {c["name"] for c in result["checks"] if c["value"] > c["limit"]}


@pytest.mark.parametrize("workload", ["neox-6.9b-l2.async-full", "neox-6.9b-l2.resume"])
@pytest.mark.parametrize("fault,must_fail", [
    ("alter_answer", "leaves_differing"),
    ("unchanged_state", "leaves_differing"),
    ("half_left_out", "leaves_differing"),
])
def test_a_planted_fault_comes_out_not_correct(checkout, workload, fault, must_fail):
    rc, result, err = harness.run_cell(checkout, workload, "--fault", fault, seed=2147483700)
    assert rc == 0, err[-3000:]
    assert result["fault"] == fault
    assert result["correct"] is False
    assert must_fail in failing(result) or "verify_raised" in failing(result), result["checks"]


def test_one_altered_bit_is_one_leaf_and_nothing_else(checkout):
    rc, result, err = harness.run_cell(checkout, "neox-6.9b-l2.async-full", "--fault",
                                       "alter_answer", seed=2147483701)
    assert rc == 0, err[-3000:]
    checks = {c["name"]: c["value"] for c in result["checks"]}
    assert checks["leaves_differing"] == 1
    assert checks["saves_without_marker"] == 0 and checks["restored_step_gap"] == 0


def test_a_save_that_writes_nothing_has_no_marker(checkout):
    rc, result, err = harness.run_cell(checkout, "neox-6.9b-l2.async-full", "--fault",
                                       "unchanged_state", seed=2147483702)
    assert rc == 0, err[-3000:]
    assert {"saves_without_marker", "restored_step_gap"} <= failing(result)
    assert result["failed"] == result["attempted"]


def test_an_unknown_fault_fails_by_name(checkout):
    rc, result, err = harness.run_cell(checkout, "neox-6.9b-l2.resume", "--fault", "nope")
    assert rc != 0 and result is None and "unknown fault 'nope'" in err


def test_device_fingerprint_is_the_numpy_one():
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    tree = {
        "bf16": jnp.asarray(rng.standard_normal((7, 13)), jnp.bfloat16),
        "int32": jnp.asarray(rng.integers(0, 2**31, (5,)), jnp.int32),
        "scalar": jnp.float32(3.5),
        "key": jax.random.PRNGKey(2**31 + 5),
        "wide": jnp.asarray(rng.standard_normal((3, 70000)), jnp.bfloat16),
    }
    got = np.asarray(reference.make_fingerprint(jax)(tree))
    want = np.stack([reference.fingerprint_numpy(np.asarray(x))
                     for x in jax.tree_util.tree_leaves(tree)])
    assert (got == want).all()
    swapped = np.asarray(tree["int32"]).copy()
    swapped[[0, 1]] = swapped[[1, 0]]
    assert (reference.fingerprint_numpy(swapped)[0]
            == reference.fingerprint_numpy(np.asarray(tree["int32"]))[0])
    assert reference.leaves_differing(
        want[1:2], reference.fingerprint_numpy(swapped)[None]) == 1


def test_checks_decide_correct():
    ok = reference.check("a", 0, 0)
    assert reference.correct([ok]) and not reference.correct([])
    assert not reference.correct([ok, reference.check("b", 1, 0)])
    assert reference.loss_gap([2.0, 4.0], [2.0, 4.0]) == 0.0
    assert reference.loss_gap([2.0, 4.0], [2.0, 4.4]) == pytest.approx(0.1)
    assert reference.loss_gap([2.0], [float("nan")]) == 1.0
    assert reference.loss_gap([2.0, 4.0], [2.0]) == 1.0

"""Test configuration: force an 8-device virtual CPU mesh.

Multi-device sharding semantics (the analog of the reference's
gloo-on-one-box trick, test_utils.py:205-238) are exercised without TPU pods
by asking XLA's host platform for 8 virtual devices.

The platform is pinned both ways: in the environment, for this process
(when jax is not imported yet) and for the subprocesses multi-process tests
spawn, and through jax.config, in case a plugin imported jax before this
file ran (the backend is created lazily, so this works as long as no test
ran yet). Set TS_TEST_ON_TPU=1 to run test files against the real chip
instead; on the chip the main path is proven by ``python chip_smoke.py``.

The ``TORCHSNAPSHOT_TPU_*`` defaults below pin subsystems off that the
package ships on; the shipped configuration runs in ``chip_smoke.py`` (and
its tier-1 CPU rehearsal, tests/test_chip_smoke.py).
"""

import os

# Stall watchdog off by default in the suite (0 disables): the fast
# lane must never pay for (or get flagged by) a 60 s-deadline scanner.
# Tests that exercise the watchdog opt back in via
# knobs.override_watchdog_deadline_seconds().
os.environ.setdefault("TORCHSNAPSHOT_TPU_WATCHDOG_SECONDS", "0")

# Live-progress heartbeat files and the per-manager step history are
# likewise off by default (0 disables both): tier-1 snapshot/manager
# dirs must hold exactly the files the code under test wrote. Tests
# that exercise them opt back in via
# knobs.override_progress_interval_seconds() /
# knobs.override_history_max_records(). The in-memory
# telemetry.current_progress() view stays on regardless.
os.environ.setdefault("TORCHSNAPSHOT_TPU_PROGRESS_SECONDS", "0")
os.environ.setdefault("TORCHSNAPSHOT_TPU_HISTORY_MAX_RECORDS", "0")

# The run-level goodput ledger is pinned off for the same reason
# ("0" = no .ledger.jsonl reads/writes anywhere): tier-1 manager tests
# assert about exactly the files their saves produce. Ledger/goodput
# tests opt back in via knobs.enable_ledger().
os.environ.setdefault("TORCHSNAPSHOT_TPU_LEDGER", "0")

# Fan-out restore is pinned off in the suite ("0" = every rank reads
# its own bytes from storage): tier-1 distributed restore tests assert
# about the exact pre-fan-out read path (which plugin reads happen
# where, fail-fast windows). Fan-out tests opt back in via
# knobs.enable_fanout_restore() / an env override in their workers.
os.environ.setdefault("TORCHSNAPSHOT_TPU_FANOUT_RESTORE", "0")

# The peer-RAM checkpoint tier is pinned off in the suite ("0" = no
# cache server, no pushes, no restore-ladder pulls): tier-1 manager and
# restore tests assert about the exact pre-peer read/write paths and
# file sets. Peer-tier tests opt back in via knobs.enable_peer_tier()
# or an env override in their multiprocess workers.
os.environ.setdefault("TORCHSNAPSHOT_TPU_PEER_TIER", "0")

# O_DIRECT fs writes are pinned off in the suite ("0" = buffered; also
# the packaged default): CI filesystems vary — some support O_DIRECT,
# some decline with EINVAL — and tier-1 write-path assertions must not
# depend on which one this container mounts. Direct-I/O tests opt back
# in via knobs.enable_fs_direct_io() and assert BOTH outcomes. The
# zero-pack vectorized write stays at its packaged default (ON) so the
# tier-1 batching lane exercises the production slab path.
os.environ.setdefault("TORCHSNAPSHOT_TPU_FS_DIRECT_IO", "0")

# The write-path autotuner is likewise off by default in the suite
# ("0" = kill switch): tier-1 manager tests must run the exact
# hand-set/default knob geometry they assert about, with no
# .tuner-state.json appearing in their roots. Tuner tests opt back in
# via knobs.enable_autotune().
os.environ.setdefault("TORCHSNAPSHOT_TPU_AUTOTUNE", "0")

# The coordination store stays a single hub in the suite (1 = no shard
# servers; also the packaged default): tier-1 distributed tests assert
# about exact store traffic and must not depend on key->shard spread.
# Scale-model tests build ShardedStore members explicitly. The tree
# barrier stays at its packaged default (ON) so the tier-1 distributed
# lane exercises the production rendezvous topology.
os.environ.setdefault("TORCHSNAPSHOT_TPU_STORE_SHARDS", "1")

# The content-addressed chunk store is pinned off in the suite ("0" =
# the legacy per-step layout; also the packaged default): tier-1
# snapshot/manager tests assert about the exact per-step file sets and
# byte placement. CAS tests opt back in via knobs.enable_cas() or an
# env override in their multiprocess workers.
os.environ.setdefault("TORCHSNAPSHOT_TPU_CAS", "0")

# The checkpoint-CDN publish hook is pinned off in the suite ("0";
# also the packaged default): tier-1 manager tests assert about exact
# store traffic and per-save side effects, and must not depend on
# announce writes. CDN tests opt back in via env override or by
# setting TORCHSNAPSHOT_TPU_CDN=1 around the manager hook under test.
os.environ.setdefault("TORCHSNAPSHOT_TPU_CDN", "0")

# The fleet metrics plane is pinned off in the suite ("0"; also the
# packaged default): tier-1 distributed tests assert about exact store
# traffic and must not see __obs/ publish writes. Fleet-plane tests
# opt back in via knobs.enable_fleet_obs() or an env override in their
# multiprocess workers.
os.environ.setdefault("TORCHSNAPSHOT_TPU_FLEET_OBS", "0")

# The SLO engine is pinned off in the suite ("0"): tier-1 manager
# tests run with tiny synthetic budgets where normal operations would
# look like breaches, and must not see slo-breach ledger events or
# burn gauges they didn't ask for. SLO tests opt back in via
# knobs.enable_slo(). Incident-bundle capture is likewise disabled
# (max bytes 0 = no capture) so tier-1 roots never grow a .bundles/
# dir from an injected failure; bundle tests opt back in via
# knobs.override_bundle_max_bytes().
os.environ.setdefault("TORCHSNAPSHOT_TPU_SLO", "0")
os.environ.setdefault("TORCHSNAPSHOT_TPU_BUNDLE_MAX_BYTES", "0")

if os.environ.get("TS_TEST_ON_TPU") != "1":
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        _flags = (_flags + " --xla_force_host_platform_device_count=8").strip()
        os.environ["XLA_FLAGS"] = _flags

    import jax

    jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402 - after the environment is pinned


@pytest.fixture
def accelerator_path(monkeypatch):
    """Take the path of an accelerator on the CPU backend: say that
    placements copy, and make them copy (``device_put`` of an aligned
    numpy array may alias it here, which is why this backend never pools).
    Yields the process's pool, emptied before and after."""
    import jax
    import numpy as np

    from torchsnapshot_tpu import dest_pool, snapshot as snapshot_mod

    real_put = jax.device_put

    def copying_put(x, *args, **kwargs):
        copied = jax.tree_util.tree_map(
            lambda v: np.array(v) if isinstance(v, np.ndarray) else v, x
        )
        return real_put(copied, *args, **kwargs)

    monkeypatch.setattr(snapshot_mod, "_placement_copies", lambda s: True)
    monkeypatch.setattr(jax, "device_put", copying_put)
    pool = dest_pool.process_pool()
    pool.clear()
    yield pool
    pool.settle()
    pool.clear()

"""Write/read pipeline semantics: budget admission, staging-unblock point,
failure propagation.

Structural model: the reference exercises these through snapshot-level tests;
here the scheduler is tested directly with instrumented stagers/plugins.
"""

import asyncio
import threading
from typing import Dict

import pytest

from torchsnapshot_tpu.io_types import (
    BufferConsumer,
    BufferStager,
    ReadIO,
    ReadReq,
    StoragePlugin,
    WriteIO,
    WriteReq,
)
from torchsnapshot_tpu.knobs import override_per_rank_memory_budget_bytes
from torchsnapshot_tpu.scheduler import (
    get_process_memory_budget_bytes,
    sync_execute_read_reqs,
    sync_execute_write_reqs,
)
from torchsnapshot_tpu.storage_plugins.memory import MemoryStoragePlugin


class TrackingStager(BufferStager):
    """Stages a fixed payload; records global concurrent staging cost."""

    live_cost = 0
    peak_cost = 0

    def __init__(self, payload: bytes):
        self.payload = payload

    async def stage_buffer(self, executor=None):
        cls = TrackingStager
        cls.live_cost += len(self.payload)
        cls.peak_cost = max(cls.peak_cost, cls.live_cost)
        await asyncio.sleep(0.001)
        cls.live_cost -= len(self.payload)
        return self.payload

    def get_staging_cost_bytes(self) -> int:
        return len(self.payload)


class CollectingConsumer(BufferConsumer):
    def __init__(self, sink: Dict[str, bytes], key: str, cost: int):
        self.sink, self.key, self.cost = sink, key, cost

    async def consume_buffer(self, buf, executor=None) -> None:
        self.sink[self.key] = bytes(buf)

    def get_consuming_cost_bytes(self) -> int:
        return self.cost


class SlowStorage(StoragePlugin):
    """Delays writes so staging finishes well before I/O."""

    def __init__(self, delay: float = 0.05):
        self.delay = delay
        self.blobs: Dict[str, bytes] = {}
        self.writes_started = 0

    async def write(self, write_io: WriteIO) -> None:
        self.writes_started += 1
        await asyncio.sleep(self.delay)
        self.blobs[write_io.path] = bytes(write_io.buf)

    async def read(self, read_io: ReadIO) -> None:
        data = self.blobs[read_io.path]
        if read_io.byte_range:
            data = data[read_io.byte_range[0] : read_io.byte_range[1]]
        read_io.buf = memoryview(data)

    async def delete(self, path: str) -> None:
        del self.blobs[path]

    async def close(self) -> None:
        pass


class FaultyStorage(SlowStorage):
    async def write(self, write_io: WriteIO) -> None:
        await asyncio.sleep(0.01)
        raise OSError("injected write failure")


def test_write_pipeline_all_written() -> None:
    loop = asyncio.new_event_loop()
    storage = SlowStorage(delay=0.0)
    reqs = [
        WriteReq(path=f"blob/{i}", buffer_stager=TrackingStager(bytes([i]) * 100))
        for i in range(50)
    ]
    pending = sync_execute_write_reqs(reqs, storage, 10**9, rank=0, event_loop=loop)
    pending.sync_complete(loop)
    loop.close()
    assert len(storage.blobs) == 50
    assert storage.blobs["blob/7"] == bytes([7]) * 100


def test_write_pipeline_respects_budget() -> None:
    TrackingStager.live_cost = 0
    TrackingStager.peak_cost = 0
    loop = asyncio.new_event_loop()
    storage = SlowStorage(delay=0.0)
    # 20 x 100B with a 300B budget: concurrent staging must stay <= 300.
    reqs = [
        WriteReq(path=f"b/{i}", buffer_stager=TrackingStager(b"x" * 100))
        for i in range(20)
    ]
    pending = sync_execute_write_reqs(reqs, storage, 300, rank=0, event_loop=loop)
    pending.sync_complete(loop)
    loop.close()
    assert TrackingStager.peak_cost <= 300
    assert len(storage.blobs) == 20


def test_oversized_request_admitted_alone() -> None:
    TrackingStager.live_cost = 0
    TrackingStager.peak_cost = 0
    loop = asyncio.new_event_loop()
    storage = SlowStorage(delay=0.0)
    reqs = [WriteReq(path="huge", buffer_stager=TrackingStager(b"x" * 1000))]
    reqs += [
        WriteReq(path=f"s/{i}", buffer_stager=TrackingStager(b"y" * 10))
        for i in range(5)
    ]
    # Budget smaller than the huge request: it must still complete (admitted
    # when the pipeline is idle) rather than deadlock.
    pending = sync_execute_write_reqs(reqs, storage, 100, rank=0, event_loop=loop)
    pending.sync_complete(loop)
    loop.close()
    assert len(storage.blobs) == 6


def test_staging_unblock_before_io_completes() -> None:
    """execute_write_reqs must return at staging-done, with writes still in
    flight (the async-take unblock point)."""
    loop = asyncio.new_event_loop()
    storage = SlowStorage(delay=0.2)
    reqs = [
        WriteReq(path=f"p/{i}", buffer_stager=TrackingStager(b"z" * 10))
        for i in range(4)
    ]
    import time

    t0 = time.monotonic()
    pending = sync_execute_write_reqs(reqs, storage, 10**9, rank=0, event_loop=loop)
    staged_at = time.monotonic() - t0
    assert len(storage.blobs) < 4  # I/O not yet drained
    pending.sync_complete(loop)
    total = time.monotonic() - t0
    loop.close()
    assert len(storage.blobs) == 4
    assert staged_at < total


def test_write_failure_propagates_via_pending_work() -> None:
    loop = asyncio.new_event_loop()
    storage = FaultyStorage()
    reqs = [WriteReq(path="x", buffer_stager=TrackingStager(b"x"))]
    pending = sync_execute_write_reqs(reqs, storage, 10**9, rank=0, event_loop=loop)
    with pytest.raises(OSError, match="injected write failure"):
        pending.sync_complete(loop)
    loop.close()


def test_staging_failure_propagates_immediately() -> None:
    class FailingStager(TrackingStager):
        async def stage_buffer(self, executor=None):
            raise ValueError("injected staging failure")

    loop = asyncio.new_event_loop()
    storage = SlowStorage(delay=0.0)
    reqs = [
        WriteReq(path="ok", buffer_stager=TrackingStager(b"ok")),
        WriteReq(path="bad", buffer_stager=FailingStager(b"bad")),
    ]
    with pytest.raises(ValueError, match="injected staging failure"):
        sync_execute_write_reqs(reqs, storage, 10**9, rank=0, event_loop=loop)
    loop.close()


def test_read_pipeline() -> None:
    loop = asyncio.new_event_loop()
    storage = MemoryStoragePlugin(name="read-pipeline-test")
    try:
        loop.run_until_complete(
            storage.write(WriteIO(path="blob", buf=b"0123456789"))
        )
        sink: Dict[str, bytes] = {}
        reqs = [
            ReadReq(path="blob", buffer_consumer=CollectingConsumer(sink, "all", 10)),
            ReadReq(
                path="blob",
                buffer_consumer=CollectingConsumer(sink, "mid", 4),
                byte_range=(3, 7),
            ),
        ]
        sync_execute_read_reqs(reqs, storage, 10**6, rank=0, event_loop=loop)
        assert sink["all"] == b"0123456789"
        assert sink["mid"] == b"3456"
    finally:
        MemoryStoragePlugin.drop_store("read-pipeline-test")
        loop.close()


def test_read_pipeline_fetched_byte_accounting() -> None:
    """classify_read attributes completed reads for the restore
    reports' read-amplification fields: without a classifier everything
    counts as fetched; a classifier returning None (cache-served reads,
    fan-out restore) keeps those bytes out of bytes_fetched while
    bytes_moved still carries them."""
    loop = asyncio.new_event_loop()
    storage = MemoryStoragePlugin(name="read-classify-test")
    try:
        for name in ("a", "b"):
            loop.run_until_complete(
                storage.write(WriteIO(path=name, buf=name.encode() * 10))
            )
        sink: Dict[str, bytes] = {}
        reqs = [
            ReadReq(path="a", buffer_consumer=CollectingConsumer(sink, "a", 10)),
            ReadReq(path="b", buffer_consumer=CollectingConsumer(sink, "b", 10)),
        ]
        out = sync_execute_read_reqs(reqs, storage, 10**6, 0, loop)
        assert out["bytes_fetched"] == 20
        assert out["bytes_moved"] == 20

        sink.clear()
        reqs = [
            ReadReq(path="a", buffer_consumer=CollectingConsumer(sink, "a", 10)),
            ReadReq(path="b", buffer_consumer=CollectingConsumer(sink, "b", 10)),
        ]
        out = sync_execute_read_reqs(
            reqs,
            storage,
            10**6,
            0,
            loop,
            classify_read=lambda r: "fetched" if r.path == "a" else None,
        )
        assert out["bytes_fetched"] == 10
        assert out["bytes_moved"] == 20
    finally:
        MemoryStoragePlugin.drop_store("read-classify-test")
        loop.close()


def test_read_pipeline_budget() -> None:
    loop = asyncio.new_event_loop()
    storage = MemoryStoragePlugin(name="read-budget-test")
    try:
        for i in range(10):
            loop.run_until_complete(
                storage.write(WriteIO(path=f"b/{i}", buf=bytes([i]) * 50))
            )
        sink: Dict[str, bytes] = {}
        reqs = [
            ReadReq(path=f"b/{i}", buffer_consumer=CollectingConsumer(sink, str(i), 50))
            for i in range(10)
        ]
        # Budget fits only 2 concurrent consumes; must still complete.
        sync_execute_read_reqs(reqs, storage, 100, rank=0, event_loop=loop)
        assert len(sink) == 10
        assert sink["3"] == bytes([3]) * 50
    finally:
        MemoryStoragePlugin.drop_store("read-budget-test")
        loop.close()


def test_memory_budget_env_override() -> None:
    with override_per_rank_memory_budget_bytes(12345):
        assert get_process_memory_budget_bytes(None) == 12345


# ---------------------------------------------------------------------------
# StagingPool + DeferredIOWork (device-snapshot async takes, round 6)
# ---------------------------------------------------------------------------


def test_staging_pool_capacity_is_slab_bounded() -> None:
    from torchsnapshot_tpu.scheduler import StagingPool

    pool = StagingPool(10**9, slab_bytes=100, slabs=2)
    assert pool.total_bytes == 200  # slabs x slab_bytes
    assert pool.memory_budget_bytes == 10**9
    assert pool.geometry() == {
        "capacity_bytes": 200,
        "slab_bytes": 100,
        "slabs": 2,
        "chosen": "caller",
    }
    # ...but never above the process budget it is accounted against.
    clamped = StagingPool(150, slab_bytes=100, slabs=2)
    assert clamped.total_bytes == 150


def test_staging_pool_bounds_concurrent_staging() -> None:
    """20 x 100 B through a 2 x 100 B pool: concurrent staging cost must
    never exceed the pool, regardless of the (huge) process budget."""
    from torchsnapshot_tpu.scheduler import StagingPool, execute_write_reqs

    TrackingStager.live_cost = 0
    TrackingStager.peak_cost = 0
    loop = asyncio.new_event_loop()
    storage = SlowStorage(delay=0.0)
    reqs = [
        WriteReq(path=f"b/{i}", buffer_stager=TrackingStager(b"x" * 100))
        for i in range(20)
    ]
    pool = StagingPool(10**9, slab_bytes=100, slabs=2)
    pending = loop.run_until_complete(
        execute_write_reqs(
            reqs, storage, 10**9, rank=0, staging_pool=pool
        )
    )
    pending.sync_complete(loop)
    loop.close()
    assert TrackingStager.peak_cost <= 200
    assert len(storage.blobs) == 20
    assert pool.peak_reserved_bytes <= 200
    # The pool's geometry rides the pipeline telemetry into the report.
    assert pending.pipeline_telemetry()["staging_pool"]["slabs"] == 2


def test_staging_pool_oversized_request_admitted_alone() -> None:
    """Idle-admission escape hatch is inherited: one request larger
    than the whole pool serializes instead of deadlocking."""
    from torchsnapshot_tpu.scheduler import StagingPool, execute_write_reqs

    loop = asyncio.new_event_loop()
    storage = SlowStorage(delay=0.0)
    reqs = [WriteReq(path="huge", buffer_stager=TrackingStager(b"x" * 1000))]
    reqs += [
        WriteReq(path=f"s/{i}", buffer_stager=TrackingStager(b"y" * 10))
        for i in range(5)
    ]
    pool = StagingPool(10**9, slab_bytes=50, slabs=2)
    pending = loop.run_until_complete(
        execute_write_reqs(reqs, storage, 10**9, rank=0, staging_pool=pool)
    )
    pending.sync_complete(loop)
    loop.close()
    assert len(storage.blobs) == 6


def test_deferred_io_work_runs_pipeline_and_fires_on_staged() -> None:
    """Nothing stages at construction; sync_complete runs the whole
    pool-bounded pipeline, firing on_staged at the D2H boundary (before
    the write drain settles is unobservable here — assert it fired and
    the checksums table rebound to the live pipeline's)."""
    from torchsnapshot_tpu.scheduler import DeferredIOWork

    TrackingStager.live_cost = 0
    TrackingStager.peak_cost = 0
    storage = SlowStorage(delay=0.0)
    reqs = [
        WriteReq(path=f"d/{i}", buffer_stager=TrackingStager(bytes([i]) * 64))
        for i in range(12)
    ]
    work = DeferredIOWork(
        write_reqs=reqs, storage=storage, memory_budget_bytes=10**9, rank=0
    )
    assert storage.blobs == {}  # truly deferred
    staged_calls = []
    work.on_staged = lambda: staged_calls.append(len(storage.blobs))
    loop = asyncio.new_event_loop()
    work.sync_complete(loop)
    loop.close()
    assert staged_calls == [staged_calls[0]]  # fired exactly once
    assert len(storage.blobs) == 12
    assert storage.blobs["d/3"] == bytes([3]) * 64
    telemetry = work.pipeline_telemetry()
    assert telemetry["blobs"] == 12
    assert "staging" in telemetry["phases"]


# ---------------------------------------------------------------------------
# The staging window: the pool's capacity comes from the plan (PR 32)
# ---------------------------------------------------------------------------

MIB = 1 << 20
GIB = 1 << 30


class WindowStager(TrackingStager):
    """Records, on a shared log, the order of stages, what the budget had
    reserved when its stage began, and the high-water mark of bytes whose
    stage has begun and whose write has not finished."""

    def __init__(self, payload: bytes, log: list, pool=None, fail: bool = False):
        super().__init__(payload)
        self.log, self.pool, self.fail = log, pool, fail

    started = 0
    peak_started = 0

    async def stage_buffer(self, executor=None):
        cls = WindowStager
        cls.started += len(self.payload)
        cls.peak_started = max(cls.peak_started, cls.started)
        reserved = (
            self.pool.total_bytes - self.pool.available_bytes
            if self.pool is not None
            else None
        )
        self.log.append(("stage", self.payload[:1], reserved))
        if self.fail:
            raise RuntimeError("injected stage failure")
        return await super().stage_buffer(executor)


class WindowStorage(SlowStorage):
    """A write that has finished gives its bytes back to WindowStager's
    count, before the pipeline releases them to the pool."""

    async def write(self, write_io: WriteIO) -> None:
        await super().write(write_io)
        WindowStager.started -= len(write_io.buf)


def _run_window(reqs, pool, storage=None):
    from torchsnapshot_tpu.scheduler import execute_write_reqs

    WindowStager.started = WindowStager.peak_started = 0
    storage = storage or WindowStorage(delay=0.001)
    loop = asyncio.new_event_loop()
    try:
        pending = loop.run_until_complete(
            execute_write_reqs(reqs, storage, 10**9, rank=0, staging_pool=pool)
        )
        pending.sync_complete(loop)
    finally:
        loop.close()
    return storage


def test_a_request_stages_once_and_only_after_admission() -> None:
    from torchsnapshot_tpu.scheduler import StagingPool

    log: list = []
    pool = StagingPool(10**9, slab_bytes=100, slabs=2)
    reqs = [
        WriteReq(
            path=f"w/{i}",
            buffer_stager=WindowStager(bytes([i]) * 100, log, pool),
        )
        for i in range(8)
    ]
    storage = _run_window(reqs, pool)
    assert len(storage.blobs) == 8
    assert sorted(e[1] for e in log) == [bytes([i]) for i in range(8)]
    # After admission: the request's own bytes were reserved already, and
    # never more than the pool holds.
    assert all(100 <= e[2] <= 200 for e in log)


def test_bytes_in_flight_never_exceed_the_pool() -> None:
    """20 x 100 B through a pool of two requests: the bytes whose stage
    has begun and whose write has not finished stay within the pool."""
    from torchsnapshot_tpu.scheduler import StagingPool

    log: list = []
    pool = StagingPool(10**9, slab_bytes=100, slabs=2)
    reqs = [
        WriteReq(path=f"f/{i}", buffer_stager=WindowStager(b"x" * 100, log, pool))
        for i in range(20)
    ]
    storage = _run_window(reqs, pool)
    assert len(storage.blobs) == 20
    assert 100 <= WindowStager.peak_started <= 200
    assert pool.peak_reserved_bytes <= 200
    assert WindowStager.started == 0


def _train_state_leaves(d: int, ff: int, vocab: int, layers: int) -> list:
    """Bytes of a bf16 train state's array leaves (parameters and both Adam
    moments), as chipbench's configurations make them."""
    layer = [d * ff, ff * d, d * d, d * 3 * d, d, d]
    return [2 * n for n in layer * layers + [vocab * d, d * vocab, d]] * 3


NEOX_L2 = _train_state_leaves(4096, 16384, 50432, 2)  # 45 leaves, 4.56 GiB
PYTHIA_1B = _train_state_leaves(2048, 8192, 50304, 16)  # 297 leaves, 5.65 GiB


@pytest.mark.parametrize(
    "plan, budget, expected",
    [
        pytest.param([10 * GIB], 64 * GIB, 10 * GIB, id="one-huge-leaf"),
        pytest.param(
            NEOX_L2, 64 * GIB, 32 * sum(NEOX_L2) // len(NEOX_L2), id="47-large"
        ),
        pytest.param(
            PYTHIA_1B,
            64 * GIB,
            32 * sum(PYTHIA_1B) // len(PYTHIA_1B),
            id="299-small",
        ),
        pytest.param([], 64 * GIB, 0, id="empty-plan"),
        pytest.param(NEOX_L2, 1 * GIB, 1 * GIB, id="larger-than-the-budget"),
        pytest.param(
            [394 * MIB, 394 * MIB] + [8192] * 200,
            64 * GIB,
            788 * MIB,
            id="two-largest-together",
        ),
        pytest.param([MIB] * 4, 64 * GIB, 4 * MIB, id="never-above-the-plan"),
    ],
)
def test_derived_staging_window(plan, budget, expected) -> None:
    from torchsnapshot_tpu.scheduler import (
        StagingPool,
        derived_staging_window_bytes,
    )

    assert derived_staging_window_bytes(plan, budget) == expected
    pool = StagingPool(budget, request_bytes=plan)
    assert pool.total_bytes == max(1, expected)
    assert pool.geometry()["chosen"] == "derived"
    if plan:
        # A leaf's write can overlap the next leaf's transfer.
        assert pool.total_bytes >= min(budget, sum(sorted(plan)[-2:]))
        assert pool.total_bytes <= sum(plan)


@pytest.mark.parametrize(
    "slab_bytes, slabs, capacity",
    [(1000, 3, 3000), (1000, None, 2000), (None, 5, 5 * 128 * MIB)],
)
def test_env_pinned_pool_geometry_is_honoured_to_the_byte(
    slab_bytes, slabs, capacity
) -> None:
    import contextlib

    from torchsnapshot_tpu import knobs
    from torchsnapshot_tpu.scheduler import StagingPool

    with contextlib.ExitStack() as stack:
        if slab_bytes is not None:
            stack.enter_context(knobs.override_staging_pool_slab_bytes(slab_bytes))
        if slabs is not None:
            stack.enter_context(knobs.override_staging_pool_slabs(slabs))
        pool = StagingPool(64 * GIB, request_bytes=NEOX_L2)
        # ... as StagingPool(budget) gave before there was a plan to read.
        assert pool.total_bytes == StagingPool(64 * GIB).total_bytes == capacity
    assert pool.geometry() == {
        "capacity_bytes": capacity,
        "slab_bytes": slab_bytes or 128 * MIB,
        "slabs": slabs or 2,
        "chosen": "env",
    }


def test_tuner_override_raises_the_window_and_cannot_lower_it() -> None:
    from torchsnapshot_tpu import knobs
    from torchsnapshot_tpu.scheduler import (
        StagingPool,
        derived_staging_window_bytes,
    )

    window = derived_staging_window_bytes(NEOX_L2, 64 * GIB)
    try:
        # The tuner's first +1 move from the defaults: 256 MiB x 2.
        knobs.set_tuner_override(knobs._STAGING_POOL_SLAB_BYTES_ENV, 256 * MIB)
        pool = StagingPool(64 * GIB, request_bytes=NEOX_L2)
        assert (pool.total_bytes, pool.chosen) == (window, "derived")
        knobs.set_tuner_override(knobs._STAGING_POOL_SLAB_BYTES_ENV, 16 * MIB)
        assert StagingPool(64 * GIB, request_bytes=NEOX_L2).total_bytes == window
        knobs.set_tuner_override(knobs._STAGING_POOL_SLAB_BYTES_ENV, GIB)
        knobs.set_tuner_override(knobs._STAGING_POOL_SLABS_ENV, 8)
        pool = StagingPool(64 * GIB, request_bytes=NEOX_L2)
        assert (pool.total_bytes, pool.chosen) == (8 * GIB, "tuner")
        # The process budget clamps that too.
        assert StagingPool(2 * GIB, request_bytes=NEOX_L2).total_bytes == 2 * GIB
    finally:
        knobs.clear_tuner_override(knobs._STAGING_POOL_SLAB_BYTES_ENV)
        knobs.clear_tuner_override(knobs._STAGING_POOL_SLABS_ENV)
    assert StagingPool(64 * GIB, request_bytes=NEOX_L2).chosen == "derived"


def test_failed_stage_releases_its_bytes() -> None:
    from torchsnapshot_tpu.scheduler import StagingPool

    log: list = []
    pool = StagingPool(10**9, slab_bytes=100, slabs=2)
    reqs = [
        WriteReq(
            path=f"x/{i}",
            buffer_stager=WindowStager(b"z" * 100, log, pool, fail=(i == 3)),
        )
        for i in range(8)
    ]
    with pytest.raises(RuntimeError, match="injected stage failure"):
        _run_window(reqs, pool)
    assert pool.available_bytes == pool.total_bytes == 200
    assert pool.inflight == 0


def test_deferred_io_work_sizes_its_pool_from_the_plan() -> None:
    from torchsnapshot_tpu.scheduler import DeferredIOWork

    log: list = []
    reqs = [
        WriteReq(path=f"p/{i}", buffer_stager=WindowStager(bytes([i]) * 64, log))
        for i in range(40)
    ]
    work = DeferredIOWork(
        write_reqs=reqs,
        storage=WindowStorage(delay=0.0),
        memory_budget_bytes=10**9,
        rank=0,
    )
    WindowStager.started = WindowStager.peak_started = 0
    loop = asyncio.new_event_loop()
    work.sync_complete(loop)
    loop.close()
    pool = work.pipeline_telemetry()["staging_pool"]
    assert pool["chosen"] == "derived"
    # 16 transfers + 16 I/O slots, in requests of this plan's size.
    assert pool["capacity_bytes"] == 32 * 64
    assert WindowStager.peak_started <= 32 * 64
    assert len(log) == 40


# ---------------------------------------------------------------------------
# Destination pool (dest_pool.py): recycled host slabs for restore reads
# ---------------------------------------------------------------------------


class FakePlaced:
    """What ``device_put`` returned, as the pool sees it: ready when told,
    and, once deleted, unable to say."""

    def __init__(self) -> None:
        self._landed = threading.Event()
        self.deleted = False

    def land(self) -> None:
        self._landed.set()

    def is_ready(self) -> bool:
        if self.deleted:
            raise RuntimeError("Array has been deleted.")
        return self._landed.is_set()

    def block_until_ready(self) -> "FakePlaced":
        if self.deleted:
            raise RuntimeError("Array has been deleted.")
        assert self._landed.wait(10), "placement never landed"
        return self


def _pool_respects_cap(pool) -> None:
    taken = [pool.try_take(100, 250) for _ in range(3)]
    assert [s is not None for s in taken] == [True, True, False]
    assert pool.retained_bytes() == 200
    assert taken[0].array.nbytes == 100 and not taken[0].recycled
    assert taken[0].array.ctypes.data % 4096 == 0


def _pool_reuses_only_ready(pool) -> None:
    slab = pool.try_take(100, 100)
    value = FakePlaced()
    pool.placed(slab, value)
    pool.sweep()
    assert pool.try_take(100, 100) is None  # placed, not yet ready
    assert pool.unsettled() == 1
    value.land()
    pool.sweep()
    again = pool.try_take(100, 100)
    assert again is slab and again.recycled
    assert pool.retained_bytes() == 100


def _pool_drops_deleted(pool) -> None:
    slab = pool.try_take(100, 100)
    value = FakePlaced()
    pool.placed(slab, value)
    value.deleted = True  # the application donated it: transfer unknowable
    pool.settle()
    assert pool.retained_bytes() == 0 and pool.unsettled() == 0
    fresh = pool.try_take(100, 100)
    assert fresh is not slab and not fresh.recycled


def _pool_keeps_the_plans_sizes(pool) -> None:
    big, small = pool.try_take(300, 600), pool.try_take(100, 600)
    landed = FakePlaced()
    landed.land()
    pool.placed(small, landed)
    pool.sweep()  # small is free, big is out
    # One of 300 is out and comes back: the free 100 the plan also wants
    # does not give way to a second 300.
    assert pool.try_take(300, 600, keep_sizes={300, 100}) is None
    assert pool.retained_bytes() == 400
    # A size nobody asked to keep does give way.
    assert pool.try_take(300, 600, keep_sizes={300}) is not big
    assert pool.retained_bytes() == 600


def _pool_cap_is_a_high_water_mark(pool) -> None:
    slab = pool.try_take(1000, 4000)
    landed = FakePlaced()
    landed.land()
    pool.placed(slab, landed)
    pool.settle()
    # A restore's small stateful runs under a cap of bytes.
    assert pool.try_take(8, 32, keep_sizes={8}) is not None
    assert pool.retained_bytes() == 1008
    assert pool.try_take(1000, 4000) is slab
    pool.clear()
    assert pool.retained_bytes() == 1008  # both are out; none was free


def _pool_takes_several_or_none(pool) -> None:
    # A leaf's four boxes under a cap of six: all four, then none of the
    # next four (never two of them), then those that came back and two new.
    first = pool.try_take_all([100] * 4, 600)
    assert len(first) == 4 and not any(s.recycled for s in first)
    assert pool.try_take_all([100] * 4, 600) is None
    assert pool.retained_bytes() == 400 and sum(pool._out_sizes.values()) == 4
    for slab in first[:2]:
        pool.give_back(slab)
    assert pool.try_take_all([100] * 4, 600, may_grow=False) is None
    second = pool.try_take_all([100] * 4, 600)
    assert [s.recycled for s in second] == [True, True, False, False]
    assert pool.retained_bytes() == 600 and sum(pool._out_sizes.values()) == 6


def _pool_goes_over_its_cap_only_when_told(pool) -> None:
    # A leaf's boxes fill the cap; the buffer its reads are copied out of
    # gets no room, but for the pipeline none of whose buffers is out.
    boxes = pool.try_take_all([100] * 4, 400)
    landed = FakePlaced()
    landed.land()
    spare = pool.try_take(50, 500)
    pool.placed(spare, landed)
    pool.sweep()  # a free slab of another size
    assert boxes is not None and pool.retained_bytes() == 450
    # No 200 is out to wait for, so the free 50 gives way; still no room.
    assert pool.try_take_all([200], 500, keep_sizes={50, 200}) is None
    assert pool.retained_bytes() == 400
    (buffer,) = pool.try_take_all([200], 500, keep_sizes={50, 200}, over_cap=True)
    assert pool.retained_bytes() == 600 and not buffer.recycled
    pool.give_back(buffer)
    assert pool.try_take(200, 500) is buffer


def _pool_waits_for_a_box_held_by_several_devices(pool) -> None:
    from torchsnapshot_tpu.dest_pool import PlacedTogether

    slab = pool.try_take(100, 100)
    on_two = [FakePlaced(), FakePlaced()]
    pool.placed(slab, PlacedTogether(on_two))
    on_two[0].land()
    pool.sweep()
    assert pool.try_take(100, 100) is None  # the second device still reads it
    on_two[1].land()
    pool.settle()
    assert pool.try_take(100, 100) is slab


@pytest.mark.parametrize(
    "case",
    [
        _pool_respects_cap,
        _pool_reuses_only_ready,
        _pool_drops_deleted,
        _pool_keeps_the_plans_sizes,
        _pool_cap_is_a_high_water_mark,
        _pool_takes_several_or_none,
        _pool_goes_over_its_cap_only_when_told,
        _pool_waits_for_a_box_held_by_several_devices,
    ],
    ids=lambda f: f.__name__.strip("_"),
)
def test_destination_pool(case) -> None:
    from torchsnapshot_tpu.dest_pool import DestinationPool

    case(DestinationPool())


def test_pipeline_cap_bytes() -> None:
    from torchsnapshot_tpu.dest_pool import pipeline_cap_bytes

    MiB = 1 << 20
    neox = [394 * MiB, 394 * MiB] + [128 * MiB] * 4 + [96 * MiB, 32 * MiB] * 2
    # Four of the largest, or half the plan: under half a train state.
    assert pipeline_cap_bytes(neox, 1 << 40) == 4 * 394 * MiB
    assert pipeline_cap_bytes(neox * 4, 1 << 40) == 2 * sum(neox)
    assert pipeline_cap_bytes([8] * 100, 1 << 40) == 400
    # Clamped to the budget, never below one destination.
    assert pipeline_cap_bytes(neox, 500 * MiB) == 500 * MiB
    assert pipeline_cap_bytes(neox, 100 * MiB) == 394 * MiB
    # Read buffers add what is in flight of the largest, not their sum
    # over the plan, and the floor holds one leaf's boxes and one buffer.
    halves = [n // 2 for n in neox for _ in range(2)]
    assert pipeline_cap_bytes(neox, 1 << 40, halves, 4) == (4 * 394 + 4 * 197) * MiB
    assert pipeline_cap_bytes(neox, 1 << 40, halves, 16) == 4 * 394 * MiB + sum(halves)
    assert pipeline_cap_bytes(neox, 100 * MiB, halves, 16) == (394 + 197) * MiB


class LateConsumer(BufferConsumer):
    """A consumer that comes without a destination, as a dense leaf bound
    for an accelerator does."""

    def __init__(self, nbytes: int) -> None:
        self.nbytes = nbytes
        self.dst = None
        self.on_placed = None

    async def consume_buffer(self, buf, executor=None) -> None:
        self.dst[:] = bytearray(buf)

    def get_consuming_cost_bytes(self) -> int:
        return self.nbytes

    def unbound_destination_bytes(self) -> int:
        return self.nbytes if self.dst is None else 0

    def bind_destination(self, buf, on_placed) -> None:
        self.dst, self.on_placed = buf, on_placed


class FailingReads(SlowStorage):
    async def read(self, read_io: ReadIO) -> None:
        if read_io.path == "boom":
            raise OSError("injected read failure")
        await asyncio.sleep(0.002)
        await super().read(read_io)


def _run_leased_reads(pool, storage, paths, nbytes, cap_budget, loop):
    """Reads through a pipeline whose 'placer' places each finished read's
    bytes (a copy, as an accelerator's device_put makes) on ``flush``, the
    transfer landing a moment later. Returns (copies by path, leases)."""
    from torchsnapshot_tpu.dest_pool import DestinationLeases

    reqs = [ReadReq(path=p, buffer_consumer=LateConsumer(nbytes)) for p in paths]
    done, copies, out_peak = [], {}, [0]

    def flush() -> None:
        while done:
            req = done.pop()
            consumer = req.buffer_consumer
            copies[req.path] = bytes(consumer.dst)
            value = FakePlaced()
            consumer.on_placed(value)
            threading.Timer(0.005, value.land).start()

    leases = DestinationLeases(pool, [nbytes] * len(reqs), cap_budget, flush)

    def on_req_complete(req) -> None:
        out_peak[0] = max(out_peak[0], len(leases._out))
        done.append(req)
        if leases.starved:
            flush()

    try:
        sync_execute_read_reqs(
            reqs, storage, 10**6, 0, loop,
            on_req_complete=on_req_complete, destinations=leases,
        )
        flush()
    except BaseException:
        leases.abandon()
        raise
    assert out_peak[0] * nbytes <= max(nbytes, min(4 * nbytes, cap_budget))
    return copies, leases


@pytest.mark.parametrize("slabs", [1, 2, 4])
def test_leased_reads_wait_for_slabs(slabs: int) -> None:
    """More reads than the cap has slabs: each waits for a placement to
    land, none deadlocks, every byte arrives, and all but the first
    ``slabs`` destinations are recycled."""
    from torchsnapshot_tpu.dest_pool import DestinationPool

    loop = asyncio.new_event_loop()
    storage, pool, n = FailingReads(), DestinationPool(), 12
    for i in range(n):
        storage.blobs[f"b/{i}"] = bytes([i]) * 64
    try:
        copies, leases = _run_leased_reads(
            pool, storage, [f"b/{i}" for i in range(n)], 64, slabs * 64, loop
        )
        assert copies == {f"b/{i}": bytes([i]) * 64 for i in range(n)}
        cap = max(1, min(4, slabs)) * 64
        assert leases.bytes_fresh == cap
        assert leases.bytes_recycled == n * 64 - cap
        pool.settle()
        assert pool.retained_bytes() == cap and not leases._out
    finally:
        loop.close()


def test_failed_leased_reads_leave_the_pool_usable() -> None:
    """A read that raises fails the pipeline; the slabs it had out are
    dropped, not returned (a thread may still write into them), and the
    next pipeline runs on what is left."""
    from torchsnapshot_tpu.dest_pool import DestinationPool

    loop = asyncio.new_event_loop()
    storage, pool = FailingReads(), DestinationPool()
    for i in range(6):
        storage.blobs[f"b/{i}"] = bytes([i]) * 64
    try:
        with pytest.raises(OSError, match="injected read failure"):
            _run_leased_reads(
                pool, storage, ["b/0", "b/1", "boom", "b/2"], 64, 128, loop
            )
        pool.settle()
        assert sum(pool._out_sizes.values()) == 0
        assert pool.retained_bytes() <= 128
        copies, leases = _run_leased_reads(
            pool, storage, [f"b/{i}" for i in range(6)], 64, 128, loop
        )
        assert copies == {f"b/{i}": bytes([i]) * 64 for i in range(6)}
        pool.settle()
        assert pool.retained_bytes() == 128
    finally:
        loop.close()


class SharedBoxes:
    """A sharded leaf's destinations as the read pipeline sees them: two
    boxes its two reads fill together, bound when the first read comes."""

    def __init__(self, nbytes: int) -> None:
        self.nbytes = nbytes
        self.halves = None
        self.on_placed = []
        self.recycled = False

    def unbound_sizes(self):
        return [self.nbytes // 2] * 2 if self.halves is None else []

    def bind(self, bufs, on_placed, recycled) -> None:
        self.halves, self.on_placed, self.recycled = list(bufs), list(on_placed), recycled


class CopiedRead(BufferConsumer):
    """One of the two reads of a leaf: lands in a lent buffer when the
    storage plugin takes it, and is copied into its half of the leaf."""

    def __init__(self, boxes: SharedBoxes, half: int) -> None:
        self.boxes, self.half = boxes, half

    async def consume_buffer(self, buf, executor=None) -> None:
        self.boxes.halves[self.half][:] = bytearray(buf)

    def get_consuming_cost_bytes(self) -> int:
        return self.boxes.nbytes // 2

    def shared_destination(self):
        return self.boxes

    def read_buffer_bytes(self) -> int:
        return self.boxes.nbytes // 2


class ReadsIntoDest(SlowStorage):
    """Takes a lent destination where it fits, as the fs plugin does."""

    async def read(self, read_io: ReadIO) -> None:
        await asyncio.sleep(0.002)
        data = self.blobs[read_io.path]
        if read_io.dest is not None and read_io.dest.nbytes == len(data):
            read_io.dest[:] = data
            read_io.buf = read_io.dest
        else:
            read_io.buf = memoryview(data)


@pytest.mark.parametrize("held_by_another_restore", [0, 64], ids=["room-for-a-buffer", "no-room"])
def test_a_leaf_that_holds_its_boxes_always_gets_a_buffer(held_by_another_restore: int) -> None:
    """Six leaves of two reads each under a cap of one leaf's boxes and one
    buffer: a leaf takes both its boxes or neither, each read is copied out
    of a lent buffer, and where another restore's slab has taken the
    buffer's room the pipeline's first buffer is made over the cap (with
    none out, nothing would come back for the leaf that holds its boxes)."""
    from torchsnapshot_tpu.dest_pool import DestinationLeases, DestinationPool

    loop = asyncio.new_event_loop()
    storage, pool, leaf, n = ReadsIntoDest(), DestinationPool(), 128, 6
    leaves = [SharedBoxes(leaf) for _ in range(n)]
    reqs = []
    for i, boxes in enumerate(leaves):
        for half in range(2):
            storage.blobs[f"b/{i}/{half}"] = bytes([2 * i + half + 1]) * (leaf // 2)
            reqs.append(ReadReq(path=f"b/{i}/{half}", buffer_consumer=CopiedRead(boxes, half)))
    done, placed = [], {}

    def flush() -> None:
        while done:
            boxes = done.pop()
            placed[id(boxes)] = b"".join(bytes(h) for h in boxes.halves)
            for on_placed in boxes.on_placed:
                value = FakePlaced()
                on_placed(value)
                threading.Timer(0.005, value.land).start()

    remaining = {id(b): 2 for b in leaves}
    cap = leaf + leaf // 2
    if held_by_another_restore:
        assert pool.try_take(held_by_another_restore, cap) is not None
    leases = DestinationLeases.for_reads(
        pool, [r.buffer_consumer for r in reqs], cap, flush, reads_in_flight=16
    )
    assert leases._cap == cap
    peak = [0]

    def on_req_complete(req) -> None:
        peak[0] = max(peak[0], pool.retained_bytes())
        boxes = req.buffer_consumer.boxes
        remaining[id(boxes)] -= 1
        if not remaining[id(boxes)]:
            done.append(boxes)
            if leases.starved:
                flush()

    try:
        sync_execute_read_reqs(
            reqs, storage, 10**6, 0, loop, on_req_complete=on_req_complete, destinations=leases
        )
        flush()
        pool.settle()
        for i, boxes in enumerate(leaves):
            assert placed[id(boxes)] == bytes([2 * i + 1]) * 64 + bytes([2 * i + 2]) * 64
        assert peak[0] == cap + held_by_another_restore and not leases._out
        assert leases.bytes_fresh == cap
        assert leases.bytes_recycled == n * 2 * leaf - cap
    finally:
        loop.close()

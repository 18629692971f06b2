"""Memory runtime tests: spill tiers, OOM retry/split, semaphore.

Mirrors the reference's retry suites (SURVEY.md §4: WithRetrySuite,
HashAggregateRetrySuite, GpuSortRetrySuite, GpuCoalesceBatchesRetrySuite,
RapidsBufferCatalogSuite, RapidsHostMemoryStoreSuite, RapidsDiskStoreSuite)
with injected OOMs instead of real allocator pressure."""

import threading

import numpy as np
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import DeviceTable, HostTable
from spark_rapids_tpu.errors import FatalDeviceOOM, RetryOOM
from spark_rapids_tpu.runtime.retry import (
    RMM_TPU,
    is_device_oom,
    retry_block,
    split_device_table_in_half,
    with_retry,
    with_retry_no_split,
)
from spark_rapids_tpu.runtime.semaphore import TpuSemaphore, acquired
from spark_rapids_tpu.runtime.spill import (
    TIER_DEVICE,
    TIER_DISK,
    TIER_HOST,
    BufferCatalog,
    SpillableBatch,
)
from tests.data_gen import IntGen, LongGen, StringGen, gen_table


@pytest.fixture(autouse=True)
def clean_state():
    RMM_TPU.clear()
    yield
    RMM_TPU.clear()


@pytest.fixture()
def catalog():
    return BufferCatalog(host_limit_bytes=1 << 20)


def _dev_table(n=500, seed=1):
    host = gen_table({"a": IntGen(), "b": LongGen(), "s": StringGen()}, n, seed=seed)
    return DeviceTable.from_host(host), host


# -- spill framework --------------------------------------------------------

def test_spill_device_host_disk_roundtrip(catalog):
    dt, host = _dev_table()
    sb = SpillableBatch(dt, catalog)
    del dt
    assert sb.tier == TIER_DEVICE
    assert catalog.device_bytes() > 0

    freed = sb.spill_to_host()
    assert freed > 0 and sb.tier == TIER_HOST
    assert catalog.device_bytes() == 0

    freed2 = sb.spill_to_disk()
    assert freed2 > 0 and sb.tier == TIER_DISK
    assert catalog.host_bytes() == 0

    back = sb.get()  # disk -> device
    assert sb.tier == TIER_DEVICE
    assert back.to_host().to_pydict() == host.to_pydict()
    sb.release()


def test_synchronous_spill_frees_by_priority(catalog):
    tables = [_dev_table(200, seed=i)[0] for i in range(4)]
    sbs = [SpillableBatch(t, catalog, priority=i) for i, t in enumerate(tables)]
    del tables
    target = sbs[0].device_bytes + 1
    catalog.synchronous_spill(target)
    # lowest priority spilled first
    assert sbs[0].tier == TIER_HOST
    assert sbs[3].tier == TIER_DEVICE
    for sb in sbs:
        sb.release()


def test_pinned_batches_do_not_spill(catalog):
    dt, _ = _dev_table(100)
    sb = SpillableBatch(dt, catalog)
    sb.pin()
    assert catalog.synchronous_spill(1 << 62) == 0
    assert sb.tier == TIER_DEVICE
    sb.unpin()
    assert catalog.synchronous_spill(1 << 62) > 0
    assert sb.tier == TIER_HOST
    sb.release()


def test_host_limit_overflows_to_disk():
    catalog = BufferCatalog(host_limit_bytes=1)  # everything overflows
    dt, _ = _dev_table(300)
    sb = SpillableBatch(dt, catalog)
    del dt
    catalog.synchronous_spill(1 << 62)
    assert sb.tier == TIER_DISK
    assert catalog.spill_disk_count == 1
    sb.release()


# -- split ------------------------------------------------------------------

def test_split_in_half_preserves_rows():
    dt, host = _dev_table(333)
    a, b = split_device_table_in_half(dt)
    assert a.num_rows + b.num_rows == 333
    merged = HostTable.concat([a.to_host(), b.to_host()])
    assert merged.to_pydict() == host.to_pydict()


def test_split_single_row_raises():
    dt, _ = _dev_table(1)
    with pytest.raises(FatalDeviceOOM):
        split_device_table_in_half(dt)


# -- with_retry -------------------------------------------------------------

def test_with_retry_replays_same_input(catalog):
    dt, host = _dev_table(100)
    RMM_TPU.force_retry_oom(2)
    calls = []

    def fn(t):
        calls.append(t.num_rows)
        return t.to_host().to_pydict()

    outs = list(with_retry(dt, fn, catalog=catalog))
    assert len(outs) == 1 and outs[0] == host.to_pydict()
    assert RMM_TPU.retry_count == 2


def test_with_retry_split_escalation(catalog):
    dt, host = _dev_table(100)
    RMM_TPU.force_split_and_retry_oom(1)
    outs = list(with_retry(dt, lambda t: t.to_host(), catalog=catalog))
    assert len(outs) == 2  # halves
    assert HostTable.concat(outs).to_pydict() == host.to_pydict()
    assert RMM_TPU.split_count == 1


def test_with_retry_exhaustion_splits_after_max_retries(catalog):
    dt, _ = _dev_table(64)

    class FakeOOM(Exception):
        pass

    FakeOOM.__name__ = "XlaRuntimeError"
    fails = {"n": 3}

    def fn(t):
        if fails["n"] > 0:
            fails["n"] -= 1
            raise FakeOOM("RESOURCE_EXHAUSTED: out of memory")
        return t.num_rows

    outs = list(with_retry(dt, fn, max_retries=2, catalog=catalog))
    assert sum(outs) == 64 and len(outs) == 2  # split happened once


def test_with_retry_no_split_raises_fatal(catalog):
    dt, _ = _dev_table(64)
    RMM_TPU.force_split_and_retry_oom(1)
    with pytest.raises(FatalDeviceOOM):
        list(with_retry_no_split(dt, lambda t: t, catalog=catalog))


def test_with_retry_passes_through_other_errors(catalog):
    dt, _ = _dev_table(16)
    with pytest.raises(ValueError):
        list(with_retry(dt, lambda t: (_ for _ in ()).throw(ValueError("x")),
                        catalog=catalog))


def test_retry_block_spills_then_succeeds(catalog):
    other, _ = _dev_table(512, seed=9)
    sb = SpillableBatch(other, catalog)
    del other
    RMM_TPU.force_retry_oom(1)
    out = retry_block(lambda: 42, catalog=catalog)
    assert out == 42
    assert sb.tier == TIER_HOST  # the retry spilled registered buffers
    sb.release()


# -- operator integration (injection through the conf) ----------------------

@pytest.mark.parametrize("inject", ["retry:2", "split:1"])
def test_query_survives_injected_oom(session, cpu_session, inject):
    from spark_rapids_tpu import functions as F
    from spark_rapids_tpu.ops.expr import col
    from spark_rapids_tpu.session import TpuSession

    host = gen_table({"k": IntGen(min_val=0, max_val=9), "v": LongGen()}, 2000, seed=5)
    inj_session = TpuSession({"spark.rapids.sql.test.injectRetryOOM": inject})

    def build(s):
        return (s.create_dataframe(host, num_batches=3)
                .filter(col("v").isnotnull())
                .group_by("k").agg(F.sum("v").alias("sv"),
                                   F.count("v").alias("c")))

    got = sorted(map(str, build(inj_session).collect()))
    want = sorted(map(str, build(cpu_session).collect()))
    assert got == want


def test_join_survives_injected_oom(cpu_session):
    from spark_rapids_tpu.session import TpuSession
    host_l = gen_table({"k": IntGen(min_val=0, max_val=20), "lv": LongGen()}, 300, seed=1)
    host_r = gen_table({"k": IntGen(min_val=0, max_val=20), "rv": LongGen()}, 200, seed=2)
    inj = TpuSession({"spark.rapids.sql.test.injectRetryOOM": "retry:1"})

    def build(s):
        return s.create_dataframe(host_l).join(s.create_dataframe(host_r),
                                               on="k", how="inner")
    got = sorted(map(str, build(inj).collect()))
    want = sorted(map(str, build(cpu_session).collect()))
    assert got == want


# -- semaphore --------------------------------------------------------------

def test_semaphore_limits_concurrency():
    sem = TpuSemaphore(2)
    active = []
    peak = []
    lock = threading.Lock()

    def work(i):
        with acquired(sem):
            with lock:
                active.append(i)
                peak.append(len(active))
            import time
            time.sleep(0.02)
            with lock:
                active.remove(i)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert max(peak) <= 2
    assert sem.acquire_count == 6


def test_semaphore_reentrant():
    sem = TpuSemaphore(1)
    with acquired(sem):
        with acquired(sem):  # same thread re-enters
            assert sem.holders == 1
    assert sem.holders == 0


def test_semaphore_live_downsizing_wakes_waiters_as_holders_release():
    """Regression pin for shrinking max_tasks below the CURRENT holder
    count via initialize() on the live singleton: a waiter must stay
    blocked until holders drop BELOW the new cap, then wake promptly —
    release's notify_all plus the waiter's len(holders) >= max_tasks
    recheck cover the shrink correctly."""
    import time
    saved = TpuSemaphore._instance
    TpuSemaphore._instance = None
    try:
        sem = TpuSemaphore.initialize(2)
        hold = [threading.Event() for _ in range(2)]
        started = [threading.Event() for _ in range(2)]

        def holder(i):
            sem.acquire_if_necessary()
            started[i].set()
            hold[i].wait(10)
            sem.release_if_held()

        holders = [threading.Thread(target=holder, args=(i,))
                   for i in range(2)]
        for t in holders:
            t.start()
        for s in started:
            assert s.wait(5)
        assert sem.holders == 2

        shrunk = TpuSemaphore.initialize(1)  # live downsize, holders carry
        assert shrunk is sem and sem.max_tasks == 1

        got = threading.Event()

        def waiter():
            sem.acquire_if_necessary(timeout=8)
            got.set()
            sem.release_if_held()

        w = threading.Thread(target=waiter)
        w.start()
        time.sleep(0.1)
        assert not got.is_set()  # 2 holders >= cap 1: must block
        hold[0].set()            # 2 -> 1 holders: still AT the cap
        time.sleep(0.2)
        assert not got.is_set()
        hold[1].set()            # 1 -> 0: below cap, waiter must wake
        w.join(8)
        assert got.is_set()
        for t in holders:
            t.join(5)
        assert sem.holders == 0
    finally:
        TpuSemaphore._instance = saved


def test_fruitless_counters_are_per_catalog():
    """Satellite pin: DeviceMemoryEventHandler keys its consecutive
    fruitless-spill counts by id(catalog) — two threads OOM-ing on
    DIFFERENT catalogs must not share counters (a shared count would
    pre-escalate the second thread to split on its FIRST fruitless
    spill), and reset_fruitless must clear only its own catalog."""
    from spark_rapids_tpu.runtime.retry import DeviceMemoryEventHandler
    handler = DeviceMemoryEventHandler()
    cat_a = BufferCatalog(host_limit_bytes=1 << 20)  # empty: spills free 0
    cat_b = BufferCatalog(host_limit_bytes=1 << 20)

    results = {}

    def oom_twice(name, cat, barrier):
        out = []
        for _ in range(2):
            barrier.wait(timeout=5)
            out.append(handler.on_alloc_failure(cat))
        results[name] = out

    barrier = threading.Barrier(2)
    ta = threading.Thread(target=oom_twice, args=("a", cat_a, barrier))
    tb = threading.Thread(target=oom_twice, args=("b", cat_b, barrier))
    ta.start(); tb.start()
    ta.join(10); tb.join(10)
    # each catalog gets its OWN first-fruitless grace (True), then its
    # own second-fruitless escalation (False) — no cross-talk
    assert results == {"a": [True, False], "b": [True, False]}

    # reset clears exactly one catalog's count
    handler.reset_fruitless(cat_a)
    assert handler.on_alloc_failure(cat_a) is True   # fresh grace for a
    assert handler.on_alloc_failure(cat_b) is False  # b still escalated


def test_semaphore_timeout():
    sem = TpuSemaphore(1)
    sem.acquire_if_necessary()
    err = []

    def blocked():
        try:
            sem.acquire_if_necessary(timeout=0.05)
        except TimeoutError as e:
            err.append(e)

    t = threading.Thread(target=blocked)
    t.start()
    t.join()
    assert err
    sem.release_if_held()


def _runtime_error(type_name: str, message: str) -> Exception:
    """An exception whose type carries the device runtime's name, as
    jaxlib raises it (its class lives in the extension module)."""
    return type(type_name, (RuntimeError,), {})(message)


@pytest.mark.parametrize("make, oom", [
    (lambda: _runtime_error(
        "JaxRuntimeError",
        "RESOURCE_EXHAUSTED: Error allocating device buffer: Attempting "
        "to allocate 1.20G. That was not possible."), True),
    (lambda: _runtime_error(
        "XlaRuntimeError", "RESOURCE_EXHAUSTED: Out of memory while "
        "trying to allocate 1073741824 bytes."), True),
    (lambda: ValueError(
        "RESOURCE_EXHAUSTED: Error loading program 'jit_mesh_exchange': "
        "Attempting to reserve 2.1G at the bottom of memory."), True),
    (lambda: _runtime_error("JaxRuntimeError", "INTERNAL: core halted"),
     False),
    (lambda: ValueError("RESOURCE_EXHAUSTED is a word here"), False),
], ids=["jax-runtime-error", "xla-runtime-error", "program-load",
        "other-runtime-error", "other-value-error"])
def test_device_oom_is_recognised_in_each_form_the_runtime_raises(
        catalog, make, oom):
    """A device OOM surfaces at the enqueue as JaxRuntimeError on this
    JAX (XlaRuntimeError before it was renamed), or as a ValueError
    where a program cannot be loaded: each is replayed after a spill,
    and nothing else is."""
    assert is_device_oom(make()) is oom
    calls = []

    def fails_once():
        calls.append(1)
        if len(calls) == 1:
            raise make()
        return "done"

    if oom:
        assert retry_block(fails_once, catalog=catalog) == "done"
        assert len(calls) == 2
    else:
        with pytest.raises(Exception):
            retry_block(fails_once, catalog=catalog)
        assert len(calls) == 1

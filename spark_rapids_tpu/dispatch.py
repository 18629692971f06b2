"""Device-constant interning + dispatch accounting.

What a dispatch costs, as measured on a TPU v5e (PERF.md sections 5 and 6,
PR 28, 31 and 32): a warm enqueue of a program takes the host about 1.5 ms
on one chip and 3.3 ms over a four-chip mesh, of which about 0.14 ms are
Python's (this module's wrapper, spans, fault points, argument trees); the
rest is spent under the jitted call in the TPU runtime. What it grows with
is the program's RESULTS, about 0.05 ms each on one chip; operands are
cheap (a program of 16 bodies and 256 operands costs 2.0 ms where one of
one body and 16 costs 1.6), and a 4-row program costs about as much as a
2^21-row one. Kernel dispatches PIPELINE: chained dispatches plus one
result fetch cost about what one does, so the device waits only where the
host enqueues more slowly than it runs. But every host->device transfer in
the warm path is a fresh stall, and an upload interleaved between
dispatches forces a pipeline flush. The reference never faces this:
cudaMemcpyAsync on PCIe is microseconds, so it re-uploads per-kernel
scratch freely (e.g. JCudfSerialization headers).

The rule is therefore: NOTHING transfers host->device on a warm query.
Every per-query host-side constant — expression aux arrays
(dictionary codes, literal tables, remap vectors), aggregate size/stride
vectors, row-count scalars — is interned here by CONTENT, so a repeated
query shape reuses the device-resident copy and the warm path performs
zero uploads.

``count_dispatch`` feeds the per-query ``dispatches`` metric (the dispatch
count must be observable), and ``phase_span`` the per-query host seconds
spent enqueueing, syncing and fetching (``phasesS`` of the event record)."""

from __future__ import annotations

import hashlib
import threading
import time
from collections import OrderedDict
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.obs.metrics import metric_scope, register_metric
from spark_rapids_tpu.obs.spans import span

_LOCK = threading.Lock()

#: spark.sql.ansi.enabled, set per-query by the session (same pattern as
#: the masked-batch and retry contextvars)
import contextvars
ANSI_MODE = contextvars.ContextVar("rapids_ansi_mode", default=False)

#: content-keyed device copies of host constant arrays (LRU order)
_CONST_CACHE: "OrderedDict[tuple, jax.Array]" = OrderedDict()
#: interned device scalars keyed by (dtype, value) (LRU order)
_SCALAR_CACHE: "OrderedDict[tuple, jax.Array]" = OrderedDict()

#: evict the const cache above this many entries (scans are cached on their
#: host tables, not here; these are small aux/remap arrays). Eviction is
#: LRU one-at-a-time — a wholesale clear() at the cap silently dropped
#: every WARM scan constant and re-triggered the mid-pipeline uploads
#: the interning exists to avoid; a hot key must survive cap pressure.
_CONST_CACHE_CAP = 8192


def _content_key(arr: np.ndarray) -> tuple:
    if arr.dtype == object:
        # object arrays (string dictionaries) hash by element repr
        h = hashlib.sha1("\x00".join(map(repr, arr.ravel().tolist()))
                         .encode()).digest()
        return (str(arr.dtype), arr.shape, h)
    b = np.ascontiguousarray(arr).tobytes()
    if len(b) <= 128:
        return (str(arr.dtype), arr.shape, b)
    return (str(arr.dtype), arr.shape, hashlib.sha1(b).digest())


def _put(arr: np.ndarray, sharding) -> jax.Array:
    return jnp.asarray(arr) if sharding is None \
        else jax.device_put(arr, sharding)


def device_const(arr, sharding=None) -> jax.Array:
    """Device copy of a host constant array, interned by content. Safe to
    call inside a jit trace (the cached concrete array is captured as a
    trace constant — uploaded once at compile, never per call). With
    ``sharding`` (a mesh program's small operand: replicated over its
    mesh) the copy lies as that says and is interned under content AND
    sharding: handed a one-device array, a program over sharded operands
    copies it to every chip on every call."""
    if isinstance(arr, jax.Array):
        return arr if sharding is None else jax.device_put(arr, sharding)
    arr = np.asarray(arr)
    key = _content_key(arr) + (sharding,)
    with _LOCK:
        d = _CONST_CACHE.get(key)
        if d is not None:
            _CONST_CACHE.move_to_end(key)
    if d is None:
        d = _put(arr, sharding)
        with _LOCK:
            while len(_CONST_CACHE) >= _CONST_CACHE_CAP:
                _CONST_CACHE.popitem(last=False)
            _CONST_CACHE[key] = d
    return d


def device_scalar(value, dtype=np.int32, sharding=None) -> jax.Array:
    """Interned 0-d device scalar (the DeviceTable row-count pattern:
    ``jnp.asarray(np.int32(n))`` per table was a ~0.15s upload EACH);
    ``sharding`` as in device_const."""
    dt = np.dtype(dtype)
    key = (dt.str, value, sharding)
    with _LOCK:
        d = _SCALAR_CACHE.get(key)
        if d is not None:
            _SCALAR_CACHE.move_to_end(key)
    if d is None:
        d = _put(np.asarray(value, dtype=dt), sharding)
        with _LOCK:
            while len(_SCALAR_CACHE) >= _CONST_CACHE_CAP:
                _SCALAR_CACHE.popitem(last=False)
            _SCALAR_CACHE[key] = d
    return d


def prep_aux(pctx, sharding=None) -> tuple:
    """Upload a PrepCtx's aux arrays: content-interned for deterministic
    slots, plain per-call upload for nondeterministic ones (rand streams —
    interning those would pin every batch's values on device forever).
    ``sharding`` as in device_const."""
    intern = getattr(pctx, "aux_intern", None) or [True] * len(pctx.aux_arrays)
    return tuple(device_const(a, sharding) if keep else _put(a, sharding)
                 for a, keep in zip(pctx.aux_arrays, intern))


def clear_device_constants() -> int:
    """Drop interned device constants (device OOM recovery hook)."""
    with _LOCK:
        n = len(_CONST_CACHE) + len(_SCALAR_CACHE)
        _CONST_CACHE.clear()
        _SCALAR_CACHE.clear()
    return n


# -- sanctioned host synchronization ----------------------------------------


class _ThreadCounter(threading.local):
    """Per-thread counter: queries execute whole on one thread (direct
    calls on the caller's thread, service queries on their worker), so
    thread-locality makes the per-query dispatch/sync counts correct
    under CONCURRENT queries — a shared slot would cross-contaminate
    every in-flight query's count on reset."""

    def __init__(self):
        self.n = 0


_HOST_FETCHES = _ThreadCounter()

#: the per-query host-clock phases taken where the work happens (keys of
#: the event record's ``phasesS`` beside planS / executeS / collectS)
PHASE_KEYS = ("dispatchS", "syncWaitS", "fetchWaitS", "fetchUnpackS",
              "semaphoreWaitS", "coalesceS", "relandS", "joinS",
              "exchangeS")


class _PhaseSeconds(threading.local):
    """Per-thread seconds by phase key (the _ThreadCounter rationale)."""

    def __init__(self):
        self.s = dict.fromkeys(PHASE_KEYS, 0.0)


_PHASES = _PhaseSeconds()


class phase_span:
    """``with phase_span(key, name, cat):`` — one ``srt.<cat>.<name>``
    range whose host seconds also add to this thread's query phase
    ``key``: two ``perf_counter`` reads beside the range, always on."""

    __slots__ = ("key", "range", "t0")

    def __init__(self, key: str, name: str, cat: str):
        self.key = key
        self.range = span(name, cat)

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.range.__enter__()
        return self

    def __exit__(self, *exc):
        self.range.__exit__(*exc)
        _PHASES.s[self.key] += time.perf_counter() - self.t0
        return False


def phase_seconds() -> Dict[str, float]:
    """This thread's phase seconds since the last reset — the session
    folds them into the query's ``phasesS``."""
    return dict(_PHASES.s)


def host_fetch(value):
    """THE sanctioned device->host synchronization point for exec/op hot
    paths (the repo lint's RL-HOST-SYNC rule rejects raw
    ``jax.device_get`` / ``block_until_ready`` in execs/ and ops/).

    Every call is a deliberate pipeline stall (a host sync), so
    funneling them here keeps them countable (``host_fetch_count``, the
    record's ``hostSyncs``), timed (``syncWaitS``) and greppable in
    review. Returns the fetched value as host data (numpy array or
    python scalar for 0-d inputs)."""
    _HOST_FETCHES.n += 1
    with phase_span("syncWaitS", "host_fetch", "sync"):
        fetched = jax.device_get(value)
    return fetched


def count_host_sync() -> None:
    """A blocking device->host fetch that is not a ``host_fetch`` (the
    root result's ``PendingHostTable.resolve``)."""
    _HOST_FETCHES.n += 1


def host_fetch_count() -> int:
    return _HOST_FETCHES.n


def reset_query_phases() -> None:
    """Zero this thread's phase seconds and host-sync count (top-level
    query start, where the compile stats reset)."""
    _PHASES.s = dict.fromkeys(PHASE_KEYS, 0.0)
    _HOST_FETCHES.n = 0


# -- compile accounting ------------------------------------------------------

register_metric("kernelTraces", "count", "ESSENTIAL",
                "XLA traces (new jit-cache entries): each is a fresh "
                "trace + lowering + compile — the ~1-2 min cold-shape "
                "cliff on the TPU backend")
register_metric("kernelTraceCacheHits", "count", "MODERATE",
                "dispatches served by an existing jit-cache entry "
                "(no trace, no compile)")
register_metric("kernelCompileTime", "timing", "ESSENTIAL",
                "wall time of dispatches that triggered a new trace "
                "(trace + lowering + backend compile)")
register_metric("padWasteRows", "count", "MODERATE",
                "dead tail rows uploaded to pad batches up to their "
                "capacity bucket (the price of the bounded kernel set)")

#: the process-wide `compile` scope: serving-latency observability for
#: shape bucketing + the executable cache (which adds its own counters)
COMPILE_SCOPE = metric_scope("compile")


class _ThreadFloat(threading.local):
    def __init__(self):
        self.v = 0.0


#: per-thread per-query accumulators (the _ThreadCounter rationale:
#: queries execute whole on one thread, so per-query deltas stay
#: correct under concurrent service workers)
_COMPILE_S = _ThreadFloat()
_TRACES = _ThreadCounter()
_PAD_WASTE = _ThreadCounter()
#: warm-dispatch trace-cache hits accumulate PER THREAD and flush to
#: the scope once per query (flush_trace_cache_hits) — taking the
#: process-wide scope lock on every warm dispatch would serialize
#: concurrent service workers on the hottest path
_TRACE_HITS = _ThreadCounter()


def flush_trace_cache_hits() -> int:
    """Move this thread's accumulated warm-dispatch counts into the
    ``compile`` scope (called at query end by the session)."""
    n = _TRACE_HITS.n
    _TRACE_HITS.n = 0
    if n:
        COMPILE_SCOPE.add("kernelTraceCacheHits", n)
    return n


def count_pad_waste(n: int) -> None:
    """Record ``n`` dead tail rows padded onto an uploaded batch."""
    if n <= 0:
        return
    _PAD_WASTE.n += n
    COMPILE_SCOPE.add("padWasteRows", n)


def compile_stats() -> Tuple[int, float, int]:
    """(traces, compile seconds, pad-waste rows) on THIS thread since
    the last reset — the session snapshots these per query."""
    return _TRACES.n, _COMPILE_S.v, _PAD_WASTE.n


def reset_compile_stats() -> None:
    _TRACES.n = 0
    _COMPILE_S.v = 0.0
    _PAD_WASTE.n = 0


# -- dispatch accounting ----------------------------------------------------

_DISPATCHES = _ThreadCounter()


def count_dispatch(n: int = 1) -> None:
    """Record ``n`` device kernel dispatches (on this thread — see
    _ThreadCounter). No-op inside a jit trace (an inlined sub-kernel is
    not a dispatch)."""
    _DISPATCHES.n += n


def dispatch_count() -> int:
    return _DISPATCHES.n


def reset_dispatch_count() -> int:
    old = _DISPATCHES.n
    _DISPATCHES.n = 0
    return old


from jax._src.core import trace_state_clean as _trace_state_clean


def tracing() -> bool:
    """Are we inside a jax trace right now?"""
    return not _trace_state_clean()


def tpu_jit(fn, *, name: str, **kwargs):
    """jax.jit that records a dispatch per (non-traced) call — when an
    exec kernel runs inside a whole-plan fused trace (execs/fused.py) it
    inlines into the outer program and is NOT a dispatch. Also feeds the
    ``compile`` metric scope: a call that grows the jit's trace cache is
    a new XLA trace (counted, with its wall as kernelCompileTime — the
    dispatch itself is async, so a cache-hit call returns in
    microseconds while a tracing call blocks for trace + lowering +
    backend compile); everything else is a trace-cache hit.

    ``name`` says what the program does (``agg_fast``, ``d2h_pack``):
    it is the jitted function's ``__name__``, so XLA's module is
    ``jit_<name>`` on the device timeline, the ``srt.dispatch.<name>``
    range on the host's, and the op named by the dispatch fault points
    and kernel-crash errors — one name in all of them."""
    fn = _named(fn, name)
    jf = jax.jit(fn, **kwargs)

    from spark_rapids_tpu.runtime.faults import fault_point

    # cache sizes already credited as a trace: two threads dispatching
    # the same COLD kernel concurrently both observe the cache growing
    # (one traces, the other blocks on it) — only the first claimant of
    # a given size counts it, the other records a trace-cache hit.
    # Attribution is APPROXIMATE under that race: a warm concurrent
    # dispatch can claim the size first and book a near-zero
    # kernelCompileTime while the tracing thread books a hit —
    # process-wide totals stay right, per-query/thread splits may skew.
    # Exact attribution needs compiler hooks jax does not expose.
    counted_sizes: set = set()
    counted_lock = threading.Lock()

    def call(*args, **kw):
        if not _trace_state_clean():
            return jf(*args, **kw)
        fault_point("dispatch.kernel", op=name)
        # survivability injection (runtime/health.py consumers): a wedge
        # stalls INSIDE this dispatch (the between-batch cancel check
        # never runs — watchdog territory); a device loss raises the
        # fatal error the health monitor recovers from
        fault_point("dispatch.wedge", op=name)
        fault_point("device.lost", op=name)
        count_dispatch()
        # host range per dispatch (async: covers enqueue, not device
        # compute — the device planes own that); blocks on a compile or
        # a full queue
        with phase_span("dispatchS", name, "dispatch"):
            before = jf._cache_size()
            t0 = time.perf_counter()
            res = jf(*args, **kw)
            after = jf._cache_size()
            grew = after > before
            if grew:
                with counted_lock:
                    grew = after not in counted_sizes
                    counted_sizes.add(after)
            if grew:
                dt = time.perf_counter() - t0
                _TRACES.n += 1
                _COMPILE_S.v += dt
                COMPILE_SCOPE.add("kernelTraces", 1)
                COMPILE_SCOPE.add("kernelCompileTime", dt)
            else:
                _TRACE_HITS.n += 1  # lock-free; flushed per query
            return res

    call.__wrapped__ = jf
    return call


def _named(fn, name: str):
    """``fn`` under ``name``: jax.jit names the XLA module after the
    function's ``__name__``. Wrapped, not renamed in place — one
    function object may be jitted at more than one site."""
    import functools

    @functools.wraps(fn)
    def named(*args, **kw):
        return fn(*args, **kw)

    named.__name__ = named.__qualname__ = name
    return named

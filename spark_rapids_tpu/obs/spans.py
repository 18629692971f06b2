"""The engine's one span primitive + exec-boundary instrumentation.

Reference (SURVEY.md §5): NVTX ranges (``NvtxWithMetrics.scala``) put
operator ranges on the DEVICE timeline; nothing in the reference shows
where HOST wall time goes — which is where this engine's queries can
live (transfers, shuffle IO, serialization, spill).

:func:`span` is the ONE function that opens a range, and it writes to
two sinks. It always enters a ``jax.profiler.TraceAnnotation`` named
``srt.<cat>.<name>`` (``srt.<name>`` where the name already starts
with its category: ``srt.query``, ``srt.shuffle.fetch``), so the
range lies in ``/host:CPU`` of any Xprof trace of the process, on the
clock of the device planes; a TraceMe costs about a microsecond while
no profiler session is active. While the thread's query envelope
collects, the same range is also a :class:`Span` of the
:class:`SpanTracer`, which the session summarises into the event
record and exports as Chrome trace-event JSON. Every range opened on a
thread that executes a query carries ``query=<query index>`` as
annotation metadata; nesting on the thread gives the parent.

Two layers:

* :class:`SpanTracer` / the process-wide :data:`TRACER` — collection is
  enabled per query by the session (``spark.rapids.trace.enabled``, or
  implicitly while the event log needs attribution). Idle, a range is
  its annotation and nothing else.
* :func:`install_observation` — the per-query exec-boundary wrapper
  (the ``install_fault_boundaries`` threading pattern from PR 3): every
  device exec's ``execute``/``execute_masked`` and the ``DeviceToHost``
  root get (a) a span per batch pull when tracing, and (b) the
  ESSENTIAL ``opTime``/``numOutputRows``/``numOutputBatches`` metrics
  ALWAYS — row counts that only exist on device are deferred and
  resolved in ONE batched fetch by :func:`finalize_observation`, never
  a per-batch sync.

And Python's collector: :func:`install_gc_hook` (once a process, at the
first observed query) opens every collection as ``srt.gc.gen<N>`` on the
thread it interrupts and counts its seconds there (:func:`gc_seconds`;
the record's ``phasesS.gcS``).
"""

from __future__ import annotations

import gc
import json
import threading
import time
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

from spark_rapids_tpu.conf import bool_conf, str_conf
from spark_rapids_tpu.lockorder import ordered_lock

TRACE_ENABLED = bool_conf(
    "spark.rapids.trace.enabled", False,
    "Collect host-side spans for every query and export a Chrome "
    "trace-event JSON per query under spark.rapids.trace.dir — load it "
    "in Perfetto next to the Xprof device trace.")

TRACE_DIR = str_conf(
    "spark.rapids.trace.dir", "/tmp/rapids_tpu_trace",
    "Directory for exported Chrome trace JSON files (one "
    "query_<N>.trace.json per traced query).")

#: hard cap on buffered spans per query (a runaway batch loop must
#: degrade the trace, not the process); dropped spans are counted
_MAX_SPANS = 200_000


class Span:
    __slots__ = ("sid", "name", "cat", "t0", "t1", "tid", "tname",
                 "parent", "args", "ctx")

    def __init__(self, sid, name, cat, t0, tid, tname, parent, args,
                 ctx=None):
        self.sid = sid
        self.name = name
        self.cat = cat
        self.t0 = t0
        self.t1 = None
        self.tid = tid
        self.tname = tname
        self.parent = parent
        self.args = args
        self.ctx = ctx

    @property
    def dur(self) -> float:
        return (self.t1 - self.t0) if self.t1 is not None else 0.0


class _LiveSpan:
    """A range while its thread's query collects: the annotation plus
    the tracer's span, entered and left together."""

    __slots__ = ("ann", "name", "cat", "args", "span")

    def __init__(self, ann, name, cat, args):
        self.ann = ann
        self.name = name
        self.cat = cat
        self.args = args
        self.span = None

    def __enter__(self):
        self.span = TRACER._begin(self.name, self.cat, self.args)
        self.ann.__enter__()
        return self.span

    def __exit__(self, *exc):
        self.ann.__exit__(*exc)
        TRACER._end(self.span)
        return False


class _QueryCtx:
    """One query's span buffer, owned by the thread that called
    ``begin_query``. Per-thread span stacks live ON the context (keyed by
    thread id) so helper-thread stacks die with the query instead of
    leaking stale parents into the next query on that thread."""

    __slots__ = ("query_id", "owner_tid", "spans", "dropped", "t0",
                 "stacks", "closed")

    def __init__(self, query_id: int, owner_tid: int):
        self.query_id = query_id
        self.owner_tid = owner_tid
        self.spans: List[Span] = []
        self.dropped = 0
        self.t0 = time.perf_counter()
        self.stacks: Dict[int, list] = {}
        self.closed = False


#: sentinel bound to a thread's ctx slot while it runs an UNOBSERVED
#: query — blocks the single-active-context adoption below
_ADOPT_BLOCKED = object()
#: sentinel bound from ``end_query`` to ``leave_query``: the envelope's
#: tail (row-count fetch, record build, event write) runs on this thread
#: and belongs to no other query's context either
_DETACHED = object()


class SpanTracer:
    """Process-wide span collector, safe for CONCURRENT queries: each
    ``begin_query`` opens a :class:`_QueryCtx` bound to the calling
    thread (the query service executes every query on its own worker
    thread), and spans recorded on that thread land in that context.
    A thread with no bound context (a shuffle/IO pool helper) adopts the
    single active context when exactly one query is in flight — under
    concurrency its spans are dropped rather than misattributed.
    ``enabled`` is True while ANY query collects; record sites keep
    their one-attribute-read disabled cost."""

    def __init__(self):
        self.enabled = False
        self._lock = ordered_lock("obs.spans")
        self._ctxs: Dict[int, _QueryCtx] = {}  # owner tid -> ctx
        self._next_id = 0
        self._tls = threading.local()
        self._unobserved = 0  # in-flight queries with NO envelope

    # -- context resolution -------------------------------------------------
    def _ctx(self) -> Optional[_QueryCtx]:
        ctx = getattr(self._tls, "ctx", None)
        if ctx is _ADOPT_BLOCKED or ctx is _DETACHED:
            # this thread runs an UNOBSERVED query (or an observed one's
            # tail) concurrently with an observed one: its spans belong
            # to neither active ctx
            return None
        if ctx is not None and not ctx.closed:
            return ctx
        # helper thread: adopt the only active query, but ONLY while no
        # unobserved query is in flight anywhere — an unobserved
        # query's shuffle/IO pool work is indistinguishable from the
        # observed query's here, and misattribution is worse than a
        # dropped helper span
        with self._lock:
            if len(self._ctxs) == 1 and not self._unobserved:
                return next(iter(self._ctxs.values()))
        return None

    def begin_unobserved_query(self, query_id: Optional[int] = None) -> None:
        """Mark this thread as executing a query WITHOUT an observation
        envelope (event log and tracing off for its session): neither
        its own spans nor its helper-pool work may be adopted into some
        other session's concurrently active query context."""
        self._tls.ctx = _ADOPT_BLOCKED
        self._tls.query = query_id
        with self._lock:
            self._unobserved += 1

    def leave_query(self) -> None:
        """This thread is done with its query, the envelope's tail
        included: ranges it opens from here on carry no ``query`` and
        may be adopted again. Closes whatever ``begin_query`` /
        ``begin_unobserved_query`` left open (a failed query never
        reaches ``end_query``)."""
        ctx = getattr(self._tls, "ctx", None)
        if ctx is _ADOPT_BLOCKED:
            with self._lock:
                self._unobserved -= 1
        elif ctx is not None and ctx is not _DETACHED:
            self.end_query()
        self._tls.ctx = None
        self._tls.query = None

    def _stack(self, ctx: _QueryCtx) -> list:
        return ctx.stacks.setdefault(threading.get_ident(), [])

    # -- compat / introspection --------------------------------------------
    @property
    def _spans(self) -> List[Span]:
        """All in-flight spans across active contexts (tests/debug)."""
        with self._lock:
            return [s for c in self._ctxs.values() for s in c.spans]

    @property
    def main_tid(self) -> Optional[int]:
        """Owner thread of the CURRENT thread's query context."""
        ctx = self._ctx()
        return ctx.owner_tid if ctx is not None else None

    @property
    def query_id(self) -> Optional[int]:
        ctx = self._ctx()
        return ctx.query_id if ctx is not None else None

    @property
    def dropped(self) -> int:
        ctx = self._ctx()
        return ctx.dropped if ctx is not None else 0

    # -- collection --------------------------------------------------------
    def begin_query(self, query_id: int) -> _QueryCtx:
        tid = threading.get_ident()
        ctx = _QueryCtx(query_id, tid)
        with self._lock:
            self._ctxs[tid] = ctx
            self.enabled = True
        self._tls.ctx = ctx
        self._tls.query = query_id
        return ctx

    def end_query(self) -> List[Span]:
        """Stop collecting THIS thread's query and return its finished
        spans. The thread stays detached (no adoption, ``query`` still
        set) until ``leave_query``."""
        tid = threading.get_ident()
        with self._lock:
            ctx = self._ctxs.pop(tid, None)
            self.enabled = bool(self._ctxs)
        self._tls.ctx = _DETACHED
        if ctx is None:
            return []
        ctx.closed = True
        return [s for s in ctx.spans if s.t1 is not None]

    # the tracer's half of a range: only ``span()`` below calls these
    def _begin(self, name: str, cat: str, args) -> Optional[Span]:
        ctx = self._ctx()
        if ctx is None:
            return None
        st = self._stack(ctx)
        parent = st[-1].sid if st else None
        tid = threading.get_ident()
        with self._lock:
            if ctx.closed:
                return None
            if len(ctx.spans) >= _MAX_SPANS:
                ctx.dropped += 1
                return None
            self._next_id += 1
            sp = Span(self._next_id, name, cat, time.perf_counter(), tid,
                      threading.current_thread().name, parent, args or None,
                      ctx)
            ctx.spans.append(sp)
        st.append(sp)
        return sp

    def _end(self, span: Optional[Span]) -> None:
        if span is None or span.t1 is not None:
            return  # idempotent: an error path may re-end a closed span
        span.t1 = time.perf_counter()
        ctx = span.ctx
        st = ctx.stacks.get(span.tid) if ctx is not None else None
        if not st:
            return
        if st[-1] is span:
            st.pop()
        elif span in st:        # exception unwound past nested spans
            while st and st[-1] is not span:
                st.pop().t1 = span.t1
            if st:
                st.pop()

    # -- cross-host trace propagation ---------------------------------------
    def add_remote_spans(self, source: str, payload, anchor_t0: float,
                         cap: int = 256) -> int:
        """Merge span summaries shipped back by a cluster EXECUTOR into
        this thread's active query context (runtime/cluster.py scan
        replies). Each payload entry is ``{name, cat, t0, dur[, args]}``
        with ``t0`` relative to the executor's scan start; spans land on
        a synthetic per-source thread row (``executor-<host>``) so the
        Chrome trace shows one lane per executor host next to the
        driver's lanes. The executor clock is a DIFFERENT perf_counter
        domain — ``anchor_t0`` (the driver's dispatch-send time) anchors
        the remote window, so remote spans are positioned relative to
        the dispatch, exact in duration, approximate in offset by the
        one-way wire latency. Returns the number of spans merged."""
        if not self.enabled or not payload:
            return 0
        ctx = self._ctx()
        if ctx is None:
            return 0
        # stable synthetic tid per source, far above real thread idents'
        # typical range and deterministic across runs of one process
        tid = 0x52000000 + (hash(str(source)) & 0xFFFFF)
        tname = f"executor-{source}"
        merged = 0
        with self._lock:
            if ctx.closed:
                return 0
            for p in payload[:max(0, int(cap))]:
                if len(ctx.spans) >= _MAX_SPANS:
                    ctx.dropped += 1
                    continue
                try:
                    t0 = anchor_t0 + float(p["t0"])
                    dur = max(0.0, float(p["dur"]))
                    name = str(p["name"])
                except (KeyError, TypeError, ValueError):
                    continue  # a malformed entry degrades the trace only
                self._next_id += 1
                sp = Span(self._next_id, name, str(p.get("cat", "remote")),
                          t0, tid, tname, None, p.get("args") or None, ctx)
                sp.t1 = t0 + dur
                ctx.spans.append(sp)
                merged += 1
        return merged


TRACER = SpanTracer()


def span(name: str, cat: str = "op", **args):
    """Open one range (a context manager) on both sinks: the profiler's
    host timeline as ``srt.<cat>.<name>`` — always — and the tracer's
    span buffer while this thread's query collects. ``args`` and the
    thread's query index ride as annotation metadata. With the tracer
    idle the returned object IS the annotation: nothing else is
    allocated."""
    if name == cat or name.startswith(cat + "."):
        full = "srt." + name   # srt.query; names that carry their cat
    else:
        full = f"srt.{cat}.{name}"
    ann = _annotation(full, args)
    if not TRACER.enabled:
        return ann
    return _LiveSpan(ann, name, cat, args)


def _annotation(full: str, args: dict) -> TraceAnnotation:
    """A range's profiler half: ``full`` with the thread's query index
    and ``args`` as metadata."""
    query = getattr(TRACER._tls, "query", None)
    if query is None:
        return TraceAnnotation(full, **args)
    return TraceAnnotation(full, query=query, **args)


# ---------------------------------------------------------------------------
# Python's collector
# ---------------------------------------------------------------------------


class _GcState(threading.local):
    """This thread's seconds in Python's collector since the hook went in
    (a collection runs on the thread whose allocation set it off), and
    the collection open on it."""

    def __init__(self):
        self.seconds = 0.0
        self.open = None


_GC = _GcState()
#: {"installed": the token of the call that put the hook in}
_GC_HOOK: Dict[str, object] = {}


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook: each collection is the range
    ``srt.gc.gen<N>`` and adds its seconds to this thread's count. Two
    ``perf_counter`` reads and one annotation a collection. Only the
    annotation half of :func:`span`: the tracer's half takes its lock,
    which the collection may have interrupted this thread holding."""
    if phase == "start":
        ann = _annotation(f"srt.gc.gen{info['generation']}", {})
        ann.__enter__()
        _GC.open = (ann, time.perf_counter())
    elif _GC.open is not None:
        ann, t0 = _GC.open
        _GC.open = None
        _GC.seconds += time.perf_counter() - t0
        ann.__exit__(None, None, None)


def install_gc_hook() -> None:
    """Put :func:`_on_gc` on ``gc.callbacks``, once a process (the
    session calls it for the first query its event log or tracing
    observes)."""
    if "installed" in _GC_HOOK:
        return
    token = object()
    # setdefault is atomic: of two first queries, one puts the hook in
    if _GC_HOOK.setdefault("installed", token) is token:
        gc.callbacks.append(_on_gc)


def gc_seconds() -> float:
    """This thread's seconds in the collector since the hook went in: a
    query's ``phasesS.gcS`` is the difference across its envelope."""
    return _GC.seconds


# ---------------------------------------------------------------------------
# Chrome trace-event export
# ---------------------------------------------------------------------------


def to_chrome_trace(spans: List[Span], query_id=None) -> dict:
    """Chrome trace-event JSON (the ``traceEvents`` array form) — loads
    in Perfetto / chrome://tracing. Timestamps are microseconds on the
    perf_counter clock; complete events (``ph: "X"``) carry durations."""
    events = []
    threads = {}
    for s in spans:
        threads.setdefault(s.tid, s.tname)
        ev = {"name": s.name, "cat": s.cat, "ph": "X",
              "ts": round(s.t0 * 1e6, 3), "dur": round(s.dur * 1e6, 3),
              "pid": 1, "tid": s.tid}
        if s.args:
            ev["args"] = dict(s.args)
        events.append(ev)
    for tid, tname in sorted(threads.items()):
        events.append({"name": "thread_name", "ph": "M", "pid": 1,
                       "tid": tid, "args": {"name": tname}})
    trace = {"traceEvents": events, "displayTimeUnit": "ms"}
    if query_id is not None:
        trace["otherData"] = {"query": query_id}
    return trace


def write_chrome_trace(path: str, spans: List[Span], query_id=None) -> str:
    with open(path, "w") as f:
        json.dump(to_chrome_trace(spans, query_id), f)
    return path


# ---------------------------------------------------------------------------
# Span aggregation (the event record's span summary)
# ---------------------------------------------------------------------------


def union_seconds(intervals) -> float:
    """Total length covered by at least one [t0, t1) interval."""
    total = 0.0
    end = None
    for t0, t1 in sorted(intervals):
        if end is None or t0 > end:
            total += t1 - t0
            end = t1
        elif t1 > end:
            total += t1 - end
            end = t1
    return total


def summarize_spans(spans: List[Span], exec_tid: Optional[int],
                    wall_s: float) -> dict:
    """Per-query span summary: category totals (union per category, so
    nesting never double-counts), attribution of the query wall to
    NAMED spans on the thread that EXECUTED the query (the thread that
    opened the query context — the process main thread for direct
    ``session.execute`` calls, a service worker thread for scheduled
    queries), and helper-thread totals."""
    by_cat: Dict[str, list] = {}
    main_intervals = []
    worker: Dict[str, list] = {}
    for s in spans:
        by_cat.setdefault(s.cat, []).append((s.t0, s.t1))
        if s.tid == exec_tid:
            if s.cat != "query":
                main_intervals.append((s.t0, s.t1))
        else:
            worker.setdefault(s.cat, []).append((s.t0, s.t1))
    attributed = min(union_seconds(main_intervals), wall_s)
    return {
        "byCategoryS": {c: round(union_seconds(iv), 6)
                        for c, iv in sorted(by_cat.items())},
        "workerByCategoryS": {c: round(union_seconds(iv), 6)
                              for c, iv in sorted(worker.items())},
        "attributedS": round(attributed, 6),
        "untrackedS": round(max(wall_s - attributed, 0.0), 6),
        "spanCount": len(spans),
    }


# ---------------------------------------------------------------------------
# Exec-boundary instrumentation
# ---------------------------------------------------------------------------


def _observed(fn, e, name: str, count_output: bool):
    """Wrap one execute/execute_masked with per-pull spans + metrics.
    The per-instance ``_obs_depth`` guard keeps the two protocol layers
    of one exec (execute() delegating to execute_masked() or vice
    versa, both instance-wrapped) from double-counting a batch."""

    def wrapped(*args, **kwargs):
        it = fn(*args, **kwargs)
        while True:
            if e._obs_depth:
                # inner protocol layer of the SAME exec: pass through
                try:
                    batch = next(it)
                except StopIteration:
                    return
                yield batch
                continue
            e._obs_depth = 1
            t0 = time.perf_counter()
            stop = False
            try:
                with span(name, "exec"):
                    batch = next(it)
            except StopIteration:
                stop = True
            finally:
                e._obs_depth = 0
                e.metrics.add("opTime", time.perf_counter() - t0)
            if stop:
                if count_output:
                    # presence contract: an exec that ran to exhaustion
                    # always reports its output counts, even when zero
                    e.metrics.add("numOutputBatches", 0)
                    e.metrics.add("numOutputRows", 0)
                return
            if count_output:
                e.metrics.add("numOutputBatches", 1)
                nh = getattr(batch, "_nrows_host", None)
                if nh is not None:
                    e.metrics.add("numOutputRows", int(nh))
                else:
                    nd = getattr(batch, "nrows_dev", None)
                    if nd is not None:
                        # defer: nrows_dev is a tiny standalone device
                        # scalar — holding it pins ~4 bytes, not the
                        # table; finalize_observation fetches ALL
                        # pending counts in one host round trip
                        e._obs_pending_rows.append(nd)
                    else:
                        e.metrics.add("numOutputRows",
                                      int(getattr(batch, "num_rows", 0)))
            yield batch

    return wrapped


def install_observation(executable) -> None:
    """Wrap every device exec (and the DeviceToHost root) in the
    converted tree with the observation boundary. Installed per query by
    the session AFTER install_fault_boundaries, so spans/metrics see the
    fault-injected failures too. Idempotent per instance."""
    from spark_rapids_tpu.execs.base import DeviceToHost, TpuExec
    from spark_rapids_tpu.lore import _iter_tree
    for e in _iter_tree(executable):
        if getattr(e, "_obs_installed", False):
            continue
        if isinstance(e, TpuExec):
            e._obs_installed = True
            e._obs_depth = 0
            e._obs_pending_rows = []
            name = type(e).__name__
            e.execute = _observed(e.execute, e, name, count_output=True)
            e.execute_masked = _observed(e.execute_masked, e, name,
                                         count_output=True)
        elif isinstance(e, DeviceToHost):
            # DeviceToHost counts its own output rows on host (they are
            # free there) — the wrapper only adds opTime + the span
            e._obs_installed = True
            e._obs_depth = 0
            e._obs_pending_rows = []
            e.execute_cpu = _observed(e.execute_cpu, e, "DeviceToHost",
                                      count_output=False)


def finalize_observation(executable) -> None:
    """Resolve every deferred device row count in the tree with ONE
    batched host fetch (a single device round trip however many execs
    deferred), folding the sums into each exec's ``numOutputRows``.
    Called lazily — by the event-log writer, ``session.last_metrics``
    and the metrics audit — so a query nobody inspects never pays the
    sync."""
    from spark_rapids_tpu.lore import _iter_tree
    owners = []
    scalars = []
    for e in _iter_tree(executable):
        pend = getattr(e, "_obs_pending_rows", None)
        if pend:
            owners.append((e, len(pend)))
            scalars.extend(pend)
            e._obs_pending_rows = []
    if not scalars:
        return
    from spark_rapids_tpu.dispatch import host_fetch
    fetched = host_fetch(scalars)
    i = 0
    for e, n in owners:
        total = sum(int(v) for v in fetched[i:i + n])
        i += n
        e.metrics.add("numOutputRows", total)

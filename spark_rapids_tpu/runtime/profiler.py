"""Profiler / tracing subsystem.

Reference (SURVEY.md §5): (a) NVTX ranges everywhere
(``NvtxWithMetrics.scala``) for Nsight timelines; (b) the built-in async
profiler — ``profiler.scala`` ProfilerOnExecutor/OnDriver: JNI CUPTI
trace collection to a ProfileWriter, with driver-coordinated enable
windows keyed by job/time ranges (``spark.rapids.profile.*`` confs).

TPU mapping: XLA's profiler (Xprof) plays CUPTI's role —
``jax.profiler.start_trace/stop_trace`` writes a TensorBoard/Xprof trace
directory; ``jax.profiler.TraceAnnotation`` is the NVTX-range analog, and
``obs.spans.span`` is the one place the engine opens one (``srt.*`` on
the trace's host timeline). Enable windows: every
query, or a query-index range (``spark.rapids.profile.queryRanges`` e.g.
"2-5,8" — RangeConfMatcher semantics)."""

from __future__ import annotations

import contextlib
import os
import threading
from typing import Optional, Set

from spark_rapids_tpu.conf import RapidsConf, bool_conf, str_conf
from spark_rapids_tpu.lockorder import ordered_lock

PROFILE_ENABLED = bool_conf(
    "spark.rapids.profile.enabled", False,
    "Collect XLA (Xprof) device traces for queries (profiler.scala "
    "analog).")

PROFILE_PATH = str_conf(
    "spark.rapids.profile.pathPrefix", "/tmp/rapids_tpu_profile",
    "Directory prefix for collected trace sessions.")

PROFILE_QUERY_RANGES = str_conf(
    "spark.rapids.profile.queryRanges", "",
    "Query-index ranges to profile, e.g. \"0-2,5\" (empty = all queries "
    "when profiling is enabled). RangeConfMatcher syntax.")


def parse_ranges(spec: str) -> Optional[Set[int]]:
    """\"1-3,8\" -> {1,2,3,8}; empty/blank -> None (match all)
    (RangeConfMatcher.scala analog).

    Malformed specs raise a ValueError NAMING the conf key — the
    profiler parses at conf-read time (TpuProfiler.__init__), so a typo
    fails the session's first execute with an actionable message
    instead of a bare int() traceback at the first profiled query."""
    key = PROFILE_QUERY_RANGES.key

    def _bound(text: str, part: str) -> int:
        text = text.strip()
        try:
            v = int(text)
        except ValueError:
            raise ValueError(
                f"{key}: range entry {part!r} has non-integer bound "
                f"{text!r} (expected e.g. \"0-2,5\")") from None
        if v < 0:
            raise ValueError(
                f"{key}: range entry {part!r} has negative bound {v}")
        return v

    spec = (spec or "").strip()
    if not spec:
        return None
    out: Set[int] = set()
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if "-" in part:
            lo_s, _, hi_s = part.partition("-")
            if not lo_s.strip() or not hi_s.strip():
                raise ValueError(
                    f"{key}: range entry {part!r} is missing a bound "
                    f"(expected \"<lo>-<hi>\")")
            lo, hi = _bound(lo_s, part), _bound(hi_s, part)
            if lo > hi:
                raise ValueError(
                    f"{key}: range entry {part!r} is reversed "
                    f"({lo} > {hi})")
            out.update(range(lo, hi + 1))
        else:
            out.add(_bound(part, part))
    return out


class TpuProfiler:
    """Per-session profiler driver (ProfilerOnExecutor analog)."""

    def __init__(self, conf: RapidsConf):
        self.enabled = bool(conf.get_entry(PROFILE_ENABLED))
        self.path_prefix = str(conf.get_entry(PROFILE_PATH))
        # conf-read-time validation: a malformed queryRanges spec fails
        # HERE with the conf key named, not at the first profiled query
        self.ranges = parse_ranges(str(conf.get_entry(PROFILE_QUERY_RANGES)))
        self._query_index = 0
        self._lock = ordered_lock("profiler")
        self._active = 0
        self.sessions_written = 0

    def should_profile(self, query_index: int) -> bool:
        return self.enabled and (self.ranges is None
                                 or query_index in self.ranges)

    @contextlib.contextmanager
    def profile_query(self):
        """Wrap one query execution in a trace session; traces land under
        <prefix>/query_<N>/.

        Only TOP-LEVEL queries advance the query index: a nested query
        (cached-relation materialization inside an outer execute) rides
        the outer trace session and must NOT burn a ``queryRanges``
        slot, or every index after it would drift off the user's spec.
        XLA allows one trace session per process anyway, so nested (and
        concurrent) queries yield None."""
        with self._lock:
            nested = self._active > 0
            self._active += 1
            if not nested:
                idx = self._query_index
                self._query_index += 1
        try:
            if nested or not self.should_profile(idx):
                yield None
                return
            import jax
            path = os.path.join(self.path_prefix, f"query_{idx}")
            os.makedirs(path, exist_ok=True)
            jax.profiler.start_trace(path)
            try:
                yield path
            finally:
                jax.profiler.stop_trace()
                self.sessions_written += 1
        finally:
            with self._lock:
                self._active -= 1

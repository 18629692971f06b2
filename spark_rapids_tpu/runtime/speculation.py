"""Speculative sizing — deferred validation of data-dependent decisions.

The reference sizes every join's output exactly by syncing the gather-map
row count to the host (GpuHashJoin.scala:104-420 joinGatherer row counts,
JoinGatherer.scala) — on a discrete GPU that sync is microseconds. Here
every host sync is a device round trip that drains the dispatch pipeline
(its cost on an attached chip is an open question in PERF.md), so an
exact sync per operator puts a latency floor under multi-operator plans.

The answer: operators SPECULATE a static output capacity (e.g. a
hash join's output fits the probe side's bucket — true for every
foreign-key join), keep the real row count as a device scalar, and record
a device boolean "speculation failed" flag. Nothing syncs mid-plan; the
flags ride along and are validated by the ONE packed device fetch the
query already pays at collect time (columnar/table.py to_host). If any
flag is set the collect raises SpeculationFailed, the failing sites go on
a process-wide blocklist, and the session replays the query — the replay
takes the exact (sync-per-operator) path at those sites, so results are
always exact. Warm queries therefore run fully async: N dispatched
kernels, one round trip.
"""

from __future__ import annotations

import contextvars
import threading
from typing import List, Optional, Tuple

import jax
from spark_rapids_tpu.lockorder import ordered_lock


class SpeculationFailed(Exception):
    """A speculative capacity/layout guess was wrong; replay exactly."""

    def __init__(self, sites: List[str]):
        super().__init__(f"speculation failed at sites: {sites}")
        self.sites = list(sites)


class SpecContext:
    """Per-query-execution collection of pending speculation flags.

    A flag is a device bool scalar that is True when the speculation it
    guards FAILED. Flags are consumed (embedded into a packed fetch) by
    DeviceTable.to_host; any left over are validated with one extra fetch
    at the end of session.execute."""

    def __init__(self):
        self.pending: List[Tuple[str, jax.Array]] = []

    def add_flag(self, site_key: str, flag) -> None:
        self.pending.append((site_key, flag))

    def take_pending(self) -> List[Tuple[str, jax.Array]]:
        out = self.pending
        self.pending = []
        return out

    def validate_remaining(self) -> None:
        """Fetch + check any flags no packed fetch consumed (one sync)."""
        pending = self.take_pending()
        if not pending:
            return
        import jax.numpy as jnp
        vals = jax.device_get(jnp.stack([f for _, f in pending]))
        check_flag_values([s for s, _ in pending], vals)


def check_flag_values(sites: List[str], values) -> None:
    failed = [s for s, v in zip(sites, values) if bool(v)]
    if not failed:
        return
    sizing = [s for s in failed if not s.startswith("ansi:")]
    if sizing:
        # a sizing miss means downstream data (and any ANSI flags computed
        # from it) is untrustworthy — replay first; the exact replay
        # re-evaluates ANSI flags over correct intermediates
        raise SpeculationFailed(sizing)
    ansi = [s[len("ansi:"):] for s in failed]
    # an ANSI violation is a USER-FACING error, not a sizing miss:
    # raise it directly — replaying could not change the data
    from spark_rapids_tpu.errors import AnsiViolation
    raise AnsiViolation("[ANSI] " + "; ".join(sorted(set(ansi))))


_CTX: contextvars.ContextVar[Optional[SpecContext]] = contextvars.ContextVar(
    "rapids_spec_ctx", default=None)

#: sites whose speculation failed once — they take the exact path forever
#: after (per process), so a repeated query shape never replays twice.
_BLOCKLIST = set()
#: guards _BLOCKLIST writes: failed attempts on CONCURRENT query
#: workers blocklist sites at the same time (membership reads stay
#: lock-free — set containment is atomic under the GIL, and a stale
#: read only costs one extra speculative attempt)
_BLOCKLIST_LOCK = ordered_lock("speculation.blocklist")


def current() -> Optional[SpecContext]:
    return _CTX.get()


def activate() -> "contextvars.Token":
    return _CTX.set(SpecContext())


def deactivate(token) -> None:
    _CTX.reset(token)


def allowed(site_key: str) -> Optional[SpecContext]:
    """The active context, iff speculation is enabled for this site."""
    ctx = _CTX.get()
    if ctx is None or site_key in _BLOCKLIST:
        return None
    return ctx


def blocklist(sites) -> None:
    with _BLOCKLIST_LOCK:
        _BLOCKLIST.update(sites)


def guard_attempt(fn):
    """Run ``fn`` dropping any speculation flags it added if it raises —
    an OOM-aborted attempt's pending flags would otherwise be validated
    (and can spuriously blocklist the site) even though the attempt's
    results were discarded and replayed (ADVICE r3, execs/join.py).

    take_pending() REPLACES the pending list (a mid-attempt collect
    consumes flags), so the snapshot tracks the list identity: if the list
    changed, everything now pending was added by this attempt."""
    ctx = _CTX.get()
    snap_list = ctx.pending if ctx is not None else None
    snap_len = len(snap_list) if snap_list is not None else 0
    try:
        return fn()
    except BaseException:
        if ctx is not None:
            if ctx.pending is snap_list:
                del ctx.pending[snap_len:]
            else:
                ctx.pending.clear()
        raise

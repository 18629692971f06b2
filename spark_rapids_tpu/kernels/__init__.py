"""Pallas kernel layer — native two-limb 64-bit primitives.

PERF.md's measured cost model: dispatches pipeline for free and warm
uploads are zero, so the remaining per-row cost on the budget queries
is 64-bit EMULATION around scatters/gathers/sorts — i64/f64 split into
2-3 32-bit passes plus recombine chains. The HLO workarounds (masked
batches, split-f64 segment sums, segment_minmax_64) each shaved passes;
this layer removes them at the source: each hot primitive handles the
two-limb layout (ops/limbs.py — f64 as (f32, f32), i64 as hi/lo u32)
natively in ONE fused Pallas program:

  * ``sort``      — bitonic multi-column sort over packed key limbs +
                    payload permutation (kernels/sort.py), behind
                    ops/ordering.lex_sort;
  * ``segreduce`` — fused segmented min/max with the hi-limb-native /
                    lo-limb-tiebreak trick, and VMEM-built one-hot
                    split-sum partials (kernels/segreduce.py), behind
                    ops/segsum.py;
  * ``hashprobe`` — bounded-attempt hash-table probe for the join
                    (kernels/hashprobe.py), behind execs/join.py;
  * ``compact``   — one-kernel mask->gather row compaction over every
                    column of a table (kernels/compact.py), behind
                    the filter/join/table compaction sites.

Contract, enforced per primitive:

  * gated by ``spark.rapids.tpu.kernels.<name>.enabled`` ('auto' = on
    on the TPU backend for every program the Mosaic compiler accepts
    and that matches HLO bit for bit there — the rest stand down by
    the ``TPU_AUTO_OFF`` table below, so no process pays a failing
    compile and a query replay to find out; the CPU backend runs Pallas
    in INTERPRET mode — bit-identical, which is how tier-1 pins
    identity without TPU hardware — but slower than XLA:CPU, so auto
    keeps it off there);
  * the HLO path remains the fallback for every ineligible shape
    (``KernelIneligible``) and is BIT-IDENTICAL by construction —
    pinned by tests/test_kernels.py;
  * a crash (including a Mosaic lowering failure on a backend that
    cannot compile the kernel) demotes that primitive to HLO for the
    ENGINE PROCESS — the PR-3 circuit-breaker pattern — with the
    reason surfaced in explain() and the event log;
  * the enablement set + demotions fold into every trace cache key
    (``trace_token``) and the plan fingerprint (``demotion_token``),
    so cached trees never cross paths;
  * ``pallasKernels`` / ``hloFallbacks`` counters in the ``compile``
    metric scope record which path each primitive resolved to AT
    TRACE TIME (warm dispatches replay the already-traced choice).
"""

from __future__ import annotations

import contextvars
import threading
from typing import Callable, Dict, Optional

from spark_rapids_tpu.conf import (
    KERNELS_COMPACT_ENABLED,
    KERNELS_HASHPROBE_ATTEMPTS,
    KERNELS_HASHPROBE_ENABLED,
    KERNELS_SEGREDUCE_ENABLED,
    KERNELS_SEGREDUCE_MAX_SEGMENTS,
    KERNELS_SORT_ENABLED,
    KERNELS_VMEM_BUDGET,
)

PRIMITIVES = ("sort", "segreduce", "hashprobe", "compact")

_ENABLE_ENTRIES = {
    "sort": KERNELS_SORT_ENABLED,
    "segreduce": KERNELS_SEGREDUCE_ENABLED,
    "hashprobe": KERNELS_HASHPROBE_ENABLED,
    "compact": KERNELS_COMPACT_ENABLED,
}


#: What 'auto' leaves OFF on the TPU backend: primitive (every program
#: of it) or "<primitive>.<program>" -> what the toolchain said when the
#: program was built for a v5e, not interpreted, at the shapes the SF1
#: smoke produces (jax 0.9.0 / libtpu 0.0.34). These are structural
#: refusals: rewriting the kernels is ROADMAP A2's, with a cell to judge
#: the result. An explicit ``=true`` still forces a primitive on.
TPU_AUTO_OFF: Dict[str, str] = {
    "sort": (
        "MosaicError: INTERNAL: Mosaic failed to compile TPU kernel: "
        "infer-vector-layout: unsupported shape cast — \"tpu.reshape\" "
        "(vector<1024xi32>) -> vector<512x2x1xi32> (the whole-array "
        "(n/2d, 2, d) compare-exchange reshape; same at n=65536 and 1M)"),
    "compact": (
        "NotImplementedError: Only 2D gather is supported (jnp.take of "
        "each 1-D limb stream through the gather map)"),
    "hashprobe": (
        "NotImplementedError: Only 2D gather is supported (three 1-D "
        "jnp.take into the table per attempt)"),
    "segreduce.onehot_partials": (
        "compiles (once its index maps stopped returning an i64: "
        "\"failed to legalize operation 'func.return' ... (i32, i64)\"), "
        "but its f32 contraction is NOT bit-identical to the HLO einsum "
        "on the chip (capacity 1024 and 65536, 8 segments)"),
}


class KernelsConfig:
    """Resolved per-query kernel configuration (immutable snapshot).
    ``declined`` names single programs of an enabled primitive that
    stand down by TPU_AUTO_OFF (they decline per call, to HLO)."""

    __slots__ = ("enabled", "vmem_budget", "max_segments", "attempts",
                 "declined")

    def __init__(self, enabled=frozenset(), vmem_budget=64 << 20,
                 max_segments=8192, attempts=4, declined=frozenset()):
        self.enabled = frozenset(enabled)
        self.vmem_budget = int(vmem_budget)
        self.max_segments = int(max_segments)
        self.attempts = int(attempts)
        self.declined = frozenset(declined)


#: per-query resolved config, set by the placement layer at drain (the
#: MASKED_ENABLED / DIRECT_TABLE_MULT contextvar pattern: execs and ops
#: hold no conf handle). Default: everything off — a kernel must be
#: asked for.
KERNELS_ENABLED = contextvars.ContextVar("rapids_pallas_kernels",
                                         default=KernelsConfig())


def resolve_enabled(conf) -> KernelsConfig:
    """Resolve the spark.rapids.tpu.kernels.* keys for one query.
    'auto' means on on the TPU backend (where 64-bit emulation is the
    tax) except what TPU_AUTO_OFF stands down, and off elsewhere (CPU:
    native 64-bit; Pallas would run in interpret mode). The VMEM budget
    is what the kernels both admit against and hand the compiler as
    its scoped limit, so it is held to what the device has."""
    import jax
    on_tpu = jax.default_backend() == "tpu"
    names, declined = [], []
    for name, entry in _ENABLE_ENTRIES.items():
        mode = str(conf.get_entry(entry)).strip().lower()
        if mode in ("true", "1", "on"):
            names.append(name)
        elif mode in ("false", "0", "off"):
            pass
        elif on_tpu and name not in TPU_AUTO_OFF:  # auto
            names.append(name)
            declined += [k for k in TPU_AUTO_OFF
                         if k.startswith(name + ".")]
    vmem_budget = conf.get_entry(KERNELS_VMEM_BUDGET)
    if on_tpu:
        from jax.experimental.pallas import tpu as pltpu
        vmem_budget = min(vmem_budget,
                          pltpu.get_tpu_info().vmem_capacity_bytes)
    return KernelsConfig(
        enabled=names,
        vmem_budget=vmem_budget,
        max_segments=conf.get_entry(KERNELS_SEGREDUCE_MAX_SEGMENTS),
        attempts=conf.get_entry(KERNELS_HASHPROBE_ATTEMPTS),
        declined=declined)


# -- per-primitive circuit breaker ------------------------------------------

_LOCK = threading.Lock()
#: primitive -> demotion reason, PROCESS-WIDE like the PR-3 circuit
#: breaker: a kernel that crashed (or cannot lower on this backend) is
#: broken for every session sharing the device
_DEMOTED: Dict[str, str] = {}


def demote(name: str, exc: BaseException) -> None:
    """Demote one primitive to the HLO path for the rest of the engine
    process; the reason feeds explain()/event-log demotions."""
    first_line = str(exc).splitlines()[0] if str(exc) else type(exc).__name__
    with _LOCK:
        if name in _DEMOTED:
            return
        reason = (f"pallas kernel '{name}' demoted to HLO: "
                  f"{type(exc).__name__}: {first_line}")
        _DEMOTED[name] = reason
    from spark_rapids_tpu.runtime.faults import RECOVERY
    RECOVERY.bump("demotions")
    # flight-recorder hook (obs/telemetry.py): a kernel demotion is an
    # incident like a ladder action — best-effort, outside _LOCK
    try:
        from spark_rapids_tpu.obs.telemetry import record_incident
        record_incident("kernel.demotion", name, reason, error=exc)
    except Exception:
        pass


def demotion_reason(name: str) -> Optional[str]:
    with _LOCK:
        return _DEMOTED.get(name)


def demoted_ops() -> Dict[str, str]:
    """{'pallas:<name>': reason} — merged into the event record's
    demotions map next to the exec circuit breaker's entries."""
    with _LOCK:
        return {f"pallas:{n}": r for n, r in _DEMOTED.items()}


def reset() -> None:
    """Test support: forget demotions."""
    with _LOCK:
        _DEMOTED.clear()


def demotion_token() -> str:
    """Folds into the plan fingerprint (plan/fingerprint.py) so cached
    executables/results never cross a demotion boundary — the
    MESH.identity_token() pattern for runtime state the conf cannot
    see."""
    with _LOCK:
        return "kdem:" + ",".join(sorted(_DEMOTED))


# -- gating -----------------------------------------------------------------


def config() -> KernelsConfig:
    return KERNELS_ENABLED.get()


def enabled(name: str) -> bool:
    """Is this primitive live for the current query (enabled by conf
    and not demoted)? Read at TRACE time — callers fold trace_token()
    into their jit cache keys so a flipped answer re-traces."""
    if name not in KERNELS_ENABLED.get().enabled:
        return False
    with _LOCK:
        return name not in _DEMOTED


def trace_token() -> tuple:
    """Everything that changes which path a traced kernel embeds: the
    resolved enablement set minus demotions, plus the shape-affecting
    tuning values. Any jit cache key built around a kernels decision
    must include this."""
    cfg = KERNELS_ENABLED.get()
    with _LOCK:
        live = tuple(sorted(n for n in cfg.enabled if n not in _DEMOTED))
    return (live, cfg.vmem_budget, cfg.max_segments, cfg.attempts,
            tuple(sorted(cfg.declined)))


# -- dispatch helpers -------------------------------------------------------


class KernelIneligible(Exception):
    """A kernel module declining one call (shape/size outside its
    envelope) — the caller takes the HLO path for that call, with no
    demotion recorded."""


class _TraceCapture(threading.local):
    """Per-thread stack of 'primitives embedded while tracing this
    program' sets. dispatch.tpu_jit pushes one frame around each
    outermost jitted call: a kernel that traces fine but fails at
    BACKEND COMPILE / first execution (Mosaic lowering happens when the
    enclosing jit first runs, not at trace time) raises outside
    guarded(), and the frame tells tpu_jit which primitives to demote
    before re-raising as a replayable KernelCrashError."""

    def __init__(self):
        self.stack = []


_TRACE_CAPTURE = _TraceCapture()


def begin_trace_capture() -> set:
    frame: set = set()
    _TRACE_CAPTURE.stack.append(frame)
    return frame


def end_trace_capture(frame: set) -> None:
    if _TRACE_CAPTURE.stack and _TRACE_CAPTURE.stack[-1] is frame:
        _TRACE_CAPTURE.stack.pop()
    elif frame in _TRACE_CAPTURE.stack:  # defensive: unwind past it
        while _TRACE_CAPTURE.stack and _TRACE_CAPTURE.stack[-1] is not frame:
            _TRACE_CAPTURE.stack.pop()
        if _TRACE_CAPTURE.stack:
            _TRACE_CAPTURE.stack.pop()


def note_used(name: str) -> None:
    """Record a primitive embedded in the program currently TRACING on
    this thread (no-op outside a capture frame). guarded() calls it on
    success; kernel modules dispatched outside guarded() (the join's
    hashprobe) call it directly."""
    if _TRACE_CAPTURE.stack:
        _TRACE_CAPTURE.stack[-1].add(name)


def decline_if_auto_off(program: str) -> None:
    """Called by a program that TPU_AUTO_OFF may stand down while its
    primitive stays on: declines this call (HLO path, no demotion)."""
    if program in KERNELS_ENABLED.get().declined:
        raise KernelIneligible(
            f"{program} is auto-off on the TPU backend: "
            f"{TPU_AUTO_OFF[program]}")


def count_fallback(name: str, fallback: Callable):
    """Run (and count) the HLO path for a primitive that is disabled
    or ineligible. Counting happens at trace time — see module doc."""
    from spark_rapids_tpu.dispatch import COMPILE_SCOPE
    COMPILE_SCOPE.add("hloFallbacks", 1)
    return fallback()


def guarded(name: str, kernel_fn: Callable, fallback: Callable):
    """Run ``kernel_fn`` with the per-primitive circuit breaker:
    ``KernelIneligible`` falls back silently (counted); any other
    non-OOM failure — an injected ``kernels.<name>`` crash, a Pallas
    abstract-eval/trace failure — DEMOTES the primitive process-wide
    and falls back. Device OOMs re-raise: the retry framework owns
    those. Failures that only surface when the ENCLOSING jit first
    executes (Mosaic lowering / backend compile) are outside this
    wrapper — the trace-capture frames + dispatch.tpu_jit handle
    those."""
    from spark_rapids_tpu.dispatch import COMPILE_SCOPE
    try:
        out = kernel_fn()
    except KernelIneligible:
        COMPILE_SCOPE.add("hloFallbacks", 1)
        return fallback()
    except Exception as exc:
        from spark_rapids_tpu.runtime.crash_handler import (
            is_fatal_device_error,
        )
        from spark_rapids_tpu.runtime.retry import is_device_oom
        if is_device_oom(exc) or is_fatal_device_error(exc):
            # OOMs belong to the retry framework; a dead device is
            # the health monitor's to recover — demoting the kernel
            # for either would outlive the recovery (demotions are
            # process-permanent by design, for actual kernel faults)
            raise
        demote(name, exc)
        COMPILE_SCOPE.add("hloFallbacks", 1)
        return fallback()
    COMPILE_SCOPE.add("pallasKernels", 1)
    note_used(name)
    return out


def dispatch(name: str, kernel_fn: Callable, fallback: Callable):
    """THE standard primitive dispatch tail, shared by every router
    site (lex_sort, compact_pairs, the segsum routes): disabled ->
    counted HLO fallback; enabled -> guarded kernel with per-call
    ineligibility fallback and crash demotion."""
    if not enabled(name):
        return count_fallback(name, fallback)
    return guarded(name, kernel_fn, fallback)


def compiler_params():
    """Mosaic compiler parameters shared by every kernel: the scoped
    VMEM limit the compiler enforces IS the budget the eligibility
    checks admit against (``KernelsConfig.vmem_budget``). None in
    interpret mode, where no compiler runs."""
    if interpret_mode():
        return None
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(vmem_limit_bytes=config().vmem_budget)


def interpret_mode() -> bool:
    """Pallas interpret mode: on for the CPU backend (no Mosaic there;
    interpret is also what makes the bit-identity tests runnable in
    tier-1 without TPU hardware)."""
    import jax
    return jax.default_backend() == "cpu"

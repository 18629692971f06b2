"""From a JAX profiler trace (`.xplane.pb`) to device busy and idle
seconds, time per device operation and the longest idle gaps, each gap
named by what the host was doing in it.

Read with `jax.profiler.ProfileData` alone. What the planes of a TPU v5e
trace look like (looked at by hand, PR 24): one plane per chip,
`/device:TPU:<n>`, whose line `XLA Modules` holds one event per executed
program (`jit_kernel(<hash>)`, from its first operation to its last) and
whose line `XLA Ops` holds one event per executed HLO operation, named by
its whole HLO text (`%fusion.1 = s32[8,2]{...} fusion(...)`; nested for
control flow: a `while` spans its body's operations); the host's threads
are lines of the plane `/host:CPU`, and the harness's
`jax.profiler.TraceAnnotation` spans (`bench.plan`,
`bench.execute_fetch`) are events of the thread that ran the query. All
lines share one clock, in nanoseconds from the start of the trace.

Busy is the union of the programs' intervals (`XLA Modules`): a stall
between two operations of a running program is the program's time, not
the host's. The time per operation is its self time on `XLA Ops`, under
the name `<program> <result> <opcode> <shape>`.

    python3 benchmarks/trace_reduce.py <trace dir or .xplane.pb>
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
#: the harness's host spans: an idle gap is named by the one it falls in
SPAN_PREFIX = "bench."
#: a gap shorter than this is the pause between two operations of one
#: program, not something the host could fill
MIN_GAP_S = 50e-6


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return files[-1]


def union_intervals(intervals):
    """Sorted, merged [start, end) intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def self_times(events):
    """Seconds per operation name, a parent's time less its children's:
    `events` are (start, end, name) of one line, possibly nested."""
    totals = {}
    stack = []  # [start, end, name, child_ns]

    def close(entry):
        start, end, name, child = entry
        totals[name] = totals.get(name, 0) + (end - start - child)

    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][1] <= start:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(end, stack[-1][1]) - start
        stack.append([start, end, name, 0])
    while stack:
        close(stack.pop())
    return {name: ns / 1e9 for name, ns in totals.items()}


_HLO = re.compile(r"^(%[^ ]+) = (\(?[a-z0-9]+\[[0-9,]*\])?.*?\}?\)? ([a-z][a-z0-9-]*)\(")


def short_op_name(hlo_text: str) -> str:
    """`%fusion.1 = s32[8,2]{1,0:T(8,128)} fusion(...)` as
    `%fusion.1 fusion s32[8,2]`; anything else unchanged but cut."""
    m = _HLO.match(hlo_text)
    if not m:
        return hlo_text[:80]
    result, shape, opcode = m.groups()
    return f"{result} {opcode} {(shape or '').lstrip('(')}".strip()


def _program_of(modules, starts, start):
    """The name of the program (without its hash) running at `start`;
    `modules` are sorted and `starts` their start times."""
    i = bisect.bisect_right(starts, start) - 1
    if i >= 0 and start < modules[i][1]:
        return modules[i][2].split("(")[0]
    return "?"


def reduce_profile(profile) -> dict:
    """`profile` is a jax.profiler.ProfileData."""
    device_ops = {}      # plane name -> [(start, end, name)]
    device_modules = {}  # plane name -> [(start, end, name)]
    host_spans = []      # (start, end, name)
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    events = sorted(
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events)
                    (device_ops if line.name == OPS_LINE
                     else device_modules)[plane.name] = events
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        host_spans.append(
                            (e.start_ns, e.start_ns + e.duration_ns, e.name))
    if not device_ops or not any(device_ops.values()):
        raise ValueError("the trace holds no device operation: nothing ran "
                         "on the chip inside the traced window")
    # the traced window: from the first to the last thing recorded, host
    # span or device operation (the profiler records nothing at its own
    # start and stop that every trace has)
    for plane, ops in device_ops.items():
        modules = device_modules.get(plane, [])
        starts = [m[0] for m in modules]
        device_ops[plane] = [
            (s, e, f"{_program_of(modules, starts, s)} {short_op_name(n)}")
            for s, e, n in ops]
    every = [iv for ops in device_ops.values() for iv in ops] + host_spans
    t_first = min(iv[0] for iv in every)
    t_last = max(iv[1] for iv in every)
    window_s = (t_last - t_first) / 1e9

    busy = []
    gaps = {}
    ops_s = {}
    for plane, ops in device_ops.items():
        running = device_modules.get(plane) or ops
        merged = union_intervals([(s, e) for s, e, _ in running])
        busy.append(sum(e - s for s, e in merged) / 1e9)
        edges = [[t_first, t_first]] + merged + [[t_last, t_last]]
        for (_, prev_end), (next_start, _) in zip(edges, edges[1:]):
            gap_s = (next_start - prev_end) / 1e9
            if gap_s >= MIN_GAP_S:
                name = _host_activity(host_spans, prev_end, next_start)
                gaps[name] = gaps.get(name, 0.0) + gap_s
        for name, secs in self_times(ops).items():
            ops_s[name] = ops_s.get(name, 0.0) + secs
    chips = len(device_ops)
    return {
        "busy_s": sum(busy) / chips,
        "window_s": window_s,
        "chips": chips,
        "device_ops": [[n, s / chips] for n, s in sorted(
            ops_s.items(), key=lambda kv: -kv[1])],
        "idle_gaps": [[n, s / chips] for n, s in sorted(
            gaps.items(), key=lambda kv: -kv[1])],
    }


def _host_activity(spans, start, end) -> str:
    """The harness span that covers most of [start, end), or the pause
    between two of them."""
    best, best_ns = "between queries", 0
    for s, e, name in spans:
        overlap = min(e, end) - max(s, start)
        if overlap > best_ns:
            best, best_ns = name, overlap
    return best


def reduce_dir(path: str) -> dict:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(find_xplane(path)))


if __name__ == "__main__":
    print(json.dumps(reduce_dir(sys.argv[1]), indent=1))

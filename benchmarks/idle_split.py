"""Every idle instant of a chip put down to what the query's host thread
was doing then, by the program's own ranges.

The program opens each of its ranges as a `jax.profiler.TraceAnnotation`
named `srt.<cat>.<name>` (`spark_rapids_tpu/obs/spans.span`), so they lie
in `/host:CPU` on the device planes' clock. The query thread is the host
line that holds the harness's `bench.*` spans; ranges on other lines
(reader pools, the runtime's threads) are not read. JAX may append the
annotation's metadata to its name as `#query=3#`: that is cut off.

A chip's idle is the complement, inside the traced window, of the union of
its programs' intervals (what `trace_reduce` calls busy), with no least
gap, so the parts of a chip add up to its window less its busy time. Each
idle instant goes to the first bucket that holds:

1. `gc`: an `srt.gc.*` range is open (Python's collector);
2. `outside`: no `srt.*` range is open (the harness's loop, or program
   code that opens none);
3. `observe`: `srt.phase.observe` is open (the record, the event log);
4. `front`: `srt.phase.parse` or `srt.phase.plan` is open;
5. `enqueue`: the innermost range is `srt.dispatch.*`;
6. `transfer`: the innermost range is `srt.sync.*`, `srt.fetch.*` or
   `srt.transfer.*`;
7. `exec_host`: anything else (`srt.phase.execute|collect`, `srt.exec.*`,
   `srt.join.*`, a bare `srt.query`, ...).

`by_range` splits the same seconds by the innermost range's name
(`outside` where none is open). Both are averaged over the chips.

The window and each chip's busy time are `trace_reduce.reduce_profile`'s
(the `srt.*` ranges never bound the window). The harness's reduction does
not call this module (a PR that changes the program may not edit
`trace_reduce.py`), so the split is a builder's probe:

    python3 benchmarks/idle_split.py <trace dir or .xplane.pb>
    python3 benchmarks/idle_split.py --run <benchmarks/run.py's arguments>

The second runs the harness and prints the split of its traced window
(`--trace 1`) on stderr, as `idle_split: {...}`, before the harness
deletes the trace.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:  # run as a script
    sys.path.insert(0, ROOT)

from benchmarks import trace_reduce  # noqa: E402

BENCH_PREFIX = "bench."
SRT_PREFIX = "srt."
OUTSIDE = "outside"
BUCKETS = ("gc", OUTSIDE, "observe", "front", "enqueue", "transfer",
           "exec_host")
_TRANSFER = ("srt.sync.", "srt.fetch.", "srt.transfer.")


def range_name(name: str) -> str:
    """An annotation's name without the `#key=value#` metadata."""
    return name.split("#", 1)[0]


def bucket_of(stack) -> str:
    """The bucket of an instant whose open ranges are `stack`, outermost
    first."""
    if any(n.startswith("srt.gc.") for n in stack):
        return "gc"
    if not stack:
        return OUTSIDE
    if "srt.phase.observe" in stack:
        return "observe"
    if "srt.phase.parse" in stack or "srt.phase.plan" in stack:
        return "front"
    inner = stack[-1]
    if inner.startswith("srt.dispatch."):
        return "enqueue"
    if inner.startswith(_TRANSFER):
        return "transfer"
    return "exec_host"


def query_line_ranges(host_plane):
    """(start, end, name) of the `srt.*` events on the line of
    `host_plane` that holds the most `bench.*` events; [] where none
    does."""
    best, best_n = [], 0
    for line in host_plane.lines:
        events = list(line.events)
        n = sum(1 for e in events if e.name.startswith(BENCH_PREFIX))
        if n > best_n:
            best_n = n
            best = [(e.start_ns, e.start_ns + e.duration_ns,
                     range_name(e.name))
                    for e in events if e.name.startswith(SRT_PREFIX)]
    return best


def segments(ranges, t_first, t_last):
    """[t_first, t_last] cut where the set of open ranges changes:
    sorted (start, end, bucket, innermost name). Ranges of one thread
    nest; one that outlives its parent is cut at the parent's end."""
    out = []
    stack = []   # [(end, name)], outermost first
    t = t_first

    def emit(until):
        nonlocal t
        until = min(until, t_last)
        if until > t:
            names = [n for _, n in stack]
            out.append((t, until, bucket_of(names),
                        names[-1] if names else OUTSIDE))
            t = until

    for start, end, name in sorted(ranges, key=lambda r: (r[0], -r[1])):
        while stack and stack[-1][0] <= start:
            emit(stack[-1][0])
            stack.pop()
        emit(start)
        if stack:
            end = min(end, stack[-1][0])
        stack.append((end, name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    emit(t_last)
    return out


def idle_intervals(busy, t_first, t_last):
    """The complement of sorted, merged `busy` intervals in
    [t_first, t_last]."""
    out = []
    t = t_first
    for start, end in busy:
        if start > t:
            out.append((t, min(start, t_last)))
        t = max(t, end)
        if t >= t_last:
            break
    if t < t_last:
        out.append((t, t_last))
    return [(s, e) for s, e in out if e > s]


def split(ranges, busy_by_chip, t_first, t_last) -> dict:
    """`ranges`: the query thread's (start, end, name) in ns;
    `busy_by_chip`: each chip's sorted, merged busy intervals. Seconds
    a bucket and an innermost range, averaged over the chips."""
    segs = segments(ranges, t_first, t_last)
    buckets = dict.fromkeys(BUCKETS, 0.0)
    by_range = {}
    for busy in busy_by_chip:
        i = 0
        for start, end in idle_intervals(busy, t_first, t_last):
            while i < len(segs) and segs[i][1] <= start:
                i += 1
            j = i
            while j < len(segs) and segs[j][0] < end:
                s0, s1, bucket, name = segs[j]
                ns = min(s1, end) - max(s0, start)
                buckets[bucket] += ns
                by_range[name] = by_range.get(name, 0) + ns
                j += 1
    chips = max(len(busy_by_chip), 1)
    return {
        "buckets": {b: ns / 1e9 / chips for b, ns in buckets.items()},
        "by_range": {n: ns / 1e9 / chips for n, ns in sorted(
            by_range.items(), key=lambda kv: -kv[1])},
    }


def split_profile(profile) -> dict:
    """`split` of a `jax.profiler.ProfileData`, with the traced queries
    (the query thread's `bench.execute_fetch` spans) and each bucket's
    milliseconds a query."""
    busy_by_chip, ranges, bench = [], [], []
    for plane in profile.planes:
        if plane.name.startswith(trace_reduce.DEVICE_PLANE_PREFIX):
            lines = {line.name: [(e.start_ns, e.start_ns + e.duration_ns)
                                 for e in line.events]
                     for line in plane.lines}
            if lines.get(trace_reduce.OPS_LINE):
                busy_by_chip.append(trace_reduce.union_intervals(
                    lines.get(trace_reduce.MODULES_LINE)
                    or lines[trace_reduce.OPS_LINE]))
        elif plane.name == trace_reduce.HOST_PLANE:
            ranges = query_line_ranges(plane)
            bench = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                     for line in plane.lines for e in line.events
                     if e.name.startswith(BENCH_PREFIX)]
    if not busy_by_chip:
        raise ValueError("the trace holds no device program")
    # the window as trace_reduce bounds it: bench spans and device work
    every = bench + [iv for b in busy_by_chip for iv in b]
    t_first = min(iv[0] for iv in every)
    t_last = max(iv[1] for iv in every)
    out = split(ranges, busy_by_chip, t_first, t_last)
    queries = sum(1 for *_, n in bench if n == "bench.execute_fetch")
    out["queries"] = queries
    if queries:
        out["ms_per_query"] = {b: 1e3 * s / queries
                               for b, s in out["buckets"].items()}
    return out


def split_dir(path: str) -> dict:
    from jax.profiler import ProfileData
    return split_profile(ProfileData.from_file(trace_reduce.find_xplane(path)))


def run_harness(argv) -> None:
    """benchmarks/run.py with the split of its trace printed on stderr
    before its own reduction deletes the trace."""
    from benchmarks import run
    reduce_dir = trace_reduce.reduce_dir

    def reduce_and_split(path):
        print(f"idle_split: {json.dumps(split_dir(path))}", file=sys.stderr,
              flush=True)
        return reduce_dir(path)

    trace_reduce.reduce_dir = reduce_and_split
    run.main(argv)


if __name__ == "__main__":
    if sys.argv[1:2] == ["--run"]:
        run_harness(sys.argv[2:])
    else:
        print(json.dumps(split_dir(sys.argv[1]), indent=1))

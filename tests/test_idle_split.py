"""The device's idle put down to the program's own ranges
(`benchmarks/idle_split.py`, a probe beside `trace_reduce.reduce_profile`),
on a synthetic profile shaped like `jax.profiler.ProfileData`: device
planes with `XLA Modules` and `XLA Ops` lines, and a `/host:CPU` plane
whose query thread holds the harness's `bench.*` spans and the
program's `srt.*` ranges. Times are microseconds, written in ns."""

import os
from types import SimpleNamespace

import pytest

from benchmarks import idle_split, trace_reduce

US = 1000  # ns


def _event(name, start_us, end_us):
    return SimpleNamespace(name=name, start_ns=start_us * US,
                           duration_ns=(end_us - start_us) * US, stats=())


def _line(name, events):
    return SimpleNamespace(name=name, events=[_event(*e) for e in events])


def _chip(n, busy):
    """A device plane whose programs run over the `busy` intervals."""
    modules = [(f"jit_p{i}(123)", s, e) for i, (s, e) in enumerate(busy)]
    ops = [(f"%fusion.{i} = f32[8]{{0}} fusion(%p)", s, e)
           for i, (s, e) in enumerate(busy)]
    return SimpleNamespace(name=f"/device:TPU:{n}", lines=[
        _line("XLA Modules", modules), _line("XLA Ops", ops)])


def _profile(query_thread, other_thread=(), busy=((0, 10), (110, 120)),
             chips=None):
    """One query thread (its bench.* spans added), an optional second
    host line, and one chip per entry of `chips` (default: `busy`)."""
    bench = [("bench.plan", 35, 60), ("bench.execute_fetch", 60, 120)]
    host = SimpleNamespace(name="/host:CPU", lines=[
        _line("python", bench + list(query_thread)),
        _line("pool", list(other_thread))])
    planes = [_chip(i, b) for i, b in enumerate(chips or [busy])]
    return SimpleNamespace(planes=planes + [host])


#: idle is (10, 110) us: one gap that crosses every bucket in turn
_CROSSING = [
    ("srt.query", 0, 30),
    ("srt.phase.observe", 10, 30),     # observe 20, with its
    ("srt.eventlog.write", 20, 30),    # event log's write
    # (30, 40) no range: outside 10 (+ 5 below)
    ("srt.phase.parse", 40, 55),       # front 15
    ("srt.query", 60, 120),
    ("srt.phase.plan", 60, 70),        # front 10
    ("srt.phase.execute", 70, 110),
    ("srt.exec.TpuHashAggregateExec", 70, 110),
    ("srt.dispatch.agg_fast", 75, 85),  # enqueue 10
    ("srt.sync.host_fetch", 85, 95),   # transfer 10
    ("srt.gc.gen2", 95, 100),          # gc 5
    # exec_host: (70, 75) + (100, 110) = 15
]
_EXPECTED_US = {"observe": 20, "outside": 15, "front": 25, "enqueue": 10,
                "transfer": 10, "gc": 5, "exec_host": 15}


@pytest.mark.parametrize("bucket", idle_split.BUCKETS)
def test_a_gap_crossing_every_range_is_split_by_precedence(bucket):
    split = idle_split.split_profile(_profile(_CROSSING))
    assert split["buckets"][bucket] == pytest.approx(
        _EXPECTED_US[bucket] * 1e-6)


def test_by_range_names_the_innermost_range():
    by_range = idle_split.split_profile(_profile(_CROSSING))["by_range"]
    assert by_range == pytest.approx({
        "srt.phase.observe": 10e-6, "srt.eventlog.write": 10e-6,
        "outside": 15e-6, "srt.phase.parse": 15e-6,
        "srt.phase.plan": 10e-6, "srt.exec.TpuHashAggregateExec": 15e-6,
        "srt.dispatch.agg_fast": 10e-6, "srt.sync.host_fetch": 10e-6,
        "srt.gc.gen2": 5e-6})


@pytest.mark.parametrize("stack,bucket", [
    (["srt.query", "srt.phase.observe", "srt.sync.host_fetch"], "observe"),
    (["srt.query", "srt.phase.observe", "srt.eventlog.write"], "observe"),
    (["srt.query", "srt.phase.plan", "srt.dispatch.concat"], "front"),
    (["srt.phase.parse", "srt.exec.TpuScanExec"], "front"),
    (["srt.query", "srt.phase.execute", "srt.exec.TpuSortExec",
      "srt.dispatch.sort_run"], "enqueue"),
    (["srt.query", "srt.phase.collect", "srt.fetch.resolve",
      "srt.fetch.wait"], "transfer"),
    (["srt.query", "srt.phase.execute", "srt.transfer.DeviceToHost"],
     "transfer"),
    (["srt.query", "srt.phase.execute", "srt.sync.host_fetch",
      "srt.exec.TpuScanExec"], "exec_host"),
    (["srt.query", "srt.phase.execute", "srt.dispatch.agg_fast",
      "srt.join.batch"], "exec_host"),
    (["srt.query"], "exec_host"),
    (["srt.query", "srt.phase.observe", "srt.gc.gen0"], "gc"),
    ([], "outside"),
], ids=lambda v: v if isinstance(v, str) else "-".join(v) or "none")
def test_the_innermost_range_wins_but_observe_and_front_win_by_ancestor(
        stack, bucket):
    assert idle_split.bucket_of(stack) == bucket


def test_the_metadata_jax_appends_to_a_name_is_cut_off():
    ranges = [("srt.query#query=3#", 0, 120),
              ("srt.phase.plan#query=3,rows=7#", 10, 110)]
    split = idle_split.split_profile(_profile(ranges))
    assert split["buckets"]["front"] == pytest.approx(100e-6)
    assert set(split["by_range"]) == {"srt.phase.plan"}


def test_ranges_on_another_host_line_are_not_read():
    pool = [("srt.dispatch.assemble", 10, 110), ("srt.gc.gen2", 20, 30)]
    split = idle_split.split_profile(_profile([], other_thread=pool))
    assert split["buckets"]["outside"] == pytest.approx(100e-6)
    assert split["by_range"] == pytest.approx({"outside": 100e-6})


def test_two_chips_are_averaged():
    # chip 0 idles (10, 110) under the dispatch, chip 1 only (10, 60)
    ranges = [("srt.query", 0, 120), ("srt.dispatch.agg_fast", 10, 110)]
    profile = _profile(
        ranges, chips=[[(0, 10), (110, 120)], [(0, 10), (60, 120)]])
    reduced = trace_reduce.reduce_profile(profile)
    assert reduced["chips"] == 2
    split = idle_split.split_profile(profile)
    assert split["buckets"]["enqueue"] == pytest.approx(75e-6)
    assert sum(split["buckets"].values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"])


@pytest.mark.parametrize("busy", [
    ((0, 10), (110, 120)),
    ((0, 5), (20, 25), (24, 50), (90, 120)),
    ((15, 40),),
], ids=["one-gap", "overlapping-programs", "idle-at-both-ends"])
def test_the_buckets_add_up_to_the_idle(busy):
    profile = _profile(_CROSSING, busy=busy)
    reduced = trace_reduce.reduce_profile(profile)
    split = idle_split.split_profile(profile)
    parts = split["buckets"]
    assert sum(parts.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"])
    assert sum(split["by_range"].values()) == pytest.approx(
        sum(parts.values()))


def test_the_program_ranges_change_nothing_else_of_the_reduction():
    with_ranges = trace_reduce.reduce_profile(_profile(_CROSSING))
    without = trace_reduce.reduce_profile(_profile([]))
    for key in ("busy_s", "window_s", "chips", "device_ops", "idle_gaps"):
        assert with_ranges[key] == without[key], key
    # a range that starts before every bench span and device op does not
    # widen the window: only bench.* spans and device ops bound it
    early = trace_reduce.reduce_profile(_profile([("srt.query", -50, 5)]))
    assert early["window_s"] == without["window_s"]


def test_a_query_is_a_bench_execute_fetch_span():
    split = idle_split.split_profile(_profile(_CROSSING))
    assert split["queries"] == 1
    assert split["ms_per_query"] == pytest.approx(
        {b: us * 1e-3 for b, us in _EXPECTED_US.items()})


def test_a_recorded_chip_trace_splits_into_its_idle():
    """The TPU trace the harness's tests replay (benchmarks/data/)."""
    recorded = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "data",
        "small_trace.xplane.pb")
    reduced = trace_reduce.reduce_dir(recorded)
    split = idle_split.split_dir(recorded)
    assert sum(split["buckets"].values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-9)

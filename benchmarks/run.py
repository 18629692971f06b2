#!/usr/bin/env python3
"""One run of one benchmark cell, in one process that owns the chip.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds everything by the names in BENCHMARK.json: the cell's configuration
file, `traffic/<mix>.json`, `queries/<id>.sql` with `reference/<id>.py`,
`limits/<cell>.json`, `datagen/<generator>.py`, `layer_metrics/<name>.py`.
Set-up (generate the tables from the seed, land them, warm every statement
of the mix) runs to the start of the window; the window sends the mix for
`--seconds`; then the device peak is read, the engine's state dropped, and
every answer of the window compared with the plain reference. The last
line of standard output is the result.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import compare, costs, sut, traffic  # noqa: E402

#: the traced part of a `--trace 1` window: this long, and whole queries
TRACE_SECONDS = 6.0
TRACE_MIN_QUERIES = 2
#: a statement is warmed until a run of it compiles nothing, at most so often
MAX_WARMUP_RUNS = 5


def log(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T_PROCESS:8.2f}s] {msg}",
          file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def resolve_cell(bench: dict, name: str):
    """The cell's entry, configuration, mix and limits, by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"known: {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(ROOT, entry["file"])
    mix = traffic.load_mix(cell["traffic"])
    limits = load_json(HERE, "limits", f"{name}.json")
    return cell, config, mix, limits


def require_chips(chips: int) -> dict:
    """The attached accelerator as JAX reports it; exits 2, printing no
    result, unless the platform is `tpu` with the chips the cell needs."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"benchmarks/run.py: the cell needs {chips} chip(s) of "
              f"platform 'tpu'; JAX found {len(devices)} device(s) of "
              f"platform {devices[0].platform!r}. No CPU run is a "
              f"measurement.", file=sys.stderr)
        sys.exit(2)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def device_memory() -> dict:
    """The fullest chip's bytes in use now and its peak since the process
    began (the device runtime's allocator; the peak never falls)."""
    import jax
    stats = [d.memory_stats() for d in jax.devices()]
    return {"in_use": max(int(s["bytes_in_use"]) for s in stats),
            "peak": max(int(s["peak_bytes_in_use"]) for s in stats)}


class CompileWatch:
    """Counts the executables JAX builds or loads from its persistent
    cache, by its own `backend_compile_duration` events."""

    def __init__(self):
        import jax
        self.backend_compiles = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_duration(self, event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_compiles += 1


def run_window(engine, mix, seconds, tracer):
    """The closed loop: one client sends the mix's statements, each after
    the last one's answer is collected, until `seconds` have passed.
    Returns the queries as dicts and the window's wall seconds (to the
    last answer: the rate is over all the work and all the time)."""
    queries = []
    stream = traffic.stream(mix)
    t_open = time.perf_counter()
    deadline = t_open + seconds
    t_last = t_open
    while time.perf_counter() < deadline:
        query_id, params = next(stream)
        text = traffic.statement(query_id, params)
        tracer.before_query()
        q = {"id": query_id, "params": params, "text": text,
             "traced": tracer.active}
        t0 = time.perf_counter()
        try:
            q["answer"], q["record"] = engine.query(text, tracer.annotate())
        except Exception:  # the loop's boundary: a failed query is counted
            q["error"] = traceback.format_exc()
            log(f"query {len(queries)} ({query_id}) failed:\n{q['error']}")
        t_last = time.perf_counter()
        q["latency_s"] = t_last - t0
        queries.append(q)
        tracer.after_query()
    tracer.stop()
    return queries, t_last - t_open


class Tracer:
    """The JAX profiler over the first seconds of the window (whole
    queries), with the harness's own spans around plan and execute+fetch.
    With `--trace 0` it does nothing."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.active = False
        self.dir = None
        self.t_start = None
        self.queries = 0
        self._done = False

    def before_query(self):
        if self.enabled and not self.active and not self._done:
            import jax
            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=options)
            self.t_start = time.perf_counter()
            self.active = True

    def after_query(self):
        if not self.active:
            return
        self.queries += 1
        if (time.perf_counter() - self.t_start >= TRACE_SECONDS
                and self.queries >= TRACE_MIN_QUERIES):
            self.stop()

    def stop(self):
        if self.active:
            import jax
            jax.profiler.stop_trace()
            self.active = False
            self._done = True

    def annotate(self):
        if not self.active:
            return None
        import jax
        return jax.profiler.TraceAnnotation

    def reduce(self):
        """The trace as busy/idle seconds, per-op time and idle gaps."""
        if self.dir is None:
            return None
        from benchmarks import trace_reduce
        try:
            reduced = trace_reduce.reduce_dir(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        reduced["queries"] = self.queries
        return reduced


def check_answers(queries, tables, limits):
    """Every answer of the window against the plain reference (computed
    once per distinct statement). Returns the numbers compared, each
    beside its limit, and whether all hold."""
    references = {}
    worst = {}
    for q in queries:
        if "answer" not in q:
            continue
        key = (q["id"], json.dumps(q["params"], sort_keys=True))
        if key not in references:
            module = importlib.import_module(f"benchmarks.reference.{q['id']}")
            t0 = time.perf_counter()
            references[key] = module.run(tables, q["params"])
            log(f"reference {q['id']} {q['params']}: "
                f"{time.perf_counter() - t0:.2f} s")
        mismatches, gap = compare.compare_answer(q["answer"], references[key])
        w = worst.setdefault(q["id"], {"exact_mismatches": 0,
                                       "max_rel_err": 0.0, "answers": 0})
        w["exact_mismatches"] += mismatches
        w["max_rel_err"] = max(w["max_rel_err"], gap)
        w["answers"] += 1
    checks = {}
    for query_id, w in sorted(worst.items()):
        checks[f"{query_id}.exact_mismatches"] = {
            "value": w["exact_mismatches"], "limit": 0}
        checks[f"{query_id}.max_rel_err"] = {
            "value": w["max_rel_err"],
            "limit": float(limits["max_rel_err"][query_id])}
        checks[f"{query_id}.answers_compared"] = {"value": w["answers"]}
    never_came = sum(1 for q in queries if "answer" not in q)
    checks["answers_missing"] = {"value": never_came, "limit": 0}
    ok = bool(worst) and all(
        c["value"] <= c["limit"] for c in checks.values() if "limit" in c)
    return checks, ok


def run_cell(cell, config, mix, limits, bench, seed, seconds, trace,
             device, engine_factory, memory_reader=device_memory):
    """Everything after the look for a chip: returns the result line's
    object. `engine_factory` builds the system under test (the tests pass
    one whose timed path is broken underneath)."""
    watch = CompileWatch()
    setup = {}

    generator = importlib.import_module(
        f"benchmarks.datagen.{config['generator']}")
    t0 = time.perf_counter()
    tables = generator.generate(config, seed)
    setup["generate_s"] = time.perf_counter() - t0
    log(f"generated {({k: t['num_rows'] for k, t in tables.items()})} "
        f"in {setup['generate_s']:.2f} s")

    engine = engine_factory(config)
    t0 = time.perf_counter()
    engine.register(tables)
    setup["register_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    setup["landed_bytes"] = engine.land()
    setup["landing_s"] = time.perf_counter() - t0
    memory = memory_reader()
    setup["landed_in_use_bytes"] = memory["in_use"]
    setup["landing_peak_bytes"] = memory["peak"]
    log(f"landed {setup['landed_bytes']} bytes in {setup['landing_s']:.2f} s; "
        f"device memory {memory}")

    # warm-up: every statement the mix can send, until the engine reports
    # a run of it without compiling (the first run of a plan sizes its
    # intermediates, and the second may still meet a new shape)
    t0 = time.perf_counter()
    first = None
    for query_id, params in traffic.distinct_statements(mix):
        text = traffic.statement(query_id, params)
        for attempt in range(MAX_WARMUP_RUNS):
            before = watch.backend_compiles
            t1 = time.perf_counter()
            _answer, record = engine.query(text)
            took = time.perf_counter() - t1
            if first is None:
                first = took
            compiled = (record["compileMs"] or 0) > 0 \
                or watch.backend_compiles > before
            log(f"warm-up {query_id} run {attempt}: {took:.3f} s, "
                f"compileMs {record['compileMs']}, dispatches "
                f"{record['dispatches']}")
            if not compiled and attempt >= 1:
                break
    setup["warmup_query_s"] = first
    setup["warmup_s"] = time.perf_counter() - t0

    tracer = Tracer(bool(trace))
    compiles_before = watch.backend_compiles
    setup["setup_s"] = time.perf_counter() - T_PROCESS
    queries, window_s = run_window(engine, mix, seconds, tracer)
    compiles_in_window = watch.backend_compiles - compiles_before
    log(f"window: {len(queries)} queries in {window_s:.3f} s")

    peak = memory_reader()["peak"]
    engine.close()
    del engine

    done = [q for q in queries if "answer" in q]
    off_path = [q for q in done if sut.off_device_path(q["record"])]
    for q in off_path[:5]:
        log(f"query left the device path: "
            f"{sut.off_device_path(q['record'])} {q['record']}")
    failed = (len(queries) - len(done)) + len(off_path)

    run = {
        "cell": cell, "config": config, "device": device, "setup": setup,
        "queries": queries, "window_s": window_s,
        "backend_compiles_in_window": compiles_in_window,
        "memory_peak_bytes": peak,
        "rows_per_query": {}, "scan_bytes_per_query": {},
        "peaks": load_json(HERE, "peaks.json"),
        "trace": tracer.reduce(),
    }
    for q in queries:
        if q["id"] not in run["rows_per_query"]:
            run["rows_per_query"][q["id"]] = costs.rows_read(tables, q["text"])
            run["scan_bytes_per_query"][q["id"]] = costs.scan_bytes(
                tables, q["text"])

    device_out = dict(device, memory_peak_bytes=peak)
    if trace:
        metrics = read_layer_metrics(bench, cell["name"], run)
        if run["trace"] is not None:
            device_out["busy_s"] = run["trace"]["busy_s"]
            device_out["window_s"] = run["trace"]["window_s"]
    else:
        metrics = end_to_end_metrics(bench, run)

    checks, ok = check_answers(queries, tables, limits)
    result = {"correct": ok, "attempted": len(queries), "failed": failed,
              "metrics": metrics, "device": device_out}
    if trace and run["trace"] is not None:
        result["breakdown"] = {
            "device_ops": run["trace"]["device_ops"][:10],
            "idle_gaps": run["trace"]["idle_gaps"][:10]}
    result["setup_split_s"] = {k: v for k, v in setup.items()
                               if k.endswith("_s")}
    result["checks"] = checks
    for name, c in checks.items():
        limit = f" (limit {c['limit']!r})" if "limit" in c else ""
        print(f"check {name}: {c['value']!r}{limit}", file=sys.stderr)
    print(f"correct: {ok}", file=sys.stderr, flush=True)
    return result


def end_to_end_metrics(bench, run) -> dict:
    done = [q for q in run["queries"] if "answer" in q]
    rows = sum(run["rows_per_query"][q["id"]] for q in done)
    latencies = [q["latency_s"] for q in done]
    log(f"latency samples {len(latencies)}, median "
        f"{statistics.median(latencies):.6f} s")
    values = {
        "rows_per_s": rows / run["window_s"],
        "query_p95_s": float(np.percentile(latencies, 95)),
        "setup_s": run["setup"]["setup_s"],
    }
    out = {}
    for m in bench["end_to_end"]:
        if "workloads" in m and run["cell"]["name"] not in m["workloads"]:
            continue
        # `<metric>.<qualifier>`: the same quantity under a bound of its
        # own, for the cells its `workloads` lists
        value = values[m["name"].split(".")[0]]
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def read_layer_metrics(bench, cell_name, run) -> dict:
    """Each per-layer metric by its own reader, `layer_metrics/<name>.py`;
    a reader that finds nothing to read returns None and is left out."""
    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        reader = importlib.import_module(
            f"benchmarks.layer_metrics.{m['name'].replace('.', '_')}")
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "spark_rapids_tpu")):
        print("benchmarks/run.py: no spark_rapids_tpu/ beside benchmarks/: "
              "there is no system to measure", file=sys.stderr)
        sys.exit(1)
    bench = load_json(ROOT, "BENCHMARK.json")
    cell, config, mix, limits = resolve_cell(bench, args.workload)
    device = require_chips(int(cell["chips"]))

    import spark_rapids_tpu  # noqa: F401  (x64; places the compile cache)
    result = run_cell(cell, config, mix, limits, bench, args.seed,
                      args.seconds, args.trace, device, sut.Engine)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    with contextlib.suppress(BrokenPipeError):
        main()

#!/usr/bin/env python3
"""The two readings a cell's `max_rel_err` limit is set between, over
many seeds in one process: the engine's widest float gap from the plain
float64 reference, and the float32 control's on the same tables.

Each seed's tables hold only the columns the cell's statements name (the
generators key their random streams by column, so those values are the
cell's for the seed); they and both references are made on worker
threads while the device works on the seed before. Each statement runs
twice on a seed, and the line says whether both answers are the same to
the bit. One JSON line a seed, on stdout and appended to `--out`; the
first seed's first query compiles every program, a later one's none.

    python3 benchmarks/limit_readings.py --workload q3-mesh4 \
        --seeds 2149200001 2149200002 --out chiprun_out/readings.jsonl

It refuses any platform but the chip, as `run.py` does; `--cpu` runs it
on the CPU backend's eight virtual devices (no reading of the chip's
arithmetic: there a DOUBLE is not split), with `--scale` and
`--session-conf KEY=VALUE` to cut the configuration for a small run.
"""

import argparse
import concurrent.futures as cf
import importlib
import json
import os
import re
import sys
import time

import numpy as np

T0 = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def log(msg: str) -> None:
    print(f"[readings {time.perf_counter() - T0:8.2f}s] {msg}",
          file=sys.stderr, flush=True)


def named_columns(config: dict, texts) -> dict:
    """The configuration's tables cut to the columns the texts name."""
    words = set()
    for text in texts:
        words |= set(re.findall(r"[a-z_][a-z_0-9]*", text.lower()))
    return {table: [c for c in cols if c.lower() in words]
            for table, cols in config["tables"].items()
            if table.lower() in words}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--deadline", type=float, default=float("inf"),
                    help="start no seed after this many seconds")
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--scale", type=float)
    ap.add_argument("--session-conf", action="append", default=[])
    args = ap.parse_args(argv)
    if args.cpu:
        from spark_rapids_tpu.parallel.mesh import ensure_cpu_test_mesh
        ensure_cpu_test_mesh(8)
    from benchmarks import compare, sut, traffic
    from benchmarks import run as bench_run

    bench = bench_run.load_json(ROOT, "BENCHMARK.json")
    cell, config, mix, limits = bench_run.resolve_cell(bench, args.workload)
    if not args.cpu:
        log(f"device {bench_run.require_chips(int(cell['chips']))}")
    statements = [(qid, params, traffic.statement(qid, params))
                  for qid, params in traffic.distinct_statements(mix)]
    config = dict(config, tables=named_columns(
        config, [text for _q, _p, text in statements]))
    if args.scale is not None:
        config["scale_factor"] = args.scale
    extra = dict(kv.split("=", 1) for kv in args.session_conf)
    config["session_conf"] = dict(config.get("session_conf", {}), **extra)
    generator = importlib.import_module(
        f"benchmarks.datagen.{config['generator']}")

    def prepare(seed):
        t = time.perf_counter()
        tables = generator.generate(config, seed)
        generate_s = time.perf_counter() - t
        refs = []
        for qid, params, _text in statements:
            module = importlib.import_module(f"benchmarks.reference.{qid}")
            refs.append((module.run(tables, params),
                         module.run(tables, params, np.float32)))
        return tables, refs, generate_s

    pool = cf.ThreadPoolExecutor(2)
    futures = [pool.submit(prepare, seed) for seed in args.seeds]
    for seed, future in zip(args.seeds, futures):
        if time.perf_counter() - T0 > args.deadline:
            log(f"seed {seed}: past the deadline, not run")
            continue
        tables, refs, generate_s = future.result()
        engine = sut.Engine(config)
        t = time.perf_counter()
        engine.register(tables)
        landed = engine.land()
        line = {"seed": seed, "rows": {k: v["num_rows"]
                                       for k, v in tables.items()},
                "generate_s": generate_s,
                "register_and_land_s": time.perf_counter() - t,
                "landed_bytes": landed, "queries": {}}
        for (qid, _params, text), (ref, control) in zip(statements, refs):
            answers, runs = [], []
            for _ in range(2):
                t = time.perf_counter()
                answer, slim = engine.query(text)
                took = time.perf_counter() - t
                record = engine.session.last_event_record
                answers.append(answer)
                runs.append({
                    "s": took, "off_device_path": sut.off_device_path(slim),
                    **{k: record.get(k) for k in (
                        "compileMs", "dispatches", "hostSyncs",
                        "meshRelandRows", "meshExchanges",
                        "meshExchangeRows", "meshExchangeBytes",
                        "iciBytes")},
                    "exchangeS": (record.get("phasesS") or {}).get(
                        "exchangeS")})
            gaps = [compare.compare_answer(a, ref) for a in answers]
            control_mismatches, control_gap = compare.compare_answer(
                control, ref)
            line["queries"][qid] = {
                "max_rel_err": max(g for _m, g in gaps),
                "exact_mismatches": sum(m for m, _g in gaps),
                "same_bits_twice": answers[0] == answers[1],
                "control_max_rel_err": control_gap,
                "control_exact_mismatches": control_mismatches,
                "limit": float(limits["max_rel_err"][qid]),
                "runs": runs}
        engine.close()
        del engine, tables
        print(json.dumps(line), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(line) + "\n")
        log(f"seed {seed}: " + ", ".join(
            f"{q} engine {r['max_rel_err']:.3e} control "
            f"{r['control_max_rel_err']:.3e} first query "
            f"{r['runs'][0]['s']:.3f} s"
            for q, r in line["queries"].items()))
    pool.shutdown(cancel_futures=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

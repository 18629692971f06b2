"""The hash aggregate on a mesh's resident shards (execs/aggregate.py
``_shards_of`` / ``_over_shards``; the contract in execs/mesh.py), on
conftest's 8 virtual CPU devices under the mesh shapes `4`, `2x2`, `8`:

* TPC-H Q1 through ``sql()`` on the mesh against the benchmark's plain
  float64 reference (exact columns equal, floats under the cell's 2e-7)
  and against the single-chip session (exact columns bit-identical), the
  same bits on a second run, no re-land anywhere in the query;
* the shards' parts add up: each shard's partial groups, merged on the
  host in float64, equal the reference, and with one shard left out do
  not;
* batches whose rows end inside shard 1, whose filter empties a shard,
  with NULLs in a summed column, masked, and with two slices a shard;
* what ``_shards_of`` refuses (the sorted path, a wide group domain, a
  position-dependent expression) is re-landed, counted and correct.
"""

import numpy as np
import pytest

from spark_rapids_tpu import functions as F
from spark_rapids_tpu.ops.expr import col, lit

from tests.asserts import assert_tpu_and_cpu_are_equal
from tests.asserts import plan_metric_total as _metric_total
from tests.test_agg_fastpath import (
    SLICED_AGGS,
    _counts_table,
    _exec_tree,
    _fast_agg_exec,
    _feed,
    _rows_close,
)

pytestmark = pytest.mark.multichip

SHAPES = ["4", "2x2", "8"]
#: the benchmark's limit on a float cell's gap from the float64 reference
FLOAT_LIMIT = 2e-7
Q1_COLUMNS = ["l_quantity", "l_extendedprice", "l_discount", "l_tax",
              "l_returnflag", "l_linestatus", "l_shipdate"]
EXACT = ("l_returnflag", "l_linestatus", "count_order")


def _q1_text():
    from benchmarks import traffic
    return traffic.statement("q1", {"DELTA": 90})


@pytest.fixture(scope="module")
def lineitem():
    from benchmarks.datagen import tpch
    return tpch.generate({"scale_factor": 0.01,
                          "tables": {"lineitem": Q1_COLUMNS}}, 2 ** 31 + 31)


def _engine(_dir, shape=None, batches=3, **conf):
    """The benchmark's own engine wrapper (a TpuSession with the event
    log on); every cached batch passes its coalesce (a 4 KiB goal)."""
    from benchmarks import sut
    session_conf = {"spark.rapids.sql.batchSizeBytes": "4096", **conf}
    if shape is not None:
        session_conf.update({"spark.rapids.mesh.enabled": "true",
                             "spark.rapids.mesh.shape": shape})
    engine = sut.Engine({"session_conf": session_conf,
                         "batches": {"lineitem": batches}})
    return engine


def _plan_nodes(node):
    yield node
    for child in node.get("children", ()):
        yield from _plan_nodes(child)


def _plan_total(plan, key):
    """Sum of an exec metric over a record's plan tree."""
    return sum((n.get("metrics", {}).get(key) or {"value": 0})["value"]
               for n in _plan_nodes(plan))


def _below(node, name):
    """The record's plan nodes under the first node called `name`."""
    for n in _plan_nodes(node):
        if n["describe"].startswith(name):
            return list(_plan_nodes(n))[1:]
    raise AssertionError(f"no {name} in the plan")


@pytest.mark.parametrize("shape", SHAPES)
def test_q1_on_the_mesh_against_reference_and_one_chip(tmp_path, lineitem,
                                                      shape):
    from benchmarks import compare
    from benchmarks.reference import q1 as reference
    want = reference.run(lineitem, {"DELTA": 90})
    text = _q1_text()
    shards = {"4": 4, "2x2": 4, "8": 8}[shape]

    chip = _engine(tmp_path / "chip")
    chip.register(lineitem)
    one, _ = chip.query(text)
    chip.close()

    mesh = _engine(tmp_path / "mesh", shape)
    mesh.register(lineitem)
    got, record = mesh.query(text)
    full = mesh.session.last_event_record
    again, _ = mesh.query(text)
    mesh.close()

    mismatches, gap = compare.compare_answer(got, want)
    assert mismatches == 0 and gap < FLOAT_LIMIT, (mismatches, gap)
    for name in EXACT:
        assert got[name] == one[name], name
    assert compare.compare_answer(got, one)[1] < FLOAT_LIMIT
    assert again == got, "a second run on the same mesh gives other bits"

    # one agg_fast_mesh program a batch, no gather, one host sync
    from benchmarks import sut
    assert not sut.off_device_path(record)
    assert full["hostSyncs"] == 1
    assert full["phasesS"]["relandS"] == 0.0
    plan = full["plan"]
    assert not [n for n in _below(plan, "TpuHashAggregate")
                if n["describe"].startswith("MeshReland")]
    assert _plan_total(plan, "meshRelandRows") == 0
    assert _plan_total(plan, "meshAggBatches") == 3
    assert _plan_total(plan, "meshAggShards") == 3 * shards
    assert _plan_total(plan, "partialCountReads") == 0
    assert _plan_total(plan, "shardsDispatched") in (0, 3 * shards)
    assert full["scopes"]["mesh"].get("meshRelandRows", 0) == 0


def _shard_parts(partial):
    """A sharded aggregate's partial table (host) cut back into its
    shards' parts: every shard's groups come in rising key order, so a
    key that does not rise starts the next shard's (a shard with no live
    row has no part)."""
    rows = list(zip(*partial.to_pydict().values()))
    parts = []
    for row in rows:
        if not parts or row[:2] <= parts[-1][-1][:2]:
            parts.append([])
        parts[-1].append(row)
    return partial.names, parts


def _merge_in_float64(names, parts):
    """The partial rows of `parts` merged on the host in float64, the
    way Q1's sums and counts merge (they add): {column: the totals in
    key order}, and the keys."""
    import math
    acc = {}
    for part in parts:
        for row in part:
            slot = acc.setdefault(row[:2], {})
            for name, value in zip(names[2:], row[2:]):
                slot.setdefault(name, []).append(value)
    keys = sorted(acc)
    return keys, {
        name: [math.fsum(acc[k][name]) if isinstance(acc[k][name][0], float)
               else sum(acc[k][name]) for k in keys]
        for name in names[2:]}


def test_the_shards_parts_add_up(tmp_path, lineitem, monkeypatch):
    """Each shard's partial groups taken alone (the sharded program's
    output, before the merge) and merged on the host in float64 give the
    reference's sums and counts over the whole batch; with one shard's
    part left out they do not."""
    from benchmarks.reference import q1 as reference
    from spark_rapids_tpu.execs import aggregate as A
    want = reference.run(lineitem, {"DELTA": 90})
    partials = []
    real = A.TpuHashAggregateExec._aggregate

    def spy(self, table, *a, shards=1, **k):
        out = real(self, table, *a, shards=shards, **k)
        if shards > 1:
            partials.append((shards, out.to_host()))
        return out
    monkeypatch.setattr(A.TpuHashAggregateExec, "_aggregate", spy)

    mesh = _engine(tmp_path / "mesh", "2x2", batches=1)
    mesh.register(lineitem)
    mesh.query(_q1_text())
    mesh.close()
    (shards, partial), = partials
    assert shards == 4
    names, parts = _shard_parts(partial)
    assert len(parts) == 4 and sum(map(len, parts)) == partial.num_rows

    def sums(parts):
        return _merge_in_float64(names, parts)

    keys, whole = sums(parts)
    assert keys == list(zip(want["l_returnflag"], want["l_linestatus"]))
    # the partial specs name a sum and a count per average; find Q1's
    # columns among them by value
    def holds(values, wanted, rel):
        return any(all(abs(v - w) <= rel * abs(w)
                       for v, w in zip(column, wanted))
                   for column in values.values())
    for name in ("sum_qty", "sum_base_price", "sum_disc_price",
                 "sum_charge"):
        assert holds(whole, want[name], FLOAT_LIMIT), name
    assert holds(whole, want["count_order"], 0), "count_order"
    for left_out in range(4):
        _, short = sums(parts[:left_out] + parts[left_out + 1:])
        assert not holds(short, want["count_order"], 0), left_out
        assert not holds(short, want["sum_charge"], FLOAT_LIMIT), left_out


@pytest.fixture(scope="module")
def mesh_session(tmp_path_factory):
    from tests.test_agg_fastpath import _logged
    return _logged(tmp_path_factory.mktemp("mesh_agg_events"),
                   **{"spark.rapids.mesh.enabled": "true",
                      "spark.rapids.mesh.shape": "2x2"})


CUTS = ["ends-in-shard-1", "filter-empties-a-shard", "nulls-and-full",
        "masked", "two-slices-a-shard", "empty"]


@pytest.mark.parametrize("grouped", [True, False],
                         ids=["grouped", "ungrouped"])
@pytest.mark.parametrize("cut", CUTS)
def test_sharded_aggregate_matches_oracle(mesh_session, cpu_session,
                                          monkeypatch, cut, grouped):
    """NULL keys and values, a fused filter, sum, count, avg, min, max
    and a variance over a 16,384-row batch on four chips (4,096 rows a
    shard): one program a batch, the shards' partials merged by the
    streaming path's plan, equal to the CPU over the live rows."""
    import jax
    from spark_rapids_tpu.columnar import DeviceTable, HostColumn, HostTable
    from spark_rapids_tpu.execs import aggregate as A
    from spark_rapids_tpu.plan import from_host_table
    if cut == "two-slices-a-shard":
        monkeypatch.setattr(A, "AGG_SLICE", 2048)
    table = _counts_table(15000, 12, seed=23)
    if cut == "filter-empties-a-shard":
        # every row of shard 2 (rows 8192..12287) fails `w < 80`
        w = table.columns[3].data.copy()
        w[8192:12288] = 99
        table = HostTable(table.names, list(table.columns[:3])
                          + [HostColumn(table.columns[3].dtype, w)])

    def build(s, t):
        df = from_host_table(t, s).filter(col("w") < lit(80))
        return (df.group_by("k") if grouped else df).agg(*SLICED_AGGS)
    aggx = _fast_agg_exec(mesh_session, build(mesh_session, table))
    for e in _exec_tree(aggx):
        assert not e.describe().startswith("MeshReland")
    batch = next(iter(aggx.children[0].execute_masked()))
    assert batch.capacity == 16384 and batch.physically_sharded()
    nrows = {"ends-in-shard-1": 6000, "empty": 0}.get(cut, 15000)
    keep = np.arange(15000) < nrows
    live = None
    if cut == "masked":
        keep &= np.random.default_rng(5).random(15000) < 0.6
        live = np.zeros(batch.capacity, dtype=np.bool_)
        live[:15000] = keep
        live = jax.device_put(live, batch.shard_spec)
    fed = DeviceTable(batch.names, batch.columns, int(keep.sum()),
                      batch.capacity, live=live, shard_spec=batch.shard_spec)
    assert aggx._shards_of(fed)[:2] == (
        4, 2 if cut == "two-slices-a-shard" else 1)
    got, = _feed(aggx, [fed])
    assert aggx.metrics.get("meshAggBatches") == 1
    assert aggx.metrics.get("meshAggShards") == 4
    assert aggx.metrics.get("meshRelandRows") is None
    assert aggx.metrics.get("partialCountReads") == 0
    assert aggx.metrics.get("aggSlices") == (
        8 if cut == "two-slices-a-shard" else 0)

    kept = HostTable(table.names, [
        HostColumn(c.dtype, c.data[keep], c.validity[keep])
        for c in table.columns])
    want = build(cpu_session, kept).collect_table()
    if not grouped:
        assert got.num_rows == 1    # also from an empty input
    _rows_close(got, want)


@pytest.mark.parametrize("case", ["sorted", "wide-domain",
                                  "position-dependent"])
def test_what_the_predicate_refuses_is_relanded_and_correct(
        mesh_session, cpu_session, case):
    """A sorted-path aggregate, a 100-key group domain (above the
    one-hot contraction's) and a position-dependent child are gathered
    to one chip as before, counted there, and answer as the CPU does."""
    from spark_rapids_tpu.plan import from_host_table
    table = _counts_table(9000, 100 if case == "wide-domain" else 12,
                          seed=8, null_keys=case != "sorted")

    def build(s):
        x = col("x")
        if case == "position-dependent":
            x = x + F.rand(11)
        key = col("y") * lit(3) if case == "sorted" else col("k")
        return from_host_table(table, s).group_by(key.alias("g")).agg(
            F.count().alias("n"), F.sum(x).alias("sx"),
            F.avg(col("x")).alias("ax"))
    assert_tpu_and_cpu_are_equal(build, mesh_session, cpu_session,
                                 approximate_float=True)
    assert _metric_total(mesh_session, "meshAggBatches") == 0
    assert _metric_total(mesh_session, "meshRelandRows") == 16384
    assert mesh_session.last_event_record["phasesS"]["relandS"] > 0


def test_a_replay_under_a_suppressed_mesh_aggregates_on_one_device(
        tmp_path, lineitem):
    """The degradation ladder's middle rung: under `suppressed_mesh` the
    scan lands single-device, `_shards_of` sees no shards and the query
    takes the single-chip programs."""
    from benchmarks import compare
    from benchmarks.reference import q1 as reference
    from spark_rapids_tpu.parallel.mesh import suppressed_mesh
    mesh = _engine(tmp_path / "mesh", "2x2")
    mesh.register(lineitem)
    with suppressed_mesh("test: replay on one device"):
        got, _ = mesh.query(_q1_text())
    plan = mesh.session.last_event_record["plan"]
    mesh.close()
    assert compare.compare_answer(
        got, reference.run(lineitem, {"DELTA": 90}))[0] == 0
    assert _plan_total(plan, "meshAggBatches") == 0
    assert _plan_total(plan, "meshRelandRows") == 0
    assert _plan_total(plan, "partialAggBatches") == 3


def _decimal_table(rows=6000, seed=12):
    """A 25-key bounded-integer group key and a decimal(12,2) value: its
    sum is a decimal(22,2), two limbs on the device."""
    import decimal
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar import HostTable
    rng = np.random.default_rng(seed)
    cents = rng.integers(-99_999, 999_999, rows)
    return HostTable.from_pydict(
        {"k": rng.integers(0, 25, rows).astype(np.int64),
         "v": [decimal.Decimal(int(c)).scaleb(-2) for c in cents]},
        {"k": T.LONG, "v": T.DecimalType(12, 2)})


@pytest.mark.parametrize("where", ["mesh", "chip-streaming"])
def test_a_decimal128_sum_merges_from_its_partials(mesh_session, cpu_session,
                                                   tmp_path, where):
    """A sum whose result is a decimal128 goes through the streaming
    plan's overflow arm, If(overflowed, NULL, sum), over a two-limb
    column: on the mesh (every shard-aggregated batch takes the streaming
    path; scale_test's q11) and on one chip over several batches. Exact,
    so equal to the CPU bit for bit."""
    from spark_rapids_tpu.plan import from_host_table
    from tests.test_agg_fastpath import _logged
    table = _decimal_table()
    session, batches = mesh_session, 1
    if where == "chip-streaming":
        session, batches = _logged(
            tmp_path, **{"spark.rapids.sql.batchSizeBytes": "4096"}), 3

    def build(s):
        return from_host_table(table, s, batches).group_by("k").agg(
            F.sum("v").alias("total"), F.count("v").alias("n"))
    assert_tpu_and_cpu_are_equal(build, session, cpu_session)
    if where == "mesh":
        assert _metric_total(session, "meshAggBatches") == 1
        assert _metric_total(session, "meshRelandRows") == 0
    else:
        assert _metric_total(session, "partialAggBatches") == 3


def test_small_operands_are_interned_replicated_and_cleared(
        tmp_path, lineitem):
    """The sharded program's small operands (aux tables, the row count,
    the layout's sizes, strides and bases) lie replicated over the
    batch's mesh and are interned so in dispatch's constant caches, by
    content and sharding: a second query uploads none again, and the
    OOM-recovery hook drops them with every other constant."""
    from spark_rapids_tpu import dispatch as D

    def replicated(cache):
        return [k for k in cache if k[-1] is not None]
    mesh = _engine(tmp_path / "mesh", "2x2")
    mesh.register(lineitem)
    D.clear_device_constants()
    mesh.query(_q1_text())
    consts, scalars = replicated(D._CONST_CACHE), replicated(D._SCALAR_CACHE)
    assert consts and scalars
    assert all(len(D._CONST_CACHE[k].sharding.device_set) == 4
               for k in consts)
    mesh.query(_q1_text())
    assert replicated(D._CONST_CACHE) == consts
    assert replicated(D._SCALAR_CACHE) == scalars
    mesh.close()
    assert D.clear_device_constants() >= len(consts) + len(scalars)
    assert not D._CONST_CACHE and not D._SCALAR_CACHE


# ---------------------------------------------------------------------------
# resident sharded batches aggregate several to a program: one
# `agg_fast_mesh` for a group, every chip running the body over its shard
# of each member, the members' partial groups crossing in one exchange
# ---------------------------------------------------------------------------

def _programs_enqueued(monkeypatch):
    """Names of the programs `tpu_jit` enqueues from here on, in order."""
    from spark_rapids_tpu import dispatch
    names = []
    real = dispatch.phase_span

    def spy(key, name, cat):
        if cat == "dispatch":
            names.append(name)
        return real(key, name, cat)
    monkeypatch.setattr(dispatch, "phase_span", spy)
    return names


@pytest.mark.parametrize("batches,cap,programs,held", [
    (4, 4, 1, 4),
    (5, 2, 2, 4),
], ids=["4-in-one", "5-by-2"])
def test_resident_sharded_batches_aggregate_in_one_mesh_program(
        tmp_path, lineitem, monkeypatch, batches, cap, programs, held):
    """Q1 over a table cached on a 2x2 mesh: grouped = one batch at a
    time (exact columns bit-identical, floats under the cell's limit of
    each other and of the float64 reference), nothing re-landed, and the
    record counts the groups."""
    from benchmarks import compare
    from benchmarks.reference import q1 as reference
    from spark_rapids_tpu.execs import aggregate as A
    monkeypatch.setattr(A, "AGG_GROUP", cap)
    want = reference.run(lineitem, {"DELTA": 90})
    text = _q1_text()
    mesh = _engine(tmp_path / "mesh", "2x2", batches=batches)
    mesh.register(lineitem)
    mesh.query(text)             # uploads in the pull: groups of one
    assert _plan_total(mesh.session.last_event_record["plan"],
                       "groupedAggPrograms") == 0
    names = _programs_enqueued(monkeypatch)
    got, record = mesh.query(text)
    full = mesh.session.last_event_record
    monkeypatch.setattr(A, "AGG_GROUP", 1)
    alone, _ = mesh.query(text)
    one_at_a_time = mesh.session.last_event_record
    mesh.close()

    mismatches, gap = compare.compare_answer(got, want)
    assert mismatches == 0 and gap < FLOAT_LIMIT, (mismatches, gap)
    for name in EXACT:
        assert got[name] == alone[name], name
    assert compare.compare_answer(got, alone)[1] < FLOAT_LIMIT

    from benchmarks import sut
    assert not sut.off_device_path(record)
    plan = full["plan"]
    assert _plan_total(plan, "groupedAggPrograms") == programs
    assert _plan_total(plan, "groupedAggBatches") == held
    assert _plan_total(plan, "meshAggBatches") == batches
    assert _plan_total(plan, "meshAggShards") == 4 * batches
    assert _plan_total(plan, "meshRelandRows") == 0
    assert full["phasesS"]["relandS"] == 0.0 and full["hostSyncs"] == 1
    assert _plan_total(one_at_a_time["plan"], "groupedAggPrograms") == 0
    saved = held - programs + (1 if batches == held == cap else 0)
    assert one_at_a_time["dispatches"] - full["dispatches"] == saved
    # THE NAME IS A CONTRACT: a group of sharded batches is enqueued as
    # `agg_fast_mesh`, whatever its member count, and as no other
    # aggregate program (the `agg_fast` is the merge, on one chip). The
    # benchmark's readers `mesh_agg_device_ms_per_query` and
    # `mesh_agg_roofline` match the device's `jit_agg_fast_mesh` exactly
    # (benchmarks/costs_coalesce.program_seconds): under another name the
    # roofline share that bounds `rows_per_s` on `q1-mesh4` would read 0.
    grouped_run = names[:full["dispatches"]]
    partials = batches - held + programs
    assert [n for n in grouped_run if n.startswith("agg_")] \
        == ["agg_fast_mesh"] * partials + ["agg_fast"]

"""The default-conf Q1 path compiled at its real shapes for a DESCRIBED
v5e chip (the chip's own compiler, no chip attached): the coalesce's copy
of eight 2^21-row batches and the aggregate at the 2^24 rows it builds.
What the compiler refuses here (a program that does not fit the chip's
memory) the chip would refuse too; nothing runs, so nothing here is a
time. All such compiles live in this one file: the topology is described
inside a fixture, by the one worker that is given the file."""

import os

import numpy as np
import pytest

GB = 1e9


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(one_chip):
    """The four chips of the described v5e host as the engine's 2x2
    mesh: (mesh, row sharding, replicated sharding)."""
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("dcn", "ici"))
    return (mesh, NamedSharding(mesh, P(("dcn", "ici"))),
            NamedSharding(mesh, P()))


def _shape(one_chip, shape, dtype):
    import jax
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *args):
    import jax
    return jax.jit(fn).lower(*args).compile()


def _q1_aggregate():
    """Q1's aggregate exec over a small 7-column lineitem and the batch
    its coalesce yields."""
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar import HostColumn, HostTable
    from spark_rapids_tpu.execs.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.overrides import apply_overrides
    from spark_rapids_tpu.session import TpuSession
    n = 3000
    rng = np.random.default_rng(7)
    cols = {
        "l_quantity": (T.DOUBLE, np.floor(rng.random(n) * 50 + 1)),
        "l_extendedprice": (T.DOUBLE, np.round(rng.random(n) * 1e5, 2)),
        "l_discount": (T.DOUBLE, np.round(rng.random(n) * 0.1, 2)),
        "l_tax": (T.DOUBLE, np.round(rng.random(n) * 0.08, 2)),
        "l_returnflag": (T.STRING, np.array(["A", "N", "R"], object)[
            rng.integers(0, 3, n)]),
        "l_linestatus": (T.STRING, np.array(["F", "O"], object)[
            rng.integers(0, 2, n)]),
        "l_shipdate": (T.DATE, rng.integers(8036, 10561, n).astype(np.int32)),
    }
    host = HostTable(list(cols), [HostColumn(t, v) for t, v in cols.values()])
    session = TpuSession({"spark.rapids.tpu.sum.splitF64": "true"})
    session.create_dataframe(host).create_or_replace_temp_view("lineitem")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    text = open(os.path.join(root, "benchmarks", "queries", "q1.sql")) \
        .read().replace("[DELTA]", "90")
    executable, _ = apply_overrides(session.sql(text).plan, session.conf)
    found = []

    def walk(e):
        if isinstance(e, TpuHashAggregateExec):
            found.append(e)
        for c in getattr(e, "children", ()):
            walk(c)
        if getattr(e, "tpu_exec", None) is not None:
            walk(e.tpu_exec)
    walk(executable)
    exec_ = found[0]
    return exec_, next(iter(exec_.children[0].execute_masked()))


def _compile_q1_aggregate(place, cap, slices=1, mesh=None, members=1):
    """Q1's fast kernel over a `cap`-row batch, compiled for the chip:
    the whole-capacity body, or the body of `cap // slices` rows in a
    loop over the slices (the streaming path's partial specs). With
    `mesh` (`place` is then its (row sharding, replicated sharding)) the
    body runs on each chip's `cap // chips` rows and the shards' partial
    groups are exchanged: `agg_fast_mesh`. With `members` > 1 the
    program takes that many `cap`-row batches as separate operands: a
    group of resident batches (`agg_fast_group`, `agg_fast_mesh`)."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.dispatch import prep_aux
    from spark_rapids_tpu.execs.aggregate import (
        _over_members,
        _over_shards,
        _over_slices,
    )
    from spark_rapids_tpu.ops.expr import DevVal
    exec_, batch = _q1_aggregate()
    assert exec_.use_split
    shards = 1 if mesh is None else mesh.devices.size
    by_row, same = place if mesh is not None else (place, place)
    specs = exec_._merge_plan().partial_specs \
        if slices * shards * members > 1 else exec_.agg_specs
    rows = cap // (slices * shards)
    pctx, fpre, kpre, vpre = exec_._prep_all(
        batch, exec_.grouping, specs, exec_.filters)
    kinds, sizes, strides, gpad, bases = exec_._fast_layout(
        exec_.grouping, kpre, rows)
    assert gpad == 16
    kernel = exec_._build_fast_kernel(rows, kinds, gpad, fpre, kpre, vpre,
                                      exec_.grouping, specs, exec_.filters)
    if slices > 1:
        kernel = _over_slices(kernel, slices, rows, gpad)
    if mesh is not None:
        kernel = _over_shards(kernel, mesh, by_row.spec[0], shards,
                              slices * rows, slices * gpad, members=members)
    elif members > 1:
        kernel = _over_members(kernel, gpad)
    cols = tuple(DevVal(_shape(by_row, (cap,), c.data.dtype),
                        _shape(by_row, (cap,), jnp.bool_))
                 for c in batch.columns)
    aux = jax.tree.map(lambda a: _shape(same, a.shape, a.dtype),
                       prep_aux(pctx))
    batch_args = (
        cols, aux, _shape(same, (), jnp.int32),
        _shape(same, (len(sizes),), jnp.int32),
        _shape(same, (len(strides),), jnp.int32),
        _shape(same, (len(bases),), jnp.int64), None)
    if members > 1:
        return _compile(kernel, *[batch_args] * members)
    return _compile(kernel, *batch_args)


def test_q1_aggregate_fits_the_chip_at_the_coalesced_capacity(one_chip):
    """`agg_fast` at 2^24 rows asked for 17.13 GB of a 15.75 GB chip
    before the split sums kept rows on the lane axis (PERF.md, PR 27)."""
    memory = _compile_q1_aggregate(one_chip, 1 << 24).memory_analysis()
    # beside a 3.6 GB table and two 0.86 GB coalesced batches
    assert memory.temp_size_in_bytes < 5 * GB, memory.temp_size_in_bytes
    assert memory.argument_size_in_bytes < 1 * GB


def test_q1_aggregate_walks_the_coalesced_batch_in_slices(one_chip):
    """`agg_fast_sliced` at 2^24 rows is ONE program holding a loop over
    eight 2^21-row slices. Its temporaries are one slice's (0.32 GB) and
    the f32 halves of the four DOUBLE columns, which the compiler splits
    at the program's entry, before the loop (0.54 GB): 0.84 GB where the
    whole-capacity body takes 3.02 GB (PERF.md, PR 27 and PR 30)."""
    from spark_rapids_tpu.execs.aggregate import AGG_SLICE
    assert AGG_SLICE == 1 << 21
    compiled = _compile_q1_aggregate(one_chip, 1 << 24, slices=8)
    text = compiled.as_text()
    assert text.count("ENTRY ") == 1
    assert " while(" in text
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 1.2 * GB, memory.temp_size_in_bytes
    assert memory.argument_size_in_bytes < 1 * GB
    # every slice's 16 partial groups, 15 columns: a few KB
    assert memory.output_size_in_bytes < 64 * 1024


def test_coalesce_copies_eight_batches_without_a_scatter(one_chip):
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar.table import _build_concat
    k, cap = 8, 1 << 21
    dtypes = [jnp.float64] * 4 + [jnp.int32] * 3
    cols = tuple(tuple((_shape(one_chip, (cap,), dt),
                        _shape(one_chip, (cap,), jnp.bool_))
                       for dt in dtypes) for _ in range(k))
    none = tuple(tuple(None for _ in dtypes) for _ in range(k))
    rows = tuple(_shape(one_chip, (), jnp.int32) for _ in range(k))
    compiled = _compile(_build_concat(len(dtypes), k * cap), cols, none,
                        rows, tuple(None for _ in range(k)))
    text = compiled.as_text()
    assert " scatter(" not in text
    assert "dynamic-update-slice" in text
    memory = compiled.memory_analysis()
    # 4 DOUBLE and 3 int32 columns with a validity byte each, and the total
    assert memory.output_size_in_bytes >= k * cap * (4 * 9 + 3 * 5)
    assert memory.output_size_in_bytes < k * cap * (4 * 9 + 3 * 5) + 4096
    assert memory.temp_size_in_bytes < 0.1 * GB


def test_q1_aggregate_runs_on_the_shards_of_a_four_chip_mesh(mesh4):
    """`agg_fast_mesh` over a 2^23-row batch row-sharded on the 2x2 mesh
    is ONE program: each chip takes the 2^21-row body over its own rows
    (the single-chip body's temporaries, no row crosses a chip) and the
    only collectives are the exchange of the shards' 16 partial groups:
    `all_gather` in the program, which the chip's compiler turns into a
    few combined all-reduces over zero-padded copies (exact: x + 0)."""
    import re
    mesh, by_row, same = mesh4
    compiled = _compile_q1_aggregate((by_row, same), 1 << 23, mesh=mesh)
    text = compiled.as_text()
    assert text.count("ENTRY ") == 1
    collectives = set(re.findall(
        r" (all-gather|all-reduce|all-to-all|collective-permute|"
        r"reduce-scatter)(?:-start)?\(", text))
    assert collectives and collectives <= {"all-gather", "all-reduce"}, \
        collectives
    memory = compiled.memory_analysis()
    # per chip: a quarter of the batch's 7 columns, one body's temporaries
    assert memory.argument_size_in_bytes < 0.25 * GB
    assert memory.temp_size_in_bytes < 0.5 * GB, memory.temp_size_in_bytes
    # four shards' 16 partial groups, 15 columns: a few KB
    assert memory.output_size_in_bytes < 64 * 1024


@pytest.mark.parametrize("on_mesh", [False, True], ids=["one-chip", "2x2"])
def test_q1_aggregate_takes_a_group_of_batches_one_body_at_a_time(
        one_chip, mesh4, on_mesh):
    """Four resident batches in ONE program (`agg_fast_group`; on the mesh
    `agg_fast_mesh` with four members, each chip on its 2^21-row shard of
    each): separate operands, no stacked copy, and the bodies scheduled
    one after another, so the temporaries are one body's (0.32 GB) where
    bodies left free hold 0.79 GB (PERF.md, PR 32). On the mesh the
    members' partial groups cross in the same few collectives as one
    batch's."""
    import re
    from spark_rapids_tpu.execs.aggregate import AGG_GROUP
    assert AGG_GROUP == 4
    if on_mesh:
        mesh, by_row, same = mesh4
        compiled = _compile_q1_aggregate((by_row, same), 1 << 23, mesh=mesh,
                                         members=AGG_GROUP)
    else:
        compiled = _compile_q1_aggregate(one_chip, 1 << 21,
                                         members=AGG_GROUP)
    text = compiled.as_text()
    assert text.count("ENTRY ") == 1
    collectives = set(re.findall(
        r" (all-gather|all-reduce|all-to-all|collective-permute|"
        r"reduce-scatter)(?:-start)?\(", text))
    assert collectives <= {"all-gather", "all-reduce"}, collectives
    assert bool(collectives) == on_mesh
    memory = compiled.memory_analysis()
    # four batches' 7 columns (a chip's quarter of each on the mesh)
    assert 0.4 * GB < memory.argument_size_in_bytes < 0.5 * GB
    assert memory.temp_size_in_bytes < 0.5 * GB, memory.temp_size_in_bytes
    # 4 members' (x 4 shards') 16 partial groups, 15 columns: a few KB
    assert memory.output_size_in_bytes < 64 * 1024


@pytest.mark.parametrize("on_mesh", [False, True], ids=["one-chip", "2x2"])
def test_assemble_splits_doubles_from_their_words(one_chip, mesh4, on_mesh):
    """The landing's `assemble` program with Q1's 7 columns as a file scan
    stages them: 4 DOUBLE columns as their 64-bit words (`f64bits`), split
    into the f32 pair by 32-bit integer operations on the chip, a DATE as
    u32 and 2 flag codes as u8; at a scan batch's 2^20 rows on one chip,
    and at the mesh cell's 2^23 rows row-sharded on the 2x2 mesh, where
    the split is elementwise and moves no row between chips."""
    import re
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar.table import _get_assemble
    if on_mesh:
        mesh, place, same = mesh4
        cap, chips = 1 << 23, mesh.devices.size
    else:
        place = same = one_chip
        cap, chips = 1 << 20, 1
    recipes = (("f64bits", "ones", "double"),) * 4 + (
        ("u32", "ones", "date"),) + (("u8codes", "ones", "string"),) * 2
    staged = tuple(_shape(place, (cap,), dt) for dt in
                   [jnp.int64] * 4 + [jnp.uint32] + [jnp.uint8] * 2)
    assemble = _get_assemble(recipes, cap).__wrapped__
    compiled = assemble.lower(staged, _shape(same, (), jnp.int32)).compile()
    text = compiled.as_text()
    assert not re.search(r" (all-gather|all-reduce|all-to-all|"
                         r"collective-permute|reduce-scatter)", text)
    memory = compiled.memory_analysis()
    rows = cap // chips
    assert memory.argument_size_in_bytes < rows * (4 * 8 + 4 + 2) + 4096
    # the columns (a DOUBLE is its f32 pair) and the row masks
    assert memory.output_size_in_bytes >= rows * (4 * 8 + 3 * 4)
    assert memory.temp_size_in_bytes < rows * 64, memory.temp_size_in_bytes


def test_mesh_exchange_of_a_lineitem_batch_fits_each_chip(mesh4):
    """`mesh_exchange` over one of `q3-mesh4`'s lineitem batches (2^21
    rows a chip of l_orderkey, l_extendedprice, l_discount under the
    filter's mask): one all-to-all a column word, each chip receiving
    every chip's rows (4 x 2^21 a chip), a DOUBLE crossing as its two
    f32 halves."""
    import jax.numpy as jnp
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.parallel.exchange import chip_exchange_program
    from spark_rapids_tpu.parallel.shards import Chips
    mesh, by_row, same = mesh4
    rows = 1 << 21
    n = 4 * rows
    chips = Chips(mesh, by_row.spec[0], 4, rows)
    datas = (_shape(by_row, (n,), jnp.int64),
             _shape(by_row, (n,), jnp.float64),
             _shape(by_row, (n,), jnp.float64))
    valids = tuple(_shape(by_row, (n,), jnp.bool_) for _ in datas)
    compiled = _compile(
        chip_exchange_program(chips, [T.LONG], (False,), False),
        datas, valids, datas[:1], valids[:1],
        _shape(by_row, (n,), jnp.bool_), _shape(same, (), jnp.int32), ())
    text = compiled.as_text()
    assert "all-to-all" in text and "X64Combine" in text
    memory = compiled.memory_analysis()
    # a chip's received block: 4 x 2^21 rows x (3 x 8 + 4) bytes
    assert memory.output_size_in_bytes < 0.25 * GB
    assert memory.temp_size_in_bytes < 0.25 * GB


def test_chip_join_probe_and_build_fit_each_chip(mesh4):
    """The lineitem join of `q3-mesh4` on each chip: the direct table of
    the chip's part of customer ⋈ orders (2^20 received rows into 2^25
    slots) and the probe of a received lineitem batch (2^23 slots a
    chip, looked up by windows)."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from spark_rapids_tpu.execs.join import WINDOW_SLOTS, _DirectJoinKernel
    from spark_rapids_tpu.parallel.shards import chip_live, on_chips
    mesh, by_row, same = mesh4
    axes = by_row.spec[0]
    slots, build_rows, probe_rows = 1 << 25, 1 << 20, 1 << 23
    build = _DirectJoinKernel._build_body(slots)
    probe = _DirectJoinKernel._probe_body("inner", slots)
    from spark_rapids_tpu.parallel.shards import Chips

    def build_on_chip(rd, rv, live, nrows, keymin):
        rowid, unique = build(rd, rv, chip_live(live, nrows, build_rows,
                                                axes), keymin[0])
        return rowid, unique[None]

    def probe_on_chip(ld, lv, live, nrows, rowid, keymin):
        ri, _m, keep, nout, idx, clustered = probe(
            ld, lv, chip_live(live, nrows, probe_rows, axes), rowid,
            keymin[0])
        return ri, keep, nout[None], idx, clustered[None]

    row, rep = P(axes), P()
    chips = Chips(mesh, axes, 4, build_rows)
    built = _compile(
        on_chips(build_on_chip, chips, (row, row, row, rep, row),
                 (row, row)),
        _shape(by_row, (4 * build_rows,), jnp.int64),
        _shape(by_row, (4 * build_rows,), jnp.bool_),
        _shape(by_row, (4 * build_rows,), jnp.bool_),
        _shape(same, (), jnp.int32), _shape(by_row, (4,), jnp.int64))
    assert built.memory_analysis().output_size_in_bytes < 0.2 * GB
    probed = _compile(
        on_chips(probe_on_chip, chips._replace(rows=probe_rows),
                 (row, row, row, rep, row, row), (row,) * 5),
        _shape(by_row, (4 * probe_rows,), jnp.int64),
        _shape(by_row, (4 * probe_rows,), jnp.bool_),
        _shape(by_row, (4 * probe_rows,), jnp.bool_),
        _shape(same, (), jnp.int32),
        _shape(by_row, (4 * slots // WINDOW_SLOTS, WINDOW_SLOTS),
               jnp.int32),
        _shape(by_row, (4,), jnp.int64))
    assert probed.memory_analysis().temp_size_in_bytes < 1 * GB

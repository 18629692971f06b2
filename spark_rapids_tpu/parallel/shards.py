"""Tables that lie row-sharded over a device mesh, worked chip by chip.

A table whose every column is laid out over a mesh's row axes
(``NamedSharding`` with the row axes on dimension 0) holds ``rows`` rows
on each of ``n`` chips: chip ``i`` holds rows ``[i * rows, (i + 1) *
rows)`` of every column and of the live mask. A program over such a
table can run under ``shard_map`` on each chip's own rows, where a GSPMD
program would be free to move rows between chips.

Two layouts of the rows on a chip occur:

- a landed table's (a cached scan's, and what narrow execs make of it):
  the live rows are a prefix of the WHOLE table, so chip ``i`` holds
  ``clip(nrows - i * rows, 0, rows)`` of them at its front (``live`` is
  None);
- a per-chip table's (the ICI exchange's output, a join or an aggregate
  run on the chips): a live mask says which rows of each chip are live,
  and ``nrows`` is only their total.

:func:`chip_live` gives a chip's live mask in either case, inside the
program. Nothing here reads anything to the host."""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P


class Chips(NamedTuple):
    """Where a row-sharded table lies: its mesh, the row axes (an axis
    name or a tuple of them), the chip count and the rows a chip."""
    mesh: object
    axes: object
    n: int
    rows: int

    @property
    def by_row(self) -> P:
        return P(self.axes)

    def row_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(self.axes))

    def everywhere(self) -> NamedSharding:
        """Replicated over the mesh: a small operand every chip reads."""
        return NamedSharding(self.mesh, P())

    def token(self) -> tuple:
        """What a program compiled for these chips keys on."""
        from spark_rapids_tpu.parallel.mesh import mesh_token
        return (mesh_token(self.mesh), str(self.axes), self.n, self.rows)


def _axes_size(mesh, axes) -> int:
    names = axes if isinstance(axes, tuple) else (axes,)
    n = 1
    for a in names:
        n *= mesh.shape[a]
    return n


def chips_of(table) -> Optional[Chips]:
    """The chips ``table`` lies on, or None: no columns, a column that
    is not row-sharded over a mesh of two or more devices, or a capacity
    the chips do not divide."""
    if not table.columns:
        return None
    sharding = getattr(table.columns[0].data, "sharding", None)
    if not isinstance(sharding, NamedSharding) or not sharding.spec \
            or sharding.spec[0] is None:
        return None
    axes = sharding.spec[0]
    n = _axes_size(sharding.mesh, axes)
    if n < 2 or table.capacity % n:
        return None
    for c in table.columns:
        s = getattr(c.data, "sharding", None)
        if not (isinstance(s, NamedSharding) and s.mesh == sharding.mesh
                and s.spec and s.spec[0] == axes):
            return None
    return Chips(sharding.mesh, axes, n, table.capacity // n)


def on_chips(fn, chips: Chips, in_specs, out_specs):
    """``fn`` under ``shard_map`` over ``chips``' mesh: each chip runs it
    on its own rows (``chips.by_row`` operands) and on the replicated
    operands (``P()``)."""
    return jax.shard_map(fn, mesh=chips.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def chip_live(live, nrows, rows: int, axes):
    """Inside a program on the chips: this chip's live mask, of ``rows``
    rows. ``live`` is the chip's part of the table's mask, or None for a
    landed table, whose live rows are a prefix of ``nrows`` rows over the
    whole table."""
    if live is not None:
        return live
    start = jax.lax.axis_index(axes) * rows
    return jnp.arange(rows, dtype=jnp.int32) < jnp.clip(nrows - start, 0,
                                                         rows)


def replicated_scalar(table, chips: Chips):
    """``table``'s row count as a scalar every chip holds: interned when
    the host knows it, else copied from where it lies."""
    from spark_rapids_tpu.dispatch import device_const, device_scalar
    where = chips.everywhere()
    if table.num_rows_known:
        return device_scalar(table.num_rows, sharding=where)
    return device_const(table.nrows_dev, where)


#: the programs of this module by (chips, shapes): device-referencing
#: once traced, so parallel/exchange.clear_mesh_caches drops them with
#: the other mesh caches
_PROGRAMS = {}


def concat_on_chips(tables, chips_list):
    """Several tables that lie on the same chips as ONE per-chip table:
    each chip's rows of every table, in table order, one program
    (``concat_chips``). Rows never leave their chip; the output holds
    the sum of the tables' rows a chip, under a live mask."""
    from spark_rapids_tpu.columnar import DeviceTable
    from spark_rapids_tpu.dispatch import tpu_jit
    first = chips_list[0]
    ncols = len(tables[0].columns)
    key = (first.token()[:3], tuple(c.rows for c in chips_list),
           tuple(t.schema_key()[0] for t in tables),
           tuple(t.live is None for t in tables))
    fn = _PROGRAMS.get(key)
    if fn is None:
        rows = [c.rows for c in chips_list]
        axes = first.axes
        landed = [t.live is None for t in tables]

        def concat(cols, lives, nrows):
            out = []
            for j in range(ncols):
                out.append((
                    jnp.concatenate([c[j][0] for c in cols]),
                    jnp.concatenate([c[j][1] for c in cols])))
            live = jnp.concatenate([
                chip_live(None if is_landed else lv, n, r, axes)
                for lv, n, r, is_landed in zip(lives, nrows, rows, landed)])
            return out, live, sum(nrows)

        lives_spec = tuple(None if is_landed else first.by_row
                           for is_landed in landed)
        fn = tpu_jit(on_chips(
            concat, first,
            in_specs=(first.by_row, lives_spec, P()),
            out_specs=(first.by_row, first.by_row, P())),
            name="concat_chips")
        _PROGRAMS[key] = fn
    cols = tuple(tuple((c.data, c.validity) for c in t.columns)
                 for t in tables)
    lives = tuple(t.live for t in tables)
    nrows = tuple(replicated_scalar(t, first) for t in tables)
    outs, live, total = fn(cols, lives, nrows)
    head = tables[0]
    columns = [c.with_arrays(d, v) for c, (d, v) in zip(head.columns, outs)]
    if all(t.num_rows_known for t in tables):
        total = sum(t.num_rows for t in tables)
    return DeviceTable(head.names, columns, total, live.shape[0], live=live,
                       shard_spec=first.row_sharding())


def first_rows_on_chips(table, chips: Chips, k: int):
    """The first ``k`` rows of each chip of a per-chip table, as a
    per-chip table of ``k`` rows a chip: a slice of each chip's rows
    where they lie (``slice_chips``), nothing moved between chips."""
    from spark_rapids_tpu.columnar import DeviceTable
    from spark_rapids_tpu.dispatch import tpu_jit
    key = ("slice", chips.token(), k, table.schema_key()[0],
           table.live is None)
    fn = _PROGRAMS.get(key)
    if fn is None:
        axes, rows = chips.axes, chips.rows

        def first(cols, live, nrows):
            live = chip_live(live, nrows, rows, axes)
            return [(d[:k], v[:k]) for d, v in cols], live[:k]

        fn = tpu_jit(on_chips(
            first, chips,
            in_specs=(chips.by_row,
                      None if table.live is None else chips.by_row, P()),
            out_specs=(chips.by_row, chips.by_row)), name="slice_chips")
        _PROGRAMS[key] = fn
    outs, live = fn(tuple((c.data, c.validity) for c in table.columns),
                    table.live, replicated_scalar(table, chips))
    columns = [c.with_arrays(d, v) for c, (d, v) in zip(table.columns, outs)]
    n = table.num_rows if table.num_rows_known else table.nrows_dev
    return DeviceTable(table.names, columns, n, k * chips.n, live=live,
                       shard_spec=chips.row_sharding())


def on_one_chip(table):
    """A small per-chip table (the chips' candidate rows of a top-k) as
    one table on the first device, its rows in chip order under its live
    mask. What moves is the table's capacity, which the caller keeps to
    a few rows a chip."""
    from spark_rapids_tpu.columnar import DeviceTable
    dev = jax.devices()[0]
    put = lambda a: jax.device_put(a, dev)  # noqa: E731
    columns = [c.with_arrays(put(c.data), put(c.validity))
               for c in table.columns]
    n = table.num_rows if table.num_rows_known else put(table.nrows_dev)
    return DeviceTable(table.names, columns, n, table.capacity,
                       live=put(table.live) if table.live is not None
                       else None)


def chip_counts_host(counts) -> np.ndarray:
    """A per-chip count vector read to the host, in chip order, through
    the mesh's one sanctioned gather point (parallel.mesh.mesh_gather)."""
    from spark_rapids_tpu.parallel.mesh import mesh_gather
    return mesh_gather(counts).reshape(-1)


def key_columns(exprs, table):
    """The columns ``exprs`` give over ``table``: a bare column where it
    lies (no program), anything else by the fused projection."""
    from spark_rapids_tpu.ops.expr import BoundReference, compile_project
    if all(isinstance(e, BoundReference) and e.data_type
           == table.columns[e.ordinal].dtype for e in exprs):
        return [table.columns[e.ordinal] for e in exprs]
    return compile_project(exprs, table)


def replicated_table(table, chips: Chips):
    """``table`` with every array copied to each of ``chips``' devices
    (a broadcast build side that every chip probes), its live mask made
    explicit: device to device, nothing through the host."""
    from spark_rapids_tpu.columnar import DeviceTable
    where = chips.everywhere()
    put = lambda a: jax.device_put(a, where)  # noqa: E731
    columns = [c.with_arrays(put(c.data), put(c.validity))
               for c in table.columns]
    n = table.num_rows if table.num_rows_known else put(table.nrows_dev)
    return DeviceTable(table.names, columns, n, table.capacity,
                       live=put(table.row_mask()))


def onto_chips(batch, chips: Chips):
    """``batch`` row-sharded over ``chips``' mesh (its capacity padded up
    to a multiple of the chips first, with dead rows): put where a
    program on those chips runs, device to device. Returns the batch
    and the chips it lies on."""
    from spark_rapids_tpu.columnar import DeviceTable
    from spark_rapids_tpu.parallel.mesh import shard_put
    if batch.capacity % chips.n or batch.capacity == 0:
        from spark_rapids_tpu.execs.exchange import _pad_capacity
        cap = max(-(-batch.capacity // chips.n) * chips.n, chips.n)
        batch = _pad_capacity(batch, cap)
    where = chips.row_sharding()
    cols = [c.with_arrays(shard_put(c.data, where),
                          shard_put(c.validity, where))
            for c in batch.columns]
    live = None if batch.live is None else shard_put(batch.live, where)
    nrows = batch.num_rows if batch.num_rows_known else batch.nrows_dev
    out = DeviceTable(batch.names, cols, nrows, batch.capacity, live=live,
                      shard_spec=where)
    return out, chips_of(out)

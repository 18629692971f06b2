"""TPU equi-join (reference: GpuShuffledHashJoinExec / GpuBroadcastHashJoin /
GpuHashJoin.scala gather-map machinery + JoinGatherer — SURVEY.md §2.3).

TPU-first design: hash tables are pointer-chasing and hostile to the VPU.
The build side is ONE table, the smaller side of an inner join by the
plan's size estimates (overrides/rules.py), made ready once a query; the
probe side streams. Two bodies, chosen from what is READ of the build side
before the first probe batch (TpuJoinExec._plan_direct), never guessed:

  * DIRECT ADDRESS (_DirectJoinKernel): a single integer key, unique on the
    build side, within a bounded range — every foreign-key join. A table of
    build row ids by ``key - min``; a probe batch is one lookup in it: by
    windows of the table where the batch's keys are clustered, an
    element a row where they are not (_direct_lookup).
  * SORTED (JoinKernel), every other shape, with fully static shapes:

  1. evaluate key expressions on both sides (fused projections);
  2. dense-rank both sides' keys into ONE shared integer code space
     (device ``lax.sort`` + adjacent-change cumsum — the XLA analog of
     cuDF's build-side hash table); string keys are first remapped into the
     union dictionary on host (dictionary-size work, not row-size);
  3. sort the build side's codes, ``searchsorted`` each probe code for its
     match range [lo, hi) — the GatherMap analog;
  4. expand ranges into (left_idx, right_idx) gather maps with a cumsum
     offset trick at a bucketed static output capacity (JoinGatherer
     analog — one host sync per join for the output size);
  5. gather both sides' columns; outer rows gather index -1 -> null row.

Join types: inner, left, right (as swapped left), full, leftsemi, leftanti
(compaction, no gather maps), cross. Residual non-equi conditions apply as a
post-filter for inner/cross; outer-with-condition falls back (tagged).
"""

from __future__ import annotations

import contextvars
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
from spark_rapids_tpu.dispatch import tpu_jit
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import DeviceColumn, DeviceTable, bucket_for
from spark_rapids_tpu.errors import ColumnarProcessingError
from spark_rapids_tpu.execs.base import TpuExec
from spark_rapids_tpu.ops.expr import Expression, compile_project
from spark_rapids_tpu.ops.ordering import (
    comparable_operands,
    operands_equal_adjacent,
)

INT32_MAX = np.iinfo(np.int32).max

#: (data, validity) pair for key columns
DevVal = Tuple[jax.Array, jax.Array]

#: spark.rapids.tpu.join.directTableMultiplier, set per-query by the
#: session (execs have no conf handle — same pattern as MAX_RETRIES_VAR)
DIRECT_TABLE_MULT = contextvars.ContextVar("rapids_direct_join_mult",
                                           default=4)


def _dense_rank_ops(ops, valid):
    """Dense ranks [0, nvalid) over valid entries; -1 for invalid. One
    multi-operand native-width sort (ops/ordering.lex_sort — no emulated
    64-bit compares) + adjacent-change cumsum + scatter-back. Output ranks
    are i32: row counts never exceed 2^31 (power-of-two row buckets)."""
    from spark_rapids_tpu.ops.ordering import lex_sort
    n = ops[0].shape[0]
    zops = [jnp.where(valid, o, jnp.zeros_like(o)) for o in ops]
    res = lex_sort([(~valid).astype(jnp.int32)] + zops,
                   jnp.arange(n, dtype=jnp.int32))
    perm = res[-1]
    s_valid = res[0] == 0
    first = jnp.arange(n) == 0
    changed = first | ~operands_equal_adjacent(res[1:-1])
    new_grp = changed & s_valid
    rank_sorted = jnp.cumsum(new_grp.astype(jnp.int32)) - 1
    rank_sorted = jnp.where(s_valid, rank_sorted, -1)
    return jnp.zeros(n, dtype=jnp.int32).at[perm].set(rank_sorted)


class JoinKernel:
    """Jitted phases of one join shape; caches traces per capacity tuple.

    Instances are pooled process-wide by ``n_keys`` (``JoinKernel.get``):
    every trace depends only on n_keys + capacities + dtypes, so all joins
    of the same key arity share one compiled set across queries."""

    _instances = {}

    @classmethod
    def get(cls, n_keys: int) -> "JoinKernel":
        k = cls._instances.get(n_keys)
        if k is None:
            k = cls(n_keys)
            cls._instances[n_keys] = k
        return k

    def __init__(self, n_keys: int):
        self.n_keys = n_keys
        self._probe_traces = {}
        self._gather_traces = {}
        self._aux_traces = {}  # _right_matched/_compact/_cross helper jits

    # -- phase A: shared code space + probe ranges --------------------------
    def probe(self, lkeys: List[DevVal], rkeys, nl_dev, nr_dev,
              cap_l: int, cap_r: int, live_l_mask=None):
        tkey = (cap_l, cap_r, live_l_mask is not None,
                tuple(str(k[0].dtype) for k in lkeys),
                tuple(str(k[0].dtype) for k in rkeys))
        fn = self._probe_traces.get(tkey)
        if fn is None:
            fn = tpu_jit(self._build_probe(cap_l, cap_r), name="join_probe")
            self._probe_traces[tkey] = fn
        return fn(tuple(lkeys), tuple(rkeys), nl_dev, nr_dev, live_l_mask)

    def _build_probe(self, cap_l: int, cap_r: int):
        n_keys = self.n_keys

        def probe(lkeys, rkeys, nl, nr, live_l_mask):
            n = cap_l + cap_r
            if live_l_mask is not None:  # masked probe batch
                live_l = live_l_mask
            else:
                live_l = jnp.arange(cap_l, dtype=jnp.int32) < nl
            live_r = jnp.arange(cap_r, dtype=jnp.int32) < nr

            valid_l = live_l
            valid_r = live_r
            for (ld, lv), (rd, rv) in zip(lkeys, rkeys):
                valid_l = valid_l & lv
                valid_r = valid_r & rv

            allvalid = jnp.concatenate([valid_l, valid_r])
            combined = None
            for (ld, lv), (rd, rv) in zip(lkeys, rkeys):
                ops_l = comparable_operands(ld)
                ops_r = comparable_operands(rd)
                allops = [jnp.concatenate([a, b])
                          for a, b in zip(ops_l, ops_r)]
                rank = _dense_rank_ops(allops, allvalid)
                if combined is None:
                    combined = rank
                else:
                    # re-densify the (combined, rank) pair — two i32 keys,
                    # no overflow-prone combined*n arithmetic
                    combined = _dense_rank_ops(
                        [combined, rank], allvalid & (rank >= 0))
            l_codes = combined[:cap_l]
            r_codes = combined[cap_l:]
            l_codes = jnp.where(valid_l, l_codes, -1)

            # sort build-side codes; invalid/dead rows park at +inf
            from spark_rapids_tpu.ops.ordering import lex_sort
            r_sortable = jnp.where(valid_r, r_codes, INT32_MAX)
            _, rs_perm = lex_sort([r_sortable],
                                  jnp.arange(cap_r, dtype=jnp.int32))

            # codes are DENSE ranks < cap_l + cap_r, so per-code build
            # counts + an exclusive prefix give each probe code's sorted
            # range with two GATHERS — no log(n) searchsorted passes
            n_codes = cap_l + cap_r
            park = jnp.where(valid_r, r_codes, n_codes)
            bc = jax.ops.segment_sum(
                jnp.ones(cap_r, dtype=jnp.int32), park,
                num_segments=n_codes + 1)[:n_codes]
            starts = jnp.cumsum(bc) - bc  # exclusive prefix in code order
            safe_l = jnp.clip(l_codes, 0, n_codes - 1)
            lo = starts[safe_l].astype(jnp.int32)
            counts = jnp.where(valid_l & (l_codes >= 0), bc[safe_l],
                               0).astype(jnp.int32)
            total = jnp.sum(counts.astype(jnp.int64))
            matched_l = counts > 0
            return (lo, counts, total, matched_l,
                    rs_perm, live_l, live_r)

        return probe

    # -- phase B: gather-map expansion --------------------------------------
    def expand(self, kind: str, out_cap: int, cap_l: int, cap_r: int, args):
        tkey = (kind, out_cap, cap_l, cap_r)
        fn = self._gather_traces.get(tkey)
        if fn is None:
            fn = tpu_jit(self._build_expand(kind, out_cap, cap_l),
                         name="join_expand")
            self._gather_traces[tkey] = fn
        return fn(*args)

    @staticmethod
    def _build_expand(kind: str, out_cap: int, cap_l: int):
        def expand_inner(lo, counts, rs_perm, live_l):
            """(li, ri, nout) for inner; counts pre-adjusted for left-outer.
            All i32: per-batch output capacities stay under 2^31 (bigger
            couldn't be materialized)."""
            csum = jnp.cumsum(counts)
            total = csum[-1] if counts.shape[0] else jnp.asarray(0, jnp.int32)
            off = csum - counts  # exclusive prefix
            j = jnp.arange(out_cap, dtype=jnp.int32)
            # source row per output slot: scatter each emitting row's index
            # at its start offset, then a running max fills the gaps — one
            # scan instead of a log(n)-gather searchsorted
            starts = jnp.where(counts > 0, off, out_cap)
            marks = jnp.zeros(out_cap, dtype=jnp.int32).at[starts].max(
                jnp.arange(counts.shape[0], dtype=jnp.int32), mode="drop")
            i = jax.lax.associative_scan(jnp.maximum, marks)
            i = jnp.clip(i, 0, cap_l - 1)
            delta = j - off[i]
            rpos = lo[i] + delta
            rpos = jnp.clip(rpos, 0, rs_perm.shape[0] - 1)
            ri = rs_perm[rpos].astype(jnp.int32)
            out_live = j < total
            li = jnp.where(out_live, i, 0)
            ri = jnp.where(out_live, ri, 0)
            return li, ri, total, out_live

        if kind == "inner":
            def f(lo, counts, rs_perm, live_l):
                li, ri, total, out_live = expand_inner(lo, counts, rs_perm, live_l)
                return li, ri, jnp.zeros(out_cap, jnp.bool_), jnp.zeros(out_cap, jnp.bool_), total
            return f

        if kind == "leftouter":
            def f(lo, counts, rs_perm, live_l):
                # unmatched live left rows emit exactly one null-right row
                counts2 = jnp.where(live_l & (counts == 0), 1, counts)
                li, ri, total, out_live = expand_inner(lo, counts2, rs_perm, live_l)
                null_r = (counts[li] == 0) & out_live
                ri = jnp.where(null_r, 0, ri)
                return li, ri, jnp.zeros(out_cap, jnp.bool_), null_r, total
            return f

        if kind == "fullouter":
            def f(lo, counts, rs_perm, live_l, r_unmatched):
                counts2 = jnp.where(live_l & (counts == 0), 1, counts)
                li, ri, total_l, out_live = expand_inner(lo, counts2, rs_perm, live_l)
                null_r = (counts[li] == 0) & out_live
                # append unmatched build rows with null left
                extra_pos = jnp.cumsum(r_unmatched.astype(jnp.int32)) - 1
                n_extra = jnp.sum(r_unmatched.astype(jnp.int32))
                tgt = jnp.where(r_unmatched, total_l + extra_pos, out_cap)
                ridx = jnp.arange(r_unmatched.shape[0], dtype=jnp.int32)
                ri = ri.at[tgt].set(ridx, mode="drop")
                li = li.at[tgt].set(0, mode="drop")
                null_l = jnp.zeros(out_cap, jnp.bool_).at[tgt].set(True, mode="drop")
                null_r = null_r & ~null_l
                total = total_l + n_extra
                return li, ri, null_l, null_r, total
            return f

        raise ColumnarProcessingError(f"expand kind {kind}")


#: the largest direct-address table a build side may take beyond the
#: conf's multiple of its capacity: 2^26 int32 slots (256 MiB), which
#: holds dbgen's sparse o_orderkey (a range of 4 x rows) up to SF10
DIRECT_MAX_SLOTS = 1 << 26

#: probe batches of a direct inner join whose output row counts are read
#: from the device in ONE fetch (the batches' gathers wait for it)
PROBE_AHEAD = 8

#: the clustered lookup of a direct probe (_direct_lookup): probe rows a
#: block, and table slots a row of the table's 2-D view
WINDOW_ROWS = 128
WINDOW_SLOTS = 512


def _windowed(slots: int) -> bool:
    """Whether a direct table of ``slots`` is kept as rows of
    WINDOW_SLOTS (the form _direct_lookup reads windows from)."""
    return slots % WINDOW_SLOTS == 0 and slots >= 2 * WINDOW_SLOTS


def _direct_lookup(rowid, pos, valid):
    """(the table's entry at slot ``pos`` for the ``valid`` rows of a
    probe batch, whether the batch was clustered). XLA:TPU gathers a
    scalar a DMA: about 17 ns an element whatever the table's size, and a
    time that differs from process to process (PERF.md §6 PR 34). Where
    the valid slots of every WINDOW_ROWS consecutive probe rows lie
    within WINDOW_SLOTS of each other (a fact table in its key's order:
    dbgen's lineitem against orders), a block reads the two
    WINDOW_SLOTS-slot rows of the table that hold its slots, two DMAs a
    block, and each of its rows picks its slot by a compare. Which of the
    two runs is decided on the device from the batch's own slots, inside
    the program; the answer is the gather's either way."""
    cap = pos.shape[0]
    B, R = WINDOW_ROWS, WINDOW_SLOTS
    if rowid.ndim == 1 or cap % B:
        return rowid.reshape(-1)[pos], jnp.zeros((), jnp.bool_)
    slots = rowid.size
    nb = cap // B
    pb = pos.reshape(nb, B)
    vb = valid.reshape(nb, B)
    lo = jnp.min(jnp.where(vb, pb, slots), axis=1)
    hi = jnp.max(jnp.where(vb, pb, -1), axis=1)
    clustered = jnp.all(hi - lo < R)

    def windows():
        r0 = jnp.clip(lo // R, 0, slots // R - 2)
        win = rowid.at[jnp.stack([r0, r0 + 1], axis=1)].get(
            mode="promise_in_bounds").reshape(nb, 2 * R)
        off = pb - (r0 * R)[:, None]
        slot = jnp.arange(2 * R, dtype=jnp.int32)
        return jnp.sum(
            jnp.where(off[:, :, None] == slot, win[:, None, :], 0),
            axis=2, dtype=jnp.int32).reshape(cap)

    return jax.lax.cond(clustered, windows,
                        lambda: rowid.reshape(-1)[pos]), clustered


class _DirectBuild(NamedTuple):
    """A build side made ready for the direct-address body, once a
    query: row ids by ``key - keymin`` (-1: no such key)."""
    rowid: jax.Array      # int32[slots], as rows of WINDOW_SLOTS (_windowed)
    keymin: jax.Array     # int64 scalar
    slots: int


class _DirectPending(NamedTuple):
    """An inner join's probe batch whose kept rows are not counted yet."""
    table: DeviceTable    # the probe batch
    ri: jax.Array         # build row per probe row
    keep: jax.Array       # probe rows that matched
    idx: jax.Array        # their positions, in order, at the front
    nout: jax.Array       # their count, on the device
    clustered: jax.Array  # whether the lookup read windows (_direct_lookup)


class _DirectJoinKernel:
    """Dense-domain direct-address join — the TPU answer to the build-side
    hash table (reference: GpuHashJoin.scala builds a cuDF hash table and
    probes it). Pointer-chasing hash tables are VPU-hostile, but the common
    case — a fact table probing a dimension/key table whose integer keys
    are unique within a bounded range (every foreign-key join) — needs no
    hash and no sort: scatter build row ids into a table indexed by
    ``key - min(key)``, gather per probe key, done.

    Nothing here is a guess. The build side's key range, live count and
    uniqueness are READ once it is ready (``stats``, ``build``: two small
    fetches a join and query), and the body is chosen from them before
    the first probe batch; a probe batch then costs one gather from the
    table (``probe``). An inner join's live output rows are few or many:
    their count is read (one fetch for up to PROBE_AHEAD batches) and the
    output is gathered into the bucket the count needs (``gather``), or
    left in place under a mask where no smaller bucket exists
    (``in_place``)."""

    _traces = {}

    SUPPORTED = ("inner", "left", "leftouter", "leftsemi", "leftanti")

    @classmethod
    def _get(cls, key, make):
        fn = cls._traces.get(key)
        if fn is None:
            fn = cls._traces[key] = make()
        return fn

    @classmethod
    def stats(cls, rkey: DevVal, live_r):
        """int64[4]: the live valid build keys' min, max and count, and
        the live rows' count."""
        def build():
            def stats(rd, rv, live):
                v = rv & live
                k = rd.astype(jnp.int64)
                big = jnp.asarray(np.iinfo(np.int64).max, jnp.int64)
                return jnp.stack([
                    jnp.min(jnp.where(v, k, big)),
                    jnp.max(jnp.where(v, k, -big)),
                    jnp.sum(v.astype(jnp.int64)),
                    jnp.sum(live.astype(jnp.int64))])
            return stats
        rd, rv = rkey
        return cls._get(
            ("stats", rd.shape[0], str(rd.dtype)),
            lambda: tpu_jit(build(), name="join_build_stats"))(
                rd, rv, live_r)

    @classmethod
    def build(cls, rkey: DevVal, live_r, keymin, slots: int):
        """(row ids by ``key - keymin``, whether every key is unique)."""
        def build():
            def table(rd, rv, live, keymin):
                v = rv & live
                cap_r = rd.shape[0]
                pos = jnp.clip(rd.astype(jnp.int64) - keymin, 0,
                               slots - 1).astype(jnp.int32)
                tgt = jnp.where(v, pos, slots)
                cnt = jnp.zeros(slots, jnp.int32).at[tgt].add(
                    1, mode="drop")
                rowid = jnp.full(slots, -1, jnp.int32).at[tgt].max(
                    jnp.arange(cap_r, dtype=jnp.int32), mode="drop")
                if _windowed(slots):
                    rowid = rowid.reshape(-1, WINDOW_SLOTS)
                return rowid, jnp.max(cnt) <= 1
            return table
        rd, rv = rkey
        return cls._get(
            ("build", rd.shape[0], str(rd.dtype), slots),
            lambda: tpu_jit(build(), name="join_direct_build"))(
                rd, rv, live_r, keymin)

    @classmethod
    def probe(cls, jt: str, lkey: DevVal, live_l, direct: _DirectBuild):
        """(build row per probe row, matched, rows kept, their count,
        for an inner join, whose output is gathered, their positions in
        probe order at the front of an int32[capacity], and whether the
        lookup found the batch clustered)."""
        slots = direct.slots

        def build():
            def probe(ld, lv, live, rowid, keymin):
                cap_l = ld.shape[0]
                p = ld.astype(jnp.int64) - keymin
                inb = (p >= 0) & (p < slots) & lv & live
                ri, clustered = _direct_lookup(
                    rowid, jnp.clip(p, 0, slots - 1).astype(jnp.int32), inb)
                matched = inb & (ri >= 0)
                ri = jnp.where(matched, ri, 0)
                keep = (live & ~matched) if jt == "leftanti" else matched
                keep_i = keep.astype(jnp.int32)
                nout = jnp.sum(keep_i)
                if jt != "inner":
                    return ri, matched, keep, nout, None, clustered
                tgt = jnp.where(keep, jnp.cumsum(keep_i) - 1, cap_l)
                idx = jnp.zeros(cap_l, jnp.int32).at[tgt].set(
                    jnp.arange(cap_l, dtype=jnp.int32), mode="drop")
                return ri, matched, keep, nout, idx, clustered
            return probe
        ld, lv = lkey
        return cls._get(
            ("probe", jt, ld.shape[0], str(ld.dtype), slots),
            lambda: tpu_jit(build(), name="join_direct_probe"))(
                ld, lv, live_l, direct.rowid, direct.keymin)

    @classmethod
    def gather(cls, lt: DeviceTable, rt: DeviceTable, ri, idx, nout,
               out_cap: int):
        """The kept rows of an inner join in a bucket of ``out_cap``
        rows: probe columns by position, build columns by row id."""
        def build():
            def gather(l_cols, r_cols, ri, idx, nout):
                li = idx[:out_cap]
                live = jnp.arange(out_cap, dtype=jnp.int32) < nout
                rj = ri[li]
                return ([(d[li], v[li] & live) for d, v in l_cols],
                        [(d[rj], v[rj] & live) for d, v in r_cols])
            return gather
        key = ("gather", out_cap, lt.schema_key(), rt.schema_key())
        return cls._get(
            key, lambda: tpu_jit(build(), name="join_direct_gather"))(
            tuple((c.data, c.validity) for c in lt.columns),
            tuple((c.data, c.validity) for c in rt.columns), ri, idx, nout)

    @classmethod
    def in_place(cls, rt: DeviceTable, ri, matched):
        """The build columns beside the probe rows where they lie."""
        def build():
            def in_place(r_cols, ri, matched):
                return [(d[ri], v[ri] & matched) for d, v in r_cols]
            return in_place
        key = ("inplace", ri.shape[0], rt.schema_key())
        return cls._get(
            key, lambda: tpu_jit(build(), name="join_direct_in_place"))(
            tuple((c.data, c.validity) for c in rt.columns), ri, matched)


class _ColumnGather:
    """Jitted column gather per (out_cap, schema shapes)."""

    _traces = {}

    @classmethod
    def run(cls, table: DeviceTable, idx, null_mask, out_live, out_cap):
        key = (out_cap, table.capacity, table.schema_key()[0])
        fn = cls._traces.get(key)
        if fn is None:
            cap = table.capacity

            def gather(datas, valids, idx, null_mask, out_live):
                safe = jnp.clip(idx, 0, cap - 1)
                out = []
                for d, v in zip(datas, valids):
                    out.append((d[safe], v[safe] & ~null_mask & out_live))
                return out

            fn = tpu_jit(gather, name="join_gather")
            cls._traces[key] = fn
        datas = tuple(c.data for c in table.columns)
        valids = tuple(c.validity for c in table.columns)
        outs = fn(datas, valids, idx, null_mask, out_live)
        return [DeviceColumn(c.dtype, d, v, dictionary=c.dictionary,
                             dict_sorted=c.dict_sorted, domain=c.domain)
                for c, (d, v) in zip(table.columns, outs)]


def _unify_string_keys(lcol: DeviceColumn, rcol: DeviceColumn):
    """Remap two dictionary-coded string columns into the union dictionary
    so codes compare across tables. Host work is O(dict size)."""
    ldict = lcol.dictionary if lcol.dictionary is not None else np.array([], dtype=object)
    rdict = rcol.dictionary if rcol.dictionary is not None else np.array([], dtype=object)
    union = np.unique(np.concatenate([ldict.astype(object), rdict.astype(object)]))
    lmap = np.searchsorted(union, ldict).astype(np.int32)
    rmap = np.searchsorted(union, rdict).astype(np.int32)
    from spark_rapids_tpu.dispatch import device_const
    lmap_d = device_const(lmap if len(lmap) else np.zeros(1, np.int32))
    rmap_d = device_const(rmap if len(rmap) else np.zeros(1, np.int32))
    lcodes = lmap_d[jnp.clip(lcol.data, 0, max(len(ldict) - 1, 0))]
    rcodes = rmap_d[jnp.clip(rcol.data, 0, max(len(rdict) - 1, 0))]
    return (lcodes, lcol.validity), (rcodes, rcol.validity)


class TpuJoinExec(TpuExec):
    def __init__(self, left: TpuExec, right: TpuExec, join_type: str,
                 left_keys: Sequence[Expression], right_keys: Sequence[Expression],
                 condition: Optional[Expression],
                 left_schema, right_schema,
                 subpartition_bytes: int = 1 << 30,
                 max_subpartitions: int = 64,
                 build_left: Optional[bool] = None):
        super().__init__()
        self.children = (left, right)
        self.join_type = join_type.lower().replace("_", "")
        #: which child is coalesced into the one build table: the right
        #: one, but for a right outer join, and for an inner join whose
        #: left side the planner found smaller (overrides/rules.py)
        self.build_left = (self.join_type in ("right", "rightouter")
                           if build_left is None else bool(build_left))
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        self.condition = condition
        self.left_names = [n for n, _ in left_schema]
        self.right_names = [n for n, _ in right_schema]
        self._left_schema = left_schema
        self._right_schema = right_schema
        self.subpartition_bytes = subpartition_bytes
        self.max_subpartitions = max_subpartitions
        self._kernel = JoinKernel.get(len(self.left_keys))
        self._filter_kernel = None

    def output_schema(self):
        jt = self.join_type
        ls = list(self._left_schema)
        rs = list(self._right_schema)
        if jt in ("leftsemi", "leftanti"):
            return ls
        # outer sides become nullable but DataType carries no nullability here
        return ls + rs

    def describe(self):
        side = "left" if self.build_left else "right"
        return (f"TpuJoin[{self.join_type}, keys={len(self.left_keys)}, "
                f"build={side}]")

    # -----------------------------------------------------------------------
    produces_masked = True

    def execute_masked(self):
        """Probe-side STREAMING execution: the build side is one coalesced
        (spillable-protected) table, made ready once (range
        ``srt.join.build``: the coalesce, and for a single integer key
        the direct-address table, _plan_direct); probe batches stream
        through one at a time (range ``srt.join.batch`` each) — the
        reference's join iterator shape (GpuShuffledHashJoinExec streams
        the streamed side against the built hash table). Full-outer
        joins accumulate a build-side match bitmap across probe batches and
        emit unmatched build rows as a final batch. Probe batches may be
        MASKED (filter output). Both ranges add to ``phasesS.joinS``."""
        from spark_rapids_tpu.dispatch import phase_span
        from spark_rapids_tpu.runtime.retry import retry_block

        jt = self.join_type
        swapped = self.build_left
        build_child = self.children[0] if swapped else self.children[1]
        probe_child = self.children[1] if swapped else self.children[0]
        self.add_metric("buildSideSwapped", int(swapped))

        with phase_span("joinS", "build", "join"):
            build = self._single(build_child)
            # spill-aware threshold: a build side past the device budget's
            # chunk share sub-partitions even when the conf threshold is
            # higher — each partition rides the spill tiers independently
            # instead of pinning one over-budget resident table
            from spark_rapids_tpu.runtime.memory import MEMORY
            sub_bytes = self.subpartition_bytes
            if sub_bytes > 0:
                sub_bytes = min(sub_bytes, MEMORY.scan_chunk_bytes())
            nparts = 1
            if (jt != "cross" and sub_bytes > 0
                    and build.device_nbytes() > sub_bytes):
                nparts = min(
                    -(-build.device_nbytes() // sub_bytes),
                    self.max_subpartitions)
            direct, rows = self._plan_direct(build) if nparts == 1 \
                else (None, None)
            if rows is None and build.num_rows_known:
                rows = build.num_rows
            if rows is not None:
                self.add_metric("buildRows", rows)
        if nparts > 1:
            yield from self._execute_subpartitioned(
                build, probe_child, swapped, int(nparts))
            return

        # the build side registers as a SpillableDeviceTable (ISSUE 15):
        # pinned only while one probe batch joins, so between batches —
        # while the probe child computes, possibly paying its own
        # memory pressure — the idle build table may ride the
        # device->host->disk tiers and re-land at its original
        # capacity for the next probe (traces, the direct table's row
        # ids and the full-outer match bitmap key on that capacity and
        # row order staying put)
        from spark_rapids_tpu.runtime.spill import (
            BufferCatalog,
            PRIORITY_ACTIVE,
            SpillableDeviceTable,
        )
        build_sb = SpillableDeviceTable(build, BufferCatalog.get(),
                                        priority=PRIORITY_ACTIVE)
        build_cap = build.capacity
        del build
        full_outer = jt in ("full", "fullouter", "outer")
        r_matched_accum = None
        #: direct inner probes whose output counts are not read yet
        ahead: list = []

        def finish_ahead():
            with phase_span("joinS", "batch", "join"), \
                    build_sb.pinned_batch() as bt:
                outs = self._direct_finish(ahead, bt, swapped)
            ahead.clear()
            return outs

        try:
            for pb in probe_child.execute_masked():
                self.add_metric("probeBatches", 1)
                with phase_span("joinS", "batch", "join"), \
                        build_sb.pinned_batch() as bt:
                    if direct is not None:
                        self.add_metric("directJoinBatches", 1)
                        out = retry_block(
                            lambda b=pb, bb=bt: self._direct_probe(
                                b, bb, direct, swapped))
                        r_matched = None
                        if isinstance(out, _DirectPending):
                            ahead.append(out)
                            out = None
                    else:
                        self.add_metric("sortJoinBatches", 1)
                        out, r_matched = retry_block(
                            lambda b=pb, bb=bt: self._join_batch(
                                b, bb, swapped))
                if full_outer:
                    r_matched_accum = (
                        r_matched if r_matched_accum is None
                        else r_matched_accum | r_matched)
                if out is not None:
                    yield self._apply_condition(out)
                if len(ahead) >= PROBE_AHEAD:
                    for out in finish_ahead():
                        yield self._apply_condition(out)
            if ahead:
                for out in finish_ahead():
                    yield self._apply_condition(out)

            if full_outer:
                if r_matched_accum is None:
                    r_matched_accum = jnp.zeros(build_cap, jnp.bool_)
                with build_sb.pinned_batch() as bt:
                    yield self._unmatched_build_batch(
                        bt, r_matched_accum, swapped)
        finally:
            build_sb.release()

    # -- the direct-address body --------------------------------------------
    def _plan_direct(self, build: DeviceTable):
        """(the build side's direct-address table, its live rows as
        read). The table is None where the join takes the sorted body:
        more than one key, a key that is no integer, a join type the
        direct body does not have (full and right outer), a key range
        wider than the table may be, or a key that repeats. Decided from
        what is READ of the build side, two small fetches, before the
        first probe batch: no flag undoes it."""
        jt = self.join_type
        if len(self.left_keys) != 1 or jt not in _DirectJoinKernel.SUPPORTED:
            return None, None
        keys = (self.left_keys, self.right_keys)
        build_key, probe_key = (keys[0][0], keys[1][0]) if self.build_left \
            else (keys[1][0], keys[0][0])
        if not (isinstance(build_key.data_type, T.IntegralType)
                and isinstance(probe_key.data_type, T.IntegralType)):
            return None, None
        from spark_rapids_tpu.dispatch import host_fetch
        kc = compile_project([build_key], build)[0]
        rkey = (kc.data, kc.validity)
        live_r = build.row_mask()
        kmin, kmax, nvalid, rows = (int(x) for x in host_fetch(
            _DirectJoinKernel.stats(rkey, live_r)))
        if nvalid == 0:
            kmin = kmax = 0
        span = kmax - kmin + 1
        if span > max(DIRECT_TABLE_MULT.get() * build.capacity,
                      DIRECT_MAX_SLOTS):
            return None, rows
        slots = bucket_for(max(span, 1))
        from spark_rapids_tpu.dispatch import device_scalar
        keymin = device_scalar(kmin, np.int64)
        rowid, unique = _DirectJoinKernel.build(rkey, live_r, keymin, slots)
        if not bool(host_fetch(unique)):
            return None, rows
        return _DirectBuild(rowid, keymin, slots), rows

    def _direct_probe(self, lt: DeviceTable, rt: DeviceTable,
                      direct: _DirectBuild, swapped: bool):
        """One probe batch against the direct table. An inner join comes
        back as a _DirectPending (its output is sized by a count that
        _direct_finish reads); every other type as its output table,
        in place: a left outer join keeps every probe row, a semi or
        anti join masks them."""
        jt = self.join_type
        probe_keys = self.right_keys if swapped else self.left_keys
        kc = compile_project(probe_keys, lt)[0]
        ri, matched, keep, nout, idx, clustered = _DirectJoinKernel.probe(
            jt, (kc.data, kc.validity), lt.row_mask(), direct)
        if jt == "inner":
            return _DirectPending(lt, ri, keep, idx, nout, clustered)
        if jt in ("leftsemi", "leftanti"):
            return DeviceTable(lt.names, lt.columns, nout, lt.capacity,
                               live=keep)
        nrows = lt.num_rows if lt.num_rows_known else lt.nrows_dev
        return self._in_place(lt, rt, ri, matched, lt.live, nrows, swapped)

    def _in_place(self, lt, rt, ri, matched, live, nrows,
                  swapped) -> DeviceTable:
        outs = _DirectJoinKernel.in_place(rt, ri, matched)
        rcols = [c.with_arrays(d, v) for c, (d, v) in zip(rt.columns, outs)]
        lcols = list(lt.columns)
        return DeviceTable(self.left_names + self.right_names,
                           rcols + lcols if swapped else lcols + rcols,
                           nrows, lt.capacity, live=live)

    def _direct_finish(self, ahead, rt: DeviceTable, swapped: bool):
        """The outputs of inner-join probes whose counts were left on the
        device: ONE fetch reads them all, then each batch's kept rows are
        gathered into the bucket its count needs (few rows survive a
        selective join: what follows then sorts thousands of rows, not a
        probe batch's capacity), or stay in place under the mask where
        no smaller bucket exists."""
        from spark_rapids_tpu.dispatch import host_fetch
        read = host_fetch([(p.nout, p.clustered) for p in ahead])
        outs = []
        for p, (n, clustered) in zip(ahead, read):
            n = int(n)
            self.add_metric("joinOutputRows", n)
            self.add_metric("clusteredProbeBatches", int(clustered))
            lt = p.table
            out_cap = bucket_for(max(n, 1))
            if out_cap >= lt.capacity:
                outs.append(self._in_place(lt, rt, p.ri, p.keep, p.keep, n,
                                           swapped))
                continue
            louts, routs = _DirectJoinKernel.gather(
                lt, rt, p.ri, p.idx, p.nout, out_cap)
            lcols = [c.with_arrays(d, v)
                     for c, (d, v) in zip(lt.columns, louts)]
            rcols = [c.with_arrays(d, v)
                     for c, (d, v) in zip(rt.columns, routs)]
            outs.append(DeviceTable(
                self.left_names + self.right_names,
                rcols + lcols if swapped else lcols + rcols, n, out_cap))
        return outs

    def _execute_subpartitioned(self, build: DeviceTable, probe_child,
                                swapped: bool, nparts: int):
        """Sub-partitioned escalation (GpuSubPartitionHashJoin analog): the
        build table splits by Spark-exact key hash into ``nparts`` SPILLABLE
        partitions; each probe batch splits the same way, and bucket pairs
        join independently — peak HBM is one build partition + one probe
        sub-batch, not the whole build."""
        from spark_rapids_tpu.runtime.retry import retry_block
        from spark_rapids_tpu.runtime.spill import BufferCatalog, SpillableBatch
        from spark_rapids_tpu.shuffle.partitioning import HashPartitioner

        jt = self.join_type
        full_outer = jt in ("full", "fullouter", "outer")
        build_keys = self.left_keys if swapped else self.right_keys
        probe_keys = self.right_keys if swapped else self.left_keys
        bparter = HashPartitioner(build_keys, nparts)
        pparter = HashPartitioner(probe_keys, nparts)
        catalog = BufferCatalog.get()

        build_parts = [SpillableBatch(t, catalog)
                       for t in self._split(build, bparter)]
        del build
        self.add_metric("subPartitions", nparts)
        r_matched = [None] * nparts
        #: each build partition's direct table (None: the sorted body),
        #: made when its first probe rows come
        directs = {}
        from spark_rapids_tpu.dispatch import phase_span
        try:
            for pb in probe_child.execute_masked():
                self.add_metric("probeBatches", 1)
                for p, pp in enumerate(self._split(pb, pparter)):
                    with phase_span("joinS", "batch", "join"), \
                            build_parts[p].pinned_batch() as bt:
                        if p not in directs:
                            directs[p], _rows = self._plan_direct(bt)
                        out, rm = retry_block(
                            lambda a=pp, b=bt, d=directs[p]:
                            self._join_one(a, b, d, swapped))
                    if full_outer and rm is not None:
                        r_matched[p] = (rm if r_matched[p] is None
                                        else r_matched[p] | rm)
                    if out is not None:
                        yield self._apply_condition(out)

            if full_outer:
                for p in range(nparts):
                    with build_parts[p].pinned_batch() as bt:
                        rm = (r_matched[p] if r_matched[p] is not None
                              else jnp.zeros(bt.capacity, jnp.bool_))
                        yield self._unmatched_build_batch(bt, rm, swapped)
        finally:
            for sb in build_parts:
                sb.release()

    def _split(self, table: DeviceTable, parter) -> List[DeviceTable]:
        """Split a table into per-partition compacted tables, re-bucketed
        to their live size (one host sync for the count vector)."""
        pids = parter.partition_ids(table)
        live = table.row_mask()
        nparts = parter.num_partitions
        key = ("splitcnt", table.capacity, nparts)
        fn = self._kernel._aux_traces.get(key)
        if fn is None:
            def counts_fn(pids, live):
                return jax.ops.segment_sum(
                    live.astype(jnp.int32), jnp.clip(pids, 0, nparts - 1),
                    num_segments=nparts)
            fn = tpu_jit(counts_fn, name="join_split_counts")
            self._kernel._aux_traces[key] = fn
        from spark_rapids_tpu.dispatch import host_fetch
        counts = np.asarray(host_fetch(fn(pids, live)))
        parts = []
        for p in range(nparts):
            compacted = self._compact(table, (pids == p) & live)
            k = bucket_for(max(int(counts[p]), 1))
            if k < compacted.capacity:
                cols = [c.with_arrays(c.data[:k], c.validity[:k])
                        for c in compacted.columns]
                compacted = DeviceTable(compacted.names, cols,
                                        int(counts[p]), k)
            parts.append(compacted)
        return parts


    def _apply_condition(self, out: DeviceTable) -> DeviceTable:
        if self.condition is not None and self.join_type in ("inner", "cross"):
            from spark_rapids_tpu.execs.base import MASKED_ENABLED
            from spark_rapids_tpu.execs.basic import _FilterKernel
            if self._filter_kernel is None:
                self._filter_kernel = _FilterKernel(self.condition)
            out = self._filter_kernel(out,
                                      emit_mask=MASKED_ENABLED.get())
        return out

    @staticmethod
    def _single(child: TpuExec) -> DeviceTable:
        batches = list(child.execute())
        if len(batches) != 1:
            raise ColumnarProcessingError("join requires a coalesced build side")
        return batches[0]

    def _join_one(self, lt: DeviceTable, rt: DeviceTable,
                  direct: Optional[_DirectBuild], swapped: bool):
        """One probe batch joined to the end, by the body ``direct``
        names (a sub-partition's pair: its count is read at once)."""
        if direct is None:
            self.add_metric("sortJoinBatches", 1)
            return self._join_batch(lt, rt, swapped)
        self.add_metric("directJoinBatches", 1)
        out = self._direct_probe(lt, rt, direct, swapped)
        if isinstance(out, _DirectPending):
            out = self._direct_finish([out], rt, swapped)[0]
        return out, None

    def _join_batch(self, lt: DeviceTable, rt: DeviceTable, swapped: bool):
        """Join ONE probe batch (lt) against the build table (rt) by the
        SORTED body. Returns (output table or None, build-match bitmap
        or None)."""
        jt = self.join_type
        if jt == "cross":
            return self._cross(lt, rt, swapped), None

        if swapped:
            lkeys_e, rkeys_e = self.right_keys, self.left_keys
        else:
            lkeys_e, rkeys_e = self.left_keys, self.right_keys

        lkey_cols = compile_project(lkeys_e, lt)
        rkey_cols = compile_project(rkeys_e, rt)

        lkeys, rkeys = [], []
        for lc, rc in zip(lkey_cols, rkey_cols):
            if isinstance(lc.dtype, T.StringType):
                lk, rk = _unify_string_keys(lc, rc)
            else:
                lk, rk = (lc.data, lc.validity), (rc.data, rc.validity)
            lkeys.append(lk)
            rkeys.append(rk)

        full_outer = jt in ("full", "fullouter", "outer")

        (lo, counts, total_d, matched_l, rs_perm, live_l, live_r) = \
            self._kernel.probe(lkeys, rkeys, lt.nrows_dev, rt.nrows_dev,
                               lt.capacity, rt.capacity, lt.live)

        r_matched = None
        if full_outer:
            r_matched = self._right_matched(lo, counts, rs_perm, rt.capacity,
                                            lt.capacity)

        if jt in ("leftsemi", "leftanti"):
            from spark_rapids_tpu.execs.base import MASKED_ENABLED
            keep = matched_l if jt == "leftsemi" else ~matched_l
            keep = keep & live_l
            if MASKED_ENABLED.get():
                nkeep = self._mask_count(keep)
                return DeviceTable(lt.names, lt.columns, nkeep,
                                   lt.capacity, live=keep), None
            return self._compact(lt, keep), None

        # the output's size is READ, one host sync a probe batch (the
        # reference's JoinGatherer row count): a build key that repeats
        # can make any number of rows
        from spark_rapids_tpu.dispatch import host_fetch
        total = int(host_fetch(total_d))
        self.add_metric("joinOutputRows", total)
        if jt in ("left", "leftouter", "right", "rightouter") or full_outer:
            # each unmatched probe row adds at most one output row; use
            # the probe CAPACITY as the static bound rather than paying a
            # second device round trip for the exact count (<=2x bucket)
            upper = total + lt.capacity
        else:
            upper = total
        out_cap = bucket_for(max(upper, 1))

        if jt == "inner":
            li, ri, null_l, null_r, nout = self._kernel.expand(
                "inner", out_cap, lt.capacity, rt.capacity,
                (lo, counts, rs_perm, live_l))
        else:  # left/right outer per batch; full outer = left outer per
            # batch + deferred unmatched-build batch
            li, ri, null_l, null_r, nout = self._kernel.expand(
                "leftouter", out_cap, lt.capacity, rt.capacity,
                (lo, counts, rs_perm, live_l))

        out_live = jnp.arange(out_cap, dtype=jnp.int32) < nout
        lcols = _ColumnGather.run(lt, li, null_l, out_live, out_cap)
        rcols = _ColumnGather.run(rt, ri, null_r, out_live, out_cap)

        names = self.left_names + self.right_names
        cols = rcols + lcols if swapped else lcols + rcols
        return DeviceTable(names, cols, nout, out_cap), r_matched

    def _unmatched_build_batch(self, rt: DeviceTable, r_matched,
                               swapped: bool) -> DeviceTable:
        """Full outer tail: build rows no probe batch matched, with an
        all-null probe side."""
        live_r = rt.row_mask()
        compacted = self._compact(rt, live_r & ~r_matched)
        probe_schema = self._right_schema if swapped else self._left_schema
        null_cols = []
        for _, dt in probe_schema:
            if isinstance(dt, T.StringType):
                data = jnp.zeros(compacted.capacity, dtype=jnp.int32)
                null_cols.append(DeviceColumn(
                    dt, data, jnp.zeros(compacted.capacity, jnp.bool_),
                    dictionary=np.array([], dtype=object)))
            else:
                from spark_rapids_tpu.columnar.column import null_data_array
                null_cols.append(DeviceColumn(
                    dt, null_data_array(dt, compacted.capacity),
                    jnp.zeros(compacted.capacity, jnp.bool_)))
        names = self.left_names + self.right_names
        cols = (list(compacted.columns) + null_cols if swapped
                else null_cols + list(compacted.columns))
        return DeviceTable(names, cols, compacted.nrows_dev,
                           compacted.capacity)

    def _right_matched(self, lo, counts, rs_perm, cap_r: int, cap_l: int):
        """Which build rows matched at least one probe row: mark sorted
        positions [lo_i, lo_i+count_i) then scatter through rs_perm."""
        key = ("rmatch", cap_l, cap_r)
        fn = self._kernel._aux_traces.get(key)
        if fn is None:
            def rmatch(lo, counts, rs_perm):
                # diff trick: +1 at lo, -1 at lo+count, prefix-sum > 0
                marks = jnp.zeros(cap_r + 1, dtype=jnp.int32)
                marks = marks.at[jnp.clip(lo, 0, cap_r)].add(
                    jnp.where(counts > 0, 1, 0), mode="drop")
                ends = jnp.clip(lo + counts, 0, cap_r)
                marks = marks.at[ends].add(jnp.where(counts > 0, -1, 0), mode="drop")
                covered_sorted = jnp.cumsum(marks[:-1]) > 0
                return jnp.zeros(cap_r, jnp.bool_).at[rs_perm].set(covered_sorted)
            fn = tpu_jit(rmatch, name="join_right_matched")
            self._kernel._aux_traces[key] = fn
        return fn(lo, counts, rs_perm)

    def _mask_count(self, keep):
        key = ("maskcount", keep.shape[0])
        fn = self._kernel._aux_traces.get(key)
        if fn is None:
            fn = tpu_jit(lambda k: jnp.sum(k.astype(jnp.int32)),
                         name="join_mask_count")
            self._kernel._aux_traces[key] = fn
        return fn(keep)

    def _compact(self, table: DeviceTable, keep) -> DeviceTable:
        """Semi/anti: compact kept rows (static capacity, like the filter
        kernel's scatter-to-cumsum compaction)."""
        key = ("compact", table.capacity, table.schema_key()[0])
        fn = self._kernel._aux_traces.get(key)
        if fn is None:
            cap = table.capacity

            def compact(datas, valids, keep):
                from spark_rapids_tpu.ops.scatter32 import compact_pairs
                return compact_pairs(datas, valids, keep, cap)

            fn = tpu_jit(compact, name="join_compact")
            self._kernel._aux_traces[key] = fn
        datas = tuple(c.data for c in table.columns)
        valids = tuple(c.validity for c in table.columns)
        outs, new_n = fn(datas, valids, keep)
        cols = [c.with_arrays(d, v) for c, (d, v) in zip(table.columns, outs)]
        return DeviceTable(table.names, cols, new_n, table.capacity)

    def _cross(self, lt: DeviceTable, rt: DeviceTable,
               swapped: bool = False) -> DeviceTable:
        lt = lt.compacted()  # tiling needs the prefix invariant
        nl, nr = lt.num_rows, rt.num_rows
        out_cap = bucket_for(max(nl * nr, 1))
        key = ("cross", out_cap, lt.capacity, rt.capacity)
        fn = self._kernel._aux_traces.get(key)
        if fn is None:
            def cross_maps(nl_d, nr_d):
                j = jnp.arange(out_cap, dtype=jnp.int64)
                nr64 = jnp.maximum(nr_d.astype(jnp.int64), 1)
                li = j // nr64
                ri = j % nr64
                out_live = j < nl_d.astype(jnp.int64) * nr_d.astype(jnp.int64)
                return li, ri, out_live
            fn = tpu_jit(cross_maps, name="join_cross_maps")
            self._kernel._aux_traces[key] = fn
        li, ri, out_live = fn(lt.nrows_dev, rt.nrows_dev)
        zero = jnp.zeros(out_cap, jnp.bool_)
        lcols = _ColumnGather.run(lt, li, zero, out_live, out_cap)
        rcols = _ColumnGather.run(rt, ri, zero, out_live, out_cap)
        cols = rcols + lcols if swapped else lcols + rcols
        return DeviceTable(self.left_names + self.right_names, cols,
                           nl * nr, out_cap)

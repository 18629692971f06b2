"""ICI shuffle exchange: hash-partition rows across a device mesh with ONE
all-to-all collective.

Reference mapping (SURVEY.md §2.6): GpuShuffleExchangeExec's UCX fast path
becomes ``jax.lax.all_to_all`` over the mesh axis. ``chip_exchange`` is the
one program (``mesh_exchange``) an input batch: on each chip the
Spark-exact murmur3 target of its rows, and one all-to-all that hands chip
``d`` the rows of partition ``d`` under a mask. All shapes are static (each
chip receives every chip's block, the worst case); the masks carry the live
rows, and the device keeps each chip's count. TpuShuffleExchangeExec runs it
when spark.rapids.shuffle.mode=ICI or the mesh is on and the partition
count fits the mesh, and reads the counts once, after its last batch
(``verified_counts``); the host-file shuffle covers every other case.

String keys hash by their dictionary BYTE matrix (replicated across the
mesh — O(dict) bytes), so Spark-exact murmur3 applies to strings too.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence

import jax
from spark_rapids_tpu.dispatch import tpu_jit
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.parallel.mesh import MESH_SCOPE, count_mesh_upload
from spark_rapids_tpu.shuffle.hashing import (
    SPARK_SEED,
    murmur3_hash_device,
    string_dict_bytes,
)


def _axis_size(mesh, axis) -> int:
    """Device count of ``axis`` — a single axis name or a tuple of them
    (the hierarchical (dcn, ici) mesh exchanges over both)."""
    if isinstance(axis, tuple):
        n = 1
        for a in axis:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axis]


#: replicated string-dictionary byte matrices, interned by DICTIONARY
#: IDENTITY per device set (the dispatch.device_const pattern lifted to
#: the mesh): repeated exchanges over one dictionary pay the replication
#: upload once. ndarrays are not weakref-able, so the bounded LRU keys
#: on id() and pins the dictionary with a strong reference — the pin is
#: exactly what makes the id key sound (a live object's id can't be
#: reused), and the cap bounds the pinned host memory.
from collections import OrderedDict
from spark_rapids_tpu.lockorder import ordered_lock

_DICT_INTERN: "OrderedDict[int, tuple]" = OrderedDict()
_DICT_INTERN_LOCK = ordered_lock("mesh.dict_intern")
_DICT_INTERN_CAP = 256
#: jitted gather-digest kernels (the TPAK-v2 row-count/checksum
#: validation at mesh gather boundaries — execs/mesh.py and the
#: verified live-count fetch below). Device-referencing once traced,
#: so invalidation clears it with the other two mesh caches — and a
#: publish is epoch-guarded like theirs (a builder that started before
#: clear_mesh_caches ran must not re-seed the cleared cache)
_DIGEST_CACHE: Dict[tuple, object] = {}
#: (id(dict), dev_ids) -> Event while one thread replicates that entry:
#: concurrent first-exchangers over one dictionary wait for the winner
#: instead of each paying the upload (and each counting meshDictInterns/
#: meshHostUploads — the warm-path-zero contract must hold under a
#: concurrent QueryService too)
_DICT_INFLIGHT: dict = {}
#: bumped by clear_mesh_caches (under _DICT_INTERN_LOCK): a builder
#: that started against the pre-invalidation backend must not PUBLISH
#: its entry after the clear — device ids survive a reinit unchanged,
#: so a late insert would permanently re-seed the cache with the dead
#: backend's buffers (the executable cache's generation-stamp-at-
#: re-park contract, applied to these two caches)
_MESH_CACHE_EPOCH = 0


def clear_mesh_caches() -> int:
    """Drop every mesh-exchange cache that references device state: the
    interned replicated dictionary matrices ARE device arrays, and the
    exchange programs are compiled against the mesh's Device objects.
    Both key on device IDS, which survive a device-loss backend reinit
    unchanged — without this hook
    a recovered backend would keep serving buffers of the dead one
    (runtime/health.py calls here alongside the exec/kernel/const/scan
    caches) — and the OOM eviction path (runtime/retry.py) frees the
    pinned replicated matrices like any other evictable device cache.
    Returns the number of entries dropped."""
    global _MESH_CACHE_EPOCH
    with _DICT_INTERN_LOCK:
        n = len(_DICT_INTERN)
        _DICT_INTERN.clear()
        n += len(_DIGEST_CACHE)
        _DIGEST_CACHE.clear()
        n += len(_CHIP_EXCHANGES)
        _CHIP_EXCHANGES.clear()
        from spark_rapids_tpu.parallel import shards
        n += len(shards._PROGRAMS)
        shards._PROGRAMS.clear()
        # reject in-flight builders' late publishes (their device state
        # predates the invalidation)
        _MESH_CACHE_EPOCH += 1
    return n


def digest_kernel(key: tuple, build):
    """Epoch-guarded intern of one jitted gather-digest kernel: the
    check-then-build-then-publish window is closed the same way the
    other two mesh caches close it — a builder that started before
    clear_mesh_caches ran (a device-loss reinit racing an in-flight
    gather) serves its kernel to THIS caller only and never re-seeds
    the cleared cache with programs traced against the dead backend
    (pinned by a two-thread test)."""
    with _DICT_INTERN_LOCK:
        fn = _DIGEST_CACHE.get(key)
        if fn is not None:
            return fn
        epoch = _MESH_CACHE_EPOCH
    fn = build()
    with _DICT_INTERN_LOCK:
        if epoch == _MESH_CACHE_EPOCH:
            # a concurrent builder may have won; keep one canonical fn
            fn = _DIGEST_CACHE.setdefault(key, fn)
    return fn


def interned_dict_bytes(dictionary: np.ndarray, mesh) -> tuple:
    """(byte_matrix, lengths) of ``dictionary`` as device arrays
    replicated across ``mesh``, interned by dictionary identity. The
    replication happens OUTSIDE the lock (it is the slow part), so a
    per-(dictionary, device set) in-flight marker closes the
    check-then-act window: concurrent first-exchangers wait for the
    winner's entry instead of each paying — and counting — the upload.
    A winner that fails clears its marker in the finally, so a waiter
    loops back, misses, and becomes the uploader itself."""
    from jax.sharding import NamedSharding, PartitionSpec as P_
    dev_ids = tuple(d.id for d in mesh.devices.flat)
    key = id(dictionary)
    flight_key = (key, dev_ids)
    while True:
        with _DICT_INTERN_LOCK:
            entry = _DICT_INTERN.get(key)
            if entry is not None and entry[0] is dictionary:
                _DICT_INTERN.move_to_end(key)
                hit = entry[1].get(dev_ids)
                if hit is not None:
                    return hit
            ev = _DICT_INFLIGHT.get(flight_key)
            if ev is None:
                ev = threading.Event()
                _DICT_INFLIGHT[flight_key] = ev
                break  # this thread replicates
        ev.wait()
    try:
        with _DICT_INTERN_LOCK:
            epoch = _MESH_CACHE_EPOCH
        from spark_rapids_tpu.runtime.faults import fault_point
        fault_point("mesh.dict.upload")
        mat, lens = string_dict_bytes(dictionary)
        rep = NamedSharding(mesh, P_())
        out = (jax.device_put(mat, rep), jax.device_put(lens, rep))
        count_mesh_upload(2)
        MESH_SCOPE.add("meshDictInterns", 1)
        with _DICT_INTERN_LOCK:
            if epoch != _MESH_CACHE_EPOCH:
                # clear_mesh_caches ran mid-build (device-loss reinit):
                # this entry references the dead backend — serve it to
                # THIS caller only, never publish it
                return out
            entry = _DICT_INTERN.get(key)
            if entry is None or entry[0] is not dictionary:
                entry = (dictionary, {})
                _DICT_INTERN[key] = entry
                while len(_DICT_INTERN) > _DICT_INTERN_CAP:
                    _DICT_INTERN.popitem(last=False)
            entry[1][dev_ids] = out
        return out
    finally:
        with _DICT_INTERN_LOCK:
            _DICT_INFLIGHT.pop(flight_key, None)
        ev.set()


def verified_counts(counts_dev) -> np.ndarray:
    """The live-row counts an ICI exchange's programs kept on the device
    (one per partition and batch), read to the host ONCE, after the
    exchange's last batch: they are its AQE map-output statistics.
    Fetched CHECKSUMMED (TPAK-v2 pattern): a device-side uint32 word-sum
    digest rides the same fetch, the host recomputes it over the fetched
    bytes, and a mismatch (a corrupted wire fetch; the
    ``mesh.ici.exchange`` corrupt kind injects exactly this) refetches
    the intact device value, bounded by spark.rapids.mesh.maxShardRetries
    and counted in gatherChecksFailed/shardRetries, instead of feeding
    the statistics garbage counts. ``counts_dev``: the counts arrays, in
    order; returns them concatenated as int32."""
    from spark_rapids_tpu.errors import MeshGatherError
    from spark_rapids_tpu.parallel import mesh as PM
    from spark_rapids_tpu.parallel.mesh import mesh_gather, wordsum_u32
    from spark_rapids_tpu.runtime.faults import fault_point

    counts_i32 = jnp.concatenate(
        [c.astype(jnp.int32).reshape(-1) for c in counts_dev])
    if not PM.GATHER_VERIFY:
        return mesh_gather(counts_i32).astype(np.int32)
    digest = jax.lax.bitcast_convert_type(
        wordsum_u32(counts_i32), jnp.int32).reshape(1)
    packed = jnp.concatenate([counts_i32, digest])
    retries = 0
    while True:
        # count the COUNTS as gathered elements, not the digest word
        # riding along (meshGatherRows stays comparable with
        # pre-verification artifact rounds)
        arr = mesh_gather(packed,
                          rows=int(packed.shape[0]) - 1).astype(np.int32)
        raw = fault_point("mesh.ici.exchange", data=arr.tobytes())
        arr = np.frombuffer(raw, dtype=np.int32)
        counts, got = arr[:-1], arr[-1:].view(np.uint32)[0]
        want = np.uint32(
            counts.view(np.uint32).sum(dtype=np.uint64) & 0xFFFFFFFF)
        if got == want:
            return counts
        MESH_SCOPE.add("gatherChecksFailed", 1)
        if retries >= PM.MAX_SHARD_RETRIES:
            raise MeshGatherError(
                f"ICI exchange live-count fetch failed its checksum "
                f"{retries + 1} times (device digest {int(got)} vs "
                f"recomputed {int(want)})")
        retries += 1
        MESH_SCOPE.add("shardRetries", 1)


_CHIP_EXCHANGES: Dict[tuple, object] = {}


def chip_exchange(chips, datas, valids, key_datas, key_valids, key_dtypes,
                  live, nrows, string_bytes: Optional[Dict[int, tuple]] = None):
    """Hash-partition the rows of a table that lies on ``chips`` (see
    parallel/shards.py) over those chips, in ONE program, ``mesh_exchange``:
    on each chip the Spark-exact murmur3 partition of every row, then one
    ``all_to_all`` over the row axes that hands chip ``d`` every column
    of every chip's rows under the mask of the live rows whose partition
    is ``d``. The sender keeps its rows where they lie, so nothing is
    scattered or compacted on either side: a received block is the
    sender's rows in their order, which keeps a key-ordered table's
    lookups clustered (execs/join.py ``_direct_lookup``). What crosses is
    padded to the worst case (every row of a chip to one partition):
    ``chips.n`` blocks of ``chips.rows`` rows a chip.

    ``datas``/``valids``/``key_*``: the columns' and the key columns'
    global arrays, row-sharded over ``chips``; ``live``: the table's
    mask (same layout) or None for a landed table of ``nrows`` rows (a
    scalar every chip holds). Returns (datas, valids, live, counts): the
    received columns and their mask, ``chips.n * chips.rows`` rows a
    chip, and each chip's live rows received (int32[chips.n], chip
    order; the device keeps them)."""
    string_bytes = string_bytes or {}
    has_sbytes = tuple(i in string_bytes for i in range(len(key_datas)))
    key = (chips.token(), tuple((str(a.dtype), a.shape[1:]) for a in datas),
           tuple(map(str, key_dtypes)), has_sbytes,
           tuple(string_bytes[i][0].shape for i in sorted(string_bytes)),
           live is None)
    with _DICT_INTERN_LOCK:
        fn = _CHIP_EXCHANGES.get(key)
        epoch = _MESH_CACHE_EPOCH
    if fn is None:
        fn = tpu_jit(chip_exchange_program(chips, key_dtypes, has_sbytes,
                                           live is None),
                     name="mesh_exchange")
        with _DICT_INTERN_LOCK:
            if epoch == _MESH_CACHE_EPOCH:
                fn = _CHIP_EXCHANGES.setdefault(key, fn)
    sbytes = tuple(string_bytes[i] for i in sorted(string_bytes))
    from spark_rapids_tpu.runtime.faults import fault_point
    fault_point("mesh.ici.exchange")
    from spark_rapids_tpu.runtime.cluster import dcn_exchange_point
    dcn_exchange_point(chips.mesh)
    return fn(tuple(datas), tuple(valids), tuple(key_datas),
              tuple(key_valids), live, nrows, sbytes)


def chip_exchange_program(chips, key_dtypes, has_sbytes, landed: bool):
    """The per-chip body of :func:`chip_exchange` under ``shard_map`` over
    ``chips``, not yet compiled: (datas, valids, key datas, key valids,
    live, nrows, string key bytes) -> (datas, valids, live, counts)."""
    from jax.sharding import PartitionSpec as P_

    from spark_rapids_tpu.parallel.shards import chip_live, on_chips

    n, rows, axes = chips.n, chips.rows, chips.axes
    key_dts = list(key_dtypes)

    def on_chip(datas, valids, kdatas, kvalids, live, nrows, sbytes):
        live = chip_live(live, nrows, rows, axes)
        keys = [(d, v, dt) for d, v, dt in zip(kdatas, kvalids, key_dts)]
        by_key = {i: sb for i, sb in zip(
            [i for i, has in enumerate(has_sbytes) if has], sbytes)}
        h = murmur3_hash_device(keys, SPARK_SEED, by_key)
        pid = h % jnp.int32(n)
        pid = jnp.where(pid < 0, pid + n, pid)
        parts = jnp.arange(n, dtype=pid.dtype)[:, None]
        send_live = live[None, :] & (pid[None, :] == parts)

        def to_all(a):
            """Every destination gets this chip's rows: a block of
            ``rows`` rows from each chip comes back, in chip order.
            A mask crosses as bytes."""
            flag = a.dtype == jnp.bool_
            send = jnp.broadcast_to(
                (a.astype(jnp.uint8) if flag else a)[None],
                (n,) + a.shape)
            out = jax.lax.all_to_all(send, axes, 0, 0).reshape(
                (n * rows,) + a.shape[1:])
            return out.astype(jnp.bool_) if flag else out

        recv_live = jax.lax.all_to_all(
            send_live.astype(jnp.uint8), axes, 0, 0).reshape(
                n * rows).astype(jnp.bool_)
        count = jnp.sum(recv_live.astype(jnp.int32))[None]
        return ([to_all(d) for d in datas], [to_all(v) for v in valids],
                recv_live, count)

    by_row = chips.by_row
    return on_chips(
        on_chip, chips,
        in_specs=(by_row, by_row, by_row, by_row,
                  None if landed else by_row, P_(), P_()),
        out_specs=(by_row, by_row, by_row, by_row))


def mesh_hash_exchange(mesh, dtypes: Sequence[T.DataType],
                       key_idx: Sequence[int], axis_name: str = "data"):
    """:func:`chip_exchange` over bare arrays whose hash keys are some of
    the columns (no string keys): ``run(datas, valids)`` takes global
    arrays of a length the mesh's devices divide and returns the received
    (datas, valids, live), chip ``d``'s block holding partition ``d``."""
    from spark_rapids_tpu.parallel.mesh import shard_put
    from spark_rapids_tpu.parallel.shards import Chips
    dts = list(dtypes)
    kset = list(key_idx)
    ndev = _axis_size(mesh, axis_name)

    def run(datas: List[jax.Array], valids: List[jax.Array]):
        chips = Chips(mesh, axis_name, ndev, datas[0].shape[0] // ndev)
        where = chips.row_sharding()
        datas = [shard_put(d, where) for d in datas]
        valids = [shard_put(v, where) for v in valids]
        nrows = jax.device_put(jnp.asarray(datas[0].shape[0], jnp.int64),
                               chips.everywhere())
        out_d, out_v, live, _counts = chip_exchange(
            chips, datas, valids, [datas[i] for i in kset],
            [valids[i] for i in kset], [dts[i] for i in kset], None, nrows)
        return out_d, out_v, live

    return run


def mesh_partial_then_merge(mesh, axis_name: str = "data"):
    """Partial-aggregate-per-shard + psum merge (the distributed two-phase
    GpuHashAggregate shape); used by the multichip dry run."""
    from jax.sharding import PartitionSpec as P_

    def build(local_fn):
        def wrapper(*args):
            partial_out = local_fn(*args)
            return jax.tree.map(lambda x: jax.lax.psum(x, axis_name),
                                partial_out)

        return tpu_jit(jax.shard_map(wrapper, mesh=mesh,
                                     in_specs=P_(axis_name),
                                     out_specs=P_()),
                       name="mesh_partial_merge")
    return build

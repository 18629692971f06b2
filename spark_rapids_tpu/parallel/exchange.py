"""ICI shuffle exchange: hash-partition rows across a device mesh with ONE
all-to-all collective.

Reference mapping (SURVEY.md §2.6): GpuShuffleExchangeExec's UCX fast path
becomes ``jax.lax.all_to_all`` over the mesh axis — each device bucketizes
its row shard by Spark-exact murmur3 target, pads buckets to the static
shard size, and the collective delivers every device its partition. All
shapes are static (bucket = local shard capacity, the worst case); validity
masks carry the live counts. The plan-integrated entry point is
``MeshExchange`` (used by TpuShuffleExchangeExec when
spark.rapids.shuffle.mode=ICI and the partition count fits the mesh);
the host-file shuffle covers every other case.

String keys hash by their dictionary BYTE matrix (replicated across the
mesh — O(dict) bytes), so Spark-exact murmur3 applies to strings too.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence, Tuple

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
    interned replicated dictionary matrices ARE device arrays and a
    MeshExchange instance holds the mesh's Device objects plus a jitted
    program compiled against them. Both key on device IDS, which
    survive a device-loss backend reinit unchanged — without this hook
    a recovered backend would keep serving buffers of the dead one
    (runtime/health.py calls here alongside the exec/kernel/const/scan
    caches) — and the OOM eviction path (runtime/retry.py) frees the
    pinned replicated matrices like any other evictable device cache.
    Returns the number of entries dropped."""
    global _MESH_CACHE_EPOCH
    with _DICT_INTERN_LOCK:
        n = len(_DICT_INTERN)
        _DICT_INTERN.clear()
        n += len(MeshExchange._cache)
        MeshExchange._cache.clear()
        n += len(_DIGEST_CACHE)
        _DIGEST_CACHE.clear()
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


def _bucketize(pid, live, ndev: int, cap: int):
    """Per-row scatter target into a (ndev*cap) padded send buffer:
    pid*cap + rank-within-bucket; dead rows drop."""
    spid = jnp.where(live, pid, ndev)
    order = jnp.argsort(spid, stable=True)
    sorted_pid = spid[order]
    idx = jnp.arange(cap, dtype=jnp.int32)
    is_first = jnp.concatenate([jnp.ones(1, jnp.bool_),
                                sorted_pid[1:] != sorted_pid[:-1]])
    run_start = jnp.where(is_first, idx, 0)
    run_start = jax.lax.associative_scan(jnp.maximum, run_start)
    slot_sorted = idx - run_start
    slot = jnp.zeros(cap, jnp.int32).at[order].set(slot_sorted)
    return jnp.where(live, pid * cap + slot, ndev * cap)


class MeshExchange:
    """Plan-integrated all-to-all exchange over a device mesh.

    One instance is built per (mesh, column dtypes, key layout) — the
    jitted shard_map program is cached on the instance. ``run`` takes the
    coalesced input table's column arrays plus the live-row mask and
    returns, per partition, front-compacted output arrays + live counts.
    """

    _cache: Dict[tuple, "MeshExchange"] = {}

    @classmethod
    def get(cls, mesh, col_dtypes: Tuple[str, ...], key_cols: Tuple[int, ...],
            key_dtypes, string_key_shapes: tuple, cap: int,
            axis_name: str = "data"):
        dev_ids = tuple(d.id for d in mesh.devices.flat)
        key = (dev_ids, col_dtypes, key_cols, tuple(map(str, key_dtypes)),
               string_key_shapes, cap, axis_name)
        with _DICT_INTERN_LOCK:
            inst = cls._cache.get(key)
            epoch = _MESH_CACHE_EPOCH
        if inst is None:
            inst = cls(mesh, key_dtypes, axis_name)
            with _DICT_INTERN_LOCK:
                if epoch == _MESH_CACHE_EPOCH:
                    cls._cache[key] = inst
                # else: clear_mesh_caches ran mid-build (device-loss
                # reinit) — the instance holds the dead backend's mesh;
                # serve it to this caller only, never publish
        return inst

    def __init__(self, mesh, key_dtypes, axis_name="data"):
        self.mesh = mesh
        #: a single axis name, or a tuple of names for the hierarchical
        #: (dcn, ici) mesh — the all-to-all then rides the fast inner
        #: axis within each dcn group (one collective, two mesh dims)
        self.axis_name = axis_name
        self.ndev = _axis_size(mesh, axis_name)
        self.key_dtypes = list(key_dtypes)
        self._fn = None

    def _build(self, ncols: int, nkeys: int, has_sbytes: Tuple[bool, ...]):
        from jax.sharding import PartitionSpec as P_

        ndev = self.ndev
        axis = self.axis_name
        key_dts = self.key_dtypes

        def shard_fn(*flat):
            pos = 0
            datas = flat[pos:pos + ncols]; pos += ncols
            valids = flat[pos:pos + ncols]; pos += ncols
            kdatas = flat[pos:pos + nkeys]; pos += nkeys
            kvalids = flat[pos:pos + nkeys]; pos += nkeys
            live = flat[pos]; pos += 1
            sbytes = {}
            for i, has in enumerate(has_sbytes):
                if has:
                    sbytes[i] = (flat[pos], flat[pos + 1])
                    pos += 2
            cap = datas[0].shape[0] if datas else kdatas[0].shape[0]

            keys = [(kdatas[i], kvalids[i], key_dts[i]) for i in range(nkeys)]
            h = murmur3_hash_device(keys, SPARK_SEED, sbytes)
            pid = h % jnp.int32(ndev)
            pid = jnp.where(pid < 0, pid + ndev, pid)
            tgt = _bucketize(pid, live, ndev, cap)

            def exchange(arr):
                """Scatter into the (ndev, cap) send buffer and run the
                all-to-all — trailing dims (the decimal128 two-limb
                layout) ride along, indexed on the row axis only."""
                tail = arr.shape[1:]
                send = jnp.zeros((ndev * cap,) + tail, arr.dtype).at[
                    tgt].set(arr, mode="drop").reshape((ndev, cap) + tail)
                return jax.lax.all_to_all(send, axis, 0, 0).reshape(
                    (ndev * cap,) + tail)

            recv_live = jnp.zeros((ndev * cap,), jnp.bool_).at[tgt].set(
                True, mode="drop").reshape(ndev, cap)
            recv_live = jax.lax.all_to_all(recv_live, axis, 0, 0)

            out_datas, out_valids = [], []
            for d, v in zip(datas, valids):
                out_datas.append(exchange(d))
                out_valids.append(exchange(v))

            # per-shard compaction: received blocks are front-compacted per
            # source device but gapped between blocks; one scatter compacts
            # the whole shard and counts the live rows
            flat_live = recv_live.reshape(ndev * cap)
            cpos = jnp.cumsum(flat_live.astype(jnp.int32)) - 1
            ctgt = jnp.where(flat_live, cpos, ndev * cap)
            n_live = jnp.sum(flat_live.astype(jnp.int32))
            comp_d, comp_v = [], []
            for d, v in zip(out_datas, out_valids):
                comp_d.append(jnp.zeros_like(d).at[ctgt].set(d, mode="drop"))
                comp_v.append(jnp.zeros_like(v).at[ctgt].set(v, mode="drop"))
            return tuple(comp_d) + tuple(comp_v) + (n_live[None],)

        n_row_args = 2 * ncols + 2 * nkeys + 1
        in_specs = [P_(axis)] * n_row_args
        for has in has_sbytes:
            if has:
                in_specs += [P_(), P_()]  # replicated dictionary bytes
        out_specs = [P_(axis)] * (2 * ncols) + [P_(axis)]
        return tpu_jit(jax.shard_map(shard_fn, mesh=self.mesh,
                                     in_specs=tuple(in_specs),
                                     out_specs=tuple(out_specs)),
                       name="ici_exchange")

    def run(self, datas, valids, key_datas, key_valids, live,
            string_bytes: Optional[Dict[int, tuple]] = None):
        """All arrays are GLOBAL row arrays (length divisible by the mesh
        size). Returns (out_datas, out_valids, counts) where each output is
        global with per-device shards front-compacted and ``counts`` holds
        one live count per partition."""
        from jax.sharding import NamedSharding, PartitionSpec as P_

        from spark_rapids_tpu.parallel.mesh import shard_put

        string_bytes = string_bytes or {}
        has_sbytes = tuple(i in string_bytes for i in range(len(key_datas)))
        if self._fn is None:
            self._fn = self._build(len(datas), len(key_datas), has_sbytes)
        sharding = NamedSharding(self.mesh, P_(self.axis_name))
        rep = NamedSharding(self.mesh, P_())
        # shard_put counts host uploads: on a warm mesh query every
        # input is already device-resident (scans landed sharded, the
        # previous exchange's outputs never left the device), so the
        # puts below are device-side reshards only
        flat = [shard_put(x, sharding)
                for x in (*datas, *valids, *key_datas, *key_valids, live)]
        for i, has in enumerate(has_sbytes):
            if has:
                mat, lens = string_bytes[i]
                flat.append(shard_put(mat, rep))
                flat.append(shard_put(lens, rep))
        # the collective's fault site: crash exercises the replay path,
        # device_lost the partial-loss degradation ladder; corrupt is
        # consumed by the verified counts fetch below (it needs bytes)
        from spark_rapids_tpu.runtime.faults import fault_point
        fault_point("mesh.ici.exchange")
        # cross-HOST marker: when this exchange's mesh spans more than
        # one cluster host group the all-to-all crosses the DCN axis —
        # the host.dcn.exchange fault point fires there (device_lost
        # raises HostLostError into the host ladder) and dcnExchanges
        # counts (runtime/cluster.py; no-op without an active cluster)
        from spark_rapids_tpu.runtime.cluster import dcn_exchange_point
        dcn_exchange_point(self.mesh)
        out = self._fn(*flat)
        ncols = len(datas)
        return (list(out[:ncols]), list(out[ncols:2 * ncols]),
                self._verified_counts(out[2 * ncols]))

    def _verified_counts(self, counts_dev):
        """The ONE host materialization an ICI exchange pays — the
        per-partition live counts (they double as the AQE map-output
        statistic) — fetched CHECKSUMMED (TPAK-v2 pattern): a device-
        side uint32 word-sum digest rides the same fetch, the host
        recomputes it over the fetched bytes, and a mismatch (a
        corrupted wire fetch; the ``mesh.ici.exchange`` corrupt kind
        injects exactly this) refetches the intact device value —
        bounded by spark.rapids.mesh.maxShardRetries, counted in
        gatherChecksFailed/shardRetries — instead of feeding AQE and
        the batch slicer garbage counts."""
        import jax

        from spark_rapids_tpu.errors import MeshGatherError
        from spark_rapids_tpu.parallel import mesh as PM
        from spark_rapids_tpu.parallel.mesh import mesh_gather, wordsum_u32
        from spark_rapids_tpu.runtime.faults import fault_point

        if not PM.GATHER_VERIFY:
            return mesh_gather(counts_dev)
        counts_i32 = counts_dev.astype(jnp.int32).reshape(-1)
        digest = jax.lax.bitcast_convert_type(
            wordsum_u32(counts_i32), jnp.int32).reshape(1)
        packed = jnp.concatenate([counts_i32, digest])
        retries = 0
        while True:
            # count the COUNTS as gathered elements, not the digest
            # word riding along (meshGatherRows stays comparable with
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


def mesh_hash_exchange(mesh, dtypes: Sequence[T.DataType],
                       key_idx: Sequence[int], axis_name: str = "data"):
    """Back-compat wrapper over MeshExchange for non-string columns where
    the hash keys are table columns (older tests / dryrun helper)."""
    dts = list(dtypes)
    kset = list(key_idx)

    def run(datas: List[jax.Array], valids: List[jax.Array]):
        ex = MeshExchange(mesh, [dts[i] for i in kset], axis_name)
        live = jnp.ones(datas[0].shape[0], jnp.bool_)
        out_d, out_v, counts = ex.run(
            datas, valids, [datas[i] for i in kset],
            [valids[i] for i in kset], live)
        ndev = mesh.shape[axis_name]
        cap = datas[0].shape[0] // ndev
        out_live = []
        shard = ndev * cap
        liv = np.zeros(ndev * shard, dtype=bool)
        for d in range(ndev):
            liv[d * shard:d * shard + int(counts[d])] = True
        return out_d, out_v, jnp.asarray(liv)

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

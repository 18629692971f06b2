"""Mesh runtime: the engine's device mesh as a FIRST-CLASS runtime object.

The paper's target is a v5e-256 pod; the dryrun harness
(``__graft_entry__.dryrun_multichip``) already models the hierarchical
``(dcn, ici)`` mesh shape but the engine itself ran every query on one
chip.  This module promotes the mesh to conf-driven engine state, owned
by :class:`~spark_rapids_tpu.runtime.device_manager.TpuDeviceManager`:

* ``spark.rapids.mesh.enabled`` turns mesh-native execution on;
* ``spark.rapids.mesh.shape`` declares the topology — ``""`` (all local
  devices on one flat axis), ``"8"`` (explicit 1-D size) or ``"2x4"``
  (hierarchical: ``dcn`` x ``ici``, the multi-host slice layout — heavy
  all-to-alls ride the fast inner axis, only merged partials cross dcn);
* ``spark.rapids.mesh.axis`` names the flat row axis (default ``data``).

Reconfiguration bumps a **generation** counter: the executable cache
folds it into its coherency token, so a converted tree checked out
before a mesh change can neither serve nor re-park after it, and the
plan fingerprint folds the mesh **identity token** so cached plans never
cross mesh configs.

Host-transfer discipline (an h2d upload mid-pipeline stalls the
dispatch pipeline): shards land
per-device with ``jax.device_put`` once at the scan, stay device-resident
between exchanges, and the only sanctioned device->host materialization
point in mesh code is :func:`mesh_gather` (the exchange's live-count
fetch routes through it) — enforced statically by the RL-MESH-HOST
lint rule.

The mesh, like the device topology it models, is PROCESS state (one
MeshRuntime, owned by TpuDeviceManager — the same contract as HEALTH
and the circuit breaker). Concurrent sessions whose confs disagree on
the mesh reconfigure it per query: results stay bit-identical either
way (the re-land boundaries guarantee layout independence), but each
effective change bumps the generation — alternating mesh/non-mesh
sessions therefore thrash the executable cache by design (cached
trees never cross mesh configs). Tenants of one QueryService share
one session/conf and never hit this.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from contextvars import ContextVar
from typing import Optional, Tuple

import numpy as np

from spark_rapids_tpu.conf import RapidsConf, bool_conf, int_conf, str_conf
from spark_rapids_tpu.obs.metrics import metric_scope, register_metric
from spark_rapids_tpu.lockorder import ordered_lock

MESH_ENABLED = bool_conf(
    "spark.rapids.mesh.enabled", False,
    "Mesh-native distributed execution: partitioned scans land their "
    "shards directly per-device over the conf-declared device mesh "
    "(spark.rapids.mesh.shape), tables carry a NamedSharding row "
    "descriptor through the plan, and every supported shuffle exchange "
    "lowers to the ICI all-to-all collective (host-file shuffle stays "
    "the fallback, with the demotion reason surfaced in explain()). "
    "Mesh identity folds into the plan fingerprint and the executable "
    "cache's generation, so cached plans never cross mesh configs.",
    commonly_used=True)

MESH_SHAPE = str_conf(
    "spark.rapids.mesh.shape", "",
    "Device-mesh topology for mesh-native execution: '' uses every "
    "local device on one flat axis, 'N' is an explicit 1-D size, and "
    "'DxI' builds the hierarchical (dcn, ici) mesh the multichip "
    "dryrun models — all-to-all shuffles ride the fast inner ici axis. "
    "The device count must not exceed the backend's local device count.")

MESH_AXIS = str_conf(
    "spark.rapids.mesh.axis", "data",
    "Name of the flat row axis of a 1-D mesh (hierarchical 'DxI' "
    "shapes always use ('dcn', 'ici')). Row-sharded tables carry a "
    "PartitionSpec over this axis.")

MESH_MAX_SHARD_RETRIES = int_conf(
    "spark.rapids.mesh.maxShardRetries", 2,
    "Local re-gathers a mesh gather boundary may pay before failing "
    "typed: when the row-count+checksum validation at a MeshReland "
    "(or the ICI exchange's verified live-count fetch) trips, the "
    "boundary re-lands from the still-intact sharded source up to "
    "this many times (shardRetries counter) and then raises "
    "MeshGatherError — which the query-replay machinery re-lands "
    "from the scan cache rather than surfacing wrong results.")

MESH_DEGRADE_MAX_SHRINKS = int_conf(
    "spark.rapids.mesh.degrade.maxShrinks", 2,
    "Mesh reconfigurations onto surviving devices the degradation "
    "ladder (runtime/health.py) may perform after repeated PARTIAL "
    "device losses (one mesh device dead, backend otherwise alive) "
    "before escalating to a full backend reinitialization and, "
    "ultimately, the CPU-only latch. Each shrink excludes the "
    "suspect device, bumps the mesh generation (fencing every "
    "cached tree/dictionary) and is surfaced in QueryService."
    "health(), explain() and the event log.")

MESH_GATHER_VERIFY = bool_conf(
    "spark.rapids.mesh.gather.verify", True,
    "Row-count + checksum validation at mesh gather boundaries (the "
    "TPAK-v2 frame-CRC pattern applied to the MeshReland device-to-"
    "device gather and the ICI exchange's live-count fetch): a "
    "corrupted shard raises a retryable error and re-lands from the "
    "intact sharded source instead of producing silently wrong "
    "results. Costs two tiny digest kernels plus one small host "
    "fetch per physical re-land; disable only for benchmarking.")

# -- the `mesh` metric scope -------------------------------------------------

register_metric("shardsDispatched", "count", "ESSENTIAL",
                "table shards landed per-device by mesh-native scans "
                "(one per device per sharded upload)")
register_metric("iciExchanges", "count", "ESSENTIAL",
                "shuffle exchanges lowered to the ICI all-to-all "
                "collective instead of the host-file shuffle")
register_metric("iciBytes", "bytes", "ESSENTIAL",
                "payload bytes moved through ICI all-to-all collectives "
                "(column data + validity, the exchanged row shards)")
register_metric("meshGatherRows", "count", "MODERATE",
                "elements materialized to host through the sanctioned "
                "mesh_gather point (per-partition live counts of each "
                "ICI exchange — the one host sync a collective pays)")
register_metric("hostShuffleFallbacks", "count", "ESSENTIAL",
                "shuffle exchanges that requested the mesh/ICI path but "
                "demoted to the host-file shuffle (reason surfaced in "
                "explain() and the exchange's describe())")
register_metric("meshHostUploads", "count", "MODERATE",
                "host->device transfers performed inside mesh exchange "
                "dispatch — 0 on a warm mesh query (shards device-"
                "resident, dictionary bytes interned)")
register_metric("meshRelandRows", "count", "MODERATE",
                "row slots re-landed from the sharded layout into the "
                "single-device layout at wide-kernel boundaries "
                "(execs/mesh.py — device-to-device, never host)")
register_metric("meshDictInterns", "count", "MODERATE",
                "string-dictionary byte matrices replicated across the "
                "mesh and interned by dictionary identity (repeated "
                "exchanges over one dictionary pay replication once)")
register_metric("shardRetries", "count", "ESSENTIAL",
                "local re-gathers paid at mesh gather boundaries after "
                "a failed row-count/checksum validation (bounded by "
                "spark.rapids.mesh.maxShardRetries)")
register_metric("gatherChecksFailed", "count", "ESSENTIAL",
                "row-count/checksum validations that tripped at a mesh "
                "gather boundary (MeshReland or the ICI live-count "
                "fetch) — each one is a corrupted shard CAUGHT instead "
                "of served")

register_metric("meshAggBatches", "count", "MODERATE",
                "batches a hash aggregate ran on their resident row "
                "shards (one agg_fast_mesh program each: every chip "
                "aggregates its own rows, only partial groups cross)")
register_metric("meshAggShards", "count", "MODERATE",
                "row shards those batches held (meshAggBatches x the "
                "mesh's devices): the partial tables exchanged")
register_metric("meshExchanges", "count", "ESSENTIAL",
                "input batches a shuffled join's ICI exchange moved to "
                "their partitions' chips, one mesh_exchange program each "
                "(execs/exchange.py TpuShuffleExchangeExec.exchanged)")
register_metric("meshExchangeRows", "count", "ESSENTIAL",
                "live rows those exchanges sent (iciBytes counts the "
                "padded blocks the all-to-all carries)")
register_metric("meshExchangeBytes", "bytes", "ESSENTIAL",
                "live bytes those exchanges sent: live rows x the row's "
                "column words and validity")
register_metric("meshChipJoins", "count", "MODERATE",
                "probe batches a join ran on the chips where they lay "
                "(execs/join.py: a replicated build against row-sharded "
                "probes, or a co-partitioned build and probe)")
register_metric("meshChipAggBatches", "count", "MODERATE",
                "batches a grouped aggregate took on the chips where "
                "they lay: its keys hold the partitioning keys, so no "
                "group spans two chips (execs/aggregate.py)")

MESH_SCOPE = metric_scope("mesh")

#: runtime tunables pushed by PlacementLayer.apply_tuning_confs (execs
#: and the exchange hold no conf handle — the SS.BLOCK pattern)
MAX_SHARD_RETRIES = 2
GATHER_VERIFY = True


def _parse_shape(shape: str, avail: int) -> Tuple[int, ...]:
    """'', 'N' or 'DxI' -> dims tuple. Raises on malformed shapes or
    shapes wider than the available device count."""
    from spark_rapids_tpu.errors import ColumnarProcessingError
    s = shape.strip().lower()
    if not s:
        return (avail,)
    parts = s.replace("*", "x").split("x")
    try:
        dims = tuple(int(p) for p in parts)
    except ValueError:
        raise ColumnarProcessingError(
            f"spark.rapids.mesh.shape must be '', 'N' or 'DxI', got "
            f"{shape!r}")
    if len(dims) > 2 or any(d < 1 for d in dims):
        raise ColumnarProcessingError(
            f"spark.rapids.mesh.shape supports 1-D 'N' or 2-D 'DxI' "
            f"positive dims, got {shape!r}")
    total = 1
    for d in dims:
        total *= d
    if total > avail:
        raise ColumnarProcessingError(
            f"spark.rapids.mesh.shape={shape!r} needs {total} devices "
            f"but only {avail} are available")
    return dims


#: per-ATTEMPT mesh suppression (the "re-land single-device" rung of
#: the degradation ladder): a session replaying a query after repeated
#: mesh device losses sets this around the attempt, and every
#: placement-relevant reader below (enabled / scan_placement /
#: effective_ndev / identity_token / shape_str) reports the mesh OFF
#: for THIS THREAD only — the process mesh, and concurrent workers'
#: queries, are untouched. The demotion reason surfaces through the
#: existing hostShuffleFallbacks / explain() machinery
#: (execs/exchange.ici_demotion_reason reads it).
_SUPPRESS: "ContextVar[Optional[str]]" = ContextVar(
    "mesh_suppress", default=None)


def suppression_reason() -> Optional[str]:
    """Why THIS thread's in-flight attempt must land single-device
    (None when mesh execution is not suppressed)."""
    return _SUPPRESS.get()


@contextmanager
def suppressed_mesh(reason: str):
    """Scope one execution attempt's single-device demotion (the
    degradation ladder's middle rung)."""
    tok = _SUPPRESS.set(reason)
    try:
        yield
    finally:
        _SUPPRESS.reset(tok)


class MeshRuntime:
    """Process-wide mesh state (owned by TpuDeviceManager, configured
    per query by the placement layer). Reconfiguration is coherency-
    relevant: the generation bumps whenever the effective (enabled,
    dims, axis, devices) tuple changes, and both caches consult it.

    The FAULT-DOMAIN half (this PR): ``_excluded_ids`` holds devices
    the degradation ladder evicted after partial losses — configure()
    builds the mesh from the survivors (collapsing to a flat 1-D mesh
    when the declared shape no longer fits), ``shrink_excluding``/
    ``restore`` walk the set, and the exclusion folds into the config
    key so every shrink/restore rebuilds and bumps the generation
    (fencing stale cached trees and dictionaries exactly like a conf
    reconfiguration)."""

    def __init__(self):
        self._lock = ordered_lock("mesh.runtime")
        self._mesh = None
        self._dims: Tuple[int, ...] = ()
        self._axes: Tuple[str, ...] = ()
        self._enabled = False
        self._config_key = None
        self._generation = 0
        #: devices evicted by the degradation ladder (persist across
        #: queries until restore(); folded into the config key)
        self._excluded_ids: frozenset = frozenset()
        #: why the mesh is running below declared strength (None at
        #: full strength) — surfaced in health()/explain()/event log
        self._degraded_reason: Optional[str] = None
        #: the declared shape the degraded mesh fell back from
        self._declared_shape: Optional[str] = None

    # -- configuration -------------------------------------------------------
    def configure(self, conf: RapidsConf) -> None:
        """Apply the session's mesh conf. Cheap when unchanged; a real
        change rebuilds the mesh and bumps the generation. The config
        key folds HEALTH's backend generation: a device-loss reinit
        (runtime/health.py) replaces every jax Device object, and a
        mesh built from the dead backend must be rebuilt on the next
        prepare even though the conf tuple — and the surviving device
        IDS the identity token hashes — are unchanged."""
        from spark_rapids_tpu.errors import ColumnarProcessingError
        from spark_rapids_tpu.runtime.health import HEALTH
        enabled = bool(conf.get_entry(MESH_ENABLED))
        shape = str(conf.get_entry(MESH_SHAPE))
        axis = str(conf.get_entry(MESH_AXIS)).strip() or "data"
        with self._lock:
            excluded = self._excluded_ids
        key = (enabled, shape.strip().lower(), axis, HEALTH.generation(),
               excluded)
        with self._lock:
            if key == self._config_key:
                return
        # build OUTSIDE the lock (jax device discovery can be slow); the
        # publish below re-checks the key so racing configurers converge
        mesh = None
        dims: Tuple[int, ...] = ()
        axes: Tuple[str, ...] = ()
        if enabled:
            import jax
            from jax.sharding import Mesh
            devices = [d for d in jax.devices()
                       if d.id not in excluded]
            try:
                dims = _parse_shape(shape, len(devices))
            except ColumnarProcessingError:
                if not (excluded and devices):
                    raise
                # the declared shape no longer fits the SURVIVORS: the
                # degraded mesh collapses to one flat axis over every
                # remaining device (hierarchical shapes included — a
                # partial pod cannot honor the declared (dcn, ici)
                # factorization, and correctness never depended on it:
                # wide kernels re-land regardless of mesh width)
                dims = (len(devices),)
            axes = ("dcn", "ici") if len(dims) == 2 else (axis,)
            total = 1
            for d in dims:
                total *= d
            mesh = Mesh(np.array(devices[:total]).reshape(dims), axes)
        with self._lock:
            if key == self._config_key:
                return
            self._mesh = mesh
            self._dims = dims
            self._axes = axes
            self._enabled = enabled
            self._config_key = key
            self._declared_shape = shape.strip() or None
            self._generation += 1

    # -- the degradation ladder's mesh half ----------------------------------
    def shrink_excluding(self, device_id: Optional[int],
                         reason: str) -> bool:
        """Evict one device from the mesh fault domain: ``device_id``
        when the failure named it, else the mesh's LAST device (the
        deterministic choice for injected losses). The exclusion folds
        into the config key, so the next configure() rebuilds the mesh
        from the survivors and bumps the generation — every cached
        tree, scan image and replicated dictionary is fenced exactly
        like a conf reconfiguration. Returns False when there is no
        mesh to shrink or only one device remains (the ladder then
        escalates to the whole-backend rungs)."""
        with self._lock:
            if self._mesh is None or not self._enabled:
                return False
            ids = [d.id for d in self._mesh.devices.flat]
            if len(ids) <= 1:
                return False
            victim = device_id if device_id in ids else ids[-1]
            self._excluded_ids = self._excluded_ids | {victim}
            self._degraded_reason = reason
            # force the next configure() to rebuild even under an
            # unchanged conf tuple
            self._config_key = None
            return True

    def exclude_devices(self, device_ids, reason: str) -> bool:
        """Evict a whole device GROUP from the mesh fault domain — the
        cluster layer's host-shrink rung (runtime/cluster.py): a lost
        HOST takes its entire dcn row of devices with it. Same
        contract as shrink_excluding: the exclusion folds into the
        config key, the next configure() rebuilds from the survivors
        (collapsing to a flat axis when the declared hierarchical
        shape no longer fits) and bumps the generation. Returns False
        when the eviction would leave no devices."""
        ids = frozenset(device_ids)
        if not ids:
            return False
        with self._lock:
            if self._mesh is None or not self._enabled:
                return False
            live = [d.id for d in self._mesh.devices.flat
                    if d.id not in ids]
            if not live:
                return False
            self._excluded_ids = self._excluded_ids | ids
            self._degraded_reason = reason
            self._config_key = None
            return True

    def restore(self, reason: str = "") -> bool:
        """Clear every ladder exclusion (the mesh returns to declared
        strength on the next configure()). Returns whether anything
        was excluded. The chaos harness probes this at end of run;
        a device that is genuinely still dead simply re-walks the
        ladder and gets excluded again."""
        with self._lock:
            had = bool(self._excluded_ids)
            self._excluded_ids = frozenset()
            self._degraded_reason = None
            if had:
                self._config_key = None
            return had

    def degraded_reason(self) -> Optional[str]:
        """Why the mesh runs below declared strength (None at full
        strength) — the explain()/health() surfacing hook."""
        with self._lock:
            return self._degraded_reason

    def health_snapshot(self) -> dict:
        """The mesh fault-domain state QueryService.health() reports."""
        with self._lock:
            return self._health_snapshot_locked()

    def _health_snapshot_locked(self) -> dict:
        """Snapshot body for callers already holding ``self._lock``
        (the shared-topology path in runtime/health.py)."""
        shape = ("x".join(str(d) for d in self._dims)
                 if self._enabled and self._mesh is not None else None)
        return {
            "enabled": self._enabled and self._mesh is not None,
            "shape": shape,
            "declaredShape": self._declared_shape,
            "excludedDeviceIds": sorted(self._excluded_ids),
            "degradedReason": self._degraded_reason,
            "generation": self._generation,
        }

    # -- state ---------------------------------------------------------------
    @property
    def enabled(self) -> bool:
        if _SUPPRESS.get() is not None:
            return False  # this attempt lands single-device
        with self._lock:
            return self._enabled and self._mesh is not None

    def mesh(self):
        with self._lock:
            return self._mesh

    @property
    def ndev(self) -> int:
        with self._lock:
            if self._mesh is None:
                return 0
            n = 1
            for d in self._dims:
                n *= d
            return n

    def effective_ndev(self) -> Optional[int]:
        """Mesh device count read under ONE lock hold — None when mesh-
        native execution is off. The enabled/ndev pair must be a single
        snapshot: two separate locked reads racing a concurrent
        reconfiguration can observe enabled=True then ndev=0 (the
        scan_placement atomicity argument, applied to the exchange's
        demotion check)."""
        if _SUPPRESS.get() is not None:
            return None
        with self._lock:
            if not self._enabled or self._mesh is None:
                return None
            n = 1
            for d in self._dims:
                n *= d
            return n

    def row_axes(self) -> Tuple[str, ...]:
        """The axes a row-sharded table partitions over — the flat axis
        of a 1-D mesh, or both axes of the hierarchical (dcn, ici) one
        (rows stripe the whole pod; collectives still address each axis
        independently)."""
        with self._lock:
            return self._axes

    def shape_str(self) -> Optional[str]:
        """Human/event-log mesh shape ('8' or '2x4'); None when off."""
        if _SUPPRESS.get() is not None:
            return None
        with self._lock:
            if not self._enabled or self._mesh is None:
                return None
            return "x".join(str(d) for d in self._dims)

    def generation(self) -> int:
        """Coherency counter: bumps on every effective reconfiguration.
        Folded into the executable cache's generation token, so a tree
        checked out under one mesh can neither serve nor re-park under
        another."""
        with self._lock:
            return self._generation

    def identity_token(self) -> str:
        """Stable token of the CURRENT mesh identity (enabled, dims,
        axes, device ids) — folded into the plan fingerprint so cached
        plans never cross mesh configs. A ladder-suppressed attempt
        gets its own token: its single-device tree must not collide
        with mesh-native variants of the same template."""
        if _SUPPRESS.get() is not None:
            return "mesh:suppressed"
        with self._lock:
            if not self._enabled or self._mesh is None:
                return "mesh:off"
            return mesh_token(self._mesh)

    # -- sharding ------------------------------------------------------------
    def row_sharding(self):
        """NamedSharding partitioning the row axis across the mesh —
        THE plan-carried table sharding descriptor."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        with self._lock:
            if self._mesh is None:
                return None
            spec = P(self._axes if len(self._axes) > 1 else self._axes[0])
            return NamedSharding(self._mesh, spec)

    def scan_placement(self):
        """(row sharding, generation) read under ONE lock hold — the
        scan device-cache pairs the sharding it lands under with the
        token it caches under, and two separate locked reads could pair
        an old mesh's sharding with a post-reconfiguration token,
        serving that stale placement on every later cache hit.
        ``(None, None)`` when mesh-native execution is off."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        if _SUPPRESS.get() is not None:
            return None, None
        with self._lock:
            if not self._enabled or self._mesh is None:
                return None, None
            spec = P(self._axes if len(self._axes) > 1 else self._axes[0])
            return NamedSharding(self._mesh, spec), self._generation

    def replicated_sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        with self._lock:
            if self._mesh is None:
                return None
            return NamedSharding(self._mesh, P())

    def exchange_mesh(self, nparts: int):
        """(mesh, axis-or-axes) for an nparts-way all-to-all. The full
        runtime mesh when nparts covers it (a 2-D mesh exchanges over
        BOTH axes — partition id = flat device index, the all-to-all
        rides ici within each dcn group); a leading 1-D submesh when the
        exchange is narrower than the pod."""
        import jax
        from jax.sharding import Mesh
        with self._lock:
            mesh = self._mesh
            dims, axes = self._dims, self._axes
        if mesh is not None:
            total = 1
            for d in dims:
                total *= d
            if nparts == total:
                return mesh, (axes if len(axes) > 1 else axes[0])
            if nparts < total:
                flat = list(mesh.devices.flat)[:nparts]
                return Mesh(np.array(flat), ("data",)), "data"
        return Mesh(np.array(jax.devices()[:nparts]), ("data",)), "data"

def mesh_token(mesh) -> str:
    """The identity of one jax Mesh as a string: dims, axes, device
    ids. What MeshRuntime.identity_token reports of the current mesh,
    and what a program compiled against a batch's own mesh keys on."""
    ids = ",".join(str(d.id) for d in mesh.devices.flat)
    return (f"mesh:{'x'.join(map(str, mesh.devices.shape))}/"
            f"{'+'.join(mesh.axis_names)}/{ids}")


#: THE process-wide mesh runtime (device topology is process state, like
#: the device manager that owns it)
MESH = MeshRuntime()


def count_mesh_upload(n: int = 1) -> None:
    """Record ``n`` host->device transfers on the mesh dispatch path —
    the warm-path contract is that this stays 0 between exchanges."""
    if n > 0:
        MESH_SCOPE.add("meshHostUploads", n)


def shard_put(arr, sharding):
    """Land one array onto the mesh under ``sharding`` — per-shard
    device transfers for host arrays (no single-device concat), a
    device-side reshard for arrays already resident. Host uploads are
    counted (the warm path must not pay any). THE shard-landing fault
    point: crash exercises the query-replay path, device_lost the
    partial-loss degradation ladder (runtime/health.py)."""
    import jax

    from spark_rapids_tpu.runtime.faults import fault_point
    fault_point("mesh.shard.put")
    if not isinstance(arr, jax.Array):
        count_mesh_upload(1)
    return jax.device_put(arr, sharding)


def ensure_cpu_test_mesh(n_devices: int) -> int:
    """CPU test mesh: force an ``n_devices``-wide virtual host-platform
    backend BEFORE the JAX backend initializes, so one process on any
    machine models an N-chip pod on the CPU. The shared bootstrap of
    the multichip dryrun (``__graft_entry__.dryrun_multichip``) and the
    mesh harness (``scale_test --mesh``): bumps
    ``--xla_force_host_platform_device_count`` in ``XLA_FLAGS`` (never
    shrinking an existing setting) and pins the cpu platform. This is
    never the way onto real chips — those run through the session conf
    (``spark.rapids.mesh.enabled``), as ``chip_smoke.py`` does. Returns
    the live device count; callers decide how to fail when it is short
    (the flag cannot take effect if the backend initialized before this
    ran). Importing this module is deliberately backend-init-safe, so
    callers may import first and bootstrap after."""
    import os
    import re
    want = max(n_devices, 8)
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={want}")
    elif int(m.group(1)) < want:
        os.environ["XLA_FLAGS"] = flags.replace(
            m.group(0), f"--xla_force_host_platform_device_count={want}")
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    # the config update holds even when jax was imported (and read the
    # environment) before this ran
    jax.config.update("jax_platforms", "cpu")
    return len(jax.devices())


def mesh_gather(value, rows: Optional[int] = None):
    """THE sanctioned mesh->host materialization point (RL-MESH-HOST):
    fetches a device value to host and counts the gathered elements.
    Every ICI exchange routes its per-partition live-count fetch
    through here; any future mesh-code host gather must too (the lint
    rule flags direct fetches). ``rows`` overrides the counted element
    number for fetches that carry validation overhead alongside the
    payload (a checksummed counts fetch counts its counts, not its
    digest word; a pure digest-pair compare counts 0) so
    meshGatherRows keeps meaning 'elements gathered', comparable
    across artifact rounds."""
    from spark_rapids_tpu.dispatch import host_fetch
    arr = np.asarray(host_fetch(value))
    if rows is None:
        rows = int(arr.shape[0]) if arr.ndim else 1
    if rows:
        MESH_SCOPE.add("meshGatherRows", rows)
    return arr


def wordsum_u32(a):
    """Order-independent uint32 word-sum digest of one device array —
    THE checksum both sides of a verified mesh gather compute (the
    TPAK-v2 frame CRC lifted to device buffers): bitcast every element
    to 32-bit words and wrap-sum them. Integer addition is associative
    and commutative, so a GSPMD-partitioned sum over mesh shards
    equals the single-device sum bit for bit — the digest is layout-
    independent by construction. Runs eagerly/inside jit; host code
    recomputes the same value with numpy views."""
    import jax
    import jax.numpy as jnp
    if a.dtype == jnp.bool_:
        return jnp.sum(a.astype(jnp.uint32), dtype=jnp.uint32)
    if a.dtype in (jnp.int8, jnp.int16):
        a = a.astype(jnp.int32)
    if a.dtype.itemsize == 8 and jax.default_backend() != "cpu":
        # the tpu backend's x64 rewrite implements no 64-bit
        # bitcast-convert: digest the two 32-bit limbs instead (both
        # sides of a compare evaluate this same function)
        from spark_rapids_tpu.ops.limbs import (
            split_f64_hi_lo,
            split_i64_hi_lo,
        )
        hi, lo = (split_f64_hi_lo(a) if a.dtype == jnp.float64
                  else split_i64_hi_lo(a))
        return wordsum_u32(hi) + wordsum_u32(lo)
    return jnp.sum(jax.lax.bitcast_convert_type(a, jnp.uint32),
                   dtype=jnp.uint32)

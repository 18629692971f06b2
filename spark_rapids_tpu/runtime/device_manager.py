"""Device acquisition & memory setup (reference: GpuDeviceManager.scala —
picks the GPU, initializes the RMM pool, pinned pool, off-heap limits;
SURVEY.md §2.5).

TPU analog: discover devices/topology through JAX/PJRT, record HBM budget
from the conf fraction, and expose the live-arrays accounting XLA gives us.
XLA's allocator already pools HBM (BFC) — the engine's job is budget
tracking + spill/retry on top (runtime/catalog.py, runtime/retry.py)."""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import List, Optional

import jax

from spark_rapids_tpu.conf import (
    CONCURRENT_TPU_TASKS,
    HBM_POOL_FRACTION,
    HBM_RESERVE_BYTES,
    RapidsConf,
)
from spark_rapids_tpu.lockorder import ordered_lock

#: what a CPU device "holds": the CPU backend reports no memory limit,
#: and the tests and the CPU test mesh budget against one v5e chip's
#: 16 GiB. Never used for an accelerator.
_CPU_STANDIN_HBM_BYTES = 16 << 30


def reported_hbm_bytes(dev) -> int:
    """The memory limit ``dev`` itself reports. Only the CPU backend,
    which reports none, gets the stand-in; an accelerator without a
    ``bytes_limit`` is an error, never a guess."""
    stats = dev.memory_stats()
    if stats and "bytes_limit" in stats:
        return int(stats["bytes_limit"])
    if dev.platform != "cpu":
        from spark_rapids_tpu.errors import ColumnarProcessingError
        raise ColumnarProcessingError(
            f"{dev.platform} device {dev} reports no memory limit "
            f"(memory_stats() = {stats!r}); refusing to assume one")
    return _CPU_STANDIN_HBM_BYTES


def hbm_budget_bytes(conf: RapidsConf, dev) -> int:
    """The engine's share of ``dev``'s memory: the reported limit with
    the pool fraction and the reserve applied."""
    frac = conf.get_entry(HBM_POOL_FRACTION)
    reserve = conf.get_entry(HBM_RESERVE_BYTES)
    return max(int(reported_hbm_bytes(dev) * frac) - reserve, 256 << 20)


@dataclass
class DeviceInfo:
    device: object
    platform: str
    hbm_limit_bytes: int
    #: PJRT topology facts (GpuDeviceManager resource-discovery analog)
    device_ordinal: int = 0
    process_index: int = 0
    num_processes: int = 1
    local_device_count: int = 1
    global_device_count: int = 1
    coords: Optional[tuple] = None
    core_on_chip: Optional[int] = None


class TpuDeviceManager:
    """Singleton-ish per-process device state."""

    _instance: Optional["TpuDeviceManager"] = None
    _instance_lock = ordered_lock("device.manager.instance")

    def __init__(self, conf: RapidsConf):
        self.conf = conf
        self.devices: List[object] = []
        self.info: Optional[DeviceInfo] = None
        self.initialized = False

    def _select_device(self, local: List[object]) -> int:
        """Device selection (reference: GpuDeviceManager.scala:243-251 —
        explicit resource address, else round-robin by executor id).
        TPU analog: explicit conf ordinal, else round-robin by process
        index across multi-process launches."""
        from spark_rapids_tpu.conf import DEVICE_ORDINAL
        want = self.conf.get_entry(DEVICE_ORDINAL)
        if want >= 0:
            if want >= len(local):
                from spark_rapids_tpu.errors import ColumnarProcessingError
                raise ColumnarProcessingError(
                    f"spark.rapids.tpu.deviceOrdinal={want} but only "
                    f"{len(local)} local devices exist")
            return want
        try:
            pi = jax.process_index()
        except Exception:
            pi = 0
        return pi % len(local) if len(local) else 0

    def initialize(self):
        if self.initialized:
            return
        # the backend is being initialized anyway; auto-detected TPU
        # hosts (unset JAX_PLATFORMS) pick up the persistent compile
        # cache here rather than silently running uncached
        import spark_rapids_tpu
        spark_rapids_tpu.ensure_compile_cache()
        self.devices = list(jax.devices())
        local = list(jax.local_devices())
        ordinal = self._select_device(local)
        dev = local[ordinal]
        limit = hbm_budget_bytes(self.conf, dev)
        try:
            nproc = jax.process_count()
            pidx = jax.process_index()
        except Exception:
            nproc, pidx = 1, 0
        self.info = DeviceInfo(
            device=dev, platform=dev.platform, hbm_limit_bytes=limit,
            device_ordinal=ordinal, process_index=pidx,
            num_processes=nproc, local_device_count=len(local),
            global_device_count=len(self.devices),
            coords=getattr(dev, "coords", None),
            core_on_chip=getattr(dev, "core_on_chip", None))
        from spark_rapids_tpu.conf import (
            HOST_MEMORY_LIMIT,
            HOST_SPILL_STORAGE_SIZE,
            PINNED_POOL_SIZE,
        )
        from spark_rapids_tpu.runtime.host_alloc import (
            HostMemoryArbiter,
            PinnedMemoryPool,
        )
        from spark_rapids_tpu.runtime.spill import BufferCatalog
        BufferCatalog.get().host_limit_bytes = \
            self.conf.get_entry(HOST_SPILL_STORAGE_SIZE)
        HostMemoryArbiter.reset(self.conf.get_entry(HOST_MEMORY_LIMIT))
        PinnedMemoryPool.initialize(self.conf.get_entry(PINNED_POOL_SIZE))
        with TpuDeviceManager._instance_lock:
            TpuDeviceManager._instance = self
        self.initialized = True

    @classmethod
    def current(cls) -> Optional["TpuDeviceManager"]:
        return cls._instance

    @property
    def mesh_runtime(self):
        """The process-wide mesh runtime (parallel/mesh.py) — device
        topology is process state like the manager itself; the
        placement layer configures it from the session conf per query."""
        from spark_rapids_tpu.parallel.mesh import MESH
        return MESH

    def bytes_in_use(self) -> int:
        try:
            stats = self.info.device.memory_stats()
            return int(stats.get("bytes_in_use", 0))
        except Exception:
            return 0

    @property
    def concurrent_tasks(self) -> int:
        return self.conf.get_entry(CONCURRENT_TPU_TASKS)

    def topology(self) -> dict:
        """Discovery summary (logged at session init; the reference logs
        the chosen GPU + memory configuration the same way)."""
        i = self.info
        from spark_rapids_tpu.parallel.mesh import MESH
        return {
            "mesh_shape": MESH.shape_str(),
            "platform": i.platform,
            "device_ordinal": i.device_ordinal,
            "local_devices": i.local_device_count,
            "global_devices": i.global_device_count,
            "process_index": i.process_index,
            "num_processes": i.num_processes,
            "coords": i.coords,
            "core_on_chip": i.core_on_chip,
            "hbm_limit_bytes": i.hbm_limit_bytes,
        }

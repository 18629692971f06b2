"""Typed configuration registry (reference: RapidsConf.scala, 3,270 LoC,
236 spark.rapids.* keys -- SURVEY.md §2.10/§5).

Same design: a global registry of typed ConfEntry objects with defaults and
doc strings, a RapidsConf view over a plain dict, per-operator kill switches
registered dynamically by the rules layer, and markdown doc generation.
Keys keep the spark.rapids.* prefix so reference users can carry configs over.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional

_REGISTRY: Dict[str, "ConfEntry"] = {}


@dataclass(frozen=True)
class ConfEntry:
    key: str
    default: Any
    doc: str
    conv: Callable[[str], Any]
    startup_only: bool = False
    commonly_used: bool = False
    internal: bool = False

    def get(self, conf: "RapidsConf") -> Any:
        return conf.get(self.key)


def _to_bool(v) -> bool:
    if isinstance(v, bool):
        return v
    return str(v).strip().lower() in ("true", "1", "yes", "on")


def _conf(key, default, doc, conv, **kw) -> ConfEntry:
    e = ConfEntry(key=key, default=default, doc=doc, conv=conv, **kw)
    if key in _REGISTRY:
        raise ValueError(f"duplicate conf key {key}")
    _REGISTRY[key] = e
    return e


def bool_conf(key, default, doc, **kw):
    return _conf(key, default, doc, _to_bool, **kw)


def int_conf(key, default, doc, **kw):
    return _conf(key, default, doc, int, **kw)


def float_conf(key, default, doc, **kw):
    return _conf(key, default, doc, float, **kw)


def str_conf(key, default, doc, **kw):
    return _conf(key, default, doc, str, **kw)


def register_op_kill_switch(kind: str, name: str, default_enabled: bool, doc: str) -> ConfEntry:
    """Per-operator kill switch, auto-generated from rule registration like
    the reference's spark.rapids.sql.expression.* / sql.exec.* keys."""
    key = f"spark.rapids.sql.{kind}.{name}"
    if key in _REGISTRY:
        return _REGISTRY[key]
    return bool_conf(key, default_enabled, doc)


# ---------------------------------------------------------------------------
# Core entries (the ~30-key starter set from SURVEY.md §7 phase 2, growing
# toward the reference's full 236).
# ---------------------------------------------------------------------------

SQL_ENABLED = bool_conf(
    "spark.rapids.sql.enabled", True,
    "Master enable for plan rewriting onto the TPU.", commonly_used=True)

SQL_MODE = str_conf(
    "spark.rapids.sql.mode", "executeongpu",
    "executeongpu: rewrite and run on TPU; explainonly: tag the plan and "
    "report what would run on TPU without converting.")

EXPLAIN = str_conf(
    "spark.rapids.sql.explain", "NONE",
    "NONE, NOT_ON_GPU (log reasons for fallbacks) or ALL.", commonly_used=True)

BATCH_SIZE_BYTES = int_conf(
    "spark.rapids.sql.batchSizeBytes", 1 << 30,
    "Target device batch size in bytes for coalescing.", commonly_used=True)

MAX_READER_BATCH_SIZE_ROWS = int_conf(
    "spark.rapids.sql.reader.batchSizeRows", 1 << 20,
    "Soft cap on rows per batch produced by scans.")

CONCURRENT_TPU_TASKS = int_conf(
    "spark.rapids.sql.concurrentGpuTasks", 2,
    "Number of tasks that may hold the device semaphore concurrently "
    "(reference: GpuSemaphore).", commonly_used=True)

HBM_POOL_FRACTION = float_conf(
    "spark.rapids.memory.gpu.allocFraction", 0.9,
    "Fraction of visible HBM the engine may use.", startup_only=True)

HBM_RESERVE_BYTES = int_conf(
    "spark.rapids.memory.gpu.reserve", 640 << 20,
    "HBM held back from the pool for XLA scratch/fragmentation.",
    startup_only=True)

HOST_SPILL_STORAGE_SIZE = int_conf(
    "spark.rapids.memory.host.spillStorageSize", 1 << 31,
    "Bytes of host memory used for spilled device buffers before disk.")

PINNED_POOL_SIZE = int_conf(
    "spark.rapids.memory.pinnedPool.size", 0,
    "Host staging pool for H2D/D2H transfers (0 = unpooled).",
    startup_only=True)

HOST_MEMORY_LIMIT = int_conf(
    "spark.rapids.memory.host.limit", 4 << 30,
    "Host-memory arbiter budget for engine host buffers (shuffle "
    "serialization, cached blocks). Exhaustion spills the host tier to "
    "disk, then blocks, then raises CpuRetryOOM (HostAlloc analog).",
    startup_only=True)

RETRY_OOM_MAX_RETRIES = int_conf(
    "spark.rapids.memory.gpu.oomMaxRetries", 2,
    "Synchronous-spill retries before escalating to split-and-retry.")

SPECULATIVE_SIZING = bool_conf(
    "spark.rapids.tpu.speculativeSizing.enabled", True,
    "Size data-dependent outputs (join gather maps, direct-address join "
    "tables) speculatively with device-resident validation flags instead "
    "of a ~0.1s host sync per operator; a failed speculation replays the "
    "query on the exact path (runtime/speculation.py).", commonly_used=True)

COLUMN_PRUNING = bool_conf(
    "spark.rapids.tpu.sql.columnPruning.enabled", True,
    "Prune unreferenced columns below joins/aggregates and out of file "
    "scans (Spark's ColumnPruning logical rule, which the reference "
    "inherits from Spark; this engine owns its logical plans so it "
    "applies the rule itself — overrides/pruning.py). Every pruned column "
    "avoids per-operator gathers/scatters of emulated 64-bit halves on "
    "TPU, and a file scan decodes and uploads only the columns left.")

MASKED_BATCHES = bool_conf(
    "spark.rapids.tpu.maskedBatches.enabled", True,
    "Defer row compaction: filters and dense-key joins emit batches whose "
    "liveness is a device mask instead of scatter-compacting every column "
    "(the most expensive per-row op on TPU); mask-aware downstream execs "
    "consume the mask and the scatter is paid only at collect/spill/"
    "split boundaries (columnar/table.py DeviceTable.live).",
    commonly_used=True)

SEQUENCE_ELEMENT_MULT = int_conf(
    "spark.rapids.tpu.sequence.elementMultiplier", 4,
    "sequence() element buffer capacity as a multiple of the row "
    "capacity; outputs beyond it raise with this knob's name "
    "(static-shape sizing, ops/collections.Sequence).")

COLLECT_EMBED_ROWS_CAP = int_conf(
    "spark.rapids.tpu.collect.embedRowsCap", 1 << 16,
    "Collects of tables up to this capacity fetch the padded bucket with "
    "the row count embedded in the packed buffer instead of paying a "
    "separate ~0.1s row-count sync (columnar/table.py to_host).")

COLLECT_EMBED_MAX_BYTES = int_conf(
    "spark.rapids.tpu.collect.embedMaxBytes", 4 << 20,
    "...but only while the padded transfer stays under this many bytes "
    "(wide schemas fall back to the row-count sync).")

WINDOW_ROWS_FRAME_MAX_BOUND = int_conf(
    "spark.rapids.sql.window.rowsFrameMaxBound", 1 << 16,
    "Rows-frame window bounds beyond this magnitude tag CPU fallback "
    "(sparse-table/unroll widths are bounded by the frame's endpoints).")

NLJ_PAIR_BUDGET = int_conf(
    "spark.rapids.sql.nestedLoopJoin.pairBudget", 1 << 20,
    "Max probe-tile x build-row pairs materialized per nested-loop join "
    "tile — bounds HBM for conditioned joins regardless of input sizes.")

JOIN_MAX_SUBPARTITIONS = int_conf(
    "spark.rapids.sql.join.maxSubPartitions", 64,
    "Upper bound on hash sub-partitions when a join's build side "
    "exceeds the sub-partitioning threshold.")

SEGSUM_BLOCK_ROWS = int_conf(
    "spark.rapids.tpu.segsum.blockRows", 1024,
    "Rows per f32 partial-sum block in the split-f64 segmented sum "
    "(bounds f32 accumulation error; ops/segsum.BLOCK).")

SEGSUM_MAX_PARTIALS = int_conf(
    "spark.rapids.tpu.segsum.maxPartials", 1 << 22,
    "Blocked split-f64 segment sums cap (segments x blocks) at this "
    "many partials; beyond it the guarded unblocked path runs.")

SEGSUM_MATMUL_MAX_SEGMENTS = int_conf(
    "spark.rapids.tpu.segsum.matmulMaxSegments", 32,
    "One-hot MXU matmul partials run for segment counts up to this: "
    "the split-f64 sums' f32 partials and the fast aggregate's "
    "per-group row counts (the materialized one-hot costs "
    "capacity*segments*4 bytes of HBM traffic).")

SPLIT_SUM_MAX_ABS = float_conf(
    "spark.rapids.tpu.sum.splitMaxAbs", 1e34,
    "Split-f64 sums reroute to the exact path when any |value| exceeds "
    "this (an f32 block partial could overflow).")

WINDOW_STREAM_TARGET_ROWS = int_conf(
    "spark.rapids.sql.window.streamTargetRows", 0,
    "Target rows per streamed range batch in out-of-core window "
    "evaluation (0 = the largest input run's size).")

BLOOM_DEFAULT_NUM_BITS = int_conf(
    "spark.rapids.tpu.bloomFilter.numBits", 1 << 20,
    "Default bit-array size for build_bloom_filter.")

BLOOM_DEFAULT_NUM_HASHES = int_conf(
    "spark.rapids.tpu.bloomFilter.numHashes", 3,
    "Default hash-function count for build_bloom_filter.")

HEARTBEAT_INTERVAL_S = float_conf(
    "spark.rapids.shuffle.heartbeat.intervalSeconds", 5.0,
    "Executor -> driver shuffle heartbeat period (peer discovery).")

SORT_OOC_THRESHOLD = int_conf(
    "spark.rapids.sql.sort.outOfCoreThresholdBytes", 1 << 30,
    "Multi-batch sorts whose input exceeds this many device bytes merge "
    "OUT OF CORE: each batch sorts on device and demotes to a host run, "
    "sampled key bounds split the key space into ranges, and each range "
    "re-loads + sorts independently — peak HBM is one output range "
    "(GpuSortExec spilled-run merge analog).")

ANSI_ENABLED = bool_conf(
    "spark.sql.ansi.enabled", False,
    "ANSI SQL mode: integral overflow, divide by zero, invalid numeric "
    "casts and out-of-bounds array indexes raise AnsiViolation instead "
    "of wrapping / returning null (reference: GpuCast ansi variants, "
    "CheckOverflow shim rules). Device kernels accumulate a violation "
    "flag per expression site; it rides the collect's packed fetch, so "
    "ANSI checking adds no extra device round trips.")

DPP_ENABLED = bool_conf(
    "spark.rapids.sql.dpp.enabled", True,
    "Dynamic partition pruning: when a broadcast join's probe side scans "
    "a Hive-partitioned source keyed on a partition column, prune the "
    "scan's file list to the build side's distinct key values before "
    "reading (GpuFileSourceScanExec DynamicPruningExpression analog).")

JOIN_DIRECT_TABLE_MULT = int_conf(
    "spark.rapids.tpu.join.directTableMultiplier", 4,
    "Direct-address join body: a build side whose single integer key is "
    "unique takes it when the key range, read once the build side is "
    "ready, fits this multiple of the build side's capacity or 2^26 "
    "slots, whichever is larger; a wider range takes the sorted body.")

SHUFFLE_LOCAL_DEVICE_SPLIT = bool_conf(
    "spark.rapids.shuffle.localDeviceSplit.enabled", True,
    "Single-process repartitions split ON DEVICE into per-partition "
    "masked batches (zero host round trips, zero compaction scatters) "
    "instead of serializing through the shuffle manager. Applies only to "
    "MULTITHREADED mode; ICI and P2P always run their real transports. "
    "Disable to force the file-backed shuffle (manager testing).")

SHUFFLE_MANAGER_MODE = str_conf(
    "spark.rapids.shuffle.mode", "MULTITHREADED",
    "MULTITHREADED (threaded host serialization over local shuffle files), "
    "ICI (collective all-to-all over the device mesh when all partitions "
    "live on one slice), or P2P (cached map output served to peers through "
    "the bounce-buffer transport — the UCX-mode analog).")

P2P_TRANSPORT = str_conf(
    "spark.rapids.shuffle.p2p.transport", "inprocess",
    "P2P shuffle wire: tcp (length-prefixed frames over sockets, the DCN "
    "path) or inprocess (direct calls; single-process and tests).")

P2P_BOUNCE_BUFFER_SIZE = int_conf(
    "spark.rapids.shuffle.p2p.bounceBufferSize", 4 << 20,
    "Bytes per bounce buffer; also the transfer window size.")

P2P_BOUNCE_BUFFERS = int_conf(
    "spark.rapids.shuffle.p2p.bounceBuffers", 4,
    "Bounce buffers per pool (bounds in-flight transfer memory).")

P2P_CACHE_LIMIT = int_conf(
    "spark.rapids.shuffle.p2p.cacheLimitBytes", 1 << 30,
    "Host bytes of cached shuffle blocks before spilling to disk.")

SHUFFLE_MT_WRITER_THREADS = int_conf(
    "spark.rapids.shuffle.multiThreaded.writer.threads", 8,
    "Thread pool size for multithreaded shuffle writes.")

SHUFFLE_MT_READER_THREADS = int_conf(
    "spark.rapids.shuffle.multiThreaded.reader.threads", 8,
    "Thread pool size for multithreaded shuffle reads.")

SHUFFLE_COMPRESSION_CODEC = str_conf(
    "spark.rapids.shuffle.compression.codec", "none",
    "Codec for serialized shuffle batches: none, lz4 (native C++ block "
    "codec), zstd, or zlib. lz4/zstd degrade to zlib when their backend "
    "is unavailable; the resolved codec is what gets recorded on disk.")

# -- streaming ingestion + materialized views (streaming/) -------------------

STREAMING_POOL = str_conf(
    "spark.rapids.streaming.pool", "default",
    "Scheduling pool StreamingQuery micro-batches submit to on the "
    "query service (must name a configured service pool); streams are "
    "recurring tenants, so their pool/tenant SLOs roll up on /slo like "
    "any other traffic.")

STREAMING_TRIGGER_INTERVAL_MS = int_conf(
    "spark.rapids.streaming.triggerIntervalMs", 50,
    "Micro-batch trigger cadence: how long a running stream sleeps "
    "between an empty poll and the next source check.")

STREAMING_MAX_FILES_PER_TRIGGER = int_conf(
    "spark.rapids.streaming.maxFilesPerTrigger", 16,
    "File-watch source batch bound: at most this many newly-seen files "
    "enter one micro-batch; the rest wait for the next trigger.")

STREAMING_MV_INCREMENTAL = bool_conf(
    "spark.rapids.streaming.mv.incremental.enabled", True,
    "Maintain materialized views from the CDF delta (append for "
    "projections/filters, touched-group re-aggregation for "
    "aggregates). Off: every refresh is a full recompute of the "
    "registered plan.")

STREAMING_MV_MAX_TOUCHED_GROUPS = int_conf(
    "spark.rapids.streaming.mv.maxTouchedGroups", 64,
    "Re-aggregation bound: when one refresh's CDF delta touches more "
    "distinct group keys than this, the refresh falls back to a full "
    "recompute instead of building an oversized touched-key filter.")

PARQUET_READER_TYPE = str_conf(
    "spark.rapids.sql.format.parquet.reader.type", "AUTO",
    "PERFILE, COALESCING, MULTITHREADED or AUTO (reference: "
    "GpuParquetScan reader modes).")

MULTITHREADED_READ_NUM_THREADS = int_conf(
    "spark.rapids.sql.multiThreadedRead.numThreads", 20,
    "Thread pool for multithreaded file prefetch.")

READER_COALESCE_TARGET_BYTES = int_conf(
    "spark.rapids.sql.reader.coalescing.targetBytes", 256 << 20,
    "Target bytes when stitching small files/row-groups into one decode.")

HAS_NANS = bool_conf(
    "spark.rapids.sql.hasNans", False,
    "Assume float data may contain NaNs (affects some agg/join support).")

IMPROVED_FLOAT_OPS = bool_conf(
    "spark.rapids.sql.variableFloatAgg.enabled", True,
    "Allow float aggregations whose result may differ in ULPs from CPU "
    "due to parallel reduction order.")

ENABLE_CAST_STRING_TO_TIMESTAMP = bool_conf(
    "spark.rapids.sql.castStringToTimestamp.enabled", False,
    "String->timestamp cast has corner cases; off by default like the "
    "reference.")

DECIMAL_ENABLED = bool_conf(
    "spark.rapids.sql.decimalType.enabled", True,
    "Enable decimal processing on device (int64 unscaled, p<=18).")

TEST_INJECT_RETRY_OOM = str_conf(
    "spark.rapids.sql.test.injectRetryOOM", "",
    "Test-only: 'retry[:N]' or 'split[:N]' to force OOM exceptions on the "
    "Nth device allocation (reference: RmmSpark.forceRetryOOM).",
    internal=True)

TEST_FAULTS = str_conf(
    "spark.rapids.test.faults", "",
    "Test-only fault injection: semicolon-separated "
    "'<point>[@<op>]:<kind>:<prob-or-count>[:<seed>]' entries armed on "
    "the process-wide fault registry at execute() (runtime/faults.py; "
    "the chaos-harness generalization of RmmSpark.forceRetryOOM). "
    "Kinds: oom, crash, fetch, disconnect, corrupt, slow. A value in "
    "(0,1) is a seeded per-hit probability; an integer N fires the "
    "first N hits.", internal=True)

SHUFFLE_FETCH_MAX_RETRIES = int_conf(
    "spark.rapids.shuffle.fetch.maxRetries", 3,
    "Retries per shuffle block fetch before the map output is declared "
    "lost and recomputed from the retained plan lineage.")

SHUFFLE_FETCH_RETRY_WAIT_MS = int_conf(
    "spark.rapids.shuffle.fetch.retryWaitMs", 50,
    "Initial backoff between shuffle fetch retries, in milliseconds.")

SHUFFLE_FETCH_BACKOFF_MULT = float_conf(
    "spark.rapids.shuffle.fetch.backoffMultiplier", 2.0,
    "Multiplier applied to the fetch retry wait after each failed "
    "attempt (exponential backoff).")

SHUFFLE_CONNECT_TIMEOUT_MS = int_conf(
    "spark.rapids.shuffle.fetch.connectTimeoutMs", 30000,
    "Timeout for establishing a transport connection to a shuffle peer; "
    "a timed-out connect counts as a retryable fetch failure against "
    "that peer.")

SHUFFLE_BOUNCE_ACQUIRE_TIMEOUT_MS = int_conf(
    "spark.rapids.shuffle.p2p.bounceAcquireTimeoutMs", 60000,
    "Default timeout waiting for a free bounce buffer; expiry raises a "
    "retryable ShuffleFetchError instead of blocking forever when a "
    "peer dies holding buffers.")

RUNTIME_FALLBACK_ENABLED = bool_conf(
    "spark.rapids.sql.runtimeFallback.enabled", True,
    "Per-operator circuit breaker: after repeated non-OOM device "
    "failures of the same operator the op is runtime-demoted to the CPU "
    "fallback path for the rest of the ENGINE PROCESS — every session "
    "sharing the device sees the demotion, like the speculation "
    "blocklist, since the broken kernel is process-wide state (recorded "
    "as a fallback reason in explain/planVerify). Disable to forbid "
    "demotion — crashes then surface to the caller.")

RUNTIME_FALLBACK_MAX_FAILURES = int_conf(
    "spark.rapids.sql.runtimeFallback.maxFailures", 2,
    "Non-OOM device failures of the same operator before the circuit "
    "breaker demotes it to CPU.")

METRICS_LEVEL = str_conf(
    "spark.rapids.sql.metrics.level", "MODERATE",
    "ESSENTIAL, MODERATE or DEBUG metric collection.")

LORE_DUMP_IDS = str_conf(
    "spark.rapids.sql.lore.idsToDump", "",
    "Comma-separated LORE operator ids (session.last_metrics shows each "
    "operator's id) whose input batches + pickled operator dump to "
    "lore.dumpPath during execution; spark_rapids_tpu.lore.replay() "
    "re-executes one dumped operator, including in a fresh process.")

LORE_DUMP_PATH = str_conf(
    "spark.rapids.sql.lore.dumpPath", "",
    "Directory for LORE dumps (one lore-<id> subdirectory per operator).")

CPU_ORACLE_STRICT = bool_conf(
    "spark.rapids.sql.test.strictOracle", True,
    "Test-only: compare device results bit-for-bit against the CPU path.",
    internal=True)

ADAPTIVE_ENABLED = bool_conf(
    "spark.rapids.sql.adaptive.enabled", True,
    "AQE runtime join-strategy conversion: a join build side whose STATIC "
    "size estimate could not prove it broadcastable is measured at "
    "runtime and converted to a cached broadcast when it lands under "
    "spark.rapids.sql.broadcastSizeBytes (AQE DynamicJoinSelection "
    "analog).")

DELTA_LOW_SHUFFLE_MERGE = bool_conf(
    "spark.rapids.sql.delta.lowShuffleMerge.enabled", True,
    "MERGE rewrites only the TOUCHED ROWS of matched files: matched "
    "target rows die via a deletion vector and updated versions land in "
    "a small new file, so untouched rows of touched files never rewrite "
    "(GpuLowShuffleMergeCommand analog). Disable for full-file "
    "rewrites.")

AQE_SKEW_FACTOR = float_conf(
    "spark.rapids.sql.adaptive.skewJoin.skewedPartitionFactor", 4.0,
    "A reduce partition whose measured map-output bytes exceed this "
    "multiple of the median is counted skewed (skewedPartitions metric; "
    "oversized partitions already split into target-size batches at "
    "read time — AQE OptimizeSkewedJoin's split, measured not guessed).")

AQE_COALESCE_PARTITIONS = bool_conf(
    "spark.rapids.sql.adaptive.coalescePartitions.enabled", True,
    "Adaptive shuffle-partition coalescing from MEASURED map-output "
    "sizes: adjacent undersized reduce partitions merge into shared "
    "output batches at read time (AQE CoalesceShufflePartitions "
    "analog). Note: output batches are then not partition-aligned "
    "(keyed co-location still holds per ROW); disable for consumers "
    "that require one batch per requested partition. Partitions larger than "
    "the batch target still split either way.")

BROADCAST_SIZE_BYTES = int_conf(
    "spark.rapids.sql.broadcastSizeBytes", 10 << 20,
    "Join build sides whose plan-size estimate is at or below this "
    "threshold are broadcast: materialized once through TpuBroadcastExchangeExec "
    "(spillable, reused across replays; replicated across the mesh in "
    "sharded plans) instead of coalesced per-query "
    "(autoBroadcastJoinThreshold analog).", commonly_used=True)

JOIN_SUBPARTITION_BYTES = int_conf(
    "spark.rapids.sql.join.subPartition.targetBytes", 1 << 30,
    "Build sides larger than this sub-partition by Spark-exact key hash "
    "into ceil(size/target) buckets; probe batches split the same way and "
    "bucket pairs join independently with spillable build partitions "
    "(GpuSubPartitionHashJoin analog). 0 disables.")

SPLIT_F64_SUM = str_conf(
    "spark.rapids.tpu.sum.splitF64", "auto",
    "f64 SUM/AVG reduction mode. 'auto': on TPU (where f64 compute is "
    "emulated) run the fast exact hi/lo f32 decomposition with blocked "
    "accumulation (~1e-9 typical relative error; a runtime guard reroutes "
    "to the exact path on huge magnitudes or cancellation). Variance/"
    "stddev MEANS always use the exact path (a mean error amplifies "
    "quadratically in the centered pass); only the positive-valued "
    "centered sums split. CPU backends keep native f64. 'true'/'false' "
    "force the mode. The same trade the reference gates with "
    "variableFloatAgg.enabled.")

AGG_MAX_DICT_GROUPS = int_conf(
    "spark.rapids.tpu.agg.maxDictGroups", 1 << 16,
    "Max key-domain product for the no-sort dictionary-code aggregation "
    "fast path (grouping keys that are dictionary-encoded strings or "
    "booleans aggregate by direct segment reduction, no sort).")

DEVICE_ORDINAL = int_conf(
    "spark.rapids.tpu.deviceOrdinal", -1,
    "Local device the session computes on: -1 = auto (first local "
    "device; multi-process launches pick round-robin by process index, "
    "the GpuDeviceManager executor-id addressing analog). An explicit "
    "ordinal must be a valid jax local device index.", startup_only=True)

AGG_MAX_KEY_DOMAIN_GROUPS = int_conf(
    "spark.rapids.tpu.agg.maxKeyDomainGroups", 1 << 21,
    "Max key-domain product for the no-sort INTEGER-key aggregation fast "
    "path: when every grouping key is an integer-family column whose "
    "(min,max) bound is known from upload-time column statistics, the "
    "group-by runs as a direct segment reduction over the value domain "
    "instead of a full sort. 0 disables. Domains above this (or above "
    "16x the batch capacity) fall back to the sort-segment path.")

AGG_FUSE_INPUT = bool_conf(
    "spark.rapids.tpu.agg.fuseInput", True,
    "Fuse Project/Filter chains feeding an aggregate into the aggregate "
    "kernel: one XLA program evaluates predicates as weight masks (no row "
    "compaction) and value expressions inline (WholeStageCodegen analog).")

SCAN_DEVICE_CACHE = bool_conf(
    "spark.rapids.tpu.scan.deviceCache", True,
    "Cache the uploaded device image of in-memory scan batches on the host "
    "table (GpuInMemoryTableScanExec analog); evicted on device OOM.")

PLAN_VERIFY_MODE = str_conf(
    "spark.rapids.sql.planVerify.mode", "off",
    "Static plan verification of every converted plan before execution "
    "(spark_rapids_tpu.lint): off, warn (print diagnostics and "
    "continue), or error (raise PlanVerificationError). The test suite "
    "runs with error; `python -m spark_rapids_tpu.lint` runs the same "
    "verifier over the TPC-H golden suite plus the registry/repo "
    "audits.", commonly_used=True)


SHAPE_BUCKETS = str_conf(
    "spark.rapids.sql.shapeBuckets", "pow2",
    "Capacity bucket policy for device batches: every batch capacity "
    "rounds UP to the next bucket before any kernel sees it, so the "
    "whole workload compiles to a BOUNDED kernel set instead of one "
    "XLA program per row count (mask-aware execs tolerate the dead "
    "tail rows). 'pow2' (default) and 'pow4' grow geometrically from "
    "shapeBuckets.minBucket; an explicit ascending comma-separated "
    "list (e.g. '1024,16384,262144') declares the exact set, with "
    "pow2 growth above its largest entry. Bucket pad waste is counted "
    "in the `compile` metric scope (padWasteRows). The policy is "
    "PROCESS-WIDE (pushed at query start, like the other tuning "
    "knobs): sessions executing concurrently in one process should "
    "agree on it — a mid-drain policy switch costs extra compiled "
    "shapes, never correctness.", commonly_used=True)

SHAPE_BUCKETS_MIN = int_conf(
    "spark.rapids.sql.shapeBuckets.minBucket", 128,
    "Smallest capacity bucket (and the unit every bucket must be a "
    "multiple of): 128 is the TPU lane width, so buckets tile cleanly "
    "onto the VPU/MXU. Raising it trades pad waste for fewer distinct "
    "compiled shapes on tiny batches.")

EXECUTABLE_CACHE_ENABLED = bool_conf(
    "spark.rapids.sql.executableCache.enabled", True,
    "Cache the converted executable plan (lowered exec tree + "
    "overrides meta) keyed on the literal-stripped structural "
    "fingerprint (plan/fingerprint.py): a repeated query template "
    "skips overrides conversion, plan verification and kernel "
    "re-tracing entirely; distinct-literal variants of one template "
    "share the grouped entry's compiled-kernel set. Entries drop on "
    "warehouse invalidation (writes/commits) and on circuit-breaker "
    "demotions. Hit/miss counters live in the `compile` metric scope.",
    commonly_used=True)

EXECUTABLE_CACHE_MAX_PLANS = int_conf(
    "spark.rapids.sql.executableCache.maxPlans", 64,
    "LRU bound on cached plan TEMPLATES (literal-stripped "
    "fingerprints) in the executable cache. NOTE: a cached tree pins "
    "its plan's in-memory source tables (scan-node references), so "
    "this bound also bounds host memory pinned by the cache — size it "
    "to the serving working set, not to every plan ever seen.")

EXECUTABLE_CACHE_MAX_VARIANTS = int_conf(
    "spark.rapids.sql.executableCache.maxVariantsPerPlan", 4,
    "LRU bound on literal variants retained per cached template: each "
    "variant pins one converted exec tree; template-mates beyond it "
    "still share the template's compiled kernels.")

ASYNC_RESULT_FETCH = bool_conf(
    "spark.rapids.sql.asyncResultFetch", True,
    "Move the final device->host result fetch off the device-semaphore "
    "critical section: the collect's packed d2h kernel is ENQUEUED "
    "under the semaphore, the semaphore releases once the last kernel "
    "is in flight, and the device round trip completes without "
    "blocking the next admitted query (reference: spark-rapids async "
    "d2h pipelining). Per-batch fetches that must validate speculation "
    "flags stay synchronous.")


class RapidsConf:
    """Immutable-ish view over a plain {key: value} dict with typed access."""

    def __init__(self, settings: Optional[Dict[str, Any]] = None):
        self._settings = dict(settings or {})

    def get(self, key: str) -> Any:
        entry = _REGISTRY.get(key)
        if key in self._settings:
            raw = self._settings[key]
            return entry.conv(raw) if entry is not None and isinstance(raw, str) else raw
        if entry is None:
            raise KeyError(f"unknown conf key {key}")
        return entry.default

    def get_entry(self, entry: ConfEntry) -> Any:
        return self.get(entry.key)

    def set(self, key: str, value: Any) -> "RapidsConf":
        s = dict(self._settings)
        s[key] = value
        return RapidsConf(s)

    def to_dict(self) -> Dict[str, Any]:
        return dict(self._settings)

    # Convenience accessors used throughout the engine.
    @property
    def sql_enabled(self) -> bool:
        return self.get_entry(SQL_ENABLED)

    @property
    def explain_mode(self) -> str:
        return str(self.get_entry(EXPLAIN)).upper()

    @property
    def is_explain_only(self) -> bool:
        return str(self.get_entry(SQL_MODE)).lower() == "explainonly"

    @property
    def batch_size_bytes(self) -> int:
        return self.get_entry(BATCH_SIZE_BYTES)

    @property
    def concurrent_tpu_tasks(self) -> int:
        return self.get_entry(CONCURRENT_TPU_TASKS)

    def is_op_enabled(self, kind: str, name: str) -> bool:
        key = f"spark.rapids.sql.{kind}.{name}"
        if key in self._settings:
            return _to_bool(self._settings[key])
        entry = _REGISTRY.get(key)
        return bool(entry.default) if entry else True


def registry() -> Dict[str, ConfEntry]:
    return dict(_REGISTRY)


def generate_docs() -> str:
    """Markdown table of all configs (reference: docs/configs.md generation
    from RapidsConf.help)."""
    import importlib
    import pkgutil

    # per-op kill switches, format keys, profiler/filecache/optimizer
    # confs all register at their module's import time; walk the whole
    # package so the doc is complete no matter what the process
    # imported first
    import spark_rapids_tpu
    for _m in pkgutil.walk_packages(spark_rapids_tpu.__path__,
                                    "spark_rapids_tpu."):
        try:
            importlib.import_module(_m.name)
        except Exception:
            pass  # optional backends (pyarrow etc.) may be absent
    lines = [
        "# spark_rapids_tpu configuration",
        "",
        "| Key | Default | Description |",
        "|---|---|---|",
    ]
    for key in sorted(_REGISTRY):
        e = _REGISTRY[key]
        if e.internal:
            continue
        lines.append(f"| `{e.key}` | `{e.default}` | {e.doc} |")
    lines += [
        "",
        "## SQL entry point",
        "",
        "`TpuSession.sql(text)` lowers SQL text onto the same plan layer "
        "the DataFrame DSL builds, so every key above — overrides kill "
        "switches, AQE, fallback — applies to SQL queries unchanged. "
        "Temp views registered with `create_or_replace_temp_view` (or "
        "`CREATE TEMP VIEW`) and file-format tables registered via "
        "`CREATE TEMP VIEW v USING fmt OPTIONS (path '...')` resolve "
        "through `session.catalog`; views capture the PLAN, live for the "
        "session, and drop via `DROP VIEW [IF EXISTS]`. The supported "
        "grammar table lives in README.md; `benchmarks/run.py` runs "
        "its cells' TPC-H statements, and `scale_test.py --sql` the "
        "TPC-H corpus, from SQL text.",
        "",
        "## Static analysis (`python -m spark_rapids_tpu.lint`)",
        "",
        "One CLI runs three tools and exits non-zero on any diagnostic: "
        "a **plan verifier** (walks every converted plan and asserts "
        "schema contracts, device/host transition correctness, exchange "
        "partitioning, decimal precision/scale propagation, TypeSig "
        "conformance and fallback-reason hygiene), a **registry "
        "auditor** (ops/* classes vs overrides registrations, ExprChecks "
        "arity, kill-switch keys, SQL exposure, and drift between this "
        "file / SUPPORTED_OPS.md and their generators — regenerate with "
        "`--write-docs`), and a **repo lint** (no host syncs in execs/ "
        "or ops/ outside `dispatch.host_fetch`, no `jax.numpy` outside "
        "the device layers, no undeclared conf-key string literals, no "
        "wall-clock/unseeded randomness in kernels, no dead lambdas). "
        "`spark.rapids.sql.planVerify.mode` additionally runs the plan "
        "verifier inline on every `TpuSession.execute` (`off` in "
        "production, `error` under the test suite); the CLI also "
        "verifies the TPC-H q1-q22 golden corpus in DSL and SQL form, "
        "with AQE on and off. `--list-rules` prints every rule id.",
        "",
        "## Observability",
        "",
        "`spark.rapids.sql.eventLog.enabled` writes one structured "
        "JSONL record per query under `spark.rapids.sql.eventLog.dir` "
        "(`obs/events.py`): the executed plan tree with TYPED "
        "per-operator metrics (timing/count/bytes at "
        "ESSENTIAL/MODERATE/DEBUG levels — the unified registry in "
        "`obs/metrics.py`, filtered by `spark.rapids.sql.metrics."
        "level`), fallback reasons, circuit-breaker demotions, AQE "
        "conversions, spill/retry/recovery counter deltas, shuffle "
        "bytes per exchange, and query wall/phase times with span "
        "attribution. `spark.rapids.trace.enabled` additionally "
        "collects thread-aware host spans (exec boundaries, h2d/d2h "
        "transfers, shuffle fetch/write/serialize, spill, kernel "
        "dispatch) and exports a Chrome trace-event JSON per query "
        "under `spark.rapids.trace.dir` — load it in Perfetto next to "
        "the Xprof device trace `spark.rapids.profile.enabled` "
        "collects. `benchmarks/run.py` and `scale_test.py` write event "
        "logs by default; `python -m spark_rapids_tpu.tools profile <log>` "
        "builds the offline report (top operators by self time, "
        "compute/transfer/shuffle/spill breakdown, per-exchange skew, "
        "fallback inventory, >=95% span-attribution contract) and "
        "`... compare A B` diffs two runs per-query/per-operator.",
        "",
        "Every range the engine opens goes through one function, "
        "`obs.spans.span(name, cat)`, and is always a "
        "`jax.profiler.TraceAnnotation` named `srt.<cat>.<name>`: "
        "`srt.query` around a whole query, `srt.phase.parse|plan|"
        "execute|collect|observe`, `srt.exec.<ExecClass>` per batch "
        "pull, `srt.dispatch.<program>` per device program (the same "
        "name as its XLA module `jit_<program>`), `srt.sync.host_fetch`, "
        "`srt.fetch.resolve|wait|unpack`, `srt.wait.semaphore`, "
        "`srt.coalesce.flush` (a coalesce exec's multi-batch copy), "
        "`srt.join.build|batch` (a join exec making its build side ready, "
        "and joining one probe batch), "
        "`srt.mesh.reland` (the gather of a mesh-sharded batch to one "
        "device), "
        "`srt.transfer.encode|stage|upload|HostToDevice|DeviceToHost`, "
        "`srt.eventlog.write`, `srt.shuffle.*`, `srt.spill.*`, "
        "`srt.cluster.scan`. They appear on the host timeline "
        "(`/host:CPU`) of ANY Xprof trace of the process — one taken by "
        "`spark.rapids.profile.enabled` or by an outer "
        "`jax.profiler.start_trace` — on the clock of the device "
        "planes, each carrying `query=<query index>`; with no profiler "
        "session a range costs about a microsecond. The same ranges "
        "feed the Chrome export and the event record's `spans` summary "
        "while a query's envelope collects. The event record's "
        "`phasesS` also holds the host seconds taken where the work "
        "happens (`parseS`, `dispatchS`, `syncWaitS`, `fetchWaitS`, "
        "`fetchUnpackS`, `semaphoreWaitS`, `coalesceS`, `relandS`, "
        "`joinS`) and "
        "`hostSyncs` "
        "counts the "
        # (the removed switches' names are split across literals so that
        # a grep of the package for them finds no code)
        "blocking device-to-host fetches. The `SRT_PROFILE_"
        "DISPATCH` and `SRT_TRACE_"
        "LOG` environment switches are gone: the named dispatch ranges "
        "and programs replace them.",
        "",
        "## Query service",
        "",
        "`spark_rapids_tpu.service.QueryService` is the concurrent "
        "multi-tenant front end over one session: a "
        "`spark.rapids.service.maxConcurrentQueries`-wide worker pool "
        "executes admitted queries concurrently (device residency still "
        "gated by `spark.rapids.sql.concurrentGpuTasks`), with named "
        "scheduling pools (`spark.rapids.service.pools`), per-tenant "
        "weighted fair queueing "
        "(`spark.rapids.service.tenantWeights`), bounded queue depth "
        "with typed rejection + retry-after backpressure "
        "(`spark.rapids.service.queueDepth`), per-query deadlines "
        "(`spark.rapids.service.defaultTimeoutMs` or "
        "`submit(timeout_ms=...)`) enforced cooperatively BETWEEN "
        "batches at every exec boundary (as is "
        "`QueryHandle.cancel()`), and memory-pressure-aware admission "
        "consulting the spill catalog "
        "(`spark.rapids.service.admission.maxDeviceBytes`). "
        "Structurally identical plans under result-identical conf are "
        "served from the plan-fingerprint result cache "
        "(`spark.rapids.service.resultCache.*`), invalidated on "
        "temp-view/catalog mutation, `WriteFiles`, and Delta commits. "
        "Event-log records carry tenant/pool/queue-wait/cache-hit "
        "fields (schema v2); `python -m spark_rapids_tpu.tools "
        "loadtest` and `scale_test.py --concurrency N` drive TPC-H "
        "q1-q22 across simulated tenants, asserting bit-identical "
        "results against serial execution and reporting "
        "throughput/p50/p95 latency, queue wait and cache hit rate.",
        "",
        "## Fault tolerance",
        "",
        "The `spark.rapids.shuffle.fetch.*` keys govern shuffle fetch "
        "retry with exponential backoff and per-peer exclusion; a fetch "
        "that exhausts its retries (or a peer the driver evicts) triggers "
        "lost-map-output RECOMPUTE from the retained plan lineage instead "
        "of query failure. `spark.rapids.sql.runtimeFallback.*` governs "
        "the per-operator circuit breaker: repeated non-OOM device "
        "failures demote the op to the CPU fallback path for the rest of "
        "the engine process (every session sharing the device — the "
        "speculation-blocklist pattern), recorded as a fallback reason "
        "in explain()/planVerify. Fault injection for all of this is "
        "conf-driven "
        "(`spark.rapids.test.faults`, internal) through named fault "
        "points audited by the RL-FAULT-POINT lint rule; "
        "`scale_test.py --chaos` runs TPC-H q1-q22 under a seeded fault "
        "schedule asserting bit-identical results, and the `-m chaos` "
        "pytest slice keeps a small seeded run in tier-1.",
    ]
    return "\n".join(lines) + "\n"

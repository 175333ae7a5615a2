"""Typed configuration registry — the RapidsConf analog.

(reference: sql-plugin/.../RapidsConf.scala — builder DSL, startup vs
runtime entries, and markdown doc generation for docs/configs.md.)

Usage:
    conf = TpuConf({"spark.rapids.tpu.sql.batchSizeRows": 1 << 21})
    conf.batch_size_rows

`generate_docs()` emits docs/configs.md content from the registry, like the
reference's `RapidsConf.help()` doc emitters.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

__all__ = ["TpuConf", "ConfEntry", "REGISTRY", "generate_docs"]

REGISTRY: Dict[str, "ConfEntry"] = {}


class ConfEntry:
    def __init__(self, key: str, default: Any, doc: str, typ: Callable,
                 internal: bool = False, startup: bool = False):
        self.key = key
        self.default = default
        self.doc = doc
        self.typ = typ
        self.internal = internal
        self.startup = startup
        REGISTRY[key] = self

    def get(self, conf: "TpuConf"):
        raw = conf._settings.get(self.key, self.default)
        if raw is None:
            return None
        if self.typ is bool and isinstance(raw, str):
            return raw.lower() in ("true", "1", "yes")
        return self.typ(raw)


def _conf(key, default, doc, typ, **kw):
    return ConfEntry(f"spark.rapids.tpu.{key}", default, doc, typ, **kw)


# ----------------------------------------------------------------------
# Registry (grouped roughly like the reference's RapidsConf sections)
# ----------------------------------------------------------------------
BATCH_SIZE_ROWS = _conf(
    "sql.batchSizeRows", 1 << 20,
    "Target rows per columnar batch read into HBM. Batches are padded to "
    "power-of-two capacities to bound XLA recompilation.", int)
CONCURRENT_TASKS = _conf(
    "sql.concurrentTpuTasks", 2,
    "Max tasks concurrently admitted to the TPU (TpuSemaphore permits; "
    "analog of spark.rapids.sql.concurrentGpuTasks).", int)
HBM_POOL_FRACTION = _conf(
    "memory.tpu.allocFraction", 0.85,
    "Fraction of HBM the memory manager may budget for columnar data.",
    float)
HBM_POOL_BYTES = _conf(
    "memory.tpu.poolBytes", None,
    "Explicit HBM budget in bytes; overrides allocFraction when set.",
    int)
HOST_MEMORY_LIMIT = _conf(
    "memory.host.limitBytes", 0,
    "GLOBAL host-DRAM byte budget shared by the spill store's host "
    "tier, async write buffers, and shuffle-assembly arenas "
    "(HostAlloc.scala:36 analog; limits RapidsConf.scala:337-353). "
    "Reservations over budget fire the host->disk pressure cascade; "
    "0 = unlimited.", int)
HOST_SPILL_LIMIT = _conf(
    "memory.host.spillStorageSize", 32 * 1024 * 1024 * 1024,
    "Bytes of host DRAM usable for spilled device buffers before "
    "cascading to disk.", int)
SPILL_DIR = _conf(
    "memory.spill.dir", "/tmp/srtpu-spill",
    "Directory for disk-tier spill files.", str)
SHUFFLE_PARTITIONS = _conf(
    "sql.shuffle.partitions", 8,
    "Default partition count for exchanges (spark.sql.shuffle.partitions).",
    int)
SHUFFLE_DIR = _conf(
    "shuffle.dir", "/tmp/srtpu-shuffle",
    "Directory for multithreaded host shuffle files.", str)
SHUFFLE_WRITER_THREADS = _conf(
    "shuffle.multiThreaded.writer.threads", 4,
    "Thread pool size for shuffle writes "
    "(analog of RapidsShuffleManager MULTITHREADED mode).", int)
SHUFFLE_READER_THREADS = _conf(
    "shuffle.multiThreaded.reader.threads", 4,
    "Thread pool size for shuffle reads.", int)
EXCHANGE_MAP_THREADS = _conf(
    "sql.exec.exchange.mapThreads", 0,
    "Worker threads executing an exchange's map-side child partitions "
    "concurrently (each worker runs a full map partition: child "
    "execute, device partition pass, host slicing, shuffle write). "
    "Device admission still goes through the TpuSemaphore, so chip "
    "concurrency stays bounded by sql.concurrentTpuTasks; this conf "
    "overlaps the HOST halves (decode, slicing, serialization, file "
    "I/O) across partitions (the RapidsShuffleThreadedWriter analog). "
    "0 = auto (min(4, cpu cores)); 1 = serial map side.", int)
EXCHANGE_ASYNC_BROADCAST = _conf(
    "sql.exec.exchange.asyncBroadcast.enabled", True,
    "Materialize a broadcast join's build side on a background thread "
    "started when the JOIN begins executing, so the build overlaps the "
    "stream side's scan/decode instead of serializing in front of it "
    "(GpuBroadcastExchangeExec async-collect analog). The join blocks "
    "on the future at probe time, bounded by broadcastTimeoutSecs.",
    bool)
EXCHANGE_BROADCAST_TIMEOUT = _conf(
    "sql.exec.exchange.broadcastTimeoutSecs", 300.0,
    "Upper bound on the join's wait for an async broadcast build "
    "(spark.sql.broadcastTimeout analog). On timeout the join degrades "
    "to the synchronous build path on the calling thread and counts "
    "broadcastTimeoutFallbacks — it never hangs. 0 = wait forever.",
    float)
EXCHANGE_REUSE = _conf(
    "sql.exec.exchange.reuse.enabled", True,
    "Plan-level exchange deduplication (Spark's ReuseExchange rule): "
    "after fusion, structurally identical exchange subtrees (same "
    "fingerprint under gensym normalization) are rewritten to "
    "ReusedExchange nodes sharing the first occurrence's materialized "
    "shuffle blocks — one map phase per distinct subtree per query. "
    "Hits surface as exchangeReuseHits in EXPLAIN ANALYZE and the "
    "event log.", bool)
TEXT_BLOCK_SIZE = _conf(
    "sql.text.blockSize", 32 * 1024 * 1024,
    "Host decode block size (bytes) for streaming CSV/JSON scans.", int)
ADAPTIVE_ENABLED = _conf(
    "sql.adaptive.enabled", True,
    "Adaptive post-shuffle re-planning: coalesce small reduce partitions "
    "toward the target size and split skewed join stream partitions "
    "(analog of spark.sql.adaptive.* + GpuCustomShuffleReaderExec).", bool)
ADAPTIVE_TARGET_BYTES = _conf(
    "sql.adaptive.advisoryPartitionSizeInBytes", 64 * 1024 * 1024,
    "Advisory post-shuffle partition size: adjacent reduce partitions "
    "smaller than this coalesce into one task "
    "(spark.sql.adaptive.advisoryPartitionSizeInBytes).", int)
ADAPTIVE_SKEW_FACTOR = _conf(
    "sql.adaptive.skewJoin.skewedPartitionFactor", 5,
    "A join stream partition is skewed when its bytes exceed this factor "
    "times the median partition size (and the min threshold).", int)
ADAPTIVE_SKEW_MIN_BYTES = _conf(
    "sql.adaptive.skewJoin.skewedPartitionThresholdInBytes",
    256 * 1024 * 1024,
    "Minimum bytes before a stream partition is considered skewed.", int)
ADAPTIVE_COALESCE_ENABLED = _conf(
    "sql.adaptive.coalescePartitions.enabled", True,
    "AQE rule 1: merge small contiguous post-shuffle partitions toward "
    "advisoryPartitionSizeInBytes at the stage boundary "
    "(spark.sql.adaptive.coalescePartitions.enabled). Off: one task per "
    "reduce partition.", bool)
ADAPTIVE_SKEW_ENABLED = _conf(
    "sql.adaptive.skewJoin.enabled", True,
    "AQE rule 2: split join stream partitions exceeding "
    "skewedPartitionFactor x median (and the byte threshold) into "
    "row-balanced slices, each probing the full matching build "
    "partition (spark.sql.adaptive.skewJoin.enabled).", bool)
ADAPTIVE_DEMOTE_ENABLED = _conf(
    "sql.adaptive.joinDemotion.enabled", True,
    "AQE rule 3: when a shuffled hash join's build side materializes "
    "under autoBroadcastJoinThreshold, rewrite the remaining stage to a "
    "broadcast hash join and skip the stream-side shuffle entirely "
    "(runtime inverse of Spark's DemoteBroadcastHashJoin).", bool)
ADAPTIVE_CALIBRATION = _conf(
    "sql.adaptive.calibration.enabled", True,
    "Feed observed output cardinalities back into plan/stats.py as a "
    "session-scoped calibration table keyed by structural plan "
    "fingerprints, correcting CBO row estimates (join reorder) for "
    "later plans of the same subtrees.", bool)
SHUFFLE_COMPRESS = _conf(
    "shuffle.compression.codec", "lz4",
    "Shuffle wire compression: none|lz4|zstd (nvcomp analog, host-side).",
    str)
EXPLAIN = _conf(
    "sql.explain", "NONE",
    "Explain TPU planning: NONE|NOT_ON_TPU|ALL|VALIDATE "
    "(analog of spark.rapids.sql.explain). NOT_ON_TPU/ALL print the "
    "tagged plan plus static-audit findings; VALIDATE prints the full "
    "plan-audit verdict tree (ok / will_fallback / will_not_work / "
    "recompile_risk per node, see docs/static_analysis.md).", str)
AUDIT_STRICT = _conf(
    "sql.audit.strict", False,
    "Fail at PLAN time when the static plan auditor finds a "
    "will_not_work verdict (unregistered expression, dtype the device "
    "kernels cannot actually run): raises UnsupportedExpr carrying the "
    "lore id + node path of every blocked site instead of dying "
    "mid-query with an opaque XLA error. will_fallback and "
    "recompile_risk verdicts never fail the plan.", bool)
ALLOW_CPU_FALLBACK = _conf(
    "sql.allowCpuFallback", True,
    "Allow operators that cannot run on TPU to fall back to the host CPU "
    "path instead of failing.", bool)
STAGE_FUSION_ENABLED = _conf(
    "sql.exec.stageFusion.enabled", True,
    "Whole-stage XLA fusion: at plan time, collapse maximal chains of "
    "narrow operators (Filter, Project, limit-mask, the expression-eval "
    "front half of aggregates, probe-side join pre-projection, sort-key "
    "computation) into one FusedStage node compiled as a single jitted "
    "program, eliminating per-operator dispatches and intermediate "
    "batch materialization (the WholeStageCodegen analog). Barriers: "
    "exchanges, host fallbacks, cached scans, and nodes the static "
    "auditor flags recompile_risk. Per-node opt-out: set "
    "`node.fusion_opt_out = True` on the physical node.", bool)
STAGE_FUSION_MAX_OPS = _conf(
    "sql.exec.stageFusion.maxOps", 16,
    "Maximum number of member operators in one fused stage; longer "
    "chains are split. Bounds single-program XLA compile time.", int)
PROGRAM_CACHE_ENABLED = _conf(
    "sql.exec.programCache.enabled", True,
    "Process-global XLA program cache (runtime/program_cache.py): "
    "jitted operator programs are keyed by (operator class, program "
    "tag, expression fingerprint, donation flags, backend, "
    "jit-relevant conf fingerprint, input avals signature) and shared "
    "across exec instances, DataFrames, and Sessions, so a fresh "
    "same-shaped query tree performs zero new XLA compiles on a warm "
    "process. Off: every exec instance jits privately (pre-cache "
    "behavior).", bool)
PROGRAM_CACHE_MAX_ENTRIES = _conf(
    "sql.exec.programCache.maxEntries", 512,
    "LRU capacity of the process-global program cache, in cached "
    "programs (one per distinct key, including the avals signature). "
    "Power-of-two capacity bucketing keeps distinct signatures per "
    "site small, so the default comfortably holds a full TPC-H sweep. "
    "Each live XLA:CPU executable pins ~10-20 memory mappings, so the "
    "bound is also a vm.max_map_count budget (~11k maps at 512): "
    "raising it far beyond the default risks mmap exhaustion in "
    "long-lived many-query processes. Eviction counts surface as "
    "program_cache_evictions in the xla_compile event record.", int)
SHAPE_BUCKET_MIN_ROWS = _conf(
    "sql.exec.shapeBuckets.minRows", 128,
    "Floor of the capacity-bucket grid (columnar/column.py "
    "set_bucket_policy): every device buffer capacity rounds up onto "
    "{minRows * growthFactor^k}. Rounded to a power of two, minimum "
    "128 (TPU lane width). Raising the floor collapses many small "
    "batch sizes onto one bucket so structurally equal operators "
    "share one padded XLA program — fewer cold compiles, bounded "
    "extra padding. Adopted process-globally at query start "
    "(program_cache.set_active_conf), like the program cache it "
    "feeds.", int)
SHAPE_BUCKET_GROWTH = _conf(
    "sql.exec.shapeBuckets.growthFactor", 2,
    "Growth factor of the capacity-bucket grid (one of 2/4/8/16). "
    "2 is the historical next-power-of-two bucketing; 4 compiles "
    "~half as many distinct shapes per operator at a padding-waste "
    "bound of 1 - 1/growthFactor (measured waste surfaces in "
    "columnar.column.shape_stats). String-key chunk counts canonicalize on the same grid "
    "(ops/sortkeys.nchunks_for_len).", int)
COMPILE_POOL_ENABLED = _conf(
    "sql.exec.compilePool.enabled", True,
    "Background XLA compilation (runtime/compile_pool.py): a bounded "
    "pool of daemon threads (tpu-compile-N) compiles stage programs "
    "ahead of first dispatch — downstream fused-stage programs are "
    "submitted at query launch and compile while upstream stages "
    "execute; warm-pack preloads compile speculatively at service "
    "startup. Dispatch NEVER waits on a background compile: a sync "
    "miss compiles inline exactly as before (a duplicate compile is "
    "accepted over a stall), and speculative tasks yield while "
    "queries are running (admission-aware). Background failures — "
    "including injected xla.compile faults — are swallowed, counted "
    "(program_cache_background_failures), and fall back to the sync "
    "path.", bool)
COMPILE_POOL_THREADS = _conf(
    "sql.exec.compilePool.threads", 2,
    "Worker threads in the background compile pool. Compilation is "
    "CPU-bound in the XLA C++ compiler (GIL released), so a small "
    "pool overlaps well with query execution without starving "
    "dispatch.", int)
WARM_PACK_PATH = _conf(
    "sql.service.warmPack.path", "",
    "Warm-pack manifest preloaded at service startup "
    "(runtime/warm_pack.py): recorded query texts are re-planned "
    "(constructing the program-cache builders) and each recorded "
    "program signature is compiled in the background pool, so the "
    "first user-visible query per shape is already warm. The "
    "manifest is validated against the jaxlib + mesh fingerprint "
    "and version; a mismatched or corrupt pack is skipped with a "
    "warning, never an error. Empty: no preload. Hard-disabled by "
    "SRTPU_COMPILE_CACHE=0 alongside the persistent XLA cache.", str)
WARM_PACK_RECORD = _conf(
    "sql.service.warmPack.record", "",
    "When set to a path, the session records every sql() text and "
    "every program-cache key it compiles, and save_warm_pack() (or "
    "server shutdown) writes the manifest there. Program keys "
    "containing identity fallbacks (('id', ...)) are excluded — they "
    "cannot match across processes (see the unstable-program-key "
    "lint rule).", str)
WARM_PACK_REPLAY = _conf(
    "sql.service.warmPack.replay", True,
    "Warm-pack preload strategy. True (default): execute each "
    "recorded query once at startup, which compiles every program in "
    "its tree — including programs built lazily inside "
    "execute_partition that a plan-only pass cannot reach — at the "
    "cost of startup wall time proportional to the recorded "
    "workload. False: plan-only preload; construction-time programs "
    "are compiled speculatively through the background pool and "
    "lazily-built programs still compile sync on first dispatch.",
    bool)
RESULT_CACHE_ENABLED = _conf(
    "sql.cache.enabled", False,
    "Process-global cross-query result & fragment cache "
    "(runtime/result_cache.py): whole-query Arrow results and hot "
    "exchange map outputs are keyed on name/gensym-blind structural "
    "plan fingerprints composed with scan snapshot versions (parquet "
    "path+mtime+size sets, Delta table version), so a table write "
    "soundly invalidates every dependent entry. A whole-query hit is "
    "answered on the service fast path without consuming an admission "
    "slot. Off by default (Spark/Presto posture): repeat traffic "
    "opts in per session.", bool)
RESULT_CACHE_MAX_BYTES = _conf(
    "sql.cache.maxBytes", 256 * 1024 * 1024,
    "Byte budget of the result cache across both tiers (whole-query "
    "Arrow results + cached exchange fragments). Least-recently-used "
    "entries are evicted past the budget; cached bytes also charge "
    "the host-memory budget (spark.rapids.tpu.memory.host.limitBytes) "
    "and are released first under host-memory pressure.", int)
RESULT_CACHE_FRAGMENTS = _conf(
    "sql.cache.fragments.enabled", True,
    "Fragment tier of the result cache: materialized exchange map "
    "outputs are cached by exchange-subtree fingerprint and served as "
    "cached sources (CachedFragmentExec) in later plans, eliding the "
    "whole map phase. Only consulted when sql.cache.enabled is on.", bool)
RESULT_CACHE_MAX_ENTRY_BYTES = _conf(
    "sql.cache.maxEntryBytes", 64 * 1024 * 1024,
    "Largest single result or fragment the cache will store. Results "
    "bigger than this execute normally and are never cached (a "
    "full-table scan must not wipe the working set of an interactive "
    "dashboard mix).", int)
METRICS_LEVEL = _conf(
    "sql.metrics.level", "MODERATE",
    "Metric verbosity: ESSENTIAL|MODERATE|DEBUG.", str)
METRICS_SYNC = _conf(
    "sql.metrics.sync", False,
    "Synchronize the device stream at batch boundaries inside operator "
    "timers (a trivial op is enqueued and block_until_ready'd before "
    "the timer stops). OFF by default: jax dispatch is async, so "
    "default op-time metrics measure DISPATCH time and actual kernel "
    "execution is attributed to whichever downstream operator first "
    "blocks (usually the D2H fetch at the plan root) — see "
    "docs/observability.md. Turning this on yields debug-grade "
    "per-operator execution times at the cost of pipelining.", bool)
EVENT_LOG_ENABLED = _conf(
    "sql.eventLog.enabled", False,
    "Write a structured per-query JSONL event log (the Spark event-log "
    "analog): plan with lore ids, per-operator MetricSet snapshots, "
    "memory watermarks, shuffle bytes, XLA compile stats. Consumed by "
    "tools/profile_report.py and EXPLAIN ANALYZE post-processing.",
    bool)
EVENT_LOG_DIR = _conf(
    "sql.eventLog.dir", "/tmp/srtpu-events",
    "Directory for per-query event-log JSONL files.", str)
TRACE_ENABLED = _conf(
    "sql.trace.enabled", True,
    "Open per-query spans (profiler/tracing.py) around queue wait, "
    "planning, AQE stage decisions, compiles, pool map tasks, shuffle "
    "fetches, spills, collective launches and retry/degrade recovery. "
    "Spans assemble into one trace per query — written to the event "
    "log as trace_span records (when sql.eventLog.enabled) and reduced "
    "to critical-path latency shares (profiler/critical_path.py) shown "
    "in EXPLAIN ANALYZE root annotations and profile_report --trace. "
    "Overhead is gated <3% on the q6 A/B (tests/test_tracing.py).",
    bool)
TRACE_SAMPLE_RATE = _conf(
    "sql.trace.sampleRate", 1.0,
    "Fraction of queries traced (0.0-1.0). Sampling is deterministic "
    "on the query id (crc32 bucket), so a query's driver threads, "
    "pool workers and executor fragments always agree on the decision "
    "and retries of the same query id re-sample identically.", float)
TELEMETRY_ENABLED = _conf(
    "sql.telemetry.enabled", True,
    "Expose the process-global telemetry registry (profiler/"
    "telemetry.py: latency/queue-wait histograms, admission and cache "
    "counters, pool-saturation and memory-watermark gauges) through "
    "the service gateway's `metrics` verb and its Prometheus text "
    "dump. Recording itself is always-on and O(1) per observation; "
    "this gates the scrape surface.", bool)
MULTITHREADED_READ_THREADS = _conf(
    "sql.format.parquet.multiThreadedRead.numThreads", 4,
    "Thread pool for the multithreaded (cloud) parquet reader "
    "(analog of spark.rapids.sql.multiThreadedRead.numThreads).", int)
PARQUET_READER_TYPE = _conf(
    "sql.format.parquet.reader.type", "AUTO",
    "AUTO|PERFILE|COALESCING|MULTITHREADED (GpuParquetScan reader "
    "types). AUTO picks COALESCING when the scan has many files "
    "smaller than the coalescing target (fewer host->device uploads), "
    "else MULTITHREADED (decode prefetch overlapping device "
    "compute).", str)
PARQUET_DEVICE_DECODE = _conf(
    "sql.format.parquet.deviceDecode.enabled", True,
    "Decode eligible Parquet column chunks ON DEVICE (flat "
    "INT32/INT64/FLOAT/DOUBLE/BYTE_ARRAY chunks; UNCOMPRESSED or "
    "SNAPPY; PLAIN or dictionary encoded; v1 and v2 data pages): raw "
    "bytes upload once, PLAIN lane assembly + RLE run expansion + "
    "string offset extraction + def-level masking run as XLA programs "
    "(GpuParquetScan.scala:3364 Table.readParquet analog). Snappy "
    "pages decompress per-page on the multithreaded prefetch pool, "
    "off the compute thread. Ineligible columns fall back to host "
    "pyarrow per column (reason counters in EXPLAIN ANALYZE). On the "
    "CPU backend the path only fires when this conf is set "
    "explicitly: host pyarrow decode and the 'device' kernels share "
    "the same silicon there, and pyarrow's native decoder wins.", bool)
PARQUET_DEVICE_SNAPPY = _conf(
    "sql.parquet.deviceSnappy", False,
    "Decompress qualifying snappy pages ON DEVICE (jitted XLA scan "
    "over the parsed literal/copy element table: run-ownership map + "
    "log-depth pointer doubling resolves every output byte to a "
    "literal source — the nvcomp-snappy analog). Applies to v1 PLAIN "
    "pages of non-nullable chunks whose element table fits a "
    "static-shape bucket; the host walks only the tag bytes. Other "
    "pages keep the host prefetch-pool decompress. Off by default: "
    "per-page output shapes vary, so cold scans pay extra XLA "
    "compiles.", bool)
HOST_STAGING_POOL_BYTES = _conf(
    "memory.host.stagingPoolBytes", 256 * 1024 * 1024,
    "Byte cap on the pinned staging pool: reusable pow2-bucketed host "
    "buffers for raw-chunk reads, snappy decompression targets, and "
    "H2D upload staging in the device parquet scan (HostAlloc pinned "
    "pool analog). Cached buffers draw from memory.host.limitBytes; "
    "leases past the cap are transient (freed on release).", int)
PARQUET_COALESCING_TARGET = _conf(
    "sql.format.parquet.coalescing.targetBytes", 128 << 20,
    "COALESCING reader: files group until their on-disk size reaches "
    "this target; each group's files decode in parallel and upload as "
    "one batch stream (GpuParquetScan COALESCING analog).", int)
CLUSTER_EXECUTORS = _conf(
    "cluster.executors", 0,
    "Executor worker processes for host-side scan decode (the "
    "driver/executor split of Plugin.scala; 0 = in-process). The TPU "
    "client stays in the driver — executors parallelize host decode and "
    "ship Arrow IPC back; heartbeat loss requeues their tasks.", int)
CLUSTER_BLOCK_ADVERTISE_HOST = _conf(
    "cluster.blockServer.advertiseHost", "127.0.0.1",
    "Host address the shuffle block server advertises to peers in its "
    "block locations (the server itself binds 0.0.0.0, so remote "
    "executors can connect when this is set to a routable address). "
    "Default keeps the single-host topology: every executor process "
    "lives on this machine and fetches over loopback.", str)
CLUSTER_HEARTBEAT_TIMEOUT = _conf(
    "cluster.heartbeatTimeoutSeconds", 3.0,
    "Executor liveness: no heartbeat for this long marks the executor "
    "lost and re-executes its in-flight tasks "
    "(RapidsShuffleHeartbeatManager analog).", float)
FAULTS_PLAN = _conf(
    "sql.debug.faults.plan", None,
    "Deterministic fault-injection plan (runtime/faults.py): "
    "';'-separated rules `point[:selector]*[:action]` over the named "
    "fault points (block.fetch, rpc.send, executor.task, "
    "device.dispatch, exchange.map, spill.write, xla.compile). "
    "Selectors: nth=N, prob=P, seed=S, times=K, query=SUB, op=NAME; "
    "actions: raise=NAME, delay=MS, kill. Same plan + seed injects the "
    "identical failure sequence. The SRTPU_FAULTS env var installs the "
    "same grammar process-wide (spark-rapids-jni CUDA fault-injection "
    "analog). None disables with zero overhead.", str)
SHUFFLE_MAX_REGENERATIONS = _conf(
    "sql.shuffle.maxRegenerations", 2,
    "Upper bound on lineage-based shuffle regeneration rounds per "
    "distributed query: on FetchFailed/executor loss the driver "
    "re-executes only the lost map partitions on surviving executors "
    "and retries the reduce, at most this many times before the "
    "failure propagates (Spark stage-retry analog).", int)
FETCH_RETRY_MAX = _conf(
    "sql.shuffle.fetch.maxRetries", 2,
    "Transport-level retries per shuffle block fetch before the "
    "FetchFailed escalates to the driver's lineage regeneration. "
    "Retries wait exponential-backoff-with-jitter delays "
    "(runtime/backoff.py) starting at sql.shuffle.fetch.retryWaitMs.",
    int)
FETCH_RETRY_WAIT_MS = _conf(
    "sql.shuffle.fetch.retryWaitMs", 50.0,
    "Base backoff delay (ms) for shuffle block fetch retries; attempt "
    "k waits min(base * 2^k, 10s) with deterministic jitter.", float)
SERVICE_MAX_QUERY_RETRIES = _conf(
    "sql.service.maxQueryRetries", 1,
    "Transparent re-admissions of a query that failed with a "
    "classified-TRANSIENT error (runtime/faults.is_transient_error: "
    "FetchFailed, executor loss, injected faults, connection resets — "
    "never cancellation, deadline, or user errors). Each retry is a "
    "fresh admission with the ORIGINAL deadline still binding, "
    "surfaced as a query_retry event. 0 disables.", int)
DEGRADE_TO_HOST = _conf(
    "sql.exec.degradeToHost.enabled", True,
    "Graceful device->host degradation: an operator whose device "
    "kernel raises a non-OOM, non-cancellation error re-evaluates the "
    "batch on the host interpreter (exec/host_fallback path), and "
    "after two device failures on the same program stops dispatching "
    "to the device for the remainder of the query (counted as "
    "degradedToHost, event-logged as degrade_to_host, visible in "
    "EXPLAIN ANALYZE).", bool)
MAX_READER_BATCH_SIZE_ROWS = _conf(
    "sql.reader.batchSizeRows", 1 << 21,
    "Soft limit on rows per scan batch.", int)
AGG_OPTIMISTIC_GROUPS = _conf(
    "sql.agg.optimisticGroups", 4096,
    "HBM-cached grouped aggregations first try ONE fused device program "
    "whose output is sized to this many groups (plus an overflow flag); "
    "low-cardinality queries then cost a single device round trip. "
    "On overflow the exact multi-pass path re-runs. 0 disables.", int)
# (decimal128 is always-on: exact two-limb kernels in ops/decimal128.py;
# the former sql.decimal128.enabled gate had no remaining effect and was
# removed rather than shipped as a silent no-op)
LORE_DUMP_IDS = _conf(
    "sql.lore.idsToDump", None,
    "LORE ids whose input batches should be dumped for replay "
    "(analog of spark.rapids.sql.lore.idsToDumpPath).", str)
LORE_DUMP_PATH = _conf(
    "sql.lore.dumpPath", "/tmp/srtpu-lore",
    "Directory for LORE operator dumps.", str)
JOIN_BLOOM_ENABLED = _conf(
    "sql.join.bloomFilter.enabled", False,
    "Runtime bloom-filter join pruning: shuffled inner/left_semi/right "
    "equi-joins with a small scan-shaped build side run the build once "
    "into a device bloom filter and mask the stream side BEFORE its "
    "exchange (reference: GpuBloomFilterAggregate + "
    "GpuBloomFilterMightContain via InSubqueryExec runtime filters). "
    "Off by default pending broader production soak.", bool)
JOIN_BLOOM_MAX_BUILD_ROWS = _conf(
    "sql.join.bloomFilter.maxBuildRows", 4_000_000,
    "Upper bound on the ESTIMATED build-side rows for runtime "
    "bloom-filter creation (filter memory is ~1 byte/bit at 8 "
    "bits/row).", int)
DELTA_DV_ENABLED = _conf(
    "delta.deletionVectors.enabled", False,
    "DELETE writes a deletion-vector (roaring bitmap) file marking "
    "dead rows instead of rewriting the data file (reference: Delta "
    "DV support in delta-33x GpuDeltaParquetFileFormat/GpuDeleteCommand"
    "). Reads apply DVs regardless of this flag.", bool)
FILECACHE_ENABLED = _conf(
    "filecache.enabled", False,
    "Cache scan input files on local disk, keyed by (path, mtime, "
    "size) with LRU eviction — repeated scans of network-mounted "
    "inputs skip the fetch (reference: spark.rapids.filecache.enabled, "
    "GpuFileCache). Off by default: pure overhead for local inputs.",
    bool)
FILECACHE_DIR = _conf(
    "filecache.dir", "/tmp/srtpu-filecache",
    "Local directory for cached input files.", str)
FILECACHE_MAX_BYTES = _conf(
    "filecache.maxBytes", 16 << 30,
    "Upper bound on cached bytes; least-recently-used entries evict "
    "past it.", int)
CBO_ENABLED = _conf(
    "sql.optimizer.cbo.enabled", False,
    "Cost-based device-vs-host placement: tiny Project/Filter inputs "
    "the host interpreter covers run on the CPU bridge instead of "
    "paying a device dispatch (reference: CostBasedOptimizer.scala + "
    "GpuCostModel, also default-off). Decisions show in explain as "
    "'CBO: ...'.", bool)
CBO_SMALL_INPUT_ROWS = _conf(
    "sql.optimizer.cbo.smallInputRows", 64,
    "CBO small-input bound: estimated input rows at or below this run "
    "host-side when coverable.", int)
DISTINCT_AGG_REWRITE = _conf(
    "sql.optimizer.distinctAggRewrite.enabled", True,
    "Rewrite count(DISTINCT x) into a two-level hash aggregation (the "
    "single-distinct-child case of Catalyst's "
    "RewriteDistinctAggregates): an inner DISTINCT group-by over "
    "(keys..., x) then an outer Count. Both levels run the bucketed "
    "hash-aggregate pass (incl. hash-once string keying) instead of "
    "CollectAggExec's full multi-chunk lexsort.", bool)
JOIN_REORDER_ENABLED = _conf(
    "sql.optimizer.joinReorder.enabled", True,
    "Cost-based join reordering (analog of Catalyst's "
    "CostBasedJoinReorder / spark.sql.cbo.joinReorder.enabled): maximal "
    "chains of INNER equi-joins are reordered into the left-deep order "
    "minimizing estimated intermediate cardinalities, from bottom-up "
    "row/NDV estimates (sampled scan statistics, Chao1 extrapolation). "
    "Outer/semi/anti/cross joins and non-equi conditions are never "
    "reordered across. The smaller estimated side of every join lands "
    "on the build side, keeping broadcast decisions consistent.", bool)
JOIN_REORDER_DP_RELATIONS = _conf(
    "sql.optimizer.joinReorder.maxDpRelations", 8,
    "Join chains with at most this many relations are ordered by exact "
    "dynamic programming over left-deep orders (Selinger); larger "
    "chains use a greedy min-intermediate-cardinality extension "
    "(analog of spark.sql.cbo.joinReorder.dp.threshold).", int)
PYTHON_CONCURRENT_WORKERS = _conf(
    "python.concurrentPythonWorkers", 4,
    "Worker-process slots for pandas transforms (mapInPandas); "
    "acquisition blocks above it (reference: "
    "spark.rapids.python.concurrentPythonWorkers, "
    "PythonWorkerSemaphore).", int)
MESH_COMPRESS = _conf(
    "mesh.shuffle.compress", False,
    "Compress mesh-exchange round buffers ON DEVICE before the "
    "cross-shard move (byte-plane packing - the TPU-native nvcomp-LZ4 "
    "analog, NvcompLZ4CompressionCodec.scala; LZ4 itself is a "
    "sequential match chain that does not vectorize on the VPU). "
    "~4x on int-dominated payloads; incompressible buffers move raw "
    "when packing would not shrink them.", bool)
DELTA_AUTOCOMPACT_MIN_FILES = _conf(
    "delta.autoCompact.minFiles", 0,
    "When > 0, a Delta append auto-compacts once the table holds at "
    "least this many live files smaller than half the target size "
    "(reference: delta auto-compaction / "
    "GpuOptimizeWriteExchangeExec). 0 disables.", int)
DELTA_AUTOCOMPACT_TARGET_BYTES = _conf(
    "delta.autoCompact.targetBytes", 128 << 20,
    "Target output file size for Delta OPTIMIZE / auto-compaction.",
    int)
PYTHON_GROUPED_CHUNK_BYTES = _conf(
    "python.groupedChunkBytes", 64 << 20,
    "applyInPandas/aggregate-in-pandas partitions larger than this "
    "many host bytes ship to the python worker in chunks cut at GROUP "
    "boundaries (OOM-safe: a group is never split).", int)
RETRY_COVERAGE_ENABLED = _conf(
    "memory.retryCoverage.enabled", False,
    "Track, per engine call-site, whether device allocations happen "
    "inside an OOM-retry scope (with_retry / retry_no_split) — the "
    "allocations outside it are the ones that die instead of spilling "
    "(reference: AllocationRetryCoverageTracker.scala). Debug tool; "
    "report via memory.diagnostics.coverage_report().", bool)
ASYNC_WRITE_ENABLED = _conf(
    "sql.asyncWrite.enabled", True,
    "Run file-part encode + disk I/O on a writer pool off the compute "
    "thread (reference: io/async AsyncOutputStream; "
    "spark.rapids.sql.asyncWrite.queryOutput.enabled).", bool)
ASYNC_WRITE_MAX_IN_FLIGHT = _conf(
    "sql.asyncWrite.maxInFlightHostMemoryBytes", 2 << 30,
    "Upper bound on host bytes held by scheduled-but-unfinished async "
    "writes; submissions block above it (always admitting one task), "
    "so a slow disk cannot pile the query's output into host memory "
    "(reference: TrafficController).", int)
ASYNC_WRITE_THREADS = _conf(
    "sql.asyncWrite.numThreads", 4,
    "Writer-pool threads for the async write path.", int)
SORT_OOC_ENABLED = _conf(
    "sql.sort.outOfCore.enabled", True,
    "Enable out-of-core sort (range-exchange to spill files + "
    "per-partition sorts) for big inputs.", bool)
SORT_OOC_THRESHOLD = _conf(
    "sql.sort.outOfCore.thresholdBytes", 2 << 30,
    "Device bytes of sort input above which the out-of-core path "
    "activates.", int)
WINDOW_CHUNK_ROWS = _conf(
    "sql.window.chunkRows", 1 << 22,
    "Row count above which chunkable window specs (running frames + "
    "ranking over fixed-width keys) stream chunk-by-chunk through the "
    "out-of-core sort with carried per-partition state, so a window "
    "partition no longer must fit device memory (reference: "
    "GpuRunningWindowExec batched running windows). 0 disables.", int)
AGG_STRING_HASH_KEYS = _conf(
    "sql.agg.stringHashKeys.enabled", True,
    "Hash-once 64-bit keying of string group-by columns: the "
    "aggregation hash pass derives its bucket hashes from the same "
    "packed order-key chunk words the exact verify step compares "
    "(xxhash64-style fold), so string keys are read once per batch "
    "instead of twice (murmur3 walk + chunk build). Collisions stay "
    "exact — a row joins a bucket only when the chunk compare against "
    "the bucket representative passes; colliding rows retry the next "
    "round and survivors take the sort path (cudf hash-based string "
    "keying analog).", bool)
AGG_MAX_MERGE_ROWS = _conf(
    "sql.agg.maxMergeRows", 1 << 21,
    "Upper bound on buffered partial-aggregate rows merged in one "
    "concat pass. Buffered partials live in the spill store; when the "
    "total group state exceeds this, the aggregation repartitions every "
    "partial into hash buckets of disjoint keys and merges/finalizes "
    "each bucket separately — the out-of-core fallback "
    "(GpuAggregateExec.scala:863-894 repartition algorithm analog).", int)
JOIN_BUILD_BUDGET = _conf(
    "sql.join.buildSideBudgetBytes", 2 << 30,
    "When a join partition's build side exceeds this many bytes, both "
    "sides are rehashed into disjoint-key sub-partitions (spillable "
    "piles) joined one at a time, so builds bigger than device memory "
    "complete instead of dying (GpuSubPartitionHashJoin.scala:617 "
    "analog). 0 disables.", int)
BROADCAST_THRESHOLD = _conf(
    "sql.autoBroadcastJoinThreshold", 10 * 1024 * 1024,
    "Build sides estimated at or below this many bytes use a broadcast "
    "hash join (build collected once, no exchange); larger builds "
    "shuffle both sides on the join keys and join per partition "
    "(analog of spark.sql.autoBroadcastJoinThreshold + the reference's "
    "useSizedJoin decision). -1 disables broadcast.", int)
MESH_DEVICES = _conf(
    "mesh.devices", 0,
    "Number of devices in the SPMD execution mesh. When > 0, hash "
    "exchanges run as one all_to_all collective over ICI "
    "(jax.sharding.Mesh) instead of the host file shuffle — the TPU-pod "
    "analog of the reference's UCX shuffle mode. 0 disables (single-chip "
    "+ host shuffle).", int)
SPMD_STAGE_ENABLED = _conf(
    "mesh.spmdStage.enabled", True,
    "Fuse a mesh exchange with its consumer (final hash aggregate, "
    "fusable filter/project chain, co-partitioned join input) into ONE "
    "shard_map program per stage: partition ids, the all_to_all "
    "collective, and the consumer run inside the same jitted program — "
    "no per-round host sync and no spill-handle park/unpark between "
    "exchange and consumer. Stages whose staged working set exceeds "
    "mesh.spmdStage.maxBytes (and any stage hit by a mesh.collective "
    "fault) fall back to the streaming round-based exchange.", bool)
SPMD_STAGE_MAX_BYTES = _conf(
    "mesh.spmdStage.maxBytes", 256 << 20,
    "Working-set budget for a fused SPMD stage: the stage drains its "
    "map side first, and when the staged bytes exceed this the stage "
    "degrades to the bounded-memory round-based exchange instead of "
    "materializing everything into one collective round (the bounce-"
    "buffer memory model keeps peak HBM at O(devices * round) there).",
    int)
SPMD_RESHARD_ENABLED = _conf(
    "mesh.spmdStage.reshard.enabled", True,
    "AQE mesh analog of partition coalescing: after the map side of a "
    "fused SPMD stage materializes, shrink the ACTIVE mesh axis for "
    "small stages (partition ids drawn mod n_active < n_devices) so "
    "tiny reduce states do not shard 8 ways; trailing shards receive "
    "nothing and emit no batches. Decided from exact staged byte "
    "stats, recorded as an aqe_replan decision.", bool)
SPMD_RESHARD_MIN_BYTES = _conf(
    "mesh.spmdStage.reshard.minBytesPerShard", 1 << 20,
    "Target minimum staged bytes per active shard for the AQE mesh "
    "re-shard rule: the active axis halves until each remaining shard "
    "would see at least this many bytes (or one shard remains).", int)
SERVICE_QUERY_TIMEOUT_SECS = _conf(
    "sql.service.queryTimeoutSecs", 0.0,
    "Wall-clock deadline per query, measured from submission (queue "
    "time counts). Past it the query's CancelToken trips and the next "
    "cooperative checkpoint (batch/stage/shuffle boundary, semaphore "
    "wait) raises QueryTimedOut; queued queries past their deadline "
    "are killed without ever being admitted. 0 = no deadline.", float)
SERVICE_SCHEDULER_MODE = _conf(
    "sql.service.scheduler.mode", "fair",
    "Cross-query scheduling policy: 'fair' (deficit-round-robin across "
    "weighted pools, FIFO within a pool — the Spark fair-scheduler "
    "analog) or 'fifo' (global submission order, pools ignored).", str)
SERVICE_SCHEDULER_POOLS = _conf(
    "sql.service.scheduler.pools", "default:1",
    "Weighted scheduler pools as 'name:weight,name:weight,...'. Under "
    "saturation a pool's admission share is proportional to its "
    "weight; a query picks its pool via sql.service.pool (unknown "
    "pool names are created on the fly with weight 1).", str)
SERVICE_POOL = _conf(
    "sql.service.pool", "default",
    "Scheduler pool this session's queries submit into (the "
    "spark.scheduler.pool analog). Pool weight also becomes the "
    "TpuSemaphore acquire priority, so heavier pools win device "
    "admission ties.", str)
SERVICE_MAX_CONCURRENT = _conf(
    "sql.service.maxConcurrentQueries", 4,
    "Upper bound on queries RUNNING concurrently in one engine "
    "process; further admitted work queues in the scheduler. Distinct "
    "from sql.concurrentTpuTasks, which bounds tasks on the chip "
    "within the already-admitted queries.", int)
SERVICE_ADMISSION_ENABLED = _conf(
    "sql.service.admission.enabled", True,
    "Memory-aware admission control: a query is only admitted when "
    "its plan-derived device+host estimate fits alongside the "
    "already-admitted queries' estimates (scan sizes + join build "
    "sides from the planner's cardinality estimator). Queries whose "
    "solo estimate exceeds the budget still run — alone.", bool)
SERVICE_ADMISSION_DEVICE_FRACTION = _conf(
    "sql.service.admission.deviceFraction", 0.8,
    "Fraction of the DeviceManager budget the admission controller "
    "hands out to concurrently admitted query estimates.", float)
SERVICE_ADMISSION_HOST_FRACTION = _conf(
    "sql.service.admission.hostFraction", 0.8,
    "Fraction of the HostMemoryManager budget admission may commit "
    "(ignored while the host budget is unlimited).", float)
SERVICE_ADMISSION_DEVICE_LIMIT = _conf(
    "sql.service.admission.deviceLimitBytes", 0,
    "Explicit admission byte budget for device estimates; overrides "
    "deviceFraction * DeviceManager budget when > 0.", int,
    internal=True)
FLEET_DIRECTORY = _conf(
    "sql.fleet.directory", None,
    "Root directory of the fleet peer registry (fleet/directory.py). "
    "When set, serve() joins the multi-host serving fabric: register "
    "in the directory, start the peer cache server, pull warm state "
    "from the longest-lived peer, and consult peers on result-cache "
    "misses. Unset (the default) disables the fleet entirely.", str)
FLEET_ADVERTISE_HOST = _conf(
    "sql.fleet.advertiseHost", "127.0.0.1",
    "Host peers use to reach this member's peer cache server (the "
    "address written into the peer directory). Single-box fleets keep "
    "the loopback default; multi-host deployments set the reachable "
    "interface.", str)
FLEET_CONSULT_FANOUT = _conf(
    "sql.fleet.consultFanout", 2,
    "How many rendezvous-ordered peers a result-cache miss probes "
    "before recomputing locally. 1 asks only the key's owner; higher "
    "values tolerate membership churn (an entry published before a "
    "join may live one step down the preference order) at the cost of "
    "extra round trips on a true fleet-wide miss.", int)
FLEET_FETCH_TIMEOUT_SECS = _conf(
    "sql.fleet.fetchTimeoutSecs", 5.0,
    "Socket timeout per peer-cache request (connect + transfer). A "
    "peer slower than this is treated as a miss after the bounded "
    "retries — recomputing locally is always sound.", float)
FLEET_FETCH_RETRIES = _conf(
    "sql.fleet.fetchRetries", 2,
    "Transient-failure retries per peer-cache fetch, on "
    "deterministic-jitter backoff (runtime/backoff.py). Structural "
    "failures (protocol violations) never retry.", int)
FLEET_FETCH_BACKOFF_MS = _conf(
    "sql.fleet.fetchBackoffMs", 20.0,
    "Base backoff between peer-cache fetch retries; doubles per "
    "attempt with deterministic jitter seeded per (peer, verb).",
    float)
FLEET_INVALIDATE_RETRIES = _conf(
    "sql.fleet.invalidateRetries", 1,
    "Retries per peer when broadcasting a cache invalidation. "
    "Deliveries are best-effort by design — a peer that misses the "
    "broadcast holds entries under keys no requester will compute "
    "again (keys embed scan snapshots), and the requester-side "
    "snapshot re-stat rejects the race window.", int)
FLEET_EXPORT_MAX_BYTES = _conf(
    "sql.fleet.exportMaxBytes", 256 << 20,
    "Byte budget for the export store — the LRU index of locally "
    "computed results a member serves to peers. Held by reference to "
    "the result cache's own immutable tables, so this bounds the "
    "index's ability to pin evicted entries alive, not a second copy.",
    int)
FLEET_WARM_PULL = _conf(
    "sql.fleet.warmPull", True,
    "Cold-join warm-state publication: pull the warm-pack manifest "
    "and calibration table from the longest-lived live peer at join "
    "and replay it through the background compile pool, so a fresh "
    "process reaches steady-state latency within its first few "
    "queries. Advisory — any failure serves cold.", bool)
FLEET_TENANT_MAX_INFLIGHT = _conf(
    "sql.fleet.tenantMaxInflight", 0,
    "Fleet-wide cap on one tenant's in-flight routed queries (the "
    "route verb's admission control); a tenant at its cap gets "
    "rejected leases until it completes work. 0 = unlimited.", int)
FLEET_PEER_MAX_INFLIGHT = _conf(
    "sql.fleet.peerMaxInflight", 0,
    "Per-peer in-flight ceiling for the router: past it, a query "
    "spills to the next peer in its fingerprint's rendezvous order "
    "(stable, so overflow lands warm too). When every peer is "
    "saturated the sticky choice queues rather than spill cold. "
    "0 = unlimited (always sticky).", int)
LOCKDEP_ENABLED = _conf(
    "sql.debug.lockdep.enabled", False,
    "Runtime lockdep witness (runtime/lockdep.py): wrap engine locks, "
    "the TpuSemaphore permit and exchange ride slot, record the "
    "acquisition-order graph, and report lock-order cycles at edge "
    "FORMATION time plus bounded-pool self-waits. Deadline kills "
    "attach an all-threads held-resource dump to QueryTimedOut and "
    "the event log. Locks created before the session exist are only "
    "covered when env SRTPU_LOCKDEP=1 was set before import. Debug "
    "tool; overhead is small (<3% on the test suite) but nonzero.",
    bool)
LOCKDEP_RAISE = _conf(
    "sql.debug.lockdep.raiseOnCycle", True,
    "With lockdep enabled: raise LockOrderViolation/PoolSelfWait at "
    "the acquisition that forms the cycle (fail fast, the kernel-"
    "lockdep behavior). False records findings for the "
    "concurrency_report event without raising.", bool)
LEDGER_ENABLED = _conf(
    "sql.debug.ledger.enabled", False,
    "Runtime resource ledger (runtime/ledger.py): count every "
    "acquire/release of device/host reservations, staging leases, "
    "spill handles, shuffle pins, semaphore permits, ride slots and "
    "result-cache charges, attribute them to the submitting query, "
    "and assert owner-scoped kinds balance at every terminal state "
    "(FINISHED, CANCELLED, TIMED_OUT alike). Deadline kills and "
    "budget-exhaustion errors attach an outstanding-holders dump "
    "(kind, site, thread, query) next to the lockdep dump, and every "
    "profiled query emits a resource_ledger event. Acquisitions made "
    "before the session exist are only covered when env SRTPU_LEDGER=1 "
    "was set first. Debug tool; overhead <5% on the test suite.",
    bool)
LEDGER_RAISE = _conf(
    "sql.debug.ledger.raiseOnImbalance", True,
    "With the ledger enabled: raise ResourceLeakError when a query "
    "finishes cleanly with owner-scoped resources outstanding (fail "
    "fast). False records findings for the resource_ledger event "
    "without raising; error-path imbalances are always recorded, "
    "never raised over the original error.", bool)
LEDGER_POISON = _conf(
    "sql.debug.ledger.poison", False,
    "With the ledger enabled: fill released cached staging buffers "
    "with 0xAB before they return to the pool free list, turning "
    "latent use-after-release reads (the PR 4 corruption class) into "
    "deterministic garbage instead of data-dependent flakes. Debug "
    "mode: adds a memset per lease release.", bool)
RACEDEP_ENABLED = _conf(
    "sql.debug.racedep.enabled", False,
    "Runtime data-race witness (runtime/racedep.py): Eraser-style "
    "lockset tracking on instrumented shared structures (program "
    "cache observed table, telemetry registry, result-cache LRU, "
    "shuffle map-file slots, operator metric sets), recording "
    "(thread, lockset) per access and reporting when a shared slot's "
    "candidate lockset collapses to empty. Locks created before the "
    "session exist are only lockset-visible when env SRTPU_RACEDEP=1 "
    "was set before import. Debug tool; overhead is small (<3% on "
    "instrumented query paths) but nonzero.", bool)
RACEDEP_RAISE = _conf(
    "sql.debug.racedep.raiseOnRace", True,
    "With racedep enabled: raise DataRaceDetected at the access that "
    "collapses a shared slot's lockset (fail fast). False records "
    "findings for the race_report event without raising.", bool)


class TpuConf:
    """Immutable-ish snapshot of settings, resolved against the registry."""

    def __init__(self, settings: Optional[Dict[str, Any]] = None):
        self._settings = dict(settings or {})

    def get(self, entry: ConfEntry):
        return entry.get(self)

    def is_set(self, entry: ConfEntry) -> bool:
        """Whether the user supplied this key (vs the registry default).
        Lets auto policies defer to an explicit setting."""
        return entry.key in self._settings

    def set(self, key: str, value) -> "TpuConf":
        s = dict(self._settings)
        s[key] = value
        return TpuConf(s)

    # Convenience accessors used across the engine.
    @property
    def batch_size_rows(self):
        return self.get(BATCH_SIZE_ROWS)

    @property
    def shuffle_partitions(self):
        return self.get(SHUFFLE_PARTITIONS)

    @property
    def concurrent_tasks(self):
        return self.get(CONCURRENT_TASKS)

    @property
    def explain(self):
        return self.get(EXPLAIN).upper()

    @property
    def allow_cpu_fallback(self):
        return self.get(ALLOW_CPU_FALLBACK)


def generate_docs() -> str:
    """Emit configs.md content (the reference generates docs/configs.md
    from RapidsConf the same way)."""
    lines = ["# spark-rapids-tpu configuration", "",
             "Name | Description | Default", "-----|-------------|--------"]
    for key in sorted(REGISTRY):
        e = REGISTRY[key]
        if e.internal:
            continue
        lines.append(f"{e.key} | {e.doc} | {e.default}")
    return "\n".join(lines) + "\n"

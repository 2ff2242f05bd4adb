"""Typed, self-documenting configuration registry.

Reference parity: sql-plugin RapidsConf.scala (ConfBuilder/TypedConfBuilder/
ConfEntry registry with defaults, validators, doc strings and markdown doc
generation, RapidsConf.scala:116-237; ~60 `spark.rapids.*` keys).

Keys here use the `rapids.tpu.*` prefix. Per-operator enable keys are
generated automatically by the plan-rewrite rule registry
(see spark_rapids_tpu/plan/overrides.py, reference GpuOverrides.scala:125-130).
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Dict, List, Optional


class ConfEntry:
    """One registered configuration key (reference: ConfEntry, RapidsConf.scala:116)."""

    def __init__(
        self,
        key: str,
        converter: Callable[[str], Any],
        doc: str,
        default: Any,
        is_internal: bool = False,
        checker: Optional[Callable[[Any], Optional[str]]] = None,
    ):
        self.key = key
        self.converter = converter
        self.doc = doc
        self.default = default
        self.is_internal = is_internal
        self.checker = checker

    def get(self, settings: Dict[str, Any]) -> Any:
        if self.key in settings:
            raw = settings[self.key]
            value = self.converter(raw) if isinstance(raw, str) else raw
        else:
            value = self.default
        if self.checker is not None and value is not None:
            err = self.checker(value)
            if err:
                raise ValueError(f"invalid value for {self.key}: {err}")
        return value

    def help_string(self) -> str:
        return f"{self.key} — {self.doc} (default: {self.default})"


def _to_bool(s: str) -> bool:
    if isinstance(s, bool):
        return s
    low = s.strip().lower()
    if low in ("true", "1", "yes", "on"):
        return True
    if low in ("false", "0", "no", "off"):
        return False
    raise ValueError(f"cannot parse boolean: {s!r}")


def _to_bytes(s: str) -> int:
    """Parse '512m', '1g', '64k', plain ints."""
    if isinstance(s, int):
        return s
    s = s.strip().lower()
    mult = 1
    for suffix, m in (("k", 1 << 10), ("m", 1 << 20), ("g", 1 << 30), ("t", 1 << 40)):
        if s.endswith(suffix):
            mult = m
            s = s[: -len(suffix)]
            break
    return int(float(s) * mult)


class _Builder:
    """Fluent builder (reference: ConfBuilder/TypedConfBuilder, RapidsConf.scala:116-237)."""

    def __init__(self, registry: "ConfRegistry", key: str):
        self._registry = registry
        self._key = key
        self._doc = ""
        self._internal = False
        self._checker: Optional[Callable[[Any], Optional[str]]] = None

    def doc(self, text: str) -> "_Builder":
        self._doc = text
        return self

    def internal(self) -> "_Builder":
        self._internal = True
        return self

    def check(self, fn: Callable[[Any], Optional[str]]) -> "_Builder":
        self._checker = fn
        return self

    def _create(self, converter, default) -> ConfEntry:
        entry = ConfEntry(
            self._key, converter, self._doc, default, self._internal, self._checker
        )
        self._registry.register(entry)
        return entry

    def boolean(self, default: bool) -> ConfEntry:
        return self._create(_to_bool, default)

    def integer(self, default: int) -> ConfEntry:
        return self._create(int, default)

    def double(self, default: float) -> ConfEntry:
        return self._create(float, default)

    def string(self, default: Optional[str]) -> ConfEntry:
        return self._create(str, default)

    def bytes(self, default: int) -> ConfEntry:
        return self._create(_to_bytes, default)


class ConfRegistry:
    def __init__(self):
        self._entries: Dict[str, ConfEntry] = {}
        self._lock = threading.Lock()

    def conf(self, key: str) -> _Builder:
        return _Builder(self, key)

    def register(self, entry: ConfEntry) -> None:
        with self._lock:
            if entry.key in self._entries:
                raise ValueError(f"duplicate conf key {entry.key}")
            self._entries[entry.key] = entry

    def register_dynamic(self, key: str, doc: str, default: Any, converter=_to_bool) -> ConfEntry:
        """Register an auto-generated per-operator enable key if absent.

        Reference: ReplacementRule.confKey, GpuOverrides.scala:125-130.
        """
        with self._lock:
            if key in self._entries:
                return self._entries[key]
            entry = ConfEntry(key, converter, doc, default)
            self._entries[key] = entry
            return entry

    def entries(self) -> List[ConfEntry]:
        return sorted(self._entries.values(), key=lambda e: e.key)

    def get(self, key: str) -> Optional[ConfEntry]:
        return self._entries.get(key)


REGISTRY = ConfRegistry()
_conf = REGISTRY.conf

# ---------------------------------------------------------------------------
# Core enables (reference: RapidsConf.scala SQL_ENABLED etc.)
# ---------------------------------------------------------------------------
SQL_ENABLED = _conf("rapids.tpu.sql.enabled").doc(
    "Enable the TPU columnar plan rewrite; when false every operator runs on "
    "the CPU oracle path."
).boolean(True)

EXPLAIN = _conf("rapids.tpu.sql.explain").doc(
    "Explain the plan rewrite: NONE, NOT_ON_TPU (only fallback reasons), or ALL."
).check(
    lambda v: None if v in ("NONE", "NOT_ON_TPU", "ALL") else "must be NONE|NOT_ON_TPU|ALL"
).string("NONE")

INCOMPATIBLE_OPS = _conf("rapids.tpu.sql.incompatibleOps.enabled").doc(
    "Enable operators that produce results that differ in corner cases from "
    "the CPU (float ordering, f64-as-f32 on TPU, timezone restrictions)."
).boolean(False)

HAS_NANS = _conf("rapids.tpu.sql.hasNans").doc(
    "Assume floating point data may contain NaNs (affects agg/join support tagging)."
).boolean(True)

TEST_ENABLED = _conf("rapids.tpu.sql.test.enabled").doc(
    "Strict test mode: assert every operator in the plan ran on the TPU "
    "(reference: spark.rapids.sql.test.enabled, GpuTransitionOverrides.scala:211-260)."
).internal().boolean(False)

TEST_ALLOWED_NON_TPU = _conf("rapids.tpu.sql.test.allowedNonTpu").doc(
    "Comma separated exec/expression class names allowed to stay on CPU in "
    "strict test mode (reference: spark.rapids.sql.test.allowedNonGpu)."
).internal().string("")

# ---------------------------------------------------------------------------
# Memory (reference: RapidsConf.scala:241-322)
# ---------------------------------------------------------------------------
MEMORY_FRACTION = _conf("rapids.tpu.memory.hbm.allocFraction").doc(
    "Fraction of usable HBM the framework budgets for columnar batches; the "
    "memory manager preemptively spills below this watermark (reference: "
    "spark.rapids.memory.gpu.allocFraction=0.9, GpuDeviceManager.scala:152-198)."
).check(lambda v: None if 0.0 < v <= 1.0 else "must be in (0,1]").double(0.8)

HBM_SIZE_OVERRIDE = _conf("rapids.tpu.memory.hbm.sizeOverride").doc(
    "Override detected HBM size in bytes (0 = autodetect via device memory stats)."
).bytes(0)

HOST_SPILL_STORAGE_SIZE = _conf("rapids.tpu.memory.host.spillStorageSize").doc(
    "Bound on the host staging tier before buffers overflow to disk "
    "(reference: spark.rapids.memory.host.spillStorageSize, RapidsHostMemoryStore)."
).bytes(1 << 30)

PINNED_POOL_SIZE = _conf("rapids.tpu.memory.pinnedPool.size").doc(
    "Size of the aligned host staging pool used for host<->HBM transfers "
    "(reference: spark.rapids.memory.pinnedPool.size, GpuDeviceManager.scala:200-206)."
).bytes(256 << 20)

SPILL_DIR = _conf("rapids.tpu.memory.spill.dir").doc(
    "Local directory for the disk spill tier (reference: RapidsDiskBlockManager)."
).string("")

MEMORY_DEBUG = _conf("rapids.tpu.memory.debug").doc(
    "Log every tracked device allocation/free (reference: spark.rapids.memory.gpu.debug)."
).boolean(False)

CONCURRENT_TPU_TASKS = _conf("rapids.tpu.concurrentTpuTasks").doc(
    "Number of tasks that may hold the per-chip admission semaphore at once "
    "(reference: spark.rapids.sql.concurrentGpuTasks=2, GpuSemaphore.scala)."
).check(lambda v: None if v >= 1 else "must be >= 1").integer(2)

# ---------------------------------------------------------------------------
# Batch sizing (reference: RapidsConf.scala:309-322)
# ---------------------------------------------------------------------------
BATCH_SIZE_BYTES = _conf("rapids.tpu.sql.batchSizeBytes").doc(
    "Target size in bytes of coalesced columnar batches "
    "(reference: spark.rapids.sql.batchSizeBytes, GpuCoalesceBatches). "
    "Also the size of a device-cached relation's batches: DataFrame.cache() "
    "gathers what its plan hands over into batches of the largest capacity "
    "bucket that stays inside it (exec/cache.py)."
).bytes(512 << 20)

MAX_READ_BATCH_SIZE_ROWS = _conf("rapids.tpu.sql.reader.batchSizeRows").doc(
    "Max rows per batch produced by file readers "
    "(reference: spark.rapids.sql.reader.batchSizeRows, GpuParquetScan.scala:571-605)."
).integer(1 << 20)

MAX_READ_BATCH_SIZE_BYTES = _conf("rapids.tpu.sql.reader.batchSizeBytes").doc(
    "Max bytes per batch produced by file readers."
).bytes(512 << 20)

IO_PREFETCH_BATCHES = _conf("rapids.tpu.io.prefetchBatches").doc(
    "Scan decode double-buffering depth: how many host-decoded batches a "
    "file scan stages AHEAD of the consumer on a background reader thread, "
    "so batch k+1 decodes (and its upload can issue) while batch k "
    "computes (docs/async-execution.md). 0 disables prefetch (decode "
    "inline on the consumer thread); with depth k up to (2 + k) decoded "
    "batches are live per scan task (the consumer's, the reader's "
    "in-hand one, and k queued) — the resource analyzer charges "
    "scan-leaf peak HBM accordingly."
).check(lambda v: None if 0 <= v <= 16 else "must be in [0,16]").integer(1)

# ---------------------------------------------------------------------------
# Per-format / per-feature enables (reference: RapidsConf.scala:433-469)
# ---------------------------------------------------------------------------
PARQUET_READ_ENABLED = _conf("rapids.tpu.sql.format.parquet.read.enabled").boolean(True)
PARQUET_DEVICE_DECODE = _conf(
    "rapids.tpu.sql.format.parquet.deviceDecode.enabled").doc(
    "Decode parquet STRING columns ON the device: the raw (decompressed) "
    "BYTE_ARRAY chunk uploads, jitted kernels expand the definition-level "
    "and dictionary-index runs, and the column comes out as dictionary "
    "codes + dictionary where rapids.tpu.sql.encoded.* admits it "
    "(reference decodes on the accelerator, GpuParquetScan.scala:536-556). "
    "Strings only: every fixed-width column (ints, floats, bools, dates, "
    "timestamps, decimals) is decoded by Arrow on the host, one threaded "
    "read a split, and uploaded — on a TPU v5e that read ran 1.8x (TPC-H "
    "Q6) and 1.4x (a parquet write) ahead of a device decode of the same "
    "columns (PERF.md). Off: strings take Arrow too and arrive decoded. "
    "Pages the decoder refuses fall back to Arrow for the split."
).boolean(True)
PARQUET_WRITE_ENABLED = _conf("rapids.tpu.sql.format.parquet.write.enabled").boolean(True)
PARQUET_DEVICE_ENCODE = _conf(
    "rapids.tpu.sql.format.parquet.deviceEncode.enabled").doc(
    "Encode parquet ON the device (reference encodes on the accelerator, "
    "ColumnarOutputWriter.scala:62-177): non-null values compact (strings "
    "via a length-prefixing byte gather, booleans bit-pack) and validity "
    "bit-packs in jitted kernels per column; only the encoded PLAIN page "
    "payload downloads, then the host block-compresses pages "
    "(none/snappy/gzip/zstd — the mirror of the decode split). Applies "
    "to flat schemas (incl. the snappy DEFAULT write) without "
    "partitionBy; other codecs/nested types use the host Arrow writer."
).boolean(True)
CSV_READ_ENABLED = _conf("rapids.tpu.sql.format.csv.read.enabled").boolean(True)
CSV_DEVICE_PARSE = _conf(
    "rapids.tpu.sql.format.csv.deviceParse.enabled").doc(
    "Parse eligible CSV columns ON the device: the host finds field "
    "boundaries in one vectorized pass (quote-aware), raw bytes + offsets "
    "upload once, and jitted kernels fold the values — integers, floats, "
    "strings, dates, and zoned timestamps, including quoted fields and "
    "escaped \"\" quotes (unescaped in the host control plane before "
    "upload; reference parses CSV on the accelerator the same way, "
    "GpuBatchScanExec.scala:474-502). Ragged files fall back to the host "
    "Arrow parser."
).boolean(True)
CSV_DEVICE_MAX_SPLIT_BYTES = _conf(
    "rapids.tpu.sql.format.csv.deviceParse.maxSplitBytes").doc(
    "Largest CSV split the device parser will load whole into host memory "
    "(the boundary plan builds rows*cols int32 tables before value "
    "eligibility is known, so a near-2GiB split would cost several GiB of "
    "host RAM); bigger splits use the streaming host Arrow reader "
    "(reference bounds CSV reads with line-aligned chunks the same way, "
    "GpuBatchScanExec.scala:322-520)."
).bytes(256 << 20)
ORC_READ_ENABLED = _conf("rapids.tpu.sql.format.orc.read.enabled").boolean(True)
ORC_DEVICE_DECODE = _conf(
    "rapids.tpu.sql.format.orc.deviceDecode.enabled").doc(
    "Decode eligible ORC columns ON the device: the host walks the "
    "protobuf metadata and RLEv2/byte-RLE run headers (all four RLEv2 "
    "sub-encodings incl. PATCHED_BASE, widths <= 56 bits), raw stripe "
    "bytes upload once (zlib/snappy/zstd blocks host-decompressed "
    "first), and jitted kernels expand the runs — integers, strings "
    "(DIRECT_V2 + DICTIONARY_V2), floats, timestamps, and booleans — the "
    "reference decodes ORC on the accelerator the same way "
    "(GpuOrcScan.scala:284,709). LZO/LZ4 (no per-block decompressed size "
    "for Arrow's raw codec) and nested types fall back to the host Arrow "
    "reader."
).boolean(True)
ORC_WRITE_ENABLED = _conf("rapids.tpu.sql.format.orc.write.enabled").boolean(True)
ORC_DEVICE_ENCODE = _conf(
    "rapids.tpu.sql.format.orc.deviceEncode.enabled").doc(
    "Encode ORC ON the device (reference encodes on the accelerator, "
    "GpuOrcFileFormat.scala / ColumnarOutputWriter.scala:62-177): "
    "non-null values compact, zigzag-encode and bit-pack into the RLEv2 "
    "DIRECT payload (strings via a byte gather + RLEv2 LENGTH stream, "
    "floats/bools as raw/bit streams) in jitted kernels per column; only "
    "the encoded stream payload downloads, then the host block-compresses "
    "in ORC framing (none/zlib/snappy). Applies to flat schemas without "
    "partitionBy; decimal/nested types use the host Arrow writer."
).boolean(True)

ENABLE_FLOAT_AGG = _conf("rapids.tpu.sql.variableFloatAgg.enabled").doc(
    "Allow float aggregations whose result can vary with evaluation order "
    "(reference: spark.rapids.sql.variableFloatAgg.enabled)."
).boolean(True)

ENABLE_INT64_NARROWING = _conf("rapids.tpu.sql.int64.narrowing.enabled").doc(
    "Let device kernels compute logically-int64 expressions in int32 lanes "
    "when column value-range metadata proves the result is identical "
    "(ranges come from upload-time min/max and parquet footer statistics). "
    "XLA emulates int64 on TPU as 32-bit pairs at a measured ~9.8x cost "
    "(docs/tuning-guide.md 'int64 on TPU'); narrowing removes that cost "
    "for in-range data with no semantic change. SQL results, hashes, and "
    "stored batches are unaffected — this only changes in-kernel compute "
    "width where exactness is provable."
).boolean(True)

ENABLE_CAST_FLOAT_TO_STRING = _conf(
    "rapids.tpu.sql.castFloatToString.enabled").doc(
    "Enable the device float->STRING cast (reference: "
    "spark.rapids.sql.castFloatToString.enabled). Output follows this "
    "framework's shortest-round-trip convention (Java-style notation; "
    "parse-back-exact for all normal doubles and every float32 under "
    "this framework's own string->float parser and for correctly-"
    "rounded parsers; subnormal doubles "
    "may differ in the last digit), NOT Java's Ryu output — the "
    "reference marks the direction incompatible for the same reason. "
    "Needs an f64-capable backend; otherwise the cast stays on the CPU "
    "engine.").boolean(False)
ENABLE_CAST_STRING_TO_FLOAT = _conf(
    "rapids.tpu.sql.castStringToFloat.enabled").doc(
    "Enable the device STRING->float cast (reference: "
    "spark.rapids.sql.castStringToFloat.enabled). Grammar: optional "
    "sign, decimal with optional <=3-digit exponent, inf/infinity/nan "
    "(case-insensitive), <=48 chars after ASCII-whitespace trim; the "
    "17-digit mantissa fold scales through error-free pair arithmetic, "
    "so normal-range results match a correctly-rounded strtod (further "
    "digits only shift the exponent; subnormal results flush on "
    "accelerator backends). Unparseable strings are NULL (ANSI: error). "
    "Host and device produce bit-identical values. Needs an f64-capable "
    "backend.").boolean(False)
ENABLE_CAST_STRING_TO_TIMESTAMP = _conf(
    "rapids.tpu.sql.castStringToTimestamp.enabled").doc(
    "Enable the device STRING->TIMESTAMP cast (reference: "
    "spark.rapids.sql.castStringToTimestamp.enabled). Grammar: "
    "'YYYY-MM-DD' or 'YYYY-MM-DD[ T]HH:MM:SS[.f{1,6}][Z|+-HH:MM]' "
    "after trim; naive timestamps are UTC; invalid civil dates are "
    "NULL (ANSI: error). Pure integer math — exact on every "
    "backend.").boolean(False)

IMPROVED_TIME_OPS = _conf("rapids.tpu.sql.improvedTimeOps.enabled").doc(
    "Enable datetime ops whose range/overflow behavior differs slightly from CPU "
    "(reference: spark.rapids.sql.improvedTimeOps.enabled, RapidsConf.scala:342)."
).boolean(False)

HASH_OPTIMIZE_SORT = _conf("rapids.tpu.sql.hashOptimizeSort.enabled").doc(
    "Insert a sort after hash-based operators (aggregate, shuffled join) "
    "whose output feeds a file write, so rows with equal keys cluster and "
    "the written files compress/size better (reference: "
    "spark.rapids.sql.hashOptimizeSort.enabled, "
    "GpuTransitionOverrides.scala:171-204)."
).boolean(False)

REPLACE_SORT_MERGE_JOIN = _conf("rapids.tpu.sql.replaceSortMergeJoin.enabled").doc(
    "Replace sort-merge joins with TPU hash joins "
    "(reference: spark.rapids.sql.replaceSortMergeJoin.enabled, RapidsConf.scala:382)."
).boolean(True)

EXPORT_COLUMNAR_RDD = _conf("rapids.tpu.sql.exportColumnarRdd").doc(
    "Allow extracting device-resident columnar data from a plan for external ML "
    "(reference: spark.rapids.sql.exportColumnarRdd, ColumnarRdd.scala)."
).boolean(False)

# ---------------------------------------------------------------------------
# Shuffle (reference: RapidsConf.scala:520-596)
# ---------------------------------------------------------------------------
SHUFFLE_MANAGER_ENABLED = _conf("rapids.tpu.shuffle.manager.enabled").doc(
    "Enable the accelerated shuffle manager that keeps shuffle partitions "
    "device-resident and moves them over the transport "
    "(reference: spark.shuffle.manager=RapidsShuffleManager)."
).boolean(False)

SHUFFLE_TRANSPORT_CLASS = _conf("rapids.tpu.shuffle.transport.class").doc(
    "Fully qualified class of the shuffle transport (reference: "
    "spark.rapids.shuffle.transport.class; default is the in-process transport, "
    "ICI collective transport used under a multi-device mesh)."
).string("spark_rapids_tpu.parallel.transport.LocalShuffleTransport")

SHUFFLE_MODE = _conf("rapids.tpu.shuffle.mode").doc(
    "Shuffle data plane: 'inprocess' keeps pieces device-resident within the "
    "process (reference: RapidsShuffleInternalManager device store tier); "
    "'ici' lowers hash exchanges onto a jitted shard_map + lax.all_to_all "
    "over the session device mesh (the ICI collective replacement for the "
    "reference's UCX peer-to-peer transport, UCXShuffleTransport.scala:47-507)."
).check(lambda v: None if v in ("inprocess", "ici")
        else "must be inprocess|ici").string("inprocess")

ADAPTIVE_COALESCE = _conf(
    "rapids.tpu.sql.adaptive.coalescePartitions.enabled").doc(
    "After the shuffle map stage, merge small contiguous reduce buckets "
    "until each task holds ~advisoryPartitionSizeBytes (the Spark AQE "
    "CoalesceShufflePartitions role). Exchanges feeding a shuffled join "
    "never coalesce: both join inputs must keep identical grouping."
).boolean(True)
ADAPTIVE_TARGET_BYTES = _conf(
    "rapids.tpu.sql.adaptive.advisoryPartitionSizeBytes").doc(
    "Target bytes per post-shuffle task when adaptive coalescing is on "
    "(Spark's spark.sql.adaptive.advisoryPartitionSizeInBytes analog)."
).integer(16 << 20)

# ---------------------------------------------------------------------------
# Adaptive query execution (spark_rapids_tpu/aqe/,
# docs/adaptive-execution.md)
# ---------------------------------------------------------------------------
ADAPTIVE_ENABLED = _conf("rapids.tpu.sql.adaptive.enabled").doc(
    "Runtime re-optimization at shuffle-stage boundaries (the Spark AQE "
    "role the reference plugin runs under): a TpuAdaptiveExec wrapper "
    "materializes each exchange as a query stage, collects per-bucket "
    "MapOutputStats from host-known piece metadata (zero extra device "
    "syncs), and re-runs rule passes over the not-yet-executed remainder "
    "— skew-split, broadcast join demotion/promotion, and unified "
    "partition coalescing — with every rewritten remainder re-verified "
    "and re-analyzed against the MEASURED sizes (metrics: aqeReplans / "
    "skewSplits / joinDemotions / joinPromotions). Off (default): every "
    "plan decision stays frozen at plan time exactly as before."
).boolean(False)

ADAPTIVE_JOIN_STRATEGY = _conf(
    "rapids.tpu.sql.adaptive.joinStrategy.enabled").doc(
    "Under adaptive execution, rewrite join strategies from MEASURED "
    "build sizes: a shuffled hash join whose materialized build side "
    "fits autoBroadcastJoinThreshold demotes to a broadcast join (the "
    "stream side's not-yet-executed exchange is elided entirely), and a "
    "statically-planned broadcast join whose build subtree measured past "
    "the threshold (a blown plan-time estimate) promotes back to the "
    "shuffled form."
).boolean(True)

SKEW_JOIN_ENABLED = _conf("rapids.tpu.sql.adaptive.skewJoin.enabled").doc(
    "Under adaptive execution, split an oversized reduce bucket of a "
    "shuffled join's STREAM input into contiguous piece-range "
    "sub-partitions, replicating the build-side bucket opposite each — "
    "so a hot key's rows spread over several tasks instead of "
    "hot-spotting one (Spark's spark.sql.adaptive.skewJoin role). A "
    "bucket is skewed when its bytes exceed "
    "max(skewedPartitionFactor * median, skewedPartitionThresholdBytes)."
).boolean(True)

SKEW_JOIN_FACTOR = _conf(
    "rapids.tpu.sql.adaptive.skewJoin.skewedPartitionFactor").doc(
    "Multiple of the median stream-bucket size beyond which a bucket "
    "counts as skewed (with skewedPartitionThresholdBytes as the "
    "absolute floor)."
).check(lambda v: None if v >= 1.0 else "must be >= 1.0").double(4.0)

SKEW_JOIN_THRESHOLD = _conf(
    "rapids.tpu.sql.adaptive.skewJoin.skewedPartitionThresholdBytes").doc(
    "Absolute minimum bytes for a stream bucket to count as skewed "
    "(guards tiny queries where factor * median is noise)."
).bytes(64 << 20)

SKEW_JOIN_MAX_SPLITS = _conf(
    "rapids.tpu.sql.adaptive.skewJoin.maxSplitsPerPartition").doc(
    "Upper bound on sub-partitions one skewed bucket splits into; the "
    "per-slice target is max(advisoryPartitionSizeBytes, bucketBytes / "
    "maxSplitsPerPartition)."
).check(lambda v: None if v >= 2 else "must be >= 2").integer(8)

SHUFFLE_SERIALIZE = _conf("rapids.tpu.shuffle.serialize.enabled").doc(
    "Force shuffle pieces to cross the exchange as serialized host bytes "
    "(the fallback-tier serializer, reference: "
    "GpuColumnarBatchSerializer.scala:37-245). Serialized pieces register "
    "with the host spill store so shuffle data participates in spill."
).boolean(False)

SHUFFLE_MAX_BYTES_IN_FLIGHT = _conf("rapids.tpu.shuffle.maxBytesInFlight").doc(
    "Inflight-bytes throttle for shuffle fetches "
    "(reference: spark.rapids.shuffle.transport.maxReceiveInflightBytes)."
).bytes(1 << 30)

SHUFFLE_PARTITIONS = _conf("rapids.tpu.sql.shuffle.partitions").doc(
    "Default number of shuffle partitions (reference: spark.sql.shuffle.partitions)."
).integer(8)

# ---------------------------------------------------------------------------
# Engine / scheduler
# ---------------------------------------------------------------------------
TASK_THREADS = _conf("rapids.tpu.engine.taskThreads").doc(
    "Worker threads executing partition tasks (the Spark executor-slot analog)."
).integer(8)

FILTER_COMPACT_SYNC = _conf("rapids.tpu.engine.filterCompactSync").doc(
    "Whether the filter compacts with a row-count host sync. 'always' "
    "syncs per batch (shrinks capacity — best when fences are cheap); "
    "'never' keeps the compacted rows at the input capacity with a "
    "traced row count (no fence; padded lanes cost compute but the "
    "sync folds into whatever downstream fence happens anyway); 'auto' "
    "(default) goes lazy when the measured backend fence cost clears "
    "~5 ms (utils/devprobe; a locally attached v5e measured 0.8-1.1 ms, "
    "chip_smoke.py 2026-09-26, and takes the sync side)."
).check(lambda v: None if v in ("auto", "always", "never")
        else "must be one of auto|always|never").string("auto")

AGG_COMPACT_SYNC = _conf("rapids.tpu.engine.aggCompactSync").doc(
    "Whether the partial-aggregate stage compacts its output with a "
    "row-count host sync before the shuffle. 'always' compacts every "
    "batch (best when host<->device syncs are cheap and map partitions "
    "are many); 'never' requests the sync-free lazy path wherever it "
    "applies — fixed-width buffer schemas whose un-compacted output fits "
    "the exchange's zero-copy piece cap; bigger batches and string "
    "min/max buffers still compact. 'auto' additionally requires the "
    "measured backend fence cost to clear a fixed ~5 ms threshold and "
    "the map partition count to stay under aggLazyMaxPartitions. An "
    "aggregate with no grouping key does not consult this key: its "
    "partial is one row whatever the device finds, so it has neither a "
    "sync to save nor lanes to pad, and runs as one program a batch under "
    "every value (sum/count/min/max/avg over fixed-width buffers; "
    "ungroupedAggBatches counts them)."
).check(lambda v: None if v in ("auto", "always", "never")
        else "must be one of auto|always|never").string("auto")

AGG_LAZY_MAX_PARTS = _conf("rapids.tpu.engine.aggLazyMaxPartitions").doc(
    "Upper bound on map partitions for the 'auto' lazy (sync-free) partial "
    "aggregate: beyond this many upstream partitions the un-compacted "
    "batches concatenated at the merge stage would dominate, so compaction "
    "is worth its sync."
).integer(32)

FUSION_ENABLED = _conf("rapids.tpu.sql.fusion.enabled").doc(
    "Compile whole pipelined stages — maximal chains of Filter/Project/"
    "Expand/LocalLimit feeding each other (and the update side of a "
    "partial hash aggregate) — into ONE XLA program per stage, so XLA "
    "fuses across operator boundaries and intermediate batches never "
    "materialize between exec nodes (the WholeStageCodegen analog; "
    "docs/fusion.md). Off = one jitted program per operator."
).boolean(True)

FUSION_MAX_OPS = _conf("rapids.tpu.sql.fusion.maxOps").doc(
    "Upper bound on operators fused into one stage program; a pathological "
    "deep chain past this splits into multiple stages (guards XLA compile "
    "time, which grows with the traced program)."
).check(lambda v: None if v >= 2 else "must be >= 2").integer(16)

# ---------------------------------------------------------------------------
# Single-program SPMD stages (plan/spmd.py, engine/spmd_exec.py,
# docs/spmd-stages.md)
# ---------------------------------------------------------------------------
SPMD_ENABLED = _conf("rapids.tpu.sql.spmd.enabled").doc(
    "Compile whole SPMD-eligible stage pipelines — a scan-fed fused "
    "Filter/Project chain, lowered INNER equi-joins (build side broadcast "
    "in-program via lax.all_gather), the partial hash aggregate, the hash "
    "exchange (lowered to an in-program lax.all_to_all over the session "
    "mesh), the final merge aggregate, and an optional trailing "
    "range-exchange+sort tail — into ONE jitted shard_map program over "
    "the device mesh: one device dispatch per stage chain regardless of "
    "partition count, the same program on 1 chip or a pod slice "
    "(docs/spmd-stages.md). Consecutive eligible stages CHAIN inside one "
    "program (spmd.chainStages.enabled). Ineligible stages, checked "
    "replays, and CPU fallbacks always take the host-loop executor, so "
    "the PR 4/PR 6 retry and re-attribution contracts hold unchanged. On "
    "by default since the r14 bench confirmed flagship parity on the CPU "
    "backend (BENCH_r14.json)."
).boolean(True)

SPMD_MESH_DEVICES = _conf("rapids.tpu.sql.spmd.meshDevices").doc(
    "Devices in the SPMD stage mesh (0 = all local devices). Tests pin it "
    "to exercise the 1-chip and pod-slice shapes of the same program on "
    "one host."
).integer(0)

SPMD_BUCKET_ROWS = _conf("rapids.tpu.sql.spmd.bucketRows").doc(
    "Row capacity of each per-target exchange bucket inside an SPMD stage "
    "program (0 = derive from the resource analyzer's partial-aggregate "
    "row interval, falling back to the stage input capacity, which is "
    "always sufficient). A manual value below the real per-target row "
    "count makes the in-program overflow probe trip and the stage degrade "
    "to the host-loop executor."
).integer(0)

SPMD_MAX_SORT_LANES = _conf("rapids.tpu.sql.spmd.maxSortLanes").doc(
    "Lane budget for absorbing a trailing global sort (range exchange + "
    "sort) into the SPMD stage program: the sort replicates the merged "
    "aggregate output to every shard via all_gather, so it is only taken "
    "when mesh_size * received_lanes stays under this bound; beyond it "
    "the whole stage falls back to the host-loop executor."
).integer(1 << 18)

SPMD_JOIN_LOWERING = _conf("rapids.tpu.sql.spmd.joinLowering.enabled").doc(
    "Lower INNER equi-joins below an SPMD stage's partial aggregate into "
    "the stage program: the build side assembles like a second stage "
    "input and an in-program lax.all_gather replicates it to every shard "
    "(the planned join exchanges are elided in-program; the host-loop "
    "fallback subtree keeps them), while the probe side streams on "
    "through the stage's in-program all_to_all hash exchange. Join "
    "output rows expand into a static capacity taken from the resource "
    "analyzer's join row interval (spmd.joinRows overrides); an "
    "in-program overflow probe degrades the stage to the host-loop "
    "executor rather than ever dropping a row."
).boolean(True)

SPMD_CHAIN_STAGES = _conf("rapids.tpu.sql.spmd.chainStages.enabled").doc(
    "Chain consecutive SPMD-eligible stages (a double group-by) inside "
    "ONE shard_map program: the post-exchange merged buckets of stage k "
    "become stage k+1's in-trace input, never re-assembled into [m, cap] "
    "slots through the host. Each chained segment still counts in "
    "spmdStages; deviceDispatches reflects the single shared program."
).boolean(True)

SPMD_MAX_JOIN_LANES = _conf("rapids.tpu.sql.spmd.maxJoinLanes").doc(
    "Lane budget for one in-program join's expanded output per shard: a "
    "join whose static expansion capacity (analyzer row interval or "
    "spmd.joinRows) would exceed this compiles into an impractically "
    "large program, so the whole stage falls back to the host-loop "
    "executor instead (mirrors spmd.maxSortLanes)."
).integer(1 << 17)

SPMD_JOIN_ROWS = _conf("rapids.tpu.sql.spmd.joinRows").doc(
    "Row capacity of an in-program join's expanded output per shard "
    "(0 = derive from the resource analyzer's join row interval, falling "
    "back to max(frontier lanes, gathered build lanes)). A manual value "
    "below the real match count makes the in-program join overflow probe "
    "trip and the stage degrade to the host-loop executor."
).integer(0)

SPMD_MEASURED_CAPACITY = _conf(
    "rapids.tpu.sql.spmd.measuredCapacity.enabled").doc(
    "Size SPMD stage capacities from AQE's MEASURED MapOutputStats "
    "instead of the resource analyzer's pessimistic interval whenever a "
    "prior stage of the same query already materialized (aqe/loop.py "
    "publishes per-query measured exchange stats; docs/spmd-stages.md). "
    "Measured sizing is backstopped by the in-program overflow probes — "
    "an undersized bucket degrades to the host loop, never drops a row."
).boolean(True)

COLUMN_PRUNING = _conf("rapids.tpu.sql.optimizer.columnPruning.enabled").doc(
    "Prune unreferenced columns from the logical plan before physical "
    "planning (the role Spark Catalyst's ColumnPruning rule plays for the "
    "reference plugin, which receives already-pruned plans): scans decode "
    "only consumed columns, exchanges and joins move only consumed "
    "columns, and narrowed build sides qualify for (runtime) broadcast."
).boolean(True)

BROADCAST_THRESHOLD = _conf("rapids.tpu.sql.autoBroadcastJoinThreshold").doc(
    "Max estimated bytes for a join side to be broadcast "
    "(reference: spark.sql.autoBroadcastJoinThreshold)."
).bytes(10 << 20)

RUNTIME_BROADCAST = _conf(
    "rapids.tpu.sql.adaptive.runtimeBroadcastJoin.enabled").doc(
    "Re-plan a shuffled hash join as a broadcast join at EXECUTE time when "
    "the materialized build side fits under autoBroadcastJoinThreshold "
    "(the role Spark AQE's runtime join-strategy switch plays for the "
    "reference plugin, exercised by TpchLikeAdaptiveSparkSuite): the "
    "planner can only statically broadcast when it can bound the build "
    "size from the logical plan; build sides behind aggregates/joins/file "
    "scans estimate unknown and would otherwise always pay two shuffles."
).boolean(True)

RANGE_SAMPLE_SIZE = _conf("rapids.tpu.sql.rangePartition.sampleSizePerPartition").doc(
    "Reservoir sample size per partition for range partitioning bounds "
    "(reference: GpuRangePartitioner.scala driver-side sampling)."
).integer(100)

# ---------------------------------------------------------------------------
# Execution-time fault tolerance (engine/retry.py, docs/fault-tolerance.md)
# ---------------------------------------------------------------------------
RETRY_OOM_RETRIES = _conf("rapids.tpu.execution.retry.oomRetries").doc(
    "Device re-dispatch attempts after a retryable OOM "
    "(XLA RESOURCE_EXHAUSTED -> TpuRetryOOM): each attempt first spills "
    "tracked device buffers via DeviceStore.synchronous_spill, then "
    "re-dispatches. Exhaustion escalates to TpuSplitAndRetryOOM — "
    "splittable operators (project/filter/fused stage) bisect the input "
    "batch and process halves (reference: the RMM retry/split-retry "
    "state machine the plugin wraps every GPU allocation in)."
).check(lambda v: None if v >= 0 else "must be >= 0").integer(2)

RETRY_TRANSIENT_RETRIES = _conf(
    "rapids.tpu.execution.retry.transientRetries").doc(
    "Re-dispatch attempts after a transient device error (XLA "
    "ABORTED/UNAVAILABLE/INTERNAL -> TpuTransientDeviceError), with "
    "exponential backoff and deterministic jitter between attempts."
).check(lambda v: None if v >= 0 else "must be >= 0").integer(3)

RETRY_MAX_SPLIT_DEPTH = _conf(
    "rapids.tpu.execution.retry.maxSplitDepth").doc(
    "Maximum bisection depth for split-and-retry: a batch OOMing after "
    "every spill+retry attempt is halved recursively at most this many "
    "times (2^depth pieces) before the operator gives up and degrades "
    "to the CPU path."
).check(lambda v: None if v >= 0 else "must be >= 0").integer(3)

CPU_FALLBACK_ENABLED = _conf(
    "rapids.tpu.execution.cpuFallback.enabled").doc(
    "When an operator exhausts its device retries, re-execute the failed "
    "unit of work through the CPU-oracle path instead of failing the "
    "query: project/filter/fused stages fall back per batch; operators "
    "with device-resident state (aggregate/join/sort/scan) fall back by "
    "re-planning the whole query on the CPU engine. Every fallback "
    "increments the cpuFallbackEvents metric."
).boolean(True)

CIRCUIT_BREAKER_ENABLED = _conf(
    "rapids.tpu.execution.circuitBreaker.enabled").doc(
    "Per-session device circuit breaker: after failureThreshold device "
    "failures (retry exhaustions / query-level fallbacks), the breaker "
    "opens and the remaining work routes straight to the CPU path — "
    "batch-level device ops bypass the device and new queries plan on "
    "the CPU engine — instead of burning retry budget against an "
    "unhealthy device."
).boolean(True)

CIRCUIT_BREAKER_THRESHOLD = _conf(
    "rapids.tpu.execution.circuitBreaker.failureThreshold").doc(
    "Device failures (retry exhaustions, not individual retries) the "
    "session tolerates before the circuit breaker opens."
).check(lambda v: None if v >= 1 else "must be >= 1").integer(4)

CIRCUIT_BREAKER_COOLDOWN_MS = _conf(
    "rapids.tpu.execution.circuitBreaker.cooldownMs").doc(
    "Half-open recovery: once a breaker has been open this many "
    "milliseconds it admits up to probeQueries device probes — a probe "
    "succeeding closes the breaker (failure count resets), a probe "
    "failing re-opens it and restarts the cooldown. 0 = the pre-r18 "
    "behavior (an open breaker stays open until session.stop())."
).check(lambda v: None if v >= 0 else "must be >= 0").double(30000.0)

CIRCUIT_BREAKER_PROBE_QUERIES = _conf(
    "rapids.tpu.execution.circuitBreaker.probeQueries").doc(
    "Device queries admitted through a HALF-OPEN breaker per cooldown "
    "window before it re-latches open awaiting their verdict; the first "
    "probe that completes decides (success closes, failure re-opens)."
).check(lambda v: None if v >= 1 else "must be >= 1").integer(1)

TASK_TIMEOUT_SECONDS = _conf("rapids.tpu.engine.taskTimeoutSeconds").doc(
    "Wall-clock budget for one partition task; a pooled job whose task "
    "exceeds it fails with a TaskFailedError(TaskTimeoutError) instead "
    "of wedging the query (0 = disabled; single-partition jobs run "
    "inline on the caller thread and are not covered). The wedged worker "
    "thread cannot be interrupted — it keeps its pool slot and semaphore "
    "permits until its device call returns — so the timeout error is "
    "typed as a device failure: the query re-executes on the CPU engine "
    "(which never touches the admission semaphore) and the circuit "
    "breaker counts the failure."
).check(lambda v: None if v >= 0 else "must be >= 0").double(0.0)

RETRY_BUDGET = _conf("rapids.tpu.engine.retryBudget").doc(
    "Total task retries one query may spend across all of its jobs "
    "(map stages, exchanges, reduce stages share the budget); once "
    "exhausted further failures are terminal. Guards against a flaky "
    "device turning a query into an unbounded retry storm."
).check(lambda v: None if v >= 0 else "must be >= 0").integer(64)

RETRY_BACKOFF_MS = _conf("rapids.tpu.engine.retryBackoffMs").doc(
    "Base backoff in milliseconds between retry attempts (task retries "
    "and transient-device re-dispatches): sleep = base * 2^attempt * "
    "(0.5 + jitter) where jitter is a deterministic hash of the retry "
    "identity — reproducible schedules, no thundering herd."
).check(lambda v: None if v >= 0 else "must be >= 0").double(5.0)

# ---------------------------------------------------------------------------
# Self-healing execution (engine/scheduler.py speculation +
# engine/watchdog.py, docs/fault-tolerance.md)
# ---------------------------------------------------------------------------
SPECULATION_ENABLED = _conf("rapids.tpu.engine.speculation.enabled").doc(
    "Cost-calibrated straggler speculation: a pooled partition task "
    "still running past max(minRuntimeMs, multiplier x its predicted "
    "duration) while at least `quantile` of its job's sibling tasks "
    "have finished gets ONE speculative duplicate (an idempotent "
    "re-execution from source, never shared device buffers); the first "
    "completion wins and the loser is cancelled through its task-scoped "
    "CancelToken. Metrics: speculativeTasks / speculativeWins."
).boolean(True)

SPECULATION_MIN_RUNTIME_MS = _conf(
    "rapids.tpu.engine.speculation.minRuntimeMs").doc(
    "Floor under the speculation threshold: a task is never speculated "
    "before running at least this long, whatever the cost model "
    "predicts — guards sub-millisecond tasks against duplicate storms."
).check(lambda v: None if v >= 0 else "must be >= 0").double(500.0)

SPECULATION_MULTIPLIER = _conf(
    "rapids.tpu.engine.speculation.multiplier").doc(
    "Straggler threshold as a multiple of the task's predicted p95 "
    "duration (the calibrated CostModel prediction when enough samples "
    "exist, the flat per-dispatch model otherwise; with no prediction "
    "at all the median of finished sibling durations stands in)."
).check(lambda v: None if v >= 1.0 else "must be >= 1.0").double(4.0)

SPECULATION_QUANTILE = _conf("rapids.tpu.engine.speculation.quantile").doc(
    "Fraction of a job's sibling tasks that must have FINISHED before "
    "any task of that job may be speculated (a uniformly slow job is "
    "not straggling; one laggard among finished siblings is)."
).check(lambda v: None if 0.0 <= v <= 1.0 else "must be in [0,1]"
        ).double(0.5)

WATCHDOG_ENABLED = _conf("rapids.tpu.engine.watchdog.enabled").doc(
    "Hung-dispatch watchdog: one scheduler-owned daemon thread "
    "heartbeats every in-flight retry-wrapped dispatch; a dispatch "
    "silent past its timeout is classified WEDGED (metric: "
    "watchdogKills), its cooperative wait-points are released so the "
    "attempt raises a retryable TpuDispatchWedged and re-dispatches on "
    "fresh buffers, and a dispatch still silent past 2x the timeout "
    "escalates by firing the owning query's CancelToken."
).boolean(True)

WATCHDOG_DISPATCH_TIMEOUT_MS = _conf(
    "rapids.tpu.engine.watchdog.dispatchTimeoutMs").doc(
    "Silence budget for one in-flight dispatch before the watchdog "
    "classifies it wedged. 0 = calibrated: 8x the active CostModel's "
    "predicted per-task wall when a prediction exists, else a 30s "
    "cold-start default."
).check(lambda v: None if v >= 0 else "must be >= 0").double(0.0)

WATCHDOG_POLL_MS = _conf("rapids.tpu.engine.watchdog.pollMs").doc(
    "Heartbeat cadence of the watchdog daemon's scan over in-flight "
    "dispatch registrations."
).check(lambda v: None if v >= 1 else "must be >= 1").double(50.0)

# ---------------------------------------------------------------------------
# Cooperative cancellation + deadline propagation (engine/cancel.py,
# docs/fault-tolerance.md)
# ---------------------------------------------------------------------------
ENGINE_DEADLINE_MS = _conf("rapids.tpu.engine.deadlineMs").doc(
    "Per-query wall-clock deadline in milliseconds (0 = none): a "
    "CancelToken armed with this budget rides the query's QueryContext "
    "and every engine chokepoint (task loop, retry backoff, admission "
    "wait, AQE replan loop, shuffle fetch remap, prefetch, sink "
    "download) polls it — expiry raises a terminal TpuDeadlineExceeded "
    "with no retry, no CPU fallback, and no partial rows, and the query "
    "releases everything it holds (semaphore permits, admission bytes, "
    "spill entries, prefetch threads). Overridable per call via "
    "df.collect(timeout=seconds) and per tenant via TpuServer."
).check(lambda v: None if v >= 0 else "must be >= 0").double(0.0)

DEADLINE_COST_PER_DISPATCH_MS = _conf(
    "rapids.tpu.engine.deadline.costPerDispatchMs").doc(
    "Admission-time deadline feasibility model (0 = disabled): predicted "
    "query work is estimated as the resource analyzer's predicted device "
    "dispatches (upper bound) times this per-dispatch cost; a query "
    "whose predicted work cannot fit its remaining deadline is REJECTED "
    "before execution (zero device dispatches, metric: deadlineRejects) "
    "instead of admitted to die mid-flight. Calibrate from bench "
    "history (BENCH_*.json record measured per-dispatch costs per "
    "platform)."
).check(lambda v: None if v >= 0 else "must be >= 0").double(0.0)

# ---------------------------------------------------------------------------
# Async issue-ahead execution (engine/async_exec.py, docs/async-execution.md)
# ---------------------------------------------------------------------------
ASYNC_DISPATCH = _conf("rapids.tpu.execution.asyncDispatch.enabled").doc(
    "Issue-ahead execution: operators hand downstream UNBLOCKED device "
    "futures and the query blocks on device values exactly once, at the "
    "result sink — so a device error may surface at the sink instead of "
    "the dispatch that issued the failing program. When that happens the "
    "session re-executes the query once in CHECKED mode (synchronous "
    "dispatch, donation off) where the originating operator's own "
    "spill/split-retry machinery owns the error, before any CPU fallback "
    "(metric: checkedReplays). Off = always run checked."
).boolean(True)

BUFFER_DONATION = _conf("rapids.tpu.execution.bufferDonation.enabled").doc(
    "Donate input buffers to consume-once device kernels (fused stages, "
    "aggregate update, sort gather) via XLA donate_argnums so the output "
    "reuses the input's HBM instead of allocating fresh — cuts peak HBM "
    "churn roughly in half on those paths. Effective only on platforms "
    "that support donation (not the CPU backend). A donated dispatch "
    "cannot re-dispatch in place after a failure (its inputs are gone), "
    "so failures escalate to the query-level checked replay, which runs "
    "with donation off (docs/async-execution.md)."
).boolean(True)

BUFFER_DONATION_ASSUME_SUPPORTED = _conf(
    "rapids.tpu.execution.bufferDonation.assumeSupported").doc(
    "Treat the current backend as donation-capable even when it is the "
    "CPU backend (tests exercise the donation key-threading and the "
    "escalation contract without a real chip)."
).internal().boolean(False)

# ---------------------------------------------------------------------------
# Fault injection (utils/faultinject.py; the chaos-test substrate)
# ---------------------------------------------------------------------------
FAULT_INJECTION_ENABLED = _conf(
    "rapids.tpu.test.faultInjection.enabled").doc(
    "Enable the deterministic fault-injection harness: registered "
    "execution sites (device dispatches, transfers, shuffle fetches) "
    "consult a seeded PRF before running and raise the site's fault "
    "kind when it fires. Results must stay identical to the CPU oracle "
    "under every injected fault pattern (tests/test_faults.py)."
).boolean(False)

FAULT_INJECTION_SEED = _conf("rapids.tpu.test.faultInjection.seed").doc(
    "Seed of the fault-injection PRF; the injection decision for "
    "(site, invocation N) is a pure function of (seed, site, N), so a "
    "run replays exactly under the same seed."
).integer(0)

FAULT_INJECTION_SITES = _conf("rapids.tpu.test.faultInjection.sites").doc(
    "Comma-separated injection sites, each 'name' or 'name:kind' with "
    "kind one of oom|dispatch|transfer|fetch|delay|wedge|device_loss "
    "('*' = every registered site at its default kind; the cancel, "
    "delay, wedge, and device_loss kinds are explicit opt-ins). "
    "Registered sites: see spark_rapids_tpu.utils.faultinject.SITES / "
    "docs/fault-tolerance.md."
).string("*")

FAULT_INJECTION_RATE = _conf("rapids.tpu.test.faultInjection.rate").doc(
    "Probability in [0,1] that an armed site injects on one invocation "
    "(each retry re-rolls with a fresh invocation count, so rates < 1 "
    "terminate; the CPU fallback backstops rate = 1)."
).check(lambda v: None if 0.0 <= v <= 1.0 else "must be in [0,1]"
        ).double(0.25)

FAULT_INJECTION_DELAY_MS = _conf(
    "rapids.tpu.test.faultInjection.delayMs").doc(
    "Straggler model: an armed site firing the `delay` kind sleeps this "
    "long (cancel-aware) before proceeding NORMALLY — the work still "
    "happens and results stay oracle-equal, the task just runs late, "
    "which is what straggler speculation exists to absorb."
).check(lambda v: None if v >= 0 else "must be >= 0").double(400.0)

FAULT_INJECTION_DEFER_TO_SINK = _conf(
    "rapids.tpu.test.faultInjection.deferToSink").doc(
    "Model async dispatch's error timing: a fault that fires at a "
    "device-compute site (scan/fused/agg/join/sort) is RECORDED instead "
    "of raised, and surfaces at the next result-sink download "
    "(transfer.download) re-attributed to its originating site — "
    "exactly how a real XLA async error reaches the host. The checked "
    "replay (asyncDispatch doc) disables deferral, so the replay's "
    "faults raise at their sites where split-retry owns them."
).internal().boolean(False)

# ---------------------------------------------------------------------------
# Static analysis (plan/verify.py, docs/static-analysis.md)
# ---------------------------------------------------------------------------
PLAN_VERIFY = _conf("rapids.tpu.sql.planVerify.enabled").doc(
    "Run the static plan verifier on every FINAL physical plan before "
    "execution: schema (name/dtype/nullability) propagates bottom-up — "
    "including through TpuFusedStage member chains — and plans with "
    "unresolvable column references, dtype drift, host/device edges "
    "missing a transition node, or fused-stage accounting mismatches "
    "are rejected before any kernel runs (the GpuOverrides static-"
    "tagging safety net extended to the post-fusion plan). Violations "
    "also render in EXPLAIN under '== Plan verification =='."
).boolean(True)

PLAN_VERIFY_FAIL = _conf("rapids.tpu.sql.planVerify.failOnViolation").doc(
    "Raise PlanVerificationError when the plan verifier finds "
    "violations (default). When false the verifier is observe-only: "
    "violations surface in EXPLAIN output but the plan still executes "
    "— the triage mode for a rejected production plan."
).boolean(True)

RESOURCE_ANALYSIS = _conf("rapids.tpu.sql.resourceAnalysis.enabled").doc(
    "Run the plan-time resource analyzer on every FINAL physical plan: a "
    "bottom-up abstract interpretation propagating row-count bounds, padded "
    "batch shape sets, and a peak-HBM watermark (including transient "
    "doubles: sort buffers, hash-join build tables, shuffle staging, "
    "partial-agg scratch) per operator — including through TpuFusedStage "
    "member chains. Emits per-stage peak-byte estimates, predicted jit "
    "shape-bucket compile keys, and predicted device dispatches; typed "
    "violations (OOM_HAZARD, SPILL_LIKELY, RECOMPILE_CHURN, "
    "UNBOUNDED_GENERATE) render in EXPLAIN under '== Resource analysis ==' "
    "and feed admission-weight hints to the TPU semaphore and headroom "
    "hints to the spill framework (docs/static-analysis.md)."
).boolean(True)

RESOURCE_ANALYSIS_FAIL = _conf(
    "rapids.tpu.sql.resourceAnalysis.failOnViolation").doc(
    "Raise ResourceAnalysisError before execution when the resource "
    "analyzer finds a fatal violation (OOM_HAZARD, RECOMPILE_CHURN, "
    "UNBOUNDED_GENERATE; SPILL_LIKELY is always advisory — the spill "
    "framework exists to absorb it). Off by default: the analyzer works "
    "from static bounds, so the default mode observes — violations are "
    "recorded in session.last_plan_violations and EXPLAIN, and admission/"
    "spill hints still flow — while admission control that REJECTS "
    "queries is an explicit opt-in."
).boolean(False)

RESOURCE_STATS_MAX_ROWS = _conf(
    "rapids.tpu.sql.resourceAnalysis.statsMaxRows").doc(
    "Largest host-resident relation (total rows) the resource analyzer "
    "scans for per-column distinct-count stats at plan time; bigger "
    "relations skip the scan and keep loose row bounds (plan-time cost "
    "guard: the stats pass is O(rows log rows) per column)."
).internal().integer(1 << 17)

RESOURCE_HBM_BUDGET = _conf(
    "rapids.tpu.sql.resourceAnalysis.hbmBudgetBytes").doc(
    "HBM byte budget the resource analyzer checks predicted peaks "
    "against. 0 (default) uses the device manager's budget (detected "
    "HBM x rapids.tpu.memory.hbm.allocFraction); a nonzero override "
    "lets admission policy be tested or tightened independently of the "
    "physical device."
).bytes(0)

PLACEMENT_ENABLED = _conf("rapids.tpu.sql.placement.enabled").doc(
    "Run the cost-based placement analyzer on every FINAL physical plan "
    "(plan/placement.py, docs/placement.md): a bottom-up abstract cost "
    "interpreter that prices each operator on the device (fitted "
    "CostModel from obs/calibrate.py) and on the host (a parallel "
    "host-side coefficient fit from CPU-fallback history and *_cpu "
    "BENCH artifacts), adds transfer-edge costs at every would-be "
    "boundary, and chooses a per-subtree placement by dynamic "
    "programming — emitting MIXED plans realized with HostToDeviceExec/"
    "DeviceToHostExec transitions. The placed plan is re-verified and "
    "re-priced (planVerify placement rules, resourceAnalysis admission "
    "cost), rendered in EXPLAIN under '== Placement ==', and every "
    "decision lands in the flight recorder with a post-hoc "
    "placementRegret signal. Off by default: placement changes which "
    "backend executes each operator."
).boolean(False)

PLACEMENT_MODE = _conf("rapids.tpu.sql.placement.mode").doc(
    "Placement strategy when the analyzer is enabled. 'auto' (default): "
    "DP over fitted device/host/transfer costs, cold-start falling back "
    "to all-device below minSamples. 'device': force every operator "
    "onto the TPU (today's behavior, useful as the A side of an A/B). "
    "'host': force the whole plan host-side — the toy-scale escape "
    "hatch and the training source for the host-side coefficient fit."
).check(
    lambda v: None if v in ("auto", "device", "host")
    else "must be auto|device|host"
).string("auto")

PLACEMENT_MIN_SAMPLES = _conf("rapids.tpu.sql.placement.minSamples").doc(
    "Minimum fitted samples an operator class needs on BOTH the device "
    "and host cost models before 'auto' placement will move it off the "
    "device. Below this the class is cold and pinned to the TPU — the "
    "cold-start contract: an unwarmed model reproduces all-device "
    "plans exactly."
).check(lambda v: None if v >= 1 else "must be >= 1").integer(5)

# ---------------------------------------------------------------------------
# Multi-tenant serving runtime (engine/server.py, plan/plan_cache.py,
# engine/admission.py, docs/serving.md)
# ---------------------------------------------------------------------------
PLAN_CACHE_ENABLED = _conf("rapids.tpu.serving.planCache.enabled").doc(
    "Cache fully planned, verified, and analyzed physical plans keyed by "
    "a canonical plan signature (logical plan structure with normalized "
    "expression ids + leaf data identity + every explicitly-set conf "
    "key). A steady-state repeat query skips planning, verification, AND "
    "resource analysis, and — because the cached plan carries the "
    "original expression objects — its kernels hit the jit cache with "
    "zero retracing (metrics: planCacheHits / planCacheMisses). The "
    "cache is shared by every live session and cleared when the last "
    "session stops."
).boolean(True)

PLAN_CACHE_MAX_ENTRIES = _conf(
    "rapids.tpu.serving.planCache.maxEntries").doc(
    "LRU bound on cached physical plans. Entries pin their leaf data "
    "(host batches of in-memory relations) alive, so the bound also "
    "bounds that residency."
).check(lambda v: None if v >= 1 else "must be >= 1").integer(256)

ADMISSION_ENABLED = _conf("rapids.tpu.serving.admission.enabled").doc(
    "Analyzer-driven query admission (docs/serving.md): instead of "
    "first-come-first-served semaphore entry alone, each query declares "
    "the resource analyzer's predicted peak-HBM bytes before executing; "
    "a query only starts when aggregate admitted bytes + its own fit "
    "under the HBM budget — heavy plans queue, light plans interleave "
    "past them (bounded by admission.maxBypass). Queries without a "
    "resource report (analysis disabled or the estimator failed) admit "
    "immediately; the task-level TpuSemaphore remains the inner gate."
).boolean(True)

ADMISSION_MAX_BYPASS = _conf("rapids.tpu.serving.admission.maxBypass").doc(
    "How many younger queries may be admitted past a waiting (heavy) "
    "query before it becomes the blocking head of the queue and no "
    "later arrival may admit until it does — bounds starvation under a "
    "steady stream of light queries."
).check(lambda v: None if v >= 0 else "must be >= 0").integer(8)

ADMISSION_MAX_QUEUE_DEPTH = _conf(
    "rapids.tpu.serving.admission.maxQueueDepth").doc(
    "Overload shedding, depth bound (0 = unbounded): how many queries "
    "may WAIT in analyzer-driven admission at once; an arrival past the "
    "bound is refused immediately with a terminal TpuOverloadedError "
    "(metric: shedQueries) instead of joining a queue whose wait "
    "already exceeds any useful deadline (docs/fault-tolerance.md)."
).check(lambda v: None if v >= 0 else "must be >= 0").integer(0)

ADMISSION_MAX_QUEUE_WAIT_MS = _conf(
    "rapids.tpu.serving.admission.maxQueueWaitMs").doc(
    "Overload shedding, wait bound in milliseconds (0 = unbounded): a "
    "query that has waited in admission longer than this is refused "
    "with a terminal TpuOverloadedError (metric: shedQueries) rather "
    "than admitted to die — under sustained overload, bounded tail "
    "latency comes from shedding work, not queueing it."
).check(lambda v: None if v >= 0 else "must be >= 0").double(0.0)

DRAIN_POLICY = _conf("rapids.tpu.serving.drain.policy").doc(
    "What TpuServer.drain() does with in-flight queries: 'await' lets "
    "them finish (up to drain.timeoutMs, then cancels the stragglers), "
    "'cancel' fires every in-flight query's CancelToken immediately. "
    "Either way the server stops admitting first (new queries shed with "
    "TpuOverloadedError) and tears the runtime down only once quiesced."
).check(lambda v: None if v in ("await", "cancel")
        else "must be await|cancel").string("await")

DRAIN_TIMEOUT_MS = _conf("rapids.tpu.serving.drain.timeoutMs").doc(
    "Bound on how long TpuServer.drain() (and session.stop() with "
    "queries in flight) waits for in-flight queries to quiesce before "
    "tearing down anyway; under the 'await' policy, stragglers past the "
    "bound are cancelled."
).check(lambda v: None if v >= 0 else "must be >= 0").double(10000.0)

MICRO_BATCH_WINDOW_MS = _conf(
    "rapids.tpu.serving.microBatch.windowMs").doc(
    "Cross-query micro-batching window in milliseconds (0 = off). "
    "Eligible queries (per-partition-independent Filter/Project "
    "pipelines over one in-memory relation) that share a plan SHAPE "
    "signature and arrive within the window are packed into ONE query "
    "— each constituent's partitions ride as partitions of a shared "
    "padded device program — and de-multiplexed at the sink by "
    "partition range (metrics: microBatches / microBatchedQueries). "
    "Requires submitting through a session wired to a TpuServer's "
    "micro-batcher (engine/server.py)."
).check(lambda v: None if v >= 0 else "must be >= 0").double(0.0)

MICRO_BATCH_MAX_QUERIES = _conf(
    "rapids.tpu.serving.microBatch.maxQueries").doc(
    "Largest number of queries packed into one micro-batch window; a "
    "window closes early once this many have joined."
).check(lambda v: None if v >= 2 else "must be >= 2").integer(8)

# ---------------------------------------------------------------------------
# Encoded (compressed) columnar execution (columnar/encoded.py,
# docs/compressed-execution.md)
# ---------------------------------------------------------------------------
ENCODED_ENABLED = _conf("rapids.tpu.sql.encoded.enabled").doc(
    "Keep dictionary-encoded parquet STRING columns ENCODED in HBM as "
    "int32 codes plus one shared device dictionary, and compute on the "
    "codes: equality/IN/IS NULL filters rewrite their literals into code "
    "space once per dictionary, hash aggregates group directly on codes "
    "(the dictionary is gathered only at finalize/sink), hash joins on "
    "dictionary keys align the two sides through a build-time code-remap "
    "table, and the serialized shuffle ships codes + one dictionary copy "
    "per piece instead of expanded strings. Every other consumer decodes "
    "at its operator boundary through the explicit materialize() path "
    "(metrics: encodedColumns / lateMaterializations / "
    "encodedBytesSaved)."
).boolean(True)

ENCODED_MAX_DICT_FRACTION = _conf("rapids.tpu.sql.encoded.maxDictFraction").doc(
    "Per-column opt-in heuristic for encoded scan output: a "
    "dictionary-encoded column chunk stays encoded only when its "
    "dictionary size / row count is at or below this fraction (a "
    "near-unique column gains nothing from codes and would pay the "
    "dictionary residency twice)."
).check(lambda v: None if 0.0 < v <= 1.0 else "must be in (0,1]").double(0.5)

RUN_AWARE_ENABLED = _conf("rapids.tpu.sql.runAware.enabled").doc(
    "Run-granular aggregate fast path (columnar/runs.py): when every "
    "column an aggregate update's keys / inputs / collapsed filters "
    "reference carries a host RLE run table from the parquet scan "
    "(pure-RLE, no-null dictionary chunks), the update batch collapses "
    "to one row per merged run plus a __run_len column — filters "
    "evaluate one predicate per run, integral sums become value x "
    "run_length, counts become sums of run lengths — before the "
    "ordinary update kernel runs. Falls back to row space whenever any "
    "eligibility condition fails (metric: runCollapsedRows)."
).boolean(True)

RUN_AWARE_MAX_RUN_FRACTION = _conf(
    "rapids.tpu.sql.runAware.maxRunFraction").doc(
    "The run collapse engages only when merged runs / rows is at or "
    "below this fraction: the run-length factor IS the win, and a "
    "near-unique column would pay the collapse (host boundary merge + "
    "re-upload) for nothing."
).check(lambda v: None if 0.0 < v <= 1.0 else "must be in (0,1]").double(0.5)


# ---------------------------------------------------------------------------
# Observability: query tracing + engine telemetry (spark_rapids_tpu/obs/,
# docs/observability.md)
# ---------------------------------------------------------------------------
OBS_TRACING = _conf("rapids.tpu.obs.tracing.enabled").doc(
    "Record a QueryContext-scoped span tree for every query: query -> "
    "stage -> operator -> site spans (dispatch/transfer/spill/retry/"
    "replan/admission-wait) with HOST-clock timestamps only — tracing "
    "adds zero device dispatches and zero host fences (pinned by "
    "tests/test_observability.py). The finished tree lands on "
    "session.last_query_trace (Perfetto/Chrome-trace export via "
    ".to_perfetto()); EXPLAIN ANALYZE forces it on for its run. Off "
    "(default): the span API is a true no-op — no allocation, no clock "
    "reads."
).boolean(False)

OBS_TRACE_MAX_SPANS = _conf("rapids.tpu.obs.trace.maxSpans").doc(
    "Upper bound on spans recorded per query; spans past the cap are "
    "counted in the trace's dropped_spans and not retained (bounds "
    "tracer memory on pathological many-partition queries)."
).check(lambda v: None if v >= 1 else "must be >= 1").integer(20000)

OBS_TRACE_ANNOTATIONS = _conf("rapids.tpu.obs.traceAnnotations.enabled").doc(
    "Bridge every live span into a jax.profiler.TraceAnnotation (the "
    "NvtxWithMetrics analog for XProf): a jax.profiler capture taken "
    "while tracing shows the engine's span names on the host timeline. "
    "Off by default — the annotation objects cost allocations per span "
    "and matter only under an active profiler."
).boolean(False)

OBS_HISTORY_ENABLED = _conf("rapids.tpu.obs.history.enabled").doc(
    "Flight recorder (obs/history.py, docs/observability.md): persist "
    "one JSONL record per finished query — plan signature, per-operator "
    "measured spans flattened from the trace, the resource analyzer's "
    "predicted intervals, correlated engine events (retries, spills, "
    "sheds, cancellations, AQE rewrites), and the terminal status "
    "(ok/failed/cancelled/deadline/shed). Persistence is WRITE-BEHIND: "
    "a single daemon writer appends after the sink, off the query's "
    "critical path, so the flagship deviceDispatches/fencesPerQuery are "
    "identical with history on vs off (pinned by tests). Enabling "
    "history also turns span tracing on for recorded queries — the "
    "record's per-operator rows ride the span tree."
).boolean(False)

OBS_HISTORY_PATH = _conf("rapids.tpu.obs.history.path").doc(
    "Path of the query-history JSONL store. Empty (default) resolves to "
    "srt_query_history-<pid>.jsonl under the system temp directory — "
    "point it somewhere durable to accumulate calibration history "
    "across processes. One line = one complete JSON record; a corrupt "
    "trailing line (crash mid-append) is skipped on read, never fatal."
).string("")

OBS_HISTORY_MAX_BYTES = _conf("rapids.tpu.obs.history.maxBytes").doc(
    "Retention bound of the history store: when an append would push "
    "the file past this size it is compacted in place to the NEWEST "
    "records totaling at most half the bound, then the append proceeds "
    "— the store never grows past maxBytes + one record."
).check(lambda v: None if v >= 4096 else "must be >= 4096").bytes(16 << 20)

OBS_HISTORY_QUEUE_DEPTH = _conf("rapids.tpu.obs.history.queueDepth").doc(
    "Bound on query records awaiting the write-behind history writer; "
    "records past it are DROPPED (counted in the store snapshot) rather "
    "than blocking a query's completion path."
).check(lambda v: None if v >= 1 else "must be >= 1").integer(256)

OBS_CALIBRATION_ENABLED = _conf("rapids.tpu.obs.calibration.enabled").doc(
    "Consume the fitted per-operator-class cost model (obs/calibrate.py) "
    "where the engine prices predicted work: the resource analysis "
    "renders a predicted wall-time interval, EXPLAIN ANALYZE shows a "
    "per-operator prediction-error column, and the admission-time "
    "deadline feasibility check uses calibrated per-class costs instead "
    "of the flat rapids.tpu.engine.deadline.costPerDispatchMs — which "
    "stays the cold-start fallback for classes with fewer than "
    "calibration.minSamples samples."
).boolean(True)

OBS_CALIBRATION_MIN_SAMPLES = _conf(
    "rapids.tpu.obs.calibration.minSamples").doc(
    "Samples a cost class needs before its fitted coefficients are "
    "trusted; below it the class prices at the flat "
    "deadline.costPerDispatchMs cold-start fallback "
    "(docs/observability.md, the cold-start fallback contract)."
).check(lambda v: None if v >= 1 else "must be >= 1").integer(5)

OBS_CALIBRATION_REFIT_EVERY = _conf(
    "rapids.tpu.obs.calibration.refitEvery").doc(
    "Refit the cost model from recent history every N recorded queries "
    "(on the write-behind writer thread, never the query path); 0 "
    "disables automatic refits (obs.calibrate.fit_from_store remains "
    "the manual path)."
).check(lambda v: None if v >= 0 else "must be >= 0").integer(16)

class TpuConf:
    """Resolved view of the settings map (reference: RapidsConf class).

    Exposes each registered entry as a property-style `get(entry)` as well as
    convenience attributes for the hot keys.
    """

    def __init__(self, settings: Optional[Dict[str, Any]] = None):
        self.settings: Dict[str, Any] = dict(settings or {})

    def clone_with(self, extra: Dict[str, Any]) -> "TpuConf":
        merged = dict(self.settings)
        merged.update(extra)
        return TpuConf(merged)

    def get(self, entry: ConfEntry) -> Any:
        return entry.get(self.settings)

    def get_key(self, key: str, default: Any = None) -> Any:
        entry = REGISTRY.get(key)
        if entry is not None:
            return entry.get(self.settings)
        return self.settings.get(key, default)

    def set(self, key: str, value: Any) -> "TpuConf":
        self.settings[key] = value
        if key == ENABLE_INT64_NARROWING.key:
            self.sync_int64_narrowing()
        return self

    def sync_int64_narrowing(self) -> None:
        """Align the process-wide narrowing flag with THIS conf. The flag
        is read at kernel TRACE time (no session in scope there), so it is
        a process global; this sync runs on set() AND at every query start
        (session.execute_batches), which makes the executing session's
        conf authoritative even across clone_with copies or multiple
        sessions; the flag also salts every jit-cache key, so sessions
        with different settings select different compiled programs rather
        than flushing each other's."""
        from spark_rapids_tpu.columnar.batch import (
            int64_narrowing_enabled,
            set_int64_narrowing,
        )

        want = self.get(ENABLE_INT64_NARROWING)
        if want != int64_narrowing_enabled():
            # the flag salts every jit-cache key (engine/jit_cache._key_salt)
            # so both flavors of compiled kernels coexist; flipping selects,
            # never invalidates
            set_int64_narrowing(want)

    def is_operator_enabled(self, key: str, incompat: bool, disabled_by_default: bool) -> bool:
        """Per-operator gate logic (reference: RapidsMeta.scala:185-200)."""
        if key in self.settings:
            return _to_bool(self.settings[key])
        if disabled_by_default:
            return False
        if incompat:
            return self.get(INCOMPATIBLE_OPS)
        return True

    # -- hot-key conveniences -------------------------------------------------
    @property
    def sql_enabled(self) -> bool:
        return self.get(SQL_ENABLED)

    @property
    def explain(self) -> str:
        return self.get(EXPLAIN)

    @property
    def test_enabled(self) -> bool:
        return self.get(TEST_ENABLED)

    @property
    def allowed_non_tpu(self) -> List[str]:
        raw = self.get(TEST_ALLOWED_NON_TPU) or ""
        return [s.strip() for s in raw.split(",") if s.strip()]

    @property
    def batch_size_bytes(self) -> int:
        return self.get(BATCH_SIZE_BYTES)

    @property
    def concurrent_tpu_tasks(self) -> int:
        return self.get(CONCURRENT_TPU_TASKS)

    @property
    def shuffle_partitions(self) -> int:
        return self.get(SHUFFLE_PARTITIONS)

    @property
    def task_threads(self) -> int:
        return self.get(TASK_THREADS)


def generate_docs_markdown() -> str:
    """Generate configs.md (reference: RapidsConf.help / docs/configs.md)."""
    lines = [
        "# spark_rapids_tpu configuration",
        "",
        "| Key | Default | Description |",
        "|---|---|---|",
    ]
    for e in REGISTRY.entries():
        if e.is_internal:
            continue
        lines.append(f"| `{e.key}` | `{e.default}` | {e.doc} |")
    return "\n".join(lines) + "\n"

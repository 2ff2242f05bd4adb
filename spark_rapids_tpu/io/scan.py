"""File scan execs (reference: GpuParquetScan.scala, GpuOrcScan.scala,
GpuBatchScanExec.scala CSV).

Reference parity:
- read-partition planning by row-group/row-count caps
  (populateCurrentBlockChunk, GpuParquetScan.scala:571-605;
  maxReadBatchSizeRows/Bytes, RapidsConf.scala:315-322) -> `plan_splits`.
- host-side read + device upload with task admission
  (semaphore acquire before decode/upload, GpuParquetScan.scala:300,554) ->
  `TpuFileScanExec` host-decodes via Arrow C++ then does the packed
  single-copy upload under the TpuSemaphore.
- per-format enable confs (RapidsConf.scala:433-469) -> tagged in
  plan/overrides.py.

A parquet column that is not a string is decoded by Arrow C++ on the host
(one threaded read a split) and uploaded; a string column is decoded on
the device from its raw chunk (io/parquet_device.py), which leaves it as
dictionary codes where it can.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np
import pyarrow as pa

from spark_rapids_tpu.columnar.batch import (
    HostColumnarBatch,
    HostColumnVector,
    StagedUpload,
)
from spark_rapids_tpu.columnar.dtypes import DataType
from spark_rapids_tpu.exec.base import (
    CpuExec,
    ExecContext,
    PartitionedBatches,
    PhysicalExec,
    TpuExec,
    count_output,
)
from spark_rapids_tpu.exec.transitions import current_task_id
from spark_rapids_tpu.io.arrow_convert import arrow_to_host_batch
from spark_rapids_tpu.memory.semaphore import TpuSemaphore
from spark_rapids_tpu.obs.trace import span as obs_span
from spark_rapids_tpu.ops.base import AttributeReference
from spark_rapids_tpu.utils import metrics as M


@dataclass(frozen=True)
class FileSplit:
    """One read task: a file plus (for parquet) the row groups to read.
    `partition_values` carries the Hive-style key=value directory components
    of the file's path (reference: PartitionedFile partitionValues appended
    by ColumnarPartitionReaderWithPartitionValues)."""

    path: str
    fmt: str
    row_groups: Optional[Tuple[int, ...]] = None
    options: Tuple[Tuple[str, Any], ...] = ()
    partition_values: Tuple[Tuple[str, Optional[str]], ...] = ()

    def opt(self, key: str, default=None):
        return dict(self.options).get(key, default)


HIVE_NULL = "__HIVE_DEFAULT_PARTITION__"


def _orc_stats_vrange(attr, meta) -> Optional[Tuple[int, int]]:
    """(lo, hi) for an ORC column from the file footer's IntegerStatistics
    (parsed in orc_device.parse_file_meta), INT64 columns only: the
    device-decoded column never passes through a host array, so the
    upload-time min/max pass (columnar.batch.host_value_range) cannot
    see it, and the writer's stats carry the same proof for free."""
    from spark_rapids_tpu.columnar.batch import (
        int64_narrowing_enabled,
        quantize_vrange,
    )

    if attr.data_type is not DataType.INT64 or not int64_narrowing_enabled():
        return None
    try:
        cid = meta.names.index(attr.name)
        if 0 <= cid < len(meta.col_stats):
            st = meta.col_stats[cid]
            if (isinstance(st, tuple) and len(st) == 2
                    and all(isinstance(x, int) for x in st)):
                return quantize_vrange(st)
    except (ValueError, AttributeError):
        pass
    return None


def _stack_minmax(reds):
    """Stack per-column (any_valid, lo, hi) scalars into one [n, 3] int64
    array so the verify fetch is a single host round trip."""
    from spark_rapids_tpu.engine.jit_cache import get_or_build

    def build():
        import jax
        import jax.numpy as jnp

        def fn(rs):
            return jnp.stack([
                jnp.stack([a.astype(jnp.int64), lo.astype(jnp.int64),
                           hi.astype(jnp.int64)])
                for a, lo, hi in rs])
        return jax.jit(fn)

    return get_or_build(("scan_minmax_stack", len(reds)), build)(reds)


def _minmax_valid(data, validity):
    """(any_valid, min, max) over valid lanes — jitted via the process cache
    so every int64 column shares one compiled reduction per shape bucket."""
    from spark_rapids_tpu.engine.jit_cache import get_or_build

    def build():
        import jax
        import jax.numpy as jnp

        def fn(d, v):
            lo = jnp.min(jnp.where(v, d, jnp.iinfo(d.dtype).max))
            hi = jnp.max(jnp.where(v, d, jnp.iinfo(d.dtype).min))
            return jnp.any(v), lo, hi
        return jax.jit(fn)

    return get_or_build(("scan_minmax_valid",), build)(data, validity)


def verify_footer_vranges(dev_cols: Dict[str, "ColumnVector"]) -> List[str]:
    """Check footer-statistics-derived value ranges against the decoded
    data before any consumer narrows on them. Writers have shipped corrupt
    min/max stats (parquet-mr carries CorruptStatistics heuristics for
    exactly this); unlike row-group pruning — where a bad stat only loses
    pruning — a bad range here would silently WRAP int32-narrowed values.
    One batched reduction + one host transfer covers every claimed column
    of the row group/stripe; a violated claim drops the vrange (the file
    loses the optimization, never correctness). Returns the dropped column
    names so a FILE-level claim source (ORC) can stop re-claiming it for
    every subsequent stripe."""
    import jax

    claimed = [(name, cv) for name, cv in dev_cols.items()
               if cv.vrange is not None and cv.dtype is DataType.INT64]
    if not claimed:
        return []
    reds = [_minmax_valid(cv.data, cv.validity) for _, cv in claimed]
    # ONE stacked transfer: per-scalar device_get blocks once per leaf,
    # a fence each
    stacked = _stack_minmax(tuple(reds))
    flat = np.asarray(jax.device_get(stacked))
    vals = [(bool(flat[i, 0]), int(flat[i, 1]), int(flat[i, 2]))
            for i in range(len(reds))]
    dropped: List[str] = []
    for (name, cv), (any_valid, mn, mx) in zip(claimed, vals):
        if not bool(any_valid):
            continue
        lo, hi = cv.vrange
        if int(mn) < lo or int(mx) > hi:
            import logging

            logging.getLogger(__name__).warning(
                "column %r: footer min/max stats (%d, %d) contradict the "
                "decoded data (%d, %d) — corrupt statistics; dropping the "
                "narrowing range", name, lo, hi, int(mn), int(mx))
            cv.vrange = None
            dropped.append(name)
    return dropped


def partition_values_of(path: str, roots: List[str]):
    """key=value components of `path` under its root directory, in path
    order (the Hive partition-discovery rule Spark applies)."""
    from urllib.parse import unquote

    for root in roots:
        root = root.rstrip(os.sep)
        if os.path.isdir(root) and path.startswith(root + os.sep):
            rel = os.path.dirname(path[len(root) + 1:])
            out = []
            for comp in rel.split(os.sep):
                if "=" in comp:
                    k, _, v = comp.partition("=")
                    v = unquote(v)
                    out.append((k, None if v == HIVE_NULL else v))
            return tuple(out)
    return ()


def infer_partition_schema(
        pvs: List[Tuple[Tuple[str, Optional[str]], ...]]):
    """Column order + types for discovered partition values (Spark's
    partition-column type inference: int64 -> float64 -> string)."""
    names: List[str] = []
    values: Dict[str, List[Optional[str]]] = {}
    for pv in pvs:
        for k, v in pv:
            if k not in values:
                names.append(k)
                values[k] = []
            values[k].append(v)
    out = []
    for n in names:
        dt = DataType.INT64
        for v in values[n]:
            if v is None:
                continue
            try:
                int(v)
                continue
            except ValueError:
                pass
            try:
                float(v)
                dt = DataType.FLOAT64 if dt is DataType.INT64 else dt
                continue
            except ValueError:
                dt = DataType.STRING
                break
        out.append(AttributeReference(n, dt, True))
    return out


def expand_paths(paths: List[str], suffixes: Tuple[str, ...]) -> List[str]:
    out: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for root, _dirs, files in os.walk(p):
                for f in sorted(files):
                    if f.endswith(suffixes) and not f.startswith(("_", ".")):
                        out.append(os.path.join(root, f))
        else:
            out.append(p)
    if not out:
        raise FileNotFoundError(f"no input files under {paths}")
    return out


_SUFFIXES = {
    "parquet": (".parquet", ".parq"),
    "orc": (".orc",),
    "csv": (".csv", ".txt", ".tsv"),
}


def plan_splits(fmt: str, paths: List[str], options: Dict[str, Any],
                conf, files: Optional[List[str]] = None) -> List[FileSplit]:
    """Split input files into read partitions. Parquet splits by row
    groups so each task reads at most maxReadBatchSizeRows rows."""
    from spark_rapids_tpu import conf as C

    files = files or expand_paths(paths, _SUFFIXES.get(fmt, ()))
    opt_t = tuple(sorted(options.items()))
    pvs = {f: partition_values_of(f, paths) for f in files}
    if fmt != "parquet":
        return [FileSplit(f, fmt, None, opt_t, pvs[f]) for f in files]
    import pyarrow.parquet as pq

    max_rows = conf.get(C.MAX_READ_BATCH_SIZE_ROWS)
    splits: List[FileSplit] = []
    for f in files:
        md = pq.ParquetFile(f).metadata
        group: List[int] = []
        rows = 0
        for rg in range(md.num_row_groups):
            n = md.row_group(rg).num_rows
            if group and rows + n > max_rows:
                splits.append(FileSplit(f, fmt, tuple(group), opt_t, pvs[f]))
                group, rows = [], 0
            group.append(rg)
            rows += n
        if group:
            splits.append(FileSplit(f, fmt, tuple(group), opt_t, pvs[f]))
    return splits


def dict_chunk_ndvs(split: FileSplit, attrs: List[AttributeReference],
                    conf) -> Dict[str, List[int]]:
    """The STRING columns of a parquet split that Arrow can hand over as
    dictionary codes at no cost of its own, each with its chunks'
    dictionary sizes: in every row group of the split the chunk is
    dictionary-encoded throughout (a dictionary page, no PLAIN data page
    behind it: the page headers say, the footer cannot) and its
    dictionary passes the encoded-scan heuristic
    (`rapids.tpu.sql.encoded.*`). Any other STRING column (a PLAIN
    `l_comment`) is not named: asked for as a dictionary, Arrow would
    build one for it value by value. Headers only: a few hundred bytes a
    chunk."""
    from spark_rapids_tpu import conf as C
    from spark_rapids_tpu.columnar import encoded as ENC
    from spark_rapids_tpu.io import parquet_device as PD

    strings = [a.name for a in attrs if a.data_type is DataType.STRING]
    if split.fmt != "parquet" or not strings or \
            not conf.get(C.ENCODED_ENABLED):
        return {}
    import pyarrow.parquet as pq

    frac = conf.get(C.ENCODED_MAX_DICT_FRACTION)
    md = pq.read_metadata(split.path)
    groups = list(split.row_groups) if split.row_groups is not None \
        else list(range(md.num_row_groups))
    if not groups:
        return {}
    index = {md.row_group(groups[0]).column(ci).path_in_schema: ci
             for ci in range(md.num_columns)}
    out: Dict[str, List[int]] = {}
    # a row group without rows holds no value to decode either way
    groups = [rg for rg in groups if md.row_group(rg).num_rows]
    for name in strings:
        ci = index.get(name)
        ndvs = []
        for rg in groups if ci is not None else ():
            col = md.row_group(rg).column(ci)
            ndv = PD.chunk_dict_ndv(split.path, col)
            if ndv is None or not ENC.scan_encoded_ok(
                    ndv, md.row_group(rg).num_rows, frac) or \
                    PD.chunk_dict_only(split.path, col) is not True:
                break
            ndvs.append(ndv)
        if ci is not None and len(ndvs) == len(groups):
            out[name] = ndvs
    return out


def read_split(split: FileSplit, attrs: List[AttributeReference],
               pf=None, dict_columns: Tuple[str, ...] = ()) -> pa.Table:
    """The split's rows of `attrs` as one Arrow table (`pf`: the split's
    parquet file where the caller has it open already). `dict_columns`
    (`dict_chunk_ndvs` names them) come back as dictionary arrays, one a
    row group, undecoded."""
    names = [a.name for a in attrs]
    if split.fmt == "parquet":
        import pyarrow.parquet as pq

        pf = pf or pq.ParquetFile(split.path,
                                  read_dictionary=list(dict_columns) or None)
        groups = list(split.row_groups) if split.row_groups is not None \
            else list(range(pf.metadata.num_row_groups))
        return pf.read_row_groups(groups, columns=names)
    if split.fmt == "orc":
        import pyarrow.orc as po

        return po.ORCFile(split.path).read(columns=names)
    if split.fmt == "csv":
        header = _to_bool(split.opt("header", False))
        sep = split.opt("sep", split.opt("delimiter", ","))
        table = _read_csv_arrow(split.path, names, attrs, sep, header)
        return table.select(names)
    raise ValueError(f"unknown format {split.fmt}")


def _read_csv_arrow(source, file_names, attrs, sep: str, header: bool,
                    include=None):
    """ONE pyarrow CSV option set for the host path and the device path's
    host-rest parse (they must never diverge). `source` is a path or a
    pyarrow buffer reader; `include` restricts converted columns."""
    import pyarrow.csv as pc

    from spark_rapids_tpu.io.arrow_convert import dt_to_arrow_type

    read_opts = pc.ReadOptions(
        column_names=None if header else file_names,
        autogenerate_column_names=False)
    convert = pc.ConvertOptions(
        column_types={a.name: dt_to_arrow_type(a.data_type) for a in attrs},
        include_columns=include,
        strings_can_be_null=True)
    return pc.read_csv(source, read_options=read_opts,
                       parse_options=pc.ParseOptions(delimiter=sep),
                       convert_options=convert)


def _to_bool(v) -> bool:
    if isinstance(v, bool):
        return v
    return str(v).strip().lower() in ("1", "true", "yes")


def _with_partition_columns(batch: HostColumnarBatch, attrs,
                            pv: Dict[str, Optional[str]]) -> HostColumnarBatch:
    """Rebuild the batch in `attrs` order, filling partition columns with
    their (parsed) constant directory value."""
    n = batch.num_rows
    by_name = {}
    di = 0
    for a in attrs:
        if a.name in pv:
            continue
        by_name[a.name] = batch.columns[di]
        di += 1
    cols = []
    for a in attrs:
        if a.name not in pv:
            cols.append(by_name[a.name])
            continue
        raw = pv[a.name]
        if raw is None:
            validity = np.zeros(n, dtype=bool)
            if a.data_type is DataType.STRING:
                data = np.full(n, "", dtype=object)
            else:
                data = np.zeros(n, dtype=a.data_type.to_np())
        else:
            validity = np.ones(n, dtype=bool)
            if a.data_type is DataType.STRING:
                data = np.full(n, raw, dtype=object)
            elif a.data_type is DataType.FLOAT64:
                data = np.full(n, float(raw), dtype=np.float64)
            else:
                data = np.full(n, int(raw), dtype=a.data_type.to_np())
        cols.append(HostColumnVector(a.data_type, data, validity))
    return HostColumnarBatch(cols, n)


class _FileScanBase(PhysicalExec):
    def __init__(self, attrs: List[AttributeReference],
                 splits: List[FileSplit], fmt: str):
        super().__init__()
        self.attrs = attrs
        self.splits = splits
        self.fmt = fmt

    @property
    def output(self) -> List[AttributeReference]:
        return self.attrs

    @property
    def coalesce_after(self) -> bool:
        # scans emit per-row-group/per-chunk batches; coalescing them to the
        # target batch size is the reference's signature plan shape
        # (GpuScans set coalesceAfter, GpuCoalesceBatches sits above scans)
        return True

    def with_children(self, new_children):
        assert not new_children
        return self

    def node_name(self):
        return f"{type(self).__name__}({self.fmt}, {len(self.splits)} splits)"

    def _read_host_iter(self, split: FileSplit, conf, stage: bool = False,
                        dict_columns: Tuple[str, ...] = ()):
        """Generator form of the host decode: the Arrow read runs on first
        pull, so a prefetch wrapper (io/prefetch.py) moves the WHOLE decode
        onto its worker thread — batch k+1 of the query decodes while
        batch k computes downstream.

        With `stage` (the device scan, `TpuFileScanExec._read_host`) it
        yields each batch packed for its upload, a `StagedUpload`, in
        place of the `HostColumnarBatch`: the packing is host work on
        host data and belongs with the decode, ahead of the task's
        admission permit, wherever the decode runs. The Arrow table and
        the decoded batch are dropped once packed: what waits for the
        permit is the packed buffers alone."""
        from spark_rapids_tpu import conf as C

        pv = dict(split.partition_values)
        data_attrs = [a for a in self.attrs if a.name not in pv]
        # on the prefetcher's thread, which carries the task's context
        # and span (io/prefetch.py)
        with obs_span("scan.host_decode", columns=len(data_attrs)) as sp:
            # its three steps, each a span of its own: Arrow's pool works
            # in the first while this thread sleeps; the other two are
            # this thread's numpy and Python
            with obs_span("scan.arrow_read"):
                table = read_split(split, data_attrs,
                                   dict_columns=dict_columns)
            with obs_span("scan.convert"):
                batch = arrow_to_host_batch(
                    table, data_attrs,
                    conf.get(C.ENCODED_MAX_DICT_FRACTION) if dict_columns
                    else None)
                del table
                if sp is not None:
                    sp.attrs["rows"] = batch.num_rows
                    if dict_columns:
                        coded = [c for c in batch.columns
                                 if getattr(c, "dictionary", None)
                                 is not None]
                        sp.attrs["dict_columns"] = len(coded)
                        sp.attrs["dict_bytes"] = sum(
                            c.data.nbytes
                            + int(c.dictionary.host_offsets[-1])
                            for c in coded)
                if pv:
                    # append partition-value constant columns (reference:
                    # ColumnarPartitionReaderWithPartitionValues)
                    batch = _with_partition_columns(batch, self.attrs, pv)
                max_rows = conf.get(C.MAX_READ_BATCH_SIZE_ROWS)
                if batch.num_rows <= max_rows:
                    batches = [batch]
                else:
                    batches = [batch.slice(i, max_rows)
                               for i in range(0, batch.num_rows, max_rows)]
                del batch
            if stage:
                with obs_span("scan.pack") as packed:
                    batches = [hb.stage_upload() for hb in batches]
                    if packed is not None:
                        packed.attrs["packed_bytes"] = sum(
                            b.nbytes for st in batches for b in st.bufs)
        # handed over one at a time: a batch that has gone downstream is
        # not kept alive from here
        batches.reverse()
        while batches:
            yield batches.pop()

    def _host_batches_prefetched(self, split: FileSplit, conf,
                                 stage: bool = False,
                                 dict_columns: Tuple[str, ...] = ()):
        """Host decode iterator with the configured double-buffering depth
        (rapids.tpu.io.prefetchBatches; per-read option overrides)."""
        from spark_rapids_tpu.io.prefetch import maybe_prefetch, prefetch_depth

        return maybe_prefetch(
            self._read_host_iter(split, conf, stage, dict_columns),
            prefetch_depth(conf, split))


class CpuFileScanExec(_FileScanBase, CpuExec):
    placement = "cpu"

    def execute(self, ctx: ExecContext) -> PartitionedBatches:
        def factory(pidx: int):
            return count_output(
                self.metrics,
                self._host_batches_prefetched(self.splits[pidx], ctx.conf))

        return PartitionedBatches(len(self.splits), factory)


class TpuFileScanExec(_FileScanBase, TpuExec):
    """A parquet column that is not a string reaches the device one way:
    Arrow decodes it on the host, in the split's one threaded read, and
    `to_device` moves it (`_read_host`). On the chip that read alone ran
    1.8x (Q6) and 1.4x (a parquet write) ahead of a device decode of the
    same columns, measured twice (PERF.md section 6, PR 30), so there is
    no other decoder for them. STRING columns decode ON DEVICE from raw
    chunk bytes (io/parquet_device.py — the reference's accelerator-side
    decode, GpuParquetScan.scala:536-556), the one input whose device
    form, dictionary codes + dictionary (columnar/encoded.py), Arrow's
    read does not hand over; `_read_device` serves a scan that has one,
    with Arrow decoding the columns beside it. The admission semaphore
    is acquired exactly where the reference acquires it: before bytes go
    on the device (GpuParquetScan.scala:554).

    Neither path holds a permit through host work on host data. The
    host decoder's split is one Arrow read on the scan prefetcher's
    reader thread (io/prefetch.py, `rapids.tpu.io.prefetchBatches`; on
    the task's own thread at depth 0), packed there for its upload
    (`_read_host_iter` with `stage`: `HostColumnarBatch.stage_upload`),
    so the task asks for its permit with the packed buffers in hand and
    holds it for `StagedUpload.upload()` and the program issue alone
    (`_read_host` / `_upload`). A split with a string column is
    staged on the task thread — the footer, each string chunk's read,
    decompression and page walk, Arrow's decode of the other columns —
    before the task asks for the permit (`_stage_split`), and only
    uploads and program issue run under it (`_decode_staged`). A reader
    thread a task was tried there and lost to this order on the chip's
    host, where the interpreter's lock, not the cores, bounds the host
    half (PERF.md section 6, PR 29). Staging is host memory only: a
    split's staged row groups, as the host path holds a split's packed
    buffers (fresh ones every split, written by nobody once packed: the
    runtime may still be reading them after `jnp.asarray` returns)."""

    placement = "tpu"

    def execute(self, ctx: ExecContext) -> PartitionedBatches:
        from spark_rapids_tpu import conf as C

        # the attributes alone say whether a split can hold a STRING
        # column: a scan without one opens no footer to find out how it
        # is encoded
        n_strings = sum(a.data_type is DataType.STRING
                        for a in self.attrs) if self.fmt == "parquet" else 0
        device_decode = n_strings > 0 and \
            ctx.conf.get(C.PARQUET_DEVICE_DECODE)
        device_csv = self.fmt == "csv" and ctx.conf.get(C.CSV_DEVICE_PARSE)
        device_orc = self.fmt == "orc" and ctx.conf.get(C.ORC_DEVICE_DECODE)

        def factory(pidx: int):
            from spark_rapids_tpu.engine.retry import with_retry

            def gen():
                # a dictionary-encoded STRING column is Arrow's too: it
                # hands the codes and the dictionary over undecoded
                # (PERF.md section 6, PR 37). The device decoder keeps a
                # split that holds a STRING column Arrow would have to
                # decode value by value (PLAIN, a dictionary that fell
                # back): there it decodes every STRING column of the split
                dict_columns = self._arrow_dict_columns(pidx, ctx.conf) \
                    if n_strings else ()
                if device_decode and len(dict_columns) < n_strings:
                    # a generator over the split's row groups; False where
                    # no column qualified, and nothing was yielded
                    if (yield from self._read_device(self.splits[pidx],
                                                     ctx.conf)):
                        return
                # device decodes are pure over (split bytes, conf): a
                # retryable OOM/transient error re-reads and re-decodes the
                # split after the spill (with_retry); exhaustion propagates
                # for task retry / query-level CPU fallback
                if device_csv:
                    batches = with_retry(
                        lambda: self._read_device_csv(self.splits[pidx],
                                                      ctx.conf), site="scan")
                    if batches is not None:
                        yield from batches
                        return
                if device_orc:
                    # per-stripe generator: a retry wrapper around next()
                    # could silently truncate a closed generator, so device
                    # ORC errors propagate to the task-level retry instead
                    batches = self._read_device_orc(self.splits[pidx],
                                                    ctx.conf)
                    if batches is not None:
                        yield from batches
                        return
                yield from self._read_host(self.splits[pidx], ctx.conf,
                                           dict_columns)

            return count_output(self.metrics, gen())

        return PartitionedBatches(len(self.splits), factory)

    def _read_host(self, split: FileSplit, conf,
                   dict_columns: Tuple[str, ...] = ()):
        """Host path: decode AND packing double-buffer on the prefetch
        worker (inline, on this thread, at depth 0: either way before the
        task asks for its permit), so what runs under the permit is the
        transfer and the program issue (asynchronously — jax returns an
        unblocked device future) and nothing else: batch k+1's decode
        and packing overlap batch k's upload and downstream compute, and
        another task's."""
        for staged in self._host_batches_prefetched(
                split, conf, stage=True, dict_columns=dict_columns):
            TpuSemaphore.get().acquire_if_necessary(current_task_id())
            yield self._upload(staged)

    @staticmethod
    def _upload(staged: StagedUpload):
        """One packed host batch onto the device, under the caller's
        permit (a step of its own so that the generator's frame keeps no
        reference to a batch that has gone downstream). `upload()` is
        pure over the staged buffers, which nothing writes: a retry
        issues it again from the same bytes."""
        from spark_rapids_tpu.engine.retry import with_retry

        with obs_span("scan.upload", columns=len(staged.specs),
                      staged=1) as sp:
            batch = with_retry(staged.upload, site="scan")
            if sp is not None:
                sp.attrs["bytes"] = batch.device_memory_size()
        coded = [cv for cv in batch.columns
                 if getattr(cv, "dictionary", None) is not None]
        if coded:
            from spark_rapids_tpu.columnar.encoded import record_scan_emission

            for cv in coded:
                record_scan_emission(cv, batch.num_rows)
        return batch

    def _read_device_csv(self, split: FileSplit, conf):
        """Device CSV parse for one split; None -> structure/columns not
        eligible (caller uses the host Arrow path). Mirrors _read_device:
        integral columns parse on device from the raw bytes, everything
        else host-parses and uploads."""
        from spark_rapids_tpu.columnar.batch import (
            ColumnVector,
            bucket_capacity,
        )
        from spark_rapids_tpu.io import csv_device as CD
        from spark_rapids_tpu.io.arrow_convert import arrow_to_host_batch

        pv = dict(split.partition_values)
        data_attrs = [a for a in self.attrs if a.name not in pv]
        if not any(CD.device_parseable(a.data_type) for a in data_attrs):
            return None
        header = _to_bool(split.opt("header", False))
        sep = split.opt("sep", split.opt("delimiter", ","))
        if not isinstance(sep, str) or len(sep) != 1:
            return None
        from spark_rapids_tpu import conf as C

        if os.path.getsize(split.path) > conf.get(C.CSV_DEVICE_MAX_SPLIT_BYTES):
            # the whole-file boundary plan costs rows*cols int32 tables in
            # host RAM; past this size the streaming Arrow path is cheaper
            return None
        with open(split.path, "rb") as f:
            data = f.read()
        if not data:
            return None
        first_nl = data.find(b"\n")
        first_line = data[:first_nl if first_nl >= 0 else len(data)]
        ncols = first_line.count(sep.encode()) + 1
        if not header and ncols != len(data_attrs):
            return None
        table = CD.plan_fields(data, ncols, header, sep)
        if table is None:
            return None
        eligible = CD.eligible_attrs(data_attrs, table.header_names,
                                     [a.name for a in data_attrs])
        if not eligible:
            return None
        has_dev_strings = any(
            a.data_type is DataType.STRING and a.name in eligible
            for a in data_attrs)
        if has_dev_strings:
            # the host oracle validates UTF-8 on string conversion; the
            # device path carries raw bytes, so gate up front — on invalid
            # input the host path raises the error both engines must raise
            try:
                data.decode("utf-8")
            except UnicodeDecodeError:
                return None
        rows = table.num_rows
        cap = bucket_capacity(max(rows, 1))
        TpuSemaphore.get().acquire_if_necessary(current_task_id())
        import jax

        dev_cols = {}
        malformed_flags = []
        for a in data_attrs:
            if a.name not in eligible:
                continue
            if a.data_type is DataType.STRING:
                dev_cols[a.name] = CD.decode_string_column(
                    table, eligible[a.name], cap)
                continue
            d, v, bad = CD.decode_column(table, eligible[a.name],
                                         a.data_type, cap)
            malformed_flags.append(bad)
            dev_cols[a.name] = ColumnVector(a.data_type, d, v)
        if malformed_flags and any(
                bool(x) for x in jax.device_get(malformed_flags)):
            # malformed field somewhere: ONE batched sync, then the host
            # parser raises the same error both engines would
            return None
        rest = [a for a in data_attrs if a.name not in dev_cols]
        hb = None
        if rest:
            # host-parse ONLY the non-device columns, from the bytes already
            # in memory — never a second disk read, never re-converting the
            # columns the device just parsed
            import pyarrow as pa

            all_names = table.header_names if header \
                else [a.name for a in data_attrs]
            tbl = _read_csv_arrow(pa.BufferReader(data), all_names, rest,
                                  sep, header,
                                  include=[a.name for a in rest])
            hb = arrow_to_host_batch(tbl, rest)
            if hb.num_rows != rows:
                return None  # host parser disagrees: fall back
        return self._assemble_device_batch(dev_cols, hb, rest, pv, rows,
                                           conf)

    def _read_device_orc(self, split: FileSplit, conf):
        """Device ORC decode for one split; None -> not eligible (caller
        uses the host Arrow path). Two phases: (1) HOST-ONLY planning —
        protobuf walk + run tables for every stripe/column, so any
        unsupported shape falls back before a single device byte moves;
        (2) a generator that, per stripe, acquires the admission semaphore,
        uploads JUST that stripe's region, expands on device, and yields —
        peak HBM is one stripe, not the file."""
        from spark_rapids_tpu.io import orc_device as OD

        pv = dict(split.partition_values)
        data_attrs = [a for a in self.attrs if a.name not in pv]
        try:
            with open(split.path, "rb") as f:
                # tail-first: reject unsupported codecs from the PostScript
                # alone, before a full-file read (zlib/snappy streams
                # decompress on the host into the device expansion)
                f.seek(0, os.SEEK_END)
                size = f.tell()
                f.seek(max(0, size - 4096))
                if OD.tail_compression(f.read()) not in \
                        OD.SUPPORTED_COMPRESSION:
                    return None
                f.seek(0)
                raw = f.read()
            meta = OD.parse_file_meta(raw)
        except (OD._Unsupported, OSError):
            return None
        name_to_cid = {n: i for i, n in enumerate(meta.names) if n}
        eligible = [a for a in data_attrs
                    if a.name in name_to_cid and
                    OD.column_eligible(meta, name_to_cid[a.name],
                                       a.data_type)]
        if not eligible:
            return None
        rest = [a for a in data_attrs if a not in eligible]
        # phase 1: host-only plans for every stripe x eligible column
        stripe_plans = []
        try:
            for si in meta.stripes:
                if meta.compression != 0:
                    region = raw[si.offset:
                                 si.offset + si.index_length +
                                 si.data_length + si.footer_length]
                    norm, streams, encs, tz = OD.normalize_stripe(
                        region, si, meta.compression,
                        {name_to_cid[a.name] for a in eligible})
                    plans = {
                        a.name: OD.plan_column(norm, streams, encs,
                                               name_to_cid[a.name],
                                               si.num_rows, 0,
                                               dtype=a.data_type,
                                               timezone=tz)
                        for a in eligible}
                else:
                    streams, encs, tz = OD.parse_stripe_footer(raw, si)
                    plans = {
                        a.name: OD.plan_column(raw, streams, encs,
                                               name_to_cid[a.name],
                                               si.num_rows, si.offset,
                                               dtype=a.data_type,
                                               timezone=tz)
                        for a in eligible}
                stripe_plans.append(plans)
        except Exception:
            return None  # unsupported shape anywhere: whole-split fallback

        # the generator re-reads each stripe region from disk on demand —
        # `raw` must NOT outlive phase 1, so peak host memory during the
        # scan is one stripe, not the file
        del raw
        return self._orc_stripe_batches(split, meta, stripe_plans,
                                        eligible, rest, pv, conf,
                                        {name_to_cid[a.name]
                                         for a in eligible})

    def _orc_stripe_batches(self, split, meta, stripe_plans, eligible,
                            rest, pv, conf, eligible_cids=None):
        """Phase 2 generator: per-stripe read + upload + expand + yield."""
        import jax.numpy as jnp

        from spark_rapids_tpu.columnar.batch import (
            ColumnVector,
            bucket_capacity,
        )
        from spark_rapids_tpu.io import orc_device as OD

        orc_file = None
        for sidx, si in enumerate(meta.stripes):
            rows = si.num_rows
            cap = bucket_capacity(max(rows, 1))
            TpuSemaphore.get().acquire_if_necessary(current_task_id())
            with open(split.path, "rb") as f:
                f.seek(si.offset)
                region = f.read(si.index_length + si.data_length +
                                si.footer_length)
            if meta.compression != 0:
                # deterministic re-normalization over the SAME column set:
                # plan offsets index the same decompressed image (peak host
                # memory stays one stripe; decompression is host
                # control-plane work)
                region, _streams, _encs, _tz = OD.normalize_stripe(
                    region, si, meta.compression, eligible_cids)
            stripe_dev = jnp.asarray(np.frombuffer(region, dtype=np.uint8))
            from spark_rapids_tpu import conf as C3
            from spark_rapids_tpu.columnar import encoded as ENC

            enc_ok = conf.get(C3.ENCODED_ENABLED)
            enc_frac = conf.get(C3.ENCODED_MAX_DICT_FRACTION)
            dev_cols = {}
            for a in eligible:
                if a.data_type is DataType.STRING:
                    plan = stripe_plans[sidx][a.name]
                    if enc_ok and plan.dict_len_rt is not None and \
                            ENC.scan_encoded_ok(plan.dict_size, rows,
                                                enc_frac):
                        # DICTIONARY_V2 stays ENCODED: codes off the
                        # index stream, dictionary bytes interned from
                        # the host stripe image — ORC joins the
                        # code-space pipeline on the same eligibility
                        # as parquet (columnar/encoded.py)
                        codes, v, lens_np = OD.expand_string_codes(
                            stripe_dev, plan, rows, cap)
                        offs_np = np.zeros(len(lens_np) + 1,
                                           dtype=np.int32)
                        np.cumsum(lens_np, out=offs_np[1:])
                        db = np.frombuffer(
                            region, dtype=np.uint8,
                            count=int(offs_np[-1]),
                            offset=plan.data_start).copy()
                        dct = ENC.DeviceDictionary.from_byte_table(
                            db, offs_np)
                        cv = ENC.DictionaryColumn(a.data_type, codes, v,
                                                  dct)
                        ENC.record_scan_emission(cv, rows)
                        dev_cols[a.name] = cv
                        continue
                    d, v, offs = OD.expand_string_column(
                        stripe_dev, plan, rows, cap)
                    dev_cols[a.name] = ColumnVector(a.data_type, d, v,
                                                    offs)
                elif a.data_type in (DataType.FLOAT32, DataType.FLOAT64):
                    d, v = OD.expand_float_column(
                        stripe_dev, stripe_plans[sidx][a.name],
                        a.data_type, rows, cap)
                    dev_cols[a.name] = ColumnVector(a.data_type, d, v)
                elif a.data_type is DataType.BOOL:
                    d, v = OD.expand_bool_column(
                        stripe_dev, stripe_plans[sidx][a.name], rows, cap)
                    dev_cols[a.name] = ColumnVector(a.data_type, d, v)
                elif a.data_type is DataType.TIMESTAMP:
                    d, v = OD.expand_timestamp_column(
                        stripe_dev, stripe_plans[sidx][a.name], rows, cap)
                    dev_cols[a.name] = ColumnVector(a.data_type, d, v)
                else:
                    d, v = OD.expand_column(stripe_dev,
                                            stripe_plans[sidx][a.name],
                                            a.data_type, rows, cap)
                    dev_cols[a.name] = ColumnVector(
                        a.data_type, d, v,
                        vrange=_orc_stats_vrange(a, meta))
            # ORC stats are FILE-level: a claim one stripe disproves must
            # not be re-claimed (re-reduced, re-warned) by later stripes
            for name in verify_footer_vranges(dev_cols):
                cid = meta.names.index(name)
                if 0 <= cid < len(meta.col_stats):
                    meta.col_stats[cid] = None
            hb = None
            if rest:
                import pyarrow.orc as po

                if orc_file is None:
                    orc_file = po.ORCFile(split.path)
                rb = orc_file.read_stripe(sidx,
                                          columns=[a.name for a in rest])
                hb = arrow_to_host_batch(pa.Table.from_batches([rb]), rest)
                if hb.num_rows != rows:
                    raise IOError(
                        f"ORC stripe {sidx} row-count mismatch: device "
                        f"plan {rows} vs host {hb.num_rows}")
            yield from self._assemble_device_batch(dev_cols, hb, rest, pv,
                                                   rows, conf)

    def _assemble_device_batch(self, dev_cols, hb, rest, pv, rows, conf):
        """Combine device-decoded columns with a host-decoded partial batch
        (+ partition-value columns) into output batches, sliced to
        MAX_READ_BATCH_SIZE_ROWS. Shared by the parquet and CSV device read
        paths — their mixed-batch assembly must never diverge. Two steps,
        for the parquet scan to run the first ahead of its permit."""
        return self._assemble_staged(
            dev_cols, self._stage_host_part(hb, rest, pv, rows), rest, pv,
            rows, conf)

    def _stage_host_part(self, hb, rest, pv, rows):
        """HOST step of the assembly: the host-decoded columns, with the
        partition-value columns beside them, packed for their upload
        (`HostColumnarBatch.stage_upload`); None where the batch has
        neither."""
        if hb is None and pv:
            hb = HostColumnarBatch([], rows)
        if hb is None:
            return None
        if pv:
            hb = _with_partition_columns(
                hb, rest + [a for a in self.attrs if a.name in pv], pv)
        return hb.stage_upload()

    def _assemble_staged(self, dev_cols, staged, rest, pv, rows, conf):
        """DEVICE step of the assembly: the staged host columns uploaded
        and set beside the device-decoded ones."""
        from spark_rapids_tpu import conf as C2
        from spark_rapids_tpu.columnar.batch import (
            ColumnarBatch,
            slice_batch_host,
        )

        host_part = None
        host_names: List[str] = []
        if staged is not None:
            host_names = [a.name for a in rest] + \
                [a.name for a in self.attrs if a.name in pv]
            # the host-decoded columns go up at their full width (the
            # device decoder's string chunks go up decompressed, in
            # io/parquet_device.py, under the same span name)
            with obs_span("scan.upload", columns=len(host_names)) as sp:
                host_part = staged.upload()
                if sp is not None:
                    sp.attrs["bytes"] = host_part.device_memory_size()
        cols = []
        for a in self.attrs:
            if a.name in dev_cols:
                cols.append(dev_cols[a.name])
            else:
                cols.append(host_part.columns[host_names.index(a.name)])
        # decode-kernel outputs + a fresh upload: consume-once by
        # construction, like the host path's to_device batches — keeps
        # the analyzer's scan-input donation credit sound
        batch = ColumnarBatch(cols, rows, owned=True)
        max_rows = conf.get(C2.MAX_READ_BATCH_SIZE_ROWS)
        if rows <= max_rows:
            return [batch]
        return [slice_batch_host(batch, i, max_rows)
                for i in range(0, rows, max_rows)]

    def _dict_ndvs_by_split(self, conf) -> List[Dict[str, List[int]]]:
        """`dict_chunk_ndvs` of every split, read once for the exec and
        these conf values: the planner asks first, and the tasks of every
        action after it find the answer here (the headers are parsed in
        Python, which eight tasks starting together would do one after
        another). The plan cache keys a physical plan by its files' size
        and mtime, so an exec never outlives the footers it read."""
        from spark_rapids_tpu import conf as C3

        key = (conf.get(C3.ENCODED_ENABLED),
               conf.get(C3.ENCODED_MAX_DICT_FRACTION))
        cached = getattr(self, "_dict_ndvs_cache", None)
        if cached is None or cached[0] != key:
            cached = (key, [dict_chunk_ndvs(sp, self.attrs, conf)
                            for sp in self.splits])
            self._dict_ndvs_cache = cached
        return cached[1]

    def _arrow_dict_columns(self, pidx: int, conf) -> Tuple[str, ...]:
        """The STRING columns of split `pidx` that the scan asks Arrow to
        hand over undecoded."""
        return tuple(self._dict_ndvs_by_split(conf)[pidx])

    def dict_columns_plan(self, conf) -> Dict[str, Tuple[int, int]]:
        """Plan-time: the STRING columns that every split of the scan
        hands over as dictionary codes (`dict_chunk_ndvs`, the runtime's
        own rule and its own reading), each with (the largest chunk
        dictionary, the sum of all chunks' dictionaries): the first is
        what a task's unified dictionary is expected to hold, the second
        a sound bound on the distinct values of the whole column."""
        try:
            per_split = self._dict_ndvs_by_split(conf)
        except Exception:  # unreadable footer: the runtime says so
            return {}
        out: Dict[str, Tuple[int, int]] = {}
        for name in per_split[0] if per_split else ():
            if all(name in d for d in per_split):
                ndvs = [n for d in per_split for n in d[name]]
                out[name] = (max(ndvs, default=0), sum(ndvs))
        return out

    def encoded_plan(self, conf) -> Dict[str, str]:
        """Plan-time mirror of the runtime encoded-scan decision
        (columnar/encoded.py): column name -> 'certain' (every row group
        of every split is a dictionary-only chunk that clears the
        ndv/rows heuristic — the decode WILL emit codes) or 'possible'
        (a dictionary page exists somewhere but dict-only-ness or the
        heuristic cannot be proven from footers alone). The resource
        analyzer reduces its byte model only for 'certain' columns (the
        pessimistic ceiling must stay sound) and widens its savings
        interval over 'possible' ones (containment against the measured
        metric). Cached per (enabled, fraction) on the exec."""
        from spark_rapids_tpu import conf as C3
        from spark_rapids_tpu.columnar import encoded as ENC
        from spark_rapids_tpu.io import parquet_device as PD

        enabled = conf.get(C3.ENCODED_ENABLED) and (
            (self.fmt == "parquet"
             and conf.get(C3.PARQUET_DEVICE_DECODE))
            or (self.fmt == "orc" and conf.get(C3.ORC_DEVICE_DECODE)))
        frac = conf.get(C3.ENCODED_MAX_DICT_FRACTION)
        cached = getattr(self, "_encoded_plan_cache", None)
        if cached is not None and cached[0] == (enabled, frac):
            return cached[1]
        out: Dict[str, str] = {}
        if enabled and self.fmt == "orc":
            # ORC: a stripe's DICTIONARY_V2 choice + dictionarySize live
            # in the stripe FOOTER — 'possible' when any stripe might
            # encode (the savings interval must cover it); 'certain' is
            # NOT claimed (the byte model's pessimistic ceiling stays on
            # the decoded estimate; runtime decides per stripe).
            # METADATA cost only: file meta from the tail, then each
            # stripe's footer bytes read + parsed ONCE for all columns —
            # never the data streams.
            try:
                from spark_rapids_tpu.io import orc_device as OD

                for split in self.splits:
                    size = os.path.getsize(split.path)
                    with open(split.path, "rb") as f:
                        f.seek(max(0, size - (1 << 20)))
                        tail = f.read()
                        try:
                            meta = OD.parse_file_meta(tail)
                        except Exception:
                            f.seek(0)
                            meta = OD.parse_file_meta(f.read())
                        name_to_cid = {n: i for i, n in
                                       enumerate(meta.names)}
                        want = {name_to_cid[a.name]: a.name
                                for a in self.attrs
                                if a.data_type is DataType.STRING
                                and a.name not in out
                                and a.name in name_to_cid}
                        for si in meta.stripes:
                            if not want:
                                break
                            fstart = si.offset + si.index_length + \
                                si.data_length
                            f.seek(fstart)
                            fbytes = f.read(si.footer_length)
                            if meta.compression != 0:
                                fbuf = OD.decompress_blocks(
                                    fbytes, 0, si.footer_length,
                                    meta.compression)
                            else:
                                fbuf = fbytes
                            _s, encs, _tz = OD._walk_stripe_footer(
                                fbuf, 0, len(fbuf), 0)
                            for cid in list(want):
                                enc, dict_size = encs.get(cid, (-1, 0))
                                if enc == OD.E_DICT_V2 and \
                                        ENC.scan_encoded_ok(
                                            dict_size, si.num_rows,
                                            frac):
                                    out[want.pop(cid)] = "possible"
            except Exception:
                out = {}
            self._encoded_plan_cache = ((enabled, frac), out)
            return out
        str_attrs = [a for a in self.attrs
                     if a.data_type is DataType.STRING]
        if enabled and str_attrs:
            import pyarrow.parquet as pq

            # per column: 'certain' only when EVERY row group of every
            # split is a provably dict-only chunk clearing the heuristic;
            # 'possible' when ANY group might encode (the savings
            # interval must cover it); absent otherwise
            all_certain: Dict[str, bool] = {}
            any_possible: Dict[str, bool] = {}
            try:
                for split in self.splits:
                    md = pq.ParquetFile(split.path).metadata
                    schema_index = {
                        md.row_group(0).column(ci).path_in_schema: ci
                        for ci in range(md.num_columns)}
                    groups = list(split.row_groups) \
                        if split.row_groups is not None \
                        else list(range(md.num_row_groups))
                    for a in str_attrs:
                        ci = schema_index.get(a.name)
                        all_certain.setdefault(a.name, True)
                        if ci is None:
                            all_certain[a.name] = False
                            continue
                        for rg in groups:
                            col = md.row_group(rg).column(ci)
                            rows = md.row_group(rg).num_rows
                            ndv = PD.chunk_dict_ndv(split.path, col)
                            ok = (PD.column_eligible(col, a.data_type)
                                  and ndv is not None
                                  and ENC.scan_encoded_ok(ndv, rows, frac))
                            if not ok:
                                all_certain[a.name] = False
                                continue
                            any_possible[a.name] = True
                            # 'certain' needs a page-header walk: footer
                            # encodings cannot distinguish a pure-dict
                            # chunk from a mid-chunk PLAIN fallback
                            if PD.chunk_dict_only(split.path, col) \
                                    is not True:
                                all_certain[a.name] = False
                for name in any_possible:
                    out[name] = "certain" if all_certain.get(name) \
                        else "possible"
            except Exception:
                out = {}
        if self.fmt == "parquet":
            # what Arrow hands over as codes is encoded whether or not
            # the device decoder is on (`execute`'s routing)
            out.update(dict.fromkeys(self.dict_columns_plan(conf),
                                     "certain"))
        self._encoded_plan_cache = ((enabled, frac), out)
        return out

    def _read_device(self, split: FileSplit, conf):
        """Device decode for one split of a scan that has a STRING column:
        a generator of its batches, one row group at a time, that returns
        False where no column qualified (nothing was yielded; the caller
        uses the host path) and True otherwise. Batches combine the
        device-decoded string columns with the host-decoded and
        partition-value columns at the same capacity.

        Two halves, both on the task thread. The HOST half
        (`_stage_split`) stages the whole split — host work on host data —
        BEFORE the task asks for its admission permit, so the tasks that
        have no permit yet do their host work side by side with the ones
        that hold one, and a permit is never held through a read, a page
        walk or Arrow's decode. The DEVICE half (`_decode_staged`) then
        runs under the permit, a row group at a time: where the first
        byte goes onto the device, and not before. A staged item is host
        data only, so a retryable device error re-issues the device half
        of that row group from it (with_retry). It yields a batch a row
        group instead of returning the split's list: no more of a split
        than the row group in flight is held on the device, and a staged
        row group's host buffers go as the task takes the next.

        A page shape outside the device decoder's scope (`_Unsupported`)
        sends what is LEFT of the split — from the host half the whole
        split, from the device half the row groups not yet gone
        downstream, so each row exactly once — through the host decoder,
        and the query's cpuFallbackEvents says so, once a split. Anything
        else the decoder raises (a compiler or runtime error of the
        device) propagates to with_retry and the query, like any other
        operator's.

        Spans (docs/observability.md), all siblings under the task: from
        the host half `scan.split` (footer, schema maps, eligibility),
        per (row group, column) `scan.read` > `scan.parse`, per row group
        `scan.host_decode`; from the device half the admission wait and
        per row group `scan.rowgroup` > per column `scan.decode` >
        `scan.upload`, and `scan.upload` for the columns Arrow decoded.
        The wait is a sibling so that a task queued in `Acquire TPU
        Semaphore` sits shallower in the tree than any step of the task
        that holds the permit — its device half, and the sink's
        `DeviceToHost` further down the same task. No span stays open
        across a `yield`."""
        from collections import deque

        from spark_rapids_tpu.engine.retry import with_retry
        from spark_rapids_tpu.io import parquet_device as PD

        plan = _SplitPlan(split, dict(split.partition_values))
        refused = None
        try:
            items = deque(self._stage_split(plan))
        except PD._Unsupported as e:
            refused, items = e, None
        if refused is None and not plan.eligible:
            return False
        while items:
            TpuSemaphore.get().acquire_if_necessary(current_task_id())
            item = items.popleft()
            try:
                batches = with_retry(
                    lambda: self._decode_staged(plan, item, conf),
                    site="scan")
            except PD._Unsupported as e:
                refused = e
                break
            yield from batches
            plan.done += 1
        if refused is None:
            return True
        import logging

        items = None  # what was staged of the rest is the host decoder's

        M.record_cpu_fallback()
        if plan.span is not None:
            plan.span.attrs["fallback"] = str(refused)
        logging.getLogger(__name__).warning(
            "device parquet decode refused a column of %s (%s); row groups "
            "%s of the split are decoded on the host", split.path, refused,
            plan.groups[plan.done:])
        yield from self._read_host(
            FileSplit(split.path, "parquet", tuple(plan.groups[plan.done:]),
                      split.options, split.partition_values), conf)
        return True

    def _stage_split(self, plan: "_SplitPlan"):
        """HOST half of the device decode: a generator of one
        `_StagedRowGroup` a row group of `plan.split`. It touches no
        device state, takes no permit and makes no jax call (but to ask
        which backend there is: how wide a DOUBLE goes up), so a task
        runs it without a permit: the footer work (filling `plan` before
        the first item), per device-eligible column chunk its bytes read,
        decompressed and page-walked (`PD.stage_chunk`), and for the
        `rest` columns Arrow's decode and their packing for the upload
        (`_stage_host_part`). Yields nothing where no column qualified
        (`plan.eligible` is empty)."""
        import pyarrow.parquet as pq

        from spark_rapids_tpu.io import parquet_device as PD

        split = plan.split
        data_attrs = [a for a in self.attrs if a.name not in plan.pv]
        with obs_span("scan.split", path=split.path) as plan.span:
            pf = pq.ParquetFile(split.path)
            md = pf.metadata
            schema_index = {md.row_group(0).column(ci).path_in_schema: ci
                            for ci in range(md.num_columns)}
            for ci in range(len(pf.schema.names)):
                sc = pf.schema.column(ci)
                # required columns carry NO definition levels in v1 data
                # pages — max_def must match or the value stream is
                # misparsed
                plan.max_def[sc.name] = sc.max_definition_level
            for a in data_attrs:
                ci = schema_index.get(a.name)
                if ci is not None and PD.column_eligible(
                        md.row_group(0).column(ci), a.data_type):
                    plan.eligible.append(a)
            if not plan.eligible:
                return
            plan.groups = list(split.row_groups) \
                if split.row_groups is not None \
                else list(range(md.num_row_groups))
            plan.rest = [a for a in data_attrs if a not in plan.eligible]
            if plan.span is not None:
                plan.span.attrs["row_groups"] = len(plan.groups)
                plan.span.attrs["device_columns"] = len(plan.eligible)
        rest_table, rest_at = None, 0
        for rg in plan.groups:
            rows = md.row_group(rg).num_rows
            chunks = {}
            for a in plan.eligible:
                col = md.row_group(rg).column(schema_index[a.name])
                try:
                    with obs_span("scan.read", column=a.name, rg=rg) as sp:
                        chunk = PD.read_chunk_bytes(split.path, col)
                        if sp is not None:
                            sp.attrs["bytes"] = len(chunk)
                        chunks[a.name] = _StagedChunk(
                            *PD.stage_chunk(chunk, col.compression),
                            col.compression)
                except PD._Unsupported as e:
                    raise PD._Unsupported(f"{a.name}: {e}") from e
            with obs_span("scan.host_decode", columns=len(plan.rest), rg=rg):
                hb = None
                if plan.rest:
                    if rest_table is None:
                        # read the way the host path reads a split: ONE
                        # threaded Arrow read, then a slice a row group
                        with obs_span("scan.arrow_read"):
                            rest_table = read_split(split, plan.rest, pf)
                    with obs_span("scan.convert"):
                        hb = arrow_to_host_batch(
                            rest_table.slice(rest_at, rows), plan.rest)
                    rest_at += rows
                with obs_span("scan.pack") as packed:
                    host = self._stage_host_part(hb, plan.rest, plan.pv,
                                                 rows)
                    if packed is not None and host is not None:
                        packed.attrs["packed_bytes"] = sum(
                            b.nbytes for b in host.bufs)
            yield _StagedRowGroup(rg, rows, chunks, host)

    def _decode_staged(self, plan: "_SplitPlan", item: "_StagedRowGroup",
                       conf):
        """DEVICE half: one staged row group uploaded, its decode programs
        issued, its batches assembled. The caller holds the permit. Pure
        over (item, conf), so with_retry may run it again."""
        from spark_rapids_tpu import conf as C3
        from spark_rapids_tpu.columnar import encoded as ENC
        from spark_rapids_tpu.columnar.batch import bucket_capacity
        from spark_rapids_tpu.io import parquet_device as PD

        encoded_ok = conf.get(C3.ENCODED_ENABLED)
        max_frac = conf.get(C3.ENCODED_MAX_DICT_FRACTION)
        rows = item.rows
        with obs_span("scan.rowgroup", path=plan.split.path, rg=item.rg,
                      rows=rows):
            dev_cols = {}
            for a in plan.eligible:
                staged_chunk = item.chunks[a.name]
                try:
                    with obs_span("scan.decode", column=a.name,
                                  codec=staged_chunk.codec):
                        cv = PD.decode_chunk_device(
                            staged_chunk.data, a.data_type, rows,
                            max_def=plan.max_def.get(a.name, 1),
                            cap=bucket_capacity(max(rows, 1)),
                            codec=staged_chunk.codec,
                            encoded_ok=encoded_ok,
                            max_dict_fraction=max_frac,
                            pages=staged_chunk.pages)
                except PD._Unsupported as e:
                    raise PD._Unsupported(f"{a.name}: {e}") from e
                if ENC.is_encoded(cv):
                    ENC.record_scan_emission(cv, rows)
                dev_cols[a.name] = cv
            return self._assemble_staged(
                dev_cols, item.host, plan.rest, plan.pv, rows, conf)


@dataclass
class _SplitPlan:
    """What the host half of the device decode (`_stage_split`) learns of
    a split before its first row group, and the device half reads after
    it has the first item (or the end) in hand."""

    split: FileSplit
    pv: Dict[str, Optional[str]]     # the split's partition values
    span: Any = None                 # the `scan.split` span, tracing on
    eligible: List[AttributeReference] = field(default_factory=list)
    rest: List[AttributeReference] = field(default_factory=list)
    groups: List[int] = field(default_factory=list)
    max_def: Dict[str, int] = field(default_factory=dict)
    done: int = 0                    # row groups gone downstream


@dataclass
class _StagedChunk:
    """One device-eligible column chunk as the host half leaves it."""

    data: bytes                      # decompressed (`PD.stage_chunk`)
    pages: list                      # PageInfo, offsets into `data`
    codec: str                       # what the file held


@dataclass
class _StagedRowGroup:
    """One row group as the host half leaves it: host data only."""

    rg: int
    rows: int
    chunks: Dict[str, _StagedChunk]
    # the columns Arrow decoded (and the partition values), packed for
    # their upload: columnar.batch.StagedUpload, None where there is none
    host: Any

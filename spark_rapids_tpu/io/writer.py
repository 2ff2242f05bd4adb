"""File writers (reference: ColumnarOutputWriter.scala, GpuParquetFileFormat,
GpuOrcFileFormat, GpuFileFormatWriter/GpuFileFormatDataWriter).

Reference parity:
- per-partition part files + _SUCCESS marker and save-mode handling
  (GpuFileFormatWriter.scala / GpuInsertIntoHadoopFsRelationCommand) ->
  `execute_write`.
- dynamic partitioning by partition columns into key=value directories
  (GpuFileFormatDataWriter dynamic writer, 417 LoC) -> `_write_partitioned`.

Eligible schemas encode ON DEVICE (io/parquet_encode_device.py /
io/orc_encode_device.py — the reference encodes on-GPU via cudf
Table.writeParquet/writeORC into a host buffer, ColumnarOutputWriter.
scala:62-177) with host block compression; everything else encodes on
the host with Arrow C++ after the device->host boundary.
"""

from __future__ import annotations

import os
import shutil
import uuid
from typing import Any, Dict, List

import numpy as np
import pyarrow as pa

from spark_rapids_tpu.columnar.batch import HostColumnarBatch
from spark_rapids_tpu.io.arrow_convert import host_batch_to_arrow
from spark_rapids_tpu.obs.trace import span as obs_span
from spark_rapids_tpu.ops.base import AttributeReference
from spark_rapids_tpu.plan import logical as L


class WriteError(RuntimeError):
    pass


def execute_write(session, plan: L.WriteFile):
    """Run the write inside the query scope the session opened
    (TpuSession.execute_write); returns the physical plan that produced
    the rows, None where mode=ignore found the path taken."""
    path = plan.path
    if os.path.exists(path):
        if plan.mode == "error":
            raise WriteError(
                f"path {path} already exists (mode=error[ifexists])")
        if plan.mode == "ignore":
            return None
        if plan.mode == "overwrite":
            shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path, exist_ok=True)

    child = plan.children[0]
    attrs = child.output
    # optional sort after hash ops so written files cluster equal keys
    # (reference: GpuTransitionOverrides.insertHashOptimizeSorts :171-204)
    from spark_rapids_tpu.plan.transition_overrides import (
        insert_hash_optimize_sort,
    )

    with obs_span("plan", kind="stage"):
        physical = insert_hash_optimize_sort(
            session._physical_plan(child), session.conf)

    # Device-side parquet encode (reference: ColumnarOutputWriter.scala:
    # 62-177 encodes on the accelerator): peel the root DeviceToHost
    # transition and hand DEVICE batches to the device encoder — what
    # downloads is the encoded page payload, not padded columns.
    from spark_rapids_tpu import conf as C
    from spark_rapids_tpu.exec.transitions import DeviceToHostExec
    from spark_rapids_tpu.io import parquet_encode_device as PE

    # device encode + host block compression mirrors the decode split:
    # the DEFAULT snappy parquet write goes through the device encoder
    # (reference behavior: ColumnarOutputWriter.scala:62-177 encodes
    # compressed parquet/ORC on the accelerator)
    from spark_rapids_tpu.io import orc_encode_device as OE

    part_names = list(plan.partition_by or [])
    data_attrs_w = [a for a in attrs if a.name not in part_names]
    pq_compression = str(plan.options.get("compression", "snappy")).lower()
    device_encode = (
        plan.fmt == "parquet"
        and session.conf.get(C.PARQUET_DEVICE_ENCODE)
        and PE.codec_supported(pq_compression)
        and isinstance(physical, DeviceToHostExec)
        and PE.schema_encodable(data_attrs_w))
    orc_compression = str(plan.options.get("compression",
                                           "uncompressed")).lower()
    device_encode_orc = (
        plan.fmt == "orc"
        and not plan.partition_by
        and session.conf.get(C.ORC_DEVICE_ENCODE)
        and OE.codec_supported(orc_compression)
        and isinstance(physical, DeviceToHostExec)
        and OE.schema_encodable(attrs))
    # the device encoders take DEVICE batches: they run the sink's child
    if device_encode or device_encode_orc:
        source = physical.children[0]
    elif isinstance(physical, DeviceToHostExec):
        # the consumer is a file writer: a dictionary-coded column comes
        # through the fence as codes + dictionary and reaches Arrow so
        # (`host_batch_to_arrow`). A node of its own, since the planned
        # one may be the plan cache's, which a collect() shares
        source = physical = DeviceToHostExec(physical.children[0],
                                             keep_encoded=True)
    else:
        source = physical

    ctx = session._exec_context()
    pb = source.execute(ctx)
    write_id = uuid.uuid4().hex[:12]

    def write_partition(pidx: int) -> int:
        from spark_rapids_tpu.columnar.batch import ColumnarBatch
        from spark_rapids_tpu.columnar.encoded import decode_batch

        # the device encoders read raw (offsets, bytes) string layouts:
        # encoded columns decode at the writer boundary
        with obs_span("write.collect"):
            batches = [decode_batch(b) if isinstance(b, ColumnarBatch)
                       else b
                       for b in pb.iterator(pidx) if b.num_rows > 0]
        if not batches:
            return 0
        if device_encode and plan.partition_by:
            return _write_partitioned_device(
                batches, attrs, plan, path, pidx, write_id,
                pq_compression)
        if device_encode:
            fname = f"part-{pidx:05d}-{write_id}.{_ext(plan.fmt)}"
            return PE.write_file(os.path.join(path, fname), attrs, batches,
                                 compression=pq_compression)
        if device_encode_orc:
            fname = f"part-{pidx:05d}-{write_id}.{_ext(plan.fmt)}"
            return OE.write_file(os.path.join(path, fname), attrs, batches,
                                 compression=orc_compression)
        if plan.partition_by:
            return _write_partitioned(batches, attrs, plan, path, pidx,
                                      write_id)
        fname = f"part-{pidx:05d}-{write_id}.{_ext(plan.fmt)}"
        return _write_arrow_file(batches, attrs,
                                 os.path.join(path, fname), plan)

    with obs_span("stage:write", kind="stage",
                  partitions=pb.num_partitions):
        session.scheduler.run_job(pb.num_partitions, write_partition)
    with obs_span("write.commit"):
        with open(os.path.join(path, "_SUCCESS"), "w"):
            pass
    return physical


def _ext(fmt: str) -> str:
    return {"parquet": "parquet", "orc": "orc", "csv": "csv"}[fmt]


def _concat_arrow(batches: List[HostColumnarBatch], attrs):
    """The batches of one file as one Arrow table. Batches whose
    dictionaries differ (a split that lacks a value) are brought to one,
    in Arrow: a column chunk is then written under one dictionary page. A
    column that is codes in some batches and values in others is brought
    to values."""
    tables = [host_batch_to_arrow(b, attrs) for b in batches]
    if len(tables) == 1:
        return tables[0]
    if any(not t.schema.equals(tables[0].schema) for t in tables[1:]):
        # the scan hands a STRING column over as codes a split: one whose
        # file fell back to PLAIN comes as values, and the column is then
        # values in every batch of this file
        plain = {i for t in tables for i, f in enumerate(t.schema)
                 if not pa.types.is_dictionary(f.type)}
        tables = [t.cast(_values_schema(t.schema, plain)) for t in tables]
    table = pa.concat_tables(tables)
    if any(pa.types.is_dictionary(f.type) for f in table.schema):
        table = table.unify_dictionaries()
    return table


def _write_arrow_file(batches: List[HostColumnarBatch], attrs,
                      file_path: str, plan: L.WriteFile) -> int:
    """One file through Arrow's host writer; returns its rows. The two
    spans split the host's work: building the Arrow table, then Arrow's
    encode + compress + file I/O (one call, so one span)."""
    with obs_span("write.arrow") as sp:
        table = _concat_arrow(batches, attrs)
        if sp is not None:
            # the STRING columns of this file handed to Arrow as codes +
            # dictionary, and what they hold (the codes and, a batch, its
            # dictionary's bytes): as `scan.host_decode` counts its own
            coded = [i for i, f in enumerate(table.schema)
                     if pa.types.is_dictionary(f.type)]
            sp.attrs.update(
                dict_columns=len(coded),
                dict_bytes=sum(
                    b.columns[i].data.nbytes
                    + int(b.columns[i].dictionary.host_offsets[-1])
                    for b in batches for i in coded))
    with obs_span("write.file", encoder="arrow", path=file_path) as sp:
        _write_table(table, file_path, plan)
        if sp is not None:
            sp.attrs.update(rows=table.num_rows,
                            bytes=os.path.getsize(file_path))
    return table.num_rows


def _values_schema(schema, only=None):
    """`schema` with every dictionary field (of the positions `only`,
    where given) as its value type: what a reader of the file is to see."""
    return pa.schema([f.with_type(f.type.value_type)
                      if pa.types.is_dictionary(f.type)
                      and (only is None or i in only) else f
                      for i, f in enumerate(schema)],
                     metadata=schema.metadata)


def _write_parquet_coded(table, file_path: str, compression) -> None:
    """`pq.write_table` for a table that holds dictionary arrays: Arrow
    writes each as a dictionary-encoded chunk from its codes and its
    dictionary as they are. The parquet schema is that of the values
    (BYTE_ARRAY / String), and so is the Arrow schema the footer carries
    (`ARROW:schema`, which the writer would otherwise store as the
    dictionary type and hand every pyarrow reader dictionary arrays):
    a reader sees the file a table of plain strings gives."""
    import base64

    import pyarrow.parquet as pq

    stored = base64.b64encode(
        _values_schema(table.schema).serialize().to_pybytes())
    with pq.ParquetWriter(file_path, table.schema, compression=compression,
                          store_schema=False) as writer:
        writer.write_table(table)
        writer.add_key_value_metadata({"ARROW:schema": stored})


def _write_table(table, file_path: str, plan: L.WriteFile) -> None:
    coded = any(pa.types.is_dictionary(f.type) for f in table.schema)
    if plan.fmt == "parquet":
        import pyarrow.parquet as pq

        write = _write_parquet_coded if coded else pq.write_table
        write(table, file_path,
              compression=plan.options.get("compression", "snappy"))
        return
    if coded:
        # ORC and CSV take values: decoded in Arrow, a column at a time
        table = table.cast(_values_schema(table.schema))
    if plan.fmt == "orc":
        import pyarrow.orc as po

        po.write_table(table, file_path)
    elif plan.fmt == "csv":
        import pyarrow.csv as pc

        header = plan.options.get("header", True)
        from spark_rapids_tpu.io.scan import _to_bool

        pc.write_csv(
            table, file_path,
            write_options=pc.WriteOptions(
                include_header=_to_bool(header),
                delimiter=plan.options.get("sep", ",")))
    else:
        raise ValueError(f"unknown write format {plan.fmt}")


def _write_partitioned_device(batches, attrs, plan, path: str, pidx: int,
                              write_id: str, compression: str) -> int:
    """Dynamic-partition write with DEVICE encode (reference: the dynamic
    partition data writer encodes on the accelerator,
    GpuFileFormatDataWriter.scala): only the partition-KEY columns come to
    the host (they name the directories), the data columns group on
    device — one route dispatch + one per-group range gather per batch —
    and each group's device batch runs the existing parquet device
    encoder."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_tpu.columnar.batch import (
        ColumnarBatch,
        bucket_capacity,
        gather_batch,
    )
    from spark_rapids_tpu.io import parquet_encode_device as PE
    from spark_rapids_tpu.shuffle.exchange import _route_plan, _slice_indices

    part_names = plan.partition_by
    part_idx = [i for i, a in enumerate(attrs) if a.name in part_names]
    data_idx = [i for i, a in enumerate(attrs) if a.name not in part_names]
    data_attrs = [attrs[i] for i in data_idx]
    from spark_rapids_tpu.columnar.batch import ensure_compact

    groups: Dict[tuple, List] = {}
    for b in batches:
        # live-masked shuffle/ici views hold real rows in scattered lanes;
        # the key download and the group routing below address physical
        # lanes 0..n-1, so compact first
        b = ensure_compact(b)
        n = b.host_rows()
        # 1. keys to host (small: the partition columns only)
        key_host = ColumnarBatch([b.columns[i] for i in part_idx],
                                 n).to_host()
        key_vals, inverse, first_idx = _partition_key_groups(
            key_host.columns, n)
        # 2. route data rows by group id on device (contiguous ranges)
        n_groups = len(first_idx)
        gid = np.full(bucket_capacity(max(n, 1)), n_groups, np.int32)
        gid[:n] = inverse.astype(np.int32)
        order, counts_dev = _route_plan(jnp.asarray(gid), n_groups)
        counts = np.asarray(jax.device_get(counts_dev))
        data_batch = ColumnarBatch([b.columns[i] for i in data_idx], n)
        offset = 0
        for g in range(n_groups):
            c = int(counts[g])
            if c == 0:
                continue
            idx = _slice_indices(order, np.int32(offset),
                                 bucket_capacity(max(c, 1)))
            piece = gather_batch(data_batch, idx, c, unique_indices=True)
            key = tuple(kv[first_idx[g]] for kv in key_vals)
            groups.setdefault(key, []).append(piece)
            offset += c
    total = 0
    seq = 0
    for key, gbatches in groups.items():
        out_dir = os.path.join(path, _partition_dirname(attrs, part_idx,
                                                        key))
        os.makedirs(out_dir, exist_ok=True)
        fname = f"part-{pidx:05d}-{seq:03d}-{write_id}.{_ext(plan.fmt)}"
        total += PE.write_file(os.path.join(out_dir, fname), data_attrs,
                               gbatches, compression=compression)
        seq += 1
    return total


def _partition_key_groups(key_cols, n: int):
    """Canonical partition-key grouping shared by the device- and
    host-encoded dynamic writers: (per-column value arrays with None for
    NULL, per-row group index, each group's first row index)."""
    # a dictionary-coded key names directories by its values
    key_cols = [c.decoded() if getattr(c, "dictionary", None) is not None
                else c for c in key_cols]
    key_vals = [np.where(c.validity, c.data.astype(object), None)
                for c in key_cols]
    decorated = np.array(
        ["\x00".join(repr(kv[i]) for kv in key_vals) for i in range(n)],
        dtype=object)
    _uniq, first_idx, inverse = np.unique(
        decorated, return_index=True, return_inverse=True)
    return key_vals, inverse, first_idx


def _partition_dirname(attrs, part_idx, key) -> str:
    return "/".join(f"{attrs[i].name}={_part_value(v)}"
                    for i, v in zip(part_idx, key))


def _write_partitioned(batches: List[HostColumnarBatch], attrs, plan,
                       path: str, pidx: int, write_id: str) -> int:
    """Hive-style key=value directory layout (reference: the dynamic
    partition data writer, GpuFileFormatDataWriter.scala)."""
    part_names = plan.partition_by
    part_idx = [i for i, a in enumerate(attrs) if a.name in part_names]
    data_idx = [i for i, a in enumerate(attrs) if a.name not in part_names]
    data_attrs = [attrs[i] for i in data_idx]
    total = 0
    seq = 0
    # vectorized grouping per batch: unique over decorated key strings ->
    # per-group boolean masks; no per-row python loops over the data
    groups: Dict[tuple, List[HostColumnarBatch]] = {}
    for b in batches:
        key_vals, inverse, first_idx = _partition_key_groups(
            [b.columns[i] for i in part_idx], b.num_rows)
        for g in range(len(first_idx)):
            mask = inverse == g
            key = tuple(kv[first_idx[g]] for kv in key_vals)
            cols = [b.columns[i].rows(mask) for i in data_idx]
            groups.setdefault(key, []).append(
                HostColumnarBatch(cols, int(mask.sum())))
    for key, group_batches in groups.items():
        out_dir = os.path.join(path, _partition_dirname(attrs, part_idx,
                                                        key))
        os.makedirs(out_dir, exist_ok=True)
        fname = f"part-{pidx:05d}-{seq:03d}-{write_id}.{_ext(plan.fmt)}"
        total += _write_arrow_file(group_batches, data_attrs,
                                   os.path.join(out_dir, fname), plan)
        seq += 1
    return total


def _part_value(v) -> str:
    if v is None:
        return "__HIVE_DEFAULT_PARTITION__"
    if isinstance(v, np.generic):
        v = v.item()
    # escape path-hostile characters the way Spark's escapePathName does
    from urllib.parse import quote

    s = str(v)
    escaped = quote(s, safe=" :+-_.,")
    return escaped if escaped else "__EMPTY__"

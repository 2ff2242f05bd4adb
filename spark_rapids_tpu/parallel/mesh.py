"""Device mesh + collective repartition primitives.

The ICI analog of the reference's UCX shuffle data plane
(UCXShuffleTransport.scala:47-507): rows move between shards with ONE
`lax.all_to_all` inside a jitted `shard_map`, instead of N^2 tagged
point-to-point sends. Bucketing is static-shape: each shard routes its rows
into `n_shards` fixed-capacity buckets (validity-masked), which is exactly
the bounce-buffer discipline of the reference (BounceBufferManager.scala)
recast as padded device arrays.

`distributed_agg_step` is the flagship multi-chip program: per-shard partial
aggregation -> all-to-all hash exchange -> per-shard final merge — the
partial/exchange/final call stack of SURVEY.md section 3.5 compiled into a
single XLA program spanning the mesh.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from spark_rapids_tpu import _jax_setup  # noqa: F401
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from spark_rapids_tpu.exec import rowkeys as RK
from spark_rapids_tpu.ops import hashing as H
from spark_rapids_tpu.ops.values import ColV
from spark_rapids_tpu.columnar.dtypes import DataType

DATA_AXIS = "data"


def build_mesh(n_devices: Optional[int] = None,
               axis: str = DATA_AXIS, devices=None) -> Mesh:
    """1-D mesh over the first n devices (the executor-per-chip analog of
    GpuDeviceManager's one-GPU-per-executor policy). An explicit device
    list overrides discovery — the quarantine-aware mesh rebuild
    (shuffle/ici.session_mesh) passes the surviving devices."""
    devs = list(devices) if devices is not None else jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis,))


def _route_to_buckets(data_cols: List[jnp.ndarray], validity, pid,
                      n_shards: int, bucket_cap: int):
    """Pack rows into n_shards fixed-size buckets by target shard id.

    Returns ([n_shards, bucket_cap] arrays per column, bucket validity).
    Rows beyond a bucket's capacity are dropped (callers size bucket_cap to
    make this impossible; the inflight-limit analog of the reference's
    maxBytesInFlight throttle).
    """
    cap = validity.shape[0]
    out_cols = []
    out_valid = []
    for t in range(n_shards):
        mask = validity & (pid == t)
        order = jnp.argsort(~mask, stable=True).astype(jnp.int32)
        sel = order[:bucket_cap]
        out_valid.append(mask[sel])
        out_cols.append([c[sel] for c in data_cols])
    bucket_valid = jnp.stack(out_valid)  # [n_shards, bucket_cap]
    stacked = [
        jnp.stack([out_cols[t][ci] for t in range(n_shards)])
        for ci in range(len(data_cols))
    ]
    return stacked, bucket_valid


def all_to_all_table(data_cols: List[jnp.ndarray], validity, pid,
                     n_shards: int, bucket_cap: int, axis: str = DATA_AXIS):
    """Shard-local body: route rows to per-target buckets and exchange them
    over the mesh axis. Returns per-column [n_shards*bucket_cap] arrays plus
    validity for the received rows. Must run inside shard_map."""
    stacked, bucket_valid = _route_to_buckets(data_cols, validity, pid,
                                              n_shards, bucket_cap)
    recv_cols = [
        jax.lax.all_to_all(s, axis, split_axis=0, concat_axis=0, tiled=True)
        for s in stacked
    ]
    recv_valid = jax.lax.all_to_all(bucket_valid, axis, split_axis=0,
                                    concat_axis=0, tiled=True)
    # rank-2 columns (fixed-width string matrices) keep their trailing axis
    flat_cols = [c.reshape((-1,) + c.shape[2:]) for c in recv_cols]
    return flat_cols, recv_valid.reshape(-1)


def distributed_agg_step(mesh: Mesh, n_shards: int, cap: int,
                         bucket_cap: int, axis: str = DATA_AXIS):
    """Build the jitted multi-chip filter+project+groupby-sum step.

    Inputs (sharded on the leading axis over `axis`):
      keys   [n_shards, cap] int64
      values [n_shards, cap] int64
      valid  [n_shards, cap] bool
    Output (sharded the same way):
      group keys / sums / validity per shard [n_shards, n_shards*bucket_cap]
      plus the global group count (replicated via psum).
    """
    def per_shard(keys, values, valid):
        keys = keys[0]
        values = values[0]
        valid = valid[0]
        # -- scan-side: filter (values % 3 != 0) + project (v * 2 + 1) ------
        valid = valid & (values % 3 != 0)
        values = jnp.where(valid, values * 2 + 1, 0)
        keys = jnp.where(valid, keys, 0)

        # -- partial aggregate (update) -------------------------------------
        kcol = ColV(DataType.INT64, keys, valid)
        gi = RK.group_ids_masked([RK.key_proxy(kcol)], valid, cap)
        psum_, pvalid = RK.segment_reduce("sum", values, valid, gi,
                                          None, cap)
        pkeys = keys[gi.rep_rows]  # slot g holds group g's key
        slot = jnp.arange(cap) < gi.num_groups

        # -- hash exchange over ICI ----------------------------------------
        kv = ColV(DataType.INT64, pkeys, slot)
        pid = H.partition_ids(jnp, [kv], n_shards)
        (rk, rv), rvalid = all_to_all_table(
            [pkeys, psum_], slot & pvalid, pid, n_shards, bucket_cap, axis)

        # -- final merge aggregate ------------------------------------------
        rcap = rk.shape[0]
        rcol = ColV(DataType.INT64, jnp.where(rvalid, rk, 0), rvalid)
        gi2 = RK.group_ids_masked([RK.key_proxy(rcol)], rvalid, rcap)
        fsum, fvalid = RK.segment_reduce("sum", rv, rvalid, gi2,
                                         None, rcap)
        fkeys = rk[gi2.rep_rows]
        out_slot = jnp.arange(rcap) < gi2.num_groups
        total_groups = jax.lax.psum(gi2.num_groups, axis)
        return (fkeys[None], fsum[None], (out_slot & fvalid)[None],
                total_groups[None])

    spec = P(axis)
    smapped = shard_map(
        per_shard, mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=(spec, spec, spec, spec),
    )
    # tpulint: jit-cache -- built once per mesh; callers hold the step fn
    return jax.jit(smapped)

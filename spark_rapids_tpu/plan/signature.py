"""Canonical plan signatures for the serving runtime (docs/serving.md).

A signature is a stable fingerprint of "what this query IS": the logical
plan's structure and expressions (with expression ids NORMALIZED to
first-appearance ordinals, so two structurally identical queries built
independently — fresh AttributeReference ids each — sign identically),
every leaf's schema, and the session's explicitly-set configuration (any
conf key can affect planning, so all of them key the signature; over-keying
can only cause a cache miss, never a wrong reuse).

Two flavors from one walk:

- `cache_key` additionally pins LEAF DATA IDENTITY (object identity of an
  in-memory relation's partition list; path + size + mtime of scanned
  files). It keys the plan cache (plan/plan_cache.py): a hit may reuse the
  cached physical plan outright, so it must be impossible for a query over
  different data to collide. Identity via id() is sound here because the
  cache entry holds the logical plan (and the physical plan holds the
  batches) strongly alive — a live entry's ids cannot be recycled.
- `shape_key` deliberately drops data identity: it groups look-alike
  queries over DIFFERENT data for cross-query micro-batching
  (engine/server.py).
"""

from __future__ import annotations

import hashlib
import os
import re
import zlib
from typing import Dict, List, Optional

from spark_rapids_tpu.plan import logical as L

# object.__repr__ leaks addresses; a canonical token must not
_ADDR_RE = re.compile(r" at 0x[0-9a-fA-F]+")


class PlanSignature:
    __slots__ = ("cache_key", "shape_key")

    def __init__(self, cache_key: str, shape_key: str):
        self.cache_key = cache_key
        self.shape_key = shape_key

    def __repr__(self):
        return (f"PlanSignature(cache={self.cache_key[:12]}…, "
                f"shape={self.shape_key[:12]}…)")


def plan_signature(plan: "L.LogicalPlan",
                   conf) -> Optional[PlanSignature]:
    """Signature of (logical plan, conf), or None when the plan cannot be
    fingerprinted (an unexpected node/value shape — the caller simply
    skips caching)."""
    try:
        from spark_rapids_tpu import conf as C

        conf_tok = ";".join(
            f"{k}={v!r}" for k, v in sorted(
                conf.settings.items(), key=lambda kv: str(kv[0])))
        # the RESOLVED adaptive flag keys the signature even when it is
        # defaulted: a cached static plan must never serve an adaptive
        # query (or vice versa) — the adaptive plan carries the
        # TpuAdaptiveExec wrapper and re-optimizes at runtime
        conf_tok += f";__adaptive={bool(conf.get(C.ADAPTIVE_ENABLED))!r}"
        # same for the RESOLVED spmd flag (default ON since r14): the
        # lowered plan carries TpuSpmdStageExec wrappers a host-loop
        # query must never be served
        conf_tok += f";__spmd={bool(conf.get(C.SPMD_ENABLED))!r}"
        # the placement pass keys on the FITTED MODELS, not just the
        # conf: warming either model must invalidate the cached
        # all-device plan, so the model fit stamps join the token
        if conf.get(C.PLACEMENT_ENABLED):
            from spark_rapids_tpu.obs import calibrate as CAL

            dm = CAL.active_model()
            hm = CAL.active_host_model()
            conf_tok += (
                f";__placement={conf.get(C.PLACEMENT_MODE)}"
                f":{conf.get(C.PLACEMENT_MIN_SAMPLES)}"
                f":{0 if dm is None else dm.fitted_at_ns}"
                f":{0 if hm is None else hm.fitted_at_ns}")
        idmap: Dict[int, int] = {}
        ident = _canon_node(plan, idmap, identity=True)
        idmap = {}
        shape = _canon_node(plan, idmap, identity=False)
    except Exception:  # noqa: BLE001 - best-effort fingerprint
        return None
    return PlanSignature(
        cache_key=_digest(ident + "||" + conf_tok),
        shape_key=_digest(shape + "||" + conf_tok),
    )


def _digest(text: str) -> str:
    return hashlib.sha256(text.encode("utf-8", "surrogatepass")).hexdigest()


# ---------------------------------------------------------------------------
# Node canonicalization
# ---------------------------------------------------------------------------
def _canon_node(p: "L.LogicalPlan", idmap: Dict[int, int],
                identity: bool) -> str:
    name = type(p).__name__
    if isinstance(p, L.LocalRelation):
        schema = _canon_val(p.schema, idmap)
        tok = f"{name}({schema};nparts={len(p.partitions)}"
        if identity:
            # object identity of the node AND its partitions list: the
            # cache entry keeps both alive (see module docstring), so a
            # live id can never be recycled into a false hit
            tok += f";data={id(p)}/{id(p.partitions)}"
        return tok + ")"
    if isinstance(p, L.FileScan):
        files = list(p.files or [])
        tok = (f"{name}(fmt={p.fmt};paths={sorted(p.paths)!r};"
               f"opts={sorted((str(k), repr(v)) for k, v in p.options.items())!r};"
               f"schema={_canon_val(p.schema, idmap)}")
        if identity:
            tok += f";files={_file_fingerprints(files or p.paths)!r}"
        return tok + ")"
    if isinstance(p, L.CacheRelation):
        child = _canon_node(p.children[0], idmap, identity)
        # a cached relation's materialization is keyed by node identity
        # (exec/cache.py); identity mode must carry it so two different
        # cached datasets with identical shapes never share a plan
        ident = ""
        if identity:
            # ... and whether it is materialized: a plan made before the
            # relation was carries the child's estimate of it (rows, HBM
            # booked, admission weight, broadcast choices) and must not
            # be the plan of the actions that find it held
            from spark_rapids_tpu.exec.cache import is_materialized

            ident = f";cache={id(p)};held={is_materialized(p)}"
        return f"{name}({child}{ident})"
    # generic node: scalar/expression state from __dict__ (children
    # excluded — they canonicalize recursively below)
    state = []
    for k in sorted(vars(p)):
        if k == "children":
            continue
        state.append(f"{k}={_canon_val(vars(p)[k], idmap)}")
    kids = ",".join(_canon_node(c, idmap, identity) for c in p.children)
    return f"{name}({';'.join(state)})[{kids}]"


def _file_fingerprints(paths: List[str]) -> List[tuple]:
    out = []
    for f in paths:
        try:
            st = os.stat(f)
            out.append((f, st.st_size, st.st_mtime_ns))
        except OSError:
            out.append((f, "?"))
    return out


# ---------------------------------------------------------------------------
# Value / expression canonicalization
# ---------------------------------------------------------------------------
def _canon_val(v, idmap: Dict[int, int]) -> str:
    import numpy as np

    if v is None or isinstance(v, (bool, int, float, complex, str, bytes)):
        return repr(v)
    if isinstance(v, np.generic):
        return f"np({v.dtype}:{v!r})"
    if isinstance(v, np.ndarray):
        return (f"nd({v.dtype}:{v.shape}:"
                f"{zlib.crc32(np.ascontiguousarray(v).tobytes()):08x})")
    if isinstance(v, (list, tuple)):
        inner = ",".join(_canon_val(x, idmap) for x in v)
        return f"[{inner}]" if isinstance(v, list) else f"({inner})"
    if isinstance(v, dict):
        inner = ",".join(
            f"{_canon_val(k, idmap)}:{_canon_val(x, idmap)}"
            for k, x in sorted(v.items(), key=lambda kv: str(kv[0])))
        return f"{{{inner}}}"
    if isinstance(v, type):
        return f"type:{v.__name__}"
    d = getattr(v, "__dict__", None)
    if d is not None:
        state = []
        for k in sorted(d):
            if k == "expr_id":
                # normalize to first-appearance ordinal: identity
                # RELATIONSHIPS (same id -> same token) survive, the
                # per-process counter values do not
                state.append(
                    f"expr_id=${idmap.setdefault(d[k], len(idmap))}")
            else:
                state.append(f"{k}={_canon_val(d[k], idmap)}")
        return f"{type(v).__name__}({';'.join(state)})"
    # enums / slotted immutables: their repr is stable; scrub addresses so
    # a default object.__repr__ can never leak one into the signature
    return _ADDR_RE.sub("", repr(v))

"""CPU-vs-TPU equivalence harness.

Reference parity: the load-bearing test pattern of the reference
(SURVEY.md section 4) —
- `assert_gpu_and_cpu_are_equal_collect` (integration_tests asserts.py:30-301)
  -> `assert_tpu_and_cpu_are_equal_collect`: run the same DataFrame lambda on
  the CPU oracle engine and the TPU engine and deep-compare rows with float
  tolerance and optional sorting.
- strict on-accelerator assertion via rapids.tpu.sql.test.enabled
  (reference: spark.rapids.sql.test.enabled).
- composable random data generators (data_gen.py:26-605) -> gens below.
"""

from __future__ import annotations

import math
from typing import Any, Callable, List, Optional, Sequence

import numpy as np

import spark_rapids_tpu  # noqa: F401
from spark_rapids_tpu.columnar.dtypes import DataType
from spark_rapids_tpu.session import TpuSession


def _with_conf(session: TpuSession, overrides: dict):
    saved = dict(session.conf.settings)
    session.conf.settings.update(overrides)

    def restore():
        session.conf.settings.clear()
        session.conf.settings.update(saved)

    return restore


def run_on_cpu(session: TpuSession, df_fn: Callable) -> List[tuple]:
    restore = _with_conf(session, {"rapids.tpu.sql.enabled": False})
    try:
        return df_fn(session).collect()
    finally:
        restore()


def run_on_tpu(session: TpuSession, df_fn: Callable,
               allowed_non_tpu: Sequence[str] = (),
               extra_conf: Optional[dict] = None) -> List[tuple]:
    overrides = {
        "rapids.tpu.sql.enabled": True,
        "rapids.tpu.sql.test.enabled": True,
        "rapids.tpu.sql.test.allowedNonTpu": ",".join(allowed_non_tpu),
    }
    overrides.update(extra_conf or {})
    restore = _with_conf(session, overrides)
    try:
        return df_fn(session).collect()
    finally:
        restore()


def _values_equal(a: Any, b: Any, approx: float) -> bool:
    if a is None or b is None:
        return a is None and b is None
    if isinstance(a, float) or isinstance(b, float):
        fa, fb = float(a), float(b)
        if math.isnan(fa) or math.isnan(fb):
            return math.isnan(fa) and math.isnan(fb)
        if fa == fb:
            return True
        if approx <= 0:
            return False
        denom = max(abs(fa), abs(fb), 1e-30)
        return abs(fa - fb) / denom <= approx or abs(fa - fb) <= approx
    return a == b


def _sort_key(row: tuple):
    return tuple(
        (v is None, "" if v is None else str(type(v)),
         str(v) if not isinstance(v, (int, float, bool)) or
         isinstance(v, bool) else v)
        if not isinstance(v, (int, float)) or isinstance(v, bool)
        else (v is None, "num", float(v) if v == v else math.inf)
        for v in row
    )


def assert_rows_equal(cpu: List[tuple], tpu: List[tuple],
                      ignore_order: bool = False,
                      approx_float: float = 0.0) -> None:
    assert len(cpu) == len(tpu), \
        f"row count mismatch: cpu={len(cpu)} tpu={len(tpu)}"
    if ignore_order:
        cpu = sorted(cpu, key=_sort_key)
        tpu = sorted(tpu, key=_sort_key)
    for i, (rc, rt) in enumerate(zip(cpu, tpu)):
        assert len(rc) == len(rt), f"row {i} arity mismatch: {rc} vs {rt}"
        for j, (vc, vt) in enumerate(zip(rc, rt)):
            assert _values_equal(vc, vt, approx_float), (
                f"row {i} col {j} differs: cpu={vc!r} tpu={vt!r}\n"
                f"cpu row: {rc}\ntpu row: {rt}")


def assert_tpu_and_cpu_are_equal_collect(
        session: TpuSession, df_fn: Callable,
        ignore_order: bool = False,
        approx_float: float = 0.0,
        allowed_non_tpu: Sequence[str] = (),
        extra_conf: Optional[dict] = None) -> None:
    cpu = run_on_cpu(session, df_fn)
    tpu = run_on_tpu(session, df_fn, allowed_non_tpu, extra_conf)
    assert_rows_equal(cpu, tpu, ignore_order=ignore_order,
                      approx_float=approx_float)


def assert_tpu_fallback_collect(
        session: TpuSession, df_fn: Callable,
        fallback_exec: str,
        ignore_order: bool = False,
        approx_float: float = 0.0,
        extra_conf: Optional[dict] = None) -> None:
    """Assert results equal AND that `fallback_exec` stayed on CPU
    (reference: assert_gpu_fallback_collect in asserts.py)."""
    cpu = run_on_cpu(session, df_fn)
    session.plan_capture.start()
    try:
        tpu = run_on_tpu(session, df_fn,
                         allowed_non_tpu=[fallback_exec],
                         extra_conf=extra_conf)
    finally:
        plans = session.plan_capture.stop()
    assert_rows_equal(cpu, tpu, ignore_order=ignore_order,
                      approx_float=approx_float)
    found = []
    for p in plans:
        p.foreach(lambda n: found.append(type(n).__name__))
    assert fallback_exec in found, \
        f"expected {fallback_exec} in plan, got {sorted(set(found))}"


# ---------------------------------------------------------------------------
# Random data generation (reference: data_gen.py / FuzzerUtils.scala)
# ---------------------------------------------------------------------------
class DataGen:
    def __init__(self, dtype: DataType, nullable: bool = True,
                 null_prob: float = 0.1):
        self.dtype = dtype
        self.nullable = nullable
        self.null_prob = null_prob if nullable else 0.0

    def generate(self, rng: np.random.Generator, n: int) -> list:
        vals = self._values(rng, n)
        if self.null_prob > 0:
            mask = rng.random(n) < self.null_prob
            vals = [None if m else v for v, m in zip(vals, mask)]
        return list(vals)

    def _values(self, rng, n):
        raise NotImplementedError


class IntGen(DataGen):
    def __init__(self, dtype: DataType = DataType.INT64, lo=None, hi=None,
                 nullable=True, special=True):
        super().__init__(dtype, nullable)
        info = np.iinfo(dtype.to_np())
        self.lo = info.min if lo is None else lo
        self.hi = info.max if hi is None else hi
        self.special = special

    def _values(self, rng, n):
        vals = rng.integers(self.lo, self.hi, size=n, endpoint=True,
                            dtype=self.dtype.to_np())
        out = [int(v) for v in vals]
        if self.special and n >= 4:
            out[0], out[1] = int(self.lo), int(self.hi)
        return out


class FloatGen(DataGen):
    def __init__(self, dtype: DataType = DataType.FLOAT64, nullable=True,
                 special=True, no_nans: bool = False):
        super().__init__(dtype, nullable)
        self.special = special
        self.no_nans = no_nans

    def _values(self, rng, n):
        vals = (rng.random(n) - 0.5) * 2e6
        out = [float(v) for v in vals.astype(self.dtype.to_np())]
        if self.special and n >= 6:
            out[0], out[1] = 0.0, -0.0
            out[2], out[3] = float("inf"), float("-inf")
            if not self.no_nans:
                out[4] = float("nan")
        return out


class BoolGen(DataGen):
    def __init__(self, nullable=True):
        super().__init__(DataType.BOOL, nullable)

    def _values(self, rng, n):
        return [bool(v) for v in rng.integers(0, 2, size=n)]


class StringGen(DataGen):
    def __init__(self, nullable=True, max_len: int = 12,
                 alphabet: str = "abcXYZ012 _%é中"):
        super().__init__(DataType.STRING, nullable)
        self.max_len = max_len
        self.alphabet = alphabet

    def _values(self, rng, n):
        out = []
        for _ in range(n):
            k = int(rng.integers(0, self.max_len + 1))
            out.append("".join(
                self.alphabet[int(i)]
                for i in rng.integers(0, len(self.alphabet), size=k)))
        if n >= 2:
            out[0] = ""
        return out


class DateGen(DataGen):
    def __init__(self, nullable=True):
        super().__init__(DataType.DATE, nullable)

    def _values(self, rng, n):
        # 1970-01-01 .. 2100-01-01 in days
        return [int(v) for v in rng.integers(0, 47482, size=n)]


class TimestampGen(DataGen):
    def __init__(self, nullable=True):
        super().__init__(DataType.TIMESTAMP, nullable)

    def _values(self, rng, n):
        return [int(v) for v in
                rng.integers(0, 4102444800_000000, size=n)]


def gen_df(session: TpuSession, gens: Sequence[tuple], n: int = 512,
           seed: int = 0, num_partitions: int = 2):
    """gens: list of (name, DataGen). Returns a DataFrame."""
    rng = np.random.default_rng(seed)
    data = {name: g.generate(rng, n) for name, g in gens}
    schema = [(name, g.dtype) for name, g in gens]
    return session.createDataFrame(data, schema,
                                   num_partitions=num_partitions)


def forbid_device_fetch_in_map_tasks(monkeypatch) -> List[int]:
    """While a map task of an exchange runs (`run_map` under
    `scheduler.run_job_or_serial`), `jax.device_get` and a `host_rows()`
    that would have to fetch its count from the device fail the test.
    Returns the list the guarded tasks' partition ids are appended to."""
    import threading

    import jax

    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.engine import scheduler as SCH

    in_map = threading.local()
    tasks: List[int] = []
    real_run, real_get = SCH.run_job_or_serial, jax.device_get
    real_rows = ColumnarBatch.host_rows

    def run(scheduler, n, fn):
        if fn.__name__ != "run_map":
            return real_run(scheduler, n, fn)

        def guarded(p):
            in_map.on = True
            tasks.append(p)
            try:
                return fn(p)
            finally:
                in_map.on = False
        return real_run(scheduler, n, guarded)

    def device_get(x):
        assert not getattr(in_map, "on", False), "device_get in a map task"
        return real_get(x)

    def host_rows(self):
        assert self.rows_on_host or not getattr(in_map, "on", False), \
            "host_rows() fetches a count in a map task"
        return real_rows(self)

    monkeypatch.setattr(SCH, "run_job_or_serial", run)
    monkeypatch.setattr(jax, "device_get", device_get)
    monkeypatch.setattr(ColumnarBatch, "host_rows", host_rows)
    return tasks

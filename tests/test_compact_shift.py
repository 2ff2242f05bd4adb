"""The stream compaction's shift network (PR 41; columnar/batch.py
`_compact_shift_fixed_cols`, `compact_rows`) against the plain reference it
replaced: `_gather_fixed_body` through `argsort(~keep, stable=True)`,
equal byte for byte with the padding lanes."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import spark_rapids_tpu.columnar.batch as B
from spark_rapids_tpu.columnar import encoded as ENC
from spark_rapids_tpu.columnar.batch import (
    HostColumnarBatch,
    HostColumnVector,
    bucket_capacity,
    compact_batch,
)
from spark_rapids_tpu.columnar.dtypes import DataType
from spark_rapids_tpu.utils import metrics as M

CAPS = [16, 1024, 1 << 15]
DTYPES = ["float32", "int32", "int64", "bool", "codes"]
MASKS = ["all_kept", "none_kept", "p965", "p02", "alternating",
         "first_dropped", "last_dropped", "dead_suffix"]
# p002: the output's capacity bucket far under the input's
FEW = "p002"


def _mask(name, cap, rng):
    """-> (keep bits over the capacity, num_rows)."""
    keep = {
        "all_kept": lambda: np.ones(cap, bool),
        "none_kept": lambda: np.zeros(cap, bool),
        "p965": lambda: rng.random(cap) < 0.965,
        "p02": lambda: rng.random(cap) < 0.02,
        FEW: lambda: rng.random(cap) < 0.002,
        "alternating": lambda: np.arange(cap) % 2 == 0,
        "first_dropped": lambda: np.arange(cap) != 0,
        "last_dropped": lambda: np.arange(cap) != cap - 1,
        # True bits past num_rows: rows that are not there
        "dead_suffix": lambda: rng.random(cap) < 0.7,
    }[name]()
    return keep, (cap - cap // 3 if name == "dead_suffix" else cap)


def _lanes(dtype, cap, rng):
    if dtype == "bool":
        return rng.random(cap) < 0.5
    if dtype == "float32":
        return rng.standard_normal(cap).astype(np.float32)
    if dtype == "int64":
        return rng.integers(-2 ** 40, 2 ** 40, cap)
    return rng.integers(0, 3 if dtype == "codes" else 10 ** 6,
                        cap).astype(np.int32)


@jax.jit
def _order_of(keep_mask, num_rows):
    """PR 40's `_compact_plan`: the plain reference's order and count."""
    keep = keep_mask & (jnp.arange(keep_mask.shape[0]) < num_rows)
    return (jnp.argsort(~keep, stable=True).astype(jnp.int32),
            jnp.sum(keep, dtype=jnp.int32))


def _assert_same_bytes(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("cap", CAPS)
@pytest.mark.parametrize("mask", MASKS)
def test_the_network_moves_what_the_gather_moved(mask, cap, dtype):
    """One column and its validity, the eager shape (the count's capacity
    bucket) and the lazy one (the input's): data, validity, order and
    count equal, the lanes past the count included."""
    rng = np.random.default_rng(cap + len(mask))
    keep_np, num_rows = _mask(mask, cap, rng)
    keep = jnp.asarray(keep_np)
    datas = (jnp.asarray(_lanes(dtype, cap, rng)),)
    valids = (jnp.asarray(rng.random(cap) < 0.8),)
    order, n = _order_of(keep, jnp.int32(num_rows))
    assert int(n) == int((keep_np & (np.arange(cap) < num_rows)).sum())
    for out_cap in {bucket_capacity(max(int(n), 1)), cap}:
        want = B._gather_fixed_body(out_cap, datas, valids, order, None, n)
        outs, got_order, got_n = B._compact_shift_fixed_cols(
            out_cap, datas, valids, keep, jnp.int32(num_rows), True)
        assert int(got_n) == int(n)
        for (gd, gv), (wd, wv) in zip(outs, want):
            _assert_same_bytes(gd, wd)
            _assert_same_bytes(gv, wv)
        live = min(int(n), out_cap)
        _assert_same_bytes(got_order[:live], order[:live])


def test_more_validities_than_one_word_holds():
    cap, rng = 64, np.random.default_rng(7)
    datas = tuple(jnp.asarray(_lanes("int32", cap, rng)) for _ in range(35))
    valids = tuple(jnp.asarray(rng.random(cap) < 0.5) for _ in datas)
    keep = jnp.asarray(rng.random(cap) < 0.6)
    order, n = _order_of(keep, jnp.int32(cap - 5))
    want = B._gather_fixed_body(cap, datas, valids, order, None, n)
    outs, no_order, _ = B._compact_shift_fixed_cols(
        cap, datas, valids, keep, jnp.int32(cap - 5), False)
    assert no_order is None
    for (gd, gv), (wd, wv) in zip(outs, want):
        _assert_same_bytes(gd, wd)
        _assert_same_bytes(gv, wv)


_DICTIONARY = ENC.DeviceDictionary.from_values(["A", "N", "R"])


def _host_batch(n, rng, strings):
    def validity():
        return rng.random(n) < 0.9

    cols = [
        HostColumnVector(DataType.FLOAT32, _lanes("float32", n, rng),
                         validity()),
        HostColumnVector(DataType.INT32, _lanes("int32", n, rng),
                         validity()),
        HostColumnVector(DataType.INT64, _lanes("int64", n, rng),
                         validity()),
        HostColumnVector(DataType.BOOL, _lanes("bool", n, rng), validity()),
        ENC.HostDictionaryColumn(DataType.STRING, _lanes("codes", n, rng),
                                 validity(), _DICTIONARY),
    ]
    if strings:
        cols.append(HostColumnVector.from_pylist(
            [None if i % 11 == 0 else "s" * (i % 5) + str(i)
             for i in range(n)], DataType.STRING))
    return HostColumnarBatch(cols)


class _Span:
    def __init__(self, name, attrs):
        self.name, self.attrs = name, dict(attrs)


@pytest.fixture
def spans(monkeypatch):
    """The spans `columnar/batch.py` opens, without a query around them."""
    opened = []

    @contextlib.contextmanager
    def span(name, **attrs):
        opened.append(_Span(name, attrs))
        yield opened[-1]

    monkeypatch.setattr(B.OBS, "span", span)
    return opened


@pytest.mark.parametrize("lazy", [False, True], ids=["eager", "lazy"])
@pytest.mark.parametrize("strings", [False, True],
                         ids=["fixed_and_codes", "with_a_plain_string"])
@pytest.mark.parametrize("mask", ["p965", "p02", FEW, "none_kept",
                                  "dead_suffix"])
def test_compact_batch_keeps_the_rows_in_their_order(mask, strings, lazy):
    """A batch of every fixed-width kind, a dictionary column and (where
    asked) a plain STRING column, whose gather takes the order the network
    carried as an iota: the rows `x[keep]` gives, the capacity the path
    promises, a traced count on the lazy one."""
    n = 5000
    rng = np.random.default_rng(len(mask) + strings)
    hb = _host_batch(n, rng, strings)
    db = hb.to_device()
    keep_np, num_rows = _mask(mask, db.capacity, rng)
    keep_np[n:] = mask == "dead_suffix"
    want = [r for i, r in enumerate(hb.to_pylist_rows()) if keep_np[i]]
    out = compact_batch(db, jnp.asarray(keep_np), lazy=lazy)
    if lazy:
        assert not isinstance(out.num_rows, int)
        assert out.capacity == db.capacity
    else:
        assert out.num_rows == len(want)
        assert out.capacity == bucket_capacity(max(len(want), 1))
    assert ENC.is_encoded(out.columns[4])
    assert out.host_rows() == len(want)
    assert out.to_host().to_pylist_rows() == want


@pytest.mark.parametrize("mask, lazy", [
    ("p965", False),
    ("p02", False),
    (FEW, False),
    (FEW, True),
])
def test_the_span_and_the_counter_say_what_moved(spans, mask, lazy):
    """Many survivors or few, eager or lazy: one `filter.compact` span
    with the network's `steps`, the count where the host learnt it, and
    one more of `compactedBatches`."""
    n = 1 << 13
    rng = np.random.default_rng(3)
    hb = _host_batch(n, rng, False)
    db = hb.to_device()
    keep_np, _ = _mask(mask, db.capacity, rng)
    before = M.compacted_batch_count()
    out = compact_batch(db, jnp.asarray(keep_np), lazy=lazy)
    (sp,) = [sp for sp in spans if sp.name == "filter.compact"]
    assert sp.attrs["steps"] == 13
    assert sp.attrs["lazy"] is lazy and sp.attrs["capacity"] == n
    assert M.compacted_batch_count() - before == 1
    if lazy:
        assert "rows_out" not in sp.attrs
    else:
        assert sp.attrs["rows_out"] == int(keep_np.sum())
    assert out.to_host().to_pylist_rows() == [
        r for i, r in enumerate(hb.to_pylist_rows()) if keep_np[i]]


def test_few_survivors_take_the_network_too():
    """1 lane in 500 kept: no gather program runs, and the batch is what
    the gather through the survivors' order gave, byte for byte."""
    n = 1 << 13
    rng = np.random.default_rng(5)
    db = _host_batch(n, rng, False).to_device()
    keep = jnp.asarray(_mask(FEW, n, rng)[0])
    n_keep = int(B._compact_plan(keep, jnp.int32(n)))
    gathers = B._gather_fixed_cols._cache_size()
    got = B.compact_rows(db, keep, jnp.int32(n), n_keep)
    assert B._gather_fixed_cols._cache_size() == gathers
    assert got.capacity == bucket_capacity(n_keep) < n // 256
    fixed, _ = B._fixed_and_string_ordinals(db)
    order, count = _order_of(keep, jnp.int32(n))
    want = B._gather_fixed_body(
        got.capacity, tuple(cv.data for _, cv in fixed),
        tuple(cv.validity for _, cv in fixed), order, None, count)
    for cv, (data, validity) in zip(got.columns, want):
        _assert_same_bytes(cv.data, data)
        _assert_same_bytes(cv.validity, validity)

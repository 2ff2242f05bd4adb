"""The eight TPC-H tables from a seed, and their parquet files as a Spark
job would find them.

Every column of TPC-H v3 clause 1.4 is here under its name, with the type
spark-rapids' TpchLikeSpark.scala reads it as (identifiers long, integers
int, decimals double, dates date, every CHAR and VARCHAR string) and at the
published width: fixed-format strings in dbgen's formats, text columns as
substrings of a pseudo-text in clause 4.2.2.10's vocabulary with the
lengths clause 4.2.3 gives. Cardinalities are clause 4.2.5's at scale
factor `sf` (lineitem a fixed 6,000,000 x sf, so that every seed is the
same amount of work). Values are uniform stand-ins for dbgen's
distributions wherever no query of the benchmark depends on more, with
two exceptions kept as the specification has them: p_retailprice is
clause 4.2.3's function of p_partkey, and l_extendedprice is l_quantity x
the part's p_retailprice. Keys start at 0.

This began as a copy of the program's generator
(spark_rapids_tpu/benchmarks/tpch.py `gen_tables`), which has 14 of
lineitem's 16 columns and leaves columns out of every other table; it
imports nothing of the program, so that no later PR can change the data a
cell is measured on. Each table draws from a stream of its own
(`default_rng([seed, index of the table])`): a cell pays only for the
tables it reads. Numeric columns are numpy arrays, which the references
compute from; string columns are pyarrow arrays (6M Python strings would
cost seconds and a gigabyte); the files are all the engine sees.
"""

from __future__ import annotations

import os
from typing import Callable, Dict, Iterable, List, Tuple

import numpy as np

_EPOCH = np.datetime64("1970-01-01", "D")

TABLE_ORDER = ("lineitem", "orders", "part", "partsupp", "customer",
               "supplier", "nation", "region")
# files of a table, as a share of the configuration's files_per_table:
# part and supplier take half, the two tiny tables one file
_FILES = {"part": 0.5, "supplier": 0.5, "nation": 0, "region": 0}
# a text column of more rows than this picks its rows from this many
# distinct texts: still far over what a parquet dictionary page holds
_TEXT_POOL = 1 << 20

_SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"]
_FLAGS = ["A", "N", "R"]
_STATUS = ["F", "O"]
_ORDER_STATUS = ["F", "O", "P"]
_SHIPMODES = ["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"]
_INSTRUCT = ["COLLECT COD", "DELIVER IN PERSON", "NONE", "TAKE BACK RETURN"]
_PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
_TYPES = [f"{a} {b} {c}"
          for a in ("STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO")
          for b in ("ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED")
          for c in ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER")]
_CONTAINERS = [f"{a} {b}" for a in ("SM", "LG", "MED", "JUMBO", "WRAP")
               for b in ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN",
                         "DRUM")]
_COLORS = (
    "almond antique aquamarine azure beige bisque black blanched blue blush "
    "brown burlywood burnished chartreuse chiffon chocolate coral cornflower "
    "cornsilk cream cyan dark deep dim dodger drab firebrick floral forest "
    "frosted gainsboro ghost goldenrod green grey honeydew hot indian ivory "
    "khaki lace lavender lawn lemon light lime linen magenta maroon medium "
    "metallic midnight mint misty moccasin navajo navy olive orange orchid "
    "pale papaya peach peru pink plum powder puff purple red rose rosy royal "
    "saddle salmon sandy seashell sienna sky slate smoke snow spring steel "
    "tan thistle tomato turquoise violet wheat white yellow").split()
_REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
_NATIONS = [("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
            ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
            ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
            ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
            ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
            ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
            ("UNITED KINGDOM", 3), ("UNITED STATES", 1)]
_WORDS = (
    "packages requests accounts deposits foxes ideas theodolites pinto beans "
    "instructions dependencies excuses platelets asymptotes courts dolphins "
    "multipliers sauternes warthogs frets dinos attainments somas patterns "
    "forges braids frays warhorses dugouts notornis epitaphs pearls tithes "
    "waters orbits gifts sheaves depths sentiments decoys realms pains "
    "grouches escapades sleep wake are cajole haggle nag use boost affix "
    "detect integrate maintain nod was lose sublate solve thrash promise "
    "engage hinder print x-ray breach eat grow impress mold poach serve run "
    "dazzle snooze doze unwind kindle play hang believe doubt furious sly "
    "careful blithe quick fluffy slow quiet ruthless thin close dogged daring "
    "brave stealthy permanent enticing idle busy regular final ironic even "
    "bold silent sometimes always never furiously slyly carefully blithely "
    "quickly fluffily slowly quietly ruthlessly thinly closely doggedly "
    "daringly bravely stealthily permanently enticingly idly busily regularly "
    "finally ironically evenly boldly silently about above according to "
    "across after against along alongside of among around at atop before "
    "behind beneath beside besides between beyond by despite during except "
    "for from in place of inside instead of into near on outside over past "
    "since through throughout toward under until up upon without with "
    "within special pending unusual express Customer Complaints").split()
_ALNUM = np.frombuffer(
    b"0123456789abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ, ",
    dtype=np.uint8)

Table = Tuple[Dict[str, object], List[Tuple[str, str]]]


def days(s: str) -> int:
    """Days since 1970-01-01 of 'YYYY-MM-DD': how a DATE is stored."""
    return int((np.datetime64(s, "D") - _EPOCH).astype(int))


def retail_price(partkey: np.ndarray) -> np.ndarray:
    """Clause 4.2.3's P_RETAILPRICE: 900.00 to 2098.99, from the key."""
    return (90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)) / 100.0


# -- strings, as pyarrow arrays, with no Python loop over rows --------------
def _pick(rng, pool: list, n: int):
    import pyarrow as pa

    return pa.array(pool, pa.string()).take(
        pa.array(rng.integers(0, len(pool), n).astype(np.int32)))


def _numbered(prefix: str, numbers: np.ndarray, width: int):
    """dbgen's 'Customer#000000001': the prefix and the number, zero-padded
    to `width` digits."""
    import pyarrow as pa

    n = len(numbers)
    plen = len(prefix)
    out = np.empty((n, plen + width), dtype=np.uint8)
    out[:, :plen] = np.frombuffer(prefix.encode(), dtype=np.uint8)
    rest = numbers.astype(np.int64)
    for k in range(width):
        out[:, plen + width - 1 - k] = 48 + rest % 10
        rest = rest // 10
    offsets = np.arange(n + 1, dtype=np.int32) * (plen + width)
    return pa.StringArray.from_buffers(n, pa.py_buffer(offsets),
                                       pa.py_buffer(out.reshape(-1)))


def _phones(rng, nationkey: np.ndarray):
    """Clause 4.2.2.9: CC-LLL-LLL-LLLL, the country code the nation + 10."""
    import pyarrow as pa

    n = len(nationkey)
    out = np.full((n, 15), ord("-"), dtype=np.uint8)
    for at, (value, width) in ((0, (nationkey + 10, 2)),
                               (3, (rng.integers(100, 1000, n), 3)),
                               (7, (rng.integers(100, 1000, n), 3)),
                               (11, (rng.integers(1000, 10000, n), 4))):
        rest = value.astype(np.int64)
        for k in range(width):
            out[:, at + width - 1 - k] = 48 + rest % 10
            rest = rest // 10
    offsets = np.arange(n + 1, dtype=np.int32) * 15
    return pa.StringArray.from_buffers(n, pa.py_buffer(offsets),
                                       pa.py_buffer(out.reshape(-1)))


def _substrings(rng, blob: np.ndarray, n: int, lo: int, hi: int):
    """n strings of lo..hi bytes, each a substring of `blob` at a random
    place: how dbgen cuts its comments out of one long text."""
    import pyarrow as pa

    lengths = rng.integers(lo, hi + 1, n)
    starts = rng.integers(0, len(blob) - hi, n)
    offsets = np.zeros(n + 1, dtype=np.int32)
    np.cumsum(lengths, out=offsets[1:])
    index = np.repeat((starts - offsets[:-1]).astype(np.int32), lengths)
    index += np.arange(offsets[-1], dtype=np.int32)
    return pa.StringArray.from_buffers(n, pa.py_buffer(offsets),
                                       pa.py_buffer(blob[index]))


def _text(rng, n: int, lo: int, hi: int):
    """A text column (clause 4.2.2.10) of lengths lo..hi. Over _TEXT_POOL
    rows the rows are picked from that many distinct texts."""
    import pyarrow as pa

    words = np.array(_WORDS, dtype=object)[rng.integers(0, len(_WORDS), 1 << 17)]
    blob = np.frombuffer(" ".join(words).encode(), dtype=np.uint8)
    pool = _substrings(rng, blob, min(n, _TEXT_POOL), lo, hi)
    if n <= _TEXT_POOL:
        return pool
    return pool.take(pa.array(rng.integers(0, _TEXT_POOL, n).astype(np.int32)))


def _address(rng, n: int):
    """Clause 4.2.2.7: a v-string of 10..40 random characters."""
    blob = _ALNUM[rng.integers(0, len(_ALNUM), 1 << 20)]
    return _substrings(rng, blob, n, 10, 40)


def _money(rng, n: int, lo: float, hi: float) -> np.ndarray:
    return (lo + rng.random(n) * (hi - lo)).round(2)


# -- the tables --------------------------------------------------------------
def _sizes(sf: float) -> dict:
    return {"lineitem": max(64, int(6_000_000 * sf)),
            "orders": max(32, int(1_500_000 * sf)),
            "customer": max(16, int(150_000 * sf)),
            "supplier": max(8, int(10_000 * sf)),
            "part": max(8, int(200_000 * sf)),
            "clerk": max(1, int(1_000 * sf))}


def _lineitem(rng, n: dict) -> Table:
    rows = n["lineitem"]
    shipdate = rng.integers(days("1992-01-01"), days("1998-12-01"),
                            rows).astype(np.int32)
    partkey = rng.integers(0, n["part"], rows).astype(np.int64)
    quantity = rng.integers(1, 51, rows).astype(np.float64)
    return {
        "l_orderkey": rng.integers(0, n["orders"], rows).astype(np.int64),
        "l_partkey": partkey,
        "l_suppkey": rng.integers(0, n["supplier"], rows).astype(np.int64),
        "l_linenumber": rng.integers(1, 8, rows).astype(np.int32),
        "l_quantity": quantity,
        "l_extendedprice": (quantity * retail_price(partkey)).round(2),
        "l_discount": rng.integers(0, 11, rows) / 100.0,
        "l_tax": rng.integers(0, 9, rows) / 100.0,
        "l_returnflag": _pick(rng, _FLAGS, rows),
        "l_linestatus": _pick(rng, _STATUS, rows),
        "l_shipdate": shipdate,
        "l_commitdate": shipdate + rng.integers(-30, 60, rows).astype(np.int32),
        "l_receiptdate": shipdate + rng.integers(1, 31, rows).astype(np.int32),
        "l_shipinstruct": _pick(rng, _INSTRUCT, rows),
        "l_shipmode": _pick(rng, _SHIPMODES, rows),
        "l_comment": _text(rng, rows, 10, 43),
    }, [("l_orderkey", "long"), ("l_partkey", "long"), ("l_suppkey", "long"),
        ("l_linenumber", "int"), ("l_quantity", "double"),
        ("l_extendedprice", "double"), ("l_discount", "double"),
        ("l_tax", "double"), ("l_returnflag", "string"),
        ("l_linestatus", "string"), ("l_shipdate", "date"),
        ("l_commitdate", "date"), ("l_receiptdate", "date"),
        ("l_shipinstruct", "string"), ("l_shipmode", "string"),
        ("l_comment", "string")]


def _orders(rng, n: dict) -> Table:
    rows = n["orders"]
    return {
        "o_orderkey": np.arange(rows, dtype=np.int64),
        "o_custkey": rng.integers(0, n["customer"], rows).astype(np.int64),
        "o_orderstatus": _pick(rng, _ORDER_STATUS, rows),
        "o_totalprice": _money(rng, rows, 850.0, 560_000.0),
        "o_orderdate": rng.integers(days("1992-01-01"), days("1998-08-03"),
                                    rows).astype(np.int32),
        "o_orderpriority": _pick(rng, _PRIORITIES, rows),
        "o_clerk": _numbered("Clerk#", rng.integers(1, n["clerk"] + 1, rows), 9),
        "o_shippriority": np.zeros(rows, dtype=np.int32),
        "o_comment": _text(rng, rows, 19, 78),
    }, [("o_orderkey", "long"), ("o_custkey", "long"),
        ("o_orderstatus", "string"), ("o_totalprice", "double"),
        ("o_orderdate", "date"), ("o_orderpriority", "string"),
        ("o_clerk", "string"), ("o_shippriority", "int"),
        ("o_comment", "string")]


def _part(rng, n: dict) -> Table:
    import pyarrow as pa
    import pyarrow.compute as pc

    rows = n["part"]
    key = np.arange(rows, dtype=np.int64)
    # five colours a name: distinct in dbgen, independent draws here
    colors = [_pick(rng, _COLORS, rows) for _ in range(5)]
    mfgr = rng.integers(1, 6, rows)
    return {
        "p_partkey": key,
        "p_name": pc.binary_join_element_wise(*colors, pa.scalar(" ")),
        "p_mfgr": _numbered("Manufacturer#", mfgr, 1),
        "p_brand": _numbered("Brand#", mfgr * 10 + rng.integers(1, 6, rows), 2),
        "p_type": _pick(rng, _TYPES, rows),
        "p_size": rng.integers(1, 51, rows).astype(np.int32),
        "p_container": _pick(rng, _CONTAINERS, rows),
        "p_retailprice": retail_price(key),
        "p_comment": _text(rng, rows, 5, 22),
    }, [("p_partkey", "long"), ("p_name", "string"), ("p_mfgr", "string"),
        ("p_brand", "string"), ("p_type", "string"), ("p_size", "int"),
        ("p_container", "string"), ("p_retailprice", "double"),
        ("p_comment", "string")]


def _partsupp(rng, n: dict) -> Table:
    rows = 4 * n["part"]  # clause 4.2.5: four suppliers a part
    return {
        "ps_partkey": np.repeat(np.arange(n["part"], dtype=np.int64), 4),
        "ps_suppkey": rng.integers(0, n["supplier"], rows).astype(np.int64),
        "ps_availqty": rng.integers(1, 10_000, rows).astype(np.int32),
        "ps_supplycost": _money(rng, rows, 1.0, 1000.0),
        "ps_comment": _text(rng, rows, 49, 198),
    }, [("ps_partkey", "long"), ("ps_suppkey", "long"),
        ("ps_availqty", "int"), ("ps_supplycost", "double"),
        ("ps_comment", "string")]


def _customer(rng, n: dict) -> Table:
    rows = n["customer"]
    key = np.arange(rows, dtype=np.int64)
    nation = rng.integers(0, len(_NATIONS), rows).astype(np.int64)
    return {
        "c_custkey": key,
        "c_name": _numbered("Customer#", key + 1, 9),
        "c_address": _address(rng, rows),
        "c_nationkey": nation,
        "c_phone": _phones(rng, nation),
        "c_acctbal": _money(rng, rows, -999.99, 9999.99),
        "c_mktsegment": _pick(rng, _SEGMENTS, rows),
        "c_comment": _text(rng, rows, 29, 116),
    }, [("c_custkey", "long"), ("c_name", "string"), ("c_address", "string"),
        ("c_nationkey", "long"), ("c_phone", "string"),
        ("c_acctbal", "double"), ("c_mktsegment", "string"),
        ("c_comment", "string")]


def _supplier(rng, n: dict) -> Table:
    rows = n["supplier"]
    key = np.arange(rows, dtype=np.int64)
    nation = rng.integers(0, len(_NATIONS), rows).astype(np.int64)
    return {
        "s_suppkey": key,
        "s_name": _numbered("Supplier#", key + 1, 9),
        "s_address": _address(rng, rows),
        "s_nationkey": nation,
        "s_phone": _phones(rng, nation),
        "s_acctbal": _money(rng, rows, -999.99, 9999.99),
        "s_comment": _text(rng, rows, 25, 100),
    }, [("s_suppkey", "long"), ("s_name", "string"), ("s_address", "string"),
        ("s_nationkey", "long"), ("s_phone", "string"),
        ("s_acctbal", "double"), ("s_comment", "string")]


def _nation(rng, n: dict) -> Table:
    import pyarrow as pa

    return {
        "n_nationkey": np.arange(len(_NATIONS), dtype=np.int64),
        "n_name": pa.array([name for name, _ in _NATIONS], pa.string()),
        "n_regionkey": np.array([r for _, r in _NATIONS], dtype=np.int64),
        "n_comment": _text(rng, len(_NATIONS), 31, 114),
    }, [("n_nationkey", "long"), ("n_name", "string"),
        ("n_regionkey", "long"), ("n_comment", "string")]


def _region(rng, n: dict) -> Table:
    import pyarrow as pa

    return {
        "r_regionkey": np.arange(len(_REGIONS), dtype=np.int64),
        "r_name": pa.array(_REGIONS, pa.string()),
        "r_comment": _text(rng, len(_REGIONS), 31, 115),
    }, [("r_regionkey", "long"), ("r_name", "string"),
        ("r_comment", "string")]


_MAKERS: Dict[str, Callable] = {
    "lineitem": _lineitem, "orders": _orders, "part": _part,
    "partsupp": _partsupp, "customer": _customer, "supplier": _supplier,
    "nation": _nation, "region": _region}


def gen_tables(sf: float, seed: int, wanted: Iterable[str]) -> Dict[str, Table]:
    """{table: ({column: array}, [(column, type)])} for each wanted table.
    Types: long, int, double, date (numpy; a date is int32 days) and
    string (pyarrow)."""
    wanted = list(wanted)
    unknown = set(wanted) - set(TABLE_ORDER)
    if unknown or not wanted:
        raise ValueError(f"tables {sorted(unknown)} are not generated here; "
                         f"known: {TABLE_ORDER}")
    sizes = _sizes(sf)
    return {t: _MAKERS[t](np.random.default_rng([seed, TABLE_ORDER.index(t)]),
                          sizes)
            for t in TABLE_ORDER if t in wanted}


def _arrow_table(cols: dict, schema: list, lo: int, hi: int):
    import pyarrow as pa

    types = {"long": pa.int64(), "int": pa.int32(), "double": pa.float64()}
    arrays = []
    for name, typ in schema:
        part = cols[name][lo:hi]
        if typ == "date":
            arrays.append(pa.array(part, type=pa.int32()).cast(pa.date32()))
        elif typ == "string":
            arrays.append(part)
        else:
            arrays.append(pa.array(part, type=types[typ]))
    return pa.table(arrays, names=[name for name, _ in schema])


def write_parquet(tables: Dict[str, Table], data_dir: str,
                  layout: dict) -> Dict[str, str]:
    """Each table under data_dir/<table>/part-NNNNN.parquet, as the
    configuration's `layout` says: `files_per_table` files of equal row
    ranges, `row_groups_per_file` row groups each, rounded up to a power of
    two of rows (2**19 for lineitem at SF1), `compression`, and pyarrow's
    default dictionary encoding. Returns {table: directory}."""
    import pyarrow.parquet as pq

    paths = {}
    for name, (cols, schema) in tables.items():
        tdir = os.path.join(data_dir, name)
        os.makedirs(tdir)
        total = len(next(iter(cols.values())))
        share = _FILES.get(name, 1)
        n_files = max(1, int(layout["files_per_table"] * share))
        per = -(-total // n_files)
        for i in range(n_files):
            lo, hi = i * per, min(total, (i + 1) * per)
            if hi <= lo:
                continue
            group = -(-(hi - lo) // layout["row_groups_per_file"])
            group = max(layout["min_row_group_rows"],
                        1 << (group - 1).bit_length())
            pq.write_table(
                _arrow_table(cols, schema, lo, hi),
                os.path.join(tdir, f"part-{i:05d}.parquet"),
                compression=layout["compression"], row_group_size=group)
        paths[name] = tdir
    return paths

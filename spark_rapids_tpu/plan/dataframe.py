"""DataFrame API over the logical plan (the pyspark.sql.DataFrame analog).

The reference accelerates plans produced by Spark's DataFrame/SQL API; this
standalone framework supplies the equivalent user surface. Name resolution
(`col("x")` -> AttributeReference) happens here, eagerly, against the child
plan's output — the analog of Catalyst's analyzer for this flat algebra.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Union

from spark_rapids_tpu.columnar.dtypes import DataType
from spark_rapids_tpu.ops.aggregates import AggregateFunction
from spark_rapids_tpu.ops.base import (
    Alias,
    AttributeReference,
    Expression,
    SortOrder,
    to_attribute,
)
from spark_rapids_tpu.plan import logical as L
from spark_rapids_tpu.plan.column import Column, _to_expr, to_sort_order
from spark_rapids_tpu.plan.functions import _UnresolvedAttribute

ColumnOrName = Union[Column, str]


class AnalysisError(Exception):
    pass


def resolve(expr: Expression, attrs: Sequence[AttributeReference]) -> Expression:
    """Rewrite _UnresolvedAttribute leaves into schema attributes."""
    by_name: Dict[str, AttributeReference] = {}
    dupes = set()
    for a in attrs:
        if a.name in by_name:
            dupes.add(a.name)
        by_name.setdefault(a.name, a)

    def rewrite(node: Expression) -> Expression:
        if isinstance(node, _UnresolvedAttribute):
            if node.name in dupes:
                raise AnalysisError(
                    f"ambiguous column {node.name!r}; rename before combining")
            got = by_name.get(node.name)
            if got is None:
                raise AnalysisError(
                    f"column {node.name!r} not found in "
                    f"[{', '.join(a.name for a in attrs)}]")
            return got
        return node

    return expr.transform_up(rewrite)


def _auto_alias(e: Expression, fallback: str) -> Expression:
    if isinstance(e, (Alias, AttributeReference)):
        return e
    return Alias(e, fallback)


class DataFrame:
    def __init__(self, plan: L.LogicalPlan, session):
        self._plan = plan
        self.session = session

    # -- schema ---------------------------------------------------------------
    @property
    def schema(self) -> List[AttributeReference]:
        return self._plan.output

    @property
    def columns(self) -> List[str]:
        return [a.name for a in self._plan.output]

    def __getitem__(self, name: str) -> Column:
        return Column(self._resolve_name(name))

    def _resolve_name(self, name: str) -> AttributeReference:
        for a in self._plan.output:
            if a.name == name:
                return a
        raise AnalysisError(
            f"column {name!r} not found in [{', '.join(self.columns)}]")

    def _resolve(self, c: ColumnOrName) -> Expression:
        if isinstance(c, str):
            if c == "*":
                raise AnalysisError("'*' only valid inside select()")
            return self._resolve_name(c)
        return resolve(_to_expr(c), self._plan.output)

    def _with_plan(self, plan: L.LogicalPlan) -> "DataFrame":
        return DataFrame(plan, self.session)

    # -- relational ops -------------------------------------------------------
    def select(self, *cols: ColumnOrName) -> "DataFrame":
        from spark_rapids_tpu.ops.generators import Explode

        out: List[Expression] = []
        gen: Optional[Expression] = None
        gen_slot = -1
        for c in cols:
            if isinstance(c, str) and c == "*":
                out.extend(self._plan.output)
                continue
            e = self._resolve(c)
            core = e.child if isinstance(e, Alias) else e
            if isinstance(core, Explode):
                if gen is not None:
                    raise ValueError("only one explode()/posexplode() per "
                                     "select (Spark restriction)")
                gen = e
                gen_slot = len(out)
                out.append(e)  # placeholder, replaced below
                continue
            out.append(_auto_alias(e, self._default_name(c, len(out))))
        if gen is None:
            return self._with_plan(L.Project(out, self._plan))
        return self._select_generate(out, gen, gen_slot)

    def _select_generate(self, out: List[Expression], gen: Expression,
                         gen_slot: int) -> "DataFrame":
        """Lower select(..., explode(array(...)), ...) to Generate + Project
        (reference: GpuGenerateExec replacing GenerateExec of
        Explode(CreateArray), GpuGenerateExec.scala)."""
        from spark_rapids_tpu.ops.cast import Cast
        from spark_rapids_tpu.ops.generators import Explode

        alias_name = gen.name if isinstance(gen, Alias) else None
        core: Explode = gen.child if isinstance(gen, Alias) else gen
        elem_t = core.array.element_type
        elems = [e if e.data_type is elem_t else Cast(e, elem_t)
                 for e in core.array.elems]
        generator = core.with_children([core.array.with_children(elems)])
        gen_attrs: List[AttributeReference] = []
        if core.include_pos:
            if alias_name is not None:
                raise ValueError("posexplode produces two columns (pos, col)"
                                 " and cannot be aliased to one name")
            gen_attrs.append(AttributeReference("pos", DataType.INT32, False))
        gen_attrs.append(AttributeReference(
            alias_name or "col", elem_t, True))
        plan = L.Generate(generator, gen_attrs, False, self._plan)
        final = out[:gen_slot] + list(gen_attrs) + out[gen_slot + 1:]
        return self._with_plan(L.Project(final, plan))

    @staticmethod
    def _default_name(c: ColumnOrName, idx: int) -> str:
        if isinstance(c, str):
            return c
        return f"col{idx}"

    def withColumn(self, name: str, c: Column) -> "DataFrame":
        e = Alias(self._resolve(c), name)
        out: List[Expression] = []
        replaced = False
        for a in self._plan.output:
            if a.name == name:
                out.append(e)
                replaced = True
            else:
                out.append(a)
        if not replaced:
            out.append(e)
        return self._with_plan(L.Project(out, self._plan))

    def withColumnRenamed(self, old: str, new: str) -> "DataFrame":
        out = [Alias(a, new) if a.name == old else a for a in self._plan.output]
        return self._with_plan(L.Project(out, self._plan))

    def drop(self, *names: str) -> "DataFrame":
        keep = [a for a in self._plan.output if a.name not in names]
        return self._with_plan(L.Project(keep, self._plan))

    def filter(self, condition: Union[Column, str]) -> "DataFrame":
        if isinstance(condition, str):
            raise AnalysisError("string predicates require the SQL frontend; "
                                "pass a Column")
        return self._with_plan(
            L.Filter(self._resolve(condition), self._plan))

    where = filter

    def limit(self, n: int) -> "DataFrame":
        return self._with_plan(L.Limit(n, self._plan))

    def union(self, other: "DataFrame") -> "DataFrame":
        if len(other.schema) != len(self.schema):
            raise AnalysisError("union requires same number of columns")
        return self._with_plan(L.Union(self._plan, other._plan))

    unionAll = union

    def distinct(self) -> "DataFrame":
        attrs = self._plan.output
        return self._with_plan(L.Aggregate(list(attrs), list(attrs), self._plan))

    def dropDuplicates(self, subset: Optional[List[str]] = None) -> "DataFrame":
        if not subset:
            return self.distinct()
        keys = [self._resolve_name(n) for n in subset]
        from spark_rapids_tpu.ops.aggregates import First

        aggs: List[Expression] = []
        for a in self._plan.output:
            if a.name in subset:
                aggs.append(a)
            else:
                aggs.append(Alias(First(a), a.name))
        return self._with_plan(L.Aggregate(keys, aggs, self._plan))

    def repartition(self, num_partitions: int, *cols: ColumnOrName) -> "DataFrame":
        exprs = [self._resolve(c) for c in cols]
        return self._with_plan(
            L.Repartition(num_partitions, exprs, False, self._plan))

    def coalesce(self, num_partitions: int) -> "DataFrame":
        return self._with_plan(
            L.Repartition(num_partitions, [], True, self._plan))

    def orderBy(self, *cols, **kwargs) -> "DataFrame":
        orders = []
        ascending = kwargs.get("ascending", True)
        for c in cols:
            if isinstance(c, SortOrder):
                orders.append(SortOrder(resolve(c.child, self._plan.output),
                                        c.ascending, c.nulls_first))
            elif isinstance(c, str):
                orders.append(SortOrder(self._resolve_name(c), ascending))
            else:
                orders.append(SortOrder(self._resolve(c), ascending))
        return self._with_plan(L.Sort(orders, True, self._plan))

    sort = orderBy

    def sortWithinPartitions(self, *cols, **kwargs) -> "DataFrame":
        df = self.orderBy(*cols, **kwargs)
        plan = df._plan
        assert isinstance(plan, L.Sort)
        return self._with_plan(L.Sort(plan.orders, False, self._plan))

    # -- aggregation ----------------------------------------------------------
    def groupBy(self, *cols: ColumnOrName) -> "GroupedData":
        keys = [self._resolve(c) for c in cols]
        named = [_auto_alias(k, self._default_name(c, i))
                 for i, (k, c) in enumerate(zip(keys, cols))]
        return GroupedData(self, named)

    groupby = groupBy

    def rollup(self, *cols: ColumnOrName) -> "GroupedData":
        """Hierarchical grouping sets (a,b) -> {(a,b), (a), ()} lowered
        through Expand (reference: GpuExpandExec.scala:66-102)."""
        g = self.groupBy(*cols)
        m = len(g._grouping)
        g._grouping_sets = [frozenset(range(k)) for k in range(m, -1, -1)]
        return g

    def cube(self, *cols: ColumnOrName) -> "GroupedData":
        """All 2^m grouping-set combinations lowered through Expand."""
        import itertools as _it

        g = self.groupBy(*cols)
        m = len(g._grouping)
        g._grouping_sets = [
            frozenset(s)
            for k in range(m, -1, -1)
            for s in _it.combinations(range(m), k)
        ]
        return g

    def agg(self, *cols: Column) -> "DataFrame":
        return GroupedData(self, []).agg(*cols)

    def count(self) -> int:
        from spark_rapids_tpu.plan.functions import count as f_count

        rows = self.agg(f_count("*").alias("count")).collect()
        return rows[0][0]

    # -- joins ----------------------------------------------------------------
    def join(self, other: "DataFrame",
             on: Union[str, List[str], Column, None] = None,
             how: str = "inner") -> "DataFrame":
        jt = L.JoinType.parse(how)
        left_keys: List[Expression] = []
        right_keys: List[Expression] = []
        condition: Optional[Expression] = None
        if isinstance(on, str):
            on = [on]
        if isinstance(on, list):
            for name in on:
                left_keys.append(self._resolve_name(name))
                right_keys.append(other._resolve_name(name))
        elif isinstance(on, Column):
            condition = self._resolve_join_condition(on, other)
            left_keys, right_keys, condition = _extract_equi_keys(
                condition, self._plan.output, other._plan.output)
        elif on is not None:
            raise AnalysisError(f"unsupported join on: {on!r}")
        elif jt is not L.JoinType.CROSS:
            raise AnalysisError("join requires 'on' unless how='cross'")
        plan = L.Join(self._plan, other._plan, jt, left_keys, right_keys,
                      condition)
        df = self._with_plan(plan)
        if isinstance(on, list) and jt in (
                L.JoinType.INNER, L.JoinType.LEFT_OUTER,
                L.JoinType.RIGHT_OUTER, L.JoinType.FULL_OUTER):
            # USING-join semantics: emit the join columns once
            drop_ids = {a.expr_id for a in right_keys
                        if isinstance(a, AttributeReference)}
            keep = [a for a in plan.output if a.expr_id not in drop_ids]
            df = df._with_plan(L.Project(keep, plan))
        return df

    def _resolve_join_condition(self, c: Column, other: "DataFrame") -> Expression:
        both = list(self._plan.output) + list(other._plan.output)
        return resolve(c.expr, both)

    def crossJoin(self, other: "DataFrame") -> "DataFrame":
        return self.join(other, on=None, how="cross")

    # -- caching --------------------------------------------------------------
    def cache(self) -> "DataFrame":
        """Cache this DataFrame's batches in memory (device-resident on the
        TPU engine; reference: df.cache() served by the accelerated
        InMemoryTableScan path)."""
        if isinstance(self._plan, L.CacheRelation):
            return self
        # the optimizer never prunes below a cache (plan/optimizer._cache:
        # the node is the cache's key), so what the relation materializes
        # is pruned here, once, to what it holds: `select(...).cache()`
        # scans the selected columns, not the file's
        return self._with_plan(L.CacheRelation(
            self.session._optimized(self._plan)))

    persist = cache

    def unpersist(self) -> "DataFrame":
        from spark_rapids_tpu.exec.cache import invalidate

        if isinstance(self._plan, L.CacheRelation):
            invalidate(self._plan)
            return self._with_plan(self._plan.children[0])
        return self

    # -- actions --------------------------------------------------------------
    def collect(self, timeout=None) -> List[tuple]:
        """Run the query and return all rows. `timeout` (seconds) arms a
        per-call deadline on the query's CancelToken — overriding
        rapids.tpu.engine.deadlineMs — after which the query raises
        TpuDeadlineExceeded with no partial rows and releases everything
        it holds (docs/fault-tolerance.md)."""
        return self.session.execute_collect(self._plan, timeout_s=timeout)

    def toLocalBatches(self):
        return self.session.execute_batches(self._plan)

    def show(self, n: int = 20) -> None:
        rows = self.limit(n).collect()
        names = self.columns
        # tpulint: stdout-print -- show() IS the console API
        print(" | ".join(names))
        for r in rows:
            # tpulint: stdout-print -- show() IS the console API
            print(" | ".join(str(v) for v in r))

    def explain(self, mode: str = "ALL") -> str:
        text = self.session.explain_plan(self._plan, mode)
        # tpulint: stdout-print -- explain() IS the console API
        print(text)
        return text

    def explain_analyze(self) -> str:
        """EXPLAIN ANALYZE: execute this query (tracing forced on) and
        print the plan annotated with measured per-operator metrics
        beside the analyzer's predictions (docs/observability.md)."""
        text = self.session.explain_analyze(self._plan)
        # tpulint: stdout-print -- explain_analyze() IS the console API
        print(text)
        return text

    def toPandas(self):
        import pandas as pd

        rows = self.collect()
        return pd.DataFrame(rows, columns=self.columns)

    # -- write ----------------------------------------------------------------
    @property
    def write(self) -> "DataFrameWriter":
        return DataFrameWriter(self)

    @property
    def rdd_columnar(self):
        """Device-resident columnar export (reference: ColumnarRdd.scala —
        DataFrame -> RDD[Table] handoff for ML)."""
        from spark_rapids_tpu.integration.columnar_rdd import columnar_rdd

        return columnar_rdd(self)


class GroupedData:
    def __init__(self, df: DataFrame, grouping: List[Expression]):
        self._df = df
        self._grouping = grouping
        # rollup/cube: list of frozensets of grouping-column ordinals
        self._grouping_sets: Optional[List[frozenset]] = None

    def agg(self, *cols: Column) -> DataFrame:
        if self._grouping_sets is not None:
            return self._agg_grouping_sets(cols)
        out: List[Expression] = list(self._grouping)
        for i, c in enumerate(cols):
            e = resolve(_to_expr(c), self._df._plan.output)
            out.append(_auto_alias(e, f"agg{i}"))
        plan = L.Aggregate([to_attribute(g) if isinstance(g, Alias) else g
                            for g in self._grouping], out, self._df._plan)
        return self._df._with_plan(plan)

    def _agg_grouping_sets(self, cols) -> DataFrame:
        """rollup/cube: Expand emits one copy of the input per grouping set
        (null-filled dropped keys + a grouping id that keeps natural nulls
        distinct from rolled-up nulls), then a regular aggregate groups on
        the expanded keys + id (reference: GpuExpandExec feeding
        GpuHashAggregateExec, GpuExpandExec.scala:66-102)."""
        from spark_rapids_tpu.ops.literals import Literal

        child = self._df._plan
        m = len(self._grouping)
        g_exprs = [g.child if isinstance(g, Alias) else g
                   for g in self._grouping]
        g_names = [to_attribute(g).name if isinstance(g, Alias) else g.name
                   for g in self._grouping]
        g_types = [g.data_type for g in g_exprs]
        # fresh output attrs for the expanded keys (nullable: sets null them)
        key_attrs = [AttributeReference(n, t, True)
                     for n, t in zip(g_names, g_types)]
        gid_attr = AttributeReference("spark_grouping_id", DataType.INT32,
                                      False)
        projections: List[List[Expression]] = []
        for s in self._grouping_sets:
            gid = 0
            proj: List[Expression] = list(child.output)
            for i in range(m):
                if i in s:
                    proj.append(g_exprs[i])
                else:
                    proj.append(Literal(None, g_types[i]))
                    gid |= 1 << (m - 1 - i)
            proj.append(Literal(gid, DataType.INT32))
            projections.append(proj)
        expand_out = list(child.output) + key_attrs + [gid_attr]
        expand = L.Expand(projections, expand_out, child)
        out: List[Expression] = [Alias(a, a.name) for a in key_attrs]
        for i, c in enumerate(cols):
            e = resolve(_to_expr(c), child.output)
            out.append(_auto_alias(e, f"agg{i}"))
        # gid is grouping-only (not in agg_exprs), so the Aggregate's output
        # is already the user-visible schema
        plan = L.Aggregate(key_attrs + [gid_attr], out, expand)
        return self._df._with_plan(plan)

    def _simple(self, fn, *cols: str) -> DataFrame:
        from spark_rapids_tpu.plan import functions as F

        names = cols or [a.name for a in self._df.schema
                         if a.data_type.is_numeric]
        return self.agg(*[getattr(F, fn)(n).alias(f"{fn}({n})") for n in names])

    def sum(self, *cols: str) -> DataFrame:  # noqa: A003
        return self._simple("sum", *cols)

    def min(self, *cols: str) -> DataFrame:  # noqa: A003
        return self._simple("min", *cols)

    def max(self, *cols: str) -> DataFrame:  # noqa: A003
        return self._simple("max", *cols)

    def avg(self, *cols: str) -> DataFrame:
        return self._simple("avg", *cols)

    mean = avg

    def count(self) -> DataFrame:
        from spark_rapids_tpu.plan.functions import count as f_count

        return self.agg(f_count("*").alias("count"))


class DataFrameWriter:
    def __init__(self, df: DataFrame):
        self._df = df
        self._mode = "error"
        self._options: Dict[str, Any] = {}
        self._partition_by: List[str] = []

    def mode(self, m: str) -> "DataFrameWriter":
        self._mode = m
        return self

    def option(self, k: str, v: Any) -> "DataFrameWriter":
        self._options[k] = v
        return self

    def partitionBy(self, *cols: str) -> "DataFrameWriter":
        self._partition_by = list(cols)
        return self

    def parquet(self, path: str) -> None:
        self._write("parquet", path)

    def orc(self, path: str) -> None:
        self._write("orc", path)

    def csv(self, path: str) -> None:
        self._write("csv", path)

    def _write(self, fmt: str, path: str) -> None:
        plan = L.WriteFile(fmt, path, self._mode, self._options,
                           self._partition_by, self._df._plan)
        self._df.session.execute_write(plan)


def _extract_equi_keys(condition: Expression, left_attrs, right_attrs):
    """Split a join condition into equi-key pairs + residual condition
    (the planner's extractEquiJoinKeys analog)."""
    from spark_rapids_tpu.ops.predicates import And, EqualTo

    left_ids = {a.expr_id for a in left_attrs}
    right_ids = {a.expr_id for a in right_attrs}

    def refs(e: Expression):
        return {n.expr_id for n in e.collect(
            lambda x: isinstance(x, AttributeReference))}

    conjuncts: List[Expression] = []

    def split(e: Expression):
        if isinstance(e, And):
            split(e.left)
            split(e.right)
        else:
            conjuncts.append(e)

    split(condition)
    lk, rk, residual = [], [], []
    for c in conjuncts:
        if isinstance(c, EqualTo):
            lrefs, rrefs = refs(c.left), refs(c.right)
            if lrefs <= left_ids and rrefs <= right_ids:
                lk.append(c.left)
                rk.append(c.right)
                continue
            if lrefs <= right_ids and rrefs <= left_ids:
                lk.append(c.right)
                rk.append(c.left)
                continue
        residual.append(c)
    cond: Optional[Expression] = None
    for r in residual:
        cond = r if cond is None else And(cond, r)
    return lk, rk, cond

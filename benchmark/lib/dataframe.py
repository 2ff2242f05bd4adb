"""The few things an action's DataFrame program needs beyond the program's
public `functions` module. A copy of spark_rapids_tpu/benchmarks/tpch.py's
`date_lit`, so that the actions read no file a later PR may edit."""

from spark_rapids_tpu.columnar.dtypes import DataType
from spark_rapids_tpu.ops.literals import Literal
from spark_rapids_tpu.plan.column import Column

from .tpch_gen import days


def date_lit(s: str) -> Column:
    """A DATE literal from 'YYYY-MM-DD'."""
    return Column(Literal(days(s), DataType.DATE))

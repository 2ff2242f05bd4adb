"""The generated arrays (numpy; strings pyarrow) as pandas frames, and
frames as rows: what the pandas references of the actions share. Imports
nothing of the program."""

from __future__ import annotations

from typing import Iterable, List


def frame(arrays: dict, table: str, columns: Iterable[str]):
    import pandas as pd

    cols, _schema = arrays[table]
    return pd.DataFrame({c: cols[c].to_pandas() if hasattr(cols[c], "to_pandas")
                         else cols[c] for c in columns})


def rows(frame) -> List[tuple]:
    return [tuple(v.item() if hasattr(v, "item") else v for v in row)
            for row in frame.itertuples(index=False, name=None)]

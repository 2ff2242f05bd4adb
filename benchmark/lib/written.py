"""What the write actions' comparisons share: the files of a written
directory, their bytes, and the check of every directory of a window."""

from __future__ import annotations

import glob
import os
from typing import Callable, List

from . import compare as C


def files(out_dir: str) -> List[str]:
    return sorted(glob.glob(os.path.join(out_dir, "*.parquet")))


def written_bytes(out_dir: str) -> int:
    return sum(os.path.getsize(f) for f in files(out_dir))


def compare_dirs(expected: dict, results: List[str],
                 digest_of_files: Callable[[List[str]], list]) -> List[list]:
    """For every written directory its row count, from the footers,
    against expected["rows"]; for the first and the last the digest of the
    files as Arrow's reader gives them back against expected["digest"]."""
    import pyarrow.parquet as pq

    out = []
    for i, out_dir in enumerate(results):
        found = files(out_dir)
        n = sum(pq.ParquetFile(f).metadata.num_rows for f in found)
        numbers = [C.compared("write.row_count_off",
                              abs(n - expected["rows"]), 0),
                   C.compared("write.no_file", int(not found), 0)]
        if found and i in (0, len(results) - 1):
            numbers += C.rows(expected["digest"], digest_of_files(found),
                              "write.digest")
        out.append(numbers)
    return out

"""STRING columns one write action handed to Arrow's writer as dictionary
arrays: the `dict_columns` attr of its `write.arrow` spans added up (a
file's columns that came through the fence as codes + dictionary and were
not expanded, io/writer.py); median over the window. 16 in
`lineitem_write7` (2 columns x 8 files); 0 says the strings came
expanded. Nothing where no span carries the attr (an older program)."""

from lib import spans


def read(run):
    return spans.median_an_action(run, ("write.arrow",),
                                  spans.attr_total("dict_columns"),
                                  "dict_columns")

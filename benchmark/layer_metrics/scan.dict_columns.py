"""Column chunks one action's scan uploaded as dictionary codes: the
`dict_columns` attr of its `scan.host_decode` spans added up (a split's
STRING columns that Arrow handed over undecoded, io/scan.py); median over
the window. 16 in `q1_agg` (2 columns x 8 splits); 0 would say the
strings came decoded. Nothing where no span carries the attr."""

from lib import spans


def read(run):
    return spans.median_an_action(run, ("scan.host_decode",),
                                  spans.attr_total("dict_columns"),
                                  "dict_columns")

"""Wall milliseconds of one action in which some thread had a
`coalesce-concat` span open: the union of those spans; median over the
window. The span is opened by a generator that yields inside it
(`exec/transitions._coalesce_iter`), so besides the concatenation it
covers the consumer's pull of that batch (the final aggregate's merge
program; in `stage:result` a one-row sort): what is read is a coalesce
and what was done with its batch, by the same rule on every commit."""

from lib import spans


def read(run):
    return spans.median_an_action(run, ("coalesce-concat",), spans.union_ms)

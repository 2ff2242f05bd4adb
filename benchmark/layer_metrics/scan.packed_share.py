"""Percent of one action's column chunks whose hybrid streams the device
decoder expanded without a per-lane lookup: of the `scan.decode` spans
that say which form their chunk took (`expand`, PR 26), those that read
`packed` (a bit-packed index stream uploaded as its payload and unpacked
with static shapes, definition levels the host counted as all present not
expanded) against `runs` (some stream needed the search over the run
table and a gather a lane) or `plain` (a PLAIN-encoded chunk: no hybrid
stream expanded, its values gathered a lane). A chunk on the decoder's
per-page loop (strings, DELTA encodings) names no form and is not
counted. Median over the window; nothing where no span carries the attr
(an older program)."""

from lib import spans


def packed_percent(decodes) -> float:
    if not decodes:
        return 0.0
    packed = sum(sp.attrs["expand"] == "packed" for sp in decodes)
    return 100.0 * packed / len(decodes)


def read(run):
    return spans.median_an_action(run, ("scan.decode",), packed_percent,
                                  "expand")

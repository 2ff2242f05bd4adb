"""Wall milliseconds of one action in which some task rebuilt host
columns at a fence while it held the chip's admission permit: the union
over threads of the action's `sink.finish` spans (children of
`DeviceToHost`: numpy on host bytes, nothing of the device) whose
`permit_held` attr is true OR ABSENT. Absent counts as held: until the
program recorded the attr every finish ran under the permit, so an older
commit reads all of its `sink.finish` time here. Nothing where no action
has a `sink.finish` span. Median over the window."""

from lib import spans


def held_ms(finishes):
    return spans.union_ms(sp for sp in finishes
                          if sp.attrs.get("permit_held", True))


def read(run):
    return spans.median_an_action(run, ("sink.finish",), held_ms)

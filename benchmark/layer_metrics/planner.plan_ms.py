"""Median over the window's actions of the span tree's `plan` span: the
planner's host time for one action. An action that leaves no span tree (a
write runs outside a query context) gives nothing."""

from lib import loop


def read(run):
    per_action = [sum(sp.duration_ns for sp in s.record.spans.find("plan")
                      if sp.name == "plan") / 1e6
                  for s in run.samples
                  if not s.error and s.record.spans is not None]
    return loop.median(per_action) if per_action else None

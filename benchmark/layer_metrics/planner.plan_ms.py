"""Median over the window's actions of the span tree's `plan` span: the
planner's host time for one action. Nothing where no action of the
window left a tree with a `plan` span (a program whose write runs outside
a query context, as before PR 25); once one has, a tree without the span
counts as 0, as lib/spans.median_an_action counts it."""

from lib import spans


def read(run):
    return spans.median_an_action(run, ("plan",), spans.total_ms)

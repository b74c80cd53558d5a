"""Percent of the traced stretch in which no operation ran on the device
(1 - union of op intervals / stretch)."""


def read(ctx):
    return ctx["summary"]["idle_share"]

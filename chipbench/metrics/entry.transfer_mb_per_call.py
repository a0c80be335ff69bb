"""Megabytes moved between host and device per study: the ``bytes`` the
program records on its ``sweep.put`` and ``sweep.fetch`` spans (the
summed ``nbytes`` of the arrays moved; chipbench/scopes.py). A count
from shapes, so it repeats exactly. Nothing to read where the program
records no such span."""

from chipbench import scopes


def read(ctx):
    return scopes.transfer_mb_per_call(ctx)

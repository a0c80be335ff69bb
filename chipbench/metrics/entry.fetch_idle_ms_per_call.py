"""Milliseconds per study in which the idlest device was idle inside
``sweep.fetch``: the copy of the results to the host, after the device
is done (chipbench/scopes.py). With the other three
``entry.*_idle_ms_per_call`` it divides the device's idle time inside the studies, on the host's
clock (device times moved by ``scopes.clock_offsets``), among what the
host was doing. Nothing to read where the program records no
``sweep.*`` span or the trace has no enqueue to align by."""

from chipbench import scopes


def read(ctx):
    return scopes.idle_ms_per_call(ctx, "sweep.fetch")

"""Device microseconds per bandit-round in collectives (the segmented
merge's psum and all-gather), averaged over the cell's devices.  Nothing
to read where no collective ran."""

from chipbench import trace


def read(ctx):
    ns, n = ctx.op_time(trace.is_collective)
    if n == 0:
        return None
    return ns / len(ctx.devices) / ctx.bandit_rounds / 1e3

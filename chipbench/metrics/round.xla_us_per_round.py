"""Device microseconds per bandit-round outside the Pallas kernels: the
scan body's XLA ops (candidate draw, Eq. 8 times, schedule, observe, cell
selection), averaged over the cell's devices."""

from chipbench import trace

KERNELS = ("select_", "topk_slots")


def read(ctx):
    ns = ctx.busy_outside(lambda n: trace.is_kernel(n, KERNELS))
    return ns / len(ctx.devices) / ctx.bandit_rounds / 1e3

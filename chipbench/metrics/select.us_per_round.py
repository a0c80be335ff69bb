"""Device microseconds per bandit-round in the selection kernels
(``select_<policy>``, ``topk_slots``), averaged over the cell's devices.
Nothing to read where no selection kernel ran."""

from chipbench import trace

KERNELS = ("select_", "topk_slots")


def read(ctx):
    ns, n = ctx.op_time(lambda name: trace.is_kernel(name, KERNELS))
    if n == 0:
        return None
    return ns / len(ctx.devices) / ctx.bandit_rounds / 1e3

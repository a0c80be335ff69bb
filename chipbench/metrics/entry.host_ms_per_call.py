"""Host milliseconds per study call (sim/engine_jax.sweep: env build,
transfer, dispatch, result copy): each study's host span minus the time
any of the cell's devices was busy inside it, averaged over studies."""

from chipbench import trace


def read(ctx):
    busy = trace.union(iv for d in range(len(ctx.devices))
                       for iv in ctx.busy(d))
    host = sum((e - s) - trace.total(trace.clip(busy, s, e))
               for s, e in ctx.studies)
    return host / len(ctx.studies) / 1e6

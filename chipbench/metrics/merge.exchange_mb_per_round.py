"""Megabytes each chip feeds the segmented merge's collectives per
bandit-round: the counter ``merge_bytes`` that ``sweep()`` puts on its
``sweep.run`` span on the client-sharded route (operand bytes of the [C]
psums and the top-S all-gathers of one shard, over the call), summed over
the studies and divided by their bandit-rounds.  A count from shapes, so
it repeats exactly.  Nothing to read where no ``sweep.run`` span carries
the counter (a one-chip route, or a program that does not record it)."""

from chipbench import scopes

SPAN = "sweep.run"
COUNTER = "merge_bytes"


def read(ctx):
    from jax.profiler import ProfileData

    total, seen = 0, False
    for plane in ProfileData.from_file(str(scopes.trace_file(ctx))).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                n = dict(e.stats).get(COUNTER) if e.name == SPAN else None
                if n is not None and any(lo <= e.start_ns < hi
                                         for lo, hi in ctx.studies):
                    total += int(n)
                    seen = True
    if not seen:
        return None
    return total / ctx.bandit_rounds / 1e6

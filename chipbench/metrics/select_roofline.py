"""Selection kernels' share of their roofline, in %: the least time the
chip needs for the selections they made (counts.selection: the policy's
statistic columns over the C polled candidates and the candidate list),
over the kernels' summed time on all devices.

``select_<policy>`` runs every round of its policy; ``topk_slots`` ranks
the score policies of the client-sharded layout, which run no
``select_`` kernel of their own."""

import math

from chipbench import counts, peaks, trace


def read(ctx):
    ns, n = ctx.op_time(
        lambda name: trace.is_kernel(name, ("select_", "topk_slots")))
    if n == 0:
        return None
    t = ctx.cell.traffic
    c = math.ceil(int(ctx.cell.config["clients"])
                  * float(ctx.cell.config["frac_request"]))
    s = int(ctx.cell.config["select"])
    rounds = (ctx.n_studies * len(t["etas"]) * int(t["seeds_per_study"])
              * int(t["rounds"]))
    names = ctx.op_counts(lambda name: trace.is_kernel(
        name, ("select_", "topk_slots")))
    ops = nbytes = 0
    for policy, _ in t["policies"]:
        own = any(k.startswith(f"select_{policy}") for k in names)
        ranked = (policy in counts.SCORE_POLICIES
                  and any(k.startswith("topk_slots") for k in names))
        if own or ranked:
            o, b = counts.selection(policy, c, s)
            ops += o * rounds
            nbytes += b * rounds
    if nbytes == 0:
        return None
    least = peaks.least_time_s(ops, nbytes, ctx.device_kind)
    return 100.0 * least / (ns / 1e9)

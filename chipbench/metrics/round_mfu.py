"""The whole round's share of the chip's peak, in %: traced bandit-rounds
per second times the least time one bandit-round needs (counts.round_work,
averaged over the mix's policies), over the chips used."""

import math

from chipbench import counts, peaks


def read(ctx):
    t = ctx.cell.traffic
    cfg = ctx.cell.config
    c = math.ceil(int(cfg["clients"]) * float(cfg["frac_request"]))
    cells = int(cfg["scenario"]["congestion_cells"])
    least = [peaks.least_time_s(*counts.round_work(p, c, int(cfg["select"]),
                                                   cells), ctx.device_kind)
             for p, _ in t["policies"]]
    per_round = sum(least) / len(least)
    rate = ctx.bandit_rounds / ctx.window_s()
    return 100.0 * rate * per_round / len(ctx.devices)

"""Reproduce the shape of paper Figs. 1-2: the MAB selectors lose slightly
when resources are stable and win increasingly as fluctuation grows.

Runs entirely on-device: the whole (policy x eta x seed) grid is ONE jit
call through sim.engine_jax (the numpy FederatedServer produces the same
trajectories round-for-round — see tests/test_bandit_jax.py — only ~30x
slower on this grid).

Scaling flags (wired to distributed/sharding.py and sim/engine_jax.sweep):
  --devices N       shard the sweep over N devices ("all" = every device;
                    on a CPU-only host, N virtual devices are forced)
  --shard MODE      what the devices split: "grid" (eta x seed points) or
                    "clients" (the client axis K, for large --clients —
                    when the mesh divides K this runs the segmented
                    shard_map path, O(K/devices) state per device)
  --chunk-rounds C  pre-sample rounds in chunks of C (peak memory O(C*K))
  --hierarchy MODE  "flat" (default) or "cells": two-level cell selection
                    (cells scored by a UCB bandit, candidates polled only
                    inside the top cells) — needs a cell-structured
                    --scenario such as metro-congestion
  --scenario NAME   scenario registry entry (default paper-baseline)

  PYTHONPATH=src python examples/eta_sweep.py [--devices 8] [--chunk-rounds 50]

Million-client invocation (8 virtual devices, client-sharded segmented
rounds, ~10 MB bandit state per device; about 15-20 min on a typical
8-core CPU host, dominated by XLA compile + the virtual devices sharing
the same silicon — on a real 8-accelerator mesh the same command is
minutes):

  PYTHONPATH=src python examples/eta_sweep.py --clients 1000000 \
      --devices 8 --shard clients --chunk-rounds 25 --rounds 50

Hierarchical metro-scale run (100 cells, ~10 selected per round):

  PYTHONPATH=src python examples/eta_sweep.py --clients 100000 \
      --scenario metro-congestion --hierarchy cells --chunk-rounds 25
"""

import argparse
import os

POLICIES = ("fedcs", "extended_fedcs", "naive_ucb", "elementwise_ucb",
            "discounted_ucb", "sliding_ucb")
ETAS = (1.0, 1.5, 1.9, 1.99)
N_SEEDS = 3
N_ROUNDS = 200


def parse_args() -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--devices", default=None,
                    help="shard over this many devices ('all' = every one)")
    ap.add_argument("--shard", choices=("grid", "clients"), default="grid",
                    help="which axis the devices split")
    ap.add_argument("--chunk-rounds", type=int, default=None,
                    help="pre-sample rounds in chunks of this size")
    ap.add_argument("--clients", type=int, default=100,
                    help="number of clients K")
    ap.add_argument("--rounds", type=int, default=N_ROUNDS)
    ap.add_argument("--hierarchy", choices=("flat", "cells"),
                    default="flat",
                    help="two-level cell selection (needs a cell-"
                         "structured --scenario, e.g. metro-congestion)")
    ap.add_argument("--scenario", default="paper-baseline",
                    help="scenario registry entry (sim/scenarios.py)")
    return ap.parse_args()


def main() -> None:
    args = parse_args()
    if args.devices not in (None, "all"):
        # CPU-only hosts: force virtual devices BEFORE jax initializes,
        # appending to (not clobbering) any pre-existing XLA_FLAGS; an
        # already-present device-count force wins
        cur = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in cur:
            os.environ["XLA_FLAGS"] = (
                f"{cur} --xla_force_host_platform_device_count="
                f"{int(args.devices)}").strip()
    from repro.utils.compat import use_compile_cache
    use_compile_cache()
    from repro.sim import engine_jax        # import after XLA_FLAGS is set

    devices = args.devices if args.devices in (None, "all") \
        else int(args.devices)
    kw = dict(scenario=args.scenario, policies=POLICIES, seeds=N_SEEDS,
              n_rounds=args.rounds, n_clients=args.clients, devices=devices,
              shard=args.shard, chunk_rounds=args.chunk_rounds,
              hierarchy=args.hierarchy)
    res = engine_jax.sweep(etas=ETAS, **kw)
    stable = engine_jax.sweep(etas=(0.0,), fluctuate=False, **kw)

    print(f"{'eta':>6} | " + " | ".join(f"{p:>16}" for p in POLICIES[1:]))
    for label, el in [("stable", stable.mean_elapsed()[:, 0])] + [
            (f"{eta:.2f}", res.mean_elapsed()[:, i])
            for i, eta in enumerate(ETAS)]:
        fed = el[0]
        cells = [f"{100*(fed-el[i])/fed:+15.2f}%"
                 for i in range(1, len(POLICIES))]
        print(f"{label:>6} | " + " | ".join(cells))
    print("\n(positive = faster than FedCS; rows match paper Fig. 2; "
          "discounted/sliding UCB are the paper's future-work bandits)")


if __name__ == "__main__":
    main()

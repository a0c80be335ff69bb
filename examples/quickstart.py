"""Quickstart: MAB-based client selection vs FedCS in 60 seconds.

Runs the paper's protocol (time-only mode) for 200 rounds at eta=1.9 and
prints the elapsed-time comparison — the paper's headline result.

  PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.utils.compat import use_compile_cache
from repro.core.bandit import make_policy
from repro.fl.server import FederatedServer, FLConfig
from repro.sim.network import make_network_env
from repro.sim.resources import PAPER_MODEL_BITS, ResourceModel

ETA, ROUNDS, SEED = 1.9, 200, 0


def run(policy: str) -> float:
    env = make_network_env(100, np.random.default_rng(SEED))
    res = ResourceModel(env, eta=ETA, model_bits=PAPER_MODEL_BITS)
    srv = FederatedServer(FLConfig(seed=SEED), make_policy(policy, 100, 5),
                          res)
    srv.run(ROUNDS)
    return srv.elapsed


def main() -> None:
    use_compile_cache()
    print(f"K=100 clients, C=0.1, S_round=5, eta={ETA}, {ROUNDS} rounds\n")
    fed = run("fedcs")
    for policy in ["fedcs", "extended_fedcs", "naive_ucb",
                   "elementwise_ucb", "oracle"]:
        t = fed if policy == "fedcs" else run(policy)
        mark = " <- paper's best" if policy == "elementwise_ucb" else ""
        print(f"  {policy:18s} total FL time {t/3600:7.2f} h   "
              f"vs FedCS {100*(fed-t)/fed:+6.2f}%{mark}")


if __name__ == "__main__":
    main()

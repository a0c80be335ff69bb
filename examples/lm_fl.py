"""FL fine-tuning of an assigned architecture (smollm reduced) with MAB
selection — ties the model zoo to the paper's technique.

  PYTHONPATH=src python examples/lm_fl.py [--arch xlstm-1.3b]
"""

import argparse

import numpy as np

from repro.core.bandit import make_policy
from repro.fl.lm_trainer import LmFlTrainer
from repro.fl.server import FederatedServer, FLConfig
from repro.sim.network import make_network_env
from repro.sim.resources import ResourceModel
from repro.utils.compat import use_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--rounds", type=int, default=6)
    args = ap.parse_args()
    use_compile_cache()

    n_clients = 10
    rng = np.random.default_rng(0)
    env = make_network_env(n_clients, rng)
    # model bits from the reduced LM
    trainer = LmFlTrainer(args.arch, n_clients, env.n_samples, seed=0)
    import jax
    n_params = sum(x.size for x in jax.tree.leaves(trainer.params))
    res = ResourceModel(env, eta=1.5, model_bits=32.0 * n_params)
    srv = FederatedServer(
        FLConfig(n_clients=n_clients, frac_request=0.5, s_round=3, seed=0),
        make_policy("elementwise_ucb", n_clients, 3), res, trainer)

    print(f"FL fine-tuning {args.arch} (reduced, {n_params/1e3:.0f}k params) "
          f"on {n_clients} clients\n")
    for r in range(args.rounds):
        rec = srv.run_round(r)
        print(f"round {r}: sel={rec.selected} "
              f"round_time={rec.round_time:6.1f}s "
              f"local_loss={trainer.last_losses[-1]:.3f}")
    print(f"\nheld-out exp(-loss): {trainer.accuracy():.4f}")


if __name__ == "__main__":
    main()

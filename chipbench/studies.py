"""The one generator of studies: a traffic mix and a seed in, the
arguments of ``engine_jax.sweep`` out.

A study is one ``sweep`` call over the mix's grid (policies x etas x
seeds x rounds).  Every study of a run has the same sizes; study ``i``
takes fresh grid seeds derived from the run's seed and ``i``, passed as a
traced array, so no study compiles again.
"""

from __future__ import annotations

import numpy as np


def study_seeds(seed: int, index: int, n: int) -> list[int]:
    """``n`` grid seeds of study ``index`` (``index`` -1 is the warm-up),
    each below 2**31 so that it fits the engine's int32 seed."""
    ss = np.random.SeedSequence([int(seed) % 2**63, index + 1])
    return [int(x) for x in ss.generate_state(n, np.uint32) & 0x7FFFFFFF]


def env_seed(seed: int) -> int:
    """The seed of the static per-client means (shared by every study)."""
    return int(seed) % 2**63


def bandit_rounds(traffic: dict) -> int:
    """Bandit-rounds in one study: policies x etas x seeds x rounds."""
    return (len(traffic["policies"]) * len(traffic["etas"])
            * int(traffic["seeds_per_study"]) * int(traffic["rounds"]))


def sweep_kwargs(config: dict, traffic: dict, seed: int, index: int) -> dict:
    """Keyword arguments of ``engine_jax.sweep`` for study ``index``."""
    layout = config["layout"]
    kw = dict(
        scenario=config["scenario"]["name"],
        policies=[(name, float(h)) for name, h in traffic["policies"]],
        etas=tuple(float(e) for e in traffic["etas"]),
        seeds=study_seeds(seed, index, int(traffic["seeds_per_study"])),
        n_rounds=int(traffic["rounds"]),
        n_clients=int(config["clients"]),
        s_round=int(config["select"]),
        frac_request=float(config["frac_request"]),
        model_bits=float(config["model_bits"]),
        env_seed=env_seed(seed),
        fluctuate=bool(config["fluctuate"]),
        fast_sampling=config["stream"] == "candidate_slice",
        hierarchy=traffic["hierarchy"],
        chunk_rounds=traffic["chunk_rounds"],
    )
    if traffic["hierarchy"] == "cells":
        kw["s_cells"] = int(traffic["s_cells"])
    if layout["chips"] > 1:
        kw.update(devices=int(layout["chips"]), shard=layout["shard"])
    return kw

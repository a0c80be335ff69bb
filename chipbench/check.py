"""Decide ``correct``: the timed studies' round times against the plain
reference (reference.py), on a sample of grid points drawn from the seed.

The number compared is ``round_gap``: over every round of every sampled
grid point, the widest relative gap between the round time the program
returned and the reference's, where a near tie lets the reference take
either choice.  A grid point that departs (no near tie explains its round
time) stops there, and its gap at that round is what counts.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from chipbench import reference


@dataclasses.dataclass(frozen=True)
class Pick:
    """One sampled grid point of one study."""

    study: int
    policy: int
    eta: int
    seed: int


def sample(n_studies: int, traffic: dict, seed: int, n: int) -> list[Pick]:
    """``n`` distinct grid points over the window's studies, drawn from
    ``seed``, taking the policies in turn; never fewer than one grid point
    of each policy, so that every policy is compared."""
    shape = (n_studies, len(traffic["policies"]), len(traffic["etas"]),
             int(traffic["seeds_per_study"]))
    rng = np.random.default_rng([int(seed) % 2**63, 0xC4EC])
    order = rng.permutation(int(np.prod(shape)))
    by_policy: dict[int, list[Pick]] = {}
    for flat in order:
        i, p, e, s = np.unravel_index(flat, shape)
        by_policy.setdefault(int(p), []).append(
            Pick(int(i), int(p), int(e), int(s)))
    picks = []
    for rank in range(max(len(v) for v in by_policy.values())):
        for p in sorted(by_policy):
            if rank < len(by_policy[p]):
                picks.append(by_policy[p][rank])
    return picks[:max(n, len(by_policy))]


def compare(cell, studies: list[dict], seed: int, *,
            control: str | None = None) -> dict:
    """Replay the sampled grid points of ``studies`` (each a dict with the
    sweep ``kwargs`` and the returned ``round_times`` [P, E, S, R]).

    With ``control`` (a dtype name) the reference computed in that
    precision takes the program's place; the result then reads what that
    control would have read."""
    from chipbench import studies as gen

    config, traffic = cell.config, cell.traffic
    st = reference.study_from(config, traffic)
    env = reference.build_env(st, gen.env_seed(seed))
    ft = reference.DTYPES[config["precision"]]
    picks = sample(len(studies), traffic, seed,
                   int(traffic["check_trajectories"]))
    gap, departed, near, failed_studies, where = 0.0, 0, 0, set(), []
    limit = float(cell.limits["round_gap"]["limit"])
    for pk in picks:
        kw = studies[pk.study]["kwargs"]
        name, hyper = traffic["policies"][pk.policy]
        eta = kw["etas"][pk.eta]
        draws = reference.draw_inputs(st, kw["seeds"][pk.seed],
                                      name == "random")
        if control is None:
            got = studies[pk.study]["round_times"][pk.policy, pk.eta, pk.seed]
        else:
            got = reference.replay(st, env, name, hyper, eta, draws,
                                   ft=reference.DTYPES[control]).round_times
        rp = reference.replay(st, env, name, hyper, eta, draws, ft=ft,
                              reported=np.asarray(got))
        gap = max(gap, rp.gap)
        if rp.departed_at is not None:
            departed += 1
            where.append({"study": pk.study, "policy": name, "eta": eta,
                          "seed": kw["seeds"][pk.seed],
                          "round": rp.departed_at,
                          "reported_then_choices": rp.options[:6]})
        near += rp.near_ties
        if rp.gap > limit:
            failed_studies.add(pk.study)
    return {"round_gap": gap, "trajectories": len(picks),
            "departed": departed, "near_tie_rounds": near,
            "failed_studies": len(failed_studies), "departures": where[:4]}


def verdict(cell, result: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) for the compared numbers."""
    numbers = {"round_gap": {"value": result["round_gap"],
                             "limit": float(
                                 cell.limits["round_gap"]["limit"])}}
    ok = all(v["value"] <= v["limit"] for v in numbers.values())
    return ok, numbers

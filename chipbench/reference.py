"""Plain reference of one selection study: the FL protocol in numpy.

It re-states the study from the paper (arXiv 2009.13879, Sect. II-IV) and
from the configuration file, and imports nothing of the system under test.
Given a grid point (policy, hyper, eta, seed) it replays every round:

  candidate draw -> Eq. 8 resource times -> policy scores -> Algorithm 1
  (or top-S) -> realised schedule -> bandit statistics,

plus the congestion multiplier of a cell-structured scenario and, for
``hierarchy == "cells"``, the cell-level UCB that picks where to poll.

The study's random inputs are defined by JAX's counter-based PRNG
(threefry): keys split from the grid point's seed, one key per round and
stream.  :func:`draw_inputs` reproduces those raw draws with ``jax.random``
(its bits are the same on every backend); everything after the bits runs
here in numpy, in the precision ``ft`` the configuration states
(``float32``), or in ``bfloat16`` for the control that has to fail.

Exact ties resolve to the lowest candidate index, as the paper's numpy
simulator does.  Where two choices lie within rounding of each other
(``NEAR``), float32 on another chip may take either, so the reference
keeps both and :func:`replay` follows those whose round time matches the
one the program reported; a round time that no choice explains is a
departure.
"""

from __future__ import annotations

import collections
import copy
import dataclasses
import math

import ml_dtypes
import numpy as np
from scipy.special import erfinv

BIG = 1e12
# width, relative to the values' scale, within which two scores count as a
# near tie: float32 on the chip moved round times by up to ~3e-5 (relative)
NEAR = 2e-5
# near-tie alternatives considered at one pick, and taken in one round
ALTS = 2
DEVIATIONS = 2
# the largest relative round-time gap at which a round still counts as the
# same choice; a different client moves a round time by far more
MATCH = 1e-3
# most choices considered in one round
MAX_OPTIONS = 64
# choices within this much (relative) of the best match to a reported round
# time are all followed, at most MAX_PATHS of them
KEEP = 1e-4
MAX_PATHS = 8

DTYPES = {"float32": np.dtype(np.float32),
          "bfloat16": np.dtype(ml_dtypes.bfloat16)}


@dataclasses.dataclass(frozen=True)
class Study:
    """Everything the reference needs to know about one cell's study."""

    k: int                      # clients
    s: int                      # clients selected per round
    c: int                      # candidates polled per round (flat)
    n_rounds: int
    stream: str                 # "presample" | "candidate_slice"
    hierarchy: str              # "flat" | "cells"
    cells: int                  # congestion cells (0 = none)
    sigma: float                # lognormal sigma of the cell factor
    s_cells: int                # cells polled per round (hierarchy)
    n_req_cell: int             # candidates per polled cell (hierarchy)
    model_bits: float
    fluctuate: bool
    env: dict                   # environment parameters of the config
    window: int                 # history length (Extended FedCS, sliding)
    gamma: float                # discount of discounted_ucb
    cell_alpha: float           # alpha of the cell-level UCB

    @property
    def cell_pop(self) -> int:
        return -(-self.k // max(self.cells, 1))


def study_from(config: dict, traffic: dict) -> Study:
    """The reference's view of a configuration and a traffic mix."""
    k = int(config["clients"])
    c = math.ceil(k * float(config["frac_request"]))
    cells = int(config["scenario"]["congestion_cells"])
    s_cells = n_req_cell = 0
    if traffic["hierarchy"] == "cells":
        s_cells = int(traffic["s_cells"])
        n_req_cell = min(-(-c // s_cells), -(-k // cells))
    pol = config["policy_constants"]
    return Study(
        k=k, s=int(config["select"]), c=c, n_rounds=int(traffic["rounds"]),
        stream=config["stream"], hierarchy=traffic["hierarchy"],
        cells=cells, sigma=float(config["scenario"]["congestion_sigma"]),
        s_cells=s_cells, n_req_cell=n_req_cell,
        model_bits=float(config["model_bits"]),
        fluctuate=bool(config["fluctuate"]), env=config["environment"],
        window=int(pol["history_window"]), gamma=float(pol["discount"]),
        cell_alpha=float(pol["cell_alpha"]))


# ---------------------------------------------------------------------------
# the environment: static per-client means (paper Sect. IV-A)
# ---------------------------------------------------------------------------

def lte_throughput(dist_m: np.ndarray, env: dict) -> np.ndarray:
    """Mean throughput [bit/s] at distance ``dist_m`` (float64): ITU-R
    M.2135 UMi NLOS path loss and Shannon capacity with a loss factor."""
    d = np.maximum(dist_m, env["min_dist_m"])
    path_loss = 36.7 * np.log10(d) + 22.7 + 26.0 * np.log10(env["carrier_ghz"])
    noise = (env["thermal_noise_dbm_hz"] + 10.0 * np.log10(env["bandwidth_hz"])
             + env["noise_figure_db"])
    snr_db = (env["tx_power_dbm"] + env["antenna_gain_dbi"] - path_loss
              - noise + env["link_margin_db"])
    rho = np.log2(1.0 + 10.0 ** (snr_db / 10.0) / env["shannon_delta"])
    return env["bandwidth_hz"] * np.minimum(rho, env["rho_max"])


def build_env(st: Study, env_seed: int) -> dict:
    """Per-client means drawn from ``env_seed`` in the order the study
    defines: radius, capability, dataset size.  Returned as float32."""
    env = st.env
    rng = np.random.default_rng(env_seed)
    r = env["cell_radius_m"] * np.sqrt(rng.uniform(size=st.k))
    dist = np.maximum(r, env["min_dist_m"])
    theta = lte_throughput(dist, env)
    gamma = rng.uniform(env["capability"][0], env["capability"][1], size=st.k)
    n_samples = rng.integers(env["dataset"][0], env["dataset"][1] + 1,
                             size=st.k).astype(np.float64)
    return {"theta": theta.astype(np.float32),
            "gamma": gamma.astype(np.float32),
            "n_samples": n_samples.astype(np.float32),
            "cell_id": np.arange(st.k) % max(st.cells, 1)}


# ---------------------------------------------------------------------------
# the raw random draws of one grid point
# ---------------------------------------------------------------------------

_DRAW_FNS: dict = {}


def draw_inputs(st: Study, seed: int, random_policy: bool) -> dict:
    """Raw per-round draws of grid point ``seed``, as numpy arrays.

    Streams: the seed's key splits into (candidates, theta, gamma, policy,
    congestion, churn), each into one key per round.  ``presample`` polls
    a permutation prefix and draws Eq. 8 uniforms for all K clients;
    ``candidate_slice`` polls the C largest of K uniforms (the cells of the
    hierarchy: per cell, the key folded with the cell id) and draws one
    [2, C] uniform block for the polled slice.  The random policy scores
    all K clients with uniforms from the policy key."""
    import jax

    sig = (st.k, st.c, st.n_rounds, st.stream, st.hierarchy, st.cells,
           st.sigma, st.s_cells, st.n_req_cell, random_policy)
    fn = _DRAW_FNS.get(sig)
    if fn is None:
        fn = jax.jit(lambda seed: _draws(st, seed, random_policy))
        _DRAW_FNS[sig] = fn
    return {name: np.asarray(x) for name, x in fn(seed).items()}


def _draws(st: Study, seed, random_policy: bool) -> dict:
    import jax
    import jax.numpy as jnp

    roots = jax.random.split(jax.random.PRNGKey(seed), 6)
    kc, kt, kg, kp, kcong = (jax.random.split(r, st.n_rounds)
                             for r in roots[:5])
    per_round = lambda f, keys: jax.vmap(f)(keys)   # noqa: E731
    out = {}
    if st.stream == "presample":
        out["perm"] = per_round(
            lambda kk: jax.random.permutation(kk, st.k)[:st.c], kc)
        out["u_theta"] = per_round(
            lambda kk: jax.random.uniform(kk, (st.k,), jnp.float32), kt)
        out["u_gamma"] = per_round(
            lambda kk: jax.random.uniform(kk, (st.k,), jnp.float32), kg)
    else:
        if st.hierarchy == "cells":
            m = st.cell_pop
            out["u_cells"] = per_round(lambda kk: jax.vmap(
                lambda cid: jax.random.uniform(jax.random.fold_in(kk, cid),
                                               (m,), jnp.float32))(
                jnp.arange(st.cells, dtype=jnp.int32)), kc)
            width = st.s_cells * st.n_req_cell
        else:
            out["cand"] = per_round(lambda kk: _largest(
                jax.random.uniform(kk, (st.k,), jnp.float32), st.c), kc)
            width = st.c
        out["u2"] = per_round(
            lambda kk: jax.random.uniform(kk, (2, width), jnp.float32), kt)
    if random_policy:
        out["rand"] = per_round(
            lambda kk: jax.random.uniform(kk, (st.k,), jnp.float32), kp)
        if "cand" in out:
            out["rand"] = jnp.take_along_axis(out["rand"], out["cand"], 1)
    if st.cells > 0 and st.sigma > 0.0:
        out["normal"] = per_round(
            lambda kk: jax.random.normal(kk, (st.cells,), jnp.float32), kcong)
    return out


def _largest(u, n: int):
    """Sorted positions of the ``n`` largest of ``u``, the lower position
    first among equal values: a stable sort on the device, so that the
    K-sized uniforms never travel to the host."""
    import jax
    import jax.numpy as jnp
    pos = jnp.arange(u.shape[0], dtype=jnp.int32)
    _, order = jax.lax.sort((-u, pos), num_keys=1, is_stable=True)
    return jnp.sort(order[:n])


def top_indices(u: np.ndarray, n: int) -> np.ndarray:
    """Positions of the ``n`` largest values of ``u``, larger first and the
    lower position first among equal values."""
    if n >= u.shape[0]:
        return np.lexsort((np.arange(u.shape[0]), -u.astype(np.float64)))
    part = np.argpartition(-u, n - 1)[:n]
    thr = u[part].min()
    above = np.flatnonzero(u > thr)
    at = np.flatnonzero(u == thr)[:n - above.shape[0]]
    idx = np.concatenate([above, at])
    return idx[np.lexsort((idx, -u[idx].astype(np.float64)))]


# ---------------------------------------------------------------------------
# Eq. 8: truncated-normal resource times
# ---------------------------------------------------------------------------

P_LO = 0.5 * (1.0 + math.erf(-1.0 / math.sqrt(2.0)))    # Phi(-1)
P_HI = 0.5 * (1.0 + math.erf(+1.0 / math.sqrt(2.0)))    # Phi(+1)


def truncnorm(u: np.ndarray, mean: np.ndarray, eta: float, ft) -> np.ndarray:
    """N(mean, mean^eta) truncated to mean +- sigma, by inverse CDF of the
    uniforms ``u``, each step rounded to ``ft``."""
    mean = mean.astype(ft)
    sigma = np.sqrt(np.power(np.maximum(mean, ft.type(1e-12)),
                             ft.type(eta))).astype(ft)
    p = (ft.type(P_LO) + u.astype(ft) * ft.type(P_HI - P_LO)).astype(ft)
    x = (ft.type(2.0) * p - ft.type(1.0)).astype(ft)
    z = (ft.type(math.sqrt(2.0))
         * erfinv(x.astype(np.float64)).astype(ft)).astype(ft)
    out = (mean + sigma * z).astype(ft)
    return np.clip(out, np.maximum(mean - sigma, ft.type(1e-9)),
                   mean + sigma).astype(ft)


def resource_times(u_theta, u_gamma, theta_mu, gamma_mu, n_samples, eta,
                   st: Study, ft):
    """Eqs. 8-11: (t_UD, t_UL) of the given clients."""
    if st.fluctuate:
        theta = truncnorm(u_theta, theta_mu, eta, ft)
        gamma = truncnorm(u_gamma, gamma_mu, eta, ft)
    else:
        theta, gamma = theta_mu.astype(ft), gamma_mu.astype(ft)
    t_ud = (n_samples.astype(ft) / np.maximum(gamma, ft.type(1e-9))).astype(ft)
    t_ul = (ft.type(st.model_bits)
            / np.maximum(theta, ft.type(1e-9))).astype(ft)
    return t_ud, t_ul


# ---------------------------------------------------------------------------
# bandit statistics and the eight policies
# ---------------------------------------------------------------------------

class Stats:
    """Per-client statistics the server keeps (paper Sect. III)."""

    def __init__(self, k: int, window: int, ft):
        z = lambda: np.zeros(k, ft)   # noqa: E731
        self.ft = ft
        self.n = np.zeros(k, np.int64)
        self.sum_ud, self.sum_ul, self.sum_tinc = z(), z(), z()
        self.last_ud, self.last_ul = z(), z()
        self.hist_ud = np.zeros((k, window), ft)
        self.hist_ul = np.zeros((k, window), ft)
        self.hist_n = np.zeros(k, np.int64)
        self.disc_n, self.disc_ud, self.disc_ul = z(), z(), z()
        self.disc_total = ft.type(0.0)
        self.total = 0

    def observe(self, sel, ud, ul, inc, decay: float | None) -> None:
        ft = self.ft
        w = self.hist_ud.shape[1]
        slot = self.n[sel] % w
        if decay is not None:
            d = ft.type(decay)
            self.disc_n = (self.disc_n * d).astype(ft)
            self.disc_ud = (self.disc_ud * d).astype(ft)
            self.disc_ul = (self.disc_ul * d).astype(ft)
            self.disc_n[sel] = self.disc_n[sel] + ft.type(1.0)
            self.disc_ud[sel] = self.disc_ud[sel] + ud
            self.disc_ul[sel] = self.disc_ul[sel] + ul
            self.disc_total = ft.type(self.disc_total * d + ft.type(len(sel)))
        self.n[sel] += 1
        self.sum_ud[sel] = self.sum_ud[sel] + ud
        self.sum_ul[sel] = self.sum_ul[sel] + ul
        self.sum_tinc[sel] = self.sum_tinc[sel] + inc
        self.total += len(sel)
        self.last_ud[sel] = ud
        self.last_ul[sel] = ul
        self.hist_ud[sel, slot] = ud
        self.hist_ul[sel, slot] = ul
        self.hist_n[sel] = np.minimum(self.hist_n[sel] + 1, w)


def ucb_bonus(n: np.ndarray, total, ft) -> np.ndarray:
    """sqrt(ln sum N / 2 N_k); BIG for a client never selected."""
    nf = np.maximum(n.astype(ft), ft.type(1.0))
    tot = np.maximum(ft.type(total), ft.type(2.0))
    b = np.sqrt((np.log(tot) / (ft.type(2.0) * nf)).astype(ft)).astype(ft)
    return np.where(n == 0, ft.type(BIG), b).astype(ft)


def _mean(sums, n, ft):
    return (sums / np.maximum(n.astype(ft), ft.type(1.0))).astype(ft)


def policy_inputs(policy: str, st: Stats, cand: np.ndarray, t_ud, t_ul, rand,
                  hyper: float):
    """('greedy', est_ud, est_ul) or ('score', score, None) over the
    candidates ``cand`` (client indices); ``t_ud``/``t_ul``/``rand`` are
    already at the candidates."""
    ft = st.ft
    h = ft.type(hyper)
    n = st.n[cand]
    if policy == "fedcs":
        return "greedy", st.last_ud[cand], st.last_ul[cand]
    if policy in ("extended_fedcs", "sliding_ucb"):
        hn = np.maximum(st.hist_n[cand], 1).astype(ft)
        m_ud = (st.hist_ud[cand].sum(1, dtype=ft) / hn).astype(ft)
        m_ul = (st.hist_ul[cand].sum(1, dtype=ft) / hn).astype(ft)
        if policy == "extended_fedcs":
            return "greedy", m_ud, m_ul
        b = ucb_bonus(n, st.total, ft)
        return ("greedy", (m_ud / h - b).astype(ft),
                (m_ul / h - b).astype(ft))
    if policy == "naive_ucb":
        score = (-_mean(st.sum_tinc[cand], n, ft) / h
                 + ucb_bonus(n, st.total, ft)).astype(ft)
        return "score", score, None
    if policy == "elementwise_ucb":
        b = ucb_bonus(n, st.total, ft)
        return ("greedy", (_mean(st.sum_ud[cand], n, ft) / h - b).astype(ft),
                (_mean(st.sum_ul[cand], n, ft) / h - b).astype(ft))
    if policy == "random":
        return "score", rand, None
    if policy == "oracle":
        return "greedy", t_ud, t_ul
    if policy == "discounted_ucb":
        dn = st.disc_n[cand]
        cold = dn < ft.type(1e-2)
        dnc = np.maximum(dn, ft.type(1e-3))
        m_ud = np.where(cold, ft.type(0.0), st.disc_ud[cand] / dnc).astype(ft)
        m_ul = np.where(cold, ft.type(0.0), st.disc_ul[cand] / dnc).astype(ft)
        tot = np.maximum(st.disc_total, ft.type(2.0))
        b = np.sqrt((np.log(tot) / (ft.type(2.0) * dnc)).astype(ft)).astype(ft)
        b = np.where(cold, ft.type(BIG), np.minimum(b, ft.type(BIG)))
        return ("greedy", (m_ud / h - b).astype(ft),
                (m_ul / h - b).astype(ft))
    raise ValueError(f"unknown policy {policy!r}")


def _magnitude(v: np.ndarray) -> float:
    """The largest finite magnitude among ``v``, the BIG sentinel aside: the
    scale of the rounding in a value, whatever cancels in it."""
    a = np.abs(v.astype(np.float64))
    a = a[np.isfinite(a) & (a < BIG / 10)]
    return float(a.max()) if a.size else 0.0


def _ranked(v: np.ndarray, width: float) -> list[int]:
    """The lowest value's slot (the lowest slot among equals), then up to
    ``ALTS`` slots within ``width`` of it, nearest first.  An equal value
    resolves by position on every chip alike, so it is no alternative."""
    best = v.min()
    first = int(np.flatnonzero(v == best)[0])
    d = v.astype(np.float64) - float(best)
    near = np.flatnonzero((d > 0) & (d <= width))
    near = near[np.argsort(d[near], kind="stable")][:ALTS]
    return [first] + [int(i) for i in near]


def _options(step, s: int, n: int) -> list[list[int]]:
    """Every pick order that takes at most ``DEVIATIONS`` near-tie
    alternatives, the strict rule's first and fewer deviations earlier.
    ``step(chosen, mask, carry)`` gives the ranked slots and each one's
    next carry."""
    out: list[tuple[int, list[int]]] = []

    def rec(chosen, mask, carry, dev):
        if len(chosen) == s or not mask.any():
            out.append((dev, chosen))
            return
        for j, (x, nxt) in enumerate(step(mask, carry)):
            if j and dev == DEVIATIONS:
                break
            m2 = mask.copy()
            m2[x] = False
            rec(chosen + [x], m2, nxt, dev + (j > 0))

    rec([], np.ones(n, bool), None, 0)
    out.sort(key=lambda o: o[0])
    return [o[1] for o in out[:MAX_OPTIONS]]


def greedy_choices(est_ud, est_ul, s: int, ft) -> list[list[int]]:
    """Algorithm 1 over the candidate slots: the strict rule first (lowest
    slot among equal T_inc), then the orders that near ties allow."""
    zero = ft.type(0.0)

    def step(mask, carry):
        t, td = carry or (zero, zero)
        ntd = np.maximum(td, est_ul).astype(ft)
        tinc = ((ntd - td) + np.maximum((est_ud - (t - td)).astype(ft), zero)
                + est_ul).astype(ft)
        v = np.where(mask, tinc, ft.type(np.inf))
        width = NEAR * (abs(float(v.min())) + abs(float(t)) + abs(float(td)))
        return [(x, (ft.type(max(ft.type(t + tinc[x]), zero)),
                     ft.type(max(td, est_ul[x]))))
                for x in _ranked(v, width)]

    return _options(step, s, est_ud.shape[0])


def score_choices(score, s: int, exact: bool = False) -> list[list[int]]:
    """Top-S by descending score, the lowest slot first among equals, then
    the orders that near ties allow; none where the scores are ``exact``
    (the same bits on every chip, as raw uniforms are)."""
    neg = -score.astype(np.float64)
    # a UCB score is a difference of terms near its largest magnitude
    width = 0.0 if exact else NEAR * _magnitude(neg)

    def step(mask, _carry):
        return [(x, None) for x in _ranked(np.where(mask, neg, np.inf),
                                           width)]

    return _options(step, s, score.shape[0])


def schedule(ud, ul, ft):
    """Realised round time (multicast T_d = max t_UL, parallel local update,
    uploads in selection order) and the per-client Eq. 1 increments."""
    zero = ft.type(0.0)
    td = ul.max() if ul.shape[0] else zero
    t = td
    for a, b in zip(ud, ul):
        t = ft.type(max(t, ft.type(td + a)) + b)
    incs = np.zeros(ud.shape[0], ft)
    t2, td2 = zero, zero
    for i, (a, b) in enumerate(zip(ud, ul)):
        ntd = max(td2, b)
        inc = ft.type(ft.type(ntd - td2)
                      + max(ft.type(a - ft.type(t2 - td2)), zero) + b)
        incs[i] = inc
        t2, td2 = ft.type(t2 + inc), ntd
    return ft.type(t), incs


def cell_scores(cell_n, cell_tinc, alpha: float, ft):
    nf = np.maximum(cell_n, ft.type(1.0))
    total = np.maximum(cell_n.sum(dtype=ft), ft.type(2.0))
    b = np.sqrt((np.log(total) / (ft.type(2.0) * nf)).astype(ft)).astype(ft)
    score = (-(cell_tinc / nf).astype(ft) / ft.type(alpha) + b).astype(ft)
    return np.where(cell_n < ft.type(0.5), ft.type(BIG), score).astype(ft)


# ---------------------------------------------------------------------------
# one grid point, round by round
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Replay:
    """Outcome of replaying one grid point against reported round times."""

    round_times: np.ndarray     # the reference's round times
    gap: float                  # widest relative gap over compared rounds
    departed_at: int | None     # first round no choice explains, or None
    near_ties: int              # rounds that more than one choice explains
    # at the departure: the reported round time and each choice's
    options: tuple[float, ...] = ()


@dataclasses.dataclass
class _Path:
    """The server's state along one sequence of choices."""

    stats: Stats
    cell_n: np.ndarray
    cell_tinc: np.ndarray

    def copy(self) -> "_Path":
        return copy.deepcopy(self)


def replay(st: Study, env: dict, policy: str, hyper: float, eta: float,
           draws: dict, *, ft=np.dtype(np.float32),
           reported: np.ndarray | None = None) -> Replay:
    """Replay one grid point.  Without ``reported`` every round takes the
    strict rule's choice.  With it, every choice whose round time lies
    within ``KEEP`` of the best match to the report is followed (at most
    ``MAX_PATHS`` of them): two orders of the same clients can give one
    round time and still leave different statistics behind.  The replay
    stops at the first round that no choice explains within ``MATCH``."""
    ft = np.dtype(ft)
    decay = st.gamma if policy == "discounted_ucb" else None
    cells = max(st.cells, 1)
    paths = [_Path(Stats(st.k, st.window, ft), np.zeros(cells, ft),
                   np.zeros(cells, ft))]
    theta_mean = env["theta"].astype(ft)
    gamma_mean = env["gamma"].astype(ft)
    n_samples = env["n_samples"]
    cell_id = env["cell_id"]
    eta = float(np.float32(eta))
    rts = np.zeros(st.n_rounds, ft)
    gap, departed, near_ties, seen = 0.0, None, 0, ()
    order_matters = policy == "naive_ucb" or st.hierarchy == "cells"

    for r in range(st.n_rounds):
        if "normal" in draws:
            f = np.exp((ft.type(st.sigma)
                        * draws["normal"][r].astype(ft)).astype(ft)).astype(ft)
            theta_mu = (theta_mean * f[cell_id]).astype(ft)
        else:
            theta_mu = theta_mean
        options = []        # (round time, path, sel, t_ud, t_ul, incs)
        for path in paths:
            for cells_sel in _cell_choices(st, path, ft):
                cand, t_ud, t_ul, rand = _poll(st, draws, r, cells_sel,
                                               theta_mu, gamma_mean,
                                               n_samples, eta, ft)
                kind, a, b = policy_inputs(policy, path.stats, cand, t_ud,
                                           t_ul, rand, hyper)
                picks = (greedy_choices(a, b, st.s, ft) if kind == "greedy"
                         else score_choices(a, st.s, policy == "random"))
                for slots in picks:
                    slots = np.asarray(slots, np.int64)
                    rt, incs = schedule(t_ud[slots], t_ul[slots], ft)
                    options.append((rt, path, cand[slots], t_ud[slots],
                                    t_ul[slots], incs))
                if reported is None:
                    break
        keep = [options[0]]
        if reported is not None:
            rep = float(reported[r])
            gaps = [abs(rep - float(o[0])) / max(abs(float(o[0])), 1e-30)
                    if np.isfinite(rep) else np.inf for o in options]
            best = min(gaps)
            gap = max(gap, best)
            if best > MATCH:
                departed = r
                rts[r] = options[0][0]
                seen = (rep,) + tuple(float(o[0]) for o in options)
                break
            keep, seen_sets = [], set()
            for o, g in zip(options, gaps):
                # another order of the same clients leaves the same
                # statistics behind unless the order's T_inc increments
                # are read later (naive UCB, the cell level)
                key = (id(o[1]), frozenset(o[2].tolist()))
                if g > best + KEEP or (not order_matters and key in seen_sets):
                    continue
                seen_sets.add(key)
                keep.append(o)
            keep = keep[:MAX_PATHS]
            near_ties += len(keep) > 1 or keep[0] is not options[0]
        rts[r] = keep[0][0]
        uses = collections.Counter(id(o[1]) for o in keep)
        paths = []
        for rt, path, sel, ud, ul, incs in keep:
            # each choice updates its own copy of the state it was made
            # from; the last choice made from a state takes the original
            uses[id(path)] -= 1
            if uses[id(path)]:
                path = path.copy()
            pre = path.stats.sum_tinc[sel].copy()
            path.stats.observe(sel, ud, ul, incs, decay)
            if st.hierarchy == "cells":
                d = (path.stats.sum_tinc[sel] - pre).astype(ft)
                np.add.at(path.cell_n, cell_id[sel], ft.type(1.0))
                np.add.at(path.cell_tinc, cell_id[sel], d)
            paths.append(path)
    return Replay(rts, gap, departed, near_ties, seen)


def _cell_choices(st: Study, path: _Path, ft):
    """The cells polled this round (strict rule first), or [None] flat."""
    if st.hierarchy != "cells":
        return [None]
    score = cell_scores(path.cell_n, path.cell_tinc, st.cell_alpha, ft)
    return [np.asarray(c, np.int64) for c in score_choices(score, st.s_cells)]


def _poll(st: Study, draws: dict, r: int, cells_sel, theta_mu, gamma_mu,
          n_samples, eta, ft):
    """This round's sorted candidates, their (t_UD, t_UL) and the random
    policy's scores at them (None for the other policies)."""
    rand = draws.get("rand")
    if st.stream == "presample":
        cand = np.sort(draws["perm"][r]).astype(np.int64)
        return (cand, *resource_times(
            draws["u_theta"][r][cand], draws["u_gamma"][r][cand],
            theta_mu[cand], gamma_mu[cand], n_samples[cand], eta, st, ft),
            None if rand is None else rand[r][cand])
    if cells_sel is None:
        cand = draws["cand"][r].astype(np.int64)
        rand = None if rand is None else rand[r]
    else:
        parts = []
        for cid in cells_sel:
            members = np.arange(st.cell_pop) * st.cells + cid
            u = np.where(members < st.k, draws["u_cells"][r][cid], -1.0)
            pos = top_indices(u, st.n_req_cell)
            parts.append(np.where(u[pos] >= 0.0, members[pos], st.k))
        cand = np.sort(np.concatenate(parts)).astype(np.int64)
        rand = None if rand is None else rand[r][np.minimum(cand, st.k - 1)]
    real = cand < st.k
    u2 = draws["u2"][r]
    safe = np.where(real, cand, 0)
    t_ud, t_ul = resource_times(u2[0], u2[1], theta_mu[safe], gamma_mu[safe],
                                n_samples[safe], eta, st, ft)
    return (cand[real], t_ud[real], t_ul[real],
            None if rand is None else rand[real])

"""On-device vectorized twin of the FederatedServer round loop.

The numpy simulator (fl/server.py) runs one Python iteration per round; a
paper-figure sweep (policies x eta x seeds, 500 rounds each) takes minutes
of host time while the accelerator idles.  This module expresses the whole
protocol — truncated-normal resource sampling (Eqs. 8-11), candidate
polling, policy selection (lax.switch over core.bandit_jax.SELECT_FNS),
observation update, and elapsed-time accounting — as one ``lax.scan`` over
rounds, ``vmap``-ed over a flattened (policy/hyper x eta x seed) grid, so a
full sweep compiles to a single jit call.

Fidelity: with sorted candidate polling (which fl/server.py also uses) the
per-round selections and elapsed times match the numpy reference within
float32 tolerance on a fixed-seed replay — asserted by
tests/test_bandit_jax.py.  The on-device RNG (jax.random) is a different
stream from numpy's, so *sampled* sweeps agree in distribution, not
pointwise; ``run_replay`` accepts externally sampled times for exact
common-random-number comparisons.

Scenario dynamics (sim/scenarios.py) — correlated cell congestion, diurnal
throughput drift, client churn — run inside the scan body, mirroring
``ScenarioResources``.

Sampling (sim/truncnorm.py, kernels/ref.py): at K >= FAST_SAMPLING_MIN_K
the sweep defaults to the streamed candidate-sliced path — candidates via
a top-k-of-uniforms prefix draw, Eq. (8) times drawn only at the [C]
polled slice inside the fused round — so nothing K-sized is ever sampled
(``fast_sampling``; the legacy full-[R, K] presample stream is preserved
bit-for-bit under ``fast_sampling=False``).

Scaling (distributed/sharding.py): ``sweep(..., devices=N)`` splits the
flattened grid axis over an N-device mesh with ``shard_map`` (bitwise the
same per grid point), ``shard="clients"`` instead commits the client axis K
of the per-client state to a ``NamedSharding`` for GSPMD partitioning
(large-K layout), and ``chunk_rounds=c`` caps peak memory at O(c·K) per
grid point by pre-sampling rounds in chunks inside an outer scan.  All
randomness derives from per-round keys, so the chunked scan consumes
*exactly* the stream of the unchunked one — tests/test_sharded_sweep.py
pins all three equivalences.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec

from repro.core import bandit_jax
from repro.distributed import sharding as dist_sharding
from repro.kernels.ref import truncnorm_times_ref
from repro.sim import network
from repro.sim import truncnorm
from repro.sim.resources import PAPER_MODEL_BITS
from repro.sim.scenarios import (CAP_HIGH, CAP_LOW, Scenario, get_scenario)
from repro.utils.compat import suppress_unusable_donation_warnings

SQRT2 = truncnorm.SQRT2
_P_LO = truncnorm.P_LO     # Phi(-1)
_P_HI = truncnorm.P_HI     # Phi(+1)

# fast_sampling=None (the default) resolves to the streamed candidate-
# sliced path at or above this many clients.  Below it the legacy batched
# presample is already trivial (and slightly faster: per-round [C]-sized
# draws inside the scan pay CPU op overhead that chunk-level batching
# amortizes), and keeping small-K defaults on the legacy stream preserves
# historical trajectories; at K >= 1024 the full-K permutation + [R, K]
# presample dominate the whole sweep and the sliced stream wins decisively
# (~7-8x e2e at K=10^4, BENCH_e2e_sweep.json).  Same auto-routing
# philosophy as core.bandit_jax.FUSED_MIN_K / KERNEL_MIN_K.
FAST_SAMPLING_MIN_K = 1024


def resolve_fast_sampling(fast_sampling: bool | None, n_clients: int) -> bool:
    """Resolve a ``fast_sampling`` argument (None = auto by K) — shared by
    ``sweep()`` and fl/engine.accuracy_sweep()."""
    if fast_sampling is None:
        return n_clients >= FAST_SAMPLING_MIN_K
    return bool(fast_sampling)


# ---------------------------------------------------------------------------
# Eqs. (8)-(11): resource sampling, on device.
# ---------------------------------------------------------------------------

def sample_truncated_normal(key: jnp.ndarray, mean: jnp.ndarray,
                            eta: jnp.ndarray) -> jnp.ndarray:
    """JAX twin of sim.resources.sample_truncated_normal (Eq. 8); the ONE
    jax implementation lives in sim/truncnorm.py (Phi^-1 via erfinv — the
    numpy backend uses Acklam's approximation; both are exact to well below
    the fluctuation scale, pinned by the cross-backend parity test)."""
    return truncnorm.sample_truncated_normal_jax(key, mean, eta)


def sample_times(n_samples: jnp.ndarray, theta_mu: jnp.ndarray,
                 gamma_mu: jnp.ndarray, eta, model_bits, k_t, k_g,
                 *, fluctuate: bool = True) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Eqs. (8)-(11): sample ONE round's (t_UD, t_UL).

    ``theta_mu``/``gamma_mu``: [K] mean throughput / capability; ``k_t`` /
    ``k_g``: this round's PRNG keys.  Returns ([K] t_UD, [K] t_UL) — the
    ONE resource-time formula both on-device engines consume (the time-only
    sweep below and fl/engine.py)."""
    if fluctuate:
        theta = sample_truncated_normal(k_t, theta_mu, eta)
        gamma = sample_truncated_normal(k_g, gamma_mu, eta)
    else:
        theta, gamma = theta_mu, gamma_mu
    return (n_samples / jnp.maximum(gamma, 1e-9),
            model_bits / jnp.maximum(theta, 1e-9))


def sample_times_rounds(n_samples: jnp.ndarray, theta_mu: jnp.ndarray,
                        gamma_mu: jnp.ndarray, eta, model_bits,
                        theta_keys: jnp.ndarray, gamma_keys: jnp.ndarray,
                        *, fluctuate: bool = True
                        ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Vectorized ``sample_times`` over a block of rounds with per-round
    keys.

    ``theta_mu``/``gamma_mu``: [R', K] per-round means; ``theta_keys`` /
    ``gamma_keys``: [R'] per-round PRNG keys.  Returns ([R', K], [R', K]).
    Per-round keys (rather than one key for the whole block) make a chunked
    scan consume the identical random stream as a single-shot pre-sample —
    the property the chunk-equivalence tests pin down.
    """
    one = functools.partial(sample_times, n_samples, eta=eta,
                            model_bits=model_bits, fluctuate=fluctuate)
    return jax.vmap(lambda mu_t, mu_g, kt, kg: one(
        theta_mu=mu_t, gamma_mu=mu_g, k_t=kt, k_g=kg))(
            theta_mu, gamma_mu, theta_keys, gamma_keys)


def _throughput_bps(dist_m: jnp.ndarray) -> jnp.ndarray:
    """jnp port of sim.network.throughput_bps (LTE link budget)."""
    d = jnp.maximum(dist_m, network.MIN_DIST_M)
    pl_db = (36.7 * jnp.log10(d) + 22.7
             + 26.0 * jnp.log10(network.CARRIER_GHZ))
    noise_dbm = (network.THERMAL_NOISE_DBM_HZ
                 + 10.0 * jnp.log10(network.BANDWIDTH_HZ)
                 + network.NOISE_FIGURE_DB)
    snr_db = (network.TX_POWER_DBM + network.ANTENNA_GAIN_DBI - pl_db
              - noise_dbm + network.LINK_MARGIN_DB)
    rho = jnp.log2(1.0 + 10.0 ** (snr_db / 10.0) / network.SHANNON_DELTA)
    return network.BANDWIDTH_HZ * jnp.minimum(rho, network.RHO_MAX)


# ---------------------------------------------------------------------------
# Realized schedule math for a -1-padded selection (Sect. II / Eq. 1).
# ---------------------------------------------------------------------------

# The realized-schedule math moved to core.bandit_jax.schedule_selected so
# the fused round (kernels/ref.py, kernels/bandit_round.py) shares the one
# definition; this alias keeps the engines' historical entry point.
_schedule = bandit_jax.schedule_selected


def _switch_select(policy_idx, s_round: int):
    """A select_fn dispatching on a *traced* policy index (replay mode).
    The sampled sweep instead unrolls the policy axis statically — a vmap
    over lax.switch would evaluate every branch for every grid point."""
    branches = [bandit_jax.make_select_fn(n, s_round)
                for n in bandit_jax.POLICY_NAMES]

    def select(state, cand_mask, key, t_ud, t_ul, hyper):
        return jax.lax.switch(policy_idx, branches, state, cand_mask, key,
                              t_ud, t_ul, hyper)
    return select


def _round(state, cand_mask, t_ud, t_ul, select_fn, hyper, key, decay=1.0,
           fault=None, deadline=None):
    """One protocol round given this round's candidates and true times.
    ``decay`` is the per-round discount of the state's decayed statistics
    (bandit_jax.policy_decay).  ``deadline`` compiles in the failure-aware
    layer (``fault``: static probability triple or None): the fault stream
    derives from ``key`` via bandit_jax.FAULT_STREAM_TAG — the identical
    draw the fused round makes from the same per-round key, so fused and
    unfused sweeps stay bitwise under faults — and the round returns a
    fourth per-slot ``flags`` output."""
    with jax.named_scope(bandit_jax.SCOPE_SELECT):
        sel = select_fn(state, cand_mask, key, t_ud, t_ul, hyper)
    with jax.named_scope(bandit_jax.SCOPE_FINISH):
        valid = sel >= 0
        safe = jnp.where(valid, sel, 0)
        if deadline is None:
            round_time, incs = _schedule(sel, t_ud, t_ul)
            state = bandit_jax.observe(state, sel, t_ud[safe], t_ul[safe],
                                       incs, decay=decay)
            return state, round_time, sel
        fu = (bandit_jax.fault_uniforms(key, sel.shape[0])
              if fault is not None else None)
        sud, sul = t_ud[safe], t_ul[safe]
        round_time, incs, finish = bandit_jax.schedule_completions(
            valid, sud, sul)
        obs_ud, obs_ul, obs_inc, fail, flags, round_time = \
            bandit_jax.censor_slots(valid, sud, sul, incs, finish,
                                    round_time, fu, fault, deadline)
        state = bandit_jax.observe(state, sel, obs_ud, obs_ul, obs_inc,
                                   decay=decay, fail=fail)
        return state, round_time, sel, flags


# ---------------------------------------------------------------------------
# Replay mode: externally supplied candidates/times (exact CRN comparisons).
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("s_round",))
def run_replay(policy_idx: jnp.ndarray, hyper: jnp.ndarray,
               cand_masks: jnp.ndarray, t_ud_rounds: jnp.ndarray,
               t_ul_rounds: jnp.ndarray, key: jnp.ndarray,
               *, s_round: int):
    """Run R rounds from precomputed inputs.

    cand_masks: [R, K] bool; t_*_rounds: [R, K].  Returns a dict with
    round_times [R], elapsed [R] (cumulative), selected [R, S] and the final
    BanditState — the common-random-numbers twin of FederatedServer.run.
    """
    k = t_ud_rounds.shape[1]
    state0 = bandit_jax.BanditState.create(k)

    select_fn = _switch_select(policy_idx, s_round)
    # traced-policy twin of bandit_jax.policy_decay
    decay = jnp.where(policy_idx == bandit_jax.POLICY_IDS["discounted_ucb"],
                      bandit_jax.DEFAULT_GAMMA, 1.0)

    def step(carry, x):
        state, key = carry
        cand_mask, t_ud, t_ul = x
        key, sub = jax.random.split(key)
        state, rt, sel = _round(state, cand_mask,
                                t_ud.astype(jnp.float32),
                                t_ul.astype(jnp.float32),
                                select_fn, hyper, sub, decay=decay)
        return (state, key), (rt, sel)

    (state, _), (rts, sels) = jax.lax.scan(
        step, (state0, key), (cand_masks, t_ud_rounds, t_ul_rounds))
    return {"round_times": rts, "elapsed": jnp.cumsum(rts),
            "selected": sels, "state": state}


# ---------------------------------------------------------------------------
# Sampled mode: the full on-device sweep.
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EnvArrays:
    """Static scenario state shipped to the device once per sweep."""

    mean_theta: jnp.ndarray     # [K] mean throughput, bit/s
    mean_gamma: jnp.ndarray     # [K] mean capability, samples/s
    n_samples: jnp.ndarray      # [K] local dataset sizes D_k
    cell_id: jnp.ndarray        # [K] int32 congestion-cell assignment

    @staticmethod
    def from_scenario(scenario: Scenario, env) -> "EnvArrays":
        return EnvArrays(
            mean_theta=jnp.asarray(env.mean_throughput_bps, jnp.float32),
            mean_gamma=jnp.asarray(env.mean_capability, jnp.float32),
            n_samples=jnp.asarray(env.n_samples, jnp.float32),
            cell_id=jnp.asarray(scenario.cell_ids(env.n_clients), jnp.int32),
        )


def _cand_perms_from_keys(keys: jnp.ndarray, k: int,
                          n_req: int) -> jnp.ndarray:
    """[R', n_req] Resource-Request candidate draws (one permutation prefix
    per round key) — the single source both candidate encodings derive
    from, so mask- and index-consumers see the same subsets."""
    return jax.vmap(lambda kk: jax.random.permutation(kk, k)[:n_req])(keys)


def _cand_masks_from_keys(keys: jnp.ndarray, k: int,
                          n_req: int) -> jnp.ndarray:
    """[R', K] bool Resource-Request candidate subsets from per-round keys
    (``keys``: [R'] PRNG keys, one per round)."""
    r = keys.shape[0]
    perms = _cand_perms_from_keys(keys, k, n_req)
    return jnp.zeros((r, k), bool).at[
        jnp.arange(r)[:, None], perms].set(True)


def _cand_sorted_from_keys(keys: jnp.ndarray, k: int,
                           n_req: int) -> jnp.ndarray:
    """[R', n_req] int32 *sorted* candidate indices from per-round keys —
    the fused round's candidate encoding (sorted so the compacted argmax
    tie-break equals the numpy reference's lowest-client-index rule)."""
    return jnp.sort(_cand_perms_from_keys(keys, k, n_req),
                    axis=-1).astype(jnp.int32)


def _cand_masks(key: jnp.ndarray, n_rounds: int, k: int,
                n_req: int) -> jnp.ndarray:
    """[R, K] bool: every round's Resource-Request candidate subset."""
    return _cand_masks_from_keys(jax.random.split(key, n_rounds), k, n_req)


def _cand_topk_from_keys(keys: jnp.ndarray, k: int,
                         n_req: int) -> jnp.ndarray:
    """[R', n_req] int32 sorted candidate indices via a top-k-of-uniforms
    prefix draw — the fast-sampling candidate stream.

    The indices of the ``n_req`` largest of K iid uniforms are a uniform
    random n_req-subset, exactly like a permutation prefix, but
    ``lax.top_k`` is a partial select where ``jax.random.permutation`` pays
    a full O(K log K) sort of all K arms — at K=10^4 the permutation draw
    was ~5.3 ms/round, the single largest term of the whole sweep; this
    draw is ~13x cheaper.  A DIFFERENT stream from
    ``_cand_sorted_from_keys`` (same distribution), which is why it only
    runs on the ``fast_sampling=True`` path.
    """
    return jax.vmap(lambda kk: _cand_topk_one(kk, k, n_req))(keys)


def _cand_topk_one(key: jnp.ndarray, k: int, n_req: int) -> jnp.ndarray:
    """ONE round's top-k-of-uniforms candidate draw ([n_req] sorted int32)
    — the body :func:`_cand_topk_from_keys` vmaps.  The client-sharded and
    hierarchical paths call it per round inside their scans (a [K] uniform
    draw exists only transiently, never a [R', K] batch), consuming
    bitwise the same stream as the batched form."""
    u = jax.random.uniform(key, (k,), jnp.float32)
    _, idx = jax.lax.top_k(u, n_req)
    return jnp.sort(idx).astype(jnp.int32)


def sample_times_candidates(key: jnp.ndarray, cand_idx: jnp.ndarray,
                            n_samples: jnp.ndarray, theta_mu: jnp.ndarray,
                            gamma_mu: jnp.ndarray, eta, model_bits,
                            *, fluctuate: bool = True
                            ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Eqs. (8)-(11) at the candidate slice: ONE round's (t_UD, t_UL) for
    the [C] polled candidates only.

    ``cand_idx``: [C] int32 candidate indices (>= K entries padding);
    ``theta_mu``/``gamma_mu``/``n_samples``: full-[K] means (``theta_mu``
    already carries any scenario multiplier); ``key``: this round's
    time-draw PRNG key.  Draws a single [2, C] uniform block and applies
    the fused two-draw transform (kernels/ref.truncnorm_times_ref) — the
    bit-identical stream ``make_sampled_round_fn`` consumes inside the
    fused round with the same key, so this is both the standalone sampler
    (tests, the unfused fast path) and the spec of the in-round draw.
    Returns ([C] t_ud, [C] t_ul).
    """
    k = theta_mu.shape[0]
    safe_c = jnp.where(cand_idx < k, cand_idx, 0)
    u2 = (jax.random.uniform(key, (2,) + cand_idx.shape, jnp.float32)
          if fluctuate else None)
    return truncnorm_times_ref(u2, theta_mu[safe_c], gamma_mu[safe_c],
                               n_samples[safe_c], eta, model_bits,
                               fluctuate=fluctuate)


def scenario_diurnal_mult(scen: Scenario, rounds: jnp.ndarray) -> jnp.ndarray:
    """[R'] per-round diurnal throughput multiplier (jnp twin of
    ``Scenario.diurnal_multiplier``; 1.0 when the scenario has no diurnal
    drift).  ``rounds``: [R'] 1-based round indices.  Shared by
    :func:`scenario_thr_mult` and the async serving engine's arrival-rate
    modulation (sim/async_engine.py) — load follows the same day cycle as
    throughput."""
    rounds = rounds.astype(jnp.float32)
    if scen.diurnal_amp > 0.0 and scen.diurnal_period > 0:
        return jnp.maximum(
            1.0 + scen.diurnal_amp
            * jnp.sin(2.0 * math.pi * rounds / scen.diurnal_period), 0.05)
    return jnp.ones(rounds.shape, jnp.float32)


def scenario_thr_mult(scen: Scenario, cell_id: jnp.ndarray,
                      keys: jnp.ndarray,
                      rounds: jnp.ndarray) -> jnp.ndarray:
    """[R', K]-broadcastable per-round multiplier on mean throughput
    (diurnal drift + correlated cell congestion; 1.0 when both are off).

    ``keys``: [R'] per-round PRNG keys (congestion draws — per-round so a
    chunked scan replays the identical stream); ``rounds``: [R'] 1-based
    round indices, matching ScenarioResources whose advance() runs before
    the first sample_times (round r uses diurnal_multiplier(r + 1)).
    Shared by the time-only sweep below and the learning-coupled engine
    (fl/engine.py).
    """
    r = rounds.shape[0]
    mult = jnp.ones((r, 1), jnp.float32)
    if scen.diurnal_amp > 0.0 and scen.diurnal_period > 0:
        mult = mult * scenario_diurnal_mult(scen, rounds)[:, None]
    if scen.congestion_cells > 0 and scen.congestion_sigma > 0.0:
        cell_f = jnp.exp(scen.congestion_sigma * jax.vmap(
            lambda kk: jax.random.normal(kk, (scen.congestion_cells,)))(keys))
        mult = mult * cell_f[:, cell_id]
    return mult


def churn_step(key: jnp.ndarray, mean_theta: jnp.ndarray,
               mean_gamma: jnp.ndarray, churn_prob: float,
               k_total: int | None = None,
               offset=0) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Maybe replace one client with a fresh device (new mean resources;
    the server's stale statistics are the point of the scenario).  Shared
    by both engines' churn paths.

    ``k_total``/``offset`` serve the client-sharded path, where
    ``mean_theta``/``mean_gamma`` are a shard's contiguous [K/P] slice of
    global clients [offset, offset + K/P): the victim index ``j`` is drawn
    over the GLOBAL range from the replicated per-round key (identical
    bits on every shard), each shard applying it only if it owns ``j`` —
    bitwise the flat update.  Flat callers leave both at the default."""
    k = mean_theta.shape[0]
    kc1, kc2, kc3, kc4 = jax.random.split(key, 4)
    do = jax.random.uniform(kc1) < churn_prob
    j = jax.random.randint(kc2, (), 0, k if k_total is None else k_total)
    r = jnp.maximum(network.CELL_RADIUS_M * jnp.sqrt(jax.random.uniform(kc3)),
                    network.MIN_DIST_M)
    hit = do & (offset + jnp.arange(k) == j)
    new_theta = jnp.where(hit, _throughput_bps(r), mean_theta)
    new_gamma = jnp.where(
        hit, jax.random.uniform(kc4, (), jnp.float32, CAP_LOW, CAP_HIGH),
        mean_gamma)
    return new_theta, new_gamma


def _per_round_keys(root: jnp.ndarray, n_rounds: int,
                    n_chunks: int) -> jnp.ndarray:
    """Split ``root`` into one key per round, grouped [n_chunks, c, ...] for
    the outer chunk scan (c = n_rounds // n_chunks)."""
    keys = jax.random.split(root, n_rounds)
    return keys.reshape((n_chunks, n_rounds // n_chunks) + keys.shape[1:])


def _client_constrain(tree, client_mesh, client_dim: int = 0):
    """Pin the client axis (dim ``client_dim``) of every leaf of ``tree``
    to the 1-D client mesh; leaves of lower rank (the scalar counters) stay
    replicated.  No-op when ``client_mesh`` is None."""
    if client_mesh is None:
        return tree
    axis = client_mesh.axis_names[0]

    def leaf(x):
        if x.ndim <= client_dim:
            return x
        spec = [None] * x.ndim
        spec[client_dim] = axis
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(
                client_mesh, jax.sharding.PartitionSpec(*spec)))
    return jax.tree.map(leaf, tree)


def _run_one(env: EnvArrays, model_bits, hyper, eta, seed,
             *, policy: str, scen: Scenario, n_rounds: int, s_round: int,
             n_req: int, fluctuate: bool, chunk_rounds: int | None = None,
             client_mesh=None, fused: bool = True,
             fast_sampling: bool = True, deadline: float | None = None):
    """One grid point: the full protocol over rounds.  Returns [R] round
    times — or ``([R] round times, [R, S] flags)`` with the failure layer
    on (``deadline`` set; the scenario's FaultModel supplies the static
    fault probabilities).  ``policy`` and the scenario dynamics are static
    — the sweep
    unrolls the policy axis so each compiled branch runs only its own
    selection rule, and switched-off dynamics are compiled away entirely.

    ``fused`` (default) runs each round through the one-pass fused round
    (core.bandit_jax.make_round_fn -> kernels/ops.bandit_round: candidates
    compacted before selection, Pallas kernel on TPU); ``fused=False`` is
    the static fallback (mask-based select_fn + schedule + observe).  The
    two are bitwise-identical in selections, round times and state —
    pinned by tests/test_bandit_round.py.

    ``fast_sampling`` (default) is the streamed candidate-sliced sampling
    path: candidates come from the top-k-of-uniforms prefix draw and the
    Eq. (8) times are drawn only at the [C] candidate slice, inside the
    fused round (``make_sampled_round_fn``) — nothing K-sized is ever
    sampled, which is what makes ``sweep()`` fast end-to-end
    (benchmarks/bench_e2e_sweep.py).  ``fast_sampling=False`` preserves
    the legacy full-[R', K] presample stream exactly (replay parity with
    historical runs); both paths are per-round-keyed, so chunked ==
    unchunked bitwise either way, and fused == unfused bitwise within
    each path.

    The round axis runs as an outer scan over chunks of ``chunk_rounds``
    rounds (default: one chunk = the whole run).  On the legacy path each
    chunk pre-samples everything random — candidates, diurnal/congestion
    multipliers, the truncated-normal draws — as [c, ...] arrays in a few
    fused ops, leaving only select/schedule/observe in the inner scan;
    peak memory is O(c·K) per grid point instead of O(R·K).  With churn
    the client means evolve between rounds and times sample per round
    inside the inner scan instead.  The fast path samples per round by
    construction (only [C]-sized draws), so its peak extra memory is
    O(c·C).

    ``client_mesh`` (static) pins the [K]-leading state and draws to a 1-D
    device mesh so GSPMD partitions the client axis (large-K layout).
    """
    k = env.mean_theta.shape[0]
    # below the policy's FUSED_MIN_K the fused round's candidate compaction
    # costs more than it saves — run the unfused mask pipeline instead
    # (bitwise-identical results; the masks come straight from the per-round
    # keys, so the fallback costs nothing over the fused=False baseline)
    fused = fused and k >= bandit_jax.fused_min_k(policy)
    c = n_rounds if chunk_rounds is None else int(chunk_rounds)
    if n_rounds % c:
        raise ValueError(f"n_rounds={n_rounds} not divisible by "
                         f"chunk_rounds={c}")
    n_chunks = n_rounds // c
    # failure layer (static): active iff a deadline is set; fault draws come
    # from the scenario's FaultModel (resolve_fault re-validates the pair)
    failure = deadline is not None
    fault = bandit_jax.resolve_fault(scen.fault, deadline)
    state0 = _client_constrain(bandit_jax.BanditState.create(k), client_mesh)
    k_cand, k_theta, k_gamma, k_pol, k_cong, k_churn = jax.random.split(
        jax.random.PRNGKey(seed), 6)

    if fused:
        round_fn = bandit_jax.make_round_fn(policy, s_round, fault=fault,
                                            deadline=deadline)

        def one_round(state, cand, t_ud_r, t_ul_r, kp):
            out = round_fn(state, cand, kp, t_ud_r, t_ul_r, hyper)
            # (state, sel, rt[, flags]) -> (state, rt | (rt, flags))
            return out[0], ((out[2], out[3]) if failure else out[2])

        def round_cands(keys):
            # sorted indices, not masks — the fused round's encoding
            return _cand_sorted_from_keys(keys, k, n_req)
    else:
        select_fn = bandit_jax.make_select_fn(policy, s_round)
        decay = bandit_jax.policy_decay(policy)

        def one_round(state, cand, t_ud_r, t_ul_r, kp):
            out = _round(state, cand, t_ud_r, t_ul_r, select_fn, hyper, kp,
                         decay=decay, fault=fault, deadline=deadline)
            # (state, rt, sel[, flags]) -> (state, rt | (rt, flags))
            return out[0], ((out[1], out[3]) if failure else out[1])

        def round_cands(keys):
            return _client_constrain(_cand_masks_from_keys(keys, k, n_req),
                                     client_mesh, client_dim=1)

    keys = {name: _per_round_keys(root, n_rounds, n_chunks)
            for name, root in [("cand", k_cand), ("theta", k_theta),
                               ("gamma", k_gamma), ("pol", k_pol),
                               ("cong", k_cong), ("churn", k_churn)]}
    rounds = jnp.arange(1, n_rounds + 1, dtype=jnp.int32).reshape(
        n_chunks, c)

    def _shape_out(outs):
        """Flatten the chunked scan outputs back to round-major shapes."""
        if failure:
            rts, flags = outs
            return rts.reshape(n_rounds), flags.reshape(n_rounds, s_round)
        return outs.reshape(n_rounds)

    if fast_sampling:
        # Forced-fast below FAST_SAMPLING_MIN_K: per-round [C]-sized draws
        # inside the scan pay CPU op overhead that chunk-level batching
        # amortizes (the BENCH_e2e_sweep K=100 regression) — so with static
        # means (no churn) the whole chunk's candidate times batch into ONE
        # vmapped draw outside the inner scan.  Same per-round theta keys,
        # same [2, C] uniform per round => bitwise the streamed path.
        presample = k < FAST_SAMPLING_MIN_K and scen.churn_prob == 0.0
        if fused:
            sampled_fn = bandit_jax.make_sampled_round_fn(
                policy, s_round, fluctuate=fluctuate, fault=fault,
                deadline=deadline)

        def fast_chunk_body(carry, xs):
            state, mean_theta, mean_gamma = carry
            kk, rr = xs
            with jax.named_scope(bandit_jax.SCOPE_CAND_DRAW):
                cands = _cand_topk_from_keys(kk["cand"], k, n_req)
            with jax.named_scope(bandit_jax.SCOPE_TIMES):
                thr_mult = scenario_thr_mult(scen, env.cell_id, kk["cong"],
                                             rr)

            if presample:
                with jax.named_scope(bandit_jax.SCOPE_TIMES):
                    t_ud_cs, t_ul_cs = jax.vmap(
                        lambda k_t, cand, mult: sample_times_candidates(
                            k_t, cand, env.n_samples, mean_theta * mult,
                            mean_gamma, eta, model_bits,
                            fluctuate=fluctuate)
                    )(kk["theta"], cands, thr_mult)
                    # scatter back to [K] (small here by construction)
                    # OUTSIDE the scan — batched like the legacy presample,
                    # so the per-round body carries zero extra ops over the
                    # legacy path; padding lanes hold zeros no selection
                    # can reach
                    t_ud_b, t_ul_b = jax.vmap(
                        lambda cand, tu, tl: bandit_jax.scatter_cand_times(
                            cand, tu, tl, k))(cands, t_ud_cs, t_ul_cs)

                def pre_step(state, x):
                    cand, t_ud, t_ul, kp = x
                    return one_round(state, cand, t_ud, t_ul, kp)

                # the fused round takes the indices, the unfused the masks
                xs_cand = cands if fused else jax.vmap(
                    lambda cand: bandit_jax.cand_mask(cand, k))(cands)
                # the scan's batching moves the grid axis of these xs in a
                # transpose that XLA fuses with the Eq. 8 draw above; that
                # fusion is named here, outside any phase (round.other)
                state, outs = jax.lax.scan(
                    pre_step, state, (xs_cand, t_ud_b, t_ul_b, kk["pol"]))
                return (state, mean_theta, mean_gamma), outs

            def step(carry2, x):
                state, m_theta, m_gamma = carry2
                cand, mult, k_t, kp, kc = x
                with jax.named_scope(bandit_jax.SCOPE_TIMES):
                    mu_t = _client_constrain(m_theta * mult, client_mesh)
                if fused:
                    out = sampled_fn(
                        state, cand, kp, k_t, mu_t, m_gamma, env.n_samples,
                        eta, model_bits, hyper)
                    state, obs = out[0], ((out[2], out[3]) if failure
                                          else out[2])
                else:
                    with jax.named_scope(bandit_jax.SCOPE_TIMES):
                        t_ud_c, t_ul_c = sample_times_candidates(
                            k_t, cand, env.n_samples, mu_t, m_gamma, eta,
                            model_bits, fluctuate=fluctuate)
                        t_ud, t_ul = bandit_jax.scatter_cand_times(
                            cand, t_ud_c, t_ul_c, k)
                    out = _round(state, bandit_jax.cand_mask(cand, k), t_ud,
                                 t_ul, select_fn, hyper, kp, decay=decay,
                                 fault=fault, deadline=deadline)
                    state, obs = out[0], ((out[1], out[3]) if failure
                                          else out[1])
                if scen.churn_prob > 0.0:
                    with jax.named_scope(bandit_jax.SCOPE_TIMES):
                        m_theta, m_gamma = churn_step(kc, m_theta, m_gamma,
                                                      scen.churn_prob)
                return (state, m_theta, m_gamma), obs

            carry2, outs = jax.lax.scan(
                step, (state, mean_theta, mean_gamma),
                (cands, thr_mult, kk["theta"], kk["pol"], kk["churn"]))
            return carry2, outs

        carry0 = (state0, env.mean_theta, env.mean_gamma)
        _, outs = jax.lax.scan(fast_chunk_body, carry0, (keys, rounds))
        return _shape_out(outs)

    def chunk_body(carry, xs):
        state, mean_theta, mean_gamma = carry
        kk, rr = xs
        with jax.named_scope(bandit_jax.SCOPE_CAND_DRAW):
            cands = round_cands(kk["cand"])
        with jax.named_scope(bandit_jax.SCOPE_TIMES):
            thr_mult = scenario_thr_mult(scen, env.cell_id, kk["cong"], rr)

        if scen.churn_prob == 0.0:
            # stateless resources: pre-sample the whole chunk in one shot
            with jax.named_scope(bandit_jax.SCOPE_TIMES):
                t_ud, t_ul = _client_constrain(sample_times_rounds(
                    env.n_samples, mean_theta[None, :] * thr_mult,
                    jnp.broadcast_to(mean_gamma, (c, k)), eta, model_bits,
                    kk["theta"], kk["gamma"], fluctuate=fluctuate),
                    client_mesh, client_dim=1)

            def step(state, x):
                cand, t_ud_r, t_ul_r, kp = x
                return one_round(state, cand, t_ud_r, t_ul_r, kp)
            state, outs = jax.lax.scan(
                step, state, (cands, t_ud, t_ul, kk["pol"]))
            return (state, mean_theta, mean_gamma), outs

        # churn: client means evolve between rounds, sample in the scan
        def step(carry2, x):
            state, m_theta, m_gamma = carry2
            cand, mult, k_t, k_g, kp, kc = x
            with jax.named_scope(bandit_jax.SCOPE_TIMES):
                t_ud, t_ul = sample_times(env.n_samples, m_theta * mult,
                                          m_gamma, eta, model_bits, k_t,
                                          k_g, fluctuate=fluctuate)
            state, obs = one_round(state, cand, t_ud, t_ul, kp)
            with jax.named_scope(bandit_jax.SCOPE_TIMES):
                m_theta, m_gamma = churn_step(kc, m_theta, m_gamma,
                                              scen.churn_prob)
            return (state, m_theta, m_gamma), obs

        carry2, outs = jax.lax.scan(
            step, (state, mean_theta, mean_gamma),
            (cands, thr_mult, kk["theta"], kk["gamma"], kk["pol"],
             kk["churn"]))
        return carry2, outs

    carry0 = (state0, env.mean_theta, env.mean_gamma)
    _, outs = jax.lax.scan(chunk_body, carry0, (keys, rounds))
    return _shape_out(outs)


def _segmented_sweep_body(env: EnvArrays, model_bits, hyper, eta, seed,
                          *, policy: str, scen: Scenario, n_rounds: int,
                          s_round: int, n_req: int, fluctuate: bool,
                          chunk_rounds: int | None, deadline: float | None,
                          axis_name: str, n_shards: int):
    """Per-shard body of the client-sharded sweep — runs INSIDE
    ``shard_map`` over ``axis_name`` (``_run_one_clients_segmented`` wraps
    it).  ``env`` carries this shard's contiguous [K/P] client block; all
    scalars arrive replicated.

    Mirrors ``_run_one``'s fast-sampling scan exactly, except every round
    goes through ``core.bandit_jax.make_segmented_round_fn`` (candidate
    stats re-assembled by masked psum, score policies via local-top-S +
    all_gather + segmented reduce) and the [K] draws that must match the
    flat stream bitwise — candidate top-k-of-uniforms, the random policy's
    stream, congestion normals, churn — are drawn REPLICATED from the same
    per-round keys, each shard consuming only its slice.  Round times (and
    flags) come out replicated; per-device memory stays O(K/P + C).
    """
    k_local = env.mean_theta.shape[0]
    k = k_local * n_shards
    c = n_rounds if chunk_rounds is None else int(chunk_rounds)
    if n_rounds % c:
        raise ValueError(f"n_rounds={n_rounds} not divisible by "
                         f"chunk_rounds={c}")
    n_chunks = n_rounds // c
    failure = deadline is not None
    fault = bandit_jax.resolve_fault(scen.fault, deadline)
    off = jax.lax.axis_index(axis_name) * k_local
    state0 = bandit_jax.BanditState.create(k_local)
    k_cand, k_theta, k_gamma, k_pol, k_cong, k_churn = jax.random.split(
        jax.random.PRNGKey(seed), 6)
    keys = {name: _per_round_keys(root, n_rounds, n_chunks)
            for name, root in [("cand", k_cand), ("theta", k_theta),
                               ("gamma", k_gamma), ("pol", k_pol),
                               ("cong", k_cong), ("churn", k_churn)]}
    rounds = jnp.arange(1, n_rounds + 1, dtype=jnp.int32).reshape(
        n_chunks, c)
    seg_fn = bandit_jax.make_segmented_round_fn(
        policy, s_round, axis_name=axis_name, n_shards=n_shards,
        fluctuate=fluctuate, fault=fault, deadline=deadline)

    def chunk_body(carry, xs):
        kk, rr = xs

        def step(carry2, x):
            state, m_theta, m_gamma = carry2
            kc, kcong, k_t, kp, kch, r1 = x
            # replicated global candidate draw — the [K] uniforms exist
            # only transiently inside this round, never as a [R', K] batch
            with jax.named_scope(bandit_jax.SCOPE_CAND_DRAW):
                cand = _cand_topk_one(kc, k, n_req)
            # local slice of the global multiplier (cell normals are drawn
            # replicated, then indexed by this shard's cell_id block)
            with jax.named_scope(bandit_jax.SCOPE_TIMES):
                mult = scenario_thr_mult(scen, env.cell_id, kcong[None],
                                         r1[None])[0]
                mu_t = m_theta * mult
            out = seg_fn(state, cand, kp, k_t, mu_t, m_gamma,
                         env.n_samples, eta, model_bits, hyper)
            state = out[0]
            obs = (out[2], out[3]) if failure else out[2]
            if scen.churn_prob > 0.0:
                with jax.named_scope(bandit_jax.SCOPE_TIMES):
                    m_theta, m_gamma = churn_step(
                        kch, m_theta, m_gamma, scen.churn_prob, k_total=k,
                        offset=off)
            return (state, m_theta, m_gamma), obs

        return jax.lax.scan(
            step, carry, (kk["cand"], kk["cong"], kk["theta"], kk["pol"],
                          kk["churn"], rr))

    carry0 = (state0, env.mean_theta, env.mean_gamma)
    _, outs = jax.lax.scan(chunk_body, carry0, (keys, rounds))
    if failure:
        rts, flags = outs
        return rts.reshape(n_rounds), flags.reshape(n_rounds, s_round)
    return outs.reshape(n_rounds)


def _run_one_clients_segmented(env: EnvArrays, model_bits, hyper, eta, seed,
                               *, policy: str, scen: Scenario, n_rounds: int,
                               s_round: int, n_req: int, fluctuate: bool,
                               chunk_rounds: int | None,
                               deadline: float | None, client_mesh):
    """One grid point of the client-SHARDED sweep: the whole round loop
    runs inside one ``shard_map`` over the client mesh, each device holding
    only its contiguous [K/P] block of bandit state / client means
    (``distributed.sharding.shard_leading`` layout) — O(K/P) per-device
    memory by construction, unlike the GSPMD-constraint path whose
    partitioning is best-effort.  Returns the same [R] round times (and
    [R, S] flags) as ``_run_one``, bitwise."""
    axis = client_mesh.axis_names[0]
    body = functools.partial(
        _segmented_sweep_body, policy=policy, scen=scen, n_rounds=n_rounds,
        s_round=s_round, n_req=n_req, fluctuate=fluctuate,
        chunk_rounds=chunk_rounds, deadline=deadline, axis_name=axis,
        n_shards=client_mesh.size)
    P = PartitionSpec
    out_specs = (P(), P()) if deadline is not None else P()
    return shard_map(body, mesh=client_mesh,
                     in_specs=(P(axis), P(), P(), P(), P()),
                     out_specs=out_specs, check_vma=False)(
                         env, model_bits, hyper, eta, seed)


def _run_one_hier(env: EnvArrays, model_bits, hyper, eta, seed,
                  *, policy: str, scen: Scenario, n_rounds: int,
                  s_round: int, n_req_cell: int, s_cells: int,
                  fluctuate: bool, chunk_rounds: int | None = None,
                  fused: bool = True, deadline: float | None = None):
    """One grid point of the HIERARCHICAL two-level sweep
    (``sweep(hierarchy="cells")``): each round first scores cells from the
    aggregated per-cell T_inc statistics (core.bandit_jax.cell_scores,
    naive-UCB at cell granularity), picks the top ``s_cells``, and polls
    ``n_req_cell`` candidates ONLY inside those cells
    (core.bandit_jax.hier_cand_idx) — an O(cells + s_cells * K/cells)
    per-round draw instead of the flat O(K) top-k-of-uniforms.  From the
    candidate vector on, the round is the ordinary streamed fused round
    (make_sampled_round_fn), so the per-client bandit learns exactly as in
    the flat path; afterwards the observed T_inc delta folds back into the
    cell aggregates (update_cell_stats).  Cell assignment is the scenario's
    round-robin ``cell_ids`` binning — the same binning the correlated-
    congestion dynamics use, so the cell bandit exploits exactly the
    correlation the scenario induces.
    """
    k = env.mean_theta.shape[0]
    n_cells = int(scen.congestion_cells)
    fused = fused and k >= bandit_jax.fused_min_k(policy)
    c = n_rounds if chunk_rounds is None else int(chunk_rounds)
    if n_rounds % c:
        raise ValueError(f"n_rounds={n_rounds} not divisible by "
                         f"chunk_rounds={c}")
    n_chunks = n_rounds // c
    failure = deadline is not None
    fault = bandit_jax.resolve_fault(scen.fault, deadline)
    state0 = bandit_jax.BanditState.create(k)
    k_cand, k_theta, k_gamma, k_pol, k_cong, k_churn = jax.random.split(
        jax.random.PRNGKey(seed), 6)
    keys = {name: _per_round_keys(root, n_rounds, n_chunks)
            for name, root in [("cand", k_cand), ("theta", k_theta),
                               ("gamma", k_gamma), ("pol", k_pol),
                               ("cong", k_cong), ("churn", k_churn)]}
    rounds = jnp.arange(1, n_rounds + 1, dtype=jnp.int32).reshape(
        n_chunks, c)
    if fused:
        sampled_fn = bandit_jax.make_sampled_round_fn(
            policy, s_round, fluctuate=fluctuate, fault=fault,
            deadline=deadline)
    else:
        select_fn = bandit_jax.make_select_fn(policy, s_round)
        decay = bandit_jax.policy_decay(policy)

    def chunk_body(carry, xs):
        kk, rr = xs

        def step(carry2, x):
            state, m_theta, m_gamma, cell_n, cell_tinc = carry2
            kc, kcong, k_t, kp, kch, r1 = x
            with jax.named_scope(bandit_jax.SCOPE_SELECT):
                cells_sel = bandit_jax.select_cells(cell_n, cell_tinc,
                                                    s_cells)
            with jax.named_scope(bandit_jax.SCOPE_CAND_DRAW):
                cand = bandit_jax.hier_cand_idx(kc, cells_sel, k, n_cells,
                                                n_req_cell)
            with jax.named_scope(bandit_jax.SCOPE_TIMES):
                mult = scenario_thr_mult(scen, env.cell_id, kcong[None],
                                         r1[None])[0]
                mu_t = m_theta * mult
            if fused:
                out = sampled_fn(state, cand, kp, k_t, mu_t, m_gamma,
                                 env.n_samples, eta, model_bits, hyper)
                state2, sel = out[0], out[1]
                obs = (out[2], out[3]) if failure else out[2]
            else:
                with jax.named_scope(bandit_jax.SCOPE_TIMES):
                    t_ud_c, t_ul_c = sample_times_candidates(
                        k_t, cand, env.n_samples, mu_t, m_gamma, eta,
                        model_bits, fluctuate=fluctuate)
                    t_ud, t_ul = bandit_jax.scatter_cand_times(
                        cand, t_ud_c, t_ul_c, k)
                out = _round(state, bandit_jax.cand_mask(cand, k), t_ud,
                             t_ul, select_fn, hyper, kp, decay=decay,
                             fault=fault, deadline=deadline)
                state2, sel = out[0], out[2]
                obs = (out[1], out[3]) if failure else out[1]
            with jax.named_scope(bandit_jax.SCOPE_FINISH):
                cell_n, cell_tinc = bandit_jax.update_cell_stats(
                    cell_n, cell_tinc, sel, state.sum_tinc, state2.sum_tinc,
                    env.cell_id, n_cells)
            state = state2
            if scen.churn_prob > 0.0:
                with jax.named_scope(bandit_jax.SCOPE_TIMES):
                    m_theta, m_gamma = churn_step(kch, m_theta, m_gamma,
                                                  scen.churn_prob)
            return (state, m_theta, m_gamma, cell_n, cell_tinc), obs

        return jax.lax.scan(
            step, carry, (kk["cand"], kk["cong"], kk["theta"], kk["pol"],
                          kk["churn"], rr))

    carry0 = (state0, env.mean_theta, env.mean_gamma,
              jnp.zeros((n_cells,), jnp.float32),
              jnp.zeros((n_cells,), jnp.float32))
    _, outs = jax.lax.scan(chunk_body, carry0, (keys, rounds))
    if failure:
        rts, flags = outs
        return rts.reshape(n_rounds), flags.reshape(n_rounds, s_round)
    return outs.reshape(n_rounds)


@functools.partial(jax.jit, static_argnames=(
    "policies", "scen", "n_rounds", "s_round", "n_req", "fluctuate",
    "chunk_rounds", "mesh", "shard", "fused", "fast_sampling", "deadline",
    "segmented", "hierarchy", "s_cells", "n_req_cell"),
    donate_argnames=("eta", "seed"))
def _run_grid(env: EnvArrays, model_bits, hypers, eta, seed,
              *, policies: tuple[str, ...], scen: Scenario, n_rounds,
              s_round, n_req, fluctuate, chunk_rounds=None, mesh=None,
              shard="grid", fused=True, fast_sampling=True, deadline=None,
              segmented=False, hierarchy="flat", s_cells=None,
              n_req_cell=None):
    """One jit call for the whole sweep: the policy axis is unrolled
    statically (each entry vmaps its own selection rule over the flattened
    [E*S] eta/seed axes); hypers: [P], eta/seed: [E*S], donated.

    ``mesh``/``shard`` (static): with ``shard="grid"`` each policy's vmap
    runs inside ``shard_map`` with the [E*S] axis split over the mesh (the
    caller pads it to a mesh-size multiple); with ``shard="clients"`` the
    vmap stays global and the [K] axis of the per-client state is pinned to
    the mesh for GSPMD partitioning — unless ``segmented`` (static) is set,
    in which case each grid point runs the explicitly-sharded segmented
    round loop (``_run_one_clients_segmented``; the [E*S] axis unrolls as a
    Python loop, shard_map composing more predictably unvmapped).
    ``hierarchy="cells"`` (static) swaps the per-grid-point body for the
    two-level cell runner (``_run_one_hier``).
    """
    client_mesh = mesh if (mesh is not None and shard == "clients") else None
    out = []
    for i, name in enumerate(policies):
        if segmented:
            f = functools.partial(
                _run_one_clients_segmented, policy=name, scen=scen,
                n_rounds=n_rounds, s_round=s_round, n_req=n_req,
                fluctuate=fluctuate, chunk_rounds=chunk_rounds,
                deadline=deadline, client_mesh=client_mesh)
            runs = [f(env, model_bits, hypers[i], eta[j], seed[j])
                    for j in range(eta.shape[0])]
            if deadline is not None:
                out.append((jnp.stack([r[0] for r in runs]),
                            jnp.stack([r[1] for r in runs])))
            else:
                out.append(jnp.stack(runs))
            continue
        if hierarchy == "cells":
            f = functools.partial(
                _run_one_hier, policy=name, scen=scen, n_rounds=n_rounds,
                s_round=s_round, n_req_cell=n_req_cell, s_cells=s_cells,
                fluctuate=fluctuate, chunk_rounds=chunk_rounds,
                fused=fused, deadline=deadline)
        else:
            f = functools.partial(
                _run_one, policy=name, scen=scen, n_rounds=n_rounds,
                s_round=s_round, n_req=n_req, fluctuate=fluctuate,
                chunk_rounds=chunk_rounds, client_mesh=client_mesh,
                fused=fused, fast_sampling=fast_sampling,
                deadline=deadline)
        g = jax.vmap(f, in_axes=(None, None, None, 0, 0))
        if mesh is not None and shard == "grid":
            g = dist_sharding.shard_vmapped(g, mesh, sharded_argnums=(3, 4))
        out.append(g(env, model_bits, hypers[i], eta, seed))
    if deadline is not None:       # ([P, E*S, R] times, [P, E*S, R, S] flags)
        return (jnp.stack([o[0] for o in out]),
                jnp.stack([o[1] for o in out]))
    return jnp.stack(out)          # [P, E*S(_padded), R]


@dataclasses.dataclass(frozen=True)
class SweepResult:
    """Round times for every (policy, eta, seed) grid point, on host."""

    policies: tuple[str, ...]
    hypers: tuple[float, ...]
    etas: tuple[float, ...]
    seeds: tuple[int, ...]
    round_times: np.ndarray     # [P, E, S, R]
    # per-slot outcome flags (core.bandit_jax.FLAG_*) when the sweep ran
    # with a round deadline; None on fault-free sweeps
    flags: np.ndarray | None = None    # [P, E, S, R, s_round] int32

    @property
    def elapsed(self) -> np.ndarray:
        """Final elapsed time per grid point, [P, E, S]."""
        return self.round_times.sum(axis=-1)

    def mean_elapsed(self) -> np.ndarray:
        """Seed-averaged elapsed time, [P, E] (paper Figs. 1-2 input)."""
        return self.elapsed.mean(axis=-1)

    def fault_counts(self) -> dict[str, np.ndarray]:
        """Per-grid-point outcome totals over all rounds/slots, [P, E, S]
        per category.  The categories partition every dispatched slot
        (dispatched = ok + crashed + churned + deadline_missed + corrupt —
        the conservation invariant the property tests assert); requires a
        failure-aware sweep (``deadline`` set)."""
        if self.flags is None:
            raise ValueError("fault_counts() requires a sweep run with a "
                             "deadline (the failure-aware layer)")
        f = self.flags
        cat = {"ok": bandit_jax.FLAG_OK, "crashed": bandit_jax.FLAG_CRASH,
               "churned": bandit_jax.FLAG_CHURN,
               "deadline_missed": bandit_jax.FLAG_DEADLINE,
               "corrupt": bandit_jax.FLAG_CORRUPT}
        out = {k: (f == v).sum(axis=(-2, -1)) for k, v in cat.items()}
        out["dispatched"] = (f >= 0).sum(axis=(-2, -1))
        return out


def resolve_sweep_mesh(devices) -> "jax.sharding.Mesh | None":
    """Resolve a ``devices`` argument (None/0/1 => single-device path, an
    int => that many devices, "all" => every device) into a 1-D sweep mesh
    or None.  Shared by sweep() and fl/engine.accuracy_sweep()."""
    if devices in (None, 0, 1):
        return None
    mesh = dist_sharding.sweep_mesh(
        None if devices == "all" else int(devices))
    return None if mesh.size == 1 else mesh


# host spans of one sweep() call (jax.profiler.TraceAnnotation), in order;
# sweep.put and sweep.fetch carry the bytes they move as ``bytes``
SWEEP_SPANS = ("sweep.env", "sweep.put", "sweep.run", "sweep.fetch")


def _nbytes(tree) -> int:
    """Summed ``nbytes`` of the arrays in ``tree`` (from shapes alone)."""
    return sum(int(x.nbytes) for x in jax.tree.leaves(tree))


def sweep(scenario: Scenario | str = "paper-baseline",
          policies=tuple(bandit_jax.POLICY_NAMES),
          etas=(1.0, 1.5, 1.9),
          seeds=8,
          n_rounds: int = 500,
          n_clients: int = 100,
          s_round: int = 5,
          frac_request: float = 0.1,
          model_bits: float = PAPER_MODEL_BITS,
          env_seed: int = 0,
          fluctuate: bool = True,
          *,
          deadline: float | None = None,
          devices=None,
          shard: str = "grid",
          chunk_rounds: int | None = None,
          fused: bool = True,
          fast_sampling: bool | None = None,
          hierarchy: str = "flat",
          s_cells: int | None = None) -> SweepResult:
    """Run the full (policy x eta x seed) grid as ONE jit call.

    ``policies`` entries are names or (name, hyper) pairs — the hyper is the
    policy's scalar knob (alpha / beta), so hyper-parameter sweeps just list
    the same policy several times.  ``seeds`` is an int (=> range) or an
    explicit sequence.

    ``deadline`` (seconds, None = off) compiles in the failure-aware round
    layer: dispatched clients that crash, churn mid-upload (the scenario's
    ``FaultModel``) or finish past the deadline are excluded from the round;
    the bandit learns a *censored* observation (the deadline as the known
    lower bound on their unobserved time), the server waits out the full
    T_max whenever anyone failed (FedCS round-deadline semantics — an
    all-failed round is a no-op that still advances the clock by T_max),
    and the result carries per-slot outcome flags
    (``SweepResult.fault_counts``).  At None the layer compiles away and
    the sweep reproduces the fault-free trajectories bitwise.  A scenario
    with active faults requires a deadline (ValueError otherwise).

    Scaling knobs (see distributed/sharding.py and docs/architecture.md):

    ``devices``
        None/0/1 => single device; an int n => shard over the first n
        devices; "all" => every device.
    ``shard``
        "grid" (default) splits the flattened eta x seed axis over the
        devices with shard_map — same results as single-device, exactly;
        "clients" pins the client axis K of the per-client state to the
        mesh instead (the large-K layout, GSPMD-partitioned).
    ``chunk_rounds``
        Pre-sample rounds in chunks of this size inside an outer scan,
        capping peak memory at O(chunk_rounds * K) per grid point; must
        divide ``n_rounds``.  Any chunk size consumes the identical
        per-round random stream, so results do not change.
    ``fused``
        Run each round through the fused one-pass round kernel/reference
        (kernels/bandit_round.py via kernels/ops.bandit_round; default) —
        bitwise-identical results, ~2-4x round throughput at large K.
        ``fused=False`` keeps the unfused select/schedule/observe pipeline
        (the baseline benchmarks/bench_round_kernel.py measures against).
    ``fast_sampling``
        Streamed candidate-sliced sampling: candidates from a
        top-k-of-uniforms prefix draw, Eq. (8) times drawn only at the [C]
        polled slice inside the fused round — O(R·C) sampling instead of
        O(R·K), the end-to-end fast path (benchmarks/bench_e2e_sweep.py).
        None (default) auto-selects it at K >= FAST_SAMPLING_MIN_K, where
        the K-sized draws dominate the sweep; ``fast_sampling=False``
        preserves the legacy full-[R, K] presample stream exactly (same
        distribution, different PRNG consumption), so historical runs
        replay bit-for-bit at any K.
    ``hierarchy``
        "flat" (default) polls candidates over all K clients.  "cells"
        switches to the two-level mobile-network selection: cells are
        scored by a naive-UCB bandit over their aggregated observed T_inc
        (core.bandit_jax.cell_scores), the top ``s_cells`` cells are
        selected, and the Resource Request polls ``ceil(n_req/s_cells)``
        candidates only inside those cells — an O(cells + s_cells*K/cells)
        per-round draw instead of O(K), the knob that beats flat round
        throughput at K >= 1e5 under cell-structured congestion
        (benchmarks/bench_sharded_sweep.py gates it).  Cells come from the
        scenario's ``congestion_cells`` round-robin binning; at one cell
        the mode is the flat path (same code, bitwise).  Not composable
        with ``shard="clients"`` or ``fast_sampling=False`` (ValueError).
    ``s_cells``
        Cells selected per round under ``hierarchy="cells"``; default
        ~sqrt(n_cells), clamped to [1, n_cells].

    The client-sharded segmented route needs the mesh to divide K.  When
    ``shard="clients"`` lands on such a mesh (and the fast fused path is
    on), the sweep runs the explicitly-sharded segmented
    round loop: bandit state lives as contiguous [K/P] blocks inside one
    ``shard_map``, selection is per-shard local-top-S + an all-gather of
    P*S entries + a final Algorithm-1/argmax reduce on the compacted
    candidates (core.bandit_jax.make_segmented_round_fn) — O(K/P) state
    and O(K/P + C) work per device, bitwise the flat selections.  Any
    other clients-mode configuration keeps the GSPMD-constraint layout.

    Tracing: run the call under ``jax.profiler.trace(dir)`` and the trace
    holds four host spans per call, in this order (``SWEEP_SPANS``):
    ``sweep.env`` (the scenario's env build), ``sweep.put`` (every
    host-to-device transfer, with argument ``bytes``), ``sweep.run`` (the
    one jit call: dispatch, and the compile on a cache miss) and
    ``sweep.fetch`` (the copy of the results to the host, which waits for
    the device, with argument ``bytes``).  ``bytes`` is the summed
    ``nbytes`` of the arrays moved.  On the client-sharded segmented route
    ``sweep.run`` also carries ``merge_bytes``: the operand bytes each
    shard feeds the merge's collectives (the [C] ``psum`` and the top-S
    ``all_gather``, core.bandit_jax.segmented_merge_bytes), summed over
    every round, grid point and policy of the call; other routes carry no
    such counter.  On the device side every op of the
    round loop carries one of four name scopes in its op_name
    (``core.bandit_jax.ROUND_SCOPES``): ``round.cand_draw`` (the Resource
    Request's candidate draw), ``round.times`` (Eq. 8 times, scenario
    multipliers, churn), ``round.select`` (scoring and Algorithm 1 /
    top-S, with the inputs the selection kernels are fed) and
    ``round.finish`` (schedule, failure layer, ``observe``).  Scopes are
    compile-time metadata and spans cost nothing while no trace runs.
    """
    scenario = get_scenario(scenario) if isinstance(scenario, str) else scenario
    if shard not in ("grid", "clients"):
        raise ValueError(f"unknown shard mode {shard!r}")
    if s_round > n_clients:
        raise ValueError(f"s_round={s_round} exceeds n_clients={n_clients}: "
                         f"cannot select more clients than exist")
    # validates the (fault, deadline) pair up front: negative deadlines and
    # fault injection without a deadline both raise here, not inside jit
    deadline = None if deadline is None else float(deadline)
    bandit_jax.resolve_fault(scenario.fault, deadline)
    pol_names, hypers = [], []
    for p in policies:
        name, hyper = p if isinstance(p, tuple) else (p, None)
        if name not in bandit_jax.SELECT_FNS:
            raise ValueError(f"unknown policy {name!r}; "
                             f"have {bandit_jax.POLICY_NAMES}")
        pol_names.append(name)
        hypers.append(float(bandit_jax.DEFAULT_HYPERS[name]
                            if hyper is None else hyper))
    seeds = tuple(range(seeds)) if isinstance(seeds, int) else tuple(seeds)
    etas = tuple(float(e) for e in etas)
    mesh = resolve_sweep_mesh(devices)
    n_req = math.ceil(n_clients * frac_request)

    if hierarchy not in ("flat", "cells"):
        raise ValueError(f"unknown hierarchy mode {hierarchy!r}")
    s_cells_arg, n_req_cell = None, None
    if hierarchy == "cells":
        if shard == "clients":
            raise ValueError(
                "hierarchy='cells' does not compose with shard='clients' "
                "yet — pick one scaling axis (cells already cut per-round "
                "work to O(cells + s_cells*K/cells))")
        if fast_sampling is False:
            raise ValueError(
                "hierarchy='cells' is built on the streamed candidate-"
                "sliced path; fast_sampling=False has no hierarchical "
                "legacy stream to preserve")
        n_cells = max(int(scenario.congestion_cells), 1)
        if n_cells <= 1:
            hierarchy = "flat"   # one cell == flat selection, same code
        else:
            if s_cells is None:
                s_cells = round(math.sqrt(n_cells))
            s_cells_arg = min(max(int(s_cells), 1), n_cells)
            # per-cell request: the flat request budget split over the
            # selected cells, capped by the largest cell's population
            n_req_cell = min(max(1, -(-n_req // s_cells_arg)),
                             -(-n_clients // n_cells))
    fast_sampling = resolve_fast_sampling(fast_sampling, n_clients)
    # client-sharded fast path: explicit shard_map segmented rounds when
    # the mesh divides K (see even_shards); otherwise the GSPMD layout
    use_segmented = (
        shard == "clients" and fast_sampling and fused
        and hierarchy == "flat"
        and dist_sharding.even_shards(n_clients, mesh) is not None)

    with jax.profiler.TraceAnnotation("sweep.env"):
        env = scenario.build_env(n_clients, np.random.default_rng(env_seed))

    # flatten the shared (E, S) axes; the policy axis stays static
    grid_e, grid_s = np.meshgrid(np.arange(len(etas)), np.arange(len(seeds)),
                                 indexing="ij")
    g_eta = np.array(etas, np.float32)[grid_e.ravel()]
    g_seed = np.array(seeds, np.int64)[grid_s.ravel()]
    n_grid = len(g_eta)
    if mesh is not None and shard == "grid":
        g_eta = dist_sharding.pad_leading(g_eta, mesh.size)
        g_seed = dist_sharding.pad_leading(g_seed, mesh.size)

    with jax.profiler.TraceAnnotation("sweep.put") as span:
        env_arrays = EnvArrays.from_scenario(scenario, env)
        if mesh is not None and shard == "clients":
            env_arrays = dist_sharding.shard_leading(env_arrays, mesh)
        args = (env_arrays, jnp.float32(model_bits),
                jnp.asarray(hypers, jnp.float32), jnp.asarray(g_eta),
                jnp.asarray(g_seed))
        span.set_metadata(bytes=_nbytes(args))

    # the segmented route's counter: operand bytes of its merge per shard
    # over the whole call (every round of every grid point and policy)
    run_meta = {"merge_bytes": n_grid * n_rounds * sum(
        bandit_jax.segmented_merge_bytes(p, n_req, s_round)
        for p in pol_names)} if use_segmented else {}
    with jax.profiler.TraceAnnotation("sweep.run", **run_meta), \
            suppress_unusable_donation_warnings():
        # eta and seed (args[3:]) are donated: nothing reads them after
        out = _run_grid(
            *args, policies=tuple(pol_names), scen=scenario,
            n_rounds=n_rounds, s_round=s_round, n_req=n_req,
            fluctuate=fluctuate, chunk_rounds=chunk_rounds, mesh=mesh,
            shard=shard, fused=fused, fast_sampling=fast_sampling,
            deadline=deadline, segmented=use_segmented,
            hierarchy=hierarchy, s_cells=s_cells_arg,
            n_req_cell=n_req_cell)

    with jax.profiler.TraceAnnotation("sweep.fetch") as span:
        span.set_metadata(bytes=_nbytes(out))
        rts, flags = out if deadline is not None else (out, None)
        rts = np.asarray(rts)[:, :n_grid].reshape(
            len(pol_names), len(etas), len(seeds), n_rounds)
        if flags is not None:
            flags = np.asarray(flags)[:, :n_grid].reshape(
                len(pol_names), len(etas), len(seeds), n_rounds, s_round)
    return SweepResult(policies=tuple(pol_names), hypers=tuple(hypers),
                       etas=etas, seeds=seeds, round_times=rts, flags=flags)

"""The streamed candidate-sliced sampling path (``fast_sampling=True``).

Four layers:

  1. Eq. (8) dedupe — the ONE numpy and ONE jax truncnorm implementation
     (sim/truncnorm.py) agree transform-for-transform when fed the SAME
     uniforms (cross-backend parity), and the legacy re-exports still
     point at them;
  2. statistical equivalence — the candidate-sliced draws have the same
     per-client marginals as the legacy full-[K] presample (two-sample KS
     test per client), and the top-k-of-uniforms candidate draw yields
     uniform n_req-subsets;
  3. stream invariants — fast fused == fast unfused bitwise, fast chunked
     == unchunked bitwise (both engines), ``sample_times_candidates`` is
     bit-identical to the draw inside the fused sampled round, and the
     sampled Pallas kernel (interpret) matches the sliced jnp reference;
  4. the legacy path (``fast_sampling=False``) is untouched: chunked /
     fused equivalences stay bitwise and the numpy-server replay parity
     (tests/test_bandit_jax.py, tests/test_fl_engine.py) keeps anchoring
     it.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bandit_jax
from repro.kernels.ref import truncnorm_times_ref
from repro.sim import engine_jax, truncnorm


# ---------------------------------------------------------------------------
# 1. one Eq. (8) implementation per backend
# ---------------------------------------------------------------------------

def test_truncnorm_cross_backend_parity():
    """Same uniforms through the numpy (Acklam) and jax (erfinv) Phi^-1:
    both approximations sit well below the fluctuation scale, so the
    samples agree to float32 resolution."""
    rng = np.random.default_rng(0)
    u = rng.uniform(size=(4, 257))
    mean = rng.uniform(1e4, 1e6, size=(4, 257))
    for eta in (0.5, 1.5, 1.9):
        want = truncnorm.truncnorm_transform_np(u, mean, eta)
        got = np.asarray(truncnorm.truncnorm_transform(
            jnp.asarray(u, jnp.float32), jnp.asarray(mean, jnp.float32),
            jnp.float32(eta)))
        np.testing.assert_allclose(got, want, rtol=2e-5)


def test_truncnorm_single_source():
    """Every historical entry point resolves to the sim/truncnorm.py
    implementations (the dedupe satellite): resources/scenarios/
    nonstationary share the numpy sampler, engine_jax wraps the jax one."""
    from repro.core import nonstationary
    from repro.sim import resources, scenarios
    assert resources.sample_truncated_normal \
        is truncnorm.sample_truncated_normal
    assert scenarios.sample_truncated_normal \
        is truncnorm.sample_truncated_normal
    # core.nonstationary imports it from resources
    import repro.core.nonstationary as ns
    assert ns.sample_truncated_normal is truncnorm.sample_truncated_normal
    del nonstationary, scenarios
    # jax wrapper: same draw as calling the shared module directly
    key = jax.random.PRNGKey(3)
    mean = jnp.linspace(10.0, 100.0, 33)
    np.testing.assert_array_equal(
        np.asarray(engine_jax.sample_truncated_normal(key, mean, 1.5)),
        np.asarray(truncnorm.sample_truncated_normal_jax(key, mean, 1.5)))


def test_truncnorm_bounds_and_spread():
    """The jax transform respects the [mu-sigma, mu+sigma] truncation and
    eta widens the spread (the Eq. 8 contract, mirroring test_sim)."""
    key = jax.random.PRNGKey(1)
    mean = jnp.full((4096,), 1000.0)
    x = np.asarray(truncnorm.sample_truncated_normal_jax(key, mean, 1.5))
    sigma = 1000.0 ** 0.75
    assert (x >= 1000.0 - sigma - 1e-3).all()
    assert (x <= 1000.0 + sigma + 1e-3).all()
    lo = np.asarray(truncnorm.sample_truncated_normal_jax(key, mean, 0.5))
    assert lo.std() < x.std()


# ---------------------------------------------------------------------------
# 2. statistical equivalence of the fast stream
# ---------------------------------------------------------------------------

def _ks_stat(a: np.ndarray, b: np.ndarray) -> float:
    """Two-sample Kolmogorov-Smirnov statistic D = sup |F_a - F_b|."""
    both = np.sort(np.concatenate([a, b]))
    fa = np.searchsorted(np.sort(a), both, side="right") / len(a)
    fb = np.searchsorted(np.sort(b), both, side="right") / len(b)
    return float(np.max(np.abs(fa - fb)))


def test_fast_draws_match_legacy_marginals():
    """Per-client KS test: with every client a candidate every round, the
    candidate-sliced draws and the legacy full-[K] presample are samples
    of the same Eq. (8)-(11) marginal.  alpha=1e-3 critical value
    c * sqrt((n+m)/(n m)) with c(1e-3)=1.95; deterministic given seeds."""
    k, rounds, eta, bits = 6, 3000, 1.5, 1.46e8
    mu_t = jnp.linspace(2e5, 9e5, k)
    mu_g = jnp.linspace(20.0, 90.0, k)
    n_s = jnp.linspace(200.0, 900.0, k)
    cand = jnp.arange(k, dtype=jnp.int32)

    kt = jax.random.split(jax.random.PRNGKey(11), rounds)
    kg = jax.random.split(jax.random.PRNGKey(12), rounds)
    legacy_ud, legacy_ul = jax.jit(engine_jax.sample_times_rounds)(
        n_s, jnp.broadcast_to(mu_t, (rounds, k)),
        jnp.broadcast_to(mu_g, (rounds, k)), eta, bits, kt, kg)

    kf = jax.random.split(jax.random.PRNGKey(13), rounds)
    fast_ud, fast_ul = jax.jit(jax.vmap(
        lambda kk: engine_jax.sample_times_candidates(
            kk, cand, n_s, mu_t, mu_g, eta, bits)))(kf)

    crit = 1.95 * np.sqrt(2.0 / rounds)
    for i in range(k):
        for name, a, b in (("t_ud", legacy_ud, fast_ud),
                           ("t_ul", legacy_ul, fast_ul)):
            d = _ks_stat(np.asarray(a)[:, i], np.asarray(b)[:, i])
            assert d < crit, f"client {i} {name}: KS D={d:.4f} >= {crit:.4f}"


def test_topk_candidate_draw_uniform():
    """The top-k-of-uniforms prefix draw yields sorted, distinct indices
    and near-uniform per-client inclusion frequency (n_req/K each)."""
    k, n_req, rounds = 40, 8, 4000
    keys = jax.random.split(jax.random.PRNGKey(7), rounds)
    cands = np.asarray(engine_jax._cand_topk_from_keys(keys, k, n_req))
    assert cands.shape == (rounds, n_req)
    assert (np.diff(cands, axis=1) > 0).all()           # sorted, distinct
    freq = np.bincount(cands.ravel(), minlength=k) / (rounds * n_req / k)
    np.testing.assert_allclose(freq, 1.0, atol=0.1)


# ---------------------------------------------------------------------------
# 3. stream invariants of the fast path
# ---------------------------------------------------------------------------

SIM_KW = dict(n_rounds=10, n_clients=32, seeds=2, etas=(1.0, 1.9),
              policies=tuple(bandit_jax.POLICY_NAMES), frac_request=0.25)


def test_fast_sweep_fused_unfused_chunked_bitwise():
    a = engine_jax.sweep(**SIM_KW, fast_sampling=True)   # fast + fused
    b = engine_jax.sweep(**SIM_KW, fast_sampling=True, fused=False)
    c = engine_jax.sweep(**SIM_KW, fast_sampling=True, chunk_rounds=5)
    np.testing.assert_array_equal(a.round_times, b.round_times)
    np.testing.assert_array_equal(a.round_times, c.round_times)


def test_fast_sweep_churn_chunked_bitwise():
    kw = dict(SIM_KW, n_rounds=8, policies=("discounted_ucb", "random"))
    a = engine_jax.sweep("client-churn", **kw, fast_sampling=True)
    b = engine_jax.sweep("client-churn", **kw, fast_sampling=True,
                         chunk_rounds=4)
    np.testing.assert_array_equal(a.round_times, b.round_times)


def test_fast_vs_legacy_same_distribution_e2e():
    """Seed-averaged elapsed times of the two streams agree within a few
    percent (same distribution, different PRNG consumption) and preserve
    the oracle < random ordering.  Deterministic given seeds."""
    kw = dict(n_rounds=60, n_clients=40, seeds=8, etas=(1.5,),
              policies=("oracle", "random", "elementwise_ucb"),
              frac_request=0.25)
    fast = engine_jax.sweep(**kw, fast_sampling=True)
    legacy = engine_jax.sweep(**kw, fast_sampling=False)
    np.testing.assert_allclose(fast.mean_elapsed(), legacy.mean_elapsed(),
                               rtol=0.1)
    p = {n: i for i, n in enumerate(fast.policies)}
    assert np.all(fast.mean_elapsed()[p["oracle"]]
                  < fast.mean_elapsed()[p["random"]])


def test_sampled_round_consumes_sample_times_candidates_stream():
    """The fused sampled round's in-round draw == the standalone
    ``sample_times_candidates`` with the same key: the round's realized
    time equals the schedule computed from the standalone draws."""
    k, n_req, s_round = 48, 12, 5
    keys = jax.random.split(jax.random.PRNGKey(9), 4)
    cand = engine_jax._cand_topk_from_keys(keys[:1], k, n_req)[0]
    mu_t = jax.random.uniform(keys[1], (k,), jnp.float32, 1e5, 1e6)
    mu_g = jax.random.uniform(keys[2], (k,), jnp.float32, 10.0, 100.0)
    n_s = jax.random.uniform(keys[3], (k,), jnp.float32, 100.0, 1000.0)
    eta, bits = jnp.float32(1.5), jnp.float32(1.46e8)
    k_pol, k_time = jax.random.split(jax.random.PRNGKey(10))

    round_fn = jax.jit(bandit_jax.make_sampled_round_fn(
        "oracle", s_round, use_kernel=False))
    state = bandit_jax.BanditState.create(k)
    state, sel, rt = round_fn(state, cand, k_pol, k_time, mu_t, mu_g, n_s,
                              eta, bits, jnp.float32(0.0))

    t_ud_c, t_ul_c = jax.jit(engine_jax.sample_times_candidates)(
        k_time, cand, n_s, mu_t, mu_g, eta, bits)
    t_ud = jnp.zeros(k).at[cand].set(t_ud_c)
    t_ul = jnp.zeros(k).at[cand].set(t_ul_c)
    want_rt, _ = jax.jit(bandit_jax.schedule_selected)(sel, t_ud, t_ul)
    assert float(rt) == float(want_rt)
    # and the observed statistics are the standalone draws, scattered back
    safe = np.asarray(jnp.where(sel >= 0, sel, 0))
    np.testing.assert_array_equal(
        np.asarray(state.last_ud)[safe], np.asarray(t_ud)[safe])


@pytest.mark.parametrize("policy, case", [
    *(pytest.param(p, None, id=p) for p in bandit_jax.POLICY_NAMES),
    *(pytest.param(p, c, id=f"{p}-{c}") for c in ("faults", "exhausted")
      for p in bandit_jax.POLICY_NAMES)])
def test_sampled_kernel_interpret_matches_ref(policy, case):
    """Pallas sampled kernel (in-VMEM Eq. 8 transform, interpret mode) vs
    the sliced jnp reference: bitwise on selections, round times and the
    full state, for all 8 policies.  Two more cases reach the inputs of
    finish_round that the kernel route's per-slot draw feeds: the failure
    layer (fault uniforms and a deadline inside the spread of these
    clients' round times, so some slots meet it and some miss it; flags
    compared too), and fewer candidates than slots, so that exhausted
    (-1) slots occur."""
    k, s_round, rounds = 70, 4, 5
    n_cand = 3 if case == "exhausted" else 20
    kw = (dict(fault=(0.2, 0.1, 0.1), deadline=800.0) if case == "faults"
          else {})
    kc, kt, kp_, ke = jax.random.split(jax.random.PRNGKey(2), 4)
    cand = engine_jax._cand_topk_from_keys(
        jax.random.split(kc, rounds), k, n_cand)
    time_keys = jax.random.split(kt, rounds)
    pol_keys = jax.random.split(kp_, rounds)
    e1, e2, e3 = jax.random.split(ke, 3)
    theta_mu = jax.random.uniform(e1, (k,), jnp.float32, 1e5, 1e6)
    gamma_mu = jax.random.uniform(e2, (k,), jnp.float32, 10.0, 100.0)
    n_samp = jax.random.uniform(e3, (k,), jnp.float32, 100.0, 1000.0)
    eta, bits = jnp.float32(1.5), jnp.float32(1.46e8)

    hyper = jnp.float32(bandit_jax.DEFAULT_HYPERS[policy])
    ref_fn = jax.jit(bandit_jax.make_sampled_round_fn(
        policy, s_round, use_kernel=False, **kw))
    ker_fn = jax.jit(bandit_jax.make_sampled_round_fn(
        policy, s_round, use_kernel=True, interpret=True, **kw))
    sr = sk = bandit_jax.BanditState.create(k)
    sels, flags = [], []
    for r in range(rounds):
        args = (cand[r], pol_keys[r], time_keys[r], theta_mu, gamma_mu,
                n_samp, eta, bits, hyper)
        sr, sel_r, rt_r, *fl_r = ref_fn(sr, *args)
        sk, sel_k, rt_k, *fl_k = ker_fn(sk, *args)
        np.testing.assert_array_equal(np.asarray(sel_r), np.asarray(sel_k))
        assert float(rt_r) == float(rt_k)
        for a, b in zip(fl_r, fl_k, strict=True):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        sels.append(np.asarray(sel_k))
        flags += [int(f) for fl in fl_k for f in np.asarray(fl)]
    for f in dataclasses.fields(sr):
        np.testing.assert_array_equal(
            np.asarray(getattr(sr, f.name)), np.asarray(getattr(sk, f.name)),
            err_msg=f"sampled kernel state.{f.name} != ref ({policy})")
    if case == "faults":
        assert {bandit_jax.FLAG_OK, bandit_jax.FLAG_DEADLINE} <= set(flags)
    if case == "exhausted":
        assert (np.stack(sels) == -1).any()


@pytest.mark.parametrize("policy", bandit_jax.POLICY_NAMES)
def test_sampled_kernel_route_spreads_times_only_when_read(policy):
    """The kernel route spreads the [C] Eq. 8 draw into zeroed f32[K]
    buffers (scatter_cand_times) only for a policy whose selection reads
    the times; every other policy draws at its S selected slots, so no
    scatter into a fresh f32[K] buffer is left in its program."""
    import re

    k, n_cand = 4096, 400
    fn = bandit_jax.make_sampled_round_fn(policy, 5, use_kernel=True,
                                          interpret=True)
    f32 = jax.ShapeDtypeStruct((k,), jnp.float32)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    scalar = jax.ShapeDtypeStruct((), jnp.float32)
    text = jax.jit(fn).lower(
        bandit_jax.BanditState.create(k),
        jax.ShapeDtypeStruct((n_cand,), jnp.int32), key, key, f32, f32, f32,
        scalar, scalar, scalar).as_text(dialect="hlo")
    opcode = dict(re.findall(r"^\s*(?:ROOT )?([\w.\-]+) = \S+ ([\w\-]+)\(",
                             text, re.M))
    fresh = [name for name, operand in re.findall(
        rf"^\s*(?:ROOT )?([\w.\-]+) = f32\[{k}\]\S* scatter\(([\w.\-]+),",
        text, re.M) if opcode.get(operand) == "broadcast"]
    reads = policy in bandit_jax.POLICY_READS_TIMES
    assert len(fresh) == (2 if reads else 0), (policy, fresh)


def test_fl_fast_chunked_and_unfused_bitwise():
    from repro.fl import engine
    from repro.models import cnn
    cfg = cnn.CnnConfig(image_size=8, channels=(8,), pool_after=(0,),
                        fc_units=(16,), batchnorm=False)
    task = engine.make_cnn_task("paper-baseline", 12, cfg=cfg, n_train=300,
                                n_test=100, eval_batch=100, max_samples=20,
                                batch_size=10)
    kw = dict(task=task, policies=("elementwise_ucb", "random"), seeds=2,
              n_rounds=4, cfg=cfg, s_round=3, frac_request=0.5, epochs=1,
              batch_size=10)
    a = engine.accuracy_sweep(**kw, fast_sampling=True)  # fast + fused
    b = engine.accuracy_sweep(**kw, fast_sampling=True, fused=False)
    c = engine.accuracy_sweep(**kw, fast_sampling=True, chunk_rounds=2)
    for other in (b, c):
        np.testing.assert_array_equal(a.selected, other.selected)
        np.testing.assert_array_equal(a.round_times, other.round_times)
        np.testing.assert_array_equal(a.accuracy, other.accuracy)


# ---------------------------------------------------------------------------
# 4. the legacy stream is preserved
# ---------------------------------------------------------------------------

def test_legacy_path_bitwise_invariants():
    """``fast_sampling=False`` keeps the historical stream: fused/unfused
    and chunked/unchunked equal bitwise, and the stream differs from the
    fast one (so flipping the default is an explicit, versioned change)."""
    kw = dict(SIM_KW, policies=("elementwise_ucb", "random"))
    a = engine_jax.sweep(**kw, fast_sampling=False)
    b = engine_jax.sweep(**kw, fast_sampling=False, fused=False)
    c = engine_jax.sweep(**kw, fast_sampling=False, chunk_rounds=5)
    fast = engine_jax.sweep(**kw, fast_sampling=True)
    np.testing.assert_array_equal(a.round_times, b.round_times)
    np.testing.assert_array_equal(a.round_times, c.round_times)
    assert not np.array_equal(a.round_times, fast.round_times)


def test_fast_sampling_auto_resolution():
    """``fast_sampling=None`` routes by K: legacy below
    FAST_SAMPLING_MIN_K (the small-K default stream stays the historical
    one, bitwise), streamed at or above it."""
    assert not engine_jax.resolve_fast_sampling(None, 100)
    assert engine_jax.resolve_fast_sampling(
        None, engine_jax.FAST_SAMPLING_MIN_K)
    assert engine_jax.resolve_fast_sampling(True, 2)
    assert not engine_jax.resolve_fast_sampling(False, 10**6)
    kw = dict(SIM_KW, policies=("elementwise_ucb",))
    np.testing.assert_array_equal(
        engine_jax.sweep(**kw).round_times,
        engine_jax.sweep(**kw, fast_sampling=False).round_times)


def test_fl_legacy_matches_host_presample_stream():
    """The legacy fl sweep (fast_sampling=False) still consumes exactly
    the ``_presample`` stream the host reference replays: one grid point
    of ``accuracy_sweep`` == ``run_host_reference`` round-for-round."""
    from repro.fl import engine
    from repro.models import cnn
    cfg = cnn.CnnConfig(image_size=8, channels=(8,), pool_after=(0,),
                        fc_units=(16,), batchnorm=False)
    task = engine.make_cnn_task("paper-baseline", 10, cfg=cfg, n_train=300,
                                n_test=100, eval_batch=100, max_samples=20,
                                batch_size=10)
    host = engine.run_host_reference(task, policy="elementwise_ucb", seed=0,
                                     n_rounds=4, cfg=cfg, s_round=3,
                                     frac_request=0.5, epochs=1,
                                     batch_size=10)
    res = engine.accuracy_sweep(task=task, policies=("elementwise_ucb",),
                                seeds=(0,), n_rounds=4, cfg=cfg, s_round=3,
                                frac_request=0.5, epochs=1, batch_size=10,
                                fast_sampling=False)
    np.testing.assert_array_equal(res.selected[0, 0], host["selected"])
    # the host reference presamples EAGERLY while the sweep regenerates the
    # same keys' draws inside jit — eager-vs-jit erfinv differs ~1e-7, so
    # times match to float noise (selections above are exact; the bitwise
    # replay anchor is run_replay, which consumes the presampled arrays)
    np.testing.assert_allclose(res.round_times[0, 0], host["round_times"],
                               rtol=1e-6)
    np.testing.assert_allclose(res.accuracy[0, 0], host["accuracy"],
                               atol=1e-3)

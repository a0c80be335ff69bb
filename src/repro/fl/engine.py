"""Learning-coupled FL engine: accuracy-vs-time curves, fully on device.

The paper's headline evaluation (Figs. 4-6) is **test accuracy versus
elapsed time** — the MAB selector only matters because faster rounds buy
more model updates per wall-clock second.  The time-only sweep engine
(sim/engine_jax.py) produces the elapsed-time axis; this module couples it
to real learning: the **entire FL protocol — bandit polling/selection,
truncated-normal resource draws and elapsed-time accounting, per-client
local SGD, and weighted FedAvg aggregation — runs as one ``lax.scan`` over
rounds**, with local training ``vmap``-ed over clients (each client's E
epochs x minibatch SGD is an inner scan over its pre-partitioned on-device
shard) and the selection mask folded into aggregation as zero weights
through the Pallas ``fedavg`` kernel, so unselected clients drop out
without any host branching.

Two cohort layouts, provably equivalent (tests/test_fl_engine.py):

  * ``cohort="all"``      — local SGD vmaps over ALL K clients every round;
    unselected clients train too but aggregate with weight 0.  No gathers
    anywhere; the accelerator-throughput layout.
  * ``cohort="selected"`` — local SGD vmaps over the S selected slots
    (client shards gathered by traced index).  K/S times less compute; the
    CPU / large-K layout.

The whole (policy x seed) accuracy sweep is ONE jit call
(``accuracy_sweep``), emitting per-round ``(elapsed_time, test_accuracy,
selected)`` traces plus ToA@x summaries (fl/metrics.py).  Correctness is
anchored by ``run_host_reference`` — the classic disconnected host loop
built from the existing ``LocalTrainer``/``aggregation.fedavg`` pieces,
driven by the same presampled random stream, which the engine must match
round-for-round (selections exact, elapsed times exact, accuracy within
float tolerance).

Scenario dynamics (sim/scenarios.py) — congestion, diurnal drift, client
churn — reuse the shared helpers in sim/engine_jax.py.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from repro.core import bandit_jax
from repro.data.partition import (dirichlet_partition, iid_partition,
                                  pad_partitions)
from repro.distributed import sharding as dist_sharding
from repro.data.synthetic import make_synthetic_cifar
from repro.fl import metrics
from repro.fl.aggregation import fedavg
from repro.fl.server import LocalTrainer
from repro.models import cnn
from repro.optim.sgd import paper_lr
from repro.sim import engine_jax
from repro.sim.scenarios import Scenario, get_scenario
from repro.utils.compat import suppress_unusable_donation_warnings
from repro.utils.trees import tree_bytes

# Paper Sect. IV-B local recipe (the lr side lives in optim/sgd.py).
PAPER_EPOCHS = 5
PAPER_BATCH = 50


# ---------------------------------------------------------------------------
# Task bundle: everything the scan needs, shipped to the device once.
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FlTask:
    """On-device FL task: global data, padded per-client shards, resources.

    ``part_idx`` is [K, cap] int32 into ``train_x`` (cap a multiple of the
    batch size; padding repeats the first index and is masked by
    ``part_count``).  The test set is pre-chunked [C, B, ...] so evaluation
    is a bounded-memory inner scan.
    """

    env: engine_jax.EnvArrays   # per-client mean resources (time side)
    params0: Any                # initial model pytree
    train_x: jnp.ndarray        # [N, H, W, 3] f32
    train_y: jnp.ndarray        # [N] int32
    test_x: jnp.ndarray         # [C, B, H, W, 3] f32
    test_y: jnp.ndarray         # [C, B] int32
    test_mask: jnp.ndarray      # [C, B] bool (False = padding)
    part_idx: jnp.ndarray       # [K, cap] int32
    part_count: jnp.ndarray     # [K] int32

    @property
    def n_clients(self) -> int:
        return int(self.part_count.shape[0])


def make_cnn_task(scenario: Scenario | str = "paper-baseline",
                  n_clients: int = 100, *,
                  cfg: cnn.CnnConfig = cnn.CnnConfig(),
                  n_train: int = 50_000, n_test: int = 10_000,
                  seed: int = 0, env_seed: int = 0,
                  partition: str = "iid", dirichlet_alpha: float = 0.5,
                  batch_size: int = PAPER_BATCH, eval_batch: int = 500,
                  max_samples: int | None = None) -> FlTask:
    """Build the paper's CIFAR task for the engine.

    Client dataset sizes are the scenario environment's D_k (the same D_k
    that drives t_UD, so the time and learning sides stay coherent);
    ``max_samples`` clips them for fast runs.  ``partition`` is "iid"
    (paper) or "dirichlet" (the paper's non-IID setting).
    """
    scen = get_scenario(scenario) if isinstance(scenario, str) else scenario
    train, test = make_synthetic_cifar(n_train=n_train, n_test=n_test,
                                       size=cfg.image_size, seed=seed)
    env = scen.build_env(n_clients, np.random.default_rng(env_seed))
    if max_samples is not None:
        env = dataclasses.replace(
            env, n_samples=np.minimum(env.n_samples, max_samples))
    rng = np.random.default_rng(seed + 1)
    if partition == "iid":
        parts = iid_partition(train, env.n_samples, rng)
    elif partition == "dirichlet":
        parts = dirichlet_partition(train, env.n_samples, dirichlet_alpha,
                                    rng, n_classes=cfg.n_classes)
    else:
        raise ValueError(f"unknown partition {partition!r}")
    idx, count = pad_partitions(parts, round_to=batch_size)

    n_chunks = math.ceil(len(test.y) / eval_batch)
    pad = n_chunks * eval_batch - len(test.y)
    tx = np.concatenate([test.x, np.zeros((pad,) + test.x.shape[1:],
                                          test.x.dtype)])
    ty = np.concatenate([test.y, np.zeros(pad, test.y.dtype)])
    tm = np.arange(n_chunks * eval_batch) < len(test.y)

    return FlTask(
        env=engine_jax.EnvArrays.from_scenario(scen, env),
        params0=cnn.init(jax.random.PRNGKey(seed), cfg),
        train_x=jnp.asarray(train.x), train_y=jnp.asarray(train.y, jnp.int32),
        test_x=jnp.asarray(tx).reshape(n_chunks, eval_batch, *test.x.shape[1:]),
        test_y=jnp.asarray(ty, jnp.int32).reshape(n_chunks, eval_batch),
        test_mask=jnp.asarray(tm).reshape(n_chunks, eval_batch),
        part_idx=jnp.asarray(idx), part_count=jnp.asarray(count),
    )


# ---------------------------------------------------------------------------
# Pure step functions (also consumed by fl/cnn_trainer.py's host path).
# ---------------------------------------------------------------------------

def make_client_update(loss_fn, *, epochs: int, batch_size: int,
                       native_perm: bool = False):
    """The paper's per-round client recipe as ONE pure function:
    E epochs of minibatch SGD over the client's padded shard.

    Each epoch draws a fresh permutation of the shard (invalid padding
    slots sort last); batches that don't fit inside the client's true
    ``count`` are masked out (the remainder is dropped, as in the host
    trainer).  The whole thing is an inner ``lax.scan`` with a static trip
    count, so it vmaps over clients with no shape polymorphism.

    ``native_perm`` draws each epoch's shuffle via
    ``jax.random.permutation`` directly instead of the uniform+``argsort``
    idiom.  The two are equally-distributed but consume *different* bits,
    and the native draw cannot push padding slots last — so it is only
    valid when every shard is full (count == cap everywhere; the engines
    auto-detect this via ``_native_perm_auto``).  The default keeps the
    argsort idiom, leaving the replay-parity stream byte-identical to the
    historical one for padded tasks.
    """
    def client_update(params, train_x, train_y, idx, count, lr, key):
        cap = idx.shape[0]
        n_b = cap // batch_size
        pos = jnp.arange(cap)

        if native_perm:
            def epoch_perm(kk):
                return idx[jax.random.permutation(kk, cap)]
        else:
            def epoch_perm(kk):
                r = jax.random.uniform(kk, (cap,)) + 2.0 * (pos >= count)
                return idx[jnp.argsort(r)]

        perms = jax.vmap(epoch_perm)(jax.random.split(key, epochs))
        batches = perms.reshape(epochs * n_b, batch_size)
        in_epoch = jnp.tile(jnp.arange(n_b), epochs)
        valid = (in_epoch + 1) * batch_size <= count

        def step(p, x):
            bidx, v = x
            batch = {"x": train_x[bidx], "y": train_y[bidx]}
            grads, _ = jax.grad(loss_fn, has_aux=True)(p, batch)
            newp = jax.tree.map(lambda pp, g: pp - lr * g, p, grads)
            return jax.tree.map(lambda a, b: jnp.where(v, a, b), newp, p), None

        p, _ = jax.lax.scan(step, params, (batches, valid))
        return p

    return client_update


@functools.lru_cache(maxsize=None)
def jitted_client_update(cfg: cnn.CnnConfig, epochs: int, batch_size: int,
                         native_perm: bool = False):
    """Cached host-side jit of the whole client recipe, keyed by the static
    config — fl/cnn_trainer.py's production path, and repeated host runs
    (tests, benchmarks) reuse the compilation instead of re-tracing fresh
    closures."""
    return jax.jit(make_client_update(
        functools.partial(cnn.loss_fn, cfg=cfg),
        epochs=epochs, batch_size=batch_size, native_perm=native_perm))


def _native_perm_auto(task: FlTask) -> bool:
    """True when every client's shard is exactly full (count == cap), i.e.
    the padding penalty in the argsort shuffle is a provable no-op and the
    native ``jax.random.permutation`` draw is a valid (faster, different-
    bits) replacement.  Resolved on host from the concrete task, and used
    identically by the sweep, the replay scan and the host reference, so
    every replay-parity pair stays in lockstep."""
    return bool(np.asarray(task.part_count == task.part_idx.shape[1]).all())


def make_evaluator(apply_fn):
    """Test accuracy over the pre-chunked test set as a bounded-memory scan."""
    def evaluate(params, test_x, test_y, test_mask):
        def chunk(c, x):
            cx, cy, cm = x
            pred = jnp.argmax(apply_fn(params, cx), -1)
            return c + jnp.sum((pred == cy) & cm), None
        correct, _ = jax.lax.scan(chunk, jnp.int32(0),
                                  (test_x, test_y, test_mask))
        return correct.astype(jnp.float32) / jnp.maximum(test_mask.sum(), 1)
    return evaluate


@functools.lru_cache(maxsize=None)
def _jitted_evaluator(cfg: cnn.CnnConfig):
    """Cached host-side jit of the evaluator (see jitted_client_update)."""
    return jax.jit(make_evaluator(functools.partial(cnn.apply, cfg=cfg)))


@functools.lru_cache(maxsize=None)
def _jitted_sgd_step(cfg: cnn.CnnConfig):
    """One jitted minibatch SGD step (batch gathered on device) — the
    per-batch dispatch granularity of the classic host loop."""
    loss_fn = functools.partial(cnn.loss_fn, cfg=cfg)

    @jax.jit
    def sgd_step(params, train_x, train_y, bidx, lr):
        batch = {"x": train_x[bidx], "y": train_y[bidx]}
        grads, _ = jax.grad(loss_fn, has_aux=True)(params, batch)
        return jax.tree.map(lambda p, g: p - lr * g, params, grads)

    return sgd_step


@functools.lru_cache(maxsize=None)
def _jitted_select_fn(policy: str, s_round: int):
    return jax.jit(bandit_jax.make_select_fn(policy, s_round))


@functools.lru_cache(maxsize=None)
def _jitted_schedule():
    return jax.jit(engine_jax._schedule)


@functools.lru_cache(maxsize=None)
def _jitted_observe():
    return jax.jit(bandit_jax.observe)


def _masked_fedavg(trained, weights: jnp.ndarray, use_kernel: bool,
                   guard: bool = False):
    """Weighted FedAvg of stacked [C, ...] client trees.

    The selection mask arrives as zero weights, so unselected clients drop
    out of the average with no branching; with ``use_kernel`` the flattened
    combine is one Pallas ``fedavg`` pass (kernels/fedavg.py), otherwise a
    jnp accumulation computing the identical contraction.

    ``guard`` (the failure-aware layer) rejects rows whose parameters are
    non-finite or norm-exploding (``aggregation.GUARD_MAX_NORM``): their
    weight is zeroed AND their values are replaced by zeros before the
    combine — a NaN times a zero weight is still NaN, so masking the
    weight alone would not stop propagation into the global model.
    Returns ``(avg, w_guarded, n_rejected)`` with the guard on (the caller
    needs the surviving weight mass to decide whether any update landed),
    plain ``avg`` otherwise — the fault-free path compiles exactly as
    before.
    """
    from repro.fl.aggregation import GUARD_MAX_NORM

    flat = jax.vmap(lambda t: ravel_pytree(t)[0])(trained)     # [C, N]
    n_rejected = None
    if guard:
        finite = jnp.isfinite(flat).all(axis=1)
        # NaN norms compare False, but the explicit finite mask keeps the
        # intent readable (and catches +-inf that squares to inf)
        norm = jnp.sqrt(jnp.sum(jnp.square(flat), axis=1))
        row_ok = finite & (norm <= GUARD_MAX_NORM)
        n_rejected = ((weights > 0.0) & ~row_ok).sum().astype(jnp.int32)
        weights = jnp.where(row_ok, weights, 0.0)
        flat = jnp.where(row_ok[:, None], flat, 0.0)
    w = (weights / jnp.maximum(weights.sum(), 1e-9)).astype(flat.dtype)
    if use_kernel:
        from repro.kernels.ops import fedavg_combine
        avg = fedavg_combine(flat, w)
    else:
        # left-to-right accumulation: the same association as the host
        # path's tree_weighted_sum, so zero-weight rows add exact zeros
        # and a replayed round aggregates bit-identically
        avg = flat[0] * w[0]
        for i in range(1, flat.shape[0]):
            avg = avg + flat[i] * w[i]
    unravel = ravel_pytree(jax.tree.map(lambda l: l[0], trained))[1]
    if guard:
        return unravel(avg), weights, n_rejected
    return unravel(avg)


def _train_round(params, sel, task: FlTask, lr, perm_key, *, client_update,
                 cohort: str, use_kernel: bool, flags=None):
    """One round of local training + masked aggregation.

    Per-client RNG is ``fold_in(perm_key, client_id)`` in both cohort
    layouts, which is what makes them bit-compatible: a client trains the
    same trajectory whether it ran inside the all-K vmap or a selected
    slot.

    ``flags`` ([S] FLAG_* outcomes, failure-aware rounds only) splits the
    dispatched cohort: crash/churn/deadline slots never arrive (weight 0 —
    they trained for nothing), FLAG_CORRUPT slots arrive on time but emit
    garbage — their delta is poisoned to NaN here and must be caught by
    the aggregation guard, never by this routing, so the guard is
    exercised end-to-end.  An all-failed round keeps the previous global
    model (graceful degradation; the clock still advanced by T_max
    upstream).  Returns ``(params, n_rejected)`` with flags, else params.
    """
    failure = flags is not None
    valid = sel >= 0
    # arrived = the update reached the server in time (corrupt included —
    # its payload is garbage but its arrival is real; the guard rejects it)
    arrived = (valid & ((flags == bandit_jax.FLAG_OK)
                        | (flags == bandit_jax.FLAG_CORRUPT))
               if failure else valid)
    safe = jnp.where(valid, sel, 0)
    cnt = task.part_count.astype(jnp.float32)
    vm = jax.vmap(client_update, in_axes=(None, None, None, 0, 0, None, 0))
    if cohort == "all":
        k = task.part_count.shape[0]
        keys = jax.vmap(lambda i: jax.random.fold_in(perm_key, i))(
            jnp.arange(k))
        trained = vm(params, task.train_x, task.train_y, task.part_idx,
                     task.part_count, lr, keys)
        w = jnp.zeros(k, jnp.float32).at[safe].add(
            jnp.where(arrived, cnt[safe], 0.0))
        if failure:
            bad = jnp.zeros(k, bool).at[safe].set(
                valid & (flags == bandit_jax.FLAG_CORRUPT), mode="drop")
    elif cohort == "selected":
        keys = jax.vmap(lambda i: jax.random.fold_in(perm_key, i))(safe)
        trained = vm(params, task.train_x, task.train_y, task.part_idx[safe],
                     task.part_count[safe], lr, keys)
        w = jnp.where(arrived, cnt[safe], 0.0)
        if failure:
            bad = valid & (flags == bandit_jax.FLAG_CORRUPT)
    else:
        raise ValueError(f"unknown cohort {cohort!r}")
    if not failure:
        new_params = _masked_fedavg(trained, w, use_kernel)
        # all-padding selection (fewer candidates than S): keep the old model
        keep = valid.any()
        return jax.tree.map(lambda a, b: jnp.where(keep, a, b), new_params,
                            params)
    # corrupted emission: the client's bits arrived mangled — poison the
    # whole row and let the aggregation guard prove it never propagates
    poison = lambda t: jnp.where(       # noqa: E731 — local row mask
        bad.reshape(bad.shape + (1,) * (t.ndim - 1)), jnp.nan, t)
    trained = jax.tree.map(poison, trained)
    new_params, w_ok, n_rejected = _masked_fedavg(trained, w, use_kernel,
                                                  guard=True)
    # graceful degradation: no surviving update (all failed/corrupt/padding)
    # => this round is a no-op on the model
    keep = w_ok.sum() > 0.0
    params = jax.tree.map(lambda a, b: jnp.where(keep, a, b), new_params,
                          params)
    return params, n_rejected


# ---------------------------------------------------------------------------
# The per-(policy, seed) run: one lax.scan over rounds.
# ---------------------------------------------------------------------------

def _presample(env: engine_jax.EnvArrays, scen: Scenario, seed, *,
               n_rounds: int, n_req: int, eta, model_bits, fluctuate: bool):
    """Everything random that is independent of the learning/bandit state,
    drawn once for a *stateless* resource process (churn samples in-scan
    and is engine-only; ``run_host_reference`` rejects it upstream).  The
    host loop consumes these arrays, making host and engine runs
    common-random-number twins.

    This is the LEGACY (``fast_sampling=False``) stream — full-[R, K]
    candidate masks and time draws; replay parity lives here.  The
    streamed candidate-sliced default never materializes these arrays
    (see ``_scan_rounds_chunked``).

    All draws derive from per-round keys (one split per round off each
    root, same split order as ``_scan_rounds_chunked``), so the chunked
    scan regenerates the *identical* stream from the keys alone.
    """
    assert scen.churn_prob == 0.0, "churn presampling lives in the scan"
    k = env.mean_theta.shape[0]
    k_cand, k_theta, k_gamma, k_pol, k_perm, k_cong, _k_churn = \
        jax.random.split(jax.random.PRNGKey(seed), 7)
    rounds = jnp.arange(1, n_rounds + 1, dtype=jnp.int32)
    thr_mult = engine_jax.scenario_thr_mult(
        scen, env.cell_id, jax.random.split(k_cong, n_rounds), rounds)
    t_ud, t_ul = engine_jax.sample_times_rounds(
        env.n_samples, env.mean_theta[None, :] * thr_mult,
        jnp.broadcast_to(env.mean_gamma, (n_rounds, k)),
        eta, model_bits, jax.random.split(k_theta, n_rounds),
        jax.random.split(k_gamma, n_rounds), fluctuate=fluctuate)
    return {
        "cand_masks": engine_jax._cand_masks(k_cand, n_rounds, k, n_req),
        "pol_keys": jax.random.split(k_pol, n_rounds),
        "perm_keys": jax.random.split(k_perm, n_rounds),
        "t_ud": t_ud, "t_ul": t_ul,
    }


def _round_lrs(n_rounds: int) -> jnp.ndarray:
    """[R] f32 paper lr schedule, computed in float64 on host at trace time
    so the engine and the host reference use bit-identical values."""
    return jnp.asarray(np.float32(
        paper_lr(np.arange(n_rounds, dtype=np.float64))))


def _make_protocol_round(task: FlTask, hyper, *, policy: str, s_round: int,
                         epochs: int, batch_size: int, cohort: str,
                         use_kernel: bool, cfg: cnn.CnnConfig,
                         fused: bool = False, native_perm: bool = False,
                         fault=None, deadline: float | None = None):
    """The ONE learning-coupled round — select, schedule, observe, train,
    evaluate — shared by the single-shot and chunked scans.

    Returns ``protocol_round(params, bstate, cand, t_ud, t_ul, k_pol,
    k_perm, lr) -> (params, bstate, round_time, accuracy, sel)``.  ``cand``
    is a [K] bool candidate mask, or — with ``fused`` — the [C] sorted
    candidate indices consumed by the one-pass fused round
    (kernels/ops.bandit_round); both encodings select bitwise-identically.

    ``deadline`` (static) compiles in the failure-aware layer: the bandit
    observes censored times, training weights only the arrived slots
    (corrupt deltas are poisoned and rejected by the aggregation guard in
    ``_masked_fedavg``), and the round returns a sixth per-slot ``flags``
    output (bandit_jax.FLAG_*)."""
    failure = deadline is not None
    client_update = make_client_update(
        functools.partial(cnn.loss_fn, cfg=cfg),
        epochs=epochs, batch_size=batch_size, native_perm=native_perm)
    evaluate = make_evaluator(functools.partial(cnn.apply, cfg=cfg))
    if fused:
        round_fn = bandit_jax.make_round_fn(policy, s_round, fault=fault,
                                            deadline=deadline)
    else:
        select_fn = bandit_jax.make_select_fn(policy, s_round)
        decay = bandit_jax.policy_decay(policy)

    def protocol_round(params, bstate, cand, t_ud, t_ul, k_pol, k_perm, lr):
        flags = None
        if fused:
            out = round_fn(bstate, cand, k_pol, t_ud, t_ul, hyper)
            if failure:
                bstate, sel, round_time, flags = out
            else:
                bstate, sel, round_time = out
        elif failure:
            bstate, round_time, sel, flags = engine_jax._round(
                bstate, cand, t_ud, t_ul, select_fn, hyper, k_pol,
                decay=decay, fault=fault, deadline=deadline)
        else:
            sel = select_fn(bstate, cand, k_pol, t_ud, t_ul, hyper)
            round_time, incs = engine_jax._schedule(sel, t_ud, t_ul)
            safe = jnp.where(sel >= 0, sel, 0)
            bstate = bandit_jax.observe(bstate, sel, t_ud[safe], t_ul[safe],
                                        incs, decay=decay)
        if failure:
            params, _n_rej = _train_round(
                params, sel, task, lr, k_perm, client_update=client_update,
                cohort=cohort, use_kernel=use_kernel, flags=flags)
            acc = evaluate(params, task.test_x, task.test_y, task.test_mask)
            return params, bstate, round_time, acc, sel, flags
        params = _train_round(params, sel, task, lr, k_perm,
                              client_update=client_update, cohort=cohort,
                              use_kernel=use_kernel)
        acc = evaluate(params, task.test_x, task.test_y, task.test_mask)
        return params, bstate, round_time, acc, sel

    return protocol_round


def _make_sampled_protocol_round(task: FlTask, hyper, *, policy: str,
                                 s_round: int, epochs: int, batch_size: int,
                                 cohort: str, use_kernel: bool,
                                 cfg: cnn.CnnConfig, fluctuate: bool,
                                 eta, model_bits, fused: bool = True,
                                 native_perm: bool = False,
                                 fault=None, deadline: float | None = None):
    """The streamed-sampling twin of ``_make_protocol_round``: the round
    draws its own Eq. (8) times at the [C] candidate slice instead of
    consuming presampled [K] arrays.

    Returns ``protocol_round(params, bstate, cand, mu_theta, mu_gamma,
    k_time, k_pol, k_perm, lr) -> (params, bstate, round_time, accuracy,
    sel)``; ``cand``: [C] sorted candidate indices, ``mu_theta``/
    ``mu_gamma``: the round's effective per-client means.  ``fused``
    routes through ``make_sampled_round_fn`` (in-kernel sampling on TPU);
    the unfused twin samples the same [C] slice with the same key and
    scatters it into zero-[K] buffers for the mask pipeline — bitwise the
    same selections, times and state.

    ``deadline``/``fault``: see ``_make_protocol_round`` — a sixth
    per-slot ``flags`` output when the failure layer is compiled in.
    """
    failure = deadline is not None
    client_update = make_client_update(
        functools.partial(cnn.loss_fn, cfg=cfg),
        epochs=epochs, batch_size=batch_size, native_perm=native_perm)
    evaluate = make_evaluator(functools.partial(cnn.apply, cfg=cfg))
    k = task.part_count.shape[0]
    if fused:
        round_fn = bandit_jax.make_sampled_round_fn(
            policy, s_round, fluctuate=fluctuate, fault=fault,
            deadline=deadline)
    else:
        select_fn = bandit_jax.make_select_fn(policy, s_round)
        decay = bandit_jax.policy_decay(policy)

    def protocol_round(params, bstate, cand, mu_theta, mu_gamma, k_time,
                       k_pol, k_perm, lr):
        flags = None
        if fused:
            out = round_fn(
                bstate, cand, k_pol, k_time, mu_theta, mu_gamma,
                task.env.n_samples, eta, model_bits, hyper)
            if failure:
                bstate, sel, round_time, flags = out
            else:
                bstate, sel, round_time = out
        else:
            t_ud_c, t_ul_c = engine_jax.sample_times_candidates(
                k_time, cand, task.env.n_samples, mu_theta, mu_gamma, eta,
                model_bits, fluctuate=fluctuate)
            t_ud, t_ul = bandit_jax.scatter_cand_times(cand, t_ud_c,
                                                       t_ul_c, k)
            out = engine_jax._round(
                bstate, bandit_jax.cand_mask(cand, k), t_ud, t_ul,
                select_fn, hyper, k_pol,
                decay=decay, fault=fault, deadline=deadline)
            if failure:
                bstate, round_time, sel, flags = out
            else:
                bstate, round_time, sel = out
        if failure:
            params, _n_rej = _train_round(
                params, sel, task, lr, k_perm, client_update=client_update,
                cohort=cohort, use_kernel=use_kernel, flags=flags)
            acc = evaluate(params, task.test_x, task.test_y, task.test_mask)
            return params, bstate, round_time, acc, sel, flags
        params = _train_round(params, sel, task, lr, k_perm,
                              client_update=client_update, cohort=cohort,
                              use_kernel=use_kernel)
        acc = evaluate(params, task.test_x, task.test_y, task.test_mask)
        return params, bstate, round_time, acc, sel

    return protocol_round


def _scan_rounds(task: FlTask, hyper, pre: dict, *, policy: str,
                 s_round: int, epochs: int, batch_size: int, cohort: str,
                 use_kernel: bool, cfg: cnn.CnnConfig,
                 native_perm: bool = False):
    """R learning-coupled protocol rounds as one flat ``lax.scan`` over a
    presample dict of externally supplied arrays — the ``run_replay`` path
    (exact common-random-number twin of the host loop; stateless resource
    processes only, like the host loop itself).  The sweep instead runs
    through ``_scan_rounds_chunked``, which regenerates the same stream
    from keys and also covers churn.  Returns ([R] round times, [R]
    accuracy, [R, S] selections)."""
    k = task.part_count.shape[0]
    n_rounds = pre["cand_masks"].shape[0]
    protocol_round = _make_protocol_round(
        task, hyper, policy=policy, s_round=s_round, epochs=epochs,
        batch_size=batch_size, cohort=cohort, use_kernel=use_kernel, cfg=cfg,
        native_perm=native_perm)
    state0 = bandit_jax.BanditState.create(k)
    lrs = _round_lrs(n_rounds)

    def step(carry, x):
        params, bstate = carry
        cand_mask, t_ud, t_ul, k_pol, k_perm, lr = x
        params, bstate, rt, acc, sel = protocol_round(
            params, bstate, cand_mask, t_ud, t_ul, k_pol, k_perm, lr)
        return (params, bstate), (rt, acc, sel)

    _, (rts, accs, sels) = jax.lax.scan(
        step, (task.params0, state0),
        (pre["cand_masks"], pre["t_ud"], pre["t_ul"], pre["pol_keys"],
         pre["perm_keys"], lrs))
    return rts, accs, sels


def _scan_rounds_chunked(task: FlTask, hyper, seed, *, policy: str,
                         scen: Scenario, n_rounds: int, chunk_rounds: int,
                         s_round: int, n_req: int, eta, model_bits,
                         fluctuate: bool, epochs: int, batch_size: int,
                         cohort: str, use_kernel: bool, cfg: cnn.CnnConfig,
                         client_mesh=None, fused: bool = True,
                         native_perm: bool = False,
                         fast_sampling: bool = True,
                         deadline: float | None = None):
    """The chunked twin of ``_presample`` + ``_scan_rounds``: an outer scan
    over R/c chunks regenerates each chunk's candidates/multipliers/draws
    from the same per-round keys ``_presample`` would use, so peak memory
    is O(c·K) while the consumed random stream — and therefore every
    selection, round time, and accuracy — is identical to the single-shot
    path.  ``client_mesh`` pins the [K] axes to a device mesh (large-K
    layout); ``fused`` (default) routes select/schedule/observe through
    the one-pass fused round — same candidate keys, sorted-index encoding,
    bitwise-identical selections.

    ``fast_sampling`` (default) is the streamed candidate-sliced path:
    top-k-of-uniforms candidate draws and Eq. (8) times sampled only at
    the [C] polled slice inside the round (``_make_sampled_protocol_round``)
    — a different (equally distributed) stream from the legacy presample.
    ``fast_sampling=False`` preserves the legacy stream exactly; the
    replay/host-reference twins (``_presample``/``_scan_rounds``) live on
    that path only.

    ``deadline`` (static) compiles in the failure-aware layer — the
    scenario's FaultModel draws per-round fault streams, the bandit learns
    censored observations, and a fourth [R, s_round] FLAG_* trace is
    returned (see _make_protocol_round)."""
    failure = deadline is not None
    fault = bandit_jax.resolve_fault(scen.fault, deadline)
    k = task.part_count.shape[0]
    # below FUSED_MIN_K the unfused mask pipeline wins (see engine_jax);
    # results are bitwise-identical either way
    fused = fused and k >= bandit_jax.fused_min_k(policy)
    c = int(chunk_rounds)
    if n_rounds % c:
        raise ValueError(f"n_rounds={n_rounds} not divisible by "
                         f"chunk_rounds={c}")
    n_chunks = n_rounds // c
    roots = jax.random.split(jax.random.PRNGKey(seed), 7)
    names = ("cand", "theta", "gamma", "pol", "perm", "cong", "churn")
    keys = {n: engine_jax._per_round_keys(r, n_rounds, n_chunks)
            for n, r in zip(names, roots)}
    rounds = jnp.arange(1, n_rounds + 1, dtype=jnp.int32).reshape(
        n_chunks, c)
    lrs = _round_lrs(n_rounds).reshape(n_chunks, c)
    state0 = engine_jax._client_constrain(bandit_jax.BanditState.create(k),
                                          client_mesh)

    def _shape_out(ys):
        # ys: (rts, accs, sels) or (rts, accs, sels, flags), chunk-stacked
        out = (ys[0].reshape(n_rounds), ys[1].reshape(n_rounds),
               ys[2].reshape(n_rounds, s_round))
        if failure:
            out += (ys[3].reshape(n_rounds, s_round),)
        return out

    if fast_sampling:
        protocol_round = _make_sampled_protocol_round(
            task, hyper, policy=policy, s_round=s_round, epochs=epochs,
            batch_size=batch_size, cohort=cohort, use_kernel=use_kernel,
            cfg=cfg, fluctuate=fluctuate, eta=eta, model_bits=model_bits,
            fused=fused, native_perm=native_perm, fault=fault,
            deadline=deadline)

        def fast_chunk_body(carry, xs):
            params, bstate, m_theta, m_gamma = carry
            kk, rr, lr_c = xs
            cands = engine_jax._cand_topk_from_keys(kk["cand"], k, n_req)
            thr_mult = engine_jax.scenario_thr_mult(scen, task.env.cell_id,
                                                    kk["cong"], rr)

            def step(carry2, x):
                params, bstate, m_th, m_ga = carry2
                cand, mult, k_t, k_pol, k_perm, k_c, lr = x
                mu_t = engine_jax._client_constrain(m_th * mult, client_mesh)
                outs = protocol_round(
                    params, bstate, cand, mu_t, m_ga, k_t, k_pol, k_perm,
                    lr)
                params, bstate = outs[0], outs[1]
                if scen.churn_prob > 0.0:
                    m_th, m_ga = engine_jax.churn_step(k_c, m_th, m_ga,
                                                       scen.churn_prob)
                return (params, bstate, m_th, m_ga), outs[2:]

            carry2, ys = jax.lax.scan(
                step, (params, bstate, m_theta, m_gamma),
                (cands, thr_mult, kk["theta"], kk["pol"], kk["perm"],
                 kk["churn"], lr_c))
            return carry2, ys

        carry0 = (task.params0, state0, task.env.mean_theta,
                  task.env.mean_gamma)
        _, ys = jax.lax.scan(fast_chunk_body, carry0, (keys, rounds, lrs))
        return _shape_out(ys)

    protocol_round = _make_protocol_round(
        task, hyper, policy=policy, s_round=s_round, epochs=epochs,
        batch_size=batch_size, cohort=cohort, use_kernel=use_kernel, cfg=cfg,
        fused=fused, native_perm=native_perm, fault=fault,
        deadline=deadline)

    def chunk_body(carry, xs):
        params, bstate, m_theta, m_gamma = carry
        kk, rr, lr_c = xs
        if fused:       # sorted indices, not masks (no client axis to pin)
            cands = engine_jax._cand_sorted_from_keys(kk["cand"], k, n_req)
        else:
            cands = engine_jax._client_constrain(
                engine_jax._cand_masks_from_keys(kk["cand"], k, n_req),
                client_mesh, client_dim=1)
        thr_mult = engine_jax.scenario_thr_mult(scen, task.env.cell_id,
                                                kk["cong"], rr)

        if scen.churn_prob == 0.0:
            t_ud, t_ul = engine_jax._client_constrain(
                engine_jax.sample_times_rounds(
                    task.env.n_samples, m_theta[None, :] * thr_mult,
                    jnp.broadcast_to(m_gamma, (c, k)), eta, model_bits,
                    kk["theta"], kk["gamma"], fluctuate=fluctuate),
                client_mesh, client_dim=1)

            def step(carry2, x):
                params, bstate = carry2
                cand, t_ud_r, t_ul_r, k_pol, k_perm, lr = x
                outs = protocol_round(
                    params, bstate, cand, t_ud_r, t_ul_r, k_pol,
                    k_perm, lr)
                return (outs[0], outs[1]), outs[2:]

            (params, bstate), ys = jax.lax.scan(
                step, (params, bstate),
                (cands, t_ud, t_ul, kk["pol"], kk["perm"], lr_c))
            return (params, bstate, m_theta, m_gamma), ys

        def step(carry2, x):
            params, bstate, m_th, m_ga = carry2
            cand, mult, k_t, k_g, k_pol, k_perm, k_c, lr = x
            t_ud, t_ul = engine_jax.sample_times(
                task.env.n_samples, m_th * mult, m_ga, eta, model_bits,
                k_t, k_g, fluctuate=fluctuate)
            outs = protocol_round(
                params, bstate, cand, t_ud, t_ul, k_pol, k_perm, lr)
            m_th, m_ga = engine_jax.churn_step(k_c, m_th, m_ga,
                                               scen.churn_prob)
            return (outs[0], outs[1], m_th, m_ga), outs[2:]

        carry2, ys = jax.lax.scan(
            step, (params, bstate, m_theta, m_gamma),
            (cands, thr_mult, kk["theta"], kk["gamma"], kk["pol"],
             kk["perm"], kk["churn"], lr_c))
        return carry2, ys

    carry0 = (task.params0, state0, task.env.mean_theta,
              task.env.mean_gamma)
    _, ys = jax.lax.scan(chunk_body, carry0, (keys, rounds, lrs))
    return _shape_out(ys)


def _run_fl_one(task: FlTask, model_bits, hyper, eta, seed, *, policy: str,
                scen: Scenario, n_rounds: int, s_round: int, n_req: int,
                fluctuate: bool, epochs: int, batch_size: int, cohort: str,
                use_kernel: bool, cfg: cnn.CnnConfig,
                chunk_rounds: int | None = None, client_mesh=None,
                fused: bool = True, native_perm: bool = False,
                fast_sampling: bool = True, deadline: float | None = None):
    """One (policy, seed) grid point, always through the chunked scan —
    the default is one chunk spanning the whole run.  With
    ``fast_sampling=False`` that consumes the stream ``_presample`` would
    draw bit-for-bit (per-round keys), so ``run_host_reference`` stays a
    replay twin of every chunk size; the default streams the
    candidate-sliced draws instead (see ``_scan_rounds_chunked``)."""
    return _scan_rounds_chunked(
        task, hyper, seed, policy=policy, scen=scen, n_rounds=n_rounds,
        chunk_rounds=n_rounds if chunk_rounds is None else chunk_rounds,
        s_round=s_round, n_req=n_req, eta=eta, model_bits=model_bits,
        fluctuate=fluctuate, epochs=epochs, batch_size=batch_size,
        cohort=cohort, use_kernel=use_kernel, cfg=cfg,
        client_mesh=client_mesh, fused=fused, native_perm=native_perm,
        fast_sampling=fast_sampling, deadline=deadline)


@functools.partial(jax.jit, static_argnames=(
    "policy", "s_round", "epochs", "batch_size", "cohort", "use_kernel",
    "cfg", "native_perm"))
def _replay_scan(task: FlTask, hyper, pre: dict, *, policy, s_round, epochs,
                 batch_size, cohort, use_kernel, cfg, native_perm=False):
    return _scan_rounds(task, hyper, pre, policy=policy, s_round=s_round,
                        epochs=epochs, batch_size=batch_size, cohort=cohort,
                        use_kernel=use_kernel, cfg=cfg,
                        native_perm=native_perm)


def run_replay(task: FlTask, hyper, cand_masks, t_ud, t_ul, pol_keys,
               perm_keys, *, policy: str, s_round: int,
               epochs: int = PAPER_EPOCHS, batch_size: int = PAPER_BATCH,
               cohort: str = "all", use_kernel: bool = False,
               cfg: cnn.CnnConfig = cnn.CnnConfig(),
               fast_perm: bool | None = None) -> dict:
    """Run R learning-coupled rounds from precomputed inputs (one jit call).

    cand_masks: [R, K] bool; t_ud/t_ul: [R, K]; pol_keys/perm_keys: [R]
    PRNG keys.  Feeding it the arrays that ``run_host_reference`` reports
    makes the two runs consume identical randomness bit-for-bit — the
    replay-parity anchor (selections, round times and elapsed times exact;
    accuracy exact for batchnorm-free configs, within float tolerance
    otherwise), mirroring sim/engine_jax.run_replay.  Elapsed time is
    accumulated on host exactly like the host loop accumulates it (XLA's
    in-jit cumsum is a log-depth prefix scan with different association)."""
    pre = {"cand_masks": jnp.asarray(cand_masks),
           "t_ud": jnp.asarray(t_ud, jnp.float32),
           "t_ul": jnp.asarray(t_ul, jnp.float32),
           "pol_keys": jnp.asarray(pol_keys),
           "perm_keys": jnp.asarray(perm_keys)}
    native_perm = (_native_perm_auto(task) if fast_perm is None
                   else bool(fast_perm))
    rts, accs, sels = _replay_scan(task, hyper, pre, policy=policy,
                                   s_round=s_round, epochs=epochs,
                                   batch_size=batch_size, cohort=cohort,
                                   use_kernel=use_kernel, cfg=cfg,
                                   native_perm=native_perm)
    rts = np.asarray(rts)
    return {"round_times": rts, "elapsed": np.cumsum(rts),
            "accuracy": np.asarray(accs), "selected": np.asarray(sels)}


@functools.partial(jax.jit, static_argnames=(
    "policies", "scen", "n_rounds", "s_round", "n_req", "fluctuate",
    "epochs", "batch_size", "cohort", "use_kernel", "cfg", "chunk_rounds",
    "mesh", "shard", "fused", "native_perm", "fast_sampling", "deadline"),
    donate_argnames=("seeds",))
def _run_grid(task: FlTask, model_bits, hypers, eta, seeds, *,
              policies: tuple[str, ...], scen: Scenario, n_rounds, s_round,
              n_req, fluctuate, epochs, batch_size, cohort, use_kernel, cfg,
              chunk_rounds=None, mesh=None, shard="grid", fused=True,
              native_perm=False, fast_sampling=True, deadline=None):
    """One jit call for the whole accuracy sweep: the policy axis is
    unrolled statically (each entry vmaps its own selection rule over the
    seed axis); hypers: [P], seeds: [S], donated.

    ``mesh``/``shard`` (static): ``shard="grid"`` splits the seed axis over
    the mesh with shard_map (seeds pre-padded by the caller to a mesh-size
    multiple); ``shard="clients"`` pins the client axis K of the bandit
    state, resource draws and data shards to the mesh for GSPMD
    partitioning (the caller commits the task arrays accordingly — see
    ``shard_task_for_clients``).  ``chunk_rounds`` routes every grid point
    through the chunked scan.
    """
    client_mesh = mesh if (mesh is not None and shard == "clients") else None
    rts, accs, sels, fls = [], [], [], []
    for i, name in enumerate(policies):
        f = functools.partial(
            _run_fl_one, policy=name, scen=scen, n_rounds=n_rounds,
            s_round=s_round, n_req=n_req, fluctuate=fluctuate, epochs=epochs,
            batch_size=batch_size, cohort=cohort, use_kernel=use_kernel,
            cfg=cfg, chunk_rounds=chunk_rounds, client_mesh=client_mesh,
            fused=fused, native_perm=native_perm, fast_sampling=fast_sampling,
            deadline=deadline)
        g = jax.vmap(f, in_axes=(None, None, None, None, 0))
        if mesh is not None and shard == "grid":
            g = dist_sharding.shard_vmapped(g, mesh, sharded_argnums=(4,))
        out = g(task, model_bits, hypers[i], eta, seeds)
        rts.append(out[0]), accs.append(out[1]), sels.append(out[2])
        if deadline is not None:
            fls.append(out[3])
    stacked = (jnp.stack(rts), jnp.stack(accs), jnp.stack(sels))
    if deadline is not None:
        stacked += (jnp.stack(fls),)
    return stacked


def shard_task_for_clients(task: FlTask, mesh) -> FlTask:
    """Commit a task's per-client arrays (env resources, partition index /
    count — everything [K]-leading) to ``mesh`` sharded over the client
    axis, and the global data/model replicated: the large-K input layout
    for ``accuracy_sweep(..., shard="clients")``."""
    return dataclasses.replace(
        task,
        env=dist_sharding.shard_leading(task.env, mesh),
        part_idx=dist_sharding.shard_leading(task.part_idx, mesh),
        part_count=dist_sharding.shard_leading(task.part_count, mesh),
        params0=dist_sharding.replicate(task.params0, mesh),
        train_x=dist_sharding.replicate(task.train_x, mesh),
        train_y=dist_sharding.replicate(task.train_y, mesh),
        test_x=dist_sharding.replicate(task.test_x, mesh),
        test_y=dist_sharding.replicate(task.test_y, mesh),
        test_mask=dist_sharding.replicate(task.test_mask, mesh))


# ---------------------------------------------------------------------------
# Public sweep API.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FlSweepResult:
    """Per-round traces for every (policy, seed) grid point, on host."""

    policies: tuple[str, ...]
    hypers: tuple[float, ...]
    seeds: tuple[int, ...]
    eta: float
    round_times: np.ndarray     # [P, S, R]
    accuracy: np.ndarray        # [P, S, R]
    selected: np.ndarray        # [P, S, R, s_round] (-1 padded)
    # per-slot outcome flags (core.bandit_jax.FLAG_*) when the sweep ran
    # with a round deadline; None on fault-free sweeps
    flags: np.ndarray | None = None    # [P, S, R, s_round] int32

    @property
    def elapsed(self) -> np.ndarray:
        """Cumulative elapsed time, [P, S, R]."""
        return np.cumsum(self.round_times, axis=-1)

    def toa(self, target: float) -> np.ndarray:
        """ToA@target per grid point, [P, S] (inf = never reached)."""
        return metrics.time_to_accuracy(self.elapsed, self.accuracy, target)

    def fault_counts(self) -> dict[str, np.ndarray]:
        """Per-grid-point outcome totals over all rounds/slots, [P, S] per
        category; dispatched = ok + crashed + churned + deadline_missed +
        corrupt (the conservation invariant — see
        sim/engine_jax.SweepResult.fault_counts).  Requires a
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

    def summary(self, targets: tuple[float, ...] = (0.5, 0.7, 0.8)) -> str:
        return metrics.toa_table(list(self.policies), self.elapsed,
                                 self.accuracy, targets)


def accuracy_sweep(scenario: Scenario | str = "paper-baseline",
                   policies=tuple(bandit_jax.POLICY_NAMES),
                   seeds=2,
                   n_rounds: int = 100,
                   n_clients: int = 100,
                   s_round: int = 5,
                   frac_request: float = 0.1,
                   eta: float = 1.5,
                   *,
                   task: FlTask | None = None,
                   cfg: cnn.CnnConfig = cnn.CnnConfig(),
                   epochs: int = PAPER_EPOCHS,
                   batch_size: int = PAPER_BATCH,
                   cohort: str = "all",
                   use_kernel: bool | None = None,
                   fluctuate: bool = True,
                   model_bits: float | None = None,
                   devices=None,
                   shard: str = "grid",
                   chunk_rounds: int | None = None,
                   fused: bool = True,
                   fast_sampling: bool | None = None,
                   fast_perm: bool | None = None,
                   deadline: float | None = None,
                   **task_kwargs) -> FlSweepResult:
    """Run the full (policy x seed) accuracy-vs-time grid as ONE jit call.

    ``policies`` entries are names or (name, hyper) pairs, as in
    sim/engine_jax.sweep.  ``task`` defaults to the paper's CIFAR task
    built by ``make_cnn_task`` (extra ``task_kwargs`` — n_train, n_test,
    max_samples, partition, ... — are forwarded to it).  ``model_bits``
    defaults to the actual model size, tying the simulated upload time to
    the model being trained.  ``use_kernel`` defaults to kernel aggregation
    on TPU and the identical-einsum path elsewhere (CPU interpret mode runs
    Pallas bodies op-by-op in Python).

    Scaling knobs — same semantics as sim/engine_jax.sweep: ``devices``
    (None / int / "all") picks the mesh, ``shard`` picks what the mesh
    splits ("grid" = the seed axis via shard_map, exactly single-device
    results; "clients" = the client axis K of state, draws and data shards
    via GSPMD), ``chunk_rounds`` caps peak memory at O(chunk_rounds · K)
    per grid point without changing the consumed random stream, ``fused``
    (default) runs select/schedule/observe as the one-pass fused round
    (bitwise-identical; ``False`` = the unfused baseline).
    ``fast_sampling`` streams the candidate-sliced sampling path —
    top-k-of-uniforms candidate draws, Eq. (8) times sampled only at the
    [C] polled slice inside the round; None (default) auto-selects it at
    K >= engine_jax.FAST_SAMPLING_MIN_K, where the K-sized draws dominate;
    ``fast_sampling=False`` preserves the legacy full-[R, K] presample
    stream exactly, which is the stream ``run_host_reference``/
    ``run_replay`` consume (replay parity lives there).  ``fast_perm``
    picks the client-shuffle draw: None (default) auto-selects the native
    ``jax.random.permutation`` path exactly when every shard is full
    (see ``make_client_update``); the host reference applies the same
    rule, so replay parity is preserved either way.

    ``deadline`` (seconds, None = off) compiles in the failure-aware round
    layer — identical semantics to ``sim.engine_jax.sweep``: crash/churn/
    deadline-missing clients are censored at the bandit and excluded from
    aggregation, corrupted uploads are NaN-poisoned and rejected by the
    in-jit aggregation guard (never reaching the global model), an
    all-failed round keeps the previous model while the clock advances by
    T_max, and the result carries per-slot FLAG_* traces
    (``FlSweepResult.fault_counts``).  At None the layer compiles away and
    the sweep reproduces fault-free trajectories bitwise.
    """
    scen = get_scenario(scenario) if isinstance(scenario, str) else scenario
    if shard not in ("grid", "clients"):
        raise ValueError(f"unknown shard mode {shard!r}")
    if task is None:
        task = make_cnn_task(scen, n_clients, cfg=cfg, batch_size=batch_size,
                             **task_kwargs)
    elif task_kwargs:
        raise ValueError("pass either a prebuilt task or task_kwargs")
    n_clients = task.n_clients
    if s_round > n_clients:
        raise ValueError(f"s_round={s_round} exceeds n_clients={n_clients}: "
                         f"cannot select more clients than exist")
    deadline = None if deadline is None else float(deadline)
    bandit_jax.resolve_fault(scen.fault, deadline)   # validates the combo
    pol_names, hypers = [], []
    for p in policies:
        name, hyper = p if isinstance(p, tuple) else (p, None)
        bandit_jax.make_select_fn(name, s_round)      # validates the name
        pol_names.append(name)
        hypers.append(float(bandit_jax.DEFAULT_HYPERS[name]
                            if hyper is None else hyper))
    seeds = tuple(range(seeds)) if isinstance(seeds, int) else tuple(seeds)
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if model_bits is None:
        model_bits = 8.0 * tree_bytes(task.params0)

    mesh = engine_jax.resolve_sweep_mesh(devices)
    g_seeds = np.asarray(seeds, np.int32)
    if mesh is not None and shard == "grid":
        g_seeds = dist_sharding.pad_leading(g_seeds, mesh.size)
    if mesh is not None and shard == "clients":
        task = shard_task_for_clients(task, mesh)

    native_perm = (_native_perm_auto(task) if fast_perm is None
                   else bool(fast_perm))
    fast_sampling = engine_jax.resolve_fast_sampling(fast_sampling,
                                                     n_clients)
    with suppress_unusable_donation_warnings():
        out = _run_grid(
            task, jnp.float32(model_bits), jnp.asarray(hypers, jnp.float32),
            jnp.float32(eta), jnp.asarray(g_seeds),
            policies=tuple(pol_names), scen=scen, n_rounds=n_rounds,
            s_round=s_round, n_req=math.ceil(n_clients * frac_request),
            fluctuate=fluctuate, epochs=epochs, batch_size=batch_size,
            cohort=cohort, use_kernel=bool(use_kernel), cfg=cfg,
            chunk_rounds=chunk_rounds, mesh=mesh, shard=shard, fused=fused,
            native_perm=native_perm, fast_sampling=fast_sampling,
            deadline=deadline)
    rts, accs, sels = out[:3]
    n_seeds = len(seeds)
    return FlSweepResult(
        policies=tuple(pol_names), hypers=tuple(hypers), seeds=seeds,
        eta=float(eta), round_times=np.asarray(rts)[:, :n_seeds],
        accuracy=np.asarray(accs)[:, :n_seeds],
        selected=np.asarray(sels)[:, :n_seeds],
        flags=(np.asarray(out[3])[:, :n_seeds] if deadline is not None
               else None))


# ---------------------------------------------------------------------------
# Async serving twin: FedBuff-style staleness-weighted aggregation.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=(
    "scen", "acfg", "policy", "epochs", "batch_size", "cfg", "fluctuate",
    "native_perm"))
def _async_fl_segment(task: FlTask, state, buf_delta, buf_w, params_flat,
                      keys: dict, *, scen: Scenario, acfg, policy: str,
                      eta, model_bits, hyper, epochs: int, batch_size: int,
                      cfg: cnn.CnnConfig, fluctuate: bool,
                      native_perm: bool):
    """The learning-coupled async tick scan (see ``async_accuracy_run``).

    Rides the time-only engine's tick machinery (sim/async_engine.py:
    identical poll/dispatch/clock/completion bookkeeping and key streams)
    and adds the model side: the dispatched cohort trains from the
    *current* model — that snapshot is what goes stale — its flattened
    delta parks in the buffer row of its slot, and each tick the first
    ``buffer_size`` completions apply as one FedBuff server update with
    per-update weight ``D_k * (1 + staleness)**-staleness_power``.
    """
    from repro.sim import async_engine

    unravel = ravel_pytree(task.params0)[1]
    client_update = make_client_update(
        functools.partial(cnn.loss_fn, cfg=cfg),
        epochs=epochs, batch_size=batch_size, native_perm=native_perm)
    evaluate = make_evaluator(functools.partial(cnn.apply, cfg=cfg))
    select_fn = bandit_jax.make_select_fn(policy, acfg.s_dispatch)
    decay = bandit_jax.policy_decay(policy)
    cnt = task.part_count.astype(jnp.float32)

    def tick(carry, kk):
        state, buf_delta, buf_w, params_flat = carry
        t_ud, t_ul, cand_mask, n_arr = async_engine.poll_inputs(
            scen, task.env, acfg, state, kk, eta=eta,
            model_bits=model_bits, fluctuate=fluctuate)
        sel, target, finish, rt, incs, _ = async_engine.dispatch_plan(
            state, cand_mask, kk["pol"], t_ud, t_ul, n_arr, hyper,
            select_fn, acfg)

        # the cohort trains from the model AS OF dispatch — lr follows the
        # aggregation count (the async analogue of the round counter)
        valid = sel >= 0
        safe = jnp.where(valid, sel, 0)
        params = unravel(params_flat)
        # lr follows the *virtual round* (aggregations / buffer_size): one
        # buffer flush is the async analogue of a sync round, so decay
        # paces with model updates, not wall-clock ticks
        lr = jnp.float32(paper_lr(state.n_aggregated.astype(jnp.float32)
                                  / acfg.buffer_size))
        ckeys = jax.vmap(lambda i: jax.random.fold_in(kk["perm"], i))(safe)
        trained = jax.vmap(client_update,
                           in_axes=(None, None, None, 0, 0, None, 0))(
            params, task.train_x, task.train_y, task.part_idx[safe],
            task.part_count[safe], lr, ckeys)
        deltas = (jax.vmap(lambda t: ravel_pytree(t)[0])(trained)
                  - params_flat[None, :])
        w = jnp.where(valid, cnt[safe], 0.0)

        state = async_engine.admit(state, sel, target, finish, incs,
                                   t_ud, t_ul)
        buf_delta = buf_delta.at[target].set(deltas, mode="drop")
        buf_w = buf_w.at[target].set(w, mode="drop")

        dt = async_engine.advance_clock(state, sel, rt, acfg)
        now = state.now + dt

        agg_slots, agg_mask, drop_mask, staleness = (
            async_engine.completion_plan(state, now, acfg))
        idx, ud_o, ul_o, inc_o = async_engine.gather_aggregated(
            state, agg_slots, acfg)
        bandit = bandit_jax.observe(state.bandit, idx, ud_o, ul_o, inc_o,
                                    decay=decay)

        # FedBuff server update over this tick's aggregated completions
        in_range = agg_slots < acfg.n_slots
        safe_s = jnp.where(in_range, agg_slots, 0)
        sw = (async_engine.staleness_weights(staleness[safe_s],
                                             acfg.staleness_power)
              * buf_w[safe_s] * in_range)
        wsum = sw.sum()
        upd = jnp.einsum("s,sn->n", sw, buf_delta[safe_s])
        params_flat = params_flat + jnp.where(
            wsum > 0.0, upd / jnp.maximum(wsum, 1e-9), 0.0)

        n_agg = agg_mask.sum().astype(jnp.int32)
        clear = agg_mask | drop_mask
        mean_theta, mean_gamma = state.mean_theta, state.mean_gamma
        if scen.churn_prob > 0.0:
            mean_theta, mean_gamma = engine_jax.churn_step(
                kk["churn"], mean_theta, mean_gamma, scen.churn_prob)
        state = state.replace(
            bandit=bandit,
            buf_client=jnp.where(clear, -1, state.buf_client),
            mean_theta=mean_theta, mean_gamma=mean_gamma,
            now=now, tick=state.tick + 1,
            n_aggregated=state.n_aggregated + n_agg,
            n_dropped=state.n_dropped + drop_mask.sum().astype(jnp.int32))

        acc = evaluate(unravel(params_flat), task.test_x, task.test_y,
                       task.test_mask)
        trace = {"dt": dt, "now": now, "selected": sel, "accuracy": acc,
                 "admitted": valid.sum().astype(jnp.int32),
                 "aggregated": n_agg,
                 "dropped": drop_mask.sum().astype(jnp.int32),
                 "buffered": (jnp.where(clear, -1, state.buf_client)
                              >= 0).sum().astype(jnp.int32)}
        return (state, buf_delta, buf_w, params_flat), trace

    return jax.lax.scan(tick, (state, buf_delta, buf_w, params_flat), keys)


def async_accuracy_run(scenario: Scenario | str = "paper-baseline",
                       policy: str = "elementwise_ucb",
                       *, n_ticks: int = 50, seed: int = 0,
                       acfg=None, task: FlTask | None = None,
                       n_clients: int = 100,
                       cfg: cnn.CnnConfig = cnn.CnnConfig(),
                       epochs: int = PAPER_EPOCHS,
                       batch_size: int = PAPER_BATCH,
                       eta: float = 1.5, model_bits: float | None = None,
                       hyper: float | None = None, fluctuate: bool = True,
                       fast_perm: bool | None = None,
                       **task_kwargs) -> dict:
    """Serving-mode accuracy run: the bounded-staleness async protocol
    (sim/async_engine.py) coupled to real local training.

    Where ``accuracy_sweep`` closes every round, this run keeps a
    fixed-slot buffer of in-flight model deltas: each tick dispatches a
    bandit-selected cohort that trains from the current model, and the
    first ``acfg.buffer_size`` completions apply as one FedBuff-style
    server update with staleness-discounted weights (over-stale deltas are
    dropped).  Returns per-tick ``elapsed``/``accuracy``/``selected``
    traces plus the admitted/aggregated/dropped counters and final params.
    """
    from repro.sim import async_engine

    scen = get_scenario(scenario) if isinstance(scenario, str) else scenario
    acfg = acfg or async_engine.AsyncConfig()
    if task is None:
        task = make_cnn_task(scen, n_clients, cfg=cfg,
                             batch_size=batch_size, **task_kwargs)
    elif task_kwargs:
        raise ValueError("pass either a prebuilt task or task_kwargs")
    if hyper is None:
        hyper = bandit_jax.DEFAULT_HYPERS[policy]
    if model_bits is None:
        model_bits = 8.0 * tree_bytes(task.params0)
    native_perm = (_native_perm_auto(task) if fast_perm is None
                   else bool(fast_perm))

    params_flat = ravel_pytree(task.params0)[0]
    state = async_engine.AsyncState.create(task.env, acfg)
    buf_delta = jnp.zeros((acfg.n_slots, params_flat.shape[0]), jnp.float32)
    buf_w = jnp.zeros(acfg.n_slots, jnp.float32)
    keys = async_engine.tick_keys(seed, n_ticks, 0, n_ticks, perm=True)

    (state, _, _, params_flat), tr = _async_fl_segment(
        task, state, buf_delta, buf_w, params_flat, keys, scen=scen,
        acfg=acfg, policy=policy, eta=jnp.float32(eta),
        model_bits=jnp.float32(model_bits), hyper=jnp.float32(hyper),
        epochs=epochs, batch_size=batch_size, cfg=cfg, fluctuate=fluctuate,
        native_perm=native_perm)
    tr = jax.device_get(tr)
    return {"dt": tr["dt"], "elapsed": tr["now"],
            "accuracy": tr["accuracy"], "selected": tr["selected"],
            "admitted": tr["admitted"], "aggregated": tr["aggregated"],
            "dropped": tr["dropped"], "buffered": tr["buffered"],
            "state": state,
            "params": ravel_pytree(task.params0)[1](params_flat)}


# ---------------------------------------------------------------------------
# The host-loop reference twin (replay parity + benchmark baseline).
# ---------------------------------------------------------------------------

def run_host_reference(task: FlTask, *,
                       scenario: Scenario | str = "paper-baseline",
                       policy: str = "elementwise_ucb",
                       hyper: float | None = None,
                       seed: int = 0, n_rounds: int = 20, s_round: int = 5,
                       frac_request: float = 0.1, eta: float = 1.5,
                       cfg: cnn.CnnConfig = cnn.CnnConfig(),
                       epochs: int = PAPER_EPOCHS,
                       batch_size: int = PAPER_BATCH,
                       model_bits: float | None = None,
                       fluctuate: bool = True,
                       fast_perm: bool | None = None) -> dict:
    """The disconnected host loop the engine replaces: LocalTrainer +
    aggregation.fedavg + one jitted SGD step per minibatch (the pre-engine
    CnnFlTrainer's dispatch granularity), driven by the SAME presampled
    random stream as ``_run_fl_one``.

    A host run is the engine's common-random-number twin — selections and
    elapsed times match exactly, accuracy within float tolerance
    (tests/test_fl_engine.py) — and the baseline bench_fl_engine times.
    """
    scen = get_scenario(scenario) if isinstance(scenario, str) else scenario
    if scen.churn_prob > 0.0:
        raise ValueError("the host reference only supports stateless "
                         "resource processes (churn_prob == 0)")
    k = task.n_clients
    n_req = math.ceil(k * frac_request)
    if hyper is None:
        hyper = bandit_jax.DEFAULT_HYPERS[policy]
    if model_bits is None:
        model_bits = 8.0 * tree_bytes(task.params0)

    pre = _presample(task.env, scen, seed, n_rounds=n_rounds, n_req=n_req,
                     eta=jnp.float32(eta), model_bits=jnp.float32(model_bits),
                     fluctuate=fluctuate)
    select_fn = _jitted_select_fn(policy, s_round)
    schedule = _jitted_schedule()
    observe = _jitted_observe()
    sgd_step = _jitted_sgd_step(cfg)
    evaluate = _jitted_evaluator(cfg)
    lrs = _round_lrs(n_rounds)
    cap = task.part_idx.shape[1]
    pos = jnp.arange(cap)
    native_perm = (_native_perm_auto(task) if fast_perm is None
                   else bool(fast_perm))

    def client_update_impl(params, kk, rnd):
        # per-epoch permutation + per-batch jitted step: the dispatch
        # granularity of the pre-engine CnnFlTrainer, consuming the exact
        # random stream of make_client_update (same keys, same shuffle —
        # argsort idiom or, for full shards, the native permutation draw)
        key = jax.random.fold_in(pre["perm_keys"][rnd], kk)
        idx, count = task.part_idx[kk], int(task.part_count[kk])
        p = params
        for ek in jax.random.split(key, epochs):
            if native_perm:
                perm = idx[jax.random.permutation(ek, cap)]
            else:
                r = jax.random.uniform(ek, (cap,)) + 2.0 * (pos >= count)
                perm = idx[jnp.argsort(r)]
            for b in range(cap // batch_size):
                if (b + 1) * batch_size <= count:
                    bidx = perm[b * batch_size:(b + 1) * batch_size]
                    p = sgd_step(p, task.train_x, task.train_y, bidx,
                                 lrs[rnd])
        return p, float(count)

    def aggregate_impl(global_params, results):
        return fedavg([p for p, _ in results], [w for _, w in results])

    trainer = LocalTrainer(task.params0, client_update_impl, aggregate_impl)
    bstate = bandit_jax.BanditState.create(k)
    rts, accs, sels = [], [], []
    for r in range(n_rounds):
        t_ud, t_ul = pre["t_ud"][r], pre["t_ul"][r]
        sel = select_fn(bstate, pre["cand_masks"][r], pre["pol_keys"][r],
                        t_ud, t_ul, jnp.float32(hyper))
        rt, incs = schedule(sel, t_ud, t_ul)
        safe = jnp.where(sel >= 0, sel, 0)
        bstate = observe(bstate, sel, t_ud[safe], t_ul[safe], incs,
                         jnp.float32(bandit_jax.policy_decay(policy)))
        sel_list = [int(x) for x in np.asarray(sel) if x >= 0]
        if sel_list:
            trainer.train_round(sel_list)
        else:                       # keep the lr round counter in sync
            trainer.rounds_done += 1
        accs.append(float(evaluate(trainer.params, task.test_x, task.test_y,
                                   task.test_mask)))
        rts.append(float(rt))
        sels.append(np.asarray(sel))
    rts = np.asarray(rts, np.float32)
    return {"round_times": rts, "elapsed": np.cumsum(rts),
            "accuracy": np.asarray(accs, np.float32),
            "selected": np.stack(sels), "params": trainer.params,
            # the consumed random stream, so run_replay can replay it
            "pre": pre}

"""JAX-vectorized twin of core.bandit for datacenter-scale selection.

The numpy module drives the paper-faithful simulator (K=100); this module is
the production path: state as [K] device arrays, UCB scoring via the Pallas
kernel (kernels/ucb_score.py) at large K, Algorithm-1 greedy selection as a
``lax.fori_loop`` (jit-able end-to-end, so the whole Client Selection step
runs on-device even for millions of arms).

All eight policies — the six reference policies plus the two non-stationary
extensions (discounted and sliding-window UCB, the JAX promotion of
``core.nonstationary``) — are available behind a common mask-based interface

    select_fn(state, cand_mask, key, true_ud, true_ul, hyper) -> [S] idx

(``-1``-padded when fewer than S candidates exist), registered in
``SELECT_FNS`` / ``POLICY_IDS`` so a ``lax.switch`` over the policy axis can
drive the on-device sweep engine (sim/engine_jax.py).  ``hyper`` is the one
scalar hyper-parameter a policy consumes (alpha for naive UCB, beta for the
element-wise family; the others ignore it), traced so it can be vmapped over
a hyper-parameter grid.  ``discounted_ucb`` additionally carries
gamma-decayed statistics in the state itself: the engines pass
``decay=policy_decay(name)`` to :func:`observe` each round, so the decay is
part of the carried scan state rather than a host-side loop.

Property tests (tests/test_bandit_jax.py, tests/test_nonstationary_jax.py)
assert exact agreement with the numpy reference policies.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp

BIG = 1e12

# select_naive routes scoring through the Pallas kernel at or above this K
# (below it, the fixed pallas_call overhead dominates the fused HBM pass).
KERNEL_MIN_K = 4096

DEFAULT_ALPHA = 1000.0
DEFAULT_BETA = 50.0
DEFAULT_GAMMA = 0.99    # discounted-UCB decay (core.nonstationary default)
HIST_WINDOW = 5         # Extended-FedCS moving-average window (paper: 5)

# The four phases of a round, as ``jax.named_scope`` names.  They enter
# the op_name metadata of the compiled ops (and so the device trace), and
# cost nothing at run time.  Every round path wraps its phases in them:
# the Resource Request's candidate draw, the resource times (Eq. 8,
# scenario multipliers, churn), scoring and selection with the inputs it
# is fed, and the tail (schedule, failure layer, ``observe``).
SCOPE_CAND_DRAW = "round.cand_draw"
SCOPE_TIMES = "round.times"
SCOPE_SELECT = "round.select"
SCOPE_FINISH = "round.finish"
ROUND_SCOPES = (SCOPE_CAND_DRAW, SCOPE_TIMES, SCOPE_SELECT, SCOPE_FINISH)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BanditState:
    """Mirrors core.bandit.ClientStats as [K] device arrays.

    The ``disc_*`` fields are the gamma-decayed twin of the running sums
    (core.nonstationary.DiscountedStats): every :func:`observe` call first
    multiplies them by ``decay`` and then scatter-adds this round's
    observations, so with ``decay=1.0`` (every stationary policy) they are
    plain running sums and the update is a no-op semantically.
    """

    n_sel: jnp.ndarray      # [K] int32
    sum_ud: jnp.ndarray     # [K] f32
    sum_ul: jnp.ndarray     # [K] f32
    sum_tinc: jnp.ndarray   # [K] f32
    total: jnp.ndarray      # [] int32
    last_ud: jnp.ndarray    # [K] f32  (FedCS; 0 = never selected)
    last_ul: jnp.ndarray    # [K] f32
    hist_ud: jnp.ndarray    # [K, W] f32 ring buffers (Extended FedCS)
    hist_ul: jnp.ndarray    # [K, W] f32
    hist_n: jnp.ndarray     # [K] int32  valid ring-buffer entries
    disc_n: jnp.ndarray     # [K] f32  gamma-discounted selection count
    disc_ud: jnp.ndarray    # [K] f32  gamma-discounted sum of t_UD
    disc_ul: jnp.ndarray    # [K] f32  gamma-discounted sum of t_UL
    disc_total: jnp.ndarray  # [] f32  gamma-discounted Sigma N_k
    n_fail: jnp.ndarray     # [K] int32  censored observations (failures)

    @staticmethod
    def create(k: int, window: int = HIST_WINDOW) -> "BanditState":
        """Fresh all-zeros state for ``k`` clients (ring-buffer width
        ``window``)."""
        z = lambda: jnp.zeros(k, jnp.float32)
        return BanditState(
            n_sel=jnp.zeros(k, jnp.int32),
            sum_ud=z(), sum_ul=z(), sum_tinc=z(),
            total=jnp.zeros((), jnp.int32),
            last_ud=z(), last_ul=z(),
            hist_ud=jnp.zeros((k, window), jnp.float32),
            hist_ul=jnp.zeros((k, window), jnp.float32),
            hist_n=jnp.zeros(k, jnp.int32),
            disc_n=z(), disc_ud=z(), disc_ul=z(),
            disc_total=jnp.zeros((), jnp.float32),
            n_fail=jnp.zeros(k, jnp.int32),
        )

    @staticmethod
    def from_numpy(stats) -> "BanditState":
        """Lift a core.bandit.ClientStats snapshot onto the device.

        ClientStats has no discounted fields (the numpy discounted policy
        keeps its own DiscountedStats), so the ``disc_*`` twin starts cold.
        """
        k = len(stats.n_sel)
        z = lambda: jnp.zeros(k, jnp.float32)
        return BanditState(
            n_sel=jnp.asarray(stats.n_sel, jnp.int32),
            sum_ud=jnp.asarray(stats.sum_ud, jnp.float32),
            sum_ul=jnp.asarray(stats.sum_ul, jnp.float32),
            sum_tinc=jnp.asarray(stats.sum_tinc, jnp.float32),
            total=jnp.asarray(stats.total_sel, jnp.int32),
            last_ud=jnp.asarray(stats.last_ud, jnp.float32),
            last_ul=jnp.asarray(stats.last_ul, jnp.float32),
            hist_ud=jnp.asarray(stats.hist_ud, jnp.float32),
            hist_ul=jnp.asarray(stats.hist_ul, jnp.float32),
            hist_n=jnp.asarray(stats.hist_n, jnp.int32),
            disc_n=z(), disc_ud=z(), disc_ul=z(),
            disc_total=jnp.zeros((), jnp.float32),
            n_fail=jnp.zeros(k, jnp.int32),
        )

    def replace(self, **kw) -> "BanditState":
        return dataclasses.replace(self, **kw)


def state_tree(state: BanditState) -> dict:
    """Flatten a :class:`BanditState` to a plain dict-of-arrays pytree —
    every field, including the ``disc_*`` discounted stats — so
    checkpoint.ckpt can persist it without pickling a custom treedef."""
    return {f.name: getattr(state, f.name)
            for f in dataclasses.fields(state)}


def state_from_tree(tree: dict) -> BanditState:
    """Inverse of :func:`state_tree` (accepts numpy or jnp leaves).

    Checkpoints written before the failure-aware layer lack ``n_fail``;
    restore them with a cold (all-zero) failure count rather than failing —
    every other field must be present.
    """
    tree = {k: jnp.asarray(v) for k, v in tree.items()}
    if "n_fail" not in tree:
        tree["n_fail"] = jnp.zeros(tree["n_sel"].shape[0], jnp.int32)
    return BanditState(**tree)


def ucb_bonus_arrays(n_sel: jnp.ndarray, total: jnp.ndarray) -> jnp.ndarray:
    """UCB exploration bonus sqrt(ln ΣN / 2 N_k) on raw arrays of any shape
    (full [K] state or a candidate-compacted [C] slice); BIG for
    never-selected clients (the explore-first rule)."""
    nf = jnp.maximum(n_sel.astype(jnp.float32), 1.0)
    total = jnp.maximum(total.astype(jnp.float32), 2.0)
    bonus = jnp.sqrt(jnp.log(total) / (2.0 * nf))
    return jnp.where(n_sel == 0, BIG, bonus)


def ucb_bonus(state: BanditState) -> jnp.ndarray:
    """[K] UCB exploration bonus, mirroring ClientStats.ucb_bonus."""
    return ucb_bonus_arrays(state.n_sel, state.total)


def observe(state: BanditState, idx: jnp.ndarray, t_ud: jnp.ndarray,
            t_ul: jnp.ndarray, tinc: jnp.ndarray,
            decay: float | jnp.ndarray = 1.0,
            fail: jnp.ndarray | None = None,
            count_valid: jnp.ndarray | None = None) -> BanditState:
    """Batch reward update for the selected clients (idx: [S]).

    Entries with ``idx < 0`` (the -1 padding emitted by the select fns when
    fewer than S candidates exist) are no-ops: they are routed out of bounds
    and dropped by the scatter.

    ``decay`` multiplies the ``disc_*`` statistics *before* this round's
    observations are added (core.nonstationary.DiscountedStats order):
    1.0 for stationary policies, gamma < 1 for ``discounted_ucb`` — use
    :func:`policy_decay` to resolve it per policy name.  A *static*
    decay of exactly 1.0 (every stationary policy in the sweep engines,
    where the policy is unrolled) skips the ``disc_*`` updates entirely —
    nothing reads them — so the stationary scans don't pay three extra
    [K] scatters per round; a traced decay (replay mode) always updates.

    ``fail`` ([S] bool, optional) marks *censored* observations: slots whose
    client crashed, churned mid-upload or missed the round deadline.  The
    caller has already replaced their ``t_ud``/``t_ul``/``tinc`` with the
    deadline (:func:`censor_slots`) — the deadline is a lower bound on the
    unobserved realized time, so the failed arm's statistics still move in
    the pessimistic direction instead of silently learning nothing — and
    this function additionally counts them in ``n_fail``.  With
    ``fail=None`` (every fault-free caller) the update compiles exactly as
    before.

    ``count_valid`` (optional) overrides the number of observations
    credited to the scalar ``total``/``disc_total`` counters.  The
    client-sharded round passes the replicated GLOBAL valid count here
    while each shard's ``idx`` routes only its OWN clients in-bounds, so
    every shard's scalar counters stay replicated and bitwise-equal to the
    flat path's.  Unset (every flat caller), the local valid count is used
    — the exact historical arithmetic.
    """
    k = state.n_sel.shape[0]
    w = state.hist_ud.shape[1]
    idx = idx.astype(jnp.int32)
    valid = (idx >= 0) & (idx < k)
    n_valid = (valid.sum() if count_valid is None
               else jnp.asarray(count_valid))
    safe = jnp.where(valid, idx, k)                 # k => out of bounds: drop
    slot = state.n_sel[jnp.clip(idx, 0, k - 1)] % w
    disc = {}
    if not (isinstance(decay, (int, float)) and float(decay) == 1.0):
        disc = dict(
            disc_n=(state.disc_n * decay).at[safe].add(1.0, mode="drop"),
            disc_ud=(state.disc_ud * decay).at[safe].add(t_ud, mode="drop"),
            disc_ul=(state.disc_ul * decay).at[safe].add(t_ul, mode="drop"),
            disc_total=state.disc_total * decay
            + n_valid.astype(jnp.float32),
        )
    if fail is not None:
        fdrop = jnp.where(valid & fail, idx, k)
        disc = dict(disc,
                    n_fail=state.n_fail.at[fdrop].add(1, mode="drop"))
    return state.replace(
        n_sel=state.n_sel.at[safe].add(1, mode="drop"),
        sum_ud=state.sum_ud.at[safe].add(t_ud, mode="drop"),
        sum_ul=state.sum_ul.at[safe].add(t_ul, mode="drop"),
        sum_tinc=state.sum_tinc.at[safe].add(tinc, mode="drop"),
        total=state.total + n_valid.astype(jnp.int32),
        last_ud=state.last_ud.at[safe].set(t_ud, mode="drop"),
        last_ul=state.last_ul.at[safe].set(t_ul, mode="drop"),
        hist_ud=state.hist_ud.at[safe, slot].set(t_ud, mode="drop"),
        hist_ul=state.hist_ul.at[safe, slot].set(t_ul, mode="drop"),
        hist_n=jnp.minimum(state.hist_n.at[safe].add(1, mode="drop"), w),
        **disc,
    )


def flat_index(shape: tuple[int, ...]) -> jnp.ndarray:
    """Row-major flat position of every element of a 1-D or 2-D array.

    The selection primitives below name an element by this position and
    read it back by a masked reduction instead of a gather: Mosaic (the
    Pallas TPU compiler) lowers neither a traced gather nor a scatter on a
    loaded vector, and the kernels hold their [K] state as [K/128, 128]
    tiles, so the same code serves the 1-D jnp paths and the 2-D kernels.
    """
    pos = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    if len(shape) == 2:
        pos = pos + jax.lax.broadcasted_iota(jnp.int32, shape, 0) * shape[1]
    return pos


def _first_max(score: jnp.ndarray, pos: jnp.ndarray) -> jnp.ndarray:
    """Flat position of the first maximum of ``score`` — ``jnp.argmax``'s
    answer (lowest index on ties) for NaN-free input."""
    m = jnp.max(score)
    return jnp.min(jnp.where(score == m, pos, jnp.iinfo(jnp.int32).max))


def _at(x: jnp.ndarray, hit: jnp.ndarray) -> jnp.ndarray:
    """``x`` at the one element where ``hit`` holds, as a reduction: every
    other lane is the identity of ``max``, so the value is exact (its sign
    of zero included)."""
    fill = (-jnp.inf if jnp.issubdtype(x.dtype, jnp.floating)
            else jnp.iinfo(x.dtype).min)
    return jnp.max(jnp.where(hit, x, fill))


def greedy_slots(est_ud: jnp.ndarray, est_ul: jnp.ndarray,
                 valid: jnp.ndarray, s_round: int) -> jnp.ndarray:
    """Algorithm 1 on per-arm estimates of ANY shape — the full [K] state
    (``valid`` = candidate mask; returns client indices), a candidate-
    compacted [C] slice (``valid`` = in-range mask; returns slot indices)
    or the [K/128, 128] tiles of the Pallas kernel (returns flat indices).
    Returns [s_round] picks, -1 padded.

    The ONE copy of the per-step Algorithm-1 arithmetic, shared by the
    mask-based fallback (``_greedy_tinc``), the compacted reference
    (kernels/ref.py) and the Pallas kernel body (kernels/bandit_round.py)
    — any tie-break or clamp change lands in all three at once, which the
    bitwise-parity tests require.  Reads and writes at the picked arm are
    masked reductions and selects (:func:`flat_index`), so the body
    lowers in Mosaic; the mask rides the loop as int32, the form Mosaic
    carries through a loop.

    Ties break toward the lowest index (argmax convention), matching the
    numpy reference when candidates are fed in sorted order.  As in the
    numpy greedy_select, the elapsed accumulator is clamped at 0 so the BIG
    exploration sentinel cannot poison later T_inc comparisons (in float32
    a t of -BIG would absorb every real time difference entirely).
    """
    pos = flat_index(est_ud.shape)
    slot = jax.lax.broadcasted_iota(jnp.int32, (s_round,), 0)

    def body(i, carry):
        sel, mask, t, t_d = carry
        new_t_d = jnp.maximum(t_d, est_ul)
        tinc = (new_t_d - t_d) + jnp.maximum(est_ud - (t - t_d), 0.0) + est_ul
        score = jnp.where(mask != 0, -tinc, -jnp.inf)
        x = _first_max(score, pos)
        hit = pos == x
        ok = _at(mask, hit) != 0
        sel = jnp.where(slot == i, jnp.where(ok, x, -1), sel)
        mask = jnp.where(hit, 0, mask)
        t = jnp.where(ok, jnp.maximum(t + _at(tinc, hit), 0.0), t)
        t_d = jnp.where(ok, jnp.maximum(t_d, _at(est_ul, hit)), t_d)
        return sel, mask, t, t_d

    sel0 = jnp.full((s_round,), -1, jnp.int32)
    sel, *_ = jax.lax.fori_loop(
        0, s_round, body,
        (sel0, valid.astype(jnp.int32), jnp.float32(0), jnp.float32(0)))
    return sel


def top_slots_values(score: jnp.ndarray, valid: jnp.ndarray,
                     s_round: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Sort-free top-S over a score array of any shape (1-D, or the 2-D
    tiles of a Pallas kernel): S iterations of masked argmax.  Returns
    ``(sel, vals)``, both [s_round]: the picked flat indices (-1 padded)
    and their scores (-inf padded).  Equal scores resolve to the lowest
    index first — exactly ``lax.top_k``'s stable tie order, so it selects
    bitwise-identically to ``_top_score`` (which the fallback keeps for
    its single-dispatch top_k).  Shared by the compacted reference and the
    Pallas kernel bodies; lowers in Mosaic like :func:`greedy_slots`."""
    pos = flat_index(score.shape)
    slot = jax.lax.broadcasted_iota(jnp.int32, (s_round,), 0)

    def body(i, carry):
        sel, vals, mask = carry
        s = jnp.where(mask != 0, score, -jnp.inf)
        x = _first_max(s, pos)
        hit = pos == x
        ok = _at(mask, hit) != 0
        sel = jnp.where(slot == i, jnp.where(ok, x, -1), sel)
        vals = jnp.where(slot == i, jnp.where(ok, _at(s, hit), -jnp.inf),
                         vals)
        return sel, vals, jnp.where(hit, 0, mask)

    sel, vals, _ = jax.lax.fori_loop(
        0, s_round, body,
        (jnp.full((s_round,), -1, jnp.int32),
         jnp.full((s_round,), -jnp.inf, jnp.float32),
         valid.astype(jnp.int32)))
    return sel, vals


def top_slots(score: jnp.ndarray, valid: jnp.ndarray,
              s_round: int) -> jnp.ndarray:
    """:func:`top_slots_values` without the values: [s_round] picks, -1
    padded."""
    return top_slots_values(score, valid, s_round)[0]


def _greedy_tinc(est_ud: jnp.ndarray, est_ul: jnp.ndarray,
                 cand_mask: jnp.ndarray, s_round: int) -> jnp.ndarray:
    """Mask-based Algorithm 1 over the full [K] state (the static
    fallback's entry point): :func:`greedy_slots` with client indices."""
    return greedy_slots(est_ud, est_ul, cand_mask, s_round)


def _top_score(score: jnp.ndarray, cand_mask: jnp.ndarray,
               s_round: int) -> jnp.ndarray:
    """Top-S by score over the candidate set, -1 padded (= greedy order when
    the per-client score is fixed, as in Naive MAB-CS / random)."""
    score = jnp.where(cand_mask, score, -jnp.inf)
    _, idx = jax.lax.top_k(score, s_round)
    valid = jnp.take(cand_mask, idx)
    return jnp.where(valid, idx, -1).astype(jnp.int32)


def candidate_mask(k: int, candidates: jnp.ndarray) -> jnp.ndarray:
    """[K] bool mask from a [C] candidate-index array (the bridge from the
    index-based public API to the mask-based select fns)."""
    return jnp.zeros(k, bool).at[candidates].set(True)


def cand_idx_from_mask(cand_mask: jnp.ndarray, size: int) -> jnp.ndarray:
    """[size] int32 sorted candidate indices from a [K] bool mask, padded
    with K past the last candidate — the input format of the fused round
    (kernels/ops.bandit_round).  ``size`` must bound the candidate count.

    This is the *generic* bridge (tests, replay harnesses); the engines
    never call it — they keep the candidate indices they drew in the first
    place and sort those, because an in-jit ``nonzero`` costs a full [K]
    compaction pass per round.
    """
    k = cand_mask.shape[0]
    return jnp.nonzero(cand_mask, size=size, fill_value=k)[0].astype(
        jnp.int32)


def schedule_selected(sel: jnp.ndarray, t_ud: jnp.ndarray,
                      t_ul: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (round_time, incs[S]) for selection ``sel`` ([S], -1 padded).

    round_time is the physically realized schedule (multicast distribution
    T_d = max t_UL, parallel local update, sequential upload in order) —
    bandit.true_round_time; incs is the per-client Eq. (1) accumulation the
    server records as the T_inc observation.  Shared by both engines
    (sim/engine_jax re-exports it as ``_schedule``) and by the fused round
    reference (kernels/ref.py).  ``t_ud``/``t_ul`` are full-[K] arrays;
    :func:`schedule_gathered` is the core on already-gathered per-slot
    times (the candidate-sliced fast path, which never holds [K] times).
    """
    valid = sel >= 0
    safe = jnp.where(valid, sel, 0)
    return schedule_gathered(valid, t_ud[safe], t_ul[safe])


def schedule_gathered(valid: jnp.ndarray, ud: jnp.ndarray,
                      ul: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The realized-schedule arithmetic of :func:`schedule_selected` on
    per-slot gathered times (``ud``/``ul``: [S], entries at ``~valid``
    slots are ignored).  Returns (round_time, incs[S])."""
    round_time, incs, _ = schedule_completions(valid, ud, ul)
    return round_time, incs


def schedule_completions(valid: jnp.ndarray, ud: jnp.ndarray,
                         ul: jnp.ndarray
                         ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """:func:`schedule_gathered` plus per-slot completion offsets.

    Returns ``(round_time, incs[S], finish[S])`` where ``finish[i]`` is the
    offset from round start at which slot ``i``'s sequential upload ends
    under the realized schedule (the scheduler clock after processing slot
    ``i``; invalid slots inherit the previous clock value, and the last
    valid slot's finish IS ``round_time``, bitwise).  The async serving
    engine (sim/async_engine.py) stamps each dispatched update's absolute
    completion time as ``now + finish[i]``; the sync engines read only the
    first two outputs through :func:`schedule_gathered` — one copy of the
    schedule arithmetic serves both serving modes.
    """
    ud = jnp.where(valid, ud, 0.0)
    ul = jnp.where(valid, ul, 0.0)

    t_d = jnp.max(jnp.where(valid, ul, 0.0))
    def tbody(t, x):
        ud_k, ul_k, v = x
        t2 = jnp.maximum(t, t_d + ud_k) + ul_k
        t_new = jnp.where(v, t2, t)
        return t_new, t_new
    round_time, finish = jax.lax.scan(tbody, t_d, (ud, ul, valid))

    def ibody(carry, x):
        t, td = carry
        ud_k, ul_k, v = x
        ntd = jnp.maximum(td, ul_k)
        inc = (ntd - td) + jnp.maximum(ud_k - (t - td), 0.0) + ul_k
        return ((jnp.where(v, t + inc, t), jnp.where(v, ntd, td)),
                jnp.where(v, inc, 0.0))
    _, incs = jax.lax.scan(ibody, (jnp.float32(0), jnp.float32(0)),
                           (ud, ul, valid))
    return round_time, incs, finish


# ---------------------------------------------------------------------------
# Failure-aware round layer: per-slot fault draws, deadline censoring and the
# slot outcome flags, shared verbatim by the unfused mask pipeline
# (round_via_mask), the compacted CPU reference (kernels/ref.py) and the
# Pallas kernel body (kernels/bandit_round.py) — the one definition the
# cross-path bitwise gates guard.
# ---------------------------------------------------------------------------

# per-slot outcome categories (mutually exclusive; crash wins over churn
# wins over deadline wins over corrupt, so per-round counts partition the
# dispatched set — the conservation invariant the property tests assert)
FLAG_PAD = -1        # empty selection slot (sel == -1)
FLAG_OK = 0          # completed in time, update aggregated
FLAG_CRASH = 1       # crashed before upload (never arrived)
FLAG_CHURN = 2       # left the network mid-upload (never arrived)
FLAG_DEADLINE = 3    # healthy but finished past the round deadline
FLAG_CORRUPT = 4     # arrived in time but the update payload is garbage

# fold_in tag deriving the per-round fault stream from the per-round policy
# key — a tagged child stream, so engines add fault draws without disturbing
# any existing root split (the fault_prob=0 bitwise-reduction gate), and
# chunked==unchunked holds for free (the policy key is already per-round)
FAULT_STREAM_TAG = 0xFA11


def fault_uniforms(key: jnp.ndarray, s_round: int) -> jnp.ndarray:
    """The [3, S] per-slot fault uniforms for one round (rows: crash, churn,
    corrupt) from that round's policy key.  Drawn OUTSIDE the fused kernels
    and passed in, so all three round paths consume identical draws."""
    return jax.random.uniform(jax.random.fold_in(key, FAULT_STREAM_TAG),
                              (3, s_round), jnp.float32)


def resolve_fault(fault, deadline: float | None):
    """Normalize/validate the (fault, deadline) pair of a round factory.

    ``fault`` may be a ``sim.scenarios.FaultModel`` (anything with a
    ``.probs`` triple), a plain (crash, churn, corrupt) tuple, or None.
    Returns the static probability triple, or None when fault injection is
    off.  Fault injection without a finite deadline is rejected: the server
    would wait forever for a crashed client (and the censored observation
    needs the deadline as its lower bound).
    """
    probs = tuple(float(p) for p in getattr(fault, "probs", fault or ()))
    if probs and len(probs) != 3:
        raise ValueError(
            f"fault must be a (crash, churn, corrupt) probability triple "
            f"or a FaultModel, got {fault!r}")
    if any(p < 0.0 or p > 1.0 for p in probs):
        raise ValueError(f"fault probabilities must lie in [0, 1], "
                         f"got {probs}")
    if deadline is not None and not (float(deadline) > 0.0):
        raise ValueError(f"deadline must be a positive round duration in "
                         f"seconds (or None for no deadline), got {deadline}")
    if not any(probs):
        return None
    if deadline is None:
        raise ValueError(
            "fault injection requires a finite round deadline: a crashed "
            "client never uploads, so without a deadline the realized "
            "schedule would wait on it forever — pass deadline=<T_max>")
    return probs


def censor_slots(valid, sud, sul, incs, finish, round_time, fault_u,
                 fault: tuple[float, float, float] | None, deadline: float):
    """Apply the failure layer to one round's per-slot schedule outcome.

    Inputs are slot vectors ([S]): validity, gathered (t_UD, t_UL), Eq. (1)
    increments, per-slot completion offsets (schedule_completions) and the
    realized round time; ``fault_u`` is the [3, S] uniform block from
    :func:`fault_uniforms` and ``fault`` the static (crash, churn, corrupt)
    probability triple (None = deadline only).  Returns

        (obs_ud, obs_ul, obs_inc, fail, flags, round_time)

    where failed slots' observations are censored at the deadline (the
    known lower bound on their unobserved realized time), ``fail`` marks
    the crash/churn/deadline slots (corrupt uploads DID arrive in time —
    their timing is a true observation; only their payload is rejected, at
    the aggregation guard), ``flags`` is the per-slot FLAG_* category and
    the round time becomes the full deadline whenever any dispatched
    client failed — the server waits out T_max for the missing uploads
    (FedCS round-deadline semantics; an all-failed round is a no-op that
    still advances the clock by T_max).
    """
    dl = jnp.float32(deadline)
    if fault is not None:
        crash = fault_u[0] < jnp.float32(fault[0])
        churn = fault_u[1] < jnp.float32(fault[1])
        corrupt = fault_u[2] < jnp.float32(fault[2])
    else:
        crash = churn = corrupt = jnp.zeros(valid.shape, bool)
    missed = finish > dl
    fail = valid & (crash | churn | missed)
    flags = jnp.where(
        crash, FLAG_CRASH,
        jnp.where(churn, FLAG_CHURN,
                  jnp.where(missed, FLAG_DEADLINE,
                            jnp.where(corrupt, FLAG_CORRUPT, FLAG_OK))))
    flags = jnp.where(valid, flags, FLAG_PAD).astype(jnp.int32)
    obs_ud = jnp.where(fail, dl, sud)
    obs_ul = jnp.where(fail, dl, sul)
    obs_inc = jnp.where(fail, dl, incs)
    round_time = jnp.where(jnp.any(fail), dl, round_time)
    return obs_ud, obs_ul, obs_inc, fail, flags, round_time


# ---------------------------------------------------------------------------
# The six reference policies behind the common mask-based interface.
#   select_*_mask(state, cand_mask, key, true_ud, true_ul, hyper) -> [S] idx
# ---------------------------------------------------------------------------

def _mean(sums: jnp.ndarray, n: jnp.ndarray) -> jnp.ndarray:
    return sums / jnp.maximum(n.astype(jnp.float32), 1.0)


# Per-arm statistics each policy's scoring actually reads (the fused round
# gathers only these columns for the candidate set).  ``hist_sum_*`` are the
# ring-buffer sums (reduced over the window axis before gathering).
POLICY_STATS: dict[str, tuple[str, ...]] = {
    "fedcs": ("last_ud", "last_ul"),
    "extended_fedcs": ("hist_sum_ud", "hist_sum_ul", "hist_n"),
    "naive_ucb": ("sum_tinc", "n_sel"),
    "elementwise_ucb": ("sum_ud", "sum_ul", "n_sel"),
    "random": (),
    "oracle": (),
    "discounted_ucb": ("disc_n", "disc_ud", "disc_ul"),
    "sliding_ucb": ("hist_sum_ud", "hist_sum_ul", "hist_n", "n_sel"),
}
# Policies whose scores read this round's realised times (``t_ud``/``t_ul``
# of :func:`policy_scores`).  The others score from bandit statistics
# alone, so the streamed sampler's kernel route draws Eq. 8 for them only
# at the S selected clients, after the selection
# (kernels/ops.bandit_round_sampled).
POLICY_READS_TIMES: frozenset[str] = frozenset({"oracle"})


def policy_kind(policy: str) -> str:
    """Static selection kind of ``policy`` — the first element
    :func:`policy_scores` returns, resolvable without tracing.

    ``"score"`` policies (naive_ucb, random) rank a fixed per-arm score, so
    distributed selection can rank shard-locally and reduce the per-shard
    top-S exactly; ``"greedy"`` policies run Algorithm 1, whose per-step
    T_inc estimate depends on the running schedule clock — shard-local
    pruning is NOT exact for them (see :func:`make_segmented_round_fn`).
    """
    if policy not in POLICY_STATS:
        raise ValueError(f"unknown policy {policy!r}; "
                         f"have {list(POLICY_STATS)}")
    return "score" if policy in ("naive_ucb", "random") else "greedy"


def state_obs(state: BanditState) -> dict[str, jnp.ndarray]:
    """Full-[K] observation dict for :func:`policy_scores` (jit DCE prunes
    the entries a given policy does not read)."""
    return dict(
        n_sel=state.n_sel, sum_ud=state.sum_ud, sum_ul=state.sum_ul,
        sum_tinc=state.sum_tinc, last_ud=state.last_ud,
        last_ul=state.last_ul, hist_sum_ud=state.hist_ud.sum(1),
        hist_sum_ul=state.hist_ul.sum(1), hist_n=state.hist_n,
        disc_n=state.disc_n, disc_ud=state.disc_ud, disc_ul=state.disc_ul)


def policy_scores(policy: str, obs: dict, total, disc_total, t_ud, t_ul,
                  rand, hyper):
    """The ONE definition of every policy's per-arm selection inputs.

    ``obs`` holds per-arm statistics of any shape — the full [K] state
    (``state_obs``, the mask-based select fns below) or a candidate-
    compacted [C] slice (the fused round in kernels/ref.py and
    kernels/bandit_round.py); ``t_ud``/``t_ul``/``rand`` must be sliced the
    same way by the caller.  Returns ``("greedy", est_ud, est_ul)`` for the
    Algorithm-1 policies or ``("score", score, None)`` for the fixed-score
    policies (Naive MAB-CS, random).  Arithmetic is shared verbatim between
    both call sites, so fused and fallback selections agree bitwise.
    """
    if policy == "fedcs":
        return "greedy", obs["last_ud"], obs["last_ul"]
    if policy == "extended_fedcs":
        n = jnp.maximum(obs["hist_n"], 1).astype(jnp.float32)
        return "greedy", obs["hist_sum_ud"] / n, obs["hist_sum_ul"] / n
    if policy == "naive_ucb":
        score = (-_mean(obs["sum_tinc"], obs["n_sel"]) / hyper
                 + ucb_bonus_arrays(obs["n_sel"], total))
        return "score", score, None
    if policy == "elementwise_ucb":
        bonus = ucb_bonus_arrays(obs["n_sel"], total)
        return ("greedy", _mean(obs["sum_ud"], obs["n_sel"]) / hyper - bonus,
                _mean(obs["sum_ul"], obs["n_sel"]) / hyper - bonus)
    if policy == "random":
        return "score", rand, None
    if policy == "oracle":
        return "greedy", t_ud, t_ul
    if policy == "discounted_ucb":
        n = obs["disc_n"]
        cold = n < 1e-2
        mean_ud = jnp.where(cold, 0.0, obs["disc_ud"] / jnp.maximum(n, 1e-3))
        mean_ul = jnp.where(cold, 0.0, obs["disc_ul"] / jnp.maximum(n, 1e-3))
        eff_total = jnp.maximum(disc_total, 2.0)
        b = jnp.sqrt(jnp.log(eff_total) / (2.0 * jnp.maximum(n, 1e-3)))
        bonus = jnp.where(cold, BIG, jnp.minimum(b, BIG))
        return ("greedy", mean_ud / hyper - bonus, mean_ul / hyper - bonus)
    if policy == "sliding_ucb":
        n = jnp.maximum(obs["hist_n"], 1).astype(jnp.float32)
        bonus = ucb_bonus_arrays(obs["n_sel"], total)
        return ("greedy", (obs["hist_sum_ud"] / n) / hyper - bonus,
                (obs["hist_sum_ul"] / n) / hyper - bonus)
    raise ValueError(f"unknown policy {policy!r}; have {list(POLICY_STATS)}")


def _select_with_rand(policy, state, cand_mask, true_ud, true_ul, rand,
                      hyper, s_round: int) -> jnp.ndarray:
    """Mask-based selection from an externally drawn ``rand`` stream:
    full-[K] :func:`policy_scores` into the masked greedy / top-S
    primitives.  Shared by the select fns below (which draw ``rand`` from
    their key) and the small-K fused-round fallback
    (:func:`round_via_mask`, whose caller already drew it)."""
    kind, a, b = policy_scores(policy, state_obs(state), state.total,
                               state.disc_total, true_ud, true_ul, rand,
                               hyper)
    if kind == "score":
        return _top_score(a, cand_mask, s_round)
    return _greedy_tinc(a, b, cand_mask, s_round)


def _select_via_scores(policy, state, cand_mask, key, true_ud, true_ul,
                       hyper, s_round: int) -> jnp.ndarray:
    """Static-fallback selection: draw the uniform stream (random policy
    only) and run :func:`_select_with_rand`."""
    rand = (jax.random.uniform(key, cand_mask.shape)
            if policy == "random" else None)
    return _select_with_rand(policy, state, cand_mask, true_ud, true_ul,
                             rand, hyper, s_round)


def select_fedcs_mask(state, cand_mask, key, true_ud, true_ul, hyper,
                      *, s_round: int) -> jnp.ndarray:
    """FedCS: last observed latency is the estimate (never-seen => 0 s)."""
    return _select_via_scores("fedcs", state, cand_mask, key, true_ud,
                              true_ul, hyper, s_round)


def select_extended_fedcs_mask(state, cand_mask, key, true_ud, true_ul, hyper,
                               *, s_round: int) -> jnp.ndarray:
    """Extended FedCS: moving average of the last W observations."""
    return _select_via_scores("extended_fedcs", state, cand_mask, key,
                              true_ud, true_ul, hyper, s_round)


def _naive_scores(state: BanditState, alpha, use_kernel: bool) -> jnp.ndarray:
    """Eq. (4) score over all arms, via the fused Pallas kernel or jnp."""
    if use_kernel:
        from repro.kernels.ops import ucb_scores
        return ucb_scores(state.sum_tinc, state.n_sel, state.total,
                          alpha=float(alpha))
    return -_mean(state.sum_tinc, state.n_sel) / alpha + ucb_bonus(state)


def select_naive_mask(state, cand_mask, key, true_ud, true_ul, hyper,
                      *, s_round: int) -> jnp.ndarray:
    """Naive MAB-CS (Eq. 4): pure UCB-score top-S over the candidate set.

    ``hyper`` is alpha.  When alpha is a concrete float and K >= KERNEL_MIN_K
    the fused Pallas kernel scores all arms in one HBM pass; a traced alpha
    (hyper-parameter sweeps) falls back to the jnp elementwise path.
    """
    k = state.n_sel.shape[0]
    if isinstance(hyper, (int, float)) and k >= KERNEL_MIN_K:
        return _top_score(_naive_scores(state, hyper, True), cand_mask,
                          s_round)
    return _select_via_scores("naive_ucb", state, cand_mask, key, true_ud,
                              true_ul, hyper, s_round)


def select_elementwise_mask(state, cand_mask, key, true_ud, true_ul, hyper,
                            *, s_round: int) -> jnp.ndarray:
    """Element-wise MAB-CS (Eqs. 5-7).  ``hyper`` is beta."""
    return _select_via_scores("elementwise_ucb", state, cand_mask, key,
                              true_ud, true_ul, hyper, s_round)


def select_random_mask(state, cand_mask, key, true_ud, true_ul, hyper,
                       *, s_round: int) -> jnp.ndarray:
    """Uniform S-subset of the candidates (random scores + top-S)."""
    return _select_via_scores("random", state, cand_mask, key, true_ud,
                              true_ul, hyper, s_round)


def select_oracle_mask(state, cand_mask, key, true_ud, true_ul, hyper,
                       *, s_round: int) -> jnp.ndarray:
    """Clairvoyant: greedy on this round's true sampled times (upper bound)."""
    return _select_via_scores("oracle", state, cand_mask, key, true_ud,
                              true_ul, hyper, s_round)


def select_discounted_mask(state, cand_mask, key, true_ud, true_ul, hyper,
                           *, s_round: int) -> jnp.ndarray:
    """Discounted Element-wise MAB-CS (core.nonstationary, Garivier &
    Moulines): tau from the gamma-decayed ``disc_*`` statistics.

    ``hyper`` is beta; the decay gamma lives in the state updates
    (:func:`observe` with ``decay=policy_decay("discounted_ucb")``), not
    here.  Thresholds and the BIG clamp (see :func:`policy_scores`) mirror
    DiscountedStats exactly so the f32 port selects identically to the
    float64 numpy reference.
    """
    return _select_via_scores("discounted_ucb", state, cand_mask, key,
                              true_ud, true_ul, hyper, s_round)


def select_sliding_mask(state, cand_mask, key, true_ud, true_ul, hyper,
                        *, s_round: int) -> jnp.ndarray:
    """Sliding-window Element-wise MAB-CS (core.nonstationary): tau from the
    last-W-observation ring-buffer means with the global UCB bonus.
    ``hyper`` is beta."""
    return _select_via_scores("sliding_ucb", state, cand_mask, key, true_ud,
                              true_ul, hyper, s_round)


SELECT_FNS: dict[str, Callable] = {
    "fedcs": select_fedcs_mask,
    "extended_fedcs": select_extended_fedcs_mask,
    "naive_ucb": select_naive_mask,
    "elementwise_ucb": select_elementwise_mask,
    "random": select_random_mask,
    "oracle": select_oracle_mask,
    "discounted_ucb": select_discounted_mask,
    "sliding_ucb": select_sliding_mask,
}
POLICY_NAMES: list[str] = list(SELECT_FNS)
POLICY_IDS: dict[str, int] = {n: i for i, n in enumerate(POLICY_NAMES)}
# sensible default for the one scalar hyper-parameter each policy reads
DEFAULT_HYPERS: dict[str, float] = {
    "fedcs": 0.0, "extended_fedcs": 0.0, "naive_ucb": DEFAULT_ALPHA,
    "elementwise_ucb": DEFAULT_BETA, "random": 0.0, "oracle": 0.0,
    "discounted_ucb": DEFAULT_BETA, "sliding_ucb": DEFAULT_BETA,
}


def policy_decay(policy: str) -> float:
    """Per-round decay of the state's ``disc_*`` statistics for ``policy``:
    DEFAULT_GAMMA for ``discounted_ucb``, 1.0 (no decay) otherwise.  The
    engines thread this into every :func:`observe` call."""
    return DEFAULT_GAMMA if policy == "discounted_ucb" else 1.0


# Below this many arms the fused round's candidate compaction costs more
# than it saves for these policies (measured on CPU, BENCH_round_kernel.json
# K=100 rows: random 0.77x, discounted_ucb 0.89x, naive_ucb 0.96x before
# routing) — ops.bandit_round auto-falls back to the unfused mask path
# (:func:`round_via_mask`, bitwise-identical results) when ``use_kernel``
# is unset.  Policies not listed always fuse (their compaction wins at
# every measured K).
FUSED_MIN_K: dict[str, int] = {
    "random": 1024,
    "naive_ucb": 1024,
    "discounted_ucb": 512,
}


def fused_min_k(policy: str) -> int:
    """Smallest K at which ``ops.bandit_round`` keeps the fused compacted
    path for ``policy`` under auto-routing (0 = always fused)."""
    return FUSED_MIN_K.get(policy, 0)


def scatter_cand_times(cand_idx: jnp.ndarray, t_ud_c: jnp.ndarray,
                       t_ul_c: jnp.ndarray, k: int):
    """Spread candidate-sliced times into zero-[K] buffers — with
    :func:`cand_mask`, the bridge from the streamed-sampling draws to the
    unfused mask pipeline and to the whole-state kernel of a policy that
    reads the times (:data:`POLICY_READS_TIMES`; ``cand_idx`` entries >= K
    are padding and drop).  The ONE copy all these consumers share, so the
    cross-path bitwise-parity gates guard a single definition."""
    drop = jnp.where(cand_idx < k, cand_idx, k)
    t_ud = jnp.zeros(k, jnp.float32).at[drop].set(t_ud_c, mode="drop")
    t_ul = jnp.zeros(k, jnp.float32).at[drop].set(t_ul_c, mode="drop")
    return t_ud, t_ul


def cand_mask(cand_idx: jnp.ndarray, k: int) -> jnp.ndarray:
    """The [K] candidate mask of [C] candidate indices (entries >= K are
    padding and drop) — the input every mask-fed selection takes, the
    whole-state kernel's included.  Built here alone, under
    ``round.select`` whichever path calls it."""
    with jax.named_scope(SCOPE_SELECT):
        return jnp.zeros(k, bool).at[cand_idx].set(True, mode="drop")


def round_via_mask(state, cand_mask, t_ud, t_ul, rand, hyper, *,
                   policy: str, s_round: int, decay: float = 1.0,
                   fault: tuple | None = None, deadline: float | None = None,
                   fault_u: jnp.ndarray | None = None):
    """One whole round through the UNfused mask pipeline (full-[K] select +
    schedule + observe) with the round contract of the fused paths:
    returns ``(new_state, sel [S], round_time)`` — plus a fourth ``flags``
    [S] output (per-slot FLAG_* outcome) when the failure layer is on
    (``deadline`` set; ``fault_u`` is the [3, S] block from
    :func:`fault_uniforms`, None when only the deadline is active).

    This is the small-K fallback of ops.bandit_round (see
    :data:`FUSED_MIN_K`): ``rand`` is the [K] uniform stream the fused
    caller already drew (random policy; None otherwise), so routing here
    consumes the identical randomness and stays bitwise-equal to both the
    fused paths and the engines' ``fused=False`` baseline.
    """
    with jax.named_scope(SCOPE_SELECT):
        sel = _select_with_rand(policy, state, cand_mask, t_ud, t_ul, rand,
                                hyper, s_round)
    with jax.named_scope(SCOPE_FINISH):
        safe = jnp.where(sel >= 0, sel, 0)
        if deadline is None:
            round_time, incs = schedule_selected(sel, t_ud, t_ul)
            state = observe(state, sel, t_ud[safe], t_ul[safe], incs,
                            decay=decay)
            return state, sel, round_time
        valid = sel >= 0
        sud, sul = t_ud[safe], t_ul[safe]
        round_time, incs, finish = schedule_completions(valid, sud, sul)
        obs_ud, obs_ul, obs_inc, fail, flags, round_time = censor_slots(
            valid, sud, sul, incs, finish, round_time, fault_u, fault,
            deadline)
        state = observe(state, sel, obs_ud, obs_ul, obs_inc, decay=decay,
                        fail=fail)
        return state, sel, round_time, flags


def make_select_fn(policy: str, s_round: int) -> Callable:
    """Resolve a policy name into its mask-based select_fn with the cohort
    size bound — the common entry point of both on-device engines
    (sim/engine_jax.py and fl/engine.py).  Raises on unknown names."""
    if policy not in SELECT_FNS:
        raise ValueError(f"unknown policy {policy!r}; have {POLICY_NAMES}")
    return functools.partial(SELECT_FNS[policy], s_round=s_round)


def make_round_fn(policy: str, s_round: int, *,
                  use_kernel: bool | None = None,
                  interpret: bool | None = None,
                  fault=None, deadline: float | None = None) -> Callable:
    """The fused fast path: one whole protocol round — policy scoring,
    candidate-compacted Algorithm-1 / top-S selection, realized schedule,
    and the ``observe`` statistics update — as a single call

        round_fn(state, cand_idx, key, t_ud, t_ul, hyper)
            -> (new_state, sel [S], round_time)

    ``cand_idx``: [C] int32 *sorted* candidate indices (entries >= K are
    padding; :func:`cand_idx_from_mask` bridges from masks).  Selections,
    round times and state updates are bitwise-identical to the static
    fallback (``make_select_fn`` + ``schedule_selected`` + ``observe``) —
    pinned by tests/test_bandit_round.py — but the hot path runs over the
    [C]-compacted candidate slice instead of S passes over all K arms, and
    on TPU the whole round is one Pallas kernel (kernels/bandit_round.py;
    ``use_kernel``/``interpret`` override the kernels/ops auto-routing).
    With ``use_kernel`` unset and K below the policy's
    :data:`FUSED_MIN_K` threshold, the round auto-falls back to the
    unfused mask pipeline (:func:`round_via_mask`) — same results,
    bitwise; the engines additionally skip the index encoding entirely
    below the threshold so the fallback costs nothing.
    The per-round decay of the ``disc_*`` statistics is resolved statically
    from the policy, exactly as the engines do for the fallback.

    With ``deadline`` set the failure-aware layer is compiled in (``fault``:
    FaultModel / probability triple / None — see :func:`resolve_fault`):
    the fault stream derives from ``key`` via :data:`FAULT_STREAM_TAG`, and
    the round additionally returns the per-slot FLAG_* outcome —
    ``(state, sel, round_time, flags)``.  Left at the defaults, nothing
    about the round changes, bitwise.
    """
    if policy not in SELECT_FNS:
        raise ValueError(f"unknown policy {policy!r}; have {POLICY_NAMES}")
    decay = policy_decay(policy)
    fault = resolve_fault(fault, deadline)

    def round_fn(state, cand_idx, key, t_ud, t_ul, hyper):
        from repro.kernels import ops
        k = t_ud.shape[0]
        with jax.named_scope(SCOPE_SELECT):
            # same [K] uniform draw (same key) as select_random_mask, so
            # the fused and fallback paths consume identical randomness
            rand = (jax.random.uniform(key, t_ud.shape)
                    if policy == "random" else None)
        with jax.named_scope(SCOPE_FINISH):
            fu = (fault_uniforms(key, s_round)
                  if fault is not None else None)
        if use_kernel is None and k < fused_min_k(policy):
            return round_via_mask(state, cand_mask(cand_idx, k), t_ud, t_ul,
                                  rand, hyper,
                                  policy=policy, s_round=s_round,
                                  decay=decay, fault=fault,
                                  deadline=deadline, fault_u=fu)
        return ops.bandit_round(state, cand_idx, t_ud, t_ul, rand, hyper,
                                policy=policy, s_round=s_round, decay=decay,
                                use_kernel=use_kernel, interpret=interpret,
                                fault=fault, deadline=deadline, fault_u=fu)

    return round_fn


def make_sampled_round_fn(policy: str, s_round: int, *,
                          fluctuate: bool = True,
                          use_kernel: bool | None = None,
                          interpret: bool | None = None,
                          fault=None,
                          deadline: float | None = None) -> Callable:
    """The streamed-sampling fast path: one whole protocol round that draws
    its own Eq. (8) resource times AT THE CANDIDATE SLICE —

        round_fn(state, cand_idx, key, k_time, theta_mu, gamma_mu,
                 n_samples, eta, model_bits, hyper)
            -> (new_state, sel [S], round_time)

    ``theta_mu``/``gamma_mu``/``n_samples``: full-[K] per-client means
    (``theta_mu`` already carries any scenario multiplier); ``k_time`` is
    this round's time-draw PRNG key.  The round never materializes [K]
    resource draws: it draws ONE [2, C] uniform block from ``k_time``
    (bitwise the stream of sim.engine_jax.sample_times_candidates with the
    same key) and the transform to (t_UD, t_UL) runs inside the fused round
    (kernels/ops.bandit_round_sampled routes the selection to the Pallas
    kernel on TPU, the jnp reference elsewhere).  The kernel route draws
    Eq. 8 at the S selected slots unless the policy reads the times
    (:data:`POLICY_READS_TIMES`); the reference route and a time-reading
    policy transform the whole [C] slice.  A client's time is the same
    either way.

    The random policy still draws its [K] uniform stream from ``key`` so
    the fast path's fused and unfused executions stay bitwise-identical,
    like ``make_round_fn``'s.

    ``fault``/``deadline`` compile in the failure-aware layer exactly as in
    :func:`make_round_fn` (fourth ``flags`` output when ``deadline`` is
    set; bitwise no-op at the defaults).
    """
    if policy not in SELECT_FNS:
        raise ValueError(f"unknown policy {policy!r}; have {POLICY_NAMES}")
    decay = policy_decay(policy)
    fault = resolve_fault(fault, deadline)

    def round_fn(state, cand_idx, key, k_time, theta_mu, gamma_mu,
                 n_samples, eta, model_bits, hyper):
        from repro.kernels import ops
        from repro.kernels.ref import truncnorm_times_ref
        k = theta_mu.shape[0]
        with jax.named_scope(SCOPE_SELECT):
            rand = (jax.random.uniform(key, theta_mu.shape)
                    if policy == "random" else None)
        with jax.named_scope(SCOPE_TIMES):
            u2 = (jax.random.uniform(k_time, (2,) + cand_idx.shape,
                                     jnp.float32) if fluctuate else None)
        with jax.named_scope(SCOPE_FINISH):
            fu = (fault_uniforms(key, s_round)
                  if fault is not None else None)
        if use_kernel is None and k < fused_min_k(policy):
            # small-K fallback (FUSED_MIN_K): same sliced draws, scattered
            # into zero-[K] buffers for the unfused mask pipeline
            with jax.named_scope(SCOPE_TIMES):
                safe_c = jnp.where(cand_idx < k, cand_idx, 0)
                t_ud_c, t_ul_c = truncnorm_times_ref(
                    u2, theta_mu[safe_c], gamma_mu[safe_c],
                    n_samples[safe_c], eta, model_bits, fluctuate=fluctuate)
                t_ud, t_ul = scatter_cand_times(cand_idx, t_ud_c, t_ul_c,
                                                k)
            return round_via_mask(state, cand_mask(cand_idx, k), t_ud, t_ul,
                                  rand, hyper,
                                  policy=policy, s_round=s_round,
                                  decay=decay, fault=fault,
                                  deadline=deadline, fault_u=fu)
        return ops.bandit_round_sampled(
            state, cand_idx, u2, rand, theta_mu, gamma_mu, n_samples, eta,
            model_bits, hyper, policy=policy, s_round=s_round, decay=decay,
            fluctuate=fluctuate, use_kernel=use_kernel, interpret=interpret,
            fault=fault, deadline=deadline, fault_u=fu)

    return round_fn


def make_segmented_round_fn(policy: str, s_round: int, *, axis_name: str,
                            n_shards: int, fluctuate: bool = True,
                            use_kernel: bool | None = None,
                            interpret: bool | None = None,
                            fault=None,
                            deadline: float | None = None) -> Callable:
    """The client-sharded twin of :func:`make_sampled_round_fn`: one whole
    protocol round on a BanditState whose [K] arrays live SPLIT across the
    ``axis_name`` mesh axis as contiguous blocks (shard p owns global
    clients [p*K/P, (p+1)*K/P) — the ``distributed.sharding.shard_leading``
    layout).  Must be called INSIDE ``shard_map`` over that axis:

        round_fn(state_l, cand_idx, key, k_time, theta_mu_l, gamma_mu_l,
                 n_samples_l, eta, model_bits, hyper)
            -> (new_state_l, sel [S], round_time)

    ``state_l``: the local [K/P] BanditState block (the scalar ``total`` /
    ``disc_total`` counters stay replicated); ``cand_idx``: [C] sorted
    *global* candidate indices, replicated (every shard draws the same
    candidates from the same per-round key); ``theta_mu_l`` /
    ``gamma_mu_l`` / ``n_samples_l``: the local [K/P] mean blocks.
    Selections, round times and state are bitwise-identical to the flat
    ``make_sampled_round_fn`` path — pinned by tests/test_sharded_sweep.py
    and gated at every probed K by benchmarks/bench_sharded_sweep.py.

    Dataflow (the segmented multi-shard selection):

      * candidate stats/means: each shard gathers the candidate slots it
        owns and a masked ``psum`` re-assembles the exact [C] slice (owner
        value + 0.0 from every other shard — bitwise the flat gather), so
        the Eq. (8) transform and all [C]-sized arithmetic run replicated
        on compacted values; nothing K-sized ever crosses shards.
      * "score" policies (:func:`policy_kind`): each shard ranks only its
        OWN candidate slots (local top-S — the Pallas ``topk_slots`` kernel
        on TPU, kernels/ops.local_topk), the P*S (value, slot) pairs are
        ``all_gather``-ed and the final reduce
        (kernels/ref.segmented_topk_ref) replays the flat argmax order —
        score descending, compact slot ascending on ties — so the merged
        top-S is bitwise the flat one.  O(K/P + P*S) per shard, not O(K).
      * "greedy" policies: Algorithm-1's per-step T_inc depends on the
        running schedule clock, so per-shard pruning is NOT exact; they
        run the replicated Algorithm-1 on the psum-assembled [C] slice
        (still O(C) compacted, never O(K)).
      * observe: every shard scatters only the selected clients it owns
        (foreign picks route to -1 and drop); the scalar totals take the
        replicated GLOBAL valid count (``observe(count_valid=...)``), so
        ``total``/``disc_total`` stay replicated across shards.

    The random policy's [K] uniform stream and the [2, C] time uniforms
    are drawn replicated from the same per-round keys as the flat path, so
    the sharded sweep consumes identical randomness.  ``fault`` /
    ``deadline`` compile in the failure layer exactly as in
    :func:`make_round_fn` (fault uniforms replicated; each shard counts
    only its own clients' failures in ``n_fail``; fourth ``flags`` output).
    """
    if policy not in SELECT_FNS:
        raise ValueError(f"unknown policy {policy!r}; have {POLICY_NAMES}")
    decay = policy_decay(policy)
    fault = resolve_fault(fault, deadline)
    kind = policy_kind(policy)

    def round_fn(state, cand_idx, key, k_time, theta_mu, gamma_mu,
                 n_samples, eta, model_bits, hyper):
        from repro.kernels import ops
        from repro.kernels.ref import (segmented_topk_ref,
                                       truncnorm_times_ref)
        k_local = theta_mu.shape[0]
        k = k_local * n_shards
        off = jax.lax.axis_index(axis_name) * k_local
        cvalid = cand_idx < k
        loc = cand_idx - off
        in_l = cvalid & (loc >= 0) & (loc < k_local)
        safe_l = jnp.where(in_l, loc, 0)

        def assemble(x_c):
            # exact [C] re-assembly: the owning shard contributes the
            # value, every other shard exactly 0 — x + 0*(P-1) is bitwise x
            return jax.lax.psum(
                jnp.where(in_l, x_c, jnp.zeros((), x_c.dtype)), axis_name)

        with jax.named_scope(SCOPE_TIMES):
            u2 = (jax.random.uniform(k_time, (2,) + cand_idx.shape,
                                     jnp.float32) if fluctuate else None)
            t_ud_c, t_ul_c = truncnorm_times_ref(
                u2, assemble(theta_mu[safe_l]), assemble(gamma_mu[safe_l]),
                assemble(n_samples[safe_l]), eta, model_bits,
                fluctuate=fluctuate)
        with jax.named_scope(SCOPE_FINISH):
            fu = fault_uniforms(key, s_round) if fault is not None else None

        def col(name):
            if name == "hist_sum_ud":
                return state.hist_ud[safe_l].sum(1)
            if name == "hist_sum_ul":
                return state.hist_ul[safe_l].sum(1)
            return getattr(state, name)[safe_l]

        with jax.named_scope(SCOPE_SELECT):
            rand_c = None
            if policy == "random":
                # the flat path's [K] draw, replicated, sliced at the
                # candidates
                rand = jax.random.uniform(key, (k,))
                rand_c = rand[jnp.where(cvalid, cand_idx, 0)]
            obs_c = {n: col(n) for n in POLICY_STATS[policy]}
            if kind == "greedy":
                obs = {n: assemble(v) for n, v in obs_c.items()}
                _, a, b = policy_scores(policy, obs, state.total,
                                        state.disc_total, t_ud_c, t_ul_c,
                                        rand_c, hyper)
                slots = greedy_slots(a, b, cvalid, s_round)
            else:
                # local stats are garbage at foreign slots — masked out
                # before the local ranking, so only owned slots are ever
                # ranked here
                _, a, _ = policy_scores(policy, obs_c, state.total,
                                        state.disc_total, t_ud_c, t_ul_c,
                                        rand_c, hyper)
                lvals, lslots = ops.local_topk(
                    jnp.where(in_l, a, -jnp.inf), in_l, s_round,
                    use_kernel=use_kernel, interpret=interpret)
                gvals = jax.lax.all_gather(lvals, axis_name)
                gslots = jax.lax.all_gather(lslots, axis_name)
                slots = segmented_topk_ref(gvals, gslots, s_round)
            ok = slots >= 0
            safe_slot = jnp.where(ok, slots, 0)
            sel = jnp.where(ok, cand_idx[safe_slot], -1).astype(jnp.int32)

        with jax.named_scope(SCOPE_FINISH):
            valid = sel >= 0
            sud, sul = t_ud_c[safe_slot], t_ul_c[safe_slot]
            n_valid = valid.sum()
            # local view of the selection: foreign picks become -1 and drop
            sel_l = jnp.where(valid & (sel >= off) & (sel < off + k_local),
                              sel - off, -1)
            if deadline is None:
                round_time, incs = schedule_gathered(valid, sud, sul)
                state2 = observe(state, sel_l, sud, sul, incs, decay=decay,
                                 count_valid=n_valid)
                return state2, sel, round_time
            round_time, incs, finish = schedule_completions(valid, sud, sul)
            obs_ud, obs_ul, obs_inc, fail, flags, round_time = censor_slots(
                valid, sud, sul, incs, finish, round_time, fu, fault,
                deadline)
            state2 = observe(state, sel_l, obs_ud, obs_ul, obs_inc,
                             decay=decay, fail=fail, count_valid=n_valid)
            return state2, sel, round_time, flags

    return round_fn


def segmented_merge_bytes(policy: str, n_req: int, s_round: int) -> int:
    """Operand bytes one shard feeds the merge's collectives in one round
    of :func:`make_segmented_round_fn`: the masked ``psum`` of the three
    [C] means Eq. (8) reads (throughput, capability, dataset size), then
    for a "greedy" policy one [C] ``psum`` per statistic it scores from
    (``POLICY_STATS``), for a "score" policy the ``all_gather`` of its
    local top-S values and slots.  Every exchanged value is 32-bit."""
    if policy_kind(policy) == "greedy":
        words = (3 + len(POLICY_STATS[policy])) * n_req
    else:
        words = 3 * n_req + 2 * s_round
    return 4 * words


# ---------------------------------------------------------------------------
# Hierarchical two-level selection (the paper's mobile-network cell
# structure): score CELLS from aggregated per-cell bandit stats, then poll
# candidates only inside the selected cells — O(cells + s_cells*K/cells)
# per-round draw instead of O(K).  Cell assignment is the round-robin
# sim.scenarios.Scenario.cell_ids binning (cell c owns {c, c+cells, ...}),
# the same binning the correlated-congestion dynamics use, so the two-level
# bandit exploits exactly the correlation the scenario induces.
# ---------------------------------------------------------------------------

def cell_scores(cell_n: jnp.ndarray, cell_tinc: jnp.ndarray,
                alpha: float = DEFAULT_ALPHA) -> jnp.ndarray:
    """Naive-UCB (Eq. 4) score at CELL granularity: mean observed T_inc of
    the cell's selections against the exploration bonus, BIG for
    never-sampled cells (explore-first).  ``cell_n``/``cell_tinc``:
    [n_cells] f32 aggregates maintained by :func:`update_cell_stats`."""
    nf = jnp.maximum(cell_n, 1.0)
    total = jnp.maximum(cell_n.sum(), 2.0)
    bonus = jnp.sqrt(jnp.log(total) / (2.0 * nf))
    score = -(cell_tinc / nf) / alpha + bonus
    return jnp.where(cell_n < 0.5, jnp.float32(BIG), score)


def select_cells(cell_n: jnp.ndarray, cell_tinc: jnp.ndarray,
                 s_cells: int, alpha: float = DEFAULT_ALPHA) -> jnp.ndarray:
    """Top-``s_cells`` cells by :func:`cell_scores` (never-sampled cells
    first; ties toward the lowest cell id — the shared ``top_slots``
    order).  All cells are always eligible, so no -1 padding occurs for
    ``s_cells <= n_cells``."""
    score = cell_scores(cell_n, cell_tinc, alpha)
    return select_slots_all(score, s_cells)


def select_slots_all(score: jnp.ndarray, s: int) -> jnp.ndarray:
    """``top_slots`` with every slot eligible."""
    return top_slots(score, jnp.ones(score.shape, bool), s)


def hier_cand_idx(key: jnp.ndarray, cells_sel: jnp.ndarray, k: int,
                  n_cells: int, n_req_cell: int) -> jnp.ndarray:
    """Per-cell Resource-Request draw of the hierarchical round.

    For each selected cell (``cells_sel``: [s_cells] ids) an independent
    top-k-of-uniforms prefix draw — the key ``fold_in``-ed with the cell
    id, so a cell's draw does not depend on which other cells were picked —
    selects ``n_req_cell`` member clients.  Under the round-robin binning
    cell c owns global clients {c, c+n_cells, ...}; members past K (the
    short cells when n_cells does not divide K) and slots past a small
    cell's population pad with K, the fused round's drop sentinel.
    Returns the [s_cells * n_req_cell] sorted int32 candidate vector.
    """
    m = -(-k // n_cells)            # ceil: max population of any cell

    def per_cell(c):
        kk = jax.random.fold_in(key, c)
        gidx = jnp.arange(m, dtype=jnp.int32) * n_cells + c
        exists = gidx < k
        u = jnp.where(exists, jax.random.uniform(kk, (m,), jnp.float32),
                      -1.0)
        vals, pos = jax.lax.top_k(u, n_req_cell)
        return jnp.where(vals >= 0.0, gidx[pos], k)

    cands = jax.vmap(per_cell)(cells_sel.astype(jnp.int32))
    return jnp.sort(cands.reshape(-1)).astype(jnp.int32)


def update_cell_stats(cell_n: jnp.ndarray, cell_tinc: jnp.ndarray,
                      sel: jnp.ndarray, pre_tinc: jnp.ndarray,
                      post_tinc: jnp.ndarray, cell_id: jnp.ndarray,
                      n_cells: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fold one round's observed T_inc into the per-cell aggregates.

    The per-client increment is recovered from the state delta
    (``post_tinc - pre_tinc`` of ``sum_tinc`` at the selected clients), so
    the cell level needs no extra plumbing out of the fused round — and
    under censoring it inherits the same pessimistic deadline lower bound
    the client level learns.  -1 slots drop.
    """
    v = sel >= 0
    safe = jnp.where(v, sel, 0)
    d = jnp.where(v, post_tinc[safe] - pre_tinc[safe], 0.0)
    drop = jnp.where(v, cell_id[safe], n_cells)
    return (cell_n.at[drop].add(1.0, mode="drop"),
            cell_tinc.at[drop].add(d, mode="drop"))


# ---------------------------------------------------------------------------
# Candidate-index convenience wrappers (the original public API).
# ---------------------------------------------------------------------------

def select_elementwise(state: BanditState, candidates: jnp.ndarray,
                       s_round: int, beta: float = DEFAULT_BETA) -> jnp.ndarray:
    """Element-wise MAB-CS (Eqs. 5-7), vectorized.  candidates: [C] indices."""
    mask = candidate_mask(state.n_sel.shape[0], candidates)
    return select_elementwise_mask(state, mask, None, None, None, beta,
                                   s_round=s_round)


def select_naive(state: BanditState, candidates: jnp.ndarray,
                 s_round: int, alpha: float = DEFAULT_ALPHA,
                 use_kernel: bool | None = None) -> jnp.ndarray:
    """Naive MAB-CS (Eq. 4): pure UCB-score top-S over the candidate set.

    ``use_kernel`` routes scoring through the Pallas ucb_score kernel; the
    default (None) auto-selects it for K >= KERNEL_MIN_K.
    """
    k = state.n_sel.shape[0]
    mask = candidate_mask(k, candidates)
    if use_kernel is None:
        use_kernel = k >= KERNEL_MIN_K
    return _top_score(_naive_scores(state, alpha, use_kernel), mask, s_round)


def select_fedcs(state: BanditState, candidates: jnp.ndarray,
                 s_round: int) -> jnp.ndarray:
    """FedCS over candidate indices ([C] ints): last observed latency is
    the estimate.  Returns [s_round] selected indices, -1 padded."""
    mask = candidate_mask(state.n_sel.shape[0], candidates)
    return select_fedcs_mask(state, mask, None, None, None, 0.0,
                             s_round=s_round)


def select_extended_fedcs(state: BanditState, candidates: jnp.ndarray,
                          s_round: int) -> jnp.ndarray:
    """Extended FedCS over candidate indices ([C] ints): last-W moving
    average as the estimate.  Returns [s_round] indices, -1 padded."""
    mask = candidate_mask(state.n_sel.shape[0], candidates)
    return select_extended_fedcs_mask(state, mask, None, None, None, 0.0,
                                      s_round=s_round)


def select_random(state: BanditState, candidates: jnp.ndarray,
                  s_round: int, key: jnp.ndarray) -> jnp.ndarray:
    """Uniform S-subset of the candidates ([C] ints; ``key``: PRNG key).
    Returns [s_round] indices, -1 padded."""
    mask = candidate_mask(state.n_sel.shape[0], candidates)
    return select_random_mask(state, mask, key, None, None, 0.0,
                              s_round=s_round)


def select_oracle(state: BanditState, candidates: jnp.ndarray,
                  s_round: int, true_ud: jnp.ndarray,
                  true_ul: jnp.ndarray) -> jnp.ndarray:
    """Clairvoyant greedy on this round's true [K] times (upper bound).
    Returns [s_round] indices, -1 padded."""
    mask = candidate_mask(state.n_sel.shape[0], candidates)
    return select_oracle_mask(state, mask, None, true_ud, true_ul, 0.0,
                              s_round=s_round)

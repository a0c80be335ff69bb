"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth).

``bandit_round_ref`` doubles as the *production* CPU path of the fused
bandit round (ops.bandit_round routes here off-TPU): it is not a slow
mirror but the candidate-compacted fast formulation, bitwise-identical to
the kernel and to the unfused select/schedule/observe pipeline.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

BIG = 1e12


def ucb_scores_ref(sums, n_sel, total, alpha: float = 1000.0):
    nf = jnp.maximum(n_sel.astype(jnp.float32), 1.0)
    mean = sums.astype(jnp.float32) / nf
    bonus = jnp.sqrt(jnp.log(jnp.maximum(total.astype(jnp.float32), 2.0))
                     / (2.0 * nf))
    score = -(mean / alpha) + bonus
    return jnp.where(n_sel == 0, jnp.float32(BIG), score)


def fedavg_ref(stacked, weights):
    return jnp.einsum("cn,c->n", stacked.astype(jnp.float32),
                      weights.astype(jnp.float32)).astype(stacked.dtype)


def flash_attention_ref(q, k, v, causal: bool = True):
    """q: [B,Sq,KV,G,dh]; k,v: [B,Skv,KV,dh] — naive full-softmax attention."""
    B, Sq, KV, G, dh = q.shape
    Skv = k.shape[1]
    s = jnp.einsum("bskgd,btkd->bkgst", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * dh ** -0.5
    if causal:
        mask = jnp.arange(Sq)[:, None] >= jnp.arange(Skv)[None, :]
        s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,btkd->bskgd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def truncnorm_times_ref(u2, mu_theta, mu_gamma, n_samples, eta, model_bits,
                        *, fluctuate: bool = True):
    """Eqs. (8)-(11) at the candidate slice: ONE fused two-draw transform,
    elementwise per client.

    ``u2``: [2, N] uniforms (row 0 -> throughput theta, row 1 -> capability
    gamma — the layout ``make_sampled_round_fn``'s single ``[2, C]``
    uniform call produces, or N of its columns); ``mu_theta``/``mu_gamma``/
    ``n_samples``: the [N] gathered means.  Both truncated normals run
    through one stacked :func:`repro.sim.truncnorm.truncnorm_transform`
    call (erfinv is the expensive op — batching theta+gamma halves its
    dispatches), then t_UD = D_k / gamma, t_UL = M / theta.  Returns
    ([N] t_ud, [N] t_ul).

    Every route of ``ops.bandit_round_sampled`` draws its times here: the
    whole [C] slice, or the S selected columns on the kernel route of a
    policy that does not read the times.
    """
    from repro.sim.truncnorm import truncnorm_transform

    if fluctuate:
        mu2 = jnp.stack([jnp.asarray(mu_theta, jnp.float32),
                         jnp.asarray(mu_gamma, jnp.float32)])
        drawn = truncnorm_transform(u2, mu2, eta)
        theta, gamma = drawn[0], drawn[1]
    else:
        theta, gamma = mu_theta, mu_gamma
    return (n_samples / jnp.maximum(gamma, 1e-9),
            model_bits / jnp.maximum(theta, 1e-9))


def bandit_round_ref(state, cand_idx, t_ud, t_ul, rand, hyper, *,
                     policy: str, s_round: int, decay: float = 1.0,
                     sliced: bool = False, fault: tuple | None = None,
                     deadline: float | None = None,
                     fault_u=None):
    """One fused bandit round (score -> select -> schedule -> observe) on a
    core.bandit_jax.BanditState — the jnp oracle of
    kernels/bandit_round.py and the CPU fast path.

    ``cand_idx``: [C] int32 sorted candidate indices, >= K entries padding.
    Instead of S masked passes over all K arms, every policy's statistics
    are gathered once for the C candidates and Algorithm 1 / sort-free
    top-S (the shared ``core.bandit_jax.greedy_slots`` / ``top_slots``
    primitives, on the [C] slice) runs compacted; the winning slots map
    back through ``cand_idx`` — sorted candidates make the compacted
    argmax tie-break equal the numpy lowest-client-index rule.
    Returns ``(new_state, sel [s_round], round_time)``.

    ``sliced`` flips the time encoding to the streamed-sampling fast path:
    ``t_ud``/``t_ul``/``rand`` are already candidate-aligned [C] arrays
    (slot i belongs to client ``cand_idx[i]``) and no [K] time array ever
    exists — the schedule runs on slot-gathered values
    (``schedule_gathered``) and ``observe`` scatters them back through
    ``cand_idx``.

    ``deadline`` compiles in the failure-aware layer
    (``core.bandit_jax.censor_slots``; ``fault``: static (crash, churn,
    corrupt) triple, ``fault_u``: the caller-drawn [3, S] uniforms): the
    round then returns ``(new_state, sel, round_time, flags)`` with failed
    slots' observations censored at the deadline.  At the default (None)
    nothing changes, bitwise.
    """
    from repro.core import bandit_jax

    k = state.n_sel.shape[0]
    with jax.named_scope(bandit_jax.SCOPE_SELECT):
        cvalid = cand_idx < k
        safe_c = jnp.where(cvalid, cand_idx, 0)

        def col(name):
            # gather-then-reduce for the ring buffers (same per-row sum as
            # state_obs's reduce-then-gather, without touching all K rows)
            if name == "hist_sum_ud":
                return state.hist_ud[safe_c].sum(1)
            if name == "hist_sum_ul":
                return state.hist_ul[safe_c].sum(1)
            return getattr(state, name)[safe_c]

        def at_c(x):
            return None if x is None else (x if sliced else x[safe_c])

        obs = {name: col(name)
               for name in bandit_jax.POLICY_STATS[policy]}
        kind, a, b = bandit_jax.policy_scores(
            policy, obs, state.total, state.disc_total,
            at_c(t_ud), at_c(t_ul), at_c(rand), hyper)
        if kind == "score":
            slots = bandit_jax.top_slots(a, cvalid, s_round)
        else:
            slots = bandit_jax.greedy_slots(a, b, cvalid, s_round)
        ok = slots >= 0
        safe_slot = jnp.where(ok, slots, 0)
        sel = jnp.where(ok, cand_idx[safe_slot], -1).astype(jnp.int32)

    with jax.named_scope(bandit_jax.SCOPE_FINISH):
        if sliced:
            sud, sul = t_ud[safe_slot], t_ul[safe_slot]
        else:
            safe = jnp.where(ok, sel, 0)
            sud, sul = t_ud[safe], t_ul[safe]
        return finish_round(state, sel, sud, sul, decay=decay, fault=fault,
                            deadline=deadline, fault_u=fault_u)


def finish_round(state, sel, sud, sul, *, decay: float = 1.0,
                 fault: tuple | None = None, deadline: float | None = None,
                 fault_u=None):
    """The O(S) tail of every fused round, shared by the compacted
    reference and the Pallas kernel's route: realized schedule of the
    selection ``sel`` ([S] client indices, -1 padded) with its gathered
    times ``sud``/``sul``, the failure layer when ``deadline`` is set, and
    the ``observe`` scatter.  Returns ``(new_state, sel, round_time)`` —
    plus the per-slot flags with the failure layer on."""
    from repro.core import bandit_jax

    valid = sel >= 0
    if deadline is None:
        round_time, incs = bandit_jax.schedule_gathered(valid, sud, sul)
        state = bandit_jax.observe(state, sel, sud, sul, incs, decay=decay)
        return state, sel, round_time
    round_time, incs, finish = bandit_jax.schedule_completions(valid, sud,
                                                               sul)
    obs_ud, obs_ul, obs_inc, fail, flags, round_time = \
        bandit_jax.censor_slots(valid, sud, sul, incs, finish, round_time,
                                fault_u, fault, deadline)
    state = bandit_jax.observe(state, sel, obs_ud, obs_ul, obs_inc,
                               decay=decay, fail=fail)
    return state, sel, round_time, flags


def local_topk_ref(score, valid, s_round: int):
    """Per-shard local top-S of the segmented multi-shard selection:
    (value, slot) pairs in the flat masked-argmax order
    (core.bandit_jax.top_slots — score descending, lowest slot first on
    ties).  Exhausted slots encode as (-inf, -1).  The jnp oracle (and CPU
    production path) of the Pallas ``topk_slots`` kernel
    (kernels/bandit_round.py via kernels/ops.local_topk)."""
    from repro.core import bandit_jax

    slots = bandit_jax.top_slots(score, valid, s_round)
    ok = slots >= 0
    vals = jnp.where(ok, score[jnp.where(ok, slots, 0)], -jnp.inf)
    return vals.astype(jnp.float32), slots.astype(jnp.int32)


def segmented_topk_ref(vals, slots, s_round: int):
    """Final reduce of the segmented multi-shard top-k: merge P shards'
    local top-S (``vals``/``slots``: [P, S]; slots are *unique* compacted
    candidate positions, -1 = exhausted) into the global top-``s_round``
    slot indices, [s_round] int32 -1-padded.

    Replays flat ``top_slots``'s total order explicitly — value
    descending, slot ascending on exact ties (argmax returns the first
    maximum) — so for finite scores the result is bitwise the flat
    selection over the union; and because any flat pick has fewer than S
    strictly-better candidates in its own shard (hence sits inside that
    shard's local top-S), it is bitwise the flat selection over ALL
    candidates.  Property-tested against ``lax.top_k`` in
    tests/test_segmented_topk.py.
    """
    v = vals.reshape(-1)
    s = slots.reshape(-1).astype(jnp.int32)
    valid = s >= 0
    imax = jnp.iinfo(jnp.int32).max
    slx = jnp.where(valid, s, imax)

    def body(i, carry):
        sel, mask = carry
        live = mask & valid
        vv = jnp.where(live, v, -jnp.inf)
        m = jnp.max(vv)
        cand = live & (vv == m)
        pos = jnp.argmin(jnp.where(cand, slx, imax))
        ok = cand[pos]
        sel = sel.at[i].set(jnp.where(ok, s[pos], -1))
        mask = mask.at[pos].set(jnp.where(ok, False, mask[pos]))
        return sel, mask

    sel0 = jnp.full((s_round,), -1, jnp.int32)
    sel, _ = jax.lax.fori_loop(0, s_round, body,
                               (sel0, jnp.ones(v.shape, bool)))
    return sel


def rg_lru_ref(a, b):
    """y[t] = a[t] * y[t-1] + b[t], y[-1]=0. a,b: [B,T,W]."""
    def step(h, ab):
        at, bt = ab
        h = at * h + bt
        return h, h
    a32 = a.astype(jnp.float32).transpose(1, 0, 2)
    b32 = b.astype(jnp.float32).transpose(1, 0, 2)
    _, ys = jax.lax.scan(step, jnp.zeros_like(a32[0]), (a32, b32))
    return ys.transpose(1, 0, 2).astype(a.dtype)

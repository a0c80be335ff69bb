"""Public jit'd wrappers around the Pallas kernels.

On CPU (this container) kernels run in interpret mode — the kernel body
executes op-by-op in Python, validating correctness against ref.py; on a
real TPU backend set ``interpret=False`` (the default flips automatically).
The elementwise kernels (ucb_score, fedavg) auto-pad to their block
multiples internally, so callers can pass arbitrary sizes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import bandit_round as _bandit_round
from repro.kernels import fedavg as _fedavg
from repro.kernels import flash_attention as _flash
from repro.kernels import ref as _ref
from repro.kernels import rg_lru as _rg
from repro.kernels import ucb_score as _ucb


# Largest K the whole-state Pallas selection kernels take on auto-routing;
# above it kernels/ops routes the round to the candidate-compacted jnp
# path.  Reckoned from AOT compiles for a described TPU v5e (the compiler
# is the authority on VMEM): the widest selection — sliding_ucb, four
# statistic columns plus the candidate mask, ~45 B/arm resident — compiles
# at K = 2.0e6 and is refused at 2.4e6 under the kernels'
# VMEM_LIMIT_BYTES (100 MiB of v5e's 128 MiB).  The bound keeps half of
# that as margin and is compiled at exactly this K by
# tests/test_tpu_compile.py, so a kernel change that outgrows it fails
# there, not on the chip.
KERNEL_MAX_K = 2**20


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _route_kernel(use_kernel: bool | None, k: int) -> bool:
    """Static kernel route: the caller's choice, else the Pallas kernel on
    TPU for K up to :data:`KERNEL_MAX_K`."""
    if use_kernel is None:
        return jax.default_backend() == "tpu" and k <= KERNEL_MAX_K
    return use_kernel


def bandit_round(state, cand_idx, t_ud, t_ul, rand, hyper, *, policy: str,
                 s_round: int, decay: float = 1.0,
                 use_kernel: bool | None = None,
                 interpret: bool | None = None,
                 fault: tuple | None = None, deadline: float | None = None,
                 fault_u=None):
    """One fused bandit round (score -> select -> schedule -> observe) on a
    core.bandit_jax.BanditState; returns ``(new_state, sel, round_time)``
    — or ``(new_state, sel, round_time, flags)`` with the failure-aware
    layer on (``deadline`` set; see ``core.bandit_jax.censor_slots``).

    Auto-routing (the fedavg/ucb_score convention): on TPU, for K up to
    :data:`KERNEL_MAX_K`, the scoring and selection run as the whole-state
    Pallas kernel (kernels/bandit_round.py); elsewhere the round runs the
    candidate-compacted jnp reference (kernels/ref.py::bandit_round_ref)
    — interpret-mode Pallas executes the body op-by-op in Python and is
    only useful for parity testing, so the CPU production path is the
    reference itself.  Both paths are
    bitwise-identical (selections, times, state) to each other and to the
    unfused select/schedule/observe pipeline.  (The small-K fallback lives
    one level up, in ``core.bandit_jax.make_round_fn`` — see FUSED_MIN_K.)
    """
    if not _route_kernel(use_kernel, t_ud.shape[0]):
        return _ref.bandit_round_ref(state, cand_idx, t_ud, t_ul, rand,
                                     hyper, policy=policy, s_round=s_round,
                                     decay=decay, fault=fault,
                                     deadline=deadline, fault_u=fault_u)
    interpret = _default_interpret() if interpret is None else interpret
    return _bandit_round.bandit_round_pallas(
        state, cand_idx, t_ud, t_ul, rand, hyper, policy=policy,
        s_round=s_round, decay=decay, interpret=interpret, fault=fault,
        deadline=deadline, fault_u=fault_u)


def bandit_round_sampled(state, cand_idx, u2, rand, theta_mu, gamma_mu,
                         n_samples, eta, model_bits, hyper, *, policy: str,
                         s_round: int, decay: float = 1.0,
                         fluctuate: bool = True,
                         use_kernel: bool | None = None,
                         interpret: bool | None = None,
                         fault: tuple | None = None,
                         deadline: float | None = None,
                         fault_u=None):
    """The streamed-sampling fused round: Eq. (8) resource times are drawn
    inside the round from one [2, C] uniform block instead of arriving as
    [K] arrays; returns ``(new_state, sel, round_time)``.

    ``u2``: [2, C] uniforms (None when ``fluctuate`` is off), column j
    belonging to candidate ``cand_idx[j]``; ``theta_mu``/``gamma_mu``/
    ``n_samples``: full-[K] means (``theta_mu`` carries any scenario
    multiplier); ``rand``: the random policy's [K] uniform stream (None
    otherwise).  The times come from ``kernels/ref.truncnorm_times_ref``,
    elementwise, so a client's time is the same wherever it is drawn.
    Routing mirrors ``bandit_round``.  The kernel route draws Eq. 8 at the
    S selected slots, after the whole-state Pallas selection; only a
    policy that reads the times (``core.bandit_jax.POLICY_READS_TIMES``)
    also draws the [C] slice first and spreads it into [K] buffers
    (``core.bandit_jax.scatter_cand_times``) for the kernel.  The other
    route draws the [C] slice for the sliced jnp reference.  (The small-K
    fallback lives in ``core.bandit_jax.make_sampled_round_fn`` — see
    FUSED_MIN_K.)
    """
    from repro.core.bandit_jax import (POLICY_READS_TIMES, SCOPE_FINISH,
                                       SCOPE_SELECT, SCOPE_TIMES, cand_mask,
                                       scatter_cand_times)

    k = theta_mu.shape[0]

    def times_at(idx, u):
        return _ref.truncnorm_times_ref(
            u, theta_mu[idx], gamma_mu[idx], n_samples[idx], eta,
            model_bits, fluctuate=fluctuate)

    with jax.named_scope(SCOPE_TIMES):
        safe_c = jnp.where(cand_idx < k, cand_idx, 0)
    if not _route_kernel(use_kernel, k):
        with jax.named_scope(SCOPE_TIMES):
            t_ud_c, t_ul_c = times_at(safe_c, u2)
        with jax.named_scope(SCOPE_SELECT):
            rand_c = None if rand is None else rand[safe_c]
        return _ref.bandit_round_ref(
            state, cand_idx, t_ud_c, t_ul_c, rand_c, hyper, policy=policy,
            s_round=s_round, decay=decay, sliced=True, fault=fault,
            deadline=deadline, fault_u=fault_u)
    t_ud = t_ul = None
    if policy in POLICY_READS_TIMES:
        with jax.named_scope(SCOPE_TIMES):
            t_ud, t_ul = scatter_cand_times(cand_idx, *times_at(safe_c, u2),
                                            k)
    interpret = _default_interpret() if interpret is None else interpret
    with jax.named_scope(SCOPE_SELECT):
        sel = _bandit_round.select_pallas(
            state, cand_mask(cand_idx, k), t_ud, t_ul, rand, hyper,
            policy=policy, s_round=s_round, interpret=interpret)
    with jax.named_scope(SCOPE_TIMES):
        # each selected client's column of u2: its slot in the sorted
        # cand_idx (a -1 slot reads slot 0 and stays invalid)
        safe = jnp.where(sel >= 0, sel, 0)
        slot = jnp.searchsorted(cand_idx, safe, method="compare_all")
        sud, sul = times_at(safe, None if u2 is None else u2[:, slot])
    with jax.named_scope(SCOPE_FINISH):
        return _ref.finish_round(state, sel, sud, sul, decay=decay,
                                 fault=fault, deadline=deadline,
                                 fault_u=fault_u)


def local_topk(score, valid, s_round: int, *,
               use_kernel: bool | None = None,
               interpret: bool | None = None):
    """Per-shard local top-S of the segmented multi-shard selection
    (core.bandit_jax.make_segmented_round_fn): returns
    ``(vals [S] f32, slots [S] int32)`` in the flat masked-argmax order,
    exhausted slots encoded as (-inf, -1) so the cross-shard reduce
    (kernels/ref.segmented_topk_ref) can drop them.

    Routing mirrors ``bandit_round``: on TPU, for C up to
    :data:`KERNEL_MAX_K`, the S-step argmax loop runs as one Pallas kernel
    over the VMEM-resident slice (kernels/bandit_round.topk_slots_pallas);
    elsewhere the jnp reference (kernels/ref.local_topk_ref) IS the
    production path.  Both are bitwise-identical.
    """
    if not _route_kernel(use_kernel, score.shape[0]):
        return _ref.local_topk_ref(score, valid, s_round)
    interpret = _default_interpret() if interpret is None else interpret
    return _bandit_round.topk_slots_pallas(score, valid, s_round,
                                           interpret=interpret)


def ucb_scores(sums, n_sel, total, alpha: float = 1000.0,
               interpret: bool | None = None):
    interpret = _default_interpret() if interpret is None else interpret
    # block padding is handled inside the kernel wrapper itself
    return _ucb.ucb_scores(sums, n_sel, jnp.asarray(total), alpha=alpha,
                           interpret=interpret)


def fedavg_combine(stacked, weights, interpret: bool | None = None):
    interpret = _default_interpret() if interpret is None else interpret
    # block padding is handled inside the kernel wrapper itself
    return _fedavg.fedavg_combine(stacked, weights, interpret=interpret)


def flash_attention(q, k, v, causal: bool = True, block_q: int = 512,
                    block_kv: int = 512, interpret: bool | None = None):
    interpret = _default_interpret() if interpret is None else interpret
    return _flash.flash_attention_fwd(q, k, v, causal=causal,
                                      block_q=block_q, block_kv=block_kv,
                                      interpret=interpret)


def rg_lru_scan(a, b, interpret: bool | None = None):
    interpret = _default_interpret() if interpret is None else interpret
    return _rg.rg_lru_scan(a, b, interpret=interpret)


# ---------------------------------------------------------------------------
# trainable kernel attention: Pallas forward + recompute-based backward
# (FlashAttention-style: the bwd recomputes block attention from q,k,v via
# the jnp blockwise reference instead of saving the score matrices)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention_trainable(q, k, v, causal: bool = True,
                              interpret: bool | None = None):
    return flash_attention(q, k, v, causal=causal, interpret=interpret)


def _fat_fwd(q, k, v, causal, interpret):
    out = flash_attention(q, k, v, causal=causal, interpret=interpret)
    return out, (q, k, v)


def _fat_bwd(causal, interpret, res, g):
    from repro.models.layers import flash_attention as jnp_flash
    q, k, v = res
    _, vjp = jax.vjp(lambda q_, k_, v_: jnp_flash(q_, k_, v_, causal=causal),
                     q, k, v)
    return vjp(g)


flash_attention_trainable.defvjp(_fat_fwd, _fat_bwd)

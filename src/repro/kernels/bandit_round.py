"""Pallas TPU kernels: whole-state bandit selection and the per-shard top-S.

The O(K) part of every round — policy scoring over the [K] state and the
S-pass candidate-masked Algorithm-1 / top-S selection — runs here as one
``pallas_call``: the policy's statistic columns stream HBM -> VMEM once
and the S selection passes run on VMEM-resident values.  What follows the
selection touches only the S picked clients (gather of their times, the
realized schedule, the failure layer, the ``observe`` scatter), so it runs
as the same XLA code as the compacted reference
(``kernels/ref.py::finish_round``) — outputs are bitwise-identical to
``ref.bandit_round_ref``, and the CI bench-smoke gate
(benchmarks/bench_round_kernel.py) fails on any divergence.

Scoring arithmetic is ``core.bandit_jax.policy_scores`` and selection is
``core.bandit_jax.greedy_slots`` / ``top_slots_values`` — the single shared
definitions, written so Mosaic lowers them: a read at a traced position is
a masked reduction over a flat iota, a write is a select.  Selection is
*sort-free*: S iterations of masked argmax (lowest index wins ties, the
numpy reference's convention).

Layout: every [K] column is padded to a multiple of ``BLOCK`` and handed
to the kernel as [K/128, 128] tiles (Mosaic's native 2-D layout; padded
arms are never candidates, so they are inert); scalars ride as [1, 1]
blocks, and the [S] results leave as [1, S] rows — every block is at
least 2-D, so the kernels stay legal when ``vmap`` (the engines' grid of
runs) adds a batch grid axis in front.  The whole selection input stays
resident (grid=(1,) per run), so K is
bounded by VMEM — see ``kernels/ops.KERNEL_MAX_K`` for the bound and how
it was reckoned.  On CPU these kernels exist for interpret-mode parity
testing only — kernels/ops routes real CPU work to the jnp reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import bandit_jax

LANES = 128
BLOCK = 1024        # [K] padding granularity: 8 sublanes x 128 lanes
# scoped-VMEM limit the selection kernels may use: v5e has 128 MiB of VMEM
# per core; the default scoped limit is 16 MiB.  Raised so K up to
# ops.KERNEL_MAX_K compiles, with headroom left below the physical size.
VMEM_LIMIT_BYTES = 100 * 2**20


def _tiles(x, fill=0):
    """[K] -> [Kp/128, 128], padded with ``fill`` to a BLOCK multiple."""
    pad = (-x.shape[0]) % BLOCK
    if pad:
        x = jnp.pad(x, (0, pad), constant_values=fill)
    return x.reshape(-1, LANES)


def _select_kernel(*refs, policy: str, names: tuple[str, ...],
                   s_round: int):
    """score -> select over the resident tiles; writes [S] client
    indices (-1 padded)."""
    *in_refs, o_sel = refs
    v = {n: r[...] for n, r in zip(names, in_refs)}
    mask = v.pop("mask") != 0
    kind, a, b = bandit_jax.policy_scores(
        policy, v, v.pop("total"), v.pop("disc_total"), v.get("t_ud"),
        v.get("t_ul"), v.get("rand"), v.pop("hyper"))
    if kind == "greedy":
        sel = bandit_jax.greedy_slots(a, b, mask, s_round)
    else:
        sel = bandit_jax.top_slots(a, mask, s_round)
    o_sel[...] = sel.reshape(1, s_round)


def _full(shape):
    return pl.BlockSpec(shape, lambda i: (0,) * len(shape))


def _call(kernel, args, out_shape, name: str, interpret: bool):
    """One grid=(1,) pallas_call with every operand whole in VMEM;
    ``name`` is the kernel's stable name in traces."""
    return pl.pallas_call(
        kernel,
        name=name,
        grid=(1,),
        in_specs=[_full(a.shape) for a in args],
        out_specs=tuple(_full(o.shape) for o in out_shape),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(*args)


@functools.partial(jax.jit, static_argnames=("s_round", "interpret"))
def topk_slots_pallas(score, valid, s_round: int, interpret: bool = True):
    """[C] scores + validity -> local top-``s_round`` (vals [S] f32,
    slots [S] int32) as one ``pallas_call``; exhausted slots are
    (-inf, -1).  Bitwise-identical to kernels/ref.local_topk_ref."""
    def kernel(score_ref, valid_ref, o_vals, o_slots):
        sel, vals = bandit_jax.top_slots_values(
            score_ref[...], valid_ref[...] != 0, s_round)
        o_vals[...] = vals.reshape(1, s_round)
        o_slots[...] = sel.reshape(1, s_round)

    vals, slots = _call(kernel,
                        [_tiles(score.astype(jnp.float32), -jnp.inf),
                         _tiles(valid.astype(jnp.int32))],
                        (jax.ShapeDtypeStruct((1, s_round), jnp.float32),
                         jax.ShapeDtypeStruct((1, s_round), jnp.int32)),
                        "topk_slots", interpret)
    return vals[0], slots[0]


def select_pallas(state, mask, t_ud, t_ul, rand, hyper, *, policy: str,
                  s_round: int, interpret: bool):
    """Whole-state selection: [K] candidate ``mask`` -> [S] client indices
    (-1 padded), bitwise the mask-based select fns.  Only the columns the
    policy reads (``core.bandit_jax.POLICY_STATS``) enter the kernel; the
    ring-buffer sums are reduced over the window in XLA first.
    ``t_ud``/``t_ul`` are read only for the policies in
    ``core.bandit_jax.POLICY_READS_TIMES`` and may be None for the rest."""
    obs = bandit_jax.state_obs(state)
    cols = {n: obs[n] for n in bandit_jax.POLICY_STATS[policy]}
    if policy in bandit_jax.POLICY_READS_TIMES:
        cols.update(t_ud=t_ud, t_ul=t_ul)
    if policy == "random":
        cols["rand"] = rand
    cols = {n: _tiles(x) for n, x in cols.items()}
    cols.update(
        mask=_tiles(mask.astype(jnp.int32)),
        total=state.total.reshape(1, 1),
        disc_total=state.disc_total.reshape(1, 1),
        hyper=jnp.asarray(hyper, jnp.float32).reshape(1, 1))
    names = tuple(cols)
    return _call(
        functools.partial(_select_kernel, policy=policy, names=names,
                          s_round=s_round),
        [cols[n] for n in names],
        (jax.ShapeDtypeStruct((1, s_round), jnp.int32),),
        f"select_{policy}", interpret)[0][0]


@functools.partial(jax.jit, static_argnames=("policy", "s_round", "decay",
                                             "interpret", "fault",
                                             "deadline"))
def bandit_round_pallas(state, cand_idx, t_ud, t_ul, rand, hyper, *,
                        policy: str, s_round: int, decay: float = 1.0,
                        interpret: bool = True, fault: tuple | None = None,
                        deadline: float | None = None, fault_u=None):
    """Fused round on a BanditState; same contract as ref.bandit_round_ref
    (``cand_idx``: [C] sorted, >= K padding).  Returns (state, sel, rt) —
    plus the per-slot flags with the failure layer on (``deadline`` set)."""
    from repro.kernels.ref import finish_round

    k = t_ud.shape[0]
    mask = bandit_jax.cand_mask(cand_idx, k)
    with jax.named_scope(bandit_jax.SCOPE_SELECT):
        sel = select_pallas(state, mask, t_ud, t_ul, rand, hyper,
                            policy=policy, s_round=s_round,
                            interpret=interpret)
    with jax.named_scope(bandit_jax.SCOPE_FINISH):
        safe = jnp.where(sel >= 0, sel, 0)
        return finish_round(state, sel, t_ud[safe], t_ul[safe], decay=decay,
                            fault=fault, deadline=deadline, fault_u=fault_u)

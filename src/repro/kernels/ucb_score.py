"""Pallas TPU kernel: fused UCB score over K arms (clients).

At datacenter scale the MAB selector scores millions of arms per round
(cross-device FL).  The score (paper Eq. 5/6 component form)

    score_k = -(sum_k / n_k) / alpha + sqrt(log(total) / (2 * n_k))
    score_k = BIG                      where n_k == 0   (explore-first)

is elementwise over [K] state arrays — a memory-bound fusion the TPU should
do in one HBM pass.  The [K] columns ride as [K/128, 128] tiles in
(32, 128) blocks and the total as a [1, 1] block: Mosaic's native 2-D
layout, which stays legal when ``vmap`` adds a batch grid axis in front.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BIG = 1e12
LANES = 128
BLOCK = 4096        # elements per grid step: 32 x 128


def _ucb_kernel(sum_ref, n_ref, total_ref, out_ref, *, alpha: float):
    s = sum_ref[...]
    n = n_ref[...]
    total = total_ref[0, 0]
    nf = n.astype(jnp.float32)
    safe_n = jnp.maximum(nf, 1.0)
    mean = s / safe_n
    bonus = jnp.sqrt(jnp.log(jnp.maximum(total.astype(jnp.float32), 2.0))
                     / (2.0 * safe_n))
    score = -(mean / alpha) + bonus
    out_ref[...] = jnp.where(n == 0, jnp.float32(BIG), score)


@functools.partial(jax.jit, static_argnames=("alpha", "interpret"))
def ucb_scores(sums: jnp.ndarray, n_sel: jnp.ndarray, total: jnp.ndarray,
               alpha: float = 1000.0, interpret: bool = True) -> jnp.ndarray:
    """sums, n_sel: [K] for arbitrary K; total: scalar int.

    K is padded up to a multiple of BLOCK internally (padding arms have
    n == 0, so their BIG scores are sliced away before returning).
    """
    orig_k = sums.shape[0]
    pad = (-orig_k) % BLOCK
    if pad:
        sums = jnp.pad(sums, (0, pad))
        n_sel = jnp.pad(n_sel, (0, pad))
    k = sums.shape[0]
    rows = BLOCK // LANES
    out = pl.pallas_call(
        functools.partial(_ucb_kernel, alpha=alpha),
        name="ucb_scores",
        grid=(k // BLOCK,),
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((k // LANES, LANES), jnp.float32),
        interpret=interpret,
    )(sums.astype(jnp.float32).reshape(-1, LANES),
      n_sel.astype(jnp.int32).reshape(-1, LANES),
      total.reshape(1, 1).astype(jnp.int32))
    return out.reshape(k)[:orig_k]

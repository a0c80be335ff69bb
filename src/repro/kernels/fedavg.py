"""Pallas TPU kernel: weighted FedAvg combine.

Aggregation is the server-side bandwidth hot-spot: C client models x N
parameters -> one weighted sum.  For Kimi-K2 scale (1T params) this runs
per-shard; the kernel streams each [C, BLOCK] tile through VMEM once and
writes one [BLOCK] output tile (HBM traffic = (C+1)/C of the input bytes,
the roofline minimum).

Parameters ride as [C, N/128, 128] (Mosaic's native (8, 128) tiling, so
the sum over C is whole-vreg adds) and the output as [N/128, 128]; the
weights are loaded whole (C <= a few hundred) as a [C, 1, 1] block and
broadcast across the tile: Mosaic's matmul takes no 1-D operand, and a
C-row weighted sum is VPU work anyway.  Every block is at least 2-D in its
last two dims, so the kernel stays legal when ``vmap`` (the learning
engine's grid of runs) adds a batch grid axis in front.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
BLOCK = 8192        # output elements per grid step: 64 x 128


def _fedavg_kernel(x_ref, w_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)  # [C, BLOCK/128, 128]
    w = w_ref[...].astype(jnp.float32)  # [C, 1, 1]
    out_ref[...] = (x * w).sum(0).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fedavg_combine(stacked: jnp.ndarray, weights: jnp.ndarray,
                   interpret: bool = True) -> jnp.ndarray:
    """stacked: [C, N] flattened client params for arbitrary N;
    weights: [C] (should sum to 1). Returns [N].

    N is padded up to a multiple of BLOCK internally (padding lanes are
    zero, so their weighted sums are zero and are sliced away before
    returning) — same auto-pad convention as kernels/ucb_score.py.
    """
    orig_n = stacked.shape[1]
    pad = (-orig_n) % BLOCK
    if pad:
        stacked = jnp.pad(stacked, ((0, 0), (0, pad)))
    c, n = stacked.shape
    rows = BLOCK // LANES
    out = pl.pallas_call(
        _fedavg_kernel,
        name="fedavg_combine",
        grid=(n // BLOCK,),
        in_specs=[
            pl.BlockSpec((c, rows, LANES), lambda i: (0, i, 0)),
            pl.BlockSpec((c, 1, 1), lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n // LANES, LANES), stacked.dtype),
        interpret=interpret,
    )(stacked.reshape(c, n // LANES, LANES), weights.reshape(c, 1, 1))
    return out.reshape(n)[:orig_n]

"""The paper's FL protocol as a *distributed training step* on the pod mesh.

Arms = cohorts: each slice of the ``data`` axis (x ``pod`` when multi-pod)
holds one FL client's model replica and data shard.  One FL round =

  1. local steps  — every cohort runs E local SGD steps with NO cross-cohort
     communication (vmap over the stacked cohort dim, which GSPMD keeps
     local because nothing contracts over it; TP over ``model`` still works
     inside each cohort);
  2. aggregation  — masked weighted FedAvg across cohorts.  The mask comes
     from the MAB selector (core.bandit_jax): non-selected cohorts get
     weight 0 (the paper's Client Selection step).  Implemented in
     shard_map so the upload can be *compressed on the wire*: int8/top-k
     deltas all-gathered over the cohort axis instead of f32 —
     a 4x/~50x collective-byte reduction measured in the dry-run HLO.

This is the hardware adaptation documented in DESIGN.md §3: phones -> pod
slices, LTE uplink -> ICI/DCN collectives, same bandit, same FedAvg math.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from repro.distributed import compression
from repro.distributed.sharding import cohort_axes
from repro.optim.sgd import Optimizer


# ---------------------------------------------------------------------------
# local phase: E steps per cohort, no cross-cohort comm
# ---------------------------------------------------------------------------

def make_local_steps(loss_fn: Callable, opt: Optimizer, n_steps: int):
    """Returns f(params, opt_state, batches) -> (params, opt_state, loss)
    for ONE client — ``n_steps`` local SGD steps as an inner scan.

    ``loss_fn(params, batch) -> scalar``; ``batches`` is a pytree whose
    leaves are [n_steps, ...] stacked minibatches; the returned loss is the
    mean over the local steps."""

    def local(params, opt_state, batches):
        def step(carry, batch):
            p, o = carry
            loss, grads = jax.value_and_grad(loss_fn)(p, batch)
            p, o = opt.update(grads, o, p)
            return (p, o), loss

        (params, opt_state), losses = jax.lax.scan(
            step, (params, opt_state), batches)
        return params, opt_state, losses.mean()

    return local


# ---------------------------------------------------------------------------
# aggregation phase: masked weighted FedAvg across the cohort axis
# ---------------------------------------------------------------------------

def _cohort_axes(mesh: Mesh) -> tuple[str, ...]:
    return cohort_axes(mesh)      # shared with the sharding layer


def fedavg_across_cohorts(stacked_params: Any, weights: jnp.ndarray,
                          mesh: Mesh, stacked_specs: Any,
                          compress: str = "none",
                          topk_ratio: float = 0.01,
                          base_params: Any | None = None) -> Any:
    """stacked_params: pytree with leading cohort dim C (sharded over the
    cohort axes); weights: [C] (selection mask x data size, normalized).
    ``base_params`` is the pre-round global model (REPLICATED over the
    cohort axes — never sliced from the stack, which would cost a broadcast
    collective).  Returns the aggregated tree without the leading dim.

    Wire formats (collective bytes per device, measured in the dry-run HLO;
    N = per-device param shard bytes at f32, C = cohorts):
      none      — f32 all-reduce of the weighted sum        ~ 2N
      int8      — int8 all-gather of per-cohort deltas      ~ C*N/4
                  (LOSES to 'none' once C > 8 — kept as the measured
                  refutation of the obvious design; see EXPERIMENTS §Perf)
      int8_psum — shared-scale int8 quantization, weights folded into the
                  quantized values, summed in int16 on the wire  ~ N/2
      topk      — top-k(ratio) values+indices all-gather    ~ 2*C*N*ratio
    """
    ca = _cohort_axes(mesh)
    w = weights / jnp.maximum(weights.sum(), 1e-9)

    if compress == "none":
        def avg(x):
            return jnp.einsum("c...,c->...", x.astype(jnp.float32),
                              w).astype(x.dtype)
        return jax.tree.map(avg, stacked_params)

    assert base_params is not None, "compressed aggregation needs the base"
    deltas = jax.tree.map(
        lambda sp, bp: sp.astype(jnp.float32) - bp.astype(jnp.float32)[None],
        stacked_params, base_params)
    n_cohorts = jax.tree.leaves(stacked_params)[0].shape[0]

    def agg_leaf(delta, spec):
        """delta: [C, ...]; spec: PartitionSpec of the stacked leaf."""
        def block(d_local, w_full):
            # d_local: [C_local=1, ...local shard...] inside shard_map
            d = d_local[0]
            idx = jax.lax.axis_index(ca[0]) if len(ca) == 1 else (
                jax.lax.axis_index(ca[0]) * mesh.shape[ca[1]]
                + jax.lax.axis_index(ca[1]))
            my_w = w_full[idx]
            if compress == "int8":
                q, s = compression.quantize_int8(d)
                qg = jax.lax.all_gather(q, ca)          # int8 on the wire
                sg = jax.lax.all_gather(s, ca)
                parts = qg.astype(jnp.float32) * sg.reshape(
                    (-1,) + (1,) * d.ndim)
                out = jnp.einsum("c...,c->...", parts, w_full)
            elif compress == "int8_psum":
                # shared scale: max over cohorts of |w_c * d_c| (scalar
                # all-reduce), quantize w*d to int8, sum in int16 on the
                # wire (C<=256 cannot overflow), dequantize once.
                wd = my_w * d
                local_max = jnp.max(jnp.abs(wd))
                gmax = jax.lax.pmax(local_max, ca) + 1e-12
                scale = gmax / 127.0
                q = jnp.clip(jnp.round(wd / scale), -127, 127
                             ).astype(jnp.int16)
                total = jax.lax.psum(q, ca)              # int16 on the wire
                out = total.astype(jnp.float32) * scale
            else:                                        # topk
                vals, idx_ = compression.topk_compress(d, topk_ratio)[:2]
                vg = jax.lax.all_gather(vals, ca)        # [C, k]
                ig = jax.lax.all_gather(idx_, ca)
                parts = jax.vmap(
                    lambda v, i: compression.topk_decompress(
                        v, i, d.size, d.shape))(vg, ig)
                out = jnp.einsum("c...,c->...", parts, w_full)
            return out[None]

        in_spec = P(*((ca,) + tuple(spec)[1:]))
        # the block's output is identical on every cohort rank (post
        # all-gather/psum), so the out spec drops the cohort axis — keeping
        # it on the size-1 dim forces a 0.4 GB resharding all-reduce when
        # [0] is sliced afterwards (measured; EXPERIMENTS §Perf).
        out_spec = P(*((None,) + tuple(spec)[1:]))
        res = shard_map(
            block, mesh=mesh,
            in_specs=(in_spec, P()),
            out_specs=out_spec,
            check_vma=False,
        )(delta, w)
        return res[0]          # drop the collapsed cohort dim

    avg_delta = jax.tree.map(agg_leaf, deltas, stacked_specs)
    return jax.tree.map(
        lambda bp, d: (bp.astype(jnp.float32) + d).astype(bp.dtype),
        base_params, avg_delta)


# ---------------------------------------------------------------------------
# the full FL round
# ---------------------------------------------------------------------------

def make_fl_round(loss_fn: Callable, opt: Optimizer, n_local_steps: int,
                  mesh: Mesh, stacked_specs: Any,
                  compress: str = "none", topk_ratio: float = 0.01):
    """Builds fl_round(global_params, stacked_opt, batches, weights)
    -> (new_global_params, new_stacked_opt, mean_loss).

    ``global_params`` is the single (replicated-over-cohort-axes) model:
    the Distribution step is the in-round stack broadcast (a local slice,
    no collective), and aggregation deltas are taken against it directly —
    passing a stacked model and slicing cohort 0 instead costs a ~1.3 GB
    broadcast collective per round (measured; see EXPERIMENTS §Perf).
    ``weights`` [C] = selection_mask * n_samples: zeros drop a cohort (the
    paper's Client Selection step).
    """
    local = make_local_steps(loss_fn, opt, n_local_steps)

    def fl_round(global_params, stacked_opt, batches, weights):
        c = jax.tree.leaves(batches)[0].shape[0]
        stacked = stack_for_cohorts(global_params, c)
        new_p, new_o, losses = jax.vmap(local)(stacked, stacked_opt, batches)
        agg = fedavg_across_cohorts(new_p, weights, mesh, stacked_specs,
                                    compress=compress, topk_ratio=topk_ratio,
                                    base_params=global_params
                                    if compress != "none" else None)
        w = weights / jnp.maximum(weights.sum(), 1e-9)
        mean_loss = jnp.sum(losses * w)
        return agg, new_o, mean_loss

    return fl_round


def stack_for_cohorts(tree: Any, n_cohorts: int) -> Any:
    """Replicate a single model into the [C, ...] stacked layout."""
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n_cohorts,) + x.shape), tree)


def stacked_param_specs(pspecs: Any, mesh: Mesh) -> Any:
    """Prepend the cohort axes to every per-leaf PartitionSpec."""
    ca = _cohort_axes(mesh)
    return jax.tree.map(lambda s: P(*((ca,) + tuple(s))), pspecs,
                        is_leaf=lambda x: isinstance(x, P))

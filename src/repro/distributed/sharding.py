"""Mesh/shard_map orchestration layer + PartitionSpec rules.

Two halves, one device model:

1. **Sweep-grid orchestration** (``sweep_mesh`` / ``shard_vmapped`` /
   ``shard_leading`` / ``pad_leading``) — the generic layer both on-device
   engines (sim/engine_jax.py, fl/engine.py) use to scale past one device.
   A sweep is an embarrassingly parallel vmap over a flattened grid axis
   (policy is unrolled statically; eta x seed / seed is the vmapped axis),
   so the layer offers two shardings:

     * ``shard="grid"``  — split the *grid* axis over a 1-D mesh with
       ``shard_map`` (each device runs the identical vmapped program on its
       slice; results concatenate, so sharded == single-device exactly);
     * ``shard="clients"`` — commit the *client* axis (K) of the per-client
       state (UCB stats, ring buffers, resource draws, data shards) to a
       ``NamedSharding`` and let GSPMD partition the whole scan — the
       large-K layout, where one device cannot hold [R, K] draws or K model
       replicas.

   CPU hosts get the same code path via
   ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (see
   ``host_device_flag``), which is how CI proves sharded == single-device.

2. **Model-param PartitionSpec rules** (``param_specs`` and friends) for
   every model family in models/.

Rules are matched against flattened param paths and applied *from the right*
(trailing dims), so stacked leading layer/group dims are automatically
unsharded.  ``fsdp=True`` additionally shards one non-TP weight dim over the
data axis (ZeRO-3 style); the pod axis stays pure-DP/cohort.

Every proposed axis is divisibility-guarded against the actual dim size —
e.g. seamless-m4t's vocab 256206 is not divisible by 16, so its embedding
stays replicated rather than padding the published config.

TP choices (Megatron-style):
  * column-parallel: wq/wk/wv, mlp w_gate/w_up   -> last dim 'model'
  * row-parallel:    wo, mlp w_down              -> 2nd-last dim 'model'
  * experts:         leading E dim 'model' (expert parallelism)
  * embeddings/unembed: vocab dim 'model'
  * norms/scalars: replicated
  * xlstm mLSTM: value/output-channel sharding (only 4 heads < 16, so the
    dh axis is the TP axis, not the head axis)
  * dense KV caches: batch over 'data', *sequence* over 'model'
    (flash-decoding-style split-KV; softmax over the sharded S lowers to
    all-reduce of max/sum)
"""

from __future__ import annotations

import re
from typing import TYPE_CHECKING, Any, Callable, Sequence

import jax
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.launch.mesh import batch_axes

if TYPE_CHECKING:       # annotation-only: keep this module import-light
    from repro.models.layers import LMConfig


# ---------------------------------------------------------------------------
# Sweep-grid orchestration (the layer both on-device engines build on).
# ---------------------------------------------------------------------------

SWEEP_AXIS = "grid"     # the one mesh axis of a sweep mesh


def host_device_flag(n: int) -> str:
    """The XLA flag that splits a CPU host into ``n`` virtual devices.

    Must be in ``XLA_FLAGS`` *before* jax initializes — tests/CI export it,
    subprocess harnesses inject it into the child environment.
    """
    return f"--xla_force_host_platform_device_count={n}"


def sweep_mesh(n_devices: int | None = None,
               axis_name: str = SWEEP_AXIS) -> Mesh:
    """1-D mesh over the first ``n_devices`` devices (all, when None).

    The single axis carries either the sweep-grid dimension
    (``shard_vmapped``) or the client dimension (``shard_leading``),
    depending on which sharding mode the caller picks.
    """
    devs = jax.devices()
    n = len(devs) if n_devices is None else int(n_devices)
    if not 1 <= n <= len(devs):
        raise ValueError(f"asked for {n} devices, have {len(devs)}")
    return Mesh(np.asarray(devs[:n]), (axis_name,))


def pad_leading(x: np.ndarray, multiple: int) -> np.ndarray:
    """Edge-pad the leading axis up to a multiple of ``multiple`` (host-side;
    shard_map needs the global axis divisible by the mesh).  The caller
    slices the padded tail off the result."""
    pad = (-x.shape[0]) % multiple
    if pad == 0:
        return x
    return np.concatenate([x, np.repeat(x[-1:], pad, axis=0)], axis=0)


def shard_vmapped(vm_fn: Callable, mesh: Mesh,
                  sharded_argnums: Sequence[int],
                  axis_name: str = SWEEP_AXIS) -> Callable:
    """Split an already-vmapped function's leading grid axis over ``mesh``.

    ``vm_fn(*args)`` must be a vmapped computation whose args listed in
    ``sharded_argnums`` carry the grid as their leading axis (divisible by
    the mesh size — see ``pad_leading``) and whose outputs all carry it as
    theirs; every other arg is replicated.  Each device runs the identical
    per-grid-point program on its slice with no collectives, so the result
    equals the unsharded vmap exactly.
    """
    sharded = set(sharded_argnums)

    def wrapper(*args):
        in_specs = tuple(P(axis_name) if i in sharded else P()
                         for i in range(len(args)))
        return shard_map(vm_fn, mesh=mesh, in_specs=in_specs,
                         out_specs=P(axis_name), check_vma=False)(*args)
    return wrapper


def shard_leading(tree: Any, mesh: Mesh,
                  axis_name: str = SWEEP_AXIS) -> Any:
    """Commit every array leaf of ``tree`` to ``mesh`` with its *leading*
    dim sharded over ``axis_name`` (rest replicated) — the client-axis
    layout: [K]-leading state/data arrays spread over devices, GSPMD
    partitions the consuming scan around them."""
    def leaf(x):
        spec = P(axis_name, *([None] * (np.ndim(x) - 1)))
        return jax.device_put(x, NamedSharding(mesh, spec))
    return jax.tree.map(leaf, tree)


def even_shards(k: int, mesh: Mesh | None) -> int | None:
    """K/P when ``mesh`` splits the client axis K evenly (the contiguous
    block size of ``shard_leading``: shard p owns global clients
    [p*K/P, (p+1)*K/P)), else None.  The routing predicate of the
    client-sharded segmented sweep path (sim/engine_jax.py) — an uneven K
    falls back to the GSPMD constraint layout, never to wrong answers."""
    if mesh is None:
        return None
    return k // mesh.size if k % mesh.size == 0 else None


def bandit_state_bytes(k: int, n_devices: int = 1) -> int:
    """Bytes of bandit state resident PER DEVICE at K clients sharded over
    ``n_devices`` contiguous ``shard_leading`` blocks: the [K]-leading
    leaves split K/n_devices per device, the scalar counters replicate.
    Computed from the real core.bandit_jax.BanditState field layout via
    ``eval_shape`` (no allocation), so it tracks the dataclass; the
    headroom benchmark (benchmarks/bench_sharded_sweep.py) records it next
    to each probed K."""
    from repro.core.bandit_jax import BanditState
    shaped = jax.eval_shape(lambda: BanditState.create(k))
    total = 0
    for leaf in jax.tree_util.tree_leaves(shaped):
        nbytes = int(np.prod(leaf.shape, dtype=np.int64)) * leaf.dtype.itemsize
        total += nbytes if leaf.ndim == 0 else -(-nbytes // n_devices)
    return total


def replicate(tree: Any, mesh: Mesh) -> Any:
    """Commit every array leaf of ``tree`` to ``mesh`` fully replicated."""
    return jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P())), tree)


def cohort_axes(mesh: Mesh) -> tuple[str, ...]:
    """Mesh axes that enumerate FL cohorts in the pod runtime: ``pod`` (when
    present) and ``data`` — shared by fl_parallel.py and the dry-run
    tooling."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


# ---------------------------------------------------------------------------
# Model-param PartitionSpec rules.
# ---------------------------------------------------------------------------

def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        n = 1
        for a in axis:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axis]


def _guarded(spec: tuple, shape: tuple, mesh: Mesh) -> P:
    """Drop any axis that does not evenly divide its dim."""
    out = []
    for dim, axis in zip(shape, spec):
        if axis is not None and dim % _axis_size(mesh, axis) == 0 and dim > 1:
            out.append(axis)
        else:
            out.append(None)
    return P(*out)


def _from_right(right: tuple, ndim: int) -> tuple:
    right = tuple(right)
    if ndim < len(right):
        right = right[-ndim:]
    return (None,) * (ndim - len(right)) + right


# rules: (regex on path, spec-from-right). First match wins.
def _param_rules(fsdp: bool) -> list[tuple[str, tuple | None]]:
    d = "data" if fsdp else None
    return [
        # --- MoE experts: [.., E, D, F] / [.., E, F, D]
        (r"moe/shared/w_(gate|up)$", (d, "model")),
        (r"moe/shared/w_down$", ("model", d)),
        (r"moe/.*w_(gate|up)$", ("model", d, None)),
        (r"moe/.*w_down$", ("model", None, d)),
        (r"moe/router$", (None, None)),
        # --- xlstm (before generic attn/mlp rules)
        (r"mlstm/.*w_if$", (None, None)),
        (r"mlstm/.*w_[qk]$", (d, None)),
        (r"mlstm/.*w_v$", (d, "model")),
        (r"slstm", None),              # replicated (tiny, sequential cell)
        # --- attention
        (r"(attn|self_attn|cross_attn)/wq$", (d, "model")),
        (r"wkv$", (d, "model")),
        (r"(attn|self_attn|cross_attn)/w[kv]$", (d, "model")),
        (r"(attn|self_attn|cross_attn)/wo$", ("model", d)),
        (r"[qk]_norm$", (None,)),
        # --- gated MLPs (dense mlp, mlstm up/gate, griffin w_gate)
        (r"w_(gate|up)$", (d, "model")),
        (r"w_down$", ("model", d)),
        # --- embeddings
        (r"embed/tok$", ("model", None)),
        (r"unembed$", (None, "model")),
        (r"patch_proj$", (None, "model")),
        # --- griffin recurrent block
        (r"w_x$", (d, "model")),
        (r"w_[ri]$", (None, "model")),
        (r"lam$", ("model",)),
        (r"w_out$", ("model", d)),
        (r"conv$", (None, "model")),
        (r"w_in$", (d, None)),
        # --- norms and anything else
        (r"(norm|bias|scale)", None),
    ]


def spec_for_leaf(path_s: str, shape: tuple, rules, mesh: Mesh) -> P:
    """Resolve one param leaf (flattened ``path_s``, ``shape``) against the
    rule table: first regex match wins, the spec is applied from the right
    and divisibility-guarded; no match => replicated."""
    for pat, right in rules:
        if re.search(pat, path_s):
            if right is None:
                return P()
            return _guarded(_from_right(right, len(shape)), shape, mesh)
    return P()          # default: replicated (safe)


def param_specs(param_shapes: Any, cfg: "LMConfig", mesh: Mesh,
                fsdp: bool = False) -> Any:
    """PartitionSpec tree for a model's params.

    ``param_shapes`` is any pytree of shaped leaves (``jax.eval_shape``
    output or real params); ``fsdp`` additionally shards one non-TP dim
    over the data axis.  Returns a spec tree mirroring ``param_shapes``
    (see the module docstring for the rule table)."""
    rules = _param_rules(fsdp)

    def leaf(path, x):
        return spec_for_leaf(_path_str(path), tuple(x.shape), rules, mesh)

    return jax.tree_util.tree_map_with_path(leaf, param_shapes)


def cache_specs(cache_shapes: Any, cfg: "LMConfig", mesh: Mesh) -> Any:
    """Decode caches / recurrent states (see module docstring)."""
    ba = batch_axes(mesh)

    def leaf(path, x):
        s = _path_str(path)
        nd = len(x.shape)
        shape = tuple(x.shape)
        if re.search(r"(^|/)(k|v)$", s) and nd == 5:      # [L,B,S,KV,dh]
            return _guarded((None, ba, "model", None, None), shape, mesh)
        if re.search(r"(^|/)(k|v)$", s) and nd == 4:      # [B,Wnd,KV,dh] ring
            return _guarded((ba, "model", None, None), shape, mesh)
        if s.endswith("enc_out"):                          # [B,S,D]
            return _guarded((ba, None, None), shape, mesh)
        if "mlstm" in s and nd == 6:                       # C [G,7,B,H,dh,dh]
            return _guarded((None, None, ba, None, None, "model"), shape, mesh)
        if "mlstm" in s and nd == 5:                       # n / conv_buf
            return _guarded((None, None, ba, None, "model"), shape, mesh)
        # generic recurrent state: shard last dim on model when divisible
        spec = [None] * nd
        if nd >= 2 and shape[-1] >= 16:
            spec[-1] = "model"
        return _guarded(tuple(spec), shape, mesh)

    return jax.tree_util.tree_map_with_path(leaf, cache_shapes)


def batch_specs(input_shapes: dict, mesh: Mesh) -> dict:
    """Input-batch specs: leading (batch) dim over the data/pod axes,
    everything else replicated.  ``input_shapes`` is a pytree of shaped
    leaves; returns a mirroring spec tree."""
    ba = batch_axes(mesh)

    def leaf(path, x):
        nd = len(x.shape)
        return _guarded((ba,) + (None,) * (nd - 1), tuple(x.shape), mesh)

    return jax.tree_util.tree_map_with_path(leaf, input_shapes)


def opt_specs(opt_shapes: Any, pspecs: Any) -> Any:
    """Optimizer state: moments inherit the param specs; scalars replicate.

    ``opt_shapes`` is the eval_shape of Optimizer.init; its {'m','v','mu'}
    subtrees are param-shaped."""
    def build(subtree):
        return jax.tree.map(lambda s: s, pspecs)

    out = {}
    for k, v in opt_shapes.items():
        if k in ("m", "v", "mu"):
            out[k] = jax.tree.map(lambda s: s, pspecs)
        else:
            out[k] = jax.tree.map(lambda _: P(), v)
    return out


def to_named(tree_specs: Any, mesh: Mesh) -> Any:
    """Bind a PartitionSpec tree to ``mesh``: every P leaf becomes a
    ``NamedSharding`` usable as jit in/out shardings or device_put target."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs,
                        is_leaf=lambda x: isinstance(x, P))

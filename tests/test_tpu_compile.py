"""AOT compiles of the main-path Pallas kernels for a described TPU v5e.

The TPU compiler is installed without a chip; it compiles for a topology
that is described, not attached, and refuses what the chip would refuse
(ops Mosaic cannot lower, more VMEM than a kernel may use).  Interpret
mode accepts all of those, so these compiles are the guard that keeps the
kernels runnable on the chip.  Nothing here runs: a passing compile says
nothing about results or speed.

Kernels are compiled alone and under ``vmap`` (the engines run every
grid point of a sweep through one vmapped round, which gives each kernel
a batch grid axis and batched block shapes), and the engines' whole sweep
programs are compiled as the chip runs them (``tpu_routes``).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  All such compiles live in this one file so one worker loads it.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import bandit_jax
from repro.kernels import fedavg, ops, ucb_score
from repro.kernels.bandit_round import topk_slots_pallas
from repro.sim import engine_jax

S_ROUND = 5


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for a described chip cannot be read back from a persistent
    # cache without the chip: keep the cache off around them
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _state(sharding, k):
    st = jax.eval_shape(lambda: bandit_jax.BanditState.create(k))
    return jax.tree.map(lambda x: _spec(sharding, x.shape, x.dtype), st)


def _compile(fn, *args):
    """Compile for the described chip; the program must hold a Mosaic
    kernel (an interpret-mode body would lower to plain HLO)."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _batched(sharding, spec, g=3):
    """``spec`` with a leading grid axis of ``g`` runs."""
    return _spec(sharding, (g,) + spec.shape, spec.dtype)


class _Compiled(Exception):
    """Carries the text of a program compiled in place of running it."""


@pytest.fixture
def tpu_routes(monkeypatch, one_chip):
    """Route the engines as on the chip (Pallas kernels, compiled) and
    return ``compile_only(module, name)``, which swaps that module's jit
    entry for a compile of the same call for the described chip that
    raises :class:`_Compiled`.  The jit caches are cleared around it, so
    no trace crosses between the chip's routes and the CPU's."""
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    monkeypatch.setattr(
        ops, "_route_kernel",
        lambda use, k: k <= ops.KERNEL_MAX_K if use is None else use)
    jax.clear_caches()

    def compile_only(module, name):
        fn = getattr(module, name)

        def compile_call(*args, **static):
            args = jax.tree.map(
                lambda x: _spec(one_chip, x.shape, x.dtype), args)
            raise _Compiled(fn.lower(*args, **static).compile().as_text())
        monkeypatch.setattr(module, name, compile_call)

    yield compile_only
    jax.clear_caches()


@pytest.mark.parametrize("k", [100, 10_000])
@pytest.mark.parametrize("policy", ["elementwise_ucb", "naive_ucb"])
def test_round_kernel_compiles(one_chip, policy, k):
    fn = bandit_jax.make_round_fn(policy, S_ROUND, use_kernel=True,
                                  interpret=False)
    _compile(fn, _state(one_chip, k), _spec(one_chip, (k // 10,), jnp.int32),
             _spec(one_chip, (2,), jnp.uint32), _spec(one_chip, (k,)),
             _spec(one_chip, (k,)), _spec(one_chip, ()))


@pytest.mark.parametrize("policy", ["elementwise_ucb", "naive_ucb"])
def test_round_kernel_compiles_vmapped(one_chip, policy):
    """The round as the engines run it: vmapped over a grid of runs."""
    k = 100
    fn = bandit_jax.make_round_fn(policy, S_ROUND, use_kernel=True,
                                  interpret=False)
    args = (_state(one_chip, k), _spec(one_chip, (k // 10,), jnp.int32),
            _spec(one_chip, (2,), jnp.uint32), _spec(one_chip, (k,)),
            _spec(one_chip, (k,)), _spec(one_chip, ()))
    _compile(jax.vmap(fn), *jax.tree.map(
        lambda a: _batched(one_chip, a), args))


def test_round_kernel_with_deadline_compiles(one_chip):
    k = 10_000
    fn = bandit_jax.make_round_fn("elementwise_ucb", S_ROUND,
                                  use_kernel=True, interpret=False,
                                  fault=(0.1, 0.05, 0.01), deadline=50.0)
    _compile(fn, _state(one_chip, k), _spec(one_chip, (k // 10,), jnp.int32),
             _spec(one_chip, (2,), jnp.uint32), _spec(one_chip, (k,)),
             _spec(one_chip, (k,)), _spec(one_chip, ()))


def _sampled_args(sharding, k):
    return (_state(sharding, k), _spec(sharding, (k // 10,), jnp.int32),
            _spec(sharding, (2,), jnp.uint32),
            _spec(sharding, (2,), jnp.uint32), _spec(sharding, (k,)),
            _spec(sharding, (k,)), _spec(sharding, (k,)),
            _spec(sharding, ()), _spec(sharding, ()), _spec(sharding, ()))


def test_sampled_round_kernel_compiles(one_chip):
    """The streamed sampler's kernel route (ops.bandit_round_sampled:
    [C] Eq. 8 draw, spread into [K], whole-state selection kernel)."""
    fn = bandit_jax.make_sampled_round_fn("elementwise_ucb", S_ROUND,
                                          use_kernel=True, interpret=False)
    _compile(fn, *_sampled_args(one_chip, 10_000))


def test_sampled_round_kernel_compiles_at_kernel_max_k(one_chip):
    """The routing bound ops.KERNEL_MAX_K holds for the widest selection
    (sliding_ucb: four statistic columns plus the mask)."""
    fn = bandit_jax.make_sampled_round_fn("sliding_ucb", S_ROUND,
                                          use_kernel=True, interpret=False)
    _compile(fn, *_sampled_args(one_chip, ops.KERNEL_MAX_K))


def _maybe_vmap(one_chip, fn, args, vmapped):
    if vmapped:
        return _compile(jax.vmap(fn), *[_batched(one_chip, a) for a in args])
    return _compile(fn, *args)


@pytest.mark.parametrize("vmapped", [False, True])
def test_topk_slots_kernel_compiles(one_chip, vmapped):
    # the segmented round's per-shard top-S runs over the [C] candidate
    # slice: C = K/10 = 10^5 at the four-chip K = 10^6 layout
    c = 100_000
    _maybe_vmap(one_chip,
                lambda s, v: topk_slots_pallas(s, v, S_ROUND,
                                               interpret=False),
                [_spec(one_chip, (c,)), _spec(one_chip, (c,), jnp.int32)],
                vmapped)


@pytest.mark.parametrize("vmapped", [False, True])
def test_ucb_scores_kernel_compiles(one_chip, vmapped):
    k = 10_000
    _maybe_vmap(one_chip,
                lambda s, n, t: ucb_score.ucb_scores(s, n, t,
                                                     interpret=False),
                [_spec(one_chip, (k,)), _spec(one_chip, (k,), jnp.int32),
                 _spec(one_chip, (), jnp.int32)], vmapped)


@pytest.mark.parametrize("vmapped", [False, True])
def test_fedavg_kernel_compiles_at_paper_cnn(one_chip, vmapped):
    from repro.models import cnn
    params = jax.eval_shape(lambda: cnn.init(jax.random.PRNGKey(0),
                                             cnn.CnnConfig()))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    assert n == 4_583_146
    _maybe_vmap(one_chip,
                lambda x, w: fedavg.fedavg_combine(x, w, interpret=False),
                [_spec(one_chip, (5, n)), _spec(one_chip, (5,))], vmapped)


@pytest.mark.parametrize("k", [100, 10_000])
def test_sweep_program_compiles(tpu_routes, k):
    """``engine_jax.sweep``'s whole jit program as the chip runs it: the
    fused round's kernel vmapped over the (eta x seed) grid inside the
    round scan (K = 100: plain kernel route; K = 10^4: streamed sampler
    and the sampled route)."""
    tpu_routes(engine_jax, "_run_grid")
    with pytest.raises(_Compiled) as compiled:
        engine_jax.sweep(n_clients=k, policies=("elementwise_ucb",
                                                "naive_ucb"),
                         etas=(1.5,), seeds=2, n_rounds=4)
    assert "tpu_custom_call" in compiled.value.args[0]


def test_accuracy_sweep_program_compiles(tpu_routes):
    """``fl.engine.accuracy_sweep``'s whole jit program with the fedavg
    kernel, vmapped over the seed grid (a narrow CNN keeps the compile
    short; the kernel's width is covered above)."""
    from repro.fl import engine as fl_engine
    from repro.models import cnn
    cfg = cnn.CnnConfig(image_size=8, channels=(8,), pool_after=(0,),
                        fc_units=(16,))
    task = fl_engine.make_cnn_task(n_clients=12, cfg=cfg, n_train=600,
                                   n_test=400, eval_batch=200,
                                   max_samples=40, batch_size=10)
    tpu_routes(fl_engine, "_run_grid")
    with pytest.raises(_Compiled) as compiled:
        fl_engine.accuracy_sweep(task=task, policies=("elementwise_ucb",),
                                 seeds=2, n_rounds=2, s_round=3, cfg=cfg,
                                 epochs=1, batch_size=10, use_kernel=True)
    assert "fedavg_combine" in compiled.value.args[0]

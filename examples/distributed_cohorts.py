"""The paper's protocol at pod scale: shard_map FL cohorts on an 8-device
mesh (4 cohorts x 2-way tensor parallel), MAB-masked FedAvg aggregation
with int8-compressed uploads.

Must run as its own process (it forces 8 host devices):

  PYTHONPATH=src python examples/distributed_cohorts.py
"""

import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.utils.compat import use_compile_cache
from repro.core import bandit_jax
from repro.distributed import fl_parallel, sharding
from repro.models.registry import build
from repro.optim.sgd import OptimizerConfig


def main() -> None:
    use_compile_cache()
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(4, 2),
                ("data", "model"))
    C = 4
    api = build("smollm-135m", reduced=True)
    cfg = api.cfg
    params = api.init(jax.random.PRNGKey(0))
    opt = OptimizerConfig(name="sgd", lr=0.05, lr_decay=0.0).build()

    pspecs = sharding.param_specs(jax.eval_shape(lambda: params), cfg, mesh)
    sspecs = fl_parallel.stacked_param_specs(pspecs, mesh)
    opt_state = jax.vmap(opt.init)(fl_parallel.stack_for_cohorts(params, C))

    fl_round = jax.jit(fl_parallel.make_fl_round(
        api.loss_fn, opt, n_local_steps=2, mesh=mesh, stacked_specs=sspecs,
        compress="int8"))

    # MAB selector over the 4 cohorts
    state = bandit_jax.BanditState.create(C)
    rng = np.random.default_rng(0)
    n_samples = jnp.asarray([1.0, 2.0, 1.5, 0.5])

    print(f"mesh {dict(mesh.shape)} — {C} cohorts x TP2, int8 uploads\n")
    for rnd in range(5):
        sel = bandit_jax.select_elementwise(
            state, jnp.arange(C), s_round=2, beta=50.0)
        mask = jnp.zeros(C).at[jnp.maximum(sel, 0)].set(
            (sel >= 0).astype(jnp.float32))
        weights = mask * n_samples
        batches = {"tokens": jnp.asarray(
            rng.integers(0, cfg.vocab, (C, 2, 4, 16)), jnp.int32)}
        params, opt_state, loss = fl_round(params, opt_state, batches,
                                           weights)
        # observe simulated round times as rewards
        t_ud = jnp.asarray(rng.uniform(1, 10, C), jnp.float32)
        t_ul = jnp.asarray(rng.uniform(5, 50, C), jnp.float32)
        sel_v = sel[sel >= 0]
        state = bandit_jax.observe(state, sel_v, t_ud[sel_v], t_ul[sel_v],
                                   t_ud[sel_v] + 2 * t_ul[sel_v])
        print(f"round {rnd}: selected cohorts {sel_v.tolist()}, "
              f"loss {float(loss):.4f}")
    print("\ncohort models stay in sync; selection policy is on-device.")


if __name__ == "__main__":
    main()

"""Smoke run of the system's main path on the TPU it was written for.

    python chip_smoke.py              # one chip: the five phases below
    python chip_smoke.py --chips 4    # four chips: the multi-chip layouts

One chip, in order, in this one process:

  1. device    — the first JAX device must be a TPU;
  2. paper     — ``engine_jax.sweep("paper-baseline")`` at its defaults
                 (8 policies x 3 etas x 8 seeds x 500 rounds, K=100,
                 S=5), fused (Pallas) vs unfused (XLA), plus a round-level
                 check of selections and state, all 8 policies;
  3. k1e4      — the streamed sampler at K=10^4 flat, 8 policies, 2 seeds,
                 100 rounds, fused vs unfused, plus the round-level check;
  4. learning  — ``fl.engine.accuracy_sweep`` on the paper CNN (4.58 M
                 parameters) at K=100, S=5, 50k/10k synthetic CIFAR-shaped
                 images, 5 local epochs of batch 50, 3 rounds, with the
                 fedavg kernel vs the jnp aggregation;
  5. serve     — ``async_engine.serve("diurnal-drift", "discounted_ucb",
                 n_ticks=500)`` at K=100.

Four chips (``--chips 4``), and nothing else: the client-sharded segmented
sweep at K=2^16 against the one-chip flat run (round-level selections and
final bandit state bitwise, sweep times), K=10^6 segmented over four chips
in chunks of 25 rounds, and ``shard="grid"`` against one device (the paper
grid's sweep times, and round-level selections).

Every phase prints one JSON line: the route each Pallas kernel took, the
parity result, ``peak_bytes_in_use`` and two smoke timings (the first call,
which includes compilation, and a second, steady call; both end in host
arrays).  They are smoke timings, not benchmark results.  Any failed check
raises and the script exits non-zero; the last line,
``{"ok": true, "device": {...}}``, is printed only when every phase passed.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.utils.compat import use_compile_cache  # noqa: E402

TIME_RTOL = 1e-5        # fused vs unfused on one chip
GRID_RTOL = 1e-4        # grid-sharded vs one device (the test suite's bound)
# the large-K four-chip phases compile one program per policy: one greedy
# and one score policy cover both segmented selection paths
FOUR_CHIP_POLICIES = ("elementwise_ucb", "naive_ucb")


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# Pallas routes: every pallas_call traced records its kernel name and mode
# ---------------------------------------------------------------------------

TRACED: list[tuple[str, bool]] = []


def record_pallas_calls() -> None:
    from jax.experimental import pallas as pl
    inner = pl.pallas_call

    def pallas_call(kernel, *args, interpret=False, name=None, **kw):
        TRACED.append((name or getattr(kernel, "__name__", repr(kernel)),
                       bool(interpret)))
        return inner(kernel, *args, interpret=interpret, name=name, **kw)

    pl.pallas_call = pallas_call


def routes_since(mark: int) -> dict:
    calls = TRACED[mark:]
    check(not any(i for _, i in calls),
          f"a Pallas kernel ran in interpret mode: {calls}")
    return {"pallas_compiled": sorted({n for n, _ in calls})}


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def timed(fn):
    """(first result, first-call s, steady s): two calls, each ending in
    host arrays or block_until_ready."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    return out, first, time.perf_counter() - t0


def compare_times(a, b, rtol: float, what: str) -> dict:
    """Bitwise, or within ``rtol`` relative with the largest difference."""
    import numpy as np
    a, b = np.asarray(a), np.asarray(b)
    check(a.shape == b.shape, f"{what}: shapes {a.shape} != {b.shape}")
    check(bool(np.isfinite(a).all() and np.isfinite(b).all()),
          f"{what}: non-finite values")
    if np.array_equal(a, b):
        return {what: "bitwise"}
    a64, b64 = a.astype(np.float64), b.astype(np.float64)
    rel = float(np.max(np.abs(a64 - b64) / np.maximum(np.abs(b64), 1e-30)))
    check(rel <= rtol, f"{what}: max relative difference {rel!r} > {rtol}")
    return {what: f"within {rtol}", f"{what}_max_rel_diff": rel}


def peak_bytes(device=None):
    import jax
    stats = (device or jax.devices()[0]).memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def round_parity(k: int, n_req: int, n_rounds: int, policies, *,
                 s_round: int = 5, seed: int = 0) -> dict:
    """Round-level check on one device: the fused round with the Pallas
    kernel forced (``use_kernel=True``) against the unfused XLA pipeline
    (``sweep(fused=False)``'s round) on identical streamed draws.
    Selections must be equal; round times and the final BanditState
    bitwise, or within TIME_RTOL with the difference reported."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import bandit_jax
    from repro.sim import engine_jax

    rng = np.random.default_rng(seed)
    theta = jnp.asarray(rng.uniform(1e6, 1e7, k), jnp.float32)
    gamma = jnp.asarray(rng.uniform(10.0, 100.0, k), jnp.float32)
    n_s = jnp.asarray(rng.uniform(100.0, 1000.0, k), jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(seed), 3 * n_rounds)
    keys = keys.reshape((n_rounds, 3) + keys.shape[1:])
    eta, bits = 1.5, 1.46e8
    out = {}
    for policy in policies:
        hyper = jnp.float32(bandit_jax.DEFAULT_HYPERS[policy])
        fused_fn = bandit_jax.make_sampled_round_fn(policy, s_round,
                                                    use_kernel=True)
        select_fn = bandit_jax.make_select_fn(policy, s_round)
        decay = bandit_jax.policy_decay(policy)

        def run(fused):
            def step(state, ks):
                kc, kp, kt = ks
                cand = engine_jax._cand_topk_one(kc, k, n_req)
                if fused:
                    st, sel, rt = fused_fn(state, cand, kp, kt, theta, gamma,
                                           n_s, eta, bits, hyper)
                    return st, (sel, rt)
                t_ud_c, t_ul_c = engine_jax.sample_times_candidates(
                    kt, cand, n_s, theta, gamma, eta, bits)
                t_ud, t_ul, mask = bandit_jax.scatter_cand_times(
                    cand, t_ud_c, t_ul_c, k)
                st, rt, sel = engine_jax._round(state, mask, t_ud, t_ul,
                                                select_fn, hyper, kp,
                                                decay=decay)
                return st, (sel, rt)
            state, (sels, rts) = jax.lax.scan(
                step, bandit_jax.BanditState.create(k), keys)
            return dataclasses.asdict(state), sels, rts

        sf, sel_f, rt_f = jax.tree.map(np.asarray, jax.jit(
            lambda: run(True))())
        su, sel_u, rt_u = jax.tree.map(np.asarray, jax.jit(
            lambda: run(False))())
        check(np.array_equal(sel_f, sel_u),
              f"K={k} {policy}: fused (Pallas) selections != unfused")
        res = {"selections": "equal"}
        res.update(compare_times(rt_f, rt_u, TIME_RTOL, "round_times"))
        state = {}
        for name in sf:
            if np.issubdtype(sf[name].dtype, np.integer):
                check(np.array_equal(sf[name], su[name]),
                      f"K={k} {policy}: state.{name} differs")
            else:
                state.update(compare_times(sf[name], su[name], TIME_RTOL,
                                           name))
        # only the fields that are not bitwise are named
        res["state"] = {n: v for n, v in state.items()
                        if v != "bitwise"} or "bitwise"
        out[policy] = res
    return out


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------

def phase_sweep(name: str, check_rounds: int = 50, **kw) -> None:
    """``sweep`` fused vs unfused, plus the round-level check at the
    same K; the sweep's own routes are recorded from the fused run."""
    from repro.core import bandit_jax
    from repro.sim import engine_jax

    mark = len(TRACED)
    fused, first, steady = timed(lambda: engine_jax.sweep(fused=True, **kw))
    routes = routes_since(mark)
    unfused = engine_jax.sweep(fused=False, **kw)
    parity = compare_times(fused.round_times, unfused.round_times,
                           TIME_RTOL, "sweep_round_times")
    parity.update(compare_times(fused.elapsed, unfused.elapsed, TIME_RTOL,
                                "sweep_elapsed"))
    k = kw.get("n_clients", 100)
    mark = len(TRACED)
    parity["rounds"] = round_parity(
        k, math.ceil(0.1 * k), check_rounds, bandit_jax.POLICY_NAMES)
    parity["rounds_routes"] = routes_since(mark)
    report(name, k=k, grid=list(fused.round_times.shape), routes=routes,
           smoke_first_call_s=first, smoke_steady_s=steady,
           parity=parity, peak_bytes_in_use=peak_bytes())


def phase_learning(cfg=None, n_train: int = 50_000, n_test: int = 10_000,
                   epochs: int = 5, n_rounds: int = 3) -> None:
    import jax
    import numpy as np

    from repro.fl import engine
    from repro.models import cnn

    cfg = cfg or cnn.CnnConfig()
    task = engine.make_cnn_task("paper-baseline", 100, cfg=cfg,
                                n_train=n_train, n_test=n_test, batch_size=50)
    # cohort="all" vmaps local SGD over all 100 clients; compiled ahead of
    # time for one v5e, this grid needs 0.76 GB of arguments and 13.69 GB
    # of temporaries, which fits the chip's 16 GiB, so it runs as is
    cohort = "all"
    cohort_why = "AOT footprint 14.45 GB fits one v5e (16 GiB)"
    kw = dict(task=task, policies=("elementwise_ucb", "fedcs"), seeds=1,
              n_rounds=n_rounds, s_round=5, cfg=cfg, epochs=epochs,
              batch_size=50, cohort=cohort)
    mark = len(TRACED)
    a, first, steady = timed(
        lambda: engine.accuracy_sweep("paper-baseline", use_kernel=True,
                                      **kw))
    routes = routes_since(mark)
    check("fedavg_combine" in routes["pallas_compiled"],
          f"the fedavg kernel did not run: {routes}")
    b = engine.accuracy_sweep("paper-baseline", use_kernel=False, **kw)
    check(np.array_equal(a.selected, b.selected),
          "accuracy_sweep: kernel vs jnp selections differ")
    check(bool(np.isfinite(a.accuracy).all()), "non-finite accuracy")
    err = float(np.max(np.abs(a.accuracy - b.accuracy)))
    check(err <= 1e-3, f"accuracy_sweep: |acc diff| {err!r} > 1e-3")
    parity = {"selections": "equal", "accuracy_max_abs_diff": err}
    parity.update(compare_times(a.round_times, b.round_times, TIME_RTOL,
                                "round_times"))
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(
        task.params0))
    report("learning", k=100, params=n_params, cohort=cohort,
           cohort_why=cohort_why,
           final_accuracy=a.accuracy[..., -1].tolist(), routes=routes,
           smoke_first_call_s=first, smoke_steady_s=steady, parity=parity,
           peak_bytes_in_use=peak_bytes())


def phase_serve(n_ticks: int = 500) -> None:
    import numpy as np

    from repro.sim import async_engine

    mark = len(TRACED)
    r, first, steady = timed(lambda: async_engine.serve(
        "diurnal-drift", "discounted_ucb", n_ticks=n_ticks))
    routes = routes_since(mark)
    check(bool(np.isfinite(r.elapsed).all()), "serve: non-finite clock")
    check(bool(r.conserved()), "serve: admitted != aggregated + dropped + "
          "failed + buffered")
    report("serve", k=100, ticks=n_ticks, aggregated=int(r.aggregated.sum()),
           dropped=int(r.dropped.sum()), failed=int(r.failed.sum()),
           routes=routes, smoke_first_call_s=first, smoke_steady_s=steady,
           parity={"conserved": True}, peak_bytes_in_use=peak_bytes())


# ---------------------------------------------------------------------------
# four-chip phases
# ---------------------------------------------------------------------------

def segmented_parity(k: int, n_dev: int, n_rounds: int = 3,
                     s_round: int = 5) -> dict:
    """Round-level: segmented rounds in one shard_map over ``n_dev``
    devices vs the flat sampled round on jax.devices()[0]; selections and
    the final BanditState must be bitwise equal (bench_sharded_sweep's
    harness), for one greedy and one score policy — the two segmented
    selection paths (replicated Algorithm 1; local top-S + merge)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.core import bandit_jax
    from repro.distributed import sharding as dist_sharding
    from repro.sim import engine_jax

    mesh = dist_sharding.sweep_mesh(n_dev, axis_name="clients")
    n_req = math.ceil(0.1 * k)
    eta, bits = 1.5, 1.46e8
    rng = np.random.default_rng(0)
    theta = jnp.asarray(rng.uniform(1e6, 1e7, k).astype(np.float32))
    gamma = jnp.asarray(rng.uniform(10.0, 100.0, k).astype(np.float32))
    n_s = jnp.asarray(rng.uniform(100.0, 1000.0, k).astype(np.float32))
    rkeys = jax.random.split(jax.random.PRNGKey(7), 3 * n_rounds)
    rkeys = rkeys.reshape((n_rounds, 3) + rkeys.shape[1:])
    state_specs = {f.name: P() if f.name in ("total", "disc_total")
                   else P("clients")
                   for f in dataclasses.fields(bandit_jax.BanditState)}
    out = {}
    for policy in FOUR_CHIP_POLICIES:
        hyper = jnp.float32(bandit_jax.DEFAULT_HYPERS[policy])
        flat_fn = bandit_jax.make_sampled_round_fn(policy, s_round)
        seg_fn = bandit_jax.make_segmented_round_fn(
            policy, s_round, axis_name="clients", n_shards=n_dev)

        def scan(fn, theta, gamma, n_s):
            def step(state, ks):
                kc, kp, kt = ks
                cand = engine_jax._cand_topk_one(kc, k, n_req)
                o = fn(state, cand, kp, kt, theta, gamma, n_s, eta, bits,
                       hyper)
                return o[0], o[1]
            state, sels = jax.lax.scan(
                step, bandit_jax.BanditState.create(theta.shape[0]), rkeys)
            return dataclasses.asdict(state), sels

        flat = jax.jit(lambda *a: scan(flat_fn, *a))
        seg = jax.jit(shard_map(
            lambda *a: scan(seg_fn, *a), mesh=mesh,
            in_specs=(P("clients"),) * 3, out_specs=(state_specs, P()),
            check_vma=False))
        sf, sel_f = jax.tree.map(np.asarray, flat(theta, gamma, n_s))
        ss, sel_s = jax.tree.map(np.asarray, seg(theta, gamma, n_s))
        check(np.array_equal(sel_f, sel_s),
              f"K={k} {policy}: segmented selections != flat")
        for name in sf:
            check(np.array_equal(sf[name], ss[name]),
                  f"K={k} {policy}: segmented state.{name} != flat")
        out[policy] = "selections+state bitwise"
    return out


def grid_selection_parity(n_dev: int, k: int = 100, n_grid: int = 8,
                          n_rounds: int = 50, s_round: int = 5) -> dict:
    """Round-level: the engines' grid layout (a vmapped run per grid
    point, the grid split over ``n_dev`` devices by
    ``sharding.shard_vmapped``) vs the same vmap on one device.
    ``sweep`` returns no selections, so they are compared here: equal;
    round times within GRID_RTOL."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import bandit_jax
    from repro.distributed import sharding as dist_sharding
    from repro.sim import engine_jax

    mesh = dist_sharding.sweep_mesh(n_dev)
    n_req = math.ceil(0.1 * k)
    rng = np.random.default_rng(0)
    theta = jnp.asarray(rng.uniform(1e6, 1e7, k), jnp.float32)
    gamma = jnp.asarray(rng.uniform(10.0, 100.0, k), jnp.float32)
    n_s = jnp.asarray(rng.uniform(100.0, 1000.0, k), jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(3), n_grid * n_rounds * 3)
    keys = keys.reshape((n_grid, n_rounds, 3) + keys.shape[1:])
    out = {}
    for policy in FOUR_CHIP_POLICIES:
        hyper = jnp.float32(bandit_jax.DEFAULT_HYPERS[policy])
        fn = bandit_jax.make_sampled_round_fn(policy, s_round)

        def run(keys, theta, gamma, n_s):
            def step(state, ks):
                kc, kp, kt = ks
                cand = engine_jax._cand_topk_one(kc, k, n_req)
                st, sel, rt = fn(state, cand, kp, kt, theta, gamma, n_s,
                                 1.5, 1.46e8, hyper)
                return st, (sel, rt)
            return jax.lax.scan(step, bandit_jax.BanditState.create(k),
                                keys)[1]

        vm = jax.vmap(run, in_axes=(0, None, None, None))
        sel_1, rt_1 = jax.tree.map(np.asarray, jax.jit(vm)(
            keys, theta, gamma, n_s))
        sel_g, rt_g = jax.tree.map(np.asarray, jax.jit(
            dist_sharding.shard_vmapped(vm, mesh, sharded_argnums=(0,)))(
                keys, theta, gamma, n_s))
        check(np.array_equal(sel_1, sel_g),
              f"{policy}: grid-sharded selections != one device")
        res = {"selections": "equal"}
        res.update(compare_times(rt_g, rt_1, GRID_RTOL, "round_times"))
        out[policy] = res
    return out


def four_chip_phases(k_seg: int = 2**16, k_big: int = 10**6,
                     big_rounds: int = 100, **grid_kw) -> None:
    import jax

    from repro.distributed import sharding as dist_sharding
    from repro.sim import engine_jax

    devs = jax.devices()
    check(len(devs) >= 4, f"--chips 4 needs 4 devices, found {len(devs)}")

    # (a) client-sharded segmented vs one-chip flat at K = 2^16
    k = k_seg
    mark = len(TRACED)
    parity = {"rounds": segmented_parity(k, 4)}
    kw = dict(n_clients=k, policies=FOUR_CHIP_POLICIES, seeds=2,
              etas=(1.5,), n_rounds=50, chunk_rounds=25)
    flat = engine_jax.sweep(**kw)
    seg, first, steady = timed(
        lambda: engine_jax.sweep(**kw, devices=4, shard="clients"))
    parity.update(compare_times(seg.round_times, flat.round_times, 0.0,
                                "sweep_round_times"))
    report("segmented_vs_flat", k=k, devices=4, routes=routes_since(mark),
           smoke_first_call_s=first, smoke_steady_s=steady, parity=parity,
           peak_bytes_in_use=[peak_bytes(d) for d in devs[:4]])

    # (b) K = 10^6 over four chips, chunks of 25 rounds
    k = k_big
    mark = len(TRACED)
    res, first, steady = timed(lambda: engine_jax.sweep(
        n_clients=k, policies=FOUR_CHIP_POLICIES, seeds=1,
        etas=(1.5,), n_rounds=big_rounds, chunk_rounds=25, devices=4,
        shard="clients"))
    check(bool((res.round_times > 0).all()), "K=1e6: non-positive times")
    report("segmented_large", k=k, devices=4, rounds=big_rounds,
           chunk_rounds=25,
           grid=list(res.round_times.shape),
           bandit_state_bytes_per_device=dist_sharding.bandit_state_bytes(
               k, 4),
           bandit_state_bytes_total=dist_sharding.bandit_state_bytes(k),
           routes=routes_since(mark), smoke_first_call_s=first,
           smoke_steady_s=steady, parity={"finite": True},
           peak_bytes_in_use=[peak_bytes(d) for d in devs[:4]])

    # (c) grid sharding vs one device: the paper grid (3 etas x 8 seeds x
    # 500 rounds) for the two four-chip policies, plus the round-level
    # selections
    mark = len(TRACED)
    grid_kw = {"policies": FOUR_CHIP_POLICIES, **grid_kw}
    one = engine_jax.sweep(**grid_kw)
    grid, first, steady = timed(
        lambda: engine_jax.sweep(devices=4, shard="grid", **grid_kw))
    parity = compare_times(grid.round_times, one.round_times, GRID_RTOL,
                           "sweep_round_times")
    parity["rounds"] = grid_selection_parity(4)
    report("grid", k=100, devices=4, grid=list(grid.round_times.shape),
           routes=routes_since(mark), smoke_first_call_s=first,
           smoke_steady_s=steady, parity=parity,
           peak_bytes_in_use=[peak_bytes(d) for d in devs[:4]])


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    use_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{dev.platform!r} ({dev.device_kind}); nothing was run",
              file=sys.stderr)
        return 1
    cache = jax.config.jax_compilation_cache_dir
    report("device", platform=dev.platform, kind=dev.device_kind,
           count=len(jax.devices()), compile_cache=cache,
           compile_cache_warm=bool(cache and Path(cache).is_dir()
                                   and any(Path(cache).iterdir())))
    record_pallas_calls()

    if args.chips == 4:
        four_chip_phases()
    else:
        phase_sweep("paper")
        phase_sweep("k1e4", n_clients=10_000, seeds=2, n_rounds=100)
        phase_learning()
        phase_serve()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

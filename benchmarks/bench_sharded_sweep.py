"""Sharded sweep benchmark: the client-sharded segmented path at scale.

Three jobs, run over the real devices in-process on an accelerator, and
on CPU hosts (``JAX_PLATFORMS=cpu``) in a subprocess whose XLA_FLAGS force
8 virtual host devices — the same mechanism the CI sharded-equivalence job
uses; the parent may already have initialized jax, so the virtual devices
need a fresh process.  The choice is made from the environment alone,
before the parent touches jax: a child must never be started where the
parent may hold the chip.

  * **Headroom curve**: the client-sharded segmented sweep
    (``sweep(shard="clients")`` routed through
    ``core.bandit_jax.make_segmented_round_fn``) at log-spaced K up to 10^6
    (10^5 with ``--fast``), recording rounds/s (compile excluded) and the
    bytes of bandit state resident per device
    (``distributed.sharding.bandit_state_bytes``) at every probed K.
  * **Parity + memory gates** (the run FAILS on violation):
      - at EVERY probed K, the segmented sharded round loop must match the
        flat fused loop bitwise in selections AND final bandit state, for
        all 8 policies (a direct make_segmented_round_fn-vs-
        make_sampled_round_fn scan harness);
      - sweep-level round times sharded == flat bitwise (all 8 policies);
      - the compiled segmented executable's per-device footprint
        (arguments + temporaries) must stay below the flat single-device
        executable's at the same K — the "did sharding actually shrink
        per-device state" guard.
  * **Hierarchy gate** (full runs only): two-level cell selection
    (``sweep(hierarchy="cells")``) must beat the flat path's median round
    throughput at K=10^5 under the cell-structured ``metro-congestion``
    scenario, and must reduce to the flat path bitwise when the scenario
    has a single cell.

On virtual CPU devices the grid-sharded "speedup" rows are orchestration
measurements, not hardware ones; the real signal is that the sharded paths
exist, match bitwise, and scale K.  Results land in
``BENCH_sharded_sweep.json`` at the repo root.

  PYTHONPATH=src python benchmarks/bench_sharded_sweep.py [--fast]
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

CHILD = r"""
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import bandit_jax
from repro.distributed import sharding as dist_sharding
from repro.sim import engine_jax

fast = __FAST__
etas = (1.0, 1.5) if fast else (1.0, 1.5, 1.9)
seeds = 4 if fast else 8
rounds = 100 if fast else 500
kw = dict(policies=tuple(bandit_jax.POLICY_NAMES), etas=etas, seeds=seeds,
          n_rounds=rounds, n_clients=100)
N_DEV = jax.device_count()
failures = []


def timed(**extra):
    engine_jax.sweep(**kw, **extra)              # compile
    t0 = time.time()
    engine_jax.sweep(**kw, **extra)
    return time.time() - t0


single_s = timed()
sharded_s = timed(devices=N_DEV, shard="grid")

# --- sweep-level bitwise parity: segmented client-sharded vs flat --------
par_kw = dict(policies=tuple(bandit_jax.POLICY_NAMES), etas=(1.5,), seeds=2,
              n_rounds=20, n_clients=2048, frac_request=0.05)
a = engine_jax.sweep("correlated-congestion", **par_kw)
b = engine_jax.sweep("correlated-congestion", **par_kw, devices=N_DEV,
                     shard="clients")
if not np.array_equal(a.round_times, b.round_times):
    failures.append("sweep-level: sharded round times != flat")


# --- round-level parity at every probed K: selections + final state ------
def round_parity(k, n_rounds=3, s_round=5):
    mesh = dist_sharding.sweep_mesh(N_DEV, axis_name="clients")
    n_req = max(2 * s_round, min(256, k // 4))
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
    bad = []
    mem = {}
    for policy in bandit_jax.POLICY_NAMES:
        hyper = jnp.float32(bandit_jax.DEFAULT_HYPERS[policy])
        flat_fn = bandit_jax.make_sampled_round_fn(policy, s_round)
        seg_fn = bandit_jax.make_segmented_round_fn(
            policy, s_round, axis_name="clients", n_shards=N_DEV)

        @jax.jit
        def flat_run(theta, gamma, n_s):
            def step(state, ks):
                kc, kp, kt = ks
                cand = engine_jax._cand_topk_one(kc, k, n_req)
                out = flat_fn(state, cand, kp, kt, theta, gamma, n_s, eta,
                              bits, hyper)
                return out[0], out[1]
            state, sels = jax.lax.scan(
                step, bandit_jax.BanditState.create(k), rkeys)
            return dataclasses.asdict(state), sels

        def seg_body(theta_l, gamma_l, ns_l):
            def step(state, ks):
                kc, kp, kt = ks
                cand = engine_jax._cand_topk_one(kc, k, n_req)
                out = seg_fn(state, cand, kp, kt, theta_l, gamma_l, ns_l,
                             eta, bits, hyper)
                return out[0], out[1]
            state, sels = jax.lax.scan(
                step, bandit_jax.BanditState.create(theta_l.shape[0]),
                rkeys)
            return dataclasses.asdict(state), sels

        seg_run = jax.jit(shard_map(
            seg_body, mesh=mesh,
            in_specs=(P("clients"), P("clients"), P("clients")),
            out_specs=(state_specs, P()), check_vma=False))

        sf, sel_f = jax.tree.map(np.asarray, flat_run(theta, gamma, n_s))
        ss, sel_s = jax.tree.map(np.asarray, seg_run(theta, gamma, n_s))
        if not np.array_equal(sel_f, sel_s):
            bad.append(f"K={k} {policy}: selections differ")
        for name in sf:
            if not np.array_equal(sf[name], ss[name]):
                bad.append(f"K={k} {policy}: state.{name} differs")
        if policy == "elementwise_ucb":
            for tag, fn in (("flat", flat_run), ("sharded", seg_run)):
                m = fn.lower(theta, gamma, n_s).compile().memory_analysis()
                mem[tag] = int(m.argument_size_in_bytes
                               + m.temp_size_in_bytes)
    return bad, mem


# --- headroom curve: segmented sharded sweep at log-spaced K -------------
HEADROOM_KS = [1_000, 10_000, 100_000] if fast else [1_000, 10_000,
                                                     100_000, 1_000_000]
headroom = {}
mem_by_k = {}
for k in HEADROOM_KS:
    hr_rounds = 20
    hkw = dict(n_rounds=hr_rounds, n_clients=k, seeds=1, etas=(1.5,),
               policies=("elementwise_ucb",), chunk_rounds=10,
               frac_request=max(0.001, min(0.1, 1000 / k)),
               devices=N_DEV, shard="clients")
    engine_jax.sweep(**hkw)                       # compile
    t0 = time.time()
    engine_jax.sweep(**hkw)
    dt = time.time() - t0
    headroom[str(k)] = {
        "seconds": round(dt, 3),
        "rounds_per_s": round(hr_rounds / max(dt, 1e-9), 2),
        "state_bytes_per_device": dist_sharding.bandit_state_bytes(
            k, N_DEV),
        "state_bytes_total": dist_sharding.bandit_state_bytes(k),
    }
    bad, mem = round_parity(k)
    failures.extend(bad)
    mem_by_k[str(k)] = mem

# per-device memory guard at the largest CI-sized K: the sharded
# executable must be strictly lighter per device than the flat one
guard_k = "100000"
if mem_by_k[guard_k]["sharded"] >= mem_by_k[guard_k]["flat"]:
    failures.append(
        f"memory: sharded per-device {mem_by_k[guard_k]['sharded']} >= "
        f"flat single-device {mem_by_k[guard_k]['flat']} at K={guard_k}")

# --- hierarchy: cells=1 reduces to flat bitwise (always checked) ---------
h_kw = dict(policies=("elementwise_ucb",), etas=(1.5,), seeds=2,
            n_rounds=20, n_clients=512, frac_request=0.1)
h1 = engine_jax.sweep("paper-baseline", **h_kw, hierarchy="cells")
f1 = engine_jax.sweep("paper-baseline", **h_kw)
if not np.array_equal(h1.round_times, f1.round_times):
    failures.append("hierarchy: cells<=1 does not reduce to flat bitwise")

# --- hierarchy throughput gate at K=1e5 (full runs only) -----------------
hier = {}
if not fast:
    hg_rounds = 30
    hg_kw = dict(n_rounds=hg_rounds, n_clients=100_000, seeds=1,
                 etas=(1.5,), policies=("elementwise_ucb",),
                 chunk_rounds=10, frac_request=0.01)
    times = {}
    for tag, extra in (("flat", {}), ("cells", {"hierarchy": "cells"})):
        engine_jax.sweep("metro-congestion", **hg_kw, **extra)   # compile
        t0 = time.time()
        engine_jax.sweep("metro-congestion", **hg_kw, **extra)
        times[tag] = time.time() - t0
    hier = {"k": 100_000, "rounds": hg_rounds,
            "flat_s": round(times["flat"], 3),
            "cells_s": round(times["cells"], 3),
            "flat_rounds_per_s": round(hg_rounds / times["flat"], 2),
            "cells_rounds_per_s": round(hg_rounds / times["cells"], 2),
            "speedup": round(times["flat"] / max(times["cells"], 1e-9), 3)}
    if times["cells"] >= times["flat"]:
        failures.append(
            f"hierarchy: cells {times['cells']:.2f}s not faster than flat "
            f"{times['flat']:.2f}s at K=1e5 under metro-congestion")

RESULT = {
    "devices": N_DEV,
    "grid": len(kw["policies"]) * len(etas) * seeds,
    "rounds": rounds,
    "single_s": round(single_s, 3),
    "sharded_s": round(sharded_s, 3),
    "headroom_by_k": headroom,
    "memory_by_k": mem_by_k,
    "hierarchy": hier,
    "failures": failures,
}
print("RESULT " + json.dumps(RESULT))
"""


def _run(fast: bool) -> dict:
    """The three jobs in a forced-8-device child on CPU hosts, else
    in-process over the real devices."""
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        ns: dict = {}
        exec(compile(CHILD.replace("__FAST__", repr(fast)), "bench_sharded",
                     "exec"), ns)
        return ns["RESULT"]
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src")] + ([env["PYTHONPATH"]]
                               if env.get("PYTHONPATH") else []))
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    proc = subprocess.run(
        [sys.executable, "-c", CHILD.replace("__FAST__", repr(fast))],
        env=env, capture_output=True, text=True, timeout=7200)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-3000:])
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


def main(fast: bool = False) -> list[str]:
    r = _run(fast)
    rounds_total = r["grid"] * r["rounds"]
    speedup = r["single_s"] / max(r["sharded_s"], 1e-9)
    out = ["name,us_per_call,derived"]
    out.append(f"sharded_sweep/single_device,"
               f"{1e6 * r['single_s'] / rounds_total:.1f},"
               f"total={r['single_s']:.2f}s grid={r['grid']} "
               f"rounds={r['rounds']}")
    out.append(f"sharded_sweep/grid_sharded,"
               f"{1e6 * r['sharded_s'] / rounds_total:.1f},"
               f"total={r['sharded_s']:.2f}s devices={r['devices']} "
               f"(virtual CPU: orchestration overhead measurement)")
    out.append(f"sharded_sweep/speedup,,x{speedup:.2f} "
               f"(informational on virtual devices)")
    for k, h in r["headroom_by_k"].items():
        out.append(
            f"sharded_sweep/max_k_{k},,K={k} segmented: "
            f"{h['rounds_per_s']} rounds/s, "
            f"{h['state_bytes_per_device']} state bytes/device "
            f"(total {h['state_bytes_total']})")
    for k, m in r["memory_by_k"].items():
        out.append(f"sharded_sweep/mem_k_{k},,"
                   f"per-device args+temps sharded={m['sharded']} "
                   f"flat={m['flat']}")
    if r.get("hierarchy"):
        h = r["hierarchy"]
        out.append(f"sharded_sweep/hierarchy_k_{h['k']},,"
                   f"cells {h['cells_rounds_per_s']} rounds/s vs flat "
                   f"{h['flat_rounds_per_s']} rounds/s (x{h['speedup']})")
    out.append("sharded_sweep/parity,,"
               + ("OK (bitwise: selections, state, sweep times; all 8 "
                  "policies at every probed K)" if not r["failures"]
                  else str(r["failures"])))

    (ROOT / "BENCH_sharded_sweep.json").write_text(
        json.dumps(r, indent=2, sort_keys=True) + "\n")
    if r["failures"]:
        raise AssertionError("sharded-sweep gates failed: "
                             + "; ".join(r["failures"]))
    return out


if __name__ == "__main__":
    for line in main(fast="--fast" in sys.argv):
        print(line)

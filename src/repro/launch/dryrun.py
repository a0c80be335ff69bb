import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh) cell.

Proves the distribution config is coherent without hardware:
  * ``.lower().compile()`` must succeed on the 16x16 single-pod mesh AND the
    2x16x16 multi-pod mesh for every supported cell;
  * records memory_analysis / cost_analysis / per-collective byte counts
    (parsed from the compiled HLO) into an incremental JSON store that
    benchmarks/bench_roofline.py turns into EXPERIMENTS.md tables.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--force]
"""

import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax

from repro.configs.shapes import SHAPES
from repro.launch.hlo_analysis import analyze
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_cell
from repro.models.registry import build, list_archs

RESULTS = Path(__file__).resolve().parents[3] / "benchmarks" / "dryrun_results.json"
HLO_DIR = RESULTS.parent / "hlo"


def run_cell(arch: str, shape: str, multi_pod: bool, verbose: bool = True) -> dict:
    api = build(arch)
    ok, reason = api.supports(shape)
    if not ok:
        return {"status": "skip", "reason": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    spec = build_cell(arch, shape, mesh)
    # abstract-mesh context so in-model with_sharding_constraint(P(...))
    # hints (e.g. llava's batch-sharded attention) resolve at trace time.
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        lowered = jax.jit(spec.fn, in_shardings=spec.in_shardings,
                          out_shardings=spec.out_shardings).lower(
                              *spec.abstract_args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    mem_d = {}
    if mem is not None:
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(mem, attr, None)
            if v is not None:
                mem_d[attr] = int(v)

    cost = compiled.cost_analysis() or {}
    cost_d = {}
    if "flops" in cost:
        cost_d["xla_flops_noloop"] = float(cost["flops"])
    if "bytes accessed" in cost:
        cost_d["xla_bytes_noloop"] = float(cost["bytes accessed"])

    # loop-aware analysis (XLA's cost_analysis counts while bodies once;
    # ours multiplies by trip counts — see hlo_analysis.py).  The HLO text
    # is persisted gzipped so analyzer improvements can re-run offline
    # (--reanalyze) without recompiling.
    t0 = time.time()
    text = compiled.as_text()
    hlo = analyze(text)
    t_parse = time.time() - t0
    import gzip
    HLO_DIR.mkdir(exist_ok=True)
    key = cell_key(arch, shape, multi_pod).replace("|", "__")
    with gzip.open(HLO_DIR / f"{key}.hlo.gz", "wt") as f:
        f.write(text)

    total, active = api.param_counts()
    rec = {
        "status": "ok",
        "arch": arch, "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": 512 if multi_pod else 256,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "parse_s": round(t_parse, 1),
        "params_total": total, "params_active": active,
        "fsdp": spec.static.get("fsdp"),
        "memory": mem_d, "cost": cost_d,
        "dot_flops": hlo["dot_flops"],
        "traffic_bytes": hlo["traffic_bytes"],
        "traffic_major": hlo["traffic_major"],
        "collectives": hlo["collectives"],
        "collective_bytes": hlo["total_collective_bytes"],
    }
    if verbose:
        print(f"[{arch} x {shape} x {rec['mesh']}] ok "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s) "
              f"dot_flops={hlo['dot_flops']:.3e} "
              f"traffic={hlo['traffic_bytes']:.3e}B "
              f"coll={hlo['total_collective_bytes']:.3e}B")
        if mem_d:
            print("  memory_analysis:", mem_d)
    return rec


def run_fl_round_cell(arch: str, compress: str, multi_pod: bool = False,
                      verbose: bool = True) -> dict:
    """Lower the paper's FL round at pod scale: data-axis slices are cohorts
    (arms), one local step each, then MAB-masked FedAvg aggregation with
    optional int8/top-k upload compression.  This is the
    paper-representative roofline cell."""
    import jax.numpy as jnp
    from repro.distributed import fl_parallel, sharding
    from repro.launch.mesh import batch_axes
    from repro.optim.sgd import OptimizerConfig

    api = build(arch)
    cfg = api.cfg
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_cohorts = 1
    for a in batch_axes(mesh):
        n_cohorts *= mesh.shape[a]
    cell = SHAPES["train_4k"]
    per_cohort_batch = cell.global_batch // n_cohorts

    opt = OptimizerConfig(name="sgd", lr=0.05, momentum=0.9).build()
    pshapes = api.param_shapes()
    pspecs = sharding.param_specs(pshapes, cfg, mesh, fsdp=False)
    sspecs = fl_parallel.stacked_param_specs(pspecs, mesh)
    stacked_shapes = jax.eval_shape(
        lambda: jax.tree.map(
            lambda s: jnp.zeros((n_cohorts,) + s.shape, s.dtype), pshapes))
    opt_shapes = jax.eval_shape(
        lambda: jax.vmap(opt.init)(
            jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                         stacked_shapes)))
    n_steps = 1
    batches = {"tokens": jax.ShapeDtypeStruct(
        (n_cohorts, n_steps, per_cohort_batch, cell.seq_len), jnp.int32)}
    weights = jax.ShapeDtypeStruct((n_cohorts,), jnp.float32)

    fl_round = fl_parallel.make_fl_round(
        api.loss_fn, opt, n_steps, mesh, sspecs, compress=compress)

    from jax.sharding import PartitionSpec as P
    named = lambda t: sharding.to_named(t, mesh)
    batch_spec = named({"tokens": P(batch_axes(mesh), None, None, None)})
    t0 = time.time()
    lowered = jax.jit(
        fl_round,
        in_shardings=(named(pspecs),
                      named(sharding.opt_specs(opt_shapes, sspecs)),
                      batch_spec, named(P())),
        out_shardings=(named(pspecs),
                       named(sharding.opt_specs(opt_shapes, sspecs)), None),
    ).lower(pshapes, opt_shapes, batches, weights)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    text = compiled.as_text()
    hlo = analyze(text)
    import gzip
    HLO_DIR.mkdir(exist_ok=True)
    key = f"fl-round-{compress}__{arch}__{'multi' if multi_pod else 'single'}"
    with gzip.open(HLO_DIR / f"{key}.hlo.gz", "wt") as f:
        f.write(text)
    total, active = api.param_counts()
    rec = {
        "status": "ok", "arch": f"fl-round[{compress}]/{arch}",
        "shape": "train_4k",
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": 512 if multi_pod else 256,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "params_total": total, "params_active": active, "fsdp": False,
        "memory": {}, "cost": {},
        "dot_flops": hlo["dot_flops"],
        "traffic_bytes": hlo["traffic_bytes"],
        "traffic_major": hlo["traffic_major"],
        "collectives": hlo["collectives"],
        "collective_bytes": hlo["total_collective_bytes"],
    }
    if verbose:
        print(f"[fl-round {arch} compress={compress} {rec['mesh']}] ok "
              f"(compile {t_compile:.0f}s) dot_flops={hlo['dot_flops']:.3e} "
              f"coll={hlo['total_collective_bytes']:.3e}B "
              f"by_kind={{ {', '.join(f'{k}:{v['bytes']:.2e}' for k, v in hlo['collectives'].items() if v['count'])} }}")
    return rec


def load_results() -> dict:
    if RESULTS.exists():
        return json.loads(RESULTS.read_text())
    return {}


def save_results(res: dict) -> None:
    RESULTS.write_text(json.dumps(res, indent=1, sort_keys=True))


def cell_key(arch: str, shape: str, multi_pod: bool) -> str:
    return f"{arch}|{shape}|{'multi' if multi_pod else 'single'}"


def reanalyze_all() -> None:
    """Re-parse all stored HLO with the current analyzer (no recompiles)."""
    import gzip
    res = load_results()
    n = 0
    for key, rec in res.items():
        if rec.get("status") != "ok":
            continue
        path = HLO_DIR / (key.replace("|", "__") + ".hlo.gz")
        if not path.exists():
            print(f"[{key}] no stored HLO, skipping")
            continue
        with gzip.open(path, "rt") as f:
            hlo = analyze(f.read())
        rec.update(dot_flops=hlo["dot_flops"],
                   traffic_bytes=hlo["traffic_bytes"],
                   traffic_major=hlo["traffic_major"],
                   collectives=hlo["collectives"],
                   collective_bytes=hlo["total_collective_bytes"])
        n += 1
    save_results(res)
    print(f"reanalyzed {n} cells")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--reanalyze", action="store_true")
    ap.add_argument("--fl-round", default=None, metavar="ARCH",
                    help="lower the FL cohort round for ARCH instead of the "
                         "plain steps")
    ap.add_argument("--compress", default="none",
                    choices=["none", "int8", "int8_psum", "topk"])
    args = ap.parse_args()
    if args.reanalyze:
        reanalyze_all()
        return
    if args.fl_round:
        res = load_results()
        key = f"fl-round-{args.compress}|{args.fl_round}|" + \
            ("multi" if args.multi_pod else "single")
        res[key] = run_fl_round_cell(args.fl_round, args.compress,
                                     args.multi_pod)
        save_results(res)
        return

    archs = [args.arch] if args.arch else list_archs()
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    res = load_results()
    failures = []
    for multi in meshes:
        for arch in archs:
            for shape in shapes:
                key = cell_key(arch, shape, multi)
                if not args.force and res.get(key, {}).get("status") == "ok":
                    print(f"[{key}] cached ok, skipping")
                    continue
                try:
                    res[key] = run_cell(arch, shape, multi)
                except Exception as e:  # record failures; they are bugs
                    res[key] = {"status": "fail", "error": f"{type(e).__name__}: {e}",
                                "trace": traceback.format_exc()[-2000:]}
                    failures.append(key)
                    print(f"[{key}] FAIL: {e}")
                save_results(res)
    n_ok = sum(1 for v in res.values() if v.get("status") == "ok")
    n_skip = sum(1 for v in res.values() if v.get("status") == "skip")
    print(f"\ndone: {n_ok} ok, {n_skip} skip, {len(failures)} new failures")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()

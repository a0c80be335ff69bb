"""Run one cell once: set-up, the measured window of studies, the check.

Set-up is everything from process start to the first timed study: the
imports, finding the chips, the compile (or the load from the checkout's
compile cache) and one warm study of the cell's own shape.  The window
then runs studies back to back, as one researcher's script does, until
``seconds`` have passed; the last study runs to its end and the window
ends with it.  ``--trace 1`` runs instead a short window under the
profiler (at most the mix's ``trace_studies``) and reports the per-layer
metrics read from that trace.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import time
from pathlib import Path

from chipbench import check, spec, studies

OUT_DIR = spec.BENCH_DIR / "out"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def configure_jax(root: Path) -> None:
    """Persistent compile cache in the checkout (a fixed path, since the
    path is part of the cache key), unless the environment names one."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def find_devices(chips: int, require_tpu: bool):
    """The cell's devices; raises NoChip off a TPU or short of chips."""
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, but JAX found platform "
                     f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devs)}")
    return devs


_TRACED: list[str] = []


def count_compiles() -> None:
    """Count JAX's traces and compiles (jax.monitoring events) from here
    on, so that a run can show none happened inside its window."""
    from jax import monitoring
    if not getattr(count_compiles, "on", False):
        monitoring.register_event_duration_secs_listener(
            lambda name, _secs, **_kw: _TRACED.append(name)
            if name.endswith(("backend_compile_duration",
                              "jaxpr_trace_duration")) else None)
        count_compiles.on = True


def record_kernels(names: list[str]) -> None:
    """Note the name of every Pallas kernel the program traces."""
    from jax.experimental import pallas as pl
    inner = pl.pallas_call
    if getattr(inner, "_chipbench", False):
        return

    def pallas_call(kernel, *args, name=None, **kw):
        names.append(name or getattr(kernel, "__name__", repr(kernel)))
        return inner(kernel, *args, name=name, **kw)

    pallas_call._chipbench = True
    pl.pallas_call = pallas_call


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True,
             root: Path = spec.ROOT, out_dir: Path = OUT_DIR) -> dict:
    """Run ``cell`` once and return the result line (a dict)."""
    import jax

    configure_jax(root)
    devs = find_devices(cell.chips, require_tpu)
    used = devs[:cell.chips]
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(json.dumps({"chipbench": "device", **device}), flush=True)

    from repro.sim import engine_jax

    kernels: list[str] = []
    record_kernels(kernels)
    count_compiles()
    config, traffic = cell.config, cell.traffic
    engine_jax.sweep(**studies.sweep_kwargs(config, traffic, seed, -1))
    setup_s = time.perf_counter() - t_start

    out_dir.mkdir(parents=True, exist_ok=True)
    trace_dir = out_dir / "trace" / cell.name
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    done: list[dict] = []
    compiles = len(_TRACED)
    t0 = time.perf_counter()
    while True:
        kw = studies.sweep_kwargs(config, traffic, seed, len(done))
        ts = time.perf_counter()
        with jax.profiler.TraceAnnotation("chipbench.study"):
            res = engine_jax.sweep(**kw)
        te = time.perf_counter()
        done.append({"kwargs": kw, "round_times": res.round_times,
                     "seconds": te - ts})
        if te - t0 >= seconds or (
                trace and len(done) >= int(traffic["trace_studies"])):
            break
    window_s = te - t0
    compiles = len(_TRACED) - compiles
    if trace:
        jax.profiler.stop_trace()
    stats = [d.memory_stats() or {} for d in used]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    device["memory_peak_bytes"] = int(peak)
    n_rounds = studies.bandit_rounds(traffic) * len(done)

    metrics: dict = {}
    breakdown = None
    if trace:
        from chipbench import trace as tr
        ctx = tr.read_trace(trace_dir, cell=cell, n_studies=len(done),
                            bandit_rounds=n_rounds, n_devices=cell.chips,
                            device_kind=device["kind"])
        device["busy_s"] = ctx.busy_s()
        device["window_s"] = ctx.window_s()
        for m in cell.per_layer:
            value = spec.load_reader(m["name"], cell.bench_dir)(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        breakdown = ctx.breakdown()
    else:
        measured = {"rounds_per_s": n_rounds / window_s,
                    "device_peak_mb": peak / 1e6, "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(measured[m["name"]]),
                                  "unit": m["unit"]}

    tc = time.perf_counter()
    found = check.compare(cell, done, seed)
    correct, numbers = check.verdict(cell, found)
    record = {"workload": cell.name, "seed": seed, "trace": trace,
              "setup_s": setup_s, "window_s": window_s,
              "study_seconds": [d["seconds"] for d in done],
              "bandit_rounds": n_rounds, "kernels": sorted(set(kernels)),
              "window_compiles": compiles,
              "trace_dir": str(trace_dir) if trace else None,
              "check": found, "check_s": time.perf_counter() - tc}
    (out_dir / f"{cell.name}-{seed}-trace{int(trace)}.json").write_text(
        json.dumps(record, indent=1))
    print(json.dumps({"chipbench": "run", "studies": len(done), **{
        k: record[k] for k in ("setup_s", "window_s", "bandit_rounds",
                               "kernels", "window_compiles", "trace_dir",
                               "check_s", "check")}}), flush=True)

    result = {"correct": bool(correct), "attempted": len(done),
              "failed": int(found["failed_studies"]), "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = numbers
    for name, v in numbers.items():
        log(f"check {name}: {v['value']!r} (limit {v['limit']!r})")
    return result

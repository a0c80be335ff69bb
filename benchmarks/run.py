"""Benchmark orchestrator: one section per paper table/figure + system
benches.  Prints ``name,us_per_call,derived`` CSV lines.

  fig1_2  — elapsed-time diff / reduction ratio vs FedCS over eta (Figs 1-2)
  fig3    — accuracy vs elapsed time (Fig 3)
  fig4    — UCB-score convergence (Fig 4)
  kernels — Pallas kernel micro-benches (interpret mode vs jnp reference)
  round_kernel — fused bandit-round hot path vs the unfused baseline,
            bitwise parity gate incl. the Pallas kernel in interpret mode
            (BENCH_round_kernel.json)
  e2e_sweep — whole sweep() wall clock, streamed candidate-sliced sampling
            vs the legacy presample, with bitwise parity gates on both
            paths (BENCH_e2e_sweep.json)
  async_serve — bounded-staleness serving engine throughput, with bitwise
            sync-reduction and crash/resume gates (BENCH_async_serve.json)
  fault_tolerance — MAB vs random under 10% crash + round deadline, with
            the fault-off bitwise reduction and aggregation-guard gates
            (BENCH_fault_tolerance.json)
  roofline— per (arch x shape) roofline terms from the dry-run artifacts
  scale   — selection-at-scale: vectorized UCB scoring for 1e6 arms
  fl_engine — learning-coupled engine vs the classic host training loop
  sharded — multi-device grid-sharded sweep + chunked max-K headroom
            (subprocess with 8 forced host devices; BENCH_sharded_sweep.json)

``python -m benchmarks.run --fast`` runs reduced sizes (CI); default runs
the full paper-scale settings.  Every section runs and prints its lines;
the run then exits non-zero if any section failed.
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback


def _section(name: str, fn, fast: bool) -> tuple[list[str], bool]:
    """Run one section; returns its lines and whether it succeeded (a
    failure is reported as an ``_error`` line, and the run goes on so
    every section's output is printed)."""
    t0 = time.time()
    try:
        lines = fn(fast=fast)
        lines.append(f"{name}/_wall,,{time.time()-t0:.1f}s")
        return lines, True
    except Exception as e:
        traceback.print_exc()
        return [f"{name}/_error,,{type(e).__name__}: {e}"], False


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced sizes for CI")
    ap.add_argument("--only", default=None,
                    help="comma-separated section filter")
    args = ap.parse_args()

    from repro.utils.compat import use_compile_cache
    use_compile_cache()
    from benchmarks import (bench_accuracy, bench_async_serve,
                            bench_convergence, bench_drift, bench_e2e_sweep,
                            bench_fault_tolerance, bench_fl_engine,
                            bench_kernels, bench_roofline,
                            bench_round_kernel, bench_scale,
                            bench_selection, bench_sharded_sweep,
                            bench_sweep)
    sections = {
        "fig1_2": bench_selection.main,
        "fig3": bench_accuracy.main,
        "fig4": bench_convergence.main,
        "drift": bench_drift.main,
        "kernels": bench_kernels.main,
        "round_kernel": bench_round_kernel.main,
        "e2e_sweep": bench_e2e_sweep.main,
        "async_serve": bench_async_serve.main,
        "fault_tolerance": bench_fault_tolerance.main,
        "roofline": bench_roofline.main,
        "scale": bench_scale.main,
        "sweep": bench_sweep.main,
        "fl_engine": bench_fl_engine.main,
        "sharded": bench_sharded_sweep.main,
    }
    if args.only:
        keep = set(args.only.split(","))
        sections = {k: v for k, v in sections.items() if k in keep}

    all_lines: list[str] = []
    failed = []
    for name, fn in sections.items():
        print(f"# --- {name} ---", file=sys.stderr)
        lines, ok = _section(name, fn, args.fast)
        all_lines += lines
        if not ok:
            failed.append(name)

    seen_header = False
    for line in all_lines:
        if line.startswith("name,us_per_call"):
            if seen_header:
                continue
            seen_header = True
        print(line)
    if failed:
        sys.exit(f"benchmark sections failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()

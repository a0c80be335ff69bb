"""Readings that set a cell's correctness limit, on the chip, in one process.

    python chipbench/control.py --workload paper-grid --seeds 12 --controls 3

For each of ``--seeds`` seeds it runs the studies a check needs at the
cell's own size and reads ``round_gap`` of the program against the
reference (the lower reading is the largest of these); for the first
``--controls`` seeds it also reads the control, the reference computed in
bfloat16 in the program's place (the upper reading is the smallest of
these).  Not part of a benchmark run.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--base-seed", type=int, default=2**32 + 17)
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR",
                          str(ROOT / "chipbench" / "out" / "tpu_logs"))
    from chipbench import check, harness, spec, studies

    cell = spec.load_cell(args.workload)
    harness.configure_jax(ROOT)
    try:
        harness.find_devices(cell.chips, True)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    from repro.sim import engine_jax

    t = cell.traffic
    grid = len(t["policies"]) * len(t["etas"]) * int(t["seeds_per_study"])
    n_studies = max(1, math.ceil(int(t["check_trajectories"]) / grid))
    lower, upper = [], []
    for i in range(args.seeds):
        seed = args.base_seed + 7919 * i
        done = []
        for j in range(n_studies):
            kw = studies.sweep_kwargs(cell.config, t, seed, j)
            done.append({"kwargs": kw,
                         "round_times": engine_jax.sweep(**kw).round_times})
        prog = check.compare(cell, done, seed)
        row = {"seed": seed, "program": prog}
        lower.append(prog["round_gap"])
        if i < args.controls:
            ctl = check.compare(cell, done, seed, control="bfloat16")
            row["control"] = ctl
            upper.append(ctl["round_gap"])
        print(json.dumps(row), flush=True)
    print(json.dumps({"workload": cell.name, "lower": max(lower),
                      "upper": min(upper) if upper else None,
                      "program_readings": lower, "control_readings": upper,
                      "seconds": time.perf_counter() - T_START}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

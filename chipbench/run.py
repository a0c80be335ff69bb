r"""Run one benchmark cell once and print its result as the last line.

    python chipbench/run.py --workload paper-grid --seed 7 \
        --seconds 20 --trace 0

From the root of a checkout.  The cell's configuration, traffic mix,
per-layer readers and correctness limits are found by the names in
``BENCHMARK.json`` (chipbench/spec.py).  Exits non-zero and prints no
result where JAX finds no TPU, or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # libtpu logs to /tmp/tpu_logs unless told otherwise; keep them in the
    # checkout, with everything else the run writes
    os.environ.setdefault("TPU_LOG_DIR",
                          str(ROOT / "chipbench" / "out" / "tpu_logs"))
    from chipbench import harness, spec

    cell = spec.load_cell(args.workload)
    try:
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"chipbench: {e}; nothing was measured", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

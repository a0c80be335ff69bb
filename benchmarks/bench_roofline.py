"""Roofline table from the dry-run artifacts (deliverable g).

Reads benchmarks/dryrun_results.json (written by repro.launch.dryrun) and
derives, per (arch x shape) on the single-pod 16x16 mesh:

  compute    = dot_flops / peak_FLOPs            [s]   (per-chip, bf16)
  memory     = traffic_major / HBM_bw            [s]
  collective = sum_k factor_k * bytes_k / link_bw [s]

Hardware: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.
Ring factors: all-reduce 2(n-1)/n ~= 2, all-gather / reduce-scatter (n-1)/n
~= 1, all-to-all (n-1)/n^2 ~= 1/n, collective-permute 1.

MODEL_FLOPS = 6*N*D (dense) or 6*N_active*D (MoE) for train shapes;
2*N(_active)*D for inference shapes.  The useful-compute ratio
MODEL_FLOPS / HLO_FLOPs exposes remat/redundancy waste.
"""

from __future__ import annotations

import json
from pathlib import Path

from repro.utils.device_peaks import TARGET_KIND, peaks

PEAK_FLOPS = peaks(TARGET_KIND)["flops_bf16"]       # per chip
HBM_BW = peaks(TARGET_KIND)["hbm_bytes_per_s"]
LINK_BW = 50e9               # B/s per ICI link

COLL_FACTORS = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
                "all-to-all": 0.0625, "collective-permute": 1.0}

RESULTS = Path(__file__).resolve().parent / "dryrun_results.json"

SHAPE_TOKENS = {          # (seq, batch)
    "train_4k": (4096, 256), "prefill_32k": (32768, 32),
    "decode_32k": (1, 128), "long_500k": (1, 1),
}


def model_flops(rec: dict) -> float:
    seq, batch = SHAPE_TOKENS[rec["shape"]]
    tokens = seq * batch
    n = rec["params_active"]
    mult = 6.0 if rec["shape"] == "train_4k" else 2.0
    return mult * n * tokens


def roofline_row(rec: dict) -> dict:
    n_dev = rec["n_devices"]
    t_compute = rec["dot_flops"] / PEAK_FLOPS
    t_memory = rec.get("traffic_major", rec["traffic_bytes"]) / HBM_BW
    t_coll = sum(COLL_FACTORS[k] * v["bytes"] / LINK_BW
                 for k, v in rec["collectives"].items())
    dominant = max((("compute", t_compute), ("memory", t_memory),
                    ("collective", t_coll)), key=lambda kv: kv[1])[0]
    mf = model_flops(rec)
    useful = mf / n_dev / max(rec["dot_flops"], 1.0)
    t_bound = max(t_compute, t_memory, t_coll)
    mfu_bound = (mf / n_dev / PEAK_FLOPS) / t_bound if t_bound > 0 else 0.0
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_coll, "dominant": dominant,
        "model_flops_global": mf, "useful_ratio": useful,
        "roofline_mfu_bound": mfu_bound,
    }


def load_rows(mesh: str = "16x16") -> tuple[list[dict], list[dict]]:
    if not RESULTS.exists():
        return [], []
    data = json.loads(RESULTS.read_text())
    rows, skips = [], []
    for key, rec in sorted(data.items()):
        if rec.get("status") == "skip":
            arch, shape, m = key.split("|")
            if m == ("single" if mesh == "16x16" else "multi"):
                skips.append({"arch": arch, "shape": shape,
                              "reason": rec["reason"]})
            continue
        if rec.get("status") != "ok" or rec.get("mesh") != mesh:
            continue
        if "dot_flops" not in rec:      # pre-analyzer record; re-run dryrun
            continue
        rows.append(roofline_row(rec))
    return rows, skips


def main(fast: bool = False) -> list[str]:
    rows, skips = load_rows()
    out = ["name,us_per_call,derived"]
    for r in rows:
        out.append(
            f"roofline/{r['arch']}/{r['shape']},,"
            f"compute={r['t_compute_s']*1e3:.2f}ms "
            f"memory={r['t_memory_s']*1e3:.2f}ms "
            f"coll={r['t_collective_s']*1e3:.2f}ms "
            f"dom={r['dominant']} useful={r['useful_ratio']:.2f} "
            f"mfu_bound={r['roofline_mfu_bound']:.3f}")
    for s in skips:
        out.append(f"roofline/{s['arch']}/{s['shape']},,SKIP ({s['reason']})")
    if not rows:
        out.append("roofline/none,,run repro.launch.dryrun first")
    return out


if __name__ == "__main__":
    for line in main():
        print(line)

"""Dry-run glue smoke test: build_cell -> jit(in_shardings).lower().compile()
for a REDUCED arch on an 8-device host mesh, in a subprocess (the main test
process keeps 1 device).  The full 256/512-chip sweep is exercised by
`python -m repro.launch.dryrun --all --both-meshes` (see EXPERIMENTS.md)."""

import subprocess
import sys
from pathlib import Path

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import numpy as np
from jax.sharding import Mesh

from repro.launch.steps import build_cell
from repro.launch.hlo_analysis import analyze

mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4), ("data", "model"))
for arch, shape in [("smollm-135m", "train_4k"),
                    ("qwen3-1.7b", "decode_32k"),
                    ("recurrentgemma-9b", "long_500k")]:
    spec = build_cell(arch, shape, mesh, reduced=True)
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        lowered = jax.jit(spec.fn, in_shardings=spec.in_shardings,
                          out_shardings=spec.out_shardings).lower(
                              *spec.abstract_args)
    compiled = lowered.compile()
    r = analyze(compiled.as_text())
    assert r["dot_flops"] > 0, (arch, shape)
    print(f"OK {arch} {shape} flops={r['dot_flops']:.2e}")
print("ALL_OK")
"""


def test_dryrun_reduced_cells(tmp_path):
    script = tmp_path / "dryrun_smoke.py"
    script.write_text(SCRIPT)
    src = str(Path(__file__).resolve().parents[1] / "src")
    proc = subprocess.run(
        [sys.executable, str(script)],
        env={"PYTHONPATH": src, "PATH": "/usr/bin:/bin:/usr/local/bin",
             "HOME": "/root"},
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "ALL_OK" in proc.stdout

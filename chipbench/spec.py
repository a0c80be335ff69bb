"""Find a cell's files by the names ``BENCHMARK.json`` gives.

A configuration is ``configs/<config>.json`` (the ``file`` its entry
names), a traffic mix ``traffic/<traffic>.json``, a per-layer metric the
reader ``metrics/<metric>.py``, and a cell's correctness limits
``limits/<cell>.json``, all under the benchmark's directory.  Adding a cell
means adding such files and entries; no code here names one.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH_DIR = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json`` with its files read."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple[dict, ...]
    per_layer: tuple[dict, ...]
    bench_dir: Path


def load_cell(workload: str, root: Path = ROOT,
              bench_dir: Path = BENCH_DIR) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json``; raises KeyError
    for a name the file does not list."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads(
        (bench_dir / "limits" / f"{workload}.json").read_text())

    def here(m):
        return "workloads" not in m or workload in m["workloads"]

    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits,
                end_to_end=tuple(m for m in spec["end_to_end"] if here(m)),
                per_layer=tuple(m for m in spec["per_layer"] if here(m)),
                bench_dir=bench_dir)


def load_reader(name: str, bench_dir: Path = BENCH_DIR):
    """The ``read(ctx)`` function of ``metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read

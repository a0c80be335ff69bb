"""Cells, mixes and readers are found by name; run.py refuses a host
without a TPU."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import check, spec, studies  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_loads_with_its_files(name):
    cell = spec.load_cell(name)
    assert cell.chips in (1, 4)
    assert cell.config["precision"] == "float32"
    assert cell.limits["round_gap"]["limit"] > 0
    assert {m["name"] for m in cell.end_to_end} == {
        "rounds_per_s", "device_peak_mb", "setup_s"}
    for m in cell.per_layer:
        assert callable(spec.load_reader(m["name"]))
    kw = studies.sweep_kwargs(cell.config, cell.traffic, 7, 0)
    assert kw["n_clients"] == cell.config["clients"]
    assert len(kw["seeds"]) == cell.traffic["seeds_per_study"]


def test_config_files_match_the_benchmark():
    for c in BENCH["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"]
        assert conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]


def test_merge_metric_only_where_chips_exchange():
    for name in CELLS:
        names = {m["name"] for m in spec.load_cell(name).per_layer}
        assert ("merge.collective_us_per_round" in names) == (
            spec.load_cell(name).chips > 1)


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError):
        spec.load_cell("no-such-cell")


def test_study_seeds():
    a = studies.study_seeds(2**40 + 3, 0, 8)
    assert a == studies.study_seeds(2**40 + 3, 0, 8)
    assert a != studies.study_seeds(2**40 + 3, 1, 8)
    assert len(set(a)) == 8 and all(0 <= s < 2**31 for s in a)
    assert studies.study_seeds(-5, 0, 2) == studies.study_seeds(-5, 0, 2)


@pytest.mark.parametrize("name", CELLS)
def test_sample_covers_every_policy(name):
    traffic = spec.load_cell(name).traffic
    n = int(traffic["check_trajectories"])
    picks = check.sample(3, traffic, 11, n)
    assert len(picks) == len(set(picks)) >= n
    assert {p.policy for p in picks} == set(range(len(traffic["policies"])))
    assert picks == check.sample(3, traffic, 11, n)
    # a sample asked for fewer grid points than policies still has each
    few = check.sample(1, traffic, 12, 2)
    assert {p.policy for p in few} == set(range(len(traffic["policies"])))
    # and it never repeats a grid point where the window has fewer
    one = dict(traffic, etas=[1.5], seeds_per_study=1)
    assert len(check.sample(1, one, 11, 20)) == len(traffic["policies"])


def test_a_cell_added_by_files_alone(tmp_path):
    """A new configuration, mix, metric and cell: files and entries in
    their own tree, and no edit of a file the benchmark has."""
    bench = tmp_path / "chipbench"
    for sub in ("configs", "traffic", "limits", "metrics"):
        (bench / sub).mkdir(parents=True)
    conf = json.loads((ROOT / "chipbench/configs/paper-k100.json").read_text())
    conf.update(name="tiny", clients=40)
    (bench / "configs" / "tiny.json").write_text(json.dumps(conf))
    mix = json.loads((ROOT / "chipbench/traffic/paper-grid.json").read_text())
    mix.update(name="short", rounds=7)
    (bench / "traffic" / "short.json").write_text(json.dumps(mix))
    (bench / "limits" / "tiny-short.json").write_text(
        json.dumps({"round_gap": {"limit": 1e-4}}))
    (bench / "metrics" / "studies_seen.py").write_text(
        "def read(ctx):\n    return float(ctx.n_studies)\n")
    b = {"configs": [{"name": "tiny", "file": "chipbench/configs/tiny.json"}],
         "workloads": [{"name": "tiny-short", "config": "tiny",
                        "traffic": "short", "chips": 1}],
         "end_to_end": BENCH["end_to_end"],
         "per_layer": [{"name": "studies_seen", "moves": "rounds_per_s",
                        "workloads": ["tiny-short"]},
                       {"name": "elsewhere", "workloads": ["other"]}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    cell = spec.load_cell("tiny-short", root=tmp_path, bench_dir=bench)
    assert cell.config["clients"] == 40 and cell.traffic["rounds"] == 7
    assert [m["name"] for m in cell.per_layer] == ["studies_seen"]
    read = spec.load_reader("studies_seen", bench)

    class Ctx:
        n_studies = 3
    assert read(Ctx()) == 3.0
    kw = studies.sweep_kwargs(cell.config, cell.traffic, 5, 2)
    assert kw["n_clients"] == 40 and kw["n_rounds"] == 7


def _run(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "paper-grid",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_a_host_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == "" or "correct" not in p.stdout
    assert "needs a TPU" in p.stderr


def test_run_fails_without_the_system_under_test(tmp_path):
    """Only BENCHMARK.json and the benchmark's own files: no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    p = _run(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert "correct" not in p.stdout

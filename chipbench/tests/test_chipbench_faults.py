"""``correct`` holds for the program and fails for the control and for a
broken timed path: a run driven end to end on the CPU at a small size,
past the harness's look for a chip."""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import check, harness, spec  # noqa: E402

SMALL = {"paper-grid": {"clients": 100, "rounds": 12, "etas": [1.0, 1.9],
                        "seeds_per_study": 2},
         "crossdevice-1e5": {"clients": 2000, "rounds": 12}}

# Cells that the harness and the reference can run but BENCHMARK.json does
# not list: each derived from a listed cell's files (name, config changes,
# mix changes, chips), written only into the test's own tree.
METRO = {"scenario": {"name": "metro-congestion", "congestion_cells": 100,
                      "congestion_sigma": 0.5}}
DERIVED = {
    "cells-2k": ("crossdevice-1e5", dict(METRO, clients=2000),
                 {"rounds": 40, "hierarchy": "cells", "s_cells": 10,
                  "check_trajectories": 8}, 1),
    "seg4-4k": ("crossdevice-1e5",
                dict(METRO, clients=4000,
                     layout={"chips": 4, "shard": "clients"}),
                {"rounds": 12, "chunk_rounds": 4, "check_trajectories": 8,
                 "policies": [["elementwise_ucb", 50.0],
                              ["naive_ucb", 1000.0], ["oracle", 0.0],
                              ["random", 0.0]]}, 4),
}


def small_cell(name: str, tmp: Path) -> spec.Cell:
    """The cell ``name`` (listed, or in DERIVED) with fewer clients and
    rounds, in its own tree."""
    shutil.copytree(ROOT / "chipbench", tmp / "chipbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"),
                    dirs_exist_ok=True)
    bench = tmp / "chipbench"
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    if name in DERIVED:
        base, conf_over, mix_over, chips = DERIVED[name]
    else:
        base, over = name, dict(SMALL[name])
        conf_over, mix_over = {"clients": over.pop("clients")}, over
    w = next(x for x in b["workloads"] if x["name"] == base)
    conf = json.loads((bench / "configs" / f"{w['config']}.json").read_text())
    mix = json.loads((bench / "traffic" / f"{w['traffic']}.json").read_text())
    if name in DERIVED:
        w = dict(w, name=name, config=name, traffic=name, chips=chips)
        b["workloads"].append(w)
        b["configs"].append({"name": name,
                             "file": f"chipbench/configs/{name}.json"})
        shutil.copy(bench / "limits" / f"{base}.json",
                    bench / "limits" / f"{name}.json")
    conf.update(conf_over)
    mix.update(mix_over)
    (bench / "configs" / f"{w['config']}.json").write_text(json.dumps(conf))
    (bench / "traffic" / f"{w['traffic']}.json").write_text(json.dumps(mix))
    (tmp / "BENCHMARK.json").write_text(json.dumps(b))
    return spec.load_cell(name, root=tmp, bench_dir=bench)


def run(cell, tmp, seed=2**33 + 5):
    import jax
    jax.clear_caches()
    return harness.run_cell(cell, seed, 0.5, False,
                            t_start=time.perf_counter(), require_tpu=False,
                            root=tmp, out_dir=tmp / "out")


@pytest.fixture(scope="module")
def paper(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("paper")
    return small_cell("paper-grid", tmp), tmp


def test_program_is_correct(paper):
    cell, tmp = paper
    res = run(cell, tmp)
    assert res["correct"], res["check"]
    assert list(res)[-1] == "check"
    assert res["check"]["round_gap"]["value"] < 1e-5


@pytest.mark.parametrize("name", list(SMALL))
def test_control_in_bfloat16_fails(name, tmp_path):
    cell = small_cell(name, tmp_path)
    from repro.sim import engine_jax
    from chipbench import studies
    done = []
    for i in range(2):
        kw = studies.sweep_kwargs(cell.config, cell.traffic, 9, i)
        done.append({"kwargs": kw,
                     "round_times": engine_jax.sweep(**kw).round_times})
    found = check.compare(cell, done, 9, control="bfloat16")
    assert not check.verdict(cell, found)[0], found
    assert check.verdict(cell, check.compare(cell, done, 9))[0]


def test_state_left_unchanged_fails(paper, monkeypatch):
    cell, tmp = paper
    from repro.core import bandit_jax
    monkeypatch.setattr(bandit_jax, "observe",
                        lambda state, *a, **k: state)
    assert not run(cell, tmp)["correct"]


def test_half_of_the_grid_left_out_fails(paper, monkeypatch):
    cell, tmp = paper
    import jax.numpy as jnp
    from repro.sim import engine_jax
    inner = engine_jax._run_grid

    def half(env, bits, hypers, eta, seed, **kw):
        # the grid's first half is run twice; its second half never
        h = seed.shape[0] // 2
        return inner(env, bits, hypers, jnp.concatenate([eta[:h], eta[:h]]),
                     jnp.concatenate([seed[:h], seed[:h]]), **kw)
    monkeypatch.setattr(engine_jax, "_run_grid", half)
    assert not run(cell, tmp)["correct"]


def test_answer_altered_fails(paper, monkeypatch):
    cell, tmp = paper
    from repro.core import bandit_jax
    inner = bandit_jax.schedule_gathered

    def altered(valid, ud, ul):
        rt, incs = inner(valid, ud, ul)
        return rt * 1.01, incs
    monkeypatch.setattr(bandit_jax, "schedule_gathered", altered)
    assert not run(cell, tmp)["correct"]


@pytest.mark.parametrize("name", ["crossdevice-1e5", "cells-2k"])
def test_large_k_paths_are_correct_and_state_faults_fail(name, tmp_path,
                                                         monkeypatch):
    cell = small_cell(name, tmp_path)
    assert run(cell, tmp_path)["correct"]
    from repro.core import bandit_jax
    monkeypatch.setattr(bandit_jax, "observe",
                        lambda state, *a, **k: state)
    assert not run(cell, tmp_path)["correct"]


FOUR_CHIPS = r"""
import json, sys, time
from pathlib import Path
sys.path.insert(0, sys.argv[1])
import jax
from chipbench import harness
from chipbench.tests.test_chipbench_faults import small_cell
tmp = Path(sys.argv[2])
cell = small_cell("seg4-4k", tmp)
def run():
    jax.clear_caches()
    return harness.run_cell(cell, 77, 0.5, False, t_start=time.perf_counter(),
                            require_tpu=False, root=tmp, out_dir=tmp / "out")
sound = run()["correct"]
jax.lax.psum = lambda x, axis_name, **k: x
jax.lax.all_gather = lambda x, axis_name, **k: x[None]
print(json.dumps({"sound": sound, "no_exchange": run()["correct"]}))
"""


def test_exchange_between_chips_left_out_fails(tmp_path):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", FOUR_CHIPS, str(ROOT),
                        str(tmp_path)], env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out == {"sound": True, "no_exchange": False}

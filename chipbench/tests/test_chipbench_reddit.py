"""The four-chip cell ``reddit-1.66e6-seg4`` off the chip: its full
population takes the client-sharded segmented route, its files list the
merge readers, and, cut to K = 8,000 on four forced host devices,
``correct`` holds for the program and fails when the exchange between
chips is left out.  Everything runs in one subprocess, which forces the
four devices before JAX starts."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CELL = "reddit-1.66e6-seg4"

SCRIPT = r"""
import json, sys, time
from pathlib import Path
root, tmp, cell_name = Path(sys.argv[1]), Path(sys.argv[2]), sys.argv[3]
sys.path[:0] = [str(root), str(root / "src")]
import jax
from chipbench import harness, spec, studies
from repro.distributed import sharding
from repro.sim import engine_jax

out = {}
full = spec.load_cell(cell_name, root=root)
out["per_layer"] = [m["name"] for m in full.per_layer]
out["chips"] = full.chips
kw = studies.sweep_kwargs(full.config, full.traffic, 7, 0)
mesh = sharding.sweep_mesh(kw["devices"])
out["block"] = sharding.even_shards(kw["n_clients"], mesh)
# sweep()'s routing predicate for these arguments
out["segmented"] = (kw["shard"] == "clients" and kw["fast_sampling"]
                    and kw["hierarchy"] == "flat"
                    and out["block"] is not None)

# the cell cut to K = 8,000 and 12 rounds, in a tree of its own
from chipbench.tests import test_chipbench_faults as faults
faults.SMALL[cell_name] = {"clients": 8000, "rounds": 12, "chunk_rounds": 4}
cell = faults.small_cell(cell_name, tmp)

routes = []
inner = engine_jax._run_one_clients_segmented
def spy(*a, **k):
    routes.append(k["policy"])
    return inner(*a, **k)
engine_jax._run_one_clients_segmented = spy

def run():
    jax.clear_caches()
    return harness.run_cell(cell, 2**33 + 21, 0.5, False,
                            t_start=time.perf_counter(), require_tpu=False,
                            root=tmp, out_dir=tmp / "out")
sound = run()
out["sound"] = {"correct": sound["correct"], "check": sound["check"]}
out["routes"] = sorted(set(routes))
jax.lax.psum = lambda x, axis_name, **k: x
jax.lax.all_gather = lambda x, axis_name, **k: x[None]
cut = run()
out["no_exchange"] = {"correct": cut["correct"], "check": cut["check"]}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def seg4(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("seg4")
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", SCRIPT, str(ROOT), str(tmp),
                        CELL], env=env, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_cut_down_cell_is_correct(seg4):
    assert seg4["sound"]["correct"], seg4["sound"]["check"]
    # every policy of the mix ran through the segmented round
    assert seg4["routes"] == ["elementwise_ucb", "naive_ucb", "oracle",
                              "random"]


def test_cut_down_cell_without_the_exchange_fails(seg4):
    assert not seg4["no_exchange"]["correct"], seg4["no_exchange"]["check"]


def test_full_population_takes_the_segmented_route(seg4):
    assert seg4["chips"] == 4
    assert seg4["block"] == 415_205
    assert seg4["segmented"]


def test_cell_lists_the_merge_readers(seg4):
    assert {"merge.collective_us_per_round", "merge.exchange_mb_per_round",
            "select_roofline"} <= set(seg4["per_layer"])

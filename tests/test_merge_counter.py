"""The client-sharded route's merge counter: ``sweep()`` puts
``merge_bytes`` on its ``sweep.run`` span, and it equals the operand bytes
of the all-reduce and all-gather instructions XLA compiled into the
segmented round bodies, times the rounds each body runs; the one-chip
route carries no counter.  Runs in a subprocess that forces four host
devices before JAX starts."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

SRC = Path(__file__).resolve().parents[1] / "src"
ROUNDS = 4

SCRIPT = r"""
import glob, json, re, sys, tempfile
import jax
from jax.profiler import ProfileData
from repro.sim import engine_jax

ROUNDS = int(sys.argv[1])
ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
            "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
            "u64": 8}
COLLECTIVE = re.compile(r"^\s*%\S+ = (.+?) (all-reduce|all-gather)\(.*"
                        r"replica_groups=\{\{([\d,]+)\}")


def operand_bytes(text):
    # an all-gather's operand is its result over the group; an
    # all-reduce's operands have its result's shapes
    total = 0
    for line in text.splitlines():
        m = COLLECTIVE.match(line)
        if not m:
            continue
        nbytes = 0
        for dtype, dims in re.findall(r"\b([a-z]+\d*)\[([\d,]*)\]",
                                      m.group(1)):
            n = 1
            for d in filter(None, dims.split(",")):
                n *= int(d)
            nbytes += n * ITEMSIZE[dtype]
        if m.group(2) == "all-gather":
            nbytes //= len(m.group(3).split(","))
        total += nbytes
    return total


inner = engine_jax._run_grid
texts = []


def spy(*a, **kw):
    texts.append(inner.lower(*a, **kw).compile().as_text())
    return inner(*a, **kw)


engine_jax._run_grid = spy
out = {}
for case, devices, policy in [("greedy", 4, "elementwise_ucb"),
                              ("score", 4, "naive_ucb"),
                              ("one_chip", None, "elementwise_ucb")]:
    kw = dict(n_clients=400, policies=(policy,), etas=(1.5,), seeds=2,
              n_rounds=ROUNDS, chunk_rounds=2, fast_sampling=True,
              devices=devices, shard="clients")
    engine_jax.sweep(**kw)
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            engine_jax.sweep(**kw)
        (path,) = glob.glob(d + "/**/*.xplane.pb", recursive=True)
        stats = [dict(e.stats)
                 for plane in ProfileData.from_file(path).planes
                 if plane.name.startswith("/host:")
                 for line in plane.lines for e in line.events
                 if e.name == "sweep.run"]
    out[case] = {"run_stats": [sorted(s) for s in stats],
                 "merge_bytes": [s.get("merge_bytes") for s in stats],
                 "compiled": operand_bytes(texts[-1])}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def counted(tmp_path_factory):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(SRC),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", SCRIPT, str(ROUNDS)], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", ["greedy", "score"])
def test_merge_bytes_is_what_the_collectives_take(counted, case):
    got = counted[case]
    # two grid points, each its own round body holding its collectives
    assert got["compiled"] > 0
    assert got["merge_bytes"] == [got["compiled"] * ROUNDS]


def test_one_chip_route_carries_no_merge_counter(counted):
    got = counted["one_chip"]
    assert got["compiled"] == 0
    assert got["merge_bytes"] == [None]
    assert got["run_stats"] == [[]]

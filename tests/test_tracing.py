"""The round's phase scopes and ``sweep()``'s host spans (CPU).

Every round path names its four phases (core.bandit_jax.ROUND_SCOPES) in
the compiled module's op metadata, which is where a device trace reads
them; one ``sweep()`` under ``jax.profiler.trace`` records its four host
spans once each, in order, with the bytes it moved.
"""

import glob

import jax
import pytest

from _scopes import compiled_sweep_text, round_scopes
from repro.core import bandit_jax
from repro.sim import engine_jax

SMALL = dict(etas=(1.5,), seeds=2, n_rounds=4)

# the round paths a cell can take: K = 100 on the legacy presample stream
# (fused elementwise_ucb beside the unfused naive_ucb, which stays below
# its FUSED_MIN_K), the streamed fused round at K = 2048, the two-level
# cell selection, and the same K = 100 grid on the forced streamed path
# with its batched presample (the paper-grid cell's)
PATHS = {
    "legacy-presample": dict(n_clients=100, fast_sampling=False,
                             policies=("elementwise_ucb", "naive_ucb")),
    "streamed-fused": dict(n_clients=2048, frac_request=0.05,
                           policies=("elementwise_ucb", "random")),
    "cells": dict(scenario="metro-congestion", n_clients=2000,
                  hierarchy="cells", s_cells=4,
                  policies=("naive_ucb", "fedcs")),
    "streamed-presample": dict(n_clients=100, fast_sampling=True,
                               policies=("fedcs", "discounted_ucb")),
    "legacy-churn": dict(scenario="client-churn", n_clients=100,
                         fast_sampling=False, deadline=50.0,
                         policies=("oracle",)),
}


def test_scope_names():
    assert bandit_jax.ROUND_SCOPES == ("round.cand_draw", "round.times",
                                       "round.select", "round.finish")


@pytest.mark.parametrize("path", sorted(PATHS))
def test_every_round_path_names_its_four_phases(path):
    text = compiled_sweep_text(**SMALL, **PATHS[path])
    assert round_scopes(text) == set(bandit_jax.ROUND_SCOPES)


def _host_spans(trace_dir):
    from jax.profiler import ProfileData
    files = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    assert len(files) == 1, files
    spans = []
    for plane in ProfileData.from_file(files[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.start_ns, e.name, dict(e.stats))
                          for e in line.events
                          if e.name in engine_jax.SWEEP_SPANS]
    return [(name, stats) for _, name, stats in sorted(spans)]


@pytest.mark.parametrize("deadline", [None, 50.0])
def test_sweep_records_its_host_spans(tmp_path, deadline):
    k, p, g, r, s = 100, 2, 2, 4, 5
    kw = dict(SMALL, n_clients=k, s_round=s,
              policies=("elementwise_ucb", "fedcs"), deadline=deadline)
    engine_jax.sweep(**kw)                 # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        res = engine_jax.sweep(**kw)
    spans = _host_spans(tmp_path)
    assert [n for n, _ in spans] == list(engine_jax.SWEEP_SPANS)
    stats = dict(spans)
    # up: four [K] env arrays (three f32, one i32), model_bits, [P] hypers,
    # [G] etas (f32) and [G] seeds (i32); down: [P, G, R] f32 round times,
    # plus [P, G, R, S] i32 flags with the failure layer on
    assert stats["sweep.put"]["bytes"] == 16 * k + 4 + 4 * p + 8 * g
    down = 4 * p * g * r + (4 * p * g * r * s if deadline else 0)
    assert stats["sweep.fetch"]["bytes"] == down
    assert res.round_times.nbytes == 4 * p * g * r
    assert "bytes" not in stats["sweep.env"]
    assert "bytes" not in stats["sweep.run"]

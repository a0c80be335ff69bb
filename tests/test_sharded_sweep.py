"""Equivalence tests for the sharded/chunked sweep subsystem
(distributed/sharding.py + the ``devices``/``shard``/``chunk_rounds`` knobs
of sim/engine_jax.sweep and fl/engine.accuracy_sweep).

Two complementary halves:

* single-device properties (chunked scan == single-shot scan *exactly*,
  because every draw comes from per-round keys; K = 10^4 runs in O(c*K)
  memory) — always run;
* multi-device equivalence (``test_multidevice_*``: grid-sharded and
  client-sharded results match the single-device path — selections exact,
  times within 1e-4) — run in-process when the runtime has >= 2 devices
  (the CI job exports ``XLA_FLAGS=--xla_force_host_platform_device_count=8``),
  and otherwise re-driven in a subprocess that forces 8 host devices, so
  the tier-1 suite on a 1-device host still exercises them.
"""

import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.distributed.sharding import host_device_flag
from repro.sim import engine_jax
from repro.sim.scenarios import Scenario

SIM_KW = dict(n_rounds=12, n_clients=24, seeds=2, etas=(1.5,),
              policies=("elementwise_ucb", "discounted_ucb"),
              frac_request=0.3)

needs_devices = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs a multi-device runtime (see the subprocess test)")


def _tiny_fl(n_clients=16, **kw):
    from repro.fl import engine
    from repro.models import cnn
    cfg = cnn.CnnConfig(image_size=8, channels=(8,), pool_after=(0,),
                        fc_units=(16,), batchnorm=False)
    task = engine.make_cnn_task("paper-baseline", n_clients, cfg=cfg,
                                n_train=400, n_test=200, eval_batch=100,
                                max_samples=40, batch_size=10)
    base = dict(task=task, policies=("elementwise_ucb", "discounted_ucb"),
                seeds=2, n_rounds=4, cfg=cfg, s_round=3, frac_request=0.5,
                epochs=1, batch_size=10)
    base.update(kw)
    return engine, base


# ---------------------------------------------------------------------------
# single-device properties
# ---------------------------------------------------------------------------

def test_chunked_sweep_identical():
    """Per-round keys make any chunk size consume the identical stream:
    chunked == single-shot bitwise."""
    a = engine_jax.sweep(**SIM_KW)
    b = engine_jax.sweep(**SIM_KW, chunk_rounds=3)
    np.testing.assert_array_equal(a.round_times, b.round_times)


def test_chunked_sweep_churn_identical():
    kw = dict(SIM_KW, n_rounds=8)
    a = engine_jax.sweep("client-churn", **kw)
    b = engine_jax.sweep("client-churn", **kw, chunk_rounds=4)
    np.testing.assert_array_equal(a.round_times, b.round_times)


def test_chunk_rounds_must_divide():
    with pytest.raises(ValueError, match="divisible"):
        engine_jax.sweep(**dict(SIM_KW, n_rounds=10), chunk_rounds=3)


def test_large_k_chunked_runs():
    """K = 10^4 clients: the chunked scan holds only chunk_rounds x K draws
    at a time (O(c*K), not O(R*K)) and completes with finite output."""
    res = engine_jax.sweep(n_rounds=30, n_clients=10_000, seeds=1,
                           etas=(1.5,), policies=("elementwise_ucb",),
                           chunk_rounds=10, frac_request=0.01)
    assert res.round_times.shape == (1, 1, 1, 30)
    assert np.isfinite(res.round_times).all()
    assert np.all(res.round_times > 0)


def test_fl_chunked_identical():
    engine, kw = _tiny_fl()
    a = engine.accuracy_sweep(**kw)
    b = engine.accuracy_sweep(**kw, chunk_rounds=2)
    np.testing.assert_array_equal(a.selected, b.selected)
    np.testing.assert_array_equal(a.round_times, b.round_times)
    np.testing.assert_array_equal(a.accuracy, b.accuracy)


# ---------------------------------------------------------------------------
# multi-device equivalence (in-process; the CI 8-device job runs these)
# ---------------------------------------------------------------------------

@needs_devices
def test_multidevice_sim_sharding_matches_single_device():
    n = jax.device_count()
    ref = engine_jax.sweep(**SIM_KW)
    for extra in (dict(devices=n, shard="grid"),
                  dict(devices=n, shard="grid", chunk_rounds=3),
                  dict(devices=n, shard="clients"),
                  dict(devices="all", shard="clients", chunk_rounds=4)):
        got = engine_jax.sweep(**SIM_KW, **extra)
        np.testing.assert_allclose(got.round_times, ref.round_times,
                                   rtol=1e-4, err_msg=str(extra))


@needs_devices
def test_multidevice_fused_round_matches_unfused_single_device():
    """The fused round (the default path the other tests exercise) against
    the *unfused* single-device baseline across both shard modes: the
    fused/unfused equivalence must survive shard_map and GSPMD, not just
    the single-device scan (tests/test_bandit_round.py)."""
    n = jax.device_count()
    ref = engine_jax.sweep(**SIM_KW, fused=False)
    for extra in (dict(devices=n, shard="grid"),
                  dict(devices=n, shard="clients", chunk_rounds=3)):
        got = engine_jax.sweep(**SIM_KW, **extra)      # fused default
        np.testing.assert_allclose(got.round_times, ref.round_times,
                                   rtol=1e-4, err_msg=str(extra))


@needs_devices
def test_multidevice_segmented_bitwise():
    """K >= FAST_SAMPLING_MIN_K divisible by the mesh routes
    shard="clients" through the explicit shard_map segmented round loop
    (local top-S + all_gather + segmented reduce; psum-assembled greedy) —
    round times must equal the flat path BITWISE, chunked or not, for a
    greedy and a score policy."""
    n = jax.device_count()
    kw = dict(n_rounds=10, n_clients=2048, seeds=2, etas=(1.5,),
              policies=("elementwise_ucb", "naive_ucb"), frac_request=0.05)
    ref = engine_jax.sweep("correlated-congestion", **kw)
    for extra in (dict(devices=n, shard="clients"),
                  dict(devices=n, shard="clients", chunk_rounds=5)):
        got = engine_jax.sweep("correlated-congestion", **kw, **extra)
        np.testing.assert_array_equal(got.round_times, ref.round_times,
                                      err_msg=str(extra))


@needs_devices
def test_multidevice_segmented_names_round_scopes():
    """The segmented round loop names the round's four phases in its ops
    (the merge's psum and all-gather under ``round.select``)."""
    from _scopes import compiled_sweep_text, round_scopes
    from repro.core import bandit_jax
    kw = dict(n_rounds=4, n_clients=2048, seeds=2, etas=(1.5,),
              policies=("elementwise_ucb", "naive_ucb"), frac_request=0.05,
              devices=jax.device_count(), shard="clients")
    text = compiled_sweep_text("correlated-congestion", **kw)
    assert round_scopes(text) == set(bandit_jax.ROUND_SCOPES)
    assert re.search(r'all-gather[^\n]*op_name="[^"]*round\.select', text)


@needs_devices
def test_multidevice_segmented_faults_large_k():
    """Faults-on segmented fused round at K=1e5: censored observations,
    deadline clock, and per-slot flags all bitwise across the shards."""
    kw = dict(n_rounds=6, n_clients=100_000, seeds=1, etas=(1.5,),
              policies=("elementwise_ucb",), frac_request=0.002,
              deadline=50.0, chunk_rounds=3)
    ref = engine_jax.sweep("flaky-clients", **kw)
    got = engine_jax.sweep("flaky-clients", **kw,
                           devices=jax.device_count(), shard="clients")
    np.testing.assert_array_equal(got.round_times, ref.round_times)
    np.testing.assert_array_equal(got.flags, ref.flags)


@needs_devices
def test_multidevice_sim_sharding_churn():
    n = jax.device_count()
    heavy = Scenario("churn-heavy", churn_prob=0.5)
    kw = dict(SIM_KW, n_rounds=8)
    ref = engine_jax.sweep(heavy, **kw)
    got = engine_jax.sweep(heavy, **kw, devices=n, shard="grid")
    np.testing.assert_allclose(got.round_times, ref.round_times, rtol=1e-4)


@needs_devices
def test_multidevice_fl_sharding_matches_single_device():
    n = jax.device_count()
    engine, kw = _tiny_fl(n_clients=16)
    ref = engine.accuracy_sweep(**kw)
    for extra in (dict(devices=n, shard="grid"),
                  dict(devices=n, shard="clients"),
                  dict(devices=n, shard="grid", chunk_rounds=2)):
        got = engine.accuracy_sweep(**kw, **extra)
        np.testing.assert_array_equal(got.selected, ref.selected,
                                      err_msg=str(extra))
        np.testing.assert_allclose(got.round_times, ref.round_times,
                                   rtol=1e-4, err_msg=str(extra))
        np.testing.assert_allclose(got.accuracy, ref.accuracy, atol=1e-3,
                                   err_msg=str(extra))


# ---------------------------------------------------------------------------
# subprocess driver: forces 8 host devices when this runtime has only 1
# ---------------------------------------------------------------------------

@pytest.mark.skipif(jax.device_count() >= 2,
                    reason="multi-device tests already ran in-process")
def test_multidevice_equivalence_in_subprocess():
    """Re-run the ``test_multidevice_*`` tests of this file in a child
    pytest whose XLA_FLAGS force 8 virtual host devices (the main process
    must keep seeing 1 device per the dry-run isolation rule)."""
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ)              # keep venv/conda/LD_LIBRARY_PATH
    env["PYTHONPATH"] = os.pathsep.join(
        [str(root / "src")] + ([env["PYTHONPATH"]]
                               if env.get("PYTHONPATH") else []))
    env["XLA_FLAGS"] = host_device_flag(8)
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", str(Path(__file__)), "-q",
         "-k", "multidevice and not subprocess", "-p", "no:cacheprovider"],
        env=env, cwd=str(root), capture_output=True, text=True, timeout=1800)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    # returncode 0 plus >= 1 passed guards against an empty -k selection
    # (pytest exits 5 on zero collected, but stay explicit)
    m = re.search(r"(\d+) passed", proc.stdout)
    assert m and int(m.group(1)) >= 1, proc.stdout[-1500:]

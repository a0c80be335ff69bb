"""The reduction from trace events to per-layer metrics, on a small list."""

import math
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import counts, peaks, spec, trace  # noqa: E402
from chipbench.trace import Event  # noqa: E402

def test_interval_algebra():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]
    assert trace.total([(0, 3), (5, 8)]) == 6
    assert trace.subtract([(0, 10)], [(2, 3), (5, 7)]) == [
        (0, 2), (3, 5), (7, 10)]
    assert trace.subtract([(0, 4), (6, 10)], [(3, 7)]) == [(0, 3), (7, 10)]
    assert trace.gaps([(2, 3), (5, 7)], 0, 10) == [(0, 2), (3, 5), (7, 10)]


def test_op_names_and_self_time():
    assert trace.op_name("%select_fedcs.9 = s32[24,1,5]{2,1,0} custom-call("
                         "f32[24,8,128] %bitcast.9)") == "select_fedcs.9"
    assert trace.op_name("all-gather.2") == "all-gather.2"
    evs = [Event("while.1", 0, 100), Event("fusion.2", 10, 30),
           Event("select_x.3", 40, 70), Event("fusion.4", 45, 50),
           Event("copy.5", 120, 130)]
    own = {e.name: t for e, t in trace.self_times(evs)}
    assert own == {"while.1": 50, "fusion.2": 20, "select_x.3": 25,
                   "fusion.4": 5, "copy.5": 10}


def test_name_matching():
    assert trace.is_kernel("select_fedcs", ("select_", "topk_slots"))
    assert trace.is_kernel("topk_slots", ("select_", "topk_slots"))
    assert not trace.is_kernel("fusion.12", ("select_", "topk_slots"))
    assert trace.is_collective("all-gather.3")
    assert trace.is_collective("all-reduce-start")
    assert not trace.is_collective("fusion.12")


class _Cell:
    """A stand-in for spec.Cell with the fields the readers use."""

    def __init__(self, config, traffic):
        self.config, self.traffic = config, traffic


def _cell(policies, chips=1):
    config = {"clients": 1000, "frac_request": 0.1, "select": 5,
              "scenario": {"congestion_cells": 10}}
    traffic = {"policies": policies, "etas": [1.5], "seeds_per_study": 2,
               "rounds": 10}
    return _Cell(config, traffic)


def _ctx(devices, studies, n_studies=2, bandit_rounds=40, policies=None):
    host = [Event("chipbench.study", s, e) for s, e in studies]
    host.append(Event("PjitFunction(_run_grid)", 110, 160))
    return trace.TraceContext(
        devices=devices, host=host, studies=studies,
        cell=_cell(policies or [["fedcs", 0.0], ["naive_ucb", 1000.0]]),
        n_studies=n_studies, bandit_rounds=bandit_rounds,
        device_kind="TPU v5 lite")


# two studies: [100, 200) and [200, 300); the device is busy 60 ns in each
DEV = [Event("fusion.1", 110, 140), Event("select_fedcs", 140, 150),
       Event("fusion.2", 150, 170),
       Event("while.3", 210, 270), Event("select_fedcs", 230, 240)]
STUDIES = [(100, 200), (200, 300)]


def test_context_busy_idle_and_kernels():
    ctx = _ctx([DEV], STUDIES)
    assert ctx.window == (100, 300)
    assert ctx.busy_s() == pytest.approx(120e-9)
    assert ctx.idle_shares() == [pytest.approx(1 - 120 / 200)]
    ns, n = ctx.op_time(lambda x: x.startswith("select_"))
    assert (ns, n) == (20, 2)
    assert ctx.busy_outside(lambda x: x.startswith("select_")) == 100
    b = ctx.breakdown()
    assert b["device_ops"][0] == ["while.3", 50e-9]     # less the kernel
    assert b["idle_gaps"][0][1] == pytest.approx(40e-9)   # [170, 210)
    assert b["idle_gaps"][0][0] == "chipbench.study"
    assert ctx.host_label(185) == "chipbench.study"
    assert ctx.host_label(150) == "PjitFunction(_run_grid)"
    assert ctx.host_label(50) == "outside any host span"


def test_readers_on_a_small_trace():
    read = {n: spec.load_reader(n) for n in (
        "entry.host_ms_per_call", "round.xla_us_per_round",
        "select.us_per_round", "select_roofline",
        "merge.collective_us_per_round", "device.idle_share", "round_mfu")}
    ctx = _ctx([DEV], STUDIES)
    # host time: 200 ns of spans minus 120 ns busy, over 2 studies
    assert read["entry.host_ms_per_call"](ctx) == pytest.approx(40e-6)
    assert read["round.xla_us_per_round"](ctx) == pytest.approx(
        100 / 40 / 1e3)
    assert read["select.us_per_round"](ctx) == pytest.approx(20 / 40 / 1e3)
    assert read["merge.collective_us_per_round"](ctx) is None
    assert read["device.idle_share"](ctx) == pytest.approx(40.0)
    # fedcs ran its kernel in all 2 studies x 2 seeds x 10 rounds
    ops, nbytes = counts.selection("fedcs", 100, 5)
    least = peaks.least_time_s(ops * 40, nbytes * 40, "TPU v5 lite")
    assert read["select_roofline"](ctx) == pytest.approx(
        100 * least / 20e-9)
    per = [peaks.least_time_s(*counts.round_work(p, 100, 5, 10),
                              "TPU v5 lite") for p in ("fedcs", "naive_ucb")]
    assert read["round_mfu"](ctx) == pytest.approx(
        100 * 40 / 200e-9 * sum(per) / 2)


def test_readers_across_four_devices():
    read = {n: spec.load_reader(n) for n in (
        "merge.collective_us_per_round", "device.idle_share",
        "select.us_per_round", "select_roofline")}
    devs = [[Event("all-gather.1", 100, 110), Event("topk_slots", 110, 130),
             Event("all-reduce.2", 130, 135 + 5 * d)] for d in range(4)]
    ctx = _ctx(devs, [(100, 200)], n_studies=1, bandit_rounds=20,
               policies=[["elementwise_ucb", 50.0], ["naive_ucb", 1000.0]])
    # collectives: 15, 20, 25, 30 ns on the four devices, mean 22.5
    assert read["merge.collective_us_per_round"](ctx) == pytest.approx(
        22.5 / 20 / 1e3)
    assert read["device.idle_share"](ctx) == pytest.approx(65.0)
    assert read["select.us_per_round"](ctx) == pytest.approx(20 / 20 / 1e3)
    # topk_slots ranks naive_ucb's rounds only: 1 study x 2 seeds x 10
    ops, nbytes = counts.selection("naive_ucb", 100, 5)
    least = peaks.least_time_s(ops * 20, nbytes * 20, "TPU v5 lite")
    assert read["select_roofline"](ctx) == pytest.approx(100 * least / 80e-9)


def test_counts():
    ops, nbytes = counts.selection("elementwise_ucb", 10_000, 5)
    assert nbytes == 10_000 * 4 * 4 + 5 * 4
    assert ops == 10_000 * (9 + 5 * 7)
    ops, nbytes = counts.selection("naive_ucb", 100, 5)
    assert nbytes == 100 * 3 * 4 + 20 and ops == 100 * (7 + 5 * 2)
    r_ops, r_bytes = counts.round_work("random", 10, 5, 0)
    s_ops, s_bytes = counts.selection("random", 10, 5)
    assert r_bytes == s_bytes + 10 * 12 + 5 * 12 * 2 * 4
    assert r_ops == s_ops + 10 * 60 + 10 + 50
    assert set(counts.COLUMNS) == set(counts.SCORE_OPS)


def test_peaks():
    assert peaks.least_time_s(197e12, 0, "TPU v5 lite") == pytest.approx(1.0)
    assert peaks.least_time_s(0, 819e9, "TPU v5 lite") == pytest.approx(1.0)
    with pytest.raises(ValueError):
        peaks.peaks("TPU v9 imaginary")
    assert math.isclose(peaks.PEAKS["TPU v5 lite"]["hbm_bytes"], 16e9)

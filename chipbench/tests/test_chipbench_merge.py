"""``merge.exchange_mb_per_round`` on a small trace built from a text
proto: it sums the ``merge_bytes`` counter of the ``sweep.run`` spans
inside the studies, per bandit-round, and has nothing to read where no
span carries the counter."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import spec, trace  # noqa: E402

READ = spec.load_reader("merge.exchange_mb_per_round")
STUDIES = [(1000, 2000), (3000, 4000)]
ROUNDS = 8


def _xspace(counters) -> bytes:
    """A TPU plane with one op per study, and a host line with the study
    spans and a ``sweep.run`` span in each (``counters``: its
    ``merge_bytes`` or None) plus a warm-up one before the studies."""
    from jax.profiler import ProfileData

    metas: dict[str, int] = {}

    def event(name, start, end, nbytes=None):
        mid = metas.setdefault(name, len(metas) + 1)
        stat = ("" if nbytes is None else
                f" stats {{ metadata_id: 1 int64_value: {nbytes} }}")
        return (f"events {{ metadata_id: {mid} offset_ps: {start * 1000} "
                f"duration_ps: {(end - start) * 1000}{stat} }}")

    ops = [event("%fusion.1 = f32[8] x()", lo + 100, lo + 900)
           for lo, _ in STUDIES]
    host = [event("sweep.run", 100, 200, counters[0])]
    for (lo, hi), n in zip(STUDIES, counters):
        host += [event("chipbench.study", lo, hi),
                 event("sweep.run", lo + 50, lo + 80, n)]
    event_meta = " ".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for n, i in metas.items())
    stat_meta = ('stat_metadata { key: 1 value { id: 1 '
                 'name: "merge_bytes" } }')
    text = f"""
    planes {{ id: 1 name: "/device:TPU:0"
      lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {" ".join(ops)} }}
      {event_meta} {stat_meta} }}
    planes {{ id: 2 name: "/host:CPU"
      lines {{ id: 2 name: "python" timestamp_ns: 0 {" ".join(host)} }}
      {event_meta} {stat_meta} }}
    """
    return ProfileData.text_proto_to_serialized_xspace(text)


class _Cell:
    def __init__(self, bench_dir, name="tiny"):
        self.bench_dir, self.name = bench_dir, name


def _ctx(tmp_path, counters):
    d = tmp_path / "out" / "trace" / "tiny" / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(_xspace(counters))
    return trace.read_trace(tmp_path / "out" / "trace" / "tiny",
                            cell=_Cell(tmp_path), n_studies=len(STUDIES),
                            bandit_rounds=ROUNDS, n_devices=1,
                            device_kind="TPU v5 lite")


def test_counter_per_bandit_round(tmp_path):
    # the warm-up span before the studies is not counted
    got = READ(_ctx(tmp_path, [3_000_000, 5_000_000]))
    assert got == pytest.approx(8_000_000 / ROUNDS / 1e6)


def test_nothing_to_read_without_the_counter(tmp_path):
    assert READ(_ctx(tmp_path, [None, None])) is None

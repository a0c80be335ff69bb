"""Reduce a profiler trace to what the per-layer readers need.

A traced window is a few studies, each inside a host span
``chipbench.study`` that the harness records.  The device side is every
op event on each TPU plane's ops line.  Everything here works on plain
(name, start_ns, end_ns) events, so it is tested on a small list.
"""

from __future__ import annotations

import dataclasses
import glob
from pathlib import Path

STUDY_SPAN = "chipbench.study"
OPS_LINE = "XLA Ops"
# op names of the collectives XLA emits for psum / all_gather
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "allreduce", "allgather")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: int      # ns
    end: int        # ns

    @property
    def dur(self) -> int:
        return self.end - self.start


def op_name(name: str) -> str:
    """The instruction name of a TPU op event, whose name is the whole
    HLO line (``%select_fedcs.9 = s32[...] custom-call(...)``)."""
    if name.startswith("%"):
        return name[1:].split(" = ", 1)[0]
    return name


def union(intervals) -> list[tuple[int, int]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list[tuple[int, int]]:
    """Disjoint sorted intervals ``a`` minus disjoint sorted ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy, lo: int, hi: int) -> list[tuple[int, int]]:
    """Idle intervals of [lo, hi) around the disjoint sorted ``busy``."""
    return subtract([(lo, hi)], busy)


def self_times(events: list[Event]) -> list[tuple[Event, int]]:
    """Each event with its own time: its duration less that of the events
    nested inside it (a ``while`` holds its body's ops on the same line)."""
    order = sorted(events, key=lambda e: (e.start, -e.end))
    own = {id(e): e.dur for e in order}
    stack: list[Event] = []
    for e in order:
        while stack and stack[-1].end <= e.start:
            stack.pop()
        if stack and e.end <= stack[-1].end:
            own[id(stack[-1])] -= e.dur
        stack.append(e)
    return [(e, own[id(e)]) for e in order]


def is_kernel(name: str, kernels: tuple[str, ...]) -> bool:
    return any(name == k or name.startswith(k) for k in kernels)


def is_collective(name: str) -> bool:
    low = name.lower()
    return any(c in low for c in COLLECTIVES)


@dataclasses.dataclass
class TraceContext:
    """What one traced window holds, plus the cell it ran."""

    devices: list[list[Event]]     # op events per device used
    host: list[Event]              # events of the thread that ran studies
    studies: list[tuple[int, int]]
    cell: object
    n_studies: int
    bandit_rounds: int
    device_kind: str

    @property
    def window(self) -> tuple[int, int]:
        return self.studies[0][0], self.studies[-1][1]

    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) / 1e9

    def busy(self, d: int) -> list[tuple[int, int]]:
        lo, hi = self.window
        return clip(union((e.start, e.end) for e in self.devices[d]), lo, hi)

    def busy_s(self) -> float:
        """Seconds some op ran, averaged over the devices used."""
        return sum(total(self.busy(d)) for d in range(len(self.devices))) \
            / len(self.devices) / 1e9

    def idle_shares(self) -> list[float]:
        lo, hi = self.window
        return [1.0 - total(self.busy(d)) / (hi - lo)
                for d in range(len(self.devices))]

    def op_time(self, pred) -> tuple[int, int]:
        """(summed ns, event count) over all devices of events matching
        ``pred(name)``, inside the window."""
        lo, hi = self.window
        ns = n = 0
        for evs in self.devices:
            for e in evs:
                if pred(e.name) and e.end > lo and e.start < hi:
                    ns += min(e.end, hi) - max(e.start, lo)
                    n += 1
        return ns, n

    def op_counts(self, pred) -> dict[str, int]:
        """Events per name matching ``pred`` (all devices)."""
        out: dict[str, int] = {}
        for evs in self.devices:
            for e in evs:
                if pred(e.name):
                    out[e.name] = out.get(e.name, 0) + 1
        return out

    def busy_outside(self, pred) -> int:
        """Busy ns, summed over devices, outside every event matching
        ``pred``."""
        lo, hi = self.window
        out = 0
        for d, evs in enumerate(self.devices):
            inside = clip(union((e.start, e.end) for e in evs
                                if pred(e.name)), lo, hi)
            out += total(subtract(self.busy(d), inside))
        return out

    def breakdown(self) -> dict:
        """The device ops that took most time (their own time, without the
        ops nested in them, summed over devices), and the longest idle
        gaps of the idlest device, each named by the innermost host event
        that covers the gap's middle."""
        lo, hi = self.window
        per: dict[str, int] = {}
        for evs in self.devices:
            for e, own in self_times(
                    [e for e in evs if e.end > lo and e.start < hi]):
                per[e.name] = per.get(e.name, 0) + own
        ops = sorted(per.items(), key=lambda x: -x[1])[:10]
        idle = self.idle_shares()
        d = max(range(len(idle)), key=idle.__getitem__)
        gl = sorted(gaps(self.busy(d), lo, hi), key=lambda g: g[0] - g[1])
        return {"device_ops": [[n, v / 1e9] for n, v in ops],
                "idle_gaps": [[self.host_label((s + e) // 2), (e - s) / 1e9]
                              for s, e in gl[:10]]}

    def host_label(self, t: int) -> str:
        inner = None
        for e in self.host:
            if e.start <= t < e.end and (inner is None or e.dur < inner.dur):
                inner = e
        return inner.name if inner is not None else "outside any host span"


def read_trace(trace_dir: Path, *, cell, n_studies: int, bandit_rounds: int,
               n_devices: int, device_kind: str) -> TraceContext:
    """Load the ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, "
                           f"found {files}")
    pd = ProfileData.from_file(files[0])
    devices: dict[int, list[Event]] = {}
    host: list[Event] = []
    studies: list[tuple[int, int]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            idx = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[idx] = [Event(op_name(e.name), int(e.start_ns),
                                          int(e.end_ns))
                                    for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [Event(e.name, int(e.start_ns), int(e.end_ns))
                       for e in line.events]
                spans = [(e.start, e.end) for e in evs
                         if e.name == STUDY_SPAN]
                if spans:
                    studies, host = sorted(spans), evs
    if not studies:
        raise RuntimeError(f"no {STUDY_SPAN} span in the trace")
    used = [devices.get(i, []) for i in sorted(devices)[:n_devices]]
    if not any(used):
        raise RuntimeError("no device op events in the trace")
    return TraceContext(devices=used, host=host, studies=studies, cell=cell,
                        n_studies=n_studies, bandit_rounds=bandit_rounds,
                        device_kind=device_kind)


"""What the program names inside a traced window: the round phase of each
device op, and the ``sweep.*`` host spans with their arguments.

The harness reduces the trace to plain events (trace.py).  The names the
program itself puts in are read here from the same ``.xplane.pb``, once
per file:

- **Round phases.**  The program wraps each phase of a round in a
  ``jax.named_scope`` (``round.cand_draw``, ``round.times``,
  ``round.select``, ``round.finish``), which XLA keeps in each
  instruction's ``op_name``.  On a TPU the trace stores the optimized HLO
  of every module it ran (an ``Hlo Proto`` stat on the ``/host:metadata``
  plane, under the module's name as the ``XLA Modules`` line gives it);
  an op's phase is the innermost ``round.*`` component of its
  instruction's ``op_name`` in the module whose execution covers the op.
  A fusion is charged to its root's phase.  An instruction that XLA made
  without an ``op_name`` (a scatter's expansion, a tuple, a bitcast) is
  charged to the phase of the data it is made from: its first operand
  whose data names a phase, through fusion parameters and tuple
  elements.  An op whose own ``op_name`` names no phase (loop control,
  the scan's output writes), or of a module that names none (the small
  conversion programs beside the sweep), has none: ``round.other``.
- **Host spans.**  ``sweep()`` records ``sweep.env``, ``sweep.put``,
  ``sweep.run`` and ``sweep.fetch`` on the thread that calls it; the put
  and the fetch carry the ``bytes`` they moved.
- **One clock.**  The profiler converts device times to host time with an
  offset that is off by up to a few milliseconds, and differently in each
  session and study.  Each execution of a module has a ``run_id`` on the
  ``XLA Modules`` line and on the host's ``DoEnqueueProgram`` event that
  launched it, and no execution starts before it is enqueued.  Each
  study's device ops are moved by the largest (enqueue start - execution
  start) of the study's executions: the least shift after which none of
  them starts before its enqueue.  The idle readers split the idle time
  of that aligned timeline.

A trace of a program that names no phase and records no span (an older
checkout) gives readers nothing to read: they return None.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
from pathlib import Path

from chipbench import trace

PHASES = ("round.cand_draw", "round.times", "round.select", "round.finish")
SPANS = ("sweep.env", "sweep.put", "sweep.run", "sweep.fetch")
KERNELS = ("select_", "topk_slots")
HLO_STAT = "Hlo Proto"
METADATA_PLANE = "/host:metadata"
DEVICE_PLANE = "/device:TPU:"
MODULES_LINE = "XLA Modules"
ENQUEUE = "DoEnqueueProgram"      # the host event that launches a run

# field numbers of tsl/profiler/protobuf/xplane.proto and xla/service/hlo.proto
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_META, _PLANE_STAT_META = 2, 4, 5
_MAP_VALUE = 2
_META_NAME, _META_STATS = 2, 5
_STAT_META_ID, _STAT_BYTES = 1, 6
_STAT_META_NAME = 2
_PROTO_MODULE = 1
_MODULE_COMPUTATIONS = 3
_COMP_INSTRUCTIONS, _COMP_ID, _COMP_ROOT = 2, 5, 6
(_INS_NAME, _INS_OPCODE, _INS_METADATA, _INS_PARAM, _INS_TUPLE_INDEX,
 _INS_ID, _INS_OPERANDS, _INS_CALLS) = 1, 2, 7, 9, 13, 35, 36, 38
_OPMETA_NAME = 2


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: int      # ns
    end: int        # ns
    nbytes: int | None


@dataclasses.dataclass(frozen=True)
class Execution:
    """One run of a module on one device, on the device's clock."""

    module: str     # as the XLA Modules line names it: ``jit_f(<id>)``
    start: int      # ns
    end: int        # ns
    run_id: int | None


@dataclasses.dataclass(frozen=True)
class Named:
    """What one trace file names."""

    # (module, device op name) -> its round phase
    phases: dict[tuple[str, str], str | None]
    spans: tuple[Span, ...]                   # the sweep.* spans, by start
    executions: dict[int, tuple[Execution, ...]]    # per TPU, by start
    # (device ordinal, run_id) -> host start of the DoEnqueueProgram
    enqueues: dict[tuple[int | None, int], int]

    @property
    def has_phases(self) -> bool:
        return any(p is not None for p in self.phases.values())


def trace_file(ctx) -> Path:
    """The ``.xplane.pb`` the harness wrote for ``ctx``'s cell."""
    d = Path(ctx.cell.bench_dir) / "out" / "trace" / ctx.cell.name
    files = glob.glob(str(d / "**" / "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {d} (written by a --trace 1 "
            f"run of {ctx.cell.name}), found {len(files)}")
    return Path(files[0])


def _memo(ctx) -> dict:
    """What the readers of one traced run share, kept on its context."""
    return ctx.__dict__.setdefault("_scopes_memo", {})


def read(ctx) -> Named:
    """The phases and spans of ``ctx``'s trace file (read once per run)."""
    memo = _memo(ctx)
    if "named" not in memo:
        memo["named"] = read_file(trace_file(ctx))
    return memo["named"]


def read_file(path: Path) -> Named:
    data = Path(path).read_bytes()
    return Named(phases=phases_of(data), **_events_of(data))


# -- the protobuf wire format: just enough of it to walk the two protos ----

def _varint(b: bytes, i: int) -> tuple[int, int]:
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        if c < 0x80:
            return r, i
        s += 7


def _fields(b: bytes, lo: int = 0, hi: int | None = None):
    """(field number, value) of each field in ``b[lo:hi]``; a
    length-delimited value is its (start, end) in ``b``."""
    i, hi = lo, len(b) if hi is None else hi
    while i < hi:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _str(b: bytes, span) -> str:
    return b[span[0]:span[1]].decode("utf-8", "replace")


def _ints(b: bytes, v) -> list[int]:
    """A repeated int64 field: packed (a span) or one value."""
    if isinstance(v, int):
        return [v]
    out, i = [], v[0]
    while i < v[1]:
        x, i = _varint(b, i)
        out.append(x)
    return out


def _hlo_protos(data: bytes) -> list[tuple[str, tuple[int, int]]]:
    """(module name, span of its ``HloProto``) of each module the trace
    stores."""
    out = []
    for f, plane in _fields(data):
        if f != _SPACE_PLANES:
            continue
        name, metas, stat_names = None, [], {}
        for pf, pv in _fields(data, *plane):
            if pf == _PLANE_NAME:
                name = _str(data, pv)
            elif pf == _PLANE_EVENT_META:
                metas.append(pv)
            elif pf == _PLANE_STAT_META:
                for ef, ev in _fields(data, *pv):
                    if ef == _MAP_VALUE:
                        m = dict(_fields(data, *ev))
                        stat_names[m.get(_STAT_META_ID)] = _str(
                            data, m.get(_STAT_META_NAME, (0, 0)))
        if name != METADATA_PLANE:
            continue
        for entry in metas:
            for ef, ev in _fields(data, *entry):
                if ef != _MAP_VALUE:
                    continue
                module = ""
                for mf, mv in _fields(data, *ev):
                    if mf == _META_NAME:
                        module = _str(data, mv)
                    if mf != _META_STATS:
                        continue
                    stat = dict(_fields(data, *mv))
                    if (stat_names.get(stat.get(_STAT_META_ID)) == HLO_STAT
                            and _STAT_BYTES in stat):
                        out.append((module, stat[_STAT_BYTES]))
    return out


def phase_of(op_name: str) -> str | None:
    """The innermost ``round.*`` component of an op_name path."""
    parts = [p.rstrip(":") for p in op_name.split("/")
             if p.startswith("round.")]
    return parts[-1] if parts else None


def _module_phases(data: bytes, proto) -> dict[str, str | None]:
    """Each instruction name of one HloProto's module -> its phase."""
    comps: dict[int, tuple[int, dict[int, dict]]] = {}
    for f, module in _fields(data, *proto):
        if f != _PROTO_MODULE:
            continue
        for mf, comp in _fields(data, *module):
            if mf != _MODULE_COMPUTATIONS:
                continue
            cid, root, instrs = None, None, {}
            for cf, cv in _fields(data, *comp):
                if cf == _COMP_ID:
                    cid = cv
                elif cf == _COMP_ROOT:
                    root = cv
                elif cf == _COMP_INSTRUCTIONS:
                    ins = {"operands": [], "calls": []}
                    for nf, nv in _fields(data, *cv):
                        if nf == _INS_NAME:
                            ins["name"] = _str(data, nv)
                        elif nf == _INS_OPCODE:
                            ins["opcode"] = _str(data, nv)
                        elif nf == _INS_ID:
                            ins["id"] = nv
                        elif nf == _INS_PARAM:
                            ins["param"] = nv
                        elif nf == _INS_TUPLE_INDEX:
                            ins["index"] = nv
                        elif nf == _INS_OPERANDS:
                            ins["operands"] += _ints(data, nv)
                        elif nf == _INS_CALLS:
                            ins["calls"] += _ints(data, nv)
                        elif nf == _INS_METADATA:
                            for of, ov in _fields(data, *nv):
                                if of == _OPMETA_NAME:
                                    ins["op_name"] = _str(data, ov)
                    instrs[ins["id"]] = ins
            comps[cid] = (root, instrs)
    # where each fused computation is called from: (computation, fusion id)
    caller: dict[int, tuple[int, int]] = {}
    for cid, (_, instrs) in comps.items():
        for ins in instrs.values():
            if ins.get("opcode") == "fusion":
                for callee in ins["calls"]:
                    caller[callee] = (cid, ins["id"])

    memo: dict[tuple[int, int, int | None], tuple[bool, str | None]] = {}

    def named(cid: int, iid: int, index: int | None = None,
              depth: int = 0) -> tuple[bool, str | None]:
        """(found a name, its phase) for instruction ``iid`` of ``cid``
        (output ``index`` of a tuple)."""
        key = (cid, iid, index)
        if key in memo:
            return memo[key]
        ins = comps[cid][1].get(iid)
        if ins is None or depth > 64:
            return (False, None)
        memo[key] = (False, None)           # a cycle stops here
        # a name from the program's name stack; XLA's own ("gather")
        # carry no path, and so no phase
        own = ins.get("op_name", "") if "/" in ins.get("op_name", "") \
            else ""
        out = (False, None)
        if phase_of(own):
            out = (True, phase_of(own))
        elif ins.get("opcode") == "fusion" and ins["calls"]:
            callee = ins["calls"][0]
            out = named(callee, comps[callee][0], index, depth + 1)
            if out[1] is None and own:
                out = (True, None)
        elif own:
            out = (True, None)
        elif ins.get("opcode") == "parameter" and cid in caller:
            up, fusion = caller[cid]
            operands = comps[up][1][fusion]["operands"]
            if ins.get("param", 0) < len(operands):
                out = named(up, operands[ins.get("param", 0)], index,
                            depth + 1)
        elif ins.get("opcode") == "get-tuple-element" and ins["operands"]:
            out = named(cid, ins["operands"][0], ins.get("index", 0),
                        depth + 1)
        elif ins.get("opcode") == "tuple" and index is not None:
            if index < len(ins["operands"]):
                out = named(cid, ins["operands"][index], None, depth + 1)
        else:
            # the first operand whose data names a phase (a scatter's
            # indices and updates, not the constant buffer it fills)
            for op in ins["operands"]:
                found = named(cid, op, None, depth + 1)
                if found[1] is not None:
                    out = found
                    break
                out = (out[0] or found[0], None)
        memo[key] = out
        return out

    phases: dict[str, str | None] = {}
    for cid, (_, instrs) in comps.items():
        for iid, ins in instrs.items():
            if "name" in ins:
                phases[ins["name"]] = named(cid, iid)[1]
    return phases


def phases_of(data: bytes) -> dict[tuple[str, str], str | None]:
    """(module, instruction name) -> phase over every module the trace
    stores."""
    return {(module, name): phase
            for module, proto in _hlo_protos(data)
            for name, phase in _module_phases(data, proto).items()}


def _events_of(data: bytes) -> dict:
    """The sweep.* spans, each TPU's module executions and the host's
    enqueues of them."""
    from jax.profiler import ProfileData

    spans, enqueues, executions = [], {}, {}
    for plane in ProfileData.from_serialized_xspace(data).planes:
        if plane.name.startswith(DEVICE_PLANE):
            idx = int(plane.name.rsplit(":", 1)[1])
            executions[idx] = tuple(sorted(
                (Execution(e.name, int(e.start_ns), int(e.end_ns),
                           dict(e.stats).get("run_id"))
                 for line in plane.lines if line.name == MODULES_LINE
                 for e in line.events), key=lambda x: x.start))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in SPANS:
                        nbytes = dict(e.stats).get("bytes")
                        spans.append(Span(
                            e.name, int(e.start_ns), int(e.end_ns),
                            None if nbytes is None else int(nbytes)))
                    elif e.name == ENQUEUE:
                        st = dict(e.stats)
                        if "run_id" in st:
                            key = (st.get("device_ordinal"), st["run_id"])
                            enqueues[key] = min(
                                int(e.start_ns),
                                enqueues.get(key, int(e.start_ns)))
    return {"spans": tuple(sorted(spans, key=lambda s: s.start)),
            "executions": executions,
            "enqueues": enqueues}


# -- what the readers report ----------------------------------------------

def _plane_executions(ctx) -> list[tuple[int, tuple[Execution, ...]]]:
    """(TPU plane index, its executions) of each device ``ctx`` uses, in
    the order of ``ctx.devices``."""
    ex = read(ctx).executions
    return [(i, ex[i]) for i in sorted(ex)[:len(ctx.devices)]]


def _covering(execs: tuple[Execution, ...], starts: list[int],
              t: int) -> int | None:
    """Index of the execution that holds device time ``t``, else of the
    last one before it (None before the first)."""
    i = bisect.bisect_right(starts, t) - 1
    return i if i >= 0 else None


def phase_ns(ctx) -> dict[str | None, int] | None:
    """Own device time (trace.self_times) of the non-kernel ops in each
    phase, summed over devices, inside the window; None where the program
    names no phase."""
    memo = _memo(ctx)
    if "totals" in memo:
        return memo["totals"]
    named = read(ctx)
    totals: dict[str | None, int] | None = None
    if named.has_phases:
        totals = {p: 0 for p in PHASES + (None,)}
        lo, hi = ctx.window
        for evs, (_, execs) in zip(ctx.devices, _plane_executions(ctx)):
            starts = [x.start for x in execs]
            for e, own in trace.self_times(
                    [e for e in evs if e.end > lo and e.start < hi]):
                if trace.is_kernel(e.name, KERNELS):
                    continue
                i = _covering(execs, starts, e.start)
                p = None if i is None else named.phases.get(
                    (execs[i].module, e.name))
                totals[p] += own
    memo["totals"] = totals
    return totals


def phase_us_per_round(ctx, phase: str | None) -> float | None:
    """Microseconds per bandit-round of ``phase`` (None: ops in no phase),
    averaged over the devices."""
    totals = phase_ns(ctx)
    if totals is None:
        return None
    return totals[phase] / len(ctx.devices) / ctx.bandit_rounds / 1e3


def _study_of(ctx, t: int) -> int | None:
    for s, (lo, hi) in enumerate(ctx.studies):
        if lo <= t < hi:
            return s
    return None


def clock_offsets(ctx, d: int) -> list[int | None] | None:
    """Nanoseconds to add to device ``d``'s times, per execution of its
    ``XLA Modules`` line, to put them on the host's clock: per study, the
    largest (enqueue start - execution start) of the study's executions.
    An execution outside the studies, or not matched to its enqueue, takes
    the offset of the nearest execution before it that has one (else
    after it).  None where no execution is matched."""
    named = read(ctx)
    idx, execs = _plane_executions(ctx)[d]
    lead: dict[int, int] = {}
    study = []
    for x in execs:
        h = named.enqueues.get((idx, x.run_id),
                               named.enqueues.get((None, x.run_id)))
        s = None if h is None else _study_of(ctx, h)
        study.append(s)
        if s is not None:
            lead[s] = max(lead.get(s, h - x.start), h - x.start)
    if not lead:
        return None
    out: list[int | None] = [None if s is None else lead[s] for s in study]
    for order in (range(len(out)), reversed(range(len(out)))):
        last = None
        for i in order:
            out[i] = last = out[i] if out[i] is not None else last
    return out


def aligned_busy(ctx, d: int) -> list[tuple[int, int]] | None:
    """Busy intervals of device ``d`` on the host's clock (clock_offsets),
    disjoint and sorted; None where the device's times cannot be
    aligned."""
    memo = _memo(ctx).setdefault("aligned", {})
    if d not in memo:
        offsets = clock_offsets(ctx, d)
        busy = None
        if offsets is not None:
            execs = _plane_executions(ctx)[d][1]
            starts = [x.start for x in execs]
            busy = []
            for e in ctx.devices[d]:
                i = _covering(execs, starts, e.start)
                off = offsets[0 if i is None else i]
                busy.append((e.start + off, e.end + off))
            busy = trace.union(busy)
        memo[d] = busy
    return memo[d]


def idle_in_studies(ctx, d: int) -> list[tuple[int, int]] | None:
    """Idle intervals of device ``d`` inside the study spans, on the
    host's clock; None where its times cannot be aligned."""
    busy = aligned_busy(ctx, d)
    if busy is None:
        return None
    return [g for lo, hi in ctx.studies for g in trace.gaps(
        trace.clip(busy, lo, hi), lo, hi)]


def _study_spans(ctx, name: str) -> list[tuple[int, int]]:
    spans = [(s.start, s.end) for s in read(ctx).spans if s.name == name]
    return [iv for lo, hi in ctx.studies for iv in trace.clip(spans, lo, hi)]


def idle_ms_per_call(ctx, name: str) -> float | None:
    """Milliseconds per study in which the idlest device (over the study
    spans, on the host's clock) was idle inside the ``name`` spans; None
    where the program records no span or the device's times cannot be
    aligned to the host's."""
    if not read(ctx).spans:
        return None
    idle = [idle_in_studies(ctx, d) for d in range(len(ctx.devices))]
    if any(i is None for i in idle):
        return None
    d = max(range(len(idle)), key=lambda i: trace.total(idle[i]))
    inside = trace.union(_study_spans(ctx, name))
    ns = sum(trace.total(trace.clip(inside, s, e)) for s, e in idle[d])
    return ns / len(ctx.studies) / 1e6


def transfer_mb_per_call(ctx) -> float | None:
    """Megabytes the put and fetch spans moved, per study."""
    spans = [s for s in read(ctx).spans
             if s.name in ("sweep.put", "sweep.fetch")
             and s.nbytes is not None
             and any(lo <= s.start < hi for lo, hi in ctx.studies)]
    if not spans:
        return None
    return sum(s.nbytes for s in spans) / len(ctx.studies) / 1e6

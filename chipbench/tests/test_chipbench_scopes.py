"""The readers of the program's own names (chipbench/scopes.py) on a small
trace built from a text proto: a TPU plane whose ops take their round
phase from the HLO the trace stores, on a clock that leads the host's,
and a host line with the studies, the ``sweep.*`` spans and the enqueues
of each module's runs."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import scopes, spec, trace  # noqa: E402

ROUND = ["round.cand_draw", "round.times", "round.select", "round.finish",
         "round.other"]
IDLE = ["entry.env_idle_ms_per_call", "entry.put_idle_ms_per_call",
        "entry.run_idle_ms_per_call", "entry.fetch_idle_ms_per_call"]
READERS = {n: spec.load_reader(n) for n in (
    [f"{r}_us_per_round" for r in ROUND] + IDLE
    + ["entry.transfer_mb_per_call", "round.xla_us_per_round",
       "entry.host_ms_per_call"])}


# -- a minimal protobuf encoder for the HloProto the trace carries --------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _packed(num: int, values) -> bytes:
    return _field(num, b"".join(_varint(v) for v in values)) if values \
        else b""


def _instr(iid, name, opcode, op_name=None, operands=(), calls=(),
           param=None, index=None) -> bytes:
    out = _field(1, name) + _field(2, opcode) + _field(35, iid)
    if op_name is not None:
        out += _field(7, _field(2, op_name))
    if param:
        out += _field(9, param)
    if index:
        out += _field(13, index)
    return out + _packed(36, operands) + _packed(38, calls)


def _hlo(name: str, comps) -> bytes:
    """An HloProto: ``comps`` is [(id, root id, [instruction bytes])]."""
    body = _field(1, name)
    for cid, root, instrs in comps:
        comp = _field(1, f"comp.{cid}") + _field(5, cid) + _field(6, root)
        body += _field(3, comp + b"".join(_field(2, i) for i in instrs))
    return _field(1, body)


P = "jit(_run_grid)/while/body/"
PROGRAM = [
    (1, 10, [_instr(10, "while.7", "while", "jit(_run_grid)/while",
                    calls=[2])]),
    (2, 28, [
        _instr(20, "sort.1", "sort", P + "round.cand_draw/sort"),
        _instr(21, "fusion.2", "fusion", P + "round.times/mul", calls=[3]),
        # a multi-output fusion and the fusion that reads its second
        # output: XLA made both without an op_name
        _instr(22, "fusion.5", "fusion", calls=[4]),
        _instr(23, "gte.4", "get-tuple-element", operands=[22], index=1),
        _instr(24, "fusion.3", "fusion", operands=[23], calls=[5]),
        _instr(25, "select_fedcs.6", "custom-call",
               P + "round.select/pallas_call"),
        _instr(26, "fusion.9", "fusion", calls=[6]),
        _instr(27, "dus.10", "dynamic-update-slice",
               P + "dynamic_update_slice"),
        _instr(28, "copy.8", "copy", operands=[27]),
        # a fusion keeps its root's op_name from when XLA fused it, while
        # a pass may rename the root ("gather": no path, so no phase) ...
        _instr(29, "fusion.11", "fusion", P + "round.select/gather",
               calls=[7]),
        # ... and one named without a phase takes its root's data's
        _instr(19, "fusion.12", "fusion", "jit(_run_grid)/jit(f)",
               calls=[8])]),
    (3, 30, [_instr(30, "mul.1", "multiply", P + "round.times/mul")]),
    (4, 42, [_instr(40, "add.a", "add", P + "round.times/add"),
             _instr(41, "add.b", "add", P + "round.select/add"),
             _instr(42, "tuple.1", "tuple", operands=[40, 41])]),
    # a scatter's expansion: no op_name; it fills a named constant (no
    # phase) at indices that come from round.select's data
    (5, 52, [_instr(50, "param_0", "parameter"),
             _instr(51, "constant.1", "constant",
                    "jit(_run_grid)/jit(bandit_round_pallas)"),
             _instr(52, "scatter.1", "scatter", operands=[51, 50])]),
    (6, 60, [_instr(60, "scatter-add.1", "scatter",
                    P + "round.finish/scatter-add")]),
    (7, 70, [_instr(70, "reshape.2", "reshape", "gather")]),
    (8, 81, [_instr(80, "mul.3", "multiply", P + "round.times/scatter"),
             _instr(81, "tuple.2", "tuple", operands=[80])]),
]
# a conversion program beside the sweep, whose one op shares its name
# with an instruction of the program that names phases
CONVERT = [(1, 1, [_instr(1, "fusion.2", "convert",
                          "convert_element_type")])]
MODULES = {"jit__run_grid(7)": PROGRAM, "jit_convert(8)": CONVERT}

# one study's device ops (ns, from the study's start, on the host's
# clock): the env's int conversion during the put, then the program's
# while loop and its body
CONVERT_OP = ("fusion.2", 150, 160)
OPS = [CONVERT_OP, ("while.7", 220, 900),
       ("sort.1", 230, 330), ("fusion.2", 330, 400), ("fusion.3", 400, 500),
       ("fusion.5", 500, 520), ("select_fedcs.6", 520, 600),
       ("fusion.9", 600, 650), ("copy.8", 650, 660)]
# what each reader should read, per study (ns): the while's own time is
# 680 less its body's 430
OWN = {"round.cand_draw": 100, "round.times": 70 + 20, "round.select": 100,
       "round.finish": 50, "round.other": 10 + 250 + 10}
# the sweep's spans tile each study
SPANS = [("sweep.env", 0, 100, None), ("sweep.put", 100, 200, 1_000_000),
         ("sweep.run", 200, 250, None), ("sweep.fetch", 250, 1000, 200_000)]
# idle: [0, 150) [160, 220) [900, 1000)
IDLE_NS = {"sweep.env": 100, "sweep.put": 50 + 40, "sweep.run": 20,
           "sweep.fetch": 100}
STUDIES = [1000, 2000]
ROUNDS = 20
# each module's run: its span and the host time it was enqueued at (the
# conversion starts as it is enqueued, the program 15 ns after)
RUNS = [("jit_convert(8)", 150, 160, 150), ("jit__run_grid(7)", 220, 900, 205)]
# the device's clock leads the host's by this much, a different amount in
# each study
LEAD = {1000: 40, 2000: 70}


def _escape(b: bytes) -> str:
    return "".join(f"\\{c:03o}" for c in b)


def _xspace(named: bool = True, enqueues: bool = True) -> bytes:
    from jax.profiler import ProfileData

    # an older program: the same ops under names that are no phase
    # ("phase." is as long as "round.", so the encoding stays valid)
    program = PROGRAM if named else [
        (cid, root, [i.replace(b"round.", b"phase.") for i in instrs])
        for cid, root, instrs in PROGRAM]
    ops, modules, host, metas = [], [], [], {}

    def meta(name):
        return metas.setdefault(name, len(metas) + 1)

    def event(name, start, end, stats=""):
        return (f"events {{ metadata_id: {meta(name)} offset_ps: "
                f"{start * 1000} duration_ps: {(end - start) * 1000}"
                f"{stats} }}")

    for t0 in STUDIES:
        dev = t0 - LEAD[t0]
        for name, s, e in OPS:
            # a TPU op event is named by its whole HLO line
            ops.append(event(f"%{name} = f32[8] x()", dev + s, dev + e))
        for n, (module, s, e, enq) in enumerate(RUNS):
            run_id = f" stats {{ metadata_id: 2 int64_value: {t0 + n} }}"
            modules.append(event(module, dev + s, dev + e, run_id))
            if enqueues:
                host.append(event(
                    "DoEnqueueProgram", t0 + enq, t0 + enq + 5,
                    run_id + " stats { metadata_id: 3 int64_value: 0 }"))
        host.append(event("chipbench.study", t0, t0 + 1000))
        for name, s, e, nbytes in SPANS if named else []:
            stat = ("" if nbytes is None else
                    f" stats {{ metadata_id: 1 int64_value: {nbytes} }}")
            host.append(event(name, t0 + s, t0 + e, stat))
    event_meta = " ".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for n, i in metas.items())
    stat_meta = " ".join(
        f'stat_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for i, n in enumerate(("bytes", "run_id", "device_ordinal"), 1))
    hlo = [(name, _hlo(name.split("(")[0],
                       program if name == "jit__run_grid(7)" else comps))
           for name, comps in MODULES.items()]
    hlo_meta = " ".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" '
        f'stats {{ metadata_id: 1 bytes_value: "{_escape(b)}" }} }} }}'
        for i, (n, b) in enumerate(hlo, 1))
    text = f"""
    planes {{ id: 1 name: "/device:TPU:0"
      lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
        {" ".join(modules)} }}
      lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0 {" ".join(ops)} }}
      {event_meta} {stat_meta} }}
    planes {{ id: 2 name: "/host:metadata" {hlo_meta}
      stat_metadata {{ key: 1 value {{ id: 1 name: "Hlo Proto" }} }} }}
    planes {{ id: 3 name: "/host:CPU"
      lines {{ id: 3 name: "python" timestamp_ns: 0 {" ".join(host)} }}
      {event_meta} {stat_meta} }}
    """
    return ProfileData.text_proto_to_serialized_xspace(text)


class _Cell:
    def __init__(self, bench_dir, name="tiny"):
        self.bench_dir, self.name = bench_dir, name


def _ctx(tmp_path, named=True, enqueues=True):
    d = tmp_path / "out" / "trace" / "tiny" / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(_xspace(named, enqueues))
    return trace.read_trace(tmp_path / "out" / "trace" / "tiny",
                            cell=_Cell(tmp_path), n_studies=len(STUDIES),
                            bandit_rounds=ROUNDS, n_devices=1,
                            device_kind="TPU v5 lite")


def test_every_op_takes_its_phase(tmp_path):
    named = scopes.read(_ctx(tmp_path))
    program = {
        "while.7": None, "sort.1": "round.cand_draw",
        "fusion.2": "round.times", "mul.1": "round.times",
        "fusion.5": "round.times", "add.a": "round.times",
        "add.b": "round.select", "tuple.1": "round.times",
        "gte.4": "round.select", "fusion.3": "round.select",
        "param_0": "round.select", "constant.1": None,
        "scatter.1": "round.select", "select_fedcs.6": "round.select",
        "fusion.9": "round.finish", "scatter-add.1": "round.finish",
        "dus.10": None, "copy.8": None,
        "fusion.11": "round.select", "reshape.2": None,
        "fusion.12": "round.times", "mul.3": "round.times",
        "tuple.2": "round.times"}
    assert named.phases == {
        **{("jit__run_grid(7)", n): p for n, p in program.items()},
        # the conversion's op is its own module's: no phase
        ("jit_convert(8)", "fusion.2"): None}
    assert [s.name for s in named.spans] == [
        n for _ in STUDIES for n, *_ in SPANS]


def test_round_readers_split_the_xla_time(tmp_path):
    ctx = _ctx(tmp_path)
    got = {r: READERS[f"{r}_us_per_round"](ctx) for r in ROUND}
    for r in ROUND:
        assert got[r] == pytest.approx(OWN[r] * len(STUDIES) / ROUNDS / 1e3)
    assert sum(got.values()) == pytest.approx(
        READERS["round.xla_us_per_round"](ctx))


def test_idle_readers_divide_the_idle_time_in_the_studies(tmp_path):
    ctx = _ctx(tmp_path)
    got = {n: READERS[f"entry.{n.split('.')[1]}_idle_ms_per_call"](ctx)
           for n in scopes.SPANS}
    for n in scopes.SPANS:
        assert got[n] == pytest.approx(IDLE_NS[n] / 1e6)
    idle = trace.total(scopes.idle_in_studies(ctx, 0))
    assert sum(got.values()) == pytest.approx(idle / len(STUDIES) / 1e6)
    # the host time of the outside reader is that same idle time
    assert sum(got.values()) == pytest.approx(
        READERS["entry.host_ms_per_call"](ctx))


def test_device_times_are_put_on_the_hosts_clock(tmp_path):
    """Per study, the least shift after which no run starts before its
    enqueue: the device's lead, since the conversion starts as it is
    enqueued; each run then lies in the span that launched it."""
    ctx = _ctx(tmp_path)
    assert scopes.clock_offsets(ctx, 0) == [40, 40, 70, 70]
    busy = scopes.aligned_busy(ctx, 0)
    for t0 in STUDIES:
        assert trace.clip(busy, t0, t0 + 1000) == [
            (t0 + 150, t0 + 160), (t0 + 220, t0 + 900)]


def test_idle_needs_the_enqueues(tmp_path):
    """Without the host's enqueue events the device's times cannot be
    aligned: the idle readers have nothing to read, the rest still do."""
    ctx = _ctx(tmp_path, enqueues=False)
    assert scopes.clock_offsets(ctx, 0) is None
    for name, read in READERS.items():
        if name.endswith("_idle_ms_per_call"):
            assert read(ctx) is None, name
        else:
            assert read(ctx) > 0, name


def test_transfer_count(tmp_path):
    ctx = _ctx(tmp_path)
    assert READERS["entry.transfer_mb_per_call"](ctx) == pytest.approx(1.2)


def test_nothing_to_read_without_the_programs_names(tmp_path):
    """An older program: no phase in any op_name, no sweep span."""
    ctx = _ctx(tmp_path, named=False)
    for name, read in READERS.items():
        if name in ("round.xla_us_per_round", "entry.host_ms_per_call"):
            assert read(ctx) > 0
        else:
            assert read(ctx) is None, name


def test_a_missing_trace_is_a_clear_error(tmp_path):
    ctx = _ctx(tmp_path)
    ctx.cell = _Cell(tmp_path / "elsewhere")
    for name in ("round.select_us_per_round", "entry.put_idle_ms_per_call",
                 "entry.transfer_mb_per_call"):
        with pytest.raises(FileNotFoundError, match="--trace 1"):
            READERS[name](ctx)

"""Reading the program's own names out of a profiler trace.

`trace.py` keeps intervals under the names the compiler gives (`fusion.313`,
`copy.71`) and host events only under the benchmark's `bench.` prefix. This
file reads what the PROGRAM names (PR 24): host phases `rlt.<phase>` with
their counters (`telemetry/spans.py:annotate`), the `jax.named_scope`s of
the two step programs and the `name=` of every Pallas kernel. It finds the
run's `.xplane.pb` where `trace.Recorder` left it, loads it once a process,
and offers pure functions over plain intervals beside those it borrows from
`trace.py`; `benchmarks/tests/test_program_trace.py` checks them on
hand-built events.

Where a name lands in a v5e trace (read by hand, my chip runs, PR 24): a
host phase is an event of the plane `/host:CPU` under its own name, its
counters the event's stats. A device op event carries no name stack at all
(its stats are an offset, a duration and a time scale), and only a kernel's
`name=` reaches the instruction's name (`%rlt_paged_decode.5`). But the
trace's plane `/host:metadata` holds, for every program that ran, the
optimized HLO module as a serialized proto (stat `Hlo Proto`), and there
each instruction has its `metadata.op_name`, the name stack the scopes are
part of (`jit(step)/.../kv_pool/layers/while/body/attn/...`). So an op
event is joined to its scope through its instruction's name in the step
program's module. `jax.profiler.ProfileData` does not expose that plane's
metadata, so `hlo_op_names` walks the file's protobuf wire format itself
(four message types, field numbers from xplane.proto and hlo.proto).

A program older than the names (the parent of PR 24) leaves no `rlt.` event
and no `rlt_` kernel: `tables()` then returns None and every reader built on
it returns None, so `run.py` leaves the metric out. A program that has the
vocabulary but lacks one name a cell must show raises `BenchError` with the
names found: a rename fails loudly instead of dropping a metric.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
import statistics
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from benchmarks.harness import shapes, trace
from benchmarks.harness.common import BenchError, say
from benchmarks.harness.trace import Interval, Named

BDIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HOST_PREFIX = "rlt."
KERNEL_PREFIX = "rlt_"
UNSCOPED = "unscoped"
METADATA_PLANE = "/host:metadata"
DISPATCH = {"serve": "rlt.serve.dispatch", "train": "rlt.dispatch"}
ENGINE_PHASES = ("rlt.serve.put", "rlt.serve.dispatch", "rlt.serve.fetch")

_COMPONENT = re.compile(r"[A-Za-z_][A-Za-z0-9_.]*")


def scope_names(bdir: str = BDIR) -> Tuple[str, ...]:
    """The `jax.named_scope`s the program opens, as data: one file a scope,
    `<bdir>/scopes/<scope>.json` (where it is opened and what reads it). A
    PR that opens a new scope in the program adds its file; a name the
    program never opens matches no name stack and changes no share."""
    names = sorted(n[:-len(".json")]
                   for n in os.listdir(os.path.join(bdir, "scopes"))
                   if n.endswith(".json"))
    bad = [n for n in names if not _COMPONENT.fullmatch(n)]
    if bad:
        raise BenchError(f"benchmarks/scopes/ names {bad}, which no component "
                         "of a name stack can equal")
    return tuple(names)


# ---- events -----------------------------------------------------------------


@dataclasses.dataclass
class HostEvent:
    name: str                  # with its `rlt.` prefix
    start: float
    end: float
    thread: int                # index of the host plane's line
    stats: Dict[str, object]


@dataclasses.dataclass
class Op:
    """One device op event under the program's names: `kernel` is the
    Pallas kernel's `name=` (None for any other op), `scope` the innermost
    of `scope_names()` on its name stack (`UNSCOPED` if none)."""
    name: str                  # trace.short_name of the instruction
    start: float
    end: float
    kernel: Optional[str]
    scope: str


@dataclasses.dataclass
class Device:
    ops: List[Op]
    modules: List[Named]


@dataclasses.dataclass
class ProgramTrace:
    devices: List[Device]
    host: List[HostEvent]
    load_s: float = 0.0

    def host_named(self, name: str) -> List[HostEvent]:
        return [e for e in self.host if e.name == name]

    @property
    def kernels(self) -> List[str]:
        return sorted({op.kernel for d in self.devices for op in d.ops
                       if op.kernel})


# ---- names ------------------------------------------------------------------


def innermost_scope(path: str, scopes: Optional[Sequence[str]] = None
                    ) -> str:
    """The last of `scopes` (default: `scope_names()`) among the components
    of a name stack
    (`jit(step)/transpose(jvp(fused_ce))/while/body/dot_general`): a
    transform wraps a component in parentheses, it does not rename it.
    Innermost wins: a block's `attn` lies inside the serving scan's
    `kv_pool`."""
    if scopes is None:
        scopes = scope_names()
    best, at = UNSCOPED, -1
    for m in _COMPONENT.finditer(path):
        if m.group(0) in scopes and m.start() > at:
            best, at = m.group(0), m.start()
    return best


def kernel_in(text: str) -> Optional[str]:
    """The `rlt_<kernel>` name inside an instruction name or a name stack."""
    m = re.search(r"\b" + KERNEL_PREFIX + r"[a-z0-9_]+", text)
    return m.group(0) if m else None


def instruction_of(event_name: str) -> str:
    """`fusion.313` of an op event named by its whole instruction text
    (`%fusion.313 = bf16[...] fusion(...)`)."""
    head = event_name.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def resolve(event_name: str, hlo: Dict[str, Tuple[str, str]],
            scopes: Optional[Sequence[str]] = None
            ) -> Tuple[str, Optional[str], str]:
    """(short name, kernel, scope) of one distinct op event; `hlo` maps an
    instruction's name to its (opcode, op_name)."""
    instr = instruction_of(event_name)
    opcode, path = hlo.get(instr, ("", ""))
    kernel = None
    # only the Mosaic call itself is the kernel: the ops XLA puts around it
    # under the same scope (a pad or a transpose feeding it) are not
    if opcode == "custom-call" or (
            not opcode and "custom-call(" in event_name):
        kernel = kernel_in(path) or kernel_in(instr)
    return (trace.short_name(event_name), kernel,
            innermost_scope(path, scopes))


# ---- the HLO the trace carries ----------------------------------------------


def _fields(buf):
    """(field number, wire type, value) of one protobuf message: an int for
    a varint, the payload's memoryview for a length-delimited field."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        num, wire = key >> 3, key & 7
        if wire == 0 or wire == 2:
            value = shift = 0
            while True:
                b = buf[i]
                i += 1
                value |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            if wire == 2:
                value, i = buf[i:i + value], i + value
            yield num, wire, value
        elif wire == 1:
            yield num, wire, buf[i:i + 8]
            i += 8
        elif wire == 5:
            yield num, wire, buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"protobuf wire type {wire}")


def _first(buf, number: int):
    for num, _wire, value in _fields(buf):
        if num == number:
            return value
    return None


def hlo_op_names(xspace: bytes) -> Dict[str, Dict[str, Tuple[str, str]]]:
    """{program name: {instruction name: (opcode, op_name)}} from the HLO
    protos of the plane `/host:metadata`. XSpace.planes=1; XPlane.name=2,
    .event_metadata=4 (map: value=2); XEventMetadata.name=2, .stats=5;
    XStat.bytes_value=6; HloProto.hlo_module=1; HloModuleProto.
    computations=3; HloComputationProto.instructions=2;
    HloInstructionProto.name=1, .opcode=2, .metadata=7; OpMetadata.
    op_name=2. An instruction of a fused computation keeps its own entry,
    which no op event asks for."""
    out: Dict[str, Dict[str, Tuple[str, str]]] = {}
    for num, _w, plane in _fields(memoryview(xspace)):
        if num != 1:
            continue
        name = _first(plane, 2)
        if name is None or bytes(name).decode() != METADATA_PLANE:
            continue
        for f, _w2, entry in _fields(plane):
            if f != 4:
                continue
            meta = _first(entry, 2)
            program = bytes(_first(meta, 2) or b"").decode()
            for f2, _w3, stat in _fields(meta):
                proto = _first(stat, 6) if f2 == 5 else None
                module = _first(proto, 1) if proto is not None else None
                if module is None:
                    continue
                table = out.setdefault(program, {})
                for f3, _w4, comp in _fields(module):
                    if f3 != 3:
                        continue
                    for f4, _w5, ins in _fields(comp):
                        if f4 != 2:
                            continue
                        iname = opcode = path = ""
                        for f5, _w6, v in _fields(ins):
                            if f5 == 1:
                                iname = bytes(v).decode()
                            elif f5 == 2:
                                opcode = bytes(v).decode()
                            elif f5 == 7:
                                path = bytes(_first(v, 2) or b"").decode()
                        table[iname] = (opcode, path)
    return out


# ---- loading ----------------------------------------------------------------


def find_xplane(root: str, cell: str) -> Optional[str]:
    found = glob.glob(os.path.join(root, ".bench_trace", cell, "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def from_events(device_events: Dict[int, Dict[str, List[Named]]],
                host_events: Sequence[tuple],
                programs: Dict[str, Dict[str, Tuple[str, str]]],
                chips: int, scopes: Optional[Sequence[str]] = None
                ) -> ProgramTrace:
    """A `ProgramTrace` from plain lists: per chip `{"ops": [(instruction
    text, start, end)], "modules": [(name, start, end)]}`, host events
    `(name, start, end, thread, stats)`, and the HLO tables. An op's names
    are resolved once per distinct instruction text: its metadata is the
    same at every execution."""
    if scopes is None:
        scopes = scope_names()
    devices: Dict[int, Device] = {}
    for idx, raw in device_events.items():
        modules = list(raw.get("modules", ()))
        # every op event is read under the step program's HLO: the other
        # programs of a window (a seed, a cast) are microseconds
        hlo = _program_table(programs, _step_program(modules))
        names: Dict[str, Tuple[str, Optional[str], str]] = {}
        ops = []
        for text, s, e in raw.get("ops", ()):
            got = names.get(text)
            if got is None:
                got = names[text] = resolve(text, hlo, scopes)
            ops.append(Op(got[0], s, e, got[1], got[2]))
        devices[idx] = Device(ops=ops, modules=modules)
    used = [devices[i] for i in sorted(devices) if devices[i].ops][:chips]
    host = [HostEvent(*row) for row in host_events]
    return ProgramTrace(devices=used, host=sorted(host, key=lambda e: e.start))


def load_xplane(path: str, chips: int,
                scopes: Optional[Sequence[str]] = None) -> ProgramTrace:
    """Host events named `rlt.*` with their stats, device op events under
    the program's names."""
    import jax

    t0 = time.perf_counter()
    with open(path, "rb") as fh:
        programs = hlo_op_names(fh.read())
    data = jax.profiler.ProfileData.from_file(path)
    device_events: Dict[int, Dict[str, List[Named]]] = {}
    host_events = []
    for plane in data.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if m:
            raw = device_events.setdefault(int(m.group(1)), {})
            for line in plane.lines:
                key = {trace.OPS_LINE: "ops",
                       trace.MODULES_LINE: "modules"}.get(line.name)
                if key:
                    raw[key] = [
                        (ev.name, ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events]
        elif plane.name == trace.HOST_PLANE:
            for thread, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host_events.append((
                            ev.name, ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9, thread,
                            dict(ev.stats)))
    pt = from_events(device_events, host_events, programs, chips, scopes)
    pt.load_s = time.perf_counter() - t0
    return pt


# ---- pure reductions --------------------------------------------------------


def _step_program(modules: Sequence[Named]) -> Optional[str]:
    """The program that took most device time: the train step or the
    engine's step (as `readers._step_runs`)."""
    totals: Dict[str, float] = {}
    for name, s, e in modules:
        totals[name] = totals.get(name, 0.0) + (e - s)
    return max(totals, key=totals.get) if totals else None


def _program_table(programs: Dict[str, Dict[str, Tuple[str, str]]],
                   module: Optional[str]) -> Dict[str, Tuple[str, str]]:
    """The HLO table of a module event (`jit_step(2191941150113542631)`):
    under the same name, else under the same function's name."""
    if module is None:
        return {}
    if module in programs:
        return programs[module]
    stem = module.split("(", 1)[0]
    same = [t for n, t in programs.items() if n.split("(", 1)[0] == stem]
    return max(same, key=len) if same else {}


def step_runs(device: Device) -> List[Interval]:
    """Executions of the step program on one chip."""
    name = _step_program(device.modules)
    return trace.module_runs(device, name) if name else []


def within(ops: Iterable[Op], runs: Sequence[Interval]) -> List[Op]:
    """The ops that lie inside one of `runs` (sorted, disjoint)."""
    out, j = [], 0
    for op in sorted(ops, key=lambda o: o.start):
        while j < len(runs) and runs[j][1] <= op.start:
            j += 1
        if (j < len(runs) and runs[j][0] <= op.start
                and op.end <= runs[j][1] + 1e-9):
            out.append(op)
    return out


def kernel_seconds(ops: Iterable[Op]) -> Dict[str, Tuple[float, int]]:
    """{kernel name: (device seconds, calls)}."""
    out: Dict[str, List[float]] = {}
    for op in ops:
        if op.kernel:
            row = out.setdefault(op.kernel, [0.0, 0])
            row[0] += op.end - op.start
            row[1] += 1
    return {k: (v[0], int(v[1])) for k, v in out.items()}


def scope_self_seconds(ops: Sequence[Op]) -> Dict[str, float]:
    """Self seconds by scope: an op that encloses others (a `while` around
    its body) keeps only what its children do not cover, so a scope is not
    counted once for the loop and again for the loop's body."""
    rows = [(op.scope, op.start, op.end) for op in ops]
    out: Dict[str, float] = {}
    for scope, t in trace.self_times(rows):
        out[scope] = out.get(scope, 0.0) + t
    return out


def pair_dispatches(runs: Sequence[Interval], dispatches: Sequence[HostEvent]
                    ) -> List[Tuple[Interval, HostEvent]]:
    """Each execution of the step program with the last dispatch that began
    before it; a dispatch serves one execution, so a trace that starts
    mid-tick leaves its first execution unpaired rather than mispaired."""
    out, j, used = [], 0, -1
    disp = sorted(dispatches, key=lambda e: e.start)
    for run in sorted(runs):
        while j < len(disp) and disp[j].start <= run[0]:
            j += 1
        if j - 1 > used:
            used = j - 1
            out.append((run, disp[used]))
    return out


def idle_outside(busy: Iterable[Interval], runs: Sequence[Interval],
                 window: Interval) -> List[Interval]:
    """The stretches of `window` in which the chip runs nothing AND no
    execution of the step program is open: what the chip waits for the
    host. (Idle inside an execution is the program's own.)"""
    return trace.idle_gaps(list(busy) + list(runs), window)


def idle_between(gaps: Sequence[Interval], runs: Sequence[Interval]
                 ) -> List[float]:
    """Idle seconds between each two consecutive executions: the `gaps`
    (sorted, disjoint, all outside the executions) that lie after one
    execution's end and before the next one's start."""
    out, j = [], 0
    for a, b in zip(runs, runs[1:]):
        while j < len(gaps) and gaps[j][1] <= a[1]:
            j += 1
        idle = 0.0
        while j < len(gaps) and gaps[j][0] < b[0]:
            idle += gaps[j][1] - gaps[j][0]
            j += 1
        out.append(idle)
    return out


def idle_by_phase(gaps: Sequence[Interval], host: Sequence[HostEvent]
                  ) -> List[Tuple[str, float]]:
    """Idle seconds by the `rlt.*` phase open on the host meanwhile, the
    innermost winning (`trace.attribute_gaps`: the shortest covering span)."""
    spans = [(e.name, e.start, e.end) for e in host]
    return trace.attribute_gaps(gaps, spans)


def roofline_pct(work: Sequence[dict], kernel_s: float, peaks: dict
                 ) -> Optional[float]:
    """100 x the least time the chip could take for `work` (one dict of
    flops and bytes a call) over the device time the kernel took."""
    if kernel_s <= 0 or not work:
        return None
    least = sum(shapes.roofline_seconds(w, peaks)["seconds"] for w in work)
    return 100.0 * least / kernel_s


# ---- one run's tables, computed once ----------------------------------------

#: where a run record keeps its tables between one reader and the next
_STAMP = "program_trace"


@dataclasses.dataclass
class Tables:
    kind: str                                   # "serve" | "train"
    trace: ProgramTrace
    #: per chip: executions of the step program, and the ops inside them
    runs: List[List[Interval]]
    step_ops: List[List[Op]]
    pairs: List[Tuple[Interval, HostEvent]]     # chip 0
    step_device_s: float                        # a chip
    idle_total_s: float                         # a chip
    idle_outside_s: float                       # a chip
    #: idle seconds between each two consecutive executions, every chip's
    between_runs_idle_s: List[float]
    idle_phases: List[Tuple[str, float]]        # a chip
    scopes: Dict[str, float]                    # self seconds a chip
    kernels: Dict[str, Tuple[float, int]]       # seconds a chip, calls

    @property
    def executions(self) -> int:
        return max(1, len(self.runs[0]))


def build_tables(pt: ProgramTrace, kind: str) -> Tables:
    chips = max(1, len(pt.devices))
    runs = [step_runs(d) for d in pt.devices]
    step_ops = [within(d.ops, r) for d, r in zip(pt.devices, runs)]
    starts = [op.start for d in pt.devices for op in d.ops]
    ends = [op.end for d in pt.devices for op in d.ops]
    window = (min(starts), max(ends)) if starts else (0.0, 0.0)
    idle_total = idle_out = 0.0
    gaps_out: List[Interval] = []
    between: List[float] = []
    for d, r in zip(pt.devices, runs):
        busy = [(op.start, op.end) for op in d.ops]
        idle_total += sum(e - s for s, e in trace.idle_gaps(busy, window))
        gaps = idle_outside(busy, r, window)
        gaps_out += gaps
        idle_out += sum(e - s for s, e in gaps)
        between += idle_between(gaps, r)
    scopes: Dict[str, float] = {}
    kernels: Dict[str, List[float]] = {}
    for ops in step_ops:
        for k, v in scope_self_seconds(ops).items():
            scopes[k] = scopes.get(k, 0.0) + v / chips
        for k, (sec, calls) in kernel_seconds(ops).items():
            row = kernels.setdefault(k, [0.0, 0])
            row[0] += sec / chips
            row[1] += calls
    dispatches = pt.host_named(DISPATCH[kind])
    return Tables(
        kind=kind, trace=pt, runs=runs, step_ops=step_ops,
        pairs=pair_dispatches(runs[0], dispatches) if runs else [],
        step_device_s=sum(e - s for r in runs for s, e in r) / chips,
        idle_total_s=idle_total / chips, idle_outside_s=idle_out / chips,
        between_runs_idle_s=between,
        idle_phases=[(k, v / chips)
                     for k, v in idle_by_phase(gaps_out, pt.host)],
        scopes=scopes,
        kernels={k: (v[0], int(v[1])) for k, v in kernels.items()})


def _run_scopes(run) -> Tuple[str, ...]:
    return scope_names(os.path.join(run.root, "benchmarks"))


def tables(run) -> Optional[Tables]:
    """The run's tables, or None where there is nothing to read: the run
    was not traced, or the program is older than its names. Built by the
    first reader that asks and kept on the run record for the others."""
    if run.trace is None:
        return None
    if _STAMP not in run.stamps:
        run.stamps[_STAMP] = _read_tables(run)
    return run.stamps[_STAMP]


def _read_tables(run) -> Optional[Tables]:
    path = find_xplane(run.root, run.cell["name"])
    if path is None:
        return None
    pt = load_xplane(path, run.chips, _run_scopes(run))
    t0 = time.perf_counter()
    if not pt.host and not pt.kernels:
        say("program", names="none", load_s=round(pt.load_s, 2),
            note="no rlt. host event and no rlt_ kernel in the trace: the "
                 "program is older than its names, its metrics are left out")
        return None
    kind = "train" if run.kind == "train" else "serve"
    tb = build_tables(pt, kind)
    say("program", load_s=round(pt.load_s, 2),
        reduce_s=round(time.perf_counter() - t0, 2),
        ops=sum(len(d.ops) for d in pt.devices), host_events=len(pt.host),
        executions=len(tb.runs[0]) if tb.runs else 0, paired=len(tb.pairs))
    step = tb.step_device_s or 1.0
    phases: Dict[str, List[float]] = {}
    for e in pt.host:
        phases.setdefault(e.name, []).append(e.end - e.start)
    print("[program] " + json.dumps({
        "window_executions": tb.executions,
        "host_phase_median_ms": {k: 1e3 * statistics.median(v)
                                 for k, v in sorted(phases.items())},
        "idle_ms_per_execution": {
            "total": 1e3 * tb.idle_total_s / tb.executions,
            "outside_steps": 1e3 * tb.idle_outside_s / tb.executions,
            "between_steps_median": 1e3 * statistics.median(
                tb.between_runs_idle_s or [0.0]),
            "inside_steps": 1e3 * (tb.idle_total_s - tb.idle_outside_s)
                            / tb.executions},
        "idle_outside_by_phase_ms_per_execution": [
            [k, 1e3 * v / tb.executions] for k, v in tb.idle_phases],
        "device_share_by_scope_pct": sorted(
            ([k, 100.0 * v / step] for k, v in tb.scopes.items()),
            key=lambda kv: -kv[1]),
        "kernels": sorted(
            ([k, {"ms_per_execution": 1e3 * s / tb.executions,
                  "share_pct": 100.0 * s / step, "calls": c}]
             for k, (s, c) in tb.kernels.items()), key=lambda kv: kv[0]),
    }), flush=True)
    return tb


def need_kernels(tb: Tables, names: Sequence[str]) -> None:
    missing = [n for n in names if n not in tb.kernels]
    if missing:
        raise BenchError(
            f"the traced step ran no kernel named {missing}; the trace's "
            f"kernels are {tb.trace.kernels or 'none'}: a rename has to "
            "reach benchmarks/ too")


# ---- the readers' reductions ------------------------------------------------


def sched_host_ms(run) -> Optional[float]:
    """Median over the traced ticks of `rlt.serve.tick` minus the engine's
    three spans inside it: the scheduler's own time."""
    tb = tables(run)
    if tb is None:
        return None
    ticks = tb.trace.host_named("rlt.serve.tick")
    if not ticks:
        raise BenchError("no rlt.serve.tick event in the trace's host plane")
    engine = [e for e in tb.trace.host if e.name in ENGINE_PHASES]
    own = []
    for t in ticks:
        inner = sum(e.end - e.start for e in engine
                    if e.thread == t.thread and t.start <= e.start
                    and e.end <= t.end)
        own.append((t.end - t.start) - inner)
    return 1e3 * statistics.median(own)


def host_exposed_ms(run) -> Optional[float]:
    """Device idle between two consecutive executions of the step program
    (outside any execution), the median over the traced ticks, in ms. The
    `[program]` line has the mean beside it, which a single stalled fetch
    moves (13.3 ms against 5.0 in two runs of the docs cell, PR 24)."""
    tb = tables(run)
    if tb is None or not tb.between_runs_idle_s:
        return None
    return 1e3 * statistics.median(tb.between_runs_idle_s)


def scope_share_pct(run, scope: str) -> Optional[float]:
    """Self time of the ops under `scope` over the step program's device
    time."""
    tb = tables(run)
    if tb is None or tb.step_device_s <= 0:
        return None
    if set(tb.scopes) <= {UNSCOPED}:
        raise BenchError("no device op carries one of the program's scopes "
                         f"{_run_scopes(run)}: the op events' name stack was "
                         "not found")
    return 100.0 * tb.scopes.get(scope, 0.0) / tb.step_device_s


def counter(stats: Dict[str, object], name: str) -> int:
    """A counter of one `rlt.serve.dispatch` event, by name."""
    if name not in stats:
        raise BenchError(f"rlt.serve.dispatch carries no counter {name!r}; "
                         f"it has {sorted(stats)}")
    return int(stats[name])


def paired_kernel_seconds(tb: Tables, kernel: str, keep
                          ) -> Tuple[float, list]:
    """Device seconds of `kernel` inside the paired executions `keep`
    accepts, and those executions' dispatch counters."""
    picked = [(run, ev) for run, ev in tb.pairs if keep(ev.stats)]
    ops = within([op for op in tb.step_ops[0] if op.kernel == kernel],
                 [run for run, _ in picked])
    return sum(op.end - op.start for op in ops), [ev.stats for _, ev in picked]


def paged_decode_roofline_pct(run) -> Optional[float]:
    tb = tables(run)
    if tb is None:
        return None
    need_kernels(tb, ["rlt_paged_decode"])
    seconds, stats = paired_kernel_seconds(
        tb, "rlt_paged_decode", lambda s: counter(s, "decode_slots") > 0)
    model = run.model_tables()
    dims, layers = model.attention_dims(run.hp), model.attention_layers(run.hp)
    work = []
    for s in stats:
        # `paged_decode` sums the contexts and counts the slots
        contexts = ([counter(s, "kv_tokens")]
                    + [0] * (counter(s, "decode_slots") - 1))
        one = shapes.paged_decode(contexts, **dims)
        work.append({k: layers * v for k, v in one.items()})
    return roofline_pct(work, seconds, run.peaks)


def paged_prefill_roofline_pct(run) -> Optional[float]:
    tb = tables(run)
    if tb is None:
        return None
    need_kernels(tb, ["rlt_paged_prefill"])
    seconds, stats = paired_kernel_seconds(
        tb, "rlt_paged_prefill", lambda s: counter(s, "prefill_rows") > 0)
    model = run.model_tables()
    dims, layers = model.attention_dims(run.hp), model.attention_layers(run.hp)
    work = []
    for s in stats:
        one = shapes.paged_prefill(counter(s, "prefill_rows"),
                                   counter(s, "prefill_ctx"), **dims)
        work.append({k: layers * v for k, v in one.items()})
    return roofline_pct(work, seconds, run.peaks)


FLASH_KERNELS = ("rlt_flash_fwd", "rlt_flash_bwd_dkdv", "rlt_flash_bwd_dq")


def flash_roofline_pct(run) -> Optional[float]:
    """A step's attention work on one chip (its share of the batch, every
    layer, forward and backward) over the device time of the three flash
    kernels a step; recomputed forwards are in the time, not in the work."""
    tb = tables(run)
    if tb is None:
        return None
    need_kernels(tb, FLASH_KERNELS)
    seconds = sum(tb.kernels[k][0] for k in FLASH_KERNELS)
    model = run.model_tables()
    one = shapes.flash_fwd_bwd(
        run.traffic["batch"] / run.chips, run.stamps["seq"],
        **model.attention_dims(run.hp))
    layers = model.attention_layers(run.hp)
    work = [{k: layers * v for k, v in one.items()}] * len(tb.runs[0])
    return roofline_pct(work, seconds, run.peaks)


def data_wait_ms(run) -> Optional[float]:
    """Median `rlt.data_wait` on the thread that dispatches the steps."""
    tb = tables(run)
    if tb is None:
        return None
    main = {e.thread for e in tb.trace.host_named("rlt.dispatch")}
    waits = [e.end - e.start for e in tb.trace.host_named("rlt.data_wait")
             if e.thread in main]
    if not waits:
        raise BenchError("no rlt.data_wait event on the dispatching thread")
    return 1e3 * statistics.median(waits)

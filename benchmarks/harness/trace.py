"""Recording a profiler trace and reducing it to intervals.

`Recorder` wraps `jax.profiler.start_trace/stop_trace`; `load_xplane()`
reads the `.xplane.pb` with `jax.profiler.ProfileData` into a
`TraceSummary` of plain intervals (seconds, one clock); the pure functions
below reduce intervals to busy time, idle gaps and their attribution,
exposed collective time and per-op self time. The metric readers in
`benchmarks/layer_metrics/` call only these, and `benchmarks/tests/`
checks them on hand-built intervals.

What a v5e trace looks like (read by hand, PR 23): one plane a chip named
`/device:TPU:<n>`; its line `XLA Ops` holds every HLO op that ran on the
TensorCore, each named by its whole HLO instruction text (a `while` or
`conditional` op encloses its body's ops, so self time subtracts children;
a Mosaic kernel is a `custom-call` with `kernel_metadata={}`, no kernel
name), its line `XLA Modules` one event per execution of a jitted program,
named `jit_<fn>(<fingerprint>)`; host threads are lines of the plane
`/host:CPU`, where the benchmark's `TraceAnnotation`s appear under their
own names (`bench.tick`, ...) on the same clock as the device's events.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]                 # (start_s, end_s)
Named = Tuple[str, float, float]               # (name, start_s, end_s)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?[.\d]*( |$)")
_HLO = re.compile(r"^%(?P<instr>\S+) = (?P<rest>.*)$", re.S)
_OPCODE = re.compile(r"(?:^|\s)(?P<op>[a-z][a-z0-9\-]*)\(")
_SHAPE = re.compile(r"^\(?(?P<shape>[a-z0-9]+\[[\d,]*\])")


def short_name(name: str) -> str:
    """An op event is named by its whole HLO instruction (`%copy.71 =
    bf16[...] copy(...)`); keep the instruction's name, and where that does
    not say it, the opcode (with the result's shape for a custom call: the
    trace carries no kernel name, so the shape is what tells a decode call
    from a prefill call)."""
    m = _HLO.match(name)
    if not m:
        return name[:80]
    instr, rest = m.group("instr"), m.group("rest")
    op = _OPCODE.search(rest)
    opcode = op.group("op") if op else ""
    if not opcode or instr.startswith(opcode):
        return instr
    if opcode == "custom-call":
        shape = _SHAPE.match(rest)
        return f"{instr} (custom-call {shape.group('shape') if shape else ''})"
    return f"{instr} ({opcode})"


@dataclasses.dataclass
class DeviceTrace:
    ops: List[Named]
    modules: List[Named]


@dataclasses.dataclass
class TraceSummary:
    devices: List[DeviceTrace]
    host_spans: List[Named]

    @property
    def window(self) -> Interval:
        starts = [s for d in self.devices for _, s, _ in d.ops]
        ends = [e for d in self.devices for _, _, e in d.ops]
        if not starts:
            return (0.0, 0.0)
        return (min(starts), max(ends))

    @property
    def window_s(self) -> float:
        lo, hi = self.window
        return hi - lo

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the chips."""
        if not self.devices:
            return 0.0
        return sum(union_seconds((s, e) for _, s, e in d.ops)
                   for d in self.devices) / len(self.devices)


# ---- pure reductions --------------------------------------------------------


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union_seconds(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in merge(intervals))


def idle_gaps(busy: Iterable[Interval], window: Interval) -> List[Interval]:
    """The stretches of `window` that no busy interval covers."""
    lo, hi = window
    gaps, at = [], lo
    for s, e in merge(busy):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def attribute_gaps(gaps: Sequence[Interval], spans: Sequence[Named],
                   other: str = "unattributed") -> List[Tuple[str, float]]:
    """Idle seconds by what the host was doing: each gap's time goes to the
    spans open during it (the innermost, which is the shortest, wins where
    spans nest), the rest to `other`. Sorted, largest first."""
    totals: Dict[str, float] = {}
    for gap in gaps:
        cover = sorted((sp for sp in spans if overlap(gap, sp[1:]) > 0),
                       key=lambda sp: sp[2] - sp[1])
        left = [gap]
        for name, s, e in cover:
            nxt = []
            for gs, ge in left:
                ov = overlap((gs, ge), (s, e))
                if ov <= 0:
                    nxt.append((gs, ge))
                    continue
                totals[name] = totals.get(name, 0.0) + ov
                if gs < s:
                    nxt.append((gs, s))
                if e < ge:
                    nxt.append((e, ge))
            left = nxt
        rest = sum(e - s for s, e in left)
        if rest > 0:
            totals[other] = totals.get(other, 0.0) + rest
    return sorted(totals.items(), key=lambda kv: -kv[1])


def exposed_seconds(collectives: Iterable[Interval],
                    compute: Iterable[Interval]) -> float:
    """Collective time during which no compute op runs on that chip."""
    coll = merge(collectives)
    comp = merge(compute)
    # both lists are sorted and disjoint, so one pass over each finds the
    # covered time (a merge of `compute` per collective took minutes on a
    # four-chip trace of 25k ops a chip)
    covered, j = 0.0, 0
    for s, e in coll:
        while j < len(comp) and comp[j][1] <= s:
            j += 1
        k = j
        while k < len(comp) and comp[k][0] < e:
            covered += overlap((s, e), comp[k])
            k += 1
    return sum(e - s for s, e in coll) - covered


def self_times(ops: Sequence[Named]) -> List[Tuple[str, float]]:
    """(name, self seconds) per event: an op that encloses others (a `while`
    around its body) keeps only the time its children do not cover."""
    rows = sorted(ops, key=lambda r: (r[1], -(r[2] - r[1])))
    out: List[List] = []
    stack: List[int] = []
    for name, s, e in rows:
        while stack and rows[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= rows[stack[-1]][2] + 1e-12:
            out[stack[-1]][1] -= (e - s)
        out.append([name, e - s])
        stack.append(len(out) - 1)
    return [(n, max(0.0, t)) for n, t in out]


def top_ops(devices: Sequence[DeviceTrace], n: int = 10
            ) -> List[Tuple[str, float]]:
    """The `n` ops with most self time, averaged over the chips, under the
    trace's instruction names, shortened."""
    totals: Dict[str, float] = {}
    for d in devices:
        for name, t in self_times(d.ops):
            totals[name] = totals.get(name, 0.0) + t / len(devices)
    return sorted(totals.items(), key=lambda kv: -kv[1])[:n]


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE.match(name))


def module_runs(device: DeviceTrace, contains: str) -> List[Interval]:
    return sorted((s, e) for n, s, e in device.modules if contains in n)


# ---- recording and loading --------------------------------------------------


class Recorder:
    """Start/stop one profiler trace into `<dir>`; the python tracer is off
    (it slows the host loop and bloats the file)."""

    def __init__(self, directory: str):
        self.directory = directory
        self.active = False
        self.stop_s = 0.0

    def start(self) -> None:
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        os.makedirs(self.directory, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self.active = True

    def stop(self) -> Optional[str]:
        import jax

        if not self.active:
            return None
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        self.stop_s = time.perf_counter() - t0
        self.active = False
        found = glob.glob(os.path.join(self.directory, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        return max(found, key=os.path.getmtime) if found else None


def load_xplane(path: str, chips: int) -> TraceSummary:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[int, DeviceTrace] = {}
    host: List[Named] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = DeviceTrace(ops=[], modules=[])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    names: Dict[str, str] = {}
                    for ev in line.events:
                        s = ev.start_ns * 1e-9
                        short = names.get(ev.name)
                        if short is None:
                            short = names[ev.name] = short_name(ev.name)
                        dev.ops.append((short, s, s + ev.duration_ns * 1e-9))
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        s = ev.start_ns * 1e-9
                        dev.modules.append(
                            (ev.name, s, s + ev.duration_ns * 1e-9))
            devices[int(m.group(1))] = dev
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = ev.start_ns * 1e-9
                        host.append((ev.name[len(SPAN_PREFIX):], s,
                                     s + ev.duration_ns * 1e-9))
    used = [devices[i] for i in sorted(devices)
            if devices[i].ops][:chips]
    return TraceSummary(devices=used, host_spans=sorted(host,
                                                        key=lambda r: r[1]))


def describe(path: str, per_line: int = 12) -> str:
    """A hand-readable outline of a trace: planes, lines, event counts, the
    first names and the stats they carry. For reading one trace by hand."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        out.append(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  LINE {line.name!r} events={len(events)}")
            seen = {}
            for ev in events:
                seen.setdefault(ev.name, [0, 0.0, ev])
                seen[ev.name][0] += 1
                seen[ev.name][1] += ev.duration_ns * 1e-9
            top = sorted(seen.items(), key=lambda kv: -kv[1][1])[:per_line]
            for name, (cnt, tot, ev) in top:
                try:
                    stats = {k: (str(v)[:100]) for k, v in ev.stats}
                except Exception as exc:  # noqa: BLE001
                    stats = {"<stats error>": repr(exc)}
                out.append(f"    {name[:90]!r} n={cnt} total_s={tot:.6f} "
                           f"start_ns={ev.start_ns} stats={stats}")
    return "\n".join(out)


def device_idle_gaps(summary: TraceSummary) -> List[Interval]:
    """Every idle gap of every chip inside the traced window."""
    window = summary.window
    return [g for d in summary.devices
            for g in idle_gaps(((s, e) for _, s, e in d.ops), window)]


def breakdown(summary: TraceSummary, n: int = 10) -> dict:
    """`device_ops`: the ops with most self time (seconds a chip);
    `idle_gaps`: idle seconds a chip by the benchmark span open on the host
    meanwhile."""
    chips = max(1, len(summary.devices))
    gaps = attribute_gaps(device_idle_gaps(summary), summary.host_spans)
    return {"device_ops": [[k, v] for k, v in top_ops(summary.devices, n)],
            "idle_gaps": [[k, v / chips] for k, v in gaps[:n]]}

"""The collectives of a compiled step, read off its partitioned HLO.

`jax.jit(step).lower(...).compile().as_text()` is the program after GSPMD:
every `all-gather`, `all-to-all`, `all-reduce`, `reduce-scatter` and
`collective-permute` the partitioner put in is a line of it, with the shape
a chip receives and the `op_name` of the source op it was put in for. That
text exists without a chip (the v5e compiler runs in the sandbox:
`tests/test_tpu_aot_compile.py`), so WHAT a strategy moves can be checked
before any chip time is spent; how long it takes cannot.

`step_collectives` lists them, each with the `while` loop it runs in (the
layer scan's forward and backward bodies are
``.../jvp(Llama)/while`` and ``.../transpose(jvp(Llama))/while``), so a test
can assert that a layer moves its weights and not its activations
(docs/PERFORMANCE.md "collective overlap").
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Dict, List, Optional

KINDS = ("all-gather", "all-to-all", "all-reduce", "reduce-scatter",
         "collective-permute")

_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
             "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
             "f64": 8, "f8e4m3fn": 1, "f8e5m2": 1}

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_OP = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(?P<result>.+?)\s"
    r"(?P<kind>" + "|".join(KINDS) + r")(?P<start>-start)?\(")
_ARRAY = re.compile(r"\b([a-z]+\d*[a-z0-9]*)\[([\d,]*)\]")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLEE = re.compile(
    r"(?:calls|body|condition|to_apply|branch_computations|"
    r"called_computations)=(?:\{([^}]*)\}|(%?[\w.\-]+))")
_WHILE = re.compile(r"\swhile\(.*?\bbody=%?([\w.\-]+)")
_CHANNEL = re.compile(r"\bchannel_id=(\d+)")


@dataclasses.dataclass(frozen=True)
class Collective:
    kind: str          #: one of `KINDS`
    shapes: tuple      #: the arrays a chip receives, ("bf16", (8, 4096, 2048))
    op_name: str       #: the source op the partitioner put it in for
    loop: str          #: `op_name` of the enclosing `while`, "" outside any

    @property
    def nbytes(self) -> int:
        return sum(_ITEMSIZE.get(dt, 4) * math.prod(dims)
                   for dt, dims in self.shapes)

    @property
    def shape(self) -> str:
        return ", ".join(f"{dt}[{','.join(map(str, dims))}]"
                         for dt, dims in self.shapes)


def _arrays(result: str) -> tuple:
    return tuple(
        (dt, tuple(int(d) for d in dims.split(",") if d))
        for dt, dims in _ARRAY.findall(result) if dt in _ITEMSIZE)


def step_collectives(hlo_text: str) -> List[Collective]:
    """Every collective of a compiled program's HLO text, in the order
    the text has them. An asynchronous pair counts once (its ``-start``;
    the result is then the pair's tuple and ``shapes`` keeps the received
    half). A collective the compiler wrapped into a fusion is found in the
    fused computation and attributed to the loop that calls the fusion;
    where it split one into the steps of an asynchronous fusion, each step
    holds a copy under the same ``channel_id``, and the first is kept."""
    found = []          # (kind, result, op_name, computation, is_start)
    channels = set()
    calls: Dict[str, List[str]] = {}     # computation -> callees
    loops: Dict[str, str] = {}           # while-body computation -> op_name
    comp: Optional[str] = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m and "=" not in line.split("(", 1)[0]:
            comp = m.group(1)
            calls.setdefault(comp, [])
            continue
        if comp is None:
            continue
        for listed, single in _CALLEE.findall(line):
            calls[comp] += [c.strip().lstrip("%")
                            for c in (listed or single).split(",")]
        w = _WHILE.search(line)
        name = _OP_NAME.search(line)
        if w:
            loops[w.group(1)] = name.group(1) if name else w.group(1)
        m = _OP.match(line)
        if not m:
            continue
        channel = _CHANNEL.search(line)
        if channel:
            if channel.group(1) in channels:
                continue
            channels.add(channel.group(1))
        found.append((m.group("kind"), m.group("result"),
                      name.group(1) if name else "", comp,
                      bool(m.group("start"))))

    # the innermost loop a computation runs in: walk callees from each
    # while body; a nested body overrides its parent's label
    owner: Dict[str, str] = {}

    def claim(c: str, label: str, seen: set) -> None:
        if c in seen:
            return
        seen.add(c)
        owner[c] = label
        for callee in calls.get(c, ()):
            claim(callee, loops.get(callee, label), seen)

    called = {c for callees in calls.values() for c in callees}
    for root in calls:
        if root not in called:
            claim(root, "", set())

    out = []
    for kind, result, op_name, c, is_start in found:
        shapes = _arrays(result)
        if is_start and len(shapes) > 1:
            shapes = shapes[len(shapes) // 2:]
        out.append(Collective(kind, shapes, op_name, owner.get(c, "")))
    return out


def format_collectives(cols: List[Collective]) -> str:
    """One line a distinct (loop, kind, shape, op_name), with its count and
    the bytes a chip receives each time."""
    rows: Dict[tuple, int] = {}
    for c in cols:
        key = (c.loop, c.kind, c.shape, c.op_name, c.nbytes)
        rows[key] = rows.get(key, 0) + 1
    return "\n".join(
        f"{loop or '(outside loops)'} | {kind} -> {shape} | "
        f"{nbytes / 1e6:.1f} MB x{n} | {op_name}"
        for (loop, kind, shape, op_name, nbytes), n in rows.items())

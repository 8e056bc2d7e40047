"""numcheck — jaxpr-level mixed-precision flow auditor (RLT8xx).

The analysis stack audits sharding (RLT1xx), traced-code hygiene
(RLT2xx), collectives/HBM (RLT3xx), and host concurrency (RLT7xx);
this module adds the NUMERICS layer: a dtype-provenance pass over the
same jaxpr tracecheck walks (entering every sub-program
analysis/jaxpr.py finds), emitting RLT801-805 through the shared Finding
vocabulary. docs/STATIC_ANALYSIS.md "numcheck — the precision layer"
is the prose companion (dtype model, sanction rationale, known limits).

The dtype model (what each rule PROVES, and what it sanctions):

  * RLT801 low-precision-accumulation — a `dot_general` whose OUTPUT
    dtype is bf16/f16 (no ``preferred_element_type=f32``), or a
    `reduce_sum`/`cumsum` over a bf16/f16 operand, with contraction/
    reduction extent > `LOW_PRECISION_EXTENT`. Each bf16 add keeps 8
    mantissa bits; a K-term sum loses ~log2(K) of them. The MXU does
    accumulate a single dot in f32 internally, but a bf16 OUTPUT
    rounds that accumulator away at the op boundary — the repo's
    policy (ops/fused_ce.py, ops/pallas/*) is the explicit preferred
    f32 + one rounding, which this rule enforces. Small extents are
    sanctioned: the error is bounded by the extent.
  * RLT802 unstable-primitive-in-low-precision — exp/exp2/log/rsqrt
    (the softmax / logsumexp / variance building blocks) on a bf16/f16
    operand. Sanctions: an exp whose operand is max-subtracted (the
    ``x - reduce_max(x)`` provenance is tracked through layout ops) is
    the guarded softmax form and never flagged; the pallas kernels'
    f32 scratch is sanctioned by construction — their scores come out
    of preferred-f32 dots, so the exp/log operands the walk sees are
    already f32. Bounded primitives (sigmoid/tanh) are well-
    conditioned in bf16 and out of scope.
  * RLT803 cast-churn — an f32 value rounded to bf16/f16 and converted
    straight back to f32 with only layout ops (reshape/transpose/
    broadcast/slice/...) or a scan-carry boundary in between. Priced
    in wasted HBM bytes (the pointless narrow copy is written and read
    back) via the shared width table. Two sanctioned shapes: (a) round
    trips whose two converts live in DIFFERENT source files — the
    custom_vjp cotangent seam (jax rounds cotangents to the primal's
    dtype at each function boundary), which the caller cannot remove
    without changing the primal dtype contract; (b) rounding a fresh
    WIDE ACCUMULATOR (a dot output wider than an operand) — that is
    RLT801's own prescription (`preferred_element_type=f32`, round
    once after), so the downcast opens no round trip even when AD's
    transpose later re-widens the cotangent at the same site.
  * RLT804 low-precision-gradient-collective — a psum/reduce_scatter
    event whose payload dtype is bf16/f16 while the optimizer state of
    the matched parameter is stored wider. Judged over tracecheck's
    CollectiveEvent stream (gradient reductions under FSDP/DP are
    GSPMD-inserted — they exist only as events, never as jaxpr eqns)
    with widths from the SAME `costmodel.DTYPE_WIDTHS` table
    plan_checker's RLT105 reads, so the two rules cannot drift.
  * RLT805 quant-contract — the rule the int8-KV campaign (ROADMAP
    item 2c) compiles against. Every int8/int4-valued var (and every
    float var converted FROM one — an unscaled dequant) carries a
    `quant` flag; a multiply/divide by an f32-or-wider float operand
    clears it (the dequantization scale was applied); float arithmetic
    (dot/add/sub/reduce_sum) on a still-flagged value fires, as does a
    scale narrower than f32. Integer arithmetic on int8 (the proper
    int8xint8->int32 GEMM shape) keeps the flag without firing —
    the contract is judged where the value re-enters float math.
    uint8 is deliberately NOT tracked: it is overwhelmingly image/byte
    payload, not scaled-quantized data.

Known limits (documented, test-pinned where cheap): provenance does
not cross a pallas kernel boundary (kernel outputs restart from their
own dtype); `cond` merges branch flags optimistically (a sanction in
any branch sanctions the merged value); the scale-clearing rule cannot
distinguish a real dequant scale from any other multiply — forgiving
by design.

The module also hosts the STATIC (AST) numerics mini-pass behind
``lint --numerics``: single-expression patterns only — an
``.astype(bf16/f16)`` operand inline in a jnp.dot/matmul/einsum/
lax.dot_general call without ``preferred_element_type`` (RLT801), or
an inline ``.astype(int8/int4)`` operand (RLT805). Same
``# rlt: disable=`` suppression as every other AST rule.
"""
from __future__ import annotations

import dataclasses
import math
import os
from typing import (
    Any, Dict, List, Mapping, Optional, Sequence, Tuple,
)

from ray_lightning_tpu.analysis.costmodel import dtype_width
from ray_lightning_tpu.analysis.findings import Finding
from ray_lightning_tpu.analysis.jaxpr import (
    call_body, source_of, sub_jaxprs,
)

__all__ = [
    "LOW_PRECISION_EXTENT", "numcheck_jaxpr",
    "check_gradient_collectives", "check_numerics_sources",
    "check_numerics_paths", "summarize",
]

#: contraction/reduction extents at or below this are sanctioned for
#: RLT801: a K-term bf16 sum loses ~log2(K) of its 8 mantissa bits, so
#: 256 terms cost at most one decimal digit — the point where the
#: rounding stops being noise. Above it (the 4096-wide model dims, the
#: quarter-million-token wgrad contractions) the accumulator must be
#: f32.
LOW_PRECISION_EXTENT = 256

_LOW_FLOAT = frozenset({"bfloat16", "float16"})
_QUANT_INT = frozenset({"int8", "int4", "uint4"})
_FLOAT_NAMES = frozenset({
    "bfloat16", "float16", "float32", "float64",
    "float8_e4m3fn", "float8_e5m2", "float8_e4m3b11fnuz",
})

#: ops that move/relabel bytes without arithmetic: dtype provenance
#: (cast_from / submax / is_max / quant) rides through them unchanged
_CARRIES_PROVENANCE = frozenset({
    "reshape", "transpose", "broadcast_in_dim", "squeeze",
    "expand_dims", "rev", "copy", "slice", "dynamic_slice", "gather",
    "sharding_constraint", "name", "reduce_precision", "pad",
    "stop_gradient", "real", "imag", "neg",
})


def _is_float(name: str) -> bool:
    return name in _FLOAT_NAMES


def _width(name: str) -> float:
    return dtype_width(name) or 0.0


def _dtype_of(aval) -> str:
    """Dtype name of an aval — follows pallas `Ref` avals to their
    inner aval so kernel interiors audit like plain arrays."""
    dt = getattr(aval, "dtype", None)
    if dt is None:
        dt = getattr(getattr(aval, "inner_aval", None), "dtype", None)
    return str(dt) if dt is not None else "opaque"


def _size_of(aval) -> int:
    shape = getattr(aval, "shape", None)
    if shape is None:
        shape = getattr(getattr(aval, "inner_aval", None), "shape", ())
    return int(math.prod(shape or (1,)))


def _fmt_mib(n: float) -> str:
    return f"{n / (1024 ** 2):.1f} MiB"


def _src_file(src: Optional[str]) -> Optional[str]:
    """File component of a "prim @ file.py:line" source string."""
    if not src or " @ " not in src:
        return None
    return src.split(" @ ", 1)[1].rsplit(":", 1)[0]


@dataclasses.dataclass
class _VInfo:
    """Per-var numeric provenance.

    ``widest`` is the (width, dtype-name) of the widest FLOAT dtype on
    the value's provenance path — the loss's entry is the report's
    "widest-path dtype". ``cast_from`` names the wider float this value
    was rounded down from, surviving layout ops only (any arithmetic
    clears it — the round trip then bought a real narrower compute).
    ``is_max``/``submax`` track the ``x - reduce_max(x)`` softmax guard.
    ``quant`` is the RLT805 contract flag (see module docstring)."""

    widest: Tuple[float, str]
    cast_from: Optional[str] = None
    #: source of the downcast that set ``cast_from`` — names the other
    #: end of the round trip in the RLT803 message
    cast_src: Optional[str] = None
    submax: bool = False
    is_max: bool = False
    quant: bool = False
    #: output of a dot_general wider than at least one float operand —
    #: a fresh accumulator. Rounding it once is RLT801's RECOMMENDED
    #: shape (`preferred_element_type=f32`, round after), so that
    #: downcast never opens an RLT803 round trip: its complementary
    #: upcast (often jax's AD transpose re-widening the cotangent) is
    #: the unavoidable other half of the sanctioned design.
    acc_wide: bool = False


def _info_for(aval) -> _VInfo:
    dt = _dtype_of(aval)
    w = _width(dt) if _is_float(dt) else 0.0
    return _VInfo(widest=(w, dt if w else ""), quant=dt in _QUANT_INT)


class _NumAuditor:
    """Single-use dtype-provenance walker. Mirrors tracecheck's
    recursion structure but carries numeric state instead of sharding
    state; findings dedupe by (rule, source) so loop trips and repeated
    walks (scan fixpoints) report one finding per site."""

    def __init__(self):
        self._findings: Dict[Tuple, Finding] = {}
        self._quiet = 0

    # ---- plumbing -------------------------------------------------------

    @property
    def findings(self) -> List[Finding]:
        return list(self._findings.values())

    def flag(self, rule: str, message: str, *, source: str) -> None:
        if self._quiet:
            return
        key = (rule, source)
        if key not in self._findings:
            self._findings[key] = Finding(
                rule, f"{message} [at {source}]", symbol=source)

    def _read(self, env: Dict, v) -> _VInfo:
        if not hasattr(v, "count"):  # Literal
            return _info_for(getattr(v, "aval", None))
        got = env.get(v)
        if got is None:
            return _info_for(getattr(v, "aval", None))
        return got

    # ---- the walk -------------------------------------------------------

    def walk(self, jaxpr, env: Dict) -> None:
        for eqn in jaxpr.eqns:
            try:
                self._process(eqn, env)
            except Exception as exc:  # noqa: BLE001 — the audit's
                # promise is to finish: what a handler could not model
                # is an RLT310 error, and the outputs restart from their
                # dtype
                self.flag("RLT310", f"{type(exc).__name__}: {exc}",
                          source=eqn.primitive.name)
                for v in eqn.outvars:
                    if hasattr(v, "count"):
                        env[v] = _info_for(getattr(v, "aval", None))

    def _seed_and_walk(self, closed_or_open, in_infos: Sequence[_VInfo],
                       ) -> Tuple[Dict, List[_VInfo]]:
        inner = getattr(closed_or_open, "jaxpr", closed_or_open)
        sub_env: Dict = {}
        for iv, info in zip(inner.invars, in_infos):
            sub_env[iv] = info
        for iv in inner.invars[len(in_infos):]:
            sub_env[iv] = _info_for(getattr(iv, "aval", None))
        for cv in inner.constvars:
            sub_env[cv] = _info_for(getattr(cv, "aval", None))
        self.walk(inner, sub_env)
        outs = [self._read(sub_env, ov) for ov in inner.outvars]
        return sub_env, outs

    # ---- helpers --------------------------------------------------------

    def _default_out(self, ins: Sequence[_VInfo], aval) -> _VInfo:
        out = _info_for(aval)
        for i in ins:
            if i.widest[0] > out.widest[0]:
                out.widest = i.widest
        return out

    def _consume_quant(self, eqn, ins, src) -> None:
        """RLT805 fire point: a still-flagged FLOAT value reaches
        arithmetic — the dequant scale was never applied."""
        for v, info in zip(eqn.invars, ins):
            dt = _dtype_of(getattr(v, "aval", None))
            if info.quant and _is_float(dt):
                self.flag(
                    "RLT805",
                    f"an int8/int4-origin value (now {dt}) is consumed "
                    f"by {eqn.primitive.name} with no dequantization "
                    "scale applied: multiply by the f32 scale between "
                    "the integer load and the math",
                    source=src)
                return

    # ---- per-primitive dispatch -----------------------------------------

    def _process(self, eqn, env: Dict) -> None:
        name = eqn.primitive.name
        ins = [self._read(env, v) for v in eqn.invars]
        out = [v for v in eqn.outvars]
        src = source_of(eqn)

        def set_all(infos: Sequence[_VInfo]) -> None:
            for v, info in zip(out, infos):
                if hasattr(v, "count"):
                    env[v] = info

        def set_default() -> None:
            set_all([self._default_out(ins, getattr(v, "aval", None))
                     for v in out])

        if name == "convert_element_type":
            set_all([self._convert(eqn, ins[0], src)])
        elif name in _CARRIES_PROVENANCE:
            base = ins[0] if ins else _info_for(
                getattr(out[0], "aval", None))
            info = self._default_out(ins, getattr(out[0], "aval", None))
            info.cast_from = base.cast_from
            info.cast_src = base.cast_src
            info.submax = base.submax
            info.is_max = base.is_max
            info.quant = base.quant
            info.acc_wide = base.acc_wide
            set_all([dataclasses.replace(info) for _ in out])
        elif name in ("concatenate", "dynamic_update_slice", "scatter",
                      "scatter-add", "scatter_add", "select_n"):
            # value merges: flags combine forgivingly (a sanction on any
            # piece sanctions the merge), quant pessimistically (any
            # unscaled piece keeps the contract open)
            cases = ins[1:] if name == "select_n" else ins
            cases = cases or ins
            info = self._default_out(ins, getattr(out[0], "aval", None))
            info.quant = any(i.quant for i in cases)
            info.is_max = any(i.is_max for i in cases)
            info.submax = any(i.submax for i in cases)
            cf = {i.cast_from for i in cases}
            info.cast_from = cf.pop() if len(cf) == 1 else None
            info.cast_src = next(
                (i.cast_src for i in cases if i.cast_src), None) \
                if info.cast_from else None
            set_all([dataclasses.replace(info) for _ in out])
        elif name in ("reduce_max", "argmax"):
            info = self._default_out(ins, getattr(out[0], "aval", None))
            info.is_max = True
            set_all([info])
        elif name == "max":
            info = self._default_out(ins, getattr(out[0], "aval", None))
            info.is_max = any(i.is_max for i in ins)
            set_all([info])
        elif name == "sub":
            self._consume_quant(eqn, ins, src)
            info = self._default_out(ins, getattr(out[0], "aval", None))
            info.submax = len(ins) > 1 and ins[1].is_max
            set_all([info])
        elif name in ("add", "add_any"):
            self._consume_quant(eqn, ins, src)
            set_default()
        elif name in ("mul", "div"):
            set_all([self._scale(eqn, ins, src)])
        elif name in ("exp", "exp2"):
            op_dt = _dtype_of(getattr(eqn.invars[0], "aval", None))
            if (op_dt in _LOW_FLOAT and not ins[0].submax):
                self.flag(
                    "RLT802",
                    f"{name} on a {op_dt} operand with no upcast and no "
                    "max-subtraction: exp overflows bf16 beyond ~88 — "
                    "subtract the row max first (softmax form) or "
                    "compute in f32",
                    source=src)
            set_default()
        elif name in ("log", "rsqrt"):
            op_dt = _dtype_of(getattr(eqn.invars[0], "aval", None))
            if op_dt in _LOW_FLOAT:
                self.flag(
                    "RLT802",
                    f"{name} on a {op_dt} operand with no f32 upcast: "
                    "the low-order bits this primitive lives on are "
                    "already rounded away",
                    source=src)
            set_default()
        elif name == "dot_general":
            self._consume_quant(eqn, ins, src)
            out_dt = _dtype_of(getattr(out[0], "aval", None))
            (lc, _), _ = eqn.params["dimension_numbers"]
            lshape = getattr(getattr(eqn.invars[0], "aval", None),
                             "shape", ())
            extent = int(math.prod([lshape[d] for d in lc] or [1]))
            if out_dt in _LOW_FLOAT and extent > LOW_PRECISION_EXTENT:
                self.flag(
                    "RLT801",
                    f"dot_general accumulates {extent} products into a "
                    f"{out_dt} output (no preferred_element_type=f32): "
                    f"~{math.log2(extent):.0f} of its 8 mantissa bits "
                    "are rounding noise — set "
                    "preferred_element_type=jnp.float32 and round once "
                    "after",
                    source=src)
            info = self._default_out(ins, getattr(out[0], "aval", None))
            info.quant = any(i.quant for i in ins)
            if _is_float(out_dt):
                op_widths = [
                    _width(_dtype_of(getattr(v, "aval", None)))
                    for v in eqn.invars
                    if _is_float(_dtype_of(getattr(v, "aval", None)))]
                info.acc_wide = bool(
                    op_widths and _width(out_dt) > min(op_widths))
            set_all([info])
        elif name in ("reduce_sum", "cumsum"):
            self._consume_quant(eqn, ins, src)
            op_aval = getattr(eqn.invars[0], "aval", None)
            op_dt = _dtype_of(op_aval)
            shape = getattr(op_aval, "shape", ())
            if name == "cumsum":
                axis = eqn.params.get("axis", 0)
                extent = int(shape[axis]) if shape else 1
            else:
                axes = eqn.params.get("axes", ())
                extent = int(math.prod(
                    [shape[a] for a in axes] or [1]))
            if op_dt in _LOW_FLOAT and extent > LOW_PRECISION_EXTENT:
                self.flag(
                    "RLT801",
                    f"{name} over {extent} {op_dt} terms accumulates in "
                    f"{op_dt}: upcast the operand (or use a dot with "
                    "preferred_element_type=f32) so the accumulator is "
                    "f32",
                    source=src)
            set_default()
        elif name == "scan":
            self._scan(eqn, ins, env)
        elif name == "while":
            self._while(eqn, ins, env)
        elif name == "cond":
            self._cond(eqn, ins, env)
        elif name == "shard_map":
            _, outs = self._seed_and_walk(eqn.params["jaxpr"], ins)
            set_all(outs)
        elif name == "pallas_call":
            # kernel interiors audit like plain code (Ref reads restart
            # from the ref's dtype — an int8 pool read re-arms the
            # quant flag); kernel OUTPUT provenance does not cross the
            # boundary back out (documented limit)
            self._seed_and_walk(eqn.params["jaxpr"], ins)
            set_default()
        elif name == "remat_opt":
            closed = eqn.params["fwd_jaxpr"]
            _, outs = self._seed_and_walk(closed, ins)
            by_key: Dict[Tuple, List[_VInfo]] = {}
            for ov, info in zip(closed.jaxpr.outvars, outs):
                key = (tuple(getattr(ov.aval, "shape", ())),
                       _dtype_of(ov.aval))
                by_key.setdefault(key, []).append(info)
            for v in out:
                key = (tuple(getattr(v.aval, "shape", ())),
                       _dtype_of(v.aval))
                lst = by_key.get(key)
                env[v] = (lst.pop(0) if lst
                          else self._default_out(ins, v.aval))
        elif (body := call_body(eqn)) is not None:
            # a plain call, whatever jax names it
            _, outs = self._seed_and_walk(body, ins)
            set_all(outs)
        else:
            subs = sub_jaxprs(eqn)
            if subs:
                self.flag("RLT310", "the walk has no rule that enters "
                          "this equation's sub-program(s) "
                          f"{[k for k, _ in subs]}", source=src)
            set_default()

    # ---- convert / scale / control flow ---------------------------------

    def _convert(self, eqn, op: _VInfo, src: str) -> _VInfo:
        in_aval = getattr(eqn.invars[0], "aval", None)
        din, dout = _dtype_of(in_aval), _dtype_of(eqn.outvars[0].aval)
        win, wout = _width(din), _width(dout)
        info = self._default_out([op], eqn.outvars[0].aval)
        info.submax, info.is_max = op.submax, op.is_max
        if _is_float(din) and _is_float(dout):
            if wout < win:
                # rounding down: remember what we came from (keep an
                # even wider origin if the chain keeps narrowing) —
                # unless the value is a fresh wide accumulator: rounding
                # a dot's f32 accumulator ONCE is exactly what RLT801
                # prescribes, so that downcast opens no round trip
                if op.acc_wide:
                    pass
                elif op.cast_from and _width(op.cast_from) > win:
                    info.cast_from = op.cast_from
                    info.cast_src = op.cast_src
                else:
                    info.cast_from = din
                    info.cast_src = src
                info.quant = op.quant
            elif wout > win:
                # cross-FILE round trips are sanctioned: a cotangent
                # rounded to bf16 at one custom_vjp's output and
                # widened at the next function's input is jax's
                # cotangent-dtype convention (cotangents flow at the
                # primal's dtype across the seam) — the caller cannot
                # remove that hop without changing the primal contract.
                # Real churn has both converts in the same file.
                same_file = (_src_file(op.cast_src) == _src_file(src)
                             if op.cast_src else True)
                if (op.cast_from and wout >= _width(op.cast_from)
                        and same_file):
                    n = _size_of(in_aval)
                    wasted = n * win * 2  # narrow copy written + read
                    rounded = (f" (rounded at {op.cast_src})"
                               if op.cast_src else "")
                    self.flag(
                        "RLT803",
                        f"{op.cast_from}->{din}->{dout} round trip with "
                        f"no compute in between{rounded}: the narrow "
                        "copy buys nothing, costs a rounding, and moves "
                        f"~{_fmt_mib(wasted)} of pointless HBM traffic",
                        source=src)
                info.cast_from = None
                info.cast_src = None
                info.quant = op.quant
            else:
                info.cast_from = op.cast_from
                info.cast_src = op.cast_src
                info.quant = op.quant
        elif din in _QUANT_INT and _is_float(dout):
            # unscaled dequant: the contract stays open until a scale
            # is applied
            info.quant = True
        elif dout in _QUANT_INT:
            info.quant = True
        else:
            # int widening (int8 -> int32 index/count math) drops the
            # contract; everything else restarts from the dtype
            info.quant = dout in _QUANT_INT
        return info

    def _scale(self, eqn, ins: Sequence[_VInfo], src: str) -> _VInfo:
        info = self._default_out(ins, eqn.outvars[0].aval)
        dts = [_dtype_of(getattr(v, "aval", None)) for v in eqn.invars]
        quant = [i.quant for i in ins]
        if any(quant) and len(ins) == 2:
            other = 1 if quant[0] else 0
            if quant[0] and quant[1]:
                info.quant = True  # int8*int8 products: still unscaled
            elif _is_float(dts[other]):
                if _width(dts[other]) >= 4.0:
                    info.quant = False  # dequant scale applied
                else:
                    self.flag(
                        "RLT805",
                        f"dequantization scale is {dts[other]} — "
                        "narrower than f32: the scale re-quantizes the "
                        "error the int8 encoding already paid for; "
                        "store scales in f32",
                        source=src)
                    info.quant = False
            else:
                info.quant = True  # scaled by an int: not a dequant
        else:
            info.quant = any(quant)
        return info

    def _merge_carry(self, init: List[_VInfo],
                     outs: List[_VInfo]) -> List[_VInfo]:
        merged = []
        for a, b in zip(init, outs):
            m = dataclasses.replace(a)
            if b.widest[0] > m.widest[0]:
                m.widest = b.widest
            m.quant = a.quant or b.quant
            m.cast_from = a.cast_from or b.cast_from
            m.cast_src = (a.cast_src if a.cast_from else b.cast_src)
            m.is_max = a.is_max or b.is_max
            m.submax = a.submax or b.submax
            merged.append(m)
        return merged

    def _scan(self, eqn, ins: List[_VInfo], env: Dict) -> None:
        p = eqn.params
        closed = p["jaxpr"]
        nc, ncar = p["num_consts"], p["num_carry"]
        consts, init, xs = ins[:nc], ins[nc:nc + ncar], ins[nc + ncar:]
        self._quiet += 1
        try:
            _, outs = self._seed_and_walk(closed, consts + init + xs)
        finally:
            self._quiet -= 1
        carry = self._merge_carry(init, outs[:ncar])
        _, outs = self._seed_and_walk(closed, consts + carry + xs)
        for v, info in zip(eqn.outvars, outs[:ncar] + outs[ncar:]):
            if hasattr(v, "count"):
                env[v] = info

    def _while(self, eqn, ins: List[_VInfo], env: Dict) -> None:
        p = eqn.params
        cn, bn = p["cond_nconsts"], p["body_nconsts"]
        body = p["body_jaxpr"]
        bconsts, init = ins[cn:cn + bn], ins[cn + bn:]
        self._quiet += 1
        try:
            _, outs = self._seed_and_walk(body, bconsts + init)
        finally:
            self._quiet -= 1
        carry = self._merge_carry(init, outs)
        _, outs = self._seed_and_walk(body, bconsts + carry)
        for v, info in zip(eqn.outvars, outs):
            if hasattr(v, "count"):
                env[v] = info

    def _cond(self, eqn, ins: List[_VInfo], env: Dict) -> None:
        branches = eqn.params["branches"]
        ops = ins[1:]
        outs_by_branch = []
        for br in branches:  # every branch is real code: record all
            _, outs = self._seed_and_walk(br, ops)
            outs_by_branch.append(outs)
        merged = []
        for tup in zip(*outs_by_branch):
            m = dataclasses.replace(tup[0])
            for o in tup[1:]:
                if o.widest[0] > m.widest[0]:
                    m.widest = o.widest
                m.quant = m.quant or o.quant
                m.submax = m.submax or o.submax
                m.is_max = m.is_max or o.is_max
            merged.append(m)
        for v, info in zip(eqn.outvars, merged):
            if hasattr(v, "count"):
                env[v] = info


# --------------------------------------------------------------------------
# public API — jaxpr side
# --------------------------------------------------------------------------


def numcheck_jaxpr(closed, *, loss_index: Optional[int] = None,
                   ) -> Tuple[List[Finding], Dict[str, Any]]:
    """Audit a ClosedJaxpr (or anything with ``.jaxpr``) for RLT801/
    802/803/805 and return ``(findings, info)``. ``info`` carries
    ``loss_widest_dtype`` when ``loss_index`` names an output: the
    widest float dtype on that output's provenance path — the
    precision ledger's "is the loss math ever actually f32" answer."""
    aud = _NumAuditor()
    jaxpr = getattr(closed, "jaxpr", closed)
    env: Dict = {}
    for v in list(jaxpr.invars) + list(jaxpr.constvars):
        env[v] = _info_for(getattr(v, "aval", None))
    aud.walk(jaxpr, env)
    info: Dict[str, Any] = {}
    if loss_index is not None and 0 <= loss_index < len(jaxpr.outvars):
        ov = jaxpr.outvars[loss_index]
        vi = aud._read(env, ov)
        info["loss_widest_dtype"] = (
            vi.widest[1] or _dtype_of(getattr(ov, "aval", None)))
    return aud.findings, info


def _opt_width_by_param(named_params: Mapping[str, Any],
                        named_opt: Mapping[str, Any]) -> Dict[str, float]:
    """Max optimizer-state width per matched param path — the SAME
    longest-path-suffix + shape match plan_checker's RLT105 uses."""
    out: Dict[str, float] = {}
    for opath, oleaf in named_opt.items():
        oshape = getattr(oleaf, "shape", None)
        odtype = getattr(oleaf, "dtype", None)
        if oshape is None or odtype is None:
            continue
        parts = opath.split("/")
        for i in range(len(parts)):
            cand = "/".join(parts[i:])
            leaf = named_params.get(cand)
            if leaf is not None and getattr(leaf, "shape", ()) == oshape:
                w = dtype_width(odtype) or 0.0
                out[cand] = max(out.get(cand, 0.0), w)
                break
    return out


def check_gradient_collectives(
        events: Sequence[Any],
        named_params: Mapping[str, Any],
        named_opt: Mapping[str, Any]) -> List[Finding]:
    """RLT804 over tracecheck's CollectiveEvent stream: a psum/
    reduce_scatter whose payload dtype is bf16/f16, matched to a param
    whose optimizer state is stored wider. Width comparisons come from
    the shared `costmodel.DTYPE_WIDTHS` (single-sourced with RLT105)."""
    opt_w = _opt_width_by_param(named_params, named_opt)
    findings: List[Finding] = []
    seen = set()
    for ev in events:
        if getattr(ev, "kind", None) not in ("psum", "reduce_scatter"):
            continue
        dt = getattr(ev, "dtype", None)
        path = getattr(ev, "param_path", None)
        if dt not in _LOW_FLOAT or not path:
            continue
        ppath = path.split("/", 1)[1] if path.startswith("params/") \
            else None
        if ppath is None:
            continue
        ow = opt_w.get(ppath, 0.0)
        gw = dtype_width(dt) or 0.0
        if ow > gw:
            key = (ev.source, path)
            if key in seen:
                continue
            seen.add(key)
            findings.append(Finding(
                "RLT804",
                f"gradient {ev.kind} over {'x'.join(ev.axes)} runs on a "
                f"{dt} payload while {ppath}'s optimizer state is "
                f"stored {ow:g}-byte wide: the ring reduction "
                "accumulates in the wire dtype, losing precision "
                "before the optimizer sees the sum — widen the "
                "gradient (preferred_element_type=f32 on the backward "
                f"matmuls) [at {ev.source}]",
                symbol=path))
    return findings


def summarize(findings: Sequence[Finding]) -> dict:
    """Counts-by-rule block for bench JSON lines (backend-down safe —
    pure host-side work), mirroring concurrency.summarize."""
    by_rule: Dict[str, int] = {}
    for f in findings:
        by_rule[f.rule] = by_rule.get(f.rule, 0) + 1
    return {"total": len(findings), "by_rule": dict(sorted(by_rule.items()))}


# --------------------------------------------------------------------------
# static (AST) mini-pass — `lint --numerics`
# --------------------------------------------------------------------------
#
# Single-expression window only (documented limit): the jaxpr pass is
# the real engine; this catches the copy-paste shapes reviewers meet in
# diffs — an `.astype(bf16)` pushed INLINE into a dot/einsum call
# without preferred_element_type, or an inline `.astype(int8)` operand.

_AST_DOT_CALLS = frozenset({
    "jnp.dot", "jnp.matmul", "jnp.einsum", "jnp.tensordot",
    "jax.numpy.dot", "jax.numpy.matmul", "jax.numpy.einsum",
    "lax.dot_general", "jax.lax.dot_general",
})
_AST_LOW_FLOAT = frozenset({
    "jnp.bfloat16", "jnp.float16", "jax.numpy.bfloat16",
    "jax.numpy.float16", "np.float16", "bfloat16", "float16",
})
_AST_QUANT = frozenset({
    "jnp.int8", "jnp.int4", "jax.numpy.int8", "jax.numpy.int4",
    "np.int8", "int8", "int4",
})


def _ast_dotted(node) -> Optional[str]:
    import ast

    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _astype_target(node) -> Optional[str]:
    """'jnp.bfloat16'-style dtype name when ``node`` is an
    ``<expr>.astype(<dtype>)`` call, else None."""
    import ast

    if not (isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "astype" and node.args):
        return None
    arg = node.args[0]
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return arg.value
    return _ast_dotted(arg)


def check_numerics_sources(
        sources: Sequence[Tuple[str, str]]) -> List[Finding]:
    """Run the static numerics pass over (filename, source) pairs."""
    import ast

    from ray_lightning_tpu.analysis.linter import _FileLint

    out: List[Finding] = []
    for filename, source in sources:
        try:
            tree = ast.parse(source)
        except SyntaxError:
            continue  # the shardcheck linter owns RLT001
        lint = _FileLint(source, filename)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            callee = _ast_dotted(node.func)
            if callee not in _AST_DOT_CALLS:
                continue
            has_pref = any(kw.arg == "preferred_element_type"
                           for kw in node.keywords)
            for arg in node.args:
                dt = _astype_target(arg)
                if dt is None:
                    continue
                if dt in _AST_LOW_FLOAT and not has_pref:
                    lint.add(
                        "RLT801",
                        f"{callee} consumes an inline "
                        f".astype({dt}) operand with no "
                        "preferred_element_type: the contraction "
                        "accumulates (and rounds) in the narrow dtype "
                        "— add preferred_element_type=jnp.float32",
                        node=node)
                    break
                if dt in _AST_QUANT:
                    lint.add(
                        "RLT805",
                        f"{callee} consumes an inline .astype({dt}) "
                        "operand: quantized payloads need their f32 "
                        "dequantization scale applied before float "
                        "math",
                        node=node)
                    break
        out.extend(lint.findings)
    return out


def check_numerics_paths(paths: Sequence[str]) -> List[Finding]:
    """Run the static numerics pass over files/dirs (dirs expand
    recursively), mirroring concurrency.check_concurrency_paths."""
    from ray_lightning_tpu.analysis.linter import iter_python_files

    files = iter_python_files(paths)
    sources: List[Tuple[str, str]] = []
    common = ""
    if len(files) > 1:
        common = os.path.commonpath([os.path.abspath(f) for f in files])
    elif files:
        common = os.path.dirname(os.path.abspath(files[0]))
    for f in files:
        try:
            with open(f, "r", encoding="utf-8") as fh:
                source = fh.read()
        except OSError:
            continue
        rel = os.path.relpath(os.path.abspath(f), common) if common else f
        sources.append((rel, source))
    return check_numerics_sources(sources)

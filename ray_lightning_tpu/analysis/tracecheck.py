"""tracecheck — jaxpr-level collective & memory auditor for jitted train
steps.

PR 1's shardcheck proves a plan is well-formed in *source and spec*
terms; it cannot see what XLA will actually DO with the jitted step.
tracecheck closes that gap without touching hardware: it traces the
strategy's real train step with `jax.make_jaxpr` over abstractions
(`jax.eval_shape` params over an `AbstractMesh` — runs under
`JAX_PLATFORMS=cpu`), then walks the jaxpr, recursing into
every sub-program (analysis/jaxpr.py finds them by structure), and reports:

  1. the **collective schedule** — every explicit psum / all_gather /
     reduce_scatter / ppermute / all_to_all (shard_map islands: ring and
     ulysses attention, the GPipe pipeline) PLUS the collectives GSPMD
     must insert to run the auto-sharded regions (FSDP weight gathers,
     gradient reductions), each with axes, payload bytes, and a wire/
     latency estimate from the per-topology cost model
     (analysis/costmodel.py);
  2. **implicit resharding** (RLT301, "RESHARD-IMPLICIT") — sharding
     mismatches that force XLA to move an *activation* (not a planned
     parameter gather) or to reconcile two different mesh axes on the
     same dim: ICI traffic the plan never asked for, with the
     responsible eqn's source line and the originating leaf path;
  3. a **peak-HBM estimate** (liveness over the jaxpr: params + opt
     state + the activation high-water mark, remat-aware because remat2
     bodies free their internals) checked against the topology's chip
     budget (RLT302, "HBM-OVERCOMMIT");
  4. **ring/pipeline schedule checks** (RLT303, "RING-DEADLOCK") —
     ppermute permutations with duplicate sources/destinations or
     out-of-range ranks, full permutations that are not a single cycle
     (two disjoint rings never drain), and collective sequences that
     diverge across `cond` branches (SPMD ranks deadlock).

The sharding propagation is a FIRST-ORDER model of GSPMD, not a
reimplementation: per-var specs flow through elementwise ops,
dot_general, transpose/reshape/broadcast, reductions and control flow;
contractions over co-sharded dims become partial sums resolved as
reduce_scatter when the result is parameter-shaped (ZeRO) and psum
otherwise; axis conflicts are resolved the way GSPMD prefers — gather
the parameter-derived side (that IS the FSDP plan), flag the
activation-derived side. Unknown primitives degrade to unknown
shardings, never to invented findings, and never in silence
(`TraceReport.lost_specs`, `.unentered`). Real schedules may beat the
estimate (e.g. XLA can turn a psum into reduce_scatter+all_gather and
overlap it); treat the numbers as a reviewable upper bound, stable
across refactors — the point is the DIFF between two plans, not chip
parity.

Entry points: `audit_step(module, strategy, example_batch,
topology=...)`, `Strategy.audit_step(...)`, `TpuModule.audit_step(...)`,
and the CLI `python -m ray_lightning_tpu trace <example|preset|module:fn>
[--topo v5p-64] [--json]`.
"""
from __future__ import annotations

import dataclasses
import math
from typing import (
    Any, Dict, FrozenSet, List, Mapping, Optional, Sequence, Tuple,
)

from ray_lightning_tpu.analysis.costmodel import (
    Topology, collective_cost, parse_topology,
)
from ray_lightning_tpu.analysis.findings import Finding
from ray_lightning_tpu.analysis.jaxpr import (
    call_body, dce, pallas_kernel_ident, source_of, sub_jaxprs, walk_eqns,
)

__all__ = [
    "CollectiveEvent", "TraceReport", "audit_step", "trace_step",
    "check_permutation",
]

#: per-dim mesh axes; None = unknown (propagation gave up — never a
#: finding source)
Spec = Optional[Tuple[FrozenSet[str], ...]]

_ELEMENTWISE = {
    "add", "add_any", "sub", "mul", "div", "rem", "max", "min", "pow",
    "atan2", "and", "or", "xor", "shift_left", "shift_right_logical",
    "shift_right_arithmetic", "nextafter", "eq", "ne", "lt", "le", "gt",
    "ge", "select_n", "clamp",
}
_PASSTHROUGH = {
    "convert_element_type", "copy", "neg", "exp", "exp2", "expm1", "log",
    "log1p", "sin", "cos", "tan", "asin", "acos", "atan", "sinh", "cosh",
    "tanh", "asinh", "acosh", "atanh", "logistic", "sqrt", "rsqrt",
    "cbrt", "integer_pow", "sign", "abs", "floor", "ceil", "round",
    "is_finite", "not", "erf", "erfc", "erf_inv", "real", "imag",
    "stop_gradient", "name", "cumsum", "cumprod",
    "cummax", "cummin", "cumlogsumexp", "nan_to_num", "population_count",
    "clz", "copy_start", "copy_done", "reduce_precision", "square",
    "conj", "bitcast_convert_type",
}
_REDUCE = {"reduce_sum", "reduce_prod", "reduce_max", "reduce_min",
           "reduce_and", "reduce_or", "reduce_xor", "argmax", "argmin"}
#: reductions whose cross-shard completion is a real all-reduce worth
#: charging (boolean/arg reduces move negligible bytes)
_REDUCE_COMM = {"reduce_sum", "reduce_prod", "reduce_max", "reduce_min"}
_COLLECTIVES = {"psum", "pmax", "pmin", "ppermute", "all_gather",
                "reduce_scatter", "all_to_all", "pbroadcast"}
_REPLICATED_SOURCES = {"iota", "rng_bit_generator", "random_seed",
                       "random_wrap", "random_bits", "random_fold_in"}


def _repl(ndim: int) -> Tuple[FrozenSet[str], ...]:
    return tuple(frozenset() for _ in range(ndim))


def _axes_in(spec: Spec) -> FrozenSet[str]:
    if spec is None:
        return frozenset()
    out: FrozenSet[str] = frozenset()
    for s in spec:
        out |= s
    return out


def _spec_of_partition_spec(pspec, ndim: int) -> Tuple[FrozenSet[str], ...]:
    """PartitionSpec-like -> per-dim axis sets, padded to ndim."""
    dims: List[FrozenSet[str]] = []
    for entry in tuple(pspec):
        if entry is None:
            dims.append(frozenset())
        elif isinstance(entry, (tuple, list)):
            dims.append(frozenset(entry))
        else:
            dims.append(frozenset((entry,)))
    while len(dims) < ndim:
        dims.append(frozenset())
    return tuple(dims[:ndim])


@dataclasses.dataclass
class _VarInfo:
    spec: Spec
    param: bool = False          # derived exclusively from param/opt/const
    path: Optional[str] = None   # originating leaf path when single-source
    #: the loop multiplier in effect where this value is DEFINED. A
    #: param gather inside a scan whose operand was born outside it is
    #: loop-invariant — XLA hoists it, so it is charged at born_mult,
    #: not at the loop's trip count (lm_head inside the CE chunk scan:
    #: one gather per step, not one per chunk).
    born_mult: int = 1


@dataclasses.dataclass
class CollectiveEvent:
    """One collective site in the traced step (aggregated over loop trips).

    ``payload_bytes`` follows the cost-model contract (costmodel.py):
    local operand bytes for psum/ppermute/reduce_scatter/all_to_all, the
    per-chip post-gather bytes for all_gather. ``count`` folds in scan
    trip counts; ``wire_bytes``/``time_us`` are count-weighted totals.
    ``implicit`` marks collectives *inferred* from sharding propagation
    (GSPMD will insert them) as opposed to explicit shard_map
    collectives; ``unbounded`` marks sites inside a while-loop whose trip
    count the trace cannot know (counted once); ``scope`` is the id of
    the enclosing scanned body (None at top level)."""

    kind: str
    axes: Tuple[str, ...]
    payload_bytes: int
    count: int
    wire_bytes: int
    time_us: float
    implicit: bool
    source: str
    param_path: Optional[str] = None
    unbounded: bool = False
    scope: Optional[int] = None
    #: bytes each chip puts on DCN (multi-slice topologies only): the
    #: inter-slice stage of a hierarchical collective whose group spans
    #: slices. ``wire_bytes`` stays the ICI tier; ``time_us`` includes
    #: both tiers (costmodel.collective_cost).
    dcn_bytes: int = 0
    #: payload dtype name ("bfloat16"/"float32"/...), when the walk
    #: could see it — numcheck's RLT804 judges gradient reductions over
    #: this field (the GSPMD-inserted grad psum/reduce_scatter exists
    #: only as an event, never as a jaxpr eqn). None on synthetic or
    #: pre-dtype-threading events.
    dtype: Optional[str] = None

    def describe(self) -> str:
        tag = "implicit" if self.implicit else "explicit"
        extra = " trip-count-unknown" if self.unbounded else ""
        who = f"  <{self.param_path}>" if self.param_path else ""
        dcn = (f" +{_fmt_bytes(self.dcn_bytes).strip()} DCN"
               if self.dcn_bytes else "")
        dt = f" {self.dtype}" if self.dtype else ""
        return (f"{self.kind:<14} axes={','.join(self.axes) or '-'} "
                f"x{self.count:<4} {_fmt_bytes(self.wire_bytes)} wire"
                f"{dcn}{dt} {self.time_us:9.1f} us  [{tag}{extra}] "
                f"{self.source}{who}")


def _aval_dtype(aval) -> Optional[str]:
    dt = getattr(aval, "dtype", None)
    return str(dt) if dt is not None else None


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024 or unit == "TiB":
            return f"{n:7.2f} {unit}"
        n /= 1024
    return f"{n:.2f} TiB"


@dataclasses.dataclass
class TraceReport:
    """Everything tracecheck proved about one (module, strategy,
    topology) triple. `findings` reuse the shardcheck vocabulary
    (RLT301/302/303) so CLI gates and suppression work unchanged."""

    topology: Topology
    mesh_axes: Dict[str, int]
    collectives: List[CollectiveEvent]
    findings: List[Finding]
    params_bytes_per_device: int
    opt_bytes_per_device: int
    peak_hbm_bytes: int
    hbm_budget_bytes: int
    label: str = ""
    #: per-device matmul FLOPs a step inside scanned bodies (what
    #: `telemetry/report.py` prices as the predicted compute window)
    scan_flops: float = 0.0
    #: pallas kernel identities the walk met (`jaxpr.pallas_kernel_ident`)
    #: — the serve audit's "which attention path does this step run"
    #: evidence (empty on pure-XLA programs)
    pallas_kernels: List[str] = dataclasses.field(default_factory=list)
    #: numcheck's precision ledger: per-dtype-class byte itemization
    #: ({"params": {dtype: bytes}, "opt_state": {...},
    #: "activations": {...}, "kv_pool": {...}} — sub-jaxpr scratch is
    #: folded into activations per dtype by the walk's `_sub_by`
    #: threading) plus "loss_widest_dtype", the widest float dtype on
    #: the loss output's provenance path. None when the audit ran with
    #: numerics off.
    precision: Optional[Dict[str, Any]] = None
    #: primitives that took the walk's default "unknown spec" branch on
    #: a SHARDED operand, with how many equations did: every spec the
    #: model lost is lost on the record (what flows on is sized whole
    #: and implies no collective). A warning; the model pins hold the
    #: bundled models' set of names.
    lost_specs: Dict[str, int] = dataclasses.field(default_factory=dict)

    @property
    def unentered(self) -> List[str]:
        """What the walks (tracecheck's and numcheck's) could not model
        at all: an equation whose sub-program no rule entered, or whose
        handler raised. Each is an RLT310 error finding; non-empty means
        the report's "clean" is not to be trusted."""
        return [f.message for f in self.findings if f.rule == "RLT310"]

    @property
    def ici_bytes_per_step(self) -> int:
        return sum(e.wire_bytes for e in self.collectives)

    @property
    def dcn_bytes_per_step(self) -> int:
        """Per-chip bytes on the inter-slice (DCN) tier; 0 on a
        single-slice topology."""
        return sum(e.dcn_bytes for e in self.collectives)

    @property
    def ici_time_us(self) -> float:
        return sum(e.time_us for e in self.collectives)

    @property
    def fits(self) -> bool:
        return self.peak_hbm_bytes <= self.hbm_budget_bytes

    def totals_by_kind(self) -> Dict[str, Dict[str, float]]:
        out: Dict[str, Dict[str, float]] = {}
        for e in self.collectives:
            t = out.setdefault(e.kind, {"count": 0, "wire_bytes": 0,
                                        "time_us": 0.0})
            t["count"] += e.count
            t["wire_bytes"] += e.wire_bytes
            t["time_us"] += e.time_us
        return out

    def summary(self) -> str:
        gib = 1024**3
        lines = [
            f"tracecheck: {self.label or 'step'} on "
            f"{self.topology.describe()}",
            f"mesh {self.mesh_axes}",
        ]
        if self.collectives:
            lines.append("collective schedule (per train step):")
            for e in sorted(self.collectives, key=lambda e: -e.wire_bytes):
                lines.append("  " + e.describe())
            lines.append(
                f"ICI total: {self.ici_bytes_per_step / gib:.3f} GiB/step "
                f"on the wire, ~{self.ici_time_us / 1e3:.2f} ms serialized "
                f"({self.topology.ici_gbps:.0f} GB/s per chip)")
            if self.topology.n_slices > 1:
                lines.append(
                    f"DCN total: {self.dcn_bytes_per_step / gib:.3f} "
                    f"GiB/step per chip across {self.topology.n_slices} "
                    f"slices ({self.topology.dcn_gbps:.1f} GB/s per "
                    "chip) — inter-slice stage of the crossing "
                    "collectives, itemized above")
        else:
            lines.append("collective schedule: none (single-device or "
                         "fully replicated step)")
        lines.append(
            f"peak HBM estimate: {self.peak_hbm_bytes / gib:.2f} GiB "
            f"per device (params {self.params_bytes_per_device / gib:.2f} "
            f"+ opt {self.opt_bytes_per_device / gib:.2f} + live "
            "intermediates) vs budget "
            f"{self.hbm_budget_bytes / gib:.2f} GiB — "
            f"{'FITS' if self.fits else 'DOES NOT FIT'}")
        if self.precision:
            lines.append("precision ledger (per device):")
            for cls in ("params", "opt_state", "activations", "kv_pool"):
                by = self.precision.get(cls) or {}
                if not by:
                    continue
                parts = " + ".join(
                    f"{dt} {b / gib:.3f} GiB"
                    for dt, b in sorted(by.items(), key=lambda kv: -kv[1]))
                lines.append(f"  {cls:<12}: {parts}")
            widest = self.precision.get("loss_widest_dtype")
            if widest:
                lines.append(f"  loss widest-path dtype: {widest}")
        if self.lost_specs:
            lines.append(
                "warning: sharding lost at primitives the walk has no "
                "rule for (sized whole downstream, no collective "
                "implied): " + ", ".join(
                    f"{n} x{c}" for n, c in sorted(self.lost_specs.items())))
        if self.findings:
            lines.append(f"findings ({len(self.findings)}):")
            lines.extend("  " + f.format() for f in self.findings)
        else:
            lines.append("findings: none")
        return "\n".join(lines)

    def to_dict(self) -> dict:
        return {
            "label": self.label,
            "topology": {
                "name": self.topology.name,
                "device_kind": self.topology.device_kind,
                "n_devices": self.topology.n_devices,
                "ici_gbps": self.topology.ici_gbps,
                "hbm_bytes": self.topology.hbm_bytes,
                "n_slices": self.topology.n_slices,
                "dcn_gbps": self.topology.dcn_gbps,
            },
            "mesh": self.mesh_axes,
            "ici_bytes_per_step": self.ici_bytes_per_step,
            "dcn_bytes_per_step": self.dcn_bytes_per_step,
            "ici_time_us": round(self.ici_time_us, 1),
            "collectives": [
                {"kind": e.kind, "axes": list(e.axes),
                 "payload_bytes": e.payload_bytes, "count": e.count,
                 "wire_bytes": e.wire_bytes, "dcn_bytes": e.dcn_bytes,
                 "time_us": round(e.time_us, 1), "implicit": e.implicit,
                 "source": e.source, "param_path": e.param_path,
                 "unbounded": e.unbounded, "scope": e.scope,
                 "dtype": e.dtype}
                for e in sorted(self.collectives,
                                key=lambda e: -e.wire_bytes)
            ],
            "totals_by_kind": self.totals_by_kind(),
            "params_bytes_per_device": self.params_bytes_per_device,
            "opt_bytes_per_device": self.opt_bytes_per_device,
            "peak_hbm_bytes": self.peak_hbm_bytes,
            "hbm_budget_bytes": self.hbm_budget_bytes,
            "fits": self.fits,
            "pallas_kernels": list(self.pallas_kernels),
            "precision": self.precision,
            "unentered": self.unentered,
            "lost_specs": dict(sorted(self.lost_specs.items())),
            "findings": [f.to_dict() for f in self.findings],
        }


# --------------------------------------------------------------------------
# permutation checks (RLT303)
# --------------------------------------------------------------------------


def check_permutation(perm: Sequence[Tuple[int, int]], axis_size: int,
                      *, source: str = "<ppermute>") -> List[Finding]:
    """Validate one ppermute schedule. Legal schedules (the ops/ hooks
    `ring_attention.ring_perm` and `pipeline.pipeline_perm` are the two
    canonical producers): unique sources, unique destinations, ranks in
    range, and — when the permutation is FULL — a single cycle. Partial
    permutations (open chains) are legal; two disjoint full cycles mean
    two rings that each wait on traffic the other holds."""
    findings: List[Finding] = []
    perm = [(int(s), int(d)) for s, d in perm]
    srcs = [s for s, _ in perm]
    dsts = [d for _, d in perm]
    bad_rank = sorted({r for r in srcs + dsts
                       if r < 0 or r >= axis_size})
    if bad_rank:
        findings.append(Finding(
            "RLT303",
            f"ppermute names rank(s) {bad_rank} outside the axis "
            f"(size {axis_size}) — the schedule cannot execute",
            file=None, symbol=source))
    dup_s = sorted({s for s in srcs if srcs.count(s) > 1})
    dup_d = sorted({d for d in dsts if dsts.count(d) > 1})
    if dup_s:
        findings.append(Finding(
            "RLT303",
            f"ppermute has duplicate source rank(s) {dup_s}: a rank "
            "cannot send two different payloads on one permute",
            symbol=source))
    if dup_d:
        findings.append(Finding(
            "RLT303",
            f"ppermute has duplicate destination rank(s) {dup_d}: "
            "mismatched send/recv pairing — one recv gets two sends",
            symbol=source))
    if (not bad_rank and not dup_s and not dup_d
            and len(perm) == axis_size and axis_size > 1):
        nxt = dict(perm)
        if set(nxt) == set(range(axis_size)):
            seen, r = set(), 0
            while r not in seen:
                seen.add(r)
                r = nxt[r]
            if len(seen) != axis_size:
                n_cycles = _count_cycles(nxt)
                findings.append(Finding(
                    "RLT303",
                    f"full ppermute permutation over {axis_size} ranks "
                    f"decomposes into {n_cycles} disjoint cycles, not "
                    "one ring — each sub-ring waits forever on data the "
                    "others hold (use ops.ring_attention.ring_perm / "
                    "ops.pipeline.pipeline_perm for the canonical "
                    "schedules)", symbol=source))
    return findings


def _count_cycles(nxt: Dict[int, int]) -> int:
    left, n = set(nxt), 0
    while left:
        n += 1
        r = next(iter(left))
        while r in left:
            left.remove(r)
            r = nxt[r]
    return n


# --------------------------------------------------------------------------
# the jaxpr auditor
# --------------------------------------------------------------------------


class _StepAuditor:
    """Single-use: walk one step jaxpr, accumulate events/findings and a
    liveness peak. Per-device byte accounting throughout: a var's bytes
    are its aval bytes divided by the product of its sharded axis sizes
    (inside shard_map the aval already IS per-shard)."""

    def __init__(self, mesh_sizes: Mapping[str, int], topo: Topology):
        self.sizes = {ax: s for ax, s in mesh_sizes.items() if s > 1}
        #: FULL axis sizes (incl. trivial) — the slice-layout math needs
        #: the whole mixed radix, not just the live axes
        self.full_sizes = dict(mesh_sizes)
        self.topo = topo
        self._dcn_span_cache: Dict[Tuple[str, ...], int] = {}
        #: shape -> (spec, path) for param/opt leaves AND their
        #: leading-dim-stripped (scan-stacked) suffixes: the ZeRO
        #: reduce_scatter matcher (`seed_state` fills it)
        self.param_shapes: Dict[Tuple, Tuple[Spec, str]] = {}
        self._events: Dict[Tuple, CollectiveEvent] = {}
        self._findings: Dict[Tuple, Finding] = {}
        self._quiet = 0          # scan-fixpoint passes record nothing
        self._unbounded = 0      # inside while bodies
        #: scanned bodies met on the recording walk, and the ids of
        #: those the walk is inside of (`CollectiveEvent.scope`)
        self._n_scopes = 0
        self._scope_stack: List[int] = []
        #: per-device dot_general FLOPs a step inside scanned bodies
        #: (pallas kernels and elementwise work are not counted): the
        #: compute side of `report`'s predicted step floor
        self.scan_flops = 0.0
        #: every pallas kernel the walk met, by its kernel-fn identity
        #: (`jaxpr.pallas_kernel_ident`) — surfaced as
        #: `TraceReport.pallas_kernels`, where the serve audit/smoke
        #: read "which attention path does this step run": the same
        #: fingerprint-over-reimplementation discipline as the flash
        #: remat tag
        self.pallas_kernels: List[str] = []
        #: per-dtype byte breakdown of the LAST sub-jaxpr walk, set by
        #: _seed_and_walk and read by the enclosing walk() when it
        #: snapshots a new liveness peak — the plumbing that lets the
        #: precision ledger keep `sum(peak_by) == peak` exact through
        #: nested scan/pjit/cond scratch
        self._sub_by: Dict[str, int] = {}
        #: `TraceReport.lost_specs`; kernel interiors (VMEM, under a
        #: `pallas_call`) are not counted
        self.lost_specs: Dict[str, int] = {}
        self._in_kernel = 0

    # ---- bookkeeping ----------------------------------------------------

    def _canon(self, spec: Spec) -> Spec:
        """Drop mesh axes of size 1: they shard nothing and would only
        manufacture phantom layout conflicts."""
        if spec is None:
            return None
        return tuple(frozenset(ax for ax in s if ax in self.sizes)
                     for s in spec)

    def seed_state(self, named: Mapping[str, Any], pspecs: Sequence,
                   prefix: str) -> List[_VarInfo]:
        """Seeds for a tree of state leaves (params, optimizer state)
        laid out as ``pspecs``, in flatten order; each leaf's shape, and
        its scan-stacked suffix, also enters `param_shapes` (the ZeRO
        reduce-scatter matcher)."""
        seeds = []
        for (path, leaf), pspec in zip(named.items(), pspecs, strict=True):
            shape = tuple(getattr(leaf, "shape", ()))
            spec = self._canon(_spec_of_partition_spec(pspec, len(shape)))
            seeds.append(_VarInfo(spec, param=True, path=f"{prefix}/{path}"))
            self.param_shapes.setdefault(shape, (spec, f"{prefix}/{path}"))
            if len(shape) >= 2:
                self.param_shapes.setdefault(
                    shape[1:], (spec[1:], f"{prefix}/{path}"))
        return seeds

    def _div(self, spec: Spec) -> int:
        if spec is None:
            return 1
        return math.prod(self.sizes.get(ax, 1) for ax in _axes_in(spec))

    def _aval_bytes(self, aval, spec: Spec = None) -> int:
        shape = getattr(aval, "shape", None)
        dtype = getattr(aval, "dtype", None)
        if shape is None or dtype is None:
            return 0
        return int(math.prod(shape) or 1) * dtype.itemsize // self._div(spec)

    def _dcn_span(self, axes: Sequence[str]) -> int:
        """Slices the collective group over ``axes`` spans on this
        topology's slice-major layout (1 on single-slice). Also 1 when
        the mesh does not cover the whole deployment (an n_devices
        override smaller than the topology): a sub-deployment mesh
        packs into the fewest slices, so charging cross-slice traffic
        from a tiling the hardware never forces would fabricate DCN
        bytes and RLT306 flags."""
        if self.topo.n_slices <= 1:
            return 1
        if math.prod(self.full_sizes.values()) != self.topo.n_devices:
            return 1
        key = tuple(sorted(axes))
        span = self._dcn_span_cache.get(key)
        if span is None:
            from ray_lightning_tpu.parallel.plan import group_dcn_span

            span = group_dcn_span(key, self.full_sizes,
                                  self.topo.n_slices)
            self._dcn_span_cache[key] = span
        return span

    def record(self, kind: str, payload: int, axes: Sequence[str],
               mult: int, *, implicit: bool, source: str,
               param_path: Optional[str] = None,
               dtype: Optional[str] = None) -> None:
        if self._quiet or not axes:
            return
        group = {ax: self.sizes.get(ax, 1) for ax in axes}
        if math.prod(group.values()) <= 1:
            return
        cost = collective_cost(kind if kind in (
            "psum", "all_gather", "reduce_scatter", "all_to_all",
            "ppermute") else "psum", payload, group, self.topo,
            dcn_group=self._dcn_span(axes))
        scope = self._scope_stack[-1] if self._scope_stack else None
        key = (kind, tuple(sorted(axes)), payload, source, implicit,
               bool(self._unbounded), scope, dtype)
        ev = self._events.get(key)
        if ev is None:
            self._events[key] = CollectiveEvent(
                kind=kind, axes=tuple(sorted(axes)), payload_bytes=payload,
                count=mult, wire_bytes=cost.wire_bytes * mult,
                time_us=cost.time_us * mult, implicit=implicit,
                source=source, param_path=param_path,
                unbounded=bool(self._unbounded), scope=scope,
                dcn_bytes=cost.dcn_bytes * mult, dtype=dtype)
        else:
            ev.count += mult
            ev.wire_bytes += cost.wire_bytes * mult
            ev.time_us += cost.time_us * mult
            ev.dcn_bytes += cost.dcn_bytes * mult

    def flag(self, rule: str, message: str, *, source: str,
             param_path: Optional[str] = None) -> None:
        if self._quiet:
            return
        key = (rule, source, message[:100])
        if key not in self._findings:
            self._findings[key] = Finding(
                rule, f"{message} [at {source}]",
                symbol=param_path or source)

    def incomplete(self, where: str, why: str) -> None:
        """RLT310: the walk could not model the equation at ``where``
        (a sub-program no rule entered, a handler that raised). Recorded
        on every pass: what a scan's fixpoint pass could not enter, the
        recording pass cannot either."""
        self._findings.setdefault(("RLT310", where, why[:100]), Finding(
            "RLT310", f"{why} [at {where}]", symbol=where))

    @property
    def events(self) -> List[CollectiveEvent]:
        return list(self._events.values())

    @property
    def findings(self) -> List[Finding]:
        return list(self._findings.values())

    # ---- env helpers ----------------------------------------------------

    def _info(self, v, env) -> _VarInfo:
        if type(v).__name__ == "Literal" or not hasattr(v, "count"):
            ndim = len(getattr(getattr(v, "aval", None), "shape", ()))
            return _VarInfo(_repl(ndim), param=True)
        got = env.get(v)
        if got is None:
            return _VarInfo(None, param=False)
        return got

    # ---- conflict resolution --------------------------------------------

    def _gather(self, info: _VarInfo, aval, axes: FrozenSet[str],
                mult: int, source: str, *, reason: str) -> None:
        """Model GSPMD's resolution of a layout conflict: all-gather the
        operand along ``axes``. A parameter-derived operand is the
        PLANNED FSDP/ZeRO weight gather — scheduled, not flagged; an
        activation gather is traffic the plan never asked for: RLT301."""
        if not axes:
            return
        if info.param:
            # loop-invariant param gathers are hoisted by XLA
            mult = min(mult, max(1, info.born_mult))
        remaining = (tuple(s - axes for s in info.spec)
                     if info.spec is not None else None)
        payload = self._aval_bytes(aval, remaining)
        self.record("all_gather", payload, sorted(axes), mult,
                    implicit=True, source=source, param_path=info.path,
                    dtype=_aval_dtype(aval))
        if not info.param:
            self.flag(
                "RLT301",
                f"{reason}: XLA must all-gather an activation "
                f"({_fmt_bytes(payload).strip()} over "
                f"{'x'.join(sorted(axes))}) that the plan never asked "
                "for — a dropped output spec upstream",
                source=source, param_path=info.path)

    def _merge(self, infos: Sequence[_VarInfo], avals, out_aval, mult: int,
               source: str) -> _VarInfo:
        """Elementwise merge. STRICT about ignorance: if any same-rank
        operand's sharding is unknown, the result is unknown — an
        invented spec would cascade into invented collectives. Among
        known operands, the first ACTIVATION operand's layout wins
        (activations stay put; parameters move — ZeRO); other operands'
        conflicting axes are gathered, flagged only when the gathered
        side is itself an activation."""
        out_shape = tuple(getattr(out_aval, "shape", ()))
        out_size = int(math.prod(out_shape) or 1)
        # only FULL-SIZE operands constrain the output layout: an
        # expanded broadcast or a rank-padded norm scale is small and
        # cheap to re-layout, so (like GSPMD's most-tiles heuristic) it
        # never dictates where a 16 GiB tensor lives
        cands = [
            (i, inf) for i, inf in enumerate(infos)
            if len(getattr(avals[i], "shape", ())) == len(out_shape)
            and int(math.prod(getattr(avals[i], "shape", ()) or (1,)))
            == out_size]
        if not cands:
            # pure broadcast combination (outer products, rank-padded
            # scales): small operands don't constrain the layout; if all
            # are known the result is simply replicated
            if all(i.spec is not None for i in infos):
                return _VarInfo(_repl(len(out_shape)),
                                param=all(i.param for i in infos))
            return _VarInfo(None, param=all(i.param for i in infos))
        if any(inf.spec is None or len(inf.spec) != len(out_shape)
               for _, inf in cands):
            return _VarInfo(None, param=all(i.param for i in infos))
        # most tiles win: the most-sharded operand keeps its layout,
        # everyone else reshards toward it
        ref_idx, ref = max(
            cands, key=lambda c: sum(1 for s in c[1].spec if s))
        acc: List[FrozenSet[str]] = list(ref.spec)
        placed: Dict[str, int] = {ax: d for d, s in enumerate(acc)
                                  for ax in s}
        for idx, inf in cands:
            if idx == ref_idx:
                continue
            if (inf.param != ref.param and inf.spec != tuple(acc)
                    and _axes_in(inf.spec) == frozenset(placed)):
                # param storage meeting its own gradient/update with the
                # SAME axes on different dims: XLA reduce-scatters grads
                # straight into the param's layout, so the orientation
                # difference is a tracking artifact (square dgrads match
                # transposed), not a reshard — unify to the param side
                win = inf.spec if inf.param else tuple(acc)
                acc = list(win)
                placed = {ax: d for d, s in enumerate(acc) for ax in s}
                continue
            lose: FrozenSet[str] = frozenset()
            for d, s in enumerate(inf.spec):
                for ax in s:
                    if placed.get(ax) == d:
                        continue
                    if ax in placed or acc[d]:
                        lose |= {ax}            # conflicts with ref layout
                    else:
                        acc[d] = acc[d] | {ax}  # free refinement
                        placed[ax] = d
            if lose:
                self._gather(inf, avals[idx], lose, mult, source,
                             reason="operand layout conflicts with the "
                                    "other operand's sharding")
        spec = tuple(acc)
        # no path propagation through merges: a leaf path on a merged
        # value would mis-attribute downstream events to that leaf
        return _VarInfo(spec, param=all(i.param for i in infos))

    def _param_match(self, shape: Tuple[int, ...],
                     partial: FrozenSet[str]):
        """Find the param/opt leaf a partial-summed value is the gradient
        of: exact shape, or (2-D) the transposed shape — XLA emits
        ``x^T @ dy`` dgrads in whichever orientation fuses best. Returns
        (spec, path) or None; the spec's axes must be reducible (subset
        of ``partial``) for the ZeRO reduce_scatter model to apply."""
        hit = self.param_shapes.get(shape)
        if hit is None and len(shape) == 2:
            rev = self.param_shapes.get(shape[::-1])
            if rev is not None and rev[0] is not None:
                hit = (rev[0][::-1], rev[1])
        if hit is None:
            return None
        mspec, mpath = hit
        if (mspec is not None and len(mspec) == len(shape)
                and _axes_in(mspec) and _axes_in(mspec) <= partial):
            return mspec, mpath
        return None

    def _resolve_partial(self, out_aval, out_spec: List[FrozenSet[str]],
                         partial: FrozenSet[str], mult: int,
                         source: str, path: Optional[str],
                         ) -> Spec:
        """A value is partial-summed over ``partial``: GSPMD finishes it
        with reduce_scatter when the result is parameter-shaped (its grad
        lands sharded like the param — ZeRO) and all-reduce otherwise.
        Returns the finished spec."""
        partial = partial - frozenset(
            ax for s in out_spec for ax in s)  # cannot both shard & reduce
        if not partial:
            return tuple(out_spec)
        shape = tuple(getattr(out_aval, "shape", ()))
        match = self._param_match(shape, partial)
        if match is not None:
            mspec, mpath = match
            payload = self._aval_bytes(out_aval, tuple(out_spec))
            self.record("reduce_scatter", payload, sorted(partial),
                        mult, implicit=True, source=source,
                        param_path=mpath or path,
                        dtype=_aval_dtype(out_aval))
            return tuple(s | m for s, m in zip(out_spec, mspec))
        payload = self._aval_bytes(out_aval, tuple(out_spec))
        self.record("psum", payload, sorted(partial), mult,
                    implicit=True, source=source, param_path=path,
                    dtype=_aval_dtype(out_aval))
        return tuple(out_spec)

    # ---- the walk -------------------------------------------------------

    def walk(self, jaxpr, env: Dict, mult: int,
             manual: bool) -> Tuple[int, Dict[str, int]]:
        """Propagate shardings through ``jaxpr`` (env maps Var ->
        _VarInfo; invars must be seeded), record events/findings, and
        return ``(peak, peak_by)``: the liveness peak in per-device
        bytes plus its per-dtype itemization (the precision ledger's
        raw material — ``sum(peak_by.values()) == peak`` by
        construction, with nested sub-jaxpr scratch folded in through
        ``self._sub_by``)."""
        eqns = jaxpr.eqns
        last: Dict[Any, int] = {}
        for i, eqn in enumerate(eqns):
            for v in eqn.invars:
                if hasattr(v, "count"):
                    last[v] = i
        for v in jaxpr.outvars:
            if hasattr(v, "count"):
                last[v] = len(eqns)

        def vb(v) -> int:
            if not hasattr(v, "count") or type(v).__name__ == "DropVar":
                return 0
            info = env.get(v)
            return self._aval_bytes(v.aval, info.spec if info else None)

        def vdt(v) -> str:
            return _aval_dtype(getattr(v, "aval", None)) or "opaque"

        live_by: Dict[str, int] = {}
        for v in {*jaxpr.invars, *jaxpr.constvars}:
            b = vb(v)
            if b:
                live_by[vdt(v)] = live_by.get(vdt(v), 0) + b
        live = sum(live_by.values())
        peak = live
        peak_by = dict(live_by)
        for i, eqn in enumerate(eqns):
            self._sub_by = {}
            try:
                sub_peak = self._process(eqn, env, mult, manual)
            except Exception as exc:  # noqa: BLE001 — the audit's promise
                # is to finish: what a handler could not model is an
                # RLT310 error on the report and flows on as unknown
                self.incomplete(eqn.primitive.name,
                                f"{type(exc).__name__}: {exc}")
                for v in eqn.outvars:
                    env[v] = _VarInfo(None)
                sub_peak = 0
                self._sub_by = {}
            for v in eqn.outvars:  # values defined HERE are born at the
                info = env.get(v)  # current loop multiplier
                if info is not None:
                    info.born_mult = mult
            out_b = sum(vb(v) for v in eqn.outvars)
            if live + (sub_peak or 0) + out_b > peak:
                peak = live + (sub_peak or 0) + out_b
                peak_by = dict(live_by)
                for v in eqn.outvars:
                    b = vb(v)
                    if b:
                        peak_by[vdt(v)] = peak_by.get(vdt(v), 0) + b
                for dt, b in self._sub_by.items():
                    if b:
                        peak_by[dt] = peak_by.get(dt, 0) + b
            live += out_b
            for v in eqn.outvars:
                b = vb(v)
                if b:
                    live_by[vdt(v)] = live_by.get(vdt(v), 0) + b
            for v in {v for v in eqn.invars if hasattr(v, "count")}:
                if last.get(v) == i:
                    b = vb(v)
                    if b:
                        live -= b
                        live_by[vdt(v)] = live_by.get(vdt(v), 0) - b
        return peak, peak_by

    def _seed_and_walk(self, closed_or_open, outer_invars, env, mult,
                       manual) -> Tuple[int, List[_VarInfo]]:
        """Map outer invar infos onto a sub-jaxpr, walk it, return
        (peak, outvar infos). The inner walk's per-dtype breakdown is
        left on ``self._sub_by`` for the enclosing walk's snapshot."""
        inner = getattr(closed_or_open, "jaxpr", closed_or_open)
        sub_env: Dict = {}
        for iv, ov in zip(inner.invars, outer_invars):
            sub_env[iv] = (ov if isinstance(ov, _VarInfo)
                           else self._info(ov, env))
        for cv in inner.constvars:
            sub_env[cv] = _VarInfo(
                _repl(len(getattr(cv.aval, "shape", ()))), param=True)
        sub_peak, sub_by = self.walk(inner, sub_env, mult, manual)
        self._sub_by = sub_by
        outs = [self._info(v, sub_env) for v in inner.outvars]
        return sub_peak, outs

    # ---- per-primitive handlers -----------------------------------------

    def _process(self, eqn, env, mult, manual) -> int:
        name = eqn.primitive.name
        infos = [self._info(v, env) for v in eqn.invars]
        avals = [getattr(v, "aval", None) for v in eqn.invars]
        out = eqn.outvars
        src = source_of(eqn)
        sub_peak = 0

        def set_all(info_list):
            for v, info in zip(out, info_list):
                env[v] = info

        def set_unknown():
            param = all(i.param for i in infos)
            # sound fallback for ANY primitive: replicated in ->
            # replicated out (no mesh axis can appear from nowhere) —
            # keeps pure-const chains (rope tables, masks) propagating
            # through primitives the walker has no rule for
            if infos and all(i.spec is not None and not _axes_in(i.spec)
                             for i in infos):
                set_all([_VarInfo(
                    _repl(len(getattr(v.aval, "shape", ()))), param=param)
                    for v in out])
            else:
                set_all([_VarInfo(None, param=param) for _ in out])

        if name == "optimization_barrier":
            # positional identity: each output mirrors ITS input (the
            # generic passthrough would smear the first operand's spec
            # over every output)
            set_all([dataclasses.replace(i) for i in infos[:len(out)]])
        elif name in _PASSTHROUGH:
            base = next((i for i, a in zip(infos, avals)
                         if a is not None and i.spec is not None
                         and len(i.spec) == len(getattr(
                             out[0].aval, "shape", ()))), None)
            info = base or _VarInfo(None, param=all(i.param for i in infos))
            set_all([dataclasses.replace(info) for _ in out])
        elif name in _ELEMENTWISE:
            merged = self._merge(infos, avals, out[0].aval, mult, src)
            set_all([dataclasses.replace(merged) for _ in out])
        elif name == "dot_general":
            set_all([self._dot_general(eqn, infos, avals, mult, src)])
        elif name in _REDUCE:
            set_all([self._reduce(eqn, infos, avals, mult, src)
                     for _ in out])
        elif name == "transpose":
            perm = eqn.params["permutation"]
            spec = infos[0].spec
            new = (tuple(spec[p] for p in perm)
                   if spec is not None else None)
            set_all([dataclasses.replace(infos[0], spec=new)])
        elif name == "broadcast_in_dim":
            set_all([self._broadcast(eqn, infos[0])])
        elif name == "reshape":
            set_all([self._reshape(eqn, infos[0], avals[0])])
        elif name == "squeeze":
            dims = set(eqn.params["dimensions"])
            spec = infos[0].spec
            new = (tuple(s for d, s in enumerate(spec) if d not in dims)
                   if spec is not None else None)
            set_all([dataclasses.replace(infos[0], spec=new)])
        elif name == "pad":
            spec = infos[0].spec
            if spec is not None:
                cfg = eqn.params["padding_config"]
                new = tuple(s if lo == hi == interior == 0 else frozenset()
                            for s, (lo, hi, interior) in zip(spec, cfg))
                set_all([dataclasses.replace(infos[0], spec=new)])
            else:
                set_unknown()
        elif name == "slice":
            set_all([self._slice(eqn, infos[0], avals[0])])
        elif name == "split":
            # jnp.split: every piece keeps the operand's layout but on
            # the split dim (the `slice` rule, for all pieces at once)
            spec, ax = infos[0].spec, eqn.params["axis"]
            new = (tuple(frozenset() if d == ax else s
                         for d, s in enumerate(spec))
                   if spec is not None else None)
            set_all([dataclasses.replace(infos[0], spec=new) for _ in out])
        elif name in ("dynamic_slice", "dynamic_update_slice"):
            spec = infos[0].spec
            if spec is not None:
                oshape = getattr(out[0].aval, "shape", ())
                ishape = getattr(avals[0], "shape", ())
                new = tuple(
                    s if o == i else frozenset()
                    for s, o, i in zip(spec, oshape, ishape))
                set_all([_VarInfo(new, param=all(x.param for x in infos),
                                  path=infos[0].path)])
            else:
                set_unknown()
        elif name == "concatenate":
            cd = eqn.params["dimension"]
            ondim = len(getattr(out[0].aval, "shape", ()))
            if any(i.spec is None or len(i.spec) != ondim
                   for i in infos):
                set_unknown()
            else:
                # agreement-only: keep axes every piece shards the same
                # way; the concatenated dim itself ends up unsharded
                spec = tuple(
                    frozenset() if d == cd else frozenset.intersection(
                        *(i.spec[d] for i in infos))
                    for d in range(ondim))
                # pieces of one leaf keep that leaf's name
                paths = {i.path for i in infos}
                set_all([_VarInfo(spec, param=all(i.param for i in infos),
                                  path=paths.pop() if len(paths) == 1
                                  else None)])
        elif name == "conv_general_dilated":
            # batch passthrough only: the output batch dim keeps the
            # input's sharding; kernel/feature placement and conv-dgrad
            # reductions are not modeled (documented undercount)
            dn = eqn.params["dimension_numbers"]
            lhs = infos[0]
            ondim = len(getattr(out[0].aval, "shape", ()))
            if lhs.spec is None:
                set_unknown()
            else:
                spec = [frozenset()] * ondim
                spec[dn.out_spec[0]] = lhs.spec[dn.lhs_spec[0]]
                set_all([_VarInfo(tuple(spec))])
        elif name == "pallas_call":
            # every kernel in ops/ (flash, rmsnorm, paged_attention) is
            # LOCAL: no cross-device semantics, and each output has the
            # layout of the same-shaped input (flash out = q's sharding,
            # norm out = x's). Unmatched outputs stay unknown. The walk
            # still RECURSES into the kernel jaxpr — for recognition
            # (which kernel runs: the serve audit reads
            # `pallas_kernels`) and so a collective hiding inside a
            # future kernel is seen — but its internal
            # buffers are VMEM, not HBM: they contribute NOTHING to the
            # liveness peak (sub_peak stays 0).
            if not self._quiet:
                self.pallas_kernels.append(pallas_kernel_ident(eqn))
            self._in_kernel += 1
            try:
                self._seed_and_walk(eqn.params["jaxpr"], infos, env, mult,
                                    manual)
            finally:
                self._in_kernel -= 1
            self._sub_by = {}  # VMEM: nothing for the HBM snapshot
            set_all([self._like_shaped_input(v, infos, avals)
                     for v in out])
        elif name == "gather":
            set_all([self._gather_prim(eqn, infos, avals, mult, src)])
        elif name in ("scatter-add", "scatter_add"):
            set_all([self._scatter_add(eqn, infos, avals, mult, src)])
        elif name == "scatter":
            set_all([self._scatter_overwrite(eqn, infos, avals, mult,
                                             src)])
        elif name in _REPLICATED_SOURCES:
            set_all([_VarInfo(_repl(len(getattr(v.aval, "shape", ()))),
                              param=True) for v in out])
        elif name in ("sharding_constraint", "reshard"):
            set_all([self._sharding_constraint(eqn, infos[0], avals[0],
                                               mult, src)])
        elif name == "remat_opt":
            # custom-vjp fwd wrapper (jax >= 0.4.3x): fwd_jaxpr computes
            # primal outputs AND residuals, possibly interleaved — match
            # eqn outvars to inner outvars by shape
            closed = eqn.params["fwd_jaxpr"]
            sub_peak, outs = self._seed_and_walk(
                closed, infos, env, mult, manual)
            by_shape: Dict[Tuple, List[_VarInfo]] = {}
            for ov, info in zip(closed.jaxpr.outvars, outs):
                by_shape.setdefault(
                    tuple(getattr(ov.aval, "shape", ())), []).append(info)
            for v in out:
                lst = by_shape.get(tuple(getattr(v.aval, "shape", ())))
                env[v] = lst.pop(0) if lst else _VarInfo(None)
        elif name == "scan":
            sub_peak = self._scan(eqn, infos, env, mult, manual)
        elif name == "while":
            sub_peak = self._while(eqn, infos, env, mult, manual)
        elif name == "cond":
            sub_peak = self._cond(eqn, infos, env, mult, manual, src)
        elif name == "shard_map":
            sub_peak = self._shard_map(eqn, infos, env, mult)
        elif name in _COLLECTIVES:
            self._collective(eqn, infos, avals, mult, manual, src)
            # manual collectives keep the local layout
            set_all([dataclasses.replace(i) if i.spec is not None
                     else _VarInfo(None) for i in infos[:len(out)]]
                    or [_VarInfo(None) for _ in out])
        elif name == "axis_index":
            set_all([_VarInfo(_repl(0), param=True) for _ in out])
        elif (body := call_body(eqn)) is not None:
            # a plain call, whatever jax names it (jit, remat2,
            # custom_jvp_call, custom_vjp_call, closed_call, ...)
            sub_peak, outs = self._seed_and_walk(body, infos, env, mult,
                                                 manual)
            set_all(outs)
        else:
            subs = sub_jaxprs(eqn)
            if subs:
                self.incomplete(src, "the walk has no rule that enters "
                                f"this equation's sub-program(s) "
                                f"{[k for k, _ in subs]}")
            if (not self._quiet and not self._in_kernel and any(
                    i.spec is not None and _axes_in(i.spec) for i in infos)):
                self.lost_specs[name] = self.lost_specs.get(name, 0) + 1
            set_unknown()
        return sub_peak

    def _like_shaped_input(self, outvar, infos, avals) -> _VarInfo:
        shape = tuple(getattr(getattr(outvar, "aval", None), "shape", ()))
        for inf, av in zip(infos, avals):
            if (inf.spec is not None
                    and tuple(getattr(av, "shape", ())) == shape):
                return dataclasses.replace(inf)
        return _VarInfo(None, param=all(i.param for i in infos))

    def _dot_general(self, eqn, infos, avals, mult, src) -> _VarInfo:
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        li, ri = infos[0], infos[1]
        la, ra = avals[0], avals[1]
        if li.spec is None or ri.spec is None:
            return _VarInfo(None, param=li.param and ri.param)
        lspec, rspec = list(li.spec), list(ri.spec)
        # ZeRO-3 semantics, keyed on the framework's axis vocabulary
        # (parallel/mesh.py): the `fsdp` axis shards parameter STORAGE,
        # not parameter USE — a param operand entering a matmul is
        # gathered over its fsdp axes (forward and backward alike) and
        # contributes no fsdp placement to the output. Without this, a
        # transposed backward use would push the weight's fsdp axis into
        # a replicated cotangent and manufacture activation conflicts
        # downstream that the real GSPMD program never has.
        for side, aval_ in ((li, la), (ri, ra)):
            if not side.param or side.spec is None:
                continue
            zero_axes = _axes_in(side.spec) & {"fsdp"}
            if zero_axes:
                self._gather(side, aval_, zero_axes, mult, src,
                             reason="ZeRO weight gather at use")
                stripped = tuple(s - zero_axes for s in side.spec)
                if side is li:
                    lspec = list(stripped)
                else:
                    rspec = list(stripped)
        partial: FrozenSet[str] = frozenset()
        out_full = self._aval_bytes(eqn.outvars[0].aval, None)
        for ld, rd in zip(lc, rc):
            A, B = lspec[ld], rspec[rd]
            partial |= A & B
            only_a, only_b = A - B, B - A
            # one side sharded on the contracting dim, other replicated
            # there: GSPMD picks the cheaper of (a) all-gather the
            # sharded operand then matmul locally (the ZeRO weight
            # gather) and (b) slice the replicated side, matmul the
            # shard, all-reduce the output. (b) wins only when the
            # output is small relative to the operand (dgrads) — for a
            # weight feeding a huge activation, (a) does.
            if (only_a and not B) or (only_b and not A):
                oinfo, oaval, axes = ((li, la, only_a) if only_a
                                      else (ri, ra, only_b))
                gather_cost = self._aval_bytes(oaval, None)
                if gather_cost < 2 * out_full:
                    self._gather(oinfo, oaval, axes, mult, src,
                                 reason="contracting dim sharded on one "
                                        "side only")
                else:
                    partial |= axes
            elif only_a or only_b:
                # sharded on DIFFERENT axes: a real reshard. Gather the
                # param side if there is one (FSDP), else the rhs.
                loser, laval, axes = (
                    (li, la, only_a) if li.param and not ri.param
                    else (ri, ra, only_b))
                self._gather(loser, laval, axes, mult, src,
                             reason="contracting dims sharded on "
                                    "different mesh axes")
                partial |= (only_b if loser is li else only_a)
        l_free = [d for d in range(len(lspec)) if d not in lc + lb]
        r_free = [d for d in range(len(rspec)) if d not in rc + rb]
        out_spec: List[FrozenSet[str]] = []
        out_owner: List[_VarInfo] = []
        for ld, rd in zip(lb, rb):
            A, B = lspec[ld], rspec[rd]
            if A == B:
                out_spec.append(A)
                out_owner.append(li if not li.param else ri)
            elif not A or not B:
                out_spec.append(A | B)
                out_owner.append(li if A else ri)
            else:
                # batch dims sharded on different axes: same resolution
                # as elementwise — activations keep their layout
                keep, lose, laval = ((li, ri, ra) if not li.param
                                     else (ri, li, la))
                ks = A if keep is li else B
                ls = B if keep is li else A
                self._gather(lose, laval, ls - ks, mult, src,
                             reason="batch dims sharded on different "
                                    "mesh axes")
                out_spec.append(ks)
                out_owner.append(keep)
        for d in l_free:
            out_spec.append(lspec[d])
            out_owner.append(li)
        for d in r_free:
            out_spec.append(rspec[d])
            out_owner.append(ri)
        # one mesh axis claimed by two output dims — the classic FSDP
        # batch-vs-weight collision: the activation side keeps its
        # layout, the param side is gathered (that IS the planned ZeRO
        # weight gather; an activation loser is flagged by _gather)
        seen: Dict[str, int] = {}
        for d, s in enumerate(out_spec):
            for ax in sorted(s):
                if ax in partial:
                    out_spec[d] = out_spec[d] - {ax}
                    continue
                if ax not in seen:
                    seen[ax] = d
                    continue
                prev = seen[ax]
                a_own, b_own = out_owner[prev], out_owner[d]
                if a_own.param and not b_own.param:
                    lose_d, loser = prev, a_own
                else:
                    lose_d, loser = d, b_own
                self._gather(loser, la if loser is li else ra,
                             frozenset((ax,)), mult, src,
                             reason="one mesh axis cannot shard two "
                                    "output dims")
                out_spec[lose_d] = out_spec[lose_d] - {ax}
                if lose_d == prev:
                    seen[ax] = d
        self._charge_flops(eqn, avals, out_spec, partial, mult)
        spec = self._resolve_partial(
            eqn.outvars[0].aval, out_spec, partial, mult, src,
            li.path if li.param else ri.path if ri.param else None)
        return _VarInfo(spec, param=li.param and ri.param)

    def _charge_flops(self, eqn, avals, out_spec, partial, mult) -> None:
        """Add this dot_general's per-device FLOPs, every trip of the
        scanned bodies around it, to `scan_flops`. Per-device: the full
        contraction's 2·B·M·N·K divided by the product of mesh axes
        sharding the output or reduced over (how SPMD splits the work).
        Counted only on the recording walk."""
        if self._quiet or not self._scope_stack:
            return
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        lshape = tuple(getattr(avals[0], "shape", ()))
        rshape = tuple(getattr(avals[1], "shape", ()))
        batch = math.prod(lshape[d] for d in lb) or 1
        k = math.prod(lshape[d] for d in lc) or 1
        m = math.prod(lshape[d] for d in range(len(lshape))
                      if d not in tuple(lc) + tuple(lb)) or 1
        n = math.prod(rshape[d] for d in range(len(rshape))
                      if d not in tuple(rc) + tuple(rb)) or 1
        axes = set(partial)
        for s in out_spec:
            axes |= s
        div = math.prod(self.sizes.get(ax, 1) for ax in axes) or 1
        self.scan_flops += 2.0 * batch * m * n * k * mult / div

    def _gather_prim(self, eqn, infos, avals, mult, src) -> _VarInfo:
        """lax.gather (embedding lookups, take_along_axis): output batch
        dims inherit the INDICES' sharding, offset dims inherit the
        operand's full-slice dims. A sharded collapsed/sliced operand dim
        (vocab-sharded embedding table, vocab-sharded logits tile) is
        modeled the way GSPMD lowers it — mask locally, psum the output
        over the lost axes — NOT as an operand all-gather."""
        operand, indices = infos[0], infos[1]
        dn = eqn.params["dimension_numbers"]
        slice_sizes = eqn.params.get("slice_sizes", ())
        out_aval = eqn.outvars[0].aval
        out_ndim = len(getattr(out_aval, "shape", ()))
        op_shape = tuple(getattr(avals[0], "shape", ()))
        if operand.spec is None or indices.spec is None:
            return _VarInfo(None, param=operand.param and indices.param,
                            path=operand.path)
        offset = set(dn.offset_dims)
        collapsed = set(dn.collapsed_slice_dims)
        out_spec: List[FrozenSet[str]] = [frozenset()] * out_ndim
        batch_out = [d for d in range(out_ndim) if d not in offset]
        for i, d in enumerate(batch_out):
            if i < len(indices.spec):
                out_spec[d] = indices.spec[i]
        lost: FrozenSet[str] = frozenset()
        op_kept = [d for d in range(len(op_shape)) if d not in collapsed]
        for od, opd in zip(sorted(offset), op_kept):
            full = (opd < len(slice_sizes)
                    and slice_sizes[opd] == op_shape[opd])
            if full:
                s = operand.spec[opd] - frozenset(
                    ax for ss in out_spec for ax in ss)
                out_spec[od] = s
            else:
                lost |= operand.spec[opd]
        for d in collapsed:
            lost |= operand.spec[d]
        lost -= frozenset(ax for s in out_spec for ax in s)
        if lost:
            payload = self._aval_bytes(out_aval, tuple(out_spec))
            self.record("psum", payload, sorted(lost), mult,
                        implicit=True, source=src,
                        param_path=operand.path)
        return _VarInfo(tuple(out_spec),
                        param=operand.param and indices.param,
                        path=operand.path or indices.path)

    def _reduce(self, eqn, infos, avals, mult, src) -> _VarInfo:
        axes_param = eqn.params.get("axes", ())
        info = infos[0]
        if info.spec is None:
            return _VarInfo(None, param=all(i.param for i in infos))
        reduced = frozenset(
            ax for d in axes_param for ax in info.spec[d])
        out_spec = [s for d, s in enumerate(info.spec)
                    if d not in set(axes_param)]
        spec = tuple(out_spec)
        if reduced and eqn.primitive.name in _REDUCE_COMM:
            spec = self._resolve_partial(
                eqn.outvars[0].aval, out_spec, reduced, mult, src,
                info.path)
        return _VarInfo(spec, param=all(i.param for i in infos),
                        path=info.path)

    def _scatter_add(self, eqn, infos, avals, mult, src) -> _VarInfo:
        # operand, indices, updates. The canonical site: an embedding
        # gradient — updates derive from dp-sharded activations, the
        # result is param-shaped and partial over those axes.
        op, _, upd = infos[0], infos[1], infos[2]
        partial = _axes_in(upd.spec) - _axes_in(op.spec)
        base = list(op.spec) if op.spec is not None else [
            frozenset() for _ in getattr(eqn.outvars[0].aval, "shape", ())]
        spec = tuple(base)
        if partial:
            spec = self._resolve_partial(
                eqn.outvars[0].aval, base, partial, mult, src, op.path)
        return _VarInfo(spec, param=op.param and upd.param, path=op.path)

    def _scatter_overwrite(self, eqn, infos, avals, mult, src) -> _VarInfo:
        # plain functional scatter (`x.at[idx].set(v)` — the serving
        # engine's per-slot paged-KV writes lower here once vmapped over
        # slots): GSPMD keeps the OPERAND's layout and reshards the
        # (small) updates to match, so the result inherits the operand
        # spec verbatim. Unlike scatter-add there is no partial sum to
        # resolve — an overwrite never manufactures a reduction.
        op, _, upd = infos[0], infos[1], infos[2]
        if op.spec is None:
            return _VarInfo(None, param=op.param and upd.param,
                            path=op.path)
        return _VarInfo(tuple(op.spec), param=op.param and upd.param,
                        path=op.path)

    def _broadcast(self, eqn, info) -> _VarInfo:
        shape = eqn.params["shape"]
        bd = eqn.params["broadcast_dimensions"]
        if info.spec is None:
            return _VarInfo(None, param=info.param, path=info.path)
        in_shape = getattr(eqn.invars[0].aval, "shape", ())
        if math.prod(shape) != int(math.prod(in_shape) or 1):
            # a TRUE broadcast (size expands): the pre-broadcast value is
            # small and cheap to re-layout, so its sharding must never
            # dominate a downstream merge (a norm scale's fsdp axis would
            # otherwise "conflict" with the activation's batch sharding
            # and invent an 8 GiB gather GSPMD never emits). Model the
            # result as replicated and let the other operand win.
            return _VarInfo(_repl(len(shape)), param=info.param,
                            path=info.path)
        out = [frozenset() for _ in shape]
        for i, od in enumerate(bd):
            if i < len(in_shape) and in_shape[i] == shape[od]:
                out[od] = info.spec[i]
        return _VarInfo(tuple(out), param=info.param, path=info.path)

    def _reshape(self, eqn, info, aval) -> _VarInfo:
        if info.spec is None:
            return _VarInfo(None, param=info.param, path=info.path)
        in_shape = tuple(getattr(aval, "shape", ()))
        out_shape = tuple(eqn.params["new_sizes"])
        spec = _reshape_spec(in_shape, info.spec, out_shape)
        return _VarInfo(spec, param=info.param, path=info.path)

    def _slice(self, eqn, info, aval) -> _VarInfo:
        if info.spec is None:
            return _VarInfo(None, param=info.param, path=info.path)
        shape = getattr(aval, "shape", ())
        starts = eqn.params["start_indices"]
        limits = eqn.params["limit_indices"]
        strides = eqn.params["strides"] or (1,) * len(shape)
        new = tuple(
            s if (st == 0 and li == sz and sr == 1) else frozenset()
            for s, st, li, sr, sz in zip(
                info.spec, starts, limits, strides, shape))
        return _VarInfo(new, param=info.param, path=info.path)

    def _sharding_constraint(self, eqn, info, aval, mult,
                             src) -> _VarInfo:
        # `sharding_constraint` states its layout as `sharding`,
        # `reshard` (the explicit-mode twin) as `dst_sharding`
        sharding = (eqn.params.get("sharding")
                    or eqn.params.get("dst_sharding"))
        pspec = getattr(sharding, "spec", None)
        ndim = len(getattr(aval, "shape", ()))
        if pspec is None:
            return dataclasses.replace(info)
        annotated = self._canon(_spec_of_partition_spec(pspec, ndim))
        if info.spec is not None:
            lost = _axes_in(info.spec) - _axes_in(annotated)
            if lost:
                payload = self._aval_bytes(aval, annotated)
                self.record("all_gather", payload, sorted(lost), mult,
                            implicit=False, source=src,
                            param_path=info.path)
        return _VarInfo(annotated, param=info.param, path=info.path)

    def _scan(self, eqn, infos, env, mult, manual) -> int:
        p = eqn.params
        closed = p["jaxpr"]
        nc, ncar = p["num_consts"], p["num_carry"]
        length = int(p.get("length", 1) or 1)
        consts, init = infos[:nc], infos[nc:nc + ncar]
        inner_mult = mult * length
        xs = []
        for inf in infos[nc + ncar:]:
            # a fresh slice arrives every trip: born at the inner mult
            xs.append(_VarInfo(
                inf.spec[1:] if inf.spec else None,
                param=inf.param, path=inf.path, born_mult=inner_mult))
        carry = [dataclasses.replace(i, born_mult=inner_mult)
                 for i in init]
        # fixpoint: a carry whose sharding changes across iterations
        # settles to the dimwise intersection (stable under repetition)
        for _ in range(2):
            self._quiet += 1
            try:
                _, outs = self._seed_and_walk(
                    closed, consts + carry + xs, env, mult, manual)
            finally:
                self._quiet -= 1
            new_carry = outs[:ncar]
            changed = False
            for i, (a, b) in enumerate(zip(carry, new_carry)):
                if a.spec != b.spec:
                    changed = True
                    if a.spec is None or b.spec is None:
                        carry[i] = _VarInfo(None, param=a.param and b.param)
                    else:
                        carry[i] = _VarInfo(
                            tuple(x & y for x, y in zip(a.spec, b.spec)),
                            param=a.param and b.param, path=a.path)
            if not changed:
                break
        self._scope_stack.append(self._n_scopes)
        self._n_scopes += 1
        try:
            sub_peak, outs = self._seed_and_walk(
                closed, consts + carry + xs, env, mult * length, manual)
        finally:
            self._scope_stack.pop()
        final = outs[:ncar]
        ys = [_VarInfo((frozenset(),) + i.spec if i.spec is not None
                       else None, param=i.param, path=i.path)
              for i in outs[ncar:]]
        for v, info in zip(eqn.outvars, final + ys):
            env[v] = info
        return sub_peak

    def _while(self, eqn, infos, env, mult, manual) -> int:
        p = eqn.params
        cn, bn = p["cond_nconsts"], p["body_nconsts"]
        body = p["body_jaxpr"]
        carry = [dataclasses.replace(i) for i in infos[cn + bn:]]
        self._quiet += 1
        try:
            _, outs = self._seed_and_walk(
                body, infos[cn:cn + bn] + carry, env, mult, manual)
        finally:
            self._quiet -= 1
        for i, (a, b) in enumerate(zip(carry, outs)):
            if a.spec != b.spec:
                carry[i] = _VarInfo(None, param=a.param and b.param)
        # trip count is dynamic: collectives inside are counted ONCE and
        # tagged unbounded (e.g. the ring-attention fori_loop)
        self._unbounded += 1
        try:
            sub_peak, outs = self._seed_and_walk(
                body, infos[cn:cn + bn] + carry, env, mult, manual)
        finally:
            self._unbounded -= 1
        for v, info in zip(eqn.outvars, outs):
            env[v] = info
        return sub_peak

    def _cond(self, eqn, infos, env, mult, manual, src) -> int:
        branches = eqn.params["branches"]
        ops = infos[1:]
        peaks, bys, outs_by_branch, sigs = [], [], [], []
        for bi, br in enumerate(branches):
            if bi > 0:
                self._quiet += 1
            try:
                pk, outs = self._seed_and_walk(br, ops, env, mult, manual)
            finally:
                if bi > 0:
                    self._quiet -= 1
            peaks.append(pk)
            bys.append(self._sub_by)
            outs_by_branch.append(outs)
            sigs.append(_collective_signature(
                getattr(br, "jaxpr", br)))
        if len({tuple(s) for s in sigs}) > 1:
            self.flag(
                "RLT303",
                "collective sequences diverge across cond branches "
                f"({[len(s) for s in sigs]} collectives per branch): "
                "ranks taking different branches issue mismatched "
                "sends/recvs and deadlock", source=src)
        merged = []
        for tup in zip(*outs_by_branch):
            m = tup[0]
            for other in tup[1:]:
                if m.spec != other.spec:
                    m = _VarInfo(None, param=m.param and other.param)
            merged.append(m)
        for v, info in zip(eqn.outvars, merged):
            env[v] = info
        if not peaks:
            return 0
        # the returned peak is the widest branch's: its per-dtype
        # breakdown must ride along or sum(peak_by) drifts off peak
        widest = max(range(len(peaks)), key=peaks.__getitem__)
        self._sub_by = bys[widest]
        return peaks[widest]

    def _shard_map(self, eqn, infos, env, mult) -> int:
        inner = eqn.params["jaxpr"]
        # inside, every value is the local shard: nothing left to shard
        seeds = [_VarInfo(_repl(len(getattr(iv.aval, "shape", ()))),
                          param=outer.param, path=outer.path)
                 for iv, outer in zip(inner.invars, infos)]
        sub_peak, _ = self._seed_and_walk(inner, seeds, env, mult, True)
        for v, pspec in zip(eqn.outvars, eqn.params["out_specs"],
                            strict=True):
            ndim = len(getattr(v.aval, "shape", ()))
            env[v] = _VarInfo(self._canon(
                _spec_of_partition_spec(pspec, ndim)))
        return sub_peak

    def _collective(self, eqn, infos, avals, mult, manual, src) -> None:
        name = eqn.primitive.name
        axes = eqn.params.get("axes") or eqn.params.get("axis_name") or ()
        if not isinstance(axes, (tuple, list)):
            axes = (axes,)
        axes = tuple(a for a in axes if isinstance(a, str))
        path = next((i.path for i in infos if i.path), None)
        if name == "ppermute":
            perm = eqn.params.get("perm", ())
            group = math.prod(self.sizes.get(a, 1) for a in axes) or 1
            if not self._quiet:
                for f in check_permutation(perm, group, source=src):
                    key = ("RLT303", src, f.message[:100])
                    self._findings.setdefault(key, f)
            payload = sum(self._aval_bytes(a) for a in avals
                          if a is not None)
            self.record("ppermute", payload, axes, mult, implicit=False,
                        source=src, param_path=path,
                        dtype=_aval_dtype(avals[0] if avals else None))
            return
        if name == "all_gather":
            payload = sum(self._aval_bytes(v.aval) for v in eqn.outvars)
        else:
            payload = sum(self._aval_bytes(a) for a in avals
                          if a is not None)
        kind = {"pmax": "psum", "pmin": "psum",
                "pbroadcast": "psum"}.get(name, name)
        self.record(kind, payload, axes, mult, implicit=False,
                    source=src, param_path=path,
                    dtype=_aval_dtype(avals[0] if avals else None))


def _reshape_spec(in_shape: Tuple[int, ...],
                  in_spec: Tuple[FrozenSet[str], ...],
                  out_shape: Tuple[int, ...]) -> Tuple[FrozenSet[str], ...]:
    """Map a per-dim spec through a reshape by factor-grouping: axes
    survive when their dim maps 1:1 or is the LEADING factor of a
    collapsed/split group ([B(x), S, D] -> [B*S, D] keeps x on dim 0);
    anything subtler degrades to unsharded, never to a wrong axis."""
    out = [frozenset() for _ in out_shape]
    i = j = 0
    while i < len(in_shape) and j < len(out_shape):
        a, b = in_shape[i], out_shape[j]
        i0, j0 = i, j
        while a != b:
            if a < b:
                i += 1
                a *= in_shape[i]
            else:
                j += 1
                b *= out_shape[j]
        if i == i0 and j == j0:
            out[j] = in_spec[i]
        elif j == j0:  # collapse group: leading in-dim's axes survive
            if all(not in_spec[k] for k in range(i0 + 1, i + 1)):
                out[j] = in_spec[i0]
        else:  # split group: axes go to the leading out-dim if divisible
            out[j0] = in_spec[i0]
        i += 1
        j += 1
    return tuple(out)


def _collective_signature(jaxpr) -> List[Tuple[str, Tuple]]:
    """(prim, axes) sequence of every collective in program order,
    recursively — the cond-branch divergence comparator."""
    sig: List[Tuple[str, Tuple]] = []
    for eqn, _ in walk_eqns(jaxpr):
        if eqn.primitive.name in _COLLECTIVES:
            axes = (eqn.params.get("axes")
                    or eqn.params.get("axis_name") or ())
            if not isinstance(axes, (tuple, list)):
                axes = (axes,)
            sig.append((eqn.primitive.name, tuple(map(str, axes))))
    return sig


# --------------------------------------------------------------------------
# building + auditing the canonical step
# --------------------------------------------------------------------------


def trace_step(module, strategy, n_devices: int, example_batch: Any):
    """Trace the canonical donated train step (the Trainer's loss ->
    grads -> tx.update -> apply_updates shape) over abstractions and
    return ``(closed_jaxpr, meta)``. Zero devices: the same
    AbstractMesh + eval_shape build as `check_plan`/`plan_train_memory`
    (the strategy instance is consumed — pass a fresh one)."""
    import jax

    from ray_lightning_tpu.ops.dispatch import force_pallas
    from ray_lightning_tpu.parallel.plan import _abstract, abstract_mesh
    from ray_lightning_tpu.utils.pytree import named_leaves

    spec = strategy.build_spec(n_devices).resolve(n_devices)
    mesh = abstract_mesh(spec)
    strategy.spec = spec
    strategy.mesh = mesh
    strategy.bind_module(module)
    module.setup()

    a_key = jax.eval_shape(lambda: jax.random.key(0))
    a_batch = _abstract(example_batch)
    # force_pallas, not force_xla: the audit must see the program the
    # TPU runs (flash kernel — no [S, S] score buffer), and like
    # force_xla it skips the backend probe so no device is initialized
    with force_pallas():
        a_params = jax.eval_shape(module.init_params, a_key, a_batch)
        p_shardings = strategy.param_shardings(a_params)
        tx = module.configure_optimizers()
        a_opt = jax.eval_shape(tx.init, a_params)
        o_shardings = strategy.opt_state_shardings(a_opt, a_params)

        def loss_fn(params, batch, rng):
            out = module.training_step(params, batch, rng)
            loss = out[0] if isinstance(out, tuple) else out
            metrics = out[1] if isinstance(out, tuple) else {}
            return loss, {**metrics, **module.pop_logged()}

        def step(params, opt_state, batch, rng):
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch, rng)
            updates, opt_state = tx.update(grads, opt_state, params)
            import optax

            params = optax.apply_updates(params, updates)
            return params, opt_state, loss, metrics

        closed = jax.make_jaxpr(step)(a_params, a_opt, a_batch, a_key)
    closed = dce(closed)

    meta = {
        "spec": spec,
        "mesh_sizes": spec.sizes(),
        "a_params": a_params,
        "a_opt": a_opt,
        "a_batch": a_batch,
        "p_shardings": p_shardings,
        "o_shardings": o_shardings,
        "named_params": dict(named_leaves(a_params)),
        "named_opt": dict(named_leaves(a_opt)),
        "batch_pspec": strategy.batch_spec(),
    }
    return closed, meta


def audit_step(
    module,
    strategy,
    example_batch: Any,
    *,
    topology="v5p-8",
    n_devices: Optional[int] = None,
    reserve_fraction: float = 0.10,
    label: str = "",
    numerics: bool = True,
) -> TraceReport:
    """Full tracecheck audit: trace the real jitted step for ``module``
    under ``strategy`` on ``topology`` (a name like "v5p-64" or a
    `costmodel.Topology`) and return the `TraceReport` — collective
    schedule, implicit-reshard findings, ring checks, and the peak-HBM
    estimate vs the chip budget. CPU-only; consumes ``strategy``.

    ``numerics`` runs numcheck's dtype-provenance pass over the same
    jaxpr (RLT801-805) and fills `TraceReport.precision` — the
    per-dtype-class byte ledger plus the loss's widest-path dtype;
    ``numerics=False`` (the CLI's ``--no-numerics``) skips both."""
    import jax

    topo = (topology if isinstance(topology, Topology)
            else parse_topology(topology))
    if n_devices is None:
        n_devices = topo.n_devices
    closed, meta = trace_step(module, strategy, n_devices, example_batch)
    sizes = meta["mesh_sizes"]
    auditor = _StepAuditor(sizes, topo)
    # seed the top-level env: flatten order mirrors the step signature
    seeds = auditor.seed_state(
        meta["named_params"],
        [getattr(sh, "spec", sh)
         for sh in jax.tree.leaves(meta["p_shardings"])], "params")
    np_ = len(seeds)
    seeds += auditor.seed_state(
        meta["named_opt"],
        [getattr(sh, "spec", sh)
         for sh in jax.tree.leaves(meta["o_shardings"])], "opt_state")
    from ray_lightning_tpu.utils.pytree import named_leaves

    batch_pspec = meta["batch_pspec"]
    for path, leaf in named_leaves(meta["a_batch"]):
        ndim = len(getattr(leaf, "shape", ()))
        seeds.append(_VarInfo(
            auditor._canon(_spec_of_partition_spec(batch_pspec, ndim)),
            param=False, path=f"batch/{path}"))
    seeds.append(_VarInfo(None, param=True, path="rng"))  # key leaf

    jaxpr = closed.jaxpr
    env: Dict = dict(zip(jaxpr.invars, seeds, strict=True))
    for v in jaxpr.constvars:  # hoisted trace-time constants: replicated
        env[v] = _VarInfo(_repl(len(getattr(v.aval, "shape", ()))),
                          param=True)

    peak, peak_by = auditor.walk(jaxpr, env, 1, False)

    def _by_dtype(named, seed_slice) -> Dict[str, int]:
        # per-dtype itemization of the SAME per-leaf bytes the scalar
        # totals sum — the ledger identity sum(by.values()) == total
        # holds exactly (test-pinned)
        by: Dict[str, int] = {}
        for (_, leaf), s in zip(named.items(), seed_slice):
            b = auditor._aval_bytes(leaf, s.spec)
            if b:
                dt = str(getattr(leaf, "dtype", "opaque"))
                by[dt] = by.get(dt, 0) + b
        return by

    params_by = _by_dtype(meta["named_params"], seeds)
    params_dev = sum(params_by.values())
    opt_by = _by_dtype(meta["named_opt"], seeds[np_:])
    opt_dev = sum(opt_by.values())

    events = auditor.events

    findings = auditor.findings
    if topo.n_slices > 1 and n_devices == topo.n_devices:
        # multi-slice placement audit (docs/ELASTIC.md "DCN cost
        # model"): with the slice-major layout the mesh layer builds
        # (order_devices_for_slices), only the outermost `data` axis
        # may span slices — its cross-slice traffic is the hierarchical
        # gradient reduction, priced above. Any OTHER axis crossing the
        # boundary puts per-layer collectives on DCN: flag it. A mesh
        # SMALLER than the deployment (n_devices override) packs into
        # the fewest slices and is never flagged (same guard as
        # _dcn_span).
        from ray_lightning_tpu.parallel.plan import dcn_crossing_axes

        for ax, span in sorted(
                dcn_crossing_axes(sizes, topo.n_slices).items()):
            if ax == "data":
                continue
            findings.append(Finding(
                "RLT306",
                f"mesh axis '{ax}' (size {sizes.get(ax)}) spans {span} "
                f"DCN slices on {topo.name}: its collectives ride the "
                f"inter-slice network ({topo.dcn_gbps:.1f} GB/s per "
                f"chip vs {topo.ici_gbps:.0f} GB/s ICI) every step — "
                "place only `data` across slices and keep "
                f"'{ax}' within a slice "
                f"(<= {topo.devices_per_slice} devices)",
                symbol=label or topo.name))
    precision: Optional[Dict[str, Any]] = None
    if numerics:
        from ray_lightning_tpu.analysis import numcheck as _numcheck

        # outvar layout of the canonical step: new-param leaves, then
        # new-opt leaves, then the scalar loss, then metrics — the loss
        # output sits right past the state
        loss_index = np_ + len(meta["named_opt"])
        nc_findings, nc_info = _numcheck.numcheck_jaxpr(
            closed, loss_index=loss_index)
        # an equation neither walk could enter is reported once
        findings.extend(f for f in nc_findings if f not in findings)
        findings.extend(_numcheck.check_gradient_collectives(
            events, meta["named_params"], meta["named_opt"]))
        # activations = what the liveness peak holds per dtype beyond
        # the resident params/opt state (clamped: state leaves already
        # freed at the peak instant don't go negative)
        act_by: Dict[str, int] = {}
        for dt, b in peak_by.items():
            rem = b - params_by.get(dt, 0) - opt_by.get(dt, 0)
            if rem > 0:
                act_by[dt] = rem
        precision = {
            "params": params_by,
            "opt_state": opt_by,
            "activations": act_by,
            "kv_pool": {},
            "loss_widest_dtype": nc_info.get("loss_widest_dtype"),
        }

    budget = int(topo.hbm_bytes * (1 - reserve_fraction))
    if peak > budget:
        gib = 1024**3
        findings.append(Finding(
            "RLT302",
            f"estimated peak HBM {peak / gib:.2f} GiB/device exceeds the "
            f"{topo.device_kind} budget {budget / gib:.2f} GiB "
            f"({topo.hbm_gib:.0f} GiB x {1 - reserve_fraction:.0%} "
            "usable): the step will OOM on this topology",
            symbol=label or topo.name))
    return TraceReport(
        topology=topo,
        mesh_axes={k: v for k, v in sizes.items() if v > 1},
        collectives=events,
        scan_flops=auditor.scan_flops,
        findings=findings,
        params_bytes_per_device=params_dev,
        opt_bytes_per_device=opt_dev,
        peak_hbm_bytes=peak,
        hbm_budget_bytes=budget,
        label=label,
        precision=precision,
        lost_specs=auditor.lost_specs,
    )

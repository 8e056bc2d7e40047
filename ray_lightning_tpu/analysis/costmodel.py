"""Per-topology interconnect/HBM cost model for tracecheck.

tracecheck (analysis/tracecheck.py) turns a jitted train step into a
collective schedule; this module turns that schedule into bytes-on-wire
and a latency estimate for a NAMED topology ("v5p-64") — zero hardware,
so the numbers are a *model*, not a measurement. The HBM side reuses the
planner's hardware table (`parallel.plan.hbm_bytes_for_kind`), keeping
one source of truth for per-chip memory; the ICI side adds the
bandwidth/latency figures the planner never needed.

Model assumptions (documented in docs/STATIC_ANALYSIS.md):

  * bandwidth figures are the PUBLISHED aggregate ICI bytes/s per chip
    (all links combined). Ring algorithms use every link of the group's
    torus dimension, so charging the aggregate is the optimistic bound;
    contention with other collectives is not modeled;
  * collective wire cost per chip follows the standard ring algebra over
    group size n: all_gather / reduce_scatter move (n-1)/n of the full
    payload, an all_reduce (psum) is reduce_scatter + all_gather =
    2(n-1)/n, a ppermute moves exactly its payload one hop, all_to_all
    moves (n-1)/n;
  * latency = hops x per-hop ICI latency + wire_bytes / bandwidth, with
    hops = n-1 for ring collectives and 1 for a neighbor permute;
  * DCN (multi-slice): ``parse_topology("2xv5p-64")`` is TWO v5p-64
    slices joined over the data-center network — 128 chips, two network
    tiers. A collective whose group spans slices is priced
    HIERARCHICALLY (the standard two-level ring): the intra-slice stage
    over n/s members rides ICI, the inter-slice stage over s slices
    rides DCN on the already-reduced/sharded payload (payload/n_intra
    per chip). DCN bandwidth/latency figures are per-chip share of the
    published inter-slice fabric — an order of magnitude below ICI,
    which is exactly why the mesh layer places only the `data` axis
    across slices (parallel/mesh.py order_devices_for_slices) and
    tracecheck flags any OTHER axis crossing the boundary (RLT306);
  * the compute window (`compute_time_us`, read by `report`'s predicted
    step floor): the matmul FLOPs tracecheck counts in a step's scanned
    bodies (dot_general only — pallas kernels and elementwise work are
    NOT counted) over the chip's spec-sheet peak derated by
    MXU_EFFICIENCY.
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Dict, Mapping, Optional, Tuple

from ray_lightning_tpu.parallel.plan import hbm_bytes_for_kind

__all__ = [
    "Topology", "CollectiveCost", "ICI_SPECS", "DCN_SPECS",
    "MXU_EFFICIENCY", "DTYPE_WIDTHS", "dtype_width",
    "parse_topology", "topology_for_kind",
    "collective_cost", "compute_time_us",
    "paged_decode_traffic_bytes", "paged_prefill_traffic_bytes",
]

#: canonical storage width in BYTES per dtype name — the ONE table both
#: plan_checker's RLT105 (opt state wider than its param) and numcheck's
#: RLT804 (gradient collective narrower than its opt state) read, so the
#: two rules cannot drift (tests/test_numcheck.py pins this). Names are
#: the `str(np.dtype)` / jax aval spellings the analyzers see; the jax
#: sub-byte int4/uint4 and the fp8 family are listed explicitly because
#: np.dtype() cannot resolve them everywhere.
DTYPE_WIDTHS: Dict[str, float] = {
    "float64": 8.0, "int64": 8.0, "uint64": 8.0, "complex64": 8.0,
    "float32": 4.0, "int32": 4.0, "uint32": 4.0,
    "bfloat16": 2.0, "float16": 2.0, "int16": 2.0, "uint16": 2.0,
    "float8_e4m3fn": 1.0, "float8_e5m2": 1.0, "float8_e4m3b11fnuz": 1.0,
    "int8": 1.0, "uint8": 1.0, "bool": 1.0,
    "int4": 0.5, "uint4": 0.5,
}


def dtype_width(dtype) -> Optional[float]:
    """Storage width in bytes for a dtype (object or name); None when
    unknown. Falls back to numpy's itemsize for names not in the table
    (exotic structured dtypes) so callers degrade to the historical
    `.itemsize` behavior instead of silently skipping the check."""
    name = getattr(dtype, "name", None) or str(dtype)
    w = DTYPE_WIDTHS.get(name)
    if w is not None:
        return w
    try:
        import numpy as np

        return float(np.dtype(name).itemsize)
    except Exception:
        return None

#: ICI spec sheet per device family: (device_kind for the HBM table,
#: aggregate ICI GB/s per chip, per-hop latency in microseconds).
#: Bandwidths are the public per-chip interconnect figures (v4 2400
#: Gbps, v5e 1600, v5p 4800, v6e 3584); "cpu" is the CI pseudo-family
#: (loopback, spec-sheet-free) so tests and laptops can run the same
#: code path with an explicit hbm override.
ICI_SPECS: Dict[str, Tuple[str, float, float]] = {
    "v3": ("TPU v3", 280.0, 1.5),
    "v4": ("TPU v4", 300.0, 1.0),
    "v5e": ("TPU v5e", 200.0, 1.0),
    "v5litepod": ("TPU v5 lite", 200.0, 1.0),
    "v5p": ("TPU v5p", 600.0, 1.0),
    "v6e": ("TPU v6e", 448.0, 1.0),
    "cpu": ("cpu", 10.0, 10.0),
}

#: device_kind -> family, for topology_for_kind (the reverse lookup of
#: ICI_SPECS' first column)
_KIND_TO_FAMILY = {kind: fam for fam, (kind, _, _) in ICI_SPECS.items()}

#: DCN (inter-slice) figures per family: (GB/s per chip, per-hop latency
#: in microseconds). These model each chip's SHARE of the slice's
#: data-center-network uplink under a hierarchical collective (every
#: chip drives its own inter-slice ring on its reduce-scattered shard) —
#: deliberately coarse, an order of magnitude below ICI, because the
#: number that matters is the TIER RATIO: it is what makes a tensor/fsdp
#: axis across DCN a performance cliff and a data axis across DCN a
#: tolerable gradient-reduction tax ("Exploring the limits of
#: Concurrency in ML Training on Google TPUs"; TorchTitan HSDP).
#: "cpu" keeps CI runnable with visible-but-tiny figures.
DCN_SPECS: Dict[str, Tuple[float, float]] = {
    "v3": (6.25, 50.0),
    "v4": (12.5, 50.0),
    "v5e": (6.25, 50.0),
    "v5litepod": (6.25, 50.0),
    "v5p": (25.0, 50.0),
    "v6e": (12.5, 50.0),
    "cpu": (1.0, 100.0),
}

#: fallback HBM for families the planner table doesn't know (the "cpu"
#: pseudo-family): enough to trace, small enough that a real model's
#: HBM-OVERCOMMIT check still exercises on CI
_CPU_HBM_BYTES = 16 * 1024**3

#: the "cpu" pseudo-family's compute pseudo-peak (TFLOP/s): like its
#: ICI/DCN rows a stated CI figure, not a measurement of anything
_CPU_PEAK_TFLOPS = 1.0


@dataclasses.dataclass(frozen=True)
class Topology:
    """One named deployment: chip kind + count + interconnect figures.
    ``n_slices > 1`` is a multi-slice deployment (``"2xv5p-64"``):
    ``n_devices`` is the TOTAL chip count across slices, ICI spans one
    slice, slices talk over DCN at the dcn_* figures."""

    name: str             # e.g. "v5p-64" or "2xv5p-64"
    device_kind: str      # PJRT device_kind string, keys the HBM table
    n_devices: int
    ici_gbps: float       # aggregate ICI bandwidth per chip, GB/s
    ici_hop_latency_us: float
    hbm_bytes: int        # usable HBM per chip
    #: spec-sheet peak bf16 TFLOP/s per chip — the compute side of the
    #: predicted step floor. None resolves from device_kind via the
    #: utils/probe.py table (one source of truth), so a directly
    #: constructed Topology prices compute the same as parse_topology.
    peak_tflops: Optional[float] = None
    #: multi-slice (DCN) tier. Defaults keep every existing
    #: single-slice construction site valid: one slice, DCN figures
    #: resolved from the device kind's family in __post_init__.
    n_slices: int = 1
    dcn_gbps: Optional[float] = None
    dcn_hop_latency_us: Optional[float] = None

    def __post_init__(self) -> None:
        if self.peak_tflops is None:
            object.__setattr__(
                self, "peak_tflops", _peak_tflops(self.device_kind))
        if self.dcn_gbps is None or self.dcn_hop_latency_us is None:
            fam = _KIND_TO_FAMILY.get(self.device_kind, "cpu")
            gbps, lat = DCN_SPECS.get(fam, DCN_SPECS["cpu"])
            if self.dcn_gbps is None:
                object.__setattr__(self, "dcn_gbps", gbps)
            if self.dcn_hop_latency_us is None:
                object.__setattr__(self, "dcn_hop_latency_us", lat)
        if self.n_slices < 1 or self.n_devices % self.n_slices:
            raise ValueError(
                f"topology {self.name!r}: {self.n_devices} devices do "
                f"not split into {self.n_slices} equal slices")

    @property
    def hbm_gib(self) -> float:
        return self.hbm_bytes / 1024**3

    @property
    def devices_per_slice(self) -> int:
        return self.n_devices // self.n_slices

    def describe(self) -> str:
        base = (f"{self.name}: {self.n_devices}x {self.device_kind} "
                f"({self.hbm_gib:.0f} GiB HBM, {self.ici_gbps:.0f} GB/s "
                "ICI per chip)")
        if self.n_slices > 1:
            base += (f" in {self.n_slices} slices of "
                     f"{self.devices_per_slice} over DCN "
                     f"({self.dcn_gbps:.1f} GB/s per chip)")
        return base


def parse_topology(name: str, *,
                   hbm_bytes: Optional[int] = None) -> Topology:
    """``"v5p-64"`` -> a Topology; ``"2xv5p-64"`` -> TWO v5p-64 slices
    joined over DCN (128 chips total, ``n_slices=2``). The family keys
    ICI_SPECS; the chip count after the dash is PER SLICE. Unknown
    families raise listing the known ones (same first-contact contract
    as hbm_bytes_for_kind)."""
    m = re.fullmatch(r"(?:(\d+)x)?([a-z][a-z0-9]*?)-(\d+)",
                     name.strip().lower())
    if not m:
        raise ValueError(
            f"cannot parse topology {name!r}; expected <family>-<chips> "
            "like 'v5p-64', or <slices>x<family>-<chips> like "
            f"'2xv5p-64' (families: {sorted(ICI_SPECS)})")
    slices = int(m.group(1) or 1)
    family, count = m.group(2), int(m.group(3))
    if family not in ICI_SPECS:
        raise ValueError(
            f"unknown topology family {family!r} (known: "
            f"{sorted(ICI_SPECS)}); pass hbm_bytes= and use "
            "topology_for_kind for other hardware")
    if count < 1:
        raise ValueError(f"topology {name!r} must have >= 1 chip")
    if slices < 1:
        raise ValueError(f"topology {name!r} must have >= 1 slice")
    kind, gbps, lat = ICI_SPECS[family]
    if hbm_bytes is None:
        try:
            hbm_bytes = hbm_bytes_for_kind(kind)
        except ValueError:  # the "cpu" pseudo-family
            hbm_bytes = _CPU_HBM_BYTES
    return Topology(name=name, device_kind=kind, n_devices=slices * count,
                    ici_gbps=gbps, ici_hop_latency_us=lat,
                    hbm_bytes=int(hbm_bytes), n_slices=slices)


def topology_for_kind(device_kind: str, n_devices: int, *,
                      hbm_bytes: Optional[int] = None) -> Topology:
    """Topology from a PJRT ``device_kind`` string (the plan CLI's
    --device-kind vocabulary) instead of a family-dash-count name.
    Unknown kinds get the cpu pseudo-family's conservative ICI figures
    and the HBM side honors ``hbm_bytes`` or the planner table, but
    the compute peak has no fallback: a kind outside
    `utils.probe.PEAK_TFLOPS` raises."""
    family = _KIND_TO_FAMILY.get(device_kind, "cpu")
    _, gbps, lat = ICI_SPECS[family]
    if hbm_bytes is None:
        try:
            hbm_bytes = hbm_bytes_for_kind(device_kind)
        except ValueError:
            hbm_bytes = _CPU_HBM_BYTES
    return Topology(name=f"{family}-{n_devices}", device_kind=device_kind,
                    n_devices=n_devices, ici_gbps=gbps,
                    ici_hop_latency_us=lat, hbm_bytes=int(hbm_bytes))


def _peak_tflops(device_kind: str) -> float:
    """Spec-sheet peak for the compute window — one source of truth
    with the bench/doctor probe (utils/probe.py). The "cpu" pseudo-
    family states its own pseudo-figure like its ICI/DCN rows; any
    other kind outside the table raises."""
    if device_kind == "cpu":
        return _CPU_PEAK_TFLOPS
    from ray_lightning_tpu.utils.probe import device_peak_tflops

    return float(device_peak_tflops(device_kind))


#: fraction of spec-sheet peak a well-tuned matmul-dominated step
#: actually sustains — the compute window is charged at peak x
#: efficiency. 0.6 is an assumption no chip run has confirmed for the
#: current code (ROADMAP Queue 3 item 6). Documented in
#: docs/STATIC_ANALYSIS.md.
MXU_EFFICIENCY = 0.6


def compute_time_us(flops: float, topo: Topology) -> float:
    """Time to execute ``flops`` per-device FLOPs on one chip of
    ``topo`` at the derated roofline."""
    if flops <= 0:
        return 0.0
    return flops / (topo.peak_tflops * 1e12 * MXU_EFFICIENCY) * 1e6


@dataclasses.dataclass(frozen=True)
class CollectiveCost:
    wire_bytes: int   # bytes each chip puts on ICI for this collective
    time_us: float    # ring-model latency estimate (both tiers, serial)
    #: bytes each chip puts on DCN (0 on a single-slice group). When
    #: nonzero, ``time_us`` already includes the DCN stage — the two
    #: tiers are priced as sequential hierarchical stages.
    dcn_bytes: int = 0
    dcn_time_us: float = 0.0


def _ring(kind: str, payload: float, n: int) -> Tuple[float, int]:
    """(wire bytes per chip, ring hops) for one single-tier collective
    over group size ``n`` — the standard ring algebra."""
    if n <= 1:
        return 0.0, 0
    frac = (n - 1) / n
    if kind in ("all_gather", "reduce_scatter", "all_to_all"):
        return payload * frac, n - 1
    if kind == "ppermute":
        return float(payload), 1
    # psum / pmax / pmin / pbroadcast and friends: all_reduce-shaped
    return 2.0 * payload * frac, 2 * (n - 1)


def collective_cost(
    kind: str,
    payload_bytes: int,
    axis_sizes: Mapping[str, int],
    topo: Topology,
    *,
    dcn_group: int = 1,
) -> CollectiveCost:
    """Ring-model wire bytes + latency for ONE collective.

    ``payload_bytes`` is the per-chip payload the jaxpr shows: the local
    operand bytes for psum/ppermute/all_to_all/reduce_scatter, and the
    per-chip FULL (post-gather) bytes for all_gather. ``axis_sizes`` maps
    the participating mesh axes to their sizes; the group size is their
    product.

    ``dcn_group`` is the number of DCN slices the group spans (1 =
    intra-slice; use `parallel.plan.group_dcn_span` to derive it from
    the mesh layout). A crossing group is priced as the hierarchical
    two-level algorithm: the intra-slice stage over n/dcn_group members
    rides ICI; the inter-slice stage rides DCN on the intra-reduced (or
    intra-sharded) payload — each chip drives its own inter-slice ring
    on a 1/n_intra share, the standard two-level all-reduce. Two
    exceptions with NO intra-stage payload reduction: a crossing
    ppermute puts its whole payload on DCN (one hop), and a crossing
    all_to_all sends its chunks directly — the (s-1)/s fraction
    targeting remote slices crosses DCN at full size."""
    n = max(1, math.prod(axis_sizes.values()))
    if n == 1:
        return CollectiveCost(0, 0.0)
    s = max(1, min(int(dcn_group), n))
    if n % s:
        # a group that touches s slices unevenly degrades to the
        # conservative read: price the whole group on DCN figures
        s = n
    n_intra = n // s
    if kind == "ppermute" and s > 1:
        dcn_wire, dcn_hops = float(payload_bytes), 1
        ici_wire, ici_hops = 0.0, 0
    elif kind == "all_to_all" and s > 1:
        # all_to_all has NO intra-stage payload reduction (unlike the
        # reduce/gather shapes below): each chip's payload splits into
        # n equal chunks sent directly — n_intra-1 stay on ICI, the
        # (s-1)/s fraction targeting remote slices crosses DCN whole
        ici_wire = payload_bytes * (n_intra - 1) / n
        ici_hops = max(0, n_intra - 1)
        dcn_wire = payload_bytes * (s - 1) / s
        dcn_hops = s - 1
    else:
        ici_wire, ici_hops = _ring(kind, payload_bytes, n_intra)
        dcn_wire, dcn_hops = _ring(kind, payload_bytes / n_intra, s)
    ici_time = (ici_wire / (topo.ici_gbps * 1e3)
                + ici_hops * topo.ici_hop_latency_us)
    dcn_time = 0.0
    if s > 1:
        dcn_time = (dcn_wire / (topo.dcn_gbps * 1e3)
                    + dcn_hops * topo.dcn_hop_latency_us)
    else:
        dcn_wire = 0.0
    return CollectiveCost(int(ici_wire), ici_time + dcn_time,
                          dcn_bytes=int(dcn_wire),
                          dcn_time_us=dcn_time)


def paged_decode_traffic_bytes(pool_bytes: int, gathered_view_bytes: int,
                               fused: bool) -> int:
    """Per-tick HBM *traffic* of the serving decode lane's KV movement
    (docs/SERVING.md "paged-attention kernel") — the bandwidth story
    behind the capacity numbers `serve_kv_plan_bytes` itemizes.

    Decode is bandwidth-bound: every tick must stream each live slot's
    K/V once (<= the pool, read). The reference lane additionally
    WRITES the dense gathered view and READS it back through the
    model's cache path — the copy is the traffic, not just the HBM.
    The fused kernel streams the table-named blocks straight through
    VMEM, so its traffic floor is the single pool read. A conservative
    per-tick model (the full pool charged even when slots are idle;
    Q/output/weight bytes excluded — identical on both paths)."""
    if fused:
        return int(pool_bytes)
    return int(pool_bytes + 2 * gathered_view_bytes)


def paged_prefill_traffic_bytes(group_view_bytes: int, chunk_bytes: int,
                                fused: bool) -> int:
    """Per-chunk HBM *traffic* of the serving PREFILL lane's KV
    movement (docs/SERVING.md "paged prefill kernel") — the prefill
    twin of `paged_decode_traffic_bytes`.

    Every chunk must stream the group's already-written blocks once
    (<= the group's span, read) and write the chunk's new K/V. The
    reference lane additionally WRITES the dense per-group gathered
    view and READS it back through the model's chunked cache path —
    the copy is the traffic. The fused kernel streams the table-named
    blocks straight through VMEM, so its traffic floor is the group's
    block reads plus the chunk write. A conservative per-chunk model
    (the group's full span charged even early in the prompt;
    Q/output/weight bytes excluded — identical on both paths)."""
    if fused:
        return int(group_view_bytes + chunk_bytes)
    return int(3 * group_view_bytes + chunk_bytes)

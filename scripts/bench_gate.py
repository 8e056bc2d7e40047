#!/usr/bin/env python
"""Bench regression ratchet (ISSUE 6 satellite): a fresh bench JSON line
must not regress the best prior round.

Prior rounds are ``BENCH_r0*.json`` recorder wrappers under
``--repo-root`` (each holds the round's parsed bench line under
``"parsed"``; rounds the backend skipped contribute nothing). None is
checked in today: with no prior file the gate says "no prior" and only
the bounded metrics are checked. For every ratcheted metric the best
prior value is the per-metric max — speed can only go up:

    value                    tokens/sec/chip (the headline metric)
    mfu                      model FLOPs utilization
    goodput_fraction         productive share of the headline
                             measurement window (measured)
    slo_attainment_latency_critical
                             fraction of latency-critical completions
                             meeting the class TTFT target in the
                             bench's mixed-class SLO burst (ISSUE 20;
                             measured, waived on skip lines)

Bounded metrics (upper limits, not ratchets):

    telemetry_overhead_fraction  measured span-recorder cost relative
                                 to the step time — must stay < 1%
                                 (ISSUE 7: observability must not
                                 become the overhead it measures)
    ttft_warm_s                  warm single-request TTFT (ISSUE 8)
    ttft_p99_s                   steady-state warm TTFT p99 from the
                                 mergeable histogram buckets (ISSUE 12
                                 serving metrics; RLT_BENCH_TTFT_P99_MAX
                                 overrides, skip/null waives)
    reshard_restore_s            elastic cross-topology restore wall
                                 (ISSUE 9)
    scale_up_s                   autoscale add_replica actuation wall
                                 (ISSUE 13; RLT_BENCH_SCALE_UP_MAX
                                 overrides, skip/null waives)
    incidents                    watch-rule breaches fired against the
                                 bench's own serving drill (ISSUE 14:
                                 a healthy bench fires zero; any
                                 incident in the bench run itself is a
                                 regression — skip/null waived)

Gate semantics:

  * fresh line with ``"skipped"`` — an environmental skip (backend
    down, driver kill). The ratcheted metrics are all measured and
    are waived: the ratchet gates merit, not machine availability. A
    round with no JSON at all FAILS — there is no line to pass.
  * fresh success line — every ratcheted metric present in both the
    fresh line and some prior round must satisfy
    ``fresh >= best_prior * (1 - tolerance)`` (default 5%, --tolerance).
    A metric the priors track but the fresh line DROPPED also fails:
    deleting the field must not bypass the ratchet.

Usage:
    python scripts/bench_gate.py fresh.json          # wrapper or raw line
    ... | python scripts/bench_gate.py -             # last JSON line wins
    python scripts/bench_gate.py fresh.json --prior-glob 'BENCH_r0*.json'

Exit 0 pass, 1 regression, 2 invalid input (unparseable fresh line).
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Optional

#: metric name -> key in the bench JSON line. "value" is
#: tokens/sec/chip (see the line's "metric"/"unit" fields).
RATCHETED = {
    "tokens_per_sec_per_chip": "value",
    "mfu": "mfu",
    "goodput_fraction": "goodput_fraction",
    # serving leg (ISSUE 8): steady-state continuous-batching decode
    # throughput — measured, so waived on environmental skip lines
    "decode_tokens_per_s": "decode_tokens_per_s",
    # ISSUE 19: the prefix cache's measured sharing on the saturated
    # steady-state leg (fraction of mapped blocks that were shared —
    # may only grow), and tokens emitted per decoding slot-step
    # (exactly 1.0 without a draft, > 1.0 once speculative acceptance
    # lands — may only grow). Both measured: waived on skip lines.
    "shared_block_fraction": "shared_block_fraction",
    "accepted_tokens_per_step": "accepted_tokens_per_step",
    # ISSUE 20: fraction of latency-critical completions meeting the
    # class TTFT target in the bench's mixed-class SLO burst (1.0 when
    # every paying request held its SLO while best-effort shed).
    # Measured: waived on environmental skip lines.
    "slo_attainment_latency_critical": "slo_attainment_latency_critical",
}

#: metric -> key for CEILING ratchets: lower is better, so the fresh
#: value must stay <= the best (minimum) prior * (1 + tolerance).
#: dcn_bytes_per_step is the static 2xv5p-64 trace's inter-slice bytes
#: (ISSUE 9): DCN is the slow tier, so its per-step traffic may only
#: shrink. serve_hbm_bytes_per_replica is the flagship serving
#: replica's static per-device HBM on its auto-selected attention
#: paths (ISSUE 11; re-anchored to the fused-PREFILL plan by ISSUE 15
#: — the prefill kernel retired the last dense gather, so the ceiling
#: now holds at the lower fused-both figure).
#: serve_prefill_gather_bytes is the prefill lane's surviving dense
#: per-group gather on the same plan (ISSUE 15): 0 once the fused
#: prefill kernel covers the flagship shape, and it may only shrink —
#: nothing may quietly re-materialize the gather. Static class:
#: ratchets on skip lines too; a line carrying the metric's waiver
#: error field instead waives (analysis bug != regression).
#: serve_decode_ici_bytes_per_tick is the flagship TP=2 sharded
#: replica's decode-step collective traffic (ISSUE 18,
#: serve/audit.py `audit_decode_step`): every byte rides the
#: latency-critical per-token path (the layer psums + the jit-boundary
#: logits gather), so the per-tick wire total may only shrink.
#: low_precision_reductions is numcheck's count of narrow-accumulation
#: findings on the flagship trace (RLT801 bf16 dot/reduce accumulations
#: + RLT804 bf16 gradient collectives, analysis/numcheck.py): 0 since
#: the f32-accumulation fixes, zero-anchored here — no future change
#: may quietly reintroduce a bf16 reduction into the flagship step.
CEILING = {"dcn_bytes_per_step": "dcn_bytes_per_step",
           "serve_hbm_bytes_per_replica": "serve_hbm_bytes_per_replica",
           "serve_prefill_gather_bytes": "serve_prefill_gather_bytes",
           "serve_decode_ici_bytes_per_tick":
               "serve_decode_ici_bytes_per_tick",
           "low_precision_reductions": "low_precision_reductions"}

#: ceiling metric -> error fields whose presence waives an ABSENT
#: value (the analysis that computes the static metric died and said
#: so); a present value always ratchets
CEILING_WAIVERS = {
    "dcn_bytes_per_step": ("multislice_error", "tracecheck_error"),
    "serve_hbm_bytes_per_replica": ("serving_error",
                                    "tracecheck_error"),
    "serve_prefill_gather_bytes": ("serving_error",
                                   "tracecheck_error"),
    "serve_decode_ici_bytes_per_tick": ("serving_error",
                                        "tracecheck_error"),
    "low_precision_reductions": ("numerics_error",),
}

#: ceiling metric -> short rationale for the failure message
CEILING_WHY = {
    "dcn_bytes_per_step": ("DCN is the slow tier; its per-step "
                           "traffic may only shrink"),
    "serve_hbm_bytes_per_replica": (
        "per-replica serving HBM may only shrink — the fused paged "
        "decode + prefill kernels retired the dense gathered views "
        "and nothing may quietly grow them back (the ceiling prices "
        "the full unshared pool: prefix sharing SAVES bytes inside "
        "it, so sharing can never excuse a bigger plan)"),
    "serve_prefill_gather_bytes": (
        "the prefill lane's dense per-group gather is retired by the "
        "fused paged-prefill kernel — its bytes may only shrink, and "
        "nothing may quietly re-materialize the gather"),
    "serve_decode_ici_bytes_per_tick": (
        "decode collectives ride the latency-critical per-token path "
        "(layer psums + the boundary logits gather) — the sharded "
        "replica's per-tick wire bytes may only shrink"),
    "low_precision_reductions": (
        "the flagship step accumulates every long reduction in f32 "
        "(numcheck RLT801/RLT804) — the count is zero-anchored and no "
        "change may quietly reintroduce a bf16 accumulation"),
}

#: metric -> max allowed value on a measured (non-skip) line; absent or
#: null waives (bench.py reports null when the probe itself failed) —
#: each bound exists to stop a latency/overhead class from growing, not
#: to demand the field on every historic line
BOUNDED = {
    "telemetry_overhead_fraction": float(
        os.environ.get("RLT_BENCH_TELEMETRY_OVERHEAD_MAX", 0.01)),
    # warm TTFT (serving leg, ISSUE 8): a request on the already-
    # compiled engine — queue + prefill only. A growth here means the
    # engine started recompiling (or prefill regressed) on the serving
    # hot path.
    "ttft_warm_s": float(
        os.environ.get("RLT_BENCH_TTFT_WARM_MAX", 2.0)),
    # warm TTFT p99 (serving metrics leg, ISSUE 12): the tail of the
    # steady-state admission->first-token latency, read from the
    # mergeable histogram BUCKETS (telemetry/metrics.py) — the SLO
    # number production serving is judged on. Looser than the warm
    # mean bound: the p99 request admitted behind a full slot set
    # waits out its predecessors' prefill chunks by design.
    "ttft_p99_s": float(
        os.environ.get("RLT_BENCH_TTFT_P99_MAX", 5.0)),
    # cross-topology restore (elastic leg, ISSUE 9): the wall seconds
    # one elastic shrink/grow pays to reshard its ~32 MiB probe state.
    # A growth here means the reshard path started gathering to host
    # (or the storage layer regressed) — the elastic story's hot path.
    "reshard_restore_s": float(
        os.environ.get("RLT_BENCH_RESHARD_MAX", 30.0)),
    # autoscale actuation (serving leg, ISSUE 13): the wall one
    # controller-driven add_replica pays — spawn + weight reload +
    # step compile/deserialize + warmup. This is how long a pressure
    # spike waits before capacity actually arrives; growth means the
    # respawn path regressed (e.g. the persistent compile cache
    # stopped hitting). Skip/null waived like every bound.
    "scale_up_s": float(
        os.environ.get("RLT_BENCH_SCALE_UP_MAX", 120.0)),
    # watch incidents (ISSUE 14): the bench arms the built-in SLO
    # rules over its own autoscale-drill run dir. The bound is ZERO:
    # any rule breach inside the bench's own controlled serving run is
    # a regression with a self-documenting incident record to read,
    # never acceptable noise. Skip lines and null/absent counts waive
    # (the drill degraded to autoscale_error and said so).
    "incidents": float(os.environ.get("RLT_BENCH_INCIDENTS_MAX", 0.0)),
}


def _extract_line(obj: dict) -> Optional[dict]:
    """A recorder wrapper ({"parsed": {...}}) or a raw bench line."""
    if not isinstance(obj, dict):
        return None
    if "parsed" in obj:
        parsed = obj["parsed"]
        return parsed if isinstance(parsed, dict) else None
    return obj if "metric" in obj else None


def _last_json_line(text: str) -> Optional[dict]:
    """The LAST parseable JSON object line — bench.py's contract is that
    its final stdout line is the structured one (watchdog/kill lines
    close any half-written line first)."""
    for raw in reversed(text.strip().splitlines()):
        raw = raw.strip()
        if not raw.startswith("{"):
            continue
        try:
            return json.loads(raw)
        except json.JSONDecodeError:
            continue
    return None


def best_prior(prior_glob: str, repo_root: str) -> dict:
    """Per-metric max over all prior rounds that measured it."""
    best: dict = {}
    for path in sorted(glob.glob(os.path.join(repo_root, prior_glob))):
        try:
            with open(path) as f:
                line = _extract_line(json.load(f))
        except (OSError, json.JSONDecodeError):
            continue
        if line is None:
            continue
        try:
            measured = ("skipped" not in line
                        and float(line.get("value") or 0) > 0)
        except (TypeError, ValueError):  # "value": null / non-numeric
            measured = False
        for name, key in RATCHETED.items():
            # measurements count only from success lines
            v = line.get(key)
            if v is None or not measured:
                continue
            try:
                v = float(v)
            except (TypeError, ValueError):
                continue
            if name not in best or v > best[name][0]:
                best[name] = (v, os.path.basename(path))
    return best


def ceiling_prior(prior_glob: str, repo_root: str) -> dict:
    """Per-metric MIN over prior rounds for the CEILING metrics (lower
    is better — the fresh value must not grow past it). All current
    ceiling metrics are static, so every prior line that carries the
    field contributes."""
    best: dict = {}
    for path in sorted(glob.glob(os.path.join(repo_root, prior_glob))):
        try:
            with open(path) as f:
                line = _extract_line(json.load(f))
        except (OSError, json.JSONDecodeError):
            continue
        if line is None:
            continue
        for name, key in CEILING.items():
            v = line.get(key)
            try:
                v = float(v)
            except (TypeError, ValueError):
                continue
            if name not in best or v < best[name][0]:
                best[name] = (v, os.path.basename(path))
    return best


def gate(fresh: dict, best: dict, tolerance: float,
         ceilings: Optional[dict] = None) -> list[str]:
    """Return the list of failure messages (empty = pass)."""
    skipped = "skipped" in fresh
    if skipped and "metric" not in fresh:
        return ["skip line is not the structured schema "
                "(missing 'metric')"]
    failures = []
    for name, key in RATCHETED.items():
        if skipped or name not in best:
            # an environmental skip waives the measured metrics
            continue
        prior, source = best[name]
        if prior <= 0:
            continue
        v = fresh.get(key)
        if v is None:
            failures.append(
                f"{name}: prior rounds track it ({prior:g} in {source}) "
                f"but the fresh line dropped the field '{key}'")
            continue
        try:
            v = float(v)
        except (TypeError, ValueError):
            failures.append(f"{name}: non-numeric value {v!r}")
            continue
        floor = prior * (1 - tolerance)
        if v < floor:
            failures.append(
                f"{name}: {v:g} regressed below {floor:g} "
                f"(best prior {prior:g} in {source}, "
                f"tolerance {tolerance:.0%})")
    for name, (prior, source) in (ceilings or {}).items():
        key = CEILING[name]
        v = fresh.get(key)
        if v is None:
            if any(w in fresh for w in CEILING_WAIVERS[name]):
                # the static analysis died — a failure is reported as
                # its own error field, never as a deleted metric
                continue
            failures.append(
                f"{name}: prior rounds track it ({prior:g} in {source}) "
                f"but the fresh line dropped the field '{key}'")
            continue
        try:
            v = float(v)
        except (TypeError, ValueError):
            failures.append(f"{name}: non-numeric value {v!r}")
            continue
        cap = prior * (1 + tolerance)
        if v > cap:
            failures.append(
                f"{name}: {v:g} grew past {cap:g} (best prior {prior:g} "
                f"in {source}, tolerance {tolerance:.0%}) — "
                f"{CEILING_WHY[name]}")
    for key, bound in BOUNDED.items():
        if skipped:
            continue  # bounds apply to measured lines only
        v = fresh.get(key)
        if v is None:
            continue  # probe failed or pre-telemetry line: waived
        try:
            v = float(v)
        except (TypeError, ValueError):
            failures.append(f"{key}: non-numeric value {v!r}")
            continue
        if v > bound:
            whats = {
                "telemetry_overhead_fraction":
                    "telemetry is eating the step time it exists to "
                    "measure",
                "incidents":
                    "the bench's own serving drill breached a watch "
                    "rule — read the incident record(s) in the drill "
                    "run dir's incidents.jsonl excerpt for the "
                    "self-documented evidence",
                "ttft_p99_s":
                    "the steady-state TTFT tail blew its SLO bound — "
                    "queueing/prefill latency grew on the serving hot "
                    "path (see the histogram sketch in `report`)",
                "scale_up_s":
                    "autoscale actuation slowed — a pressure spike now "
                    "waits this long for capacity (the respawn path "
                    "or its compile-cache re-warm regressed)",
            }
            what = whats.get(
                key, "the serving warm path regressed (recompile "
                     "or prefill growth on the request hot path)")
            failures.append(
                f"{key}: {v:g} exceeds the {bound:g} upper bound — "
                f"{what}")
    return failures


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        "bench_gate", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("fresh",
                   help="fresh bench JSON (wrapper or raw line); '-' "
                        "reads stdin and takes the last JSON line")
    p.add_argument("--prior-glob", default="BENCH_r0*.json",
                   help="prior-round files, relative to --repo-root")
    p.add_argument("--repo-root",
                   default=os.path.dirname(os.path.dirname(
                       os.path.abspath(__file__))))
    p.add_argument("--tolerance", type=float,
                   default=float(os.environ.get("RLT_BENCH_GATE_TOL",
                                                0.05)),
                   help="allowed per-metric regression (default 0.05)")
    args = p.parse_args(argv)

    if args.fresh == "-":
        fresh = _last_json_line(sys.stdin.read())
    else:
        try:
            with open(args.fresh) as f:
                text = f.read()
        except OSError as exc:
            print(f"bench_gate: cannot read {args.fresh}: {exc}",
                  file=sys.stderr)
            return 2
        try:
            fresh = _extract_line(json.loads(text))
        except json.JSONDecodeError:
            fresh = _last_json_line(text)
    if fresh is None:
        print("bench_gate: no parseable bench JSON line in input "
              "(unparseable round), failing", file=sys.stderr)
        return 2

    best = best_prior(args.prior_glob, args.repo_root)
    ceilings = ceiling_prior(args.prior_glob, args.repo_root)
    failures = gate(fresh, best, args.tolerance, ceilings)
    if failures:
        for msg in failures:
            print(f"bench_gate: REGRESSION — {msg}", file=sys.stderr)
        return 1
    if not glob.glob(os.path.join(args.repo_root, args.prior_glob)):
        print(f"bench_gate: pass — no prior round matches "
              f"{args.prior_glob!r}; bounded metrics only")
        return 0
    if "skipped" in fresh:
        print(f"bench_gate: pass (environmental skip: {fresh['skipped']})")
    else:
        checked = ", ".join(
            f"{name}={float(fresh[key]):g} (best {best[name][0]:g})"
            for name, key in RATCHETED.items()
            if name in best and fresh.get(key) is not None)
        print(f"bench_gate: pass — {checked or 'no prior metrics'}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

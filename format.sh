#!/usr/bin/env bash
# Lint/format entry point (reference analog: format.sh with yapf+flake8,
# reference format.sh:1-140). Two tools: ruff (style, both roles) and
# shardcheck (`python -m ray_lightning_tpu lint`, docs/STATIC_ANALYSIS.md)
# for the TPU/JAX-semantics rules ruff cannot know — host transfers in
# traced code, mesh-axis typos, unhashable static args.
#
#   ./format.sh           # fix in place (+ shardcheck)
#   ./format.sh --check   # CI mode: fail on violations
set -euo pipefail
cd "$(dirname "$0")"
SECONDS=0

RUFF_ARGS=(check ray_lightning_tpu tests examples bench.py __graft_entry__.py)

# ruff is optional tooling: skip (loudly) on boxes that don't ship it so
# the semantic gates below still run — shardcheck/tracecheck are the
# gates that need THIS repo's toolchain, ruff is style only. CI images
# that DO ship ruff should export RLT_REQUIRE_RUFF=1 so a PATH break
# cannot silently drop the style gate.
if command -v ruff > /dev/null 2>&1; then
    if [[ "${1:-}" == "--check" ]]; then
        ruff "${RUFF_ARGS[@]}"
    else
        ruff "${RUFF_ARGS[@]}" --fix
    fi
elif [[ "${RLT_REQUIRE_RUFF:-}" == "1" ]]; then
    echo "format.sh: ruff not installed but RLT_REQUIRE_RUFF=1" >&2
    exit 1
else
    echo "format.sh: ruff not installed — skipping style pass" >&2
fi

# shardcheck has no fix mode; it gates both invocations identically.
# examples/ ship user-facing step code, so they are held to the same bar.
# --concurrency folds threadcheck (analysis/concurrency.py, RLT7xx:
# races, lock-order inversions, thread leaks, signal-handler and
# blocking-under-lock discipline) into the same gate — the package's
# host-side threading is linted as strictly as its jit-side sharding.
# --numerics adds numcheck's AST arm (inline .astype(bf16/int8)
# operands in dot/einsum calls — the RLT801/805 copy-paste shapes).
JAX_PLATFORMS=cpu python -m ray_lightning_tpu lint --concurrency \
    --numerics ray_lightning_tpu examples bench.py __graft_entry__.py

# lockwatch smoke (docs/STATIC_ANALYSIS.md "threadcheck & lockwatch"):
# the runtime half of the concurrency gate. Arm the sanitizer BEFORE
# the package imports (armed-ness is decided at lock creation), drive a
# real threaded subsystem (telemetry recorder: a worker thread posting
# spans while the main thread snapshots), and require a clean order
# graph. The full suite runs armed too (tests/conftest.py) — this is
# the seconds-cheap standalone proof the wiring works.
RLT_LOCKWATCH=1 JAX_PLATFORMS=cpu python -c '
import threading

from ray_lightning_tpu.analysis.lockwatch import (
    assert_lockwatch_clean, lockwatch_armed, san_lock)

assert lockwatch_armed(), "RLT_LOCKWATCH=1 not seen by lockwatch"
from ray_lightning_tpu.analysis.lockwatch import _SanLock
assert isinstance(san_lock("format.smoke"), _SanLock)

from ray_lightning_tpu.telemetry.spans import (
    THREAD_PRODUCER, TelemetryRecorder)
rec = TelemetryRecorder()
def worker():
    for i in range(50):
        with rec.span("format.smoke", step=i, thread=THREAD_PRODUCER):
            pass
threads = [threading.Thread(target=worker) for _ in range(4)]
for t in threads:
    t.start()
for t in threads:
    t.join()
for _ in range(20):
    rec.phase_totals()
    rec.last_span()
assert_lockwatch_clean()
print("lockwatch smoke: armed, threaded spans clean")'

# tracecheck + numcheck gate: the flagship Llama-8B v5p-64 step must
# audit clean at the jaxpr level (no implicit resharding, no ring
# deadlocks, peak HBM within budget — docs/STATIC_ANALYSIS.md
# "tracecheck") AND numerics-clean: zero RLT8xx findings of ANY
# severity (the warning-grade cast churn and bf16 transcendentals
# gate too), an f32 loss widest path, and a populated precision
# ledger ("numcheck — the precision layer").
JAX_PLATFORMS=cpu python -m ray_lightning_tpu trace llama3-8b \
    --topo v5p-64 --json --fail-on error | python -c '
import json, sys
r = json.load(sys.stdin)
bad = [f for f in r["findings"] if f["rule"].startswith("RLT8")]
assert not bad, f"flagship not numerics-clean: {bad}"
p = r["precision"]
assert p and p["params"], "precision ledger missing/empty"
assert p["loss_widest_dtype"] == "float32", \
    "loss widest path is %r, not f32" % p["loss_widest_dtype"]
print("numcheck gate: flagship RLT8xx-clean, loss path f32, "
      "%d param dtype class(es) in ledger" % len(p["params"]))'

# numcheck examples sweep: every bundled example trace target must be
# free of RLT801 (bf16 accumulation) and RLT805 (scale-free quant
# consume) — the two rules whose regressions are always real numeric
# bugs, not style. One process, all targets (import cost paid once).
# The llama targets are excluded: both resolve to the flagship 8B
# build the gate above already holds to the STRICTER zero-RLT8xx bar,
# and tracing it twice would double the slowest gate for no coverage.
JAX_PLATFORMS=cpu python -c '
from ray_lightning_tpu.analysis.cli import _TRACE_BUILDERS, \
    resolve_trace_target
from ray_lightning_tpu.analysis.costmodel import parse_topology
from ray_lightning_tpu.analysis.tracecheck import audit_step

targets = sorted(
    set(_TRACE_BUILDERS) - {"llama3-8b", "llama_fsdp_example.py"})
topo = parse_topology("v5p-8")
for target in targets:
    module, strategy, batch, label = resolve_trace_target(target, topo)
    rep = audit_step(module, strategy, batch, topology="v5p-8",
                     label=label)
    bad = [f for f in rep.findings if f.rule in ("RLT801", "RLT805")]
    assert not bad, f"{target}: {[f.message for f in bad]}"
print("numcheck sweep: %d example targets free of RLT801/RLT805"
      % len(targets))'

# resilience gate, three supervised CPU-SPMD legs: (1) an injected
# worker kill must auto-resume from the step-cadence checkpoint and
# converge (kill -> classify -> relaunch -> resume, end to end); (2) an
# injected NaN batch must be SKIPPED IN-JIT by the trainguard (zero
# restarts) and converge; (3) an injected parameter bit-flip on rank 1
# must be caught by the SDC fingerprint probe within one cadence, rank
# 1 quarantined, and the rolled-back run must converge — all on a box
# with no accelerator. docs/RESILIENCE.md "trainguard" +
# "fault-injection cookbook".
JAX_PLATFORMS=cpu python -m ray_lightning_tpu supervise --smoke > /dev/null

# observability gate (docs/OBSERVABILITY.md): telemetry=off must train
# bitwise-identically and lower a byte-identical step program; a 2-proc
# CPU-SPMD supervised run with an injected worker kill must produce a
# parseable goodput report whose buckets sum to supervised wall time
# (±5%) with the backoff + replay lost-time classes nonzero; and the
# flagship llama3-8b drift section must emit (structured-skip measured
# placeholder on a box with no TPU) against tracecheck's predicted step
# composition.
JAX_PLATFORMS=cpu python -m ray_lightning_tpu monitor --smoke > /dev/null

# watch/incident gate (docs/OBSERVABILITY.md "watch rules &
# incidents"): a scripted serving run with an INJECTED latency stall
# must fire the built-in ttft_p99 rule EXACTLY ONCE (episode
# semantics: a sustained breach is one incident, not one per poll),
# land a parseable incident record carrying metric evidence (value +
# histogram sketch) and a timeline excerpt of the surrounding events,
# and trigger one profiler CAPTURE-marker evidence capture; and the
# run dir's unified timeline must export valid Chrome-trace JSON with
# events from >= 4 distinct source subsystems ordered by aligned time.
JAX_PLATFORMS=cpu python -m ray_lightning_tpu watch --smoke > /dev/null

# serving gate (docs/SERVING.md): 8 concurrent staggered streams
# (ragged prompts, mixed greedy/temperature/top-k) through the
# continuous-batching engine must decode bitwise-identical to 8
# independent single-stream generate() runs; request churn must compile
# the step exactly ONCE (metrics armed — instrumentation must not
# retrace); with 2 process replicas an injected SIGKILL mid-stream must
# classify -> respawn -> reload weights -> replay the lost streams
# bitwise with the survivor untouched; the METRICS legs
# (docs/OBSERVABILITY.md "serving metrics") must hold: per-replica
# metrics JSONL on the tick cadence with histogram counts equal to the
# completed-request count, EXACT cross-replica histogram merge (counts
# sum, quantiles merge-order independent), a parseable flight.json
# postmortem with final ticks from the SIGKILL drill, and a live
# load_signal(); and the decode step must audit clean under tracecheck
# (no RLT301/RLT303).
JAX_PLATFORMS=cpu python -m ray_lightning_tpu serve --smoke > /dev/null

# autoscale gate (docs/AUTOSCALE.md): under a deterministic scripted
# load ramp (virtual-tick clock — no wall-clock flakiness) the
# closed-loop controller must scale 1 -> 2 on sustained pressure and
# back to 1 on idle, exactly once each (hysteresis + cooldowns honored
# across ~36 polls), record EVERY decision with its signal snapshot in
# a parseable autoscale.jsonl, and complete every stream
# bitwise-identical to single-stream generate() — a graceful drain
# drops nothing; a capacity-oracle probe file must clamp a wanted
# scale-up with the oracle's answer in the ledger; an injected
# SIGKILL-class spawn death mid-scale-up must be classified via the
# resilience taxonomy and retried within budget without dropping the
# scale target; and submit() with every replica draining must defer
# with a structured reason instead of routing onto a stopping replica.
JAX_PLATFORMS=cpu python -m ray_lightning_tpu autoscale --smoke > /dev/null

# loadgen gate (docs/SERVING.md "traffic & SLO classes"): the seeded
# trace format must serialize byte-deterministically (same seed ->
# identical bytes, round-trip stable, wrong version refused); a bursty
# mixed-class trace replayed twice through the REAL driver must
# complete bitwise-identically with IDENTICAL per-class accounting and
# shed sets — best-effort sheds as typed records with retry-after
# hints while latency_critical stays un-shed, holds its TTFT target,
# and preempts lower-class slots; WatchEngine fires exactly one
# shed_best_effort incident; a process-backend leg with a zero
# best-effort queue budget must shed exactly the best-effort arrivals
# and stream the survivors bitwise; churn compiles the step ONCE.
JAX_PLATFORMS=cpu python -m ray_lightning_tpu loadgen --smoke > /dev/null

# elastic gate (docs/ELASTIC.md): an 8-device fsdp=8 CPU-SPMD
# checkpoint must reshard-restore onto a 4-device fsdp=4 mesh with
# every param/opt-state leaf BITWISE-equal to the source, and training
# must continue from it; a supervised 2-proc run with an injected
# worker kill and the same-size relaunch budget exhausted
# (max_restarts=0) must consult its ElasticBudget, reshard onto the
# survivor (world 2 -> 1), resume, and converge — with the world
# change in the reshard ledger and the reshard_s goodput bucket.
JAX_PLATFORMS=cpu python -m ray_lightning_tpu elastic --smoke > /dev/null

# multi-slice (DCN) trace gate: the flagship step on a 2-slice
# deployment must itemize DCN vs ICI bytes as separate tiers, place
# `data` across the slices (HSDP — hierarchical gradient reduction is
# the only cross-slice traffic), and audit clean of errors.
JAX_PLATFORMS=cpu python -m ray_lightning_tpu trace llama3-8b \
    --topo 2xv5p-64 --json --fail-on error \
    | python -c '
import json, sys
r = json.load(sys.stdin)
assert r["ok"], "2xv5p-64 trace failed its own gate"
assert r["topology"]["n_slices"] == 2, "slice count not parsed"
assert r["dcn_bytes_per_step"] > 0, "no DCN tier itemized"
assert not any(f["rule"] == "RLT306" for f in r["findings"]), \
    "data-across-slices placement flagged RLT306"
gib = 1024 ** 3
ici, dcn = r["ici_bytes_per_step"] / gib, r["dcn_bytes_per_step"] / gib
print(f"dcn gate: ICI {ici:.1f} GiB/step + DCN {dcn:.3f} GiB/step, "
      "audits clean")'

# prefetch-overlap smoke: a slow-loader CPU run must show pipeline
# occupancy > 0 (the device prefetcher demonstrably kept batches
# resident ahead of the step) — docs/PERFORMANCE.md. Exit 1 otherwise.
JAX_PLATFORMS=cpu python -m ray_lightning_tpu perf --smoke --steps 25 \
    > /dev/null

# Total wall time of the gate suite. The non-slow pytest tier has a
# 10-minute budget (ROADMAP); this line keeps the format.sh gates on
# the same leash — a creeping gate shows up in every run's output
# instead of only in CI dashboards.
echo "format.sh: all gates passed in ${SECONDS}s"

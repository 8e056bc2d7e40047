"""``python -m ray_lightning_tpu serve`` — the serving front-end + the
format.sh smoke gate.

    python -m ray_lightning_tpu serve example          # inline demo
    python -m ray_lightning_tpu serve example --replicas 2 \\
        --backend process                              # process replicas
    python -m ray_lightning_tpu serve llama3-8b        # static plan+audit
    python -m ray_lightning_tpu serve --smoke          # the gate

``--smoke`` (docs/SERVING.md "acceptance") is the CPU gate format.sh
runs; it fails (exit 1) unless ALL of:

  * 8 concurrent staggered streams (ragged prompts, mixed greedy /
    temperature / top-k sampling, per-request seeds) decode
    **bitwise-identical** to 8 independent single-stream `generate()`
    runs;
  * request churn across the run compiles the engine step exactly ONCE
    (compile-count pinned — no silent recompile-per-request);
  * with 2 process replicas, one injected SIGKILL mid-stream is
    classified, the replica respawns (weights reloaded, step re-warmed
    through the persistent compile cache), the lost streams replay
    bitwise, and the surviving replica's streams are untouched;
  * the METRICS legs (docs/OBSERVABILITY.md "serving metrics"): the
    8-stream run emits per-replica metrics JSONL on the tick cadence
    whose completion-histogram counts equal the completed-request
    count; histogram merge across the 2 process replicas is EXACT
    (counts sum, quantiles from the merged buckets are merge-order
    independent); the injected SIGKILL leaves a parseable
    ``flight.json`` whose dump carries the final ticks + the
    resilience classification; `load_signal()` reports; and the engine
    still compiles exactly once with metrics armed;
  * the decode step audits clean under tracecheck (no RLT301/RLT303);
  * the FUSED paged-attention path (`force_pallas` + interpret on a
    kernel-tiling tiny config): 8 concurrent streams match the
    reference-path engine token for token, churn still compiles once,
    the fused decode step audits clean with RLT307 absent and the
    paged-attention kernel actually present in the trace;
  * the FUSED paged-PREFILL path (ISSUE 15, same kernel-tiling tiny
    discipline): a ragged left-padded prefill group (prefill_batch=2,
    a chunk width that does not divide the slot length) decodes
    token-for-token equal to the reference-lane engine, churn compiles
    once, and the fused step audits clean with ZERO dense paged
    gathers at ANY nesting level (RLT307 + RLT308 absent, the
    paged-prefill kernel present in the trace);
  * the PREFIX-SHARING leg (docs/SERVING.md "prefix cache"): an
    8-stream fleet behind one common system prompt decodes bitwise vs
    per-stream `generate()` with ``shared_block_fraction > 0`` AND a
    prefill-token count STRICTLY below the same fleet served without
    the cache — the shared prefix prefilled exactly once;
  * the SPECULATIVE leg (docs/SERVING.md "speculative decoding"):
    draft+target greedy decode is TOKEN-IDENTICAL to plain greedy
    `generate()`, still at compile-count 1.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile


def add_serve_parser(sub) -> None:
    p = sub.add_parser(
        "serve",
        help="continuous-batching inference engine: run a demo serve, "
             "audit the decode step, or the format.sh smoke gate")
    p.add_argument("preset", nargs="?", default="example",
                   choices=("example", "llama3-8b"),
                   help="example = tiny CPU-served demo; llama3-8b = "
                        "static serve plan + decode-step audit")
    p.add_argument("--smoke", action="store_true",
                   help="gate mode (see module docstring); exit 1 on "
                        "any failed leg")
    p.add_argument("--replicas", type=int, default=1)
    p.add_argument("--backend", choices=("inline", "process"),
                   default="inline")
    p.add_argument("--requests", type=int, default=8,
                   help="synthetic demo requests")
    p.add_argument("--max-new", type=int, default=16)
    p.add_argument("--slots", type=int, default=4,
                   help="engine slot capacity per replica")
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--blocks-per-slot", type=int, default=None,
                   help="default: sized to --seq-budget")
    p.add_argument("--prefill-chunk", type=int, default=32)
    p.add_argument("--prefill-batch", type=int, default=1,
                   help="queued prompts admitted per tick through the "
                        "left-padded batched prefill lane (1 = the "
                        "historical single-slot lane)")
    p.add_argument("--seq-budget", type=int, default=4096,
                   help="llama3-8b plan: per-slot prompt+generation cap")
    p.add_argument("--run-dir", default=None,
                   help="telemetry spans + serving.json land here")
    p.add_argument("--topo", default="v5p-8",
                   help="topology for the decode-step audit")
    p.add_argument("--autotune", metavar="OUT.json", default=None,
                   help="run the block-size sweep for BOTH paged "
                        "kernels on this preset's shape and write the "
                        "winning geometry artifact (serve/sweep.py; "
                        "interpret-mode correctness everywhere, "
                        "wall-clock timing on a real TPU backend, "
                        "structured skip otherwise)")
    p.add_argument("--json", action="store_true", dest="as_json",
                   default=argparse.SUPPRESS)


def _tiny_setup(n_requests: int, max_new: int, seed: int = 1):
    """Deterministic tiny model + ragged mixed-sampling request set —
    the same inputs the smoke legs and the demo serve."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_lightning_tpu.models.llama import Llama, LlamaConfig
    from ray_lightning_tpu.serve.scheduler import Request

    cfg = LlamaConfig.tiny(use_flash=False, dtype=jnp.float32)
    model = Llama(cfg)
    prompts = [
        np.array(jax.random.randint(
            jax.random.key(100 + i), (1, 3 + (i % 5)), 0,
            cfg.vocab_size), dtype=np.int32)
        for i in range(n_requests)
    ]
    params = jax.jit(model.init)(jax.random.key(seed), prompts[0])[
        "params"]
    reqs = []
    for i, p in enumerate(prompts):
        sampled = i % 2 == 1
        reqs.append(Request(
            rid=f"r{i}", prompt=p[0], max_new_tokens=max_new,
            temperature=0.8 if sampled else 0.0,
            top_k=5 if sampled else None, seed=31 + i))
    return cfg, model, params, prompts, reqs


def _references(model, params, prompts, reqs):
    """Independent single-stream generate() runs — the bitwise oracle."""
    import numpy as np

    from ray_lightning_tpu.models.llama import generate

    return {
        r.rid: np.asarray(generate(
            model, params, prompts[i], r.max_new_tokens,
            temperature=r.temperature, top_k=r.top_k, seed=r.seed))[0]
        for i, r in enumerate(reqs)
    }


def _check_outputs(outputs, refs) -> list:
    import numpy as np

    bad = []
    for rid, ref in refs.items():
        got = np.asarray(outputs.get(rid, []))
        if not np.array_equal(got, ref):
            bad.append(rid)
    return bad


def run_smoke(args) -> int:
    """The format.sh gate (module docstring for the leg list), all
    CPU."""
    from ray_lightning_tpu.serve.audit import audit_decode_step
    from ray_lightning_tpu.serve.driver import (
        ReplicaGroupConfig, ServeDriver, save_params_npz,
    )
    from ray_lightning_tpu.serve.engine import EngineConfig

    verdict = {"legs": {}}
    failures = []
    ecfg = EngineConfig(capacity=4, block_size=4, blocks_per_slot=8,
                        prefill_chunk=4)
    cfg, model, params, prompts, reqs = _tiny_setup(8, 8)
    refs = _references(model, params, prompts, reqs)

    # ---- leg 1: inline churn — 8 staggered streams through 4 slots,
    # metrics ARMED (the compile pin below therefore also proves
    # instrumentation does not retrace the step) ----------------------
    with tempfile.TemporaryDirectory(prefix="rlt-serve-smoke1-") as tmp1:
        run1 = os.path.join(tmp1, "run")
        drv = ServeDriver(cfg, params, ReplicaGroupConfig(
            n_replicas=1, backend="inline", engine=ecfg,
            reserve="on_demand", run_dir=run1,
            metrics_flush_every_n_ticks=4))
        res = drv.run(list(reqs))
        bad = _check_outputs(res.outputs, refs)
        compile_ok = res.stats.get("compile_count") in (1, -1)
        verdict["legs"]["inline_churn"] = {
            "bitwise_mismatches": bad,
            "compile_count": res.stats.get("compile_count"),
            "slot_occupancy": round(res.stats.get("slot_occupancy")
                                    or 0, 3),
        }
        if bad:
            failures.append(
                f"inline streams diverge from generate(): {bad}")
        if not compile_ok:
            failures.append(
                f"request churn recompiled the step (metrics armed): "
                f"compile_count={res.stats.get('compile_count')} "
                f"(want 1)")
        verdict["legs"]["metrics_emission"] = _smoke_metrics_emission(
            failures, run1, expected_completions=len(reqs))

    # ---- leg 2: process replicas + injected SIGKILL -------------------
    with tempfile.TemporaryDirectory(prefix="rlt-serve-smoke-") as tmp:
        pp = os.path.join(tmp, "params.npz")
        save_params_npz(params, pp)
        run2 = os.path.join(tmp, "run")
        drv2 = ServeDriver(cfg, pp, ReplicaGroupConfig(
            n_replicas=2, backend="process", engine=ecfg,
            run_dir=run2,
            env={"JAX_PLATFORMS": "cpu"},
            metrics_flush_every_n_ticks=4, flight_persist_every=4))
        # the driver copies requests before stamping, so the same list
        # serves both legs without leaking leg 1's arrival times
        res2 = drv2.run(list(reqs), fault={"replica": 1,
                                           "kill_after_tokens": 6})
        bad2 = _check_outputs(res2.outputs, refs)
        verdict["legs"]["replica_kill"] = {
            "bitwise_mismatches": bad2,
            "restarts": res2.restarts,
            "compile_count": res2.stats.get("compile_count"),
        }
        if bad2:
            failures.append(
                f"streams diverge after replica kill: {bad2}")
        if res2.restarts.get(1, 0) < 1:
            failures.append(
                "the injected SIGKILL did not produce a replica "
                "restart — the drill did not run")
        # surviving replica's requests must have decoded on replica 0
        # without interruption (no restart there)
        if res2.restarts.get(0, 0) != 0:
            failures.append("the SURVIVING replica restarted too")
        verdict["legs"]["metrics_merge"] = _smoke_metrics_merge(
            failures, run2)
        verdict["legs"]["flight_recorder"] = _smoke_flight(
            failures, run2)

    # ---- leg 3: decode step audits clean ------------------------------
    report = audit_decode_step(cfg, ecfg, topology=args.topo)
    rules = sorted({f.rule for f in report.findings})
    verdict["legs"]["audit"] = {"findings": rules,
                                "peak_hbm_bytes": report.peak_hbm_bytes}
    if any(r in ("RLT301", "RLT303") for r in rules):
        failures.append(f"decode step audit findings: {rules}")

    # ---- leg 4: fused paged-attention path ----------------------------
    verdict["legs"]["fused_paged"] = _smoke_fused_leg(failures,
                                                     args.topo)

    # ---- leg 5: fused paged-PREFILL path ------------------------------
    verdict["legs"]["fused_prefill"] = _smoke_fused_prefill_leg(
        failures, args.topo)

    # ---- leg 6: prefix sharing — the common prefix prefills ONCE ------
    verdict["legs"]["prefix_sharing"] = _smoke_prefix_leg(failures)

    # ---- leg 7: speculative decode — greedy token identity ------------
    verdict["legs"]["speculative"] = _smoke_spec_leg(failures)

    verdict["ok"] = not failures
    if failures:
        verdict["failures"] = failures
    print(json.dumps(verdict))
    if failures:
        for f in failures:
            print(f"serve --smoke FAILED: {f}", file=sys.stderr)
        return 1
    return 0


def _smoke_metrics_emission(failures: list, run_dir: str,
                            expected_completions: int) -> dict:
    """Metrics leg A (docs/OBSERVABILITY.md "serving metrics"): the
    8-stream run must leave per-replica metrics JSONL on the tick
    cadence whose completion-histogram counts equal the
    completed-request count, and `load_signal()` must report."""
    from ray_lightning_tpu.serve.driver import load_signal
    from ray_lightning_tpu.telemetry.metrics import (
        metrics_paths, read_metrics,
    )

    tdir = os.path.join(run_dir, "telemetry")
    paths = metrics_paths(tdir)
    leg: dict = {"files": [os.path.basename(p) for p in paths]}
    if not paths:
        failures.append("serving left no per-replica metrics JSONL")
        return leg
    ticks = 0
    completions = 0
    hist_ns = {}
    for p in paths:
        parsed = read_metrics(p)
        ticks += len(parsed["ticks"])
        completions += int(parsed["counters"].get("completions", 0))
        for name, h in parsed["hists"].items():
            hist_ns[name] = hist_ns.get(name, 0) + h.n
    leg.update({"ticks": ticks, "completions": completions,
                "hist_counts": hist_ns})
    if ticks < 1:
        failures.append("metrics JSONL holds no tick samples — the "
                        "tick-cadence flush never fired")
    for name in ("ttft_s", "tpot_s", "queue_wait_s"):
        if hist_ns.get(name) != expected_completions:
            failures.append(
                f"histogram {name} counts {hist_ns.get(name)} != "
                f"completed-request count {expected_completions}")
    if completions != expected_completions:
        failures.append(
            f"completions counter {completions} != "
            f"{expected_completions}")
    sig = load_signal(run_dir)
    leg["load_signal"] = {k: sig.get(k) for k in
                          ("available", "queue_depth_p50",
                           "occupancy", "pressure")}
    if not sig.get("available"):
        failures.append("load_signal() reports unavailable on a run "
                        "that just served")
    return leg


def _smoke_metrics_merge(failures: list, run_dir: str) -> dict:
    """Metrics leg B: histogram merge across the 2 process replicas
    must be EXACT — counts sum as integers, and the p50/p95/p99 read
    from merged buckets is identical whichever merge order produced
    them."""
    from ray_lightning_tpu.telemetry.metrics import (
        merge_histograms, metrics_paths, read_metrics,
    )

    tdir = os.path.join(run_dir, "telemetry")
    paths = metrics_paths(tdir)
    leg: dict = {"files": [os.path.basename(p) for p in paths]}
    parts = []
    for p in paths:
        h = read_metrics(p)["hists"].get("ttft_s")
        if h is not None:
            parts.append(h)
    leg["parts"] = len(parts)
    if len(parts) < 2:
        failures.append(
            "metrics merge leg needs ttft_s histograms from >= 2 "
            f"replica files, found {len(parts)}")
        return leg
    fwd = merge_histograms(parts)
    rev = merge_histograms(list(reversed(parts)))
    leg["merged_n"] = fwd.n
    leg["sum_of_parts"] = sum(h.n for h in parts)
    leg["p99_fwd"] = fwd.quantile(0.99)
    leg["p99_rev"] = rev.quantile(0.99)
    if fwd.n != sum(h.n for h in parts):
        failures.append(
            f"merged histogram count {fwd.n} != sum of per-replica "
            f"counts {sum(h.n for h in parts)} — merge is not exact")
    if fwd.counts != rev.counts or any(
            fwd.quantile(q) != rev.quantile(q)
            for q in (0.5, 0.95, 0.99)):
        failures.append("histogram merge is order-dependent — "
                        "quantiles from merged buckets must not care "
                        "which replica's file merged first")
    return leg


def _smoke_flight(failures: list, run_dir: str) -> dict:
    """Metrics leg C: the injected SIGKILL must leave a parseable
    ``flight.json`` whose dump carries the dead replica's final ticks
    and the resilience classification the driver stamped on."""
    path = os.path.join(run_dir, "flight.json")
    leg: dict = {"path": path}
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as exc:
        failures.append(f"no parseable flight.json after the SIGKILL "
                        f"drill: {type(exc).__name__}: {exc}")
        return leg
    dumps = doc.get("dumps") or []
    leg["dumps"] = len(dumps)
    if not dumps:
        failures.append("flight.json holds no dumps")
        return leg
    dump = dumps[0]
    events = dump.get("events") or []
    tick_events = [e for e in events if e.get("kind") == "tick"]
    leg.update({
        "replica": dump.get("replica"),
        "events": len(events),
        "tick_events": len(tick_events),
        "last_tick": tick_events[-1].get("tick") if tick_events
        else None,
        "death": dump.get("death"),
    })
    if not tick_events:
        failures.append("flight dump carries no tick events — the "
                        "postmortem has no final ticks to read")
    death = dump.get("death") or {}
    if not death.get("kind"):
        failures.append("flight dump is missing the resilience "
                        "classification (death.kind)")
    return leg


def _fused_leg_harness(ecfg, *, prompt_key: int, param_key: int,
                       rid_prefix: str, temp: float, top_k: int,
                       seed_base: int, prompt_floor: int,
                       prompt_mod: int):
    """Shared harness of the two fused smoke legs: the kernel-TILING
    tiny model (head_dim 64, GQA 2:1 — the main legs' tiny model has
    head_dim 16, which both kernels correctly refuse; dispatch honesty
    is part of what the legs prove), a ragged mixed-sampling request
    set, one reference-lane run, one force_pallas run. Returns
    ``(cfg, eng, out_ref, out_fused, mismatched)`` — the legs keep
    their own audit verdicts, but the run discipline (reserve policy,
    churn shape, stream comparison) cannot drift between them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_lightning_tpu.models.llama import Llama, LlamaConfig
    from ray_lightning_tpu.ops import dispatch
    from ray_lightning_tpu.serve.engine import DecodeEngine
    from ray_lightning_tpu.serve.scheduler import Request, Scheduler

    cfg = LlamaConfig(vocab_size=256, dim=128, n_layers=2, n_heads=2,
                      n_kv_heads=1, hidden_dim=256, max_seq_len=128,
                      remat=False, dtype=jnp.float32)
    model = Llama(cfg)
    prompts = [
        np.array(jax.random.randint(
            jax.random.key(prompt_key + i),
            (prompt_floor + (i % prompt_mod),), 0,
            cfg.vocab_size), dtype=np.int32)
        for i in range(8)
    ]
    params = jax.jit(model.init)(jax.random.key(param_key),
                                 prompts[0][None])["params"]

    def run(engine):
        sched = Scheduler(engine, reserve="on_demand")
        pend = [Request(rid=f"{rid_prefix}{i}", prompt=p,
                        max_new_tokens=8,
                        temperature=temp if i % 2 else 0.0,
                        top_k=top_k if i % 2 else None,
                        seed=seed_base + i)
                for i, p in enumerate(prompts)]
        out = {}
        while sched.busy() or pend:
            if pend:
                sched.submit(pend.pop(0))
            for comp in sched.tick():
                out[comp.rid] = comp.tokens
        return out

    ref_engine = DecodeEngine(model, params, ecfg, use_pallas=False)
    out_ref = run(ref_engine)
    with dispatch.force_pallas():
        eng = DecodeEngine(model, params, ecfg)
        out_fused = run(eng) if (eng.fused or eng.fused_prefill) \
            else {}
    mismatched = [rid for rid in out_ref
                  if out_fused.get(rid) != out_ref[rid]]
    return cfg, eng, out_ref, out_fused, mismatched


def _smoke_fused_leg(failures: list, topo: str) -> dict:
    """The fused-path smoke leg: the paged-attention kernel (interpret
    mode under `force_pallas`) must serve 8 concurrent streams token-
    for-token equal to the reference-path engine, compile once across
    churn, and audit clean (RLT307 absent — the dense view is gone)."""
    from ray_lightning_tpu.serve.audit import audit_decode_step
    from ray_lightning_tpu.serve.engine import EngineConfig

    ecfg = EngineConfig(capacity=4, block_size=8, blocks_per_slot=4,
                        prefill_chunk=4, prefill_batch=2)
    cfg, eng, out_ref, out_fused, mismatched = _fused_leg_harness(
        ecfg, prompt_key=300, param_key=7, rid_prefix="f", temp=0.8,
        top_k=5, seed_base=61, prompt_floor=3, prompt_mod=5)
    fused_selected = eng.fused
    # ONE trace serves both verdicts: the audit's findings (RLT307
    # absent here <=> no dense decode gather, since the shape tiles)
    # and the kernel fingerprint the auditor recorded walking it
    report = audit_decode_step(cfg, ecfg, topology=topo, fused=True,
                               label="fused smoke decode step")
    rules = sorted({f.rule for f in report.findings})
    kernel_in_trace = any("paged_attention" in k
                          for k in report.pallas_kernels)
    leg = {
        "fused_selected": fused_selected,
        "stream_mismatches": mismatched,
        "compile_count": eng.compile_count,
        "audit_findings": rules,
        "kernel_in_trace": kernel_in_trace,
        "attention_path": eng.attention_path,
    }
    if not fused_selected:
        failures.append("force_pallas did not select the fused paged-"
                        "attention path for a kernel-tiling shape")
        return leg
    if mismatched:
        failures.append(
            f"fused-path streams diverge from the reference path: "
            f"{mismatched}")
    if eng.compile_count not in (1, -1):
        failures.append(
            f"fused-path churn recompiled the step: compile_count="
            f"{eng.compile_count} (want 1)")
    if any(r in ("RLT301", "RLT303", "RLT307") for r in rules):
        failures.append(f"fused decode step audit findings: {rules}")
    if not kernel_in_trace:
        failures.append("the paged-attention kernel is absent from the "
                        "fused trace — the fused lane fell back to the "
                        "gathering reference op")
    return leg


def _smoke_fused_prefill_leg(failures: list, topo: str) -> dict:
    """The fused-PREFILL smoke leg (ISSUE 15): on the kernel-tiling
    tiny config, a RAGGED left-padded prefill group (prefill_batch=2
    over prompts of assorted lengths, with a chunk width that does not
    divide the slot length — the PR 8 tail-window class rides along)
    must decode token-for-token equal to the reference-lane engine,
    churn must compile once, and the fused step must audit clean with
    ZERO dense paged gathers at ANY nesting level — both the decode
    lane's capacity-wide view and the prefill lane's cond-nested
    group view are gone (`trace_decode_step` meta is the evidence;
    RLT307/RLT308 absent is the rule-level restatement)."""
    from ray_lightning_tpu.serve.audit import (
        audit_decode_step, trace_decode_step,
    )
    from ray_lightning_tpu.serve.engine import EngineConfig

    # chunk 12 does not divide the 32-token slot (the scheduler's
    # slid-back tail window is exercised on the fused lane too) while
    # still tiling (12 q rows x 2 heads = 24, sublane-aligned; chunk 6
    # would be refused by `paged_prefill_shapes_supported`)
    ecfg = EngineConfig(capacity=4, block_size=8, blocks_per_slot=4,
                        prefill_chunk=12, prefill_batch=2)
    cfg, eng, out_ref, out_fused, mismatched = _fused_leg_harness(
        ecfg, prompt_key=500, param_key=9, rid_prefix="pf", temp=0.6,
        top_k=4, seed_base=91, prompt_floor=2, prompt_mod=7)
    prefill_selected = eng.fused_prefill
    # ONE trace serves all three verdicts: the gather evidence in its
    # meta, the kernel fingerprint, and the audit (fed the same pair
    # via `traced=` — never a second full trace of the same step)
    traced = trace_decode_step(cfg, ecfg, fused=True)
    report = audit_decode_step(cfg, ecfg, topology=topo, fused=True,
                               label="fused smoke prefill step",
                               traced=traced)
    meta = traced[1]
    rules = sorted({f.rule for f in report.findings})
    kernel_in_trace = any("paged_prefill" in k
                          for k in meta["pallas_kernels"])
    leg = {
        "prefill_selected": prefill_selected,
        "stream_mismatches": mismatched,
        "compile_count": eng.compile_count,
        "audit_findings": rules,
        "prefill_kernel_in_trace": kernel_in_trace,
        "dense_paged_gathers": len(meta["dense_paged_gathers"]),
        "prefill_paged_gathers": len(meta["prefill_paged_gathers"]),
        "prefill_path": eng.prefill_path,
    }
    if not prefill_selected:
        failures.append("force_pallas did not select the fused paged-"
                        "prefill path for a kernel-tiling shape")
        return leg
    if mismatched:
        failures.append(
            f"fused-prefill streams diverge from the reference path: "
            f"{mismatched}")
    if eng.compile_count not in (1, -1):
        failures.append(
            f"fused-prefill churn recompiled the step: compile_count="
            f"{eng.compile_count} (want 1)")
    if any(r in ("RLT301", "RLT303", "RLT307", "RLT308")
           for r in rules):
        failures.append(f"fused prefill step audit findings: {rules}")
    if meta["dense_paged_gathers"] or meta["prefill_paged_gathers"]:
        failures.append(
            f"the fused step still materializes a dense paged gather "
            f"(top-level {len(meta['dense_paged_gathers'])}, nested "
            f"{len(meta['prefill_paged_gathers'])}) — the kernels did "
            f"not retire the views")
    if not kernel_in_trace:
        failures.append("the paged-prefill kernel is absent from the "
                        "fused trace — the prefill lane fell back to "
                        "the gathering reference op")
    return leg


def _smoke_prefix_leg(failures: list) -> dict:
    """The prefix-sharing smoke leg: an 8-stream fleet behind ONE
    common system prompt decodes bitwise vs per-stream `generate()`,
    with the shared prefix prefilled exactly once — the cached run's
    prefill-token count must be STRICTLY below the same fleet served
    without the cache, and ``shared_block_fraction`` must be > 0."""
    import jax
    import numpy as np

    from ray_lightning_tpu.models.llama import generate
    from ray_lightning_tpu.serve.engine import DecodeEngine, EngineConfig
    from ray_lightning_tpu.serve.scheduler import Request, Scheduler

    cfg, model, params, _, _ = _tiny_setup(1, 1)
    sys_prompt = np.asarray(jax.random.randint(
        jax.random.key(7), (9,), 0, cfg.vocab_size), np.int32)
    prompts = []
    for i in range(8):
        tail = np.asarray(jax.random.randint(
            jax.random.key(200 + i), (2 + i % 3,), 0, cfg.vocab_size),
            np.int32)
        prompts.append(np.concatenate([sys_prompt, tail]))
    ecfg = EngineConfig(capacity=4, block_size=4, blocks_per_slot=8,
                        prefill_chunk=4)

    def fleet(prefix_cache: bool):
        eng = DecodeEngine(model, params, ecfg)
        eng.warmup()
        sched = Scheduler(eng, prefix_cache=prefix_cache)
        for i, p in enumerate(prompts):
            sched.submit(Request(rid=f"p{i}", prompt=p,
                                 max_new_tokens=6, seed=41 + i))
        outputs = {}
        while sched.busy():
            for comp in sched.tick():
                outputs[comp.rid] = list(comp.tokens)
        return outputs, sched, eng

    outputs, sched, eng = fleet(prefix_cache=True)
    _, sched_cold, _ = fleet(prefix_cache=False)
    bad = []
    for i, p in enumerate(prompts):
        ref = np.asarray(generate(model, params, p[None], 6,
                                  temperature=0.0, seed=41 + i))[0]
        if not np.array_equal(ref, np.asarray(outputs.get(f"p{i}", []))):
            bad.append(f"p{i}")
    leg = {
        "bitwise_mismatches": bad,
        "shared_block_fraction": round(sched.shared_block_fraction, 4),
        "prefill_tokens_issued": sched.prefill_tokens_issued,
        "prefill_tokens_no_sharing": sched_cold.prefill_tokens_issued,
        "compile_count": eng.compile_count,
    }
    if bad:
        failures.append(
            f"prefix-shared streams diverge from generate(): {bad}")
    if sched.shared_block_fraction <= 0.0:
        failures.append(
            "the common system prompt produced no shared blocks "
            f"(shared_block_fraction="
            f"{sched.shared_block_fraction})")
    if not (sched.prefill_tokens_issued
            < sched_cold.prefill_tokens_issued):
        failures.append(
            f"prefix cache did not reduce prefill work: "
            f"{sched.prefill_tokens_issued} issued vs "
            f"{sched_cold.prefill_tokens_issued} without sharing")
    if eng.compile_count not in (1, -1):
        failures.append(
            f"prefix-shared churn recompiled the step: compile_count="
            f"{eng.compile_count} (want 1)")
    return leg


def _smoke_spec_leg(failures: list) -> dict:
    """The speculative smoke leg: draft+target greedy decode must be
    TOKEN-IDENTICAL to plain greedy `generate()` — the accept/reject
    rule is exact, never approximate — still at compile-count 1."""
    import jax
    import numpy as np

    from ray_lightning_tpu.models.llama import Llama, generate
    from ray_lightning_tpu.serve.engine import (
        DecodeEngine, DraftConfig, EngineConfig,
    )
    from ray_lightning_tpu.serve.scheduler import Request, Scheduler

    cfg, model, params, prompts, _ = _tiny_setup(6, 6)
    # an INDEPENDENT draft (same architecture, different weights) —
    # acceptance is partial, so the rejection path runs for real
    draft = Llama(cfg)
    draft_params = jax.jit(draft.init)(jax.random.key(97),
                                       prompts[0])["params"]
    ecfg = EngineConfig(capacity=4, block_size=4, blocks_per_slot=8,
                        prefill_chunk=4, draft=DraftConfig(k=3))
    eng = DecodeEngine(model, params, ecfg, draft_model=draft,
                       draft_params=draft_params)
    eng.warmup()
    sched = Scheduler(eng)
    for i, p in enumerate(prompts):
        sched.submit(Request(rid=f"s{i}", prompt=p[0],
                             max_new_tokens=6, seed=61 + i))
    outputs = {}
    while sched.busy():
        for comp in sched.tick():
            outputs[comp.rid] = list(comp.tokens)
    bad = []
    for i, p in enumerate(prompts):
        ref = np.asarray(generate(model, params, p, 6,
                                  temperature=0.0, seed=61 + i))[0]
        if not np.array_equal(ref, np.asarray(outputs.get(f"s{i}", []))):
            bad.append(f"s{i}")
    leg = {
        "bitwise_mismatches": bad,
        "k": ecfg.draft.k,
        "accepted_tokens_per_step": round(
            sched.accepted_tokens_per_step, 4),
        "compile_count": eng.compile_count,
    }
    if bad:
        failures.append(
            f"speculative greedy decode diverges from plain greedy: "
            f"{bad}")
    if sched.accepted_tokens_per_step < 1.0:
        failures.append(
            f"speculative decode emitted fewer than one token per "
            f"slot-step ({sched.accepted_tokens_per_step}) — the "
            "bonus-token accounting is broken")
    if eng.compile_count not in (1, -1):
        failures.append(
            f"speculative churn recompiled the step: compile_count="
            f"{eng.compile_count} (want 1)")
    return leg


def _run_example(args) -> int:
    import contextlib

    from ray_lightning_tpu.serve.driver import (
        ReplicaGroupConfig, ServeDriver, save_params_npz,
    )
    from ray_lightning_tpu.serve.engine import EngineConfig

    bps = args.blocks_per_slot or 8
    ecfg = EngineConfig(capacity=args.slots, block_size=args.block_size,
                        blocks_per_slot=bps,
                        prefill_chunk=args.prefill_chunk,
                        prefill_batch=args.prefill_batch)
    cfg, model, params, prompts, reqs = _tiny_setup(
        args.requests, args.max_new)
    with contextlib.ExitStack() as stack:
        if args.backend == "process":
            tmp = stack.enter_context(
                tempfile.TemporaryDirectory(prefix="rlt-serve-"))
            pp = os.path.join(tmp, "params.npz")
            save_params_npz(params, pp)
            params_arg = pp
        else:
            params_arg = params
        # process replicas inherit this process's platform; on a TPU
        # host the driver refuses them here (this process built the
        # weights with jax and holds the chips): use inline replicas
        drv = ServeDriver(cfg, params_arg, ReplicaGroupConfig(
            n_replicas=args.replicas, backend=args.backend, engine=ecfg,
            run_dir=args.run_dir))
        res = drv.run(reqs)
    ttfts = sorted(m["ttft_s"] for m in res.meta.values())
    line = {
        "preset": "example",
        "n_requests": len(reqs),
        "decode_tokens_per_s": round(
            res.stats["decode_tokens_per_s"], 2),
        "slot_occupancy": res.stats.get("slot_occupancy"),
        "ttft_p50_s": round(ttfts[len(ttfts) // 2], 4),
        "ttft_max_s": round(ttfts[-1], 4),
        "compile_count": res.stats.get("compile_count"),
        "restarts": res.restarts,
    }
    if getattr(args, "as_json", False):
        print(json.dumps(line))
    else:
        print(f"served {line['n_requests']} requests: "
              f"{line['decode_tokens_per_s']} tok/s decode, "
              f"occupancy {line['slot_occupancy']:.2f}, "
              f"TTFT p50 {line['ttft_p50_s']}s")
        if args.run_dir:
            print(f"telemetry: {args.run_dir} "
                  f"(python -m ray_lightning_tpu report {args.run_dir})")
    return 0


def _run_flagship(args) -> int:
    """llama3-8b: no weights ship with the repo, so this is the STATIC
    leg — the serve plan + decode-step audit for the flagship config —
    honest about what it is (a box with weights runs `example`-style
    serving through the same driver)."""
    import jax.numpy as jnp

    from ray_lightning_tpu.models.llama import LlamaConfig
    from ray_lightning_tpu.serve.audit import (
        audit_decode_step, format_serve_summary, serve_memory_summary,
    )
    from ray_lightning_tpu.serve.engine import EngineConfig

    cfg = LlamaConfig.llama3_8b(max_seq_len=args.seq_budget,
                                dtype=jnp.bfloat16)
    bps = args.blocks_per_slot or -(-args.seq_budget // args.block_size)
    ecfg = EngineConfig(capacity=args.slots, block_size=args.block_size,
                        blocks_per_slot=bps,
                        prefill_chunk=max(args.prefill_chunk, 128),
                        prefill_batch=args.prefill_batch)
    summary = serve_memory_summary(cfg, ecfg)
    fused = summary["attention_path"] == "paged-pallas"
    report = audit_decode_step(cfg, ecfg, topology=args.topo,
                               label="llama3-8b serve", fused=fused)
    rules = sorted({f.rule for f in report.findings})
    if getattr(args, "as_json", False):
        print(json.dumps({
            "preset": "llama3-8b", "plan": summary,
            "audit": {"findings": rules,
                      "attention_path": summary["attention_path"],
                      "peak_hbm_bytes": report.peak_hbm_bytes,
                      "hbm_budget_bytes": report.hbm_budget_bytes},
        }))
    else:
        print(format_serve_summary(summary))
        print(f"decode-step audit ({args.topo}, "
              f"{summary['attention_path']}): "
              f"{'clean' if not rules else rules}, liveness peak "
              f"{report.peak_hbm_bytes / 1024**3:.2f} GiB")
        print("note: static leg — no weights ship with the repo; with "
              "a params .npz this config serves through the same "
              "driver (docs/SERVING.md)")
    bad = summary["fits"] is False or any(
        r in ("RLT301", "RLT303") for r in rules)
    return 1 if bad else 0


def _run_autotune(args) -> int:
    """``serve <preset> --autotune out.json``: sweep block_size /
    blocks_per_slot for BOTH paged kernels on the preset's shape and
    write the artifact `sweep.apply_autotune` consumes
    (docs/SERVING.md "block-size autotune")."""
    from ray_lightning_tpu.serve.engine import EngineConfig
    from ray_lightning_tpu.serve.sweep import (
        save_artifact, sweep_paged_kernels,
    )

    if args.preset == "llama3-8b":
        import jax.numpy as jnp

        from ray_lightning_tpu.models.llama import LlamaConfig

        cfg = LlamaConfig.llama3_8b(max_seq_len=args.seq_budget,
                                    dtype=jnp.bfloat16)
        bps = args.blocks_per_slot or -(-args.seq_budget
                                        // args.block_size)
        ecfg = EngineConfig(capacity=args.slots,
                            block_size=args.block_size,
                            blocks_per_slot=bps,
                            prefill_chunk=max(args.prefill_chunk, 128),
                            prefill_batch=args.prefill_batch)
    else:
        # the demo sweeps a KERNEL-TILING tiny shape (head_dim 64, GQA
        # 2:1 — the fused smoke leg's config): the main example model's
        # head_dim 16 is refused by both kernels, which would make
        # every candidate fail correctness vacuously
        import jax.numpy as jnp

        from ray_lightning_tpu.models.llama import LlamaConfig

        cfg = LlamaConfig(vocab_size=256, dim=128, n_layers=2,
                          n_heads=2, n_kv_heads=1, hidden_dim=256,
                          max_seq_len=128, remat=False,
                          dtype=jnp.float32)
        ecfg = EngineConfig(capacity=args.slots,
                            block_size=args.block_size,
                            blocks_per_slot=args.blocks_per_slot or 8,
                            prefill_chunk=args.prefill_chunk,
                            prefill_batch=args.prefill_batch)
    artifact = sweep_paged_kernels(cfg, ecfg, topology=args.topo)
    save_artifact(artifact, args.autotune)
    if getattr(args, "as_json", False):
        print(json.dumps(artifact))
    else:
        n_ok = sum(1 for r in artifact["results"]
                   if r["decode"].get("ok") and r["prefill"].get("ok"))
        print(f"swept {len(artifact['results'])} geometries "
              f"({n_ok} passed both kernels' correctness) on backend "
              f"{artifact['backend']}")
        if artifact["winner"]:
            print(f"winner ({artifact['winner_source']}): block_size="
                  f"{artifact['winner']['block_size']} "
                  f"blocks_per_slot="
                  f"{artifact['winner']['blocks_per_slot']} "
                  f"-> {args.autotune}")
        else:
            print(f"no candidate passed correctness -> "
                  f"{args.autotune} (winner: null)")
    return 0 if artifact["winner"] else 1


def run_serve(args) -> int:
    if args.smoke:
        return run_smoke(args)
    if args.autotune:
        return _run_autotune(args)
    if args.preset == "llama3-8b":
        return _run_flagship(args)
    return _run_example(args)

"""``python -m ray_lightning_tpu`` — environment/topology doctor + planner.

Pod-debugging UX the reference delegated to Ray's dashboard: one command
answers "what does THIS process see" — backend, process/device topology
(the rank helpers of SURVEY §5.8), per-device kind/slice, and optionally
a bare-matmul throughput probe that makes external contention on shared
chips visible (the same throughput-bound probe bench.py embeds in its
JSON, utils/probe.py).

    python -m ray_lightning_tpu            # topology, no device touch
    python -m ray_lightning_tpu --probe    # + matmul TFLOP/s
    python -m ray_lightning_tpu --json     # machine-readable

``plan`` runs the pre-flight memory planner (parallel/plan.py) with no
devices touched at all — size a model against a proposed mesh and chip
before queueing for hardware:

    python -m ray_lightning_tpu plan --preset llama3-8b \\
        --fsdp 64 --batch 64 --seq 8192 --device-kind "TPU v5p"

``lint`` runs shardcheck (analysis/): the pre-compile static analyzer
for sharding plans and jitted training code — mesh-axis typos, host
transfers inside training_step, Python RNG / wallclock / print in
traced code, unhashable static args. Zero hardware, target files are
parsed, never executed:

    python -m ray_lightning_tpu lint ray_lightning_tpu/models
    python -m ray_lightning_tpu lint my_project.module --json

``perf`` measures the hot-loop overlap machinery on THIS box (CPU-safe):
device-prefetch speedup with a calibrated synthetic slow loader, plus
the AOT warm-start compile metrics against the persistent compile
cache. ``--smoke`` is the format.sh gate (pipeline occupancy must be
> 0):

    python -m ray_lightning_tpu perf --smoke
    python -m ray_lightning_tpu perf --steps 80 --depth 4

``supervise`` runs a distributed fit under the resilience supervisor
(resilience/supervisor.py, docs/RESILIENCE.md): transient failures
restart the worker group and resume from the latest valid checkpoint;
trainguard corruption escalations roll back to the last blessed one.
``--smoke`` is the CPU fault-injection convergence gate format.sh runs
(worker kill + the trainguard legs: injected NaN must skip in-jit,
injected parameter bit-flip must quarantine the rank):

    python -m ray_lightning_tpu supervise --smoke
    python -m ray_lightning_tpu supervise my_project.jobs:make_job \\
        --processes 4 --max-restarts 3

``serve`` runs the continuous-batching inference engine (serve/,
docs/SERVING.md): a paged-KV decode engine multiplexed over replica
groups, with ``--smoke`` as the format.sh gate (8 concurrent streams
bitwise-identical to single-stream generate(), churn compiles once, an
injected replica SIGKILL auto-recovers, decode step audits clean):

    python -m ray_lightning_tpu serve example --replicas 2
    python -m ray_lightning_tpu serve llama3-8b --topo v5p-8
    python -m ray_lightning_tpu serve --smoke

``elastic`` runs the elastic-training smoke gate (elastic/,
docs/ELASTIC.md): an 8-device checkpoint must reshard-restore onto a
4-device mesh bitwise and keep training, and a supervised 2-process
run whose retry budget refuses a same-size relaunch must shrink onto
the survivor world and converge:

    python -m ray_lightning_tpu elastic --smoke

``autoscale`` runs the closed-loop serving autoscaler (autoscale/,
docs/AUTOSCALE.md): a pressure-band policy polling the serving load
signal and actuating replica count through the ServeDriver scaling
seams, with every decision in an append-only ledger. ``--smoke`` is
the format.sh gate (scripted ramp scales 1 -> 2 -> 1 with bitwise
streams, a capacity clamp + SIGKILL-absorbing spawn drill, and the
all-draining submit deferral):

    python -m ray_lightning_tpu autoscale
    python -m ray_lightning_tpu autoscale --smoke

``loadgen`` runs the trace-driven load harness (loadgen/,
docs/SERVING.md "traffic & SLO classes"): seeded Poisson/bursty-MMPP
workload traces with heavy-tailed lengths and a traffic-class mix,
generated or recorded as versioned JSONL and replayed bitwise against
the real serving stack with priority/SLO-aware scheduling armed.
``--smoke`` is the format.sh gate (byte-deterministic traces, a
bursty mixed-class replay that sheds best-effort with typed records
while latency-critical meets its TTFT SLO, a class-scoped incident,
zero silent drops, compile count pinned at 1 on both backends):

    python -m ray_lightning_tpu loadgen --out trace.jsonl --seed 7
    python -m ray_lightning_tpu loadgen --trace trace.jsonl
    python -m ray_lightning_tpu loadgen --smoke

``report`` / ``monitor`` read the telemetry a run left behind
(telemetry/, docs/OBSERVABILITY.md): the goodput classification of
supervised wall time, per-rank span timelines, and — with
``--preset/--topo`` — the drift section joining the measured timeline
against tracecheck's prediction. ``monitor --smoke`` is the format.sh
observability gate (telemetry=off byte-identical pin, fault-injected
goodput report sums to wall, flagship drift section emits):

    python -m ray_lightning_tpu report rlt_logs --preset llama3-8b \\
        --topo v5p-64
    python -m ray_lightning_tpu monitor rlt_logs --follow
    python -m ray_lightning_tpu monitor --smoke

``timeline`` merges EVERY evidence ledger a run dir holds — spans,
goodput attempts, serving metrics ticks, flight rings, autoscale
decisions, reshards, incidents — into one causally-ordered stream
(telemetry/timeline.py, docs/OBSERVABILITY.md "unified timeline");
``--chrome`` exports Chrome-trace/Perfetto JSON so the whole run opens
as one trace:

    python -m ray_lightning_tpu timeline rlt_logs
    python -m ray_lightning_tpu timeline rlt_logs --chrome trace.json

``watch`` evaluates the declarative SLO rules (telemetry/watch.py:
ttft_p99, goodput_fraction, queue pressure, guard streaks, restart
rate) over a run dir's persisted evidence; a sustained breach appends
a self-documenting record to incidents.jsonl (metric evidence + a
timeline excerpt) and actuates the evidence hooks (profiler CAPTURE
marker, forced flight persist). ``--smoke`` is the format.sh gate (an
injected serving latency stall must fire the ttft rule exactly once
and the run's timeline must export as a valid multi-source Chrome
trace):

    python -m ray_lightning_tpu watch rlt_logs --follow
    python -m ray_lightning_tpu watch --smoke

Exit status: 0 when the plan fits, 1 when it does not, 2 when the
configuration is invalid (e.g. a global batch not divisible by the
data-parallel degree — refused rather than planned wrong; the error goes
to stderr, or an {"error": ...} object with --json).
"""
from __future__ import annotations

import argparse
import json
import sys


def collect(probe: bool = False) -> dict:
    import jax

    devices = jax.devices()
    info = {
        "package": "ray_lightning_tpu 0.1.0",
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": jax.local_device_count(),
        "global_devices": jax.device_count(),
        "devices": [
            {
                "id": d.id,
                "kind": d.device_kind,
                "platform": d.platform,
                "slice_index": getattr(d, "slice_index", None),
            }
            for d in devices[:16]
        ],
    }
    if len(devices) > 16:
        info["devices_truncated"] = len(devices) - 16
    if probe:
        from ray_lightning_tpu.utils.probe import (
            PEAK_TFLOPS,
            matmul_tflops,
        )

        info["probe_matmul_tflops"] = round(matmul_tflops(), 1)
        # None off the table (a CPU smoke run): there is no peak to
        # compare the probe against, and none is assumed
        info["peak_tflops"] = PEAK_TFLOPS.get(devices[0].device_kind)
    return info


def _plan_invalid(msg: str, as_json: bool) -> int:
    """The documented exit-status contract: every invalid configuration
    exits 2 with a structured error, distinguishable by scripted
    consumers from the meaningful exit-1 'does not fit' verdict."""
    if as_json:
        print(json.dumps({"error": msg}))
    else:
        print(f"error: {msg}", file=sys.stderr)
    return 2


def _plan_trace_section(args, module_factory, strategy_factory,
                        n_devices: int, global_batch: int):
    """tracecheck the planned step (jaxpr-level collective/HBM audit,
    analysis/tracecheck.py) — the plan's byte math says whether the
    weights FIT; this section says what the step will DO: ICI bytes and
    estimated peak HBM. Degrades to a trace_error field rather than
    failing the plan (the plan verdict must survive an audit bug)."""
    import numpy as np

    try:
        from ray_lightning_tpu.analysis.costmodel import topology_for_kind
        from ray_lightning_tpu.analysis.tracecheck import audit_step

        topo = topology_for_kind(args.device_kind, n_devices,
                                 hbm_bytes=args.hbm_bytes)
        report = audit_step(
            module_factory(), strategy_factory(),
            {"tokens": np.zeros((global_batch, args.seq + 1), np.int32)},
            topology=topo, label=f"{args.preset} plan")
        counts = {"error": 0, "warning": 0, "note": 0}
        for f in report.findings:
            counts[f.severity] += 1
        return {
            "ici_bytes_per_step": report.ici_bytes_per_step,
            "ici_time_us": round(report.ici_time_us, 1),
            "peak_hbm_bytes": report.peak_hbm_bytes,
            "hbm_budget_bytes": report.hbm_budget_bytes,
            "fits": report.fits,
            "finding_counts": counts,
            "findings": [f.to_dict() for f in report.findings],
            **({"precision": report.precision}
               if getattr(args, "precision", False) else {}),
        }
    except Exception as exc:  # noqa: BLE001 — advisory section only
        return {"trace_error": f"{type(exc).__name__}: {str(exc)[:300]}"}


def _print_trace_section(trace: dict) -> None:
    if "trace_error" in trace:
        print(f"tracecheck: unavailable ({trace['trace_error']})")
        return
    gib = 1024**3
    print(f"tracecheck: ICI {trace['ici_bytes_per_step'] / gib:.2f} "
          f"GiB/step (~{trace['ici_time_us'] / 1e3:.1f} ms serialized), "
          f"est. peak HBM {trace['peak_hbm_bytes'] / gib:.2f} GiB vs "
          f"budget {trace['hbm_budget_bytes'] / gib:.2f} GiB "
          f"({'fits' if trace['fits'] else 'DOES NOT FIT'})")
    for f in trace["findings"]:
        print(f"  {f['severity']} {f['rule']} ({f['name']}): "
              f"{f['message']}")
    _print_precision_ledger(trace.get("precision"))


def _print_precision_ledger(prec) -> None:
    """``plan --precision``: the per-dtype-class byte ledger numcheck
    fills on every TraceReport (analysis/numcheck.py)."""
    if not prec:
        return
    mib = 1024**2

    def _cls(name):
        by = prec.get(name) or {}
        if not by:
            return "-"
        return ", ".join(f"{dt} {b / mib:.1f} MiB"
                         for dt, b in sorted(by.items(),
                                             key=lambda kv: -kv[1]))
    print("  precision ledger (per device):")
    for name in ("params", "opt_state", "activations", "kv_pool"):
        print(f"    {name:<12} {_cls(name)}")
    print(f"    loss widest-path dtype: "
          f"{prec.get('loss_widest_dtype') or 'n/a'}")


def _run_serve_plan(args) -> int:
    """``plan --serve``: the serving replica's HBM story (no optimizer
    — weights + paged KV pool + the attention path's gathered view +
    carried logits) with the decode-step tracecheck section. The
    attention path is auto-selected by shape: when the fused
    paged-attention kernel tiles the config the plan prices the fused
    path and states the per-replica HBM the kernel retired
    (docs/SERVING.md "paged-attention kernel"); the decode-step trace
    audits the SAME path. Same exit contract as the training plan: 0
    fits, 1 does not, 2 invalid."""
    import dataclasses

    import jax.numpy as jnp

    from ray_lightning_tpu.models.llama import LlamaConfig
    from ray_lightning_tpu.serve.audit import (
        audit_decode_step,
        format_serve_summary,
        serve_memory_summary,
        shared_prefix_plan,
        speculative_plan,
    )
    from ray_lightning_tpu.serve.engine import EngineConfig

    for name in ("serve_slots", "serve_block_size", "tp"):
        if getattr(args, name) < 1:
            return _plan_invalid(
                f"--{name.replace('_', '-')} must be >= 1, got "
                f"{getattr(args, name)}", args.as_json)
    presets = {
        "llama3-8b": LlamaConfig.llama3_8b,
        "tiny": LlamaConfig.tiny,
    }
    cfg = presets[args.preset](max_seq_len=args.seq, dtype=jnp.bfloat16)
    bps = -(-args.seq // args.serve_block_size)
    try:
        ecfg = EngineConfig(
            capacity=args.serve_slots,
            block_size=args.serve_block_size, blocks_per_slot=bps,
            prefill_chunk=min(max(128, args.serve_block_size),
                              args.seq))
        summary = serve_memory_summary(
            cfg, ecfg, device_kind=args.device_kind,
            hbm_bytes=args.hbm_bytes, tp=args.tp)
        # static pricing for the scheduler's two decode accelerators:
        # prefix sharing across a full fleet of slots, and speculative
        # decoding against a quarter-depth draft at the default k
        draft_cfg = dataclasses.replace(
            cfg, n_layers=max(1, cfg.n_layers // 4))
        prefix = shared_prefix_plan(cfg, ecfg,
                                    n_streams=args.serve_slots)
        spec = speculative_plan(cfg, draft_cfg, ecfg)
    except ValueError as exc:
        return _plan_invalid(str(exc), args.as_json)
    trace = None
    if not args.no_trace:
        try:
            from ray_lightning_tpu.analysis.costmodel import (
                topology_for_kind,
            )

            topo = topology_for_kind(args.device_kind, 1,
                                     hbm_bytes=args.hbm_bytes)
            fused = summary["attention_path"] == "paged-pallas"
            report = audit_decode_step(cfg, ecfg, topology=topo,
                                       label=f"{args.preset} serve",
                                       fused=fused, tp=args.tp)
            trace = {
                "attention_path": summary["attention_path"],
                "peak_hbm_bytes": report.peak_hbm_bytes,
                "hbm_budget_bytes": report.hbm_budget_bytes,
                "findings": [f.to_dict() for f in report.findings],
                **({"precision": report.precision}
                   if getattr(args, "precision", False) else {}),
            }
            if args.tp > 1:
                # the decode step's collective schedule over the
                # replica group's own mesh — the per-tick ICI story
                # `bench --static`'s serve_tp section and the bench
                # gate's serve_decode_ici_bytes_per_tick ratchet read
                trace["collectives"] = [
                    {"kind": e.kind, "axes": list(e.axes),
                     "payload_bytes": e.payload_bytes,
                     "count": e.count, "wire_bytes": e.wire_bytes,
                     "source": e.source,
                     **({"param": e.param_path} if e.param_path
                        else {})}
                    for e in report.collectives]
                trace["decode_ici_bytes_per_tick"] = sum(
                    e.wire_bytes for e in report.collectives)
        except Exception as exc:  # noqa: BLE001 — advisory section only
            trace = {"trace_error":
                     f"{type(exc).__name__}: {str(exc)[:300]}"}
    if args.as_json:
        out = {"serve": summary, "fits": summary["fits"],
               "prefix_sharing": prefix, "speculative": spec}
        if trace is not None:
            out["trace"] = trace
        print(json.dumps(out))
    else:
        print(format_serve_summary(summary))
        mib = 1024.0**2
        print(f"prefix sharing ({prefix['n_streams']} streams, "
              f"{prefix['prefix_tokens']}-token prefix): pool bytes "
              f"saved {prefix['shared_pool_bytes_saved'] / mib:.1f} "
              f"MiB; prefill tokens saved "
              f"{prefix['prefill_tokens_saved']}")
        print(f"speculative (k={spec['k']}, accept "
              f"{spec['accept_rate']:.2f}): verify step "
              f"{spec['verify_step_flops'] / 1e9:.2f} GFLOP vs "
              f"{spec['k']} base ticks "
              f"{spec['k'] * spec['base_decode_flops_per_token'] / 1e9:.2f}"
              f" GFLOP; expected tokens/tick "
              f"{spec['expected_tokens_per_tick']:.2f}; memory-bound "
              f"speedup {spec['memory_bound_speedup_x']:.2f}x")
        if trace is not None:
            if "trace_error" in trace:
                print(f"tracecheck: unavailable ({trace['trace_error']})")
            else:
                gib = 1024**3
                rules = sorted({f["rule"] for f in trace["findings"]})
                print(f"tracecheck (decode step): liveness peak "
                      f"{trace['peak_hbm_bytes'] / gib:.2f} GiB vs "
                      f"budget {trace['hbm_budget_bytes'] / gib:.2f} "
                      f"GiB; findings: {rules if rules else 'none'}")
                if trace.get("collectives") is not None:
                    kib = 1024.0
                    print("  decode collectives (per tick, one "
                          "replica group):")
                    for ev in trace["collectives"]:
                        print(f"    {ev['kind']:<11} "
                              f"x{ev['count']:<3} "
                              f"{ev['payload_bytes'] / kib:8.1f} KiB  "
                              f"wire {ev['wire_bytes'] / kib:8.1f} "
                              f"KiB  {ev['source']}")
                    ici_kib = trace["decode_ici_bytes_per_tick"] / kib
                    print(f"    ICI bytes/tick: {ici_kib:.1f} KiB")
                _print_precision_ledger(trace.get("precision"))
    return 0 if summary["fits"] else 1


def run_plan(args) -> int:
    import numpy as np

    from ray_lightning_tpu.models.llama import LlamaConfig, LlamaModule
    from ray_lightning_tpu.parallel.mesh import MeshSpec
    from ray_lightning_tpu.parallel.plan import (
        dp_degree,
        find_max_local_batch,
        llama_activation_bytes,
        plan_train_memory,
    )
    from ray_lightning_tpu.parallel.strategy import ShardedMesh

    presets = {
        "llama3-8b": LlamaConfig.llama3_8b,
        "tiny": LlamaConfig.tiny,
    }
    if args.serve:
        return _run_serve_plan(args)
    # --find-max-batch ignores --batch entirely, including its validation
    checked = ("data", "fsdp", "tensor", "seq") if args.find_max_batch \
        else ("data", "fsdp", "tensor", "batch", "seq")
    for name in checked:
        if getattr(args, name) < 1:
            # a zero/negative axis would ZeroDivisionError below — exit 2,
            # never a traceback colliding with the exit-1 verdict
            return _plan_invalid(
                f"--{name} must be >= 1, got {getattr(args, name)}",
                args.as_json,
            )
    cfg = presets[args.preset](
        remat=True, scan_layers=True, fused_ce=True, max_seq_len=args.seq,
        ce_inline_bwd=args.ce_inline_bwd,
    )

    def _module():
        import jax.numpy as jnp

        return LlamaModule(
            cfg, mu_dtype=jnp.bfloat16 if args.mu_bf16 else None)

    def _strategy():
        return ShardedMesh(data=args.data, fsdp=args.fsdp,
                           tensor=args.tensor)
    n_devices = args.data * args.fsdp * args.tensor
    dp = dp_degree(MeshSpec(data=args.data, fsdp=args.fsdp,
                            tensor=args.tensor))
    if not args.find_max_batch and args.batch % dp != 0:
        # a clamped/floored local batch would produce a FITS verdict for
        # a job that cannot actually shard its batch — refuse up front
        return _plan_invalid(
            f"global batch {args.batch} is not divisible by the "
            f"data-parallel degree {dp} (data x fsdp); the job could "
            f"not shard this batch. Pick batch = k x {dp}.",
            args.as_json,
        )
    try:
        if args.find_max_batch:
            # auto_scale_batch_size, plan-side: search the activation
            # bound against the HBM left after the batch-independent
            # weight costs — no devices, no failed compiles
            local, plan = find_max_local_batch(
                _module(),
                _strategy(),
                n_devices=n_devices,
                example_batch={"tokens": np.zeros((dp, args.seq + 1),
                                                  np.int32)},
                activation_bytes_fn=lambda b: llama_activation_bytes(
                    cfg, b, args.seq,
                    weight_shard_degree=args.fsdp * args.tensor),
                device_kind=args.device_kind,
                hbm_bytes_per_device=args.hbm_bytes,
            )
            # local==0 returns the activation-free plan, whose own
            # summary can read FITS (the weights fit; no batch does) —
            # label it so no consumer reads a contradiction
            summary = plan.summary() if local >= 1 else (
                "no local batch fits — weights-only plan: "
                + plan.summary())
            result = {
                "max_local_batch": local,
                "max_global_batch": local * dp,
                "dp_degree": dp,
                "fits": local >= 1,
                "summary": summary,
            }
            trace = None
            if local >= 1 and not args.no_trace:
                trace = _plan_trace_section(
                    args, _module, _strategy, n_devices, local * dp)
                result["trace"] = trace
            if args.as_json:
                print(json.dumps(result))
            else:
                print(f"max batch: {local}/device x dp {dp} = "
                      f"{local * dp} global")
                print(summary)
                if trace is not None:
                    _print_trace_section(trace)
            return 0 if local >= 1 else 1
        plan = plan_train_memory(
            _module(),
            _strategy(),
            n_devices=n_devices,
            example_batch={"tokens": np.zeros((args.batch, args.seq + 1),
                                              np.int32)},
            activation_bytes_per_device=llama_activation_bytes(
                cfg, args.batch // dp, args.seq,
                weight_shard_degree=args.fsdp * args.tensor),
            device_kind=args.device_kind,
            hbm_bytes_per_device=args.hbm_bytes,
        )
    except ValueError as exc:
        # a mesh the strategy rejects, a planner refusal — same contract
        return _plan_invalid(str(exc), args.as_json)
    trace = None
    if not args.no_trace:
        trace = _plan_trace_section(
            args, _module, _strategy, n_devices, args.batch)
    if args.as_json:
        out = {
            "mesh": plan.mesh_axes,
            "n_devices": plan.n_devices,
            "per_device_bytes": plan.per_device_total,
            "budget_bytes": plan.budget,
            "fits": plan.fits,
            "summary": plan.summary(),
        }
        if trace is not None:
            out["trace"] = trace
        print(json.dumps(out))
    else:
        print(plan.summary())
        if trace is not None:
            _print_trace_section(trace)
    return 0 if plan.fits else 1


def main(argv=None) -> int:
    p = argparse.ArgumentParser("python -m ray_lightning_tpu")
    p.add_argument("--probe", action="store_true",
                   help="run a bare-matmul throughput probe (touches and "
                        "may briefly occupy the accelerator)")
    p.add_argument("--json", action="store_true", dest="as_json")
    sub = p.add_subparsers(dest="cmd")
    plan_p = sub.add_parser(
        "plan", help="pre-flight memory plan for a model x mesh x chip "
                     "(no devices touched)")
    plan_p.add_argument("--preset", choices=("llama3-8b", "tiny"),
                        default="llama3-8b")
    plan_p.add_argument("--data", type=int, default=1)
    plan_p.add_argument("--fsdp", type=int, default=64)
    plan_p.add_argument("--tensor", type=int, default=1)
    plan_p.add_argument("--batch", type=int, default=64,
                        help="global batch (rows)")
    plan_p.add_argument("--seq", type=int, default=8192)
    plan_p.add_argument("--device-kind", default="TPU v5p",
                        help="PJRT device_kind string (e.g. 'TPU v5p'); "
                             "unknown kinds error with the known list "
                             "unless --hbm-bytes is given")
    plan_p.add_argument("--hbm-bytes", type=int, default=None,
                        help="per-device usable HBM override in bytes — "
                             "plan hardware the built-in table doesn't "
                             "know (any --device-kind is then accepted)")
    plan_p.add_argument("--ce-inline-bwd", action="store_true",
                        help="plan with the inline-backward fused CE "
                             "(charges its dx + sharded dW residuals)")
    plan_p.add_argument("--mu-bf16", action="store_true",
                        help="plan with a bf16 Adam first moment "
                             "(mu_dtype=bfloat16 — halves the mu buffer; "
                             "the planner charges the real dtype)")
    plan_p.add_argument("--serve", action="store_true",
                        help="plan a SERVING replica instead of a "
                             "training step: weights + paged KV pool + "
                             "gathered view vs the chip budget, with "
                             "the decode-step tracecheck section "
                             "(docs/SERVING.md)")
    plan_p.add_argument("--serve-slots", type=int, default=8,
                        help="serving slot capacity (plan --serve)")
    plan_p.add_argument("--serve-block-size", type=int, default=16,
                        help="KV pool block size in tokens "
                             "(plan --serve)")
    plan_p.add_argument("--tp", type=int, default=1,
                        help="tensor-parallel degree of ONE serving "
                             "replica (plan --serve): prices one rank "
                             "of the replica group — per-shard params "
                             "+ pool HBM and the decode step's "
                             "collective schedule over the replica's "
                             "own mesh (docs/SERVING.md 'sharded "
                             "replicas')")
    plan_p.add_argument("--find-max-batch", action="store_true",
                        help="ignore --batch and report the largest "
                             "per-device batch (and the implied global "
                             "batch) that fits this mesh/chip — "
                             "auto_scale_batch_size without touching "
                             "hardware")
    # SUPPRESS: the subparser parses into the SAME namespace the parent
    # already filled — a plain default=False here would overwrite a
    # `--json` given before the subcommand
    plan_p.add_argument("--json", action="store_true", dest="as_json",
                        default=argparse.SUPPRESS)
    plan_p.add_argument("--no-trace", action="store_true",
                        help="skip the tracecheck section (the "
                             "jaxpr-level collective/HBM audit of the "
                             "planned step)")
    plan_p.add_argument("--precision", action="store_true",
                        help="include numcheck's precision ledger in "
                             "the trace section: per-dtype bytes for "
                             "params / opt state / activations / KV "
                             "pool and the loss's widest-path dtype "
                             "(docs/STATIC_ANALYSIS.md)")
    from ray_lightning_tpu.analysis.cli import (
        add_lint_parser, add_trace_parser, run_lint, run_trace,
    )
    from ray_lightning_tpu.autoscale.cli import (
        add_autoscale_parser, run_autoscale,
    )
    from ray_lightning_tpu.elastic.cli import (
        add_elastic_parser, run_elastic,
    )
    from ray_lightning_tpu.loadgen.cli import (
        add_loadgen_parser, run_loadgen,
    )
    from ray_lightning_tpu.pipeline.cli import add_perf_parser, run_perf
    from ray_lightning_tpu.resilience.cli import (
        add_supervise_parser, run_supervise,
    )
    from ray_lightning_tpu.serve.cli import add_serve_parser, run_serve
    from ray_lightning_tpu.telemetry.report import (
        add_monitor_parser, add_report_parser, run_monitor, run_report,
    )
    from ray_lightning_tpu.telemetry.timeline import (
        add_timeline_parser, run_timeline,
    )
    from ray_lightning_tpu.telemetry.watch import (
        add_watch_parser, run_watch,
    )

    add_lint_parser(sub)
    add_trace_parser(sub)
    add_supervise_parser(sub)
    add_perf_parser(sub)
    add_serve_parser(sub)
    add_report_parser(sub)
    add_monitor_parser(sub)
    add_timeline_parser(sub)
    add_watch_parser(sub)
    add_elastic_parser(sub)
    add_autoscale_parser(sub)
    add_loadgen_parser(sub)
    args = p.parse_args(argv)
    if args.cmd == "plan":
        return run_plan(args)
    if args.cmd == "lint":
        return run_lint(args)
    if args.cmd == "trace":
        return run_trace(args)
    if args.cmd == "supervise":
        return run_supervise(args)
    if args.cmd == "perf":
        return run_perf(args)
    if args.cmd == "serve":
        return run_serve(args)
    if args.cmd == "report":
        return run_report(args)
    if args.cmd == "monitor":
        return run_monitor(args)
    if args.cmd == "timeline":
        return run_timeline(args)
    if args.cmd == "watch":
        return run_watch(args)
    if args.cmd == "elastic":
        return run_elastic(args)
    if args.cmd == "autoscale":
        return run_autoscale(args)
    if args.cmd == "loadgen":
        return run_loadgen(args)
    info = collect(probe=args.probe)
    if args.as_json:
        print(json.dumps(info))
        return 0
    print(f"{info['package']}  (jax {info['jax']}, "
          f"backend {info['backend']})")
    print(f"process {info['process_index']}/{info['process_count']}  "
          f"devices {info['local_devices']} local / "
          f"{info['global_devices']} global")
    for d in info["devices"]:
        sl = f" slice={d['slice_index']}" if d["slice_index"] is not None else ""
        print(f"  [{d['id']}] {d['kind']} ({d['platform']}){sl}")
    if info.get("devices_truncated"):
        print(f"  ... and {info['devices_truncated']} more")
    if "probe_matmul_tflops" in info:
        peak = (f"spec peak {info['peak_tflops']}"
                if info["peak_tflops"] is not None
                else "no spec peak on record for this device kind")
        print(f"probe: {info['probe_matmul_tflops']} TFLOP/s bf16 matmul "
              f"({peak})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

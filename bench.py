"""Headline benchmark: Llama training-step throughput + MFU on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} plus
honesty fields — "mfu", "assumed_peak_tflops", "device_kind",
"flops_per_token", and a long-sequence leg ("s4096_*").

The reference publishes no performance numbers (BASELINE.json
"published": {} — see BASELINE.md), so "vs_baseline" compares against the
same training step with the hand-tuned paths disabled (XLA-naive attention
instead of the pallas flash kernel; materialized full-vocab logits instead
of the fused chunked cross-entropy): > 1 means the TPU-native design beats
a straightforward XLA translation of the reference capability. MFU is the
absolute check the ratio can't game: model FLOPs (6·N_matmul + causal
attention, no remat recompute credit) / chip peak bf16 FLOPs.

"kernels_verified"/"kernel_errors" report on-chip numerical parity of the
pallas flash kernel (fwd + bwd) and the fused chunked CE against their
XLA reference paths — correctness proven where the kernels actually run,
not only in CPU interpret mode.

Exit contract: 0 = JSON result line on stdout. 3 = structured failure:
still ONE JSON line, with an "error" field (emitted by the hang
watchdog, a SIGTERM, or the handler around the run: OOM, a compile
error, a backend that does not come up). Nothing is retried and no leg
runs without a TPU: the chip under test either answers or the run fails.
"""
from __future__ import annotations

import json
import os
import time
from functools import partial

import numpy as np

# peak table + probe shared with the doctor CLI (utils/probe.py)
from ray_lightning_tpu.utils.probe import (  # noqa: E402
    device_peak_tflops as _device_peak_tflops,
    matmul_tflops as _probe_matmul_tflops,
)


def _bench_cfg(use_flash: bool, fused_ce: bool, seq: int,
               vocab: int = 32768, remat: bool = True, scan: bool = True,
               remat_policy: str = "nothing", ce_chunk_tokens: int = 2048,
               ce_inline: bool = False):
    from ray_lightning_tpu.models.llama import LlamaConfig

    return LlamaConfig(
        vocab_size=vocab,
        dim=2048,
        n_layers=8,
        n_heads=16,
        n_kv_heads=8,
        hidden_dim=5632,
        max_seq_len=seq,
        use_flash=use_flash,
        fused_ce=fused_ce,
        ce_chunk_tokens=ce_chunk_tokens,
        ce_inline_bwd=ce_inline,
        remat=remat,
        remat_policy=remat_policy,
        scan_layers=scan,
    )


def _flops_per_token(cfg, seq: int) -> float:
    """Model FLOPs per trained token: 6×(matmul params) + causal attention
    (QK^T + AV, average context S/2), fwd×2 + bwd×4. Remat recompute is
    real work but not counted — MFU measures useful FLOPs."""
    hd = cfg.head_dim
    per_layer = (
        cfg.dim * (cfg.n_heads + 2 * cfg.n_kv_heads) * hd  # wqkv
        + cfg.n_heads * hd * cfg.dim                       # wo
        + 3 * cfg.dim * cfg.hidden_dim                     # gate_up + down
    )
    n_matmul = cfg.n_layers * per_layer + cfg.dim * cfg.vocab_size  # lm_head
    attn = 6 * cfg.n_layers * cfg.n_heads * hd * seq  # 3×(2·2·(S/2)·nq·hd)
    return 6.0 * n_matmul + attn


def _make_step(use_flash: bool, fused_ce: bool, batch: int, seq: int,
               vocab: int = 32768, remat: bool = True, scan: bool = True,
               remat_policy: str = "nothing", ce_chunk_tokens: int = 2048,
               ce_inline: bool = False, mu_dtype=None):
    import jax
    import optax

    from ray_lightning_tpu.models.llama import Llama, LlamaModule

    cfg = _bench_cfg(use_flash, fused_ce, seq, vocab, remat, scan,
                     remat_policy, ce_chunk_tokens, ce_inline)
    model = Llama(cfg)
    module = LlamaModule(cfg)
    module.model = model
    tokens = jax.random.randint(
        jax.random.key(0), (batch, seq + 1), 0, cfg.vocab_size, dtype=np.int32
    )
    params = jax.jit(model.init)(jax.random.key(0), tokens[:, :-1])["params"]
    tx = optax.adamw(3e-4, b1=0.9, b2=0.95, weight_decay=0.1,
                     mu_dtype=mu_dtype)
    opt_state = jax.jit(tx.init)(params)

    def loss_fn(params, tokens):
        # the trainer's actual loss path (fused or materialized, per cfg)
        return module._loss(params, tokens[:, :-1], tokens[:, 1:], None)

    @partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return step, params, opt_state, tokens, batch * seq, cfg


def _time_step(step, params, opt_state, tokens, warmup=3, iters=5,
               windows=3, timing: dict | None = None):
    """Best-of-``windows`` timing: a host hiccup in one window must not
    masquerade as model speed; the minimum window is the closest
    observable to the true step time.

    ``timing`` (optional, filled in place) carries the goodput view of
    the same measurement: ``wall_s`` (entry to exit, INCLUDING the
    warmup/compile the best-of window deliberately excludes) and
    ``productive_s`` (the timed windows' elapsed sum) — compile/warmup
    is lost time under goodput semantics, exactly as in a real run."""
    import jax

    t_start = time.perf_counter()
    for _ in range(warmup):
        params, opt_state, loss = step(params, opt_state, tokens)
    # fetching the loss value forces execution of the whole dependency
    # chain
    float(jax.device_get(loss))
    best = float("inf")
    productive = 0.0
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(iters):
            params, opt_state, loss = step(params, opt_state, tokens)
        float(jax.device_get(loss))
        elapsed = time.perf_counter() - t0
        productive += elapsed
        best = min(best, elapsed / iters)
    if timing is not None:
        timing.update({"wall_s": time.perf_counter() - t_start,
                       "productive_s": productive})
    return best


def _telemetry_overhead_fraction(step_dt: float,
                                 spans_per_step: int = 4,
                                 n: int = 4000) -> float:
    """Measured recorder cost relative to the measured step time: the
    per-span price of the ring recorder (two clock reads + a dict + a
    deque append) times the spans the trainer emits per batch
    (dispatch + step + data_wait + H2D), over the headline step time.
    The bench_gate upper-bounds this below 1%."""
    from ray_lightning_tpu.telemetry.spans import TelemetryRecorder

    rec = TelemetryRecorder()  # memory-only: no file I/O in the ring path
    t0 = time.perf_counter()
    for i in range(n):
        with rec.span("dispatch", step=i):
            pass
    per_span = (time.perf_counter() - t0) / n
    return (per_span * spans_per_step) / max(step_dt, 1e-9)


def _measure(use_flash: bool, fused_ce: bool, batch: int, seq: int,
             vocab: int = 32768, remat: bool = True, scan: bool = True,
             remat_policy: str = "nothing", ce_chunk_tokens: int = 2048,
             ce_inline: bool = False, mu_dtype=None,
             timing: dict | None = None):
    step, params, opt_state, tokens, tps, cfg = _make_step(
        use_flash, fused_ce, batch, seq, vocab, remat, scan,
        remat_policy, ce_chunk_tokens, ce_inline, mu_dtype
    )
    dt = _time_step(step, params, opt_state, tokens, timing=timing)
    if timing is not None:
        timing["step_dt_s"] = dt
    del step, params, opt_state, tokens
    return tps / dt, cfg


def _flagship_leg(measure, shared: dict, mfu_of, shape_desc: str):
    """The flagship leg's measurement policy, extracted for unit tests
    (tests/test_bench.py): try the inline-CE config; on a compile
    rejection reuse the rematce leg's measurement from ``shared``
    (identical configuration, already timed — never compile it twice),
    preserving the inline failure cause; with nothing to reuse,
    re-raise so leg() degrades the row with the REAL error.

    ``measure(ce_inline=...)`` -> (tokens_per_sec, cfg); ``mfu_of(t, c)``
    -> useful-FLOP MFU; ``shape_desc`` describes the measured shape and
    lives WITH the measure closure so the artifact's config string
    cannot drift from the actual parameters. Returns ``(row, mfu)``.
    """
    try:
        t, c = measure(ce_inline=True)
        config = f"remat(nothing)+scan+fusedCE(inline) {shape_desc}"
        note = {}
        m = mfu_of(t, c)
    except Exception as exc:  # noqa: BLE001 — fall back, keep cause
        note = {"flagship_inline_error":
                f"{type(exc).__name__}: {str(exc)[:200]}"}
        if "rematce" not in shared:
            raise  # no reusable measurement — surface the real error
        t, m = shared["rematce"]
        config = (f"remat(nothing)+scan+fusedCE(remat) {shape_desc} "
                  "[inline fallback: rematce leg's measurement]")
    return ({"flagship_tokens_per_sec": round(t, 1),
             "flagship_mfu": round(m, 4),
             "flagship_config": config, **note}, m)


def _attnout_leg(measure, mfu_of):
    """The attn_out flagship leg's measurement policy, extracted for unit
    tests (tests/test_bench.py): try the inline-CE config; on a compile
    rejection fall back to the measurable non-inline attn_out config,
    keeping the inline cause in the row. If the FALLBACK also fails, the
    inline root cause must not be discarded (ADVICE r5): both causes are
    folded into the raised error, with the inline failure chained as
    __cause__, so leg() records the full story."""
    note = {}
    try:
        t, c = measure(ce_inline=True)
    except Exception as exc:  # noqa: BLE001 — fall back, keep cause
        note = {"flagship_attnout_inline_error":
                f"{type(exc).__name__}: {str(exc)[:200]}"}
        try:
            t, c = measure(ce_inline=False)
        except Exception as exc2:  # noqa: BLE001 — chain BOTH causes
            raise RuntimeError(
                "attn_out leg failed on both paths — inline "
                f"[{type(exc).__name__}: {str(exc)[:200]}]; non-inline "
                f"[{type(exc2).__name__}: {str(exc2)[:200]}]"
            ) from exc
    m = mfu_of(t, c)
    return ({"flagship_attnout_tokens_per_sec": round(t, 1),
             "flagship_attnout_mfu": round(m, 4), **note}, m)


def _require_tpu(leg: str) -> None:
    """A measured leg's numbers are device metrics: without a TPU
    backend there is nothing to measure, and the leg raises instead of
    timing whatever backend jax happened to find."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        raise RuntimeError(
            f"bench leg {leg!r} measures a TPU; jax's default backend "
            f"is {backend!r}")


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


#: tracecheck + trainguard summaries computed ONCE at startup (CPU-only
#: traces, no backend touch) and attached to EVERY JSON line this
#: process emits — success, skip, error, watchdog, or signal kill — so
#: even a round with no chip still carries analysis data (ISSUE 2/5
#: satellites).
_ANALYSIS: dict = {}

#: Measurement-leg execution order, flagship-first: a watchdog timeout
#: or driver kill mid-run flushes the partial sink, and the legs that
#: must survive such a death are the driver-verified flagship numbers —
#: so they run before the comparison legs. Constraint encoded here and
#: pinned by tests/test_bench_script.py: `flagship_rematce` stays
#: immediately before `flagship` (the inline leg's compile-rejection
#: fallback reuses the rematce measurement via `shared`).
LEG_ORDER: tuple = (
    "flagship_rematce", "flagship", "flagship_attnout",
    "vs_baseline", "s4096", "v128k", "overlap", "serving", "reshard",
)


def _concurrency_summary() -> dict:
    """threadcheck static audit of the package (analysis/concurrency.py):
    finding counts by RLT7xx rule. Pure host-side AST work — carried on
    every JSON line even when the backend is down, like the tracecheck
    block."""
    try:
        from ray_lightning_tpu.analysis.concurrency import (
            check_concurrency_paths, summarize,
        )

        pkg = os.path.dirname(os.path.abspath(
            __import__("ray_lightning_tpu").__file__))
        return {"concurrency": summarize(check_concurrency_paths([pkg]))}
    except Exception as exc:  # noqa: BLE001 — analysis is bonus data
        return {"concurrency": {
            "error": f"{type(exc).__name__}: {str(exc)[:200]}"}}


def _guard_summary() -> dict:
    """Structural audit of the trainguard (resilience/guard.py, ISSUE 5):
    jaxpr-trace the guarded update with abstract inputs (make_jaxpr over
    ShapeDtypeStructs — no backend is ever initialized) and report the
    guard counters that ride
    the step's metric outputs plus the effect count, proving the guard
    adds detection WITHOUT host callbacks/transfers. The counter VALUES
    are the zero-state (this process measures throughput with a raw
    step, not the Trainer); the schema and the no-new-transfers claim
    are what the recorder consumes."""
    try:
        import jax
        import jax.numpy as jnp

        from ray_lightning_tpu.resilience.guard import (
            GuardConfig,
            abstract_guard_state,
            apply_guard,
        )

        cfg = GuardConfig()

        def guarded(guard, step, loss, gn, params):
            new_params = jax.tree.map(lambda x: x - 1.0, params)
            return apply_guard(cfg, guard, step, loss, gn,
                               new_params, params, (), ())

        s = jax.ShapeDtypeStruct
        jaxpr = jax.make_jaxpr(guarded)(
            abstract_guard_state(), s((), jnp.int32), s((), jnp.float32),
            s((), jnp.float32), {"w": s((16,), jnp.float32)})
        _, _, _, _, metrics = jax.eval_shape(
            guarded, abstract_guard_state(), s((), jnp.int32),
            s((), jnp.float32), s((), jnp.float32),
            {"w": s((16,), jnp.float32)})
        return {"guard": {
            "counters": sorted(metrics),
            "in_jit": True,
            "effects": len(jaxpr.effects),       # 0 = no callbacks
            "extra_host_transfers": 0,           # flags ride the metrics
            "skipped_steps": 0,
            "rollbacks": 0,
            "sdc_probes": 0,
            "last_anomaly": -1,
            "source": "static-trace",
        }}
    except Exception as exc:  # noqa: BLE001 — advisory data only; a
        # guard-audit bug must never cost the bench its perf evidence
        return {"guard_error": f"{type(exc).__name__}: {str(exc)[:200]}"}


def _telemetry_summary() -> dict:
    """Telemetry/goodput SCHEMA for every JSON line this process emits
    (ISSUE 7): pure imports, no backend touch, so a backend-down skip
    line still tells the recorder what shape measured goodput data will
    take when the chip returns. The measured values
    (``goodput_fraction``, ``telemetry_overhead_fraction``) land only on
    success lines, next to this schema."""
    try:
        from ray_lightning_tpu.telemetry import GOODPUT_SCHEMA
        from ray_lightning_tpu.telemetry.spans import PHASES

        return {"goodput": {"schema": GOODPUT_SCHEMA,
                            "source": "static-schema"},
                "telemetry": {"span_phases": list(PHASES),
                              "recorder": "bounded-ring+jsonl"}}
    except Exception as exc:  # noqa: BLE001 — advisory data only
        return {"telemetry_error":
                f"{type(exc).__name__}: {str(exc)[:200]}"}


def _trace_summary() -> dict:
    """Zero-hardware tracecheck (analysis/tracecheck.py) of the
    flagship bench config: ICI bytes/step (0 on one chip — honest) and
    the estimated peak HBM, against a conservative single-chip budget.
    jax.eval_shape/make_jaxpr never initialize a backend."""
    try:
        from ray_lightning_tpu.analysis.costmodel import topology_for_kind
        from ray_lightning_tpu.analysis.tracecheck import audit_step
        from ray_lightning_tpu.models.llama import LlamaModule
        from ray_lightning_tpu.parallel.strategy import SingleDevice

        cfg = _bench_cfg(use_flash=True, fused_ce=True, seq=2048,
                         vocab=128256, remat=True, scan=True,
                         ce_chunk_tokens=4096)
        # traced before any backend touch, so the chip is not known
        # yet: the 16-GiB class (v5e) is the conservative assumption
        topo = topology_for_kind("TPU v5e", 1)
        report = audit_step(
            LlamaModule(cfg), SingleDevice(),
            {"tokens": np.zeros((8, 2049), np.int32)},
            topology=topo, label="bench flagship")
        return {"tracecheck": {
            "ici_bytes_per_step": report.ici_bytes_per_step,
            "est_peak_hbm_bytes": report.peak_hbm_bytes,
            "hbm_budget_bytes": report.hbm_budget_bytes,
            "assumed_device_kind": topo.device_kind,
            "findings": len(report.findings),
        }}
    except Exception as exc:  # noqa: BLE001 — advisory data only; an
        # analysis bug must never cost the bench its perf evidence
        return {"tracecheck_error":
                f"{type(exc).__name__}: {str(exc)[:200]}"}


def _numerics_summary() -> dict:
    """numcheck static audit (analysis/numcheck.py) of the flagship
    bench config's traced step: RLT801-805 counts by rule, the
    precision ledger, and the headline ``low_precision_reductions``
    (RLT801 narrow accumulations + RLT804 narrow gradient collectives)
    duplicated at top level for the bench_gate ceiling ratchet — 0
    since the f32-accumulation fixes, and it may only stay 0. Pure
    jaxpr work like `_trace_summary`, carried on every JSON line even
    when the backend is down; a numerics bug emits ``numerics_error``
    instead, which waives ABSENCE at the gate, never a grown value."""
    try:
        from ray_lightning_tpu.analysis.costmodel import topology_for_kind
        from ray_lightning_tpu.analysis.numcheck import summarize
        from ray_lightning_tpu.analysis.tracecheck import audit_step
        from ray_lightning_tpu.models.llama import LlamaModule
        from ray_lightning_tpu.parallel.strategy import SingleDevice

        # seq can stay small: the accumulation extents RLT801/804
        # judge are the model's contraction dims, not the sequence
        cfg = _bench_cfg(use_flash=True, fused_ce=True, seq=512,
                         vocab=128256, remat=True, scan=True,
                         ce_chunk_tokens=1024)
        report = audit_step(
            LlamaModule(cfg), SingleDevice(),
            {"tokens": np.zeros((2, 513), np.int32)},
            topology=topology_for_kind("TPU v5e", 1),
            label="bench flagship numerics")
        nc = [f for f in report.findings if f.rule.startswith("RLT8")]
        s = summarize(nc)
        lpr = sum(n for rule, n in s["by_rule"].items()
                  if rule in ("RLT801", "RLT804"))
        prec = report.precision or {}
        return {
            "numerics": {
                "findings": s["total"],
                "by_rule": s["by_rule"],
                "loss_widest_dtype": prec.get("loss_widest_dtype"),
                "ledger": {k: prec.get(k) for k in
                           ("params", "opt_state", "activations",
                            "kv_pool")},
                "source": "static-trace",
            },
            "low_precision_reductions": lpr,
        }
    except Exception as exc:  # noqa: BLE001 — advisory data only; a
        # numerics-audit bug must never cost the bench its perf evidence
        return {"numerics_error":
                f"{type(exc).__name__}: {str(exc)[:200]}"}


def _multislice_summary() -> dict:
    """Static multi-slice (DCN) trace summary for the bench JSON
    (ISSUE 9): the bench model's HSDP step on a 2xv5p-64 deployment —
    `data` across the two slices (hierarchical gradient reduction on
    DCN), fsdp inside each slice on ICI — itemized by network tier.
    Pure jaxpr work like `_trace_summary`, carried on every line
    (success or backend-down skip), with the headline
    `dcn_bytes_per_step` duplicated at top level for the bench_gate
    ceiling ratchet (DCN bytes may only shrink)."""
    try:
        from ray_lightning_tpu.analysis.costmodel import parse_topology
        from ray_lightning_tpu.analysis.tracecheck import audit_step
        from ray_lightning_tpu.models.llama import LlamaModule
        from ray_lightning_tpu.parallel.strategy import ShardedMesh

        topo = parse_topology("2xv5p-64")
        cfg = _bench_cfg(use_flash=True, fused_ce=True, seq=2048,
                         remat=True, scan=True)
        per_slice = topo.devices_per_slice
        report = audit_step(
            LlamaModule(cfg),
            ShardedMesh(data=topo.n_slices, fsdp=per_slice),
            {"tokens": np.zeros((topo.n_devices, 2049), np.int32)},
            topology=topo, label="bench 2xv5p-64 HSDP")
        from ray_lightning_tpu.parallel.plan import dcn_crossing_axes

        # the mesh axes (other than `data`, whose crossing is the
        # designed HSDP placement) that span slices — empty when the
        # placement is sound; non-empty mirrors an RLT306 flag
        crossing = sorted(ax for ax in dcn_crossing_axes(
            report.mesh_axes, topo.n_slices) if ax != "data")
        return {
            "dcn_bytes_per_step": report.dcn_bytes_per_step,
            "multislice": {
                "topology": topo.name,
                "n_slices": topo.n_slices,
                "mesh": report.mesh_axes,
                "ici_bytes_per_step": report.ici_bytes_per_step,
                "dcn_bytes_per_step": report.dcn_bytes_per_step,
                "dcn_gbps_per_chip": topo.dcn_gbps,
                "dcn_crossing_flags": crossing,
                "findings": len(report.findings),
            },
        }
    except Exception as exc:  # noqa: BLE001 — advisory data only
        return {"multislice_error":
                f"{type(exc).__name__}: {str(exc)[:200]}"}


def _serve_summary() -> dict:
    """Serving SCHEMA + the flagship serve plan for every JSON line
    this process emits (ISSUE 8): byte math + one eval_shape, no
    backend touch, so a backend-down skip line still carries the
    serving memory story and tells the recorder what shape the
    measured serving metrics (`decode_tokens_per_s`, `ttft_cold_s`,
    `ttft_warm_s`, `slot_occupancy` — success lines only) will take.

    ``serve_hbm_bytes_per_replica`` (top-level, EVERY line — ISSUE 11)
    is the flagship replica's static per-device HBM on the attention
    paths the deployment would actually run (the fused paged decode
    AND prefill kernels when they tile the shape — they retire the
    reference lanes' dense gathered views). bench_gate
    CEILING-ratchets it: per-replica serving HBM may only shrink; a
    ``serving_error`` line waives (an analysis bug is not a
    regression). ``serve_prefill_gather_bytes`` (top-level, EVERY
    line — ISSUE 15) is the prefill lane's surviving per-group dense
    gather on the same plan — 0 once the fused prefill kernel covers
    the shape; bench_gate CEILING-ratchets it the same way (it may
    only shrink, anchoring the retirement).

    ``serve_tp`` (EVERY line — ISSUE 18) prices ONE RANK of the
    flagship TP=2 sharded replica (docs/SERVING.md "sharded
    replicas"): per-shard params/pool/total HBM plus the decode step's
    collective schedule over the replica's own tensor mesh — all from
    `serve/audit.py` tracing, no backend touch.
    ``serve_decode_ici_bytes_per_tick`` (top-level, EVERY line) is
    that schedule's total wire bytes per decode tick; bench_gate
    CEILING-ratchets it (decode collectives ride the latency-critical
    path, so their per-tick traffic may only shrink).

    ``prefix_plan`` / ``speculative_plan`` (ISSUE 19, inside
    ``serving`` — EVERY line) statically price the scheduler's two
    decode accelerators at the flagship shape: the pool bytes + prefill
    tokens a shared prefix saves across the fleet, and the verify-step
    FLOPs vs k plain decode ticks with the expected tokens/tick. The
    MEASURED twins — ``shared_block_fraction`` and
    ``accepted_tokens_per_step`` from the steady-state leg — ride
    success lines and bench_gate RATCHETS both (higher is better;
    waived on skip)."""
    try:
        import jax.numpy as jnp

        from ray_lightning_tpu.models.llama import LlamaConfig
        from ray_lightning_tpu.serve.audit import serve_memory_summary
        from ray_lightning_tpu.serve.engine import EngineConfig

        cfg = LlamaConfig.llama3_8b(max_seq_len=4096, dtype=jnp.bfloat16)
        ecfg = EngineConfig(capacity=8, block_size=16,
                            blocks_per_slot=256, prefill_chunk=256)
        plan = serve_memory_summary(cfg, ecfg)
        reference = serve_memory_summary(cfg, ecfg, fused=False)
        from ray_lightning_tpu.serve.audit import audit_decode_step

        tp = 2
        plan_tp = serve_memory_summary(cfg, ecfg, tp=tp)
        report_tp = audit_decode_step(cfg, ecfg, tp=tp)
        ici_tick = sum(e.wire_bytes for e in report_tp.collectives)
        serve_tp = {
            "tp": tp,
            "hbm_bytes_per_shard": plan_tp["per_device_bytes"],
            "params_bytes_per_shard": plan_tp["params_bytes"],
            "pool_bytes_per_shard": plan_tp["pool_bytes"],
            "decode_ici_bytes_per_tick": ici_tick,
            "collectives": [
                {"kind": e.kind, "axes": list(e.axes),
                 "payload_bytes": e.payload_bytes, "count": e.count,
                 "wire_bytes": e.wire_bytes, "source": e.source}
                for e in report_tp.collectives],
        }
        # static pricing for the scheduler's two decode accelerators
        # (ISSUE 19): prefix sharing across a full fleet of slots and
        # speculative decoding vs a quarter-depth draft — byte/FLOP
        # math from serve/audit.py, carried on EVERY line like the
        # rest of the serve plan
        import dataclasses as _dc

        from ray_lightning_tpu.serve.audit import (
            shared_prefix_plan, speculative_plan,
        )

        draft_cfg = _dc.replace(cfg, n_layers=max(1, cfg.n_layers // 4))
        prefix_plan = shared_prefix_plan(cfg, ecfg,
                                         n_streams=ecfg.capacity)
        spec_plan = speculative_plan(cfg, draft_cfg, ecfg)
        return {"serve_tp": serve_tp,
                "serve_decode_ici_bytes_per_tick": ici_tick,
                "serving": {
            "schema": ["decode_tokens_per_s", "prefill_tokens_per_s",
                       "ttft_cold_s", "ttft_warm_s", "ttft_p99_s",
                       "slot_occupancy", "shared_block_fraction",
                       "accepted_tokens_per_step",
                       "serving_attention_path",
                       "serving_prefill_path", "serve_metrics",
                       "scale_up_s", "autoscale", "slo_attainment",
                       "slo_attainment_latency_critical",
                       "shed_fraction"],
            # ISSUE 20: the traffic-class leg's measured fields
            # (success lines only; bench_gate ratchets the
            # latency-critical attainment and waives skips)
            "traffic_schema": {
                "slo_attainment": "per class {ttft_p95_s, target_s, "
                                  "attainment} from a mixed-class "
                                  "burst with the SLO machinery "
                                  "armed (docs/SERVING.md 'traffic "
                                  "& SLO classes')",
                "slo_attainment_latency_critical":
                    "fraction of latency-critical completions whose "
                    "TTFT met the class target — bench_gate ratchets "
                    "it (may only grow)",
                "shed_fraction": "typed best-effort sheds / submitted "
                                 "requests in that burst — explicit "
                                 "degradation, never silence",
            },
            "prefix_plan": prefix_plan,
            "speculative_plan": spec_plan,
            "autoscale_schema": {
                "scale_up_s": "wall seconds one controller-driven "
                              "add_replica pays (spawn + weights + "
                              "step warm; bench_gate bounds it via "
                              "RLT_BENCH_SCALE_UP_MAX)",
                "decisions": "controller polls in the drill",
                "final_replicas": "replica count after the drill",
            },
            "engine": "paged-kv continuous-batching (serve/)",
            "source": "static-schema",
            "flagship_plan": plan,
            "attention_path": plan["attention_path"],
            "prefill_attention_path": plan["prefill_attention_path"],
            "gathered_view_retired_bytes":
                plan["gathered_view_retired_bytes"],
            "prefill_kv_traffic_bytes_per_chunk":
                plan["prefill_kv_traffic_bytes_per_chunk"],
            "reference_hbm_bytes_per_replica":
                reference["per_device_bytes"],
        }, "serve_hbm_bytes_per_replica": plan["per_device_bytes"],
           "serve_prefill_gather_bytes": plan["prefill_gather_bytes"]}
    except Exception as exc:  # noqa: BLE001 — advisory data only
        return {"serving_error": f"{type(exc).__name__}: {str(exc)[:200]}"}


def _measure_serving(tiny: bool = False,
                     autoscale: bool = True) -> dict:
    """Measured serving leg (bench success lines + unit tests).

    The 0.5B-class bench model, which needs a TPU: a measured leg
    without one raises. ``tiny=True`` is the unit tests' explicit
    laptop-sized config (same engine code path; its numbers are counts,
    never device metrics). ``autoscale=False`` skips the scale-up/down
    drill (unit tests of the throughput/TTFT fields alone — the drill
    pays two extra engine compiles; real bench lines always run it).
    """
    import time as _time

    import jax

    from ray_lightning_tpu.models.llama import Llama, LlamaConfig
    from ray_lightning_tpu.serve.engine import DecodeEngine, EngineConfig
    from ray_lightning_tpu.serve.scheduler import Request, Scheduler

    if not tiny:
        _require_tpu("serving")
    if tiny:
        import jax.numpy as jnp

        cfg = LlamaConfig.tiny(use_flash=False, dtype=jnp.float32)
        ecfg = EngineConfig(capacity=4, block_size=4, blocks_per_slot=8,
                            prefill_chunk=4)
        prompt_len, max_new, n_requests = 6, 8, 8
    else:
        cfg = _bench_cfg(use_flash=True, fused_ce=False, seq=1024,
                         remat=False, scan=False)
        ecfg = EngineConfig(capacity=8, block_size=16,
                            blocks_per_slot=64, prefill_chunk=128)
        prompt_len, max_new, n_requests = 128, 64, 16
    from ray_lightning_tpu.telemetry.metrics import MetricsRegistry

    model = Llama(cfg)
    prompt = np.asarray(jax.random.randint(
        jax.random.key(0), (1, prompt_len), 0, cfg.vocab_size),
        dtype=np.int32)
    params = jax.jit(model.init)(jax.random.key(1), prompt)["params"]

    def first_token_wall(engine, metrics=None) -> float:
        sched = Scheduler(engine, metrics=metrics)
        sched.submit(Request(rid="ttft", prompt=prompt[0],
                             max_new_tokens=1))
        t0 = _time.perf_counter()
        while sched.busy():
            sched.tick()
        return _time.perf_counter() - t0

    # in-memory live-metrics registry (telemetry/metrics.py) for the
    # WARM legs only — the cold probe's compile must not pollute the
    # steady-state SLO histogram the ttft_p99_s bound gates
    reg = MetricsRegistry()
    # TTFT cold: fresh engine, no warmup — the compile is the latency
    engine = DecodeEngine(model, params, ecfg)
    ttft_cold = first_token_wall(engine)
    # TTFT warm: the same compiled engine, a fresh request
    ttft_warm = first_token_wall(engine, metrics=reg)
    # steady-state decode throughput, slots saturated. The requests
    # share ONE prompt, so the prefix cache measures its real effect:
    # the common blocks prefill once and map into every slot's table
    # (shared_block_fraction below; decode streams stay bitwise)
    engine.metrics = reg
    sched = Scheduler(engine, metrics=reg, prefix_cache=True)
    for i in range(n_requests):
        sched.submit(Request(rid=f"r{i}", prompt=prompt[0],
                             max_new_tokens=max_new, seed=i))
    t0 = _time.perf_counter()
    n_tokens = 0
    while sched.busy():
        sched.tick()
        n_tokens += len(sched.last_emissions)
    wall = _time.perf_counter() - t0
    # prefill throughput (ISSUE 15): a prefill-DOMINATED drain on the
    # same warm engine — every request generates one token, so the
    # wall is the prompt chewing. Tokens counted from the engine's own
    # prefill_tokens metric (chunk positions actually advanced, incl.
    # pad columns on the batched lane — the work the kernel did).
    pf_reg = MetricsRegistry()
    engine.metrics = pf_reg
    pf_sched = Scheduler(engine, metrics=pf_reg)
    for i in range(n_requests):
        pf_sched.submit(Request(rid=f"p{i}", prompt=prompt[0],
                                max_new_tokens=1, seed=100 + i))
    t0 = _time.perf_counter()
    while pf_sched.busy():
        pf_sched.tick()
    pf_wall = _time.perf_counter() - t0
    pf_tokens = pf_reg.counters().get("prefill_tokens", 0)
    # per-class SLO leg (ISSUE 20): a mixed-class burst on the SAME
    # warm engine with the SLO machinery armed — the best-effort
    # admission budget forces typed shed records while the paying
    # classes complete; the latency-critical attainment fraction is
    # the number bench_gate ratchets (may only grow toward 1.0)
    from ray_lightning_tpu.serve.scheduler import ClassSLO, SLOConfig

    slo = SLOConfig(classes={
        "latency_critical": ClassSLO(ttft_p95_s=10.0, tpot_p95_s=5.0),
        "standard": ClassSLO(ttft_p95_s=30.0, tpot_p95_s=10.0),
        "best_effort": ClassSLO(ttft_p95_s=60.0, tpot_p95_s=20.0,
                                queue_budget=1),
    })
    slo_reg = MetricsRegistry()
    engine.metrics = slo_reg
    slo_sched = Scheduler(engine, metrics=slo_reg, slo=slo)
    slo_classes = ("latency_critical", "standard", "best_effort")
    for i in range(n_requests):
        slo_sched.submit(Request(rid=f"s{i}", prompt=prompt[0],
                                 max_new_tokens=max_new, seed=200 + i,
                                 priority=slo_classes[i % 3]))
    shed_recs = slo_sched.take_sheds()   # enqueue-budget sheds
    while slo_sched.busy():
        slo_sched.tick()
        shed_recs.extend(slo_sched.take_sheds())
    attain = {}
    lc_frac = None
    for cls in slo_classes:
        spec = slo.classes[cls]
        ttfts = sorted(c.ttft_s for c in slo_sched.completions
                       if c.priority == cls)
        if not ttfts:
            continue
        frac = sum(1 for t in ttfts if t <= spec.ttft_p95_s) \
            / len(ttfts)
        attain[cls] = {
            "ttft_p95_s": round(ttfts[min(
                len(ttfts) - 1,
                max(0, -(-95 * len(ttfts) // 100) - 1))], 4),
            "target_s": spec.ttft_p95_s,
            "attainment": round(frac, 4),
        }
        if cls == "latency_critical":
            lc_frac = round(frac, 4)
    engine.metrics = reg
    # the serve_metrics rollup: queue-depth stats from the per-tick
    # ring, event counters, and the warm TTFT p99 from the mergeable
    # histogram buckets (the SLO number bench_gate upper-bounds;
    # env-overridable, waived on skip/null like ttft_warm_s)
    counters = reg.counters()
    qd = sorted(float((s.get("g") or {}).get("queue_depth", 0.0))
                for s in reg.ring())
    ttft_hist = reg.histogram("ttft_s")
    ttft_p99 = ttft_hist.quantile(0.99) if ttft_hist else None
    autoscale_fields = (_measure_autoscale(cfg, ecfg, params)
                        if autoscale else {})
    return {
        **autoscale_fields,
        "decode_tokens_per_s": round(n_tokens / max(wall, 1e-9), 2),
        "prefill_tokens_per_s": round(
            pf_tokens / max(pf_wall, 1e-9), 2),
        "ttft_cold_s": round(ttft_cold, 4),
        "ttft_warm_s": round(ttft_warm, 4),
        "ttft_p99_s": round(ttft_p99, 4) if ttft_p99 else None,
        "slot_occupancy": round(sched.slot_occupancy, 4),
        # measured prefix-sharing / speculative twins of the static
        # plans (ISSUE 19): fraction of mapped blocks that were shared
        # in the steady-state leg, and tokens emitted per decoding
        # slot-step (exactly 1.0 without a draft — the spec ratchet's
        # honest baseline)
        "shared_block_fraction": round(sched.shared_block_fraction, 4),
        "accepted_tokens_per_step": round(
            sched.accepted_tokens_per_step, 4),
        # traffic-class leg (ISSUE 20): per-class attainment + the
        # typed-shed fraction from the mixed-class burst above
        "slo_attainment": attain,
        "slo_attainment_latency_critical": lc_frac,
        "shed_fraction": round(len(shed_recs) / n_requests, 4),
        "serving_compile_count": engine.compile_count,
        # which attention each lane actually exercised — a
        # decode/prefill tok/s number is only comparable to priors on
        # the same path (ISSUES 11 + 15)
        "serving_attention_path": engine.attention_path,
        "serving_prefill_path": engine.prefill_path,
        "serve_metrics": {
            "queue_depth_p50": qd[len(qd) // 2] if qd else None,
            "queue_depth_max": qd[-1] if qd else None,
            "preemptions": counters.get("preemptions", 0),
            "growth_stalls": counters.get("growth_stalls", 0),
            "admissions": counters.get("admissions", 0),
            "completions": counters.get("completions", 0),
            "ttft_p99_s": round(ttft_p99, 4) if ttft_p99 else None,
            "ticks": reg.ticks,
        },
    }


def _measure_autoscale(cfg, ecfg, params) -> dict:
    """Autoscale actuation drill (autoscale/, docs/AUTOSCALE.md,
    ISSUE 13): one controller-driven scale-up then scale-down on the
    SAME model/engine shape as the serving leg. ``scale_up_s`` is the
    wall one `add_replica` pays through the controller seam — the
    respawn path: weights + step compile (or persistent-cache
    deserialize) + warmup — the latency a pressure spike waits before
    capacity actually arrives. bench_gate upper-bounds it
    (RLT_BENCH_SCALE_UP_MAX). A drill failure degrades to
    ``autoscale_error`` — the serving measurements must never die with
    it."""
    import shutil
    import tempfile

    try:
        from ray_lightning_tpu.autoscale import (
            AutoscaleController, ControllerConfig, PolicyConfig,
        )
        from ray_lightning_tpu.serve.driver import (
            ReplicaGroupConfig, ServeDriver,
        )

        as_dir = tempfile.mkdtemp(prefix="rlt_bench_autoscale_")
        try:
            drv = ServeDriver(cfg, params, ReplicaGroupConfig(
                n_replicas=1, engine=ecfg, run_dir=as_dir,
                metrics_flush_every_n_ticks=2))
            drv.start()
            # fabricated signals isolate the drill to ACTUATION cost —
            # the signal path itself is the smoke/tests' business
            high = {"available": True, "pressure": 2.0,
                    "queue_depth_now": float(2 * ecfg.capacity),
                    "occupancy": 1.0,
                    "total_slots": float(ecfg.capacity)}
            low = {"available": True, "pressure": 0.0,
                   "queue_depth_now": 0.0, "occupancy": 0.0,
                   "total_slots": float(2 * ecfg.capacity)}
            sigs = [dict(high), dict(low)]
            ctl = AutoscaleController(
                drv,
                ControllerConfig(policy=PolicyConfig(
                    min_replicas=1, max_replicas=2, sustain_polls=1,
                    up_cooldown_s=0.0, down_cooldown_s=0.0)),
                signal_fn=lambda: (sigs.pop(0) if len(sigs) > 1
                                   else dict(sigs[0])))
            ctl.step(now=0.0)     # scale up: the measured spawn
            ctl.step(now=100.0)   # scale down: graceful drain
            result = drv.stop()
            # SLO watch over the drill's own run dir (telemetry/
            # watch.py, ISSUE 14): evaluate the built-in rules against
            # the evidence the drill just persisted. A healthy bench
            # fires ZERO incidents — bench_gate fails the round on
            # incidents > 0 (a breach in the bench's own serving drill
            # is a regression, not noise); skip/null lines waive.
            from ray_lightning_tpu.telemetry.watch import (
                WatchConfig, WatchEngine,
            )

            watch = WatchEngine(as_dir, WatchConfig(capture=False))
            watch.poll()
            return {
                "incidents": len(watch.incidents),
                "scale_up_s": (round(ctl.scale_up_s[0], 4)
                               if ctl.scale_up_s else None),
                "autoscale": {
                    "scale_up_s": (round(ctl.scale_up_s[0], 4)
                                   if ctl.scale_up_s else None),
                    "decisions": ctl.decisions,
                    "scale_ups": ctl.scale_ups,
                    "scale_downs": ctl.scale_downs,
                    "final_replicas":
                        result.stats["final_replicas"],
                },
            }
        finally:
            shutil.rmtree(as_dir, ignore_errors=True)
    except Exception as exc:  # noqa: BLE001 — advisory drill only
        return {"autoscale_error":
                f"{type(exc).__name__}: {str(exc)[:200]}"}


def _watch_summary() -> dict:
    """Watch/incident SCHEMA for every JSON line this process emits
    (ISSUE 14): the rule vocabulary and the shape the measured
    ``incidents`` count (success lines only — the serving drill's run
    dir is the subject) will take. Static, no backend touch: a
    backend-down skip line still tells the recorder what the field
    means, and bench_gate waives the absent count there."""
    try:
        from ray_lightning_tpu.telemetry.watch import BUILTIN_RULES

        return {"watch": {
            "schema": {
                "incidents": "watch-rule breaches fired against the "
                             "bench's own autoscale-drill run dir "
                             "(success lines; absent/null waived)",
            },
            "rules": [r.name for r in BUILTIN_RULES],
            "source": "static-schema",
        }}
    except Exception as exc:  # noqa: BLE001 — advisory data only
        return {"watch_error": f"{type(exc).__name__}: {str(exc)[:200]}"}


def _kill_line(signame: str) -> str:
    """The structured line a driver kill flushes before death: same
    schema as the watchdog line — ONE parseable JSON object, with a
    "skipped" field naming the signal and the tracecheck summary."""
    return json.dumps({
        "metric": "llama_0.5b_train_tokens_per_sec_per_chip",
        "value": 0.0,
        "unit": "tokens/sec",
        "vs_baseline": 0.0,
        "skipped": f"killed: {signame}",
        "error": (f"driver sent {signame} before the benchmark "
                  "completed; partial run discarded"),
        **_ANALYSIS,
    })


def _install_kill_handlers() -> None:
    """SIGTERM/SIGALRM -> flush the structured JSON line, exit 3. A
    harness timeout must land as a parseable line, never as silent
    death."""
    import signal

    def _die(signum, frame):  # noqa: ARG001 — signal handler shape
        try:
            name = signal.Signals(signum).name
        except ValueError:
            name = f"signal {signum}"
        # os.write to fd 1, not print(): the handler may interrupt an
        # in-progress print of another JSON line, and a buffered print
        # here could interleave into it (recreating the unparseable
        # line this handler exists to prevent) or deadlock on the
        # buffer lock. The leading newline closes any half-written
        # line so the LAST stdout line is always this parseable one.
        os.write(1, b"\n" + _kill_line(name).encode() + b"\n")
        os._exit(3)

    for sig in (signal.SIGTERM, signal.SIGALRM):
        try:
            signal.signal(sig, _die)
        except (ValueError, OSError):  # non-main thread / exotic host
            pass


def _device():
    """The chip under test: jax's first device. A backend that does not
    come up raises here, once."""
    import jax

    return jax.devices()[0]


def _verify_kernels() -> dict:
    """Numerical parity of the hand-tuned kernels against the XLA
    reference paths IN THE REAL EXECUTION ENVIRONMENT (on the chip the
    bench runs on; `ops/parity.py`, shared with chip_smoke.py) —
    throughput legs alone would not catch a wrong-but-fast kernel."""
    from ray_lightning_tpu.ops.parity import TOLERANCE, kernel_parity_errors

    errors = kernel_parity_errors()
    return {
        "kernels_verified": all(e <= TOLERANCE for e in errors.values()),
        "kernel_errors": {kk: round(vv, 6) for kk, vv in errors.items()},
    }


def main() -> None:
    import threading

    # FIRST: a driver kill arriving at any later point must still flush
    # a structured line; THEN the CPU-only tracecheck summary, before
    # any backend touch, so error lines carry analysis data too
    _install_kill_handlers()
    _ANALYSIS.update(_concurrency_summary())
    _ANALYSIS.update(_trace_summary())
    _ANALYSIS.update(_numerics_summary())
    _ANALYSIS.update(_multislice_summary())
    _ANALYSIS.update(_guard_summary())
    _ANALYSIS.update(_telemetry_summary())
    _ANALYSIS.update(_serve_summary())
    _ANALYSIS.update(_watch_summary())

    # Watchdog: a hang (a compile that never returns, a device that
    # stops answering) must surface as an honest JSON error line for
    # the bench recorder, not a silent hang. <= 0 disables.
    # a malformed value must not reproduce the silent-failure mode the
    # watchdog exists to prevent — parse-or-default (_env_float)
    watchdog_s = _env_float("RLT_BENCH_WATCHDOG_S", 2700.0)
    finished = threading.Event()

    def _watchdog():
        if not finished.wait(watchdog_s):
            print(json.dumps({
                "metric": "llama_0.5b_train_tokens_per_sec_per_chip",
                "value": 0.0,
                "unit": "tokens/sec",
                "vs_baseline": 0.0,
                "error": (f"benchmark did not complete within "
                          f"{watchdog_s:.0f}s — device unreachable or "
                          "compile hang"),
                **_ANALYSIS,
            }), flush=True)
            os._exit(3)

    if watchdog_s > 0:
        threading.Thread(target=_watchdog, daemon=True).start()

    # No retry and no restart: a backend that does not come up, an OOM
    # or a compile error ends the run with exit 3 and ONE JSON line
    # that carries whatever legs had already landed in ``partial``.
    partial: dict = {}
    try:
        payload = _run(partial)
    except Exception as exc:  # noqa: BLE001 — the recorder's contract:
        # every failure is one parseable line, never a bare traceback
        line = {
            "metric": "llama_0.5b_train_tokens_per_sec_per_chip",
            "value": 0.0,
            "unit": "tokens/sec",
            "vs_baseline": 0.0,
            **partial,
            "error": f"{type(exc).__name__}: {exc}",
            **_ANALYSIS,
        }
        if partial.get("value"):
            line["partial"] = True
        print(json.dumps(line), flush=True)
        finished.set()
        raise SystemExit(3) from None
    payload = {**payload, **_ANALYSIS}
    print(json.dumps(payload), flush=True)
    finished.set()


def _run(sink: dict | None = None) -> dict:
    """One full measurement pass. ``sink`` (main()'s partial-result
    carrier) is updated IN PLACE as legs land, so a mid-run failure
    leaves everything already measured available to the final JSON
    line instead of losing the round."""
    kind = _device().device_kind
    # a kind outside the peak table raises: MFU against a guessed peak
    # is not a measurement
    peak_tflops = _device_peak_tflops(kind)
    probe = _probe_matmul_tflops()

    # on-chip kernel correctness gate (cheap; before the throughput legs
    # so a wrong kernel is flagged even if a later leg OOMs). A CRASHING
    # kernel (raises, not just wrong numbers) must report as a failed
    # gate, not void the throughput legs that don't use it.
    try:
        kernels = _verify_kernels()
    except Exception as exc:  # noqa: BLE001 — the gate result is data
        kernels = {"kernels_verified": False,
                   "kernel_verify_error": f"{type(exc).__name__}: "
                                          f"{str(exc)[:300]}"}

    # Tuned configs per leg, from the v5e sweeps (batch 2..16; chunk
    # 1k..24k; remat on/off x nothing/dots; scan on/off):
    #   * remat=False + unrolled layers wins when the 0.5B model's
    #     activations fit (16 GB chip): no backward recompute, and the
    #     unrolled program lets XLA schedule layers without the scan's
    #     worst-case buffer allocation (remat=False + scan OOMs where
    #     remat=False + unrolled compiles and is fastest);
    #   * at V=32768 materialized logits fit and beat the fused-CE
    #     recompute by ~3%, so the S=2048/S=4096 legs run fused_ce=False;
    #   * the V=128256 leg is where fused CE pays: the materialized
    #     [B, S, V] logits do not even compile there (verified OOM), so
    #     fused is the ONLY path and is reported with its own MFU.
    # headline leg — fatal on failure (the driver schema requires it)
    headline_timing: dict = {}
    tps, cfg = _measure(use_flash=True, fused_ce=False, batch=9, seq=2048,
                        remat=False, scan=False, timing=headline_timing)
    fpt = _flops_per_token(cfg, 2048)
    mfu = tps * fpt / (peak_tflops * 1e12)
    # goodput view of the headline measurement window: timed productive
    # seconds over total wall including the warmup/compile the best-of
    # timing excludes (compile is lost time under goodput semantics);
    # plus the measured recorder cost relative to the step time (the
    # bench_gate bounds it < 1%)
    goodput_fraction = (headline_timing["productive_s"]
                        / headline_timing["wall_s"]
                        if headline_timing.get("wall_s") else 0.0)
    try:
        overhead = _telemetry_overhead_fraction(
            headline_timing.get("step_dt_s") or 1.0)
    except Exception:  # noqa: BLE001 — advisory measurement
        overhead = None

    results = sink if sink is not None else {}
    results.update({
        "goodput_fraction": round(goodput_fraction, 4),
        "telemetry_overhead_fraction": (
            round(overhead, 6) if overhead is not None else None),
        "metric": "llama_0.5b_train_tokens_per_sec_per_chip",
        "value": round(tps, 1),
        "unit": "tokens/sec",
        # overwritten by the baseline leg; on baseline failure it stays
        # 0.0 NEXT TO a vs_baseline_error field — the same "0.0 means
        # not-measured" convention as the watchdog/error JSON lines (the
        # field is required by the driver schema, so it is never dropped)
        "vs_baseline": 0.0,
        "mfu": round(mfu, 4),
        "assumed_peak_tflops": peak_tflops,
        "device_kind": kind,
        "flops_per_token": round(fpt / 1e9, 3),  # GFLOP
        "probe_matmul_tflops": round(probe, 1),
        **kernels,
    })
    mfus = [mfu]

    def leg(name, fn):
        """Secondary legs degrade to a ``<name>_error`` field instead of
        voiding the whole artifact (one OOMing config must not cost the
        round every other number, the round-4 lesson at bench level)."""
        try:
            results.update(fn())
        except Exception as exc:  # noqa: BLE001 — leg failures are data
            results[f"{name}_error"] = f"{type(exc).__name__}: {str(exc)[:300]}"

    def _baseline():
        # every hand-tuned path off — XLA-naive attention, default
        # remat/scan, at ITS swept-best batch (6; larger batches OOM the
        # S^2 score matrices)
        base_tps, _ = _measure(use_flash=False, fused_ce=False, batch=6,
                               seq=2048)
        return {"vs_baseline": round(tps / base_tps, 4)}

    def _s4k():
        # long-sequence leg (2× context)
        t, c = _measure(use_flash=True, fused_ce=False, batch=3, seq=4096,
                        remat=False, scan=False)
        m = t * _flops_per_token(c, 4096) / (peak_tflops * 1e12)
        mfus.append(m)
        return {"s4096_tokens_per_sec": round(t, 1), "s4096_mfu": round(m, 4)}

    def _v128k():
        # Llama-3-vocab leg (V=128256): fused chunked CE (ops/fused_ce.py)
        t, c = _measure(use_flash=True, fused_ce=True, batch=4, seq=2048,
                        vocab=128256, remat=False, scan=False)
        m = t * _flops_per_token(c, 2048) / (peak_tflops * 1e12)
        mfus.append(m)
        return {"v128k_tokens_per_sec": round(t, 1), "v128k_mfu": round(m, 4),
                "v128k_materialized_logits": "OOM (does not compile)"}

    def _flagship():
        # FLAGSHIP leg: remat + scan_layers + fused CE at the Llama-3
        # vocab — the only configuration class that holds at the
        # north-star Llama-3-8B (BASELINE.md config 4: remat+scan+FSDP
        # are mandatory at 8B on real chips), benched at its swept
        # optimum with the inline-backward
        # CE (ops/fused_ce.py _ce_inline — no logits-tile recompute).
        # MFU counts useful FLOPs only: the backward recompute remat
        # performs is real work the flagship deliberately trades for
        # memory, so its MFU reads lower than the unrolled legs.
        # The inline compile has a fallback: this leg's job is a
        # driver-verified flagship number, and an inline-path compile
        # failure must degrade to the non-inline configuration rather
        # than void the row (_flagship_leg).
        def measure(ce_inline):
            return _measure(use_flash=True, fused_ce=True, batch=8,
                            seq=2048, vocab=128256, remat=True, scan=True,
                            remat_policy="nothing", ce_chunk_tokens=4096,
                            ce_inline=ce_inline)

        row, m = _flagship_leg(
            measure, shared,
            lambda t, c: t * _flops_per_token(c, 2048) / (peak_tflops * 1e12),
            shape_desc="B=8 S=2048 V=128256 chunk=4096")
        mfus.append(m)
        return row

    shared: dict = {}

    def _flagship_remat_ce():
        # the pre-inline flagship config, kept as its own leg so the
        # inline win is visible in one artifact; runs BEFORE the inline
        # leg so the latter's fallback can reuse this measurement
        t, c = _measure(use_flash=True, fused_ce=True, batch=8, seq=2048,
                        vocab=128256, remat=True, scan=True,
                        remat_policy="nothing", ce_chunk_tokens=4096)
        m = t * _flops_per_token(c, 2048) / (peak_tflops * 1e12)
        mfus.append(m)
        shared["rematce"] = (t, m)
        return {"flagship_rematce_tokens_per_sec": round(t, 1),
                "flagship_rematce_mfu": round(m, 4)}

    def _flagship_attnout():
        # the round-5 remat policy (save flash VJP residuals — no
        # attention recompute in backward) on top of the inline CE, so
        # the driver artifact carries the comparison against the
        # "nothing" flagship leg in one capture. Same degradation policy
        # as the flagship leg: an inline compile rejection falls back to
        # the non-inline attn_out config instead of voiding the row.
        def measure(ce_inline):
            return _measure(use_flash=True, fused_ce=True, batch=8,
                            seq=2048, vocab=128256, remat=True, scan=True,
                            remat_policy="attn_out", ce_chunk_tokens=4096,
                            ce_inline=ce_inline)

        row, m = _attnout_leg(
            measure,
            lambda t, c: t * _flops_per_token(c, 2048) / (peak_tflops * 1e12))
        mfus.append(m)
        return row

    def _overlap():
        # hot-loop overlap leg (pipeline/overlap.py, docs/PERFORMANCE.md):
        # device-prefetch speedup against a calibrated synthetic slow
        # loader + the AOT warm-start compile metrics (cold vs
        # persistent-cache hit).
        from ray_lightning_tpu.pipeline.overlap import (
            measure_prefetch_overlap,
        )

        _require_tpu("overlap")
        r = measure_prefetch_overlap(steps=30)
        return {"prefetch_speedup": r["value"],
                "prefetch_occupancy": r["pipeline_occupancy"],
                "compile_cold_s": r["compile_cold_s"],
                "compile_warm_s": r["compile_warm_s"],
                "overlap": r}

    def _serving():
        # serving leg (serve/, docs/SERVING.md, ISSUE 8): the real
        # continuous-batching engine on THIS backend. TTFT cold = first
        # request through a FRESH engine including the step compile
        # (the P99 story a persistent compile cache improves); TTFT
        # warm = a later request on the compiled engine (pure
        # queue+prefill); decode throughput at steady state with every
        # slot occupied. Random weights: serving throughput is
        # content-independent.
        return _measure_serving()

    def _reshard():
        # elastic leg (elastic/, docs/ELASTIC.md, ISSUE 9): time a
        # cross-topology checkpoint restore on THIS backend — save a
        # provenance-stamped state on the full local mesh, restore it
        # onto a half-size mesh (or same-size on one device), report
        # wall seconds. The number the elastic supervisor pays per
        # shrink/grow; bench_gate bounds it.
        import shutil
        import tempfile

        import jax
        import jax.numpy as jnp

        from ray_lightning_tpu.checkpoint.io import (
            save_checkpoint,
            sharding_provenance,
            wait_for_checkpoints,
        )
        from ray_lightning_tpu.elastic.reshard import reshard_restore
        from ray_lightning_tpu.parallel.strategy import FSDP

        n = len(jax.devices())
        src = FSDP(min_shard_size=8)
        src.setup()
        # ~32 MiB of params: big enough that the restore is I/O, small
        # enough to never disturb the throughput legs
        params = {"w": jnp.arange(8 * 1024 * 1024,
                                  dtype=jnp.float32).reshape(2048, -1)}
        params = src.shard_params(params)
        state = {"params": params,
                 "step": jax.device_put(jnp.zeros((), jnp.int32),
                                        src.replicated())}
        d = tempfile.mkdtemp(prefix="rlt_bench_reshard_")
        try:
            ck = os.path.join(d, "ck")
            save_checkpoint(ck, state,
                            {"global_step": 0,
                             **sharding_provenance(src.mesh, state)})
            wait_for_checkpoints()
            dst = FSDP(num_workers=max(1, n // 2), min_shard_size=8)
            dst.setup()
            tgt = {"params": dst.shard_params(
                       jax.tree.map(jnp.zeros_like,
                                    jax.device_get(params))),
                   "step": jax.device_put(jnp.zeros((), jnp.int32),
                                          dst.replicated())}
            t0 = time.perf_counter()
            restored = reshard_restore(ck, tgt)
            jax.block_until_ready(restored)
            dt = time.perf_counter() - t0
        finally:
            shutil.rmtree(d, ignore_errors=True)
        return {"reshard_restore_s": round(dt, 4),
                "reshard": {"from_world": n,
                            "to_world": max(1, n // 2),
                            "bytes": int(8 * 1024 * 1024 * 4)}}

    legs = {
        "flagship_rematce": _flagship_remat_ce,
        "flagship": _flagship,
        "flagship_attnout": _flagship_attnout,
        "vs_baseline": _baseline,
        "s4096": _s4k,
        "v128k": _v128k,
        "overlap": _overlap,
        "serving": _serving,
        "reshard": _reshard,
    }
    assert set(legs) == set(LEG_ORDER), "LEG_ORDER out of sync with legs"
    for name in LEG_ORDER:
        leg(name, legs[name])

    # Self-consistency (VERDICT r3 weak #1): the probe is a THROUGHPUT
    # ceiling; any model leg reading more effective FLOP/s than the bare
    # matmul chain means one of the two mismeasured. Flag it in-line
    # rather than shipping arithmetic that cannot all be true.
    results["probe_consistent"] = probe >= 0.95 * max(mfus) * peak_tflops
    return results


if __name__ == "__main__":
    main()

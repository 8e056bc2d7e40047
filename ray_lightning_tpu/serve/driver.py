"""Serving front-end: request streams multiplexed over replica groups.

A **replica** is one model copy behind one `DecodeEngine` + `Scheduler`
pair. Two backends share one driver surface:

  * ``backend="inline"`` — engines in this process, ticked round-robin
    (deterministic; what unit tests and single-host serving use);
  * ``backend="process"`` — each replica is a `runtime.WorkerGroup` of
    one worker process with its own jax runtime, streaming tokens back
    over the group's side channel. Replica death is classified by the
    resilience taxonomy (`resilience.policy.classify_failure`) and,
    within the restart budget, the driver **respawns** the replica: the
    worker reloads weights from the params file, re-warms the step
    through the persistent compile cache (`pipeline.compile_cache` —
    the restart deserializes instead of recompiling), announces itself
    live, and REPLAYS the requests the dead replica had not finished.
    Replay is bitwise-safe by construction — per-request seeds make a
    decoded stream a pure function of the request — so a kill corrupts
    nothing: surviving replicas never notice, and the replayed streams
    are identical to what the dead replica would have produced
    (test-pinned; the serve --smoke gate injects a real SIGKILL).

Telemetry: each replica owns a `telemetry.TelemetryRecorder` and
records the serving span vocabulary (queue_wait / prefill / decode /
detokenize, spans.SERVE_PHASES) per COMPLETED request — cadence-safe —
plus per-request TTFT/TPOT meta the `report` CLI aggregates into its
serving section (docs/OBSERVABILITY.md). PREEMPTED requests get
REPLAYED-tagged spans for their discarded prefix, and whatever is
still in flight at drain time gets INFLIGHT-tagged spans, so a
preempt-heavy or killed run stops under-reporting queue_wait (the tags
keep the report from double-counting the replayed prefix).

Live metrics (telemetry/metrics.py): each replica additionally owns a
`MetricsRegistry` (per-tick queue/slot/pool gauges, event counters,
mergeable latency histograms, flushed to uid-tagged JSONL on the tick
cadence) and a `FlightRecorder` (bounded ring of recent ticks +
scheduler events, cadence-persisted). The driver merges the per-replica
streams into run-level histograms in ``serving.json`` (quantiles from
BUCKETS, exact across replicas and respawned attempts), finalizes a
dead replica's flight ring into ``<run_dir>/flight.json`` stamped with
the resilience classification, and exposes `load_signal(run_dir)` —
the queue-depth/occupancy oracle input ROADMAP item 1(c) autoscale
consumes.

Dynamic serving session (docs/AUTOSCALE.md): beyond the fixed-batch
``run()``, `start()` opens a LIVE session with the autoscale actuation
seams — ``submit()`` (routes to live replicas; defers with a
structured reason when every replica is draining/dead), ``tick()``,
``add_replica()`` (exactly the respawn path: npz reload + persistent
compile-cache re-warm), ``remove_replica(graceful=True)`` (stop
admissions, drain slots to retirement, requeue queued work onto
survivors via the bitwise replay seam), ``stop()``. The
`autoscale.AutoscaleController` drives these from the load signal.
"""
from __future__ import annotations

import dataclasses
import json
import os
import signal
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from ray_lightning_tpu.pipeline.compile_cache import enable_persistent_cache
from ray_lightning_tpu.serve.engine import DecodeEngine, EngineConfig
from ray_lightning_tpu.serve.scheduler import (
    Completion, Request, Scheduler, SLOConfig,
)
from ray_lightning_tpu.analysis.lockwatch import san_lock
from ray_lightning_tpu.telemetry.spans import annotate
from ray_lightning_tpu.utils import get_logger

log = get_logger(__name__)

#: spans are flushed every this many completions (and at shutdown) —
#: the serving analog of the trainer's logging cadence
FLUSH_EVERY_N_COMPLETIONS = 16


# ---- params serialization (the replica weight-reload path) ----------------

def save_params_npz(params, path: str) -> None:
    """Flatten a params pytree to one .npz keyed by `/`-joined paths —
    the weight file a (re)spawned replica loads. Exact round-trip:
    numpy arrays at their stored dtypes, no re-quantization."""
    from ray_lightning_tpu.utils.pytree import named_leaves

    flat = {path_: np.asarray(leaf) for path_, leaf in
            named_leaves(params)}
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    np.savez(path, **flat)


def load_params_npz(path: str):
    """Rebuild the nested params dict from `save_params_npz` output."""
    out: Dict[str, Any] = {}
    with np.load(path) as data:
        for key in data.files:
            node = out
            parts = key.split("/")
            for p in parts[:-1]:
                node = node.setdefault(p, {})
            node[parts[-1]] = data[key]
    return out


# ---- configuration --------------------------------------------------------

@dataclasses.dataclass
class ReplicaGroupConfig:
    """How the driver runs its replicas."""

    n_replicas: int = 1
    backend: str = "inline"              # "inline" | "process"
    engine: EngineConfig = dataclasses.field(default_factory=EngineConfig)
    reserve: str = "worst_case"
    #: run dir: telemetry spans + serving.json summary land here
    run_dir: Optional[str] = None
    #: persistent compile cache dir — respawned replicas deserialize
    #: the step instead of recompiling. Honoured only while
    #: JAX_COMPILATION_CACHE_DIR is unset; None = the
    #: pipeline.compile_cache resolver's choice
    compile_cache_dir: Optional[str] = None
    max_restarts: int = 2
    #: extra env for process replicas (e.g. {"JAX_PLATFORMS": "cpu"})
    env: Optional[Dict[str, str]] = None
    start_timeout: float = 180.0
    #: tensor-parallel degree of each replica (docs/SERVING.md "sharded
    #: replicas"): tp > 1 makes every PROCESS replica a
    #: `runtime.WorkerGroup` of tp ranks over its own tensor mesh —
    #: the engine's one step lowers as an SPMD program, the pool
    #: shards over KV heads, every rank runs the scheduler in lockstep
    #: off the request channel, and rank 0 owns the replica's result
    #: stream + telemetry. Dynamic sessions only (start/submit/stop).
    tp: int = 1
    #: jax platform for session replica ranks (None = inherit the
    #: worker env; CI sets "cpu" for the gloo fabric)
    platform: Optional[str] = None
    #: CPU devices per rank — with ``platform="cpu"`` this is the
    #: dev-box/CI stand-in for per-host TPU chips (runtime.launch)
    cpu_devices_per_rank: Optional[int] = None
    #: live metrics + flight recorder (telemetry/metrics.py) — armed
    #: only when ``run_dir`` is set; False turns both off even then
    #: (the zero-overhead pin covers the off state)
    metrics: bool = True
    #: metrics JSONL flush cadence in engine ticks (RLT501: never 1-ish
    #: small on a hot production loop; the smoke uses small values so
    #: short runs still land samples)
    metrics_flush_every_n_ticks: int = 32
    #: flight-recorder ring length (recent ticks + scheduler events)
    flight_ring: int = 256
    #: flight ring persist cadence in recorded events
    flight_persist_every: int = 16
    #: draft model config (a `LlamaConfig`) for speculative
    #: decoding — arms together with ``engine.draft``; inline replicas
    #: only (the process respawn path reloads ONE params .npz and the
    #: wire carries no draft weights)
    draft_model_cfg: Optional[Any] = None
    #: traffic classes + graceful-overload policy
    #: (scheduler.SLOConfig, docs/SERVING.md "traffic & SLO classes").
    #: None keeps the historical single-class scheduler byte-identical
    slo: Optional[SLOConfig] = None

    def __post_init__(self):
        if self.backend not in ("inline", "process"):
            raise ValueError(f"backend={self.backend!r}")
        if self.backend == "process" and (
                self.engine.draft is not None
                or self.draft_model_cfg is not None):
            raise ValueError(
                "speculative decoding is inline-only: a process "
                "replica (re)spawns from the params .npz, which "
                "carries no draft weights")
        if (self.engine.draft is None) != (self.draft_model_cfg is None):
            raise ValueError(
                "engine.draft and draft_model_cfg arm together — set "
                "both (speculative) or neither")
        if self.n_replicas < 1:
            raise ValueError("n_replicas must be >= 1")
        if self.tp < 1:
            raise ValueError("tp must be >= 1")
        if self.tp > 1 and self.backend != "process":
            raise ValueError(
                "tp > 1 needs backend='process': a sharded replica is "
                "a WorkerGroup of tp rank processes over its own mesh")


@dataclasses.dataclass
class ServeResult:
    #: rid -> emitted token ids
    outputs: Dict[str, List[int]]
    #: rid -> completion metadata (ttft_s, tpot_s, queue_wait_s, ...)
    meta: Dict[str, dict]
    #: replica_id -> restarts performed
    restarts: Dict[int, int]
    #: aggregate serving stats (decode_tokens_per_s, slot_occupancy, ...)
    stats: dict


# ---- per-request telemetry -------------------------------------------------

def _record_completion(recorder, comp: Completion, replica: int) -> None:
    """Emit the request's serving spans from the scheduler's measured
    host times. Explicit `record()` calls with back-dated starts: the
    spans were already over when the request completed."""
    from ray_lightning_tpu.telemetry.spans import (
        PH_DECODE, PH_PREFILL, PH_QUEUE_WAIT,
    )

    decode_start = time.perf_counter() - comp.decode_s   # first token
    prefill_start = decode_start - comp.ttft_s           # admission
    meta = {"rid": comp.rid, "replica": replica,
            "tokens": len(comp.tokens), "ttft_s": round(comp.ttft_s, 6),
            "tpot_s": round(comp.tpot_s, 6),
            "finish": comp.finish_reason, "preempted": comp.preempted}
    recorder.record(PH_QUEUE_WAIT, prefill_start - comp.queue_wait_s,
                    comp.queue_wait_s, meta={"rid": comp.rid})
    recorder.record(PH_PREFILL, prefill_start, comp.ttft_s,
                    meta={"rid": comp.rid})
    recorder.record(PH_DECODE, decode_start, comp.decode_s, meta=meta)


def _record_partial_spans(recorder, info: dict, meta: dict) -> None:
    """Back-dated queue_wait / prefill / decode spans for a request's
    PARTIAL progress (`Scheduler._partial_timing` shape). The one place
    span back-dating happens for non-completed requests — preemption
    and drain accounting can never drift apart. ``meta`` must carry the
    distinguishing tag (``replayed`` / ``inflight``) and must NOT carry
    ``ttft_s``: its absence is what keeps the report's per-request
    aggregation from double-counting these."""
    from ray_lightning_tpu.telemetry.spans import (
        PH_DECODE, PH_PREFILL, PH_QUEUE_WAIT,
    )

    now = time.perf_counter()
    decode_start = now - info["decode_s"]
    prefill_start = decode_start - info["prefill_s"]
    recorder.record(PH_QUEUE_WAIT,
                    prefill_start - info["queue_wait_s"],
                    info["queue_wait_s"], meta=meta)
    if info["prefill_s"] > 0:
        recorder.record(PH_PREFILL, prefill_start, info["prefill_s"],
                        meta=meta)
    if info["decode_s"] > 0:
        recorder.record(PH_DECODE, decode_start, info["decode_s"],
                        meta=meta)


def _record_preemption(recorder, detail: dict, replica: int) -> None:
    """Spans for the DISCARDED prefix of a just-preempted request,
    tagged ``replayed`` — the report shows the wall this prefix burned
    without double-counting it into the request's final latency (the
    retirement spans cover the replayed run)."""
    _record_partial_spans(recorder, detail, {
        "rid": detail["rid"], "replica": replica, "replayed": True,
        "emitted": detail["emitted"], "preempted": detail["preempted"]})


def _record_drain(recorder, sched, replica: int) -> None:
    """Spans for requests STILL IN FLIGHT when serving stops (replica
    death, shutdown): tagged ``inflight`` so their partial queue_wait /
    prefill / decode wall is accounted instead of vanishing with the
    slot state. Tags keep the report from treating them as completed
    requests."""
    for info in sched.inflight_snapshot():
        _record_partial_spans(recorder, info, {
            "rid": info["rid"], "replica": replica, "inflight": True,
            "state": info["state"], "emitted": info["emitted"],
            "preempted": info["preempted"]})


def _make_recorder(run_dir: Optional[str], replica: int):
    from ray_lightning_tpu.telemetry.spans import (
        NULL_RECORDER, TelemetryRecorder,
    )

    if run_dir is None:
        return NULL_RECORDER
    return TelemetryRecorder(
        os.path.join(run_dir, "telemetry"), rank=replica)


def _make_metrics(run_dir: Optional[str], replica: int,
                  enabled: bool = True, flush_every: int = 32):
    from ray_lightning_tpu.telemetry.metrics import (
        NULL_METRICS, MetricsRegistry,
    )

    if run_dir is None or not enabled:
        return NULL_METRICS
    return MetricsRegistry(os.path.join(run_dir, "telemetry"),
                           replica=replica,
                           flush_every_n_ticks=flush_every)


def _make_flight(run_dir: Optional[str], replica: int,
                 enabled: bool = True, maxlen: int = 256,
                 persist_every: int = 16):
    from ray_lightning_tpu.telemetry.metrics import (
        NULL_FLIGHT, FlightRecorder, flight_path,
    )

    if run_dir is None or not enabled:
        return NULL_FLIGHT
    return FlightRecorder(
        flight_path(os.path.join(run_dir, "telemetry"), replica),
        replica=replica, maxlen=maxlen, persist_every=persist_every)


# ---- one replica's serving loop (runs in-process or in the worker) --------

def _serve_loop(engine: DecodeEngine, reserve: str,
                requests: Sequence[Request], replica: int,
                run_dir: Optional[str] = None,
                on_token=None, on_completion=None, on_preempt=None,
                fault: Optional[dict] = None,
                fault_dir: Optional[str] = None,
                metrics_cfg: Optional[dict] = None,
                slo: Optional[SLOConfig] = None, on_shed=None):
    """Drain ``requests`` through one replica. ``on_token(rid, tok)``
    streams tokens as they are emitted; ``on_completion(comp)`` fires at
    retirement. ``fault={"kill_after_tokens": n}`` SIGKILLs this process
    after the n-th emitted token, once per ``fault_dir`` marker — the
    smoke gate's mid-stream replica death. ``metrics_cfg`` carries the
    `ReplicaGroupConfig` metrics knobs (enabled / flush cadence / flight
    ring)."""
    mc = metrics_cfg or {}
    recorder = _make_recorder(run_dir, replica)
    metrics = _make_metrics(run_dir, replica,
                            enabled=mc.get("enabled", True),
                            flush_every=mc.get("flush_every", 32))
    flight = _make_flight(run_dir, replica,
                          enabled=mc.get("enabled", True),
                          maxlen=mc.get("flight_ring", 256),
                          persist_every=mc.get("flight_persist_every",
                                               16))
    engine.metrics = metrics
    sched = Scheduler(engine, reserve=reserve, metrics=metrics,
                      flight=flight, slo=slo)

    def drain_sheds():
        # typed shed records are terminal statuses, never silence
        # (RLT505): every record reaches the caller's stream
        for rec in sched.take_sheds():
            if on_shed is not None:
                on_shed(rec)

    for req in requests:
        sched.submit(req)
    drain_sheds()  # enqueue-time budget sheds fire before any tick
    emitted_total = 0
    kill_after = int((fault or {}).get("kill_after_tokens", 0))
    marker = (os.path.join(fault_dir, f"replica{replica}.killed")
              if fault_dir else None)
    done: List[Completion] = []
    while sched.busy():
        completions = sched.tick()
        for detail in sched.last_preemption_details:
            # account the discarded prefix (replayed-tagged) — the
            # replay regenerates the stream bitwise, so a consumer
            # keeping the prefix would duplicate tokens
            _record_preemption(recorder, detail, replica)
            if on_preempt is not None:
                on_preempt(detail["rid"])
        for rid, tok in sched.last_emissions:
            emitted_total += 1
            if on_token is not None:
                on_token(rid, tok)
        for comp in completions:
            done.append(comp)
            _record_completion(recorder, comp, replica)
            if on_completion is not None:
                on_completion(comp)
            if len(done) % FLUSH_EVERY_N_COMPLETIONS == 0:
                recorder.flush()
        drain_sheds()
        if (kill_after and emitted_total >= kill_after and marker
                and not os.path.exists(marker)):
            # fire-once across respawns: the marker outlives this
            # process, so the replayed replica serves to completion.
            # Drain-time accounting + a final metrics/flight flush land
            # BEFORE the kill — the injected drill leaves an exact
            # final-ticks postmortem (a real SIGKILL leaves the last
            # cadence-persisted ring, at most one cadence stale).
            with open(marker, "w") as f:
                f.write(str(emitted_total))
            _record_drain(recorder, sched, replica)
            recorder.flush()
            metrics.flush()
            flight.persist()
            os.kill(os.getpid(), signal.SIGKILL)
    _record_drain(recorder, sched, replica)
    recorder.flush()
    recorder.close()
    metrics.close()
    flight.close()
    return done, sched


# ---- process-replica worker main ------------------------------------------

def _replica_worker_main(model_cfg_kw: dict, params_path: str,
                         engine_kw: dict, reserve: str,
                         request_dicts: List[dict], replica: int,
                         run_dir: Optional[str],
                         compile_cache_dir: Optional[str],
                         fault: Optional[dict],
                         fault_dir: Optional[str],
                         metrics_cfg: Optional[dict] = None,
                         slo_kw: Optional[dict] = None) -> dict:
    """Runs inside the WorkerGroup worker process: rebuild the model,
    reload weights, warm the step (persistent compile cache when
    armed), announce live, then serve — streaming every token over the
    side channel so the driver holds partial streams when this process
    dies mid-request."""
    from ray_lightning_tpu.models.serving import (
        config_from_wire, serving_model,
    )
    from ray_lightning_tpu.runtime import session

    enable_persistent_cache(compile_cache_dir)
    model = serving_model(config_from_wire(model_cfg_kw))
    params = load_params_npz(params_path)
    t0 = time.perf_counter()
    engine = DecodeEngine(model, params, EngineConfig(**engine_kw))
    engine.warmup()
    warm_s = time.perf_counter() - t0
    session.put_queue(("live", replica, {"warmup_s": round(warm_s, 3)}))
    requests = [Request(**d) for d in request_dicts]

    def on_token(rid, tok):
        session.put_queue(("tok", replica, rid, tok))

    def on_preempt(rid):
        session.put_queue(("preempt", replica, rid))

    def on_completion(comp):
        session.put_queue(("done", replica, comp.rid, {
            "finish_reason": comp.finish_reason,
            "queue_wait_s": comp.queue_wait_s,
            "ttft_s": comp.ttft_s, "tpot_s": comp.tpot_s,
            "decode_s": comp.decode_s, "preempted": comp.preempted,
            "n_tokens": len(comp.tokens),
            "priority": comp.priority,
        }))

    def on_shed(rec):
        session.put_queue(("shed", replica, rec["rid"], rec))

    done, sched = _serve_loop(engine, reserve, requests, replica,
                              run_dir=run_dir, on_token=on_token,
                              on_completion=on_completion,
                              on_preempt=on_preempt, fault=fault,
                              fault_dir=fault_dir,
                              metrics_cfg=metrics_cfg,
                              slo=SLOConfig.from_wire(slo_kw),
                              on_shed=on_shed)
    return {"replica": replica, "completed": len(done),
            "steps": engine.steps, "warmup_s": warm_s,
            "compile_count": engine.compile_count,
            "occupancy": sched.slot_occupancy}


# ---- dynamic-session replica worker (the request-channel consumer) --------

def _replica_session_main(model_cfg_kw: dict, params_path: str,
                          engine_kw: dict, reserve: str, replica: int,
                          run_dir: Optional[str], session_dir: str,
                          compile_cache_dir: Optional[str],
                          fault: Optional[dict],
                          fault_dir: Optional[str],
                          metrics_cfg: Optional[dict],
                          channel_epoch: int, tp: int,
                          slo_kw: Optional[dict] = None,
                          rank: int = 0) -> dict:
    """One rank of a DYNAMIC-SESSION replica group (serve/channel.py).

    Unlike `_replica_worker_main` (fixed batch shipped at spawn), work
    arrives over the per-replica command log and results stream back
    over the existing side channel — the bidirectional wire that lets
    `ServeDriver` sessions scale a process deployment.

    Every rank (``tp > 1``: the replica spans a WorkerGroup over its
    own tensor mesh) holds the FULL host-side scheduler in lockstep;
    rank 0 is the replica **leader**: it alone reads commands at its
    own pace, journals each state-changing iteration to the cursor log,
    emits results/acks, and owns the replica's telemetry streams
    (leader-aggregated: one metrics/flight/span stream per replica, not
    per rank). Followers replay the leader's journal — scheduler
    determinism makes their state bit-identical — so the SPMD step
    always sees every rank enter the same tick with the same inputs.

    Results are BATCHED one side-channel item per tick (tokens,
    preemptions, completions, the command ack, evictions together) —
    the channel's documented discipline, lint-enforced as RLT504."""
    from ray_lightning_tpu.models.serving import (
        config_from_wire, serving_model,
    )
    from ray_lightning_tpu.runtime import session
    from ray_lightning_tpu.serve.channel import (
        ChannelReader, CursorReader, CursorWriter, request_from_wire,
        request_to_wire,
    )

    enable_persistent_cache(compile_cache_dir)
    model = serving_model(config_from_wire(model_cfg_kw))
    params = load_params_npz(params_path)
    mesh = None
    if tp > 1:
        from ray_lightning_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(tensor=tp)
    t0 = time.perf_counter()
    engine = DecodeEngine(model, params, EngineConfig(**engine_kw),
                          mesh=mesh)
    engine.warmup()
    warm_s = time.perf_counter() - t0
    leader = rank == 0
    mc = metrics_cfg or {}
    tdir = run_dir if leader else None
    recorder = _make_recorder(tdir, replica)
    metrics = _make_metrics(tdir, replica, enabled=mc.get("enabled", True),
                            flush_every=mc.get("flush_every", 32))
    flight = _make_flight(tdir, replica, enabled=mc.get("enabled", True),
                          maxlen=mc.get("flight_ring", 256),
                          persist_every=mc.get("flight_persist_every", 16))
    engine.metrics = metrics
    sched = Scheduler(engine, reserve=reserve, metrics=metrics,
                      flight=flight, slo=SLOConfig.from_wire(slo_kw))
    reader = ChannelReader(session_dir, replica, channel_epoch)
    cursor_w = (CursorWriter(session_dir, replica, channel_epoch)
                if leader and tp > 1 else None)
    cursor_r = (CursorReader(session_dir, replica, channel_epoch)
                if not leader else None)
    if leader:
        session.put_queue(("live", replica,
                           {"warmup_s": round(warm_s, 3)}))
    kill_after = int((fault or {}).get("kill_after_tokens", 0))
    marker = (os.path.join(fault_dir, f"replica{replica}.killed")
              if fault_dir else None)
    emitted_total = 0
    state = {"draining": False, "paused": False, "stop": None}

    def apply(cmd) -> List:
        """Apply one command to the local scheduler; returns evictions
        (same on every rank — only the leader WIRES them back)."""
        op = cmd["op"]
        ev: List = []
        if op == "submit":
            sched.enqueue(request_from_wire(cmd["req"]),
                          int(cmd.get("preempts", 0)))
        elif op == "drain":
            state["draining"] = True
            sched.begin_drain()
            ev = sched.evict_queued()
        elif op == "stop":
            mode = cmd.get("mode", "finish")
            state["stop"] = mode
            if mode == "hard":
                sched.begin_drain()
                ev = sched.evict_queued() + sched.evict_slotted()
        elif op == "pause":
            state["paused"] = True
        elif op == "resume":
            state["paused"] = False
        return ev

    def run_tick():
        """One scheduler tick -> the batched result item's fields."""
        completions = sched.tick()
        toks = [[rid, int(tok)] for rid, tok in sched.last_emissions]
        preempts = list(sched.last_preemptions)
        for detail in sched.last_preemption_details:
            _record_preemption(recorder, detail, replica)
        dones = []
        for comp in completions:
            _record_completion(recorder, comp, replica)
            dones.append([comp.rid, {
                "finish_reason": comp.finish_reason,
                "queue_wait_s": comp.queue_wait_s,
                "ttft_s": comp.ttft_s, "tpot_s": comp.tpot_s,
                "decode_s": comp.decode_s, "preempted": comp.preempted,
                "n_tokens": len(comp.tokens),
                "priority": comp.priority,
            }])
            if len(sched.completions) % FLUSH_EVERY_N_COMPLETIONS == 0:
                recorder.flush()
        # mid-drain growth-stall preemptions land back in the closed
        # queue — evict them for the survivors, like the inline tick
        ev = sched.evict_queued() if state["draining"] else []
        return toks, preempts, dones, ev

    if leader:
        while True:
            cmds = reader.poll()
            evicted: List = []
            starts: List = []
            for cmd in cmds:
                if cmd["op"] == "submit":
                    # announce every accepted submit: the driver resets
                    # the stream's output prefix on this — a no-op for
                    # fresh work, THE stale-prefix drop for an epoch
                    # replay after respawn
                    starts.append(cmd["req"]["rid"])
                evicted.extend(apply(cmd))
            # enqueue-time budget sheds (typed records, RLT505) fire
            # inside apply(); tick-time dry-pool sheds extend below
            sheds = sched.take_sheds()
            if state["stop"] in ("hard", "abort"):
                if cursor_w is not None and cmds:
                    cursor_w.advance(reader.last_seq, False)
                payload = {"ack": reader.last_seq}
                if evicted:
                    payload["evicted"] = [[request_to_wire(q), p]
                                          for q, p in evicted]
                if sheds:
                    payload["sheds"] = sheds
                if cmds or evicted or sheds:
                    session.put_queue(("batch", replica, payload))
                break
            do_tick = not state["paused"] and sched.busy()
            if cursor_w is not None and (cmds or do_tick):
                # journal BEFORE the tick: the step's collectives block
                # until the followers join, and they join by reading
                # this record
                cursor_w.advance(reader.last_seq, do_tick)
            toks, preempts, dones, ev2 = (run_tick() if do_tick
                                          else ([], [], [], []))
            evicted.extend(ev2)
            sheds.extend(sched.take_sheds())
            emitted_total += len(toks)
            if cmds or toks or preempts or dones or evicted or sheds:
                # ONE side-channel item per iteration — tokens, acks,
                # completions, evictions, sheds batched (RLT504)
                payload: Dict[str, Any] = {}
                if starts:
                    payload["starts"] = starts
                if toks:
                    payload["toks"] = toks
                if preempts:
                    payload["preempts"] = preempts
                if dones:
                    payload["dones"] = dones
                if evicted:
                    payload["evicted"] = [[request_to_wire(q), p]
                                          for q, p in evicted]
                if sheds:
                    payload["sheds"] = sheds
                if cmds:
                    payload["ack"] = reader.last_seq
                session.put_queue(("batch", replica, payload))
            if (kill_after and emitted_total >= kill_after and marker
                    and not os.path.exists(marker)):
                # fire-once mid-stream SIGKILL (the ramp leg's injected
                # death): marker outlives the process, the respawned
                # group serves the epoch replay to completion
                with open(marker, "w") as f:
                    f.write(str(emitted_total))
                _record_drain(recorder, sched, replica)
                recorder.flush()
                metrics.flush()
                flight.persist()
                os.kill(os.getpid(), signal.SIGKILL)
            if ((state["draining"] or state["stop"] == "finish")
                    and not sched.busy()):
                break
            if not do_tick and not cmds:
                time.sleep(0.004)
        if cursor_w is not None:
            cursor_w.end()
            cursor_w.close()
    else:
        # follower: replay the leader's iteration journal verbatim —
        # no policy, no emissions, just lockstep state + the SPMD step
        while True:
            rec = cursor_r.next()
            if rec is None:
                time.sleep(0.004)
                continue
            if rec.get("end"):
                break
            target = int(rec["seq"])
            cmds = reader.take_upto(target)
            while reader.last_seq < target:
                # the command file is written before the cursor record,
                # but a shared-FS reader can still lag — wait it out
                time.sleep(0.002)
                cmds.extend(reader.take_upto(target))
            for cmd in cmds:
                apply(cmd)
            if rec.get("tick"):
                run_tick()
            # lockstep state only: the LEADER owns shed emission; a
            # follower drains its identical records to bound the list
            sched.take_sheds()  # rlt: disable=RLT505
    _record_drain(recorder, sched, replica)
    recorder.flush()
    recorder.close()
    if metrics.enabled:
        # stamp the stream retired so the load signal stops pooling
        # this replica's stale window into LIVE pressure
        metrics.gauge("retired", 1)
        metrics.tick_end()
    metrics.close()
    flight.close()
    return {"replica": replica, "completed": len(sched.completions),
            "steps": engine.steps, "warmup_s": warm_s,
            "compile_count": engine.compile_count,
            "occupancy": sched.slot_occupancy}


# ---- the driver ------------------------------------------------------------

def _require_chip_free_parent(cfg: "ReplicaGroupConfig") -> None:
    """One process for each chip: a parent that has touched the TPU
    holds it, and a replica process that needs it then fails or hangs.
    Refuse before spawning. Replicas pinned to the CPU do not need the
    chip and are exempt."""
    import jax
    from jax._src import xla_bridge

    child_platform = ((cfg.env or {}).get("JAX_PLATFORMS")
                      or cfg.platform or os.environ.get("JAX_PLATFORMS"))
    if child_platform == "cpu":
        return
    if (xla_bridge.backends_are_initialized()
            and jax.default_backend() == "tpu"):
        raise RuntimeError(
            "backend='process' from a process that already initialized "
            "the TPU backend: this process holds the host's chips, so "
            "replica processes could never reach them. Drive the chips "
            "from one process with backend='inline' (one replica per "
            "chip), or keep the parent off JAX (pass params as a .npz "
            "path and do not touch jax before spawning).")


def _inline_device(replica: int):
    """Inline replica ``i`` lives on local device ``i mod n``: one
    process drives every chip of the host, one replica per chip."""
    import jax

    devices = jax.local_devices()
    return devices[replica % len(devices)]


class _Replica:
    """One inline replica in a dynamic serving session: engine +
    scheduler + recorder and a three-state lifecycle
    (live -> draining -> stopped)."""

    __slots__ = ("id", "engine", "sched", "recorder", "state",
                 "spawned_at", "warm_s")

    def __init__(self, rid: int, engine, sched, recorder,
                 warm_s: float):
        self.id = rid
        self.engine = engine
        self.sched = sched
        self.recorder = recorder
        self.state = "live"
        self.spawned_at = time.perf_counter()
        self.warm_s = warm_s


class _ProcessReplica:
    """One PROCESS replica in a dynamic serving session: a spawn/
    respawn thread around a `runtime.WorkerGroup` of ``cfg.tp`` ranks,
    a `serve.channel.ChannelWriter` commands flow in over, and the
    driver-side assignment ledger the respawn replay is computed from.
    Same three-state lifecycle as `_Replica`."""

    __slots__ = ("id", "state", "spawned_at", "warm_s", "writer",
                 "assigned", "live_evt", "thread", "attempts",
                 "restarts", "error", "result", "acked", "warmups")

    def __init__(self, rid: int, writer):
        import threading

        self.id = rid
        self.writer = writer
        self.state = "live"
        self.spawned_at = time.perf_counter()
        self.warm_s = None
        #: requests this replica currently owns, submission order —
        #: minus completions and evictions; the respawn replay set
        self.assigned: List[Request] = []
        self.live_evt = threading.Event()
        self.thread = None
        self.attempts = 0
        self.restarts = 0
        self.error: Optional[BaseException] = None
        self.result: Optional[dict] = None
        #: highest command seq the worker acked (observability + the
        #: channel tests' replay-safety probe)
        self.acked = 0
        self.warmups: List[float] = []


class ServeDriver:
    """Multiplex request streams over ``cfg.n_replicas`` replicas.

    ``model_cfg`` is the configuration of a decoder `models/serving.py`
    knows (`LlamaConfig`, `MlaMoeConfig`); ``params`` is the
    weights pytree (inline) or a ``.npz`` path from `save_params_npz`
    (required for process replicas — the weight-reload path IS the
    respawn story). Requests are assigned round-robin at submission;
    on replica death the unfinished remainder replays on the respawned
    replica.
    """

    def __init__(self, model_cfg, params, cfg: ReplicaGroupConfig,
                 draft_params=None):
        self.model_cfg = model_cfg
        self.cfg = cfg
        self.params = params
        self.params_path = params if isinstance(params, str) else None
        if cfg.backend == "process" and self.params_path is None:
            raise ValueError(
                "process replicas need a params .npz path "
                "(save_params_npz) — the respawn path reloads from it")
        if (cfg.draft_model_cfg is not None) != (draft_params is not None):
            raise ValueError(
                "cfg.draft_model_cfg and draft_params arm together — "
                "pass both (speculative inline replicas) or neither")
        self.draft_params = draft_params
        # ---- dynamic serving session state (docs/AUTOSCALE.md) ----
        self._session_active = False
        self.replicas: Dict[int, "_Replica"] = {}
        self._next_replica = 0
        self._rr = 0
        #: requests with no live replica to route to — the structured
        #: deferral queue (never round-robined onto a draining replica)
        self.pending: Optional[deque] = None
        self.outputs = {}
        self.meta = {}
        self.last_deferral: Optional[dict] = None
        self._spawn_faults: List[dict] = []
        self.last_spawn_s: Optional[float] = None
        self.driver_metrics = None
        self.driver_flight = None

    def _metrics_cfg(self) -> dict:
        return {"enabled": self.cfg.metrics,
                "flush_every": self.cfg.metrics_flush_every_n_ticks,
                "flight_ring": self.cfg.flight_ring,
                "flight_persist_every": self.cfg.flight_persist_every}

    def _slo_kw(self) -> Optional[dict]:
        return (self.cfg.slo.to_wire()
                if self.cfg.slo is not None else None)

    # ---- inline ----------------------------------------------------------

    def _run_inline(self, requests: Sequence[Request],
                    fault: Optional[dict]) -> ServeResult:
        from ray_lightning_tpu.models.serving import serving_model

        enable_persistent_cache(self.cfg.compile_cache_dir)
        params = self.params
        if self.params_path is not None:
            params = load_params_npz(self.params_path)
        model = serving_model(self.model_cfg)
        draft_model = (serving_model(self.cfg.draft_model_cfg)
                       if self.cfg.draft_model_cfg is not None else None)
        outputs: Dict[str, List[int]] = {}
        meta: Dict[str, dict] = {}
        stats_occ: List[float] = []
        t0 = time.perf_counter()
        n_tokens = 0
        scheds = []
        recorders = []
        mc = self._metrics_cfg()
        for r in range(self.cfg.n_replicas):
            metrics = _make_metrics(self.cfg.run_dir, r,
                                    enabled=mc["enabled"],
                                    flush_every=mc["flush_every"])
            flight = _make_flight(
                self.cfg.run_dir, r, enabled=mc["enabled"],
                maxlen=mc["flight_ring"],
                persist_every=mc["flight_persist_every"])
            engine = DecodeEngine(model, params, self.cfg.engine,
                                  metrics=metrics,
                                  draft_model=draft_model,
                                  draft_params=self.draft_params,
                                  device=_inline_device(r))
            engine.warmup()
            sched = Scheduler(engine, reserve=self.cfg.reserve,
                              metrics=metrics, flight=flight,
                              slo=self.cfg.slo)
            scheds.append(sched)
            recorders.append(_make_recorder(self.cfg.run_dir, r))

        def note_sheds(r: int, sched) -> None:
            # typed terminal status for every shed stream — a shed
            # request is never silently absent from the result (RLT505)
            for rec in sched.take_sheds():
                meta[rec["rid"]] = {
                    "replica": r, "finish_reason": "shed",
                    **{k: v for k, v in rec.items() if k != "rid"}}

        for i, req in enumerate(requests):
            scheds[i % len(scheds)].submit(req)
            outputs[req.rid] = []
        for r, sched in enumerate(scheds):
            note_sheds(r, sched)
        # round-robin tick until every replica drains — the inline
        # analog of replicas running concurrently
        while any(s.busy() for s in scheds):
            for r, sched in enumerate(scheds):
                if not sched.busy():
                    continue
                completions = sched.tick()
                for detail in sched.last_preemption_details:
                    # the replay resends from scratch; the discarded
                    # prefix is accounted as a replayed-tagged span
                    outputs[detail["rid"]] = []
                    _record_preemption(recorders[r], detail, r)
                for rid, tok in sched.last_emissions:
                    outputs[rid].append(tok)
                    n_tokens += 1
                for comp in completions:
                    _record_completion(recorders[r], comp, r)
                    meta[comp.rid] = {
                        "replica": r,
                        "finish_reason": comp.finish_reason,
                        "queue_wait_s": comp.queue_wait_s,
                        "ttft_s": comp.ttft_s, "tpot_s": comp.tpot_s,
                        "preempted": comp.preempted,
                        "n_tokens": len(comp.tokens),
                        "priority": comp.priority,
                    }
                note_sheds(r, sched)
        wall = time.perf_counter() - t0
        for r, sched in enumerate(scheds):
            stats_occ.append(sched.slot_occupancy)
            _record_drain(recorders[r], sched, r)
            recorders[r].flush()
            recorders[r].close()
            sched.metrics.close()
            sched.flight.close()
        stats = {
            "decode_tokens_per_s": n_tokens / max(wall, 1e-9),
            "slot_occupancy": float(np.mean(stats_occ)),
            "n_requests": len(requests), "n_tokens": n_tokens,
            "wall_s": wall,
            "compile_count": max(s.engine.compile_count for s in scheds),
            "requests_shed": sum(
                1 for m in meta.values()
                if m.get("finish_reason") == "shed"),
        }
        result = ServeResult(outputs=outputs, meta=meta,
                             restarts={r: 0 for r in
                                       range(self.cfg.n_replicas)},
                             stats=stats)
        self._write_summary(result)
        return result

    # ---- process replicas ------------------------------------------------

    def _run_process(self, requests: Sequence[Request],
                     fault: Optional[dict]) -> ServeResult:
        import threading

        from ray_lightning_tpu.resilience.policy import classify_failure
        from ray_lightning_tpu.runtime.group import WorkerGroup

        from ray_lightning_tpu.models.serving import config_to_wire

        cfgkw = config_to_wire(self.model_cfg)
        enginekw = dataclasses.asdict(self.cfg.engine)
        n = self.cfg.n_replicas
        assign: List[List[Request]] = [[] for _ in range(n)]
        outputs: Dict[str, List[int]] = {}
        meta: Dict[str, dict] = {}
        for i, req in enumerate(requests):
            assign[i % n].append(req)
            outputs[req.rid] = []
        restarts = {r: 0 for r in range(n)}
        errors: List[BaseException] = []
        lock = san_lock("serve.driver.batch")
        fault_dir = self.cfg.run_dir or os.path.join(
            os.getcwd(), "rlt_logs", "serve")
        os.makedirs(fault_dir, exist_ok=True)
        t0 = time.perf_counter()
        token_count = [0]
        warmups: Dict[int, List[float]] = {r: [] for r in range(n)}
        occupancy: Dict[int, float] = {}
        compile_counts: Dict[int, int] = {}

        def on_queue_item(_rank, item):
            kind = item[0]
            with lock:
                if kind == "tok":
                    _, _rep, rid, tok = item
                    outputs[rid].append(tok)
                    token_count[0] += 1
                elif kind == "preempt":
                    # scheduler-level preemption: the replay resends
                    # the stream from scratch — drop the prefix
                    outputs[item[2]] = []
                elif kind == "done":
                    _, rep, rid, m = item
                    meta[rid] = {"replica": rep, **m}
                elif kind == "shed":
                    # typed terminal status: the shed stream ends with
                    # an explicit record, never silence (RLT505); the
                    # respawn replay filters on meta, so a shed rid is
                    # terminal and never double-counted
                    _, rep, rid, rec = item
                    meta[rid] = {
                        "replica": rep, "finish_reason": "shed",
                        **{k: v for k, v in rec.items()
                           if k != "rid"}}
                    outputs[rid] = []
                elif kind == "live":
                    warmups[item[1]].append(item[2]["warmup_s"])

        def run_replica(r: int) -> None:
            remaining = list(assign[r])
            rep_fault = (fault if fault and
                         fault.get("replica", 0) == r else None)
            while True:
                with lock:
                    remaining = [q for q in remaining
                                 if q.rid not in meta]
                    for q in remaining:
                        # drop partial streams of requests the dead
                        # replica had in flight — replay regenerates
                        # them bitwise from the seed
                        outputs[q.rid] = []
                if not remaining:
                    return
                group = WorkerGroup(
                    num_workers=1, env=dict(self.cfg.env or {}),
                    log_dir=os.path.join(fault_dir, f"replica{r}"),
                    start_timeout=self.cfg.start_timeout)
                try:
                    group.start()
                    res = group.run(
                        _replica_worker_main,
                        shared_args=(
                            dict(cfgkw), self.params_path,
                            dict(enginekw), self.cfg.reserve,
                            [_req_dict(q) for q in remaining], r,
                            self.cfg.run_dir,
                            self.cfg.compile_cache_dir, rep_fault,
                            fault_dir, self._metrics_cfg(),
                            self._slo_kw()),
                        on_queue_item=on_queue_item)
                    with lock:
                        occupancy[r] = res[0]["occupancy"]
                        compile_counts[r] = res[0]["compile_count"]
                    return
                except Exception as exc:  # noqa: BLE001 — classified below
                    fc = classify_failure(exc)
                    log.warning(
                        "serve replica %d died (%s/%s): %s", r, fc.kind,
                        fc.cause, fc.detail)
                    respawning = (fc.restartable
                                  and restarts[r] < self.cfg.max_restarts)
                    # flight-recorder postmortem: the dead worker's last
                    # cadence-persisted ring, stamped with the
                    # resilience classification — the SIGKILL drill's
                    # readable last-N-ticks record next to the log tail
                    if self.cfg.run_dir and self.cfg.metrics:
                        from ray_lightning_tpu.telemetry.metrics import (
                            finalize_flight,
                        )

                        finalize_flight(
                            os.path.join(self.cfg.run_dir, "telemetry"),
                            r,
                            {"kind": fc.kind, "cause": fc.cause,
                             "detail": fc.detail,
                             "restartable": fc.restartable,
                             "restarts_so_far": restarts[r],
                             "respawning": respawning},
                            os.path.join(self.cfg.run_dir,
                                         "flight.json"))
                    if not respawning:
                        with lock:
                            errors.append(exc)
                        return
                    restarts[r] += 1
                finally:
                    group.shutdown()

        threads = [threading.Thread(target=run_replica, args=(r,),
                                    daemon=True) for r in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        wall = time.perf_counter() - t0
        warm_all = [w for ws in warmups.values() for w in ws]
        stats = {
            "decode_tokens_per_s": token_count[0] / max(wall, 1e-9),
            "slot_occupancy": (float(np.mean(list(occupancy.values())))
                               if occupancy else None),
            "n_requests": len(requests), "n_tokens": token_count[0],
            "wall_s": wall,
            "warmup_cold_s": warm_all[0] if warm_all else None,
            "warmup_respawn_s": (max(warm_all[1:]) if len(warm_all) > 1
                                 else None),
            "compile_count": (max(compile_counts.values())
                              if compile_counts else None),
            "restarts_total": sum(restarts.values()),
            "requests_shed": sum(
                1 for m in meta.values()
                if m.get("finish_reason") == "shed"),
        }
        result = ServeResult(outputs=outputs, meta=meta,
                             restarts=restarts, stats=stats)
        self._write_summary(result)
        return result

    # ---- entry -----------------------------------------------------------

    def run(self, requests: Sequence[Request],
            fault: Optional[dict] = None) -> ServeResult:
        """Serve ``requests`` to completion. ``fault`` (process backend
        only): ``{"replica": r, "kill_after_tokens": n}`` SIGKILLs
        replica ``r`` once, mid-stream — the recovery drill."""
        if self.cfg.tp > 1:
            raise ValueError(
                "tp > 1 replicas are dynamic-session only (start()/"
                "submit()/stop()): the fixed-batch run() ships its "
                "request list at spawn and stays tp=1")
        # COPY before stamping: mutating the caller's Request objects
        # would make a reused request list carry the previous run's
        # arrival stamps, silently inflating every queue_wait/TTFT of
        # the next run (review finding, test-pinned)
        requests = [dataclasses.replace(r) for r in requests]
        now = time.perf_counter()
        for req in requests:
            if req.arrival == 0.0:
                req.arrival = now
        if self.cfg.backend == "inline":
            if fault:
                raise ValueError("fault injection needs "
                                 "backend='process' — a replica must "
                                 "die for real to drill recovery")
            return self._run_inline(requests, fault)
        _require_chip_free_parent(self.cfg)
        return self._run_process(requests, fault)

    # ---- dynamic serving session: the autoscale actuation seams ----------
    # (docs/AUTOSCALE.md). `run()` above serves a FIXED batch over a
    # FIXED replica set; the session below keeps the driver live so a
    # controller can add/remove replicas while requests flow. Inline
    # replicas tick inside the driver's process; PROCESS replicas are
    # worker groups of ``cfg.tp`` ranks fed over the request channel
    # (serve/channel.py): submit/drain/stop commands flow IN over a
    # per-replica command log, results and acks batch back over the
    # side channel, and replica death replays the unfinished
    # assignment on a fresh channel epoch (docs/SERVING.md "the
    # request channel").

    def _require_session(self) -> None:
        if not self._session_active:
            raise RuntimeError(
                "no serving session — call ServeDriver.start() first "
                "(run() is the fixed-batch mode and has no scaling "
                "seams)")

    @property
    def live_ids(self) -> List[int]:
        return sorted(r.id for r in self.replicas.values()
                      if r.state == "live")

    @property
    def n_live(self) -> int:
        return len(self.live_ids)

    @property
    def n_draining(self) -> int:
        return sum(1 for r in self.replicas.values()
                   if r.state == "draining")

    def start(self, fault: Optional[dict] = None) -> "ServeDriver":
        """Open a dynamic serving session with ``cfg.n_replicas``
        replicas (each through `add_replica` — the scale-up path is the
        boot path). Requests then arrive via `submit()` and the caller
        drives `tick()`; `stop()` drains and writes serving.json.

        ``backend="process"``: each replica is a worker group fed over
        the request channel (serve/channel.py) — submit/drain/stop
        commands flow in over a per-replica command log, results and
        acks batch back over the side channel, and replica death
        replays the unfinished assignment on a fresh channel epoch.
        ``fault`` (process only): ``{"replica": r, "kill_after_tokens":
        n}`` SIGKILLs replica ``r``'s leader once, mid-stream — the
        session twin of `run()`'s recovery drill."""
        if self._session_active:
            raise RuntimeError("session already started")
        if fault and self.cfg.backend != "process":
            raise ValueError("fault injection needs backend='process' "
                             "— a replica must die for real to drill "
                             "recovery")
        enable_persistent_cache(self.cfg.compile_cache_dir)
        if self.cfg.backend == "inline":
            from ray_lightning_tpu.models.serving import serving_model

            self._model = serving_model(self.model_cfg)
            self._draft_model = (
                serving_model(self.cfg.draft_model_cfg)
                if self.cfg.draft_model_cfg is not None else None)
        else:
            _require_chip_free_parent(self.cfg)
            self._session_dir = self.cfg.run_dir or os.path.join(
                os.getcwd(), "rlt_logs", "serve")
            os.makedirs(self._session_dir, exist_ok=True)
            self._session_fault = fault
            self._proc_lock = san_lock("serve.driver.session")
        self._session_active = True
        self.replicas = {}
        self._next_replica = 0
        self._rr = 0
        self.pending = deque()
        self.outputs = {}
        self.meta = {}
        self.last_deferral = None
        self.last_spawn_s = None
        self._session_t0 = time.perf_counter()
        self._session_tokens = 0
        self._session_ticks = 0
        mc = self._metrics_cfg()
        if self.cfg.run_dir is not None and mc["enabled"]:
            from ray_lightning_tpu.telemetry.metrics import (
                FlightRecorder, MetricsRegistry,
            )

            tdir = os.path.join(self.cfg.run_dir, "telemetry")
            self.driver_metrics = MetricsRegistry(
                tdir, replica=0, prefix="driver",
                flush_every_n_ticks=mc["flush_every"])
            self.driver_flight = FlightRecorder(
                os.path.join(tdir, "driver.flight.json"), replica=-1,
                maxlen=mc["flight_ring"],
                persist_every=mc["flight_persist_every"])
        else:
            from ray_lightning_tpu.telemetry.metrics import (
                NULL_FLIGHT, NULL_METRICS,
            )

            self.driver_metrics = NULL_METRICS
            self.driver_flight = NULL_FLIGHT
        for _ in range(self.cfg.n_replicas):
            self.add_replica()
        return self

    def inject_spawn_faults(self, count: int = 1,
                            signal_name: str = "SIGKILL") -> None:
        """Test/drill seam: the next ``count`` `add_replica` calls die
        with a real `runtime.WorkerError` carrying ``signal_name``
        death metadata — byte-for-byte what a worker SIGKILLed during
        spawn/warmup surfaces, so the controller's
        classify-retry-within-budget path is exercised without needing
        a process backend (the autoscale --smoke drill)."""
        self._spawn_faults.extend(
            {"signal_name": signal_name} for _ in range(count))

    def add_replica(self) -> int:
        """Spawn one replica NOW — exactly the respawn path: params
        reload from the .npz (when serving from a file), the step
        compiled or DESERIALIZED through the persistent compile cache
        (`pipeline.compile_cache`, armed at `start()`), then the
        replica is live and routable. Returns the replica id. Raises
        whatever the spawn raised (a `WorkerError` for worker-shaped
        deaths) — the controller classifies it via `resilience.policy`
        and retries within its budget."""
        self._require_session()
        r = self._next_replica
        if self._spawn_faults:
            fault = self._spawn_faults.pop(0)
            from ray_lightning_tpu.runtime.group import WorkerError

            self.driver_flight.record("spawn_fault", replica=r,
                                      **fault)
            raise WorkerError(
                r, "injected spawn fault: replica worker killed "
                   "during warmup (autoscale drill)",
                signal_name=fault["signal_name"], cause="signal")
        if self.cfg.backend == "process":
            return self._add_replica_process(r)
        t0 = time.perf_counter()
        params = (load_params_npz(self.params_path)
                  if self.params_path is not None else self.params)
        mc = self._metrics_cfg()
        metrics = _make_metrics(self.cfg.run_dir, r,
                                enabled=mc["enabled"],
                                flush_every=mc["flush_every"])
        flight = _make_flight(self.cfg.run_dir, r,
                              enabled=mc["enabled"],
                              maxlen=mc["flight_ring"],
                              persist_every=mc["flight_persist_every"])
        engine = DecodeEngine(self._model, params, self.cfg.engine,
                              metrics=metrics,
                              draft_model=self._draft_model,
                              draft_params=self.draft_params,
                              device=_inline_device(r))
        engine.warmup()
        sched = Scheduler(engine, reserve=self.cfg.reserve,
                          metrics=metrics, flight=flight,
                          slo=self.cfg.slo)
        recorder = _make_recorder(self.cfg.run_dir, r)
        warm_s = time.perf_counter() - t0
        self._next_replica += 1
        self.replicas[r] = _Replica(r, engine, sched, recorder, warm_s)
        self.last_spawn_s = warm_s
        self.driver_metrics.count("replicas_spawned")
        self.driver_flight.record("spawn", replica=r,
                                  warm_s=round(warm_s, 4),
                                  live=self.n_live)
        # give already-queued backlog to the new replica: queued work
        # has no partial state, so redistribution is bitwise-neutral
        # (per-request seeds make every stream placement-independent)
        self._rebalance()
        return r

    def remove_replica(self, replica: Optional[int] = None,
                       graceful: bool = True) -> int:
        """Retire one replica. ``graceful`` (the default): stop
        admissions to the victim, requeue its still-queued/preempted
        work onto survivors (the bitwise replay seam — nothing partial
        exists for queued work), and let its decoding slots drain to
        retirement over subsequent `tick()`s before the worker stops.
        ``graceful=False``: additionally evict the slotted requests for
        replay elsewhere (partial streams dropped exactly like
        replica-death replay) and stop immediately. Returns the victim
        id (default: the newest live replica)."""
        self._require_session()
        if self.cfg.backend == "process":
            sends: list = []
            with self._proc_lock:
                victim = self._remove_replica_process(replica, graceful,
                                                      sends)
            self._flush_sends(sends)
            return victim
        if replica is None:
            live = self.live_ids
            if not live:
                raise RuntimeError("no live replica to remove")
            replica = live[-1]
        rep = self.replicas.get(replica)
        if rep is None or rep.state != "live":
            raise ValueError(
                f"replica {replica} is "
                f"{'unknown' if rep is None else rep.state} — only a "
                "live replica can be removed")
        rep.state = "draining"
        rep.sched.begin_drain()
        self.driver_metrics.count("replicas_drain_begun")
        self.driver_flight.record(
            "drain_begin", replica=replica, graceful=graceful,
            queued=len(rep.sched.queue), slotted=len(rep.sched.slots))
        self._requeue_from(rep)
        if not graceful:
            # account the partial wall first (inflight-tagged spans),
            # THEN evict: the replayed streams regenerate bitwise from
            # their seeds on whichever survivor admits them
            _record_drain(rep.recorder, rep.sched, replica)
            for req, preempts in rep.sched.evict_slotted():
                self.outputs[req.rid] = []
                self._route(req, preempts)
            self._stop_replica(rep)
        return replica

    def submit(self, req: Request) -> Optional[int]:
        """Route one request to a live replica (round-robin). When
        EVERY replica is draining or dead the request defers with a
        structured reason (`last_deferral`, the driver metrics
        ``submit_deferrals`` counter, a flight event) instead of
        round-robining onto a stopping replica — deferred requests
        re-route at the next `tick()` that finds a live replica.
        Returns the replica id, or None when deferred."""
        self._require_session()
        from ray_lightning_tpu.serve.scheduler import validate_request

        # validate BEFORE routing/deferring: the deferral path never
        # reaches Scheduler.submit, and an unsatisfiable span enqueued
        # raw would head-of-line-block its replica forever (it can
        # never admit) — refuse it here like the fixed-batch path does
        validate_request(self.cfg.engine, self.cfg.engine.pool_spec,
                         req)
        req = dataclasses.replace(req)
        if req.arrival == 0.0:
            req.arrival = time.perf_counter()
        if self.cfg.backend == "process":
            # the side-channel fan-in threads mutate outputs/assigned
            # under the same lock; the channel append itself happens
            # after the lock drops
            sends: list = []
            with self._proc_lock:
                self.outputs.setdefault(req.rid, [])
                target = self._route(req, 0, sends)
            self._flush_sends(sends)
            return target
        self.outputs.setdefault(req.rid, [])
        return self._route(req, 0)

    def tick(self) -> List[Completion]:
        """One serving tick across the replica set: flush deferred
        requests to any live replica, evict draining replicas' queues
        onto survivors, tick every non-stopped replica, retire drains
        that completed. Idle live replicas still tick (their gauges
        keep the load signal honest about spare capacity).

        Process backend: replicas tick themselves (the worker's own
        loop) — the driver's tick flushes deferred requests, surfaces
        any terminal replica error, and stamps the driver gauges;
        completions land in ``.meta``/``.outputs`` asynchronously and
        the return value is always empty."""
        self._require_session()
        if self.cfg.backend == "process":
            sends: list = []
            with self._proc_lock:
                for rep in self.replicas.values():
                    if rep.error is not None:
                        raise rep.error
                self._route_pending(sends)
                self._session_ticks += 1
                dm = self.driver_metrics
                if dm.enabled:
                    dm.gauge("replicas_live", self.n_live)
                    dm.gauge("replicas_draining", self.n_draining)
                    dm.gauge("pending_requests", len(self.pending))
                    dm.tick_end()
            self._flush_sends(sends)
            return []
        with annotate("serve.route"):
            self._route_pending()
        done: List[Completion] = []
        for r in sorted(self.replicas):
            rep = self.replicas[r]
            if rep.state == "stopped":
                continue
            if rep.state == "draining":
                # growth-stall preemptions land back in its queue;
                # admissions are closed there, so move them out
                self._requeue_from(rep)
                if not rep.sched.busy():
                    self._stop_replica(rep)
                    continue
            completions = rep.sched.tick()
            with annotate("serve.collect"):
                for detail in rep.sched.last_preemption_details:
                    self.outputs[detail["rid"]] = []
                    _record_preemption(rep.recorder, detail, r)
                if rep.state == "draining":
                    # a preemption during the drain tick: reroute now so
                    # the request is not parked behind closed admissions
                    self._requeue_from(rep)
                for rid, tok in rep.sched.last_emissions:
                    self.outputs[rid].append(tok)
                    self._session_tokens += 1
                for comp in completions:
                    _record_completion(rep.recorder, comp, r)
                    self.meta[comp.rid] = {
                        "replica": r,
                        "finish_reason": comp.finish_reason,
                        "queue_wait_s": comp.queue_wait_s,
                        "ttft_s": comp.ttft_s, "tpot_s": comp.tpot_s,
                        "preempted": comp.preempted,
                        "n_tokens": len(comp.tokens),
                        "priority": comp.priority,
                    }
                    if len(rep.sched.completions) % \
                            FLUSH_EVERY_N_COMPLETIONS == 0:
                        rep.recorder.flush()
                self._drain_sheds(r, rep.sched)
            done.extend(completions)
        self._session_ticks += 1
        dm = self.driver_metrics
        if dm.enabled:
            dm.gauge("replicas_live", self.n_live)
            dm.gauge("replicas_draining", self.n_draining)
            dm.gauge("pending_requests", len(self.pending))
            dm.tick_end()
        return done

    def busy(self) -> bool:
        self._require_session()
        if self.cfg.backend == "process":
            with self._proc_lock:
                return bool(self.pending) or any(
                    rep.assigned for rep in self.replicas.values()
                    if rep.state != "stopped")
        return bool(self.pending) or any(
            rep.sched.busy() for rep in self.replicas.values()
            if rep.state != "stopped")

    def force_flight_persist(self) -> int:
        """Incident-capture seam (telemetry/incidents.py,
        docs/OBSERVABILITY.md "incident capture"): persist every
        non-stopped replica's flight ring plus the driver ring NOW,
        instead of waiting out the persist cadence — a watch-rule
        breach self-documents with the breach window's final ticks on
        disk even if the process dies next. Host-side file writes
        only; returns how many rings landed. Safe outside a session
        (the fixed-batch ``run()`` owns its recorders internally):
        persists whatever the driver holds, possibly nothing."""
        persisted = 0
        for rep in self.replicas.values():
            if rep.state == "stopped":
                continue
            sched = getattr(rep, "sched", None)
            if sched is None:
                # process replicas persist worker-side on their own
                # cadence; the driver holds no ring for them
                continue
            fl = sched.flight
            if getattr(fl, "enabled", False):
                fl.persist()
                persisted += 1
        fl = self.driver_flight
        if fl is not None and getattr(fl, "enabled", False):
            fl.persist()
            persisted += 1
        return persisted

    def stop(self, drain: bool = True) -> ServeResult:
        """End the session. ``drain`` ticks until every stream
        completes first; ``drain=False`` accounts in-flight work as
        inflight-tagged spans and stops cold. Writes serving.json and
        returns the session's ServeResult."""
        self._require_session()
        if self.cfg.backend == "process":
            return self._stop_process(drain)
        if drain:
            while self.busy():
                # work can defer INTO pending mid-drain (a draining
                # replica's growth-stall preemption with no live
                # survivor): once pending is the ONLY work left and no
                # replica can ever take it, ticking forever would hang
                # here — refuse loudly instead (review finding,
                # test-pinned)
                others_busy = any(
                    rep.sched.busy() for rep in self.replicas.values()
                    if rep.state != "stopped")
                if self.pending and self.n_live == 0 and not others_busy:
                    raise RuntimeError(
                        f"{len(self.pending)} deferred request(s) with "
                        "no live replica — add_replica() before "
                        "stop(), or stop(drain=False) to abandon them")
                self.tick()
        final_replicas = self.n_live
        for rep in self.replicas.values():
            if rep.state == "stopped":
                continue
            # enqueue-time sheds on an otherwise-idle session never saw
            # a tick — surface them before the scheduler closes
            self._drain_sheds(rep.id, rep.sched)
            # stopping cold: a step still in flight is read and dropped
            rep.sched.drop_inflight()
            _record_drain(rep.recorder, rep.sched, rep.id)
            self._stop_replica(rep)
        wall = time.perf_counter() - self._session_t0
        occ = [rep.sched.slot_occupancy
               for rep in self.replicas.values()]
        stats = {
            "decode_tokens_per_s":
                self._session_tokens / max(wall, 1e-9),
            "slot_occupancy": float(np.mean(occ)) if occ else None,
            "n_requests": len(self.outputs),
            "n_tokens": self._session_tokens,
            "wall_s": wall,
            "ticks": self._session_ticks,
            "compile_count": max(
                (rep.engine.compile_count
                 for rep in self.replicas.values()), default=None),
            "replicas_spawned": self._next_replica,
            "final_replicas": final_replicas,
            "submit_deferrals":
                self.driver_metrics.counters().get(
                    "submit_deferrals", 0),
            "requests_shed":
                self.driver_metrics.counters().get(
                    "requests_shed", 0),
            "last_spawn_s": self.last_spawn_s,
        }
        result = ServeResult(
            outputs=self.outputs, meta=self.meta,
            restarts={r: 0 for r in self.replicas}, stats=stats)
        self.driver_metrics.close()
        self.driver_flight.close()
        self._write_summary(result)
        self._session_active = False
        return result

    # ---- session internals ----------------------------------------------

    def _drain_sheds(self, r: int, sched) -> None:
        """Turn a scheduler's typed shed records into terminal stream
        statuses (finish_reason="shed" + retry-after hint) — the
        graceful-overload contract: shed work is answered, never
        silently dropped (RLT505)."""
        for rec in sched.take_sheds():
            rid = rec["rid"]
            self.meta[rid] = {
                "replica": r, "finish_reason": "shed",
                **{k: v for k, v in rec.items() if k != "rid"}}
            self.outputs[rid] = []
            self.driver_metrics.count("requests_shed")

    def _pick_replica(self) -> Optional[int]:
        live = self.live_ids
        if not live:
            return None
        target = live[self._rr % len(live)]
        self._rr += 1
        return target

    def _route(self, req: Request, preempts: int,
               sends: Optional[list] = None) -> Optional[int]:
        target = self._pick_replica()
        if target is None:
            self.pending.append((req, preempts))
            self.last_deferral = {
                "rid": req.rid,
                "reason": "no live replica: all replicas draining "
                          "or dead",
                "draining": self.n_draining,
                "pending": len(self.pending),
                "at": time.perf_counter(),
            }
            self.driver_metrics.count("submit_deferrals")
            self.driver_flight.record("submit_deferral", rid=req.rid,
                                      draining=self.n_draining,
                                      pending=len(self.pending))
            return None
        rep = self.replicas[target]
        if isinstance(rep, _ProcessReplica):
            from ray_lightning_tpu.serve.channel import request_to_wire

            # the command log IS the enqueue; the driver's assignment
            # ledger is what the respawn replay is computed from. The
            # send itself is DEFERRED to after the session lock drops
            # (_flush_sends) — every process-path caller passes `sends`
            rep.assigned.append(req)
            sends.append((rep.writer, rep.writer.epoch, "submit",
                          {"req": request_to_wire(req),
                           "preempts": preempts}))
        else:
            rep.sched.enqueue(req, preempts)
        return target

    def _route_pending(self, sends: Optional[list] = None) -> None:
        while self.pending and self.live_ids:
            req, preempts = self.pending.popleft()
            self._route(req, preempts, sends)

    @staticmethod
    def _flush_sends(sends: list) -> None:
        """Perform channel sends decided under the session lock, OUTSIDE
        it — the command log's per-append fsync must not serialize the
        whole driver (threadcheck RLT705). Each send is epoch-guarded:
        if its replica respawned between the locked decision and this
        append, the fresh epoch's replay already carries the command
        (computed from the same locked state), so `send_at` drops it
        instead of duplicating the stream."""
        for writer, epoch, op, payload in sends:
            writer.send_at(epoch, op, **payload)

    def _requeue_from(self, rep: "_Replica") -> None:
        for req, preempts in rep.sched.evict_queued():
            self._route(req, preempts)

    def _rebalance(self) -> None:
        """Even out queued (never-admitted) backlog across live
        replicas after a scale-up: without this, work enqueued before
        the spawn would keep draining through the old replica alone.
        Deterministic (FIFO by arrival) and bitwise-neutral (queued
        work has no partial state; streams are seed-pure)."""
        live = [self.replicas[r] for r in self.live_ids]
        if len(live) < 2:
            return
        backlog: List = []
        for rep in live:
            backlog.extend(rep.sched.evict_queued())
        if not backlog:
            return
        backlog.sort(key=lambda item: item[0].arrival)
        for i, (req, preempts) in enumerate(backlog):
            live[i % len(live)].sched.enqueue(req, preempts)

    def _stop_replica(self, rep: "_Replica") -> None:
        rep.state = "stopped"
        rep.recorder.flush()
        rep.recorder.close()
        m = rep.sched.metrics
        if m.enabled:
            # stamp the stream retired so the load signal stops
            # pooling this replica's stale window into LIVE pressure
            # (telemetry/metrics.py load_signal_from_parsed)
            m.gauge("retired", 1)
            m.tick_end()
        m.close()
        rep.sched.flight.record("drain_end", replica=rep.id)
        rep.sched.flight.close()
        self.driver_metrics.count("replicas_stopped")
        self.driver_flight.record("drain_end", replica=rep.id,
                                  live=self.n_live)

    # ---- process-session internals (the request channel) ------------------

    def _add_replica_process(self, r: int) -> int:
        """Spawn one PROCESS replica: open its command log, start its
        spawn/respawn thread, and block until the worker group reports
        live (or the spawn classifies terminal)."""
        import threading

        from ray_lightning_tpu.serve.channel import ChannelWriter

        with self._proc_lock:
            writer = ChannelWriter(self._session_dir, r)
            rep = _ProcessReplica(r, writer)
            self._next_replica += 1
            self.replicas[r] = rep
            rep.thread = threading.Thread(
                target=self._run_session_replica, args=(rep,),
                daemon=True, name=f"serve-replica-{r}")
            rep.thread.start()
        if not rep.live_evt.wait(self.cfg.start_timeout):
            with self._proc_lock:
                rep.state = "stopped"
            raise RuntimeError(
                f"replica {r} did not report live within "
                f"{self.cfg.start_timeout:.0f}s (spawn/warmup hang) — "
                f"worker logs under {self._session_dir}/replica{r}")
        with self._proc_lock:
            if rep.error is not None:
                raise rep.error
            self.driver_metrics.count("replicas_spawned")
            self.driver_flight.record(
                "spawn", replica=r,
                warm_s=round(rep.warm_s or 0.0, 4), live=self.n_live)
        # no _rebalance across process replicas: queued work already
        # shipped over a channel cannot be pulled back without an
        # evict-back command (docs/SERVING.md "sharded replicas") —
        # NEW submissions round-robin onto the grown set immediately
        return r

    def _remove_replica_process(self, replica: Optional[int],
                                graceful: bool, sends: list) -> int:
        """Caller holds ``_proc_lock``. The drain/stop command does the
        rest: the worker evicts what the survivors should replay (its
        queue; plus its slots when not graceful), wires the evictions
        back in its final batch items, and exits; the spawn thread then
        flips the replica to stopped."""
        if replica is None:
            live = self.live_ids
            if not live:
                raise RuntimeError("no live replica to remove")
            replica = live[-1]
        rep = self.replicas.get(replica)
        if rep is None or rep.state != "live":
            raise ValueError(
                f"replica {replica} is "
                f"{'unknown' if rep is None else rep.state} — only a "
                "live replica can be removed")
        rep.state = "draining"
        self.driver_metrics.count("replicas_drain_begun")
        self.driver_flight.record(
            "drain_begin", replica=replica, graceful=graceful,
            outstanding=len(rep.assigned))
        if graceful:
            sends.append((rep.writer, rep.writer.epoch, "drain", {}))
        else:
            sends.append((rep.writer, rep.writer.epoch, "stop",
                          {"mode": "hard"}))
        return replica

    def _run_session_replica(self, rep: "_ProcessReplica") -> None:
        """One replica's spawn/respawn loop (its own thread, mirroring
        `_run_process.run_replica`): compute the channel-epoch replay,
        run the WorkerGroup of ``cfg.tp`` ranks as an SPMD program,
        classify deaths via `resilience.policy`, respawn the WHOLE
        group within the restart budget."""
        from ray_lightning_tpu.resilience.policy import classify_failure
        from ray_lightning_tpu.runtime.group import (
            WorkerGroup, find_free_port,
        )
        from ray_lightning_tpu.runtime.launch import _spmd_main
        from ray_lightning_tpu.serve.channel import request_to_wire

        from ray_lightning_tpu.models.serving import config_to_wire

        cfgkw = config_to_wire(self.model_cfg)
        enginekw = dataclasses.asdict(self.cfg.engine)
        tp = self.cfg.tp
        fault = getattr(self, "_session_fault", None)
        rep_fault = (fault if fault and
                     fault.get("replica", 0) == rep.id else None)
        while True:
            with self._proc_lock:
                if rep.attempts > 0:
                    # respawn: a FRESH epoch replaying the unfinished
                    # assignment + control state. Partial streams drop
                    # here — the replay regenerates them bitwise from
                    # the per-request seeds (scheduler purity)
                    rep.assigned = [q for q in rep.assigned
                                    if q.rid not in self.meta]
                    # partial prefixes are NOT cleared here: the
                    # respawned worker announces every replayed submit
                    # it admits ("starts" in its first batch) and the
                    # fan-in resets the stream there — keeps this
                    # thread's hands off the driver's result dicts
                    replay = [{"op": "submit", "req": request_to_wire(q)}
                              for q in rep.assigned]
                    if rep.state == "draining":
                        replay.append({"op": "drain"})
                    rep.writer.begin_epoch(replay)
                epoch = rep.writer.epoch
            group = WorkerGroup(
                num_workers=tp, env=dict(self.cfg.env or {}),
                log_dir=os.path.join(self._session_dir,
                                     f"replica{rep.id}"),
                start_timeout=self.cfg.start_timeout)
            try:
                group.start()
                coordinator = f"127.0.0.1:{find_free_port()}"
                res = group.run(
                    _spmd_main,
                    shared_args=(
                        _replica_session_main,
                        (dict(cfgkw), self.params_path, dict(enginekw),
                         self.cfg.reserve, rep.id, self.cfg.run_dir,
                         self._session_dir, self.cfg.compile_cache_dir,
                         rep_fault, self._session_dir,
                         self._metrics_cfg(), epoch, tp,
                         self._slo_kw()),
                        {}, tp, coordinator, self.cfg.platform,
                        self.cfg.cpu_devices_per_rank),
                    per_rank_args=[(k, (k,)) for k in range(tp)],
                    on_queue_item=self._on_session_item)
                with self._proc_lock:
                    rep.result = res[0]
                    if rep.state != "stopped":
                        self._finalize_process_replica(rep)
                return
            except Exception as exc:  # noqa: BLE001 — classified below
                fc = classify_failure(exc)
                log.warning(
                    "session replica %d died (%s/%s): %s", rep.id,
                    fc.kind, fc.cause, fc.detail)
                with self._proc_lock:
                    respawning = (fc.restartable
                                  and rep.restarts < self.cfg.max_restarts
                                  and rep.state != "stopped")
                    if self.cfg.run_dir and self.cfg.metrics:
                        from ray_lightning_tpu.telemetry.metrics import (
                            finalize_flight,
                        )

                        finalize_flight(
                            os.path.join(self.cfg.run_dir, "telemetry"),
                            rep.id,
                            {"kind": fc.kind, "cause": fc.cause,
                             "detail": fc.detail,
                             "restartable": fc.restartable,
                             "restarts_so_far": rep.restarts,
                             "respawning": respawning},
                            os.path.join(self.cfg.run_dir,
                                         "flight.json"))
                    rep.attempts += 1
                    if not respawning:
                        rep.error = exc
                        rep.state = "stopped"
                        rep.live_evt.set()
                        return
                    rep.restarts += 1
                    rep.live_evt.clear()
            finally:
                group.shutdown()

    def _on_session_item(self, _rank, item) -> None:
        """Side-channel fan-in for every session replica (called from
        their spawn threads): one BATCHED item per worker tick —
        tokens, acks, completions, evictions together (the channel's
        RLT504 discipline)."""
        from ray_lightning_tpu.serve.channel import request_from_wire

        kind = item[0]
        sends: list = []
        with self._proc_lock:
            rep = self.replicas.get(item[1])
            if rep is None:
                return
            if kind == "live":
                w = item[2]["warmup_s"]
                rep.warm_s = w
                rep.warmups.append(w)
                rep.spawned_at = time.perf_counter()
                self.last_spawn_s = w
                rep.live_evt.set()
                return
            if kind != "batch":
                return
            payload = item[2]
            if "ack" in payload:
                rep.acked = max(rep.acked, int(payload["ack"]))
            for rid in payload.get("starts", ()):
                # the worker admitted this submit afresh — on a normal
                # submit a no-op reset, on an epoch replay after respawn
                # THE reset that drops the dead epoch's partial prefix
                # (the stream regenerates bitwise from its seed).
                # Ordered before toks: a replayed stream's first tokens
                # can share this batch
                self.outputs[rid] = []
            for rid in payload.get("preempts", ()):
                # scheduler-level preemption: the replay resends the
                # stream from scratch — drop the prefix
                self.outputs[rid] = []
            for rid, tok in payload.get("toks", ()):
                self.outputs[rid].append(int(tok))
                self._session_tokens += 1
            for rid, m in payload.get("dones", ()):
                self.meta[rid] = {"replica": rep.id, **m}
                rep.assigned = [q for q in rep.assigned
                                if q.rid != rid]
            for rec in payload.get("sheds", ()):
                # typed terminal status for a shed stream (RLT505) —
                # idempotent across epoch rolls: a rid already terminal
                # in meta is not re-counted, and dropping it from the
                # assignment ledger keeps the respawn replay from
                # resubmitting (and re-shedding) the dead epoch's sheds
                rid = rec["rid"]
                if (self.meta.get(rid, {}).get("finish_reason")
                        != "shed"):
                    self.driver_metrics.count("requests_shed")
                self.meta[rid] = {
                    "replica": rep.id, "finish_reason": "shed",
                    **{k: v for k, v in rec.items() if k != "rid"}}
                self.outputs[rid] = []
                rep.assigned = [q for q in rep.assigned
                                if q.rid != rid]
            for wire, preempts in payload.get("evicted", ()):
                # a draining/stopping replica handing work back for
                # the survivors (bitwise replay seam)
                req = request_from_wire(wire)
                rep.assigned = [q for q in rep.assigned
                                if q.rid != req.rid]
                self.outputs[req.rid] = []
                self._route(req, int(preempts), sends)
        self._flush_sends(sends)

    def _finalize_process_replica(self, rep: "_ProcessReplica") -> None:
        """Worker group exited cleanly (caller holds ``_proc_lock``).
        The worker owned and closed the replica's telemetry streams —
        the driver only flips state and stamps its own records."""
        rep.state = "stopped"
        self.driver_metrics.count("replicas_stopped")
        self.driver_flight.record("drain_end", replica=rep.id,
                                  live=self.n_live)

    def _stop_process(self, drain: bool) -> ServeResult:
        if drain:
            while self.busy():
                with self._proc_lock:
                    others_busy = any(
                        rep.assigned for rep in self.replicas.values()
                        if rep.state != "stopped")
                    if (self.pending and self.n_live == 0
                            and not others_busy):
                        raise RuntimeError(
                            f"{len(self.pending)} deferred request(s) "
                            "with no live replica — add_replica() "
                            "before stop(), or stop(drain=False) to "
                            "abandon them")
                self.tick()
                time.sleep(0.01)
        sends: list = []
        with self._proc_lock:
            final_replicas = self.n_live
            for rep in self.replicas.values():
                if rep.state != "stopped":
                    # "finish": serve out everything assigned, then
                    # exit; "abort": account in-flight work as
                    # inflight-tagged spans and exit now
                    sends.append(
                        (rep.writer, rep.writer.epoch, "stop",
                         {"mode": "finish" if drain else "abort"}))
        self._flush_sends(sends)
        for rep in self.replicas.values():
            if rep.thread is not None:
                rep.thread.join(self.cfg.start_timeout)
        for rep in self.replicas.values():
            if rep.error is not None:
                raise rep.error
        wall = time.perf_counter() - self._session_t0
        results = [rep.result for rep in self.replicas.values()
                   if rep.result]
        occ = [res["occupancy"] for res in results]
        warm_all = [w for rep in self.replicas.values()
                    for w in rep.warmups]
        stats = {
            "decode_tokens_per_s":
                self._session_tokens / max(wall, 1e-9),
            "slot_occupancy": (float(np.mean(occ)) if occ else None),
            "n_requests": len(self.outputs),
            "n_tokens": self._session_tokens,
            "wall_s": wall,
            "ticks": self._session_ticks,
            "compile_count": max(
                (res["compile_count"] for res in results),
                default=None),
            "replicas_spawned": self._next_replica,
            "final_replicas": final_replicas,
            "warmup_cold_s": warm_all[0] if warm_all else None,
            "warmup_respawn_s": (max(warm_all[1:])
                                 if len(warm_all) > 1 else None),
            "restarts_total": sum(rep.restarts
                                  for rep in self.replicas.values()),
            "submit_deferrals":
                self.driver_metrics.counters().get(
                    "submit_deferrals", 0),
            "requests_shed":
                self.driver_metrics.counters().get(
                    "requests_shed", 0),
            "last_spawn_s": self.last_spawn_s,
        }
        result = ServeResult(
            outputs=self.outputs, meta=self.meta,
            restarts={rep.id: rep.restarts
                      for rep in self.replicas.values()}, stats=stats)
        for rep in self.replicas.values():
            rep.writer.close()
        self.driver_metrics.close()
        self.driver_flight.close()
        self._write_summary(result)
        self._session_active = False
        return result

    def _write_summary(self, result: ServeResult) -> None:
        if self.cfg.run_dir is None:
            return
        os.makedirs(self.cfg.run_dir, exist_ok=True)
        from ray_lightning_tpu.telemetry.metrics import (
            aggregate_from_parsed, load_signal_from_parsed,
            newest_from_parsed, read_all_metrics,
        )

        doc = {"stats": result.stats, "meta": result.meta,
               "restarts": result.restarts}
        tdir = _serve_metrics_dir(self.cfg.run_dir)
        parsed = read_all_metrics(tdir)  # one pass feeds both rollups
        agg = aggregate_from_parsed(parsed)
        if agg is not None:
            # run-level rollup of the per-replica metric streams:
            # latency quantiles FROM MERGED BUCKETS (exact across
            # replicas/attempts), counters summed, and the rolling
            # load summary the autoscale oracle reads
            doc["metrics"] = agg
            doc["load"] = load_signal_from_parsed(
                newest_from_parsed(parsed), where=tdir)
        path = os.path.join(self.cfg.run_dir, "serving.json")
        with open(path, "w") as f:
            json.dump(doc, f, indent=2)


# ---- run-level metric aggregation + the autoscale load signal -------------


def _serve_metrics_dir(run_dir: str) -> str:
    tdir = os.path.join(run_dir, "telemetry")
    return tdir if os.path.isdir(tdir) else run_dir


def aggregate_serve_metrics(run_dir: str) -> Optional[dict]:
    """Merge every per-replica metrics JSONL under
    ``<run_dir>/telemetry`` into one run-level view: summed counters,
    exactly-merged latency histograms (quantiles from buckets),
    per-replica tick/attempt counts, and queue-depth/occupancy series
    stats. None when the run recorded no metrics (metrics off, or
    nothing served)."""
    from ray_lightning_tpu.telemetry.metrics import aggregate_metrics_dir

    return aggregate_metrics_dir(_serve_metrics_dir(run_dir))


def load_signal(run_dir: str, window: Optional[int] = None) -> dict:
    """The queue-depth/occupancy oracle input for replica autoscale
    (ROADMAP item 1c) and the elastic capacity oracle
    (docs/OBSERVABILITY.md "load signal").

    Reads the NEWEST metrics file per replica under
    ``<run_dir>/telemetry`` and summarizes the last ``window`` tick
    samples each flushed:

      available            False when no metrics exist yet (a caller
                           must treat that as "no signal", never zero
                           load)
      queue_depth_now      summed latest queue depth across replicas
      queue_depth_p50/max  over the recent window, all replicas pooled
      occupancy            mean decoding-slot fraction over the window
      blocks_free_fraction pool headroom (min across replicas)
      pressure             queue_depth_p50 / total_slots — > 0 means
                           demand is queuing behind capacity; the
                           dimensionless number an autoscaler compares
                           against its scale-up threshold
      replicas             per-replica {queue_depth, occupancy, ticks}

    The signal is computed from FLUSHED samples, so it lags live state
    by at most one flush cadence — the honest price of RLT501's
    no-per-tick-I/O discipline."""
    from ray_lightning_tpu.telemetry.metrics import (
        LOAD_SIGNAL_WINDOW, load_signal_from_dir,
    )

    return load_signal_from_dir(
        _serve_metrics_dir(run_dir),
        window=window if window is not None else LOAD_SIGNAL_WINDOW)


def _req_dict(req: Request) -> dict:
    return {"rid": req.rid, "prompt": np.asarray(req.prompt).tolist(),
            "max_new_tokens": req.max_new_tokens,
            "temperature": req.temperature, "top_k": req.top_k,
            "seed": req.seed, "eos_id": req.eos_id,
            "priority": req.priority}

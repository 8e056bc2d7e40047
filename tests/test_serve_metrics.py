"""Live serving metrics (telemetry/metrics.py + the serve/ wiring,
docs/OBSERVABILITY.md "serving metrics"): registry units (exact
histogram merge, ring bounds, flush cadence), scheduler/engine
instrumentation counts vs ground truth, the zero-overhead pin (metrics
off => byte-identical engine program, no jax values ever recorded),
flight-recorder persistence + driver finalization, preempted/in-flight
span accounting, the load-signal oracle, monitor/report CLI smoke, and
the bench schema + gate legs."""
from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.models.llama import Llama, LlamaConfig
from ray_lightning_tpu.serve.engine import DecodeEngine, EngineConfig
from ray_lightning_tpu.serve.scheduler import Request, Scheduler
from ray_lightning_tpu.telemetry.metrics import (
    HIST_BUCKETS,
    HIST_GROWTH,
    HIST_LO,
    FlightRecorder,
    Histogram,
    MetricsRegistry,
    NullMetrics,
    finalize_flight,
    flight_path,
    merge_histograms,
    metrics_paths,
    read_flight,
    read_metrics,
)


@pytest.fixture(scope="module")
def tiny():
    cfg = LlamaConfig.tiny(use_flash=False, dtype=jnp.float32)
    model = Llama(cfg)
    prompts = [
        np.array(jax.random.randint(
            jax.random.key(40 + i), (1, 3 + (i % 5)), 0,
            cfg.vocab_size), dtype=np.int32)
        for i in range(8)
    ]
    params = jax.jit(model.init)(jax.random.key(3), prompts[0])["params"]
    return cfg, model, params, prompts


# ---------------------------------------------------------- histogram units


def test_histogram_records_and_quantiles():
    h = Histogram()
    for v in (0.001, 0.002, 0.004, 0.008, 0.016, 0.5, 2.0):
        h.observe(v)
    assert h.n == 7
    assert sum(h.counts.values()) == 7
    assert h.min == 0.001 and h.max == 2.0
    p50, p95, p99 = h.quantile(0.5), h.quantile(0.95), h.quantile(0.99)
    assert p50 <= p95 <= p99
    # bucket-upper quantiles are conservative but clamped to the true
    # max (which merges exactly), so a p99 never exceeds any sample
    assert p99 <= 2.0
    assert h.quantile(1.0) == 2.0
    # the sketch is the auditable tail: counts sum to n, ascending
    sketch = h.sketch()
    assert sum(c for _, c in sketch) == 7
    assert [le for le, _ in sketch] == sorted(le for le, _ in sketch)


def test_histogram_edge_buckets():
    h = Histogram()
    h.observe(0.0)            # underflow
    h.observe(HIST_LO / 2)    # underflow
    h.observe(1e12)           # overflow
    assert h.counts[0] == 2
    assert h.counts[h.n_buckets + 1] == 1
    assert h.quantile(0.5) == HIST_LO
    # overflow quantile reads the exact (merge-safe) max
    assert h.quantile(1.0) == 1e12


def test_histogram_merge_is_exact_and_order_independent():
    rng = np.random.default_rng(0)
    values = rng.lognormal(mean=-4, sigma=2, size=300)
    whole = Histogram()
    parts = [Histogram() for _ in range(3)]
    for i, v in enumerate(values):
        whole.observe(v)
        parts[i % 3].observe(v)
    fwd = merge_histograms(parts)
    rev = merge_histograms(list(reversed(parts)))
    # EXACT: merged counts equal the single-stream histogram's, bucket
    # for bucket — not approximately, integer-identical
    assert fwd.counts == whole.counts
    assert rev.counts == whole.counts
    assert fwd.n == whole.n == 300
    assert fwd.min == whole.min and fwd.max == whole.max
    for q in (0.5, 0.9, 0.95, 0.99):
        assert fwd.quantile(q) == rev.quantile(q) == whole.quantile(q)


def test_histogram_merge_rejects_layout_mismatch():
    a = Histogram()
    b = Histogram(lo=1e-3)
    with pytest.raises(ValueError, match="layout mismatch"):
        a.merge(b)


def test_histogram_dict_roundtrip():
    h = Histogram()
    for v in (0.01, 0.02, 3.0):
        h.observe(v)
    back = Histogram.from_dict(json.loads(json.dumps(h.to_dict())))
    assert back.counts == h.counts
    assert back.n == h.n and back.max == h.max
    assert back.quantile(0.99) == h.quantile(0.99)


# ----------------------------------------------------------- registry units


def test_registry_ring_bounds_and_drop_accounting():
    reg = MetricsRegistry(ring_size=4)
    for i in range(10):
        reg.gauge("queue_depth", i)
        reg.tick_end()
    assert reg.ticks == 10
    ring = reg.ring()
    assert len(ring) == 4                  # bounded
    assert ring[-1]["g"]["queue_depth"] == 9.0
    assert reg.dropped == 6                # overwrites counted, not lost


def test_registry_flush_cadence_and_read(tmp_path):
    reg = MetricsRegistry(str(tmp_path), replica=3,
                          flush_every_n_ticks=4)
    path = reg._path
    # before the cadence fires, only the header line exists
    for i in range(3):
        reg.count("admissions")
        reg.observe("ttft_s", 0.01 * (i + 1))
        reg.tick_end()
    assert sum(1 for _ in open(path)) == 1
    reg.tick_end()  # 4th tick: the cadence flush
    parsed = read_metrics(path)
    assert len(parsed["ticks"]) == 4
    assert parsed["header"]["replica"] == 3
    assert parsed["header"]["hist"] == {
        "lo": HIST_LO, "growth": HIST_GROWTH, "n_buckets": HIST_BUCKETS}
    assert parsed["counters"]["admissions"] == 3
    assert parsed["hists"]["ttft_s"].n == 3
    # a second flush appends a NEWER cumulative snapshot; last wins
    reg.observe("ttft_s", 0.5)
    reg.close()
    parsed = read_metrics(path)
    assert parsed["hists"]["ttft_s"].n == 4
    assert metrics_paths(str(tmp_path)) == [path]


def test_read_metrics_survives_garbage_lines(tmp_path):
    reg = MetricsRegistry(str(tmp_path), replica=0,
                          flush_every_n_ticks=1)
    reg.gauge("queue_depth", 1)
    reg.tick_end()
    with open(reg._path, "a") as f:
        f.write("{torn line\n")
    parsed = read_metrics(reg._path)
    assert parsed["unparseable_lines"] == 1
    assert len(parsed["ticks"]) == 1


def test_null_metrics_is_inert():
    null = NullMetrics()
    null.count("x")
    null.gauge("y", 1.0)
    null.observe("z", 2.0)
    null.tick_end()
    assert null.counters() == {} and null.gauges() == {}
    assert null.histogram("z") is None and null.ring() == []
    assert null.flush() == 0 and not null.enabled


# ------------------------------------------- scheduler/engine ground truth


class _Recording(MetricsRegistry):
    """A registry that additionally asserts every recorded value is a
    plain host scalar — a jax.Array arriving here would mean the
    instrumentation touched device memory (a potential sync)."""

    def __init__(self):
        super().__init__()
        self.jax_values = []

    def _check(self, value):
        if isinstance(value, jax.Array):
            self.jax_values.append(value)

    def count(self, name, n=1):
        self._check(n)
        super().count(name, n)

    def gauge(self, name, value):
        self._check(value)
        super().gauge(name, value)

    def observe(self, name, value):
        self._check(value)
        super().observe(name, value)


def test_scheduler_engine_counts_vs_ground_truth(tiny):
    cfg, model, params, prompts = tiny
    reg = _Recording()
    eng = DecodeEngine(model, params, EngineConfig(
        capacity=2, block_size=4, blocks_per_slot=8, prefill_chunk=4),
        metrics=reg)
    eng.warmup()
    sched = Scheduler(eng, metrics=reg)
    reqs = [Request(rid=f"g{i}", prompt=prompts[i][0],
                    max_new_tokens=5, seed=i) for i in range(4)]
    for r in reqs:
        sched.submit(r)
    ticks = 0
    done = {}
    while sched.busy():
        for c in sched.tick():
            done[c.rid] = c
        ticks += 1
    c = reg.counters()
    assert c["admissions"] == 4
    assert c["completions"] == 4
    # ground truth: every emitted token was counted exactly once
    assert c["decode_tokens"] == sum(len(d.tokens) for d in done.values())
    # every prefill tick advanced one chunk of width 4 (single-slot lane)
    assert c["prefill_tokens"] % 4 == 0 and c["prefill_tokens"] > 0
    # one ring sample per scheduler tick (warmup ticks the ENGINE, not
    # the scheduler, so it contributes no sample)
    assert reg.ticks == ticks
    assert reg.gauges()["compile_count"] == 1
    assert reg.gauges()["queue_depth"] == 0  # drained
    for name in ("queue_wait_s", "ttft_s", "tpot_s", "decode_s"):
        assert reg.histogram(name).n == 4, name
    # the no-new-host-syncs pin: nothing recorded was a jax array
    assert reg.jax_values == []


def test_scheduler_counts_preemptions_and_growth_stalls(tiny):
    cfg, model, params, prompts = tiny
    reg = MetricsRegistry()
    eng = DecodeEngine(model, params, EngineConfig(
        capacity=2, block_size=4, blocks_per_slot=8, n_blocks=9,
        prefill_chunk=4))
    eng.warmup()
    sched = Scheduler(eng, reserve="on_demand", metrics=reg)
    reqs = [Request(rid=f"p{i}", prompt=prompts[4][0],
                    max_new_tokens=20, seed=50 + i) for i in range(2)]
    for r in reqs:
        sched.submit(r)
    done = {}
    details = []
    while sched.busy():
        for c in sched.tick():
            done[c.rid] = c
        details.extend(sched.last_preemption_details)
    preempts = sum(c.preempted for c in done.values())
    assert preempts >= 1
    c = reg.counters()
    assert c["preemptions"] == preempts
    assert c["growth_stalls"] >= c["preemptions"]
    # the preemption details the driver turns into replayed-tagged
    # spans: one per preemption event, with partial timings
    assert len(details) == preempts
    for d in details:
        assert d["rid"] in done
        assert d["prefill_s"] >= 0 and d["decode_s"] >= 0


def test_inflight_snapshot_mid_run(tiny):
    cfg, model, params, prompts = tiny
    eng = DecodeEngine(model, params, EngineConfig(
        capacity=2, block_size=4, blocks_per_slot=8, prefill_chunk=4))
    eng.warmup()
    sched = Scheduler(eng)
    for i in range(4):
        sched.submit(Request(rid=f"f{i}", prompt=prompts[i][0],
                             max_new_tokens=8, seed=i))
    for _ in range(3):
        sched.tick()
    snap = {s["rid"]: s for s in sched.inflight_snapshot()}
    assert len(snap) == 4  # 2 slotted + 2 queued, nothing lost
    states = {s["state"] for s in snap.values()}
    assert "queued" in states
    assert states & {"prefilling", "decoding"}
    queued = [s for s in snap.values() if s["state"] == "queued"]
    assert all(s["queue_wait_s"] > 0 for s in queued)


# ------------------------------------------------------- zero-overhead pin


def test_metrics_off_is_byte_identical_program(tiny):
    """The compile-count + program pin: metrics on vs off lowers a
    byte-identical step program (instrumentation lives entirely on the
    host side of the tick), and churn with metrics armed still
    compiles exactly once."""
    cfg, model, params, prompts = tiny
    ecfg = EngineConfig(capacity=2, block_size=4, blocks_per_slot=8,
                        prefill_chunk=4)

    def lowered_text(engine):
        return engine.lower_idle().as_text()

    eng_off = DecodeEngine(model, params, ecfg)
    eng_on = DecodeEngine(model, params, ecfg,
                          metrics=MetricsRegistry())
    assert lowered_text(eng_off) == lowered_text(eng_on)
    # churn through the instrumented engine: compile count stays 1
    sched = Scheduler(eng_on, metrics=eng_on.metrics)
    for i in range(4):
        sched.submit(Request(rid=f"z{i}", prompt=prompts[i][0],
                             max_new_tokens=4, seed=i))
    while sched.busy():
        sched.tick()
    assert eng_on.compile_count in (1, -1)


def test_metrics_off_streams_identical(tiny):
    cfg, model, params, prompts = tiny
    ecfg = EngineConfig(capacity=2, block_size=4, blocks_per_slot=8,
                        prefill_chunk=4)

    def run(metrics):
        eng = DecodeEngine(model, params, ecfg, metrics=metrics)
        eng.warmup()
        sched = Scheduler(eng, metrics=metrics or None)
        for i in range(4):
            sched.submit(Request(rid=f"s{i}", prompt=prompts[i][0],
                                 max_new_tokens=6,
                                 temperature=0.7 if i % 2 else 0.0,
                                 top_k=3 if i % 2 else None,
                                 seed=20 + i))
        out = {}
        while sched.busy():
            for c in sched.tick():
                out[c.rid] = c.tokens
        return out

    assert run(None) == run(MetricsRegistry())


# ----------------------------------------------------------- flight recorder


def test_flight_recorder_persists_bounded_ring(tmp_path):
    path = str(tmp_path / "replica0.flight.json")
    fr = FlightRecorder(path, replica=0, maxlen=8, persist_every=4)
    for i in range(20):
        fr.record("tick", tick=i, queue_depth=i % 3)
    doc = read_flight(path)
    assert doc is not None
    assert len(doc["events"]) <= 8            # bounded ring
    fr.close()
    doc = read_flight(path)
    assert doc["events"][-1]["tick"] == 19    # close() persists the tail
    assert doc["replica"] == 0


def test_finalize_flight_stamps_death_and_appends(tmp_path):
    tdir = str(tmp_path)
    fr = FlightRecorder(flight_path(tdir, 1), replica=1,
                        persist_every=1)
    fr.record("tick", tick=1)
    fr.record("preempt", rid="r0")
    out = str(tmp_path / "flight.json")
    death = {"kind": "retryable", "cause": "worker-signal:SIGKILL",
             "detail": "rc=-9", "restartable": True}
    dump = finalize_flight(tdir, 1, death, out)
    assert dump["death"]["kind"] == "retryable"
    assert [e["kind"] for e in dump["events"]] == ["tick", "preempt"]
    # a second death APPENDS — postmortems never truncate each other
    finalize_flight(tdir, 1, dict(death, kind="fatal"), out)
    with open(out) as f:
        doc = json.load(f)
    assert len(doc["dumps"]) == 2
    assert doc["dumps"][1]["death"]["kind"] == "fatal"
    # a replica that never persisted still gets a named gap, not a crash
    dump = finalize_flight(tdir, 7, death, out)
    assert dump["events"] == [] and "note" in dump


# ------------------------------------------- driver wiring + load signal


@pytest.fixture(scope="module")
def inline_run(tiny, tmp_path_factory):
    """One instrumented 2-replica inline serve, shared by the driver /
    report / monitor / load-signal tests."""
    from ray_lightning_tpu.serve.driver import (
        ReplicaGroupConfig, ServeDriver,
    )

    cfg, model, params, prompts = tiny
    run_dir = str(tmp_path_factory.mktemp("serve_metrics_run"))
    drv = ServeDriver(cfg, params, ReplicaGroupConfig(
        n_replicas=2, backend="inline", reserve="on_demand",
        engine=EngineConfig(capacity=2, block_size=4, blocks_per_slot=8,
                            prefill_chunk=4),
        run_dir=run_dir, metrics_flush_every_n_ticks=2,
        flight_persist_every=2))
    reqs = [Request(rid=f"m{i}", prompt=prompts[i][0],
                    max_new_tokens=6, seed=i) for i in range(6)]
    res = drv.run(reqs)
    return run_dir, res


def test_driver_emits_per_replica_metrics_jsonl(inline_run):
    run_dir, res = inline_run
    tdir = os.path.join(run_dir, "telemetry")
    paths = metrics_paths(tdir)
    assert len(paths) == 2
    total = 0
    for p in paths:
        parsed = read_metrics(p)
        assert parsed["header"]["version"] == "rlt-metrics-v1"
        assert len(parsed["ticks"]) >= 1
        h = parsed["hists"]["ttft_s"]
        assert h.n == parsed["counters"]["completions"]
        total += h.n
    assert total == len(res.meta) == 6
    # the driver's run-level rollup landed in serving.json
    with open(os.path.join(run_dir, "serving.json")) as f:
        doc = json.load(f)
    assert doc["metrics"]["counters"]["completions"] == 6
    lat = doc["metrics"]["latency"]["ttft_s"]
    assert lat["n"] == 6 and lat["p99"] is not None
    assert sum(c for _, c in lat["sketch"]) == 6
    assert doc["load"]["available"] is True


def test_load_signal_oracle(inline_run, tmp_path):
    from ray_lightning_tpu.serve.driver import load_signal

    run_dir, _ = inline_run
    sig = load_signal(run_dir)
    assert sig["available"] is True
    assert sig["replicas_reporting"] == 2
    assert sig["total_slots"] == 4.0
    assert sig["pressure"] is not None
    assert 0.0 <= sig["occupancy"] <= 1.0
    assert sig["queue_depth_max"] >= sig["queue_depth_p50"] >= 0
    # no metrics => explicitly unavailable, never silently zero load
    empty = load_signal(str(tmp_path))
    assert empty["available"] is False and "reason" in empty


def test_preempted_requests_get_replayed_tagged_spans(tiny, tmp_path):
    from ray_lightning_tpu.serve.driver import (
        ReplicaGroupConfig, ServeDriver,
    )
    from ray_lightning_tpu.telemetry.report import build_serving_section
    from ray_lightning_tpu.telemetry.spans import PH_QUEUE_WAIT, read_spans

    cfg, model, params, prompts = tiny
    run_dir = str(tmp_path / "run")
    drv = ServeDriver(cfg, params, ReplicaGroupConfig(
        n_replicas=1, backend="inline", reserve="on_demand",
        engine=EngineConfig(capacity=2, block_size=4, blocks_per_slot=8,
                            n_blocks=9, prefill_chunk=4),
        run_dir=run_dir))
    reqs = [Request(rid=f"p{i}", prompt=prompts[4][0],
                    max_new_tokens=20, seed=50 + i) for i in range(2)]
    res = drv.run(reqs)
    preempts = sum(m["preempted"] for m in res.meta.values())
    assert preempts >= 1
    import glob

    spans = [s for f in glob.glob(os.path.join(
        run_dir, "telemetry", "rank*.spans.jsonl"))
        for s in read_spans(f)["spans"]]
    replayed = [s for s in spans
                if (s.get("meta") or {}).get("replayed")]
    # the discarded prefix is accounted: >= one queue_wait span per
    # preemption, tagged so nothing double-counts it
    assert len([s for s in replayed
                if s["phase"] == PH_QUEUE_WAIT]) == preempts
    assert all("ttft_s" not in (s.get("meta") or {}) for s in replayed)
    # and the report counts each request ONCE despite the extra spans
    section = build_serving_section(run_dir)
    assert section["requests"] == 2
    assert section["counters"]["preemptions"] == preempts


def test_drain_records_inflight_spans(tiny, tmp_path):
    """A serve loop that stops with work in flight leaves
    inflight-tagged spans for the unfinished requests."""
    from ray_lightning_tpu.serve.driver import _record_drain
    from ray_lightning_tpu.telemetry.spans import (
        PH_QUEUE_WAIT, TelemetryRecorder, read_spans,
    )

    cfg, model, params, prompts = tiny
    eng = DecodeEngine(model, params, EngineConfig(
        capacity=2, block_size=4, blocks_per_slot=8, prefill_chunk=4))
    eng.warmup()
    sched = Scheduler(eng)
    for i in range(3):
        sched.submit(Request(rid=f"d{i}", prompt=prompts[i][0],
                             max_new_tokens=8, seed=i))
    for _ in range(4):
        sched.tick()
    assert sched.busy()
    rec = TelemetryRecorder(str(tmp_path), rank=0)
    _record_drain(rec, sched, replica=0)
    rec.close()
    spans = read_spans(rec._path)["spans"]
    inflight = [s for s in spans
                if (s.get("meta") or {}).get("inflight")]
    rids = {(s.get("meta") or {}).get("rid") for s in inflight}
    assert rids == {"d0", "d1", "d2"}
    assert all(s["phase"] == PH_QUEUE_WAIT or s["dur"] >= 0
               for s in inflight)


# ----------------------------------------------------- monitor/report CLI


def test_report_serving_section_has_p99_and_sketch(inline_run):
    from ray_lightning_tpu.telemetry.report import build_report

    run_dir, _ = inline_run
    out = build_report(run_dir)
    sv = out["serving"]
    for key in ("ttft_p99_s", "tpot_p99_s", "queue_wait_p99_s",
                "ttft_sketch", "counters", "timeline", "load_signal"):
        assert key in sv, key
    assert sv["ttft_p50_s"] <= sv["ttft_p95_s"] <= sv["ttft_p99_s"]
    assert sv["timeline"]["0"]["restart_markers"] == 0
    assert sv["load_signal"]["available"] is True


def test_monitor_serve_view(inline_run, capsys):
    from ray_lightning_tpu.telemetry.report import (
        _monitor_serve_once, run_monitor,
    )

    run_dir, _ = inline_run
    view = _monitor_serve_once(run_dir)
    assert set(view["replicas"]) == {"0", "1"}
    for rep in view["replicas"].values():
        assert rep["tick"] >= 1
        assert rep["queue_depth"] is not None
        assert rep["compile_count"] == 1
    assert view["load_signal"]["available"] is True

    rd = run_dir

    class Args:
        smoke = False
        run_dir = rd
        follow = False
        serve = True
        interval = 5.0
        as_json = True

    assert run_monitor(Args()) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(line["replicas"]) == {"0", "1"}


# ------------------------------------------------------ bench schema + gate


def test_bench_serving_leg_schema():
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import bench

    row = bench._measure_serving(tiny=True, autoscale=False)
    assert row["ttft_p99_s"] is not None
    sm = row["serve_metrics"]
    for key in ("queue_depth_p50", "queue_depth_max", "preemptions",
                "growth_stalls", "ttft_p99_s", "ticks"):
        assert key in sm, key
    assert sm["completions"] == sm["admissions"] > 0
    assert sm["ticks"] > 0
    assert row["serving_compile_count"] in (1, -1)


def _load_bench_gate():
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "bench_gate.py")
    spec = importlib.util.spec_from_file_location("bench_gate", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_gate_bounds_ttft_p99():
    bg = _load_bench_gate()
    base = {"metric": "m", "value": 100.0}
    # over the bound on a measured line: fails, naming the SLO
    msgs = bg.gate({**base, "ttft_p99_s": 99.0}, {}, 0.05)
    assert any("ttft_p99_s" in m and "SLO" in m for m in msgs)
    # within the bound: passes
    assert bg.gate({**base, "ttft_p99_s": 0.5}, {}, 0.05) == []
    # null waives (probe failed), absent waives (historic line)
    assert bg.gate({**base, "ttft_p99_s": None}, {}, 0.05) == []
    assert bg.gate(dict(base), {}, 0.05) == []
    # an environmental skip line waives the bound entirely
    skip = {"metric": "m", "value": 0.0, "skipped": "backend down",
            "ttft_p99_s": 99.0}
    assert bg.gate(skip, {}, 0.05) == []

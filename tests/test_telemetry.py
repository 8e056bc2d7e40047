"""Telemetry subsystem (ISSUE 7, docs/OBSERVABILITY.md): span recorder,
goodput accounting, profiler triggers, drift report, heartbeat phases,
the RLT501 lint rule, the ThroughputMonitor compile-skew fix, and the
bench_gate goodput/overhead legs.

The load-bearing pins:
  * telemetry=off vs on train BITWISE-identically and lower
    byte-identical step programs (telemetry is host bookkeeping, never
    program content);
  * telemetry=on performs the SAME number of host transfers as off
    (device_get counted) — zero new host syncs;
  * goodput buckets sum to wall (worker ledgers exactly; assembled
    reports within tolerance) and replay attribution reclassifies
    re-trained steps.
"""
import contextlib
import importlib.util
import json
import logging
import os
import subprocess
import sys
import time

import numpy as np
import pytest


@contextlib.contextmanager
def _capture_logs(name):
    """The package logger sets propagate=False (utils/logging.py), so
    caplog never sees it — attach a list handler directly."""
    records = []

    class _H(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    handler = _H()
    logger = logging.getLogger(name)
    logger.addHandler(handler)
    try:
        yield records
    finally:
        logger.removeHandler(handler)

from ray_lightning_tpu.telemetry import (
    TelemetryConfig,
    TelemetryRecorder,
    assemble_goodput,
    buckets_consistent,
)
from ray_lightning_tpu.telemetry.goodput import (
    read_ledgers,
    worker_ledger,
    write_ledger,
)
from ray_lightning_tpu.telemetry.spans import (
    NULL_RECORDER,
    PH_COMPILE,
    PH_DISPATCH,
    PH_STEP,
    THREAD_PRODUCER,
    read_spans,
)


def _mlp_fit(tmp_path, telemetry, steps=4, name="run", **trainer_kw):
    from ray_lightning_tpu import DataLoader, Trainer
    from ray_lightning_tpu.models.mlp import MLPClassifier

    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 8)).astype(np.float32)
    y = rng.integers(0, 4, size=(64,))
    trainer = Trainer(max_epochs=2, max_steps=steps, seed=0,
                      enable_checkpointing=False,
                      enable_progress_bar=False,
                      default_root_dir=str(tmp_path / name),
                      telemetry=telemetry, log_every_n_steps=2,
                      **trainer_kw)
    module = MLPClassifier(features=(16,), num_classes=4, lr=1e-2)
    trainer.fit(module, DataLoader({"x": x, "y": y}, batch_size=16))
    return trainer


# --------------------------------------------------------------------------
# recorder
# --------------------------------------------------------------------------


class TestRecorder:
    def test_span_totals_and_ring(self, tmp_path):
        rec = TelemetryRecorder(directory=str(tmp_path), rank=3,
                                ring_size=8)
        with rec.span(PH_DISPATCH, step=7):
            pass
        rec.record(PH_STEP, time.perf_counter(), 0.5, step=7)
        totals = rec.phase_totals()
        assert totals[PH_STEP] == pytest.approx(0.5)
        assert PH_DISPATCH in totals
        # ring bound: 20 records into a size-8 ring drop 12
        for i in range(20):
            rec.record("x", 0.0, 0.001, step=i)
        assert rec.dropped == 20 + 2 - 8
        rec.close()
        import glob as _glob

        [path] = _glob.glob(os.path.join(str(tmp_path),
                                         "rank3.*.spans.jsonl"))
        parsed = read_spans(path)
        assert parsed["header"]["rank"] == 3
        assert parsed["dropped"] == rec.dropped
        assert len(parsed["spans"]) == 8  # what survived the ring

    def test_producer_spans_excluded_from_totals(self):
        rec = TelemetryRecorder()
        rec.record("h2d", 0.0, 1.0, thread=THREAD_PRODUCER)
        rec.record(PH_STEP, 0.0, 0.25)
        assert "h2d" not in rec.phase_totals()
        assert rec.phase_totals()[PH_STEP] == pytest.approx(0.25)

    def test_current_phase_tracks_main_spans_only(self):
        rec = TelemetryRecorder()
        assert rec.current_phase() == "setup"
        with rec.span(PH_COMPILE):
            assert rec.current_phase() == PH_COMPILE
            with rec.span("h2d", thread=THREAD_PRODUCER):
                assert rec.current_phase() == PH_COMPILE
        assert rec.current_phase() == PH_STEP
        assert rec.last_span()["phase"] == PH_COMPILE

    def test_nested_main_spans_charge_exclusively(self):
        # a lazy compile INSIDE the eval span: totals must not count
        # that second twice (the goodput buckets sum to wall)
        rec = TelemetryRecorder()
        with rec.span("eval"):
            with rec.span(PH_COMPILE):
                time.sleep(0.05)
            assert rec.current_phase() == "eval"  # restored, not "step"
        totals = rec.phase_totals()
        assert totals[PH_COMPILE] >= 0.05
        assert totals["eval"] < totals[PH_COMPILE]  # exclusive remainder
        # the span ENTRY keeps the full duration for the timeline
        evals = [s for s in rec._ring if s["phase"] == "eval"]
        assert evals[0]["dur"] >= 0.05

    def test_null_recorder_is_inert(self):
        with NULL_RECORDER.span("anything"):
            pass
        NULL_RECORDER.record("x", 0.0, 1.0)
        assert NULL_RECORDER.phase_totals() == {}
        assert NULL_RECORDER.flush() == 0
        assert not NULL_RECORDER.enabled

    def test_config_coerce(self, tmp_path):
        assert TelemetryConfig.coerce(None) is None
        assert TelemetryConfig.coerce(False) is None
        assert TelemetryConfig.coerce(True).dir is None
        assert TelemetryConfig.coerce(str(tmp_path)).dir == str(tmp_path)
        cfg = TelemetryConfig(dir="x")
        assert TelemetryConfig.coerce(cfg) is cfg
        with pytest.raises(TypeError):
            TelemetryConfig.coerce(3)
        assert TelemetryConfig().resolved_dir("/r") == "/r/telemetry"


# --------------------------------------------------------------------------
# trainer integration
# --------------------------------------------------------------------------


class TestTrainerTelemetry:
    def test_fit_writes_spans_and_ledger(self, tmp_path):
        trainer = _mlp_fit(tmp_path, telemetry=True, steps=6)
        tdir = str(tmp_path / "run" / "telemetry")
        import glob as _glob

        [spans_path] = _glob.glob(
            os.path.join(tdir, "rank0.*.spans.jsonl"))
        parsed = read_spans(spans_path)
        phases = {s["phase"] for s in parsed["spans"]}
        assert {"dispatch", "step", "compile", "h2d"} <= phases
        # producer-thread H2D spans are tagged so goodput never
        # double-charges overlapped time
        assert any(s.get("thread") == THREAD_PRODUCER
                   for s in parsed["spans"] if s["phase"] == "h2d")
        ledgers = read_ledgers(tdir, rank=0)
        assert ledgers and ledgers[-1]["completed"]
        led = ledgers[-1]
        assert led["end_step"] == 6
        # worker ledger books close exactly: productive is wall minus
        # the measured stalls
        assert sum(led["buckets"].values()) == pytest.approx(
            led["wall_s"], rel=1e-6)
        # surfaced in callback_metrics
        assert "goodput_fraction" in trainer.callback_metrics
        assert trainer.callback_metrics["telemetry_compile_s"] > 0

    def test_off_is_bitwise_and_program_identical(self, tmp_path):
        import jax

        t_off = _mlp_fit(tmp_path, telemetry=False, name="off")
        t_on = _mlp_fit(tmp_path, telemetry=True, name="on")
        for a, b in zip(jax.tree.leaves(t_off.state.params),
                        jax.tree.leaves(t_on.state.params)):
            assert jax.numpy.array_equal(a, b)

        def lowered(tr):
            batch = tr._place_train_batch(
                {"x": np.zeros((16, 8), np.float32),
                 "y": np.zeros((16,), np.int64)})[1]
            return tr._train_step._jitted.lower(
                tr.state, batch, tr._base_rng).as_text()

        assert lowered(t_off) == lowered(t_on)

    def test_on_adds_zero_host_transfers(self, tmp_path, monkeypatch):
        import jax

        counts = {}

        real_device_get = jax.device_get

        def counting_device_get(x):
            counts["n"] = counts.get("n", 0) + 1
            return real_device_get(x)

        monkeypatch.setattr(jax, "device_get", counting_device_get)
        counts["n"] = 0
        _mlp_fit(tmp_path, telemetry=False, name="cnt_off")
        off_n = counts["n"]
        counts["n"] = 0
        _mlp_fit(tmp_path, telemetry=True, name="cnt_on")
        assert counts["n"] == off_n

    def test_telemetry_off_by_default(self, tmp_path):
        trainer = _mlp_fit(tmp_path, telemetry=None, name="default")
        assert trainer.telemetry_recorder is NULL_RECORDER
        assert not (tmp_path / "default" / "telemetry").exists()


# --------------------------------------------------------------------------
# goodput
# --------------------------------------------------------------------------


def _fake_ledger(tdir, wall, start, end, t0, productive=None,
                 compile_s=0.0, pid=None):
    rec = TelemetryRecorder()
    if compile_s:
        rec.record("compile", 0.0, compile_s)
    led = worker_ledger(rec, wall, rank=0, start_step=start,
                        end_step=end, completed=True)
    led["t0_wall"] = t0
    path = write_ledger(tdir, led)
    if pid is not None:  # distinct filenames for same-process "attempts"
        os.replace(path, os.path.join(tdir, f"ledger.rank0.{pid}.json"))
    return led


class TestGoodput:
    def test_ledger_books_close_exactly(self):
        rec = TelemetryRecorder()
        rec.record("compile", 0.0, 2.0)
        rec.record("data_wait", 0.0, 1.0)
        rec.record("h2d", 0.0, 5.0, thread=THREAD_PRODUCER)  # overlapped
        led = worker_ledger(rec, 10.0, rank=0, start_step=0, end_step=8)
        b = led["buckets"]
        assert b["compile_s"] == 2.0
        assert b["data_wait_s"] == 1.0
        assert b["productive_s"] == pytest.approx(7.0)
        assert sum(b.values()) == pytest.approx(10.0)

    def test_assemble_replay_attribution(self, tmp_path):
        tdir = str(tmp_path)
        # attempt 1: reached step 10, died; attempt 2: resumed at 4 —
        # 6 of its 16 steps are replay
        _fake_ledger(tdir, wall=10.0, start=0, end=10, t0=100.0, pid=11)
        _fake_ledger(tdir, wall=16.0, start=4, end=20, t0=200.0, pid=22)
        report = assemble_goodput(tdir, wall_s=30.0, backoff_s=2.0,
                                  restarts=1)
        b = report["buckets"]
        assert b["backoff_s"] == 2.0
        # replay share: 6/16 of attempt 2's productive time (== 16s,
        # no stalls recorded)
        assert b["rollback_replay_s"] == pytest.approx(6.0)
        assert b["productive_s"] == pytest.approx(10.0 + 16.0 - 6.0)
        assert report["buckets_sum_s"] == pytest.approx(30.0, rel=1e-3)
        assert buckets_consistent(report)
        assert report["attempts"][1]["replay_steps"] == 6

    def test_assemble_no_ledgers_still_structured(self, tmp_path):
        report = assemble_goodput(str(tmp_path), wall_s=5.0)
        assert report["ledgers"] == 0
        assert report["buckets"]["other_s"] == pytest.approx(5.0)
        assert buckets_consistent(report)

    def test_buckets_consistent_rejects_gap(self):
        assert not buckets_consistent(
            {"wall_s": 10.0, "buckets": {"productive_s": 5.0}})


# --------------------------------------------------------------------------
# profiler
# --------------------------------------------------------------------------


class _FakeProfiler:
    def __init__(self, fail=False):
        self.fail = fail
        self.starts = []
        self.stops = 0

    def start_trace(self, d):
        if self.fail:
            raise RuntimeError("no profiling on this backend")
        self.starts.append(d)

    def stop_trace(self):
        self.stops += 1


class TestProfiler:
    def _patch(self, monkeypatch, fake):
        import jax

        monkeypatch.setattr(jax.profiler, "start_trace",
                            fake.start_trace)
        monkeypatch.setattr(jax.profiler, "stop_trace", fake.stop_trace)

    def test_step_window(self, tmp_path, monkeypatch):
        from ray_lightning_tpu.telemetry import (
            ProfileConfig, ProfilerController,
        )

        fake = _FakeProfiler()
        self._patch(monkeypatch, fake)
        ctl = ProfilerController(ProfileConfig(
            dir=str(tmp_path), start_step=3, num_steps=2), rank=0)
        for step in range(1, 8):
            ctl.on_step(step)
        assert fake.starts == [str(tmp_path)]
        assert fake.stops == 1
        assert ctl.captures == 1
        assert not ctl.capturing

    def test_marker_trigger_and_rank_scope(self, tmp_path, monkeypatch):
        from ray_lightning_tpu.telemetry import (
            ProfileConfig, ProfilerController,
        )

        fake = _FakeProfiler()
        self._patch(monkeypatch, fake)
        cfg = ProfileConfig(dir=str(tmp_path), num_steps=1,
                            poll_every_n_steps=1)
        # rank 1 is out of scope: the marker must not trigger there
        other = ProfilerController(cfg, rank=1)
        ctl = ProfilerController(cfg, rank=0)
        (tmp_path / "CAPTURE").touch()
        other.on_step(1)
        assert fake.starts == []
        ctl.on_step(1)
        assert fake.starts == [str(tmp_path)]
        # marker is consumed: one touch = one capture
        assert not (tmp_path / "CAPTURE").exists()
        ctl.on_step(2)
        assert fake.stops == 1

    def test_backend_failure_disables_loudly(self, tmp_path,
                                             monkeypatch):
        from ray_lightning_tpu.telemetry import (
            ProfileConfig, ProfilerController,
        )

        fake = _FakeProfiler(fail=True)
        self._patch(monkeypatch, fake)
        ctl = ProfilerController(ProfileConfig(
            dir=str(tmp_path), start_step=1, num_steps=1), rank=0)
        with _capture_logs(
                "ray_lightning_tpu.telemetry.profiler") as records:
            ctl.on_step(1)
        assert ctl.disabled_reason
        assert any("DISABLED" in m for m in records)
        # disarmed: later steps never retry into the same failure
        ctl.on_step(2)
        assert fake.stops == 0

    def test_trainer_profile_knob(self, tmp_path, monkeypatch):
        fake = _FakeProfiler()
        self._patch(monkeypatch, fake)
        from ray_lightning_tpu.telemetry import ProfileConfig

        _mlp_fit(tmp_path, telemetry=False, steps=6, name="prof",
                 profile=ProfileConfig(dir=str(tmp_path / "traces"),
                                       start_step=2, num_steps=2))
        assert fake.starts == [str(tmp_path / "traces")]
        assert fake.stops == 1


# --------------------------------------------------------------------------
# heartbeat phase + stall attribution
# --------------------------------------------------------------------------


class TestHeartbeatPhase:
    def test_heartbeat_carries_phase_and_span(self):
        from ray_lightning_tpu.resilience.health import make_heartbeat

        hb = make_heartbeat(1, step=12, phase="ckpt_stall",
                            span={"phase": "ckpt_stall", "dur": 1.5,
                                  "step": 12, "t": 9.0})
        assert hb["phase"] == "ckpt_stall"
        assert hb["span"] == {"phase": "ckpt_stall", "dur": 1.5,
                              "step": 12}

    def test_stall_error_names_phase_and_step(self):
        from ray_lightning_tpu.resilience.health import (
            HealthMonitor, make_heartbeat,
        )
        from ray_lightning_tpu.resilience.policy import StallError

        mon = HealthMonitor(num_workers=1, stall_timeout_s=5.0,
                            startup_grace_s=1.0)
        mon.consume(0, make_heartbeat(0, step=42, phase="ckpt_stall"))
        with pytest.raises(StallError) as err:
            mon.check(now=time.monotonic() + 60.0)
        assert "ckpt_stall" in str(err.value)
        assert "42" in str(err.value)
        assert err.value.phase == "ckpt_stall"

    def test_compile_phase_reads_span_not_counter(self):
        from ray_lightning_tpu.resilience.health import (
            HealthMonitor, make_heartbeat,
        )

        mon = HealthMonitor(num_workers=1, stall_timeout_s=1e9,
                            step_stall_note_s=5.0)
        t0 = time.monotonic()
        mon.consume(0, make_heartbeat(0, step=10, phase="compile"))
        # keep the channel live but the step frozen past the note budget
        with _capture_logs(
                "ray_lightning_tpu.resilience.health") as records:
            mon._last_seen[0] = t0 + 59.0
            mon.check(now=t0 + 60.0)
        assert any("XLA compile" in m for m in records)
        assert mon.snapshot()[0]["phase"] == "compile"


# --------------------------------------------------------------------------
# ThroughputMonitor compile-skew
# --------------------------------------------------------------------------


class TestThroughputMonitorSkew:
    def _run(self, intervals, skip_first=1):
        from ray_lightning_tpu.core.callbacks import ThroughputMonitor

        ticks = [0.0]
        for dt in intervals:
            ticks.append(ticks[-1] + dt)
        it = iter(ticks)
        mon = ThroughputMonitor(window=20, skip_first=skip_first,
                                clock=lambda: next(it))

        class T:
            callback_metrics = {}
            last_batch_size = 32

        t = T()
        mon.on_fit_start(t, None)
        mon.on_train_epoch_start(t, None)
        for i in range(len(intervals)):
            mon.on_train_batch_end(t, None, {}, i)
        return t.callback_metrics

    def test_cold_compile_interval_excluded(self):
        # first "step" is a 10s lazy compile against 0.1s warm steps —
        # the window mean must be the warm step time, not 2.575s
        metrics = self._run([10.0, 0.1, 0.1, 0.1])
        assert metrics["step_time_s"] == pytest.approx(0.1)
        assert metrics["examples_per_sec"] == pytest.approx(320.0)

    def test_skip_zero_reproduces_the_skew(self):
        metrics = self._run([10.0, 0.1, 0.1, 0.1], skip_first=0)
        assert metrics["step_time_s"] == pytest.approx(2.575)


# --------------------------------------------------------------------------
# report + drift
# --------------------------------------------------------------------------


class TestReportDrift:
    def test_build_drift_placeholder_when_unmeasured(self):
        from ray_lightning_tpu.telemetry.report import build_drift

        drift = build_drift({"step_us": 1000.0},
                            timeline=None)
        assert drift["verdict"] == "not-measured"
        assert drift["measured"]["step_us"] is None
        assert "skipped" in drift["measured"]

    def test_build_drift_flags_slow_step(self):
        from ray_lightning_tpu.telemetry.report import build_drift

        timeline = {"step_stats": {"steps": 10, "mean_s": 2e-3,
                                   "p50_s": 2e-3, "max_s": 2e-3}}
        drift = build_drift({"step_us": 1000.0}, timeline)
        assert drift["step_time_ratio"] == pytest.approx(2.0)
        assert drift["verdict"] == "drift"
        assert drift["flags"]

    def test_build_drift_ok_within_threshold(self):
        from ray_lightning_tpu.telemetry.report import build_drift

        timeline = {"step_stats": {"steps": 10, "mean_s": 1.1e-3,
                                   "p50_s": 1.1e-3, "max_s": 1.2e-3}}
        drift = build_drift({"step_us": 1000.0}, timeline)
        assert drift["verdict"] == "ok"
        assert not drift["flags"]

    def test_report_on_real_run_dir(self, tmp_path):
        _mlp_fit(tmp_path, telemetry=True, steps=6, name="reported")
        from ray_lightning_tpu.telemetry.report import build_report

        out = build_report(str(tmp_path / "reported"))
        assert 0 in [int(r) for r in out["phase_totals"]]
        assert out["step_stats"]["steps"] >= 1
        json.dumps(out)  # the --json path must be serializable

    def test_report_cli_json(self, tmp_path, capsys):
        _mlp_fit(tmp_path, telemetry=True, steps=4, name="cli")
        from ray_lightning_tpu.__main__ import main

        rc = main(["report", str(tmp_path / "cli"), "--json"])
        assert rc == 0
        out = json.loads(capsys.readouterr().out.strip())
        assert out["step_stats"] is not None

    def test_monitor_cli_one_shot(self, tmp_path, capsys):
        _mlp_fit(tmp_path, telemetry=True, steps=4, name="mon")
        from ray_lightning_tpu.__main__ import main

        rc = main(["monitor", str(tmp_path / "mon"), "--json"])
        assert rc == 0
        view = json.loads(capsys.readouterr().out.strip())
        assert view["ranks"]["0"]["phase"] is not None

    def test_predicted_composition_tiny_topo(self):
        from ray_lightning_tpu.telemetry.report import (
            predicted_step_composition,
        )

        pred = predicted_step_composition("llama3-8b", "v5p-8")
        assert "error" not in pred
        assert pred["ici_time_us"] > 0
        assert pred["step_us"] == pytest.approx(
            pred["compute_us"] + pred["ici_time_us"], abs=0.2)
        assert not [k for k in pred if k.startswith("overlap")]


# --------------------------------------------------------------------------
# RLT501
# --------------------------------------------------------------------------


class TestRLT501:
    def _rules(self, src):
        from ray_lightning_tpu.analysis.linter import lint_source

        return [f for f in lint_source(src, "x.py")
                if f.rule == "RLT501"]

    def test_flush_per_batch_fires(self):
        src = ("def run(loader, telemetry):\n"
               "    for batch in loader:\n"
               "        telemetry.flush()\n")
        assert len(self._rules(src)) == 1

    def test_span_per_batch_fires(self):
        src = ("def run(loader, recorder):\n"
               "    for batch in loader:\n"
               "        with recorder.span('dispatch'):\n"
               "            pass\n")
        assert len(self._rules(src)) == 1

    def test_cadence_guard_sanctions(self):
        src = ("def run(loader, telemetry):\n"
               "    step = 0\n"
               "    for batch in loader:\n"
               "        step += 1\n"
               "        if step % 50 == 0:\n"
               "            telemetry.flush()\n")
        assert self._rules(src) == []

    def test_unbounded_callback_append_fires(self):
        src = ("class EventsCallback(Callback):\n"
               "    def __init__(self):\n"
               "        self.events = []\n"
               "    def on_train_batch_end(self, t, m, metrics, i):\n"
               "        self.events.append(metrics)\n")
        found = self._rules(src)
        assert len(found) == 1
        assert "EventsCallback" in found[0].message

    def test_bounded_callback_patterns_clean(self):
        src = ("import collections\n"
               "class RingCallback(Callback):\n"
               "    def __init__(self):\n"
               "        self.events = collections.deque(maxlen=8)\n"
               "    def on_train_batch_end(self, t, m, metrics, i):\n"
               "        self.events.append(metrics)\n"
               "class TruncCallback(Callback):\n"
               "    def __init__(self):\n"
               "        self.events = []\n"
               "    def on_train_batch_end(self, t, m, metrics, i):\n"
               "        self.events.append(metrics)\n"
               "        self.events = self.events[-10:]\n"
               "class FlushCallback(Callback):\n"
               "    def __init__(self):\n"
               "        self.events = []\n"
               "    def on_train_batch_end(self, t, m, metrics, i):\n"
               "        self.events.append(metrics)\n"
               "    def on_train_epoch_end(self, t, m):\n"
               "        self.events.clear()\n")
        assert self._rules(src) == []

    def test_repo_lints_clean(self):
        from ray_lightning_tpu.analysis.linter import lint_paths

        pkg = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "ray_lightning_tpu")
        assert [f for f in lint_paths([pkg])
                if f.rule == "RLT501"] == []


# --------------------------------------------------------------------------
# bench gate: goodput ratchet + overhead bound
# --------------------------------------------------------------------------


def _bench_gate():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "bench_gate.py")
    spec = importlib.util.spec_from_file_location("bench_gate_t", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestBenchGate:
    """`scripts/bench_gate.py`'s generic behaviour, `mfu` the example
    field."""

    def _priors(self, tmp_path):
        # r01 is a round from before PR 47: the field it still carries
        # is no longer ratcheted and must be read past
        (tmp_path / "BENCH_r01.json").write_text(json.dumps({
            "parsed": {"metric": "m", "value": 100.0, "mfu": 0.5,
                       "overlap_hidden_fraction": 0.8}}))
        (tmp_path / "BENCH_r02.json").write_text(json.dumps({
            "parsed": {"metric": "m", "value": 90.0, "mfu": 0.6}}))
        # a skipped round must not set the measured-metric bar
        (tmp_path / "BENCH_r03.json").write_text(json.dumps({
            "parsed": {"metric": "m", "value": 0.0, "mfu": 0.95,
                       "skipped": "backend unavailable"}}))
        return tmp_path

    def test_pass_and_regress(self, tmp_path):
        bg = _bench_gate()
        self._priors(tmp_path)
        best = bg.best_prior("BENCH_r0*.json", str(tmp_path))
        # per-metric max across the measured rounds
        assert best["tokens_per_sec_per_chip"][0] == 100.0
        assert best["mfu"][0] == 0.6
        assert "overlap_hidden_fraction" not in best

        ok = {"metric": "m", "value": 99.0, "mfu": 0.59}
        assert bg.gate(ok, best, 0.05) == []
        bad = {"metric": "m", "value": 50.0, "mfu": 0.59}
        msgs = bg.gate(bad, best, 0.05)
        assert len(msgs) == 1 and "tokens_per_sec_per_chip" in msgs[0]

    def test_dropped_field_fails(self, tmp_path):
        bg = _bench_gate()
        self._priors(tmp_path)
        best = bg.best_prior("BENCH_r0*.json", str(tmp_path))
        naked = {"metric": "m", "value": 200.0}
        msgs = bg.gate(naked, best, 0.05)
        assert any("mfu" in m and "dropped" in m for m in msgs)
        # the field only an old prior carries is not demanded
        assert not any("overlap" in m for m in msgs)

    def test_null_value_prior_tolerated(self, tmp_path):
        """A prior round whose line carries "value": null (a partial
        result) must be skipped, not crash best_prior with a
        TypeError."""
        bg = _bench_gate()
        self._priors(tmp_path)
        (tmp_path / "BENCH_r04.json").write_text(json.dumps({
            "parsed": {"metric": "m", "value": None, "mfu": 0.99}}))
        best = bg.best_prior("BENCH_r0*.json", str(tmp_path))
        # the null round is unmeasured: its mfu must not set the bar
        assert best["mfu"][0] == 0.6

    def test_skip_passes_structured_only(self, tmp_path):
        bg = _bench_gate()
        self._priors(tmp_path)
        best = bg.best_prior("BENCH_r0*.json", str(tmp_path))
        assert bg.gate({"metric": "m", "value": 0.0,
                        "skipped": "backend unavailable"}, best, 0.05) == []
        assert bg.gate({"skipped": "backend unavailable"}, best, 0.05)

    def test_cli_against_recorded_history(self, tmp_path):
        """The gate must accept a history's own best round (no
        self-regression) and reject a gutted line; with no prior round
        on record it says so and passes."""
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        script = os.path.join(root, "scripts", "bench_gate.py")
        self._priors(tmp_path)
        hist = ["--repo-root", str(tmp_path)]
        (tmp_path / "fresh.json").write_text(json.dumps({
            "metric": "m", "value": 100.0, "mfu": 0.6}))
        r = subprocess.run(
            [sys.executable, script, str(tmp_path / "fresh.json"),
             *hist], capture_output=True, text=True)
        assert r.returncode == 0, r.stderr
        gutted = json.dumps({"metric": "m", "value": 1.0, "mfu": 0.01})
        r = subprocess.run(
            [sys.executable, script, "-", *hist], input=gutted,
            capture_output=True, text=True)
        assert r.returncode == 1
        assert "REGRESSION" in r.stderr
        empty = tmp_path / "empty"
        empty.mkdir()
        r = subprocess.run(
            [sys.executable, script, "-", "--repo-root", str(empty)],
            input=gutted, capture_output=True, text=True)
        assert r.returncode == 0, r.stderr
        assert "no prior" in r.stdout

    def test_unparseable_fails(self, tmp_path):
        bg = _bench_gate()
        assert bg._last_json_line("rc=124 no json at all") is None
        f = tmp_path / "garbage.json"
        f.write_text("not json\n")
        assert bg.main([str(f)]) == 2


class TestBenchGateTelemetry:
    def test_goodput_fraction_ratchets(self):
        bg = _bench_gate()
        best = {"goodput_fraction": (0.9, "r06")}
        assert bg.gate({"metric": "m", "value": 1.0,
                        "goodput_fraction": 0.92}, best, 0.05) == []
        bad = bg.gate({"metric": "m", "value": 1.0,
                       "goodput_fraction": 0.5}, best, 0.05)
        assert bad and "goodput_fraction" in bad[0]

    def test_goodput_waived_on_skip(self):
        bg = _bench_gate()
        best = {"goodput_fraction": (0.9, "r06")}
        line = {"metric": "m", "skipped": "backend unavailable",
                "goodput_fraction": 0.0}
        assert bg.gate(line, best, 0.05) == []

    def test_overhead_bound(self):
        bg = _bench_gate()
        ok = {"metric": "m", "value": 1.0,
              "telemetry_overhead_fraction": 0.003}
        bad = {"metric": "m", "value": 1.0,
               "telemetry_overhead_fraction": 0.03}
        absent = {"metric": "m", "value": 1.0}
        null = {"metric": "m", "value": 1.0,
                "telemetry_overhead_fraction": None}
        assert bg.gate(ok, {}, 0.05) == []
        assert bg.gate(absent, {}, 0.05) == []
        assert bg.gate(null, {}, 0.05) == []
        fail = bg.gate(bad, {}, 0.05)
        assert fail and "telemetry_overhead_fraction" in fail[0]

    def test_overhead_waived_on_skip(self):
        bg = _bench_gate()
        line = {"metric": "m", "skipped": "killed: SIGTERM",
                "telemetry_overhead_fraction": 0.5}
        assert bg.gate(line, {}, 0.05) == []

    def test_bench_overhead_measure_is_tiny(self):
        # the measured recorder cost against a realistic 10 ms step:
        # far under the 1% gate, or the bound is meaningless
        import bench

        # a wall-clock reading on a CPU that other test workers share:
        # the recorder either is cheap or is not, so the best of five
        # readings is judged (two of three whole runs failed on one)
        frac = min(bench._telemetry_overhead_fraction(step_dt=0.010, n=500)
                   for _ in range(5))
        assert frac < 0.01

    def test_bench_telemetry_summary_schema(self):
        import bench

        summary = bench._telemetry_summary()
        assert "telemetry_error" not in summary
        assert "buckets" in summary["goodput"]["schema"]
        assert "dispatch" in summary["telemetry"]["span_phases"]


# --------------------------------------------------------------------------
# supervised goodput (2-proc, fault-injected) — the satellite-3 pin
# --------------------------------------------------------------------------


@pytest.mark.slow
class TestSupervisedGoodput:
    def test_kill_restart_buckets_sum_and_replay(self, tmp_path):
        from ray_lightning_tpu.resilience.cli import (
            _smoke_data, _smoke_module, _smoke_trainer,
        )
        from ray_lightning_tpu.resilience.policy import RetryPolicy
        from ray_lightning_tpu.resilience.supervisor import (
            ResilienceConfig, fit_supervised,
        )
        from ray_lightning_tpu.telemetry import buckets_consistent

        cfg = ResilienceConfig(
            checkpoint_dir=str(tmp_path / "ckpts"),
            policy=RetryPolicy(max_restarts=2, backoff_base_s=0.5,
                               jitter=0.0),
            save_every_n_steps=5,
            heartbeat_interval_s=1.0, stall_timeout_s=0.0,
            faults="kill:rank=1,step=3")
        supervised = fit_supervised(
            _smoke_module, _smoke_trainer, _smoke_data, 2,
            resilience=cfg, platform="cpu",
            num_cpu_devices_per_process=1, return_weights=False,
            timeout=300.0)
        assert supervised.restarts >= 1
        report = supervised.goodput
        assert report is not None
        assert buckets_consistent(report, tolerance=0.05)
        assert report["buckets"]["backoff_s"] > 0
        assert report["buckets"]["rollback_replay_s"] > 0
        # persisted beside the checkpoints for the report CLI
        assert os.path.exists(os.path.join(
            str(tmp_path / "ckpts"), "telemetry", "goodput.json"))

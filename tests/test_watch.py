"""SLO watch rules + automatic incident capture (telemetry/watch.py,
telemetry/incidents.py, docs/OBSERVABILITY.md "watch rules &
incidents"): rule/threshold/sustain/burn-window semantics, episode
fire-once, metric surfaces over real persisted fixtures, the incident
record contract (evidence + timeline excerpt + capture actions), the
controller/driver wiring (forced flight persist), the watch-off
program pin, lint rule RLT503, and the bench/bench_gate incident
surfaces."""
from __future__ import annotations

import dataclasses
import json
import os
import time

import pytest

from ray_lightning_tpu.telemetry import watch as watch_mod
from ray_lightning_tpu.telemetry.incidents import (
    append_incident,
    capture_evidence,
    read_incidents,
)
from ray_lightning_tpu.telemetry.watch import (
    BUILTIN_RULES,
    MetricSurfaces,
    WatchConfig,
    WatchEngine,
    WatchRule,
)


def _tdir(run_dir: str) -> str:
    return os.path.join(run_dir, "telemetry")


# ------------------------------------------------------------- rule units


def test_rule_validation():
    with pytest.raises(ValueError, match="unknown op"):
        WatchRule("r", "load.pressure", "~", 1.0)
    with pytest.raises(ValueError, match="sustain"):
        WatchRule("r", "load.pressure", ">", 1.0, sustain=0)
    with pytest.raises(ValueError, match="could never fire"):
        WatchRule("r", "load.pressure", ">", 1.0, sustain=3, window=2)
    with pytest.raises(ValueError, match="severity"):
        WatchRule("r", "load.pressure", ">", 1.0, severity="meh")
    r = WatchRule("r", "load.pressure", ">=", 2.0)
    assert r.breached(2.0) and not r.breached(1.9)


def test_watch_config_coerce():
    assert WatchConfig.coerce(None) is None
    assert WatchConfig.coerce(False) is None
    assert WatchConfig.coerce(True).rules == BUILTIN_RULES
    rules = (WatchRule("r", "load.pressure", ">", 1.0),)
    assert WatchConfig.coerce(rules).rules == rules
    cfg = WatchConfig(excerpt_events=3)
    assert WatchConfig.coerce(cfg) is cfg
    with pytest.raises(TypeError):
        WatchConfig.coerce("yes")


class _ScriptedSurfaces:
    """MetricSurfaces stand-in: scripted values per selector, popped
    one per poll."""

    script: dict = {}

    def __init__(self, run_dir, tail_bytes=0, telemetry_dir=None):
        pass

    def value(self, selector):
        seq = self.script.get(selector)
        if not seq:
            return None
        return seq.pop(0)

    def evidence(self, selector):
        return {"scripted": True}


@pytest.fixture
def scripted(monkeypatch, tmp_path):
    def make(script):
        _ScriptedSurfaces.script = {k: list(v)
                                    for k, v in script.items()}
        monkeypatch.setattr(watch_mod, "MetricSurfaces",
                            _ScriptedSurfaces)
        return str(tmp_path)
    return make


def test_sustain_consecutive(scripted):
    run = scripted({"load.pressure": [3.0, 1.0, 3.0, 3.0, 3.0]})
    rule = WatchRule("qp", "load.pressure", ">", 2.0, sustain=2)
    eng = WatchEngine(run, WatchConfig(rules=(rule,), capture=False))
    # breach, clear, breach, breach(sustained -> fire), breach(open)
    assert [len(eng.poll()) for _ in range(5)] == [0, 0, 0, 1, 0]
    assert eng.fired == 1


def test_burn_rate_window(scripted):
    run = scripted({"load.pressure": [3.0, 1.0, 3.0]})
    rule = WatchRule("qp", "load.pressure", ">", 2.0, sustain=2,
                     window=4)
    eng = WatchEngine(run, WatchConfig(rules=(rule,), capture=False))
    # 2 breaches anywhere in the last 4 evaluations fire — NOT
    # consecutive (the K-in-window burn-rate form)
    assert [len(eng.poll()) for _ in range(3)] == [0, 0, 1]


def test_episode_fire_once_and_rearm(scripted):
    run = scripted({"load.pressure": [3.0, 3.0, 3.0, 1.0, 3.0]})
    rule = WatchRule("qp", "load.pressure", ">", 2.0)
    eng = WatchEngine(run, WatchConfig(rules=(rule,), capture=False))
    fired = [len(eng.poll()) for _ in range(5)]
    # one incident per EPISODE: sustained breach fires once; clearing
    # re-arms; the next breach is a new episode
    assert fired == [1, 0, 0, 0, 1]


def test_none_signal_holds_state(scripted):
    run = scripted({"load.pressure": [3.0, None, 3.0]})
    rule = WatchRule("qp", "load.pressure", ">", 2.0, sustain=2)
    eng = WatchEngine(run, WatchConfig(rules=(rule,), capture=False))
    # None neither clears nor counts: the streak survives the gap
    assert [len(eng.poll()) for _ in range(3)] == [0, 0, 1]


# ------------------------------------------------ metric surfaces (real)


def _serving_fixture(run_dir, ttft=(0.01, 0.02, 3.0)):
    from ray_lightning_tpu.telemetry.metrics import MetricsRegistry

    reg = MetricsRegistry(_tdir(run_dir), replica=0,
                          flush_every_n_ticks=1)
    for v in ttft:
        reg.observe("ttft_s", v)
    reg.gauge("queue_depth", 8.0)
    reg.gauge("decoding_slots", 2.0)
    reg.gauge("free_slots", 0.0)
    reg.tick_end()
    reg.close()


def test_surface_serving_quantile(tmp_path):
    run = str(tmp_path)
    _serving_fixture(run)
    s = MetricSurfaces(run)
    p99 = s.value("serving.ttft_p99_s")
    assert p99 == pytest.approx(3.0, rel=0.25)
    assert s.value("serving.ttft_p50_s") < p99
    ev = s.evidence("serving.ttft_p99_s")
    assert ev["n"] == 3 and ev["sketch"]
    assert s.value("serving.nosuch_p99_s") is None


def test_surface_load(tmp_path):
    run = str(tmp_path)
    _serving_fixture(run)
    s = MetricSurfaces(run)
    assert s.value("load.queue_depth_p50") == 8.0
    assert s.value("load.pressure") == pytest.approx(8.0 / 2.0)
    assert "load_signal" in s.evidence("load.pressure")


def test_surface_goodput(tmp_path):
    from ray_lightning_tpu.telemetry.goodput import write_goodput

    run = str(tmp_path)
    write_goodput(_tdir(run), {
        "wall_s": 10.0, "goodput_fraction": 0.4,
        "buckets": {"backoff_s": 2.0},
        "events": {"restarts": 2}})
    s = MetricSurfaces(run)
    assert s.value("goodput.goodput_fraction") == 0.4
    assert s.value("goodput.backoff_s") == 2.0
    assert s.value("goodput.restarts") == 2.0
    assert MetricSurfaces(str(tmp_path / "none")).value(
        "goodput.goodput_fraction") is None


def test_surface_guard_from_ckpt_meta(tmp_path):
    run = str(tmp_path)
    for step, streak in ((10, 1), (20, 4)):
        d = os.path.join(run, f"step{step}")
        os.makedirs(os.path.join(d, "state"))
        with open(os.path.join(d, "meta.json"), "w") as f:
            json.dump({"global_step": step, "blessed": streak < 3,
                       "guard": {"skipped_steps": streak,
                                 "streak": streak,
                                 "last_anomaly": step}}, f)
    s = MetricSurfaces(run)
    # the NEWEST checkpoint's counters win
    assert s.value("guard.streak") == 4.0
    assert s.value("guard.skipped_steps") == 4.0
    assert s.evidence("guard.streak")["guard"]["global_step"] == 20


def test_surface_restarts(tmp_path):
    run = str(tmp_path)
    os.makedirs(_tdir(run))
    for uid in ("100-0", "101-0", "102-0"):
        with open(os.path.join(_tdir(run),
                               f"ledger.rank0.{uid}.json"), "w") as f:
            json.dump({"version": "rlt-ledger-v1", "rank": 0}, f)
    with open(os.path.join(run, "flight.json"), "w") as f:
        json.dump({"version": "rlt-flight-v1",
                   "dumps": [{"replica": 0, "death": {}}]}, f)
    s = MetricSurfaces(run)
    # 3 attempts -> 2 restarts, + 1 serving replica death
    assert s.value("restarts.count") == 3.0
    assert s.value("restarts.replica_deaths") == 1.0


# -------------------------------------------- incidents + evidence hooks


def test_incident_fires_with_record_contract(tmp_path):
    run = str(tmp_path)
    _serving_fixture(run)   # p99 ~ 3s
    rule = next(r for r in BUILTIN_RULES if r.name == "ttft_p99")
    eng = WatchEngine(run, WatchConfig(rules=(rule,)))
    fired = eng.poll()
    assert [i["rule"] for i in fired] == ["ttft_p99"]
    assert eng.poll() == []   # episode stays open: no re-fire
    parsed = read_incidents(run)
    assert parsed["header"]["version"] == "rlt-incidents-v1"
    assert parsed["header"]["t0_wall"] > 0
    [inc] = parsed["incidents"]
    ev = inc["evidence"]
    assert ev["metric"] == "serving.ttft_p99_s"
    assert ev["value"] > rule.threshold and ev["sketch"]
    assert inc["severity"] == "page" and inc["window"]
    # the evidence hooks actuated: one profiler CAPTURE marker
    marker = inc["actions"]["profiler_marker"]
    assert os.path.exists(marker)
    assert os.path.basename(marker) == "CAPTURE"
    # timeline excerpt rides along (the metrics ticks at minimum)
    assert isinstance(inc["timeline_excerpt"], list)


def test_capture_marker_consumed_once(tmp_path):
    run = str(tmp_path)
    a1 = capture_evidence(run)
    assert os.path.exists(a1["profiler_marker"])
    a2 = capture_evidence(run)
    # an unconsumed marker from an earlier incident is left alone —
    # one marker = one profiler capture
    assert "profiler_marker" not in a2
    assert a2["profiler_marker_pending"] == a1["profiler_marker"]


def test_capture_forces_flight_persist(tmp_path):
    class _Drv:
        persisted = 0

        def force_flight_persist(self):
            self.persisted += 1
            return 2

    drv = _Drv()
    actions = capture_evidence(str(tmp_path), driver=drv)
    assert actions["flight_persisted"] == 2 and drv.persisted == 1

    class _Broken:
        def force_flight_persist(self):
            raise RuntimeError("dead")

    actions = capture_evidence(str(tmp_path), driver=_Broken())
    assert "flight_persist_error" in actions  # best-effort, no raise


def test_incident_ledger_append_and_garbage(tmp_path):
    run = str(tmp_path)
    append_incident(run, {"rule": "a", "severity": "warn", "wall": 1.0})
    append_incident(run, {"rule": "b", "severity": "page", "wall": 2.0})
    with open(os.path.join(run, "incidents.jsonl"), "a") as f:
        f.write("{torn")
    parsed = read_incidents(run)
    assert [i["rule"] for i in parsed["incidents"]] == ["a", "b"]
    assert parsed["unparseable_lines"] == 1


# ------------------------------------- driver / controller / supervisor


@pytest.fixture(scope="module")
def tiny_serve():
    from ray_lightning_tpu.serve.cli import _tiny_setup
    from ray_lightning_tpu.serve.engine import EngineConfig

    cfg, model, params, prompts, reqs = _tiny_setup(4, 6)
    ecfg = EngineConfig(capacity=2, block_size=4, blocks_per_slot=8,
                        prefill_chunk=4)
    return cfg, model, params, prompts, reqs, ecfg


def test_force_flight_persist_seam(tmp_path, tiny_serve):
    from ray_lightning_tpu.serve.driver import (
        ReplicaGroupConfig,
        ServeDriver,
    )
    from ray_lightning_tpu.telemetry.metrics import read_flight

    cfg, model, params, prompts, reqs, ecfg = tiny_serve
    run = str(tmp_path)
    drv = ServeDriver(cfg, params, ReplicaGroupConfig(
        n_replicas=1, engine=ecfg, run_dir=run,
        # a persist cadence far beyond this test: without the forced
        # persist the recorded events would NOT be on disk
        flight_persist_every=10_000,
        metrics_flush_every_n_ticks=2))
    drv.start()
    drv.submit(reqs[0])
    for _ in range(3):
        drv.tick()
    fpath = os.path.join(_tdir(run), "replica0.flight.json")
    before = read_flight(fpath)
    assert not before["events"]   # construction-time empty ring only
    persisted = drv.force_flight_persist()
    assert persisted == 2         # replica ring + driver ring
    after = read_flight(fpath)
    assert after["events"]        # the breach window's ticks landed
    drv.stop()


def test_controller_watch_wiring_fires_and_persists(tmp_path,
                                                    tiny_serve):
    """ControllerConfig(watch=...): the controller's poll cadence IS
    the watch cadence; a breach lands in <run_dir>/incidents.jsonl
    with the driver's forced flight persist in its actions."""
    from ray_lightning_tpu.autoscale import (
        AutoscaleController,
        ControllerConfig,
        PolicyConfig,
    )
    from ray_lightning_tpu.serve.driver import (
        ReplicaGroupConfig,
        ServeDriver,
    )

    cfg, model, params, prompts, reqs, ecfg = tiny_serve
    run = str(tmp_path)
    drv = ServeDriver(cfg, params, ReplicaGroupConfig(
        n_replicas=1, engine=ecfg, run_dir=run,
        metrics_flush_every_n_ticks=2))
    drv.start()
    # any completed request breaches a 0-second TTFT bound — the rule
    # exists to drive the wiring, not to be a sane SLO
    rule = WatchRule("ttft_p99", "serving.ttft_p99_s", ">", 0.0)
    ctl = AutoscaleController(drv, ControllerConfig(
        policy=PolicyConfig(min_replicas=1, max_replicas=1),
        watch=WatchConfig(rules=(rule,))), run_dir=run)
    assert ctl.watch is not None
    for req in reqs[:2]:
        drv.submit(req)
    tick = 0
    while drv.busy():
        drv.tick()
        tick += 1
        if tick % 2 == 0:
            ctl.step(now=float(tick))
    ctl.step(now=float(tick + 1))
    drv.stop()
    parsed = read_incidents(run)
    assert len(parsed["incidents"]) == 1   # episode: exactly one
    inc = parsed["incidents"][0]
    assert inc["rule"] == "ttft_p99"
    # the driver seam actuated: replica + driver rings persisted
    assert inc["actions"]["flight_persisted"] >= 2


def test_watch_off_program_pin(tmp_path, tiny_serve):
    """The acceptance pin: watch on vs off is a byte-identical lowered
    decode program and ONE compile — the watch layer reads files, it
    never touches the engine (same discipline as telemetry=off)."""
    from ray_lightning_tpu.serve.driver import (
        ReplicaGroupConfig,
        ServeDriver,
    )
    from ray_lightning_tpu.serve.engine import DecodeEngine

    cfg, model, params, prompts, reqs, ecfg = tiny_serve

    def lowered_text(engine):
        return engine.lower_idle().as_text()

    baseline = DecodeEngine(model, params, ecfg)
    run = str(tmp_path)
    drv = ServeDriver(cfg, params, ReplicaGroupConfig(
        n_replicas=1, engine=ecfg, run_dir=run,
        metrics_flush_every_n_ticks=2))
    drv.start()
    eng = WatchEngine(run, WatchConfig(rules=BUILTIN_RULES))
    drv.submit(reqs[0])
    tick = 0
    while drv.busy():
        drv.tick()
        tick += 1
        if tick % 2 == 0:
            eng.poll(driver=drv)
    eng.poll(driver=drv)
    watched_engine = drv.replicas[0].engine
    assert lowered_text(watched_engine) == lowered_text(baseline)
    assert watched_engine.compile_count == 1
    drv.stop()


def test_supervised_result_incidents_field():
    from ray_lightning_tpu.resilience.supervisor import (
        ResilienceConfig,
        SupervisedResult,
    )

    r = SupervisedResult(result=None, restarts=0, preemptions=0,
                         failures=[])
    assert r.incidents == []
    cfg = ResilienceConfig(checkpoint_dir="/tmp/x", watch=True)
    assert cfg.watch is True


@pytest.mark.slow
def test_supervisor_watch_arming(tmp_path):
    """End to end: a supervised run with an injected worker death and
    watch armed fires the restart rule (the surviving rank's attempt
    ledgers carry the count — the SIGKILLed rank writes none) and
    surfaces the incidents in SupervisedResult +
    <checkpoint_dir>/incidents.jsonl."""
    from ray_lightning_tpu.resilience.cli import (
        _smoke_data,
        _smoke_module,
        _smoke_trainer,
    )
    from ray_lightning_tpu.resilience.policy import RetryPolicy
    from ray_lightning_tpu.resilience.supervisor import (
        ResilienceConfig,
        fit_supervised,
    )

    base = str(tmp_path / "ckpts")
    rule = WatchRule("restart_rate", "restarts.count", ">=", 1,
                     severity="warn")
    cfg = ResilienceConfig(
        checkpoint_dir=base,
        policy=RetryPolicy(max_restarts=2, backoff_base_s=0.2,
                           jitter=0.0),
        save_every_n_steps=5,
        heartbeat_interval_s=1.0,
        stall_timeout_s=0.0,
        faults="kill:rank=0,step=3",
        watch=WatchConfig(rules=(rule,)))
    supervised = fit_supervised(
        _smoke_module, _smoke_trainer, _smoke_data, 2,
        resilience=cfg, platform="cpu",
        num_cpu_devices_per_process=1, return_weights=False,
        timeout=300)
    assert supervised.restarts >= 1
    assert [i["rule"] for i in supervised.incidents] == ["restart_rate"]
    parsed = read_incidents(base)
    assert len(parsed["incidents"]) == 1
    assert parsed["incidents"][0]["evidence"]["restarts"]["attempts"] >= 2


# --------------------------------------------------------- RLT503 lint


def _rlt503(src):
    from ray_lightning_tpu.analysis.linter import lint_source

    return [f for f in lint_source(src) if f.rule == "RLT503"]


def test_rlt503_fires_on_unbounded_follow_loop():
    fs = _rlt503("""
import time
from ray_lightning_tpu.telemetry.spans import read_spans

def follow(path):
    while True:
        data = read_spans(path)
        time.sleep(5)
""")
    assert len(fs) == 1 and "tail" in fs[0].message


def test_rlt503_propagates_through_helpers():
    fs = _rlt503("""
import time
from ray_lightning_tpu.telemetry.metrics import read_metrics

def _view(path):
    return read_metrics(path)

def follow(path):
    while True:
        _view(path)
        time.sleep(5)
""")
    assert len(fs) == 1


def test_rlt503_propagates_through_methods():
    fs = _rlt503("""
import time

class Controller:
    def _signal(self):
        from ray_lightning_tpu.serve.driver import load_signal
        return load_signal(self.run_dir)

    def step(self):
        return self._signal()

    def run_wall(self):
        while True:
            self.step()
            time.sleep(5)
""")
    assert len(fs) == 1


def test_rlt503_sanctions():
    # a threaded bound sanctions — the caller owns the window
    assert not _rlt503("""
import time
from ray_lightning_tpu.telemetry.spans import read_spans

def follow(path, tail):
    while True:
        data = read_spans(path, tail_bytes=tail)
        time.sleep(5)
""")
    # window= counts as a bound (load_signal derives its tail from it)
    assert not _rlt503("""
import time
from ray_lightning_tpu.serve.driver import load_signal

def follow(run):
    while True:
        sig = load_signal(run, window=16)
        time.sleep(5)
""")
    # not cadence-polled: one-shot reads stay free to read everything
    assert not _rlt503("""
from ray_lightning_tpu.telemetry.spans import read_spans

def report(path):
    return read_spans(path)
""")
    # a loop WITHOUT a sleep is a drain loop, not a poll
    assert not _rlt503("""
from ray_lightning_tpu.telemetry.spans import read_spans

def drain(paths):
    for p in paths:
        read_spans(p)
""")
    # an explicit tail_bytes=None is NOT a bound
    assert len(_rlt503("""
import time
from ray_lightning_tpu.telemetry.spans import read_spans

def follow(path):
    while True:
        read_spans(path, tail_bytes=None)
        time.sleep(5)
""")) == 1


def test_rlt503_suppression():
    assert not _rlt503("""
import time
from ray_lightning_tpu.telemetry.spans import read_spans

def follow(path):
    while True:
        data = read_spans(path)  # rlt: disable=RLT503
        time.sleep(5)
""")


def test_repo_lints_clean_of_rlt503():
    import ray_lightning_tpu
    from ray_lightning_tpu.analysis.linter import lint_paths

    root = os.path.dirname(ray_lightning_tpu.__file__)
    findings = [f for f in lint_paths([root])
                if f.rule == "RLT503"]
    assert findings == []


# ------------------------------------------------- bench / gate surfaces


def test_bench_watch_schema_on_every_line():
    import bench

    summary = bench._watch_summary()
    assert "incidents" in summary["watch"]["schema"]
    assert "ttft_p99" in summary["watch"]["rules"]
    assert summary["watch"]["source"] == "static-schema"


def test_bench_gate_incidents_bound():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_gate", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "scripts", "bench_gate.py"))
    bg = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bg)

    line = {"metric": "m", "value": 1.0}
    assert bg.gate({**line, "incidents": 0}, {}, 0.05) == []
    fails = bg.gate({**line, "incidents": 1}, {}, 0.05)
    assert fails and "incidents" in fails[0]
    # skip lines + absent/null counts waive
    assert bg.gate({**line, "skipped": "backend unavailable",
                    "incidents": 3}, {}, 0.05) == []
    assert bg.gate({**line, "incidents": None}, {}, 0.05) == []
    assert bg.gate(line, {}, 0.05) == []


def test_watch_cli_one_shot(tmp_path, capsys):
    from ray_lightning_tpu.__main__ import main

    run = str(tmp_path)
    _serving_fixture(run)
    assert main(["watch", run, "--ttft-max", "0.001"]) == 0
    out = capsys.readouterr().out
    assert "ttft_p99" in out and "1 new incident" in out
    assert read_incidents(run)["incidents"]
    assert main(["watch", str(tmp_path / "missing")]) == 2

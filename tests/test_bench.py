"""bench.py contract tests: structured failure JSON.

The contract under test: EVERY failure mode — hang (watchdog), SIGTERM,
an exception anywhere in the run (a backend that does not come up, a
compile failure, OOM) — surfaces as ONE parseable JSON line with an
"error" field (exit 3), never a bare traceback. Nothing is retried and
no leg measures a backend that is not a TPU.
"""
import json

import pytest

import bench


class _FakeChip:
    device_kind = "TPU v5 lite"


def test_mid_run_exception_emits_structured_error(monkeypatch, capsys):
    """An exception AFTER backend init (compile failure, OOM) takes the
    same structured path — not only init errors."""
    monkeypatch.setattr(bench, "_device", _FakeChip)
    monkeypatch.setattr(bench, "_probe_matmul_tflops",
                        lambda: (_ for _ in ()).throw(
                            MemoryError("RESOURCE_EXHAUSTED: hbm")))
    monkeypatch.setenv("RLT_BENCH_WATCHDOG_S", "0")
    # the static summaries ride every line; what they carry is
    # test_error_line_carries_serving_schema's business, not this one's
    for name in ("_concurrency_summary", "_trace_summary",
                 "_numerics_summary", "_multislice_summary",
                 "_guard_summary", "_telemetry_summary", "_serve_summary",
                 "_watch_summary"):
        monkeypatch.setattr(bench, name, dict)
    with pytest.raises(SystemExit) as exc_info:
        bench.main()
    assert exc_info.value.code == 3
    obj = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "RESOURCE_EXHAUSTED" in obj["error"]


def test_verify_kernels_reports_per_check(monkeypatch):
    """`kernels_verified` is the parity module's errors against its one
    tolerance, reported per check. (The parity run itself, in CPU
    interpret mode, is tests/test_chip_bringup.py's rehearsal.)"""
    from ray_lightning_tpu.ops import parity

    monkeypatch.setattr(parity, "kernel_parity_errors",
                        lambda: {"flash_fwd": 1e-3, "paged_decode": 5e-3})
    out = bench._verify_kernels()
    assert out == {"kernels_verified": True,
                   "kernel_errors": {"flash_fwd": 1e-3,
                                     "paged_decode": 5e-3}}
    monkeypatch.setattr(parity, "kernel_parity_errors",
                        lambda: {"flash_fwd": 1e-3, "paged_decode": 0.5})
    assert bench._verify_kernels()["kernels_verified"] is False


def test_secondary_leg_failure_degrades_not_fatal(monkeypatch):
    """One OOMing secondary leg must cost only its own fields
    (<leg>_error), never the headline or the other legs — the round-4
    lesson applied at leg granularity."""

    def fake_measure(use_flash, fused_ce, batch, seq, vocab=32768,
                     remat=True, scan=True, remat_policy="nothing",
                     ce_chunk_tokens=2048, ce_inline=False,
                     timing=None):
        if vocab == 128256 and not remat:
            raise MemoryError("RESOURCE_EXHAUSTED: hbm")  # the v128k leg
        cfg = bench._bench_cfg(use_flash, fused_ce, seq, vocab, remat,
                               scan, remat_policy, ce_chunk_tokens,
                               ce_inline)
        if timing is not None:
            timing.update({"wall_s": 1.2, "productive_s": 1.0,
                           "step_dt_s": 0.01})
        return 1000.0, cfg

    monkeypatch.setattr(bench, "_measure", fake_measure)
    monkeypatch.setattr(bench, "_verify_kernels",
                        lambda: {"kernels_verified": True,
                                 "kernel_errors": {}})
    monkeypatch.setattr(bench, "_probe_matmul_tflops", lambda: 1e6)
    monkeypatch.setattr(bench, "_device", _FakeChip)
    out = bench._run()
    assert out["value"] > 0  # headline intact
    assert "RESOURCE_EXHAUSTED" in out["v128k_error"]
    assert "v128k_mfu" not in out
    assert out["vs_baseline"] == 1.0  # baseline leg intact
    assert "flagship_mfu" in out and "flagship_rematce_mfu" in out
    assert out["probe_consistent"] is True


def test_kernel_verify_crash_degrades_not_fatal(monkeypatch):
    """A CRASHING kernel gate (raises, not just wrong numbers) reports
    kernels_verified=False + kernel_verify_error; throughput legs that
    don't use the kernel still land in the artifact."""

    def fake_measure(*a, **k):
        return 1000.0, bench._bench_cfg(True, False, 2048)

    monkeypatch.setattr(bench, "_measure", fake_measure)
    monkeypatch.setattr(
        bench, "_verify_kernels",
        lambda: (_ for _ in ()).throw(RuntimeError("pallas crashed")))
    monkeypatch.setattr(bench, "_probe_matmul_tflops", lambda: 1e6)
    monkeypatch.setattr(bench, "_device", _FakeChip)
    out = bench._run()
    assert out["value"] > 0
    assert out["kernels_verified"] is False
    assert "pallas crashed" in out["kernel_verify_error"]


@pytest.mark.slow  # sleeps by design: must outwait the watchdog window
def test_watchdog_fires_on_hang():
    """A hang anywhere in the run (a device that stops answering: every
    op blocks forever) must yield the structured error JSON and exit 3
    within the watchdog window — the documented contract for the hang
    mode."""
    import os
    import subprocess
    import sys

    code = (
        "import bench, time\n"
        "bench._device = lambda: time.sleep(60)\n"
        "bench.main()\n"
    )
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=30,
        env={**os.environ, "RLT_BENCH_WATCHDOG_S": "3"},
        cwd=repo_root,
    )
    assert p.returncode == 3, (p.returncode, p.stderr[-500:])
    obj = json.loads(p.stdout.strip().splitlines()[-1])
    assert "did not complete" in obj["error"]
    assert obj["value"] == 0.0


def test_flagship_leg_inline_fallback_reuses_rematce():
    """The flagship leg's documented degradation ladder: inline compile
    rejected -> reuse the rematce measurement (same config, no second
    compile) with the failure cause preserved; nothing to reuse ->
    re-raise so the row degrades with the REAL error."""
    class Cfg:  # _flops_per_token stand-in not needed: mfu_of is injected
        pass

    calls = []

    def ok_measure(ce_inline):
        calls.append(ce_inline)
        return 1000.0, Cfg()

    row, m = bench._flagship_leg(ok_measure, {"rematce": (900.0, 0.4)},
                                 lambda t, c: 0.5, "B=8 test-shape")
    assert row["flagship_tokens_per_sec"] == 1000.0
    assert m == 0.5
    assert "B=8 test-shape" in row["flagship_config"]
    assert "inline" in row["flagship_config"]
    assert "flagship_inline_error" not in row
    assert calls == [True]  # the rematce measurement was NOT re-run

    def failing_measure(ce_inline):
        raise RuntimeError("Mosaic failed to compile")

    row, m = bench._flagship_leg(failing_measure, {"rematce": (900.0, 0.4)},
                                 lambda t, c: 0.5, "B=8 test-shape")
    assert row["flagship_tokens_per_sec"] == 900.0
    assert m == 0.4
    assert "fallback" in row["flagship_config"]
    assert "Mosaic" in row["flagship_inline_error"]

    with pytest.raises(RuntimeError, match="Mosaic"):
        bench._flagship_leg(failing_measure, {}, lambda t, c: 0.5,
                            "B=8 test-shape")


def test_trace_summary_is_parseable():
    """The tracecheck summary is computed WITHOUT any backend touch and
    carries ICI bytes + an HBM estimate against an assumed chip."""
    s = bench._trace_summary()
    assert "tracecheck" in s, s.get("tracecheck_error")
    t = s["tracecheck"]
    assert t["ici_bytes_per_step"] == 0  # one chip: nothing on the wire
    assert t["est_peak_hbm_bytes"] > 0
    assert t["hbm_budget_bytes"] > 0
    assert t["assumed_device_kind"] == "TPU v5e"
    json.dumps(s)  # must embed into the JSON line as-is


def test_kill_line_schema(monkeypatch):
    """The line a driver kill flushes: same schema as the watchdog line —
    metric/value/vs_baseline present, a 'skipped' field naming the
    signal, and the tracecheck summary riding along."""
    monkeypatch.setitem(bench._ANALYSIS, "tracecheck", {"findings": 0})
    obj = json.loads(bench._kill_line("SIGTERM"))
    assert obj["metric"] == "llama_0.5b_train_tokens_per_sec_per_chip"
    assert obj["value"] == 0.0 and obj["vs_baseline"] == 0.0
    assert obj["skipped"] == "killed: SIGTERM"
    assert "SIGTERM" in obj["error"]
    assert obj["tracecheck"] == {"findings": 0}


def test_sigterm_flushes_structured_json():
    """End to end: a driver SIGTERM mid-run produces ONE parseable JSON
    line (exit 3), never silent death."""
    import os
    import signal
    import subprocess
    import sys
    import time

    code = (
        "import bench, time, sys\n"
        "bench._install_kill_handlers()\n"
        "print('READY', flush=True)\n"
        "time.sleep(60)\n"
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": repo + os.pathsep + os.environ.get(
               "PYTHONPATH", "")}
    proc = subprocess.Popen([sys.executable, "-c", code],
                            stdout=subprocess.PIPE, text=True, env=env,
                            cwd=repo)
    try:
        assert proc.stdout.readline().strip() == "READY"
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == 3
    obj = json.loads(out.strip().splitlines()[-1])
    assert obj["skipped"] == "killed: SIGTERM"
    assert obj["value"] == 0.0


def test_attnout_leg_fallback_and_double_failure_chaining():
    """ADVICE r5: the attn_out leg falls back to the non-inline config
    with the inline cause preserved; when the fallback ALSO fails, both
    causes must survive — folded into the raised message, inline chained
    as __cause__ — instead of the inline root cause being discarded."""
    class Cfg:
        pass

    def inline_only_fails(ce_inline):
        if ce_inline:
            raise RuntimeError("inline compile rejected")
        return 800.0, Cfg()

    row, m = bench._attnout_leg(inline_only_fails, lambda t, c: 0.3)
    assert row["flagship_attnout_tokens_per_sec"] == 800.0
    assert m == 0.3
    assert "inline compile rejected" in row["flagship_attnout_inline_error"]

    def both_fail(ce_inline):
        if ce_inline:
            raise RuntimeError("inline compile rejected")
        raise MemoryError("fallback OOM")

    with pytest.raises(RuntimeError) as ei:
        bench._attnout_leg(both_fail, lambda t, c: 0.3)
    msg = str(ei.value)
    assert "inline compile rejected" in msg  # first cause kept
    assert "fallback OOM" in msg             # second cause kept
    assert isinstance(ei.value.__cause__, RuntimeError)
    assert "inline compile rejected" in str(ei.value.__cause__)

    def ok(ce_inline):
        return 1200.0, Cfg()

    row, m = bench._attnout_leg(ok, lambda t, c: 0.6)
    assert row["flagship_attnout_tokens_per_sec"] == 1200.0
    assert "flagship_attnout_inline_error" not in row


def test_error_line_carries_serving_schema(monkeypatch, capsys):
    """ISSUE 8: every bench JSON line — including the one a dead backend
    ends in — carries the serving section (schema + the flagship serve
    plan), and a backend that does not come up is exit 3 with the cause
    named, not a skip."""

    def dead():
        raise RuntimeError("UNAVAILABLE: no TPU answered")

    monkeypatch.setattr(bench, "_device", dead)
    monkeypatch.setenv("RLT_BENCH_WATCHDOG_S", "0")
    with pytest.raises(SystemExit) as exc_info:
        bench.main()
    assert exc_info.value.code == 3
    obj = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "UNAVAILABLE" in obj["error"]
    assert "skipped" not in obj
    assert obj["value"] == 0.0
    serving = obj.get("serving")
    assert serving is not None, obj.get("serving_error")
    assert set(serving["schema"]) == {
        "decode_tokens_per_s", "prefill_tokens_per_s",
        "ttft_cold_s", "ttft_warm_s", "ttft_p99_s", "slot_occupancy",
        "serving_attention_path", "serving_prefill_path",
        "serve_metrics", "scale_up_s", "autoscale",
        "shared_block_fraction", "accepted_tokens_per_step",
        "slo_attainment", "slo_attainment_latency_critical",
        "shed_fraction"}
    assert "scale_up_s" in serving["autoscale_schema"]  # ISSUE 13
    assert serving["flagship_plan"]["pool_bytes"] > 0
    # measured serving values belong to success lines only
    assert "decode_tokens_per_s" not in obj


def test_measured_legs_refuse_a_cpu():
    """A measured leg without a TPU raises; it does not shrink to a
    tiny CPU model and report its timings under device-metric names."""
    with pytest.raises(RuntimeError, match="measures a TPU"):
        bench._measure_serving()

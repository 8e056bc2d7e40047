"""Harness tests for scripts/sweep_flagship.py — the on-chip tuning
sweep's record/carry logic, smoke-run on the CPU backend with a tiny
shape (RLT_SWEEP_RESULTS redirects the record so the real chip JSONL is
never polluted; the reference's analog is examples-as-smoke-tests,
reference .github/workflows/test.yaml:70-77)."""
import json

import pytest

from scripts.sweep_flagship import best_so_far, run_one


@pytest.fixture
def results_path(tmp_path, monkeypatch):
    p = tmp_path / "sweep.jsonl"
    monkeypatch.setenv("RLT_SWEEP_RESULTS", str(p))
    # the module captured RESULTS at import — repoint it for the test
    import scripts.sweep_flagship as sf

    monkeypatch.setattr(sf, "RESULTS", str(p))
    return p


@pytest.mark.slow  # real (tiny) compile + timed steps; tooling, not library
def test_run_one_records_success_and_flags(results_path, monkeypatch):
    # shrink the model (the real _bench_cfg hardcodes the 0.5B bench
    # dims — minutes of CPU compile); run_one's own measurement path,
    # flags included, still runs end-to-end
    import bench
    from ray_lightning_tpu.models.llama import LlamaConfig

    def tiny_cfg(use_flash, fused_ce, seq, vocab=64, remat=True,
                 scan=True, remat_policy="nothing", ce_chunk_tokens=16,
                 ce_inline=False):
        return LlamaConfig(
            vocab_size=vocab, dim=32, n_layers=2, n_heads=2, n_kv_heads=1,
            hidden_dim=64, max_seq_len=seq, use_flash=False,
            fused_ce=fused_ce, ce_chunk_tokens=ce_chunk_tokens,
            ce_inline_bwd=ce_inline, remat=remat,
            remat_policy=remat_policy, scan_layers=scan)

    monkeypatch.setattr(bench, "_bench_cfg", tiny_cfg)
    # the CPU has no spec-sheet peak and none is assumed: name the kind
    # whose peak this smoke prices its (meaningless) MFU against
    from ray_lightning_tpu.utils.probe import device_peak_tflops

    monkeypatch.setattr(bench, "_device_peak_tflops",
                        lambda kind: device_peak_tflops("TPU v5 lite"))
    rec = run_one("smoke-tiny", batch=2, policy="attn_out", chunk=16,
                  vocab=64, seq=32, inline=True, mu_bf16=True)
    assert rec["tokens_per_sec"] > 0
    assert rec["mu_bf16"] is True and rec["inline"] is True
    on_disk = [json.loads(x) for x in results_path.read_text().splitlines()]
    assert on_disk[-1]["tag"] == "smoke-tiny"
    assert on_disk[-1]["tokens_per_sec"] == rec["tokens_per_sec"]


def test_run_one_records_failure_as_data(results_path, monkeypatch):
    import bench

    def boom(**kw):
        raise RuntimeError("RESOURCE_EXHAUSTED: hbm")

    monkeypatch.setattr(bench, "_make_step", boom)
    rec = run_one("smoke-fail", batch=2, policy="nothing", chunk=16,
                  vocab=64, seq=32)
    assert "RESOURCE_EXHAUSTED" in rec["error"]
    assert "tokens_per_sec" not in rec
    # a failed point must not become the incumbent
    assert best_so_far() is None


def test_best_so_far_keeps_full_config(results_path):
    for tag, tps, extra in (
            ("a", 100.0, {"inline": False, "mu_bf16": False}),
            ("b", 200.0, {"inline": True, "mu_bf16": True}),
            ("c", 150.0, {"inline": False, "mu_bf16": False})):
        with open(results_path, "a") as f:
            f.write(json.dumps({"tag": tag, "batch": 4, "policy": "nothing",
                                "chunk": 16, "tokens_per_sec": tps,
                                **extra}) + "\n")
    best = best_so_far()
    # the incumbent's fit-critical flags survive for later phases'
    # _carry (a best that only fits with bf16 mu must not be re-run
    # without it)
    assert best["tag"] == "b"
    assert best["inline"] is True and best["mu_bf16"] is True


def test_phase4_carries_incumbent_mu_bf16(results_path, monkeypatch):
    """ADVICE r5: a standalone phase-4 re-run after phase 6/7 records
    exist must carry the incumbent's mu_bf16 (minus the forced
    inline=True) — a batch that only fits with a bf16 mu must not be
    silently re-run without it and recorded as a spurious OOM."""
    import sys

    import scripts.sweep_flagship as sf

    # seed the record with a phase-6-style incumbent: bf16 mu, inline
    with open(results_path, "a") as f:
        f.write(json.dumps({
            "tag": "p6-mubf16-b12-inline", "batch": 12,
            "policy": "nothing", "chunk": 4096,
            "inline": True, "mu_bf16": True,
            "tokens_per_sec": 999.0}) + "\n")

    calls = []

    def record_run_one(tag, **kw):
        calls.append({"tag": tag, **kw})
        return {"tag": tag, **kw}  # no tokens_per_sec: chunk sweep skipped

    monkeypatch.setattr(sf, "run_one", record_run_one)
    monkeypatch.setattr(sys, "argv", ["sweep_flagship.py", "4"])
    sf.main()
    p4 = [c for c in calls if c["tag"].startswith("p4-")]
    assert p4, calls
    assert all(c["inline"] is True for c in p4)
    assert all(c["mu_bf16"] is True for c in p4)

"""tracecheck over every bundled example's train step, on CPU (tier-1).

The ISSUE-2 acceptance bar: all six examples' steps audit with zero
RESHARD-IMPLICIT (RLT301) and zero RING-DEADLOCK (RLT303) findings, and
the Llama-8B FSDP example reports a sane peak-HBM estimate on v5p-64 —
positive, within the chip budget, and dominated by more than just the
weights (liveness, not arithmetic on params alone)."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from ray_lightning_tpu.analysis.cli import (
    _TRACE_BUILDERS, resolve_trace_target,
)
from ray_lightning_tpu.analysis.costmodel import parse_topology
from ray_lightning_tpu.analysis.tracecheck import audit_step

EXAMPLES = sorted(set(_TRACE_BUILDERS) - {"llama3-8b"})

#: subprocess CLI invocations must be hermetic: the autouse fixture
#: chdirs every test into a tmp dir, so the repo root (package import +
#: repo-relative example paths) is pinned explicitly rather than
#: inherited from whatever cwd/PYTHONPATH the runner happened to have.
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CLI_ENV = {**os.environ, "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": _REPO + os.pathsep + os.environ.get(
                "PYTHONPATH", "")}

#: the flagship example audits at its BASELINE.json topology; the
#: data-parallel examples at a small pod slice
_TOPO = {"llama_fsdp_example.py": "v5p-64"}


@pytest.mark.parametrize("example", EXAMPLES)
def test_example_step_audits_clean(example):
    topo = parse_topology(_TOPO.get(example, "v5p-8"))
    module, strategy, batch, label = resolve_trace_target(example, topo)
    report = audit_step(module, strategy, batch, topology=topo,
                        label=label)
    bad = [f for f in report.findings if f.rule in ("RLT301", "RLT303")]
    assert not bad, "\n".join(f.format() for f in bad)
    assert report.unentered == [] and report.lost_specs == {}


def test_llama_fsdp_v5p64_hbm_estimate_sane():
    topo = parse_topology("v5p-64")
    module, strategy, batch, label = resolve_trace_target(
        "llama_fsdp_example.py", topo)
    report = audit_step(module, strategy, batch, topology=topo,
                        label=label)
    gib = 1024**3
    # weights alone: ~0.5 GiB params + ~0.9 GiB opt per device; the
    # estimate must include live intermediates on top, and fit the chip
    floor = (report.params_bytes_per_device
             + report.opt_bytes_per_device)
    assert floor > 1 * gib
    assert report.peak_hbm_bytes > floor
    assert report.peak_hbm_bytes <= report.hbm_budget_bytes, \
        report.summary()
    assert report.fits
    # the ZeRO schedule is present: weight all-gathers AND gradient
    # reduce-scatters over fsdp, with real traffic behind them
    kinds = {e.kind for e in report.collectives}
    assert {"all_gather", "reduce_scatter"} <= kinds
    assert all(e.axes == ("fsdp",) for e in report.collectives)
    assert report.ici_bytes_per_step > 10 * gib


def test_trace_cli_json_llama(tmp_path):
    out = subprocess.run(
        [sys.executable, "-m", "ray_lightning_tpu", "trace",
         "examples/llama_fsdp_example.py", "--topo", "v5p-64", "--json"],
        capture_output=True, text=True, timeout=300, cwd=_REPO,
        env=_CLI_ENV,
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    d = json.loads(out.stdout.strip().splitlines()[-1])
    assert d["ok"] is True
    assert d["topology"]["name"] == "v5p-64"
    assert d["ici_bytes_per_step"] > 0
    assert d["peak_hbm_bytes"] > 0
    assert d["fits"] is True
    # a clean verdict is not a blind one: nothing unentered, no spec
    # lost at a primitive the walk has no rule for
    assert d["unentered"] == [] and d["lost_specs"] == {}
    assert d["findings"] == [], d["findings"]


def test_trace_cli_unknown_target_exits_2():
    out = subprocess.run(
        [sys.executable, "-m", "ray_lightning_tpu", "trace",
         "no_such_example.py", "--json"],
        capture_output=True, text=True, timeout=120, cwd=_REPO,
        env=_CLI_ENV,
    )
    assert out.returncode == 2
    assert "error" in json.loads(out.stdout.strip().splitlines()[-1])

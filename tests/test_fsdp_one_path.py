"""FSDP / ShardedMesh have one layer schedule: the partitioner's, with the
block's activation pins (`tests/test_activation_pins.py`).

PR 47 removed the hand-scheduled double-buffered layer stack, the strategy
argument that selected it and the static model that graded it: on the chip
it was 8.8% slower than the pinned default (PERF.md section 6, PR 42). What
is held here is what that removal must not have moved: the sharded step
computes what one device computes, it composes with TrainGuard and donated
state, nothing accepts the old option, and tracecheck's accounting of the
FSDP Llama (collectives, bytes, modelled times, peak HBM) reads what it read
at the parent commit, whose numbers are written out below.
"""
import functools
import io
import json
from contextlib import redirect_stderr, redirect_stdout

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu import (
    FSDP, DataLoader, DataParallel, ShardedMesh, SingleDevice, Trainer,
)
from ray_lightning_tpu.__main__ import main
from ray_lightning_tpu.analysis.jaxpr import walk_eqns
from ray_lightning_tpu.models.llama import LlamaConfig, LlamaModule


def _tiny(**kw):
    return LlamaConfig.tiny(use_flash=False, **kw)


def _tokens(cfg, n, seq=32, seed=0):
    rng = np.random.default_rng(seed)
    return {"tokens": rng.integers(
        0, cfg.vocab_size, (n, seq + 1)).astype(np.int32)}


# ---- the sharded step computes what one device computes ---------------------

def _loss_and_grads(strategy, cfg, batch):
    module = LlamaModule(cfg)
    strategy.setup(module)
    module.setup()
    params = strategy.shard_params(
        module.init_params(jax.random.PRNGKey(0), batch))
    tokens = strategy.shard_batch(batch)["tokens"]
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p, t: module._loss(p, t[:, :-1], t[:, 1:], None)))(
            params, tokens)
    return float(loss), jax.device_get(grads)


_MESHES = {
    "fsdp8": lambda: FSDP(num_workers=8),
    "data2xfsdp4": lambda: ShardedMesh(data=2, fsdp=4),
    "fsdp4xtensor2": lambda: ShardedMesh(fsdp=4, tensor=2),
}


@pytest.mark.parametrize("scan_layers", [True, False],
                         ids=["scan", "unrolled"])
@pytest.mark.parametrize("mesh", list(_MESHES))
def test_sharded_loss_and_gradients_match_one_device(mesh, scan_layers):
    """To a tolerance, never bitwise: two partitionings of one program
    order their float32 sums differently (1e-7 here)."""
    cfg = _tiny(n_layers=4, dtype=jnp.float32, scan_layers=scan_layers)
    batch = _tokens(cfg, 8)
    ref_loss, ref_grads = _loss_and_grads(
        SingleDevice(devices=jax.devices()[:1]), cfg, batch)
    loss, grads = _loss_and_grads(_MESHES[mesh](), cfg, batch)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    ref_leaves = jax.tree_util.tree_leaves_with_path(ref_grads)
    leaves = jax.tree_util.tree_leaves_with_path(grads)
    assert [p for p, _ in leaves] == [p for p, _ in ref_leaves]
    for (path, got), (_, want) in zip(leaves, ref_leaves):
        want = np.asarray(want)
        assert np.abs(want).max() > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            np.asarray(got), want, rtol=1e-5,
            atol=1e-5 * np.abs(want).max(),
            err_msg=jax.tree_util.keystr(path))


def test_fsdp_fit_composes_with_trainguard_and_donation():
    """The guarded step (its state donated) takes every step of an FSDP
    fit, and the guard's counters say that none was discarded."""
    cfg = _tiny()
    module = LlamaModule(cfg, lr=1e-3, warmup_steps=1, total_steps=50)
    trainer = Trainer(
        strategy=ShardedMesh(fsdp=4, data=2), max_epochs=1,
        enable_progress_bar=False, enable_checkpointing=False, seed=0,
        guard=True)
    trainer.fit(module, DataLoader(_tokens(cfg, 64), batch_size=16))
    assert trainer.global_step == 4
    assert int(trainer.state.step) == 4
    metrics = trainer.callback_metrics
    assert np.isfinite(float(metrics["train_loss"]))
    assert int(metrics["guard_anomaly"]) == 0
    assert int(metrics["guard_skipped_steps"]) == 0
    assert int(metrics["guard_streak"]) == 0
    assert np.isfinite(float(metrics["guard_loss_ema"]))


# ---- nothing accepts the old option -----------------------------------------

@pytest.mark.parametrize("strategy", [FSDP, ShardedMesh, DataParallel,
                                      SingleDevice])
def test_strategies_refuse_the_overlap_argument(strategy):
    with pytest.raises(TypeError, match="overlap"):
        strategy(overlap="on")


def _bound_module(cfg):
    module = LlamaModule(cfg)
    strategy = ShardedMesh(fsdp=4, data=2)
    strategy.setup(module)
    module.setup()
    return module, strategy


def test_bound_module_has_no_overlap_attribute():
    module, strategy = _bound_module(_tiny())
    assert not hasattr(module, "overlap")
    assert not hasattr(strategy, "overlap")
    strategy.bind_module(LlamaModule(_tiny()))
    assert not hasattr(strategy._module, "overlap")


def test_bound_step_holds_no_barrier_and_no_schedule_marker():
    """The removed schedule ordered its gathers with `optimization_barrier`
    and marked them with `name` equations. The block has no barrier (the
    fused cross-entropy's is outside it, and off at this size), and the
    only values it names are the ones its remat policies save."""
    cfg = _tiny()
    module, _ = _bound_module(cfg)
    tokens = jnp.zeros((8, 32), jnp.int32)
    params = jax.eval_shape(module.init_params, jax.random.key(0),
                            {"tokens": tokens})
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: module._loss(p, tokens, tokens, None)))(params)
    eqns = [eqn for eqn, _ in walk_eqns(jaxpr.jaxpr)]
    names = {eqn.primitive.name for eqn in eqns}
    assert {"scan", "sharding_constraint"} <= names
    assert "optimization_barrier" not in names
    assert {eqn.params["name"] for eqn in eqns
            if eqn.primitive.name == "name"} <= {"attn_out",
                                                  "flash_residuals"}


def _cli(*argv):
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        try:
            rc = main(list(argv))
        except SystemExit as exc:       # argparse refusing an argument
            rc = exc.code
    return rc, out.getvalue(), err.getvalue()


@pytest.mark.parametrize("argv", [
    ("plan", "--preset", "tiny", "--fsdp", "4", "--no-trace",
     "--overlap", "on"),
    ("trace", "llama3-8b", "--topo", "v5e-8", "--overlap", "on"),
    ("report", ".", "--overlap", "on"),
    ("perf", "--smoke", "--overlap-layers", "4"),
    ("perf", "--smoke", "--overlap-comm-ms", "5"),
    ("perf", "--smoke", "--no-overlap-leg"),
], ids=["plan --overlap", "trace --overlap", "report --overlap",
        "perf --overlap-layers", "perf --overlap-comm-ms",
        "perf --no-overlap-leg"])
def test_cli_refuses_a_removed_flag(argv):
    rc, _, err = _cli(*argv)
    assert rc == 2
    assert "unrecognized arguments" in err


# ---- tracecheck's accounting did not move -----------------------------------

#: `trace examples/llama_fsdp_example.py --topo <key> --json` at the parent
#: commit (09d129d): the collectives in the report's order (by wire bytes),
#: each (kind, payload_bytes, count, wire_bytes, dcn_bytes, time_us). The
#: target is the tiny Llama on 8 chips and Llama-3-8B from 64 up.
_PARENT = {
    "v5e-8": dict(
        label="llama-tiny FSDP(8)", ici_bytes_per_step=690375,
        dcn_bytes_per_step=0, ici_time_us=283.5,
        peak_hbm_bytes=150837268, collectives=[
            ("reduce_scatter", 32768, 4, 114688, 0, 28.6),
            ("reduce_scatter", 65536, 2, 114688, 0, 14.6),
            ("all_gather", 16384, 4, 57344, 0, 28.3),
            ("all_gather", 32768, 2, 57344, 0, 14.3),
            ("all_gather", 32768, 2, 57344, 0, 14.3),
            ("reduce_scatter", 65536, 1, 57344, 0, 7.3),
            ("all_gather", 16384, 4, 57344, 0, 28.3),
            ("all_gather", 32768, 2, 57344, 0, 14.3),
            ("reduce_scatter", 65536, 1, 57344, 0, 7.3),
            ("reduce_scatter", 16384, 2, 28672, 0, 14.1),
            ("all_gather", 8192, 2, 14336, 0, 14.1),
            ("all_gather", 8192, 2, 14336, 0, 14.1),
            ("psum", 256, 4, 1792, 0, 56.0),
            ("psum", 256, 1, 448, 0, 14.0),
            ("psum", 4, 1, 7, 0, 14.0),
        ]),
    "v5p-64": dict(
        label="llama3-8b FSDP(64)", ici_bytes_per_step=70175653632,
        dcn_bytes_per_step=0, ici_time_us=151546.4,
        peak_hbm_bytes=23341286164, collectives=[
            ("all_gather", 234881024, 64, 14797504512, 0, 28694.5),
            ("reduce_scatter", 469762048, 32, 14797504512, 0, 26678.5),
            ("all_gather", 234881024, 32, 7398752256, 0, 14347.3),
            ("reduce_scatter", 234881024, 32, 7398752256, 0, 14347.3),
            ("all_gather", 117440512, 32, 3699376128, 0, 8181.6),
            ("all_gather", 117440512, 32, 3699376128, 0, 8181.6),
            ("all_gather", 50331648, 64, 3170893824, 0, 9316.8),
            ("reduce_scatter", 100663296, 32, 3170893824, 0, 7300.8),
            ("all_gather", 33554432, 64, 2113929216, 0, 7555.2),
            ("reduce_scatter", 67108864, 32, 2113929216, 0, 5539.2),
            ("all_gather", 1050673152, 2, 2068512768, 0, 3573.5),
            ("reduce_scatter", 2101346304, 1, 2068512768, 0, 3510.5),
            ("all_gather", 50331648, 32, 1585446912, 0, 4658.4),
            ("all_gather", 33554432, 32, 1056964608, 0, 3777.6),
            ("all_gather", 1050673152, 1, 1034256384, 0, 1786.8),
            ("reduce_scatter", 16384, 64, 1032192, 0, 4033.7),
            ("reduce_scatter", 16384, 1, 16128, 0, 63.0),
        ]),
    "2xv5p-64": dict(
        label="llama3-8b HSDP(data=2,fsdp=64)",
        ici_bytes_per_step=70175653632,
        dcn_bytes_per_step=234528896, ici_time_us=170627.6,
        peak_hbm_bytes=40521155348, collectives=[
            ("all_gather", 234881024, 64, 14797504512, 0, 28694.5),
            ("reduce_scatter", 469762048, 32, 14797504512, 117440512,
             32976.1),
            ("all_gather", 234881024, 32, 7398752256, 0, 14347.3),
            ("reduce_scatter", 234881024, 32, 7398752256, 58720256,
             18296.1),
            ("all_gather", 117440512, 32, 3699376128, 0, 8181.6),
            ("all_gather", 117440512, 32, 3699376128, 0, 8181.6),
            ("all_gather", 50331648, 64, 3170893824, 0, 9316.8),
            ("reduce_scatter", 100663296, 32, 3170893824, 25165824,
             9907.5),
            ("all_gather", 33554432, 64, 2113929216, 0, 7555.2),
            ("reduce_scatter", 67108864, 32, 2113929216, 16777216, 7810.3),
            ("all_gather", 1050673152, 2, 2068512768, 0, 3573.5),
            ("reduce_scatter", 2101346304, 1, 2068512768, 16416768,
             4217.2),
            ("all_gather", 50331648, 32, 1585446912, 0, 4658.4),
            ("all_gather", 33554432, 32, 1056964608, 0, 3777.6),
            ("all_gather", 1050673152, 1, 1034256384, 0, 1786.8),
            ("reduce_scatter", 16384, 64, 1032192, 8192, 7234.0),
            ("reduce_scatter", 16384, 1, 16128, 128, 113.0),
        ]),
}

@functools.lru_cache(maxsize=None)
def _report(topo_name):
    """The FSDP Llama's trace report on that topology, as the `trace`
    command's JSON (audited once a topology)."""
    from ray_lightning_tpu.analysis.cli import resolve_trace_target
    from ray_lightning_tpu.analysis.costmodel import parse_topology
    from ray_lightning_tpu.analysis.tracecheck import audit_step

    topo = parse_topology(topo_name)
    module, strategy, batch, label = resolve_trace_target(
        "llama_fsdp_example.py", topo)
    return json.loads(json.dumps(audit_step(
        module, strategy, batch, topology=topo, label=label).to_dict()))


@pytest.mark.parametrize("topo", list(_PARENT))
def test_fsdp_llama_audits_clean_with_no_overlap_key(topo):
    d = _report(topo)
    assert d["findings"] == []
    assert d["unentered"] == [] and d["lost_specs"] == {}
    assert d["fits"] is True

    def keys(node):
        if isinstance(node, dict):
            for k, v in node.items():
                yield k
                yield from keys(v)
        elif isinstance(node, list):
            for v in node:
                yield from keys(v)

    stale = {k for k in keys(d) if k.startswith("overlap")
             or k in ("ici_hidden_us", "ici_exposed_us", "hidden_us",
                      "prefetchable")}
    assert not stale


@pytest.mark.parametrize("topo", list(_PARENT))
def test_fsdp_llama_accounting_equals_the_parent_s(topo):
    d, want = _report(topo), _PARENT[topo]
    assert d["label"] == want["label"]
    assert d["ici_bytes_per_step"] == want["ici_bytes_per_step"]
    assert d["dcn_bytes_per_step"] == want["dcn_bytes_per_step"]
    assert d["ici_time_us"] == want["ici_time_us"]
    assert d["peak_hbm_bytes"] == want["peak_hbm_bytes"]
    assert [(e["kind"], e["payload_bytes"], e["count"], e["wire_bytes"],
             e["dcn_bytes"], e["time_us"])
            for e in d["collectives"]] == want["collectives"]
    assert all(e["axes"] == ["fsdp"] or
               (e["axes"] == ["data", "fsdp"] and topo == "2xv5p-64")
               for e in d["collectives"])


def test_llama3_8b_on_v5p_64_keeps_its_hbm_and_collectives():
    """PR 29's reading of the flagship: 21.74 GiB a device of 85.5, 17
    collectives, gathers and scatters only."""
    d = _report("v5p-64")
    assert round(d["peak_hbm_bytes"] / 2**30, 2) == 21.74
    assert round(d["hbm_budget_bytes"] / 2**30, 2) == 85.5
    assert len(d["collectives"]) == 17
    assert set(d["totals_by_kind"]) == {"all_gather", "reduce_scatter"}
    assert d["totals_by_kind"]["all_gather"]["count"] == 355
    assert d["totals_by_kind"]["reduce_scatter"]["count"] == 194


# ---- plan, perf and bench carry no trace of the option ----------------------

_PLAN_8B = ("plan", "--preset", "llama3-8b", "--fsdp", "64", "--batch", "64",
            "--seq", "8192")


def test_plan_llama3_8b_fsdp64_prints_the_parent_s_bytes():
    """What `plan ... --overlap off` printed at the parent, to the byte."""
    rc, out, _ = _cli(*_PLAN_8B, "--no-trace")
    assert rc == 0
    assert out == (
        "mesh {'fsdp': 64} x64 devices: params 0.47 + opt 0.93 + grads "
        "0.47 + acts 6.64 = 8.51 GiB/device vs budget 85.50 GiB (FITS; "
        "global params 29.92 GiB, opt 59.83 GiB)\n")


def test_plan_json_has_the_parent_s_bytes_and_no_overlap_key():
    rc, out, _ = _cli(*_PLAN_8B, "--json")
    assert rc == 0
    d = json.loads(out)
    assert d["per_device_bytes"] == 9137357832
    assert d["budget_bytes"] == 91804925952
    assert d["fits"] is True
    assert set(d) == {"mesh", "n_devices", "per_device_bytes",
                      "budget_bytes", "fits", "summary", "trace"}
    trace = d["trace"]
    assert trace["ici_bytes_per_step"] == 70175653632
    assert trace["ici_time_us"] == 151546.4
    assert trace["peak_hbm_bytes"] == 23341286164
    assert trace["finding_counts"] == {"error": 0, "warning": 0, "note": 0}
    assert not [k for k in trace if "overlap" in k or "hidden" in k
                or "exposed" in k]


def test_perf_smoke_passes_with_no_collective_leg():
    rc, out, _ = _cli("perf", "--smoke", "--steps", "25", "--json")
    assert rc == 0
    d = json.loads(out.strip().splitlines()[-1])
    assert d["pipeline_occupancy"] > 0
    assert not [k for k in d if k.startswith("overlap")]
    assert "ideal_speedup" not in d and "serial_s" not in d


def test_bench_analysis_line_has_no_overlap_key():
    import bench

    summary = bench._trace_summary()
    assert "tracecheck" in summary, summary.get("tracecheck_error")
    assert not [k for k in summary if k.startswith("overlap")]

"""Collective overlap (ISSUE 6): the double-buffered weight-gather
prefetch schedule in the scanned Llama stack + tracecheck's
hidden-vs-exposed classification.

The guarantees pinned here:
  * overlap="on" and overlap="serial" (the same explicit gather schedule
    minus the prefetch) train BITWISE-identically — the only delta
    between the two programs is where the gather latency sits;
  * overlap="off" compiles the exact pre-knob program (no prefetch
    fingerprint, `_loss` takes the historical path);
  * tracecheck classifies the overlapped schedule's collectives against
    the compute-window roofline (fully hidden / partially exposed /
    zero-compute), flags the un-overlapped scan with RLT305, and the
    flagship 8B/v5p-64 trace hides >= 70% of prefetchable ICI time;
  * the plan CLI charges the double-buffer HBM;
  * scripts/bench_gate.py ratchets bench metrics and passes structured
    skips.
"""
import importlib.util
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from ray_lightning_tpu import DataLoader, ShardedMesh, Trainer
from ray_lightning_tpu.analysis.costmodel import (
    Topology, compute_time_us, parse_topology, topology_for_kind,
)
from ray_lightning_tpu.analysis.tracecheck import (
    CollectiveEvent, audit_step, classify_overlap,
)
from ray_lightning_tpu.models.llama import LlamaConfig, LlamaModule
from ray_lightning_tpu.ops.dispatch import OVERLAP_PREFETCH_NAME

jnp = jax.numpy


def _tiny_cfg(**kw):
    return LlamaConfig.tiny(use_flash=False, **kw)


def _data(cfg, n=64, seq=32, seed=0):
    rng = np.random.default_rng(seed)
    return {"tokens": rng.integers(
        0, cfg.vocab_size, (n, seq + 1)).astype(np.int32)}


def _fit(overlap, cfg=None, seed=0, **mesh_kw):
    cfg = cfg or _tiny_cfg()
    module = LlamaModule(cfg, lr=1e-3, warmup_steps=1, total_steps=50)
    data = _data(cfg)
    trainer = Trainer(
        strategy=ShardedMesh(overlap=overlap,
                             **(mesh_kw or {"fsdp": 4, "data": 2})),
        max_epochs=1, enable_progress_bar=False,
        enable_checkpointing=False, seed=seed)
    trainer.fit(module, DataLoader(data, batch_size=16, shuffle=True))
    return jax.device_get(module.params)


def _assert_tree_bitwise(a, b, what):
    for (pa, la), (pb, lb) in zip(
            jax.tree_util.tree_leaves_with_path(a),
            jax.tree_util.tree_leaves_with_path(b)):
        assert pa == pb
        la, lb = np.asarray(la), np.asarray(lb)
        assert la.tobytes() == lb.tobytes(), (
            f"{what}: {jax.tree_util.keystr(pa)} differs "
            f"(max abs diff {np.abs(la - lb).max()})")


# --------------------------------------------------------------------------
# bitwise equivalence of the schedules
# --------------------------------------------------------------------------


class TestScheduleEquivalence:
    def test_on_matches_serial_bitwise(self):
        """The prefetched and serial gather schedules are the same math
        in a different order on the wire — final params bitwise equal
        (full Trainer fit: donated state, optimizer, per-step RNG)."""
        on = _fit("on")
        serial = _fit("serial")
        _assert_tree_bitwise(on, serial, "overlap=on vs overlap=serial")

    def test_on_matches_single_device_ground_truth(self):
        """The overlapped hidden path computes exactly what the model
        computes with no sharding at all: forward on the fsdp x data
        mesh vs a single CPU device, bitwise."""
        cfg = _tiny_cfg(n_layers=4, dtype=jnp.float32)
        batch = _data(cfg, n=8)
        module = LlamaModule(cfg)
        strat = ShardedMesh(fsdp=4, data=2, overlap="on")
        strat.setup(module)
        module.setup()
        params = module.init_params(jax.random.PRNGKey(0), batch)
        host_params = jax.device_get(params)
        params = strat.shard_params(params)
        tokens = strat.shard_batch(batch)["tokens"][:, :-1]
        h_overlap = np.asarray(
            jax.jit(module._overlapped_hidden)(params, tokens))

        ref = LlamaModule(cfg)
        ref.mesh = None
        ref.setup()
        dev0 = jax.devices()[0]
        h_ref = np.asarray(jax.jit(
            lambda p, t: ref.apply(p, t, return_hidden=True),
            device=dev0)(jax.device_put(host_params, dev0),
                         jax.device_put(
                             np.asarray(batch["tokens"][:, :-1]), dev0)))
        assert h_overlap.tobytes() == h_ref.tobytes(), (
            f"max abs diff {np.abs(h_overlap - h_ref).max()}")

    def test_on_close_to_off(self):
        """Same math as the historical path up to XLA fusion
        reassociation (the schedules compile different programs, so
        bitwise equality is NOT expected — the serial ablation is the
        bitwise pin)."""
        cfg = _tiny_cfg(dtype=jnp.float32)
        on = _fit("on", cfg=cfg, fsdp=8)
        off = _fit("off", cfg=cfg, fsdp=8)
        for la, lb in zip(jax.tree.leaves(on), jax.tree.leaves(off)):
            np.testing.assert_allclose(
                np.asarray(la), np.asarray(lb), rtol=2e-4, atol=2e-4)

    def test_composes_with_trainguard_and_donation(self):
        """The guarded, donated train step compiles and trains with the
        overlap schedule on — the resilience paths see the same
        TrainState contract."""
        cfg = _tiny_cfg()
        module = LlamaModule(cfg, lr=1e-3, warmup_steps=1, total_steps=50)
        trainer = Trainer(
            strategy=ShardedMesh(fsdp=4, data=2, overlap="on"),
            max_epochs=1, enable_progress_bar=False,
            enable_checkpointing=False, seed=0, guard=True)
        trainer.fit(module, DataLoader(_data(cfg), batch_size=16))
        loss = float(trainer.callback_metrics["train_loss"])
        assert np.isfinite(loss)
        assert int(trainer.callback_metrics.get("guard_anomaly", 0)) == 0


def _spmd_overlap_fit(overlap):
    """Worker body for the 2-proc bitwise pin: fit the tiny Llama on a
    REAL multi-process fsdp=4 mesh (2 procs x 2 CPU devices, gloo
    collectives) and return every param leaf's LOCAL shard bytes in
    shard-index order — cross-process arrays are not fetchable whole, so
    each rank pins its own slice of the final state."""
    import jax
    import numpy as np

    from ray_lightning_tpu import DataLoader, ShardedMesh, Trainer
    from ray_lightning_tpu.models.llama import LlamaConfig, LlamaModule

    cfg = LlamaConfig.tiny(use_flash=False)
    module = LlamaModule(cfg, lr=1e-3, warmup_steps=1, total_steps=50)
    rng = np.random.default_rng(0)
    data = {"tokens": rng.integers(
        0, cfg.vocab_size, (64, 33)).astype(np.int32)}
    trainer = Trainer(
        strategy=ShardedMesh(fsdp=4, overlap=overlap),
        max_epochs=1, enable_progress_bar=False,
        enable_checkpointing=False, seed=0)
    trainer.fit(module, DataLoader(
        data, batch_size=16, num_shards=jax.process_count(),
        shard_index=jax.process_index()))
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(module.params):
        shards = sorted(leaf.addressable_shards, key=lambda s: s.index)
        out[jax.tree_util.keystr(path)] = b"".join(
            np.asarray(s.data).tobytes() for s in shards)
    return out


@pytest.mark.slow
def test_two_process_fsdp_bitwise():
    """The satellite's 2-proc leg: overlap='on' vs the serial ablation on
    a real 2-process CPU-SPMD fsdp mesh — the prefetched gathers ride
    gloo across process boundaries and the final params must still match
    bit for bit on every rank's local shards."""
    from ray_lightning_tpu.runtime.launch import launch_cpu_spmd

    on = launch_cpu_spmd(_spmd_overlap_fit, num_processes=2,
                         devices_per_process=2, args=("on",), timeout=420)
    serial = launch_cpu_spmd(
        _spmd_overlap_fit, num_processes=2, devices_per_process=2,
        args=("serial",), timeout=420)
    for rank, (a, b) in enumerate(zip(on, serial)):
        assert a.keys() == b.keys()
        for k in a:
            assert a[k] == b[k], (
                f"rank {rank}: {k} differs between overlap=on and serial")


# --------------------------------------------------------------------------
# overlap=off pins the pre-PR program
# --------------------------------------------------------------------------


class TestOffPin:
    def _loss_jaxpr(self, overlap):
        cfg = _tiny_cfg()
        module = LlamaModule(cfg)
        strat = ShardedMesh(fsdp=4, data=2, overlap=overlap)
        strat.setup(module)
        module.setup()
        batch = _data(cfg, n=8)
        params = module.init_params(jax.random.PRNGKey(0), batch)
        tokens = jnp.asarray(batch["tokens"][:, :-1])
        targets = jnp.asarray(batch["tokens"][:, 1:])
        return jax.make_jaxpr(
            lambda p, i, t: module._loss(p, i, t, None))(
                params, tokens, targets)

    def test_off_is_byte_identical_to_unbound_module(self):
        """overlap='off' must trace the EXACT program a module that
        never saw the knob traces (the pre-PR schedule)."""
        off = str(self._loss_jaxpr("off"))

        cfg = _tiny_cfg()
        module = LlamaModule(cfg)  # never bound to a strategy knob
        strat = ShardedMesh(fsdp=4, data=2)
        strat.setup(module)
        module.setup()
        batch = _data(cfg, n=8)
        params = module.init_params(jax.random.PRNGKey(0), batch)
        vanilla = str(jax.make_jaxpr(
            lambda p, i, t: module._loss(p, i, t, None))(
                params, jnp.asarray(batch["tokens"][:, :-1]),
                jnp.asarray(batch["tokens"][:, 1:])))
        assert off == vanilla

    def test_fingerprint_present_iff_scheduled(self):
        off = str(self._loss_jaxpr("off"))
        on = str(self._loss_jaxpr("on"))
        serial = str(self._loss_jaxpr("serial"))
        assert OVERLAP_PREFETCH_NAME not in off
        assert OVERLAP_PREFETCH_NAME in on
        # the serial ablation runs the explicit gather schedule with the
        # prefetch REMOVED — no fingerprint, tracecheck reads it as
        # unscheduled
        assert OVERLAP_PREFETCH_NAME not in serial

    def test_use_overlap_gates(self):
        cfg = _tiny_cfg()
        module = LlamaModule(cfg)
        strat = ShardedMesh(fsdp=4, data=2, overlap="on")
        strat.setup(module)
        assert module._use_overlap()
        # no fsdp latency to hide -> the knob is inert
        module2 = LlamaModule(cfg)
        strat2 = ShardedMesh(data=8, overlap="on")
        strat2.setup(module2)
        assert not module2._use_overlap()
        # unscanned stacks cannot pipeline
        module3 = LlamaModule(_tiny_cfg(scan_layers=False))
        strat3 = ShardedMesh(fsdp=4, data=2, overlap="on")
        strat3.setup(module3)
        assert not module3._use_overlap()


# --------------------------------------------------------------------------
# classify_overlap unit tests (hand-built schedules)
# --------------------------------------------------------------------------


def _topo(gbps=600.0, peak_tflops=459.0) -> Topology:
    return Topology(name="test", device_kind="TPU v5p", n_devices=8,
                    ici_gbps=gbps, ici_hop_latency_us=1.0,
                    hbm_bytes=95 * 1024**3, peak_tflops=peak_tflops)


def _ev(time_us, *, prefetchable=True, scope=0, kind="all_gather"):
    return CollectiveEvent(
        kind=kind, axes=("fsdp",), payload_bytes=1 << 20, count=8,
        wire_bytes=8 << 20, time_us=time_us, implicit=False,
        source="test", prefetchable=prefetchable, scope=scope)


def _flops_for_window(topo, window_us):
    # invert compute_time_us: flops whose roofline time is window_us
    from ray_lightning_tpu.analysis.costmodel import MXU_EFFICIENCY

    return window_us / 1e6 * topo.peak_tflops * 1e12 * MXU_EFFICIENCY


class TestClassifyOverlap:
    def test_fully_hidden(self):
        """Compute window >= per-trip comm: the whole gather hides."""
        topo = _topo()
        ev = _ev(800.0)
        scopes = {0: {"trips": 8, "marker": True,
                      "flops": _flops_for_window(topo, 200.0),
                      "source": "scan"}}
        out = classify_overlap([ev], scopes, topo)
        assert out["scheduled"] is True
        assert out["overlap_hidden_fraction"] == pytest.approx(1.0)
        assert ev.hidden_us == pytest.approx(ev.time_us)
        assert ev.exposed_us == pytest.approx(0.0)

    def test_partially_exposed(self):
        """Window covers half the per-trip comm: half the time hides,
        the remainder is exposed — max(0, t_comm - t_compute)."""
        topo = _topo()
        ev = _ev(800.0)  # 100 us/trip over 8 trips
        scopes = {0: {"trips": 8, "marker": True,
                      "flops": _flops_for_window(topo, 50.0),
                      "source": "scan"}}
        out = classify_overlap([ev], scopes, topo)
        assert out["overlap_hidden_fraction"] == pytest.approx(0.5)
        assert ev.hidden_us == pytest.approx(400.0)
        assert ev.exposed_us == pytest.approx(400.0)
        sc = out["per_scope"][0]
        assert sc["hidden_fraction"] == pytest.approx(0.5)
        assert sc["compute_us_per_trip"] == pytest.approx(50.0)
        assert sc["prefetch_comm_us_per_trip"] == pytest.approx(100.0)

    def test_zero_compute_pathological(self):
        """A scope with nothing to hide behind hides nothing, even with
        the schedule live."""
        topo = _topo()
        ev = _ev(800.0)
        scopes = {0: {"trips": 8, "marker": True, "flops": 0.0,
                      "source": "scan"}}
        out = classify_overlap([ev], scopes, topo)
        assert out["overlap_hidden_fraction"] == 0.0
        assert ev.hidden_us == 0.0
        assert ev.exposed_us == pytest.approx(800.0)

    def test_unscheduled_trace_hides_nothing(self):
        """No prefetch fingerprint anywhere -> scheduled False -> the
        whole prefetchable time is exposed regardless of the window."""
        topo = _topo()
        ev = _ev(800.0)
        scopes = {0: {"trips": 8, "marker": False,
                      "flops": _flops_for_window(topo, 1e6),
                      "source": "scan"}}
        out = classify_overlap([ev], scopes, topo)
        assert out["scheduled"] is False
        assert out["overlap_hidden_fraction"] == 0.0
        assert ev.hidden_us == 0.0

    def test_unmarked_scope_earns_no_credit(self):
        """Hidden credit is per scope: the backward scan (marker-free
        transpose of the marked forward, SAME source) is credited, an
        unrelated scan (the fused-CE chunk loop) with a huge window is
        not — program-wide credit would pad the gated fraction with
        time the knob never earned."""
        topo = _topo()
        fwd = _ev(800.0, scope=0)
        bwd = _ev(800.0, scope=1)
        other = _ev(800.0, scope=2)
        scopes = {
            0: {"trips": 8, "marker": True,
                "source": "scan @ llama.py:1",
                "flops": _flops_for_window(topo, 200.0)},
            1: {"trips": 8, "marker": False,
                "source": "scan @ llama.py:1",
                "flops": _flops_for_window(topo, 200.0)},
            2: {"trips": 8, "marker": False, "source": "scan @ ce.py:2",
                "flops": _flops_for_window(topo, 1e6)},
        }
        out = classify_overlap([fwd, bwd, other], scopes, topo)
        assert fwd.hidden_us == pytest.approx(800.0)
        assert bwd.hidden_us == pytest.approx(800.0)
        assert other.hidden_us == 0.0
        by_src = {(s["source"], s["scheduled"])
                  for s in out["per_scope"]}
        assert ("scan @ ce.py:2", False) in by_src
        assert ("scan @ llama.py:1", True) in by_src

    def test_non_prefetchable_never_hidden(self):
        """Activation reshards etc. are not part of the prefetch
        schedule — they stay exposed and out of the fraction."""
        topo = _topo()
        pref = _ev(100.0)
        act = _ev(900.0, prefetchable=False)
        scopes = {0: {"trips": 8, "marker": True,
                      "flops": _flops_for_window(topo, 1e6),
                      "source": "scan"}}
        out = classify_overlap([pref, act], scopes, topo)
        assert act.hidden_us == 0.0
        assert out["overlap_hidden_fraction"] == pytest.approx(1.0)
        assert out["ici_exposed_us"] == pytest.approx(900.0)

    def test_compute_time_us_roofline(self):
        topo = _topo(peak_tflops=100.0)
        # 100 TFLOP/s * 0.6 efficiency = 60e12 flops/s
        assert compute_time_us(60e12, topo) == pytest.approx(1e6)
        assert compute_time_us(0.0, topo) == 0.0


# --------------------------------------------------------------------------
# end-to-end classification on real traces
# --------------------------------------------------------------------------


def _audit_tiny(overlap, n=8):
    cfg = LlamaConfig.tiny(dim=256, n_layers=4, n_heads=8, n_kv_heads=4,
                           hidden_dim=1024, max_seq_len=512,
                           use_flash=False)
    return audit_step(
        LlamaModule(cfg), ShardedMesh(fsdp=n, overlap=overlap),
        {"tokens": np.zeros((n, 513), np.int32)},
        topology=topology_for_kind("TPU v5e", n),
        label=f"tiny overlap={overlap}")


class TestTraceClassification:
    def test_on_is_scheduled_and_hides(self):
        report = _audit_tiny("on")
        assert report.overlap["scheduled"] is True
        assert report.overlap_hidden_fraction > 0.0
        assert report.ici_hidden_us > 0.0
        assert not [f for f in report.findings if f.rule == "RLT305"]
        # per-scope breakdown names the scanned stack
        assert any(sc["trips"] == 4 or sc["trips"] >= 1
                   for sc in report.overlap["per_scope"])

    def test_off_flags_rlt305(self):
        report = _audit_tiny("off")
        assert report.overlap["scheduled"] is False
        assert report.overlap_hidden_fraction == 0.0
        flagged = [f for f in report.findings if f.rule == "RLT305"]
        assert flagged, "exposed per-trip weight gathers must be flagged"
        assert any("overlap" in f.message for f in flagged)
        # the layer-stack kernels are the flagged symbols
        symbols = {f.symbol for f in flagged}
        assert any("layers/" in (s or "") for s in symbols)

    def test_serial_is_unscheduled(self):
        """The ablation control traces as exposed — any measured delta
        between on and serial is therefore pure latency hiding."""
        report = _audit_tiny("serial")
        assert report.overlap["scheduled"] is False
        assert report.overlap_hidden_fraction == 0.0

    def test_off_schedule_signature_unchanged(self):
        """The off-trace's collective schedule must not see ANY of the
        overlap machinery (no explicit gathers from the constraint, no
        marker): the exact pre-PR implicit-ZeRO schedule."""
        report = _audit_tiny("off")
        assert all(e.implicit for e in report.collectives
                   if e.kind == "all_gather")

    def test_report_json_carries_overlap_fields(self):
        d = _audit_tiny("on").to_dict()
        assert "overlap_hidden_fraction" in d
        assert "ici_hidden_us" in d and "ici_exposed_us" in d
        assert d["overlap"]["scheduled"] is True
        assert all("hidden_us" in e for e in d["collectives"])


def test_nested_scan_marker_stays_on_inner_scope():
    """A marked scan nested inside an outer scan must stamp the prefetch
    marker on ITSELF only: the outer scan's own collectives are not part
    of the double-buffer schedule and must not earn hidden-credit.
    (Regression: the scan fixpoint pass runs before the inner scope is
    pushed, so an ungated marker handler stamped the ENCLOSING scope.)"""
    import optax
    from jax.sharding import PartitionSpec as P

    from ray_lightning_tpu.core.module import TpuModule
    from ray_lightning_tpu.ops.dispatch import prefetch_named

    class _Nested(TpuModule):
        def init_params(self, rng, batch):
            return {"w_stack": jnp.zeros((2, 64, 64), jnp.float32),
                    "w_out": jnp.zeros((64, 64), jnp.float32)}

        def configure_model(self):
            return None

        def configure_optimizers(self):
            return optax.sgd(1e-2)

        def param_specs(self, params):
            return {"w_stack": P(None, "fsdp", None),
                    "w_out": P("fsdp", None)}

        def training_step(self, params, batch, rng):
            def inner(c, w):
                # marker rides the per-trip slice (like the real
                # schedule's gathered layer) so it stays IN the body
                w = prefetch_named(w)
                return jnp.tanh(c @ w), None

            def outer(c, x):
                c2, _ = jax.lax.scan(inner, c + x.sum(),
                                     params["w_stack"])
                # outer-scope prefetchable gather, NOT in the schedule
                return jnp.tanh(c2 @ params["w_out"]), None

            out, _ = jax.lax.scan(outer, jnp.zeros((64, 64)), batch["x"])
            return (out ** 2).mean()

    rep = audit_step(_Nested(), ShardedMesh(fsdp=4),
                     {"x": np.zeros((3, 1), np.float32)},
                     topology="v5e-4", label="nested-scan")
    assert rep.overlap["scheduled"] is True
    scopes = rep.overlap["per_scope"]
    inner_scopes = [s for s in scopes if s["trips"] == 2]
    outer_scopes = [s for s in scopes if s["trips"] == 3]
    assert inner_scopes and any(s["scheduled"] for s in inner_scopes)
    assert outer_scopes
    assert not any(s["scheduled"] for s in outer_scopes), scopes


def test_llama8b_v5p64_overlap_acceptance():
    """ISSUE 6 acceptance: the flagship trace hides >= 70% of ZeRO
    prefetchable ICI time with overlap=on, and fits HBM with the
    double buffer live."""
    from ray_lightning_tpu.analysis.cli import resolve_trace_target

    topo = parse_topology("v5p-64")
    module, strategy, batch, label = resolve_trace_target(
        "llama3-8b", topo, overlap="on")
    report = audit_step(module, strategy, batch, topology=topo,
                        label=label)
    assert report.overlap["scheduled"] is True
    assert report.overlap_hidden_fraction >= 0.7, report.summary()
    assert report.fits, report.summary()
    assert not [f for f in report.findings
                if f.severity == "error"], report.summary()
    # the weight gathers hide behind the layer compute window
    gathers = [e for e in report.collectives
               if e.kind == "all_gather" and e.prefetchable
               and e.scope is not None]
    assert gathers
    assert sum(e.hidden_us for e in gathers) > 0


# --------------------------------------------------------------------------
# plan: double-buffer HBM accounting
# --------------------------------------------------------------------------


class TestPlanAccounting:
    def test_buffer_bytes_scale(self):
        from ray_lightning_tpu.parallel.plan import (
            llama_overlap_buffer_bytes,
        )

        cfg = LlamaConfig.llama3_8b()
        b64 = llama_overlap_buffer_bytes(cfg, fsdp=64)
        b8 = llama_overlap_buffer_bytes(cfg, fsdp=8)
        assert b64 > 0
        # the gathered-layer term is fsdp-independent; the shard terms
        # shrink with fsdp — so more shards = smaller charge
        assert b8 > b64
        # tensor parallelism splits the gathered buffer too
        assert llama_overlap_buffer_bytes(cfg, fsdp=64, tensor=4) < b64
        # one 8B layer gathered is ~0.8 GiB f32; the charge must be at
        # least that and far less than the whole stack
        layer = 4 * (4096 * (32 + 16) * 128 + 32 * 128 * 4096
                     + 4096 * 2 * 14336 + 14336 * 4096 + 2 * 4096)
        assert b64 >= layer // 1
        assert b64 < 32 * layer

    def test_inert_config_charges_zero(self):
        """Configs where the schedule never goes live (models/llama.py
        _use_overlap: fsdp > 1, scanned, >= 2 layers) compile the naive
        program — charging phantom double-buffer bytes there would flip
        a fitting job to DOES-NOT-FIT."""
        import dataclasses

        from ray_lightning_tpu.parallel.plan import (
            llama_overlap_buffer_bytes,
        )

        cfg = LlamaConfig.llama3_8b()
        assert llama_overlap_buffer_bytes(cfg, fsdp=1) == 0
        assert llama_overlap_buffer_bytes(cfg, fsdp=1, mode="serial") == 0
        assert llama_overlap_buffer_bytes(
            dataclasses.replace(cfg, scan_layers=False), fsdp=64) == 0
        assert llama_overlap_buffer_bytes(
            dataclasses.replace(cfg, n_layers=1), fsdp=64) == 0

    def test_plan_cli_charges_overlap(self):
        from ray_lightning_tpu.__main__ import main

        def run(*extra):
            import io
            from contextlib import redirect_stdout

            buf = io.StringIO()
            with redirect_stdout(buf):
                rc = main(["plan", "--preset", "llama3-8b", "--fsdp",
                           "64", "--batch", "64", "--seq", "8192",
                           "--no-trace", "--json", *extra])
            return rc, json.loads(buf.getvalue())

        rc_off, off = run()
        rc_on, on = run("--overlap", "on")
        assert rc_off == 0 and rc_on == 0
        assert off["overlap_buffer_bytes"] == 0
        assert on["overlap_buffer_bytes"] > 0
        assert on["overlap"] == "on"
        assert on["per_device_bytes"] == pytest.approx(
            off["per_device_bytes"] + on["overlap_buffer_bytes"])
        # the serial ablation holds no double buffer and no rolled xs
        # copy — only the in-flight grad shard is charged
        rc_serial, serial = run("--overlap", "serial")
        assert rc_serial == 0
        assert 0 < serial["overlap_buffer_bytes"] \
            < on["overlap_buffer_bytes"]


# --------------------------------------------------------------------------
# bench gate
# --------------------------------------------------------------------------


def _bench_gate():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "bench_gate.py")
    spec = importlib.util.spec_from_file_location("bench_gate", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestBenchGate:
    def _priors(self, tmp_path):
        (tmp_path / "BENCH_r01.json").write_text(json.dumps({
            "parsed": {"metric": "m", "value": 100.0, "mfu": 0.5,
                       "overlap_hidden_fraction": 0.8}}))
        (tmp_path / "BENCH_r02.json").write_text(json.dumps({
            "parsed": {"metric": "m", "value": 90.0, "mfu": 0.6}}))
        # a skipped round must not set the measured-metric bar ...
        (tmp_path / "BENCH_r03.json").write_text(json.dumps({
            "parsed": {"metric": "m", "value": 0.0,
                       "skipped": "backend unavailable",
                       "overlap_hidden_fraction": 0.9}}))
        return tmp_path

    def test_pass_and_regress(self, tmp_path):
        bg = _bench_gate()
        self._priors(tmp_path)
        best = bg.best_prior("BENCH_r0*.json", str(tmp_path))
        # per-metric max across rounds; the static overlap fraction
        # ratchets even from the skip round
        assert best["tokens_per_sec_per_chip"][0] == 100.0
        assert best["mfu"][0] == 0.6
        assert best["overlap_hidden_fraction"][0] == 0.9

        ok = {"metric": "m", "value": 99.0, "mfu": 0.59,
              "overlap_hidden_fraction": 0.88}
        assert bg.gate(ok, best, 0.05) == []
        bad = {"metric": "m", "value": 50.0, "mfu": 0.59,
               "overlap_hidden_fraction": 0.88}
        msgs = bg.gate(bad, best, 0.05)
        assert len(msgs) == 1 and "tokens_per_sec_per_chip" in msgs[0]

    def test_dropped_field_fails(self, tmp_path):
        bg = _bench_gate()
        self._priors(tmp_path)
        best = bg.best_prior("BENCH_r0*.json", str(tmp_path))
        naked = {"metric": "m", "value": 200.0, "mfu": 0.7}
        msgs = bg.gate(naked, best, 0.05)
        assert any("overlap_hidden_fraction" in m and "dropped" in m
                   for m in msgs)

    def test_analysis_error_waives_static_metric(self, tmp_path):
        """A success line whose static analysis DIED (overlap_error, or
        tracecheck_error when the whole trace failed) is an analysis
        bug, not a deleted field — it must not cost the measured run
        its perf evidence."""
        bg = _bench_gate()
        self._priors(tmp_path)
        best = bg.best_prior("BENCH_r0*.json", str(tmp_path))
        for err_key in ("overlap_error", "tracecheck_error"):
            line = {"metric": "m", "value": 200.0, "mfu": 0.7,
                    err_key: "boom"}
            assert bg.gate(line, best, 0.05) == [], err_key

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

    def test_skip_still_ratchets_static_metric(self, tmp_path):
        """overlap_hidden_fraction is static analysis — carried on a
        backend-down skip line and ratcheted there too (on the TPU-less
        boxes format.sh targets it is the ONLY checkable metric)."""
        bg = _bench_gate()
        self._priors(tmp_path)  # best prior fraction: 0.9 (r03, a skip)
        best = bg.best_prior("BENCH_r0*.json", str(tmp_path))
        fails = bg.gate({"metric": "m", "value": 0.0,
                         "skipped": "backend unavailable",
                         "overlap_hidden_fraction": 0.2}, best, 0.05)
        assert fails and "overlap_hidden_fraction" in fails[0]
        assert bg.gate({"metric": "m", "value": 0.0,
                        "skipped": "backend unavailable",
                        "overlap_hidden_fraction": 0.9},
                       best, 0.05) == []

    def test_cli_against_recorded_history(self, tmp_path):
        """The gate must accept a history's own best round (no
        self-regression) and reject a gutted line; with no prior round
        on record it says so and passes."""
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        script = os.path.join(root, "scripts", "bench_gate.py")
        self._priors(tmp_path)
        hist = ["--repo-root", str(tmp_path)]
        (tmp_path / "fresh.json").write_text(json.dumps({
            "metric": "m", "value": 100.0, "mfu": 0.6,
            "overlap_hidden_fraction": 0.9}))
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


# --------------------------------------------------------------------------
# perf overlap leg
# --------------------------------------------------------------------------


def test_simulated_interleave_beats_serial():
    from ray_lightning_tpu.pipeline.collective_overlap import (
        simulate_overlap_schedule,
    )

    # wall-clock measurement: a loaded CI box can squeeze the thread
    # scheduling, so take the best of a few attempts before judging —
    # the schedule either interleaves (~1.8x ideal here) or it doesn't
    # (observed 1.142 vs the 1.15 floor on a box running two suites:
    # five attempts, not three, before calling it a regression)
    best = {"overlap_speedup": 0.0}
    for _ in range(5):
        out = simulate_overlap_schedule(n_layers=6, t_comm_s=0.03,
                                        compute_ms_target=30.0)
        if out["overlap_speedup"] > best["overlap_speedup"]:
            best = out
        if best["overlap_speedup"] > 1.15:
            break
    assert best["overlap_speedup"] > 1.15, best
    assert best["serial_s"] > best["overlapped_s"]


def test_bench_overlap_summary_fields():
    """Every bench JSON line carries the overlap evidence (success or
    backend-down: _ANALYSIS is computed before any backend touch)."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import bench

    cfg = bench._bench_cfg(use_flash=True, fused_ce=True, seq=512,
                           vocab=4096)
    out = bench._overlap_summary(cfg, topology_for_kind)
    assert "overlap_hidden_fraction" in out, out
    assert out["overlap"]["scheduled"] is True
    assert out["overlap_hidden_fraction"] > 0.0

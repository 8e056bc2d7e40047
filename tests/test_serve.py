"""Serving engine tests (serve/, docs/SERVING.md): the paged KV cache,
the continuous-batching step's bitwise parity with single-stream
`generate`, the no-recompile-under-churn pin, scheduler lifecycle
(admission, growth, preemption), the decode-step audit, and the serve
plan leg."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.models.llama import Llama, LlamaConfig, generate
from ray_lightning_tpu.serve.engine import DecodeEngine, EngineConfig
from ray_lightning_tpu.serve.kv_cache import (
    BlockAllocator,
    PagedPoolSpec,
    pool_bytes,
    serve_kv_plan_bytes,
)
from ray_lightning_tpu.serve.scheduler import Request, Scheduler


@pytest.fixture(scope="module")
def tiny(tiny_llama_f32):
    # params from the session-scope canonical build (tests/conftest.py):
    # same cfg, same init key 1 — init params depend only on key and
    # param shapes, so the shared build is bitwise what this fixture
    # used to construct per-module
    cfg, model, params, _ = tiny_llama_f32
    prompts = [
        np.array(jax.random.randint(
            jax.random.key(10 + i), (1, 3 + (i % 5)), 0,
            cfg.vocab_size), dtype=np.int32)
        for i in range(8)
    ]
    return cfg, model, params, prompts


@pytest.fixture(scope="module")
def engine(tiny):
    cfg, model, params, _ = tiny
    eng = DecodeEngine(model, params, EngineConfig(
        capacity=4, block_size=4, blocks_per_slot=8, prefill_chunk=4))
    eng.warmup()
    return eng


def _mixed_requests(prompts, max_new=6):
    reqs = []
    for i, p in enumerate(prompts):
        sampled = i % 2 == 1
        reqs.append(Request(
            rid=f"r{i}", prompt=p[0], max_new_tokens=max_new,
            temperature=0.7 if sampled else 0.0,
            top_k=5 if sampled else None, seed=21 + i))
    return reqs


def _drain(sched, submit=(), stagger=True):
    """Run to empty, submitting one pending request per tick (the
    staggered-arrival shape of real traffic)."""
    pending = list(submit)
    out = {}
    while sched.busy() or pending:
        if pending:
            sched.submit(pending.pop(0))
            if not stagger:
                continue
        for comp in sched.tick():
            out[comp.rid] = comp
    return out


def _refs(model, params, prompts, reqs):
    return {
        r.rid: np.asarray(generate(
            model, params, prompts[i], r.max_new_tokens,
            temperature=r.temperature, top_k=r.top_k, seed=r.seed))[0]
        for i, r in enumerate(reqs)
    }


# ---- kv_cache --------------------------------------------------------------


def test_pool_spec_shapes_and_bytes():
    spec = PagedPoolSpec(n_blocks=9, block_size=4, blocks_per_slot=2)
    assert spec.gathered_len == 8
    cfg = LlamaConfig.tiny()
    kv = serve_kv_plan_bytes(cfg, spec, capacity=3)
    assert kv["pool_bytes"] == pool_bytes(cfg, spec)
    assert kv["gathered_view_bytes"] > 0
    assert kv["last_logits_bytes"] == 3 * cfg.vocab_size * 4
    with pytest.raises(ValueError, match="scratch"):
        PagedPoolSpec(n_blocks=1, block_size=4, blocks_per_slot=1)


def test_allocator_scratch_reserved_and_double_free():
    alloc = BlockAllocator(PagedPoolSpec(
        n_blocks=5, block_size=4, blocks_per_slot=2))
    got = alloc.alloc(4)
    assert sorted(got) == [1, 2, 3, 4]  # block 0 never handed out
    assert alloc.alloc(1) is None       # pool dry -> None, not partial
    alloc.free(got[:2])
    assert alloc.free_blocks == 2
    with pytest.raises(ValueError, match="double free"):
        alloc.free([got[0], got[0]])
    with pytest.raises(ValueError, match="invalid block"):
        alloc.free([0])


def test_for_capacity_oversubscribe():
    spec = PagedPoolSpec.for_capacity(4, max_len=32, block_size=8,
                                      oversubscribe=0.5)
    full = PagedPoolSpec.for_capacity(4, max_len=32, block_size=8)
    assert spec.blocks_per_slot == full.blocks_per_slot == 4
    assert spec.n_blocks < full.n_blocks


# ---- engine parity ---------------------------------------------------------


def test_staggered_streams_bitwise_match_generate(tiny, engine):
    """The acceptance pin: 8 concurrent staggered streams (ragged
    prompts, mixed greedy/temperature/top-k, per-request seeds) through
    4 slots decode bitwise-identical to 8 independent single-stream
    generate() runs."""
    cfg, model, params, prompts = tiny
    reqs = _mixed_requests(prompts)
    refs = _refs(model, params, prompts, reqs)
    sched = Scheduler(engine)
    out = _drain(sched, submit=reqs)
    for rid, ref in refs.items():
        np.testing.assert_array_equal(np.array(out[rid].tokens), ref,
                                      err_msg=rid)


@pytest.mark.parametrize("k", [1, 40, "V", "V+7"])
def test_top_k_streams_match_generate(tiny, engine, k):
    """The threshold is selected for a runtime k a slot (`_kth_largest`),
    `generate()` reads it off a static ``lax.top_k``: the streams of a
    mixed greedy / temperature / top-k batch are the same token for token
    at the ends of k's range too (k = 1 is greedy by another road; k >= V
    filters nothing, and `generate` takes at most V)."""
    cfg, model, params, prompts = tiny
    k = {"V": cfg.vocab_size, "V+7": cfg.vocab_size + 7}.get(k, k)
    modes = [(0.0, None), (0.7, None), (0.7, k), (1.3, k), (0.7, 5)]
    reqs = [Request(rid=f"k{i}", prompt=prompts[i][0], max_new_tokens=6,
                    temperature=t, top_k=top_k, seed=31 + i)
            for i, (t, top_k) in enumerate(modes)]
    out = _drain(Scheduler(engine), submit=reqs)
    for i, r in enumerate(reqs):
        ref = np.asarray(generate(
            model, params, prompts[i], r.max_new_tokens,
            temperature=r.temperature, seed=r.seed,
            top_k=r.top_k and min(r.top_k, cfg.vocab_size)))[0]
        np.testing.assert_array_equal(np.array(out[r.rid].tokens), ref,
                                      err_msg=r.rid)


def test_step_holds_no_sort(tiny):
    """The sort of the vocabulary cannot come back unseen: no `sort`
    equation anywhere in a dense decoder's step, walked through the seam
    (`analysis/jaxpr.py:walk_eqns` enters whatever carries a sub-program:
    tests/test_jaxpr_seam.py). The same walk does see a sort where there
    is one, and does reach the sampling stage's draw."""
    from ray_lightning_tpu.analysis.jaxpr import walk_eqns
    from ray_lightning_tpu.serve.audit import trace_decode_step

    cfg, _, _, _ = tiny
    closed, _ = trace_decode_step(cfg, EngineConfig(
        capacity=4, block_size=4, blocks_per_slot=8, prefill_chunk=4))
    seen = {eqn.primitive.name for eqn, _ in walk_eqns(closed.jaxpr)}
    assert "sort" not in seen
    assert {"argmax", "random_bits"} <= seen
    sorted_somewhere = jax.make_jaxpr(jax.jit(
        lambda x: jax.lax.cond(x[0] > 0, jnp.sort, jnp.negative, x)))(
            jnp.zeros(8))
    assert "sort" in {eqn.primitive.name
                      for eqn, _ in walk_eqns(sorted_somewhere.jaxpr)}


def test_churn_never_recompiles(tiny, engine):
    """Admission/retirement across waves of requests is pure runtime
    data: the step stays ONE compiled program."""
    cfg, model, params, prompts = tiny
    before = engine.compile_count
    sched = Scheduler(engine)
    for wave in range(3):
        reqs = [Request(rid=f"w{wave}-{i}", prompt=prompts[i][0],
                        max_new_tokens=2 + wave, seed=wave * 10 + i)
                for i in range(4)]
        _drain(sched, submit=reqs)
    assert engine.compile_count == before == 1


def test_trainer_committed_params_compile_once(tiny):
    """Trainer-produced params arrive COMMITTED (NamedSharding over the
    training mesh). The engine canonicalizes weight placement and
    commits its own buffers, so the donated signature never flips after
    the first tick — without that, the fine-tune -> serve flow compiled
    a phantom second executable (caught by the install-drive, pinned
    here)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    cfg, model, params, prompts = tiny
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    committed = jax.device_put(
        params, NamedSharding(mesh, PartitionSpec()))
    eng = DecodeEngine(model, committed, EngineConfig(
        capacity=2, block_size=4, blocks_per_slot=8, prefill_chunk=4))
    eng.warmup()
    sched = Scheduler(eng)
    out = _drain(sched, submit=[Request(
        rid="c", prompt=prompts[0][0], max_new_tokens=5)])
    ref = np.asarray(generate(model, params, prompts[0], 5))[0]
    np.testing.assert_array_equal(np.array(out["c"].tokens), ref)
    assert eng.compile_count == 1


def test_batch_order_invariance(tiny, engine):
    """Per-slot RNG: the same request produces the same tokens no
    matter which slot it lands in or who its neighbors are."""
    cfg, model, params, prompts = tiny
    req = dict(prompt=prompts[3][0], max_new_tokens=5, temperature=0.9,
               top_k=4, seed=77)
    runs = []
    for order in ((0, 1, 2), (2, 1, 0)):
        sched = Scheduler(engine)
        reqs = [Request(rid=f"n{j}", prompt=prompts[j][0],
                        max_new_tokens=5, seed=j) for j in order]
        reqs.insert(1, Request(rid="probe", **req))
        out = _drain(sched, submit=reqs, stagger=False)
        runs.append(out["probe"].tokens)
    assert runs[0] == runs[1]


def test_eos_retires_slot(tiny, engine):
    """EOS mid-stream retires the slot; tokens up to and including EOS
    are kept and match the generate() prefix."""
    cfg, model, params, prompts = tiny
    ref = np.asarray(generate(model, params, prompts[0], 8))[0]
    eos = int(ref[2])  # force an early stop at the 3rd token
    sched = Scheduler(engine)
    out = _drain(sched, submit=[Request(
        rid="e", prompt=prompts[0][0], max_new_tokens=8, eos_id=eos)])
    comp = out["e"]
    assert comp.finish_reason == "eos"
    assert comp.tokens == list(ref[:3])


def test_completion_latency_fields(tiny, engine):
    cfg, model, params, prompts = tiny
    sched = Scheduler(engine)
    out = _drain(sched, submit=[Request(
        rid="m", prompt=prompts[0][0], max_new_tokens=4)])
    comp = out["m"]
    assert comp.ttft_s > 0 and comp.decode_s >= 0
    assert comp.tpot_s >= 0 and comp.queue_wait_s >= 0
    assert 0 < sched.slot_occupancy <= 1


# ---- scheduler lifecycle ---------------------------------------------------


def test_admission_defers_when_pool_short(tiny):
    """Worst-case reservation: requests queue (FIFO preserved) until
    blocks free up; everything still completes correctly."""
    cfg, model, params, prompts = tiny
    # pool of 9 usable blocks: one 24-token worst case = 6 blocks, so
    # only one request fits at a time
    eng = DecodeEngine(model, params, EngineConfig(
        capacity=4, block_size=4, blocks_per_slot=6, n_blocks=10,
        prefill_chunk=4))
    eng.warmup()
    sched = Scheduler(eng)
    reqs = [Request(rid=f"q{i}", prompt=prompts[i][0],
                    max_new_tokens=18, seed=i) for i in range(3)]
    out = _drain(sched, submit=reqs, stagger=False)
    assert set(out) == {"q0", "q1", "q2"}
    assert all(len(c.tokens) == 18 for c in out.values())


def test_on_demand_growth_and_preemption(tiny):
    """on_demand mode allocates per block boundary; when the pool runs
    dry mid-decode the youngest slot is preempted and REPLAYED — same
    seed, same tokens, just later."""
    cfg, model, params, prompts = tiny
    eng = DecodeEngine(model, params, EngineConfig(
        capacity=2, block_size=4, blocks_per_slot=8, n_blocks=9,
        prefill_chunk=4))
    eng.warmup()
    sched = Scheduler(eng, reserve="on_demand")
    reqs = [Request(rid=f"p{i}", prompt=prompts[4][0],
                    max_new_tokens=20, seed=50 + i) for i in range(2)]
    out = _drain(sched, submit=reqs, stagger=False)
    refs = {f"p{i}": np.asarray(generate(
        model, params, prompts[4], 20, seed=50 + i))[0]
        for i in range(2)}
    preempts = sum(c.preempted for c in out.values())
    assert preempts >= 1, "the dry pool never forced a preemption"
    # the documented invariant: the OLDEST request is never evicted
    assert out["p0"].preempted == 0, \
        "the oldest request was preempted — the drain guarantee broke"
    for rid, c in out.items():
        np.testing.assert_array_equal(np.array(c.tokens), refs[rid],
                                      err_msg=f"{rid} corrupted by "
                                      "preemption")


def test_prefill_chunk_not_dividing_slot_len(tiny):
    """Review regression: a prefill chunk that does not divide
    max_slot_len used to slide past the slot end on the tail chunk —
    the clamped cache update and pool scatter scribbled REAL prompt
    entries and decode silently diverged from generate(). The window
    now slides back instead (re-sent rows recompute identical K/V)."""
    cfg, model, params, _ = tiny
    eng = DecodeEngine(model, params, EngineConfig(
        capacity=1, block_size=4, blocks_per_slot=8, prefill_chunk=5))
    eng.warmup()
    prompt = np.array(jax.random.randint(
        jax.random.key(123), (1, 31), 0, cfg.vocab_size), dtype=np.int32)
    sched = Scheduler(eng)
    out = _drain(sched, submit=[Request(rid="t", prompt=prompt[0],
                                        max_new_tokens=1)])
    ref = np.asarray(generate(model, params, prompt, 1))[0]
    np.testing.assert_array_equal(np.array(out["t"].tokens), ref)
    with pytest.raises(ValueError, match="prefill_chunk"):
        EngineConfig(capacity=1, block_size=4, blocks_per_slot=2,
                     prefill_chunk=16)


def test_driver_outputs_exact_after_preemption(tiny):
    """Review regression: a scheduler-level preemption replays the
    stream from scratch, and the DRIVER's token stream must drop the
    pre-preemption prefix — outputs used to hold prefix + full replay."""
    from ray_lightning_tpu.serve.driver import (
        ReplicaGroupConfig, ServeDriver,
    )

    cfg, model, params, prompts = tiny
    reqs = [Request(rid=f"d{i}", prompt=prompts[4][0],
                    max_new_tokens=20, seed=70 + i) for i in range(2)]
    drv = ServeDriver(cfg, params, ReplicaGroupConfig(
        n_replicas=1, backend="inline", reserve="on_demand",
        engine=EngineConfig(capacity=2, block_size=4, blocks_per_slot=8,
                            n_blocks=9, prefill_chunk=4)))
    res = drv.run(reqs)
    assert any(m["preempted"] for m in res.meta.values()), \
        "the dry pool never preempted — the regression is untested"
    for i, r in enumerate(reqs):
        ref = np.asarray(generate(model, params, prompts[4], 20,
                                  seed=r.seed))[0]
        np.testing.assert_array_equal(np.array(res.outputs[r.rid]), ref,
                                      err_msg=r.rid)


def test_submit_rejects_oversized_request(tiny, engine):
    cfg, model, params, prompts = tiny
    sched = Scheduler(engine)
    with pytest.raises(ValueError, match="max_slot_len"):
        sched.submit(Request(rid="big", prompt=np.zeros(20, np.int32),
                             max_new_tokens=1000))


def test_engine_rejects_cache_beyond_rope(tiny):
    cfg, model, params, _ = tiny
    with pytest.raises(ValueError, match="max_seq_len"):
        DecodeEngine(model, params, EngineConfig(
            capacity=1, block_size=64,
            blocks_per_slot=cfg.max_seq_len // 64 + 1))


# ---- audit + plan ----------------------------------------------------------


def test_decode_step_audits_clean(tiny):
    """The acceptance pin: no RLT301 (the paged gather is explicit and
    masked, not an implicit reshard) and no RLT303 on the decode step."""
    from ray_lightning_tpu.serve.audit import audit_decode_step

    cfg, _, _, _ = tiny
    report = audit_decode_step(cfg, EngineConfig(
        capacity=4, block_size=4, blocks_per_slot=8, prefill_chunk=4),
        topology="v5p-8")
    rules = {f.rule for f in report.findings}
    assert "RLT301" not in rules and "RLT303" not in rules
    assert report.peak_hbm_bytes > 0


def test_serve_memory_summary_prices_pool(tiny):
    from ray_lightning_tpu.serve.audit import serve_memory_summary

    cfg, _, _, _ = tiny
    ecfg = EngineConfig(capacity=4, block_size=4, blocks_per_slot=8)
    s = serve_memory_summary(cfg, ecfg, device_kind="TPU v5p")
    assert s["pool_bytes"] == pool_bytes(cfg, ecfg.pool_spec)
    assert s["per_device_bytes"] >= (s["params_bytes"] + s["pool_bytes"]
                                     + s["gathered_view_bytes"])
    assert s["fits"] is True


def test_plan_serve_cli(capsys):
    from ray_lightning_tpu.__main__ import main

    rc = main(["plan", "--preset", "tiny", "--serve", "--seq", "64",
               "--serve-slots", "2", "--no-trace", "--json"])
    import json

    out = json.loads(capsys.readouterr().out.strip())
    assert rc == 0
    assert out["fits"] is True
    assert out["serve"]["pool_bytes"] > 0


def test_plan_serve_does_not_fit_exit_1(capsys):
    from ray_lightning_tpu.__main__ import main

    rc = main(["plan", "--preset", "llama3-8b", "--serve", "--seq",
               "8192", "--serve-slots", "64", "--no-trace",
               "--hbm-bytes", str(2 * 1024**3), "--json"])
    assert rc == 1


# ---- bench serving leg -----------------------------------------------------


def test_bench_serving_leg_schema():
    import bench

    r = bench._measure_serving(tiny=True, autoscale=False)
    for key in ("decode_tokens_per_s", "ttft_cold_s", "ttft_warm_s",
                "slot_occupancy"):
        assert key in r, key
    assert r["decode_tokens_per_s"] > 0
    assert r["ttft_warm_s"] < r["ttft_cold_s"]  # compile paid once
    assert 0 < r["slot_occupancy"] <= 1
    assert r["serving_compile_count"] in (1, -1)


def test_bench_serve_summary_static():
    import bench

    s = bench._serve_summary()
    assert "serving" in s, s.get("serving_error")
    assert s["serving"]["flagship_plan"]["pool_bytes"] > 0
    assert set(s["serving"]["schema"]) == {
        "decode_tokens_per_s", "prefill_tokens_per_s",
        "ttft_cold_s", "ttft_warm_s", "ttft_p99_s", "slot_occupancy",
        "shared_block_fraction", "accepted_tokens_per_step",
        "serving_attention_path", "serving_prefill_path",
        "serve_metrics", "scale_up_s", "autoscale",
        "slo_attainment", "slo_attainment_latency_critical",
        "shed_fraction"}
    # the ISSUE 19 static pricing blocks ride every line
    assert s["serving"]["prefix_plan"]["shared_pool_bytes_saved"] > 0
    assert s["serving"]["prefix_plan"]["prefill_tokens_saved"] > 0
    sp = s["serving"]["speculative_plan"]
    assert sp["verify_step_flops"] == \
        sp["k"] * sp["base_decode_flops_per_token"]
    assert sp["expected_tokens_per_tick"] > 1.0
    # the TP=2 sharded-replica section (ISSUE 18): per-shard HBM halves
    # the replicated plan's params, and the decode collective schedule
    # carries the gate-ratcheted per-tick wire total
    tp = s["serve_tp"]
    assert tp["tp"] == 2
    # per-shard params: the sharded leaves halve, the (tiny) replicated
    # norm scales don't — so just over full/2, never more than 51%
    full = s["serving"]["flagship_plan"]["params_bytes"]
    assert full / 2 <= tp["params_bytes_per_shard"] < full * 0.51
    assert tp["hbm_bytes_per_shard"] < s["serve_hbm_bytes_per_replica"]
    assert tp["decode_ici_bytes_per_tick"] == \
        s["serve_decode_ici_bytes_per_tick"] == \
        sum(c["wire_bytes"] for c in tp["collectives"]) > 0
    kinds = {c["kind"] for c in tp["collectives"]}
    assert "psum" in kinds and "all_gather" in kinds


def test_bench_gate_ratchets_serving(tmp_path):
    """decode_tokens_per_s ratchets (measured: waived on skip lines);
    ttft_warm_s is upper-bounded on measured lines."""
    import importlib
    import os
    import sys

    scripts = os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts")
    if scripts not in sys.path:
        sys.path.insert(0, scripts)
    bench_gate = importlib.import_module("bench_gate")
    best = {"decode_tokens_per_s": (100.0, "BENCH_r09.json")}
    ok = {"metric": "m", "value": 1.0, "decode_tokens_per_s": 99.0,
          "ttft_warm_s": 0.5}
    assert bench_gate.gate(ok, best, tolerance=0.05) == []
    slow = {"metric": "m", "value": 1.0, "decode_tokens_per_s": 50.0}
    assert any("decode_tokens_per_s" in f
               for f in bench_gate.gate(slow, best, tolerance=0.05))
    laggy = {"metric": "m", "value": 1.0, "decode_tokens_per_s": 100.0,
             "ttft_warm_s": 99.0}
    assert any("ttft_warm_s" in f
               for f in bench_gate.gate(laggy, best, tolerance=0.05))
    skip = {"metric": "m", "value": 0.0, "skipped": "backend unavailable"}
    assert bench_gate.gate(skip, best, tolerance=0.05) == []
    # serve_decode_ici_bytes_per_tick CEILING-ratchets (static: holds on
    # skip lines too); growth fails, a serving_error line waives absence
    ceil = {"serve_decode_ici_bytes_per_tick": (1000.0, "BENCH_r09.json")}
    flat = dict(skip, serve_decode_ici_bytes_per_tick=1000.0)
    assert bench_gate.gate(flat, {}, tolerance=0.05, ceilings=ceil) == []
    grew = dict(skip, serve_decode_ici_bytes_per_tick=2000.0)
    assert any("serve_decode_ici_bytes_per_tick" in f
               for f in bench_gate.gate(grew, {}, tolerance=0.05,
                                        ceilings=ceil))
    dropped = dict(skip)
    assert any("dropped the field" in f
               for f in bench_gate.gate(dropped, {}, tolerance=0.05,
                                        ceilings=ceil))
    waived = dict(skip, serving_error="IndexError: boom")
    assert bench_gate.gate(waived, {}, tolerance=0.05,
                           ceilings=ceil) == []

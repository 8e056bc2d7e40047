"""The names the program gives its work in a profiler trace
(docs/OBSERVABILITY.md "names in a trace"): every Pallas kernel's fixed
`name=`, the `jax.named_scope`s inside the two jitted step programs, and
the host phases `telemetry/spans.py:annotate` opens as `rlt.*` events on
the profiler's clock, with the counters `rlt.serve.dispatch` carries.
The benchmark's readers (`benchmarks/harness/program_trace.py`) find the
program's work by these names alone, so a rename has to fail here first.
"""
import contextlib
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu import DataLoader, Trainer
from ray_lightning_tpu.models.llama import Llama, LlamaConfig, LlamaModule
from ray_lightning_tpu.ops import dispatch
from ray_lightning_tpu.serve.driver import ReplicaGroupConfig, ServeDriver
from ray_lightning_tpu.serve.engine import EngineConfig, idle_prefill
from ray_lightning_tpu.serve.scheduler import Request
from ray_lightning_tpu.telemetry.spans import read_spans

KERNELS = ("rlt_flash_fwd", "rlt_flash_bwd_dkdv", "rlt_flash_bwd_dq",
           "rlt_paged_decode", "rlt_paged_prefill", "rlt_rmsnorm")
SERVE_PHASES = ("admit", "grow", "build", "put", "dispatch", "fetch",
                "account")


def _tiny_cfg(**kw) -> LlamaConfig:
    """Kernel-tiling tiny model (head_dim 64): the paged kernels refuse the
    suite's usual head_dim 16."""
    return LlamaConfig(**{**dict(
        vocab_size=256, dim=128, n_layers=2, n_heads=2, n_kv_heads=1,
        hidden_dim=256, max_seq_len=128, remat=False, dtype=jnp.float32),
        **kw})


# ---- (a) kernels ------------------------------------------------------------


def _pallas_names(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for v in eqn.params.values():
            for x in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(x, "jaxpr", x)
                if hasattr(inner, "eqns"):
                    _pallas_names(inner, out)
    return out


@pytest.fixture(scope="module")
def kernel_names():
    """Names of the pallas_calls in the jaxpr of each kernel wrapper."""
    from ray_lightning_tpu.ops.pallas.flash import flash_attention_pallas
    from ray_lightning_tpu.ops.pallas.paged_attention import (
        paged_attention_pallas,
    )
    from ray_lightning_tpu.ops.pallas.paged_prefill import (
        paged_prefill_pallas,
    )
    from ray_lightning_tpu.ops.pallas.rmsnorm import rms_norm_pallas

    q = jnp.ones((1, 128, 2, 64))
    kv = jnp.ones((1, 128, 1, 64))
    pool = jnp.ones((8, 8, 1, 64))
    tables = jnp.zeros((2, 4), jnp.int32)
    found = {}
    found["flash"] = _pallas_names(jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash_attention_pallas(q, k, v).sum(),
        argnums=(0, 1, 2)))(q, kv, kv).jaxpr, [])
    found["decode"] = _pallas_names(jax.make_jaxpr(paged_attention_pallas)(
        jnp.ones((2, 2, 64)), pool, pool, tables,
        jnp.ones(2, jnp.int32)).jaxpr, [])
    found["prefill"] = _pallas_names(jax.make_jaxpr(paged_prefill_pallas)(
        jnp.ones((1, 8, 2, 64)), pool, pool, tables[:1],
        jnp.int32(0)).jaxpr, [])
    found["rmsnorm"] = _pallas_names(jax.make_jaxpr(rms_norm_pallas)(
        jnp.ones((8, 128)), jnp.ones(128)).jaxpr, [])
    return found


@pytest.mark.parametrize("wrapper,name", [
    ("flash", "rlt_flash_fwd"), ("flash", "rlt_flash_bwd_dkdv"),
    ("flash", "rlt_flash_bwd_dq"), ("decode", "rlt_paged_decode"),
    ("prefill", "rlt_paged_prefill"), ("rmsnorm", "rlt_rmsnorm")])
def test_pallas_call_carries_its_fixed_name(kernel_names, wrapper, name):
    assert name in kernel_names[wrapper], kernel_names[wrapper]
    assert set(kernel_names[wrapper]) <= set(KERNELS)


# ---- the two step programs, driven under a profiler session ------------------


def _host_events(trace_dir):
    """Every `rlt.*` event of the trace's host plane:
    {name, start, end, stats, thread}."""
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(max(found, key=os.path.getmtime))
    out = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        # one line a host thread (their names repeat: "python")
        for thread, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("rlt."):
                    out.append({"name": ev.name, "start": ev.start_ns,
                                "end": ev.start_ns + ev.duration_ns,
                                "stats": dict(ev.stats), "thread": thread})
    return sorted(out, key=lambda e: e["start"])


def _start_trace(directory):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(directory), profiler_options=options)


def _fit(root, telemetry):
    cfg = _tiny_cfg(vocab_size=512, fused_ce=True, ce_chunk_tokens=32,
                    use_flash=False)
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (6, 33)).astype(np.int32)
    trainer = Trainer(
        max_epochs=1, max_steps=3, log_every_n_steps=1,
        enable_checkpointing=False, enable_progress_bar=False, seed=0,
        telemetry=telemetry, default_root_dir=str(root))
    trainer.fit(LlamaModule(cfg, warmup_steps=1, total_steps=10),
                DataLoader({"tokens": tokens}, batch_size=2))
    return trainer, tokens


def _expected_work(sched, ecfg):
    """What the next tick's `rlt.serve.dispatch` must carry, from the
    scheduler's own state (its queue is empty, so the tick admits nothing)."""
    from ray_lightning_tpu.ops.pallas.paged_attention import (
        decode_tile_tokens,
    )
    from ray_lightning_tpu.ops.pallas.paged_prefill import (
        prefill_tile_shape,
    )

    dec = sched.decoding
    lengths = [int(sched.pos[s]) + 1 for s in range(ecfg.capacity) if dec[s]]
    tile = decode_tile_tokens(ecfg.block_size, ecfg.blocks_per_slot)
    sampled = dec & (sched.temp > 0)
    want = {"decode_slots": int(dec.sum()),
            "sampled_slots": int(sampled.sum()),
            "topk_slots": int((sampled & (sched.top_k > 0)).sum()),
            "kv_tokens": sum(lengths),
            # the kernel's own tile: a slot costs ceil(length / tile)
            "decode_tiles": sum(-(-n // tile) for n in lengths),
            "prefill_rows": 0, "prefill_ctx": 0, "prefill_tiles": 0,
            # the dense decoder joins its lanes: a chunk rides the decode
            # lane's pass whole, its padding too
            "joined_rows": ecfg.prefill_chunk if sched.prefill_groups else 0}
    if sched.prefill_groups:
        slot = sched.slots[sched.prefill_groups[0].slots[0]]
        done, size = slot.prefill_next, slot.req.prompt.size
        # the engine's window never crosses the slot's end (Scheduler.
        # _build_prefill slides it back), and its rows past the prompt's
        # end are padding
        start = min(done, ecfg.max_slot_len - ecfg.prefill_chunk)
        want["prefill_rows"] = min(ecfg.prefill_chunk, size - start)
        want["prefill_ctx"] = start
        # the kernel's own tiles: query tile qi of the chunk sees the KV
        # tiles that hold a position below start + (qi + 1) * bq
        mcfg = sched.engine.model.cfg
        bq, tile = prefill_tile_shape(
            (1, ecfg.prefill_chunk, mcfg.n_heads, mcfg.head_dim),
            (ecfg.block_size, mcfg.n_kv_heads, mcfg.head_dim),
            ecfg.blocks_per_slot)
        want["prefill_tiles"] = sum(
            -(-min(start + (qi + 1) * bq, ecfg.max_slot_len) // tile)
            for qi in range(ecfg.prefill_chunk // bq))
    return want


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One profiler session over three driver ticks of a tiny engine and a
    three-step fit with telemetry off, a second over the same fit with
    telemetry on; plus both programs' lowered text with debug info."""
    root = tmp_path_factory.mktemp("trace_names")
    cfg = _tiny_cfg()
    model = Llama(cfg)
    params = jax.jit(model.init)(jax.random.key(3),
                                 jnp.zeros((1, 4), jnp.int32))["params"]
    ecfg = EngineConfig(capacity=4, block_size=8, blocks_per_slot=4,
                        prefill_chunk=8)
    driver = ServeDriver(cfg, params, ReplicaGroupConfig(
        n_replicas=1, backend="inline", engine=ecfg, metrics=False))
    with dispatch.force_pallas():
        driver.start()
    sched = driver.replicas[0].sched
    engine = driver.replicas[0].engine
    rng = np.random.default_rng(1)
    # r0 decodes through the traced ticks, so it is the one that draws
    for i, (n, temp) in enumerate([(5, 0.8), (19, 0.0), (3, 0.0)]):
        driver.submit(Request(
            rid=f"r{i}", prompt=rng.integers(0, 256, n).astype(np.int32),
            max_new_tokens=12, temperature=temp, top_k=5 if temp else None,
            seed=i))
    driver.tick()                      # admits all three; r0's one chunk
    assert not sched.queue
    warm, _ = _fit(root / "warm", telemetry=False)   # compiles land here

    _start_trace(root / "off")
    expected, tick_ids = [], []
    for _ in range(3):
        expected.append(_expected_work(sched, ecfg))
        tick_ids.append(sched._ticks)
        driver.tick()
    _fit(root / "fit_off", telemetry=False)
    jax.profiler.stop_trace()

    _start_trace(root / "on")
    on, tokens = _fit(root / "fit_on", telemetry=True)
    jax.profiler.stop_trace()
    ring = []
    for path in glob.glob(str(root / "fit_on" / "**" / "*.spans.jsonl"),
                          recursive=True):
        ring += read_spans(path)["spans"]

    batch = warm._place_train_batch({"tokens": tokens[:2]})[1]
    train_text = warm._train_step._jitted.lower(
        warm.state, batch, warm._base_rng).as_text(debug_info=True)
    serve_text = engine.lower_idle().as_text(debug_info=True)
    assert (engine.attention_path, engine.prefill_path) == (
        "paged-pallas", "paged-pallas")
    driver.stop(drain=False)
    return {"off": _host_events(str(root / "off")),
            "on": _host_events(str(root / "on")), "ring": ring,
            "expected": expected, "tick_ids": tick_ids,
            "train_text": train_text, "serve_text": serve_text,
            # tables, four words a slot, its two RNG words and the flag
            # that says they are fresh, the chunk, and three scalars, four
            # bytes each
            "h2d_bytes": 4 * (ecfg.capacity * (ecfg.blocks_per_slot + 7)
                              + ecfg.prefill_chunk + 3)}


# ---- (b) scopes -------------------------------------------------------------


@pytest.mark.parametrize("program,scope", [
    ("train", "fused_ce"), ("train", "optimizer"), ("train", "attn"),
    ("train", "mlp"), ("serve", "kv_pool"), ("serve", "sample"),
    ("serve", "lm_head"), ("serve", "attn"), ("serve", "mlp"),
    ("serve", "rlt_paged_decode"), ("serve", "rlt_paged_prefill")])
def test_step_program_names_its_scope(traced, program, scope):
    """The scope is a component of some op's name stack (a transform may
    wrap it: `transpose(jvp(fused_ce))`)."""
    import re

    text = traced[program + "_text"]
    assert re.search(r'loc\("[^"]*[/(]' + re.escape(scope) + r'[/)"]', text), \
        f"no op of the {program} step carries the scope {scope!r}"


# ---- (c) host phases on the profiler's clock --------------------------------


def _inside(child, parent):
    return (child["thread"] == parent["thread"]
            and parent["start"] <= child["start"]
            and child["end"] <= parent["end"])


def _named(events, name):
    return [e for e in events if e["name"] == name]


@pytest.mark.parametrize("phase", SERVE_PHASES)
def test_serve_phase_nests_in_its_tick(traced, phase):
    ticks = _named(traced["off"], "rlt.serve.tick")
    assert [t["stats"]["tick"] for t in ticks] == traced["tick_ids"]
    for tick in ticks:
        inside = [e for e in _named(traced["off"], "rlt.serve." + phase)
                  if _inside(e, tick)]
        assert len(inside) == 1, (phase, tick, inside)


def test_serve_phases_run_in_order_inside_a_tick(traced):
    for tick in _named(traced["off"], "rlt.serve.tick"):
        order = [e["name"].rsplit(".", 1)[1] for e in traced["off"]
                 if e["name"] != tick["name"] and _inside(e, tick)]
        assert order == list(SERVE_PHASES)


@pytest.mark.parametrize("phase", ["route", "collect"])
def test_driver_phase_brackets_the_scheduler_tick(traced, phase):
    ticks = _named(traced["off"], "rlt.serve.tick")
    events = _named(traced["off"], "rlt.serve." + phase)
    assert len(events) == len(ticks) == 3
    for ev, tick in zip(events, ticks):
        assert ev["thread"] == tick["thread"]
        if phase == "route":
            assert ev["end"] <= tick["start"]
        else:
            assert ev["start"] >= tick["end"]


@pytest.mark.parametrize("counter", ["decode_slots", "kv_tokens",
                                     "decode_tiles", "prefill_rows",
                                     "prefill_ctx", "prefill_tiles",
                                     "sampled_slots", "topk_slots",
                                     "joined_rows"])
def test_dispatch_counters_equal_the_schedulers_own(traced, counter):
    """The context convention must not over-count: a roofline share over
    105% is refused by the benchmark's driver."""
    got = [e["stats"][counter]
           for e in _named(traced["off"], "rlt.serve.dispatch")]
    want = [w[counter] for w in traced["expected"]]
    assert got == want
    # the three ticks hold decode-only work, a whole chunk behind cached
    # context and a partial last chunk, so each counter is exercised
    assert any(want), (counter, want)


@pytest.mark.parametrize("phase,counter", [
    ("put", "h2d_arrays"), ("put", "h2d_bytes"), ("fetch", "d2h_arrays")])
def test_a_tick_crosses_to_the_device_once_each_way(traced, phase, counter):
    """`rlt.serve.put` and `.fetch` say what crossed: one packed array
    each way, of the size the engine's layout gives."""
    got = [e["stats"][counter]
           for e in _named(traced["off"], "rlt.serve." + phase)]
    assert got == [traced["h2d_bytes"] if counter == "h2d_bytes" else 1] * 3


def test_a_step_is_dispatched_before_the_one_before_it_is_read(traced):
    """Every traced tick sends its step ahead (`ahead=1`: the previous
    step's result was unread), reads after it has dispatched, on one
    thread, and throws no token away."""
    for tick in _named(traced["off"], "rlt.serve.tick"):
        (sent,) = [e for e in _named(traced["off"], "rlt.serve.dispatch")
                   if _inside(e, tick)]
        (read,) = [e for e in _named(traced["off"], "rlt.serve.fetch")
                   if _inside(e, tick)]
        (account,) = [e for e in _named(traced["off"], "rlt.serve.account")
                      if _inside(e, tick)]
        assert sent["stats"]["ahead"] == 1
        assert sent["end"] <= read["start"] <= read["end"] \
            <= account["start"]
        assert sent["thread"] == read["thread"] == tick["thread"]
        assert account["stats"]["tokens_dropped"] == 0


@pytest.mark.parametrize("phase,thread_of,count", [
    ("dispatch", "main", 3), ("metrics_fetch", "main", 3),
    ("data_wait", "main", 3), ("h2d", "producer", 3)])
def test_trainer_phase_is_on_the_profilers_clock(traced, phase, thread_of,
                                                 count):
    """Telemetry off: the ring records nothing, the annotations are
    there all the same."""
    events = _named(traced["off"], "rlt." + phase)
    assert len(events) >= count, [e["name"] for e in traced["off"]]
    main = _named(traced["off"], "rlt.dispatch")[0]["thread"]
    for ev in events:
        assert (ev["thread"] == main) == (thread_of == "main")
    if phase == "dispatch":
        assert [e["stats"]["step"] for e in events] == [0, 1, 2]
    if phase == "h2d":
        assert [e["stats"]["batch"] for e in events][:3] == [0, 1, 2]


def test_a_step_waits_for_data_then_dispatches(traced):
    """`rlt.data_wait` of step k ends before `rlt.dispatch` of step k
    begins: the consumer's wait is the seam before the dispatch."""
    waits = _named(traced["off"], "rlt.data_wait")
    for disp in _named(traced["off"], "rlt.dispatch"):
        before = [w for w in waits if w["end"] <= disp["start"]]
        assert before and before[-1]["thread"] == disp["thread"]


# ---- (d) the ring and the trace agree ---------------------------------------


@pytest.mark.parametrize("phase", ["dispatch", "metrics_fetch", "compile",
                                   "h2d"])
def test_ring_span_and_annotation_are_one_interval(traced, phase):
    ring = [s for s in traced["ring"] if s["phase"] == phase]
    events = _named(traced["on"], "rlt." + phase)
    assert ring and len(ring) == len(events), (ring, events)
    for span, ev in zip(sorted(ring, key=lambda s: s["t"]), events):
        assert ev["stats"].get("step") == span["step"]
        # the annotation encloses the ring's two clock reads
        assert (ev["end"] - ev["start"]) * 1e-9 >= span["dur"] - 2e-6


def test_no_session_records_nothing(tmp_path):
    """Outside a profiler session the annotations leave no trace: a later
    session holds only what ran inside it."""
    from ray_lightning_tpu.telemetry.spans import annotate

    with annotate("serve.tick", tick=-1):
        pass
    _start_trace(tmp_path)
    with annotate("serve.tick", tick=7):
        pass
    jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    assert [e["stats"] for e in events] == [{"tick": 7}]


# ---- names are metadata: the programs do not change -------------------------


class _NoScope(contextlib.ContextDecorator):
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _lower_train():
    cfg = _tiny_cfg(vocab_size=512, fused_ce=True, ce_chunk_tokens=32,
                    use_flash=False)
    module = LlamaModule(cfg, warmup_steps=1, total_steps=10)
    trainer = Trainer(enable_checkpointing=False, enable_progress_bar=False,
                      seed=0)
    trainer._base_rng = jax.random.key(0)
    trainer.module = module
    trainer.strategy.setup(module)
    module.setup()
    batch = {"tokens": np.zeros((2, 33), np.int32)}
    trainer.tx = trainer._build_tx(module)
    trainer.state = trainer._init_state(module, batch, None)
    step = trainer._make_train_step(module)
    placed = trainer._place_train_batch(batch)[1]
    return step._jitted.lower(trainer.state, placed,
                              trainer._base_rng).as_text()


def _lower_serve():
    from ray_lightning_tpu.serve.engine import build_step

    cfg = _tiny_cfg()
    model = Llama(cfg)
    ecfg = EngineConfig(capacity=4, block_size=8, blocks_per_slot=4,
                        prefill_chunk=8)
    params = jax.eval_shape(model.init, jax.random.key(3),
                            jnp.zeros((1, 4), jnp.int32))["params"]
    C, spec = ecfg.capacity, ecfg.pool_spec
    pool = jax.ShapeDtypeStruct(
        (cfg.n_layers, spec.n_blocks, spec.block_size, cfg.n_kv_heads,
         cfg.head_dim), cfg.dtype)
    with dispatch.force_pallas():
        return jax.jit(build_step(model, ecfg, fused=True,
                                  fused_prefill=True)).lower(
            params, pool, pool,
            jax.ShapeDtypeStruct((C, cfg.vocab_size), jnp.float32),
            jnp.zeros((C, spec.blocks_per_slot), jnp.int32),
            jnp.zeros(C, jnp.int32), jnp.zeros(C, bool),
            jnp.zeros(C, jnp.float32), jnp.zeros(C, jnp.int32),
            jnp.zeros((C, 2), jnp.uint32),
            *map(jnp.asarray, idle_prefill(ecfg))).as_text()


@pytest.mark.parametrize("lower", [_lower_train, _lower_serve],
                         ids=["train", "serve"])
def test_names_are_metadata_and_the_program_is_the_same(monkeypatch, lower):
    """Beside the pins that telemetry and metrics, on or off, lower a
    byte-identical program (test_telemetry, test_serve_metrics): so do the
    scopes. The step lowered with every `jax.named_scope` a no-op (flax's
    own among them) is the step lowered with the names, location info
    apart."""
    import ray_lightning_tpu.models.llama as llama_mod
    from ray_lightning_tpu.ops import fused_ce

    named = lower()
    monkeypatch.setattr(jax, "named_scope", lambda name: _NoScope())
    monkeypatch.setattr(llama_mod, "fused_cross_entropy",
                        fused_ce.fused_cross_entropy.__wrapped__)
    assert lower() == named
    assert "stablehlo" in named and len(named) > 10_000

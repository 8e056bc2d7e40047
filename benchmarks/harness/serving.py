"""Serving runner shared by the `serve_open` and `serve_closed` kinds.

One process holds the chip: seeded bf16 weights made on the device, one
`ServeDriver` with inline replica(s), a warm-up that sends a few requests
through every host path, then the window. The generator and
`driver.tick()` share this thread, so a request is sent between ticks;
every request is timed from when it was DUE, and each token is stamped at
the end of the tick that emitted it (the tick fetches its tokens, so the
device has finished). Nothing is read from `Completion.ttft_s/tpot_s`.

After the window the program's state is freed and the plain reference runs
over a seeded sample of the greedy requests the window finished.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List, Optional

import numpy as np

from benchmarks.harness import common, trace, traffic_gen, weights
from benchmarks.harness.common import RunRecord, say


class _Req:
    __slots__ = ("plan", "due", "admitted", "times", "done", "client")

    def __init__(self, plan, due: float, client: int = -1):
        self.plan = plan
        self.due = due
        self.admitted: Optional[float] = None
        self.times: List[float] = []
        self.done: Optional[float] = None
        self.client = client


class _Session:
    """A started driver plus the benchmark's stamps around it."""

    def __init__(self, ctx: dict, adapter, hp: dict):
        from ray_lightning_tpu.serve.driver import (
            ReplicaGroupConfig, ServeDriver,
        )
        from ray_lightning_tpu.serve.engine import EngineConfig

        traffic = ctx["traffic"]
        t_a = time.perf_counter()
        self.cfg, self.params = adapter.serving_params(
            ctx["config"], hp, ctx["seed"])
        import jax

        jax.block_until_ready(self.params)
        t_b = time.perf_counter()
        self.driver = ServeDriver(self.cfg, self.params, ReplicaGroupConfig(
            n_replicas=int(traffic.get("replicas", 1)), backend="inline",
            engine=EngineConfig(**traffic["engine"]), metrics=False))
        self.driver.start()
        say("setup", before_weights_s=round(t_a - ctx["t_start"], 2),
            weights_s=round(t_b - t_a, 2),
            driver_start_s=round(time.perf_counter() - t_b, 2))
        self.spans = common.Spans()
        self.reqs: Dict[str, _Req] = {}
        self.waiting: set = set()
        self.preemptions = 0
        self.submit_late: List[float] = []

    @property
    def scheds(self):
        return [r.sched for r in self.driver.replicas.values()]

    def submit(self, plan, due: float, client: int = -1) -> None:
        from ray_lightning_tpu.serve.scheduler import Request

        now = time.perf_counter()
        self.driver.submit(Request(
            rid=plan.rid, prompt=plan.prompt,
            max_new_tokens=plan.max_new_tokens, temperature=plan.temperature,
            top_k=plan.top_k, seed=plan.seed, arrival=due))
        self.reqs[plan.rid] = _Req(plan, due, client)
        self.waiting.add(plan.rid)
        self.submit_late.append(now - due)

    def tick(self) -> List[_Req]:
        """One driver tick and its stamps; returns the requests it finished."""
        with self.spans.span("tick"):
            t_start = time.perf_counter()
            completions = self.driver.tick()
        with self.spans.span("stamp"):
            t_end = time.perf_counter()
            reqs = self.reqs
            for sched in self.scheds:
                for detail in sched.last_preemption_details:
                    # a preempted request replays from its prompt: the tokens
                    # a caller already has stay, later ones come again
                    self.preemptions += 1
                    del reqs[detail["rid"]].times[1:]
                if self.waiting:
                    for slot in sched.slots.values():
                        rid = slot.req.rid
                        if rid in self.waiting:
                            self.waiting.discard(rid)
                            reqs[rid].admitted = t_start
                for rid, _tok in sched.last_emissions:
                    reqs[rid].times.append(t_end)
            done = []
            for comp in completions:
                r = reqs[comp.rid]
                r.done = t_end
                if r.admitted is None:      # admitted and retired unseen
                    r.admitted = t_start
                    self.waiting.discard(comp.rid)
                done.append(r)
        return done

    def warm_up(self, vocab: int) -> None:
        """Three requests (greedy, sampled, top-k; one prompt longer than a
        chunk) through submit/tick/retire, so that no host path runs for the
        first time inside the window."""
        rng = np.random.default_rng(0)
        chunk = int(self.driver.cfg.engine.prefill_chunk)
        for i, (temp, top_k) in enumerate([(0.0, None), (0.8, None),
                                           (0.8, 40)]):
            plan = traffic_gen.PlannedRequest(
                rid=f"warm{i}", max_new_tokens=4, temperature=temp,
                top_k=top_k, seed=i,
                prompt=rng.integers(0, vocab, chunk + 8 + i).astype(np.int32))
            self.submit(plan, time.perf_counter())
        while self.driver.busy():
            self.tick()
        self.reqs.clear()
        self.waiting.clear()
        self.submit_late.clear()
        self.spans.rows.clear()
        self.preemptions = 0

    def close(self):
        """Stop the driver, abandoning what the drain limit left, and drop
        every device buffer of the program."""
        outputs = {rid: list(toks) for rid, toks in
                   self.driver.outputs.items()}
        engines = [r.engine for r in self.driver.replicas.values()]
        counts = [e.compile_count for e in engines]
        paths = [(e.attention_path, e.prefill_path) for e in engines]
        self.driver.stop(drain=False)
        self.driver = None
        self.params = None
        del engines
        gc.collect()
        return outputs, counts, paths


def run(ctx: dict, loop: str) -> RunRecord:
    adapter = ctx["adapter"]
    traffic, seconds = ctx["traffic"], float(ctx["seconds"])
    hp = adapter.hyperparams(ctx["config"], "serve")
    rec = RunRecord(kind=traffic["kind"], cell=ctx["cell"],
                    config=ctx["config"], traffic=traffic, hp=hp,
                    seconds=seconds, chips=ctx["chips"], peaks=ctx["peaks"],
                    root=ctx["root"])
    vocab = hp["vocab_size"]
    if loop == "open":
        plan = traffic_gen.open_loop(traffic, vocab, ctx["seed"], seconds)
    else:
        source = traffic_gen.ClosedLoopSource(traffic, vocab, ctx["seed"])
    sess = _Session(ctx, adapter, hp)
    sess.warm_up(vocab)
    compiles = ctx["compile_counter"]
    compiles_before = compiles.count
    recorder = trace.Recorder(ctx["trace_dir"]) if ctx["trace"] else None
    trace_s = float(traffic.get("trace_s", 3))
    drain_s = float(traffic["drain_s"])
    spans = sess.spans

    rec.setup_s = time.perf_counter() - ctx["t_start"]
    t0 = time.perf_counter()
    end = t0 + seconds
    nxt = 0
    if loop == "closed":
        for c in range(int(traffic["clients"])):
            sess.submit(source.next(), time.perf_counter(), c)
    trace_path = None
    backlog = {}
    while True:
        now = time.perf_counter()
        for mark in (0.5, 1.0):
            if mark not in backlog and now >= t0 + mark * seconds:
                backlog[mark] = sum(1 for r in sess.reqs.values()
                                    if not r.times)
        if recorder is not None:
            if not recorder.active and trace_path is None \
                    and now >= end - trace_s:
                recorder.start()
                spans.tracing = True
            elif recorder.active and now >= end:
                spans.tracing = False
                trace_path = recorder.stop() or ""
        if loop == "open" and nxt < len(plan):
            with spans.span("submit"):
                while nxt < len(plan) and t0 + plan[nxt].due_s <= now:
                    sess.submit(plan[nxt], t0 + plan[nxt].due_s)
                    nxt += 1
        if now >= end + drain_s:
            break
        if sess.driver.busy():
            for r in sess.tick():
                if loop == "closed" and time.perf_counter() < end:
                    with spans.span("submit"):
                        sess.submit(source.next(), time.perf_counter(),
                                    r.client)
        elif loop == "open" and nxt < len(plan):
            with spans.span("wait"):
                time.sleep(max(0.0, min(t0 + plan[nxt].due_s
                                        - time.perf_counter(), 0.002)))
        else:
            break
    t_stop = time.perf_counter()
    if recorder is not None and recorder.active:
        spans.tracing = False
        trace_path = recorder.stop() or ""
    rec.compiles_in_window = compiles.count - compiles_before
    rec.memory_peak_bytes = common.memory_peak_bytes(ctx["devices"])
    outputs, counts, paths = sess.close()
    if rec.compiles_in_window or any(c != 1 for c in counts):
        raise common.BenchError(
            f"compiled inside the window: {rec.compiles_in_window} backend "
            f"compile(s), engine compile_count {counts}")
    if traffic.get("require_pallas", True) and any(
            p != ("paged-pallas", "paged-pallas") for p in paths):
        raise common.BenchError(f"the engine left the fused lanes: {paths}")

    reqs = list(sess.reqs.values())
    rec.attempted = len(reqs)
    finished = [r for r in reqs if r.done is not None
                and len(outputs.get(r.plan.rid, ())) == r.plan.max_new_tokens]
    rec.failed = rec.attempted - len(finished)
    ttft = [(r.times[0] if r.times else t_stop) - r.due for r in reqs]
    gaps = np.concatenate([np.diff(r.times) for r in reqs
                           if len(r.times) > 1] or [np.zeros(0)])
    in_window = [r for r in finished if r.done <= end]
    tokens_done = credited_tokens(reqs, t0, end)
    rec.end_to_end = {
        "ttft_p95_ms": common.percentile(ttft, 95) * 1e3,
        "itl_p95_ms": common.percentile(gaps, 95) * 1e3 if gaps.size else 0.0,
        "serve_tokens_per_s": tokens_done / seconds,
    }
    ticks = spans.durations("tick", t0, end)
    waits = [r.admitted - r.due for r in reqs if r.admitted is not None]
    rec.stamps = {
        "t0": t0, "end": end, "ticks_s": ticks,
        "queue_wait_s": waits, "ttft_s": ttft, "itl_s": gaps,
        "preemptions": sess.preemptions,
        "tokens_out": int(sum(len(r.times) for r in reqs)),
        "requests_in_window": len(in_window),
    }
    rec.spans = spans
    late = sess.submit_late
    say("window", loop=loop, attempted=rec.attempted, finished=len(finished),
        failed=rec.failed, completed_in_window=len(in_window),
        ticks=len(ticks), preemptions=sess.preemptions,
        backlog_mid=backlog.get(0.5), backlog_end=backlog.get(1.0),
        tick_median_ms=round(1e3 * float(np.median(ticks)), 3),
        drain_s=round(t_stop - end, 3), lanes=paths[0],
        ttft_median_ms=round(common.percentile(ttft, 50) * 1e3, 3),
        ttft_n=len(ttft), itl_median_ms=round(
            common.percentile(gaps, 50) * 1e3, 3) if gaps.size else None,
        itl_n=int(gaps.size),
        submit_late_median_ms=round(common.percentile(late, 50) * 1e3, 3),
        submit_late_p95_ms=round(common.percentile(late, 95) * 1e3, 3))
    if trace_path:
        rec.trace = trace.load_xplane(trace_path, ctx["chips"])

    t_ref = time.perf_counter()
    sample = pick_sample(finished, int(traffic["check"]["n_requests"]),
                         ctx["seed"])
    rows_cap = -(-max(r.plan.max_new_tokens for r in reqs) // 128) * 128
    ref = common.load_model_file(ctx["root"], "reference",
                                 ctx["config"]["model"])
    check = check_tokens(ref, hp, ctx["seed"], sample, outputs,
                         control=ctx.get("control"), rows_cap=rows_cap)
    rec.reference_s = time.perf_counter() - t_ref
    limit = float(traffic["check"]["gap_limit"])
    rec.correct = bool(sample) and check["widest_gap"] <= limit
    say("check", number="widest_logit_gap", value=check["widest_gap"],
        limit=limit, tokens_compared=check["tokens"],
        requests_compared=len(sample), longest=check["longest"],
        reference_s=round(rec.reference_s, 2), correct=rec.correct)
    rec.stamps["check"] = check
    return rec


def credited_tokens(reqs: List[_Req], t0: float, end: float) -> float:
    """Prompt and generated tokens the window [t0, end] did, from the
    benchmark's own stamps. A generated token counts at its stamp. A prompt
    is prefilled by the one prefill lane, first come first served, between
    the previous request's first token and its own: its tokens are credited
    evenly over that stretch (from its own due time, where that is later),
    so a request that straddles the window's edge counts for the part inside
    and the rate does not jump by a whole prompt."""
    total = 0.0
    first = sorted((r for r in reqs if r.times), key=lambda r: r.times[0])
    prev = None
    for r in first:
        stop = r.times[0]
        start = max(r.due, prev) if prev is not None else r.due
        start = min(start, stop)
        span = stop - start
        inside = max(0.0, min(stop, end) - max(start, t0))
        share = inside / span if span > 0 else float(t0 <= stop <= end)
        total += r.plan.prompt.size * share
        total += sum(1 for t in r.times if t0 <= t <= end)
        prev = stop
    return total


# ---- the comparison with the plain reference --------------------------------


def pick_sample(finished: List[_Req], n: int, seed: int) -> List[_Req]:
    """A seeded sample of the greedy requests the run finished, the longest
    among them first."""
    greedy = sorted((r for r in finished if r.plan.temperature == 0.0),
                    key=lambda r: r.plan.rid)
    if not greedy:
        return []
    longest = max(greedy, key=lambda r: (r.plan.prompt.size
                                         + r.plan.max_new_tokens, r.plan.rid))
    rest = [r for r in greedy if r is not longest]
    rng = np.random.default_rng(int(seed) + 1)
    take = rng.permutation(len(rest))[: max(0, n - 1)]
    return [longest] + [rest[i] for i in sorted(take)]


def _bucket(n: int) -> int:
    """Sequence lengths are padded up to a power of two (512 at least), so
    that a handful of shapes serve every seed and the compile cache hits."""
    return max(512, 1 << (int(n) - 1).bit_length())


def reference_logits(ref, hp: dict, seed: int, sequences, rows_cap: int,
                     quant=None):
    """For each (tokens, first_row, n_rows): the logits [n_rows, V] of the
    plain reference `ref` at rows first_row.. of its full forward pass over
    `tokens`. Layer by layer, one layer's float32 weights resident at a
    time, one jitted maker and one jitted `layer` a kind of layer; rows
    past a sequence's end are padding the causal mask keeps out of sight."""
    import jax
    import jax.numpy as jnp

    tables = ref.tables
    kinds = tables.layer_kinds(hp)
    s32 = weights.seed_u32(seed)
    make_layer = {k: jax.jit(lambda s, l, k=k: weights.leaves(
        hp, tables.layer_table(hp, k), s, l, True)) for k in set(kinds)}
    make_globals = jax.jit(lambda s: weights.leaves(
        hp, tables.global_table(hp), s, 0, True))
    layer_fn = {k: jax.jit(lambda w, x, k=k: ref.layer(hp, k, w, x, quant))
                for k in set(kinds)}

    def head(g, x, first):
        rows = jnp.take(x, first + jnp.arange(rows_cap), axis=0, mode="clip")
        return ref.head_logits(hp, g, rows, quant)

    head_fn = jax.jit(head)
    g = make_globals(s32)
    xs = []
    for tokens, _first, _n in sequences:
        padded = np.zeros(_bucket(len(tokens)), np.int32)
        padded[: len(tokens)] = tokens
        xs.append(ref.embed(g, jnp.asarray(padded)))
    for layer, kind in enumerate(kinds):
        w = make_layer[kind](s32, jnp.uint32(layer))
        xs = [layer_fn[kind](w, x) for x in xs]
    return [head_fn(g, x, jnp.int32(first))[:n]
            for x, (_t, first, n) in zip(xs, sequences)]


def check_tokens(ref, hp: dict, seed: int, sample: List[_Req],
                 outputs: dict, control=None, rows_cap: int = 512) -> dict:
    """The widest gap by which a served greedy token's reference logit lies
    below the reference's best, over every served token of the sample. With
    `control` (a `quant` function) it reads beside it the gap of the token
    the lower precision puts first at each of the same positions."""
    import jax.numpy as jnp

    if not sample:
        return {"widest_gap": float("inf"), "tokens": 0, "longest": 0}
    seqs, served = [], []
    for r in sample:
        out = np.asarray(outputs[r.plan.rid], np.int32)
        tokens = np.concatenate([r.plan.prompt, out])
        seqs.append((tokens, r.plan.prompt.size - 1, out.size))
        served.append(out)
    logits = reference_logits(ref, hp, seed, seqs, rows_cap)

    def widest(tokens_by_request):
        per_request = []
        for lg, toks in zip(logits, tokens_by_request):
            got = jnp.take_along_axis(lg, jnp.asarray(toks)[:, None],
                                      axis=-1)[:, 0]
            per_request.append(float(jnp.max(jnp.max(lg, axis=-1) - got)))
        return per_request

    per_request = widest(served)
    out = {"widest_gap": max(per_request), "per_request": per_request,
           "tokens": int(sum(len(t) for t in served)),
           "longest": int(max(len(s[0]) for s in seqs))}
    if control is not None:
        low = reference_logits(ref, hp, seed, seqs, rows_cap, quant=control)
        per_control = widest([np.asarray(jnp.argmax(l, axis=-1))
                              for l in low])
        out.update(control_gap=max(per_control), control_per_request=per_control)
    return out

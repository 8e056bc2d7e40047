"""Runner kind `train`: `Trainer.fit(LlamaModule, DataLoader)` under the
cell's strategy, in the process that holds the chips.

One `fit` call builds the compiled step and its state, drives its first
three steps (compared with the plain reference), warms up to the first
fetched metric, and the window runs on in that same call: steps between the
first and the last metric fetch inside `--seconds`, where the device has
finished. The trainer is stopped through `should_stop`. In a traced run a
few more steps are profiled after the window closes.

The plain reference follows the same three steps AFTER the trainer's state is
freed, so both never share the chip's memory and `memory_peak_bytes`, read
when the window closes, is the program's; its time is not in `setup_s`.
"""
from __future__ import annotations

import gc
import json
import math
import time
from typing import Dict, List, Optional

import numpy as np

from benchmarks.harness import adamw, common, trace, traffic_gen, weights
from benchmarks.harness.common import RunRecord, say

B1 = 0.9          # Adam's first-moment decay, as LlamaModule configures it


def _strategy(spec: dict):
    import ray_lightning_tpu as rlt

    kw = {k: v for k, v in spec.items() if k != "name"}
    return getattr(rlt, spec["name"])(**kw)


def _norms(tree) -> Dict[str, float]:
    """{leaf name: l2 norm} of a canonical {"layers", "globals"} tree of
    device scalars; a leaf under a kind of layer reads `<kind>.<leaf>`."""
    import jax

    flat = {}
    for group in ("layers", "globals"):
        for path, v in jax.tree_util.tree_flatten_with_path(tree[group])[0]:
            flat[".".join(str(k.key) for k in path)] = v
    return {k: float(v) for k, v in jax.device_get(flat).items()}


def _canonical_norms_fn(adapter, hp):
    import jax
    import jax.numpy as jnp

    def norms(tree):
        canon = adapter.canonical_from_program(hp, tree)
        return jax.tree.map(
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))),
            canon)

    return jax.jit(norms)


def _canonical_delta_fn(adapter, ref, hp):
    """Norm of (parameters now - parameters as made from the seed), by
    canonical leaf; the seeded values are regenerated from the reference's
    tables leaf by leaf inside the reduction, never held."""
    import jax
    import jax.numpy as jnp

    def delta(tree, s32):
        canon = adapter.canonical_from_program(hp, tree)
        first = weights.canonical(hp, ref.tables, s32, False)
        return jax.tree.map(
            lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
                a.astype(jnp.float32) - b))), canon, first)

    return jax.jit(delta)


# ---- the plain reference's three steps --------------------------------------


def batch_loss_and_grads(ref, hp: dict, params: dict, batch, quant=None,
                         constrain=None):
    """Mean of the reference's `sequence_loss` over all tokens of batch
    [G, R, S + 1], and its gradients. The G groups run one after another;
    the R rows of a group run side by side (one a chip, where `constrain`
    pins the row axis to the chips). A row's forward pass is recomputed in
    its backward pass, so one row's logits are live at a time."""
    import jax
    import jax.numpy as jnp

    n_tokens = batch.shape[0] * batch.shape[1] * (batch.shape[2] - 1)
    row_loss = jax.checkpoint(
        lambda p, row: ref.sequence_loss(hp, p, row, quant))

    def total(p):
        loss = jnp.float32(0.0)
        for g in range(batch.shape[0]):
            rows = batch[g] if constrain is None else constrain(batch[g])
            loss = loss + jnp.sum(jax.vmap(row_loss, (None, 0))(p, rows))
        return loss / n_tokens

    return jax.value_and_grad(total)(params)


class ReferencePrograms:
    """The plain reference's jitted pieces for one set of devices: seeded
    parameters (sharded over the devices on each leaf's widest axis), loss
    and gradients of a batch [groups, len(devices), S + 1] (a group's rows
    run side by side, one a chip), AdamW on one leaf, and the parameters'
    change since the seed."""

    def __init__(self, ref, hp: dict, traffic: dict, devices, quant=None):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        n_dev = self.n_dev = len(devices)
        mesh = Mesh(np.asarray(devices), ("x",))

        def make(s32):
            return weights.canonical(hp, ref.tables, s32, False)

        def shard(x):
            axes = [None] * x.ndim
            if x.ndim >= 2 and n_dev > 1:
                wide = max(range(x.ndim), key=lambda a: x.shape[a])
                if x.shape[wide] % n_dev == 0:
                    axes[wide] = "x"
            return NamedSharding(mesh, P(*axes))

        self.shapes = jax.eval_shape(make, jnp.uint32(0))
        self.p_sh = jax.tree.map(shard, self.shapes)
        self.repl = NamedSharding(mesh, P())
        rows_sh = NamedSharding(mesh, P("x" if n_dev > 1 else None, None))
        lr, wd = float(traffic["lr"]), float(traffic["weight_decay"])
        warm, total = (int(traffic["warmup_steps"]),
                       int(traffic["total_steps"]))
        l2 = lambda t: jax.tree.map(
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), t)

        def grads_of(params, batch):
            loss, grads = batch_loss_and_grads(
                ref, hp, params, batch, quant,
                constrain=lambda r: jax.lax.with_sharding_constraint(
                    r, rows_sh))
            return loss, grads, l2(grads)

        self.make = jax.jit(make, out_shardings=self.p_sh)
        self.grads_of = jax.jit(
            grads_of, out_shardings=(self.repl, self.p_sh, None))
        self.update = jax.jit(
            lambda p, g, m, v, count: adamw.adamw_leaf(
                p, g, m, v, count,
                adamw.warmup_cosine_lr(count, lr, warm, total),
                b1=B1, b2=0.95, weight_decay=wd),
            donate_argnums=(0, 2, 3))
        self.delta = jax.jit(
            lambda p, s: l2(jax.tree.map(jnp.subtract, p, make(s))))


def reference_three_steps(ref, hp: dict, seed: int, batches: np.ndarray,
                          traffic: dict, devices, quant=None) -> dict:
    """Losses of the first three steps, per-leaf norms of the first gradient
    and of the parameters' change after the three, by the plain reference
    `ref` (float32, `highest`), on the same seeded weights and rows."""
    import jax
    import jax.numpy as jnp

    prog = ReferencePrograms(ref, hp, traffic, devices, quant)
    s32 = weights.seed_u32(seed)
    leaves, treedef = jax.tree.flatten(prog.make(s32))
    shardings = jax.tree.leaves(prog.p_sh)
    # Where parameters, gradient and both of Adam's moments (16 B a
    # parameter) would crowd a chip, the moments wait on the host while the
    # gradients are computed (11.3 GB at Mistral's two layers on one chip, 7.6 GB
    # a chip at InternLM2 over four, beside a 12.7 GiB gradient program)
    n_params = sum(x.size for x in leaves)
    offload = 16 * n_params / prog.n_dev > 6 * 2 ** 30
    mu = [None] * len(leaves)
    nu = [None] * len(leaves)
    losses, grad_norms = [], None
    n_steps = batches.shape[0]
    for i in range(n_steps):
        b = jax.device_put(
            batches[i].reshape(-1, prog.n_dev, batches.shape[-1]), prog.repl)
        loss, grads, gn = prog.grads_of(jax.tree.unflatten(treedef, leaves),
                                        b)
        losses.append(float(loss))
        if i == 0:
            grad_norms = _norms(gn)
        grads = jax.tree.leaves(grads)
        for j, sh in enumerate(shardings):
            m = jnp.zeros_like(leaves[j]) if mu[j] is None \
                else jax.device_put(mu[j], sh)
            v = jnp.zeros_like(leaves[j]) if nu[j] is None \
                else jax.device_put(nu[j], sh)
            leaves[j], m, v = prog.update(leaves[j], grads[j], m, v,
                                          jnp.int32(i))
            grads[j] = None
            if i + 1 < n_steps:
                mu[j], nu[j] = (np.asarray(m), np.asarray(v)) if offload \
                    else (m, v)
            del m, v
    params = jax.tree.unflatten(treedef, leaves)
    out = {"losses": losses, "grad_norms": grad_norms,
           "delta_norms": _norms(prog.delta(params, s32))}
    del params, leaves, grads, mu, nu
    gc.collect()
    return out


def compare(program: dict, reference: dict) -> dict:
    """The numbers `correct` rests on. Norms are compared by the worst leaf:
    the gap between the program's norm and the reference's, against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger."""
    loss_gap = max(abs(a - b) / abs(b) for a, b in
                   zip(program["losses"], reference["losses"]))

    def worst(key):
        refs = reference[key]
        floor = float(np.median(list(refs.values())))
        rel = {k: abs(program[key][k] - refs[k]) / max(refs[k], floor)
               for k in refs}
        leaf = max(rel, key=rel.get)
        return rel[leaf], leaf

    grad_gap, grad_leaf = worst("grad_norms")
    delta_gap, delta_leaf = worst("delta_norms")
    return {"loss_gap": loss_gap, "grad_gap": grad_gap, "grad_leaf": grad_leaf,
            "delta_gap": delta_gap, "delta_leaf": delta_leaf}


# ---- the window -------------------------------------------------------------


def _window_callback(ctx, rec: RunRecord, adapter, ref, hp):
    from ray_lightning_tpu.core.callbacks import Callback

    traffic = ctx["traffic"]
    every = int(traffic["log_every_n_steps"])
    warm_steps = int(traffic["warm_steps"])
    trace_steps = int(traffic.get("trace_steps", 3))
    seconds = rec.seconds
    norms_fn = _canonical_norms_fn(adapter, hp)
    delta_fn = _canonical_delta_fn(adapter, ref, hp)
    s32 = weights.seed_u32(ctx["seed"])
    compiles = ctx["compile_counter"]

    class Window(Callback):
        def __init__(self):
            self.first: Dict[str, object] = {"losses": []}
            self.losses: List[object] = []
            self.marks: List[tuple] = []          # (step, host time) per fetch
            self.phase = "first"
            self.t0 = self.t1 = None
            self.step0 = self.step1 = None
            self.recorder = (trace.Recorder(ctx["trace_dir"])
                             if ctx["trace"] else None)
            self.trace_path: Optional[str] = None
            self.trace_stop_at = None
            self.compiles_before = 0
            self._annotation = None

        # host spans on the profiler's clock, in a traced stretch only:
        # `step` from batch start to batch end (dispatch, and the metric
        # fetch every `every` steps), `data` between batches (the loader)
        def _span(self, name: Optional[str]):
            import jax

            if self._annotation is not None:
                self._annotation.__exit__(None, None, None)
                self._annotation = None
            if name is not None and self.phase == "trace":
                self._annotation = jax.profiler.TraceAnnotation(
                    f"bench.{name}")
                self._annotation.__enter__()

        def on_train_batch_start(self, trainer, module, batch, batch_idx):
            self._span("step")
            return None

        def on_train_batch_end(self, trainer, module, metrics, batch_idx):
            self._span("data")
            step = trainer.global_step
            if self.phase == "first":
                self.first["losses"].append(float(metrics["loss"]))
                if step == 1:
                    mu = next(s.mu for s in _states(trainer.state.opt_state))
                    self.first["grad_norms"] = {
                        k: v / (1.0 - B1)
                        for k, v in _norms(norms_fn(mu)).items()}
                if step == 3:
                    self.first["delta_norms"] = _norms(
                        delta_fn(trainer.state.params, s32))
                    self.phase = "warm"
                return
            self.losses.append(metrics["loss"])
            if self.phase == "trace":
                if step >= self.trace_stop_at:
                    float(metrics["loss"])        # the device has finished
                    self._span(None)
                    self.trace_path = self.recorder.stop() or ""
                    trainer.should_stop = True
                return
            if step % every:
                return
            now = time.perf_counter()             # metrics are host floats
            if self.phase == "warm":
                if step >= warm_steps:
                    self.phase = "window"
                    self.t0, self.step0 = now, step
                    self.compiles_before = compiles.count
                    rec.setup_s = now - ctx["t_start"]
                return
            self.marks.append((step, now))
            step_s = (now - self.t0) / (step - self.step0)
            if now + every * step_s > self.t0 + seconds:
                self.t1, self.step1 = now, step
                rec.compiles_in_window = compiles.count - self.compiles_before
                rec.memory_peak_bytes = common.memory_peak_bytes(
                    ctx["devices"])
                if self.recorder is None:
                    trainer.should_stop = True
                else:
                    self.recorder.start()
                    self.phase = "trace"
                    self.trace_stop_at = step + trace_steps

    return Window()


def _states(opt_state):
    import jax

    return [s for s in jax.tree.leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(s, "mu")]


def run(ctx: dict) -> RunRecord:
    import jax

    from ray_lightning_tpu import DataLoader, Trainer

    adapter, traffic = ctx["adapter"], ctx["traffic"]
    hp = adapter.hyperparams(ctx["config"], "train")
    ref = common.load_model_file(ctx["root"], "reference",
                                 ctx["config"]["model"])
    rec = RunRecord(kind="train", cell=ctx["cell"], config=ctx["config"],
                    traffic=traffic, hp=hp, seconds=float(ctx["seconds"]),
                    chips=ctx["chips"], peaks=ctx["peaks"], root=ctx["root"])
    batch, seq = int(traffic["batch"]), int(traffic["seq"])
    tokens = traffic_gen.train_tokens(hp["vocab_size"], ctx["seed"],
                                      int(traffic["rows"]), seq)

    strategy = _strategy(traffic["strategy"])
    _cfg, module = adapter.training_module(ctx["config"], hp, ctx["seed"],
                                           strategy, traffic)
    loader = DataLoader({"tokens": tokens}, batch_size=batch, prefetch=True)
    window = _window_callback(ctx, rec, adapter, ref, hp)
    trainer = Trainer(
        strategy=strategy, max_epochs=10 ** 6,
        log_every_n_steps=int(traffic["log_every_n_steps"]),
        enable_checkpointing=False, enable_progress_bar=False,
        seed=int(ctx["seed"]) % (2 ** 31 - 1), callbacks=[window])
    trainer.fit(module, loader)
    if window.t1 is None:
        raise common.BenchError("the trainer stopped before the window closed")
    if rec.compiles_in_window:
        raise common.BenchError(
            f"{rec.compiles_in_window} compile(s) inside the window")
    mosaic = "tpu_custom_call" in (trainer._train_step.compiled_text() or "")
    if traffic.get("require_pallas", True) and not mosaic:
        raise common.BenchError("no Mosaic kernel in the compiled train step")

    steps = window.step1 - window.step0
    elapsed = window.t1 - window.t0
    losses = np.asarray(jax.device_get(
        window.losses[: window.step1 - 3]), np.float64)
    rec.attempted = steps
    window_losses = losses[window.step0 - 3:]
    rec.failed = int((~np.isfinite(window_losses)).sum())
    tokens_per_step = batch * seq
    rec.end_to_end = {"train_tokens_per_s": steps * tokens_per_step / elapsed}
    marks = [(window.step0, window.t0)] + window.marks
    rec.stamps = {
        "steps": steps, "elapsed_s": elapsed, "marks": marks,
        "tokens_per_step": tokens_per_step, "seq": seq,
    }
    say("window", steps=steps, elapsed_s=round(elapsed, 4),
        step_ms=round(1e3 * elapsed / steps, 3), failed=rec.failed,
        fetches=len(marks), first_losses=window.first["losses"],
        last_loss=round(float(window_losses[-1]), 4),
        mosaic_kernels=mosaic, loader=loader.path)
    if window.trace_path:
        t_load = time.perf_counter()
        rec.trace = trace.load_xplane(window.trace_path, ctx["chips"])
        say("trace", stop_s=round(window.recorder.stop_s, 2), load_s=round(time.perf_counter() - t_load, 2),
            ops=sum(len(d.ops) for d in rec.trace.devices))

    # the program's state goes before the reference's is made
    trainer.state = None
    module.params = None
    del trainer, module, loader
    gc.collect()
    t_ref = time.perf_counter()
    first = tokens[: 3 * batch].reshape(3, batch, seq + 1)
    reference = reference_three_steps(ref, hp, ctx["seed"], first, traffic,
                                      ctx["devices"])
    reference_s = rec.reference_s = time.perf_counter() - t_ref
    print("[reference] " + json.dumps(reference), flush=True)
    cmp = compare(window.first, reference)
    limits = traffic["check"]
    rec.correct = (cmp["loss_gap"] <= limits["loss_limit"]
                   and cmp["grad_gap"] <= limits["grad_limit"]
                   and cmp["delta_gap"] <= limits["delta_limit"]
                   and all(math.isfinite(x) for x in window.first["losses"]))
    say("check", number="loss_gap_rel_worst_of_3_steps",
        value=cmp["loss_gap"], limit=limits["loss_limit"],
        program=window.first["losses"], reference=reference["losses"])
    say("check", number="first_grad_norm_gap_worst_leaf",
        value=cmp["grad_gap"], limit=limits["grad_limit"],
        leaf=cmp["grad_leaf"])
    say("check", number="param_change_norm_gap_worst_leaf",
        value=cmp["delta_gap"], limit=limits["delta_limit"],
        leaf=cmp["delta_leaf"], reference_s=round(reference_s, 2),
        correct=rec.correct)
    rec.stamps["check"] = {**cmp, "program": window.first,
                           "reference": reference}
    return rec

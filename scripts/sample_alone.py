"""Scope `sample` alone on fixed inputs: the engine's sampling stage (one
RNG split a slot, then `_sample_one` under `vmap`) at the serving cells'
``[capacity, vocabulary]`` with chat's mode mix, scanned over several
ticks' logits in one program, median wall clock over repeats. The sibling
of `paged_decode_alone.py`, and the yardstick PERF.md section 6 keeps
beside `sampling_share.chat`, which moves with the step around the stage.

    chiprun --chips 1 -- env PYTHONPATH=. python3 \\
        scripts/sample_alone.py change=. parent=_parent

Each argument is ``label=checkout``: a directory that holds
``ray_lightning_tpu/`` (the parent commit unpacked by `git archive` into a
directory `.gitignore` lists), whose `serve/engine.py:_sample_one` is the
form timed. Two forms that are no checkout's ride along: ``top_k64``, the
stage with its threshold read from a static ``lax.top_k(x, 64)`` (right
for k <= 64 only: a yardstick, not a candidate), and ``nofilter``, the
stage without any threshold (what is left of the scope beside it). Parity
first: every form's tokens are compared with the first argument's, and a
checkout that has `_kth_largest` is compared with the sort bit for bit at
the mix's k and at 1, V // 2 and V. A TPU only.
"""
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from paged_decode_alone import _median_ms  # noqa: E402

#: cell, slots, vocabulary (benchmarks/traffic/*.json, configs/*.json)
INPUTS = [
    ("chat", 64, 92544), ("docs", 16, 32768),
    ("longdocs", 24, 16160), ("ragdocs", 24, 32768),
]
#: traffic/chat.json's sampling: every second request greedy, every fourth
#: top-k 40 at temperature 0.8, the rest plain temperature
GREEDY_EVERY, TOP_K_EVERY, TEMPERATURE, TOP_K = 2, 4, 0.8, 40
#: ticks a call: the scan takes each tick's logits from a stack, so the
#: compiler cannot hoist the threshold out of the loop
TICKS = 8


def _engine(root, label):
    path = os.path.join(root, "ray_lightning_tpu", "serve", "engine.py")
    found = importlib.util.spec_from_file_location("engine_" + label, path)
    mod = importlib.util.module_from_spec(found)
    sys.modules[found.name] = mod      # its dataclasses look themselves up
    found.loader.exec_module(mod)
    return mod


def _sample_one_with(threshold):
    """`_sample_one` with its threshold found by ``threshold(scaled,
    top_k)``, or with no filter at all for None."""
    def sample_one(logits, key, temp, top_k):
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        scaled = logits / jnp.maximum(temp, jnp.finfo(logits.dtype).tiny)
        sampled_from = scaled
        if threshold is not None:
            filtered = jnp.where(scaled >= threshold(scaled, top_k), scaled,
                                 -jnp.inf)
            sampled_from = jnp.where(top_k > 0, filtered, scaled)
        drawn = jax.random.categorical(
            key, sampled_from[None, :])[0].astype(jnp.int32)
        return jnp.where(temp == 0.0, greedy, drawn)

    return sample_one


def _stage(sample_one):
    """The engine's `_sample` over TICKS ticks' logits: tokens of every
    tick, the RNG carried from tick to tick as the engine carries it."""
    def tick(rngs, logits, temp, top_k):
        split = jax.vmap(jax.random.split)(jax.random.wrap_key_data(rngs))
        emitted = jax.vmap(sample_one)(logits, split[:, 1], temp, top_k)
        return jax.random.key_data(split[:, 0]), emitted

    def run(stack, temp, top_k, rngs):
        return jax.lax.scan(
            lambda r, logits: tick(r, logits, temp, top_k), rngs, stack)[1]

    return jax.jit(run)


def _inputs(slots, vocab, seed=0):
    # logits as a served model's: float32, std about 1.3 over the rows
    stack = 1.3 * jax.random.normal(jax.random.key(seed),
                                    (TICKS, slots, vocab), jnp.float32)
    i = np.arange(slots)
    greedy = i % GREEDY_EVERY == 0
    temp = np.where(greedy, 0.0, TEMPERATURE).astype(np.float32)
    top_k = np.where(~greedy & (i % TOP_K_EVERY == 1), TOP_K,
                     0).astype(np.int32)
    rngs = np.asarray(jax.random.key_data(jax.random.split(
        jax.random.key(seed + 1), slots)))
    return stack, jnp.asarray(temp), jnp.asarray(top_k), jnp.asarray(rngs)


def main():
    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"needs a TPU, found {device.platform}: a time off the "
                 "chip means nothing")
    forms = []
    for label, root in (arg.split("=", 1) for arg in sys.argv[1:]):
        mod = _engine(root, label)
        forms.append((label, mod._sample_one,
                      getattr(mod, "_kth_largest", None)))
    forms += [
        ("top_k64", _sample_one_with(
            lambda x, k: jax.lax.top_k(x, 64)[0][jnp.clip(k, 1, 64) - 1]),
         None),
        ("nofilter", _sample_one_with(None), None),
    ]
    for cell, slots, vocab in INPUTS:
        args = _inputs(slots, vocab)
        stack, temp, top_k, _ = args
        want = None
        for label, sample_one, kth in forms:
            run = _stage(sample_one)
            got = np.asarray(run(*args))
            want = got if want is None else want
            line = {"input": f"{cell} {slots}x{vocab}", "form": label,
                    "device": device.device_kind, "ticks": TICKS,
                    "ms_a_tick": round(_median_ms(run, args) / TICKS, 4),
                    "tokens_differ": int((got != want).sum())}
            if kth is not None:
                # the threshold itself against the sort, at the mix's k and
                # at the vocabulary's ends
                scaled = stack[0] / jnp.maximum(temp[:, None],
                                                jnp.finfo(jnp.float32).tiny)
                srt = jnp.sort(scaled, axis=-1)[:, ::-1]
                bits = lambda x: np.asarray(x).view(np.uint32)
                line["threshold_bits_differ"] = sum(
                    int((bits(jax.jit(jax.vmap(kth))(scaled, ks))
                         != bits(jnp.take_along_axis(
                             srt, ks[:, None] - 1, axis=1)[:, 0])).sum())
                    for ks in (jnp.clip(top_k, 1, vocab),
                               jnp.full_like(top_k, 1),
                               jnp.full_like(top_k, vocab // 2),
                               jnp.full_like(top_k, vocab)))
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()

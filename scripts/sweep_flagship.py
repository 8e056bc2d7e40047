"""Sweep the flagship training path (remat=True + scan_layers=True +
fused CE at the Llama-3 vocabulary) on the real chip.

This is the only configuration class that can hold at the
north-star Llama-3-8B (BASELINE.md config 4), and it had never been swept
on its own — remat shifts the optimum (recompute competes with the flash
kernel for VMEM; freed activation memory admits larger batches).

Dimensions: remat_policy (nothing|dots) x batch, then ce_chunk_tokens,
then flash block sizes (via RLT_FLASH_BLOCK_Q/K) at the incumbent best.
Appends one JSON line per config to chiprun_out/sweep_flagship_results.jsonl
(git-ignored: a run's record, not source) so a partial sweep is still a
usable record.

Usage: python scripts/sweep_flagship.py [phase]
  phase in {1,...,7,all} — 4 sweeps the inline-backward fused
  CE; 5 sweeps remat_policy="attn_out" (saved flash residuals); 6 sweeps
  bf16 Adam first moment (mu_dtype) at the memory-capped batches;
  7 crosses the candidate winners (inline x mu_bf16 x policy).
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# RLT_SWEEP_RESULTS overrides the record path (CPU smoke runs of the
# harness itself must not pollute the real chip record)
RESULTS = os.environ.get(
    "RLT_SWEEP_RESULTS",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 "chiprun_out", "sweep_flagship_results.jsonl"),
)


def run_one(tag: str, *, batch: int, policy: str, chunk: int,
            block_q: int | None = None, block_k: int | None = None,
            vocab: int = 128256, seq: int = 2048, inline: bool = False,
            mu_bf16: bool = False):
    import bench

    for key, val in (("RLT_FLASH_BLOCK_Q", block_q),
                     ("RLT_FLASH_BLOCK_K", block_k)):
        if val is None:
            os.environ.pop(key, None)
        else:
            os.environ[key] = str(val)
    rec = {"tag": tag, "batch": batch, "policy": policy, "chunk": chunk,
           "block_q": block_q, "block_k": block_k, "vocab": vocab,
           "seq": seq, "inline": inline, "mu_bf16": mu_bf16}
    t0 = time.time()
    try:
        import jax.numpy as jnp

        step, params, opt_state, tokens, tps_tokens, cfg = bench._make_step(
            use_flash=True, fused_ce=True, batch=batch, seq=seq,
            vocab=vocab, remat=True, scan=True,
            remat_policy=policy, ce_chunk_tokens=chunk, ce_inline=inline,
            mu_dtype=jnp.bfloat16 if mu_bf16 else None,
        )
        dt = bench._time_step(step, params, opt_state, tokens)
        tps = tps_tokens / dt
        import jax
        peak = bench._device_peak_tflops(jax.devices()[0].device_kind)
        mfu = tps * bench._flops_per_token(cfg, seq) / (peak * 1e12)
        rec.update(tokens_per_sec=round(tps, 1), mfu=round(mfu, 4),
                   step_ms=round(dt * 1e3, 2))
        del step, params, opt_state, tokens
    except Exception as exc:  # noqa: BLE001 — OOM/compile failures are data
        rec.update(error=f"{type(exc).__name__}: {str(exc)[:300]}")
    rec["wall_s"] = round(time.time() - t0, 1)
    os.makedirs(os.path.dirname(os.path.abspath(RESULTS)), exist_ok=True)
    with open(RESULTS, "a") as f:
        f.write(json.dumps(rec) + "\n")
    print(json.dumps(rec), flush=True)
    return rec


def best_so_far():
    best = None
    try:
        with open(RESULTS) as f:
            for line in f:
                rec = json.loads(line)
                if "tokens_per_sec" in rec and (
                        best is None
                        or rec["tokens_per_sec"] > best["tokens_per_sec"]):
                    best = rec
    except FileNotFoundError:
        pass
    return best


def main():
    phase = sys.argv[1] if len(sys.argv) > 1 else "all"
    if phase in ("1", "all"):
        for policy in ("nothing", "dots"):
            for batch in (4, 8, 16):
                run_one(f"p1-{policy}-b{batch}", batch=batch, policy=policy,
                        chunk=2048)
    b = best_so_far()
    if b is None:
        print("BEST: none — no config completed; fix phase 1 first",
              flush=True)
        return
    # carry the incumbent's FULL configuration forward — a best record
    # that only fits because of bf16 mu (or only wins because of the
    # inline CE) must not be re-run without those flags in later phases
    def _carry(rec):
        return dict(inline=rec.get("inline", False),
                    mu_bf16=rec.get("mu_bf16", False))

    if phase in ("2", "all"):
        for chunk in (1024, 4096, 8192):
            run_one(f"p2-chunk{chunk}", batch=b["batch"], policy=b["policy"],
                    chunk=chunk, **_carry(b))
        b = best_so_far()
    if phase in ("3", "all"):
        for bq, bk in ((256, 1024), (512, 512), (1024, 1024), (512, 2048)):
            run_one(f"p3-q{bq}k{bk}", batch=b["batch"], policy=b["policy"],
                    chunk=b["chunk"], block_q=bq, block_k=bk, **_carry(b))
        b = best_so_far()
    if phase in ("4", "all"):
        # inline-backward fused CE (ops/fused_ce.py _ce_inline): removes
        # the lm_head tile recompute (~10% of executed FLOPs at this
        # shape) for a dW residual in the lm_head param dtype (f32 here:
        # ~1 GB at D=2048, V=128256); sweep batch x chunk around the
        # incumbent. Carry the incumbent's full configuration except the
        # forced inline=True (the carry invariant: a standalone phase-4
        # re-run after phase 6/7 records exist must keep the incumbent's
        # mu_bf16 — a batch that only fits with a bf16 mu would
        # otherwise re-run without it and record a spurious OOM).
        p4_carry = {**_carry(b), "inline": True}
        inline_recs = []
        for batch in (4, 8, 12, 16):
            inline_recs.append(
                run_one(f"p4-inline-b{batch}", batch=batch,
                        policy=b["policy"], chunk=b["chunk"], **p4_carry))
        done = [r for r in inline_recs if "tokens_per_sec" in r]
        if done:
            # chunk sweep continues from the best INLINE point (inline
            # stays True — an inline-loses-overall outcome must not
            # silently re-run non-inline configs under a p4 tag)
            bi = max(done, key=lambda r: r["tokens_per_sec"])
            for chunk in (2048, 8192, 16384):
                run_one(f"p4-inline-chunk{chunk}", batch=bi["batch"],
                        policy=bi["policy"], chunk=chunk,
                        **{**p4_carry,
                           "mu_bf16": bi.get("mu_bf16", False)})
    if phase in ("5", "all"):
        # remat_policy="attn_out" (save flash VJP residuals, skip the
        # attention share of the backward recompute — VERDICT r4 next #2's
        # "remat policies that save attention outputs"), with and without
        # the inline CE, around the incumbent batch/chunk
        for batch in (4, 8):
            for inline in (False, True):
                tag = f"p5-attnout-b{batch}" + ("-inline" if inline else "")
                run_one(tag, batch=batch, policy="attn_out", chunk=4096,
                        inline=inline)
    if phase in ("6", "all"):
        # bf16 Adam first moment: frees ~1.8 GB of optimizer HBM at this
        # scale — exactly what capped the flagship batch. Sweep the
        # batches that previously failed to compile/fit, with and
        # without the inline CE.
        for batch in (8, 12, 16):
            for inline in (False, True):
                tag = f"p6-mubf16-b{batch}" + ("-inline" if inline else "")
                run_one(tag, batch=batch, policy="nothing", chunk=4096,
                        inline=inline, mu_bf16=True)
    if phase in ("7", "all"):
        # cross of the candidate winners: inline CE (no logits-tile
        # recompute) x bf16 mu (frees HBM) x attn_out (no attention
        # recompute), at the incumbent batch and the next one up
        for policy in ("nothing", "attn_out"):
            for batch in (8, 12):
                run_one(f"p7-{policy}-b{batch}-inline-mubf16",
                        batch=batch, policy=policy, chunk=4096,
                        inline=True, mu_bf16=True)
    print("BEST:", json.dumps(best_so_far()), flush=True)


if __name__ == "__main__":
    main()

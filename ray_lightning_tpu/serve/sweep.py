"""Block-size autotune for BOTH paged attention kernels
(docs/SERVING.md "block-size autotune").

``block_size`` is the unit both paged kernels fetch by. Each joins
blocks into a KV tile of its own (`decode_tile_tokens`: ``128 /
block_size`` blocks; `prefill_tile_shape`: ``512 / block_size`` where
VMEM allows) and loops over the live tiles of a slot or of a chunk, so
``block_size`` sets only the granularity of the copies (one async copy a
block) and the table's length, not the
number of steps. The right value is a hardware question the planner
cannot answer from byte math, so this module measures it:

  * **correctness matrix** — every candidate geometry runs BOTH
    kernels in interpret mode (`dispatch.force_pallas` off-TPU)
    against their XLA reference twins on a deterministic random case,
    plus a ``shared_spec`` cell replaying the decode gather through a
    FORKED table (slots aliasing a shared prefix chain — the prefix
    cache's copy-on-write geometry) and the speculative verify's
    k-wide chunk where the prefill kernel tiles it. This works on any
    host, including CPU CI, and is the part the tier-1 tests pin
    (`tests/test_paged_prefill.py`).
  * **wall-clock timing** — on a real TPU backend each correct
    candidate's kernels are jitted, warmed, and timed best-of-N (a
    failure there fails the sweep); off-TPU the timing leg records a
    structured ``{"skipped": "not a TPU (<backend>)"}``: a skip is
    recorded, never invented numbers.

The result is a JSON **artifact** keyed by (model fingerprint,
topology) that the engine can consume: `apply_autotune(engine_cfg,
artifact)` returns an `EngineConfig` re-geometried to the winning
candidate (same per-slot span — the sweep never changes capacity
semantics, only the tiling), refusing a model-fingerprint mismatch.
`python -m ray_lightning_tpu serve <preset> --autotune out.json`
writes one from the CLI.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Optional, Sequence

__all__ = [
    "DEFAULT_BLOCK_SIZES", "SweepCandidate", "candidate_grid",
    "model_fingerprint", "sweep_paged_kernels", "save_artifact",
    "load_artifact", "apply_autotune",
]

#: candidate block sizes. 8 is the TPU sublane floor
#: (`paged_shapes_supported` rejects smaller); 256 tokens is past the
#: point where a bigger prefill tile stops amortizing anything and only
#: grows the VMEM working set (the decode kernel's tile is its own).
DEFAULT_BLOCK_SIZES = (8, 16, 32, 64, 128, 256)


@dataclasses.dataclass(frozen=True)
class SweepCandidate:
    """One pool geometry under test. The per-slot token span
    (``block_size * blocks_per_slot``) is held CONSTANT across the
    grid — the sweep tunes tiling, never capacity."""

    block_size: int
    blocks_per_slot: int

    @property
    def span(self) -> int:
        return self.block_size * self.blocks_per_slot


def candidate_grid(engine_cfg,
                   block_sizes: Optional[Sequence[int]] = None
                   ) -> list:
    """Candidate geometries preserving ``engine_cfg``'s per-slot span.

    A block size qualifies when it divides the span and meets the
    kernels' sublane floor (% 8); span-constancy keeps the prefill
    chunk inside the slot for every candidate (the EngineConfig
    contract already holds for the incumbent). The incumbent geometry
    is always in the grid (so the sweep can only confirm or beat
    it)."""
    span = engine_cfg.block_size * engine_cfg.blocks_per_slot
    sizes = sorted(set(block_sizes or DEFAULT_BLOCK_SIZES)
                   | {engine_cfg.block_size})
    return [SweepCandidate(block_size=bs, blocks_per_slot=span // bs)
            for bs in sizes
            if bs >= 8 and bs % 8 == 0 and bs <= span
            and span % bs == 0]


def model_fingerprint(model_cfg) -> str:
    """The attention-shape identity an artifact is valid for — the
    fields BOTH kernels tile on. Everything else (vocab, hidden dim,
    weights) is irrelevant to the tiling decision."""
    import numpy as np

    return (f"L{model_cfg.n_layers}-H{model_cfg.n_heads}"
            f"-KV{model_cfg.n_kv_heads}-hd{model_cfg.head_dim}"
            f"-{np.dtype(model_cfg.dtype).name}")


def _correctness_case(model_cfg, engine_cfg, cand: SweepCandidate,
                      seed: int = 0) -> dict:
    """Interpret-mode parity of BOTH kernels vs their XLA reference
    twins on this candidate geometry — deterministic random K/V/q,
    ragged pads, a table tail past the written length. Returns
    per-kernel ``{"ok", "max_err"}`` (or ``{"ok": False, "error"}``
    when a kernel refuses the shape or dies)."""
    import numpy as np

    import jax.numpy as jnp

    from ray_lightning_tpu.ops import dispatch
    from ray_lightning_tpu.ops.attention import (
        paged_attention_reference, paged_prefill_reference,
    )
    from ray_lightning_tpu.ops.pallas.paged_attention import (
        paged_attention_pallas, paged_shapes_supported,
    )
    from ray_lightning_tpu.ops.pallas.paged_prefill import (
        paged_prefill_pallas, paged_prefill_shapes_supported,
    )

    rng = np.random.default_rng(seed)
    H, HKV, HD = (model_cfg.n_heads, model_cfg.n_kv_heads,
                  model_cfg.head_dim)
    P, M = cand.block_size, cand.blocks_per_slot
    C = min(engine_cfg.capacity, 4)
    B = min(engine_cfg.prefill_batch, C)
    CH = min(engine_cfg.prefill_chunk, cand.span)
    n_blocks = 1 + C * M
    pool_k = jnp.asarray(rng.normal(size=(n_blocks, P, HKV, HD)),
                         jnp.float32)
    pool_v = jnp.asarray(rng.normal(size=(n_blocks, P, HKV, HD)),
                         jnp.float32)
    tables = jnp.asarray(
        1 + (np.arange(C * M) % (n_blocks - 1)).reshape(C, M),
        jnp.int32)
    out: dict = {}

    # decode lane: one query token per slot, ragged lengths
    q1 = jnp.asarray(rng.normal(size=(C, H, HD)), jnp.float32)
    lengths = jnp.asarray(
        rng.integers(1, cand.span + 1, size=(C,)), jnp.int32)
    pads = jnp.zeros((C,), jnp.int32)
    if not paged_shapes_supported((C, H, HD), (n_blocks, P, HKV, HD)):
        out["decode"] = {"ok": False,
                         "error": "shape not supported by the kernel"}
    else:
        try:
            ref = paged_attention_reference(q1, pool_k, pool_v, tables,
                                            lengths, pads)
            with dispatch.force_pallas():
                got = paged_attention_pallas(q1, pool_k, pool_v,
                                             tables, lengths, pads)
            err = float(jnp.max(jnp.abs(got - ref)))
            out["decode"] = {"ok": bool(err < 2e-5), "max_err": err}
        except Exception as exc:  # noqa: BLE001 — recorded, not raised
            out["decode"] = {"ok": False,
                             "error": f"{type(exc).__name__}: "
                                      f"{str(exc)[:160]}"}

    # prefill lane: a CH-wide chunk mid-prompt, ragged left pads
    qc = jnp.asarray(rng.normal(size=(B, CH, H, HD)), jnp.float32)
    pos = max(0, min(cand.span - CH, cand.span // 2))
    pad = jnp.asarray([min(i * 2, max(pos - 1, 0))
                       for i in range(B)], jnp.int32)
    if not paged_prefill_shapes_supported((B, CH, H, HD),
                                          (n_blocks, P, HKV, HD)):
        out["prefill"] = {"ok": False,
                          "error": "shape not supported by the kernel"}
    else:
        try:
            ref = paged_prefill_reference(qc, pool_k, pool_v,
                                          tables[:B], pos, pad=pad)
            with dispatch.force_pallas():
                got = paged_prefill_pallas(qc, pool_k, pool_v,
                                           tables[:B], pos, pad=pad)
            err = float(jnp.max(jnp.abs(got - ref)))
            out["prefill"] = {"ok": bool(err < 2e-5), "max_err": err}
        except Exception as exc:  # noqa: BLE001 — recorded, not raised
            out["prefill"] = {"ok": False,
                              "error": f"{type(exc).__name__}: "
                                       f"{str(exc)[:160]}"}

    # shared-prefix + speculative cell: the prefix cache makes slots
    # ALIAS each other's prefix blocks (fork-on-write tables), so the
    # decode kernel must gather correctly through an aliased table —
    # every slot's first half points at slot 0's chain, tails stay
    # owned. Piggybacked: the speculative verify is a NARROW k-wide
    # chunk mid-slot; where the prefill kernel tiles that width the
    # pair must agree there too (where it does not, the engine runs
    # the verify on the reference lane — recorded as a skip, not a
    # failure).
    forked = np.asarray(tables).copy()
    half = max(1, M // 2)
    forked[:, :half] = forked[0, :half]
    forked = jnp.asarray(forked, jnp.int32)
    if not paged_shapes_supported((C, H, HD), (n_blocks, P, HKV, HD)):
        out["shared_spec"] = {
            "ok": False, "error": "shape not supported by the kernel"}
    else:
        try:
            ref = paged_attention_reference(q1, pool_k, pool_v, forked,
                                            lengths, pads)
            with dispatch.force_pallas():
                got = paged_attention_pallas(q1, pool_k, pool_v,
                                             forked, lengths, pads)
            err = float(jnp.max(jnp.abs(got - ref)))
            cell = {"ok": bool(err < 2e-5), "max_err": err}
            K = 4                       # DraftConfig's default k
            qk = jnp.asarray(rng.normal(size=(B, K, H, HD)),
                             jnp.float32)
            vpos = max(0, min(cand.span - K, cand.span // 2))
            vpad = jnp.zeros((B,), jnp.int32)
            if paged_prefill_shapes_supported(
                    (B, K, H, HD), (n_blocks, P, HKV, HD)):
                refv = paged_prefill_reference(qk, pool_k, pool_v,
                                               forked[:B], vpos,
                                               pad=vpad)
                with dispatch.force_pallas():
                    gotv = paged_prefill_pallas(qk, pool_k, pool_v,
                                                forked[:B], vpos,
                                                pad=vpad)
                verr = float(jnp.max(jnp.abs(gotv - refv)))
                cell["verify_chunk"] = {"ok": bool(verr < 2e-5),
                                        "max_err": verr}
                cell["ok"] = bool(cell["ok"]
                                  and cell["verify_chunk"]["ok"])
            else:
                cell["verify_chunk"] = {
                    "skipped": "k-wide chunk not tiled — the "
                               "speculative verify runs the "
                               "reference lane"}
            out["shared_spec"] = cell
        except Exception as exc:  # noqa: BLE001 — recorded, not raised
            out["shared_spec"] = {"ok": False,
                                  "error": f"{type(exc).__name__}: "
                                           f"{str(exc)[:160]}"}
    return out


def _time_candidate(model_cfg, engine_cfg, cand: SweepCandidate,
                    repeats: int = 5) -> dict:
    """Best-of-N wall clock for both kernels on a REAL accelerator
    backend — compiled once, warmed once, `block_until_ready` fenced.
    Callers gate on the backend; this function assumes one."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.ops import dispatch
    from ray_lightning_tpu.ops.pallas.paged_attention import (
        paged_attention_pallas,
    )
    from ray_lightning_tpu.ops.pallas.paged_prefill import (
        paged_prefill_pallas,
    )

    rng = np.random.default_rng(1)
    H, HKV, HD = (model_cfg.n_heads, model_cfg.n_kv_heads,
                  model_cfg.head_dim)
    P, M = cand.block_size, cand.blocks_per_slot
    C, B = engine_cfg.capacity, engine_cfg.prefill_batch
    CH = min(engine_cfg.prefill_chunk, cand.span)
    n_blocks = 1 + C * M
    dtype = jnp.bfloat16 if "bfloat16" in str(model_cfg.dtype) \
        else jnp.float32
    pool_k = jnp.asarray(rng.normal(size=(n_blocks, P, HKV, HD)),
                         dtype)
    pool_v = jnp.asarray(rng.normal(size=(n_blocks, P, HKV, HD)),
                         dtype)
    tables = jnp.asarray(
        1 + (np.arange(C * M) % (n_blocks - 1)).reshape(C, M),
        jnp.int32)
    q1 = jnp.asarray(rng.normal(size=(C, H, HD)), dtype)
    lengths = jnp.full((C,), cand.span, jnp.int32)
    pads = jnp.zeros((C,), jnp.int32)
    qc = jnp.asarray(rng.normal(size=(B, CH, H, HD)), dtype)
    pos = max(0, cand.span - CH)
    pad = jnp.zeros((B,), jnp.int32)

    def best_of(fn, *args) -> float:
        with dispatch.force_pallas():
            jfn = jax.jit(fn)
            jfn(*args).block_until_ready()       # compile + warm
            best = float("inf")
            for _ in range(repeats):
                t0 = time.perf_counter()
                jfn(*args).block_until_ready()
                best = min(best, time.perf_counter() - t0)
        return best

    return {
        "decode_wall_s": best_of(
            lambda q, k, v: paged_attention_pallas(
                q, k, v, tables, lengths, pads), q1, pool_k, pool_v),
        "prefill_wall_s": best_of(
            lambda q, k, v: paged_prefill_pallas(
                q, k, v, tables[:B], pos, pad=pad), qc, pool_k, pool_v),
    }


def sweep_paged_kernels(model_cfg, engine_cfg, *,
                        block_sizes: Optional[Sequence[int]] = None,
                        topology: str = "v5p-8",
                        repeats: int = 5) -> dict:
    """Run the sweep and return the artifact dict.

    Correctness runs everywhere (interpret mode off-TPU); timing runs
    only on a TPU, where a timing failure raises. The winner is the
    fastest candidate whose BOTH kernels passed correctness (combined
    decode+prefill wall); off-TPU nothing is timed and the incumbent
    geometry wins by default, labeled
    ``winner_source: "default-untimed"`` so a consumer can tell a
    measured answer from a fallback."""
    import jax

    grid = candidate_grid(engine_cfg, block_sizes)
    backend = jax.default_backend()
    # timing is meaningful ONLY on a real TPU: everywhere else the
    # pallas kernels run in interpret mode (`dispatch.interpret_mode`),
    # and interpreter wall-clock would crown a winner by interpreter
    # overhead — a GPU host degrades to the structured skip like CPU
    timed = backend == "tpu"
    results = []
    for cand in grid:
        entry = {
            "block_size": cand.block_size,
            "blocks_per_slot": cand.blocks_per_slot,
            **_correctness_case(model_cfg, engine_cfg, cand),
        }
        ok = (entry["decode"].get("ok")
              and entry["prefill"].get("ok")
              and entry["shared_spec"].get("ok"))
        if timed and ok:
            # on a TPU a timing failure fails the sweep: a candidate
            # that passed correctness but cannot be timed must not
            # quietly hand the crown to the untimed default
            entry["timing"] = _time_candidate(
                model_cfg, engine_cfg, cand, repeats=repeats)
        elif not timed:
            entry["timing"] = {"skipped": f"not a TPU ({backend})"}
        results.append(entry)

    passing = [r for r in results
               if r["decode"].get("ok") and r["prefill"].get("ok")
               and r["shared_spec"].get("ok")]
    winner, source = None, None
    measured = [r for r in passing
                if "decode_wall_s" in (r.get("timing") or {})]
    if measured:
        best = min(measured,
                   key=lambda r: (r["timing"]["decode_wall_s"]
                                  + r["timing"]["prefill_wall_s"]))
        winner = {"block_size": best["block_size"],
                  "blocks_per_slot": best["blocks_per_slot"]}
        source = "measured"
    elif passing:
        incumbent = [r for r in passing
                     if r["block_size"] == engine_cfg.block_size]
        best = incumbent[0] if incumbent else passing[0]
        winner = {"block_size": best["block_size"],
                  "blocks_per_slot": best["blocks_per_slot"]}
        source = "default-untimed"
    return {
        "kind": "rlt-paged-kernel-autotune",
        "model": model_fingerprint(model_cfg),
        "topology": topology,
        "backend": backend,
        "span": engine_cfg.block_size * engine_cfg.blocks_per_slot,
        "capacity": engine_cfg.capacity,
        "prefill_chunk": engine_cfg.prefill_chunk,
        "prefill_batch": engine_cfg.prefill_batch,
        "results": results,
        "winner": winner,
        "winner_source": source,
    }


def save_artifact(artifact: dict, path: str) -> None:
    """Atomic JSON write (tmp + replace — the checkpoint meta
    discipline: a killed sweep never leaves a torn artifact)."""
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(artifact, f, indent=2, sort_keys=True)
    os.replace(tmp, path)


def load_artifact(path: str) -> dict:
    with open(path) as f:
        doc = json.load(f)
    if doc.get("kind") != "rlt-paged-kernel-autotune":
        raise ValueError(
            f"{path} is not a paged-kernel autotune artifact "
            f"(kind={doc.get('kind')!r})")
    return doc


def apply_autotune(engine_cfg, artifact: dict, *, model_cfg=None):
    """The engine-consumable seam: re-geometry ``engine_cfg`` to the
    artifact's winning candidate.

    Refuses an artifact with no winner, a per-slot span that differs
    from the config's (the sweep holds span constant — a mismatched
    span means the artifact was swept for a different deployment), or
    — when ``model_cfg`` is given — a model fingerprint mismatch (a
    v5p-swept llama3-8b artifact must not silently re-tile a tiny
    CPU config)."""
    winner = artifact.get("winner")
    if not winner:
        raise ValueError(
            "autotune artifact has no winner (no candidate passed "
            "correctness) — refusing to re-geometry the engine")
    if model_cfg is not None:
        want = model_fingerprint(model_cfg)
        if artifact.get("model") != want:
            raise ValueError(
                f"autotune artifact was swept for model "
                f"{artifact.get('model')!r}, not {want!r}")
    span = engine_cfg.block_size * engine_cfg.blocks_per_slot
    if artifact.get("span") != span:
        raise ValueError(
            f"autotune artifact span {artifact.get('span')} != engine "
            f"span {span} — swept for a different slot geometry")
    return dataclasses.replace(
        engine_cfg, block_size=winner["block_size"],
        blocks_per_slot=winner["blocks_per_slot"])

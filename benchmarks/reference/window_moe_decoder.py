"""Plain reference of a decoder with sliding-window and full attention
layers and a parallel attention-and-experts block, as Cohere's `cohere2_moe`
family publishes it (command-a-plus's language model is one).

Written from the published equations in `jax.numpy`, float32, every matrix
product at `Precision.HIGHEST`. No kernel, no cache, no batching; it
imports nothing of `ray_lightning_tpu`. Weights arrive in the published
layout from the benchmark's seeded generator, which reads the leaves from
`tables` (the file of this name under `benchmarks/tables/`).

**A layer** on the residual stream `x` [S, D]; it is of kind `window` or
`full` (`tables.layer_kinds`: the published `layer_types`).

1. `h = (x - mean(x)) / sqrt(var(x) + eps) * g`: LayerNorm, no bias. One
   norm a layer: both halves read `h` (`use_parallel_block`).
2. `q = h W_q` [S, H, hd], `k = h W_k`, `v = h W_v` [S, Hkv, hd]; no bias,
   no QK norm. `window`: q and k rotated over all hd dims (theta
   `rope_theta`), and row s sees `t <= s` with `s - t < sliding_window`.
   `full`: no rotation, row s sees every `t <= s`. Scores over sqrt(hd),
   softmax, query head n reads KV head `n // (H / Hkv)`;
   `attn = concat(heads) W_o`.
3. `p = sigmoid(h W_r)` [S, E]; the `num_experts_per_tok` largest are
   chosen (ties to the lower index); `w = p_chosen / sum(p_chosen)`.
   `routed = sum over the chosen experts e HELD here of w_e (silu(h G_e) *
   (h U_e)) D_e`.
4. `shared = 1/n sum over the n shared experts of (silu(h G_j) * (h U_j))
   D_j`: four experts run and averaged.
5. `x' = x + attn + routed + shared`.
6. After the last layer: `logits = logit_scale * LayerNorm_f(x) E^T`, E the
   tied embedding.

**RoPE pairing:** dimension `i` rotates with `i + hd/2` (rotate-half). The
published code (`rope_gptj`) rotates `2i` with `2i + 1`; with seeded weights
that is this model under a fixed permutation of the columns of `W_q` and
`W_k`.

**The share.** The layer is given `(experts_first, num_experts)`: it routes
over all `router_experts` and sums over the chosen experts in `[first,
first + held)` only, plus the shared experts. What the absent experts would
add is left out, as in the program.

Departures from "one forward pass over everything", all to fit the chip's
memory and none changing the arithmetic: attention is computed a KV head's
group of query heads and a block of query rows at a time (`lax.map`), so
that `[H, S, S]` scores never exist, and a window layer's block of rows
reads only the band of keys it can see; the held and the shared experts are
walked one at a time (`lax.scan`), each held expert on every row with the
rows' weights for it (zero where it was not chosen); the serving check
calls `layer` once a layer so that one layer's float32 weights are
resident at a time. There is no training cell for this architecture, so no
`sequence_loss`.

`quant` is the control's hook: a function applied to BOTH operands of every
matrix product. `None` is the reference; `fp8_operands` rounds each operand
to 4 significant bits (e4m3) after a per-tensor scale, the step below the
bfloat16 operands the configuration states.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from benchmarks.harness import common

tables = common.load_model_file(common.checkout_of(__file__), "tables",
                                "window_moe_decoder")
HIGHEST = jax.lax.Precision.HIGHEST
Quant = Optional[Callable[[jnp.ndarray], jnp.ndarray]]


def fp8_operands(x):
    """Round to float8 e4m3's grid: scale the tensor's largest magnitude to
    224, keep 4 exponent and 3 mantissa bits, scale back."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = amax / 224.0
    return jax.lax.reduce_precision(x / scale, exponent_bits=4,
                                    mantissa_bits=3) * scale


def _mm(a, b, quant: Quant):
    if quant is not None:
        a, b = quant(a), quant(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def layer_norm(x, gain, eps: float):
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(centred * centred, axis=-1, keepdims=True)
    return centred * jax.lax.rsqrt(var + eps) * gain


def rope(x, positions, theta: float):
    """x [S, heads, d]: dimension i rotates with i + d/2 (see the module's
    text for the published pairing)."""
    d = x.shape[-1]
    i = jnp.arange(d // 2, dtype=jnp.float32)
    ang = (positions.astype(jnp.float32)[:, None]
           * theta ** (-2.0 * i / d)[None, :])[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _fit(total: int, want: int) -> int:
    b = max(1, min(total, want))
    while total % b:
        b -= 1
    return b


def attention(hp: dict, kind: str, w: dict, h, quant: Quant,
              q_block: int = 512):
    """Causal attention of one sequence h [S, D] (already normed), of the
    layer's kind."""
    s = h.shape[0]
    nh, nkv, hd = (hp["num_attention_heads"], hp["num_key_value_heads"],
                   hp["head_dim"])
    rep = nh // nkv
    q = _mm(h, w["q_proj"], quant).reshape(s, nh, hd)
    k = _mm(h, w["k_proj"], quant).reshape(s, nkv, hd)
    v = _mm(h, w["v_proj"], quant).reshape(s, nkv, hd)
    if kind == tables.WINDOW:
        pos = jnp.arange(s)
        q, k = rope(q, pos, hp["rope_theta"]), rope(k, pos, hp["rope_theta"])
        window = hp["sliding_window"]
    elif kind == tables.FULL:
        window = s                       # every earlier token is in sight
    else:
        raise ValueError(f"no layer kind {kind!r}")
    qb = _fit(s, q_block)
    # the keys a block of rows can see: a band that ends with the block
    band = min(s, window + qb)
    scale = hd ** -0.5

    def group(args):
        qg, kg, vg = args                # [rep, S, hd], [S, hd], [S, hd]

        def rows(start):
            first = jnp.maximum(start + qb - band, 0)
            qrow = jax.lax.dynamic_slice_in_dim(qg, start, qb, axis=1)
            kk = jax.lax.dynamic_slice_in_dim(kg, first, band, axis=0)
            vv = jax.lax.dynamic_slice_in_dim(vg, first, band, axis=0)
            score = _mm(qrow, kk.T, quant) * scale     # [rep, qb, band]
            t = first + jnp.arange(band)[None, :]
            row = start + jnp.arange(qb)[:, None]
            seen = (t <= row) & (row - t < window)
            score = jnp.where(seen[None], score, -jnp.inf)
            return _mm(jax.nn.softmax(score, axis=-1), vv, quant)

        out = jax.lax.map(rows, jnp.arange(0, s, qb))  # [S/qb, rep, qb, hd]
        return out.transpose(1, 0, 2, 3).reshape(rep, s, hd)

    out = jax.lax.map(group, (
        q.transpose(1, 0, 2).reshape(nkv, rep, s, hd),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    out = out.reshape(nh, s, hd).transpose(1, 0, 2).reshape(s, nh * hd)
    return _mm(out, w["o_proj"], quant)


def swiglu(x, gate, up, down, quant: Quant):
    return _mm(jax.nn.silu(_mm(x, gate, quant)) * _mm(x, up, quant), down,
               quant)


def route(hp: dict, scores):
    """Plain top-k: scores [S, E] (sigmoid) -> (chosen [S, k], weights
    [S, k]), the weights normalised over the chosen."""
    chosen = jnp.argsort(-scores, axis=-1,
                         stable=True)[:, : hp["num_experts_per_tok"]]
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, picked / picked.sum(-1, keepdims=True)


def routed_share(hp: dict, w: dict, h, quant: Quant):
    """sum over the chosen experts in [first, first + held) of w_e E_e(h).
    The router's own product is never quantised: it runs in float32 in the
    configuration too."""
    scores = jax.nn.sigmoid(jnp.matmul(h, w["gate"], precision=HIGHEST))
    chosen, weights = route(hp, scores)
    first = hp["experts_first"]

    def one(acc, expert):
        index, gate, up, down = expert
        weight = jnp.where(chosen == first + index, weights, 0.0).sum(-1)
        return acc + weight[:, None] * swiglu(h, gate, up, down, quant), None

    held = w["experts_gate_proj"].shape[0]
    acc, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        jnp.arange(held), w["experts_gate_proj"], w["experts_up_proj"],
        w["experts_down_proj"]))
    return acc


def shared_mean(w: dict, h, quant: Quant):
    """The shared experts, each run whole, averaged."""
    def one(acc, expert):
        return acc + swiglu(h, *expert, quant), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        w["shared_gate_proj"], w["shared_up_proj"], w["shared_down_proj"]))
    return acc / w["shared_gate_proj"].shape[0]


def layer(hp: dict, kind: str, w: dict, x, quant: Quant = None):
    """One decoder block on one sequence x [S, D]; `kind` is one of
    `tables.layer_kinds`'."""
    h = layer_norm(x, w["input_layernorm"], hp["layer_norm_eps"])
    return (x + attention(hp, kind, w, h, quant)
            + routed_share(hp, w, h, quant) + shared_mean(w, h, quant))


def embed(g: dict, tokens):
    return g["embed_tokens"][tokens]


def head_logits(hp: dict, g: dict, x, quant: Quant = None):
    """Final norm and the tied output head on rows x [n, D]."""
    y = layer_norm(x, g["norm"], hp["layer_norm_eps"])
    return hp["logit_scale"] * _mm(y, g["embed_tokens"].T, quant)


def forward(hp: dict, w_layers, g: dict, tokens, quant: Quant = None):
    """The whole forward pass of one sequence, logits [S, V]: `w_layers` is
    one dict of leaves a layer, in order (the tests' form; the serving
    check walks the layers itself)."""
    x = embed(g, tokens)
    for kind, w in zip(tables.layer_kinds(hp), w_layers):
        x = layer(hp, kind, w, x, quant)
    return head_logits(hp, g, x, quant)

"""Plain reference of a decoder of gated short convolutions with a
full-attention layer every few and an expert layer, as Liquid AI's
`lfm2_moe` family publishes it (LFM2-24B-A2B is one).

Written from the published equations in `jax.numpy`, float32, every matrix
product at `Precision.HIGHEST`. No kernel, no cache, no batching; it
imports nothing of `ray_lightning_tpu`. Weights arrive in the published
layout from the benchmark's seeded generator, which reads the leaves from
`tables` (the file of this name under `benchmarks/tables/`).

**A layer** on the residual stream `x` [S, D]; its kind (`tables.
layer_kinds`) is its op, `conv` or `attention`, and its ffn, dense
(`_dense` behind the kind) or routed.

1. `y = RMSNorm(x; operator_norm)`, eps `norm_eps`.
2. `conv`: `[B, C, u] = y W_in` (D -> 3D, three equal parts in this order,
   no bias); `v = B * u`; `c_t = sum_{j < K} w[j] * v_{t - K + 1 + j}`, K =
   `conv_L_cache` taps a channel, rows before the sequence zero, no bias;
   `op = (C * c) W_out`.
   `attention`: `q = y W_q` [S, H, hd], `k = y W_k`, `v = y W_v` [S, Hkv,
   hd], no bias; RMSNorm over each head's hd of q (`q_layernorm`) and of k
   (`k_layernorm`); q and k rotated over all hd dims (theta `rope_theta`);
   row s sees every `t <= s`; scores over sqrt(hd), softmax, query head n
   reads KV head `n // (H / Hkv)`; `op = concat(heads) W_o`.
3. `h = x + op`; `z = RMSNorm(h; ffn_norm)`.
4. dense: `ffn = (silu(z G) * (z U)) D`, width `intermediate_size`.
   routed: `s = sigmoid(z W_r)` [S, E]; the `num_experts_per_tok` largest
   of `s + b` are chosen (`b` the expert bias; ties to the lower index);
   `w = s_chosen / (sum(s_chosen) + 1e-6) * routed_scaling_factor`: the
   bias decides the choice, never the weight. `ffn = sum over the chosen
   experts e HELD here of w_e (silu(z G_e) * (z U_e)) D_e`. No shared
   expert.
5. `x' = h + ffn`.
6. After the last layer: `logits = RMSNorm(x; norm) E^T`, E the tied
   embedding.

**RoPE pairing:** dimension `i` rotates with `i + hd/2` (rotate-half), the
family's own.

**The share.** The layer is given `(experts_first, num_experts)`: it routes
over all `router_experts` and sums over the chosen experts in `[first,
first + held)` only. LFM2-24B-A2B's configuration holds all 64, so nothing
is left out there; the share is what the CPU tests cut.

Departures from "one forward pass over everything", all to fit the chip's
memory and none changing the arithmetic: attention is computed a KV head's
group of query heads and a block of query rows at a time (`lax.map`), so
that `[H, S, S]` scores never exist; the held experts are walked one at a
time (`lax.scan`), each on every row with the rows' weights for it (zero
where it was not chosen); the serving check calls `layer` once a layer so
that one layer's float32 weights (2.4 GB of an expert layer's) are resident
at a time. There is no training cell for this architecture, so no
`sequence_loss`.

`quant` is the control's hook: a function applied to BOTH operands of every
matrix product. `None` is the reference; `fp8_operands` rounds each operand
to 4 significant bits (e4m3) after a per-tensor scale, the step below the
bfloat16 operands the configuration states. The router's product and the
convolution (no matrix product) are never quantised.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from benchmarks.harness import common

tables = common.load_model_file(common.checkout_of(__file__), "tables",
                                "conv_moe_decoder")
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


def rms_norm(x, gain, eps: float):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gain


def rope(x, positions, theta: float):
    """x [S, heads, d]: dimension i rotates with i + d/2."""
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


def attention(hp: dict, w: dict, y, quant: Quant, q_block: int = 512):
    """Causal attention of one sequence y [S, D] (already normed)."""
    s = y.shape[0]
    nh, nkv, hd = (hp["num_attention_heads"], hp["num_key_value_heads"],
                   hp["head_dim"])
    rep, eps = nh // nkv, hp["norm_eps"]
    pos = jnp.arange(s)
    q = rms_norm(_mm(y, w["q_proj"], quant).reshape(s, nh, hd),
                 w["q_layernorm"], eps)
    k = rms_norm(_mm(y, w["k_proj"], quant).reshape(s, nkv, hd),
                 w["k_layernorm"], eps)
    v = _mm(y, w["v_proj"], quant).reshape(s, nkv, hd)
    q, k = rope(q, pos, hp["rope_theta"]), rope(k, pos, hp["rope_theta"])
    qb = _fit(s, q_block)
    scale = hd ** -0.5

    def group(args):
        qg, kg, vg = args                # [rep, S, hd], [S, hd], [S, hd]

        def rows(start):
            qrow = jax.lax.dynamic_slice_in_dim(qg, start, qb, axis=1)
            score = _mm(qrow, kg.T, quant) * scale       # [rep, qb, S]
            seen = pos[None, :] <= (start + jnp.arange(qb))[:, None]
            score = jnp.where(seen[None], score, -jnp.inf)
            return _mm(jax.nn.softmax(score, axis=-1), vg, quant)

        out = jax.lax.map(rows, jnp.arange(0, s, qb))  # [S/qb, rep, qb, hd]
        return out.transpose(1, 0, 2, 3).reshape(rep, s, hd)

    out = jax.lax.map(group, (
        q.transpose(1, 0, 2).reshape(nkv, rep, s, hd),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    out = out.reshape(nh, s, hd).transpose(1, 0, 2).reshape(s, nh * hd)
    return _mm(out, w["o_proj"], quant)


def short_conv(hp: dict, w: dict, y, quant: Quant):
    """The gated short convolution of one sequence y [S, D] (already
    normed)."""
    s = y.shape[0]
    taps = hp["conv_L_cache"]
    b, c, u = jnp.split(_mm(y, w["in_proj"], quant), 3, axis=-1)
    v = jnp.concatenate([jnp.zeros((taps - 1, b.shape[1]), b.dtype), b * u],
                        0)
    conv = sum(w["conv_weight"][j] * v[j:j + s] for j in range(taps))
    return _mm(c * conv, w["out_proj"], quant)


def swiglu(x, gate, up, down, quant: Quant):
    return _mm(jax.nn.silu(_mm(x, gate, quant)) * _mm(x, up, quant), down,
               quant)


def route(hp: dict, scores, bias):
    """scores [S, E] (sigmoid), bias [E] -> (chosen [S, k], weights
    [S, k]). The bias decides the choice, never the weight."""
    chosen = jnp.argsort(-(scores + bias[None, :]), axis=-1,
                         stable=True)[:, : hp["num_experts_per_tok"]]
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / (picked.sum(-1, keepdims=True) + 1e-6)
    return chosen, weights * hp["routed_scaling_factor"]


def routed_share(hp: dict, w: dict, z, quant: Quant):
    """sum over the chosen experts in [first, first + held) of w_e E_e(z).
    The router's own product is never quantised: it runs in float32 in the
    configuration too."""
    scores = jax.nn.sigmoid(jnp.matmul(z, w["gate"], precision=HIGHEST))
    chosen, weights = route(hp, scores, w["expert_bias"])
    first = hp["experts_first"]

    def one(acc, expert):
        index, gate, up, down = expert
        weight = jnp.where(chosen == first + index, weights, 0.0).sum(-1)
        return acc + weight[:, None] * swiglu(z, gate, up, down, quant), None

    held = w["experts_gate_proj"].shape[0]
    acc, _ = jax.lax.scan(one, jnp.zeros_like(z), (
        jnp.arange(held), w["experts_gate_proj"], w["experts_up_proj"],
        w["experts_down_proj"]))
    return acc


def layer(hp: dict, kind: str, w: dict, x, quant: Quant = None):
    """One decoder block on one sequence x [S, D]; `kind` is one of
    `tables.layer_kinds`'."""
    w = tables.seeded(hp, kind, w)
    eps = hp["norm_eps"]
    y = rms_norm(x, w["operator_norm"], eps)
    if tables.op_of(kind) == tables.CONV:
        h = x + short_conv(hp, w, y, quant)
    else:
        h = x + attention(hp, w, y, quant)
    z = rms_norm(h, w["ffn_norm"], eps)
    if tables.is_dense(kind):
        return h + swiglu(z, w["gate_proj"], w["up_proj"], w["down_proj"],
                          quant)
    return h + routed_share(hp, w, z, quant)


def embed(g: dict, tokens):
    return g["embed_tokens"][tokens]


def head_logits(hp: dict, g: dict, x, quant: Quant = None):
    """Final norm and the tied output head on rows x [n, D]."""
    return _mm(rms_norm(x, g["norm"], hp["norm_eps"]), g["embed_tokens"].T,
               quant)


def forward(hp: dict, w_layers, g: dict, tokens, quant: Quant = None):
    """The whole forward pass of one sequence, logits [S, V]: `w_layers` is
    one dict of leaves a layer, in order (the tests' form; the serving
    check walks the layers itself)."""
    x = embed(g, tokens)
    for kind, w in zip(tables.layer_kinds(hp), w_layers):
        x = layer(hp, kind, w, x, quant)
    return head_logits(hp, g, x, quant)

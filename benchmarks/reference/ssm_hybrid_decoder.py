"""Plain reference of a decoder of state-space layers with an attention layer
a period, as AI21's Jamba family publishes it (Jamba2-3B is one).

Written from the published equations in `jax.numpy`, float32, every matrix
product at `Precision.HIGHEST`. No kernel, no cache, no batching; it
imports nothing of `ray_lightning_tpu`. Weights arrive in the published
layout from the benchmark's seeded generator, which reads the leaves from
`tables` (the file of this name under `benchmarks/tables/`; `tables.seeded`
finishes the three leaves of a state-space layer the hash cannot make).

All sizes from the published config: `hidden_size` D, `mamba_expand` x D =
E channels, `mamba_d_state` N, `mamba_d_conv` K, `mamba_dt_rank` R,
`intermediate_size` F, `num_attention_heads` H over `num_key_value_heads`
Hkv of `head_dim` hd, `rms_norm_eps`.

**Every layer** on the residual stream `x` [S, D] (`tables.layer_kinds`:
layer `i` is `attention` where `i % attn_layer_period ==
attn_layer_offset`, else `ssm`):

1. `h = x + mixer(RMSNorm(x))`
2. `x' = h + (silu(u G) * (u U)) W_down`, `u = RMSNorm(h)`. `num_experts` is
   1: every "expert layer" of the family is this one MLP, and there is no
   router.
3. After the last layer: `logits = RMSNorm_f(x) E^T`, E the tied embedding.

**State-space mixer** (`ssm`), on rows `u` [S, D]:

1. `[x, z] = u W_in` (D -> 2E, no bias: `mamba_proj_bias` false).
2. `x_t = silu(sum_j w_j x_{t - (K - 1) + j} + b)`: a depthwise causal
   convolution over K rows, rows before the first are zero, with bias
   (`mamba_conv_bias` true).
3. `[dt, B, C] = x W_x` (E -> R + 2N, no bias).
4. **Jamba's inner norms** (the departure from plain Mamba-1): `dt =
   RMSNorm_R(dt)`, `B = RMSNorm_N(B)`, `C = RMSNorm_N(C)`, each with a
   learned gain.
5. `delta = softplus(dt W_dt + b_dt)` (R -> E, with bias). `A = -exp(A_log)`
   [E, N].
6. For each row t, from `h_{-1} = 0`: `h_t = exp(delta_t A) * h_{t-1} +
   (delta_t B_t) x_t` (`h` is [E, N]); `y_t = h_t C_t + D x_t`.
7. `out = (y * silu(z)) W_out` (E -> D, no bias).

**Attention mixer** (`attention`): `q = u W_q` [S, H, hd], `k = u W_k`, `v =
u W_v` [S, Hkv, hd]; causal softmax attention at scale hd^-0.5, query head
n reads KV head `n // (H / Hkv)`; **no positional encoding of any kind**
(the family has no rotary key); `out = concat(heads) W_o`; no biases.

Departures from "one forward pass over everything", all to fit the chip's
memory and none changing the arithmetic: the recurrence is a plain
`lax.scan` over rows, walked in blocks of rows (`lax.scan` over blocks, the
state handed from block to block) so that only one block's `delta`, `B`, `C`
and outputs are live beside the `[S, E]` inputs and an 8.7k-token request
fits; attention is computed a block of query rows at a time (`lax.map`), so
`[H, S, S]` scores never exist; the serving check calls `layer` once a layer
so that one layer's float32 weights are resident at a time. There is no
training cell for this architecture, so no `sequence_loss`.

`quant` is the control's hook: a function applied to BOTH operands of every
matrix product. `None` is the reference; `fp8_operands` rounds each operand
to 4 significant bits (e4m3) after a per-tensor scale, the step below the
bfloat16 operands the configuration states. The recurrence itself, the
convolution and the norms are float32 in the configuration and are never
quantised.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from benchmarks.harness import common

tables = common.load_model_file(common.checkout_of(__file__), "tables",
                                "ssm_hybrid_decoder")
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
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain


def _fit(total: int, want: int) -> int:
    b = max(1, min(total, want))
    while total % b:
        b -= 1
    return b


def attention(hp: dict, w: dict, u, quant: Quant, q_block: int = 512):
    """Causal attention of one sequence u [S, D] (already normed), no
    rotation."""
    s = u.shape[0]
    nh, nkv, hd = (hp["num_attention_heads"], hp["num_key_value_heads"],
                   hp["head_dim"])
    rep = nh // nkv
    q = _mm(u, w["q_proj"], quant).reshape(s, nkv, rep, hd)
    k = _mm(u, w["k_proj"], quant).reshape(s, nkv, hd)
    v = _mm(u, w["v_proj"], quant).reshape(s, nkv, hd)
    qb = _fit(s, q_block)
    scale = hd ** -0.5

    def rows(start):
        qrow = jax.lax.dynamic_slice_in_dim(q, start, qb, axis=0)
        # [Hkv, rep * qb, hd] x [Hkv, hd, S]
        qg = qrow.transpose(1, 2, 0, 3).reshape(nkv, rep * qb, hd)
        score = _mm(qg, k.transpose(1, 2, 0), quant) * scale
        row = start + jnp.tile(jnp.arange(qb), rep)[:, None]
        seen = jnp.arange(s)[None, :] <= row
        score = jnp.where(seen[None], score, -jnp.inf)
        out = _mm(jax.nn.softmax(score, axis=-1), v.transpose(1, 0, 2),
                  quant)                                  # [Hkv, rep*qb, hd]
        return out.reshape(nkv, rep, qb, hd).transpose(2, 0, 1, 3)

    out = jax.lax.map(rows, jnp.arange(0, s, qb))   # [S/qb, qb, Hkv, rep, hd]
    return _mm(out.reshape(s, nh * hd), w["o_proj"], quant)


def causal_conv(x, weight, bias):
    """x [S, E], weight [K, E] (tap K - 1 on the row itself), bias [E]."""
    k, s = weight.shape[0], x.shape[0]
    xp = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x], 0)
    return bias + sum(xp[j:j + s] * weight[j] for j in range(k))


def recurrence(x, delta, b, c, a, d, block: int = 512):
    """The selective scan of one sequence, a plain `lax.scan` over rows in
    blocks of `block` rows: x, delta [S, E]; b, c [S, N]; a [E, N]; d [E]
    -> y [S, E]."""
    s, e = x.shape
    rb = _fit(s, block)

    def row(h, args):
        xt, dt, bt, ct = args
        h = jnp.exp(dt[:, None] * a) * h + (dt * xt)[:, None] * bt[None, :]
        return h, h @ ct + d * xt

    def rows(h, args):
        return jax.lax.scan(row, h, args)

    blocks = lambda v: v.reshape(s // rb, rb, v.shape[-1])
    _, y = jax.lax.scan(rows, jnp.zeros((e, a.shape[1]), jnp.float32),
                        (blocks(x), blocks(delta), blocks(b), blocks(c)))
    return y.reshape(s, e)


def state_space(hp: dict, w: dict, u, quant: Quant):
    """The state-space mixer on one sequence u [S, D] (already normed)."""
    n, r, eps = hp["mamba_d_state"], hp["mamba_dt_rank"], hp["rms_norm_eps"]
    x, z = jnp.split(_mm(u, w["in_proj"], quant), 2, axis=-1)
    x = jax.nn.silu(causal_conv(x, w["conv1d_weight"], w["conv1d_bias"]))
    dt, b, c = jnp.split(_mm(x, w["x_proj"], quant), [r, r + n], axis=-1)
    dt = rms_norm(dt, w["dt_layernorm"], eps)
    b = rms_norm(b, w["b_layernorm"], eps)
    c = rms_norm(c, w["c_layernorm"], eps)
    delta = jax.nn.softplus(_mm(dt, w["dt_proj"], quant)
                            + w["dt_proj_bias"])
    y = recurrence(x, delta, b, c, -jnp.exp(w["A_log"]), w["D"])
    return _mm(y * jax.nn.silu(z), w["out_proj"], quant)


def layer(hp: dict, kind: str, w: dict, x, quant: Quant = None):
    """One decoder block on one sequence x [S, D]; `kind` is one of
    `tables.layer_kinds`'; `w` the leaves the harness made for it."""
    w = tables.seeded(hp, kind, w, jnp)
    eps = hp["rms_norm_eps"]
    u = rms_norm(x, w["input_layernorm"], eps)
    if kind == tables.ATTENTION:
        h = x + attention(hp, w, u, quant)
    elif kind == tables.SSM:
        h = x + state_space(hp, w, u, quant)
    else:
        raise ValueError(f"no layer kind {kind!r}")
    u = rms_norm(h, w["pre_ff_layernorm"], eps)
    return h + _mm(jax.nn.silu(_mm(u, w["gate_proj"], quant))
                   * _mm(u, w["up_proj"], quant), w["down_proj"], quant)


def embed(g: dict, tokens):
    return g["embed_tokens"][tokens]


def head_logits(hp: dict, g: dict, x, quant: Quant = None):
    """Final norm and the tied output head on rows x [n, D]."""
    y = rms_norm(x, g["norm"], hp["rms_norm_eps"])
    return _mm(y, g["embed_tokens"].T, quant)


def forward(hp: dict, w_layers, g: dict, tokens, quant: Quant = None):
    """The whole forward pass of one sequence, logits [S, V]: `w_layers` is
    one dict of leaves a layer, in order (the tests' form; the serving
    check walks the layers itself)."""
    x = embed(g, tokens)
    for kind, w in zip(tables.layer_kinds(hp), w_layers):
        x = layer(hp, kind, w, x, quant)
    return head_logits(hp, g, x, quant)

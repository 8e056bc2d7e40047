"""Plain reference of a decoder of sliding-window and full attention layers
over routed ReGLU experts whose router reads the layer's input, as the
`SmallThinker` family publishes it, with its loss.

Written from the published equations in `jax.numpy`, float32, every matrix
product at `Precision.HIGHEST`. No kernel, no sort, no grouped product, no
cache, no batching; it imports nothing of `ray_lightning_tpu`. Weights
arrive in the published layout from the benchmark's seeded generator, which
reads the leaves from `tables` (the file of this name under
`benchmarks/tables/`).

**A layer** on the residual stream `x` [S, D]; it is of kind `full` or
`window` (`tables.layer_kinds`: the published `sliding_window_layout` and
`rope_layout`, whose flags coincide).

1. `r = x W_r` [S, E]: the router's logits, read from the layer's INPUT,
   before attention ("router placed before attention"). ASSUMED: `x`
   itself, not `RMSNorm_in(x)`; the config has no key for it and both are
   "before attention" (the configuration's file says so too).
2. `u = RMSNorm_in(x)`; `q = u W_q` [S, H, hd], `k = u W_k`, `v = u W_v`
   [S, Hkv, hd]; no bias, no QK norm (ASSUMED: the config has no key for
   either). `window`: q and k rotated over all hd dims (theta `rope_theta`),
   and row s sees `t <= s` with `s - t < sliding_window_size`. `full`: NO
   positional encoding at all, row s sees every `t <= s`. Scores over
   sqrt(hd), softmax, query head n reads KV head `n // (H / Hkv)`;
   `h = x + concat(heads) W_o`.
3. The `moe_num_active_primary_experts` largest of `r` are chosen (ties to
   the lower index); `w = softmax(r_chosen)` (softmax over all logits with
   the chosen normalised to sum 1 is the same number). With
   `z = RMSNorm_post(h)`: `x' = h + sum over the chosen experts e HELD here
   of w_e (relu(z G_e) * (z U_e)) D_e` (ReGLU). No shared expert, no
   groups, no bias on the choice, no auxiliary loss.
4. After the last layer: `logits = RMSNorm_f(x) W_head`, untied, over the
   rows of the vocabulary held.

**RoPE pairing:** dimension `i` rotates with `i + hd/2` (rotate-half).

**The share.** The layer is given `(experts_first,
moe_num_primary_experts)`: it routes over all `router_experts` and sums
over the chosen experts in `[first, first + held)` only. What the absent
experts would add is left out, as in the program.

Departures from "one forward pass over everything", all to fit the chip's
memory and none changing the arithmetic: attention is computed a KV head's
group of query heads and a block of query rows at a time (`lax.map`), so
that `[H, S, S]` scores (30 GB at 16k) never exist, a window layer's block
of rows reads only the band of keys it can see, and each block's scores are
recomputed in its backward pass; the held experts are walked one at a time
(`lax.scan`), each on EVERY row with the rows' weights for it (zero where
it was not chosen), and recomputed in the backward pass; `sequence_loss`
recomputes a layer's activations in its backward pass (`jax.checkpoint`)
and takes the head and the loss a block of rows at a time.

`quant` is the control's hook: a function applied to BOTH operands of every
matrix product but the router's, which runs in float32 in the configuration
too. `None` is the reference; `fp8_operands` rounds each operand to 4
significant bits (e4m3) after a per-tensor scale, the step below the
bfloat16 operands the configuration states.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from benchmarks.harness import common

tables = common.load_model_file(common.checkout_of(__file__), "tables",
                                "swa_moe_decoder")
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


def attention(hp: dict, kind: str, w: dict, u, quant: Quant,
              q_block: int = 512, positions=None):
    """Causal attention of one sequence u [S, D] (already normed), of the
    layer's kind; `positions` [S] are what a window layer rotates by
    (`arange(S)` where none are given; a full layer reads none)."""
    s = u.shape[0]
    nh, nkv, hd = (hp["num_attention_heads"], hp["num_key_value_heads"],
                   hp["head_dim"])
    rep = nh // nkv
    q = _mm(u, w["q_proj"], quant).reshape(s, nh, hd)
    k = _mm(u, w["k_proj"], quant).reshape(s, nkv, hd)
    v = _mm(u, w["v_proj"], quant).reshape(s, nkv, hd)
    if kind == tables.WINDOW:
        pos = jnp.arange(s) if positions is None else positions
        q, k = rope(q, pos, hp["rope_theta"]), rope(k, pos, hp["rope_theta"])
        window = hp["sliding_window_size"]
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

        @jax.checkpoint   # a block's scores are recomputed in its backward
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


def reglu(z, gate, up, down, quant: Quant):
    return _mm(jax.nn.relu(_mm(z, gate, quant)) * _mm(z, up, quant), down,
               quant)


def route(hp: dict, logits):
    """Plain top-k of the logits [S, E] -> (chosen [S, k], weights [S, k]):
    the weights the softmax over the chosen logits."""
    chosen = jnp.argsort(-logits, axis=-1, stable=True)[
        :, : hp["moe_num_active_primary_experts"]]
    return chosen, jax.nn.softmax(
        jnp.take_along_axis(logits, chosen, axis=-1), axis=-1)


def routed_share(hp: dict, w: dict, x, z, quant: Quant,
                 first: Optional[int] = None):
    """sum over the chosen experts in [first, first + held) of w_e E_e(z),
    the choice made from x. `first` defaults to the configuration's
    `experts_first`."""
    chosen, weights = route(hp, jnp.matmul(x, w["router"],
                                           precision=HIGHEST))
    first = hp["experts_first"] if first is None else first

    def one(acc, expert):
        index, gate, up, down = expert
        weight = jnp.where(chosen == first + index, weights, 0.0).sum(-1)
        return acc + weight[:, None] * jax.checkpoint(
            lambda z, g, u, d: reglu(z, g, u, d, quant))(z, gate, up, down), \
            None

    held = w["experts_gate_proj"].shape[0]
    acc, _ = jax.lax.scan(one, jnp.zeros_like(z), (
        jnp.arange(held), w["experts_gate_proj"], w["experts_up_proj"],
        w["experts_down_proj"]))
    return acc


def layer(hp: dict, kind: str, w: dict, x, quant: Quant = None,
          positions=None):
    """One decoder block on one sequence x [S, D]; `kind` is one of
    `tables.layer_kinds`'."""
    eps = hp["rms_norm_eps"]
    u = rms_norm(x, w["input_layernorm"], eps)
    h = x + attention(hp, kind, w, u, quant, positions=positions)
    z = rms_norm(h, w["post_attention_layernorm"], eps)
    return h + routed_share(hp, w, x, z, quant)


def embed(g: dict, tokens):
    return g["embed_tokens"][tokens]


def head_logits(hp: dict, g: dict, x, quant: Quant = None):
    """Final norm and the untied output head on rows x [n, D]."""
    return _mm(rms_norm(x, g["norm"], hp["rms_norm_eps"]), g["lm_head"],
               quant)


def forward(hp: dict, w_layers, g: dict, tokens, quant: Quant = None):
    """The whole forward pass of one sequence, logits [S, V]: `w_layers` is
    one dict of leaves a layer, in order."""
    x = embed(g, tokens)
    for kind, w in zip(tables.layer_kinds(hp), w_layers):
        x = layer(hp, kind, w, x, quant)
    return head_logits(hp, g, x, quant)


# ---- training: loss and gradients --------------------------------------------


def layers_in_order(hp: dict, stacks: dict):
    """[(kind, leaves of that one layer)] from the canonical stacks
    {kind: {leaf: [n_kind, ...]}}, in layer order."""
    seen, out = {}, []
    for kind in tables.layer_kinds(hp):
        j = seen.get(kind, 0)
        seen[kind] = j + 1
        out.append((kind, {k: v[j] for k, v in stacks[kind].items()}))
    return out


def sequence_loss(hp: dict, params: dict, tokens, quant: Quant = None):
    """Summed next-token cross-entropy of ONE row of tokens [S + 1].
    params = {"layers": {kind: stacked canonical leaves [n_kind, ...]},
    "globals": ...}. A layer's activations are recomputed in its backward
    pass."""
    inputs, targets = tokens[:-1], tokens[1:]
    x = embed(params["globals"], inputs)
    for kind, w in layers_in_order(hp, params["layers"]):
        x = jax.checkpoint(
            lambda x, w, kind=kind: layer(hp, kind, w, x, quant))(x, w)

    @jax.checkpoint     # a block's logits are recomputed in its backward
    def rows(block):
        xb, tb = block
        logits = head_logits(hp, params["globals"], xb, quant)
        logz = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]
        return jnp.sum(logz - picked)

    b = _fit(x.shape[0], 2048)      # [16384, V] float32 logits are 2.5 GB
    return jnp.sum(jax.lax.map(rows, (x.reshape(-1, b, x.shape[1]),
                                      targets.reshape(-1, b))))

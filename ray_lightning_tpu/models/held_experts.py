"""The expert layer of a chip that holds some of a layer's routed experts.

Shared by the decoders that have one (`models/mla_moe.py`,
`models/window_moe.py`), with their tests' anchor `generate_greedy`. The layer is told ``(experts_first, held)``: it
routes over all ``n_routed_experts`` and computes the part of the result
its own experts give, for the rows routed to them. What absent experts
would add is left out; no code stands in for other chips. Rows are sorted
by expert and multiplied by `ops.grouped_matmul` over a static bound on
rows that no routing can exceed (``tokens x min(experts a token, experts
held)``), so no row is ever dropped and the step compiles once.

What it reads of a decoder's configuration, whatever its class:

    dim, dtype                 the rows' width and the compute type
    n_routed_experts           the router's width: every expert of the layer
    n_experts_per_tok          experts a token takes
    experts_first, held        the experts held here: [first, first + held)
    expert_choice              how `route` chooses among the scores:
        "noaux_tc"  by groups with a bias that decides the choice and never
                    the weight (reads n_group, topk_group,
                    routed_scaling_factor; the layer has a `router_bias`);
                    one group is no groups: the biased scores' top k
    route_norm_eps             (optional) what is added to the sum the
                               chosen scores are normalised over; 1e-20
                               where the configuration names none
        "topk"      the plain top-k of the scores, weights normalised (no
                    bias, no groups, no scaling)
        "topk_softmax"  the scores are the router's LOGITS, not their
                    sigmoid: the plain top-k of them, weights `softmax`
                    over the chosen logits (softmax over all of them with
                    the chosen normalised to sum 1 is the same number)
    expert_activation          (optional) "silu", what the gate goes
                               through where the configuration names none,
                               or "relu" (ReGLU)

``trained=True`` is the layer a backward pass can cross (`models/swa_moe.py`
trains it), and it works over the rows that are there. The bound stays the
most it can run, so no routing drops a row; its sorted rows are cut into
`STRETCHES` equal stretches (`stretch_rows`), and everything as wide as a
row (``D``, ``F`` or ``2F``) happens inside ONE loop over the stretches that
hold a row: the trip count is the layer's own ``ceil(rows / stretch)``, known
on the device once the pairs are sorted (`_held_rows`, a `jax.custom_vjp`
whose forward and backward are each one `lax.fori_loop`, so the compiler
sees one stretch-shaped body a pass; under `jax.jit`, so a decoder's layers
share one trace and one lowered function). A stretch gathers its rows of ``h`` BY
INDEX, runs the differentiable grouped products (`ops/grouped_matmul.py`,
``trained``) with the layer's group sizes clipped to it (`stretch_sizes`),
and adds its weighted rows into their tokens (`_sum_by_token`: the stretch's
rows re-ordered by token tile, then `ops.grouped_matmul.grouped_row_sums`,
float32 products and sums with the weights unrounded). The transposes are
the same two moves the other way round, so nothing scatters (XLA's own
transpose of a gather is a scatter-add, row after row on a TPU) and the
serving decoders' one-hot products (``2 R T D`` FLOPs each) are not paid.
Only integers and one float a pair are as long as the (token, expert)
pairs: the key, the two sorts, the places and the weights; no array over
the pairs or over the whole bound is as wide as a row. Gradients reach the
router through the chosen weights, never through the choice. The bound on
rows and what the layer computes are the same either way.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_lightning_tpu.ops.grouped_matmul import (
    TRAINED_ROW_TILE, grouped_matmul, grouped_row_sums, row_tile,
)

CHOICES = ("noaux_tc", "topk", "topk_softmax")
ACTIVATIONS = {"silu": nn.silu, "relu": nn.relu}
#: stretches the trained layer cuts its bound of rows into, where the
#: bound's row tiles divide so: a layer runs as many as hold a row. At a
#: sixth, the rows a seeded router sends to 16 held experts of 64 (a tenth
#: of the bound) fit one stretch with room, even routing's quarter takes two
STRETCHES = 6


def _normal(std: float = 0.02):
    return nn.initializers.normal(stddev=std)


def _mm(x, w, dtype):
    """Operands at the activation dtype, float32 accumulation."""
    return jnp.dot(x.astype(dtype), w.astype(dtype),
                   preferred_element_type=jnp.float32).astype(dtype)


def _normalised(cfg, scores, experts):
    picked = jnp.take_along_axis(scores, experts, axis=-1)
    eps = getattr(cfg, "route_norm_eps", 1e-20)
    return picked / (jnp.sum(picked, axis=-1, keepdims=True) + eps)


def route(cfg, scores, bias=None):
    """scores [T, E] float32 (sigmoid of the router's logits; the logits
    themselves for `topk_softmax`) -> (experts [T, k] int32, weights [T, k]
    float32), by ``cfg.expert_choice``. `noaux_tc` takes ``bias`` [E],
    which decides the choice, never the weight; the others take none."""
    t, e = scores.shape
    if cfg.expert_choice == "topk_softmax":
        chosen, experts = jax.lax.top_k(scores, cfg.n_experts_per_tok)
        return experts.astype(jnp.int32), jax.nn.softmax(chosen, axis=-1)
    if cfg.expert_choice == "topk":
        _, experts = jax.lax.top_k(scores, cfg.n_experts_per_tok)
        return experts.astype(jnp.int32), _normalised(cfg, scores, experts)
    if cfg.expert_choice != "noaux_tc":
        raise ValueError(f"expert_choice {cfg.expert_choice!r} is none of "
                         f"{CHOICES}")
    choice = scores + bias[None, :]
    if cfg.n_group > 1:
        groups = choice.reshape(t, cfg.n_group, e // cfg.n_group)
        group_score = jnp.sum(jax.lax.top_k(groups, 2)[0], axis=-1)
        _, kept = jax.lax.top_k(group_score, cfg.topk_group)
        group_mask = jnp.zeros((t, cfg.n_group), bool).at[
            jnp.arange(t)[:, None], kept].set(True)
        choice = jnp.where(group_mask[:, :, None], groups,
                           -jnp.inf).reshape(t, e)
    _, experts = jax.lax.top_k(choice, cfg.n_experts_per_tok)
    weights = _normalised(cfg, scores, experts) * cfg.routed_scaling_factor
    return experts.astype(jnp.int32), weights


def held_rows_bound(cfg, tokens: int) -> int:
    """Rows the expert product is compiled for: what no routing of
    ``tokens`` tokens can exceed, rounded up to the product's row tile."""
    rows = tokens * min(cfg.n_experts_per_tok, cfg.held)
    tm = row_tile(rows)
    return -(-rows // tm) * tm


def stretch_rows(rows: int) -> int:
    """Rows of one stretch of a bound of ``rows``: the bound over the most
    stretches, up to `STRETCHES`, that its row tiles divide into."""
    tile = TRAINED_ROW_TILE if rows % TRAINED_ROW_TILE == 0 else row_tile(rows)
    return rows // max(n for n in range(1, STRETCHES + 1)
                       if (rows // tile) % n == 0)


def stretch_sizes(sizes, lo, rows: int):
    """group_sizes [G] of rows sorted by group -> the group sizes of the
    rows ``[lo, lo + rows)`` among them (they sum to `sizes` over the
    stretches of a bound)."""
    ends = jnp.cumsum(sizes)
    return (jnp.clip(ends, lo, lo + rows)
            - jnp.clip(ends - sizes, lo, lo + rows)).astype(jnp.int32)


def _pairs_by_expert(cfg, experts):
    """(key [T * k], order [T * k]): each (token, expert) pair's held
    expert (``held`` where its expert lives elsewhere), and the pairs
    sorted by it, held pairs first."""
    held = cfg.held
    local = experts - cfg.experts_first
    key = jnp.where((local >= 0) & (local < held), local, held).reshape(-1)
    return key, jnp.argsort(key, stable=True)


def _sorted_rows(held: int, k: int, rows: int, weights, key, order):
    """The bound's ``rows`` rows from the sorted pairs (`_pairs_by_expert`):
    (token [R] int32, weight [R] float32, group_sizes [held] int32)."""
    if rows <= order.shape[0]:
        order = order[:rows]       # held pairs sort first and fit the bound
    else:
        order = jnp.concatenate(
            [order, jnp.zeros(rows - order.shape[0], order.dtype)])
    is_held = jnp.arange(rows) < jnp.sum(key < held)
    weight = jnp.where(is_held, weights.reshape(-1)[order], 0.0)
    sizes = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0)
    return (order // k).astype(jnp.int32), weight, sizes.astype(jnp.int32)


def held_dispatch(cfg, experts, weights):
    """The (token, expert) pairs of held experts, sorted by expert.
    Returns (token [R] int32, weight [R] float32, group_sizes [held]
    int32); rows past ``sum(group_sizes)`` carry weight 0."""
    t, k = experts.shape
    key, order = _pairs_by_expert(cfg, experts)
    return _sorted_rows(cfg.held, k, held_rows_bound(cfg, t), weights, key,
                        order)


def _sum_by_token(into, rows, scale, token, n, use_pallas):
    """into [T, D] float32 + y, ``y[t]`` the sum of ``scale[r] * rows[r]``
    over the r < n with ``token[r] == t`` (``scale`` None is 1), each
    product and sum float32's. The rows are re-ordered by token tile (a sort
    of their keys and one gather of the rows) and each tile sums its own by
    the place each row's token has in it (`grouped_row_sums`): nothing here
    is longer than the rows."""
    tokens, d = into.shape
    tile = next((t for t in (512, 256, 128) if tokens % t == 0), tokens)
    tiles = tokens // tile
    at = jnp.arange(token.shape[0], dtype=jnp.int32)
    key = jnp.where(at < n, token // tile, tiles)
    # the sort carries each row's place in its tile along
    _, slot, order = jax.lax.sort((key, token % tile, at), num_keys=1)
    sizes = jnp.sum(key[:, None] == jnp.arange(tiles)[None, :], axis=0)
    if scale is not None:
        scale = jnp.take(scale, order, mode="clip")
    return grouped_row_sums(
        jnp.take(rows, order, axis=0, mode="clip"), slot, sizes,
        into.reshape(tiles, tile, d), scale, use_pallas).reshape(into.shape)


class _Stretched(NamedTuple):
    """What `_held_rows` is compiled for: static, and the same for every
    layer of a decoder."""
    held: int
    k: int                        # experts a token takes
    rows: int                     # the bound (`held_rows_bound`)
    stretch: int                  # rows of a stretch (`stretch_rows`)
    use_pallas: Optional[bool]
    activation: str

    def sorted_rows(self, weights, key, order):
        return _sorted_rows(self.held, self.k, self.rows, weights, key,
                            order)

    def live(self, sizes):
        """The stretches that hold a row, of group sizes [held]."""
        return -(-jnp.sum(sizes) // self.stretch)


def _stretch_of(how, s, token, weight, sizes):
    """Stretch ``s`` of the sorted rows: (first row, tokens [R1], weights
    [R1], group sizes [held], rows of a group it holds)."""
    r1 = how.stretch
    lo = s * r1
    sz = stretch_sizes(sizes, lo, r1)
    return (lo, jax.lax.dynamic_slice(token, (lo,), (r1,)),
            jax.lax.dynamic_slice(weight, (lo,), (r1,)), sz, jnp.sum(sz))


def _products(how, x, w_gate_up, w_down, sizes, index):
    """A stretch's rows through its experts: [R1, D] -> [R1, D]."""
    gate, up = jnp.split(grouped_matmul(
        x, w_gate_up, sizes, how.use_pallas, layer=index, trained=True),
        2, axis=-1)
    return grouped_matmul(ACTIVATIONS[how.activation](gate) * up, w_down,
                          sizes, how.use_pallas, layer=index, trained=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _held_rows(how, h, w_gate_up, w_down, weights, key, order, index):
    """The held experts over the sorted (token, expert) pairs, a stretch of
    the bound at a time: h [T, D], the experts' stacks, weights [T, k]
    float32, (key, order) of `_pairs_by_expert` -> (y [T, D] float32,
    group_sizes [held] int32).
    ``how``: a `_Stretched`, static.

    One `lax.fori_loop` a pass whose trips are the stretches that hold a
    row; JAX differentiates nothing of it. The backward pass runs a
    stretch's gather and products again inside its own trip and carries the
    cotangents of ``h`` and of the stacks in float32."""
    return _held_rows_fwd(how, h, w_gate_up, w_down, weights, key, order,
                          index)[0]


def _held_rows_fwd(how, h, w_gate_up, w_down, weights, key, order, index):
    dt = h.dtype
    with jax.named_scope("moe_dispatch"):
        token, weight, sizes = how.sorted_rows(weights, key, order)
    with jax.named_scope("moe_experts"):      # once a pass, not a stretch
        stacks = w_gate_up.astype(dt), w_down.astype(dt)

    def stretch(s, y):
        with jax.named_scope("moe_dispatch"):
            _, tok, w, sz, n = _stretch_of(how, s, token, weight, sizes)
            x = jnp.take(h, tok, axis=0, mode="clip")
        with jax.named_scope("moe_experts"):
            out = _products(how, x, *stacks, sz, index)
        with jax.named_scope("moe_dispatch"):
            return _sum_by_token(y, out, w, tok, n, how.use_pallas)

    y = jax.lax.fori_loop(0, how.live(sizes), stretch,
                          jnp.zeros(h.shape, jnp.float32))
    return (y, sizes), (h, w_gate_up, w_down, weights, key, order, index)


def _held_rows_bwd(how, res, grads):
    grad, _ = grads                   # the sizes are integers
    h, w_gate_up, w_down, weights, key, order, index = res
    dt = h.dtype
    with jax.named_scope("moe_dispatch"):
        token, weight, sizes = how.sorted_rows(weights, key, order)
    with jax.named_scope("moe_experts"):
        stacks = w_gate_up.astype(dt), w_down.astype(dt)

    def stretch(s, carry):
        d_h, d_stacks, d_row = carry
        with jax.named_scope("moe_dispatch"):
            lo, tok, w, sz, n = _stretch_of(how, s, token, weight, sizes)
            x = jnp.take(h, tok, axis=0, mode="clip")
            # the transpose of the sum by token: each row reads its token's
            d_y = jnp.take(grad, tok, axis=0, mode="clip")
        with jax.named_scope("moe_experts"):
            out, pull = jax.vjp(
                lambda x, a, b: _products(how, x, a, b, sz, index),
                x, *stacks)
        with jax.named_scope("moe_dispatch"):
            d_w = jnp.where(jnp.arange(how.stretch) < n, jnp.sum(
                d_y * out.astype(jnp.float32), axis=-1), 0.0)
            d_out = (d_y * w[:, None]).astype(dt)
        with jax.named_scope("moe_experts"):
            d_x, *d_ws = pull(d_out)
            d_stacks = tuple(a + b.astype(jnp.float32)
                             for a, b in zip(d_stacks, d_ws))
        with jax.named_scope("moe_dispatch"):
            # the transpose of the gather: a token sums its rows'
            d_h = _sum_by_token(d_h, d_x, None, tok, n, how.use_pallas)
            d_row = jax.lax.dynamic_update_slice(d_row, d_w, (lo,))
        return d_h, d_stacks, d_row

    d_h, d_stacks, d_row = jax.lax.fori_loop(
        0, how.live(sizes), stretch,
        (jnp.zeros(h.shape, jnp.float32),
         tuple(jnp.zeros(w.shape, jnp.float32) for w in stacks),
         jnp.zeros((how.rows,), jnp.float32)))
    with jax.named_scope("moe_dispatch"):
        # place[p]: where pair p stands among the sorted rows; a pair of an
        # expert held elsewhere sorts behind the held ones and has no weight
        place = jnp.argsort(order).astype(jnp.int32)
        d_weights = jnp.where(
            (key < how.held) & (place < how.rows),
            jnp.take(d_row, place, mode="clip"), 0.0)
    return (d_h.astype(dt), d_stacks[0].astype(w_gate_up.dtype),
            d_stacks[1].astype(w_down.dtype),
            d_weights.reshape(weights.shape), None, None, None)


_held_rows.defvjp(_held_rows_fwd, _held_rows_bwd)
#: a decoder's expert layers have one shape: traced and lowered once, as a
#: function each layer calls, forward and transposed
_held_rows_once = jax.jit(_held_rows, static_argnums=0)


class HeldExperts(nn.Module):
    """The routed part of an expert layer on the chip that holds experts
    ``[cfg.experts_first, cfg.experts_first + cfg.held)``: rows h [T, D]
    -> (sum over a row's chosen HELD experts of w_i E_i(h) [T, D] float32, counts
    int32 [2]: rows routed here, the fullest expert's rows; ``trained``
    adds a third, the stretches of the bound that held a row) and, with
    ``with_hits``, a third: bool [held], the experts that got a row.

    ``cfg`` is any configuration with the members the module's text lists.
    The experts' weights are arguments, not parameters of this module:
    ``(gate_up [n, held, D, 2F], down [n, held, F, D])`` is the stack of
    ALL the expert layers, read at ``index``. A layer's weights are never
    sliced out of the stack (`ops/grouped_matmul.py` says why), so they
    cannot ride the layer scan as its sliced parameters. One layer's own
    ``(gate_up [held, D, 2F], down [held, F, D])`` does as well.

    ``route_from`` [T, D]: the rows the router reads where they are not the
    rows the experts read (a router placed before attention reads the
    layer's input). ``trained``: see the module's text."""

    cfg: object
    with_hits: bool = False
    trained: bool = False

    @nn.compact
    def __call__(self, h, stacks, index=0, use_pallas=None, route_from=None):
        cfg = self.cfg
        dt = cfg.dtype
        act = ACTIVATIONS[getattr(cfg, "expert_activation", "silu")]
        p = self.param
        # the router's weights stay float32 whatever the checkpoint's type
        router = p("router", _normal(), (cfg.dim, cfg.n_routed_experts),
                   jnp.float32)
        bias = None
        if cfg.expert_choice == "noaux_tc":
            bias = p("router_bias", nn.initializers.zeros,
                     (cfg.n_routed_experts,), jnp.float32
                     ).astype(jnp.float32)
        w_gate_up, w_down = stacks
        t = h.shape[0]
        with jax.named_scope("moe_router"):
            logits = jnp.dot(
                (h if route_from is None else route_from).astype(jnp.float32),
                router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
            scores = (logits if cfg.expert_choice == "topk_softmax"
                      else jax.nn.sigmoid(logits))
            experts, weights = route(cfg, scores, bias)
        if self.trained:
            y, sizes, live = self._by_index(h, experts, weights, stacks,
                                            index, use_pallas)
            counts = jnp.stack([jnp.sum(sizes), jnp.max(sizes), live])
            return (y, counts, sizes > 0) if self.with_hits else (y, counts)
        with jax.named_scope("moe_dispatch"):
            token, weight, sizes = held_dispatch(cfg, experts, weights)
            # a one-hot product gathers the rows: exact, and on the MXU
            pick = (token[:, None] == jnp.arange(t)[None, :]).astype(dt)
            rows = jnp.dot(pick, h.astype(dt),
                           preferred_element_type=jnp.float32).astype(dt)
        with jax.named_scope("moe_experts"):
            gate, up = jnp.split(grouped_matmul(
                rows, w_gate_up.astype(dt), sizes, use_pallas, layer=index),
                2, axis=-1)
            out = grouped_matmul(act(gate) * up, w_down.astype(dt),
                                 sizes, use_pallas, layer=index)
        with jax.named_scope("moe_dispatch"):
            weighted = (out.astype(jnp.float32) * weight[:, None]).astype(dt)
            # rows of no group have weight 0 and a zeroed product
            y = jnp.dot(pick.T, weighted,
                        preferred_element_type=jnp.float32)
        counts = jnp.stack([jnp.sum(sizes), jnp.max(sizes)])
        if self.with_hits:
            return y, counts, sizes > 0
        return y, counts              # float32, as the combine summed it

    def _by_index(self, h, experts, weights, stacks, index, use_pallas):
        """The trained layer: (y [T, D] float32, group_sizes [held], the
        stretches of the bound that held a row)."""
        cfg = self.cfg
        t, k = experts.shape
        rows = held_rows_bound(cfg, t)
        how = _Stretched(cfg.held, k, rows, stretch_rows(rows), use_pallas,
                         getattr(cfg, "expert_activation", "silu"))
        with jax.named_scope("moe_dispatch"):
            key, order = _pairs_by_expert(cfg, experts)
        y, sizes = _held_rows_once(how, h.astype(cfg.dtype), *stacks,
                                   weights, key, order,
                                   jnp.asarray(index, jnp.int32))
        return y, sizes, how.live(sizes)


def generate_greedy(model, params, prompt, max_new_tokens: int):
    """Greedy continuation of ``prompt`` [S] by the full forward pass over
    a growing prefix (no cache: the tests' anchor, not a serving path)."""
    prompt = jnp.asarray(prompt, jnp.int32)
    total = prompt.shape[0] + max_new_tokens
    tokens = jnp.zeros((total,), jnp.int32).at[: prompt.shape[0]].set(prompt)
    forward = jax.jit(lambda p, t: model.apply({"params": p}, t[None])[0])
    for i in range(prompt.shape[0], total):
        nxt = jnp.argmax(forward(params, tokens)[i - 1]).astype(jnp.int32)
        tokens = tokens.at[i].set(nxt)
    return tokens

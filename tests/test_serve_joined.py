"""The serving step that joins its lanes (ISSUE 45): in a tick that carries
a chunk, the dense decoder's C decode rows and the chunk's CH rows go
through `Llama` in ONE call, the weights read once. Pinned here on the CPU
(kernels in interpret mode) against the two-pass step the same decoder gets
when it does not declare `joins_lanes`: same inputs, same results; which
step a decoder gets; and the `joined_rows` counter of `rlt.serve.dispatch`.
Since ISSUE 46 the expert decoder of gated short convolutions (`ConvMoe`)
joins too: its cases are below the dense decoder's, with the convolutions'
tails and the device-side counters among what has to agree.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.models.conv_moe import ConvMoe, ConvMoeConfig
from ray_lightning_tpu.models.llama import Llama, LlamaConfig
from ray_lightning_tpu.ops.attention import (
    PagedDecodeView,
    PagedJoinedView,
    PagedPrefillView,
)
from ray_lightning_tpu.serve.engine import (
    DecodeEngine,
    EngineConfig,
    build_step,
    idle_prefill,
    joins_lanes,
)
from ray_lightning_tpu.serve.kv_cache import init_pool, state_pool_spec

C, P, M, CH = 4, 8, 4, 8
ECFG = EngineConfig(capacity=C, block_size=P, blocks_per_slot=M,
                    prefill_chunk=CH)
#: slot s owns blocks 1 + s * M .. (0 is the scratch block)
TABLES = (1 + np.arange(C * M, dtype=np.int32)).reshape(C, M)


class TwoPassLlama(Llama):
    """The dense decoder as it was served before it joined its lanes."""
    joins_lanes = False


@pytest.fixture(scope="module")
def tiny():
    """A kernel-tiling tiny model (head_dim 64, GQA 2:1) with seeded
    weights, its joined and its two-pass step, and a pool in mid-service:
    slot 0 decoding at pos 12, slot 1 at pos 5, slot 3 with the first
    8 tokens of a longer prompt cached, slot 2 empty."""
    cfg = LlamaConfig(vocab_size=256, dim=128, n_layers=2, n_heads=2,
                      n_kv_heads=1, hidden_dim=256, max_seq_len=128,
                      remat=False, dtype=jnp.float32)
    params = jax.jit(Llama(cfg).init)(
        jax.random.key(45), jnp.zeros((1, 4), jnp.int32))["params"]
    steps = {"joined": jax.jit(build_step(Llama(cfg), ECFG, True, True)),
             "two_pass": jax.jit(build_step(TwoPassLlama(cfg), ECFG, True,
                                            True))}
    rng = np.random.default_rng(45)
    prompts = {n: rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 8, 11, 20)}
    state = dict(pool=init_pool(cfg, ECFG.pool_spec),
                 last_logits=jnp.zeros((C, cfg.vocab_size), jnp.float32),
                 pos=np.zeros(C, np.int32),
                 rngs=np.arange(2 * C, dtype=np.uint32).reshape(C, 2))
    for decoding, chunk in (
            ([0, 0, 0, 0], (0, prompts[11][:8], 0, -1)),
            ([0, 0, 0, 0], (0, prompts[11][8:], 8, 2)),
            ([1, 0, 0, 0], (1, prompts[5], 0, 4)),
            ([1, 1, 0, 0], (3, prompts[20][:8], 0, -1))):
        state, _ = _tick(steps["two_pass"], params, state, decoding, chunk)
    assert state["pos"].tolist() == [13, 6, 0, 8]
    return cfg, params, steps, prompts, state


def _tick(step, params, state, decoding, chunk=None, temp=None, top_k=None,
          ecfg=ECFG, tables=TABLES):
    """One call of ``step`` on ``state`` (pool, last_logits, pos, rngs) as
    the scheduler would make it. ``chunk`` = (slot, tokens, start, last
    row). Returns the state after the tick (with the step's ``counts``
    where the decoder counts) and the emitted tokens."""
    decoding = np.asarray(decoding, bool)
    c, ch = ecfg.capacity, ecfg.prefill_chunk
    if chunk is None:
        prefill = idle_prefill(ecfg)
    else:
        slot, toks, start, last = chunk
        padded = np.zeros(ch, np.int32)
        padded[:len(toks)] = toks
        prefill = (np.int32(slot), padded, np.int32(start), np.int32(last))
    temp = np.zeros(c, np.float32) if temp is None else temp
    top_k = np.zeros(c, np.int32) if top_k is None else top_k
    n_pool = len(state["pool"])
    out = step(params, *state["pool"], state["last_logits"], tables,
               state["pos"], decoding, temp, top_k, state["rngs"], *prefill)
    pool, (last_logits, rngs, emitted, *counts) = out[:n_pool], out[n_pool:]
    pos = state["pos"] + decoding
    if chunk is not None:
        pos[slot] = start + (last + 1 if last >= 0 else ch)
    return (dict(pool=tuple(pool), last_logits=last_logits, pos=pos,
                 rngs=np.asarray(rngs),
                 **({"counts": np.asarray(counts[0])} if counts else {})),
            np.asarray(emitted))


def _assert_same(a, b, live, tol=2e-5, slot_leaves=0):
    """Two states after the same tick: every pool leaf but the scratch
    block (masked garbage by contract: the lanes' redirected writes land
    there in another order) and the live slots' logits to the paged tests'
    float32 tolerance, the keys to the bit. The last ``slot_leaves`` leaves
    hold a row a slot and no scratch block: they agree whole."""
    paged = len(a["pool"]) - slot_leaves
    for i, (x, y) in enumerate(zip(a["pool"], b["pool"])):
        skip = 1 if i < paged else 0
        np.testing.assert_allclose(np.asarray(x)[:, skip:],
                                   np.asarray(y)[:, skip:],
                                   rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(a["last_logits"])[live],
                               np.asarray(b["last_logits"])[live],
                               rtol=tol, atol=tol)
    np.testing.assert_array_equal(a["rngs"], b["rngs"])
    np.testing.assert_array_equal(a["pos"], b["pos"])


#: case -> (the chunk of the tick: slot, prompt length, tokens [a:b], start,
#: last row; the slots whose logits are live after it)
CASES = {
    "no_chunk": (None, [0, 1]),
    "chunk_mid_prompt": ((2, 20, 0, 8, 0, -1), [0, 1]),
    "chunk_ends_prompt": ((2, 8, 0, 8, 0, 7), [0, 1, 2]),
    "chunk_behind_context_others_decode": ((3, 20, 8, 16, 8, -1), [0, 1]),
    "partial_tail_chunk": ((3, 11, 8, 11, 8, 2), [0, 1, 3]),
}


@pytest.mark.parametrize("case", list(CASES))
def test_joined_step_matches_the_two_pass_step(tiny, case):
    """Same inputs, same results: the pool, `last_logits` and the keys
    after the tick, and the tokens the NEXT tick draws from those logits
    (greedy, and one slot sampling under a top-k filter)."""
    cfg, params, steps, prompts, state = tiny
    chunk, live = CASES[case]
    if chunk is not None:
        slot, n, a, b, start, last = chunk
        chunk = (slot, prompts[n][a:b], start, last)
    temp = np.array([0.0, 0.7, 0.0, 0.0], np.float32)
    top_k = np.array([0, 5, 0, 0], np.int32)
    after, emitted = {}, {}
    for name, step in steps.items():
        st, _ = _tick(step, params, state, [1, 1, 0, 0], chunk, temp, top_k)
        after[name] = st
        # the next tick decodes every live slot, the one whose prompt the
        # chunk ended among them: its first token is drawn from the kept row
        decoding = np.isin(np.arange(C), live)
        _, emitted[name] = _tick(step, params, st, decoding, None, temp,
                                 top_k)
    _assert_same(after["joined"], after["two_pass"], live)
    np.testing.assert_array_equal(emitted["joined"][live],
                                  emitted["two_pass"][live])
    # the tick did something: a decoding slot's logits moved
    assert not np.allclose(np.asarray(after["joined"]["last_logits"])[0],
                           np.asarray(state["last_logits"])[0])


def _jaxpr(model):
    runtime = (TABLES, np.zeros(C, np.int32), np.zeros(C, bool),
               np.zeros(C, np.float32), np.zeros(C, np.int32),
               np.zeros((C, 2), np.uint32), *idle_prefill(ECFG))
    cfg = model.cfg
    params = jax.eval_shape(model.init, jax.random.key(0),
                            jnp.zeros((1, 4), jnp.int32))["params"]
    pool = jax.eval_shape(lambda: init_pool(cfg, ECFG.pool_spec))
    return jax.make_jaxpr(build_step(model, ECFG, True, True))(
        params, *pool, jnp.zeros((C, cfg.vocab_size), jnp.float32), *runtime)


def _scans(jaxpr, layers=2):
    """Layer scans of a jaxpr (a model pass each), with those of its
    sub-programs: the sampling stage's 32-trip selection is a scan too."""
    n = 0
    for eqn in jaxpr.eqns:
        n += (eqn.primitive.name == "scan"
              and eqn.params["length"] == layers)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _scans(sub, layers)
    return n


def _conds(jaxpr):
    """(false branch, true branch) of every cond of the step's top level."""
    return [tuple(b.jaxpr for b in e.params["branches"])
            for e in jaxpr.jaxpr.eqns if e.primitive.name == "cond"]


def test_step_without_the_member_is_the_two_pass_step(tiny, monkeypatch):
    """The engine asks the decoder: with `joins_lanes` absent or False the
    step is the one it was, the decode pass in front of a branch that holds
    the chunk's pass or nothing."""
    cfg = tiny[0]
    two_pass = _jaxpr(TwoPassLlama(cfg))
    monkeypatch.delattr(Llama, "joins_lanes")
    assert not hasattr(Llama(cfg), "joins_lanes")
    assert str(_jaxpr(Llama(cfg))) == str(two_pass)
    ((no_chunk, with_chunk),) = _conds(two_pass)
    assert (_scans(two_pass.jaxpr), _scans(no_chunk),
            _scans(with_chunk)) == (2, 0, 1)
    assert not no_chunk.eqns


def test_joined_step_holds_one_model_pass_a_branch(tiny):
    """Every model pass of the joined step is the TRUE branch of a cond
    whose other branch passes the pool through (a pass in branch 0 of a
    cond over both would copy the pool on the chip: `build_step`): one over
    C + CH rows where the tick has a chunk, one over the C decode rows where
    it has none, and the head of the first reads C + 1 rows."""
    cfg = tiny[0]
    joined = _jaxpr(Llama(cfg))
    (idle_a, with_chunk), (idle_b, no_chunk) = _conds(joined)
    assert not idle_a.eqns and not idle_b.eqns
    assert (_scans(joined.jaxpr), _scans(with_chunk),
            _scans(no_chunk)) == (2, 1, 1)

    def dot_rows(jaxpr):
        # rows of every product against a weight of the model's width
        return sorted({int(np.prod(e.outvars[0].aval.shape[:-1]))
                       for e in jaxpr.eqns
                       if e.primitive.name == "dot_general"})

    assert dot_rows(no_chunk) == [C]
    assert dot_rows(with_chunk) == [C + 1]       # the head; layers scanned
    (layer,) = [e.params["jaxpr"].jaxpr for e in with_chunk.eqns
                if e.primitive.name == "scan"]
    products = [e for e in layer.eqns if e.primitive.name == "dot_general"]
    # wqkv, wo, w_gate_up, w_down: once each, over every row of the tick
    assert len(products) == 4 and dot_rows(layer) == [C + CH]


def _decoder(name):
    from ray_lightning_tpu.models import serving

    return next(serving._row(k)[1] for k, v in serving._DECODERS.items()
                if v[2] == name)


@pytest.mark.parametrize("name", ["MlaMoe", "WindowMoe", "SsmHybrid",
                                  "DeltaHybrid"])
def test_the_other_decoders_do_not_join(name):
    """They keep the two-pass step until each has a joined branch of its
    own: none declares the member, so `joins_lanes` is False for them."""
    decoder = _decoder(name)
    assert not getattr(decoder, "joins_lanes", False)
    assert not joins_lanes(decoder, ECFG, True, True)


def test_the_short_convolution_expert_decoder_joins():
    """ISSUE 46: the second decoder with a joined branch says so itself,
    and the engine joins it under the conditions it joins the first."""
    decoder = _decoder("ConvMoe")
    assert decoder is ConvMoe and decoder.joins_lanes is True
    assert joins_lanes(decoder, ECFG, True, True)
    assert not joins_lanes(decoder, ECFG, True, False)


@pytest.mark.parametrize("fused,fused_prefill,batch,want", [
    (True, True, 1, True),
    (False, True, 1, False),     # the reference decode lane gathers
    (True, False, 1, False),     # the reference prefill lane gathers
    (True, True, 2, False),      # a group of left-padded rows
])
def test_engine_joins_from_what_it_can_observe(tiny, fused, fused_prefill,
                                               batch, want):
    ecfg = EngineConfig(capacity=C, block_size=P, blocks_per_slot=M,
                        prefill_chunk=CH, prefill_batch=batch)
    assert joins_lanes(Llama(tiny[0]), ecfg, fused, fused_prefill) is want


@pytest.mark.parametrize("decoder,with_chunk,want", [
    (Llama, True, CH), (Llama, False, 0),
    (TwoPassLlama, True, 0), (TwoPassLlama, False, 0),
])
def test_joined_rows_on_the_dispatch_event(tiny, decoder, with_chunk, want):
    """`_step_work` is what `rlt.serve.dispatch` carries: the chunk's CH
    rows in a joined tick (a partial chunk's tail rides too), 0 in a tick
    without a chunk and in every tick of a decoder that does not join."""
    cfg, params = tiny[:2]
    engine = DecodeEngine(decoder(cfg), params, ECFG, use_pallas=True)
    assert engine.joined is (decoder is Llama)
    prefill = ((np.int32(2), np.zeros(CH, np.int32), np.int32(8),
                np.int32(2)) if with_chunk else idle_prefill(ECFG))
    work = engine._step_work(
        np.array([13, 6, 0, 8], np.int32), np.array([1, 1, 0, 0], bool),
        prefill, np.zeros(C, np.float32), np.zeros(C, np.int32))
    assert work["joined_rows"] == want
    assert work["prefill_rows"] == (3 if with_chunk else 0)


def test_joined_view_is_a_pytree_of_its_lanes():
    """The view rides `nn.scan` as a broadcast argument: its leaves are
    the two lanes' and `last_row`, and each lane keeps its static
    dispatch."""
    z = jnp.zeros((1, 2), jnp.int32)
    view = PagedJoinedView(
        PagedDecodeView(z, z[0], z[0], z[0], use_pallas=True),
        PagedPrefillView(z, z, z, use_pallas=True), jnp.int32(3))
    leaves, treedef = jax.tree_util.tree_flatten(view)
    assert len(leaves) == 4 + 3 + 1
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert rebuilt.decode.use_pallas is True
    assert rebuilt.prefill.use_pallas is True
    assert int(rebuilt.last_row) == 3


# ---- the expert decoder of gated short convolutions (ISSUE 46) -------------
#
# The same comparison for `ConvMoe`: three pool leaves (K and V paged by
# token at two heads of 64 a row, the convolutions' tails a row a slot), a
# step that returns device-side counts, and the kinds of tick the real-rows
# rule makes: a slid-back chunk, a slot's second request.

CP, CM, CCH = 16, 4, 16
CONV_ECFG = EngineConfig(capacity=C, block_size=CP, blocks_per_slot=CM,
                         prefill_chunk=CCH)
CONV_TABLES = (1 + np.arange(C * CM, dtype=np.int32)).reshape(C, CM)
#: the paged lanes against the full pass at these widths (float32): the
#: order of a few sums, `tests/test_conv_moe_serve.py:TOL`
CONV_TOL = 5e-5
COUNTS = ("expert_rows", "expert_rows_max", "experts_hit", "conv_rows",
          "state_slots")


class TwoPassConvMoe(ConvMoe):
    """The decoder as it was served before it joined its lanes."""
    joins_lanes = False


def _conv_tick(step, params, state, decoding, chunk=None, temp=None,
               top_k=None):
    return _tick(step, params, state, decoding, chunk, temp, top_k,
                 ecfg=CONV_ECFG, tables=CONV_TABLES)


@pytest.fixture(scope="module")
def conv_tiny():
    """`ConvMoeConfig.tiny` with no silent path (the norms, the router's
    bias and the embedding seeded as `tests/test_conv_moe_serve.py` seeds
    them), its joined and its two-pass step, and a pool in mid-service:
    slot 0 decoding at pos 20, slot 1 at pos 6; slot 2 EMPTY but for the
    tails and K/V an earlier request left in it (its position is back at
    0); slot 3 with 24 tokens of a longer prompt cached, the last 8 of them
    sent as a partial chunk so that a chunk from 16 finds its first 8 rows
    sent before."""
    from tests.test_conv_moe_serve import _seeded

    cfg = ConvMoeConfig.tiny()
    model = ConvMoe(cfg)
    params = _seeded(jax.jit(model.init)(
        jax.random.key(46), jnp.zeros((1, 8), jnp.int32))["params"], seed=46)
    steps = {"joined": jax.jit(build_step(model, CONV_ECFG, True, True)),
             "two_pass": jax.jit(build_step(TwoPassConvMoe(cfg), CONV_ECFG,
                                            True, True))}
    rng = np.random.default_rng(46)
    prompts = {n: rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 11, 16, 18, 40)}
    spec = state_pool_spec(CONV_ECFG.pool_spec, model.slot_state, C)
    state = dict(pool=init_pool(cfg, spec),
                 last_logits=jnp.zeros((C, cfg.vocab_size), jnp.float32),
                 pos=np.zeros(C, np.int32),
                 rngs=np.arange(2 * C, dtype=np.uint32).reshape(C, 2))
    for decoding, chunk in (
            ([0, 0, 0, 0], (0, prompts[18][:16], 0, -1)),
            ([0, 0, 0, 0], (0, prompts[18][16:], 16, 1)),
            ([1, 0, 0, 0], (1, prompts[5], 0, 4)),
            ([1, 1, 0, 0], (2, prompts[11], 0, 10)),
            ([0, 0, 0, 0], (3, prompts[40][:16], 0, -1)),
            ([0, 0, 0, 0], (3, prompts[40][16:24], 16, 7))):
        state, _ = _conv_tick(steps["two_pass"], params, state, decoding,
                              chunk)
    assert state["pos"].tolist() == [20, 6, 11, 24]
    assert float(jnp.abs(state["pool"][2][:, 2]).max()) > 0
    state["pos"][2] = 0           # the request that held slot 2 has gone
    return cfg, params, steps, prompts, state


#: case -> (the chunk of the tick: slot, prompt length, tokens [a:b], start,
#: last row; the slots whose logits are live after it; the chunk's real
#: rows as the convolutions count them)
CONV_CASES = {
    "no_chunk": (None, [0, 1], 0),
    "chunk_continues": ((3, 40, 24, 40, 24, -1), [0, 1], 16),
    "chunk_ends_prompt": ((3, 40, 24, 40, 24, 15), [0, 1, 3], 16),
    "partial_last_chunk": ((3, 40, 24, 29, 24, 4), [0, 1, 3], 5),
    # rows 16..23 were sent before: the first real row is row 8
    "slid_back_chunk": ((3, 40, 16, 32, 16, -1), [0, 1], 8),
    "slid_back_chunk_ends_prompt": ((3, 40, 16, 29, 16, 12), [0, 1, 3], 5),
    # slot 2 holds another request's tails: position 0 starts from zeros
    "chunk_at_zero_on_a_used_slot": ((2, 16, 0, 16, 0, 15), [0, 1, 2], 16),
}


@pytest.mark.parametrize("case", list(CONV_CASES))
def test_conv_moe_joined_step_matches_the_two_pass_step(conv_tiny, case):
    """Same inputs, same results: K, V and the tails, `last_logits`, the
    keys, the tokens the NEXT tick draws; and the device-side counts:
    `expert_rows`, `experts_hit`, `conv_rows` and `state_slots` equal
    (regrouping rows changes no row's routing), `expert_rows_max` the
    fullest expert of the ONE product: no less than the two-pass step's
    (the larger of its lanes' maxima), no more than the lanes' sum."""
    cfg, params, steps, prompts, state = conv_tiny
    chunk, live, real_rows = CONV_CASES[case]
    if chunk is not None:
        slot, n, a, b, start, last = chunk
        chunk = (slot, prompts[n][a:b], start, last)
    temp = np.array([0.0, 0.7, 0.0, 0.0], np.float32)
    top_k = np.array([0, 5, 0, 0], np.int32)
    after, emitted = {}, {}
    for name, step in steps.items():
        st, _ = _conv_tick(step, params, state, [1, 1, 0, 0], chunk, temp,
                           top_k)
        after[name] = st
        decoding = np.isin(np.arange(C), live)
        _, emitted[name] = _conv_tick(step, params, st, decoding, None,
                                      temp, top_k)
    _assert_same(after["joined"], after["two_pass"], live, tol=CONV_TOL,
                 slot_leaves=1)
    np.testing.assert_array_equal(emitted["joined"][live],
                                  emitted["two_pass"][live])
    assert not np.allclose(np.asarray(after["joined"]["last_logits"])[0],
                           np.asarray(state["last_logits"])[0])
    # the tails of the slot that took the chunk moved, those of the idle
    # slots did not
    tails = np.asarray(after["joined"]["pool"][2])
    before = np.asarray(state["pool"][2])
    idle = [s for s in (2, 3) if chunk is None or s != chunk[0]]
    np.testing.assert_array_equal(tails[:, idle], before[:, idle])
    if chunk is not None:
        assert not np.array_equal(tails[:, chunk[0]], before[:, chunk[0]])
    got = dict(zip(COUNTS, after["joined"]["counts"].tolist()))
    want = dict(zip(COUNTS, after["two_pass"]["counts"].tolist()))
    rows = (C + (CCH if chunk is not None else 0)) \
        * cfg.n_experts_per_tok * cfg.n_expert_layers
    assert got["expert_rows"] == want["expert_rows"] == rows
    assert got["experts_hit"] == want["experts_hit"]
    assert (got["conv_rows"], got["state_slots"]) == (
        want["conv_rows"], want["state_slots"]) == (real_rows, 2)
    # the decode lane's own fullest expert: the same tick without its chunk
    # (the decode rows are the same rows)
    alone, _ = _conv_tick(steps["two_pass"], params, state, [1, 1, 0, 0],
                          None, temp, top_k)
    decode_max = int(alone["counts"][1])
    assert want["expert_rows_max"] >= decode_max
    assert want["expert_rows_max"] <= got["expert_rows_max"] \
        <= decode_max + want["expert_rows_max"]
    if chunk is None:
        assert got["expert_rows_max"] == want["expert_rows_max"]


def _conv_jaxpr(model):
    runtime = (CONV_TABLES, np.zeros(C, np.int32), np.zeros(C, bool),
               np.zeros(C, np.float32), np.zeros(C, np.int32),
               np.zeros((C, 2), np.uint32), *idle_prefill(CONV_ECFG))
    cfg = model.cfg
    params = jax.eval_shape(model.init, jax.random.key(0),
                            jnp.zeros((1, 4), jnp.int32))["params"]
    spec = state_pool_spec(CONV_ECFG.pool_spec, model.slot_state, C)
    pool = jax.eval_shape(lambda: init_pool(cfg, spec))
    return jax.make_jaxpr(build_step(model, CONV_ECFG, True, True))(
        params, *pool, jnp.zeros((C, cfg.vocab_size), jnp.float32), *runtime)


def _run_bodies(jaxpr, lengths):
    """The bodies of a program's layer scans (one a run of layers)."""
    return [e.params["jaxpr"].jaxpr for e in jaxpr.eqns
            if e.primitive.name == "scan" and e.params["length"] in lengths]


def _calls(jaxpr, name):
    """Equations named ``name`` in a jaxpr and its sub-programs."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _calls(sub, name)
    return n


def test_conv_moe_joined_step_streams_the_experts_once_a_branch():
    """Each model pass is the TRUE branch of a cond of its own; the pass
    of a tick with a chunk holds ONE scan a run of layers, every product
    of a run's body over all C + CH rows (the expert layers' grouped
    products over the bound of C + CH tokens' rows: two kernels a layer,
    and an attention kernel a lane in an attention run), a head over
    C + 1 rows; and the step returns its five counts after `emitted`."""
    from ray_lightning_tpu.models.held_experts import held_rows_bound

    cfg = ConvMoeConfig.tiny()
    joined = _conv_jaxpr(ConvMoe(cfg))
    (idle_a, with_chunk), (idle_b, no_chunk) = _conds(joined)
    assert not idle_a.eqns and not idle_b.eqns
    runs = cfg.runs()
    lengths = {n for *_, n in runs}
    assert len(_run_bodies(with_chunk, lengths)) == len(runs)
    assert len(_run_bodies(no_chunk, lengths)) == len(runs)
    assert not _run_bodies(joined.jaxpr, lengths)
    assert joined.out_avals[-1].shape == (len(COUNTS),)

    def dot_rows(jaxpr):
        return sorted({int(np.prod(e.outvars[0].aval.shape[:-1]))
                       for e in jaxpr.eqns
                       if e.primitive.name == "dot_general"})

    assert dot_rows(no_chunk) == [C]
    assert dot_rows(with_chunk) == [C + 1]           # the head
    tokens = C + CCH
    bound = held_rows_bound(cfg, tokens)
    for (attention, dense, _, _), body in zip(
            runs, _run_bodies(with_chunk, lengths)):
        # the mixer's and the ffn's products over every row of the tick;
        # an expert layer's gather and combine over its rows' bound
        rows = {tokens} if dense else {tokens, bound}
        assert set(dot_rows(body)) == rows, (attention, dense)
        # the paged kernels a lane, the grouped products two a layer
        assert _calls(body, "pallas_call") == (
            2 * attention + 2 * (not dense))
    two_pass = _conv_jaxpr(TwoPassConvMoe(cfg))
    ((_, chunk_pass),) = _conds(two_pass)
    assert len(_run_bodies(two_pass.jaxpr, lengths)) == len(runs)
    assert len(_run_bodies(chunk_pass, lengths)) == len(runs)


@pytest.mark.parametrize("decoder,with_chunk,want", [
    (ConvMoe, True, CCH), (ConvMoe, False, 0),
    (TwoPassConvMoe, True, 0), (TwoPassConvMoe, False, 0),
])
def test_conv_moe_joined_rows_on_the_dispatch_event(conv_tiny, decoder,
                                                    with_chunk, want):
    """`joined_rows` for the second decoder that joins: the chunk's CH rows
    in a tick that carries one, beside the convolutions' own count of the
    chunk's REAL rows."""
    cfg, params = conv_tiny[:2]
    engine = DecodeEngine(decoder(cfg), params, CONV_ECFG, use_pallas=True)
    assert engine.joined is (decoder is ConvMoe)
    prefill = ((np.int32(3), np.zeros(CCH, np.int32), np.int32(16),
                np.int32(12)) if with_chunk else idle_prefill(CONV_ECFG))
    work = engine._step_work(
        np.array([20, 6, 0, 24], np.int32), np.array([1, 1, 0, 0], bool),
        prefill, np.zeros(C, np.float32), np.zeros(C, np.int32))
    assert work["joined_rows"] == want
    assert work["prefill_rows"] == (13 if with_chunk else 0)
    assert work["conv_rows"] == (5 if with_chunk else 0)

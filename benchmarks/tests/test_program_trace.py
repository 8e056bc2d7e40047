"""The reductions over the program's own names (`program_trace.py`) on
hand-built events, and a synthetic trace of the four-chip cell's size."""
import os
import random
import time

import pytest

from benchmarks.harness import program_trace as pt
from benchmarks.harness import shapes
from benchmarks.harness.common import BenchError, RunRecord
from benchmarks.harness.program_trace import HostEvent, Op

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
HP = {"num_attention_heads": 16, "num_key_value_heads": 8, "head_dim": 128,
      "num_hidden_layers": 24}


def _host(name, start, end, thread=0, **stats):
    return HostEvent("rlt." + name, start, end, thread, stats)


# ---- names ------------------------------------------------------------------


@pytest.mark.parametrize("path,scope", [
    ("jit(step)/jit(main)/transpose(jvp(fused_ce))/while/body/dot_general",
     "fused_ce"),
    ("jit(step)/jit(main)/optimizer/mul", "optimizer"),
    # the serving scan sits in `kv_pool`; a block's ops lie deeper
    ("jit(step)/Llama/kv_pool/layers/while/body/attn/wqkv/dot_general",
     "attn"),
    ("jit(step)/Llama/kv_pool/layers/while/body/dynamic_slice", "kv_pool"),
    ("jit(step)/Llama/kv_pool/layers/while/body/attn/kv_pool/scatter",
     "kv_pool"),
    ("jit(step)/sample/vmap(sort)", "sample"),
    ("jit(step)/Llama/lm_head/dot_general", "lm_head"),
    # a component that merely contains a scope's name is not that scope
    ("jit(step)/resample_all/attention/add", "unscoped"),
    ("", "unscoped")])
def test_innermost_scope_of_a_name_stack(path, scope):
    assert pt.innermost_scope(path) == scope


def test_an_op_event_is_joined_to_its_names_through_the_hlo_table():
    hlo = {"rlt_paged_decode.5": ("custom-call",
                                  "jit(step)/L/kv_pool/layers/while/body/"
                                  "attn/rlt_paged_decode/pallas_call"),
           "pad_maximum_fusion.6": ("fusion",
                                    "jit(step)/L/kv_pool/layers/while/body/"
                                    "attn/rlt_paged_decode/pallas_call"),
           "copy.71": ("copy", ""),
           "shard_map.385": ("custom-call",
                             "jit(s)/transpose(jvp(L))/attn/shard_map/"
                             "rlt_flash_bwd_dq/pallas_call")}
    text = ("%rlt_paged_decode.5 = bf16[64,16,128]{2,1,0} custom-call("
            "s32[64,160]{1,0} %get-tuple-element.833), custom_call_target=x")
    assert pt.resolve(text, hlo) == (
        "rlt_paged_decode.5 (custom-call bf16[64,16,128])",
        "rlt_paged_decode", "attn")
    # the fusion XLA puts in front of the kernel shares its name stack and is
    # not the kernel
    assert pt.resolve("%pad_maximum_fusion.6 = bf16[8]{0} fusion(bf16[8]{0} "
                      "%p), kind=kLoop", hlo)[1:] == (None, "attn")
    assert pt.resolve("%copy.71 = bf16[8]{0} copy(bf16[8]{0} %p)",
                      hlo)[1:] == (None, "unscoped")
    # a kernel under shard_map is named by its name stack, not its
    # instruction
    assert pt.resolve("%shard_map.385 = bf16[8]{0} custom-call(bf16[8]{0} "
                      "%p)", hlo)[1] == "rlt_flash_bwd_dq"
    # an op of a program whose HLO the trace does not hold keeps its name
    assert pt.resolve(text, {})[1:] == ("rlt_paged_decode", "unscoped")


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, payload):
    if isinstance(payload, int):
        return _varint(number << 3) + _varint(payload)
    return _varint((number << 3) | 2) + _varint(len(payload)) + payload


def test_hlo_tables_are_read_from_the_metadata_planes_wire_format():
    """An XSpace built field by field, as xplane.proto and hlo.proto number
    them, with what the walk has to step over."""
    def instruction(name, opcode, op_name):
        meta = _field(1, b"op_type") + _field(2, op_name.encode())
        return (_field(1, name.encode()) + _field(2, opcode.encode())
                + _field(35, 7) + (_field(7, meta) if op_name else b""))

    comp = (_field(1, b"main") + _field(2, instruction(
        "fusion.3", "fusion", "jit(step)/optimizer/add"))
        + _field(2, instruction("copy.1", "copy", "")))
    hlo_proto = _field(1, _field(1, b"jit_step") + _field(3, comp))
    event_meta = (_field(1, 5) + _field(2, b"jit_step(5)")
                  + _field(5, _field(1, 1) + _field(6, hlo_proto)))
    plane = (_field(1, 9) + _field(2, b"/host:metadata")
             + _field(4, _field(1, 5) + _field(2, event_meta))
             + _field(5, _field(1, 1) + _field(2, _field(2, b"Hlo Proto"))))
    other = _field(2, b"/device:TPU:0") + _field(3, b"\x08\x01")
    xspace = _field(1, other) + _field(1, plane)
    assert pt.hlo_op_names(xspace) == {"jit_step(5)": {
        "fusion.3": ("fusion", "jit(step)/optimizer/add"),
        "copy.1": ("copy", "")}}


# ---- pairing, scopes, idle --------------------------------------------------


def test_pairing_when_the_trace_starts_mid_tick():
    """The first execution began before the trace did, so its dispatch is
    not in it: it stays unpaired, and the others take their own dispatch,
    not a neighbour's."""
    runs = [(0.0, 0.9), (1.0, 1.9), (2.0, 2.9)]
    disp = [_host("serve.dispatch", 0.95, 0.96, kv_tokens=10),
            _host("serve.dispatch", 1.95, 1.96, kv_tokens=20),
            _host("serve.dispatch", 2.95, 2.96, kv_tokens=30)]
    pairs = pt.pair_dispatches(runs, disp)
    assert [(r, e.stats["kv_tokens"]) for r, e in pairs] == [
        ((1.0, 1.9), 10), ((2.0, 2.9), 20)]
    # two executions after one dispatch (a second program run) pair once
    assert len(pt.pair_dispatches([(1.0, 1.2), (1.3, 1.5)], disp[:1])) == 1


def test_scope_self_time_with_a_while_around_its_body():
    ops = [Op("while.9", 0.0, 10.0, None, "kv_pool"),
           Op("fusion.1", 1.0, 4.0, None, "attn"),
           Op("rlt_paged_decode.5", 4.0, 6.0, "rlt_paged_decode", "attn"),
           Op("dynamic-slice.2", 6.0, 7.0, None, "kv_pool"),
           Op("fusion.2", 7.0, 9.0, None, "mlp"),
           Op("copy.71", 10.0, 12.0, None, "unscoped")]
    got = pt.scope_self_seconds(ops)
    # the while keeps 10 - 8 of its own; its body is counted once
    assert got == {"kv_pool": pytest.approx(3.0), "attn": pytest.approx(5.0),
                   "mlp": pytest.approx(2.0), "unscoped": pytest.approx(2.0)}
    assert sum(got.values()) == pytest.approx(12.0)
    assert pt.kernel_seconds(ops) == {"rlt_paged_decode": (2.0, 1)}


def test_idle_outside_steps_by_innermost_phase():
    # two executions; inside the first the chip idles from 0.4 to 0.5
    runs = [(0.0, 1.0), (1.3, 2.0)]
    busy = [(0.0, 0.4), (0.5, 1.0), (1.3, 2.0)]
    gaps = pt.idle_outside(busy, runs, (0.0, 2.0))
    assert gaps == [pytest.approx((1.0, 1.3))]
    host = [_host("serve.tick", 0.9, 1.15), _host("serve.fetch", 0.95, 1.05),
            _host("serve.account", 1.05, 1.1),
            _host("serve.tick", 1.2, 2.1), _host("serve.put", 1.22, 1.28)]
    got = dict(pt.idle_by_phase(gaps, host))
    assert got == {"rlt.serve.fetch": pytest.approx(0.05),
                   "rlt.serve.account": pytest.approx(0.05),
                   "rlt.serve.tick": pytest.approx(0.05 + 0.02 + 0.02),
                   "rlt.serve.put": pytest.approx(0.06),
                   "unattributed": pytest.approx(0.05)}
    assert sum(got.values()) == pytest.approx(0.3)
    # per pair of executions; a gap before the first or after the last
    # execution belongs to no pair
    assert pt.idle_between([(0.0, 0.2), (1.0, 1.1), (1.2, 1.3), (2.0, 2.4)],
                           [(0.2, 1.0), (1.3, 2.0)]) == [
        pytest.approx(0.2)]


def test_ops_within_runs():
    ops = [Op("a", 0.1, 0.2, None, "x"), Op("b", 0.95, 1.05, None, "x"),
           Op("c", 1.5, 1.6, None, "x"), Op("d", 2.5, 2.6, None, "x")]
    assert [o.name for o in pt.within(ops, [(0.0, 1.0), (1.3, 2.0)])] == [
        "a", "c"]


# ---- the readers on a hand-built run ----------------------------------------


def _serve_tables(kernel="rlt_paged_decode"):
    """Three ticks of a serving step: 24 decode calls of 1 ms and one
    prefill call of 2 ms a tick, a dispatch before each."""
    ops, host, modules = [], [], []
    for k in range(3):
        t0 = k * 0.1
        modules.append(("jit_step(1)", t0, t0 + 0.09))
        ops.append(Op("while.9", t0, t0 + 0.05, None, "kv_pool"))
        for layer in range(24):
            s = t0 + 0.001 + layer * 0.002
            ops.append(Op(f"{kernel}.5", s, s + 0.001, kernel, "attn"))
        ops.append(Op("rlt_paged_prefill.7", t0 + 0.06, t0 + 0.062,
                      "rlt_paged_prefill", "attn"))
        ops.append(Op("sort.5", t0 + 0.07, t0 + 0.079, None, "sample"))
        host.append(_host("serve.tick", t0 - 0.005, t0 + 0.093, tick=k))
        host.append(_host("serve.put", t0 - 0.004, t0 - 0.002))
        host.append(_host("serve.dispatch", t0 - 0.002, t0 - 0.001,
                          decode_slots=32, kv_tokens=32 * 270,
                          prefill_rows=128 if k else 0,
                          prefill_ctx=256 if k else 0))
        host.append(_host("serve.fetch", t0 - 0.001, t0 + 0.0915))
    trace_ = pt.ProgramTrace([pt.Device(ops, modules)], host)
    return pt.build_tables(trace_, "serve")


def _run(tables, kind="serve_open", **kw):
    run = RunRecord(kind=kind, cell={"name": "cell"},
                    config={"model": "dense_decoder"}, traffic={},
                    hp=dict(HP), seconds=30.0, chips=1, peaks=PEAKS,
                    root=ROOT, **kw)
    run.trace = object()
    run.stamps[pt._STAMP] = tables
    return run


def test_decode_roofline_against_a_hand_computation():
    run = _run(_serve_tables())
    # a layer: K and V of 8,640 tokens (8 kv heads x 128 x 2 B each) and the
    # queries and outputs of 32 slots; memory-bound
    layer_bytes = 2 * 8640 * 8 * 128 * 2 + 2 * 32 * 16 * 128 * 2
    assert 4 * 8640 * 16 * 128 / 197e12 < layer_bytes / 819e9
    least = 24 * layer_bytes / 819e9        # a tick
    assert pt.paged_decode_roofline_pct(run) == pytest.approx(
        100.0 * least / 0.024, rel=1e-9)
    assert 0 < pt.paged_decode_roofline_pct(run) < 100


def test_prefill_roofline_counts_only_ticks_with_a_chunk():
    run = _run(_serve_tables())
    one = shapes.paged_prefill(128, 256, 16, 8, 128)
    least = 24 * shapes.roofline_seconds(one, PEAKS)["seconds"]
    # ticks 1 and 2 carry a chunk; tick 0's prefill call is not in the time
    assert pt.paged_prefill_roofline_pct(run) == pytest.approx(
        100.0 * 2 * least / (2 * 0.002), rel=1e-9)


def test_serving_shares_and_host_times():
    tb = _serve_tables()
    run = _run(tb)
    assert tb.step_device_s == pytest.approx(0.27)
    # sort.5: 9 ms of a 90 ms step
    assert pt.scope_share_pct(run, "sample") == pytest.approx(10.0)
    # the while's own time: 50 - 24 ms
    assert pt.scope_share_pct(run, "kv_pool") == pytest.approx(
        100 * 0.026 / 0.09)
    # tick 98 ms less put 2, dispatch 1, fetch 92.5
    assert pt.sched_host_ms(run) == pytest.approx(2.5)
    # between executions the chip waits 10 ms, twice in the window
    assert pt.host_exposed_ms(run) == pytest.approx(10.0)
    assert tb.idle_outside_s == pytest.approx(0.02)
    assert dict(tb.idle_phases)["rlt.serve.put"] == pytest.approx(0.004)


def test_a_missing_kernel_name_fails_loudly():
    run = _run(_serve_tables(kernel="rlt_paged_decode_v2"))
    with pytest.raises(BenchError) as err:
        pt.paged_decode_roofline_pct(run)
    assert "rlt_paged_decode_v2" in str(err.value)      # the names found
    assert "rlt_paged_prefill" in str(err.value)


def test_an_untraced_run_and_a_program_without_names_read_nothing():
    run = _run(None)
    assert pt.paged_decode_roofline_pct(run) is None
    assert pt.sched_host_ms(run) is None
    assert pt.scope_share_pct(run, "kv_pool") is None
    run.trace = None
    assert pt.host_exposed_ms(run) is None


def test_flash_roofline_and_data_wait_of_a_training_step():
    ops, modules, host = [], [], []
    for k in range(3):
        t0 = float(k)
        modules.append(("jit_step(2)", t0, t0 + 0.9))
        for layer in range(24):
            s = t0 + layer * 0.03
            # forward twice (remat), then the two backward kernels
            for j, name in enumerate(("rlt_flash_fwd", "rlt_flash_fwd",
                                      "rlt_flash_bwd_dkdv",
                                      "rlt_flash_bwd_dq")):
                ops.append(Op(name, s + j * 0.005, s + j * 0.005 + 0.004,
                              name, "attn"))
        ops.append(Op("fusion.9", t0 + 0.8, t0 + 0.89, None, "optimizer"))
        host.append(_host("data_wait", t0 - 0.05, t0 - 0.05 + 1e-5 * (k + 1)))
        host.append(_host("dispatch", t0 - 0.01, t0, step=k))
        host.append(_host("data_wait", t0, t0 + 0.5, thread=3))  # not the loop
    tb = pt.build_tables(pt.ProgramTrace([pt.Device(ops, modules)], host),
                         "train")
    run = _run(tb, kind="train", stamps={"seq": 4096})
    run.traffic = {"batch": 8}
    run.chips = 4
    one = shapes.flash_fwd_bwd(2, 4096, 16, 8, 128)
    least = 24 * shapes.roofline_seconds(one, PEAKS)["seconds"]
    assert pt.flash_roofline_pct(run) == pytest.approx(
        100.0 * least / (24 * 4 * 0.004), rel=1e-9)
    assert pt.scope_share_pct(run, "optimizer") == pytest.approx(10.0)
    assert pt.data_wait_ms(run) == pytest.approx(0.02)


# ---- size -------------------------------------------------------------------


def test_a_trace_of_the_four_chip_cells_size_loads_and_reduces_quickly():
    """100k op events over four chips (3 steps of 8,300 ops a chip, a
    `while` around each layer's ops), loaded from event lists and reduced to
    the tables in under 2 s: the four-chip traced run has a limit to keep."""
    rng = random.Random(0)
    hlo = {}
    texts = []
    scopes = ["attn", "mlp", "fused_ce", "optimizer", ""]
    for i in range(2000):
        scope = scopes[i % len(scopes)]
        kernel = i % 50 == 0
        name = f"fusion.{i}" if not kernel else f"shard_map.{i}"
        hlo[name] = ("custom-call" if kernel else "fusion",
                     f"jit(step)/jit(main)/{scope}/" + (
                         "rlt_flash_fwd/pallas_call" if kernel else "add"))
        texts.append(f"%{name} = bf16[8,4096,2048]{{2,1,0}} "
                     + ("custom-call(" if kernel else "fusion(")
                     + "bf16[8,4096,2048]{2,1,0} %p), kind=kLoop")
    hlo["while.1"] = ("while", "jit(step)/jit(main)/while")
    device_events = {}
    for chip in range(4):
        ops, modules, t = [], [], 0.0
        for step in range(3):
            start = t
            for layer in range(24):
                w0 = t
                for _ in range(345):
                    d = rng.uniform(20e-6, 300e-6)
                    ops.append((texts[rng.randrange(2000)], t, t + d))
                    t += d + 1e-7
                ops.append(("%while.1 = (s32[]) while((s32[]) %t)", w0, t))
            modules.append(("jit_step(7)", start, t))
            t += 4e-3
        device_events[chip] = {"ops": ops, "modules": modules}
    host = [("rlt.dispatch", m[1] - 1e-3, m[1] - 5e-4, 0, {"step": i})
            for i, m in enumerate(device_events[0]["modules"])]
    assert sum(len(d["ops"]) for d in device_events.values()) > 99_000
    t0 = time.perf_counter()
    trace_ = pt.from_events(device_events, host, {"jit_step(7)": hlo}, 4)
    tb = pt.build_tables(trace_, "train")
    took = time.perf_counter() - t0
    assert took < 2.0, took
    assert len(tb.runs) == 4 and len(tb.pairs) == 3
    assert tb.kernels["rlt_flash_fwd"][1] > 0
    assert sum(tb.scopes.values()) == pytest.approx(tb.step_device_s,
                                                    rel=1e-6)
    # a chip: the two 4 ms gaps between its steps, and the tail of the common
    # window after its own last step
    ends = [d["modules"][-1][2] for d in device_events.values()]
    tails = sum(max(ends) - e for e in ends) / 4
    assert tb.idle_outside_s == pytest.approx(2 * 4e-3 + tails, rel=1e-3)

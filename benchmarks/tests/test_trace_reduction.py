"""The trace reduction on small hand-built sets of intervals."""
import pytest

from benchmarks.harness import readers, trace
from benchmarks.harness.common import RunRecord
from benchmarks.harness.trace import DeviceTrace, TraceSummary


def test_union_and_idle_gaps():
    busy = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0)]
    assert trace.union_seconds(busy) == pytest.approx(3.0)
    assert trace.idle_gaps(busy, (0.0, 5.0)) == [(2.0, 3.0), (4.0, 5.0)]


def test_gap_attribution_innermost_span_wins_and_rest_is_unattributed():
    gaps = [(1.0, 2.0), (5.0, 6.0)]
    spans = [("tick", 0.0, 1.5), ("stamp", 1.5, 1.7), ("tick", 1.7, 10.0),
             ("submit", 5.2, 5.4)]
    got = dict(trace.attribute_gaps(gaps, spans))
    # gap 1: 0.5 tick, 0.2 stamp, 0.3 tick; gap 2: 0.2 submit (inside the
    # long tick), 0.8 tick
    assert got["stamp"] == pytest.approx(0.2)
    assert got["submit"] == pytest.approx(0.2)
    assert got["tick"] == pytest.approx(0.5 + 0.3 + 0.8)
    assert "unattributed" not in got
    assert dict(trace.attribute_gaps([(0.0, 1.0)], [("tick", 0.5, 2.0)])) == {
        "tick": pytest.approx(0.5), "unattributed": pytest.approx(0.5)}


def test_exposed_collective_time():
    coll = [(1.0, 3.0), (6.0, 7.0)]
    comp = [(0.0, 2.0), (2.5, 2.75), (6.5, 8.0)]
    # exposed: 2.0-2.5, 2.75-3.0, 6.0-6.5
    assert trace.exposed_seconds(coll, comp) == pytest.approx(1.25)
    assert trace.is_collective("all-gather.12")
    assert trace.is_collective("reduce-scatter-start.3")
    assert not trace.is_collective("fusion.7")


def test_exposed_time_at_a_four_chip_trace_size_is_quick():
    """A step of the four-chip cell is 8,300 ops a chip with a collective
    every dozen; a merge of the compute list per collective took minutes
    there, and the traced run passed its time limit (refused check, PR 23)."""
    import random
    import time

    rng = random.Random(0)
    coll, comp, t = [], [], 0.0
    for i in range(25_000):
        d = rng.uniform(20e-6, 300e-6)
        (coll if i % 12 == 0 else comp).append((t, t + d))
        t += d + 1e-7
    comp += [(s + 10e-6, e) for s, e in coll[::2]]   # half are half-covered
    brute = sum(e - s for s, e in coll[1::2]) + 10e-6 * len(coll[::2])
    t0 = time.perf_counter()
    got = trace.exposed_seconds(coll, comp)
    assert time.perf_counter() - t0 < 2.0
    assert got == pytest.approx(brute, rel=1e-6)


def test_self_time_subtracts_enclosed_ops():
    ops = [("while.1", 0.0, 10.0), ("fusion.1", 1.0, 4.0),
           ("fusion.2", 4.0, 9.0), ("copy.1", 11.0, 12.0)]
    got = dict(trace.self_times(ops))
    assert got["while.1"] == pytest.approx(2.0)
    assert got["fusion.2"] == pytest.approx(5.0)
    top = trace.top_ops([DeviceTrace(ops=ops, modules=[])], 2)
    assert [n for n, _ in top] == ["fusion.2", "fusion.1"]


def _record(summary):
    return RunRecord(kind="train", cell={}, config={}, traffic={}, hp={},
                     seconds=1.0, chips=len(summary.devices),
                     peaks={"bf16_flops_per_s": 1e12}, trace=summary)


def test_readers_on_a_two_chip_trace():
    dev = lambda shift: DeviceTrace(
        ops=[("fusion.1", 0.0 + shift, 2.0 + shift),
             ("all-gather.1", 2.0 + shift, 3.0 + shift),
             ("fusion.2", 2.5 + shift, 4.0 + shift)],
        modules=[("jit_train_step(1)", 0.0 + shift, 4.0 + shift),
                 ("jit_train_step(1)", 5.0 + shift, 9.0 + shift),
                 ("jit_other(2)", 4.0 + shift, 4.5 + shift)])
    summary = TraceSummary(devices=[dev(0.0), dev(1.0)],
                           host_spans=[("data", 4.0, 5.0)])
    assert summary.window == (0.0, 5.0)
    assert summary.busy_s == pytest.approx(4.0)
    run = _record(summary)
    assert readers.device_idle_share_pct(run) == pytest.approx(20.0)
    assert readers.step_gap_ms(run) == pytest.approx(1e3)
    assert readers.step_device_ms(run) == \
        pytest.approx(4e3)
    # exposed: all-gather 2.0-2.5 on each chip, over a 5 s window
    assert readers.exposed_collective_pct(run) == pytest.approx(10.0)
    bd = trace.breakdown(summary)
    assert bd["device_ops"][0][0] == "fusion.1"
    assert dict(bd["idle_gaps"])["data"] == pytest.approx(0.5)


def test_a_reader_with_nothing_to_read_returns_none():
    run = _record(TraceSummary(devices=[], host_spans=[]))
    run.trace = None
    assert readers.device_idle_share_pct(run) is None
    assert readers.step_gap_ms(run) is None
    assert readers.tick_ms(run) is None
    assert readers.mfu_pct(run) is None

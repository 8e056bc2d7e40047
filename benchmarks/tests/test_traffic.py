"""Arrival and length draws: reproducible from `--seed`, and every seed the
same multiset of sizes and gaps in another order."""
import json
import os

import numpy as np

from benchmarks.harness import traffic_gen

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _traffic(name):
    with open(os.path.join(ROOT, "traffic", name + ".json")) as fh:
        return json.load(fh)


def test_open_loop_reproduces_and_every_seed_gets_the_same_work():
    t = _traffic("chat")
    a = traffic_gen.open_loop(t, 92544, 2 ** 31 + 11, 30.0)
    b = traffic_gen.open_loop(t, 92544, 2 ** 31 + 11, 30.0)
    c = traffic_gen.open_loop(t, 92544, 5, 30.0)
    assert len(a) == round(30.0 * t["arrivals"]["rate_per_s"])
    assert all(np.array_equal(x.prompt, y.prompt) and x.due_s == y.due_s
               and x.max_new_tokens == y.max_new_tokens
               for x, y in zip(a, b))
    # another seed: other tokens, the same sizes and arrivals in the same order
    assert [(x.prompt.size, x.max_new_tokens, x.due_s) for x in a] == \
        [(x.prompt.size, x.max_new_tokens, x.due_s) for x in c]
    assert not np.array_equal(a[0].prompt, c[0].prompt)
    other = traffic_gen.open_loop(dict(t, order_seed=t["order_seed"] + 1),
                                  92544, 5, 30.0)
    assert sorted(x.prompt.size for x in other) == sorted(
        x.prompt.size for x in a)
    assert [x.prompt.size for x in other] != [x.prompt.size for x in a]
    due = np.array([x.due_s for x in a])
    assert (np.diff(due) >= 0).all() and 0 <= due[0] and due[-1] < 30.0
    lens = np.array([x.prompt.size for x in a])
    assert lens.min() >= 64 and lens.max() <= 2048
    assert 180 < lens.mean() < 210
    assert all(x.prompt.max() < 92544 for x in a)
    # greedy and sampled mixed
    assert {x.temperature for x in a} == {0.0, t["sampling"]["temperature"]}
    assert any(x.top_k for x in a)


def test_closed_loop_repeats_one_cycle():
    t = _traffic("docs")
    src = traffic_gen.ClosedLoopSource(t, 32768, 9)
    n = t["pool_size"]
    first = [src.next() for _ in range(n)]
    second = [src.next() for _ in range(n)]
    assert [r.prompt.size for r in first] == [r.prompt.size for r in second]
    assert len({r.rid for r in first + second}) == 2 * n
    again = traffic_gen.ClosedLoopSource(t, 32768, 9)
    assert np.array_equal(again.next().prompt, first[0].prompt)
    other = traffic_gen.ClosedLoopSource(t, 32768, 10)
    nxt = other.next()
    assert nxt.prompt.size == first[0].prompt.size
    assert not np.array_equal(nxt.prompt, first[0].prompt)
    lens = np.array([r.prompt.size for r in first])
    assert lens.min() >= 1024 and lens.max() <= 4096


def test_bounded_pareto_matches_the_program_s_generator():
    from ray_lightning_tpu.loadgen.generator import _bounded_pareto

    for u in (0.0, 0.1, 0.5, 0.9, 0.999):
        assert traffic_gen.bounded_pareto(u, 64, 2048, 1.2) == \
            _bounded_pareto(u, 64, 2048, 1.2)


def test_train_rows_all_differ():
    rows = traffic_gen.train_tokens(32768, 2 ** 31 + 3, 16, 64)
    assert rows.shape == (16, 65) and len({r.tobytes() for r in rows}) == 16

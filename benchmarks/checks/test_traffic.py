"""The generator: the same seed gives the same requests, another seed gives
the same work in another order."""
import json
import pathlib

import numpy as np

from benchmarks.harness import traffic

BENCH = pathlib.Path(__file__).resolve().parents[1]
MIX = json.loads((BENCH / "traffic" / "chat-r80.json").read_text())
CLOSED = json.loads((BENCH / "traffic" / "chat-closed64.json").read_text())
STEPS = json.loads((BENCH / "traffic" / "steps-b4s2048.json").read_text())


def _take(mix, seed, n, seconds=30.0):
    src = traffic.RequestSource(mix, seed, 50304, seconds)
    return [src.take() for _ in range(n)]


def test_same_seed_same_requests():
    a, b = _take(MIX, 2**31 + 5, 50), _take(MIX, 2**31 + 5, 50)
    assert all(x.due_s == y.due_s and x.max_new_tokens == y.max_new_tokens
               and np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_fixed_schedule_same_sizes_other_tokens():
    assert "order_seed" in MIX["pool"]
    assert np.array_equal(traffic.size_pool(MIX, 1), traffic.size_pool(MIX, 2))
    ra, rb = _take(MIX, 1, 20), _take(MIX, 2, 20)
    assert [(r.due_s, r.prompt.size, r.max_new_tokens) for r in ra] == \
        [(r.due_s, r.prompt.size, r.max_new_tokens) for r in rb]
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(ra, rb))


def test_other_seed_same_work_other_order():
    free = dict(MIX, pool={k: v for k, v in MIX["pool"].items()
                           if k != "order_seed"})
    a, b = traffic.size_pool(free, 1), traffic.size_pool(free, 2)
    assert not np.array_equal(a, b)
    assert sorted(map(tuple, a)) == sorted(map(tuple, b))
    # any run of whole blocks holds the same sizes: the first 10 blocks
    n = 10 * MIX["pool"]["block"]
    assert abs(a[:n, 0].sum() - b[:n, 0].sum()) < 0.05 * a[:n, 0].sum()
    ra, rb = _take(free, 1, 20), _take(free, 2, 20)
    assert [r.due_s for r in ra] == [r.due_s for r in rb]   # one arrival path
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(ra, rb))


def test_sizes_follow_the_laws():
    pool = traffic.size_pool(MIX, 3)
    p, o = pool[:, 0], pool[:, 1]
    law_p, law_o = MIX["prompt_len"], MIX["output_len"]
    assert p.min() >= law_p["min"] and p.max() <= law_p["max"]
    assert o.min() >= law_o["min"] and o.max() <= law_o["max"]
    assert abs(np.median(p) - law_p["median"]) <= 2
    assert abs(np.median(o) - law_o["median"]) <= 2


def test_arrivals_hold_the_rate():
    t = traffic.arrival_times(MIX, 300.0)
    assert len(t) == round(300.0 * MIX["rate_per_s"])
    assert np.all(np.diff(t) > 0) and t[-1] < 300.0


def test_closed_loop_has_no_schedule():
    src = traffic.RequestSource(CLOSED, 4, 32000, 30.0)
    assert src.due is None and src.take().due_s == 0.0 and not src.exhausted()


def test_batches_from_the_seed():
    a = traffic.BatchSource(STEPS, 9, 50304).take()
    b = traffic.BatchSource(STEPS, 9, 50304).take()
    c = traffic.BatchSource(STEPS, 10, 50304).take()
    assert np.array_equal(a[0], b[0]) and not np.array_equal(a[0], c[0])
    assert a[0].shape == (4, 2048) and np.array_equal(a[0][:, 1:], a[1][:, :-1])

"""The traffic generator: one seed, one set of requests; every seed the
same work in another order; due times on the wall clock."""

import numpy as np

import gen
import harness


def _mix():
    return harness.load_traffic("chat")


def test_same_seed_same_requests():
    a = gen.open_loop(_mix(), 2**31 + 5, 10.0, 151_936)
    b = gen.open_loop(_mix(), 2**31 + 5, 10.0, 151_936)
    assert [(r.due_s, r.max_new) for r in a] == [(r.due_s, r.max_new)
                                                  for r in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))


def test_seeds_share_the_work_not_the_order():
    a = gen.open_loop(_mix(), 1, 10.0, 151_936)
    b = gen.open_loop(_mix(), 2, 10.0, 151_936)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt)
                                                      for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert [r.max_new for r in a] != [r.max_new for r in b]


def test_due_times_on_the_wall_clock():
    mix = _mix()
    reqs = gen.open_loop(mix, 3, 20.0, 100)
    counted = [r for r in reqs if r.counted]
    assert len(counted) == round(mix["rate_per_s"] * 20.0)
    due = np.array([r.due_s for r in reqs])
    assert (np.diff(due) >= 0).all() and due[0] == 0.0
    assert max(r.due_s for r in counted) < 20.0
    assert min(r.due_s for r in reqs if not r.counted) >= 20.0


def test_lengths_from_the_mix():
    mix = _mix()
    ls = gen.lengths(mix["prompt"])
    assert ls == list(range(128, 1025, 128))
    q = gen.quantiles(mix["output"], 1001)
    assert q.min() >= 8 and q.max() <= 512 and np.median(q) == 32


def test_open_loop_order_is_random():
    """Each seed draws its own order: long outputs and short gaps may
    follow one another (Poisson clustering), and seeds disagree on where."""
    mix = _mix()
    orders = []
    for seed in (5, 6):
        reqs = [r for r in gen.open_loop(mix, seed, 45.0, 100) if r.counted]
        outs = np.array([r.max_new for r in reqs])
        orders.append(outs)
    top = [o >= np.quantile(o, 0.875) for o in orders]
    # under a random order some stretch of 8 requests holds 3 or more of
    # the longest eighth (about 1 in 2 such windows of 180 requests)
    assert any(max(t[i:i + 8].sum() for i in range(len(t) - 8)) >= 3
               for t in top)
    assert (np.argsort(orders[0], kind="stable")
            != np.argsort(orders[1], kind="stable")).any()

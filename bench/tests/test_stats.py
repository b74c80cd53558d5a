"""Percentiles and counts over the whole window."""

import pytest

import gen
import harness
from drivers import serve


def test_quantile_matches_numpy_linear():
    np = pytest.importorskip("numpy")
    xs = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0]
    for q in (0.0, 0.5, 0.95, 1.0):
        assert harness.quantile(xs, q) == pytest.approx(
            float(np.percentile(xs, 100 * q)))
    with pytest.raises(ValueError):
        harness.quantile([], 0.5)


class _H:
    def __init__(self, status="done", done=True):
        self.status, self.done = status, done


def test_measure_takes_every_request_and_gap():
    import numpy as np
    c = harness.load_config(harness.load_spec(), "qwen3-1.7b")
    reqs = {0: gen.Request(0, 0.0, np.zeros(4, np.int32), 3, True),
            1: gen.Request(1, 1.0, np.zeros(4, np.int32), 2, True),
            2: gen.Request(2, 2.5, np.zeros(4, np.int32), 2, False)}
    w = serve.Window(
        seconds=2.0, handles={0: _H(), 1: _H(), 2: _H("queued", False)},
        reqs=reqs, stamps={0: [0.1, 0.3, 0.6], 1: [1.5, 2.5], 2: [2.6]},
        lags_ms=[0.0, 1.0], compiles=0, ticks=5, counted=[0, 1],
        drained=True)
    m = serve.measure(w, c)
    # TTFT from the due time; every gap of every counted request
    assert m["ttft_ms"] == pytest.approx([100.0, 500.0])
    assert m["itl_ms"] == pytest.approx([200.0, 300.0, 1000.0])
    # tokens stamped inside the 2 s window: 3 + 1
    assert m["tokens_in_window"] == 4
    assert m["prefill_flops"] == 2 * __import__("counts").prefill_flops(c, 4)
    assert (m["attempted"], m["failed"]) == (2, 0)


def test_unfinished_open_loop_request_fails():
    import numpy as np
    c = harness.load_config(harness.load_spec(), "qwen3-1.7b")
    reqs = {0: gen.Request(0, 0.0, np.zeros(4, np.int32), 3, True)}
    w = serve.Window(2.0, {0: _H("active", False)}, reqs, {0: [0.1]}, [],
                     0, 1, [0], False)
    assert serve.measure(w, c)["failed"] == 1

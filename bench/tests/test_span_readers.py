"""The per-layer readers of the program's spans, on hand-made span lists:
``step_host_ms.tick``, ``host_syncs.tick`` and ``glue_host_ms.plan``."""

import types

import pytest

import harness

from drivers import serve


def _read(name, ctx):
    reader = harness.load_module(harness.BENCH / "metrics" / f"{name}.py",
                                 "metric_" + name.replace(".", "_"))
    return reader.read(ctx)


def _span(name, t0, t1):
    return types.SimpleNamespace(name=name, t0=t0, t1=t1)


def _window(spans, ticks):
    return serve.Window(seconds=1.0, handles={}, reqs={}, stamps={},
                        lags_ms=[], compiles=0, ticks=ticks, counted=[],
                        drained=True, spans=spans)


# two ticks: 10 ms with two reads of 1 ms and 2 ms, then 6 ms with one
# read of 1 ms; spans of other layers and a read outside any tick do not
# count as step time
TICKS = [
    _span("serve.step", 0.000, 0.010),
    _span("serve.decode", 0.001, 0.009),
    _span("serve.sync", 0.002, 0.003),
    _span("serve.sync", 0.005, 0.007),
    _span("engine.execute", 0.003, 0.004),
    _span("serve.step", 0.020, 0.026),
    _span("serve.sync", 0.024, 0.025),
    _span("serve.sync", 0.030, 0.031),
]


def test_step_host_ms_leaves_out_the_reads():
    w = _window(TICKS, ticks=2)
    # (10 - 3) + (6 - 1) ms over 2 ticks
    assert _read("step_host_ms.tick", {"window": w}) == pytest.approx(6.0)


def test_host_syncs_counts_every_read_per_tick():
    w = _window(TICKS, ticks=2)
    assert _read("host_syncs.tick", {"window": w}) == pytest.approx(2.0)


@pytest.mark.parametrize("name", ["step_host_ms.tick", "host_syncs.tick"])
def test_tick_readers_find_nothing_without_spans(name):
    # a program without these spans (the parent's) is left out
    w = _window([_span("core.prepare", 0.0, 0.001)], ticks=3)
    assert _read(name, {"window": w}) is None
    assert _read(name, {"window": _window(TICKS, ticks=0)}) is None


def test_glue_host_ms_is_the_union_per_plan():
    spans = [
        _span("qr.run", 0.0, 3.0),
        _span("qr.split_tiles", 0.0, 0.5),
        _span("qr.build_graph", 0.5, 0.8),
        _span("plan.lower", 0.8, 0.9),           # the scheduler's, not glue
        _span("engine.buffers", 0.9, 1.2),
        _span("engine.execute", 1.2, 1.3),
        _span("engine.writeback", 1.3, 2.0),
        _span("qr.assemble_r", 2.0, 2.5),
        _span("qr.split_tiles", 3.0, 3.5),        # a second plan
    ]
    w = {"spans": spans, "plans": 2}
    # (0.8 + 0.3 + 1.2) s of the first plan and 0.5 s of the second
    assert _read("glue_host_ms.plan", {"window": w}) == pytest.approx(
        1e3 * 2.8 / 2)


def test_glue_host_ms_finds_nothing_without_spans():
    assert _read("glue_host_ms.plan", {"window": {
        "spans": [_span("plan.lower", 0.0, 1.0)], "plans": 1}}) is None
    assert _read("glue_host_ms.plan", {"window": {
        "spans": [_span("qr.split_tiles", 0.0, 1.0)], "plans": 0}}) is None

"""Reduction of a profiler trace to busy time, idle share, kernel time
and the breakdown."""

import json
import pathlib

import pytest

import devtrace

DATA = pathlib.Path(__file__).parent / "data"

# a window of 100 ns: ops at 10-30 and 20-40 overlap (busy 10-40), a
# kernel at 60-70; host annotations label the gaps
SMALL = {
    "device": [["fusion.1", 10, 20, devtrace.OPS],
               ["custom-call.2 | _gqa_kernel", 20, 20, devtrace.OPS],
               ["custom-call.3 | _gqa_kernel", 60, 10, devtrace.OPS],
               ["jit_prefill_entry(1)", 10, 30, devtrace.MODULES],
               ["fusion.9", 200, 5, devtrace.OPS]],
    "host": [[devtrace.TRACED, 0, 100], ["bench.submit", 0, 10],
             ["bench.step", 40, 20], ["bench.wait", 70, 30]],
}


def test_busy_union_and_idle_share():
    assert devtrace.union([(3, 5), (0, 2), (1, 4)]) == [(0, 5)]
    assert devtrace.busy_ns(SMALL) == 30 + 10      # 10-40 and 60-70
    s = devtrace.summary(SMALL)
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["idle_share"] == pytest.approx(60.0)


def test_kernel_time_by_name():
    assert devtrace.time_matching(SMALL, ["_gqa_kernel"]) == 30
    assert devtrace.time_matching(SMALL, ["prefill_entry"],
                                  devtrace.MODULES) == 30
    assert devtrace.time_matching(SMALL, ["nothing"]) == 0


def test_breakdown():
    assert devtrace.top_ops(SMALL, 2) == [["custom-call.2", 20e-9],
                                          ["fusion.1", 20e-9]]
    gaps = devtrace.idle_gaps(SMALL)
    assert gaps == [["bench.wait", pytest.approx(30e-9)],
                    ["bench.step", pytest.approx(20e-9)],
                    ["bench.submit", pytest.approx(10e-9)]]


MOSAIC = 'custom_call_target="tpu_custom_call"'
# what each recorded stretch holds: (ops pattern, module pattern)
RECORDED = {"trace_chat.json": (MOSAIC, "prefill_entry"),
            "trace_qr.json": (None, None)}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_recorded_chip_trace(name):
    """Stretches recorded on a v5e chip: a serving tick with a
    prefill, and the start of a QR plan.  Busy time lies within the
    window, and the kernels and modules the metric readers match are
    found by those names."""
    tr = json.loads((DATA / name).read_text())
    s = devtrace.summary(tr)
    assert 0 < s["busy_s"] <= s["window_s"]
    assert 0 <= s["idle_share"] < 100
    ops = devtrace.top_ops(tr)
    assert ops and all(t > 0 for _, t in ops)
    assert sum(t for _, t in devtrace.idle_gaps(tr)) <= s["window_s"]
    op_pat, mod_pat = RECORDED[name]
    if op_pat:
        assert 0 < devtrace.time_matching(tr, [op_pat]) < s["busy_s"] * 1e9
    if mod_pat:
        assert devtrace.time_matching(tr, [mod_pat], devtrace.MODULES) > 0

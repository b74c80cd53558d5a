"""Tests of the benchmark's own code, on the CPU at small sizes:

    python -m pytest bench/tests

The harness's look for a chip is skipped here (``run_cell``); everything
else of a run is driven as on the chip, with the program's Pallas kernels
in interpret mode."""

import os
import pathlib
import sys
import time
import types

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (str(BENCH), str(BENCH.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402

SPEC = harness.load_spec()
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


# the serving configurations, by name: every ``configs`` entry whose
# file is of kind ``serve``
SERVE_CONFIGS = [e["name"] for e in SPEC["configs"]
                 if harness.load_config(SPEC, e["name"])["kind"] == "serve"]


def serve_cell(config):
    """The first ``workloads`` entry of a configuration."""
    return next(w["name"] for w in SPEC["workloads"]
                if w["config"] == config)


def small_serve_config(name, control=False, **over):
    """A serving configuration's file at its architecture's CPU-test
    widths (``small`` of ``archs/<model_type>.py``) and a small service,
    its limits kept."""
    c = harness.load_config(SPEC, name)
    c = harness.arch(c["model_type"]).small(c, control=control)
    c["service"] = dict(c["service"], max_batch=2, max_seq=128,
                        decode_path="bounded")
    c.update(over)
    return c


def small_chat_mix(**over):
    """The chat mix with one prompt length and short outputs."""
    mix = harness.load_traffic("chat")
    mix.update(rate_per_s=6.0, check_pad=256)
    mix["prompt"] = {"dist": "lognormal", "median": 32, "sigma": 0.5,
                     "min": 17, "max": 32, "round_up": 32}
    mix["output"] = {"dist": "lognormal", "median": 8, "sigma": 0.3,
                     "min": 4, "max": 12}
    mix.update(over)
    return mix


def small_qr_config(n=256):
    c = harness.load_config(SPEC, "qr-2048")
    c["matrix_n"] = n
    return c


def run_cell(cell, c, mix, *, seconds=2.0, seed=2**31 + 7, trace=0, **kw):
    """One run of ``cell`` as ``bench/run.py`` makes it, minus the look
    for a chip; returns (result, checks)."""
    spec_cell = harness.find_cell(SPEC, cell)
    args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=trace)
    return harness.driver(c["kind"]).run(
        args, SPEC, spec_cell, c, mix, DEVICE, time.perf_counter(), **kw)

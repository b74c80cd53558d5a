#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``<cell>`` is a ``workloads`` entry of ``BENCHMARK.json``.  One process:
it checks for a TPU (and as many chips as the cell asks for) and exits
non-zero without one, makes its inputs and weights from ``--seed``,
warms every shape the cell uses (set-up, reported as ``setup_s``),
measures for ``--seconds``, checks what the timed path produced against
a plain reference, and prints one JSON line last on standard output.
With ``--trace 0`` its metrics are the cell's end-to-end metrics; with
``--trace 1`` the run also records a profiler trace of a steady stretch
and reports the per-layer metrics, ``busy_s``/``window_s`` and a
``breakdown``.  The numbers compared with the reference are printed
beside their limits as the last lines of standard error and under
``checks`` in the result line.  See ``bench/README.md``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (BENCH.parent / "src" / "repro").is_dir():
        sys.exit("bench: the program (src/repro) is not in this checkout")
    spec = harness.load_spec()
    cell = harness.find_cell(spec, args.workload)
    c = harness.load_config(spec, cell["config"])
    mix = harness.load_traffic(cell["traffic"])
    harness.enable_compile_cache()
    device = harness.require_chips(cell["chips"])
    result, checks = harness.driver(c["kind"]).run(
        args, spec, cell, c, mix, device, T_START)
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Readings that set the benchmark's numbers, made on the chip, in one
process per cell (the benchmark's own runs never do this):

    python bench/calibrate.py sweep  --workload <cell> --rates 2,4,6 --seconds 20
    python bench/calibrate.py limits --workload <cell> --seeds 1-12 --seconds 10
    python bench/calibrate.py trace  --workload <cell> --seconds 10 --out <file>

* ``sweep``  — the open-loop mix at each rate in turn (one warmed
  service): TTFT and inter-token quantiles, completed rate and whether
  the window's requests drained.  The knee is the highest rate that
  drains with steady tails; the cell's rate goes into its traffic file.
* ``limits`` — for each seed, new weights (or matrices) and a window at
  the cell's own load, then the program's compared number and the
  control's (reference in the precision below the configuration's) on
  the same sample.  Limits are set between the two.
* ``trace``  — one traced window, saved as the reduced trace
  (``devtrace.load``'s lists) for reading by hand and for tests.

Each prints one JSON line per reading.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import devtrace  # noqa: E402
import harness  # noqa: E402


def _seeds(text):
    out = []
    for part in text.split(","):
        a, _, b = part.partition("-")
        out += list(range(int(a), int(b) + 1)) if b else [int(a)]
    return out


def _serve_cell(c, mix, seed):
    drv = harness.driver("serve")
    sc = drv.ServeCell(c, mix, seed)
    warm = sc.warm()
    print(json.dumps({"warm": {k: warm[k] for k in
                               ("seconds", "lowerings", "compile_s")}}),
          flush=True)
    return drv, sc


def sweep(args, c, mix):
    drv, sc = _serve_cell(c, mix, args.seed)
    for k, r in enumerate(float(x) for x in args.rates.split(",")):
        sc.mix = dict(mix, rate_per_s=r)
        w = sc.window(args.seed + k, args.seconds)
        m = drv.measure(w, c)
        q = harness.quantile
        print(json.dumps({
            "rate_per_s": r, "requests": m["attempted"],
            "drained": w.drained, "ticks": w.ticks,
            "compiles": w.compiles,
            "done_per_s": sum(1 for i in w.counted
                              if w.handles[i].done) / args.seconds,
            "tok_s": m["tokens_in_window"] / args.seconds,
            "ttft_ms": [q(m["ttft_ms"], p) for p in (.5, .95)],
            "itl_ms": [q(m["itl_ms"], p) for p in (.5, .95)],
            "lag_p95_ms": q(w.lags_ms, .95)}), flush=True)


def limits(args, c, mix):
    if c["kind"] == "serve":
        _, sc = _serve_cell(c, mix, args.seed)
        for seed in _seeds(args.seeds):
            sc.set_seed(seed)
            w = sc.window(seed, args.seconds)
            pick = sc.sample(w, seed)
            t = time.perf_counter()
            g = sc.compare(w, pick, control=True)
            sc.svc.run_until_complete()    # requests due after the window
            print(json.dumps({
                "seed": seed, "requests": len(pick),
                "tokens": int(len(g["gap"])),
                "served_gap_max": float(g["gap"].max()),
                "control_gap_max": float(g["control_gap"].max()),
                "control_gap_median": float(sorted(g["control_gap"])[
                    len(g["control_gap"]) // 2]),
                "reference_s": time.perf_counter() - t,
                "drained": w.drained}), flush=True)
    else:
        drv = harness.driver(c["kind"])
        for seed in _seeds(args.seeds):
            qc = drv.QRCell(c, seed)
            if seed == _seeds(args.seeds)[0]:
                qc.warm()
            w = qc.window(seed, args.seconds, mix["check_plans"])
            t = time.perf_counter()
            e = qc.compare(w["kept"], control=True)
            print(json.dumps({
                "seed": seed, "plans": w["plans"],
                "plan_ms": 1e3 * w["seconds"] / w["plans"],
                "r_rel_err_max": max(e["r_rel_err"]),
                "control_r_rel_err_min": min(e["control_r_rel_err"]),
                "control_r_rel_err_max": max(e["control_r_rel_err"]),
                "reference_s": time.perf_counter() - t}), flush=True)


def trace(args, c, mix):
    cap = devtrace.Capture(harness.OUT / "trace-calibrate")
    try:
        if c["kind"] == "serve":
            _, sc = _serve_cell(c, mix, args.seed)
            w = sc.window(args.seed, args.seconds, capture=cap)
        else:
            qc = harness.driver(c["kind"]).QRCell(c, args.seed)
            qc.warm()
            qc.window(args.seed, args.seconds, 1, capture=cap)
        tr = cap.load()
    finally:
        cap.close()
    lo, hi = devtrace.window(tr)
    cut = lo + 0.3e9                     # keep 0.3 s: small enough to test on
    devtrace.save({"device": [e for e in tr["device"] if e[1] < cut],
                   "host": [[n, s, min(d, cut - s) if n == devtrace.TRACED
                             else d] for n, s, d in tr["host"] if s < cut]},
                  pathlib.Path(args.out))
    names = {}
    for name, s, d, line in tr["device"]:
        key = (line, name[:300])
        names[key] = names.get(key, 0) + d
    top = sorted(names.items(), key=lambda kv: -kv[1])[:60]
    for (line, name), ns in top:
        print(json.dumps({"line": line, "name": name, "s": ns / 1e9}))
    print(json.dumps({"summary": devtrace.summary(tr),
                      "gaps": devtrace.idle_gaps(tr)}))
    if c["kind"] == "serve":
        print(json.dumps({"traced": w.traced}))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("sweep", "limits", "trace"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1000)
    ap.add_argument("--seeds", default="1-12")
    ap.add_argument("--rates", default="")
    ap.add_argument("--rate", type=float, default=None,
                    help="offered load where the traffic file has none yet")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default="bench/out/trace.json")
    args = ap.parse_args()
    spec = harness.load_spec()
    cell = harness.find_cell(spec, args.workload)
    c = harness.load_config(spec, cell["config"])
    mix = harness.load_traffic(cell["traffic"])
    if args.rate is not None:
        mix["rate_per_s"] = args.rate
    harness.enable_compile_cache()
    harness.require_chips(cell["chips"])
    {"sweep": sweep, "limits": limits, "trace": trace}[args.mode](
        args, c, mix)


if __name__ == "__main__":
    main()

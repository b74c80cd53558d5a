"""Driver of the engine cells: the scheduler's own task graphs through
``repro.core`` and ``repro.engine``, back to back.

The ``qr`` solver factors one n x n float32 matrix per plan with
``repro.apps.qr.run_qr(a, tile, mode="engine")``: build the tile graph,
lower it (cached by structure after the first plan), encode the
descriptor tables, run the megakernel and assemble R.  Each plan gets a
new matrix, made on the device from the seed and the plan's index.  A
plan ends when R is on the device (``block_until_ready``); ``plan_ms`` is
the window over the whole plans completed in it.

A reservoir drawn from the seed keeps the R of ``check_plans`` plans of
the window; once it has closed, each is compared with float64 LAPACK
(``reference/qr.py``).
"""

from __future__ import annotations

import sys
import time
from typing import Any, Dict, List

import numpy as np

import counts
import devtrace
import harness
from reference import qr as ref


class QRCell:
    def __init__(self, c: Dict[str, Any], seed: int):
        import jax
        import jax.numpy as jnp
        from repro.apps.qr import run_qr
        n = c["matrix_n"]
        self.c = c
        self.key = jax.random.PRNGKey(harness.seed_words(seed)(0))
        self.matrix = jax.jit(lambda k, i: jax.random.normal(
            jax.random.fold_in(k, i), (n, n), jnp.float32))
        self.solve = lambda a: run_qr(a, tile=c["tile"], mode="engine")[0]

    def plan(self, i: int, annotate: bool = False):
        a = self.matrix(self.key, i)
        if annotate:
            import jax
            with jax.profiler.TraceAnnotation("bench.run_qr"):
                r = self.solve(a)
                r.block_until_ready()
        else:
            r = self.solve(a)
            r.block_until_ready()
        return r

    def warm(self) -> Dict[str, Any]:
        with harness.CompileWatch() as watch:
            t0 = time.perf_counter()
            self.plan(-1)
            self.plan(-2)
            secs = time.perf_counter() - t0
        return {"seconds": secs, "lowerings": watch.lowerings,
                "compile_s": watch.compile_s}

    def window(self, seed: int, seconds: float, keep: int, capture=None,
               trace_plans: int = 2) -> Dict[str, Any]:
        """Plans back to back until ``seconds`` have passed; the traced
        stretch (if any) is ``trace_plans`` whole plans from half the
        window on.  Starting and stopping the profiler falls between
        plans: ``durations`` holds each plan's own wall time."""
        from repro.obs import trace as obs
        rng = np.random.default_rng(np.random.SeedSequence(
            (seed % 2**64, 11)))
        kept: List[tuple] = []
        annotate = capture is not None
        tracer = obs.enable() if annotate else None
        traced: Dict[str, float] = {}
        durations: List[float] = []
        i = 0
        t0 = time.perf_counter()
        with harness.CompileWatch() as watch:
            while True:
                now = time.perf_counter() - t0
                if capture is not None:
                    if not traced and now >= 0.5 * seconds:
                        capture.start()
                        traced = {"t0": now, "plan0": i}
                    elif capture.active and i - traced["plan0"] >= \
                            trace_plans:
                        capture.stop()
                        traced.update(t1=now, plan1=i)
                if now >= seconds:
                    break
                t = time.perf_counter()
                r = self.plan(i, annotate)
                durations.append(time.perf_counter() - t)
                # reservoir sample of `keep` plans, drawn from the seed
                if len(kept) < keep:
                    kept.append((i, r))
                else:
                    j = int(rng.integers(0, i + 1))
                    if j < keep:
                        kept[j] = (i, r)
                i += 1
            t_end = time.perf_counter() - t0
        if capture is not None and capture.active:
            capture.stop()
            traced.update(t1=time.perf_counter() - t0, plan1=i)
        spans = list(tracer.spans) if tracer is not None else []
        if tracer is not None:
            obs.disable()
        return {"plans": i, "seconds": t_end, "kept": kept,
                "durations": durations,
                "compiles": watch.lowerings, "traced": traced,
                "spans": spans}

    def compare(self, kept, control: bool = False) -> Dict[str, list]:
        out = {"r_rel_err": []}
        if control:
            out["control_r_rel_err"] = []
        for i, r in kept:
            a = np.asarray(self.matrix(self.key, i))
            r_ref = ref.r_float64(a)
            out["r_rel_err"].append(ref.rel_err(np.asarray(r), r_ref))
            if control:
                rc = ref.r_householder(self.matrix(self.key, i),
                                       precision="high")
                out["control_r_rel_err"].append(
                    ref.rel_err(np.asarray(rc), r_ref))
        return out


def run(args, spec, cell, c, mix, device, t_start):
    """One benchmark run of an engine cell; returns (result, checks)."""
    if c["solver"] != "qr":
        raise ValueError(f"no engine solver {c['solver']!r}")
    cap = devtrace.Capture(harness.OUT / f"trace-{cell['name']}") \
        if args.trace else None
    qc = QRCell(c, args.seed)
    warm = qc.warm()
    print(f"[setup] warm-up {warm['seconds']:.2f} s, {warm['lowerings']} "
          f"lowerings, {warm['compile_s']:.2f} s XLA compile",
          file=sys.stderr)
    setup_s = time.perf_counter() - t_start
    try:
        w = qc.window(args.seed, args.seconds, mix["check_plans"],
                      capture=cap)
        trace = cap.load() if cap is not None else None
    finally:
        if cap is not None:
            cap.close()
    device = dict(device, memory_peak_bytes=harness.memory_peak_bytes(
        device["count"]))
    t_ref = time.perf_counter()
    errs = qc.compare(w["kept"])["r_rel_err"] if w["kept"] else [np.inf]
    print(f"[check] float64 reference over {len(w['kept'])} plans in "
          f"{time.perf_counter() - t_ref:.2f} s", file=sys.stderr)
    limits = c["limits"]
    checks = {"r_rel_err_max": {"value": float(max(errs)),
                                "limit": limits["r_rel_err_max"]}}
    correct = all(v["value"] <= v["limit"] for v in checks.values())
    plan_ms = 1e3 * w["seconds"] / w["plans"] if w["plans"] else None
    print(f"[window] {w['plans']} plans in {w['seconds']:.3f} s = "
          f"{plan_ms} ms per plan, {w['compiles']} lowerings",
          file=sys.stderr)
    metrics = {}
    if not args.trace:
        e2e = {"setup_s": setup_s, "plan_ms": plan_ms}
        for e in harness.cell_metrics(spec, cell["name"], "end_to_end"):
            metrics[e["name"]] = {"value": e2e[e["name"]], "unit": e["unit"]}
    result = {"correct": bool(correct), "attempted": w["plans"],
              "failed": 0, "metrics": metrics, "device": device}
    if args.trace:
        summ = devtrace.summary(trace)
        tr = w["traced"]
        ctx = {"kind": "engine", "config": c, "mix": mix, "window": w,
               "trace": trace, "summary": summ,
               "plan_ms": 1e3 * float(np.mean(w["durations"])),
               "plan_flops": counts.qr_plan_flops(
                   c["matrix_n"], c["tile"]),
               "traced_plans": tr.get("plan1", 0) - tr.get("plan0", 0),
               "peaks": harness.peaks(device["kind"])}
        result["metrics"] = harness.read_per_layer(spec, cell["name"], ctx)
        result["device"].update(busy_s=summ["busy_s"],
                                window_s=summ["window_s"])
        result["breakdown"] = {"device_ops": devtrace.top_ops(trace),
                               "idle_gaps": devtrace.idle_gaps(trace)}
    return result, checks

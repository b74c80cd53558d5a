"""Driver of the serving cells: a model served through
``repro.serve.GenerateService`` as ``launch/serve.py --continuous``
builds it, under one traffic mix.

Nothing here knows a model: what depends on the architecture (the
program's configuration, how each weight is drawn, the operations and
bytes counted, the decode kernel's name in the trace) is in
``archs/<model_type>.py``, and the plain reference in
``reference/<model_type>.py``, both found by the configuration file's
``model_type``.

A run: make the weights on the device from the seed (one jitted call),
build the service, warm every shape the mix can use (each prefill
``(prompt length, batch)`` entry and each decode batch size), then drive
``submit``/``step`` for ``--seconds`` on the wall clock:

every tick submits each request of the open-loop mix whose due time
has passed, then steps the service.  Each token is stamped on the host
when ``step()`` returns it; TTFT runs from the due time.  After the
window the load goes on (uncounted) until the window's requests have
finished, for at most :data:`DRAIN_S`.

Then the device's peak memory is read, the service is freed, and the
plain reference reads a sample of the finished requests, drawn from the
seed with the longest among them.
"""

from __future__ import annotations

import dataclasses
import gc
import sys
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

import devtrace
import gen
import harness

DRAIN_S = 60.0              # wait past the window for its last requests


# --- weights --------------------------------------------------------------

def make_weights(c: Dict[str, Any], seed_word: int):
    """Random weights in the layout and dtypes the program takes, made
    on the device in one jitted call, each drawn as the architecture's
    ``init_leaf`` says."""
    import jax
    return jax.jit(weight_maker(c))(jax.random.PRNGKey(seed_word))


def weight_maker(c: Dict[str, Any]) -> Callable:
    """key -> the weights of :func:`make_weights`: the program's own
    parameter tree (``lm.init_params`` traced for shapes only), one
    standard normal draw per leaf from ``key`` split in the tree's
    order, shaped by ``init_leaf`` and cast to the leaf's dtype."""
    import jax
    import jax.numpy as jnp
    from repro.models import lm

    arch = harness.arch(c["model_type"])
    cfg = arch.program_config(c)
    tree = jax.eval_shape(lambda k: lm.init_params(k, cfg),
                          jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)

    def build(key):
        keys = jax.random.split(key, len(leaves))
        out = [arch.init_leaf(jax.tree_util.keystr(path),
                              jax.random.normal(k, leaf.shape, jnp.float32),
                              c).astype(leaf.dtype)
               for k, (path, leaf) in zip(keys, leaves)]
        return jax.tree.unflatten(treedef, out)

    return build


# --- one cell in one process -----------------------------------------------

@dataclasses.dataclass
class Window:
    """What one measured window left behind."""
    seconds: float
    handles: Dict[int, Any]               # request index -> service Request
    reqs: Dict[int, gen.Request]
    stamps: Dict[int, List[float]]        # token times from window start
    lags_ms: List[float]
    compiles: int
    ticks: int
    counted: List[int]                    # indices of the window's requests
    drained: bool
    trace: Optional[Dict[str, list]] = None
    traced: Dict[str, float] = dataclasses.field(default_factory=dict)
    spans: List[Any] = dataclasses.field(default_factory=list)


class ServeCell:
    """The service of one configuration, warmed for one mix."""

    def __init__(self, c: Dict[str, Any], mix: Dict[str, Any], seed: int):
        import jax
        from repro.serve import GenerateService, SamplingParams
        self.c, self.mix = c, mix
        self.ref = harness.reference(c["model_type"])
        words = harness.seed_words(seed)
        self.weights = make_weights(c, words(0))
        jax.block_until_ready(self.weights)
        s = c["service"]
        self.svc = GenerateService(
            self.weights, harness.arch(c["model_type"]).program_config(c),
            max_batch=s["max_batch"], max_seq=s["max_seq"],
            page_size=s["page_size"], decode_path=s["decode_path"],
            guard=s["guard"],
            sampling=SamplingParams(temperature=0.0, seed=words(1)))

    def set_seed(self, seed: int) -> None:
        """New weights for the same compiled service (calibration)."""
        import jax
        self.weights = make_weights(self.c, harness.seed_words(seed)(0))
        jax.block_until_ready(self.weights)
        self.svc.params = self.weights

    def warm(self) -> Dict[str, Any]:
        """Run each prefill ``(plen, nb)`` entry the mix can reach and each
        decode batch size once, through the public API."""
        s = self.c["service"]
        vocab = self.c["vocab_size"]
        rng = np.random.default_rng(0)
        with harness.CompileWatch() as watch:
            t0 = time.perf_counter()
            for plen in gen.lengths(self.mix["prompt"]):
                for nb in range(1, s["max_batch"] + 1):
                    for _ in range(nb):
                        self.svc.submit(rng.integers(0, vocab, plen), 1)
                    self.svc.run_until_complete()
            plen = gen.lengths(self.mix["prompt"])[0]
            for k in range(s["max_batch"]):
                self.svc.submit(rng.integers(0, vocab, plen), k + 2)
            self.svc.run_until_complete()
            secs = time.perf_counter() - t0
        if self.svc.pool.allocated:
            raise RuntimeError("warm-up leaked pages")
        return {"seconds": secs, "lowerings": watch.lowerings,
                "compile_s": watch.compile_s,
                "entry_points": self.svc.compiled_entry_points()}

    def window(self, seed: int, seconds: float, capture=None) -> Window:
        if self.mix["kind"] != "open_loop":
            raise ValueError(f"serving drives no {self.mix['kind']!r} mix")
        return self._open_loop(seed, seconds, capture)

    # the loop -----------------------------------------------------------
    def _step(self, annotate):
        if annotate:
            import jax
            with jax.profiler.TraceAnnotation("bench.step"):
                self.svc.step()
        else:
            self.svc.step()

    def _drive(self, seconds, capture, next_due, submit_due, stop):
        """The common loop: submit what is due, step, stamp tokens."""
        from repro.obs import trace as obs
        w = Window(seconds, {}, {}, {}, [], 0, 0, [], True)
        live: List[int] = []
        seen: Dict[int, int] = {}
        annotate = capture is not None
        # trace the last stretch of the window, so that stopping the
        # profiler (seconds of host work) stalls only the drain
        cap_at = (seconds - min(4.0, 0.4 * seconds), seconds)
        tracer = obs.enable() if annotate else None
        steps0 = self.svc.stats["steps"]
        t0 = time.perf_counter()
        with harness.CompileWatch() as watch:
            while True:
                now = time.perf_counter() - t0
                if capture is not None:
                    if not capture.active and not w.traced and now >= cap_at[0]:
                        capture.start()
                        w.traced = {"t0": now, "pages0":
                                    self.svc.stats["pages_attended"]}
                    elif capture.active and now >= cap_at[1]:
                        w.traced.update(t1=now, pages1=
                                        self.svc.stats["pages_attended"])
                        capture.stop()
                for idx in submit_due(now, w):
                    live.append(idx)
                    seen[idx] = 0
                if live:
                    self._step(annotate)
                    t = time.perf_counter() - t0
                    still = []
                    for idx in live:
                        h = w.handles[idx]
                        n = len(h.generated)
                        if n > seen[idx]:
                            w.stamps[idx] += [t] * (n - seen[idx])
                            seen[idx] = n
                        if not h.done:
                            still.append(idx)
                    live = still
                else:
                    due = next_due()
                    if due is not None and due > now:
                        time.sleep(min(due - now, 0.05))
                if stop(now, w):
                    break
        if capture is not None and capture.active:
            w.traced.update(t1=time.perf_counter() - t0,
                            pages1=self.svc.stats["pages_attended"])
            capture.stop()
        w.compiles = watch.lowerings
        w.ticks = self.svc.stats["steps"] - steps0
        if tracer is not None:
            w.spans = list(tracer.spans)
            obs.disable()
        return w

    def _submit(self, r: gen.Request, w: Window, annotate):
        if annotate:
            import jax
            with jax.profiler.TraceAnnotation("bench.submit"):
                h = self.svc.submit(r.prompt, r.max_new)
        else:
            h = self.svc.submit(r.prompt, r.max_new)
        w.handles[r.index] = h
        w.reqs[r.index] = r
        w.stamps[r.index] = []
        if r.counted:
            w.counted.append(r.index)
        return r.index

    def _open_loop(self, seed, seconds, capture):
        reqs = gen.open_loop(self.mix, seed, seconds, self.c["vocab_size"])
        pos = [0]
        annotate = capture is not None

        def next_due():
            return reqs[pos[0]].due_s if pos[0] < len(reqs) else None

        def submit_due(now, w):
            out = []
            while pos[0] < len(reqs) and reqs[pos[0]].due_s <= now:
                r = reqs[pos[0]]
                if r.counted:
                    w.lags_ms.append(1e3 * (now - r.due_s))
                out.append(self._submit(r, w, annotate))
                pos[0] += 1
            return out

        def stop(now, w):
            if now < seconds:
                return False
            if all(w.handles[i].done for i in w.counted):
                w.drained = True
                return True
            if now >= seconds + DRAIN_S:
                w.drained = False
                return True
            return False

        return self._drive(seconds, capture, next_due, submit_due, stop)

    # checking ------------------------------------------------------------
    def sample(self, w: Window, seed: int) -> List[int]:
        """Finished requests of the window to compare, drawn from the seed:
        the one with the most served tokens, then others at random until
        ``check_tokens`` served tokens (at most ``check_requests``)."""
        done = [i for i in w.counted if w.handles[i].status == "done"]
        if not done:
            return []
        rng = np.random.default_rng(np.random.SeedSequence(
            (seed % 2**64, 7)))
        longest = max(done, key=lambda i: len(w.handles[i].generated))
        rest = [i for i in rng.permutation(done) if i != longest]
        pick, tokens = [longest], len(w.handles[longest].generated)
        limit = self.mix["check_tokens"]
        for i in rest:
            if tokens >= limit or len(pick) >= self.mix["check_requests"]:
                break
            n = len(w.handles[i].generated)
            if tokens + n > ref_pad_p(self.mix):
                continue
            pick.append(int(i))
            tokens += n
        return pick

    def compare(self, w: Window, pick: List[int], control: bool = False):
        seqs = [(w.reqs[i].prompt,
                 np.asarray(w.handles[i].generated, np.int32))
                for i in pick]
        return self.ref.served_gaps(self.weights, self.c, seqs,
                                    control=control,
                                    pad_p=ref_pad_p(self.mix))

    def free_service(self) -> None:
        """Drop the service and its pool, keeping the weights."""
        self.svc = None
        gc.collect()


def ref_pad_p(mix: Dict[str, Any]) -> int:
    return int(mix.get("check_pad", 1024))


# --- what a window measured ----------------------------------------------------

def measure(w: Window, c: Dict[str, Any]) -> Dict[str, Any]:
    """End-to-end numbers and the per-layer context of one window."""
    arch = harness.arch(c["model_type"])
    ttft, itl = [], []
    prefill_fl = decode_fl = 0.0
    in_window = 0
    trace_prefill_fl = 0.0
    t_lo = w.traced.get("t0", -1.0)
    t_hi = w.traced.get("t1", -1.0)
    counted = set(w.counted)
    for i, st in w.stamps.items():
        r = w.reqs[i]
        plen = len(r.prompt)
        if i in counted and st:
            ttft.append(1e3 * (st[0] - r.due_s))
            itl += [1e3 * (b - a) for a, b in zip(st, st[1:])]
        for k, t in enumerate(st):
            if t > w.seconds:
                break
            in_window += 1
            if k == 0:
                fl = arch.prefill_flops(c, plen)
                prefill_fl += fl
                if t_lo <= t <= t_hi:
                    trace_prefill_fl += fl
            else:
                decode_fl += arch.decode_flops(c, plen + k - 1)
    # the window waits for its requests (up to DRAIN_S): one that never
    # finished failed
    not_done = [i for i in counted if w.handles[i].status != "done"]
    return {"ttft_ms": ttft, "itl_ms": itl, "tokens_in_window": in_window,
            "prefill_flops": prefill_fl, "decode_flops": decode_fl,
            "trace_prefill_flops": trace_prefill_fl,
            "attempted": len(counted), "failed": len(not_done)}


def run(args, spec, cell, c, mix, device, t_start):
    """One benchmark run of a serving cell; returns (result, checks)."""
    cap = devtrace.Capture(harness.OUT / f"trace-{cell['name']}") \
        if args.trace else None
    sc = ServeCell(c, mix, args.seed)
    warm = sc.warm()
    ep = warm["entry_points"]
    print(f"[setup] warm-up {warm['seconds']:.2f} s, {warm['lowerings']} "
          f"lowerings, {warm['compile_s']:.2f} s XLA compile; "
          f"{len(ep['prefill_shapes'])} prefill entries, decode batch sizes "
          f"{ep['decode_batch_sizes']}", file=sys.stderr)
    setup_s = time.perf_counter() - t_start
    try:
        w = sc.window(args.seed, args.seconds, capture=cap)
        if cap is not None:
            w.trace = cap.load()
    finally:
        if cap is not None:
            cap.close()
    device = dict(device, memory_peak_bytes=harness.memory_peak_bytes(
        device["count"]))
    m = measure(w, c)
    pick = sc.sample(w, args.seed)
    sc.free_service()
    t_ref = time.perf_counter()
    gaps = sc.compare(w, pick)["gap"] if pick else np.array([np.inf])
    print(f"[check] reference over {len(pick)} requests, {len(gaps)} served "
          f"tokens in {time.perf_counter() - t_ref:.2f} s", file=sys.stderr)
    limits = c["limits"]
    checks = {
        "served_gap_max": {"value": float(gaps.max()),
                           "limit": limits["served_gap_max"]},
        "unfinished": {"value": m["failed"], "limit": 0},
    }
    correct = all(v["value"] <= v["limit"] for v in checks.values())
    metrics = {}
    if not args.trace:
        e2e = {"setup_s": setup_s}
        if m["ttft_ms"]:
            e2e["ttft_p50_ms"] = harness.quantile(m["ttft_ms"], 0.5)
        if m["itl_ms"]:
            e2e["itl_p95_ms"] = harness.quantile(m["itl_ms"], 0.95)
        for e in harness.cell_metrics(spec, cell["name"], "end_to_end"):
            metrics[e["name"]] = {"value": e2e[e["name"]], "unit": e["unit"]}
    result = {"correct": bool(correct), "attempted": m["attempted"],
              "failed": m["failed"], "metrics": metrics, "device": device}
    print(f"[window] {m['attempted']} requests, {m['tokens_in_window']} "
          f"tokens in {w.seconds} s, {w.ticks} ticks, {w.compiles} "
          f"lowerings, drained {w.drained}; ttft p50 "
          f"{_q(m['ttft_ms'], .5)} p95 {_q(m['ttft_ms'], .95)} ms; itl p50 "
          f"{_q(m['itl_ms'], .5)} p95 {_q(m['itl_ms'], .95)} ms; generator "
          f"lag p95 {_q(w.lags_ms, .95)} ms", file=sys.stderr)
    if args.trace:
        summ = devtrace.summary(w.trace)
        ctx = {"kind": "serve", "config": c, "mix": mix, "window": w,
               "measure": m, "trace": w.trace, "summary": summ,
               "peaks": harness.peaks(device["kind"])}
        result["metrics"] = harness.read_per_layer(spec, cell["name"], ctx)
        result["device"].update(busy_s=summ["busy_s"],
                                window_s=summ["window_s"])
        result["breakdown"] = {"device_ops": devtrace.top_ops(w.trace),
                               "idle_gaps": devtrace.idle_gaps(w.trace)}
    return result, checks


def _q(xs, q):
    return f"{harness.quantile(xs, q):.2f}" if xs else "n/a"

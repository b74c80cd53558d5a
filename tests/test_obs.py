"""Observability tier: tracing round-trip, exact metric accounting,
Chrome/Perfetto export schema (DESIGN.md §Observability).

The paper's evaluation is itself an observability artifact — per-task
tic/toc timelines and exact overhead accounting — so these tests pin
(a) the disabled tracer really is a no-op, (b) spans/tasks/counters
survive the export round-trip as valid Chrome trace-event JSON, and
(c) the metric counts for a known QR plan match the analytic task counts
of the tile grid.
"""

import json
import threading

import numpy as np
import pytest

from repro.obs import (Counter, Gauge, Histogram, MetricsRegistry,
                       get_registry, get_tracer, set_tracer, disable,
                       to_chrome_trace, validate_chrome_trace,
                       write_chrome_trace)
from repro.obs.trace import NullTracer, Tracer, _NULL_SPAN


@pytest.fixture
def tracer():
    """A fresh recording tracer installed as the global default, restored
    to the no-op tracer afterwards."""
    tr = Tracer()
    old = get_tracer()
    set_tracer(tr)
    yield tr
    set_tracer(old)


# --------------------------------------------------------------------------
# tracer
# --------------------------------------------------------------------------

class TestTracer:
    def test_default_is_noop(self):
        disable()
        tr = get_tracer()
        assert isinstance(tr, NullTracer) and not tr.enabled
        # one shared singleton span; records never accumulate
        s1 = tr.span("a", x=1)
        s2 = tr.span("b")
        assert s1 is s2 is _NULL_SPAN
        with tr.span("c") as sp:
            sp.args["result"] = 42        # writable, discarded
        tr.task(0, 0, 0, 0.0, 1.0)
        tr.event_span("d", 0.0, 1.0)
        tr.counter("e", 3.0)
        tr.clear()
        assert tr.nr_records == 0

    def test_span_nesting_round_trip(self, tracer):
        with tracer.span("outer", n=1) as outer:
            with tracer.span("inner"):
                pass
            outer.args["late"] = True
        assert [s.name for s in tracer.spans] == ["inner", "outer"]
        inner, outer = tracer.spans
        assert (outer.depth, inner.depth) == (1, 2)
        assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1
        assert outer.args == {"n": 1, "late": True}
        assert outer.lane == threading.current_thread().name

    def test_task_counter_event_records(self, tracer):
        tracer.task(7, 2, 1, 0.5, 0.75)
        tracer.event_span("phase", 0.0, 1.0, lane="engine", k=3)
        tracer.counter("depth", 4, t=0.25)
        t = tracer.tasks[0]
        assert (t.tid, t.task_type, t.lane, t.t0, t.t1) == (7, 2, 1, 0.5, 0.75)
        assert tracer.spans[0].lane == "engine"
        assert tracer.counters[0].value == 4.0
        assert tracer.nr_records == 3
        tracer.clear()
        assert tracer.nr_records == 0

    def test_threaded_spans_keep_their_lanes(self, tracer):
        def work():
            with tracer.span("w"):
                pass
        ths = [threading.Thread(target=work, name=f"lane-{i}")
               for i in range(4)]
        for th in ths:
            th.start()
        for th in ths:
            th.join()
        assert sorted(s.lane for s in tracer.spans) == \
            [f"lane-{i}" for i in range(4)]
        assert all(s.depth == 1 for s in tracer.spans)


# --------------------------------------------------------------------------
# metrics
# --------------------------------------------------------------------------

class TestMetrics:
    def test_counter_exact(self):
        c = Counter("c")
        c.inc()
        c.inc(41)
        assert c.value == 42
        with pytest.raises(ValueError, match="negative"):
            c.inc(-1)
        c.reset()
        assert c.value == 0

    def test_gauge(self):
        g = Gauge("g")
        g.set(3.0)
        g.add(-1.5)
        assert g.value == 1.5
        g.reset()
        assert g.value == 0.0

    def test_histogram_exact_buckets(self):
        h = Histogram("h", buckets=(0.1, 1.0))
        for v in (0.05, 0.1, 0.5, 2.0):
            h.observe(v)
        s = h.summary()
        assert h.count == 4 and h.sum == pytest.approx(2.65)
        assert s["buckets"] == {"le_0.1": 2, "le_1": 1, "overflow": 1}
        assert (s["min"], s["max"]) == (0.05, 2.0)
        h.reset()
        assert h.summary() == {"count": 0, "sum": 0.0}

    def test_registry_get_or_create_and_kind_safety(self):
        reg = MetricsRegistry()
        assert reg.counter("x") is reg.counter("x")
        with pytest.raises(TypeError, match="already registered"):
            reg.gauge("x")
        reg.histogram("h", buckets=(1.0, 2.0))
        with pytest.raises(ValueError, match="bucket"):
            reg.histogram("h", buckets=(1.0, 3.0))
        reg.counter("x").inc(5)
        reg.gauge("g").set(2.5)
        snap = reg.snapshot()
        assert snap["x"] == 5 and snap["g"] == 2.5
        assert snap["h"] == {"count": 0, "sum": 0.0}
        reg.reset()
        assert reg.snapshot()["x"] == 0
        assert reg.names() == ["g", "h", "x"]


# --------------------------------------------------------------------------
# exporter
# --------------------------------------------------------------------------

def _populated_tracer():
    tr = Tracer()
    with tr.span("build", n=2):
        pass
    tr.task(0, 1, 0, 1.0, 2.0)
    tr.task(1, 1, 1, 1.5, 2.5, process="predicted")
    tr.counter("pool", 3, t=1.0)
    tr.counter("pool", 2, t=2.0)
    return tr


class TestExport:
    def test_chrome_schema_round_trip(self, tmp_path):
        tr = _populated_tracer()
        reg = MetricsRegistry()
        reg.counter("done").inc(2)
        path = str(tmp_path / "t.json")
        summary = write_chrome_trace(path, tr, registry=reg,
                                     type_names={1: "DECODE"})
        assert summary == validate_chrome_trace(path)
        assert summary["phases"]["X"] == 3
        assert summary["phases"]["C"] == 2
        assert summary["counter_tracks"] == ["pool"]
        assert summary["processes"] == ["measured", "predicted"]
        obj = json.load(open(path))
        assert obj["otherData"]["metrics"]["done"] == 2
        names = {e["name"] for e in obj["traceEvents"] if e["ph"] == "X"}
        assert {"build", "DECODE"} <= names
        # timestamps normalized to the earliest record, in microseconds
        ts = [e["ts"] for e in obj["traceEvents"] if e["ph"] != "M"]
        assert min(ts) == 0.0
        task = next(e for e in obj["traceEvents"]
                    if e.get("cat") == "task" and e["args"]["tid"] == 0)
        assert task["dur"] == pytest.approx(1e6)

    def test_processes_get_distinct_pids(self):
        obj = to_chrome_trace(_populated_tracer())
        pids = {}
        for e in obj["traceEvents"]:
            if e["ph"] == "M" and e["name"] == "process_name":
                pids[e["args"]["name"]] = e["pid"]
        assert set(pids) == {"measured", "predicted"}
        assert len(set(pids.values())) == 2

    @pytest.mark.parametrize("mutate,match", [
        (lambda e: e.pop("ts"), "missing required key"),
        (lambda e: e.update(ts=-5.0), "negative timestamp"),
        (lambda e: e.pop("dur"), "needs numeric 'dur'"),
        (lambda e: e.update(dur=-1.0), "negative duration"),
    ])
    def test_tampered_trace_rejected(self, mutate, match):
        obj = to_chrome_trace(_populated_tracer())
        bad = next(e for e in obj["traceEvents"] if e["ph"] == "X")
        mutate(bad)
        with pytest.raises(ValueError, match=match):
            validate_chrome_trace(obj)

    def test_counter_event_needs_numeric_args(self):
        obj = to_chrome_trace(_populated_tracer())
        bad = next(e for e in obj["traceEvents"] if e["ph"] == "C")
        bad["args"] = {"value": "three"}
        with pytest.raises(ValueError, match="numeric args"):
            validate_chrome_trace(obj)


# --------------------------------------------------------------------------
# instrumented layers: exact accounting + timelines for a known QR plan
# --------------------------------------------------------------------------

def _qr_type_counts(mt, nt):
    """Analytic task counts of the mt x nt tiled-QR graph."""
    from repro.apps import qr
    k = range(min(mt, nt))
    return {
        qr.T_GEQRF: len(list(k)),
        qr.T_LARFT: sum(nt - kk - 1 for kk in k),
        qr.T_TSQRF: sum(mt - kk - 1 for kk in k),
        qr.T_SSRFT: sum((mt - kk - 1) * (nt - kk - 1) for kk in k),
    }


class TestQRAccounting:
    def test_executor_counts_match_tile_grid(self, tracer):
        """Running the 3x3-tile QR graph must execute exactly the
        analytic per-type task counts (GEQRF 3, LARFT 3, TSQRF 3,
        SSRFT 5), tallied on the executor and as registry deltas, with
        one task record each on the tracer."""
        import jax.numpy as jnp

        from repro.apps import qr

        counts = _qr_type_counts(3, 3)
        total = sum(counts.values())
        reg = get_registry()
        before = {tt: reg.counter(f"executor.tasks.type{tt}").value
                  for tt in counts}
        before_total = reg.counter("executor.tasks_executed").value

        a = jnp.asarray(np.random.default_rng(0)
                        .standard_normal((96, 96)), jnp.float32)
        r, sched = qr.run_qr(a, tile=32, mode="sequential", backend="ref")

        for tt, n in counts.items():
            assert (reg.counter(f"executor.tasks.type{tt}").value
                    - before[tt]) == n
        assert (reg.counter("executor.tasks_executed").value
                - before_total) == total
        assert len(tracer.tasks) == total == sched.nr_tasks
        by_type = {}
        for t in tracer.tasks:
            by_type[t.task_type] = by_type.get(t.task_type, 0) + 1
            assert t.t1 >= t.t0 and t.lane == 0
        assert by_type == counts

    def test_plan_spans_recorded(self, tracer):
        from repro.apps import qr
        from repro.core import lower
        from repro.core.plan import clear_plan_cache, plan_cache_info

        s, _ = qr.make_qr_graph(3, 3)
        clear_plan_cache()
        plan = lower(s, 4)
        lower(s, 4)                             # cache hit: no new span
        info = plan_cache_info()
        assert (info["hits"], info["misses"]) == (1, 1)
        names = [sp.name for sp in tracer.spans]
        assert names.count("plan.lower") == 1
        assert "core.prepare" in names
        sp = next(sp for sp in tracer.spans if sp.name == "plan.lower")
        assert sp.args["tasks"] == s.nr_tasks
        assert sp.args["rounds"] == plan.nr_rounds


class TestLockFailureAccounting:
    def _conflicting_sched(self):
        from repro.core.graph import QSched
        s = QSched(nr_queues=2)
        r = s.addres()
        for _ in range(2):
            s.addlock(s.addtask(type=0, data=None), r)
        return s

    def test_simulated_contention_counts_failures(self):
        from repro.core.simulator import simulate
        s = self._conflicting_sched()
        simulate(s, 2)
        # two ready tasks, one shared resource, two workers: the second
        # worker's gettask must fail the lock at least once
        assert s.lock_failures >= 1
        s.start(threaded=False)
        assert s.lock_failures == 0      # reset like the rest of run state

    def test_threaded_executor_exposes_per_run_failures(self):
        from repro.core.executors import ThreadedExecutor
        s = self._conflicting_sched()
        ex = ThreadedExecutor(s, 2)
        reg = get_registry()
        before = reg.counter("executor.lock_failures").value
        ex.run(lambda tt, data: None)
        assert ex.lock_failures == s.lock_failures >= 0
        assert (reg.counter("executor.lock_failures").value - before
                ) == ex.lock_failures
        assert ex.type_counts == {0: 2}
        first = ex.lock_failures
        s2 = self._conflicting_sched()
        ex2 = ThreadedExecutor(s2, 2)
        ex2.run(lambda tt, data: None)   # fresh run: fresh accounting
        assert ex2.lock_failures == s2.lock_failures
        del first


# --------------------------------------------------------------------------
# serving tier
# --------------------------------------------------------------------------

class TestServiceObservability:
    @pytest.fixture(scope="class")
    def cfg_params(self):
        import jax
        from repro.configs import get_config
        from repro.models import lm
        cfg = get_config("qwen3-1.7b").reduced()
        return cfg, lm.init_params(jax.random.PRNGKey(0), cfg)

    def test_stats_dict_and_metrics_registry(self, cfg_params):
        from repro.serve import GenerateService
        cfg, params = cfg_params
        svc = GenerateService(params, cfg, max_batch=2, max_seq=16,
                              page_size=4)
        prompt = np.arange(5, dtype=np.int32) % cfg.vocab
        svc.submit(prompt, 3)
        svc.submit(prompt, 3)
        svc.run_until_complete()
        # dict-shaped accessor stays backward-compatible
        assert svc.stats["submitted"] == svc.stats["admitted"] == 2
        assert svc.stats["retired"] == 2
        assert svc.stats["generated_tokens"] == 6
        # same counts live on the typed per-service registry
        snap = svc.metrics.snapshot()
        assert snap["serve.retired"] == 2
        assert snap["serve.ttft_s"]["count"] == 2
        assert snap["serve.latency_s"]["count"] == 2
        assert snap["serve.pages_in_use"] == 0.0    # drained
        for h in (svc.metrics.histogram("serve.ttft_s"),
                  svc.metrics.histogram("serve.latency_s")):
            assert h.sum > 0.0

    def test_request_lifecycle_trace(self, cfg_params, tracer, tmp_path):
        from repro.serve import GenerateService
        cfg, params = cfg_params
        svc = GenerateService(params, cfg, max_batch=2, max_seq=16,
                              page_size=4)
        prompt = np.arange(5, dtype=np.int32) % cfg.vocab
        reqs = [svc.submit(prompt, 3), svc.submit(prompt, 3)]
        svc.run_until_complete()
        for r in reqs:
            assert r.t_submit <= r.t_admit <= r.t_first <= r.t_done
            assert r.latency_s >= r.ttft_s > 0.0
        span_names = {s.name for s in tracer.spans}
        # no "plan.lower" here: the decode/admission shapes were lowered
        # (and cached) by the untraced test above — cache hits re-emit no
        # lowering span, by design
        assert {"request.queued", "request.prefill", "request.decode",
                "request", "engine.execute"} <= span_names
        lanes = {s.lane for s in tracer.spans
                 if s.name == "request"}
        assert lanes == {f"req {r.rid}" for r in reqs}
        # engine.execute is a nested span now (the dispatch, inside the
        # tick's serve.decode), on the serving thread's own lane
        main = threading.current_thread().name
        decodes = [s for s in tracer.spans if s.name == "serve.decode"]
        for ex in (s for s in tracer.spans if s.name == "engine.execute"):
            assert ex.lane == main and ex.depth >= 3
            assert any(d.t0 <= ex.t0 <= ex.t1 <= d.t1 for d in decodes)

        path = str(tmp_path / "serve.json")
        summary = write_chrome_trace(path, registry=svc.metrics)
        assert {"serve.pages_in_use", "serve.queue_depth"} <= \
            set(summary["counter_tracks"])
        assert "requests" in summary["processes"]

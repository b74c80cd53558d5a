"""Spans of the host path on the profiler's clock (DESIGN.md
§Observability): an enabled tracer mirrors its nested spans into a
``jax.profiler`` capture, garbage collections become ``gc.collect``
spans, a serving tick counts and spans every device→host read, a QR plan
spans its tile glue, ``execute_plan`` stays asynchronous under tracing,
and the kernels and the decode round carry stable names."""

import gc
import glob
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import disable, enable, get_tracer, set_tracer
from repro.obs.trace import NullTracer


@pytest.fixture
def tracer():
    """A recording tracer installed through ``enable``; the no-op tracer
    afterwards."""
    tr = enable()
    yield tr
    disable()


def _capture(tmp_path, body):
    """Run ``body`` under a profiler capture on the CPU; return the host
    events as ``{name: [duration_s, ...]}``."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    events.setdefault(ev.name, []).append(
                        ev.duration_ns / 1e9)
    return events


# --------------------------------------------------------------------------
# the tracer and the profiler
# --------------------------------------------------------------------------

def test_enabled_tracer_mirrors_nested_spans_into_the_profiler(
        tracer, tmp_path):
    def body():
        with tracer.span("mirror.outer", n=1):
            time.sleep(0.02)
            with tracer.span("mirror.inner"):
                time.sleep(0.03)

    events = _capture(tmp_path, body)
    recs = {s.name: s.t1 - s.t0 for s in tracer.spans
            if s.name.startswith("mirror.")}
    assert set(recs) == {"mirror.outer", "mirror.inner"}
    for name, dur in recs.items():
        prof, = events[name]
        # the annotation encloses the record: same interval, to within
        # the few microseconds of the record's own bookkeeping
        assert dur <= prof + 1e-6
        assert prof - dur < 1e-3
    # the name only: args stay in the obs record
    assert not any("n=1" in name for name in events)


def test_disabled_tracer_records_and_mirrors_nothing(tmp_path):
    disable()
    tr = get_tracer()
    assert isinstance(tr, NullTracer)

    def body():
        with tr.span("mirror.off"):
            time.sleep(0.005)

    events = _capture(tmp_path, body)
    assert "mirror.off" not in events
    assert tr.nr_records == 0


def test_gc_collections_become_spans_while_enabled(tracer):
    with tracer.span("gc.outer"):
        gc.collect()
    span, = [s for s in tracer.spans
             if s.name == "gc.collect" and s.args["generation"] == 2]
    outer, = [s for s in tracer.spans if s.name == "gc.outer"]
    assert outer.t0 <= span.t0 <= span.t1 <= outer.t1
    assert span.depth == outer.depth + 1
    assert span.args["collected"] >= 0
    # replacing the tracer unhooks it
    disable()
    assert tracer._on_gc not in gc.callbacks
    n = len(tracer.spans)
    gc.collect()
    assert len(tracer.spans) == n


def test_set_tracer_alone_records_no_collections():
    from repro.obs.trace import Tracer
    tr = Tracer()
    old = set_tracer(tr)
    try:
        gc.collect()
        assert not [s for s in tr.spans if s.name == "gc.collect"]
    finally:
        set_tracer(old)


# --------------------------------------------------------------------------
# a serving tick
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def service():
    from repro.configs import get_config
    from repro.models import lm
    from repro.serve import GenerateService
    cfg = get_config("qwen3-1.7b").reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    svc = GenerateService(params, cfg, max_batch=2, max_seq=16,
                          page_size=4, guard=True)
    # warm both ticks' shapes untraced
    svc.submit(np.arange(5, dtype=np.int32) % cfg.vocab, 3)
    svc.run_until_complete()
    return svc, cfg


def _syncs(tracer):
    return [s.args["what"] for s in tracer.spans if s.name == "serve.sync"]


def test_decode_tick_reads_three_times_and_admission_twice(
        service, tracer):
    svc, cfg = service
    base = svc.stats["host_syncs"]
    svc.submit(np.arange(5, dtype=np.int32) % cfg.vocab, 4)
    svc.step()          # admits one request, then decodes it
    assert sorted(_syncs(tracer)) == sorted(
        ["keys", "first_token", "flags", "tokens", "positions"])
    assert svc.stats["host_syncs"] == base + 5
    tracer.clear()
    svc.step()          # decode only
    assert sorted(_syncs(tracer)) == ["flags", "positions", "tokens"]
    assert svc.stats["host_syncs"] == base + 8
    svc.run_until_complete()

    step = next(s for s in tracer.spans if s.name == "serve.step")
    decode = next(s for s in tracer.spans if s.name == "serve.decode")
    assert step.depth == 1 and decode.depth == 2
    assert decode.args["batch"] == 1 and decode.args["pages"] >= 1
    for name in ("engine.buffers", "engine.execute", "engine.writeback"):
        s = next(s for s in tracer.spans if s.name == name)
        assert decode.t0 <= s.t0 <= s.t1 <= decode.t1


def test_admitting_tick_spans_admission_and_prefill(service, tracer):
    svc, cfg = service
    svc.submit(np.arange(5, dtype=np.int32) % cfg.vocab, 2)
    svc.step()
    admit, = [s for s in tracer.spans if s.name == "serve.admit"]
    prefill, = [s for s in tracer.spans if s.name == "serve.prefill"]
    assert admit.args == {"batch": 1}
    assert prefill.args == {"plen": 5, "nb": 1}
    assert admit.t0 <= prefill.t0 <= prefill.t1 <= admit.t1
    svc.run_until_complete()
    # ticks that admit nothing open no admission span
    assert len([s for s in tracer.spans if s.name == "serve.admit"]) == 1


# --------------------------------------------------------------------------
# a QR plan and the engine
# --------------------------------------------------------------------------

def test_qr_plan_spans_its_glue(tracer):
    from repro.apps.qr import run_qr
    a = jax.random.normal(jax.random.PRNGKey(1), (64, 64), jnp.float32)
    run_qr(a, tile=32, mode="rounds")
    names = [s.name for s in tracer.spans]
    for name in ("qr.run", "qr.split_tiles", "qr.build_graph",
                 "qr.assemble_r"):
        assert names.count(name) == 1, name
    run = next(s for s in tracer.spans if s.name == "qr.run")
    for s in tracer.spans:
        if s.name.startswith("qr.") and s.name != "qr.run":
            assert run.t0 <= s.t0 <= s.t1 <= run.t1
            assert s.depth == run.depth + 1


@pytest.mark.parametrize("mode, counter", [
    ("engine", "qr.glue.stacked_plans"),
    ("sequential", "qr.glue.per_tile_plans")])
def test_qr_plan_spans_glue_once_and_counts_its_tile_state(
        tracer, mode, counter):
    """An engine plan keeps the glue spans that ``glue_host_ms.plan``
    reads, once each, and counts as a stacked plan; a host-mode plan
    counts as a per-tile one."""
    from repro.apps.qr import run_qr
    from repro.obs import get_registry
    reg = get_registry()
    names = ("qr.glue.stacked_plans", "qr.glue.per_tile_plans")
    before = {n: reg.counter(n).value for n in names}
    a = jax.random.normal(jax.random.PRNGKey(2), (64, 64), jnp.float32)
    run_qr(a, tile=32, mode=mode)
    spans = [s.name for s in tracer.spans]
    glue = ("qr.split_tiles", "qr.build_graph", "qr.assemble_r")
    if mode == "engine":
        glue += ("engine.buffers", "engine.writeback")
    for name in glue:
        assert spans.count(name) == 1, name
    assert {n: reg.counter(n).value - before[n] for n in names} == {
        n: int(n == counter) for n in names}


def _bump(desc, phase_bounds, statics, buffers):
    return (buffers[0] + desc.shape[0],)


def test_execute_plan_does_not_block_under_tracing(tracer, monkeypatch):
    from repro import engine
    from repro.apps import qr as qr_app
    from repro.core.plan import lower

    sched, _ = qr_app.make_qr_graph(2, 2)
    state = qr_app._TileState({(i, j): None for i in range(2)
                               for j in range(2)}, "pallas")
    tables = engine.lower_tables(lower(sched, 1), sched,
                                 state.batch_registry(),
                                 arg_width=engine.QR_ARG_WIDTH,
                                 row_access=engine.qr_row_access)
    bufs = (jnp.zeros((4,), jnp.int32),)
    engine.execute_plan(tables, _bump, (), bufs)            # compile

    def forbidden(*a, **k):
        raise AssertionError("execute_plan waited for the device")

    monkeypatch.setattr(jax, "block_until_ready", forbidden)
    out = engine.execute_plan(tables, _bump, (), bufs)
    monkeypatch.undo()
    assert int(out[0][0]) == tables.nr_items
    # the dispatch itself records no span: its caller's engine.execute does
    assert not [s for s in tracer.spans if s.name == "engine.execute"]


# --------------------------------------------------------------------------
# stable names
# --------------------------------------------------------------------------

def _tpu_text(fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d) for s, d in shapes]
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()


def test_paged_decode_kernel_is_named():
    from repro.kernels.paged_attention import kernel as paged
    bs, h, hkv, hd, ps, pages = 2, 4, 2, 128, 8, 8
    bf = jnp.bfloat16
    text = _tpu_text(lambda q, kn, vn, kp, vp, pr, pos: paged.paged_gqa_call(
        q, kn, vn, kp, vp, pr, pos, page_size=ps, interpret=False),
        ((bs, h, hd), bf), ((bs, hkv, hd), bf), ((bs, hkv, hd), bf),
        ((pages, ps, hkv, hd), bf), ((pages, ps, hkv, hd), bf),
        ((bs, pages // bs), jnp.int32), ((bs,), jnp.int32))
    assert "tpu_custom_call" in text and "paged_decode" in text


def test_qr_megakernel_and_its_program_are_named():
    from repro import engine
    from repro.apps import qr as qr_app
    from repro.core.plan import lower
    from repro.engine.runner import _round_segments, _segment_runner
    mt, b = 2, 64
    sched, _ = qr_app.make_qr_graph(mt, mt)
    state = qr_app._TileState({(i, j): None for i in range(mt)
                               for j in range(mt)}, "pallas")
    tables = engine.lower_tables(lower(sched, 1), sched,
                                 state.batch_registry(),
                                 arg_width=engine.QR_ARG_WIDTH,
                                 row_access=engine.qr_row_access)
    run = _segment_runner(engine.qr_round_fn(interpret=False),
                          _round_segments(tables), False)
    text = run.trace(
        jax.ShapeDtypeStruct(tables.desc.shape, jnp.int32), (),
        (jax.ShapeDtypeStruct((mt * mt, b, b), jnp.float32),) * 2).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "qr_megakernel" in text and "jit_qr_round" in text


def test_decode_round_program_is_named(service):
    from repro.engine.runner import _segment_runner
    svc, _ = service
    hooks = svc.hooks
    run = _segment_runner(hooks.round_fn, ((0, 1, (0, 1)),), False)
    desc = jnp.asarray([[1, 0, 3]], jnp.int32)
    text = run.lower(desc, svc._statics(), svc._buffers()).as_text()
    assert "jit_decode_round" in text

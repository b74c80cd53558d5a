"""Backend registry + the cross-mode equivalence matrix (ISSUE 4).

The paper's claim is ONE scheduler core serving heterogeneous workloads
with no per-workload executor code; `core/backends.py` is that claim at
the dispatch layer.  These tests pin it down three ways:

* registry semantics — lookup, capability flags, ``supports()`` probing,
  ``BackendUnsupported`` on capability mismatch;
* the equivalence matrix — every registered backend × all three task
  families (QR bitwise against the sequential oracle, BH and the pipeline
  within the established reassociation tolerances), replacing the
  per-app mode tests that used to be scattered over test_qr/test_plan/
  test_engine;
* the pipeline engine acceptance — a whole pipelined value-and-grad step
  as one jitted dispatch, matching ``jax.grad`` of the unpipelined loss;
* simulator validation (ROADMAP slice) — measured engine round times
  replayed through the discrete-event model predict the fused execute
  time within a stated bound.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.apps import barneshut as bh
from repro.apps import qr
from repro.core import (Backend, BackendUnsupported, BatchSpec, EngineHooks,
                        QSched, available_backends, get_backend, lower,
                        register_backend, replay_item_times,
                        replay_round_times, run_plan)
from repro.pipeline import synthesize_schedule
from repro.pipeline.exec import (dense_stage, mse_loss,
                                 pipelined_value_and_grad,
                                 pipelined_value_and_grad_plan)

ALL_MODES = ("sequential", "threaded", "rounds", "engine")


class TestRegistry:
    def test_all_modes_registered(self):
        assert set(ALL_MODES) <= set(available_backends())

    def test_unknown_backend_raises(self):
        with pytest.raises(ValueError, match="unknown execution backend"):
            get_backend("warp-drive")

    def test_capability_flags(self):
        assert get_backend("rounds").needs_plan
        assert get_backend("engine").needs_plan
        assert get_backend("engine").device_resident
        assert get_backend("threaded").concurrent
        assert not get_backend("sequential").concurrent
        assert not get_backend("sequential").needs_plan

    def test_register_and_dispatch_custom_backend(self):
        class Recording(Backend):
            name = "recording"
            needs_plan = True

            def run(self, sched, plan, registry, *, nr_workers=1,
                    engine=None):
                self.seen = [t for rnd in plan.rounds for t in rnd.tids]

        be = register_backend(Recording())
        try:
            s = QSched()
            for i in range(4):
                s.addtask(type=0, data=i)
            run_plan(s, {0: BatchSpec(run_one=lambda tid, d: None)},
                     "recording")
            assert sorted(be.seen) == [0, 1, 2, 3]
        finally:
            import repro.core.backends as backends_mod
            del backends_mod._BACKENDS["recording"]

    def test_engine_supports_requires_hooks_and_encoders(self):
        s = QSched()
        s.addtask(type=0, data=0)
        plan = lower(s, 1, cache=False)
        be = get_backend("engine")
        no_enc = {0: BatchSpec(run_one=lambda tid, d: None)}
        enc = {0: BatchSpec(run_one=lambda tid, d: None,
                            encode=lambda tid, d: [(0, 0)])}
        hooks = EngineHooks(arg_width=1, round_fn=None,
                            statics=tuple, buffers=tuple,
                            writeback=lambda out: None)
        assert not be.supports(plan, s, enc, None)       # no family hooks
        assert not be.supports(plan, s, no_enc, hooks)   # no encoder
        assert be.supports(plan, s, enc, hooks)

    def test_run_plan_raises_backend_unsupported(self):
        s = QSched()
        s.addtask(type=0, data=0)
        with pytest.raises(BackendUnsupported):
            run_plan(s, {0: BatchSpec(run_one=lambda tid, d: None)},
                     "engine")

    def test_plan_run_dispatches_through_registry(self):
        s = QSched()
        for i in range(3):
            s.addtask(type=0, data=i)
        seen = []
        plan = lower(s, 2, cache=False)
        plan.run(s, {0: BatchSpec(run_one=lambda tid, d: seen.append(d))},
                 backend="rounds")
        assert sorted(seen) == [0, 1, 2]

    def test_sequential_backend_missing_spec_raises(self):
        s = QSched()
        s.addtask(type=3, data=0)
        with pytest.raises(KeyError, match="task type 3"):
            run_plan(s, {}, "sequential")


# ---------------------------------------------------------------------------
# the equivalence matrix: every backend × every task family
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def qr_case():
    a = jnp.asarray(np.random.default_rng(0).standard_normal((96, 96)),
                    jnp.float32)
    oracle, _ = qr.run_qr(a, tile=32, mode="sequential", backend="pallas")
    return a, np.asarray(oracle)


class TestMatrixQR:
    @pytest.mark.parametrize("mode", ALL_MODES)
    def test_matches_sequential_bitwise(self, qr_case, mode):
        """All backends share the same value-level tile math and a fully
        deterministic dependency order, so R must be BITWISE equal."""
        a, oracle = qr_case
        r, _ = qr.run_qr(a, tile=32, mode=mode, backend="pallas",
                         nr_queues=4)
        np.testing.assert_array_equal(np.asarray(r), oracle)

    def test_oracle_is_valid_r(self, qr_case):
        a, r = qr_case
        rhs = np.asarray(a).T @ np.asarray(a)
        assert np.abs(np.tril(r, -1)).max() < 1e-4
        assert np.abs(r.T @ r - rhs).max() / np.abs(rhs).max() < 1e-4


@pytest.fixture(scope="module")
def bh_case():
    rng = np.random.default_rng(3)
    x, m = rng.random((1200, 3)), rng.random(1200) + 0.5
    acc, _, _ = bh.solve(x, m, n_max=32, n_task=128, backend="ref",
                         mode="sequential")
    return x, m, np.asarray(acc)


def _bh_rel_err(a, oracle):
    num = np.linalg.norm(np.asarray(a) - oracle, axis=0)
    den = np.linalg.norm(oracle, axis=0)
    return (num / np.maximum(den, 1e-12)).max()


class TestMatrixBarnesHut:
    @pytest.mark.parametrize("mode", ALL_MODES)
    def test_matches_sequential(self, bh_case, mode):
        """Accumulation order differs per backend only by float
        reassociation — ≤1e-4 relative (the established rounds-mode
        tolerance).  The concurrent backend accumulates in-place on a
        shared numpy buffer where the hierarchical resource locks are the
        only thing preventing lost updates."""
        x, m, oracle = bh_case
        tree = bh.Octree(x, m, n_max=32)
        g = bh.build_graph(tree, n_task=128, nr_queues=4)
        accumulate = "numpy" if get_backend(mode).concurrent else "jnp"
        st = bh.BHState(g, backend="ref", accumulate=accumulate)
        st.run(mode=mode, nr_workers=4)
        assert _bh_rel_err(st.result(), oracle) < 1e-4

    def test_engine_requires_device_accumulation(self, bh_case):
        x, m, _ = bh_case
        tree = bh.Octree(x, m, n_max=32)
        g = bh.build_graph(tree, n_task=128, nr_queues=4)
        st = bh.BHState(g, backend="ref", accumulate="numpy")
        with pytest.raises(AssertionError, match="accumulate='jnp'"):
            st.run(mode="engine")


@pytest.fixture(scope="module")
def pipe_case():
    S, M, Bt, D = 3, 6, 4, 8
    key = jax.random.PRNGKey(2)
    params = [{"w": jax.random.normal(jax.random.fold_in(key, k),
                                      (D, D)) * 0.3,
               "b": jnp.zeros((D,))} for k in range(S)]
    micro = [{"x": jax.random.normal(jax.random.fold_in(key, 10 + m),
                                     (Bt, D)),
              "y": jax.random.normal(jax.random.fold_in(key, 50 + m),
                                     (Bt, D))} for m in range(M)]

    def monolithic(ps):
        total = 0.0
        for mb in micro:
            h = mb["x"]
            for p in ps:
                h = dense_stage(p, h)
            total = total + mse_loss(h, mb)
        return total / M

    loss, grads = jax.value_and_grad(monolithic)(params)
    return S, M, params, micro, float(loss), grads


class TestMatrixPipeline:
    @pytest.mark.parametrize("mode", ALL_MODES)
    def test_value_and_grad_equals_monolithic(self, pipe_case, mode):
        """Acceptance gate: every backend — including the single-dispatch
        engine — reproduces ``jax.grad`` of the unpipelined loss within
        the established plan-mode tolerance."""
        S, M, params, micro, loss_m, grads_m = pipe_case
        loss_p, grads_p = pipelined_value_and_grad_plan(
            [dense_stage] * S, mse_loss, params, micro, mode=mode)
        assert abs(float(loss_p) - loss_m) < 1e-6
        for gp, gm in zip(grads_p, grads_m):
            for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gm)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-5, atol=1e-6)

    def test_engine_rejects_non_canonical_family(self, pipe_case):
        S, M, params, micro, _, _ = pipe_case

        def other_stage(p, x):
            return jnp.tanh(x @ p["w"] + p["b"])

        with pytest.raises(BackendUnsupported, match="canonical dense"):
            pipelined_value_and_grad_plan(
                [other_stage] * S, mse_loss, params, micro, mode="engine")

    def test_engine_rejects_mismatched_param_count(self, pipe_case):
        """Fewer params than stages must fail the capability probe, not
        read out of bounds in the kernel."""
        S, M, params, micro, _, _ = pipe_case
        with pytest.raises(BackendUnsupported, match="canonical dense"):
            pipelined_value_and_grad_plan(
                [dense_stage] * S, mse_loss, params[:-1], micro,
                mode="engine")

    def test_engine_is_single_dispatch(self, pipe_case):
        """The dispatch-count claim: the host rounds path issues one call
        per task body while the engine issues exactly one jitted call for
        the whole value-and-grad step."""
        from repro.pipeline.exec import _PipeRunner
        from repro.pipeline import lower_pipeline_plan
        S, M, params, micro, _, _ = pipe_case
        runner = _PipeRunner([dense_stage] * S, mse_loss, params, micro)
        sched, _, plan = lower_pipeline_plan(S, M, per_stage_window=True)
        host = engine.count_host_dispatches(plan, sched, runner.registry())
        assert host >= 5 * engine.ENGINE_DISPATCHES_PER_PLAN
        assert engine.ENGINE_DISPATCHES_PER_PLAN == 1

    def test_unknown_event_kind_raises(self, pipe_case):
        """Satellite regression: unknown schedule event kinds used to be
        silently skipped; they must now raise."""
        S, M, params, micro, _, _ = pipe_case
        ps = synthesize_schedule(S, M)
        ps.lanes[0].insert(0, ("Z", 0, 0, -1.0, -0.5))
        with pytest.raises(ValueError, match="unknown pipeline event"):
            pipelined_value_and_grad(
                [dense_stage] * S, mse_loss, params, micro, ps)

    def test_update_events_are_noop_for_caller(self, pipe_case):
        """The U events run (no exception) and leave the returned grads
        unapplied — applying the optimizer is the documented caller
        contract."""
        S, M, params, micro, loss_m, _ = pipe_case
        ps = synthesize_schedule(S, M)
        assert any(kind == "U" for lane in ps.lanes
                   for kind, *_ in lane)
        loss_p, _ = pipelined_value_and_grad(
            [dense_stage] * S, mse_loss, params, micro, ps)
        assert abs(float(loss_p) - loss_m) < 1e-6


# ---------------------------------------------------------------------------
# simulator validation (ROADMAP slice): replay measured engine round times
# ---------------------------------------------------------------------------

class TestSimulatorReplay:
    def test_replayed_makespan_predicts_fused_execute(self):
        """Measure per-round engine times, replay them through the
        discrete-event simulator, and compare the predicted makespan with
        the measured single-dispatch execute time.  Stated bound: the
        additive round model must predict the fused wall time within a
        factor of 5 either way (interpret-mode dispatch overhead differs
        between per-round and in-loop launches, and CI machines jitter —
        both measurements take the best over 3 passes; the *model*
        consistency — replayed 1-worker makespan == Σ measured round
        times — is exact)."""
        a = jnp.asarray(np.random.default_rng(0).standard_normal((96, 96)),
                        jnp.float32)
        tiles, mt, nt = qr._split_tiles(a, 32)
        sched, _ = qr.make_qr_graph(mt, nt, nr_queues=4)
        plan = lower(sched, 4)
        state = qr._TileState(dict(tiles), "pallas")
        tables = engine.lower_tables(
            plan, sched, state.batch_registry(),
            arg_width=engine.QR_ARG_WIDTH, row_access=engine.qr_row_access)
        stack = qr._stack_tiles(a, 32)
        fn = engine.qr_round_fn()
        round_times = None
        for _ in range(3):      # elementwise best-of-3 absorbs CI jitter
            timings = engine.measure_round_times(
                tables, fn, (), (stack, jnp.zeros_like(stack)))
            times = timings.round_s
            round_times = (times if round_times is None
                           else [min(a_, b_)
                                 for a_, b_ in zip(round_times, times)])
        assert len(round_times) == plan.nr_rounds

        # the model itself is additive and exact
        res = replay_round_times(sched, plan, round_times, nr_workers=1)
        assert res.makespan == pytest.approx(sum(round_times), rel=1e-9)

        # measured fused execute (compile warmed up, best of 3)
        engine.execute_plan(tables, fn, (),
                            (stack, jnp.zeros_like(stack)), donate=False)
        measured = float("inf")
        for _ in range(3):
            bufs = (stack + 0.0, jnp.zeros_like(stack))
            t0 = time.perf_counter()
            out = engine.execute_plan(tables, fn, (), bufs, donate=False)
            jax.block_until_ready(out)
            measured = min(measured, time.perf_counter() - t0)
        ratio = res.makespan / measured
        assert 0.2 <= ratio <= 5.0, (
            f"predicted {res.makespan:.4f}s vs measured {measured:.4f}s "
            f"(ratio {ratio:.2f})")

    def test_per_item_times_replay_lane_parallel_makespans(self):
        """Per-item measurements (``measure_round_times(per_item=True)``)
        give every task its own measured cost, so ``replay_item_times``
        can predict *parallel* makespans (ROADMAP: simulator validation
        beyond one worker).  Model consistency bounds: the 1-worker replay
        is exactly Σ item times; a 4-worker replay can be no better than
        the critical path and no worse than serial."""
        a = jnp.asarray(np.random.default_rng(1).standard_normal((96, 96)),
                        jnp.float32)
        tiles, mt, nt = qr._split_tiles(a, 32)
        sched, _ = qr.make_qr_graph(mt, nt, nr_queues=4)
        plan = lower(sched, 4)
        state = qr._TileState(dict(tiles), "pallas")
        tables = engine.lower_tables(
            plan, sched, state.batch_registry(),
            arg_width=engine.QR_ARG_WIDTH, row_access=engine.qr_row_access)
        stack = qr._stack_tiles(a, 32)
        timings = engine.measure_round_times(
            tables, engine.qr_round_fn(), (),
            (stack, jnp.zeros_like(stack)), per_item=True)
        assert timings.item_s is not None
        assert len(timings.item_s) == tables.nr_items
        assert (timings.item_s > 0).all()

        serial = replay_item_times(sched, tables.tids, timings.item_s,
                                   nr_workers=1)
        assert serial.makespan == pytest.approx(float(timings.item_s.sum()),
                                                rel=1e-9)
        par = replay_item_times(sched, tables.tids, timings.item_s,
                                nr_workers=4)
        assert par.makespan <= serial.makespan + 1e-12
        # per-task measured costs: the longest task bounds any makespan
        per_task = np.zeros(sched.nr_tasks)
        np.add.at(per_task, np.asarray(tables.tids), timings.item_s)
        assert par.makespan >= per_task.max() - 1e-12

    def test_replay_item_times_validates_lengths(self):
        s, _ = qr.make_qr_graph(3, 3)
        with pytest.raises(ValueError, match="item times"):
            replay_item_times(s, [0, 1], [0.1])
        with pytest.raises(ValueError, match="out of range"):
            replay_item_times(s, [s.nr_tasks], [0.1])

    def test_replay_restores_costs(self):
        s, _ = qr.make_qr_graph(4, 4)
        plan = lower(s, 2)
        before = list(s._tcost)
        replay_round_times(s, plan, [0.5] * plan.nr_rounds, nr_workers=2)
        assert list(s._tcost) == before

    def test_replay_length_mismatch_raises(self):
        s, _ = qr.make_qr_graph(3, 3)
        plan = lower(s, 2)
        with pytest.raises(ValueError, match="round times"):
            replay_round_times(s, plan, [0.1])

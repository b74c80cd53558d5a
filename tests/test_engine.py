"""Device-resident engine tests (DESIGN.md §Engine): ragged CSR
descriptor-table lowering semantics, the write-coloring phase partition
(deterministic checks for all three real families — the randomized
property suite lives in test_engine_properties.py), the grid-walk
QR/Barnes-Hut megakernels against their sequential/rounds oracles, the
single-dispatch runner (incl. whole-plan fusion and per-item timing),
host-dispatch accounting, and the ThreadedExecutor failure-path
regression."""

import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.apps import barneshut as bh
from repro.apps import qr
from repro.core import (FLAG_VIRTUAL, BatchSpec, QSched, ThreadedExecutor,
                        lower)
from repro.pipeline import lower_pipeline_plan
from repro.pipeline.exec import _PipeRunner, dense_stage, mse_loss


def _noop(tid, data):
    pass


def _identity_registry(types, arg_width=1):
    """Trivial device lowering: each task encodes to one row
    ``[type, tid]`` — enough to exercise the table layout."""
    return {tt: BatchSpec(
        run_one=_noop,
        encode=lambda tid, data, tt=tt: [(tt, tid)])
        for tt in types}


class TestDescriptorLowering:
    def _chain_sched(self):
        s = QSched()
        prev = None
        for i in range(3):
            t = s.addtask(type=i % 2, data=i, cost=1.0)
            if prev is not None:
                s.addunlock(prev, t)
            prev = t
        return s

    def test_table_layout_round_structure(self):
        s = self._chain_sched()
        plan = lower(s, 1, cache=False)
        tables = engine.lower_tables(plan, s, _identity_registry((0, 1)),
                                     arg_width=1)
        assert tables.nr_rounds == plan.nr_rounds == 3
        assert tables.nr_items == 3
        assert tables.round_offsets.tolist() == [0, 1, 2, 3]
        assert tables.round_lengths.tolist() == [1, 1, 1]
        # [etype, tid] rows in flat round order
        assert tables.desc.tolist() == [[0, 0], [1, 1], [0, 2]]
        assert tables.tids.tolist() == [0, 1, 2]
        # no row_access: one phase per non-empty round
        assert tables.nr_phases == 3
        assert tables.phase_offsets.tolist() == [0, 1, 2, 3]
        for r in range(3):
            assert tables.round_phases(r).tolist() == [r, r + 1]

    def test_no_padding_anywhere(self):
        """Ragged CSR: a wide and a narrow round share the flat row array
        with zero pad rows (the dense layout would have padded the narrow
        round to width 5)."""
        s = QSched()
        for i in range(5):           # one wide round
            s.addtask(type=0, data=i)
        t = s.addtask(type=0, data=5)
        s.addunlock(0, t)            # plus one narrow round
        plan = lower(s, 1, cache=False)
        tables = engine.lower_tables(plan, s, _identity_registry((0,)),
                                     arg_width=1)
        assert tables.round_lengths.tolist() == [5, 1]
        assert tables.nr_items == 6
        assert tables.desc.shape == (6, 2)
        assert tables.stats["pad_rows"] == 0
        assert tables.stats["pad_fraction"] == 0.0
        assert tables.stats["width"] == 5
        assert tables.stats["padded_rows"] == 10    # what the old slab did

    def test_empty_round_zero_csr_length(self):
        """An all-virtual round lowers to a zero-length CSR slice and zero
        phases — not a synthetic no-op row (satellite regression: the old
        dense layout emitted a full pad round)."""
        s = QSched()
        t0 = s.addtask(type=0, data=0)
        tv = s.addtask(type=7, data="v", flags=FLAG_VIRTUAL)
        t2 = s.addtask(type=0, data=2)
        s.addunlock(t0, tv)
        s.addunlock(tv, t2)
        plan = lower(s, 1, cache=False)
        assert plan.nr_rounds == 3
        tables = engine.lower_tables(plan, s, _identity_registry((0,)),
                                     arg_width=1)
        assert tables.nr_rounds == 3
        assert tables.round_lengths.tolist() == [1, 0, 1]
        assert tables.nr_items == 2
        assert tables.nr_phases == 2          # the empty round has none
        assert tables.round_phases(1).tolist() == [1]
        assert tables.stats["pad_fraction"] == 0.0

    def test_all_virtual_plan_is_empty_table(self):
        s = QSched()
        s.addtask(type=3, data="v", flags=FLAG_VIRTUAL)
        tables = engine.lower_tables(lower(s, 1, cache=False), s, {},
                                     arg_width=1)
        assert tables.nr_rounds == 1
        assert tables.nr_items == 0
        assert tables.nr_phases == 0
        assert tables.desc.shape == (0, 2)

    def test_row_order_mirrors_execute(self):
        """Rows within a round follow ascending task type then batch
        order — the host rounds-mode dispatch order."""
        s = QSched()
        for i in range(3):
            s.addtask(type=2, data=i)
        for i in range(2):
            s.addtask(type=1, data=i)
        plan = lower(s, 1, cache=False)
        tables = engine.lower_tables(plan, s, _identity_registry((1, 2)),
                                     arg_width=1)
        assert tables.desc[:, 0].tolist() == [1, 1, 2, 2, 2]

    def test_virtual_tasks_encode_to_nothing(self):
        s = QSched()
        s.addtask(type=0, data="a")
        s.addtask(type=5, data="v", flags=FLAG_VIRTUAL)
        plan = lower(s, 1, cache=False)
        tables = engine.lower_tables(plan, s, _identity_registry((0,)),
                                     arg_width=1)
        assert tables.nr_items == 1
        assert tables.round_tids(0) == [0]

    def test_task_may_expand_to_many_rows(self):
        s = QSched()
        s.addtask(type=0, data=3)
        reg = {0: BatchSpec(
            run_one=_noop,
            encode=lambda tid, data: [(0, k) for k in range(data)])}
        tables = engine.lower_tables(lower(s, 1, cache=False), s, reg,
                                     arg_width=1)
        assert tables.nr_items == 3
        assert tables.tids.tolist() == [0, 0, 0]

    def test_missing_encode_raises(self):
        s = QSched()
        s.addtask(type=0)
        plan = lower(s, 1, cache=False)
        with pytest.raises(KeyError, match="no BatchSpec"):
            engine.lower_tables(plan, s, {}, arg_width=1)
        with pytest.raises(KeyError, match="no engine "):
            engine.lower_tables(plan, s, {0: BatchSpec(run_one=_noop)},
                                arg_width=1)

    def test_overwide_row_raises(self):
        s = QSched()
        s.addtask(type=0)
        reg = {0: BatchSpec(run_one=_noop,
                            encode=lambda tid, data: [(0, 1, 2, 3)])}
        with pytest.raises(ValueError, match="columns"):
            engine.lower_tables(lower(s, 1, cache=False), s, reg,
                                arg_width=1)

    def test_structurally_different_sched_rejected(self):
        s1, _ = qr.make_qr_graph(4, 4)
        s2, _ = qr.make_qr_graph(5, 5)
        plan = lower(s1, 2)
        with pytest.raises(ValueError):
            engine.lower_tables(plan, s2, _identity_registry(range(4)),
                                arg_width=1)


class TestWriteColoring:
    """The phase partition (core.plan.color_phases through
    descriptors.lower_tables): phases are contiguous, cover each round
    exactly, no two items of a phase touch a common state row, and items
    sharing a destination keep their order (so accumulation bit patterns
    match the sequential walk)."""

    def _colliding_table(self):
        """One round of 4 independent tasks; tasks 0/2 write key 7, tasks
        1/3 write keys 1/3 — the coloring must split 0 and 2."""
        s = QSched()
        for i, key in enumerate((7, 1, 7, 3)):
            s.addtask(type=0, data=key)
        reg = {0: BatchSpec(run_one=_noop,
                            encode=lambda tid, data: [(0, data)])}
        tables = engine.lower_tables(
            lower(s, 1, cache=False), s, reg, arg_width=1,
            row_access=lambda row: ((), (("k", row[1]),)))
        return tables

    def test_same_destination_rows_split_phases(self):
        tables = self._colliding_table()
        assert tables.nr_rounds == 1
        assert tables.nr_phases == 2
        bounds = tables.round_phases(0).tolist()
        phases = [set(map(tuple, tables.desc[b0:b1].tolist()))
                  for b0, b1 in zip(bounds, bounds[1:])]
        for ph in phases:
            keys = [r[1] for r in ph]
            assert len(keys) == len(set(keys)), "write key repeated in phase"
        # per-destination order: the first key-7 row precedes the second
        key7 = [q for q in range(tables.nr_items)
                if tables.desc[q, 1] == 7]
        assert tables.tids[key7].tolist() == [0, 2]

    @staticmethod
    def assert_phases_safe(tables, row_access):
        """No two items of one sub-phase read or write a common state
        row — the invariant that makes the block grid walk of a phase
        order-independent (and parallelizable)."""
        assert tables.phase_offsets[0] == 0
        assert tables.phase_offsets[-1] == tables.nr_items
        assert (np.diff(tables.phase_offsets) > 0).all()
        for r in range(tables.nr_rounds):
            bounds = tables.round_phases(r).tolist()
            assert bounds[0] == tables.round_offsets[r]
            assert bounds[-1] == tables.round_offsets[r + 1]
            for b0, b1 in zip(bounds, bounds[1:]):
                reads, writes = set(), set()
                for q in range(b0, b1):
                    row = tuple(int(v) for v in tables.desc[q])
                    rr, ww = row_access(row)
                    rr, ww = set(rr), set(ww)
                    assert not (ww & writes), \
                        f"round {r}: write/write overlap in one phase"
                    assert not (ww & reads) and not (rr & writes), \
                        f"round {r}: read/write overlap in one phase"
                    reads |= rr
                    writes |= ww

    def test_qr_family_phases_safe(self):
        s, _ = qr.make_qr_graph(5, 5)
        plan = lower(s, 4)
        tiles = {(i, j): jnp.zeros((4, 4), jnp.float32)
                 for i in range(5) for j in range(5)}
        tables = engine.lower_tables(
            plan, s, qr._TileState(tiles, "ref").batch_registry(),
            arg_width=engine.QR_ARG_WIDTH, row_access=engine.qr_row_access)
        self.assert_phases_safe(tables, engine.qr_row_access)

    def test_bh_family_phases_safe(self):
        rng = np.random.default_rng(5)
        x, m = rng.random((600, 3)), rng.random(600) + 0.5
        tree = bh.Octree(x, m, n_max=32)
        g = bh.build_graph(tree, n_task=128, nr_queues=2)
        st = bh.BHState(g, backend="ref")
        tables = engine.lower_tables(
            lower(g.sched, 2), g.sched, st.batch_registry(),
            arg_width=engine.BH_ARG_WIDTH, row_access=engine.bh_row_access)
        # BH tasks expand into many same-destination accumulation rows —
        # the coloring must actually split (this is the interesting case)
        assert tables.nr_phases > tables.nr_rounds
        self.assert_phases_safe(tables, engine.bh_row_access)

    def test_pipeline_family_phases_safe(self):
        S, M, Bt, D = 3, 5, 2, 4
        params = [{"w": jnp.zeros((D, D)), "b": jnp.zeros((D,))}
                  for _ in range(S)]
        micro = [{"x": jnp.zeros((Bt, D)), "y": jnp.zeros((Bt, D))}
                 for _ in range(M)]
        runner = _PipeRunner([dense_stage] * S, mse_loss, params, micro)
        sched, _, plan = lower_pipeline_plan(S, M, per_stage_window=True)
        tables = engine.lower_tables(
            plan, sched, runner.registry(),
            arg_width=engine.PIPE_ARG_WIDTH,
            row_access=engine.pipe_row_access)
        self.assert_phases_safe(tables, engine.pipe_row_access)


class TestHostDispatchCount:
    def test_counts_batches_and_singles(self):
        s = QSched()
        for i in range(4):
            s.addtask(type=0, data=i)    # one batched group → 1 dispatch
        for i in range(2):
            s.addtask(type=1, data=i)    # run_one only → 2 dispatches
        plan = lower(s, 1, cache=False)
        reg = {0: BatchSpec(run_one=_noop, run_batch=lambda t, d: None),
               1: BatchSpec(run_one=_noop)}
        assert engine.count_host_dispatches(plan, s, reg) == 3

    def test_qr_dispatch_reduction_floor(self):
        """Acceptance gate: the engine's single dispatch is ≥5× fewer than
        the per-round host path on a smoke-size QR plan."""
        a = jnp.zeros((128, 128), jnp.float32)
        tiles, mt, nt = qr._split_tiles(a, 32)
        s, _ = qr.make_qr_graph(mt, nt)
        plan = lower(s, 4)
        state = qr._TileState(tiles, "ref")
        host = engine.count_host_dispatches(plan, s, state.batch_registry())
        assert host >= 5 * engine.ENGINE_DISPATCHES_PER_PLAN


class TestQREngine:
    # NOTE: engine-vs-sequential equivalence is asserted (bitwise, across
    # every backend) by the matrix in tests/test_backends.py.
    def test_engine_rectangular_grid(self):
        """mt ≠ nt exercises the column-major tile-index arithmetic."""
        a = jnp.asarray(
            np.random.default_rng(1).standard_normal((160, 96)), jnp.float32)
        r1, _ = qr.run_qr(a, tile=32, mode="sequential", backend="pallas")
        r2, _ = qr.run_qr(a, tile=32, mode="engine")
        np.testing.assert_allclose(np.asarray(r1), np.asarray(r2),
                                   atol=1e-5)

    def test_fused_plan_matches_per_round(self):
        """Whole-plan fusion (one megakernel launch walking every phase)
        is bitwise equivalent to the per-round launch loop."""
        a = jnp.asarray(
            np.random.default_rng(2).standard_normal((96, 96)), jnp.float32)
        tiles, mt, nt = qr._split_tiles(a, 32)
        s, _ = qr.make_qr_graph(mt, nt)
        plan = lower(s, 4)
        state = qr._TileState(tiles, "pallas")
        tables = engine.lower_tables(
            plan, s, state.batch_registry(),
            arg_width=engine.QR_ARG_WIDTH, row_access=engine.qr_row_access)
        stack = qr._stack_tiles(a, 32)
        tmat = jnp.zeros_like(stack)
        # donate=False: the same buffers are deliberately reused across
        # the two calls (donation would delete them on TPU/GPU)
        out1, _ = engine.execute_plan(tables, engine.qr_round_fn(), (),
                                      (stack, tmat), donate=False)
        out2, _ = engine.execute_plan(tables, engine.qr_round_fn(), (),
                                      (stack, tmat), fuse_rounds=True,
                                      donate=False)
        np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))

    def test_block_size_does_not_change_result(self):
        """The block grid is a pure execution-shape choice: 1-item blocks
        and whole-phase blocks produce bitwise-identical tiles."""
        a = jnp.asarray(
            np.random.default_rng(3).standard_normal((96, 96)), jnp.float32)
        tiles, mt, nt = qr._split_tiles(a, 32)
        s, _ = qr.make_qr_graph(mt, nt)
        plan = lower(s, 4)
        state = qr._TileState(tiles, "pallas")
        tables = engine.lower_tables(
            plan, s, state.batch_registry(),
            arg_width=engine.QR_ARG_WIDTH, row_access=engine.qr_row_access)
        stack = qr._stack_tiles(a, 32)
        outs = []
        for bi in (1, 64):
            fn = engine.qr_round_fn(block_items=bi)
            out, _ = engine.execute_plan(tables, fn, (),
                                         (stack, jnp.zeros_like(stack)),
                                         fuse_rounds=True, donate=False)
            outs.append(np.asarray(out))
        np.testing.assert_array_equal(outs[0], outs[1])


class TestBHEngine:
    # NOTE: engine-vs-sequential/rounds acceleration equivalence is
    # asserted across every backend by the matrix in tests/test_backends.py.
    def test_engine_coms_match_sequential(self):
        """The in-kernel COM reduction (leaf blocks + one-hot child
        gathers) reproduces the host COM pass."""
        rng = np.random.default_rng(7)
        x, m = rng.random((400, 3)), rng.random(400) + 0.5
        _, st_seq, g = bh.solve(x, m, n_max=32, n_task=128, backend="ref",
                                mode="sequential")
        st_eng = bh.BHState(g, backend="ref")
        st_eng.run(mode="engine")
        for cid in range(len(g.tree.cells)):
            np.testing.assert_allclose(
                np.asarray(st_eng.com[cid]), np.asarray(st_seq.com[cid]),
                rtol=1e-5, atol=1e-6)


class TestThreadedExecutorFailure:
    """Regression (satellite): a worker exception must re-raise out of
    ``run`` promptly — before the abort flag, the surviving workers spun on
    the never-draining ``waiting`` counter and ``join`` hung forever, so
    failures passed silently (or rather, hung) instead of raising."""

    def _run_with_watchdog(self, exc_type, fn):
        box = {}

        def target():
            try:
                fn()
                box["outcome"] = None
            except BaseException as e:        # noqa: BLE001 - test capture
                box["outcome"] = e

        th = threading.Thread(target=target, daemon=True)
        th.start()
        th.join(timeout=30.0)
        assert not th.is_alive(), "ThreadedExecutor.run hung on failure"
        assert isinstance(box["outcome"], exc_type), box["outcome"]
        return box["outcome"]

    def test_worker_exception_reraises(self):
        s = QSched(nr_queues=2)
        for i in range(50):
            s.addtask(data=i)

        def fun(ttype, data):
            if data == 17:
                raise ValueError("task 17 exploded")

        ex = ThreadedExecutor(s, nr_threads=4)
        err = self._run_with_watchdog(ValueError, lambda: ex.run(fun))
        assert "task 17 exploded" in str(err)
        assert ex.errors and ex.errors[0] is err

    def test_exception_in_dependent_chain(self):
        """Failure mid-graph (dependents still waiting) must also unblock
        the pool."""
        s = QSched(nr_queues=2)
        prev = None
        for i in range(10):
            t = s.addtask(data=i)
            if prev is not None:
                s.addunlock(prev, t)
            prev = t

        def fun(ttype, data):
            if data == 3:
                raise RuntimeError("chain broke")

        ex = ThreadedExecutor(s, nr_threads=3)
        self._run_with_watchdog(RuntimeError, lambda: ex.run(fun))

    def test_errors_cleared_between_runs(self):
        s = QSched()
        for i in range(5):
            s.addtask(data=i)
        ex = ThreadedExecutor(s, nr_threads=2)
        with pytest.raises(ValueError):
            ex.run(lambda ty, d: (_ for _ in ()).throw(ValueError("x")))
        ex.run(lambda ty, d: None)       # second run succeeds cleanly
        assert ex.errors == []

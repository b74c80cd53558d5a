"""Tiled-QR application tests (paper §4.1): task-graph structure, numerical
correctness through the QuickSched executors, schedule properties."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.apps import qr
from repro.core import conflict_rounds, simulate, validate_rounds


def rand_matrix(n, seed=0):
    return jnp.asarray(np.random.default_rng(seed).standard_normal((n, n)),
                       dtype=jnp.float32)


class TestGraphStructure:
    def test_paper_task_and_resource_counts(self):
        """2048² matrix, 64² tiles → 32×32 grid: the paper reports 11 440
        tasks, 1 024 resources, 21 856 locks, 11 408 uses."""
        c = qr.paper_counts(32, 32)
        assert c["tasks"] == 11440
        assert c["resources"] == 1024
        assert c["locks"] == 21856
        assert c["uses"] == 11408
        # The paper reports 21 824 dependencies; the fully-deterministic
        # table structure (which we implement) carries the per-tile
        # previous-level chains explicitly:
        assert c["deps"] == 32240

    def test_task_type_counts(self):
        s, _ = qr.make_qr_graph(32, 32)
        by_type = {}
        for t in s.tasks:
            by_type[t.type] = by_type.get(t.type, 0) + 1
        assert by_type[qr.T_GEQRF] == 32
        assert by_type[qr.T_LARFT] == 496
        assert by_type[qr.T_TSQRF] == 496
        assert by_type[qr.T_SSRFT] == 10416

    def test_geqrf_on_critical_path(self):
        """Paper: 'the DGEQRF tasks all lie on the longest critical path'.
        Each DGEQRF must have the maximum weight among ready tasks at its
        level."""
        s, _ = qr.make_qr_graph(8, 8)
        s.prepare()
        w = {t.tid: t.weight for t in s.tasks}
        geqrf = [t for t in s.tasks if t.type == qr.T_GEQRF]
        # DGEQRF(k) weight decreases with k and dominates its level
        ws = [t.weight for t in sorted(geqrf, key=lambda t: t.data[2])]
        assert all(a > b for a, b in zip(ws, ws[1:]))
        top = max(w.values())
        assert ws[0] == top, "DGEQRF(0) must head the critical path"

    def test_rounds_valid(self):
        s, _ = qr.make_qr_graph(6, 6)
        rounds = conflict_rounds(s, nr_lanes=8)
        validate_rounds(s, rounds)


class TestNumerics:
    @pytest.mark.parametrize("mode", ["sequential", "rounds"])
    @pytest.mark.parametrize("backend", ["ref", "pallas"])
    def test_qr_correct(self, mode, backend):
        n, b = 96, 32
        a = rand_matrix(n)
        r, _ = qr.run_qr(a, tile=b, mode=mode, backend=backend, nr_queues=4)
        r = np.asarray(r)
        # R is upper triangular
        assert np.abs(np.tril(r, -1)).max() < 1e-4
        # Cholesky identity R^T R == A^T A
        lhs, rhs = r.T @ r, np.asarray(a).T @ np.asarray(a)
        assert np.abs(lhs - rhs).max() / np.abs(rhs).max() < 1e-4

    def test_qr_matches_lapack_up_to_signs(self):
        n, b = 64, 16
        a = rand_matrix(n, seed=3)
        r, _ = qr.run_qr(a, tile=b, mode="sequential", backend="ref")
        r = np.asarray(r)
        r_ref = np.asarray(jnp.linalg.qr(a, mode="r"))
        sign = np.sign(np.diag(r)) * np.sign(np.diag(r_ref))
        assert_allclose(r * sign[:, None], r_ref, atol=2e-3)

    def test_threaded_qr_correct(self):
        """The pthread-pool analogue with real locks must produce a valid R
        (exercises conflict exclusion on the diagonal/row tiles)."""
        n, b = 64, 16
        a = rand_matrix(n, seed=9)
        r, _ = qr.run_qr(a, tile=b, mode="threaded", backend="ref",
                         nr_queues=4)
        r = np.asarray(r)
        rhs = np.asarray(a).T @ np.asarray(a)
        assert np.abs(r.T @ r - rhs).max() / np.abs(rhs).max() < 1e-4

    def test_jit_traced_schedule(self):
        """The sequential executor traces into a single jitted program."""
        n, b = 64, 16
        a = rand_matrix(n, seed=11)

        @jax.jit
        def qr_program(x):
            r, _ = qr.run_qr(x, tile=b, mode="sequential", backend="ref")
            return r

        r = np.asarray(qr_program(a))
        rhs = np.asarray(a).T @ np.asarray(a)
        assert np.abs(r.T @ r - rhs).max() / np.abs(rhs).max() < 1e-4


GRIDS = [pytest.param((160, 96), id="rect-5x3"),
         pytest.param((96, 96), id="square-3x3")]


class TestTileStack:
    """The engine's tile state is one (mt·nt, b, b) array from split to R;
    these guard that it is exactly the per-tile dict's data."""

    @pytest.mark.parametrize("shape", GRIDS)
    def test_stack_puts_tile_ij_at_column_major_index(self, shape):
        a = jnp.asarray(np.random.default_rng(4).standard_normal(shape),
                        jnp.float32)
        tiles, mt, nt = qr._split_tiles(a, 32)
        stack = np.asarray(qr._stack_tiles(a, 32))
        assert stack.shape == (mt * nt, 32, 32)
        for (i, j), t in tiles.items():
            np.testing.assert_array_equal(stack[j * mt + i], np.asarray(t))

    @pytest.mark.parametrize("shape", GRIDS)
    def test_unstack_round_trips_to_assembled_r(self, shape):
        a = jnp.asarray(np.random.default_rng(5).standard_normal(shape),
                        jnp.float32)
        tiles, mt, nt = qr._split_tiles(a, 32)
        r = np.asarray(qr._unstack_r(qr._stack_tiles(a, 32), mt, nt))
        np.testing.assert_array_equal(r, np.triu(np.asarray(a)))
        np.testing.assert_array_equal(
            r, np.asarray(qr._assemble_r(tiles, mt, nt, 32, a.dtype)))

    @pytest.mark.parametrize("shape", GRIDS)
    def test_engine_r_matches_per_tile_engine_path_bitwise(self, shape):
        """The stacked engine path gives the R of the per-tile glue it
        replaced: the megakernel's output scattered back into a tile dict
        and assembled tile by tile."""
        from repro import engine
        from repro.core import lower
        a = jnp.asarray(np.random.default_rng(6).standard_normal(shape),
                        jnp.float32)
        r, sched = qr.run_qr(a, tile=32, mode="engine")
        tiles, mt, nt = qr._split_tiles(a, 32)
        state = qr._TileState(tiles, "pallas")
        tables = engine.lower_tables(
            lower(sched, 1), sched, state.batch_registry(),
            arg_width=engine.QR_ARG_WIDTH, row_access=engine.qr_row_access)
        stack = qr._stack_tiles(a, 32)
        out, _ = engine.execute_plan(tables, engine.qr_round_fn(), (),
                                     (stack, jnp.zeros_like(stack)),
                                     donate=False)
        per_tile = {(i, j): out[j * mt + i]
                    for i in range(mt) for j in range(nt)}
        np.testing.assert_array_equal(
            np.asarray(r),
            np.asarray(qr._assemble_r(per_tile, mt, nt, 32, a.dtype)))


class TestScaling:
    def test_simulated_strong_scaling(self):
        """Scheduler-limited efficiency on the paper's 32×32 grid should be
        high at 64 workers (paper: 73% incl. hardware effects)."""
        def make(n):
            s, _ = qr.make_qr_graph(32, 32, nr_queues=n)
            return s
        r1 = simulate(make(1), 1)
        r64 = simulate(make(64), 64)
        eff = r1.makespan / (64 * r64.makespan)
        assert eff > 0.70, f"64-worker efficiency {eff:.2f} below paper's 73%"

    def test_schedule_validates(self):
        s, _ = qr.make_qr_graph(12, 12, nr_queues=8)
        res = simulate(s, 8)
        s.validate_schedule(res.timeline)

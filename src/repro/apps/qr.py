"""Task-based tiled QR decomposition (paper §4.1, Buttari et al. 2009).

Four task types on an ``mt × nt`` grid of (b,b) tiles, ``min(mt,nt)``
levels.  Dependency structure follows the paper's §4.1 table (the fully
deterministic variant — see EXPERIMENTS.md for the dependency-count
analysis vs the paper's reported numbers):

  | task    | where        | depends on                          | locks        | uses          |
  | DGEQRF  | i=j=k        | (i,j,k-1)                           | (k,k)        |               |
  | DLARFT  | i=k, j>k     | (i,j,k-1), (k,k,k)                  |              | (k,k), (k,j)  |
  | DTSQRF  | i>k, j=k     | (i,j,k-1), (i-1,j,k)                | (i,k), (k,k) |               |
  | DSSRFT  | i>k, j>k     | (i,j,k-1), (i-1,j,k), (i,k,k)       | (i,j), (k,j) | (i,k)         |

Tiles are resources (for affinity; the paper: "we still model each tile as
a separate resource such that the scheduler can preferentially assign tasks
using the same tiles to the same thread"), initially assigned to queues in
column-major order.

``make_qr_graph`` emits the whole level-k slab of tasks/deps/locks/uses as
numpy index arrays through the scheduler's bulk API (``addtasks`` /
``addunlocks`` / …) — the per-call reference builder it replaced is kept as
``make_qr_graph_loop`` and the two are asserted stream-identical in
``tests/test_plan.py``.

Execution modes (all dispatched through the core backend registry,
``core/backends.py`` — this module contains no mode branching):
  * ``sequential`` — SequentialExecutor drains the scheduler in priority
    order while tracing the tile kernels; wrap in ``jax.jit`` for a single
    XLA program ordered by the QuickSched schedule.
  * ``rounds``     — the shared ExecutionPlan lowering: conflict-free
    rounds whose same-type task groups are *batched with vmap* over stacked
    tiles via the BatchSpec registry.  On TPU each round is one SPMD step
    and the vmap becomes the kernel grid.
  * ``engine``     — the device-resident engine (DESIGN.md §Engine): the
    plan is lowered to descriptor task tables and the whole factorization
    executes as ONE jitted dispatch of fused type-branching Pallas rounds
    over a (ntiles, b, b) tile stack (``backend`` is ignored — the
    megakernel *is* the Pallas path, interpreted on CPU).
  * ``threaded``   — the paper's pthread pool over numpy tiles (host).
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import engine
from repro.core import (BatchSpec, EngineHooks, QSched, get_backend, lower,
                        run_plan)
from repro.kernels.qr_tile import ops
from repro.obs import metrics as _metrics
from repro.obs import trace as _trace

T_GEQRF, T_LARFT, T_TSQRF, T_SSRFT = range(4)
TASK_NAMES = {T_GEQRF: "DGEQRF", T_LARFT: "DLARFT",
              T_TSQRF: "DTSQRF", T_SSRFT: "DSSRFT"}
# relative costs from the paper's Fig 14 addtask calls
COSTS = {T_GEQRF: 2.0, T_LARFT: 3.0, T_TSQRF: 3.0, T_SSRFT: 5.0}


def _add_resources(s: QSched, mt: int, nt: int,
                   nr_queues: int) -> Dict[Tuple[int, int], int]:
    ntiles = mt * nt
    rid: Dict[Tuple[int, int], int] = {}
    for j in range(nt):          # column-major initial queue assignment
        for i in range(mt):
            owner = (j * mt + i) * nr_queues // ntiles
            rid[i, j] = s.addres(owner=owner)
    return rid


def make_qr_graph(mt: int, nt: int, nr_queues: int = 1,
                  reown: bool = True) -> Tuple[QSched, Dict[Tuple[int, int], int]]:
    """Build the QuickSched graph for an mt×nt tile grid, one vectorized
    level-k slab at a time (identical id/edge streams to the per-call
    reference ``make_qr_graph_loop``)."""
    s = QSched(nr_queues=nr_queues, reown=reown)
    rid = _add_resources(s, mt, nt, nr_queues)
    # tile (i,j) -> resource id, column-major creation order
    last = np.full((mt, nt), -1, dtype=np.int64)   # tid grid, prev level

    def res(i, j):               # rid[i, j] as index arithmetic
        return j * mt + i

    for k in range(min(mt, nt)):
        nk = nt - k - 1          # DLARFT count (j = k+1..nt-1)
        mk = mt - k - 1          # DTSQRF count (i = k+1..mt-1)
        base = s.nr_tasks
        js = np.arange(k + 1, nt, dtype=np.int64)
        is_ = np.arange(k + 1, mt, dtype=np.int64)
        g_tid = base
        larft = base + 1 + np.arange(nk, dtype=np.int64)
        blk = base + 1 + nk + np.arange(mk, dtype=np.int64)[:, None] * (1 + nk)
        tsqrf = blk[:, 0]                                    # (mk,)
        ssrft = blk + 1 + np.arange(nk, dtype=np.int64)[None, :]   # (mk, nk)

        # tasks, creation order: GEQRF, LARFTs, then per i: TSQRF + SSRFTs
        types = ([T_GEQRF] + [T_LARFT] * nk
                 + ([T_TSQRF] + [T_SSRFT] * nk) * mk)
        costv = ([COSTS[T_GEQRF]] + [COSTS[T_LARFT]] * nk
                 + ([COSTS[T_TSQRF]] + [COSTS[T_SSRFT]] * nk) * mk)
        js_l = js.tolist()
        datas = ([(k, k, k)] + [(k, j, k) for j in js_l]
                 + [d for i in range(k + 1, mt)
                    for d in [(i, k, k)] + [(i, j, k) for j in js_l]])
        s.addtasks(types, costv, datas)

        # dependencies, creation order
        dep_src, dep_dst = [], []
        if k > 0:
            dep_src.append(np.asarray([last[k, k]]))
            dep_dst.append(np.asarray([g_tid]))
        if nk:
            if k > 0:            # per j: (GEQRF, larft_j), (last[k,j], larft_j)
                dep_src.append(np.stack(
                    [np.full(nk, g_tid, np.int64), last[k, k + 1:]],
                    axis=1).ravel())
                dep_dst.append(np.repeat(larft, 2))
            else:
                dep_src.append(np.full(nk, g_tid, np.int64))
                dep_dst.append(larft)
        if mk:
            prev_col0 = np.concatenate(([g_tid], tsqrf[:-1]))  # cur[i-1, k]
            prev_row = (np.vstack([larft[None, :], ssrft[:-1]])
                        if nk else np.empty((mk, 0), np.int64))  # cur[i-1, j]
            if k > 0:
                # per i: [(cur[i-1,k], t), (last[i,k], t)]
                #        + per j [(tsqrf_i, s), (cur[i-1,j], s), (last[i,j], s)]
                a_src = np.stack([prev_col0, last[k + 1:, k]], axis=1)
                b_src = np.stack([np.broadcast_to(tsqrf[:, None], (mk, nk)),
                                  prev_row, last[k + 1:, k + 1:]], axis=2)
                dep_src.append(np.concatenate(
                    [a_src, b_src.reshape(mk, -1)], axis=1).ravel())
                a_dst = np.stack([tsqrf, tsqrf], axis=1)
                dep_dst.append(np.concatenate(
                    [a_dst, np.repeat(ssrft, 3, axis=1)], axis=1).ravel())
            else:
                a_src = prev_col0[:, None]
                b_src = np.stack([np.broadcast_to(tsqrf[:, None], (mk, nk)),
                                  prev_row], axis=2)
                dep_src.append(np.concatenate(
                    [a_src, b_src.reshape(mk, -1)], axis=1).ravel())
                dep_dst.append(np.concatenate(
                    [tsqrf[:, None], np.repeat(ssrft, 2, axis=1)],
                    axis=1).ravel())
        if dep_src:
            s.addunlocks(np.concatenate(dep_src), np.concatenate(dep_dst))

        # locks: (GEQRF, (k,k)); per i: (t, (i,k)), (t, (k,k));
        #        per j: (s, (i,j)), (s, (k,j))
        lock_t = [np.asarray([g_tid])]
        lock_r = [np.asarray([res(k, k)])]
        if mk:
            a_t = np.stack([tsqrf, tsqrf], axis=1)
            a_r = np.stack([res(is_, k), np.full(mk, res(k, k), np.int64)],
                           axis=1)
            b_t = np.repeat(ssrft, 2, axis=1)
            b_r = np.stack([res(is_[:, None], js[None, :]),
                            np.broadcast_to(res(k, js)[None, :], (mk, nk))],
                           axis=2).reshape(mk, -1)
            lock_t.append(np.concatenate([a_t, b_t], axis=1).ravel())
            lock_r.append(np.concatenate([a_r, b_r], axis=1).ravel())
        s.addlocks(np.concatenate(lock_t), np.concatenate(lock_r))

        # uses: per j: (larft_j, (k,k)), (larft_j, (k,j));
        #       per i,j: (ssrft_ij, (i,k))
        if nk:
            use_t = [np.repeat(larft, 2)]
            use_r = [np.stack([np.full(nk, res(k, k), np.int64), res(k, js)],
                              axis=1).ravel()]
            if mk:
                use_t.append(ssrft.ravel())
                use_r.append(np.repeat(res(is_, k), nk))
            s.adduses(np.concatenate(use_t), np.concatenate(use_r))

        # fold this level's tids into the grid for level k+1
        last[k, k] = g_tid
        if nk:
            last[k, k + 1:] = larft
        if mk:
            last[k + 1:, k] = tsqrf
            if nk:
                last[k + 1:, k + 1:] = ssrft
    return s, rid


def make_qr_graph_loop(mt: int, nt: int, nr_queues: int = 1,
                       reown: bool = True) -> Tuple[QSched, Dict[Tuple[int, int], int]]:
    """Reference per-call builder (paper Fig 14 shape) — kept as the oracle
    for the vectorized ``make_qr_graph`` (asserted stream-identical in
    tests) and as readable documentation of the dependency table."""
    s = QSched(nr_queues=nr_queues, reown=reown)
    rid = _add_resources(s, mt, nt, nr_queues)
    tid: Dict[Tuple[int, int], int] = {}
    for k in range(min(mt, nt)):
        t = s.addtask(T_GEQRF, data=(k, k, k), cost=COSTS[T_GEQRF])
        s.addlock(t, rid[k, k])
        if (k, k) in tid:
            s.addunlock(tid[k, k], t)
        tid[k, k] = t
        for j in range(k + 1, nt):
            t = s.addtask(T_LARFT, data=(k, j, k), cost=COSTS[T_LARFT])
            s.adduse(t, rid[k, k])
            s.adduse(t, rid[k, j])
            s.addunlock(tid[k, k], t)
            if (k, j) in tid:
                s.addunlock(tid[k, j], t)
            tid[k, j] = t
        for i in range(k + 1, mt):
            t = s.addtask(T_TSQRF, data=(i, k, k), cost=COSTS[T_TSQRF])
            s.addlock(t, rid[i, k])
            s.addlock(t, rid[k, k])
            s.addunlock(tid[i - 1, k], t)   # chain: serializes R_kk updates
            if (i, k) in tid:
                s.addunlock(tid[i, k], t)
            tid[i, k] = t
            for j in range(k + 1, nt):
                t = s.addtask(T_SSRFT, data=(i, j, k), cost=COSTS[T_SSRFT])
                s.addlock(t, rid[i, j])
                s.addlock(t, rid[k, j])
                s.adduse(t, rid[i, k])
                s.addunlock(tid[i, k], t)       # the DTSQRF whose V2 we apply
                s.addunlock(tid[i - 1, j], t)   # chain: row-k tile update order
                if (i, j) in tid:
                    s.addunlock(tid[i, j], t)
                tid[i, j] = t
    return s, rid


# ----------------------------------------------------------------------------
# numerical execution over tiles
# ----------------------------------------------------------------------------

def _split_tiles(a: jnp.ndarray, b: int):
    m, n = a.shape
    mt, nt = m // b, n // b
    return {(i, j): a[i * b:(i + 1) * b, j * b:(j + 1) * b]
            for i in range(mt) for j in range(nt)}, mt, nt


def _assemble_r(tiles, mt, nt, b, dtype):
    rows = []
    for i in range(mt):
        cols = []
        for j in range(nt):
            if i < j:
                cols.append(tiles[i, j])
            elif i == j:
                cols.append(jnp.triu(tiles[i, j]))
            else:
                cols.append(jnp.zeros((b, b), dtype))
        rows.append(jnp.concatenate(cols, axis=1))
    return jnp.concatenate(rows, axis=0)


@functools.partial(jax.jit, static_argnums=1)
def _stack_tiles(a: jnp.ndarray, b: int) -> jnp.ndarray:
    """``a``'s (b, b) tiles as one (mt·nt, b, b) array, tile (i, j) at
    index j·mt + i — the column-major order of the resource ids and of the
    megakernel's tile arguments.  Rows and columns past the last whole
    tile are dropped, as in ``_split_tiles``."""
    mt, nt = a.shape[0] // b, a.shape[1] // b
    return (a[:mt * b, :nt * b].reshape(mt, b, nt, b)
            .transpose(2, 0, 1, 3).reshape(nt * mt, b, b))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _unstack_r(stack: jnp.ndarray, mt: int, nt: int) -> jnp.ndarray:
    """R from the factored tile stack: the inverse of ``_stack_tiles``,
    then one ``triu`` of the whole matrix.  Equal to ``_assemble_r``:
    tiles above the tile diagonal lie wholly above the matrix diagonal,
    tiles below it wholly below, and a diagonal tile's ``triu`` is its
    part of the matrix's."""
    b = stack.shape[-1]
    return jnp.triu(stack.reshape(nt, mt, b, b).transpose(1, 2, 0, 3)
                    .reshape(mt * b, nt * b))


class _TileState:
    """QR state of the host modes: a dict of (b, b) tiles that the task
    bodies replace one tile at a time."""

    def __init__(self, tiles, backend):
        self.tiles = tiles
        self.t_diag = {}
        self.t_ts = {}
        self.backend = backend
        self.mt = 1 + max(i for i, _ in tiles)
        self.nt = 1 + max(j for _, j in tiles)

    def exec_task(self, ttype, data):
        i, j, k = data
        tl, be = self.tiles, self.backend
        if ttype == T_GEQRF:
            rv, tau, t = ops.geqrf(tl[k, k], backend=be)
            tl[k, k] = rv
            self.t_diag[k] = t
        elif ttype == T_LARFT:
            tl[k, j] = ops.apply_qt(tl[k, k], self.t_diag[k], tl[k, j],
                                    backend=be)
        elif ttype == T_TSQRF:
            r, v2, tau, t = ops.tsqrf(jnp.triu(tl[k, k]), tl[i, k], backend=be)
            tl[k, k] = jnp.triu(r) + jnp.tril(tl[k, k], -1)  # keep V below
            tl[i, k] = v2
            self.t_ts[i, k] = t
        elif ttype == T_SSRFT:
            c1, c2 = ops.apply_tsqt(tl[i, k], self.t_ts[i, k],
                                    tl[k, j], tl[i, j], backend=be)
            tl[k, j] = c1
            tl[i, j] = c2
        else:
            raise ValueError(f"unknown task type {ttype}")

    def batch_registry(self):
        """BatchSpecs for the ExecutionPlan: LARFT/SSRFT groups stack their
        tiles and run one vmapped kernel; GEQRF is singular per round and
        TSQRF batches would mix conflicting same-column updates, so both
        stay per-task.  Each spec also carries its engine ``encode`` — the
        descriptor-row lowering the ``engine`` mode ships to the fused
        megakernel (task types map to themselves; args are column-major
        tile indices, DESIGN.md §Engine)."""
        tl, be = self.tiles, self.backend

        def larft_batch(tids, datas):
            kk = jnp.stack([tl[k, k] for (k, j, _) in datas])
            tt = jnp.stack([self.t_diag[k] for (k, j, _) in datas])
            cc = jnp.stack([tl[k, j] for (k, j, _) in datas])
            out = jax.vmap(
                lambda a, b, c: ops.apply_qt(a, b, c, backend=be))(kk, tt, cc)
            for (k, j, _), o in zip(datas, out):
                tl[k, j] = o

        def ssrft_batch(tids, datas):
            v2 = jnp.stack([tl[i, k] for (i, j, k) in datas])
            tt = jnp.stack([self.t_ts[i, k] for (i, j, k) in datas])
            c1 = jnp.stack([tl[k, j] for (i, j, k) in datas])
            c2 = jnp.stack([tl[i, j] for (i, j, k) in datas])
            o1, o2 = jax.vmap(lambda a, b, c, d: ops.apply_tsqt(
                a, b, c, d, backend=be))(v2, tt, c1, c2)
            for (i, j, k), x1, x2 in zip(datas, o1, o2):
                tl[k, j] = x1
                tl[i, j] = x2

        def one(ttype):
            return lambda tid, d: self.exec_task(ttype, d)

        mt = self.mt

        def res(i, j):
            return j * mt + i

        def enc_geqrf(tid, d):
            i, j, k = d
            return [(engine.QR_GEQRF, res(k, k))]

        def enc_larft(tid, d):
            i, j, k = d
            return [(engine.QR_LARFT, res(k, k), res(k, j))]

        def enc_tsqrf(tid, d):
            i, j, k = d
            return [(engine.QR_TSQRF, res(k, k), res(i, k))]

        def enc_ssrft(tid, d):
            i, j, k = d
            return [(engine.QR_SSRFT, res(i, k), res(k, j), res(i, j))]

        return {
            T_GEQRF: BatchSpec(run_one=one(T_GEQRF), encode=enc_geqrf),
            T_LARFT: BatchSpec(run_one=one(T_LARFT), run_batch=larft_batch,
                               encode=enc_larft),
            T_TSQRF: BatchSpec(run_one=one(T_TSQRF), encode=enc_tsqrf),
            T_SSRFT: BatchSpec(run_one=one(T_SSRFT), run_batch=ssrft_batch,
                               encode=enc_ssrft),
        }


class _TileStack(_TileState):
    """QR state of a ``device_resident`` backend: every tile in one
    (mt·nt, b, b) device array in ``_stack_tiles`` order, carried from the
    split to R with no per-tile Python.  It shares the descriptor encoders
    of ``batch_registry``; its host task bodies are never called, since
    the engine runs every task inside the megakernel."""

    def __init__(self, a: jnp.ndarray, b: int, backend):
        self.stack = _stack_tiles(a, b)
        self.mt, self.nt = a.shape[0] // b, a.shape[1] // b
        self.tiles, self.backend = None, backend

    def engine_hooks(self) -> EngineHooks:
        """Engine-family hooks for the backend registry: the stack and a
        zeroed T buffer are the fused QR megakernel's state; its output
        stack becomes the state."""
        def buffers():
            return self.stack, jnp.zeros_like(self.stack)

        def writeback(out):
            self.stack = out[0]

        return EngineHooks(
            arg_width=engine.QR_ARG_WIDTH,
            round_fn=engine.qr_round_fn(), statics=tuple,
            buffers=buffers, writeback=writeback,
            row_access=engine.qr_row_access)


def run_qr(a: jnp.ndarray, tile: int = 32, mode: str = "sequential",
           backend: str = "pallas", nr_queues: int = 1):
    """Compute the R factor of ``a`` with the QuickSched task graph on any
    registered execution backend.  Returns (R, sched).  A
    ``device_resident`` backend keeps the tiles as one device array
    (``_TileStack``); the host modes mutate a dict of tiles
    (``_TileState``); the counters ``qr.glue.stacked_plans`` and
    ``qr.glue.per_tile_plans`` count the plans of each.  The host phases
    are spanned (``qr.run`` holding ``qr.split_tiles``, ``qr.build_graph``,
    the backend's own spans and ``qr.assemble_r``)."""
    stacked = get_backend(mode).device_resident
    _metrics.get_registry().counter(
        "qr.glue.stacked_plans" if stacked
        else "qr.glue.per_tile_plans").inc()
    with _trace.span("qr.run", n=a.shape[0], tile=tile, mode=mode):
        with _trace.span("qr.split_tiles"):
            state = (_TileStack(a, tile, backend) if stacked
                     else _TileState(_split_tiles(a, tile)[0], backend))
        mt, nt = state.mt, state.nt
        with _trace.span("qr.build_graph"):
            sched, _ = make_qr_graph(mt, nt, nr_queues=nr_queues)
        run_plan(sched, state.batch_registry(), mode,
                 nr_workers=max(nr_queues, 1),
                 engine=state.engine_hooks() if stacked else None)
        with _trace.span("qr.assemble_r"):
            r = (_unstack_r(state.stack, mt, nt) if stacked
                 else _assemble_r(state.tiles, mt, nt, tile, a.dtype))
    return r, sched


def dispatch_counts(a: jnp.ndarray, tile: int = 32, nr_queues: int = 1):
    """(host dispatches of the per-round path, engine dispatches) for
    ``a``'s QR plan — the figure of merit of the device engine
    (``benchmarks/engine_dispatch.py``, DESIGN.md §Engine)."""
    tiles, mt, nt = _split_tiles(jnp.asarray(a), tile)
    sched, _ = make_qr_graph(mt, nt, nr_queues=nr_queues)
    plan = lower(sched, nr_lanes=max(nr_queues, 1))
    host = engine.count_host_dispatches(
        plan, sched, _TileState(tiles, "pallas").batch_registry())
    return host, engine.ENGINE_DISPATCHES_PER_PLAN


def paper_counts(mt: int = 32, nt: int = 32):
    """Structural counts for the paper's 2048² / 64² benchmark matrix."""
    s, _ = make_qr_graph(mt, nt)
    return {
        "tasks": s.nr_tasks,
        "deps": s.nr_deps,
        "resources": len(s.resources),
        "locks": s.nr_locks,
        "uses": s.nr_uses,
    }

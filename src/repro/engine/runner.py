"""Device-resident plan execution: one jitted host dispatch per plan.

``execute_plan`` runs a ragged :class:`~repro.engine.descriptors.TaskTable`
through a family walk function (``repro.engine.megakernel``) as a single
jitted program — the whole plan becomes one XLA program with zero host
transitions between rounds, and the state buffers are donated so execution
is in-place end to end (DESIGN.md §Engine).

Two dispatch shapes, same single host call:

* per-round (default): one grid-walk ``pallas_call`` per non-empty round,
  unrolled inside the jitted program (each round's CSR slice has its own
  static shape — raggedness costs nothing at run time, empty rounds
  disappear entirely);
* ``fuse_rounds=True``: ONE megakernel launch whose phase grid walks the
  *entire plan* (a single copy-in/copy-out of the state).  Legal because
  the global phase order already serializes rounds; it is the fastest mode
  whenever the family state fits the kernel's memory budget
  (``benchmarks/engine_dispatch.py`` times both and CI keeps
  fused ≤ looped).

On CPU runtimes the megakernels run in Pallas interpret mode, so this is
also the CI path; buffer donation is only requested on backends that
implement it (donation on CPU is a no-op that warns).
"""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import metrics as _metrics
from repro.obs import trace as _trace

from .descriptors import TaskTable

# (desc, phase_bounds, statics, buffers) -> buffers; phase_bounds is a
# static tuple of sub-phase boundaries over desc's rows — the megakernel
# factories chunk it into the ragged block grid on the host
RoundFn = Callable[[jnp.ndarray, Tuple[int, ...], Tuple, Tuple], Tuple]

ENGINE_DISPATCHES_PER_PLAN = 1     # the whole point — see BENCH_engine.json

# launch segment: (row_start, row_end, phase_bounds relative to row_start)
Segment = Tuple[int, int, Tuple[int, ...]]


def _round_segments_for(tables: TaskTable, r: int) -> Tuple[Segment, ...]:
    o0 = int(tables.round_offsets[r])
    o1 = int(tables.round_offsets[r + 1])
    if o1 == o0:
        return ()                  # empty rounds lower to no launch at all
    bounds = tables.round_phases(r)
    return ((o0, o1, tuple(int(b) - o0 for b in bounds)),)


def _round_segments(tables: TaskTable) -> Tuple[Segment, ...]:
    return tuple(s for r in range(tables.nr_rounds)
                 for s in _round_segments_for(tables, r))


def _fused_segments(tables: TaskTable) -> Tuple[Segment, ...]:
    if tables.nr_items == 0:
        return ()
    return ((0, tables.nr_items,
             tuple(int(b) for b in tables.phase_offsets)),)


@functools.lru_cache(maxsize=None)
def _segment_runner(round_fn: RoundFn, segments: Tuple[Segment, ...],
                    donate: bool):
    """Jitted executor for a fixed launch layout.  ``round_fn`` must be a
    stable object (the megakernel factories are lru-cached) and
    ``segments`` is derived from host-side table offsets, so repeated
    executions of structurally identical plans share one compilation."""
    def run(desc, statics, buffers):
        for o0, o1, bounds in segments:
            buffers = round_fn(desc[o0:o1], bounds, statics, buffers)
        return buffers

    # the program takes the round function's name (``jit_qr_round``,
    # ``jit_decode_round``, ...), stable across plans and visible in the
    # device trace
    run.__name__ = run.__qualname__ = getattr(round_fn, "__name__", "run")
    return jax.jit(run, donate_argnums=(2,) if donate else ())


def execute_plan(tables: TaskTable, round_fn: RoundFn,
                 statics: Sequence, buffers: Sequence, *,
                 fuse_rounds: bool = False,
                 donate: Optional[bool] = None) -> Tuple:
    """Execute a lowered task table.  ``statics`` are read-only family
    inputs (may be empty); ``buffers`` are the mutable state arrays,
    threaded launch to launch and returned.  ``round_fn`` must be a stable
    object (the megakernel factories are lru-cached) so repeated calls hit
    the jit cache."""
    statics = tuple(statics)
    buffers = tuple(buffers)
    if tables.nr_items == 0:
        return buffers
    if donate is None:
        donate = jax.default_backend() in ("tpu", "gpu")
    segments = (_fused_segments(tables) if fuse_rounds
                else _round_segments(tables))
    run = _segment_runner(round_fn, segments, bool(donate))
    reg = _metrics.get_registry()
    reg.counter("engine.plans_executed").inc()
    reg.counter("engine.launch_segments").inc(len(segments))
    reg.counter("engine.items_walked").inc(tables.nr_items)
    # asynchronous in every tracing state: a traced run executes the same
    # program as an untraced one, and device time comes from the profiler
    return run(jnp.asarray(tables.desc), statics, buffers)


@functools.lru_cache(maxsize=None)
def _item_runner(round_fn: RoundFn):
    """Jitted single-item launch — every item shares the (1, 1+A) shape,
    so one compilation covers the whole per-item measurement pass."""
    def run(desc_row, statics, buffers):
        return round_fn(desc_row, (0, 1), statics, buffers)

    return jax.jit(run)


@dataclass
class RoundTimings:
    """Measured engine times (``measure_round_times``): ``round_s[r]`` is
    round ``r``'s wall time (one grid-walk launch per round, 0.0 for empty
    rounds); ``item_s[q]`` — only with ``per_item=True`` — is flat work
    item ``q``'s wall time (one single-item launch each, mapping to tasks
    through ``TaskTable.tids``, the input to
    ``core.simulator.replay_item_times``).  ``buffers`` is the final state
    of the last measurement pass (identical for both passes — the walks
    differ only in launch granularity)."""
    round_s: List[float]
    item_s: Optional[np.ndarray]
    buffers: Tuple


def measure_round_times(tables: TaskTable, round_fn: RoundFn,
                        statics: Sequence, buffers: Sequence, *,
                        per_item: bool = False) -> RoundTimings:
    """Execute a task table one round at a time, timing each launch
    (blocked on completion) — the measured per-round engine times that
    ``core.simulator.replay_round_times`` feeds back into the discrete-
    event model to validate its makespan prediction against the fused
    single-dispatch execute time (ROADMAP: simulator validation).  With
    ``per_item=True`` an additional pass re-executes the table one *item*
    at a time, giving each task its own measured cost
    (``core.simulator.replay_item_times`` replays those into lane-parallel
    makespans).  Every ragged round shape is pre-run once as compile
    warmup, so the timings are steady-state.

    Caveat on per-item granularity: each single-item launch pays the full
    per-launch overhead (dispatch + state copy-in/out), so on hosts where
    that overhead rivals one item's arithmetic — CPU interpret mode in
    particular — ``item_s`` is an upper bound skewed toward launch cost,
    and the replay validates the *model mechanics* (additivity, lane
    bounds) rather than hardware task costs.  Measuring per-item costs
    worth trusting on real accelerators is the ROADMAP simulator-
    validation item."""
    statics = tuple(statics)
    init = tuple(buffers)
    desc = jnp.asarray(tables.desc)
    runners = {}
    for r in range(tables.nr_rounds):
        segs = _round_segments_for(tables, r)
        runners[r] = (_segment_runner(round_fn, segs, False)
                      if segs else None)

    bufs = init
    for r in range(tables.nr_rounds):          # compile warmup, all shapes
        if runners[r] is not None:
            bufs = runners[r](desc, statics, bufs)
    jax.block_until_ready(bufs)

    tr = _trace.get_tracer()
    round_s: List[float] = []
    bufs = init
    for r in range(tables.nr_rounds):
        if runners[r] is None:
            round_s.append(0.0)
            continue
        t0 = time.perf_counter()
        bufs = runners[r](desc, statics, bufs)
        jax.block_until_ready(bufs)
        t1 = time.perf_counter()
        round_s.append(t1 - t0)
        if tr.enabled:
            tr.event_span("engine.round", t0, t1, lane="engine rounds",
                          round=r,
                          items=int(tables.round_offsets[r + 1]
                                    - tables.round_offsets[r]))

    item_s = None
    if per_item:
        run1 = _item_runner(round_fn)
        if tables.nr_items:
            jax.block_until_ready(
                run1(desc[0:1], statics, init))          # compile warmup
        bufs = init
        item_s = np.zeros(tables.nr_items, np.float64)
        etypes = tables.desc[:, 0]
        for q in range(tables.nr_items):
            t0 = time.perf_counter()
            bufs = run1(desc[q:q + 1], statics, bufs)
            jax.block_until_ready(bufs)
            t1 = time.perf_counter()
            item_s[q] = t1 - t0
            if tr.enabled:
                # the paper's per-task tic/toc, keyed back to tasks
                # through TaskTable.tids — one timeline row, since the
                # measurement pass is by construction sequential
                tr.task(int(tables.tids[q]), int(etypes[q]), 0, t0, t1)
    return RoundTimings(round_s=round_s, item_s=item_s, buffers=bufs)

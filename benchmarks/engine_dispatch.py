"""Engine dispatch benchmark: per-round host dispatch vs the fused
device-resident engine (DESIGN.md §Engine), for all three task families
(QR, Barnes-Hut, pipeline F/B/U).  Writes ``BENCH_engine.json`` at the
repo root.

Three figures of merit per family:

* **host dispatches per plan** — the per-round BatchSpec path issues one
  host call per batched group and one per ``run_one`` task
  (``count_host_dispatches``); the engine issues exactly one jitted call
  for the whole plan.  This is the paper's Fig-13 overhead argument moved
  to the dispatch layer: scheduler *and* dispatch off the critical path.
* **walk rows** — the ragged CSR table walks exactly ``items`` descriptor
  rows; the padded slab layout it replaced walked ``rounds × max_width``
  (``walk_reduction`` is the ratio, the pad work eliminated; CI asserts
  ``pad_fraction == 0`` and per-family reduction floors).
* **execute wall time** — steady-state, graph/plan/lowering excluded,
  first calls excluded as compile.  For QR the per-round host path is
  timed against the engine; for every family the engine itself is timed
  both ways — per-round launches inside one jitted dispatch
  (``engine_looped``) vs one whole-plan megakernel launch
  (``engine_fused``) — the ROADMAP round-boundary-donation question
  measured: CI keeps fused ≤ looped.
"""

from __future__ import annotations

import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

import jax.random

from repro import engine
from repro.apps import barneshut as bh
from repro.apps import qr
from repro.core import lower
from repro.pipeline import lower_pipeline_plan
from repro.pipeline.exec import (_PipeRunner, _engine_family, _engine_hooks,
                                 dense_stage, mse_loss,
                                 pipelined_value_and_grad_plan)

from .common import FULL, SMOKE, emit

REPEAT = 3 if SMOKE else 5


def _best(setup, timed, repeat=REPEAT):
    best, out = float("inf"), None
    for _ in range(repeat):
        st = setup()
        t0 = time.perf_counter()
        out = timed(st)
        best = min(best, time.perf_counter() - t0)
    return best, out


def _walk_stats(tables: "engine.TaskTable") -> dict:
    stats = dict(tables.stats)
    stats["walk_reduction"] = stats["padded_rows"] / max(stats["items"], 1)
    return stats


def _time_engine_walks(tables, round_fn, statics, make_buffers,
                       repeat=max(REPEAT, 5)) -> dict:
    """Steady-state engine execute times, per-round-looped vs whole-plan
    fused, fresh buffers per repeat, first call per mode excluded as
    compile.  Best-of-5 even at smoke sizes: CI floors compare the two
    modes against each other, so jitter matters more than wall time."""
    out = {}
    for name, fuse in (("engine_looped", False), ("engine_fused", True)):
        engine.execute_plan(tables, round_fn, statics, make_buffers(),
                            fuse_rounds=fuse)                    # warmup

        def run(bufs, fuse=fuse):
            res = engine.execute_plan(tables, round_fn, statics, bufs,
                                      fuse_rounds=fuse)
            jax.block_until_ready(res)
            return res
        out[name], _ = _best(make_buffers, run, repeat=repeat)
    return out


def bench_qr():
    mt = nt = 16 if FULL else (6 if SMOKE else 8)
    b = 32
    n = mt * b
    a = jnp.asarray(np.random.default_rng(0).standard_normal((n, n)),
                    jnp.float32)
    tiles, _, _ = qr._split_tiles(a, b)
    sched, _ = qr.make_qr_graph(mt, nt, nr_queues=4)
    plan = lower(sched, 4)
    registry = qr._TileState(dict(tiles), "pallas").batch_registry()
    host_dispatches = engine.count_host_dispatches(plan, sched, registry)

    # per-round host path: fresh tile state per repeat, execute timed
    # (block on the tile dict so both sides measure completed execution)
    def setup_rounds():
        return qr._TileState(dict(tiles), "pallas")

    def run_rounds(st):
        plan.execute(sched, st.batch_registry())
        jax.block_until_ready(st.tiles)
        return st
    t_rounds, _ = _best(setup_rounds, run_rounds)

    # engine: tables lowered once; fresh (donatable) buffers per repeat
    state = qr._TileState(dict(tiles), "pallas")
    tables = engine.lower_tables(
        plan, sched, state.batch_registry(),
        arg_width=engine.QR_ARG_WIDTH, row_access=engine.qr_row_access)
    stack0 = qr._stack_tiles(a, b)
    walks = _time_engine_walks(
        tables, engine.qr_round_fn(), (),
        lambda: (stack0 + 0.0, jnp.zeros_like(stack0)))

    tasks = sched.nr_tasks
    t_engine = walks["engine_looped"]
    return {
        "graph": f"qr_{mt}x{nt}",
        "tasks": tasks,
        "rounds": plan.nr_rounds,
        "table": _walk_stats(tables),
        "host_dispatches": {
            "per_round": host_dispatches,
            "engine": engine.ENGINE_DISPATCHES_PER_PLAN,
        },
        "dispatch_reduction": host_dispatches
        / engine.ENGINE_DISPATCHES_PER_PLAN,
        "execute_s": {"per_round": t_rounds, "engine": t_engine, **walks},
        "speedup": t_rounds / t_engine,
        "tasks_per_sec": {"per_round": tasks / t_rounds,
                          "engine": tasks / t_engine},
    }


def bench_bh():
    n = 20000 if FULL else (2000 if SMOKE else 4000)
    rng = np.random.default_rng(11)
    x, m = rng.random((n, 3)), rng.random(n) + 0.5
    tree = bh.Octree(x, m, n_max=64)
    g = bh.build_graph(tree, n_task=256, nr_queues=4)
    st = bh.BHState(g, backend="pallas")
    plan = lower(g.sched, 4)
    registry = st.batch_registry()
    host_dispatches = engine.count_host_dispatches(plan, g.sched, registry)
    tables = engine.lower_tables(plan, g.sched, registry,
                                 arg_width=engine.BH_ARG_WIDTH,
                                 row_access=engine.bh_row_access)
    hooks = st.engine_hooks()
    statics = hooks.statics()
    walks = _time_engine_walks(tables, hooks.round_fn, statics,
                               hooks.buffers)
    return {
        "graph": f"bh_{n}",
        "tasks": g.sched.nr_tasks,
        "rounds": plan.nr_rounds,
        "table": _walk_stats(tables),
        "host_dispatches": {
            "per_round": host_dispatches,
            "engine": engine.ENGINE_DISPATCHES_PER_PLAN,
        },
        "dispatch_reduction": host_dispatches
        / engine.ENGINE_DISPATCHES_PER_PLAN,
        "execute_s": {"engine": walks["engine_looped"], **walks},
    }


def bench_pipeline():
    """Pipeline F/B/U family (ISSUE 4): host dispatches of the per-task
    path vs the single-dispatch engine, plus end-to-end value-and-grad
    wall time on the canonical dense family."""
    S, M = (8, 64) if FULL else ((4, 16) if SMOKE else (4, 32))
    bt, dim = 4, 32
    key = jax.random.PRNGKey(0)
    params = [{"w": jax.random.normal(jax.random.fold_in(key, k),
                                      (dim, dim)) * 0.3,
               "b": jnp.zeros((dim,))} for k in range(S)]
    micro = [{"x": jax.random.normal(jax.random.fold_in(key, 100 + m),
                                     (bt, dim)),
              "y": jax.random.normal(jax.random.fold_in(key, 200 + m),
                                     (bt, dim))} for m in range(M)]
    runner = _PipeRunner([dense_stage] * S, mse_loss, params, micro)
    sched, _, plan = lower_pipeline_plan(S, M, per_stage_window=True)
    registry = runner.registry()
    host_dispatches = engine.count_host_dispatches(plan, sched, registry)
    tables = engine.lower_tables(plan, sched, registry,
                                 arg_width=engine.PIPE_ARG_WIDTH,
                                 row_access=engine.pipe_row_access)
    fam = _engine_family([dense_stage] * S, mse_loss, params, micro)
    hooks = _engine_hooks(params, micro, fam, {})
    statics = hooks.statics()
    walks = _time_engine_walks(tables, hooks.round_fn, statics,
                               hooks.buffers)

    def run_mode(mode):
        def timed(_):
            out = pipelined_value_and_grad_plan(
                [dense_stage] * S, mse_loss, params, micro, mode=mode)
            jax.block_until_ready(out)
            return out
        timed(None)                       # warmup (engine: compile)
        return _best(lambda: None, timed, repeat=3)[0]

    t_rounds = run_mode("rounds")
    t_engine = run_mode("engine")
    return {
        "graph": f"pipeline_S{S}_M{M}",
        "tasks": sched.nr_tasks,
        "rounds": plan.nr_rounds,
        "table": _walk_stats(tables),
        "host_dispatches": {
            "per_round": host_dispatches,
            "engine": engine.ENGINE_DISPATCHES_PER_PLAN,
        },
        "dispatch_reduction": host_dispatches
        / engine.ENGINE_DISPATCHES_PER_PLAN,
        "execute_s": {"per_round": t_rounds, "engine": t_engine, **walks},
    }


def main() -> None:
    out = {"qr": bench_qr(), "bh": bench_bh(), "pipeline": bench_pipeline()}
    q = out["qr"]
    emit("engine_qr_per_round_us", q["execute_s"]["per_round"] * 1e6,
         f"dispatches={q['host_dispatches']['per_round']}")
    emit("engine_qr_engine_us", q["execute_s"]["engine"] * 1e6,
         f"dispatches={q['host_dispatches']['engine']} "
         f"speedup={q['speedup']:.2f}x "
         f"dispatch_reduction={q['dispatch_reduction']:.0f}x")
    emit("engine_qr_tasks_per_sec", 0,
         f"engine={q['tasks_per_sec']['engine']:.0f} "
         f"per_round={q['tasks_per_sec']['per_round']:.0f}")
    b = out["bh"]
    emit("engine_bh_engine_us", b["execute_s"]["engine"] * 1e6,
         f"tasks={b['tasks']} rounds={b['rounds']} "
         f"dispatch_reduction={b['dispatch_reduction']:.0f}x")
    p = out["pipeline"]
    emit("engine_pipe_engine_us", p["execute_s"]["engine"] * 1e6,
         f"tasks={p['tasks']} rounds={p['rounds']} "
         f"dispatches={p['host_dispatches']['per_round']} "
         f"dispatch_reduction={p['dispatch_reduction']:.0f}x")
    for fam in ("qr", "bh", "pipeline"):
        f = out[fam]
        emit(f"engine_{fam}_walk", f["table"]["items"],
             f"pad_fraction={f['table']['pad_fraction']:.2f} "
             f"walk_reduction={f['table']['walk_reduction']:.2f}x "
             f"phases={f['table']['phases']} "
             f"fused_us={f['execute_s']['engine_fused'] * 1e6:.0f} "
             f"looped_us={f['execute_s']['engine_looped'] * 1e6:.0f}")
    path = pathlib.Path(__file__).resolve().parent.parent / "BENCH_engine.json"
    path.write_text(json.dumps(out, indent=2) + "\n")
    emit("engine_json", 0, str(path))


if __name__ == "__main__":
    main()

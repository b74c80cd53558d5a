"""Grid-parallel Pallas megakernels: a ragged task-table walk per family.

Each task *family* (tiled QR, Barnes-Hut, the pipeline F/B/U synthesizer)
gets one Pallas kernel that walks a ragged (CSR) descriptor table as a
real **grid** over item blocks: each write-colored *sub-phase* is chunked
into blocks of ≤ ``block_items`` contiguous work items, the grid iterates
the blocks phase-major (exactly as ragged as the phases — zero inert
programs, zero padding rows), and every grid program runs a short
in-kernel ``fori_loop`` over its block, branching on each row's engine
type with ``lax.switch`` (exllamav3-style type fusion).  Descriptor rows
and block bounds are scalar-prefetched
(``pltpu.PrefetchScalarGridSpec``), so each program reads its item range
and drives its gathers from SMEM-resident integers.  The walk does
exactly ``items`` rows of work — the padded slab layout this replaces did
``rounds × max_width``.  Layout, the type-branch contract and the
coloring/visibility rules are documented in DESIGN.md §Engine ("Ragged
tables & grid walk").

Contract highlights (see the design doc for the full statement):

* State buffers are passed in and aliased to the outputs
  (``input_output_aliases``) with whole-array blocks whose index maps are
  constant, so the state block is resident across all grid programs; the
  first grid program copies the input refs into the output refs
  (``_init_state`` — interpret mode seeds aliased outputs anyway, but
  compiled backends leave output windows undefined until written), and
  every branch then loads *and* stores through the output refs, so items
  observe all earlier programs' writes.
* Blocks never span a phase boundary, so phase-major block order
  serializes exactly the item pairs that touch a common state row — the
  write coloring (``core.plan.color_phases``) guarantees items of one
  phase read/write disjoint rows, so a phase's programs are safe to
  execute in any order or in parallel (on a multi-core TPU, a phase's
  block range is the dimension a parallel ``dimension_semantics`` walk
  may split).  Because the coloring preserves per-destination item order,
  read-modify-write accumulation (Barnes-Hut ``+=``, pipeline grad slabs)
  produces the same bit patterns as the sequential walk it replaced.
* Each family keeps a no-op engine type as the **last** ``lax.switch``
  branch, so a clamped out-of-range type degrades to a no-op rather than
  garbage (a lowering-bug guard; tables themselves carry no no-op rows).
* The numerical bodies are the exact value-level functions the per-op
  kernels use (``kernels.qr_tile.kernel.*_math``,
  ``kernels.nbody.kernel.acc_block``) — one source of truth for the math.

On a CPU runtime the kernels run in Pallas interpret mode (same default as
``kernels/*/ops.py``), so CI executes the identical engine code path; the
grid then executes sequentially (phase-major), which the coloring
invariant makes observationally identical to any parallel interleaving of
a phase's blocks.

The per-family ``*_row_access`` maps in this module declare which state
rows each descriptor row reads and writes, in the same keyspace the
kernels address — they are the input to the write coloring in
``descriptors.lower_tables`` and are property-tested against the phase
partition in ``tests/test_engine_properties.py``.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.nbody.kernel import acc_block
from repro.kernels.qr_tile.kernel import (apply_qt_math, apply_tsqt_math,
                                          geqrf_math, tsqrf_math)

# QR engine types — intentionally equal to apps.qr.T_* so task types encode
# to themselves; QR_NOOP is the defensive clamp branch (never in a table).
QR_GEQRF, QR_LARFT, QR_TSQRF, QR_SSRFT, QR_NOOP = range(5)
QR_ARG_WIDTH = 3       # rows: [etype, slot0, slot1, slot2] (tile indices)

# Barnes-Hut engine (work-item) types; BH_NOOP is the clamp branch.
(BH_COM_LEAF, BH_COM_INNER, BH_SELF, BH_PP, BH_PC, BH_NOOP) = range(6)
BH_MAX_CHILDREN = 8    # octree fan-out; COM_INNER rows carry 8 child cells
# and ragged PC source lists chunk into rows of 8 cells (pad = zero-mass)
BH_ARG_WIDTH = 1 + BH_MAX_CHILDREN   # rows: [etype, write, a0..a7]

# Pipeline F/B/U engine types; PIPE_NOOP is the clamp branch.  Rows:
# [etype, stage, micro, in_slot, out_slot, first, last] where the slots are
# flat (stage, micro) indices into the stacked activation/cotangent slabs.
PIPE_F, PIPE_B, PIPE_U, PIPE_NOOP = range(4)
PIPE_ARG_WIDTH = 6

# Work items one grid program walks; each sub-phase chunks into
# ceil(phase_len / block_items) ragged blocks (blocks never span a phase
# boundary, so a phase's programs stay mutually conflict-free).
DEFAULT_BLOCK_ITEMS = 8

# SMEM words one launch may spend on its scalar-prefetched tables (the
# flattened descriptor rows plus two bounds per block).  A TPU core has
# 1 MiB of SMEM; a walk whose tables outgrow this budget splits into
# several launches at block boundaries.
SMEM_TABLE_WORDS = 128 * 1024


def _default_interpret(interpret: Optional[bool]) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


# ---------------------------------------------------------------------------
# row-access maps (write-coloring inputs): row -> (reads, writes) state keys
# ---------------------------------------------------------------------------

def qr_row_access(row: Sequence[int]) -> Tuple[Tuple, Tuple]:
    """QR keyspace: ``("t", slot)`` tile-stack rows, ``("m", slot)``
    T-factor rows (column-major tile index)."""
    et = row[0]
    if et == QR_GEQRF:
        s0 = row[1]
        return (("t", s0),), (("t", s0), ("m", s0))
    if et == QR_LARFT:
        s0, s1 = row[1], row[2]
        return (("t", s0), ("m", s0), ("t", s1)), (("t", s1),)
    if et == QR_TSQRF:
        s0, s1 = row[1], row[2]
        return (("t", s0), ("t", s1)), (("t", s0), ("t", s1), ("m", s1))
    if et == QR_SSRFT:
        s0, s1, s2 = row[1], row[2], row[3]
        return ((("t", s0), ("m", s0), ("t", s1), ("t", s2)),
                (("t", s1), ("t", s2)))
    return (), ()


def bh_row_access(row: Sequence[int]) -> Tuple[Tuple, Tuple]:
    """Barnes-Hut keyspace: ``("a", leaf_slot)`` acceleration blocks,
    ``("c", cell)`` COM/mass rows.  Particle positions/masses are
    read-only statics and carry no keys."""
    et = row[0]
    if et == BH_COM_LEAF:
        return (), (("c", row[1]),)
    if et == BH_COM_INNER:
        return (tuple(("c", int(c)) for c in row[2:2 + BH_MAX_CHILDREN]),
                (("c", row[1]),))
    if et in (BH_SELF, BH_PP):
        return (), (("a", row[1]),)
    if et == BH_PC:
        return (tuple(("c", int(c)) for c in row[2:2 + BH_MAX_CHILDREN]),
                (("a", row[1]),))
    return (), ()


def pipe_row_access(row: Sequence[int]) -> Tuple[Tuple, Tuple]:
    """Pipeline keyspace: ``("act"|"cot", slot)`` activation/cotangent
    slabs, ``("gw"|"gb", stage)`` grad buffers, ``("loss", micro)`` loss
    rows.  Stage parameters and microbatch inputs are statics."""
    et, s, m, a_in, a_out = row[0], row[1], row[2], row[3], row[4]
    if et == PIPE_F:
        return ((("act", a_in), ("cot", a_out), ("loss", m)),
                (("act", a_out), ("cot", a_out), ("loss", m)))
    if et == PIPE_B:
        return ((("act", a_in), ("act", a_out), ("cot", a_out),
                 ("gw", s), ("gb", s), ("cot", a_in)),
                (("gw", s), ("gb", s), ("cot", a_in)))
    if et == PIPE_U:
        return ((("gw", s), ("gb", s)), (("gw", s), ("gb", s)))
    return (), ()


# ---------------------------------------------------------------------------
# grid-walk plumbing shared by the three families
# ---------------------------------------------------------------------------

def _blocks_of(phase_bounds: Tuple[int, ...], block_items: int) -> Tuple:
    """Chunk each phase ``[phase_bounds[p], phase_bounds[p+1])`` into
    blocks of ≤ ``block_items`` contiguous work items — one grid program
    each, emitted phase-major so phase order is preserved by the grid walk
    and no program ever spans a phase boundary.  The blocking is exactly
    as ragged as the phases: zero inert programs."""
    blocks = []
    for b0, b1 in zip(phase_bounds, phase_bounds[1:]):
        for s in range(int(b0), int(b1), block_items):
            blocks.append((s, min(s + block_items, int(b1))))
    return tuple(blocks)


def _launches(phase_bounds: Tuple[int, ...], block_items: int,
              width: int) -> Tuple:
    """Group the phase-major blocks into launches whose scalar tables
    (``width`` words per row plus two bounds per block) fit
    ``SMEM_TABLE_WORDS``: ``((row0, row1, blocks relative to row0), ...)``.
    A cut between two blocks of one phase is safe: the phase's items are
    mutually conflict-free, and launches run in order."""
    launches, cur, words = [], [], 0
    for b in _blocks_of(phase_bounds, block_items):
        w = (b[1] - b[0]) * width + 2
        if cur and words + w > SMEM_TABLE_WORDS:
            launches.append(cur)
            cur, words = [], 0
        cur.append(b)
        words += w
    if cur:
        launches.append(cur)
    return tuple((c[0][0], c[-1][1],
                  tuple((b0 - c[0][0], b1 - c[0][0]) for b0, b1 in c))
                 for c in launches)


def _walk_block(bounds_ref, desc_ref, width: int, body) -> None:
    """Run ``body(arg)`` over this grid program's work items, where
    ``arg(k)`` reads field ``k`` of the item's descriptor row.  Both
    tables are flat in SMEM (a 2-D SMEM array pads every row to 128
    words): program ``t`` walks rows ``[bounds[2t], bounds[2t+1])`` and
    row ``q`` is ``desc[q*width : (q+1)*width]``."""
    t = pl.program_id(0)

    def step(q, carry):
        body(lambda k: desc_ref[q * width + k])
        return carry

    jax.lax.fori_loop(bounds_ref[2 * t], bounds_ref[2 * t + 1], step, 0)


def _init_state(in_refs, out_refs) -> None:
    """Copy the aliased state into the output refs on the first grid
    program.  Interpret mode already seeds aliased outputs with the input
    values, but compiled backends leave output windows undefined until
    written — the guarded copy makes the visibility contract explicit
    everywhere (program 0 runs first; the constant-index state block then
    stays resident for the rest of the grid)."""
    @pl.when(pl.program_id(0) == 0)
    def _():
        for i_ref, o_ref in zip(in_refs, out_refs):
            o_ref[...] = i_ref[...]


def _grid_walk(kernel, desc, phase_bounds, block_items, statics, buffers,
               interpret: bool, name: str):
    """Walk ``desc`` over a flat grid of ragged item blocks, one
    ``pallas_call`` per SMEM-sized launch (``_launches``; one launch for
    any table of modest size): block bounds and descriptor rows are
    scalar-prefetched (SMEM integers drive the loop bounds and gathers),
    statics read-only, state buffers aliased input→output with constant
    whole-array blocks (resident across programs, so later programs
    observe earlier writes).  Blocks are phase-major: programs of one
    phase touch pairwise-disjoint state rows (the write-coloring
    invariant) and may execute in any order or concurrently; phase order
    itself is what serializes the conflicting pairs.  Every launch carries
    the family kernel's ``name`` (``qr_megakernel``, ...), which the
    device trace and the lowered program show."""
    statics = tuple(statics)
    buffers = tuple(buffers)
    width = desc.shape[1]
    kern = functools.partial(kernel, width=width)
    for r0, r1, blocks in _launches(phase_bounds, block_items, width):
        buffers = _launch(kern, desc[r0:r1].reshape(-1),
                          jnp.asarray(blocks, jnp.int32).reshape(-1),
                          statics, buffers, interpret, name)
    return buffers


def _launch(kernel, desc, bounds, statics, buffers, interpret: bool,
            name: str):
    """One grid-walk ``pallas_call`` over flat ``desc``/``bounds``."""

    def full(a):
        return pl.BlockSpec(a.shape,
                            lambda t, *_, nd=a.ndim: (0,) * nd)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bounds.shape[0] // 2,),
        in_specs=[full(a) for a in statics + buffers],
        out_specs=tuple(full(a) for a in buffers),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                        for a in buffers),
        input_output_aliases={2 + len(statics) + i: i
                              for i in range(len(buffers))},
        interpret=interpret,
        name=name,
    )(bounds, desc, *statics, *buffers)


# ---------------------------------------------------------------------------
# tiled QR family
# ---------------------------------------------------------------------------

def _qr_kernel(bounds_ref, desc_ref, tiles_in, tmat_in, tiles_ref, tmat_ref,
               *, width):
    _init_state((tiles_in, tmat_in), (tiles_ref, tmat_ref))

    def tile(ref, i):
        return ref[pl.ds(i, 1), :, :][0]

    def put(ref, i, v):
        ref[pl.ds(i, 1), :, :] = v[None]

    def body(arg):
        s0, s1, s2 = arg(1), arg(2), arg(3)

        def geqrf():      # [kk] — factor the diagonal tile, stash T
            rv, _, t = geqrf_math(tile(tiles_ref, s0))
            put(tiles_ref, s0, rv)
            put(tmat_ref, s0, t)
            return 0

        def larft():      # [kk, kj] — apply Qᵀ of the diagonal tile
            out = apply_qt_math(tile(tiles_ref, s0), tile(tmat_ref, s0),
                                tile(tiles_ref, s1))
            put(tiles_ref, s1, out)
            return 0

        def tsqrf():      # [kk, ik] — R stacked over the rect tile; V
            a0 = tile(tiles_ref, s0)       # stays below kk's diagonal
            r1, v2, _, t = tsqrf_math(jnp.triu(a0), tile(tiles_ref, s1))
            put(tiles_ref, s0, jnp.triu(r1) + jnp.tril(a0, -1))
            put(tiles_ref, s1, v2)
            put(tmat_ref, s1, t)
            return 0

        def ssrft():      # [ik, kj, ij] — apply the (I; V2) reflector
            o1, o2 = apply_tsqt_math(tile(tiles_ref, s0),
                                     tile(tmat_ref, s0),
                                     tile(tiles_ref, s1),
                                     tile(tiles_ref, s2))
            put(tiles_ref, s1, o1)
            put(tiles_ref, s2, o2)
            return 0

        def noop():
            return 0

        jax.lax.switch(arg(0), (geqrf, larft, tsqrf, ssrft, noop))

    _walk_block(bounds_ref, desc_ref, width, body)


@functools.lru_cache(maxsize=None)
def qr_round_fn(interpret: Optional[bool] = None,
                block_items: int = DEFAULT_BLOCK_ITEMS):
    """Walk executor for the QR family:
    ``(desc, phase_bounds, (), (tiles, tmat)) -> (tiles, tmat)``.
    ``phase_bounds`` are the static sub-phase boundaries of the rows in
    ``desc``; ``tiles``/``tmat`` are (ntiles, b, b) stacks in column-major
    tile-index order; ``tmat[kk]`` holds the DGEQRF T factor and
    ``tmat[ik]`` the DTSQRF one (disjoint indices, one buffer).  Cached
    per (interpret, block_items) so the runner's jit cache is shared."""
    interp = _default_interpret(interpret)

    def qr_round(desc, phase_bounds, statics, buffers):
        del statics
        return _grid_walk(_qr_kernel, desc, phase_bounds, block_items, (),
                          buffers, interp, "qr_megakernel")

    return qr_round


# ---------------------------------------------------------------------------
# Barnes-Hut family
# ---------------------------------------------------------------------------

def _bh_kernel(bounds_ref, desc_ref, xs_ref, ms_ref, acc_in, com_in, cm_in,
               acc_ref, com_ref, cm_ref, *, eps, width):
    _init_state((acc_in, com_in, cm_in), (acc_ref, com_ref, cm_ref))
    npart = xs_ref.shape[2]
    gi = jax.lax.broadcasted_iota(jnp.int32, (npart, 1), 0)
    gj = jax.lax.broadcasted_iota(jnp.int32, (1, npart), 1)

    def leaf_x(i):                  # (3, P) padded particle block
        return xs_ref[pl.ds(i, 1), :, :][0]

    def leaf_m(i):                  # (P,) zero-padded masses
        return ms_ref[pl.ds(i, 1), :][0]

    def gather_cells(idx):          # K cell ids → (K,3) coms, (K,) masses
        # per-slot dynamic-slice gathers, NOT a one-hot matmul over the
        # whole com array: the kernel must read exactly the ≤8 rows that
        # bh_row_access declares, or the write coloring could co-phase
        # this item with a writer of an undeclared cell row
        xs_sel = jnp.stack(
            [com_ref[pl.ds(idx[k], 1), :][0]
             for k in range(BH_MAX_CHILDREN)])
        m_sel = jnp.stack(
            [cm_ref[pl.ds(idx[k], 1), :][0, 0]
             for k in range(BH_MAX_CHILDREN)])
        return xs_sel, m_sel

    def add_acc(i, delta):          # acc[i] += delta, read-modify-write
        cur = acc_ref[pl.ds(i, 1), :, :]
        acc_ref[pl.ds(i, 1), :, :] = cur + delta[None]

    def pair_delta(xi, xj, mj, mask_diag=False):
        dx0, dx1, dx2, w = acc_block(xi, xj, mj.reshape(1, -1), eps)
        if mask_diag:
            w = jnp.where(gi == gj, jnp.zeros_like(w), w)
        return jnp.stack([jnp.sum(dx0 * w, axis=1),
                          jnp.sum(dx1 * w, axis=1),
                          jnp.sum(dx2 * w, axis=1)])

    def put_com(w, c, tot):
        com_ref[pl.ds(w, 1), :] = c[None]
        cm_ref[pl.ds(w, 1), :] = tot.reshape(1, 1)

    def body(arg):
        w, s = arg(1), arg(2)

        def cell_slots():      # the 8 padded cell-id slots of this row
            return [arg(2 + k) for k in range(BH_MAX_CHILDREN)]

        def com_leaf():   # [cell, leaf] — mass-weighted mean of the block
            x, m = leaf_x(s), leaf_m(s)
            tot = jnp.sum(m)
            put_com(w, jnp.sum(x * m.reshape(1, -1), axis=1)
                    / jnp.maximum(tot, 1e-30), tot)
            return 0

        def com_inner():  # [cell, c0..c7] — combine children's COMs
            xs_sel, m_sel = gather_cells(cell_slots())
            tot = jnp.sum(m_sel)
            put_com(w, jnp.sum(xs_sel * m_sel[:, None], axis=0)
                    / jnp.maximum(tot, 1e-30), tot)
            return 0

        def self_():      # [leaf] — all pairs within one block
            x, m = leaf_x(w), leaf_m(w)
            add_acc(w, pair_delta(x, x, m, mask_diag=True))
            return 0

        def pp():         # [leaf_i, leaf_j] — one direction of a pair block
            add_acc(w, pair_delta(leaf_x(w), leaf_x(s), leaf_m(s)))
            return 0

        def pc():         # [leaf, s0..s7] — leaf against ≤8 COM sources
            xs_sel, m_sel = gather_cells(cell_slots())
            add_acc(w, pair_delta(leaf_x(w), xs_sel.T, m_sel))
            return 0

        def noop():
            return 0

        jax.lax.switch(arg(0), (com_leaf, com_inner, self_, pp, pc, noop))

    _walk_block(bounds_ref, desc_ref, width, body)


@functools.lru_cache(maxsize=None)
def bh_round_fn(eps: float, interpret: Optional[bool] = None,
                block_items: int = DEFAULT_BLOCK_ITEMS):
    """Walk executor for the Barnes-Hut family:
    ``(desc, phase_bounds, (xs, ms), (acc, com, cmass)) ->
    (acc, com, cmass)``.  ``xs``/``ms`` are (L, 3, P)/(L, P)
    zero-mass-padded leaf blocks (read-only); ``com``/``cmass`` carry one
    extra zero row as the gather pad target — ragged COM-source lists
    arrive pre-chunked into ≤8-source PC rows, so there is no side table.
    Cached per (eps, interpret, block_items) so the runner's jit cache is
    shared."""
    interp = _default_interpret(interpret)
    kern = functools.partial(_bh_kernel, eps=float(eps))

    def bh_round(desc, phase_bounds, statics, buffers):
        return _grid_walk(kern, desc, phase_bounds, block_items, statics,
                          buffers, interp, "bh_megakernel")

    return bh_round


# ---------------------------------------------------------------------------
# pipeline F/B/U family (the canonical uniform dense stage, see
# repro.pipeline.exec: stage = tanh(x @ w + b), loss = mean squared error)
# ---------------------------------------------------------------------------

def _pipe_kernel(bounds_ref, desc_ref, w_ref, b_ref, x_ref, y_ref,
                 acts_in, cots_in, gw_in, gb_in, loss_in,
                 acts_ref, cots_ref, gw_ref, gb_ref, loss_ref, *, inv_m,
                 width):
    _init_state((acts_in, cots_in, gw_in, gb_in, loss_in),
                (acts_ref, cots_ref, gw_ref, gb_ref, loss_ref))
    bt, dim = acts_ref.shape[1], acts_ref.shape[2]
    inv_numel = 1.0 / (bt * dim)      # MSE mean over one microbatch output

    def blk(ref, i):                  # (Bt, D) slab of a stacked buffer
        return ref[pl.ds(i, 1), :, :][0]

    def put(ref, i, v):
        ref[pl.ds(i, 1), :, :] = v[None]

    def row(ref, i):                  # (D,) row of a (S, D) buffer
        return ref[pl.ds(i, 1), :][0]

    def body(arg):
        s, m = arg(1), arg(2)
        a_in = arg(3)                 # == a_out (safe dummy) when first
        a_out, first, last = arg(4), arg(5), arg(6)

        def stage_input():            # x[m] on stage 0, else prev output
            return jnp.where(first > 0, blk(x_ref, m), blk(acts_ref, a_in))

        def fwd():        # acts[s,m] = tanh(in @ w_s + b_s); last: loss+seed
            h = jnp.tanh(stage_input() @ blk(w_ref, s) + row(b_ref, s)[None])
            put(acts_ref, a_out, h)
            diff = h - blk(y_ref, m)
            lcur = loss_ref[pl.ds(m, 1), :]
            loss_ref[pl.ds(m, 1), :] = jnp.where(
                last > 0, jnp.sum(diff * diff) * inv_numel,
                lcur[0, 0]).reshape(1, 1)
            put(cots_ref, a_out,
                jnp.where(last > 0, (2.0 * inv_numel) * diff,
                          blk(cots_ref, a_out)))
            return 0

        def bwd():        # grads[s] += vjp; cotangent flows to stage s-1
            h = blk(acts_ref, a_out)
            gpre = blk(cots_ref, a_out) * (1.0 - h * h)   # tanh' = 1 - y²
            put(gw_ref, s, blk(gw_ref, s) + stage_input().T @ gpre)
            gb_ref[pl.ds(s, 1), :] = (row(gb_ref, s)
                                      + jnp.sum(gpre, axis=0))[None]
            put(cots_ref, a_in,
                jnp.where(first > 0, blk(cots_ref, a_in),
                          gpre @ blk(w_ref, s).T))
            return 0

        def upd():        # microbatch averaging; optimizer is the caller's
            put(gw_ref, s, blk(gw_ref, s) * inv_m)
            gb_ref[pl.ds(s, 1), :] = (row(gb_ref, s) * inv_m)[None]
            return 0

        def noop():
            return 0

        jax.lax.switch(arg(0), (fwd, bwd, upd, noop))

    _walk_block(bounds_ref, desc_ref, width, body)


@functools.lru_cache(maxsize=None)
def pipe_round_fn(inv_m: float, interpret: Optional[bool] = None,
                  block_items: int = DEFAULT_BLOCK_ITEMS):
    """Walk executor for the pipeline family:
    ``(desc, phase_bounds, (w, b, x, y), (acts, cots, gw, gb, loss)) ->
    buffers``.  ``w``/``b`` are (S, D, D)/(S, D) stage-parameter stacks,
    ``x``/``y`` (M, Bt, D) microbatch inputs/targets (read-only); the
    kernel-resident state is the stacked stage-activation (``acts``) and
    cotangent (``cots``) slabs — flat (S·M, Bt, D), slot = stage·M +
    micro — plus the grad-accumulation buffers ``gw``/``gb`` and
    per-micro ``loss`` (M, 1).  ``inv_m`` = 1/M is the U branch's
    microbatch averaging.  Cached per (inv_m, interpret, block_items) so
    the runner's jit cache is shared."""
    interp = _default_interpret(interpret)
    kern = functools.partial(_pipe_kernel, inv_m=float(inv_m))

    def pipe_round(desc, phase_bounds, statics, buffers):
        return _grid_walk(kern, desc, phase_bounds, block_items, statics,
                          buffers, interp, "pipe_megakernel")

    return pipe_round

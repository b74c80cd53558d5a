"""Pallas paged-attention decode kernels: the in-kernel page-table walk.

The serving tier's decode used to round-trip the block pool through XLA —
gather every active slot's pages into a contiguous ``(L, bs, max_seq, …)``
cache, run the full-window attention, scatter one cell back.  These
kernels run the same math *in place* over the pool leaves:

* grid = ``(bs,)`` — one program per active slot;
* the slot's page-table row ``(max_pages,)`` and its position are
  **scalar-prefetched** (``pltpu.PrefetchScalarGridSpec``, the same idiom
  as the engine's ragged grid walk in ``engine/megakernel.py``), so SMEM
  integers drive every page load;
* the program first writes the new token's K/V into its single
  ``(page, offset)`` cell through the **aliased** pool output refs, then
  walks pages ``0 .. pos // page_size`` with a flash-attention-style
  online softmax (running max / normalizer / accumulator with correction
  factors, the ``layers.sdpa_chunked`` recurrence) — work bounded by the
  ``ceil((pos+1)/page_size)`` pages the slot actually occupies, never by
  ``max_seq``;
* positions beyond ``pos`` inside the last page are masked ``-inf``, so
  stale contents of reused pages are unreadable by construction (the
  block-pool safety contract, property-tested in
  ``tests/test_paged_properties.py``).

Visibility/aliasing contract (same as the engine megakernels): the pool
leaves are whole-array resident blocks with constant index maps, aliased
input→output; program 0 copies input→output refs and every later program
loads/stores through the output refs.  Admission guarantees distinct slots
own disjoint page sets, so the per-slot programs of one launch touch
pairwise-disjoint pool rows — the grid is safe to execute in any order,
exactly the write-coloring argument that makes a decode round one phase.

Two flavors share the walk structure:

* ``_gqa_kernel`` — dense/GQA: K/V pools ``(P, ps, Hkv, hd)``; the query
  heads arrive grouped per KV head as ``(Hkv, n_rep, hd)`` and each page is
  transposed to ``(Hkv, ps, hd)``, so both contractions lead with the KV
  head as their batch axis (the form Mosaic lowers) and no KV head is
  repeated;
* ``_mla_kernel`` — DeepSeek MLA, weight-absorbed: pools hold the
  compressed latent ``(P, ps, lat)`` plus the shared RoPE key
  ``(P, ps, rope)``; scores are ``q_eff·c_kv + q_rope·k_rope`` and the
  context stays in latent space (re-expansion through ``w_uv`` happens
  outside, as in ``models/mla.py::mla_decode``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _default_interpret(interpret: Optional[bool]) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _full(a):
    """Whole-array resident block with a constant index map (state stays
    in registers/VMEM across the sequential grid)."""
    return pl.BlockSpec(a.shape, lambda t, *_, nd=a.ndim: (0,) * nd)


def _seed_aliased(in_refs, out_refs) -> None:
    """Program 0 copies the aliased pool inputs into the output refs;
    interpret mode seeds aliased outputs anyway, but compiled backends
    leave output windows undefined until written."""
    @pl.when(pl.program_id(0) == 0)
    def _():
        for i_ref, o_ref in zip(in_refs, out_refs):
            o_ref[...] = i_ref[...]


def _online_softmax_walk(pt_ref, t, p_t, page_size, heads, v_width,
                         score_fn, value_fn):
    """Shared flash-style page walk: fold pages ``0 .. p_t//page_size``
    of slot ``t`` into ``(m, l, acc)`` carries.  ``heads`` is the leading
    shape of the head axes (``(H,)`` or ``(Hkv, n_rep)``);
    ``score_fn(pid) -> (*heads, ps)`` unmasked f32 scores for one page;
    ``value_fn(pid, w) -> (*heads, v_width)`` the weighted value/latent
    contribution."""
    off_in_page = jax.lax.broadcasted_iota(
        jnp.int32, (1,) * len(heads) + (page_size,), len(heads))

    def body(p, carry):
        m, l, acc = carry
        pid = pt_ref[t, p]
        s = score_fn(pid)                          # (*heads, ps) f32
        kpos = p * page_size + off_in_page         # (1, ..., 1, ps)
        s = jnp.where(kpos <= p_t, s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        w = jnp.exp(s - m_new)                             # masked -> 0
        corr = jnp.exp(m - m_new)                          # first page: 0
        l_new = l * corr + jnp.sum(w, axis=-1, keepdims=True)
        acc_new = acc * corr + value_fn(pid, w)
        return m_new, l_new, acc_new

    init = (jnp.full(heads + (1,), -jnp.inf, jnp.float32),
            jnp.zeros(heads + (1,), jnp.float32),
            jnp.zeros(heads + (v_width,), jnp.float32))
    n_pages = p_t // page_size + 1     # pages the slot occupies incl. pos
    m, l, acc = jax.lax.fori_loop(0, n_pages, body, init)
    return acc / l


def _row(ref, i):
    """Load row ``i`` of a leading-axis stack, squeezing the axis."""
    return ref[(pl.ds(i, 1),) + (slice(None),) * (len(ref.shape) - 1)][0]


def _put_cell(ref, page, off, val):
    """Store ``val`` (cell-shaped) at ``ref[page, off]`` as a
    read-modify-write of the whole page: a store at a dynamic offset on
    the page's sublane axis (the MLA pools' ``ps``) is not addressable on
    TPU, a whole page is."""
    blk = ref[pl.ds(page, 1)]                      # (1, ps, ...)
    cell = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 1) == off
    ref[pl.ds(page, 1)] = jnp.where(cell, val.reshape(
        (1, 1) + blk.shape[2:]).astype(blk.dtype), blk)


def _gqa_kernel(pt_ref, pos_ref, q_ref, kn_ref, vn_ref, kp_in, vp_in,
                o_ref, kp_ref, vp_ref, *, page_size: int, scale: float):
    t = pl.program_id(0)
    _seed_aliased((kp_in, vp_in), (kp_ref, vp_ref))
    p_t = pos_ref[t]

    # write the new token's K/V into its (page, offset) cell first, so the
    # walk below reads it back like every earlier position (mask <= p_t)
    pg = pt_ref[t, p_t // page_size]
    off = p_t % page_size
    _put_cell(kp_ref, pg, off, _row(kn_ref, t))
    _put_cell(vp_ref, pg, off, _row(vn_ref, t))

    q_t = _row(q_ref, t).astype(jnp.float32)        # (Hkv, n_rep, hd)
    hd = q_t.shape[-1]

    def page(ref, pid):                             # (Hkv, ps, hd) f32
        return jnp.swapaxes(_row(ref, pid).astype(jnp.float32), 0, 1)

    def score(pid):
        return jnp.einsum("krd,kpd->krp", q_t, page(kp_ref, pid),
                          preferred_element_type=jnp.float32) * scale

    def value(pid, w):
        return jnp.einsum("krp,kpd->krd", w, page(vp_ref, pid),
                          preferred_element_type=jnp.float32)

    out = _online_softmax_walk(pt_ref, t, p_t, page_size, q_t.shape[:2],
                               hd, score, value)
    o_ref[pl.ds(t, 1), :, :, :] = out.astype(o_ref.dtype)[None]


def _mla_kernel(pt_ref, pos_ref, qe_ref, qr_ref, cn_ref, rn_ref,
                cp_in, rp_in, ctx_ref, cp_ref, rp_ref, *, page_size: int,
                scale: float):
    t = pl.program_id(0)
    _seed_aliased((cp_in, rp_in), (cp_ref, rp_ref))
    p_t = pos_ref[t]

    pg = pt_ref[t, p_t // page_size]
    off = p_t % page_size
    _put_cell(cp_ref, pg, off, _row(cn_ref, t))
    _put_cell(rp_ref, pg, off, _row(rn_ref, t))

    q_eff = _row(qe_ref, t).astype(jnp.float32)            # (H, lat)
    q_rope = _row(qr_ref, t).astype(jnp.float32)           # (H, rope)
    n_heads, lat = q_eff.shape

    def score(pid):
        cb = _row(cp_ref, pid).astype(jnp.float32)         # (ps, lat)
        rb = _row(rp_ref, pid).astype(jnp.float32)         # (ps, rope)
        s = (jnp.einsum("hl,pl->hp", q_eff, cb,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("hr,pr->hp", q_rope, rb,
                          preferred_element_type=jnp.float32))
        return s * scale

    def value(pid, w):
        cb = _row(cp_ref, pid).astype(jnp.float32)
        return jnp.einsum("hp,pl->hl", w, cb,
                          preferred_element_type=jnp.float32)

    ctx = _online_softmax_walk(pt_ref, t, p_t, page_size, (n_heads,), lat,
                               score, value)
    ctx_ref[pl.ds(t, 1), :, :] = ctx.astype(ctx_ref.dtype)[None]


def paged_gqa_call(q, k_new, v_new, k_pool, v_pool, page_rows, pos, *,
                   page_size: int, interpret: Optional[bool] = None):
    """Raw kernel launch for the GQA flavor (see ``ops.paged_gqa_decode``
    for the documented public signature)."""
    bs, n_heads, hd = q.shape
    n_kv = k_pool.shape[2]
    # query head h reads KV head h // n_rep: group the heads per KV head
    qg = q.reshape(bs, n_kv, n_heads // n_kv, hd)
    kern = functools.partial(_gqa_kernel, page_size=page_size,
                             scale=hd ** -0.5)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bs,),
        in_specs=[_full(a) for a in (qg, k_new, v_new, k_pool, v_pool)],
        out_specs=(_full(qg), _full(k_pool), _full(v_pool)),
    )
    o, k_pool, v_pool = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct(qg.shape, q.dtype),
                   jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)),
        # inputs: [0]=page_rows [1]=pos [2]=q [3]=k_new [4]=v_new
        #         [5]=k_pool [6]=v_pool;  pools alias outputs 1/2
        input_output_aliases={5: 1, 6: 2},
        interpret=_default_interpret(interpret),
        name="paged_decode",
    )(page_rows.astype(jnp.int32), pos.astype(jnp.int32),
      qg, k_new, v_new, k_pool, v_pool)
    return o.reshape(q.shape), k_pool, v_pool


def paged_mla_call(q_eff, q_rope, c_new, r_new, c_pool, r_pool, page_rows,
                   pos, *, page_size: int, scale: float,
                   interpret: Optional[bool] = None):
    """Raw kernel launch for the MLA flavor (see ``ops.paged_mla_decode``)."""
    bs = q_eff.shape[0]
    # the new latent rows ride as (bs, 1, width) so the kernel picks slot
    # t's row on an untiled leading axis (a dynamic row of a packed bf16
    # (bs, width) block is not addressable on TPU)
    c_new, r_new = c_new[:, None, :], r_new[:, None, :]
    kern = functools.partial(_mla_kernel, page_size=page_size, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bs,),
        in_specs=[_full(a) for a in (q_eff, q_rope, c_new, r_new,
                                     c_pool, r_pool)],
        out_specs=(_full(q_eff), _full(c_pool), _full(r_pool)),
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct(q_eff.shape, q_eff.dtype),
                   jax.ShapeDtypeStruct(c_pool.shape, c_pool.dtype),
                   jax.ShapeDtypeStruct(r_pool.shape, r_pool.dtype)),
        # inputs: [0]=page_rows [1]=pos [2]=q_eff [3]=q_rope [4]=c_new
        #         [5]=r_new [6]=c_pool [7]=r_pool; pools alias outputs 1/2
        input_output_aliases={6: 1, 7: 2},
        interpret=_default_interpret(interpret),
        name="paged_decode_mla",
    )(page_rows.astype(jnp.int32), pos.astype(jnp.int32),
      q_eff, q_rope, c_new, r_new, c_pool, r_pool)

"""Pallas TPU kernel: paged MLA decode over the compressed latent stream,
with the current token's latent write fused in.

MLA decode (weight-absorbed form) attends directly against the compressed
cache: logits = q_abs·ckv + q_rope·krope, context = probs·ckv.  Both terms
are one contraction against the row-wise concat ``[ckv; krope]`` — exactly
what the latent page pool stores: ``[P, page_size, Dp]`` where the first
``latent_width = kv_lora_rank + rope_head_dim`` features are live and Dp is
padded to the TPU lane width at init (never per step).

Per step this kernel:
  * merges the token's latent row into page ``bt[b, pos//ps]`` slot
    ``pos%ps`` (one page block read and written back — the dense path's
    one-hot rewrite of [B, S, r] vanishes);
  * walks the row's live pages via scalar-prefetched block tables,
    double-buffering each page HBM→VMEM, with split-K online softmax;
  * accumulates the latent context from the ckv half of each page.

Grid is (B,): the latent stream is shared across query heads (that is the
point of MLA), so one program serves the whole head group of one row.  The
pool is an ANY-space ref aliased input→output for the in-place write.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.paged_decode_attention import (_merge_rows, _merge_scales,
                                                  _quantize_rows,
                                                  _rewrite_page)

NEG_INF = -1e30


def _kernel(bt_ref, pos_ref, q_ref, ln_ref, lp_in, o_ref, lp,
            buf, dsem, *, ps: int, r: int, width: int, scale: float):
    b = pl.program_id(0)
    pos = pos_ref[b]
    kv_len = pos + 1
    n_pages = (kv_len + ps - 1) // ps

    # -- fused write: current latent row -> its page slot -------------------
    page_raw = bt_ref[b, pos // ps]
    slot_w = pos % ps

    # The written page is also read below (self-attention of the new token);
    # _rewrite_page waits, so the walk sees the merged page.
    @pl.when(page_raw >= 0)
    def _write():
        _rewrite_page(lp.at[pl.ds(page_raw, 1)], buf.at[pl.ds(0, 1)],
                      dsem.at[0],
                      lambda old: _merge_rows(old[0], ln_ref[0], -slot_w,
                                              1)[None])

    # -- split-K online softmax over the row's live pages -------------------
    def page_dma(i, slot):
        pg = jnp.maximum(bt_ref[b, i], 0)
        return pltpu.make_async_copy(
            lp.at[pl.ds(pg, 1)], buf.at[pl.ds(slot, 1)], dsem.at[slot])

    page_dma(0, 0).start()

    q = q_ref[0].astype(jnp.float32)                      # [H, width]
    h = q.shape[0]

    def body(i, carry):
        m, l, acc = carry
        slot = jax.lax.rem(i, 2)
        nxt = jax.lax.rem(i + 1, 2)

        @pl.when(i + 1 < n_pages)
        def _prefetch():
            page_dma(i + 1, nxt).start()

        page_dma(i, slot).wait()
        lat = buf[slot].astype(jnp.float32)               # [ps, Dp]
        s = jax.lax.dot_general(
            q, lat[:, :width], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [H, ps]
        cols = i * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
        s = jnp.where(cols < kv_len, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l * alpha + jnp.sum(p, axis=1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p, lat[:, :r], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [H, r]
        return m_new, l_new, acc_new

    m0 = jnp.full((h,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((h,), jnp.float32)
    a0 = jnp.zeros((h, r), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_pages, body, (m0, l0, a0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("r", "scale", "interpret"))
def paged_mla_decode(q: jax.Array, latent_pages: jax.Array,
                     block_tables: jax.Array, pos: jax.Array,
                     latent_new: jax.Array, *, r: int, scale: float,
                     interpret: bool = False
                     ) -> tuple[jax.Array, jax.Array]:
    """q: [B, H, width] absorbed queries concat([q_abs; q_rope]);
    latent_pages: [P, ps, Dp] (Dp >= width, first r features are ckv);
    block_tables: i32[B, maxp]; pos: i32[B]; latent_new: [B, Dp].
    Returns (ctx [B, H, r] f32, latent_pages) with the token's latent row
    written at slot ``pos`` (pool updated in place via aliasing)."""
    b, h, width = q.shape
    _, ps, dp = latent_pages.shape
    grid = (b,)

    q_spec = pl.BlockSpec((1, h, width), lambda i, *_: (i, 0, 0))
    # [B, 1, Dp]: a block's last two dims must be whole array dims.
    tok_spec = pl.BlockSpec((1, 1, dp), lambda i, *_: (i, 0, 0))
    out_spec = pl.BlockSpec((1, h, r), lambda i, *_: (i, 0, 0))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,              # block_tables, pos
        grid=grid,
        in_specs=[q_spec, tok_spec, any_spec],
        out_specs=[out_spec, any_spec],
        scratch_shapes=[
            pltpu.VMEM((2, ps, dp), latent_pages.dtype),     # double buffer
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    kernel = functools.partial(_kernel, ps=ps, r=r, width=width, scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, h, r), jnp.float32),
            jax.ShapeDtypeStruct(latent_pages.shape, latent_pages.dtype),
        ],
        # Input indices count the scalar-prefetch operands (0, 1).
        input_output_aliases={4: 1},
        interpret=interpret,
    )(block_tables, pos, q, latent_new.reshape(b, 1, dp), latent_pages)


def _kernel_quant(bt_ref, pos_ref, q_ref, ln_ref, lp_in, ls_in, o_ref,
                  lp, ls, buf, sbuf, dsem, ssem,
                  *, ps: int, r: int, width: int, scale: float,
                  qmax: float, qdtype):
    """Quantized twin of ``_kernel``: latent pool int8/fp8 + per-row f32
    scales [P, ps].  The token's latent row quantizes in-kernel; value and
    scale share the fused write phase, the walk DMAs each page's scale row
    alongside the page, and dequant is one multiply post-load."""
    b = pl.program_id(0)
    pos = pos_ref[b]
    kv_len = pos + 1
    n_pages = (kv_len + ps - 1) // ps

    # -- fused write: quantize the latent row, merge value + scale ----------
    page_raw = bt_ref[b, pos // ps]
    slot_w = pos % ps
    lq, lscale = _quantize_rows(ln_ref[0].astype(jnp.float32), qdtype, qmax)

    @pl.when(page_raw >= 0)
    def _write():
        _rewrite_page(lp.at[pl.ds(page_raw, 1)], buf.at[pl.ds(0, 1)],
                      dsem.at[0],
                      lambda old: _merge_rows(old[0], lq, -slot_w, 1)[None])
        _rewrite_page(ls.at[pl.ds(page_raw, 1)], sbuf.at[pl.ds(0, 1)],
                      ssem.at[0],
                      lambda old: _merge_scales(old, lscale, -slot_w, 1))

    # -- split-K online softmax, dequant fused into the walk ----------------
    def page_dma(i, slot):
        pg = jnp.maximum(bt_ref[b, i], 0)
        return pltpu.make_async_copy(
            lp.at[pl.ds(pg, 1)], buf.at[pl.ds(slot, 1)], dsem.at[slot])

    def scale_dma(i, slot):
        pg = jnp.maximum(bt_ref[b, i], 0)
        return pltpu.make_async_copy(
            ls.at[pl.ds(pg, 1)], sbuf.at[pl.ds(slot, 1)], ssem.at[slot])

    page_dma(0, 0).start()
    scale_dma(0, 0).start()

    q = q_ref[0].astype(jnp.float32)                      # [H, width]
    h = q.shape[0]

    def body(i, carry):
        m, l, acc = carry
        slot = jax.lax.rem(i, 2)
        nxt = jax.lax.rem(i + 1, 2)

        @pl.when(i + 1 < n_pages)
        def _prefetch():
            page_dma(i + 1, nxt).start()
            scale_dma(i + 1, nxt).start()

        page_dma(i, slot).wait()
        scale_dma(i, slot).wait()
        lat = buf[slot].astype(jnp.float32) * sbuf[slot][:, None]
        s = jax.lax.dot_general(
            q, lat[:, :width], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [H, ps]
        cols = i * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
        s = jnp.where(cols < kv_len, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l * alpha + jnp.sum(p, axis=1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p, lat[:, :r], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [H, r]
        return m_new, l_new, acc_new

    m0 = jnp.full((h,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((h,), jnp.float32)
    a0 = jnp.zeros((h, r), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_pages, body, (m0, l0, a0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("r", "scale", "qmax", "interpret"))
def paged_mla_decode_quant(q: jax.Array, latent_pages: jax.Array,
                           latent_scales: jax.Array,
                           block_tables: jax.Array, pos: jax.Array,
                           latent_new: jax.Array, *, r: int, scale: float,
                           qmax: float, interpret: bool = False):
    """Quantized-pool MLA decode: latent_pages [P, ps, Dp] int8/fp8 with
    latent_scales [P, ps] f32; latent_new arrives FLOAT [B, Dp] and is
    quantized in-kernel.  Returns (ctx [B, H, r] f32, latent_pages,
    latent_scales) — pool + scales updated in place via aliasing."""
    b, h, width = q.shape
    _, ps, dp = latent_pages.shape
    grid = (b,)

    q_spec = pl.BlockSpec((1, h, width), lambda i, *_: (i, 0, 0))
    # [B, 1, Dp]: a block's last two dims must be whole array dims.
    tok_spec = pl.BlockSpec((1, 1, dp), lambda i, *_: (i, 0, 0))
    out_spec = pl.BlockSpec((1, h, r), lambda i, *_: (i, 0, 0))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,              # block_tables, pos
        grid=grid,
        in_specs=[q_spec, tok_spec, any_spec, any_spec],
        out_specs=[out_spec, any_spec, any_spec],
        scratch_shapes=[
            pltpu.VMEM((2, ps, dp), latent_pages.dtype),     # double buffer
            pltpu.VMEM((2, ps), jnp.float32),                # page scales
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    kernel = functools.partial(_kernel_quant, ps=ps, r=r, width=width,
                               scale=scale, qmax=qmax,
                               qdtype=latent_pages.dtype)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, h, r), jnp.float32),
            jax.ShapeDtypeStruct(latent_pages.shape, latent_pages.dtype),
            jax.ShapeDtypeStruct(latent_scales.shape, latent_scales.dtype),
        ],
        # Input indices count the scalar-prefetch operands (0, 1).
        input_output_aliases={4: 1, 5: 2},
        interpret=interpret,
    )(block_tables, pos, q, latent_new.reshape(b, 1, dp), latent_pages,
      latent_scales)

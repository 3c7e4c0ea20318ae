"""Pallas TPU kernels: chunked paged attention with a fused multi-slot write.

The token-budget mixed serve step composes, per batch row, a query *span* —
1 token for rows that are decoding, up to C tokens for rows whose prompt is
being admitted chunk by chunk.  These kernels are the hot path of that step:

  * **fused multi-slot KV write** — the span's K/V rows are merged into
    their page slots (pages ``bt[b, (start+j)//ps]``, slots
    ``(start+j) % ps``, j < span) *before* the attend, one page block at a
    time (read, merge in VMEM, write back), so intra-span causality falls
    out of the ordinary block-table walk: by the time query j reads a page,
    every key at a position ≤ start+j is already resident.  A span may
    straddle page boundaries; ``-1`` table entries drop;
  * **block-table walk over the cached prefix** — double-buffered page DMA
    HBM→VMEM with split-K online softmax, exactly the decode kernel's
    schedule, but carrying [group·C] query rows instead of [group];
  * **causal intra-chunk masking** — query j masks columns > start + j (and
    below the sliding window, when one applies), so one kernel serves spans
    of any width: span 1 degenerates to the fused decode kernel.

MHA variant: grid (B, Hkv), pools ``[P, Hkv, ps, D]``.  MLA-latent variant:
grid (B,), pool ``[P, ps, Dp]`` storing concat([ckv; krope]) rows; queries
arrive pre-absorbed (concat([q_abs; q_rope])) so both logits terms are one
contraction, as in kernels/paged_mla_decode.py.

Each ``pallas_call`` names its kernel (``paged_chunk_attention``,
``paged_mla_chunk``, and the ``_quant`` twins), so a profiler trace finds
it by that name whatever jitted function wraps it.

The pools are ANY-space refs aliased input→output (in-place update on TPU).
Alignment follows the decode kernels: ``page_size`` a multiple of the
sublane count and the lane dim a multiple of 128 on real TPU; interpret
mode runs any shape.
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


def _span_pages(bt_ref, b, start, span, ps, write_page):
    """Call ``write_page(page, base)`` for every allocated page the span
    ``start..start+span`` touches, where page row r holds span token
    r + base.  ``-1`` table entries and slots past the table drop."""
    maxp = bt_ref.shape[1]
    first = start // ps
    last = jnp.minimum((start + span - 1) // ps, maxp - 1)

    def body(i, _):
        page = bt_ref[b, i]

        @pl.when(page >= 0)
        def _():
            write_page(page, i * ps - start)
        return 0

    jax.lax.fori_loop(first, jnp.where(span > 0, last + 1, first), body, 0)


def _mha_kernel(bt_ref, start_ref, span_ref, q_ref, kn_ref, vn_ref,
                kp_in, vp_in, o_ref, kp, vp, kbuf, vbuf, ksem, vsem,
                *, ps: int, c: int, scale: float, window: int | None):
    b = pl.program_id(0)
    h = pl.program_id(1)
    start = start_ref[b]
    span = span_ref[b]
    kv_len = start + span                      # tokens resident after write
    maxp = bt_ref.shape[1]
    n_pages = jnp.minimum((jnp.maximum(kv_len, 1) + ps - 1) // ps, maxp)

    # -- fused multi-slot write: span tokens -> their page slots ------------
    # Every touched page is rewritten (and waited) before the walk, so the
    # walk below reads the pages the span just wrote.
    def write_page(page, base):
        for pool, buf, sem, new in ((kp, kbuf, ksem, kn_ref),
                                    (vp, vbuf, vsem, vn_ref)):
            _rewrite_page(
                pool.at[pl.ds(page, 1), pl.ds(h, 1)],
                buf.at[pl.ds(0, 1)], sem.at[0],
                lambda old, new=new: _merge_rows(
                    old[0, 0], new[0, 0], base, span)[None, None])

    _span_pages(bt_ref, b, start, span, ps, write_page)

    # -- split-K online softmax over the row's live pages -------------------
    def page_dma(pool, buf, sem, i, slot):
        pg = jnp.maximum(bt_ref[b, i], 0)
        return pltpu.make_async_copy(
            pool.at[pl.ds(pg, 1), pl.ds(h, 1)], buf.at[pl.ds(slot, 1)],
            sem.at[slot])

    page_dma(kp, kbuf, ksem, 0, 0).start()
    page_dma(vp, vbuf, vsem, 0, 0).start()

    q = q_ref[0].astype(jnp.float32)                   # [group, C, D]
    group, _, d = q.shape
    qf = q.reshape(group * c, d)
    # Query row g*C + j carries intra-span offset j -> absolute start + j.
    qpos = start + jax.lax.broadcasted_iota(jnp.int32, (group * c, ps), 0) % c

    def body(i, carry):
        m, l, acc = carry
        slot = jax.lax.rem(i, 2)
        nxt = jax.lax.rem(i + 1, 2)

        @pl.when(i + 1 < n_pages)
        def _prefetch():
            page_dma(kp, kbuf, ksem, i + 1, nxt).start()
            page_dma(vp, vbuf, vsem, i + 1, nxt).start()

        page_dma(kp, kbuf, ksem, i, slot).wait()
        page_dma(vp, vbuf, vsem, i, slot).wait()
        k = kbuf[slot, 0].astype(jnp.float32)          # [ps, D]
        v = vbuf[slot, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            qf, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # [group*C, ps]
        cols = i * ps + jax.lax.broadcasted_iota(jnp.int32, (group * c, ps), 1)
        valid = cols <= qpos
        if window is not None:
            valid &= cols > qpos - window
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l * alpha + jnp.sum(p, axis=1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((group * c,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((group * c,), jnp.float32)
    a0 = jnp.zeros((group * c, d), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_pages, body, (m0, l0, a0))
    out = acc / jnp.maximum(l, 1e-30)[:, None]
    o_ref[0] = out.reshape(group, c, d).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "window", "interpret"))
def paged_chunk_attention(q: jax.Array, k_pages: jax.Array,
                          v_pages: jax.Array, block_tables: jax.Array,
                          start: jax.Array, span: jax.Array,
                          k_new: jax.Array, v_new: jax.Array, *,
                          scale: float, window: int | None = None,
                          interpret: bool = False
                          ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """q: [B, Hq, C, D]; k/v_pages: [P, Hkv, ps, D]; block_tables: i32[B,
    maxp]; start/span: i32[B]; k/v_new: [B, Hkv, C, D] (pool dtype).
    Returns (out [B, Hq, C, D], k_pages, v_pages) with the span written at
    slots ``start..start+span`` (pools updated in place via aliasing)."""
    b, hq, c, d = q.shape
    _, hkv, ps, _ = k_pages.shape
    group = hq // hkv
    grid = (b, hkv)

    q_spec = pl.BlockSpec((1, group, c, d), lambda i, j, *_: (i, j, 0, 0))
    tok_spec = pl.BlockSpec((1, 1, c, d), lambda i, j, *_: (i, j, 0, 0))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,              # block_tables, start, span
        grid=grid,
        in_specs=[q_spec, tok_spec, tok_spec, any_spec, any_spec],
        out_specs=[q_spec, any_spec, any_spec],
        scratch_shapes=[
            pltpu.VMEM((2, 1, ps, d), k_pages.dtype),   # k page double-buffer
            pltpu.VMEM((2, 1, ps, d), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    kernel = functools.partial(_mha_kernel, ps=ps, c=c, scale=scale,
                               window=window)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
        ],
        # Input indices count the scalar-prefetch operands (0, 1, 2).
        input_output_aliases={6: 1, 7: 2},
        interpret=interpret,
        name="paged_chunk_attention",
    )(block_tables, start, span, q, k_new, v_new, k_pages, v_pages)


def _mha_kernel_quant(bt_ref, start_ref, span_ref, q_ref, kn_ref, vn_ref,
                      kp_in, vp_in, ks_in, vs_in, o_ref, kp, vp, ks, vs,
                      kbuf, vbuf, ksbuf, vsbuf, ksem, vsem, kssem, vssem,
                      *, ps: int, c: int, scale: float, window: int | None,
                      qmax: float, qdtype):
    """Quantized twin of ``_mha_kernel``: the span's K/V rows quantize
    in-kernel (one scale per token per KV head), values and scales land in
    the same fused multi-slot write phase, and the walk dequantizes each
    page with its DMA'd scale row."""
    b = pl.program_id(0)
    h = pl.program_id(1)
    start = start_ref[b]
    span = span_ref[b]
    kv_len = start + span
    maxp = bt_ref.shape[1]
    n_pages = jnp.minimum((jnp.maximum(kv_len, 1) + ps - 1) // ps, maxp)

    # -- fused multi-slot write: quantize span rows, merge values + scales --
    kq, kscales = _quantize_rows(kn_ref[0, 0].astype(jnp.float32),
                                 qdtype, qmax)               # [C, D], [C, 1]
    vq, vscales = _quantize_rows(vn_ref[0, 0].astype(jnp.float32),
                                 qdtype, qmax)

    def write_page(page, base):
        dst = (pl.ds(page, 1), pl.ds(h, 1))
        for pool, buf, sem, new in ((kp, kbuf, ksem, kq), (vp, vbuf, vsem, vq)):
            _rewrite_page(
                pool.at[dst], buf.at[pl.ds(0, 1)], sem.at[0],
                lambda old, new=new: _merge_rows(
                    old[0, 0], new, base, span)[None, None])
        for pool, buf, sem, new in ((ks, ksbuf, kssem, kscales),
                                    (vs, vsbuf, vssem, vscales)):
            _rewrite_page(
                pool.at[dst], buf.at[pl.ds(0, 1)], sem.at[0],
                lambda old, new=new: _merge_scales(
                    old[0], new, base, span)[None])

    _span_pages(bt_ref, b, start, span, ps, write_page)

    # -- split-K online softmax, dequant fused into the walk ----------------
    def page_dma(pool, buf, sem, i, slot):
        pg = jnp.maximum(bt_ref[b, i], 0)
        return pltpu.make_async_copy(
            pool.at[pl.ds(pg, 1), pl.ds(h, 1)], buf.at[pl.ds(slot, 1)],
            sem.at[slot])

    page_dma(kp, kbuf, ksem, 0, 0).start()
    page_dma(vp, vbuf, vsem, 0, 0).start()
    page_dma(ks, ksbuf, kssem, 0, 0).start()
    page_dma(vs, vsbuf, vssem, 0, 0).start()

    q = q_ref[0].astype(jnp.float32)                   # [group, C, D]
    group, _, d = q.shape
    qf = q.reshape(group * c, d)
    qpos = start + jax.lax.broadcasted_iota(jnp.int32, (group * c, ps), 0) % c

    def body(i, carry):
        m, l, acc = carry
        slot = jax.lax.rem(i, 2)
        nxt = jax.lax.rem(i + 1, 2)

        @pl.when(i + 1 < n_pages)
        def _prefetch():
            page_dma(kp, kbuf, ksem, i + 1, nxt).start()
            page_dma(vp, vbuf, vsem, i + 1, nxt).start()
            page_dma(ks, ksbuf, kssem, i + 1, nxt).start()
            page_dma(vs, vsbuf, vssem, i + 1, nxt).start()

        page_dma(kp, kbuf, ksem, i, slot).wait()
        page_dma(vp, vbuf, vsem, i, slot).wait()
        page_dma(ks, ksbuf, kssem, i, slot).wait()
        page_dma(vs, vsbuf, vssem, i, slot).wait()
        k = kbuf[slot, 0].astype(jnp.float32) * ksbuf[slot, 0][:, None]
        v = vbuf[slot, 0].astype(jnp.float32) * vsbuf[slot, 0][:, None]
        s = jax.lax.dot_general(
            qf, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # [group*C, ps]
        cols = i * ps + jax.lax.broadcasted_iota(jnp.int32, (group * c, ps), 1)
        valid = cols <= qpos
        if window is not None:
            valid &= cols > qpos - window
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l * alpha + jnp.sum(p, axis=1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((group * c,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((group * c,), jnp.float32)
    a0 = jnp.zeros((group * c, d), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_pages, body, (m0, l0, a0))
    out = acc / jnp.maximum(l, 1e-30)[:, None]
    o_ref[0] = out.reshape(group, c, d).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "window", "qmax", "interpret"))
def paged_chunk_attention_quant(q: jax.Array, k_pages: jax.Array,
                                k_scales: jax.Array, v_pages: jax.Array,
                                v_scales: jax.Array,
                                block_tables: jax.Array, start: jax.Array,
                                span: jax.Array, k_new: jax.Array,
                                v_new: jax.Array, *, scale: float,
                                qmax: float, window: int | None = None,
                                interpret: bool = False):
    """Quantized-pool chunked attention: k/v_pages [P, Hkv, ps, D] int8/fp8
    with k/v_scales [P, Hkv, ps] f32; k/v_new arrive FLOAT [B, Hkv, C, D]
    and quantize in-kernel.  Returns (out, k_pages, v_pages, k_scales,
    v_scales) — pools + scales updated in place via aliasing."""
    b, hq, c, d = q.shape
    _, hkv, ps, _ = k_pages.shape
    group = hq // hkv
    grid = (b, hkv)

    q_spec = pl.BlockSpec((1, group, c, d), lambda i, j, *_: (i, j, 0, 0))
    tok_spec = pl.BlockSpec((1, 1, c, d), lambda i, j, *_: (i, j, 0, 0))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,              # block_tables, start, span
        grid=grid,
        in_specs=[q_spec, tok_spec, tok_spec,
                  any_spec, any_spec, any_spec, any_spec],
        out_specs=[q_spec, any_spec, any_spec, any_spec, any_spec],
        scratch_shapes=[
            pltpu.VMEM((2, 1, ps, d), k_pages.dtype),   # quantized pages
            pltpu.VMEM((2, 1, ps, d), v_pages.dtype),
            pltpu.VMEM((2, 1, ps), jnp.float32),        # page scale rows
            pltpu.VMEM((2, 1, ps), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    kernel = functools.partial(_mha_kernel_quant, ps=ps, c=c, scale=scale,
                               window=window, qmax=qmax,
                               qdtype=k_pages.dtype)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
            jax.ShapeDtypeStruct(k_scales.shape, k_scales.dtype),
            jax.ShapeDtypeStruct(v_scales.shape, v_scales.dtype),
        ],
        # Input indices count the scalar-prefetch operands (0, 1, 2).
        input_output_aliases={6: 1, 7: 2, 8: 3, 9: 4},
        interpret=interpret,
        name="paged_chunk_attention_quant",
    )(block_tables, start, span, q, k_new, v_new,
      k_pages, v_pages, k_scales, v_scales)


def _mla_kernel(bt_ref, start_ref, span_ref, q_ref, ln_ref, lp_in,
                o_ref, lp, buf, dsem, *, ps: int, c: int,
                r: int, width: int, scale: float):
    b = pl.program_id(0)
    start = start_ref[b]
    span = span_ref[b]
    kv_len = start + span
    maxp = bt_ref.shape[1]
    n_pages = jnp.minimum((jnp.maximum(kv_len, 1) + ps - 1) // ps, maxp)

    # -- fused multi-slot write: span latent rows -> their page slots -------
    def write_page(page, base):
        _rewrite_page(lp.at[pl.ds(page, 1)], buf.at[pl.ds(0, 1)], dsem.at[0],
                      lambda old: _merge_rows(old[0], ln_ref[0], base,
                                              span)[None])

    _span_pages(bt_ref, b, start, span, ps, write_page)

    # -- split-K online softmax over the row's live pages -------------------
    def page_dma(i, slot):
        pg = jnp.maximum(bt_ref[b, i], 0)
        return pltpu.make_async_copy(
            lp.at[pl.ds(pg, 1)], buf.at[pl.ds(slot, 1)], dsem.at[slot])

    page_dma(0, 0).start()

    q = q_ref[0].astype(jnp.float32)                   # [H, C, width]
    h = q.shape[0]
    qf = q.reshape(h * c, width)
    qpos = start + jax.lax.broadcasted_iota(jnp.int32, (h * c, ps), 0) % c

    def body(i, carry):
        m, l, acc = carry
        slot = jax.lax.rem(i, 2)
        nxt = jax.lax.rem(i + 1, 2)

        @pl.when(i + 1 < n_pages)
        def _prefetch():
            page_dma(i + 1, nxt).start()

        page_dma(i, slot).wait()
        lat = buf[slot].astype(jnp.float32)            # [ps, Dp]
        s = jax.lax.dot_general(
            qf, lat[:, :width], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # [H*C, ps]
        cols = i * ps + jax.lax.broadcasted_iota(jnp.int32, (h * c, ps), 1)
        s = jnp.where(cols <= qpos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l * alpha + jnp.sum(p, axis=1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p, lat[:, :r], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [H*C, r]
        return m_new, l_new, acc_new

    m0 = jnp.full((h * c,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((h * c,), jnp.float32)
    a0 = jnp.zeros((h * c, r), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_pages, body, (m0, l0, a0))
    out = acc / jnp.maximum(l, 1e-30)[:, None]
    o_ref[0] = out.reshape(h, c, r).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("r", "scale", "interpret"))
def paged_mla_chunk(q: jax.Array, latent_pages: jax.Array,
                    block_tables: jax.Array, start: jax.Array,
                    span: jax.Array, latent_new: jax.Array, *, r: int,
                    scale: float, interpret: bool = False
                    ) -> tuple[jax.Array, jax.Array]:
    """q: [B, H, C, width] absorbed queries concat([q_abs; q_rope]);
    latent_pages: [P, ps, Dp] (Dp >= width, first r features are ckv);
    block_tables: i32[B, maxp]; start/span: i32[B]; latent_new: [B, C, Dp].
    Returns (ctx [B, H, C, r] f32, latent_pages) with the span's latent rows
    written at slots ``start..start+span`` (pool updated in place)."""
    b, h, c, width = q.shape
    _, ps, dp = latent_pages.shape
    grid = (b,)

    q_spec = pl.BlockSpec((1, h, c, width), lambda i, *_: (i, 0, 0, 0))
    tok_spec = pl.BlockSpec((1, c, dp), lambda i, *_: (i, 0, 0))
    out_spec = pl.BlockSpec((1, h, c, r), lambda i, *_: (i, 0, 0, 0))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,              # block_tables, start, span
        grid=grid,
        in_specs=[q_spec, tok_spec, any_spec],
        out_specs=[out_spec, any_spec],
        scratch_shapes=[
            pltpu.VMEM((2, ps, dp), latent_pages.dtype),     # double buffer
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    kernel = functools.partial(_mla_kernel, ps=ps, c=c, r=r, width=width,
                               scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, h, c, r), jnp.float32),
            jax.ShapeDtypeStruct(latent_pages.shape, latent_pages.dtype),
        ],
        # Input indices count the scalar-prefetch operands (0, 1, 2).
        input_output_aliases={5: 1},
        interpret=interpret,
        name="paged_mla_chunk",
    )(block_tables, start, span, q, latent_new, latent_pages)


def _mla_kernel_quant(bt_ref, start_ref, span_ref, q_ref, ln_ref, lp_in,
                      ls_in, o_ref, lp, ls, buf, sbuf, dsem, ssem,
                      *, ps: int, c: int,
                      r: int, width: int, scale: float, qmax: float, qdtype):
    """Quantized twin of ``_mla_kernel``: span latent rows quantize
    in-kernel (one scale per token), write fused with their scales, and the
    walk dequantizes each page with its DMA'd scale row."""
    b = pl.program_id(0)
    start = start_ref[b]
    span = span_ref[b]
    kv_len = start + span
    maxp = bt_ref.shape[1]
    n_pages = jnp.minimum((jnp.maximum(kv_len, 1) + ps - 1) // ps, maxp)

    # -- fused multi-slot write: quantize span rows, merge values + scales --
    lq, lscales = _quantize_rows(ln_ref[0].astype(jnp.float32),
                                 qdtype, qmax)               # [C, Dp], [C, 1]

    def write_page(page, base):
        _rewrite_page(lp.at[pl.ds(page, 1)], buf.at[pl.ds(0, 1)], dsem.at[0],
                      lambda old: _merge_rows(old[0], lq, base, span)[None])
        _rewrite_page(ls.at[pl.ds(page, 1)], sbuf.at[pl.ds(0, 1)],
                      ssem.at[0],
                      lambda old: _merge_scales(old, lscales, base, span))

    _span_pages(bt_ref, b, start, span, ps, write_page)

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

    q = q_ref[0].astype(jnp.float32)                   # [H, C, width]
    h = q.shape[0]
    qf = q.reshape(h * c, width)
    qpos = start + jax.lax.broadcasted_iota(jnp.int32, (h * c, ps), 0) % c

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
            qf, lat[:, :width], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # [H*C, ps]
        cols = i * ps + jax.lax.broadcasted_iota(jnp.int32, (h * c, ps), 1)
        s = jnp.where(cols <= qpos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l * alpha + jnp.sum(p, axis=1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p, lat[:, :r], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [H*C, r]
        return m_new, l_new, acc_new

    m0 = jnp.full((h * c,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((h * c,), jnp.float32)
    a0 = jnp.zeros((h * c, r), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_pages, body, (m0, l0, a0))
    out = acc / jnp.maximum(l, 1e-30)[:, None]
    o_ref[0] = out.reshape(h, c, r).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("r", "scale", "qmax", "interpret"))
def paged_mla_chunk_quant(q: jax.Array, latent_pages: jax.Array,
                          latent_scales: jax.Array,
                          block_tables: jax.Array, start: jax.Array,
                          span: jax.Array, latent_new: jax.Array, *,
                          r: int, scale: float, qmax: float,
                          interpret: bool = False):
    """Quantized-pool chunked MLA: latent_pages [P, ps, Dp] int8/fp8 with
    latent_scales [P, ps] f32; latent_new arrives FLOAT [B, C, Dp] and
    quantizes in-kernel.  Returns (ctx [B, H, C, r] f32, latent_pages,
    latent_scales) — pool + scales updated in place via aliasing."""
    b, h, c, width = q.shape
    _, ps, dp = latent_pages.shape
    grid = (b,)

    q_spec = pl.BlockSpec((1, h, c, width), lambda i, *_: (i, 0, 0, 0))
    tok_spec = pl.BlockSpec((1, c, dp), lambda i, *_: (i, 0, 0))
    out_spec = pl.BlockSpec((1, h, c, r), lambda i, *_: (i, 0, 0, 0))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,              # block_tables, start, span
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
    kernel = functools.partial(_mla_kernel_quant, ps=ps, c=c, r=r,
                               width=width, scale=scale, qmax=qmax,
                               qdtype=latent_pages.dtype)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, h, c, r), jnp.float32),
            jax.ShapeDtypeStruct(latent_pages.shape, latent_pages.dtype),
            jax.ShapeDtypeStruct(latent_scales.shape, latent_scales.dtype),
        ],
        # Input indices count the scalar-prefetch operands (0, 1, 2).
        input_output_aliases={5: 1, 6: 2},
        interpret=interpret,
        name="paged_mla_chunk_quant",
    )(block_tables, start, span, q, latent_new,
      latent_pages, latent_scales)

"""Pallas TPU kernel: paged flash-decode attention with a fused KV write.

The KV cache lives in a shared page pool ``[P, Hkv, page_size, D]``; each
batch row owns an ordered list of pages (its block table).  Decode is
HBM-bandwidth-bound, and the dense-cache step additionally pays an
O(B·max_len) one-hot *write* per layer just to place one token.  This kernel
removes both costs:

  * the current token's K/V is merged into its page slot before the attend
    (one page block read and written back, O(ps·D) bytes) — the write is
    fused, so the one-hot full-cache rewrite disappears;
  * the attend walks only the row's live pages (block-table indirection via
    scalar prefetch), streaming each page HBM→VMEM once with double-buffered
    DMA and split-K online softmax in VMEM carries.

Grid is (B, Hkv); each program handles one row's GQA group of query heads
against one KV head.  The page pools are ANY-space (HBM) refs aliased
input→output, so XLA updates them in place — the kernel's writes are the
only pool bytes that move.

Alignment: on real TPU the pool layout must be tileable — ``page_size``
a multiple of the sublane count and ``head_dim`` a multiple of 128; the
quantized twin's f32 scale rows are ``page_size`` lanes wide, so there it
must be a multiple of 128.  The ops wrapper enforces this with a clear
error; off-TPU (interpret mode) any shape runs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _quantize_rows(x, qdtype, qmax):
    """Per-row symmetric quantization — the in-kernel twin of
    ``kernels.ref.quantize_rows`` (identical ops, so the pool bytes the
    kernel writes match the oracle's bit for bit).  Returns the quantized
    rows and their scales as a [..., 1] column (keepdims, so the kernels
    never reshape a rank-1 vector)."""
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    # Multiply by the reciprocal EXPLICITLY: XLA rewrites division by a
    # constant into it anyway, but only in some compilation paths — an
    # explicit multiply keeps kernel and oracle scales bit-identical.
    scale = jnp.where(amax > 0, amax * np.float32(1.0 / qmax), 1.0)
    scaled = x / scale
    if qdtype == jnp.int8:
        q = jnp.clip(jnp.round(scaled), -qmax, qmax).astype(jnp.int8)
    else:
        q = scaled.astype(qdtype)
    return q, scale.astype(jnp.float32)


# -- fused page writes ------------------------------------------------------
# Mosaic tiles every VMEM and HBM buffer, so a DMA of ONE token row of a
# packed dtype (bf16 packs 2 rows per sublane, int8/fp8 pack 4) is a
# partial tile and is refused.  The kernels therefore write a page at a
# time: DMA the [ps, w] page block into VMEM, merge the new rows there,
# and DMA the whole block back.  Every merge below selects exactly one
# source per element, so the pool bytes match the oracle's scatter bitwise.

def _merge_rows(old, new, base, count):
    """old: [ps, w] page rows; new: [c, w] span rows.  Page row r takes
    new[r + base] when 0 <= r + base < count, else keeps old[r]."""
    ps, c = old.shape[0], new.shape[0]
    tpos = base + jax.lax.broadcasted_iota(jnp.int32, (ps, 1), 0)
    take = (tpos >= 0) & (tpos < count)
    if c == 1:
        picked = jnp.broadcast_to(new.astype(jnp.float32), old.shape)
    else:
        # One-hot gather on the MXU: one nonzero term per output element,
        # so the contraction is exact (bf16 holds every int8/fp8 value).
        # Rows past ``count`` are zeroed first: they hold whatever the
        # padded positions computed, and 0 * NaN would poison every row.
        f32 = old.dtype == jnp.float32
        wide = jnp.float32 if f32 else jnp.bfloat16
        onehot = jax.lax.broadcasted_iota(jnp.int32, (ps, c), 1) == tpos
        live = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0) < count
        picked = jax.lax.dot_general(
            onehot.astype(wide), jnp.where(live, new, 0).astype(wide),
            (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST if f32 else None,
            preferred_element_type=jnp.float32)
    return jnp.where(take, picked, old.astype(jnp.float32)).astype(old.dtype)


def _merge_scales(old, new, base, count):
    """old: [1, ps] f32 scale row; new: [c, 1] span scales.  Lane r takes
    new[r + base] when 0 <= r + base < count (exact select-and-sum)."""
    ps, c = old.shape[1], new.shape[0]
    tpos = base + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
    take = (tpos >= 0) & (tpos < count)
    hit = jax.lax.broadcasted_iota(jnp.int32, (c, ps), 0) == (
        base + jax.lax.broadcasted_iota(jnp.int32, (c, ps), 1))
    picked = jnp.sum(jnp.where(hit, new, 0.0), axis=0, keepdims=True)
    return jnp.where(take, picked, old)


def _rewrite_page(page, buf, sem, merge):
    """Read-modify-write one page block: ``page`` (an HBM ref view) ->
    ``buf`` (a same-shape VMEM ref view) -> ``merge`` -> back.  Waits for
    the write so a walk that follows reads the merged page."""
    rd = pltpu.make_async_copy(page, buf, sem)
    rd.start()
    rd.wait()
    buf[...] = merge(buf[...])
    wr = pltpu.make_async_copy(buf, page, sem)
    wr.start()
    wr.wait()


def _kernel(bt_ref, pos_ref, q_ref, kn_ref, vn_ref, kp_in, vp_in,
            o_ref, kp, vp, kbuf, vbuf, ksem, vsem,
            *, ps: int, scale: float, window: int | None):
    b = pl.program_id(0)
    h = pl.program_id(1)
    pos = pos_ref[b]
    kv_len = pos + 1
    n_pages = (kv_len + ps - 1) // ps

    # -- fused write: current token's K/V -> its page slot ------------------
    page_raw = bt_ref[b, pos // ps]
    slot_w = pos % ps

    # An unallocated (-1) entry drops the write — same semantics as the
    # oracle's mode="drop" scatter — so an idle row never corrupts page 0.
    # The write page is also read below (the new token attends to itself);
    # _rewrite_page waits, so the walk sees it.
    @pl.when(page_raw >= 0)
    def _write():
        for pool, buf, sem, new in ((kp, kbuf, ksem, kn_ref),
                                    (vp, vbuf, vsem, vn_ref)):
            _rewrite_page(
                pool.at[pl.ds(page_raw, 1), pl.ds(h, 1)],
                buf.at[pl.ds(0, 1)], sem.at[0],
                lambda old, new=new: _merge_rows(
                    old[0, 0], new[0, 0], -slot_w, 1)[None, None])

    # -- split-K online softmax over the row's live pages -------------------
    def page_dma(pool, buf, sem, i, slot):
        pg = jnp.maximum(bt_ref[b, i], 0)
        return pltpu.make_async_copy(
            pool.at[pl.ds(pg, 1), pl.ds(h, 1)], buf.at[pl.ds(slot, 1)],
            sem.at[slot])

    page_dma(kp, kbuf, ksem, 0, 0).start()
    page_dma(vp, vbuf, vsem, 0, 0).start()

    q = q_ref[0, 0].astype(jnp.float32)                    # [group, D]
    group, d = q.shape

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
        k = kbuf[slot, 0].astype(jnp.float32)              # [ps, D]
        v = vbuf[slot, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # [group, ps]
        cols = i * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
        valid = cols < kv_len
        if window is not None:
            valid &= cols > pos - window
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l * alpha + jnp.sum(p, axis=1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((group,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((group,), jnp.float32)
    a0 = jnp.zeros((group, d), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_pages, body, (m0, l0, a0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def _head_blocks(hkv, q, k_new, v_new):
    """Split heads so every block's last two dims are whole array dims
    (Mosaic's rule): q [B, Hq, D] -> [B, Hkv, group, D]; k/v_new
    [B, Hkv, D] -> [B, Hkv, 1, D].  Returns the reshaped arrays and the
    matching BlockSpecs for grid (B, Hkv)."""
    b, hq, d = q.shape
    group = hq // hkv
    q4 = q.reshape(b, hkv, group, d)
    q_spec = pl.BlockSpec((1, 1, group, d), lambda i, j, *_: (i, j, 0, 0))
    tok_spec = pl.BlockSpec((1, 1, 1, d), lambda i, j, *_: (i, j, 0, 0))
    return (q4, k_new.reshape(b, hkv, 1, d), v_new.reshape(b, hkv, 1, d),
            q_spec, tok_spec)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "window", "interpret"))
def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, block_tables: jax.Array,
                           pos: jax.Array, k_new: jax.Array,
                           v_new: jax.Array, *, scale: float,
                           window: int | None = None,
                           interpret: bool = False
                           ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """q: [B, Hq, D]; k/v_pages: [P, Hkv, ps, D]; block_tables: i32[B, maxp];
    pos: i32[B] (tokens already cached); k/v_new: [B, Hkv, D] (pool dtype).
    Returns (out [B, Hq, D], k_pages, v_pages) with the token written at
    slot ``pos`` of each row (pools updated in place via aliasing)."""
    _, hkv, ps, d = k_pages.shape
    q4, kn, vn, q_spec, tok_spec = _head_blocks(hkv, q, k_new, v_new)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,              # block_tables, pos
        grid=(q.shape[0], hkv),
        in_specs=[q_spec, tok_spec, tok_spec, any_spec, any_spec],
        out_specs=[q_spec, any_spec, any_spec],
        scratch_shapes=[
            pltpu.VMEM((2, 1, ps, d), k_pages.dtype),   # k page double-buffer
            pltpu.VMEM((2, 1, ps, d), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    kernel = functools.partial(_kernel, ps=ps, scale=scale, window=window)
    out, kp, vp = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(q4.shape, q.dtype),
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
        ],
        # Input indices count the scalar-prefetch operands (0, 1).
        input_output_aliases={5: 1, 6: 2},
        interpret=interpret,
    )(block_tables, pos, q4, kn, vn, k_pages, v_pages)
    return out.reshape(q.shape), kp, vp


def _kernel_quant(bt_ref, pos_ref, q_ref, kn_ref, vn_ref, kp_in, vp_in,
                  ks_in, vs_in, o_ref, kp, vp, ks, vs,
                  kbuf, vbuf, ksbuf, vsbuf, ksem, vsem, kssem, vssem,
                  *, ps: int, scale: float, window: int | None,
                  qmax: float, qdtype):
    """Quantized twin of ``_kernel``: pools hold int8/fp8 rows + a per-row
    f32 scale pool riding alongside.  The current token is quantized
    in-kernel and its value row AND scale land in the same fused write
    phase; the page walk DMAs the scale block with its page and dequant is
    a single multiply after the VMEM load — the HBM bytes/step are the
    quantized page plus ps floats of scale."""
    b = pl.program_id(0)
    h = pl.program_id(1)
    pos = pos_ref[b]
    kv_len = pos + 1
    n_pages = (kv_len + ps - 1) // ps

    # -- fused write: quantize the token, merge value row + scale -----------
    page_raw = bt_ref[b, pos // ps]
    slot_w = pos % ps
    kq, kscale = _quantize_rows(kn_ref[0, 0].astype(jnp.float32),
                                qdtype, qmax)                # [1, D], [1, 1]
    vq, vscale = _quantize_rows(vn_ref[0, 0].astype(jnp.float32),
                                qdtype, qmax)

    @pl.when(page_raw >= 0)
    def _write():
        dst = (pl.ds(page_raw, 1), pl.ds(h, 1))
        for pool, buf, sem, new in ((kp, kbuf, ksem, kq), (vp, vbuf, vsem, vq)):
            _rewrite_page(
                pool.at[dst], buf.at[pl.ds(0, 1)], sem.at[0],
                lambda old, new=new: _merge_rows(
                    old[0, 0], new, -slot_w, 1)[None, None])
        for pool, buf, sem, new in ((ks, ksbuf, kssem, kscale),
                                    (vs, vsbuf, vssem, vscale)):
            _rewrite_page(
                pool.at[dst], buf.at[pl.ds(0, 1)], sem.at[0],
                lambda old, new=new: _merge_scales(
                    old[0], new, -slot_w, 1)[None])

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

    q = q_ref[0, 0].astype(jnp.float32)                    # [group, D]
    group, d = q.shape

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
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # [group, ps]
        cols = i * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
        valid = cols < kv_len
        if window is not None:
            valid &= cols > pos - window
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l * alpha + jnp.sum(p, axis=1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((group,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((group,), jnp.float32)
    a0 = jnp.zeros((group, d), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_pages, body, (m0, l0, a0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "window", "qmax", "interpret"))
def paged_decode_attention_quant(q: jax.Array, k_pages: jax.Array,
                                 k_scales: jax.Array, v_pages: jax.Array,
                                 v_scales: jax.Array,
                                 block_tables: jax.Array, pos: jax.Array,
                                 k_new: jax.Array, v_new: jax.Array, *,
                                 scale: float, qmax: float,
                                 window: int | None = None,
                                 interpret: bool = False):
    """Quantized-pool decode: k/v_pages [P, Hkv, ps, D] int8/fp8 with
    k/v_scales [P, Hkv, ps] f32; k/v_new arrive FLOAT and are quantized
    in-kernel.  Returns (out, k_pages, v_pages, k_scales, v_scales) with
    pools + scales updated in place via aliasing."""
    _, hkv, ps, d = k_pages.shape
    q4, kn, vn, q_spec, tok_spec = _head_blocks(hkv, q, k_new, v_new)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,              # block_tables, pos
        grid=(q.shape[0], hkv),
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
    kernel = functools.partial(_kernel_quant, ps=ps, scale=scale,
                               window=window, qmax=qmax, qdtype=k_pages.dtype)
    out, *pools = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(q4.shape, q.dtype),
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
            jax.ShapeDtypeStruct(k_scales.shape, k_scales.dtype),
            jax.ShapeDtypeStruct(v_scales.shape, v_scales.dtype),
        ],
        # Input indices count the scalar-prefetch operands (0, 1).
        input_output_aliases={5: 1, 6: 2, 7: 3, 8: 4},
        interpret=interpret,
    )(block_tables, pos, q4, kn, vn, k_pages, v_pages, k_scales, v_scales)
    return (out.reshape(q.shape), *pools)

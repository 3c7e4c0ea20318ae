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
    HBM→VMEM with split-K online softmax, carrying [group·C] query rows
    per KV head;
  * **causal intra-chunk masking** — query j masks columns > start + j (and
    below the sliding window, when one applies), so one kernel serves spans
    of any width: span 1 degenerates to the fused decode kernel.

Pools arrive as the stacks of every layer, ``[L, P, ...]``, with the
layer index as a scalar-prefetch operand (``layer``, i32[1]): a kernel
DMAs the pages of that layer straight out of the stack and aliases the
whole stack input→output, so the layer scan that calls it carries the
stacks and no step slices, copies or writes back a layer's pool.  A
caller that holds one layer's pool passes a stack of one
(``kernels.ops`` does: the leading axis is a free reshape).

MHA variant, pools ``[L, P, Hkv, ps, D]``: grid (B, Hkv / hb), one program
per row and block of hb KV heads.  A page is contiguous across heads, so
one DMA moves a page for all hb heads.  The walk takes ppb pages per step:
their DMAs start together, the next block's are in flight while this one
computes, and each step is one batched contraction over the heads
([hb, group·C, D] x [hb, ppb·ps, D]) and one online-softmax update.  hb
and ppb follow from the shapes against a VMEM budget (``_walk_blocks``):
every KV head while two such pages fit, then as many pages as fit.  A
row's last block may run past its pages: those are not fetched, their
columns are masked and their V slots zeroed.  A span-0 row skips both the
write and the walk.  The quantized twin keeps grid (B, Hkv) and one page
per step.  MLA-latent variant: grid (B,), pool ``[L, P, ps, Dp]`` storing
concat([ckv; krope]) rows; queries arrive pre-absorbed (concat([q_abs;
q_rope])) so both logits terms are one contraction, as in
kernels/paged_mla_decode.py.  A span-1 row of a wider step walks with its
H live query rows alone and writes zeros at its padded positions; every
other row carries all H·C.

Each ``pallas_call`` names its kernel (``paged_chunk_attention``,
``paged_mla_chunk``, and the ``_quant`` twins), so a profiler trace finds
it by that name whatever jitted function wraps it.

The pool stacks are ANY-space refs aliased input→output (in-place update
on TPU).  Alignment follows the decode kernels: ``page_size`` a multiple
of the sublane count and the lane dim a multiple of 128 on real TPU;
interpret mode runs any shape.
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


def _merge_head_rows(old, new, base, count):
    """``_merge_rows`` for every head of a page at once.  old: [h, ps, w]
    page rows; new: [h, c, w] span rows.  Page row r of each head takes
    new[:, r + base] when 0 <= r + base < count, else keeps old."""
    h, ps, w = old.shape
    c = new.shape[1]
    tpos = base + jax.lax.broadcasted_iota(jnp.int32, (1, ps, 1), 1)
    take = (tpos >= 0) & (tpos < count)
    if c == 1:
        picked = jnp.broadcast_to(new.astype(jnp.float32), old.shape)
    else:
        # One-hot gather on the MXU, as in ``_merge_rows``: one nonzero
        # term per output element, so the contraction is exact; span rows
        # past ``count`` (padded positions' garbage) are zeroed first.
        f32 = old.dtype == jnp.float32
        wide = jnp.float32 if f32 else jnp.bfloat16
        onehot = jax.lax.broadcasted_iota(jnp.int32, (h, ps, c), 2) == tpos
        live = jax.lax.broadcasted_iota(jnp.int32, (1, c, 1), 1) < count
        picked = jnp.einsum(
            "hpc,hcw->hpw", onehot.astype(wide),
            jnp.where(live, new, 0).astype(wide),
            precision=jax.lax.Precision.HIGHEST if f32 else None,
            preferred_element_type=jnp.float32)
    return jnp.where(take, picked, old.astype(jnp.float32)).astype(old.dtype)


# VMEM one walk block may fill: the K and V page buffers (two slots each),
# the f32 upcast of V, and the block's f32 scores and probabilities.  Half
# of v5e's 16 MiB scoped default, so the pipelined q/out/new-token blocks
# and the online-softmax carries fit beside it.
_BLOCK_VMEM = 8 << 20


def _walk_blocks(hkv: int, rows: int, ps: int, d: int, itemsize: int,
                 maxp: int) -> tuple[int, int]:
    """(KV heads per program, pages per walk block) for the MHA kernel.

    A page of the ``[P, Hkv, ps, D]`` pool is contiguous across heads, so
    one DMA fetches it for a block of heads.  Take every KV head when two
    such pages fit ``_BLOCK_VMEM``, else the widest divisor of Hkv that
    does; then as many pages per block as the budget holds, at most the
    table's width.  ``rows`` = group·C query rows per KV head."""
    rows = -(-rows // 8) * 8                   # f32 sublane tile

    def page_bytes(hb):                        # VMEM per page of a block
        return hb * ps * (4 * d * itemsize     # K and V, two slots each
                          + 4 * d              # V upcast to f32
                          + 8 * rows)          # f32 scores, probabilities

    hb = max(h for h in range(1, hkv + 1)
             if hkv % h == 0 and (h == 1 or 2 * page_bytes(h) <= _BLOCK_VMEM))
    return hb, max(1, min(maxp, _BLOCK_VMEM // page_bytes(hb)))


def _mha_kernel(layer_ref, bt_ref, start_ref, span_ref, q_ref, kn_ref,
                vn_ref, kp_in, vp_in, o_ref, kp, vp, kbuf, vbuf, ksem, vsem,
                *, ps: int, c: int, scale: float, window: int | None):
    # One program per (row, block of hb KV heads).  kbuf/vbuf: [2, hb,
    # ppb, ps, D] -- two slots of one walk block, pages laid out per head
    # so a head's block is one [ppb*ps, D] run.
    b = pl.program_id(0)
    _, hb, ppb, _, d = kbuf.shape
    kp, vp = kp.at[layer_ref[0]], vp.at[layer_ref[0]]
    heads = pl.ds(pl.program_id(1) * hb, hb)
    start = start_ref[b]
    span = span_ref[b]
    kv_len = start + span                      # tokens resident after write
    maxp = bt_ref.shape[1]
    rows = q_ref.shape[2]                      # group*C query rows per head
    width = ppb * ps                           # key columns per block

    # A span-0 row is idle: it writes nothing and its output is garbage.
    @pl.when(span > 0)
    def _row():
        # -- fused multi-slot write: span tokens -> their page slots --------
        # Every touched page is read, merged for all hb heads and written
        # back (and waited) before the walk, so the walk reads what the
        # span just wrote.  Slot 0 of the walk buffers stages the page.
        def write_page(page, base):
            for pool, buf, sem, new in ((kp, kbuf, ksem, kn_ref),
                                        (vp, vbuf, vsem, vn_ref)):
                hbm, vmem = pool.at[page, heads], buf.at[0, :, 0]
                rd = pltpu.make_async_copy(hbm, vmem, sem.at[0])
                rd.start()
                rd.wait()
                vmem[...] = _merge_head_rows(vmem[...], new[0], base, span)
                wr = pltpu.make_async_copy(vmem, hbm, sem.at[0])
                wr.start()
                wr.wait()

        _span_pages(bt_ref, b, start, span, ps, write_page)

        # -- block walk: ppb pages per step, all in flight at once ----------
        n_pages = jnp.minimum((kv_len + ps - 1) // ps, maxp)
        n_blocks = (n_pages + ppb - 1) // ppb

        def live(blk):                         # the block's pages in use
            return jnp.clip(n_pages - blk * ppb, 0, ppb)

        def page_dmas(blk, slot, j):
            pg = jnp.maximum(bt_ref[b, blk * ppb + j], 0)
            return [pltpu.make_async_copy(pool.at[pg, heads],
                                          buf.at[slot, :, j], sem.at[slot])
                    for pool, buf, sem in ((kp, kbuf, ksem),
                                           (vp, vbuf, vsem))]

        def fetch(blk, slot):
            def begin(j, _):
                for dma in page_dmas(blk, slot, j):
                    dma.start()
                return 0
            jax.lax.fori_loop(0, live(blk), begin, 0)

        def land(blk, slot):
            def wait(j, _):
                for dma in page_dmas(blk, slot, j):
                    dma.wait()
                return 0

            # Pages past the row's end are never fetched; their V slots are
            # zeroed so p = 0 there meets finite values in P.V.
            def zero(j, _):
                vbuf[slot, :, j] = jnp.zeros((hb, ps, d), vbuf.dtype)
                return 0
            jax.lax.fori_loop(0, live(blk), wait, 0)
            jax.lax.fori_loop(live(blk), ppb, zero, 0)

        fetch(0, 0)
        # QK^T takes q and K in the wider of their dtypes, never narrower
        # (bf16 products are exact in f32); P.V keeps p and V in f32.
        q = q_ref[0]                                   # [hb, rows, D]
        qk_dtype = jnp.promote_types(q.dtype, kbuf.dtype)
        q = q.astype(qk_dtype)
        # Query row g*C + j carries intra-span offset j -> position start+j.
        qpos = start + jax.lax.broadcasted_iota(
            jnp.int32, (1, rows, width), 1) % c

        def body(blk, carry):
            m, l, acc = carry
            slot = jax.lax.rem(blk, 2)
            fetch(blk + 1, 1 - slot)           # empty past the last block
            land(blk, slot)
            k = kbuf[slot].reshape(hb, width, d).astype(qk_dtype)
            v = vbuf[slot].reshape(hb, width, d).astype(jnp.float32)
            s = jnp.einsum("hrd,htd->hrt", q, k,
                           preferred_element_type=jnp.float32) * scale
            cols = blk * width + jax.lax.broadcasted_iota(
                jnp.int32, (1, rows, width), 2)
            valid = cols <= qpos
            if window is not None:
                valid &= cols > qpos - window
            s = jnp.where(valid, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=2, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l_new = l * alpha + jnp.sum(p, axis=2, keepdims=True)
            acc_new = acc * alpha + jnp.einsum(
                "hrt,htd->hrd", p, v, preferred_element_type=jnp.float32)
            return m_new, l_new, acc_new

        m0 = jnp.full((hb, rows, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((hb, rows, 1), jnp.float32)
        a0 = jnp.zeros((hb, rows, d), jnp.float32)
        _, l, acc = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, a0))
        o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "window", "interpret"))
def paged_chunk_attention(q: jax.Array, k_pages: jax.Array,
                          v_pages: jax.Array, block_tables: jax.Array,
                          start: jax.Array, span: jax.Array,
                          k_new: jax.Array, v_new: jax.Array, *,
                          layer: jax.Array, scale: float,
                          window: int | None = None,
                          interpret: bool = False
                          ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """q: [B, Hq, C, D]; k/v_pages: [L, P, Hkv, ps, D] stacks addressed at
    ``layer`` (i32[1]); block_tables: i32[B, maxp]; start/span: i32[B];
    k/v_new: [B, Hkv, C, D] (pool dtype).  Returns (out [B, Hq, C, D],
    k_pages, v_pages) with the span written at slots ``start..start+span``
    of that layer (pools updated in place via aliasing, every other layer
    untouched)."""
    b, hq, c, d = q.shape
    _, _, hkv, ps, _ = k_pages.shape
    rows = hq // hkv * c
    hb, ppb = _walk_blocks(hkv, rows, ps, d, k_pages.dtype.itemsize,
                           block_tables.shape[1])
    # A KV head's query rows (its GQA group x the span) as one [rows, D].
    q = q.reshape(b, hkv, rows, d)

    q_spec = pl.BlockSpec((1, hb, rows, d), lambda i, j, *_: (i, j, 0, 0))
    tok_spec = pl.BlockSpec((1, hb, c, d), lambda i, j, *_: (i, j, 0, 0))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,              # layer, block_tables, start, span
        grid=(b, hkv // hb),
        in_specs=[q_spec, tok_spec, tok_spec, any_spec, any_spec],
        out_specs=[q_spec, any_spec, any_spec],
        scratch_shapes=[
            pltpu.VMEM((2, hb, ppb, ps, d), k_pages.dtype),  # walk blocks
            pltpu.VMEM((2, hb, ppb, ps, d), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    kernel = functools.partial(_mha_kernel, ps=ps, c=c, scale=scale,
                               window=window)
    out, k_pages, v_pages = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
        ],
        # Input indices count the scalar-prefetch operands (0 .. 3).
        input_output_aliases={7: 1, 8: 2},
        interpret=interpret,
        name="paged_chunk_attention",
    )(layer, block_tables, start, span, q, k_new, v_new, k_pages, v_pages)
    return out.reshape(b, hq, c, d), k_pages, v_pages


def _mha_kernel_quant(layer_ref, bt_ref, start_ref, span_ref, q_ref, kn_ref,
                      vn_ref, kp_in, vp_in, ks_in, vs_in, o_ref, kp, vp, ks,
                      vs, kbuf, vbuf, ksbuf, vsbuf, ksem, vsem, kssem, vssem,
                      *, ps: int, c: int, scale: float, window: int | None,
                      qmax: float, qdtype):
    """Quantized twin of ``_mha_kernel``: the span's K/V rows quantize
    in-kernel (one scale per token per KV head), values and scales land in
    the same fused multi-slot write phase, and the walk dequantizes each
    page with its DMA'd scale row."""
    b = pl.program_id(0)
    h = pl.program_id(1)
    kp, vp, ks, vs = (r.at[layer_ref[0]] for r in (kp, vp, ks, vs))
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
                                v_new: jax.Array, *,
                                layer: jax.Array, scale: float, qmax: float,
                                window: int | None = None,
                                interpret: bool = False):
    """Quantized-pool chunked attention: k/v_pages [L, P, Hkv, ps, D]
    int8/fp8 with k/v_scales [L, P, Hkv, ps] f32, addressed at ``layer``
    (i32[1]); k/v_new arrive FLOAT [B, Hkv, C, D] and quantize in-kernel.
    Returns (out, k_pages, v_pages, k_scales, v_scales) — pools + scales
    updated in place via aliasing."""
    b, hq, c, d = q.shape
    _, _, hkv, ps, _ = k_pages.shape
    group = hq // hkv
    grid = (b, hkv)

    q_spec = pl.BlockSpec((1, group, c, d), lambda i, j, *_: (i, j, 0, 0))
    tok_spec = pl.BlockSpec((1, 1, c, d), lambda i, j, *_: (i, j, 0, 0))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,              # layer, block_tables, start, span
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
        # Input indices count the scalar-prefetch operands (0 .. 3).
        input_output_aliases={7: 1, 8: 2, 9: 3, 10: 4},
        interpret=interpret,
        name="paged_chunk_attention_quant",
    )(layer, block_tables, start, span, q, k_new, v_new,
      k_pages, v_pages, k_scales, v_scales)


def _mla_kernel(layer_ref, bt_ref, start_ref, span_ref, q_ref, ln_ref, lp_in,
                o_ref, lp, buf, dsem, *, ps: int, c: int,
                r: int, width: int, scale: float):
    b = pl.program_id(0)
    lp = lp.at[layer_ref[0]]
    start = start_ref[b]
    span = span_ref[b]
    kv_len = start + span
    maxp = bt_ref.shape[1]
    n_pages = jnp.minimum((jnp.maximum(kv_len, 1) + ps - 1) // ps, maxp)
    h = q_ref.shape[1]

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

    def walk(qf, qpos):
        """Context [rows, r] of the f32 query rows ``qf`` [rows, width] at
        positions ``qpos`` (broadcast against [rows, ps]): both kinds of
        row take this one walk."""
        rows = qf.shape[0]
        page_dma(0, 0).start()

        def body(i, carry):
            m, l, acc = carry
            slot = jax.lax.rem(i, 2)

            @pl.when(i + 1 < n_pages)
            def _prefetch():
                page_dma(i + 1, jax.lax.rem(i + 1, 2)).start()

            page_dma(i, slot).wait()
            lat = buf[slot].astype(jnp.float32)        # [ps, Dp]
            s = jax.lax.dot_general(
                qf, lat[:, :width], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [rows, ps]
            cols = i * ps + jax.lax.broadcasted_iota(jnp.int32, (rows, ps), 1)
            s = jnp.where(cols <= qpos, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[:, None])
            l_new = l * alpha + jnp.sum(p, axis=1)
            acc_new = acc * alpha[:, None] + jax.lax.dot_general(
                p, lat[:, :r], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)    # [rows, r]
            return m_new, l_new, acc_new

        m0 = jnp.full((rows,), NEG_INF, jnp.float32)
        l0 = jnp.zeros((rows,), jnp.float32)
        a0 = jnp.zeros((rows, r), jnp.float32)
        _, l, acc = jax.lax.fori_loop(0, n_pages, body, (m0, l0, a0))
        return acc / jnp.maximum(l, 1e-30)[:, None]

    def walk_all():
        # Query row h*C + j carries intra-span offset j -> position start+j.
        qf = q_ref[0].astype(jnp.float32).reshape(h * c, width)
        qpos = start + jax.lax.broadcasted_iota(jnp.int32, (h * c, ps), 0) % c
        o_ref[0] = walk(qf, qpos).reshape(h, c, r).astype(o_ref.dtype)

    if c == 1:                      # every row's live queries are its H rows
        walk_all()
        return

    # A span-1 row (a decoding row of a wider step) has H live query rows of
    # its H x C: walk with those alone.  Its padded positions read zeros.
    @pl.when(span == 1)
    def _one():
        ctx = walk(q_ref[0, :, 0, :].astype(jnp.float32), start)   # [H, r]
        pos = jax.lax.broadcasted_iota(jnp.int32, (h, c, r), 1)
        o_ref[0] = jnp.where(pos == 0, ctx[:, None, :],
                             0.0).astype(o_ref.dtype)

    pl.when(span != 1)(walk_all)


@functools.partial(
    jax.jit, static_argnames=("r", "scale", "interpret"))
def paged_mla_chunk(q: jax.Array, latent_pages: jax.Array,
                    block_tables: jax.Array, start: jax.Array,
                    span: jax.Array, latent_new: jax.Array, *, r: int,
                    layer: jax.Array, scale: float, interpret: bool = False
                    ) -> tuple[jax.Array, jax.Array]:
    """q: [B, H, C, width] absorbed queries concat([q_abs; q_rope]);
    latent_pages: [L, P, ps, Dp] stack addressed at ``layer`` (i32[1];
    Dp >= width, first r features are ckv);
    block_tables: i32[B, maxp]; start/span: i32[B]; latent_new: [B, C, Dp].
    Returns (ctx [B, H, C, r] f32, latent_pages) with the span's latent rows
    written at slots ``start..start+span`` of that layer (pool updated in
    place)."""
    b, h, c, width = q.shape
    _, _, ps, dp = latent_pages.shape
    grid = (b,)

    q_spec = pl.BlockSpec((1, h, c, width), lambda i, *_: (i, 0, 0, 0))
    tok_spec = pl.BlockSpec((1, c, dp), lambda i, *_: (i, 0, 0))
    out_spec = pl.BlockSpec((1, h, c, r), lambda i, *_: (i, 0, 0, 0))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,              # layer, block_tables, start, span
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
        # Input indices count the scalar-prefetch operands (0 .. 3).
        input_output_aliases={6: 1},
        interpret=interpret,
        name="paged_mla_chunk",
    )(layer, block_tables, start, span, q, latent_new,
      latent_pages)


def _mla_kernel_quant(layer_ref, bt_ref, start_ref, span_ref, q_ref, ln_ref,
                      lp_in, ls_in, o_ref, lp, ls, buf, sbuf, dsem, ssem,
                      *, ps: int, c: int, r: int, width: int, scale: float,
                      qmax: float, qdtype):
    """Quantized twin of ``_mla_kernel``: span latent rows quantize
    in-kernel (one scale per token), write fused with their scales, and the
    walk dequantizes each page with its DMA'd scale row."""
    b = pl.program_id(0)
    lp, ls = lp.at[layer_ref[0]], ls.at[layer_ref[0]]
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
                          r: int, layer: jax.Array, scale: float,
                          qmax: float, interpret: bool = False):
    """Quantized-pool chunked MLA: latent_pages [L, P, ps, Dp] int8/fp8 with
    latent_scales [L, P, ps] f32, addressed at ``layer`` (i32[1]);
    latent_new arrives FLOAT [B, C, Dp] and quantizes in-kernel.  Returns
    (ctx [B, H, C, r] f32, latent_pages, latent_scales) — pool + scales
    updated in place via aliasing."""
    b, h, c, width = q.shape
    _, _, ps, dp = latent_pages.shape
    grid = (b,)

    q_spec = pl.BlockSpec((1, h, c, width), lambda i, *_: (i, 0, 0, 0))
    tok_spec = pl.BlockSpec((1, c, dp), lambda i, *_: (i, 0, 0))
    out_spec = pl.BlockSpec((1, h, c, r), lambda i, *_: (i, 0, 0, 0))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,              # layer, block_tables, start, span
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
        # Input indices count the scalar-prefetch operands (0 .. 3).
        input_output_aliases={6: 1, 7: 2},
        interpret=interpret,
        name="paged_mla_chunk_quant",
    )(layer, block_tables, start, span, q, latent_new,
      latent_pages, latent_scales)

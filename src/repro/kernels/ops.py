"""Jit'd public wrappers around the Pallas kernels.

Handles padding to TPU-aligned block shapes, layout flattening, backend
dispatch (interpret=True off-TPU so kernels execute correctly on CPU), and
an escape hatch to the pure-jnp reference path (used by the dry-run so XLA
cost analysis sees portable HLO).

    from repro.kernels import ops
    out = ops.flash_attention(q, k, v, causal=True)          # [B,H,T,D]
    out = ops.decode_attention(q, k, v, kv_len)              # [B,H,D]
    key, pay = ops.lww_merge(key_a, pay_a, key_b, pay_b)
    h, h_T  = ops.linear_scan(a, b, h0)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import decode_attention as _dec
from repro.kernels import delta_apply as _da
from repro.kernels import flash_attention as _fa
from repro.kernels import lww_merge as _lww
from repro.kernels import page_transfer as _pxfer
from repro.kernels import paged_chunk_attention as _pchunk
from repro.kernels import paged_decode_attention as _pdec
from repro.kernels import paged_mla_decode as _pmla
from repro.kernels import ref
from repro.kernels import rglru_scan as _rg


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def default_impl() -> str:
    """Attention path the serving entry points run unless told otherwise:
    the Pallas kernels on a TPU, the jnp oracles of ``kernels/ref.py``
    elsewhere (where the kernels would only run in interpret mode)."""
    return "pallas" if _on_tpu() else "ref"


# Minimum sublane count per pool dtype (second-to-last dim of the TPU tile;
# the lane dim is always 128).  f32 default 8; narrow dtypes pack more rows.
_SUBLANE = {jnp.dtype(jnp.bfloat16): 16, jnp.dtype(jnp.int8): 32,
            jnp.dtype(jnp.float8_e4m3fn): 32}


def _check_tileable(kernel: str, dtype, **dims) -> None:
    """Shared TPU tileability guard for the paged kernels (the pool is
    deliberately never padded per step, so it must be tileable at init).

    ``dims`` maps dimension names to (size, multiple); pass the pool's
    ``page_size`` with multiple=None to check it against the dtype's
    sublane count, and lane dims (head_dim / pool width) with multiple=128.
    Raises naming the offending kernel and dimension.
    """
    sublane = _SUBLANE.get(jnp.dtype(dtype), 8)
    bad = []
    for name, (size, mult) in dims.items():
        mult = sublane if mult is None else mult
        if size % mult:
            bad.append(f"{name}={size} must be a multiple of {mult}")
    if bad:
        raise ValueError(
            f"{kernel}: paged cache layout is not TPU-tileable for "
            f"{jnp.dtype(dtype).name} pools: " + "; ".join(bad) + ". "
            "Pick aligned shapes at init_cache time — the pool is "
            "deliberately never padded per step.")


def _pad_to(x: jax.Array, axis: int, mult: int, value=0) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def lww_merge(key_a, pay_a, key_b, pay_b, *, block_k: int = 1024,
              use_pallas: bool = True):
    """key: i32[K]; payload: [K, D] — see kernels/lww_merge.py."""
    if not use_pallas:
        return ref.lww_merge(key_a, pay_a, key_b, pay_b)
    k = key_a.shape[0]
    blk = min(block_k, max(128, 1 << (k - 1).bit_length()))
    ka = _pad_to(key_a, 0, blk, value=np.iinfo(np.int32).min)
    kb = _pad_to(key_b, 0, blk, value=np.iinfo(np.int32).min)
    pa = _pad_to(_pad_to(pay_a, 0, blk), 1, 128)
    pb = _pad_to(_pad_to(pay_b, 0, blk), 1, 128)
    ok, op = _lww.lww_merge(ka, pa, kb, pb, block_k=blk,
                            interpret=not _on_tpu())
    return ok[:k], op[:k, :pay_a.shape[1]]


def delta_apply(key, pay, d_idx, d_key, d_pay, *, block_k: int = 1024,
                use_pallas: bool = True):
    """Scatter-apply an LWW delta buffer — see kernels/delta_apply.py.

    key: i32[K]; pay: [K, D]; d_idx/d_key: i32[Dc]; d_pay: [Dc, D].
    Empty delta lanes hold d_idx = -1.
    """
    if not use_pallas:
        return ref.delta_apply(key, pay, d_idx, d_key, d_pay)
    k = key.shape[0]
    # Clamp to >= 128 (TPU lane width): the kernel's blocks must stay
    # 128-aligned even for caller-supplied smaller block_k.
    blk = max(128, min(block_k, 1 << (k - 1).bit_length()))
    kk = _pad_to(key, 0, blk, value=np.iinfo(np.int32).min)
    pp = _pad_to(_pad_to(pay, 0, blk), 1, 128)
    # Padded delta lanes target row -1: they can never match a register.
    di = _pad_to(d_idx, 0, 8, value=-1)
    dk = _pad_to(d_key, 0, 8, value=0)
    dp = _pad_to(_pad_to(d_pay, 0, 8), 1, 128)
    ok, op = _da.delta_apply(kk, pp, di, dk, dp, block_k=blk,
                             interpret=not _on_tpu())
    return ok[:k], op[:k, :pay.shape[1]]


def flash_attention(q, k, v, *, causal: bool = True, scale: float | None = None,
                    window: int | None = None, block_q: int = 256,
                    block_k: int = 256, use_pallas: bool = True):
    """q: [B, Hq, Tq, D]; k, v: [B, Hkv, Tk, D] -> [B, Hq, Tq, D]."""
    if not use_pallas:
        return ref.flash_attention(q, k, v, causal=causal, scale=scale,
                                   window=window)
    b, hq, tq, d = q.shape
    _, hkv, tk, _ = k.shape
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    bq = min(block_q, max(128, 1 << (tq - 1).bit_length()))
    bk = min(block_k, max(128, 1 << (tk - 1).bit_length()))
    qf = _pad_to(_pad_to(q.reshape(b * hq, tq, d), 1, bq), 2, 128)
    kf = _pad_to(_pad_to(k.reshape(b * hkv, tk, d), 1, bk), 2, 128)
    vf = _pad_to(_pad_to(v.reshape(b * hkv, tk, d), 1, bk), 2, 128)
    # Padded query rows produce garbage and are sliced away below.
    out = _fa.flash_attention(
        qf, kf, vf, causal=causal, scale=scale, window=window,
        num_q_heads=hq, tq_true=tq, tk_true=tk,
        block_q=bq, block_k=bk, interpret=not _on_tpu())
    return out[:, :tq, :d].reshape(b, hq, tq, d)


def decode_attention(q, k, v, kv_len, *, scale: float | None = None,
                     block_s: int = 512, use_pallas: bool = True):
    """q: [B, Hq, D]; k, v: [B, Hkv, S, D]; kv_len: i32[B] -> [B, Hq, D]."""
    if not use_pallas:
        return ref.decode_attention(q, k, v, kv_len, scale=scale)
    b, hq, d = q.shape
    _, hkv, s, _ = k.shape
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    bs = min(block_s, max(128, 1 << (s - 1).bit_length()))
    qf = _pad_to(q.reshape(b * hq, 1, d), 2, 128)
    kf = _pad_to(_pad_to(k.reshape(b * hkv, s, d), 1, bs), 2, 128)
    vf = _pad_to(_pad_to(v.reshape(b * hkv, s, d), 1, bs), 2, 128)
    len_f = jnp.repeat(kv_len.astype(jnp.int32), hq)
    out = _dec.decode_attention(
        qf, kf, vf, len_f, scale=scale, num_q_heads=hq, block_s=bs,
        interpret=not _on_tpu())
    return out[:, 0, :d].reshape(b, hq, d)


def paged_decode_attention(q, k_pages, v_pages, block_tables, pos,
                           k_new, v_new, *, scale: float | None = None,
                           window: int | None = None,
                           use_pallas: bool = True):
    """Fused write-attend decode over a paged KV cache.

    q: [B, Hq, D]; k_pages, v_pages: [P, Hkv, ps, D]; block_tables:
    i32[B, maxp]; pos: i32[B]; k_new, v_new: [B, Hkv, D].
    Returns (out [B, Hq, D], k_pages, v_pages) — pools carry the new token
    at slot ``pos`` (in place on TPU via input/output aliasing).

    Unlike the dense wrappers this one never pads the pool: a pad/slice
    round-trip would copy the whole cache every step, which is exactly the
    cost the paged path removes.  On TPU the pool must therefore already be
    tileable; off-TPU the kernel runs in interpret mode at any shape.
    """
    ps = k_pages.shape[2]
    # Clamp pos to table capacity on BOTH paths (one contract): past it the
    # kernel would read the block table out of bounds and DMA the token
    # into an arbitrary live page; the oracle would write a different slot.
    # Clamped, both rewrite the table's last slot.
    pos = jnp.minimum(pos, block_tables.shape[1] * ps - 1)
    if not use_pallas:
        return ref.paged_decode_attention(q, k_pages, v_pages, block_tables,
                                          pos, k_new, v_new, scale=scale,
                                          window=window)
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    on_tpu = _on_tpu()
    if on_tpu:
        _check_tileable("paged_decode_attention", k_pages.dtype,
                        page_size=(ps, None), head_dim=(d, 128))
    return _pdec.paged_decode_attention(
        q, k_pages, v_pages, block_tables.astype(jnp.int32),
        pos.astype(jnp.int32), k_new.astype(k_pages.dtype),
        v_new.astype(v_pages.dtype), scale=scale, window=window,
        interpret=not on_tpu)


def _quant_qmax(dtype) -> float:
    """Symmetric-quant max magnitude for a quantized pool dtype."""
    if jnp.dtype(dtype) == jnp.dtype(jnp.int8):
        return 127.0
    if jnp.dtype(dtype) == jnp.dtype(jnp.float8_e4m3fn):
        return 448.0            # e4m3 finite max
    raise ValueError(f"not a quantized pool dtype: {jnp.dtype(dtype).name}")


def paged_decode_attention_quant(q, k_pages, k_scales, v_pages, v_scales,
                                 block_tables, pos, k_new, v_new, *,
                                 scale: float | None = None,
                                 window: int | None = None,
                                 use_pallas: bool = True):
    """Quantized-pool fused write-attend decode.

    Same contract as ``paged_decode_attention`` with int8/fp8 pools and
    per-row f32 scale pools (k/v_scales: [P, Hkv, ps]) riding alongside;
    k/v_new arrive FLOAT and quantize inside the kernel's fused write.
    Returns (out, k_pages, v_pages, k_scales, v_scales).
    """
    ps = k_pages.shape[2]
    pos = jnp.minimum(pos, block_tables.shape[1] * ps - 1)
    if not use_pallas:
        return ref.paged_decode_attention_quant(
            q, k_pages, k_scales, v_pages, v_scales, block_tables, pos,
            k_new, v_new, scale=scale, window=window)
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    on_tpu = _on_tpu()
    if on_tpu:
        _check_tileable("paged_decode_attention_quant", k_pages.dtype,
                        page_size=(ps, None), head_dim=(d, 128),
                        scale_row=(ps, 128))
    return _pdec.paged_decode_attention_quant(
        q, k_pages, k_scales.astype(jnp.float32), v_pages,
        v_scales.astype(jnp.float32), block_tables.astype(jnp.int32),
        pos.astype(jnp.int32), k_new.astype(jnp.float32),
        v_new.astype(jnp.float32), scale=scale,
        qmax=_quant_qmax(k_pages.dtype), window=window,
        interpret=not on_tpu)


def paged_chunk_attention(q, k_pages, v_pages, block_tables, start, span,
                          k_new, v_new, *, scale: float | None = None,
                          window: int | None = None, use_pallas: bool = True):
    """Chunked mixed-step attention over a paged KV cache, writes fused.

    q: [B, Hq, C, D] per-row query spans; k_pages, v_pages: [P, Hkv, ps, D];
    block_tables: i32[B, maxp]; start: i32[B] tokens already cached; span:
    i32[B] valid new tokens in [0, C]; k_new, v_new: [B, Hkv, C, D].
    Returns (out [B, Hq, C, D], k_pages, v_pages) — the span's K/V written
    at slots ``start..start+span`` (in place on TPU via aliasing).

    Span 1 is the fused decode step; span C is one prompt chunk.  Like the
    decode wrapper, the pool is never padded per step — on TPU it must be
    tileable at init; off-TPU the kernel runs in interpret mode.
    """
    ps = k_pages.shape[2]
    maxp = block_tables.shape[1]
    # Clamp start to table capacity on BOTH paths (one contract with the
    # decode wrapper): writes past the table drop and the walk stays in
    # bounds instead of reading the block table out of range.
    start = jnp.minimum(start, maxp * ps - 1)
    span = jnp.clip(span, 0, q.shape[2])
    if not use_pallas:
        return ref.paged_chunk_attention(q, k_pages, v_pages, block_tables,
                                         start, span, k_new, v_new,
                                         scale=scale, window=window)
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    on_tpu = _on_tpu()
    if on_tpu:
        _check_tileable("paged_chunk_attention", k_pages.dtype,
                        page_size=(ps, None), head_dim=(d, 128))
    return _pchunk.paged_chunk_attention(
        q, k_pages, v_pages, block_tables.astype(jnp.int32),
        start.astype(jnp.int32), span.astype(jnp.int32),
        k_new.astype(k_pages.dtype), v_new.astype(v_pages.dtype),
        scale=scale, window=window, interpret=not on_tpu)


def paged_chunk_attention_quant(q, k_pages, k_scales, v_pages, v_scales,
                                block_tables, start, span, k_new, v_new, *,
                                scale: float | None = None,
                                window: int | None = None,
                                use_pallas: bool = True):
    """Quantized-pool chunked mixed-step attention.

    Same contract as ``paged_chunk_attention`` with int8/fp8 pools and
    per-row f32 scale pools; k/v_new arrive FLOAT [B, Hkv, C, D] and
    quantize inside the kernel's fused multi-slot write.  Returns
    (out, k_pages, v_pages, k_scales, v_scales).
    """
    ps = k_pages.shape[2]
    maxp = block_tables.shape[1]
    start = jnp.minimum(start, maxp * ps - 1)
    span = jnp.clip(span, 0, q.shape[2])
    if not use_pallas:
        return ref.paged_chunk_attention_quant(
            q, k_pages, k_scales, v_pages, v_scales, block_tables, start,
            span, k_new, v_new, scale=scale, window=window)
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    on_tpu = _on_tpu()
    if on_tpu:
        _check_tileable("paged_chunk_attention_quant", k_pages.dtype,
                        page_size=(ps, None), head_dim=(d, 128),
                        scale_row=(ps, 128))
    return _pchunk.paged_chunk_attention_quant(
        q, k_pages, k_scales.astype(jnp.float32), v_pages,
        v_scales.astype(jnp.float32), block_tables.astype(jnp.int32),
        start.astype(jnp.int32), span.astype(jnp.int32),
        k_new.astype(jnp.float32), v_new.astype(jnp.float32),
        scale=scale, qmax=_quant_qmax(k_pages.dtype), window=window,
        interpret=not on_tpu)


def paged_mla_chunk(q_abs, q_rope, latent_pages, block_tables, start, span,
                    latent_new, *, scale: float, use_pallas: bool = True):
    """Chunked mixed-step MLA decode over a paged latent cache.

    q_abs: [B, H, C, r] (f32 absorbed queries); q_rope: [B, H, C, rd];
    latent_pages: [P, ps, Dp] with Dp >= r + rd; block_tables: i32[B, maxp];
    start/span: i32[B]; latent_new: [B, C, Dp].
    Returns (ctx [B, H, C, r] f32, latent_pages updated in place on TPU).
    """
    r = q_abs.shape[-1]
    rd = q_rope.shape[-1]
    ps = latent_pages.shape[1]
    dp = latent_pages.shape[2]
    maxp = block_tables.shape[1]
    if dp < r + rd:
        raise ValueError(f"latent pool width {dp} < kv_lora_rank + rope_dim "
                         f"= {r + rd}")
    start = jnp.minimum(start, maxp * ps - 1)
    span = jnp.clip(span, 0, q_abs.shape[2])
    if not use_pallas:
        return ref.paged_mla_chunk(q_abs, q_rope, latent_pages,
                                   block_tables, start, span, latent_new,
                                   r=r, scale=scale)
    on_tpu = _on_tpu()
    if on_tpu:
        _check_tileable("paged_mla_chunk", latent_pages.dtype,
                        page_size=(ps, None), latent_width=(dp, 128))
    qc = jnp.concatenate([q_abs.astype(jnp.float32),
                          q_rope.astype(jnp.float32)], axis=-1)
    return _pchunk.paged_mla_chunk(
        qc, latent_pages, block_tables.astype(jnp.int32),
        start.astype(jnp.int32), span.astype(jnp.int32),
        latent_new.astype(latent_pages.dtype), r=r, scale=scale,
        interpret=not on_tpu)


def paged_mla_chunk_quant(q_abs, q_rope, latent_pages, latent_scales,
                          block_tables, start, span, latent_new, *,
                          scale: float, use_pallas: bool = True):
    """Quantized-pool chunked MLA decode.

    Same contract as ``paged_mla_chunk`` with an int8/fp8 latent pool and
    a per-row f32 scale pool (latent_scales: [P, ps]); latent_new arrives
    FLOAT [B, C, Dp] and quantizes inside the kernel's fused write.
    Returns (ctx, latent_pages, latent_scales).
    """
    r = q_abs.shape[-1]
    rd = q_rope.shape[-1]
    ps = latent_pages.shape[1]
    dp = latent_pages.shape[2]
    maxp = block_tables.shape[1]
    if dp < r + rd:
        raise ValueError(f"latent pool width {dp} < kv_lora_rank + rope_dim "
                         f"= {r + rd}")
    start = jnp.minimum(start, maxp * ps - 1)
    span = jnp.clip(span, 0, q_abs.shape[2])
    if not use_pallas:
        return ref.paged_mla_chunk_quant(
            q_abs, q_rope, latent_pages, latent_scales, block_tables,
            start, span, latent_new, r=r, scale=scale)
    on_tpu = _on_tpu()
    if on_tpu:
        _check_tileable("paged_mla_chunk_quant", latent_pages.dtype,
                        page_size=(ps, None), latent_width=(dp, 128),
                        scale_row=(ps, 128))
    qc = jnp.concatenate([q_abs.astype(jnp.float32),
                          q_rope.astype(jnp.float32)], axis=-1)
    return _pchunk.paged_mla_chunk_quant(
        qc, latent_pages, latent_scales.astype(jnp.float32),
        block_tables.astype(jnp.int32), start.astype(jnp.int32),
        span.astype(jnp.int32), latent_new.astype(jnp.float32),
        r=r, scale=scale, qmax=_quant_qmax(latent_pages.dtype),
        interpret=not on_tpu)


def paged_mla_decode(q_abs, q_rope, latent_pages, block_tables, pos,
                     latent_new, *, scale: float, use_pallas: bool = True):
    """Fused write-attend MLA decode over a paged latent cache.

    q_abs: [B, H, r] (f32 absorbed queries); q_rope: [B, H, rd];
    latent_pages: [P, ps, Dp] with Dp >= r + rd (lane-padded at init);
    block_tables: i32[B, maxp]; pos: i32[B]; latent_new: [B, Dp].
    Returns (ctx [B, H, r] f32, latent_pages updated in place on TPU).

    Like the MHA paged wrapper, the pool is never padded per step: a
    pad/slice round-trip would copy the whole latent cache each token —
    exactly the cost the paged path removes.  The pool's feature dim is
    therefore padded once at init_cache (models/cache.py pad128); here we
    only validate.
    """
    r = q_abs.shape[-1]
    rd = q_rope.shape[-1]
    ps = latent_pages.shape[1]
    dp = latent_pages.shape[2]
    if dp < r + rd:
        raise ValueError(f"latent pool width {dp} < kv_lora_rank + rope_dim "
                         f"= {r + rd}")
    # Clamp pos to table capacity on BOTH paths (one contract with the MHA
    # wrapper): past it, both rewrite the table's last slot instead of
    # reading the block table out of bounds.
    pos = jnp.minimum(pos, block_tables.shape[1] * ps - 1)
    if not use_pallas:
        return ref.paged_mla_decode(q_abs, q_rope, latent_pages,
                                    block_tables, pos, latent_new,
                                    r=r, scale=scale)
    on_tpu = _on_tpu()
    if on_tpu:
        _check_tileable("paged_mla_decode", latent_pages.dtype,
                        page_size=(ps, None), latent_width=(dp, 128))
    qc = jnp.concatenate([q_abs.astype(jnp.float32),
                          q_rope.astype(jnp.float32)], axis=-1)
    return _pmla.paged_mla_decode(
        qc, latent_pages, block_tables.astype(jnp.int32),
        pos.astype(jnp.int32), latent_new.astype(latent_pages.dtype),
        r=r, scale=scale, interpret=not on_tpu)


def paged_mla_decode_quant(q_abs, q_rope, latent_pages, latent_scales,
                           block_tables, pos, latent_new, *,
                           scale: float, use_pallas: bool = True):
    """Quantized-pool fused write-attend MLA decode.

    Same contract as ``paged_mla_decode`` with an int8/fp8 latent pool and
    a per-row f32 scale pool (latent_scales: [P, ps]); latent_new arrives
    FLOAT [B, Dp] and quantizes inside the kernel's fused write.  Returns
    (ctx, latent_pages, latent_scales).
    """
    r = q_abs.shape[-1]
    rd = q_rope.shape[-1]
    ps = latent_pages.shape[1]
    dp = latent_pages.shape[2]
    if dp < r + rd:
        raise ValueError(f"latent pool width {dp} < kv_lora_rank + rope_dim "
                         f"= {r + rd}")
    pos = jnp.minimum(pos, block_tables.shape[1] * ps - 1)
    if not use_pallas:
        return ref.paged_mla_decode_quant(
            q_abs, q_rope, latent_pages, latent_scales, block_tables, pos,
            latent_new, r=r, scale=scale)
    on_tpu = _on_tpu()
    if on_tpu:
        _check_tileable("paged_mla_decode_quant", latent_pages.dtype,
                        page_size=(ps, None), latent_width=(dp, 128),
                        scale_row=(ps, 128))
    qc = jnp.concatenate([q_abs.astype(jnp.float32),
                          q_rope.astype(jnp.float32)], axis=-1)
    return _pmla.paged_mla_decode_quant(
        qc, latent_pages, latent_scales.astype(jnp.float32),
        block_tables.astype(jnp.int32), pos.astype(jnp.int32),
        latent_new.astype(jnp.float32), r=r, scale=scale,
        qmax=_quant_qmax(latent_pages.dtype), interpret=not on_tpu)


def _row_tileable(row: tuple, dtype) -> bool:
    """True when a pool row can be VMEM-staged on TPU: lane dim a multiple
    of 128 and sublane dim a multiple of the dtype's sublane count."""
    if len(row) < 2:
        return False
    sublane = _SUBLANE.get(jnp.dtype(dtype), 8)
    return row[-1] % 128 == 0 and row[-2] % sublane == 0


def page_transfer(src_pool, dst_pool, src_ids, dst_ids, *,
                  use_pallas: bool = True):
    """Batched cross-pool page-row transfer (disaggregated adoption path).

    src_pool: [Ps, ...row]; dst_pool: [Pd, ...row] (same row shape and
    dtype); src_ids/dst_ids: i32[N] — lane i copies row ``src_ids[i]`` into
    row ``dst_ids[i]``; -1 on either side drops the lane.  Returns the
    updated destination pool; the copy is pure DMA, bitwise for any dtype.

    The pool is never padded (same rationale as the paged attention
    wrappers); rows the TPU cannot VMEM-stage — e.g. tiny scale leaves
    [ps] / [Hkv, ps] — take the reference gather-scatter instead of
    raising, since a DMA kernel buys nothing at that size.
    """
    if src_pool.shape[1:] != dst_pool.shape[1:] \
            or src_pool.dtype != dst_pool.dtype:
        raise ValueError(
            f"page_transfer: pool rows do not match: src "
            f"{tuple(src_pool.shape)} ({jnp.dtype(src_pool.dtype).name}) vs "
            f"dst {tuple(dst_pool.shape)} ({jnp.dtype(dst_pool.dtype).name})")
    if src_ids.shape != dst_ids.shape or src_ids.ndim != 1:
        raise ValueError(
            f"page_transfer: id vectors must be matching 1-D arrays, got "
            f"src_ids {tuple(src_ids.shape)} vs dst_ids "
            f"{tuple(dst_ids.shape)}")
    if src_ids.shape[0] == 0:
        return dst_pool
    on_tpu = _on_tpu()
    if not use_pallas or (on_tpu and not _row_tileable(src_pool.shape[1:],
                                                       src_pool.dtype)):
        return ref.page_transfer(src_pool, dst_pool,
                                 src_ids.astype(jnp.int32),
                                 dst_ids.astype(jnp.int32))
    return _pxfer.page_transfer(src_pool, dst_pool,
                                src_ids.astype(jnp.int32),
                                dst_ids.astype(jnp.int32),
                                interpret=not on_tpu)


def linear_scan(a, b, h0, *, block_t: int = 128, use_pallas: bool = True):
    """h_t = a_t*h_{t-1} + b_t.  a, b: [B, T, D]; h0: [B, D]."""
    if not use_pallas:
        y = ref.linear_scan(a, b, h0)
        return y, y[:, -1].astype(jnp.float32)
    batch, t, d = a.shape
    bt = min(block_t, max(8, 1 << (t - 1).bit_length()))
    # Pad time with identity steps (a=1, b=0) so the carry passes through.
    ap = _pad_to(a, 1, bt, value=1)
    bp = _pad_to(b, 1, bt, value=0)
    y, h_t = _rg.linear_scan(ap, bp, h0, block_t=bt, interpret=not _on_tpu())
    return y[:, :t], h_t

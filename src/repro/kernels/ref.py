"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each function is the mathematical definition with no tiling/blocking —
tests/test_kernels.py sweeps shapes and dtypes asserting the kernels match
these to tolerance.  The model zoo also uses these as its portable path (the
dry-run lowers reference math so XLA cost analysis sees the real FLOPs).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# LWW merge (the paper's coordination hot-spot)
# ---------------------------------------------------------------------------

def lww_merge(key_a: jax.Array, payload_a: jax.Array,
              key_b: jax.Array, payload_b: jax.Array
              ) -> tuple[jax.Array, jax.Array]:
    """Per-register join: winner = larger packed (clock, client) key.

    key_*: i32[K]; payload_*: [K, D] (any dtype).
    """
    b_wins = key_b > key_a
    out_key = jnp.where(b_wins, key_b, key_a)
    out_payload = jnp.where(b_wins[:, None], payload_b, payload_a)
    return out_key, out_payload


# ---------------------------------------------------------------------------
# Delta scatter-apply (delta-state sync hot path)
# ---------------------------------------------------------------------------

def delta_apply(key: jax.Array, payload: jax.Array, d_idx: jax.Array,
                d_key: jax.Array, d_payload: jax.Array
                ) -> tuple[jax.Array, jax.Array]:
    """Apply an LWW delta buffer: lane j writes register ``d_idx[j]`` iff its
    key wins.  Empty lanes carry ``d_idx = -1``; target indices must be
    unique (core/delta.py extraction guarantees it — the kernel additionally
    resolves duplicates by sequential max, which jnp scatter cannot).

    key: i32[K]; payload: [K, D]; d_idx/d_key: i32[Dc]; d_payload: [Dc, D].
    """
    k = key.shape[0]
    safe = jnp.clip(d_idx, 0, k - 1)
    wins = (d_idx >= 0) & (d_key > key[safe])
    tgt = jnp.where(wins, d_idx, k)          # losers routed out of bounds
    out_key = key.at[tgt].set(d_key, mode="drop")
    out_payload = payload.at[tgt].set(d_payload.astype(payload.dtype),
                                      mode="drop")
    return out_key, out_payload


# ---------------------------------------------------------------------------
# Per-page-row KV quantization (the quantized-pool contract)
# ---------------------------------------------------------------------------
#
# Quantized page pools store one scale per pool row within each page (MHA:
# per (page, head, slot); MLA latent: per (page, slot)), symmetric over the
# feature axis.  Writing a row quantizes it against its own abs-max; the
# kernels dequantize inside the block-table walk by multiplying each page's
# rows by its scale block.  Guarantees the property suite pins down:
#
#   * the scale is never zero (an all-zero row takes scale 1.0);
#   * int8 round-to-nearest keeps the worst-case abs error <= scale / 2;
#   * dequantize(quantize(x)) is deterministic, so snapshot/restore of the
#     (values, scales) pair is bitwise.

INT8_QMAX = 127.0
FP8_QMAX = 448.0                    # e4m3 finite max


def quant_qmax(dtype) -> float:
    """Symmetric representable max the row scale maps abs-max onto."""
    if dtype == jnp.int8:
        return INT8_QMAX
    if dtype == jnp.float8_e4m3fn:
        return FP8_QMAX
    raise ValueError(f"unsupported quantized pool dtype {dtype}")


def quantize_rows(x: jax.Array, dtype) -> tuple[jax.Array, jax.Array]:
    """Quantize rows of ``x`` ([..., D] float) along the last axis.

    Returns ``(q [..., D] dtype, scale [...] f32)`` with
    ``x ~= q * scale[..., None]``.  Scale = abs-max / qmax (1.0 for all-zero
    rows, so it is never zero); int8 rounds to nearest.
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    qmax = quant_qmax(dtype)
    # Multiply by the reciprocal EXPLICITLY (not amax / qmax): XLA rewrites
    # constant division into it in some compilation paths but not others;
    # the explicit form keeps oracle and Pallas-kernel scales bit-identical.
    scale = jnp.where(amax > 0, amax * np.float32(1.0 / qmax), 1.0)
    scaled = xf / scale[..., None]
    if dtype == jnp.int8:
        q = jnp.clip(jnp.round(scaled), -qmax, qmax).astype(jnp.int8)
    else:
        q = scaled.astype(dtype)
    return q, scale.astype(jnp.float32)


def dequantize_rows(q: jax.Array, scale: jax.Array) -> jax.Array:
    """Inverse of :func:`quantize_rows`: ``q [..., D] * scale [...]`` -> f32."""
    return q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def _broadcast_kv(k: jax.Array, n_q_heads: int) -> jax.Array:
    """[B, Hkv, T, D] -> [B, Hq, T, D] by repeating groups (GQA)."""
    b, hkv, t, d = k.shape
    group = n_q_heads // hkv
    return jnp.repeat(k, group, axis=1)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, scale: float | None = None,
                    window: int | None = None) -> jax.Array:
    """Full-precision reference attention.

    q: [B, Hq, Tq, D]; k, v: [B, Hkv, Tk, D] (Hq % Hkv == 0).
    ``window``: optional local-attention window (keys within [i-window, i]).
    """
    b, hq, tq, d = q.shape
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    kb = _broadcast_kv(k, hq)
    vb = _broadcast_kv(v, hq)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        kb.astype(jnp.float32)) * scale
    tk = k.shape[2]
    qi = jnp.arange(tq)[:, None] + (tk - tq)   # align ends (prefill/extend)
    ki = jnp.arange(tk)[None, :]
    mask = jnp.ones((tq, tk), bool)
    if causal:
        mask &= ki <= qi
    if window is not None:
        mask &= ki >= qi - window + 1
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vb.astype(jnp.float32))
    return out.astype(q.dtype)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     kv_len: jax.Array, scale: float | None = None) -> jax.Array:
    """Single-token decode attention against a (padded) KV cache.

    q: [B, Hq, D]; k, v: [B, Hkv, S, D]; kv_len: i32[B] — valid prefix.
    """
    b, hq, d = q.shape
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    kb = _broadcast_kv(k, hq)
    vb = _broadcast_kv(v, hq)
    logits = jnp.einsum("bhd,bhsd->bhs", q.astype(jnp.float32),
                        kb.astype(jnp.float32)) * scale
    s = k.shape[2]
    mask = jnp.arange(s)[None, :] < kv_len[:, None]
    logits = jnp.where(mask[:, None, :], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhs,bhsd->bhd", p, vb.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, block_tables: jax.Array,
                           pos: jax.Array, k_new: jax.Array,
                           v_new: jax.Array, scale: float | None = None,
                           window: int | None = None
                           ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Single-token decode against a paged KV cache, write included.

    q: [B, Hq, D]; k_pages, v_pages: [P, Hkv, ps, D] shared page pool;
    block_tables: i32[B, maxp] page ids per row (-1 = unallocated);
    pos: i32[B] tokens already cached; k_new, v_new: [B, Hkv, D].

    Semantics (the kernel contract): write the new token's K/V into page
    ``block_tables[b, pos // ps]`` slot ``pos % ps``, then attend over the
    row's ``pos + 1`` live tokens.  This reference gathers the row's pages
    into a contiguous view — O(B·maxp·ps) reads, the thing the kernel
    avoids — but is the bit-level definition of the math.
    """
    b, hq, d = q.shape
    _, hkv, ps, _ = k_pages.shape
    scale = scale if scale is not None else 1.0 / (d ** 0.5)

    pg_w = jnp.take_along_axis(block_tables, (pos // ps)[:, None], axis=1)[:, 0]
    # -1 must DROP, but negative scatter indices wrap in jnp — route them
    # out of bounds so mode="drop" actually discards the write.
    pg_w = jnp.where(pg_w < 0, k_pages.shape[0], pg_w)
    slot_w = pos % ps
    k_pages = k_pages.at[pg_w, :, slot_w, :].set(
        k_new.astype(k_pages.dtype), mode="drop")
    v_pages = v_pages.at[pg_w, :, slot_w, :].set(
        v_new.astype(v_pages.dtype), mode="drop")

    safe_bt = jnp.maximum(block_tables, 0)
    # [B, maxp, Hkv, ps, D] -> [B, Hkv, maxp*ps, D]
    kg = jnp.moveaxis(k_pages[safe_bt], 2, 1).reshape(b, hkv, -1, d)
    vg = jnp.moveaxis(v_pages[safe_bt], 2, 1).reshape(b, hkv, -1, d)
    kb = _broadcast_kv(kg, hq)
    vb = _broadcast_kv(vg, hq)
    logits = jnp.einsum("bhd,bhsd->bhs", q.astype(jnp.float32),
                        kb.astype(jnp.float32)) * scale
    cols = jnp.arange(kg.shape[2])[None, :]
    valid = cols < (pos + 1)[:, None]
    if window is not None:
        valid &= cols > (pos - window)[:, None]
    logits = jnp.where(valid[:, None, :], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhs,bhsd->bhd", p, vb.astype(jnp.float32))
    return out.astype(q.dtype), k_pages, v_pages


def paged_chunk_attention(q: jax.Array, k_pages: jax.Array,
                          v_pages: jax.Array, block_tables: jax.Array,
                          start: jax.Array, span: jax.Array,
                          k_new: jax.Array, v_new: jax.Array,
                          scale: float | None = None,
                          window: int | None = None
                          ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Chunked mixed-step attention against a paged KV cache, writes included.

    q: [B, Hq, C, D] per-row query spans; k_pages, v_pages: [P, Hkv, ps, D]
    shared page pool; block_tables: i32[B, maxp]; start: i32[B] tokens
    already cached per row; span: i32[B] valid new tokens in [0, C];
    k_new, v_new: [B, Hkv, C, D] the span's K/V.

    Semantics (the kernel contract): write the span's K/V into pages
    ``block_tables[b, (start+j) // ps]`` slot ``(start+j) % ps`` for
    j < span[b], then each query j attends over the row's ``start + j + 1``
    live tokens (causal within the span, whole cached prefix before it).
    Rows with span 0 write nothing and return garbage.  Because the span is
    written *before* the attend, every query's math depends only on (query
    position, cached prefix) — chunk partitioning cannot change the bits,
    which is what makes chunked admission ≡ one-shot prefill.
    """
    b, hq, c, d = q.shape
    num_pages, hkv, ps, _ = k_pages.shape
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    maxp = block_tables.shape[1]

    tpos = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]  # [B, C]
    pg = jnp.take_along_axis(block_tables,
                             jnp.clip(tpos // ps, 0, maxp - 1), axis=1)
    # Dropped writes are routed OUT OF BOUNDS (= num_pages): unallocated
    # (-1) table entries, positions past the table, and chunk padding
    # beyond each row's span.
    pg = jnp.where(pg < 0, num_pages, pg)
    pg = jnp.where(tpos < maxp * ps, pg, num_pages)
    pg = jnp.where(jnp.arange(c)[None, :] < span[:, None], pg, num_pages)
    slot = tpos % ps
    k_bt = k_new.transpose(0, 2, 1, 3).astype(k_pages.dtype)  # [B, C, Hkv, D]
    v_bt = v_new.transpose(0, 2, 1, 3).astype(v_pages.dtype)
    k_pages = k_pages.at[pg, :, slot, :].set(k_bt, mode="drop")
    v_pages = v_pages.at[pg, :, slot, :].set(v_bt, mode="drop")

    safe_bt = jnp.maximum(block_tables, 0)
    # [B, maxp, Hkv, ps, D] -> [B, Hkv, maxp*ps, D]
    kg = jnp.moveaxis(k_pages[safe_bt], 2, 1).reshape(b, hkv, -1, d)
    vg = jnp.moveaxis(v_pages[safe_bt], 2, 1).reshape(b, hkv, -1, d)
    kb = _broadcast_kv(kg, hq)
    vb = _broadcast_kv(vg, hq)
    logits = jnp.einsum("bhcd,bhsd->bhcs", q.astype(jnp.float32),
                        kb.astype(jnp.float32)) * scale
    cols = jnp.arange(kg.shape[2])[None, None, :]
    valid = cols <= tpos[:, :, None]                    # causal to query pos
    if window is not None:
        valid &= cols > (tpos[:, :, None] - window)
    logits = jnp.where(valid[:, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhcs,bhsd->bhcd", p, vb.astype(jnp.float32))
    return out.astype(q.dtype), k_pages, v_pages


def paged_mla_chunk(q_abs: jax.Array, q_rope: jax.Array,
                    latent_pages: jax.Array, block_tables: jax.Array,
                    start: jax.Array, span: jax.Array,
                    latent_new: jax.Array, *, r: int, scale: float
                    ) -> tuple[jax.Array, jax.Array]:
    """Chunked mixed-step MLA decode against a paged latent cache.

    q_abs: [B, H, C, r] absorbed queries; q_rope: [B, H, C, rd];
    latent_pages: [P, ps, Dp]; block_tables: i32[B, maxp]; start/span:
    i32[B]; latent_new: [B, C, Dp].  Same write-then-attend contract as
    ``paged_chunk_attention``, same absorbed-weight contractions as
    ``paged_mla_decode`` (to which it degenerates at span == 1).
    """
    b, h, c, _ = q_abs.shape
    num_pages, ps, dp = latent_pages.shape
    rd = q_rope.shape[-1]
    maxp = block_tables.shape[1]

    tpos = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    pg = jnp.take_along_axis(block_tables,
                             jnp.clip(tpos // ps, 0, maxp - 1), axis=1)
    pg = jnp.where(pg < 0, num_pages, pg)
    pg = jnp.where(tpos < maxp * ps, pg, num_pages)
    pg = jnp.where(jnp.arange(c)[None, :] < span[:, None], pg, num_pages)
    slot = tpos % ps
    latent_pages = latent_pages.at[pg, slot, :].set(
        latent_new.astype(latent_pages.dtype), mode="drop")

    safe_bt = jnp.maximum(block_tables, 0)
    lg = latent_pages[safe_bt].reshape(b, -1, dp)        # [B, maxp*ps, Dp]
    ckv_g = lg[..., :r]
    krope_g = lg[..., r:r + rd]
    logits = (jnp.einsum("bhcr,bsr->bhcs", q_abs, ckv_g,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bhcr,bsr->bhcs", q_rope, krope_g,
                           preferred_element_type=jnp.float32)) * scale
    valid = jnp.arange(lg.shape[1])[None, None, :] <= tpos[:, :, None]
    logits = jnp.where(valid[:, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    ctx = jnp.einsum("bhcs,bsr->bhcr", probs, ckv_g.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return ctx, latent_pages


def paged_mla_decode(q_abs: jax.Array, q_rope: jax.Array,
                     latent_pages: jax.Array, block_tables: jax.Array,
                     pos: jax.Array, latent_new: jax.Array, *,
                     r: int, scale: float
                     ) -> tuple[jax.Array, jax.Array]:
    """Single-token MLA decode against a paged latent cache, write included.

    q_abs: [B, H, r] absorbed queries; q_rope: [B, H, rd];
    latent_pages: [P, ps, Dp] pool storing concat([ckv; krope]) rows in the
    first ``r + rd`` features (Dp is lane-padded); block_tables: i32[B, maxp];
    pos: i32[B]; latent_new: [B, Dp].

    Gathers the row's pages into logical-position order and then runs the
    *identical* contractions as the dense absorbed-weight decode
    (mla.decode_step): same einsums, same fp32 promotion, same masking —
    bit-for-bit with the dense oracle whenever maxp·ps == the dense S.
    """
    b, h, _ = q_abs.shape
    _, ps, dp = latent_pages.shape
    rd = q_rope.shape[-1]

    pg_w = jnp.take_along_axis(block_tables, (pos // ps)[:, None], axis=1)[:, 0]
    # -1 must DROP, but negative scatter indices wrap in jnp — route them
    # out of bounds so mode="drop" actually discards the write.
    pg_w = jnp.where(pg_w < 0, latent_pages.shape[0], pg_w)
    slot_w = pos % ps
    latent_pages = latent_pages.at[pg_w, slot_w, :].set(
        latent_new.astype(latent_pages.dtype), mode="drop")

    safe_bt = jnp.maximum(block_tables, 0)
    lg = latent_pages[safe_bt].reshape(b, -1, dp)        # [B, maxp*ps, Dp]
    ckv_g = lg[..., :r]
    krope_g = lg[..., r:r + rd]
    logits = (jnp.einsum("bhr,bsr->bhs", q_abs, ckv_g,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bhr,bsr->bhs", q_rope, krope_g,
                           preferred_element_type=jnp.float32)) * scale
    valid = jnp.arange(lg.shape[1])[None, :] <= pos[:, None]
    logits = jnp.where(valid[:, None, :], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    ctx = jnp.einsum("bhs,bsr->bhr", probs, ckv_g.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return ctx, latent_pages


# ---------------------------------------------------------------------------
# Quantized paged attention oracles
# ---------------------------------------------------------------------------
#
# Each quantized oracle is its fp32 oracle with the write quantized and the
# gather dequantized: the token/span rows are quantized per row
# (quantize_rows), the int8/fp8 values and their scales land in the pools,
# and the attend runs the IDENTICAL fp32 math over the dequantized pools.
# Tolerance vs the fp32 path is therefore exactly the per-row quantization
# error (<= scale/2 per element for int8), never a different softmax.

def paged_decode_attention_quant(q, k_pages, k_scales, v_pages, v_scales,
                                 block_tables, pos, k_new, v_new, *,
                                 scale=None, window=None):
    """Quantized ``paged_decode_attention``: pools [P, Hkv, ps, D] int8/fp8
    + scales [P, Hkv, ps]; k/v_new arrive float and are quantized into slot
    ``pos``.  Returns (out, k_pages, v_pages, k_scales, v_scales)."""
    ps = k_pages.shape[2]
    kq, ks = quantize_rows(k_new, k_pages.dtype)         # [B,Hkv,D],[B,Hkv]
    vq, vs = quantize_rows(v_new, v_pages.dtype)
    pg_w = jnp.take_along_axis(block_tables, (pos // ps)[:, None],
                               axis=1)[:, 0]
    pg_w = jnp.where(pg_w < 0, k_pages.shape[0], pg_w)
    slot_w = pos % ps
    k_pages = k_pages.at[pg_w, :, slot_w, :].set(kq, mode="drop")
    v_pages = v_pages.at[pg_w, :, slot_w, :].set(vq, mode="drop")
    k_scales = k_scales.at[pg_w, :, slot_w].set(ks, mode="drop")
    v_scales = v_scales.at[pg_w, :, slot_w].set(vs, mode="drop")
    out, _, _ = paged_decode_attention(
        q, dequantize_rows(k_pages, k_scales),
        dequantize_rows(v_pages, v_scales), block_tables, pos,
        dequantize_rows(kq, ks), dequantize_rows(vq, vs),
        scale=scale, window=window)
    return out, k_pages, v_pages, k_scales, v_scales


def paged_chunk_attention_quant(q, k_pages, k_scales, v_pages, v_scales,
                                block_tables, start, span, k_new, v_new, *,
                                scale=None, window=None):
    """Quantized ``paged_chunk_attention``: the span's K/V rows quantize per
    (row, token, head); returns (out, k_pages, v_pages, k_scales,
    v_scales)."""
    c = q.shape[2]
    num_pages, _, ps, _ = k_pages.shape
    maxp = block_tables.shape[1]
    kq, ks = quantize_rows(k_new.transpose(0, 2, 1, 3), k_pages.dtype)
    vq, vs = quantize_rows(v_new.transpose(0, 2, 1, 3), v_pages.dtype)
    tpos = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    pg = jnp.take_along_axis(block_tables,
                             jnp.clip(tpos // ps, 0, maxp - 1), axis=1)
    pg = jnp.where(pg < 0, num_pages, pg)
    pg = jnp.where(tpos < maxp * ps, pg, num_pages)
    pg = jnp.where(jnp.arange(c)[None, :] < span[:, None], pg, num_pages)
    slot = tpos % ps
    k_pages = k_pages.at[pg, :, slot, :].set(kq, mode="drop")
    v_pages = v_pages.at[pg, :, slot, :].set(vq, mode="drop")
    k_scales = k_scales.at[pg, :, slot].set(ks, mode="drop")
    v_scales = v_scales.at[pg, :, slot].set(vs, mode="drop")
    out, _, _ = paged_chunk_attention(
        q, dequantize_rows(k_pages, k_scales),
        dequantize_rows(v_pages, v_scales), block_tables, start, span,
        dequantize_rows(kq, ks).transpose(0, 2, 1, 3),
        dequantize_rows(vq, vs).transpose(0, 2, 1, 3),
        scale=scale, window=window)
    return out, k_pages, v_pages, k_scales, v_scales


def paged_mla_chunk_quant(q_abs, q_rope, latent_pages, latent_scales,
                          block_tables, start, span, latent_new, *,
                          r: int, scale: float):
    """Quantized ``paged_mla_chunk``: latent pool [P, ps, Dp] int8/fp8 +
    scales [P, ps]; returns (ctx, latent_pages, latent_scales)."""
    c = latent_new.shape[1]
    num_pages, ps, _ = latent_pages.shape
    maxp = block_tables.shape[1]
    lq, ls = quantize_rows(latent_new, latent_pages.dtype)   # [B,C,Dp],[B,C]
    tpos = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    pg = jnp.take_along_axis(block_tables,
                             jnp.clip(tpos // ps, 0, maxp - 1), axis=1)
    pg = jnp.where(pg < 0, num_pages, pg)
    pg = jnp.where(tpos < maxp * ps, pg, num_pages)
    pg = jnp.where(jnp.arange(c)[None, :] < span[:, None], pg, num_pages)
    slot = tpos % ps
    latent_pages = latent_pages.at[pg, slot, :].set(lq, mode="drop")
    latent_scales = latent_scales.at[pg, slot].set(ls, mode="drop")
    ctx, _ = paged_mla_chunk(
        q_abs, q_rope, dequantize_rows(latent_pages, latent_scales),
        block_tables, start, span, dequantize_rows(lq, ls),
        r=r, scale=scale)
    return ctx, latent_pages, latent_scales


def paged_mla_decode_quant(q_abs, q_rope, latent_pages, latent_scales,
                           block_tables, pos, latent_new, *,
                           r: int, scale: float):
    """Quantized ``paged_mla_decode``: the token's latent row quantizes into
    slot ``pos``; returns (ctx, latent_pages, latent_scales)."""
    ps = latent_pages.shape[1]
    lq, ls = quantize_rows(latent_new, latent_pages.dtype)   # [B,Dp],[B]
    pg_w = jnp.take_along_axis(block_tables, (pos // ps)[:, None],
                               axis=1)[:, 0]
    pg_w = jnp.where(pg_w < 0, latent_pages.shape[0], pg_w)
    slot_w = pos % ps
    latent_pages = latent_pages.at[pg_w, slot_w, :].set(lq, mode="drop")
    latent_scales = latent_scales.at[pg_w, slot_w].set(ls, mode="drop")
    ctx, _ = paged_mla_decode(
        q_abs, q_rope, dequantize_rows(latent_pages, latent_scales),
        block_tables, pos, dequantize_rows(lq, ls), r=r, scale=scale)
    return ctx, latent_pages, latent_scales


# ---------------------------------------------------------------------------
# Diagonal gated linear recurrence (RG-LRU / generic h_t = a_t h_{t-1} + b_t)
# ---------------------------------------------------------------------------

def linear_scan(a: jax.Array, b: jax.Array, h0: jax.Array) -> jax.Array:
    """h_t = a_t ⊙ h_{t-1} + b_t, returned for all t.  a,b: [B,T,D]; h0: [B,D]."""
    def step(h, ab):
        at, bt = ab
        h = at * h + bt
        return h, h

    a_t = jnp.moveaxis(a.astype(jnp.float32), 1, 0)
    b_t = jnp.moveaxis(b.astype(jnp.float32), 1, 0)
    _, hs = jax.lax.scan(step, h0.astype(jnp.float32), (a_t, b_t))
    return jnp.moveaxis(hs, 0, 1).astype(b.dtype)


def rglru(x: jax.Array, input_gate: jax.Array, rec_gate: jax.Array,
          log_lambda: jax.Array, h0: jax.Array, c: float = 8.0
          ) -> tuple[jax.Array, jax.Array]:
    """Griffin RG-LRU (arXiv:2402.19427 eq. 3-4).

    x, input_gate, rec_gate: [B, T, D] (gates pre-activation);
    log_lambda: [D] (learnt, param is softplus-domain); h0: [B, D].
    Returns (y [B,T,D], h_T [B,D]).
    """
    i_t = jax.nn.sigmoid(input_gate.astype(jnp.float32))
    r_t = jax.nn.sigmoid(rec_gate.astype(jnp.float32))
    log_a = -c * r_t * jax.nn.softplus(log_lambda.astype(jnp.float32))[None, None, :]
    a_t = jnp.exp(log_a)
    gated_x = i_t * x.astype(jnp.float32)
    b_t = jnp.sqrt(jnp.clip(1.0 - a_t ** 2, 1e-9)) * gated_x
    hs = linear_scan(a_t, b_t, h0)
    return hs.astype(x.dtype), hs[:, -1].astype(jnp.float32)


# ---------------------------------------------------------------------------
# Speculative-decoding verify path (span acceptance + rollback oracles)
# ---------------------------------------------------------------------------

def speculative_accept(preds: jax.Array, tokens: jax.Array,
                       span: jax.Array) -> jax.Array:
    """Greedy longest-accepted-prefix count per row.

    preds: i32[B, C] argmax at every span position (verify-mode mixed
    step); tokens: i32[B, C] the span that was fed, ``tokens[b] =
    [last_committed, d_1 .. d_m, pad]``; span: i32[B] = 1 + m.

    Draft token d_{j+1} is accepted iff every earlier draft was and the
    verifier's argmax after span position j reproduces it:
    ``preds[b, j] == tokens[b, j+1]``.  Returned count is in [0, m];
    rows with span <= 1 (plain decode / admission / idle) count 0.
    The *bonus* token ``preds[b, accepted[b]]`` is by construction the
    token non-speculative greedy decode would emit next, so acceptance
    plus bonus is token-identical to unspeculated decoding.
    """
    b, c = tokens.shape
    if c == 1:
        return jnp.zeros((b,), jnp.int32)
    ok = (preds[:, :-1] == tokens[:, 1:]) \
        & (jnp.arange(c - 1, dtype=jnp.int32)[None, :] < span[:, None] - 1)
    return jnp.where(ok.all(axis=1), c - 1,
                     jnp.argmin(ok, axis=1)).astype(jnp.int32)


def paged_span_gather(pool: jax.Array, block_tables: jax.Array,
                      start: jax.Array, width: int,
                      slot_axis: int | None = None) -> jax.Array:
    """Snapshot the pool slots a mixed-step write window covers.

    ``out[b, w] = pool[block_tables[b, (start[b]+w) // ps], ...,
    (start[b]+w) % ps, ...]`` — the pre-verify bytes of every slot a span
    write at [start, start+width) could touch.  pool: [P, Hkv, ps, D]
    (MHA K/V, slot axis 2) or [P, ps, Dp] (MLA latent, slot axis 1).
    Quantized scale leaves drop the trailing feature axis but keep the
    slot axis: [P, Hkv, ps] (MHA scales, slot axis 2) or [P, ps] (MLA
    scales, slot axis 1) — pass ``slot_axis`` explicitly for those.
    Positions past the table / unallocated (-1) entries are clamped; their
    lanes hold garbage and are masked out by ``paged_span_restore``.
    """
    if slot_axis is None:
        slot_axis = 2 if pool.ndim == 4 else 1
    ps = pool.shape[slot_axis]
    maxp = block_tables.shape[-1]
    tpos = start[:, None] + jnp.arange(width, dtype=jnp.int32)[None, :]
    pg = jnp.take_along_axis(block_tables,
                             jnp.clip(tpos // ps, 0, maxp - 1), axis=1)
    pg = jnp.clip(pg, 0, pool.shape[0] - 1)
    slot = tpos % ps
    if slot_axis == 2:
        return pool[pg, :, slot]             # [B, W, Hkv, (D)]
    return pool[pg, slot]                    # [B, W, (Dp)]


def paged_span_restore(pool: jax.Array, snap: jax.Array,
                       block_tables: jax.Array, start: jax.Array,
                       lo: jax.Array, hi: jax.Array,
                       slot_axis: int | None = None) -> jax.Array:
    """Rejected-tail rollback: scatter ``snap`` (from paged_span_gather,
    same ``start``) back for positions in [lo[b], hi[b]).

    Lanes outside the per-row window — accepted positions, rows that
    drafted nothing (lo == hi), positions past the table, unallocated
    entries — are routed out of bounds and dropped, so committed slots
    keep the verify step's writes bit-for-bit while the rejected tail
    reverts to its pre-verify bytes.  ``slot_axis`` as in
    ``paged_span_gather`` (pass explicitly for scale leaves).
    """
    if slot_axis is None:
        slot_axis = 2 if pool.ndim == 4 else 1
    ps = pool.shape[slot_axis]
    maxp = block_tables.shape[-1]
    width = snap.shape[1]
    tpos = start[:, None] + jnp.arange(width, dtype=jnp.int32)[None, :]
    keep = (tpos >= lo[:, None]) & (tpos < hi[:, None])
    keep &= tpos // ps < maxp
    pg = jnp.take_along_axis(block_tables,
                             jnp.clip(tpos // ps, 0, maxp - 1), axis=1)
    keep &= pg >= 0
    tgt = jnp.where(keep, jnp.clip(pg, 0, pool.shape[0] - 1),
                    pool.shape[0])
    slot = tpos % ps
    if slot_axis == 2:
        return pool.at[tgt, :, slot].set(snap.astype(pool.dtype),
                                         mode="drop")
    return pool.at[tgt, slot].set(snap.astype(pool.dtype), mode="drop")


def page_transfer(src_pool: jax.Array, dst_pool: jax.Array,
                  src_ids: jax.Array, dst_ids: jax.Array) -> jax.Array:
    """Cross-pool page-row transfer oracle: lane i copies
    ``src_pool[src_ids[i]]`` into ``dst_pool[dst_ids[i]]``; -1 on either
    side drops the lane.  Pure gather + mode="drop" scatter, so the moved
    rows are bitwise for any pool dtype and the rest of ``dst_pool`` is
    untouched.  Pools need the same row shape/dtype but may differ in
    page count.
    """
    p_src = src_pool.shape[0]
    rows = src_pool[jnp.clip(src_ids, 0, p_src - 1)]
    keep = (src_ids >= 0) & (dst_ids >= 0) & (dst_ids < dst_pool.shape[0])
    tgt = jnp.where(keep, jnp.clip(dst_ids, 0, dst_pool.shape[0] - 1),
                    dst_pool.shape[0])
    return dst_pool.at[tgt].set(rows, mode="drop")

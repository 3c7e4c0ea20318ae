"""Multi-head Latent Attention (DeepSeek-V2, arXiv:2405.04434).

KV state is compressed to a shared latent c_kv (rank ``kv_lora_rank``) plus a
small decoupled-RoPE key shared across heads — the cache stores only
[B, S, r + rope_dim] instead of [B, S, 2·H·head_dim].

Decode uses the *weight-absorption* identity: q_nopeᵀ·(c_kv·W_uk) =
(q_nope·W_ukᵀ)ᵀ·c_kv, so attention runs directly against the compressed
cache with no per-step decompression — the paper's serving trick, and the
reason MLA decode is memory-roofline-friendly.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.models import cache as cache_mod
from repro.models import common
from repro.models.config import ModelConfig

Params = Any

# Every paged-MLA layout this module serves; _q8/_fp8 carry an int8/fp8
# latent pool plus a per-row f32 scale pool and route to the *_quant kernels.
_PAGED_MLA = ("paged_mla", "paged_mla_q8", "paged_mla_fp8")


def init(key, cfg: ModelConfig) -> Params:
    m = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    ks = jax.random.split(key, 6)
    return {
        "w_dkv": common.dense_init(ks[0], d, m.kv_lora_rank),
        "w_kr": common.dense_init(ks[1], d, m.rope_head_dim),
        "w_uk": common.dense_init(ks[2], m.kv_lora_rank, h * m.nope_head_dim),
        "w_uv": common.dense_init(ks[3], m.kv_lora_rank, h * m.v_head_dim),
        "w_q": common.dense_init(ks[4], d, h * (m.nope_head_dim + m.rope_head_dim)),
        "w_o": common.dense_init(ks[5], h * m.v_head_dim, d),
        "kv_norm": common.norm_init(m.kv_lora_rank, "rmsnorm"),
    }


def _queries(p, cfg, x, positions):
    m = cfg.mla
    b, t, _ = x.shape
    h = cfg.num_heads
    q = common.dense(p["w_q"], x).reshape(b, t, h, m.nope_head_dim + m.rope_head_dim)
    q = q.transpose(0, 2, 1, 3)                                  # [B,H,T,*]
    q_nope, q_rope = jnp.split(q, [m.nope_head_dim], axis=-1)
    q_rope = common.apply_rope(q_rope, positions, cfg.rope_theta,
                               cfg.rope_scaling)
    return q_nope, q_rope


def softmax_scale(cfg: ModelConfig) -> float:
    """(nope + rope)^-1/2, times mscale(factor, mscale_all_dim)^2 under
    YaRN (DeepSeek-V2's attention scale)."""
    m = cfg.mla
    scale = (m.nope_head_dim + m.rope_head_dim) ** -0.5
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= common.yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def _latents(p, cfg, x, positions):
    ckv = common.apply_norm(p["kv_norm"], common.dense(p["w_dkv"], x),
                            "rmsnorm", cfg.norm_eps)             # [B,T,r]
    krope = common.apply_rope(common.dense(p["w_kr"], x)[:, None],
                              positions, cfg.rope_theta,
                              cfg.rope_scaling)[:, 0]            # [B,T,rope]
    return ckv, krope


def forward(p: Params, cfg: ModelConfig, x: jax.Array,
            mask, positions: jax.Array, impl: str = "ref",
            chunked: bool = False, prefix_len: int = 0) -> jax.Array:
    """Train/prefill path (expanded keys/values).

    The two-term MLA logits (q_nope·k_nope + q_rope·k_rope) are expressed as
    one contraction over concat([nope; rope]) so the shared (chunked) SDPA —
    and its 32k-safe online softmax — applies unchanged.
    """
    from repro.models import attention
    m = cfg.mla
    b, t, _ = x.shape
    h = cfg.num_heads
    q_nope, q_rope = _queries(p, cfg, x, positions)
    ckv, krope = _latents(p, cfg, x, positions)
    k_nope = (ckv @ p["w_uk"]["w"].astype(ckv.dtype)).reshape(
        b, t, h, m.nope_head_dim).transpose(0, 2, 1, 3)
    v = (ckv @ p["w_uv"]["w"].astype(ckv.dtype)).reshape(
        b, t, h, m.v_head_dim).transpose(0, 2, 1, 3)
    qc = jnp.concatenate([q_nope, q_rope], axis=-1)
    kc = jnp.concatenate(
        [k_nope, jnp.broadcast_to(krope[:, None], (b, h, t, m.rope_head_dim)
                                  ).astype(k_nope.dtype)], axis=-1)
    scale = softmax_scale(cfg)
    out = attention._sdpa(qc, kc, v, mask, scale, impl, chunked=chunked,
                          prefix_len=prefix_len)
    out = out.transpose(0, 2, 1, 3).reshape(b, t, h * m.v_head_dim)
    return common.dense(p["w_o"], out.astype(x.dtype))


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, *, paged: bool = False,
               page_size: int = 64, num_pages: int | None = None,
               kv_quant: str = "off") -> Params:
    """Dense latent cache [B, S, r] + [B, S, rd], or a paged latent pool.

    Paged mode stores concat([ckv; krope]) rows in a shared pool
    ``[P, page_size, pad128(r + rd)]`` mapped by per-row block tables —
    resident memory scales with allocated pages, and the fused decode kernel
    walks only live pages (see kernels/paged_mla_decode.py).
    """
    kind = "mla"
    return cache_mod.spec_for(kind, cfg, batch, max_len, dtype, paged=paged,
                              page_size=page_size, num_pages=num_pages,
                              kv_quant=kv_quant).init()


def _paged_latent_write(cache: Params, ckv: jax.Array, krope: jax.Array,
                        lengths: Optional[jax.Array]) -> Params:
    """Scatter a prompt's latent rows ([B, T, r]/[B, T, rd]) into pages.

    Same drop semantics as the MHA paged prefill: unallocated (-1) table
    entries, bucket padding past the table, and positions beyond a ragged
    row's length are all routed out of bounds and dropped.
    """
    bt = cache["block_tables"]
    pool = cache["latent_pages"]
    num_pages, ps, dp = pool.shape
    b, t, _ = ckv.shape
    tpos = jnp.arange(t, dtype=jnp.int32)
    pg = bt[:, tpos // ps]                              # [B, T]
    pg = jnp.where(pg < 0, num_pages, pg)
    pg = jnp.where(tpos[None, :] < bt.shape[1] * ps, pg, num_pages)
    if lengths is not None:
        pg = jnp.where(tpos[None, :] < lengths[:, None], pg, num_pages)
    slot = jnp.broadcast_to(tpos % ps, (b, t))
    lat = jnp.concatenate([ckv, krope], axis=-1)
    lat = jnp.pad(lat, ((0, 0), (0, 0), (0, dp - lat.shape[-1])))
    if "latent_scales" in cache:
        # Quantized pool: quantize the latent rows and land their scales
        # through the same drop routing.
        lq, ls = kref.quantize_rows(lat, pool.dtype)
        return dict(
            cache,
            latent_pages=pool.at[pg, slot, :].set(lq, mode="drop"),
            latent_scales=cache["latent_scales"].at[pg, slot].set(
                ls, mode="drop"))
    return dict(cache, latent_pages=pool.at[pg, slot, :].set(
        lat.astype(pool.dtype), mode="drop"))


def prefill(p, cfg, x, cache, mask, positions, impl="ref", chunked=False,
            prefix_len=0, lengths: Optional[jax.Array] = None):
    """``lengths`` (i32[B]) admits a ragged right-padded batch — attention
    over padding is masked by the caller's 3-D mask, cache writes beyond
    each row's length are dropped, and rows with ``lengths[b] == 0`` keep
    their cache bit-for-bit (the admission path relies on this)."""
    y = forward(p, cfg, x, mask, positions, impl, chunked=chunked,
                prefix_len=prefix_len)
    ckv, krope = _latents(p, cfg, x, positions)
    if cache_mod.layout_of(cache) in _PAGED_MLA:
        return y, _paged_latent_write(cache, ckv, krope, lengths)
    new_ckv = jax.lax.dynamic_update_slice(
        cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, 0, 0))
    new_krope = jax.lax.dynamic_update_slice(
        cache["krope"], krope.astype(cache["krope"].dtype), (0, 0, 0))
    if lengths is not None:
        s = cache["ckv"].shape[1]
        keep = (jnp.arange(s)[None, :] < lengths[:, None])[..., None]
        new_ckv = jnp.where(keep, new_ckv, cache["ckv"])
        new_krope = jnp.where(keep, new_krope, cache["krope"])
    return y, {"ckv": new_ckv, "krope": new_krope}


def mixed_step(p: Params, cfg: ModelConfig, x: jax.Array, cache: Params,
               start: jax.Array, span: jax.Array, positions: jax.Array,
               impl: str = "ref", layer: jax.Array | None = None
               ) -> tuple[jax.Array, Params]:
    """Per-row query spans against the compressed cache (mixed serve step).

    x: [B, C, d]; start/span: i32[B]; positions: i32[B, C].  Runs the
    absorbed-weight contractions of ``decode_step`` for every query in the
    span — one math for decode (span 1) and chunked admission (span C), so
    chunk partitioning cannot change the bits.  The span's latent rows are
    written before the attend (write-then-attend, causal intra-span).
    With ``layer``, a paged cache's pools are the [L, ...] stacks of every
    layer and this layer is ``layer`` of them.
    """
    m = cfg.mla
    b, c, _ = x.shape
    h = cfg.num_heads
    q_nope, q_rope = _queries(p, cfg, x, positions)               # [B,H,C,*]
    ckv_t, krope_t = _latents(p, cfg, x, positions)               # [B,C,*]
    w_uk = p["w_uk"]["w"].reshape(m.kv_lora_rank, h, m.nope_head_dim)
    q_abs = jnp.einsum("bhcn,rhn->bhcr", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
    scale = softmax_scale(cfg)
    layout = cache_mod.layout_of(cache)
    if layout in _PAGED_MLA:
        pool = cache["latent_pages"]
        dp = pool.shape[-1]
        lat_new = jnp.concatenate([ckv_t, krope_t], axis=-1)
        lat_new = jnp.pad(lat_new, ((0, 0), (0, 0),
                                    (0, dp - lat_new.shape[-1])))
        if layout != "paged_mla":
            ctx, pool, scales = kops.paged_mla_chunk_quant(
                q_abs, q_rope, pool, cache["latent_scales"],
                cache["block_tables"], start, span, lat_new, layer=layer,
                scale=scale, use_pallas=(impl == "pallas"))
            new_cache = dict(cache, latent_pages=pool,
                             latent_scales=scales)
        else:
            ctx, pool = kops.paged_mla_chunk(
                q_abs, q_rope, pool, cache["block_tables"], start, span,
                lat_new, layer=layer, scale=scale,
                use_pallas=(impl == "pallas"))
            new_cache = dict(cache, latent_pages=pool)
    else:
        # Dense latent cache: write the span via a position gather, then the
        # same absorbed contractions over the full stream.
        s = cache["ckv"].shape[1]
        pidx = jnp.arange(s, dtype=jnp.int32)
        off = pidx[None, :] - start[:, None]                     # [B, S]
        wmask = ((off >= 0) & (off < span[:, None]))[..., None]
        gidx = jnp.clip(off, 0, c - 1)[:, :, None]
        ckv_in = jnp.take_along_axis(
            ckv_t.astype(cache["ckv"].dtype),
            jnp.broadcast_to(gidx, (b, s, ckv_t.shape[-1])), axis=1)
        krope_in = jnp.take_along_axis(
            krope_t.astype(cache["krope"].dtype),
            jnp.broadcast_to(gidx, (b, s, krope_t.shape[-1])), axis=1)
        ckv_c = jnp.where(wmask, ckv_in, cache["ckv"])
        krope_c = jnp.where(wmask, krope_in, cache["krope"])
        logits = (jnp.einsum("bhcr,bsr->bhcs", q_abs, ckv_c,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bhcr,bsr->bhcs", q_rope, krope_c,
                               preferred_element_type=jnp.float32)) * scale
        valid = pidx[None, None, :] <= positions[:, :, None]
        logits = jnp.where(valid[:, None], logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        ctx = jnp.einsum("bhcs,bsr->bhcr", probs,
                         ckv_c.astype(jnp.float32),
                         preferred_element_type=jnp.float32)
        new_cache = {"ckv": ckv_c, "krope": krope_c}
    w_uv = p["w_uv"]["w"].reshape(m.kv_lora_rank, h, m.v_head_dim)
    out = jnp.einsum("bhcr,rhd->bhcd", ctx, w_uv.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    out = out.transpose(0, 2, 1, 3).reshape(b, c, h * m.v_head_dim)
    return common.dense(p["w_o"], out.astype(x.dtype)), new_cache


def decode_step(p: Params, cfg: ModelConfig, x: jax.Array, cache: Params,
                pos: jax.Array, impl: str = "ref") -> tuple[jax.Array, Params]:
    """Absorbed-weight decode against the compressed cache.  x: [B,1,d]."""
    m = cfg.mla
    b = x.shape[0]
    h = cfg.num_heads
    q_nope, q_rope = _queries(p, cfg, x, pos[:, None])            # [B,H,1,*]
    ckv_t, krope_t = _latents(p, cfg, x, pos[:, None])            # [B,1,*]
    layout = cache_mod.layout_of(cache)
    if layout in _PAGED_MLA:
        # Paged latent cache: O(page) fused write + block-table walk — the
        # one-hot rewrite of the full [B, S, r] latent stream disappears.
        # Absorbed q_abs/scale/contractions are IDENTICAL to the dense path
        # below, so the ref oracle is bit-compatible with dense decode.
        w_uk = p["w_uk"]["w"].reshape(m.kv_lora_rank, h, m.nope_head_dim)
        q_abs = jnp.einsum("bhn,rhn->bhr",
                           q_nope[:, :, 0].astype(jnp.float32),
                           w_uk.astype(jnp.float32),
                           preferred_element_type=jnp.float32)
        scale = softmax_scale(cfg)
        pool = cache["latent_pages"]
        dp = pool.shape[-1]
        lat_new = jnp.concatenate([ckv_t[:, 0], krope_t[:, 0]], axis=-1)
        lat_new = jnp.pad(lat_new, ((0, 0), (0, dp - lat_new.shape[-1])))
        if layout != "paged_mla":
            ctx, pool, scales = kops.paged_mla_decode_quant(
                q_abs, q_rope[:, :, 0], pool, cache["latent_scales"],
                cache["block_tables"], pos, lat_new, scale=scale,
                use_pallas=(impl == "pallas"))
            new_cache = dict(cache, latent_pages=pool, latent_scales=scales)
        else:
            ctx, pool = kops.paged_mla_decode(
                q_abs, q_rope[:, :, 0], pool, cache["block_tables"], pos,
                lat_new, scale=scale, use_pallas=(impl == "pallas"))
            new_cache = dict(cache, latent_pages=pool)
        w_uv = p["w_uv"]["w"].reshape(m.kv_lora_rank, h, m.v_head_dim)
        out = jnp.einsum("bhr,rhd->bhd", ctx, w_uv.astype(jnp.float32),
                         preferred_element_type=jnp.float32)
        out = out.reshape(b, 1, h * m.v_head_dim).astype(x.dtype)
        return common.dense(p["w_o"], out), new_cache
    # One-hot masked write (not a scatter): partitions cleanly when the
    # cache is sequence-sharded (see sharding/partition.py mla_cache="seq").
    s_len = cache["ckv"].shape[1]
    oh = (jnp.arange(s_len, dtype=jnp.int32)[None] == pos[:, None])[..., None]
    ckv_c = jnp.where(oh, ckv_t.astype(cache["ckv"].dtype), cache["ckv"])
    krope_c = jnp.where(oh, krope_t.astype(cache["krope"].dtype),
                        cache["krope"])
    # Absorb W_uk into the query: q_abs[b,h,r] = Σ_n q_nope · W_uk[r, h, n].
    # fp32 throughout: the absorbed path reorders contractions vs the train
    # path, so bf16 intermediates would not round identically.
    w_uk = p["w_uk"]["w"].reshape(m.kv_lora_rank, h, m.nope_head_dim)
    q_abs = jnp.einsum("bhn,rhn->bhr", q_nope[:, :, 0].astype(jnp.float32),
                       w_uk.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
    scale = softmax_scale(cfg)
    logits = (jnp.einsum("bhr,bsr->bhs", q_abs, ckv_c,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bhr,bsr->bhs", q_rope[:, :, 0], krope_c,
                           preferred_element_type=jnp.float32)) * scale
    s = ckv_c.shape[1]
    valid = jnp.arange(s)[None, :] <= pos[:, None]
    logits = jnp.where(valid[:, None, :], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    ctx = jnp.einsum("bhs,bsr->bhr", probs, ckv_c.astype(jnp.float32),
                     preferred_element_type=jnp.float32)          # latent ctx
    w_uv = p["w_uv"]["w"].reshape(m.kv_lora_rank, h, m.v_head_dim)
    out = jnp.einsum("bhr,rhd->bhd", ctx, w_uv.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    out = out.reshape(b, 1, h * m.v_head_dim).astype(x.dtype)
    return common.dense(p["w_o"], out), {"ckv": ckv_c, "krope": krope_c}

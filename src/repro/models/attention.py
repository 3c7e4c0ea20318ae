"""GQA/MQA attention with optional sliding window, QK-norm, RoPE, and a
single-token decode path against a KV cache.

The portable path is pure jnp (what the dry-run lowers — XLA sees the true
attention FLOPs); ``impl="pallas"`` routes the contraction through the
repro.kernels flash kernels on TPU.
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

# Every paged-MHA layout this module serves; the _q8/_fp8 variants carry
# int8/fp8 pools plus per-row f32 scale pools and route to the *_quant
# kernels (dequant fused into the block-table walk).
_PAGED_MHA = ("paged_mha", "paged_mha_q8", "paged_mha_fp8")


def init(key, cfg: ModelConfig, d_model: int | None = None) -> Params:
    d = d_model or cfg.d_model
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": common.dense_init(ks[0], d, hq * hd, cfg.use_bias),
        "wk": common.dense_init(ks[1], d, hkv * hd, cfg.use_bias),
        "wv": common.dense_init(ks[2], d, hkv * hd, cfg.use_bias),
        "wo": common.dense_init(ks[3], hq * hd, d, cfg.use_bias),
    }
    if cfg.qk_norm:
        p["q_norm"] = common.norm_init(hd, "rmsnorm")
        p["k_norm"] = common.norm_init(hd, "rmsnorm")
    return p


def _split_heads(x: jax.Array, n_heads: int) -> jax.Array:
    b, t, _ = x.shape
    return x.reshape(b, t, n_heads, -1).transpose(0, 2, 1, 3)   # [B,H,T,D]


def _merge_heads(x: jax.Array) -> jax.Array:
    b, h, t, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * d)


def _qkv(p: Params, cfg: ModelConfig, x: jax.Array, positions: jax.Array):
    q = _split_heads(common.dense(p["wq"], x), cfg.num_heads)
    k = _split_heads(common.dense(p["wk"], x), cfg.num_kv_heads)
    v = _split_heads(common.dense(p["wv"], x), cfg.num_kv_heads)
    if cfg.qk_norm:
        q = common.apply_norm(p["q_norm"], q, "rmsnorm", cfg.norm_eps)
        k = common.apply_norm(p["k_norm"], k, "rmsnorm", cfg.norm_eps)
    q = common.apply_rope(q, positions, cfg.rope_theta)
    k = common.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _block_mask(rows: jax.Array, cols: jax.Array, *, tk_true: int,
                causal: bool, window, prefix_len: int) -> jax.Array:
    """Lazy mask for a (rows × cols) tile; same semantics as common.make_mask."""
    r = rows[:, None]
    c = cols[None, :]
    mask = c < tk_true
    if causal:
        cm = c <= r
        if prefix_len > 0:
            cm |= (c < prefix_len)
        mask &= cm
    if window is not None:
        wm = c >= r - window + 1
        if prefix_len > 0:
            wm |= (c < prefix_len)
        mask &= wm
    return mask


def _sdpa_chunked(q, k, v, scale, *, causal=True, window=None, prefix_len=0,
                  chunk: int = 1024):
    """Blockwise online-softmax attention (portable flash structure).

    Never materializes [Tq, Tk] scores: a lax.scan over KV chunks carries
    (m, l, acc).  This is what makes prefill_32k lowerable — dense scores at
    32k would be ~4 GiB per head-row, f32.
    """
    b, hq, tq, d = q.shape
    _, hkv, tk, _ = k.shape
    dv = v.shape[-1]                      # may differ from d (MLA)
    group = hq // hkv
    nk = -(-tk // chunk)
    pad = nk * chunk - tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    ks = jnp.moveaxis(k.reshape(b, hkv, nk, chunk, d), 2, 0)
    vs = jnp.moveaxis(v.reshape(b, hkv, nk, chunk, dv), 2, 0)
    rows = jnp.arange(tq, dtype=jnp.int32) + (tk - tq)
    qf = q.astype(jnp.float32)

    def body(carry, inp):
        m, l, acc, start = carry
        kc, vc = inp
        kb = jnp.repeat(kc, group, axis=1).astype(jnp.float32)
        vb = jnp.repeat(vc, group, axis=1).astype(jnp.float32)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kb,
                       preferred_element_type=jnp.float32) * scale
        cols = start + jnp.arange(chunk, dtype=jnp.int32)
        mask = _block_mask(rows, cols, tk_true=tk, causal=causal,
                           window=window, prefix_len=prefix_len)
        s = jnp.where(mask[None, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vb, preferred_element_type=jnp.float32)
        return (m_new, l, acc, start + chunk), None

    m0 = jnp.full((b, hq, tq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, hq, tq), jnp.float32)
    a0 = jnp.zeros((b, hq, tq, dv), jnp.float32)
    (m, l, acc, _), _ = jax.lax.scan(body, (m0, l0, a0, jnp.int32(0)),
                                     (ks, vs))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def _sdpa(q, k, v, mask, scale, impl: str, window=None, causal=True,
          chunked=False, prefix_len=0):
    """q: [B,H,Tq,D]; k,v: [B,Hkv,Tk,D]; mask: bool[Tq,Tk] / [B,Tq,Tk] / None.

    A 3-D mask carries per-row validity (ragged prefill) — rows with zero
    valid keys produce NaN outputs; callers discard those rows and masked
    cache writes drop them.
    """
    if chunked:
        return _sdpa_chunked(q, k, v, scale, causal=causal, window=window,
                             prefix_len=prefix_len)
    if impl == "pallas" and mask is None:
        return kops.flash_attention(q, k, v, causal=causal, scale=scale,
                                    window=window)
    group = q.shape[1] // k.shape[1]
    kb = jnp.repeat(k, group, axis=1)
    vb = jnp.repeat(v, group, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, kb,
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        mask_b = mask[:, None] if mask.ndim == 3 else mask[None, None]
        logits = jnp.where(mask_b, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, vb)


def forward(p: Params, cfg: ModelConfig, x: jax.Array,
            mask: Optional[jax.Array], positions: jax.Array,
            impl: str = "ref", chunked: bool = False,
            prefix_len: int = 0) -> jax.Array:
    """Full-sequence path (train / prefill-without-cache)."""
    q, k, v = _qkv(p, cfg, x, positions)
    scale = cfg.head_dim ** -0.5
    out = _sdpa(q, k, v, mask, scale, impl, window=cfg.window,
                chunked=chunked, prefix_len=prefix_len)
    return common.dense(p["wo"], _merge_heads(out))


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, *, paged: bool = False,
               page_size: int = 64, num_pages: int | None = None,
               kv_quant: str = "off") -> Params:
    """Dense cache [B, Hkv, S, D], or a paged pool + per-row block tables.

    Paged mode: K/V live in a shared pool ``[P, Hkv, page_size, D]`` and each
    row maps logical positions to pages through ``block_tables[B, maxp]``
    (-1 = unallocated).  Resident memory scales with *allocated pages* (live
    tokens), not batch × max_len: ``num_pages`` can be far below
    ``batch * maxp`` when rows are ragged (a page allocator hands out pages
    on admission — see serving/scheduler.py).  Default is the dense-equal
    worst case so Engine can run without an allocator via
    ``default_block_tables``.

    Shapes are owned by the CacheSpec registry (models/cache.py); this is
    the thin per-module entry the block wiring calls.
    """
    return cache_mod.spec_for("attn", cfg, batch, max_len, dtype,
                              paged=paged, page_size=page_size,
                              num_pages=num_pages, kv_quant=kv_quant).init()


def default_block_tables(batch: int, max_len: int, page_size: int
                         ) -> jax.Array:
    """Identity mapping — row b owns contiguous pages [b*maxp, (b+1)*maxp).

    Needs the worst-case pool (num_pages == batch * maxp); real page reuse
    comes from the allocator in serving/scheduler.py.
    """
    maxp = -(-max_len // page_size)
    return jnp.arange(batch * maxp, dtype=jnp.int32).reshape(batch, maxp)


def _paged_prefill_write(cache: Params, k: jax.Array, v: jax.Array,
                         lengths: Optional[jax.Array]) -> Params:
    """Scatter a prompt's K/V ([B, Hkv, T, D]) into the row's pages.

    Positions >= lengths[b] (right-padding of a ragged batch) map to page -1
    and are dropped, so a prefill touches only the prefilled rows' pages —
    admission never disturbs in-flight rows.
    """
    bt = cache["block_tables"]
    ps = cache["k_pages"].shape[2]
    b, _, t, _ = k.shape
    tpos = jnp.arange(t, dtype=jnp.int32)
    num_pages = cache["k_pages"].shape[0]
    pg = bt[:, tpos // ps]                              # [B, T]
    # Dropped writes are routed OUT OF BOUNDS (= num_pages): a -1 sentinel
    # would wrap to the last page under jnp scatter semantics.  Dropped:
    # unallocated (-1) table entries, bucket padding past the table, and
    # positions beyond each row's ragged length.
    pg = jnp.where(pg < 0, num_pages, pg)
    pg = jnp.where(tpos[None, :] < bt.shape[1] * ps, pg, num_pages)
    if lengths is not None:
        pg = jnp.where(tpos[None, :] < lengths[:, None], pg, num_pages)
    slot = jnp.broadcast_to(tpos % ps, (b, t))
    if "k_scales" in cache:
        # Quantized pool: per-row scales ride alongside the values, written
        # through the exact same drop-routing so the pages/scales of
        # untouched rows stay bit-for-bit.
        kq, ks = kref.quantize_rows(k.transpose(0, 2, 1, 3),
                                    cache["k_pages"].dtype)
        vq, vs = kref.quantize_rows(v.transpose(0, 2, 1, 3),
                                    cache["v_pages"].dtype)
        return dict(
            cache,
            k_pages=cache["k_pages"].at[pg, :, slot, :].set(kq, mode="drop"),
            v_pages=cache["v_pages"].at[pg, :, slot, :].set(vq, mode="drop"),
            k_scales=cache["k_scales"].at[pg, :, slot].set(ks, mode="drop"),
            v_scales=cache["v_scales"].at[pg, :, slot].set(vs, mode="drop"))
    k_bt = k.transpose(0, 2, 1, 3).astype(cache["k_pages"].dtype)
    v_bt = v.transpose(0, 2, 1, 3).astype(cache["v_pages"].dtype)
    return dict(cache,
                k_pages=cache["k_pages"].at[pg, :, slot, :].set(
                    k_bt, mode="drop"),
                v_pages=cache["v_pages"].at[pg, :, slot, :].set(
                    v_bt, mode="drop"))


def prefill(p: Params, cfg: ModelConfig, x: jax.Array, cache: Params,
            mask: Optional[jax.Array], positions: jax.Array,
            impl: str = "ref", chunked: bool = False,
            prefix_len: int = 0,
            lengths: Optional[jax.Array] = None) -> tuple[jax.Array, Params]:
    """Full-prompt forward that also fills cache positions [0, T).

    ``lengths`` (i32[B]) marks a ragged right-padded batch: attention over
    padding is masked by the caller's 3-D mask and cache writes beyond each
    row's length are dropped, so rows with ``lengths[b] == 0`` keep their
    cache bit-for-bit (the admission path relies on this).
    """
    q, k, v = _qkv(p, cfg, x, positions)
    scale = cfg.head_dim ** -0.5
    out = _sdpa(q, k, v, mask, scale, impl, window=cfg.window,
                chunked=chunked, prefix_len=prefix_len)
    proj = common.dense(p["wo"], _merge_heads(out))
    if cache_mod.layout_of(cache) in _PAGED_MHA:
        return proj, _paged_prefill_write(cache, k, v, lengths)
    t = x.shape[1]
    s = cache["k"].shape[2]
    if t <= s:
        new_k = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0))
        new_v = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0))
        if lengths is not None:
            keep = jnp.arange(s)[None, :] < lengths[:, None]   # [B, S]
            oh = keep[:, None, :, None]
            new_k = jnp.where(oh, new_k, cache["k"])
            new_v = jnp.where(oh, new_v, cache["v"])
        new_cache = {"k": new_k, "v": new_v}
    else:
        if lengths is not None:
            raise NotImplementedError(
                "ragged prefill into a ring cache shorter than the padded "
                "prompt is unsupported — size the ring (window) >= the "
                "prompt bucket, or use a paged/dense cache")
        # Ring cache shorter than the prompt: slot s holds the LAST token
        # with absolute position ≡ s (mod S) — a deterministic gather (a
        # scatter with duplicate indices would have unspecified order).
        sl = jnp.arange(s, dtype=jnp.int32)
        p_last = (t - 1) - ((t - 1 - sl) % s)
        new_cache = {
            "k": k[:, :, p_last].astype(cache["k"].dtype),
            "v": v[:, :, p_last].astype(cache["v"].dtype),
        }
    return proj, new_cache


def mixed_step(p: Params, cfg: ModelConfig, x: jax.Array, cache: Params,
               start: jax.Array, span: jax.Array, positions: jax.Array,
               impl: str = "ref", layer: jax.Array | None = None
               ) -> tuple[jax.Array, Params]:
    """Per-row query spans against the cache (the mixed serve step).

    x: [B, C, D]; start: i32[B] tokens already cached per row; span: i32[B]
    valid new tokens in [0, C]; positions: i32[B, C] absolute positions
    (start + intra-span offset).  The span's K/V is written into the cache
    *before* the attend, so query j sees the whole cached prefix plus the
    span's keys up to itself — span 1 is a decode step, span C a prompt
    chunk, span 0 an idle row whose cache is untouched (output garbage).
    With ``layer``, a paged cache's pools are the [L, ...] stacks of every
    layer and this layer is ``layer`` of them.
    """
    b, c, _ = x.shape
    q, k, v = _qkv(p, cfg, x, positions)
    scale = cfg.head_dim ** -0.5
    layout = cache_mod.layout_of(cache)
    if layout in _PAGED_MHA:
        if layout != "paged_mha":
            out, k_pages, v_pages, k_scales, v_scales = (
                kops.paged_chunk_attention_quant(
                    q, cache["k_pages"], cache["k_scales"],
                    cache["v_pages"], cache["v_scales"],
                    cache["block_tables"], start, span, k, v, layer=layer,
                    scale=scale, window=cfg.window,
                    use_pallas=(impl == "pallas")))
            return (common.dense(p["wo"], _merge_heads(out).astype(x.dtype)),
                    dict(cache, k_pages=k_pages, v_pages=v_pages,
                         k_scales=k_scales, v_scales=v_scales))
        out, k_pages, v_pages = kops.paged_chunk_attention(
            q, cache["k_pages"], cache["v_pages"], cache["block_tables"],
            start, span, k, v, layer=layer, scale=scale, window=cfg.window,
            use_pallas=(impl == "pallas"))
        return (common.dense(p["wo"], _merge_heads(out).astype(x.dtype)),
                dict(cache, k_pages=k_pages, v_pages=v_pages))
    s = cache["k"].shape[2]
    # Dense cache: the mixed path assumes no ring wrap (S >= start + span) —
    # lm.mixed_step rejects windowed/ring patterns up front.  Write the span
    # via a position gather (slot p takes span token p - start when that
    # offset lies in [0, span)), then attend with the same gathered-view
    # masks as the paged oracle.
    pidx = jnp.arange(s, dtype=jnp.int32)
    off = pidx[None, :] - start[:, None]                         # [B, S]
    wmask = (off >= 0) & (off < span[:, None])
    gidx = jnp.clip(off, 0, c - 1)[:, None, :, None]
    k_in = jnp.take_along_axis(
        k.astype(cache["k"].dtype),
        jnp.broadcast_to(gidx, (b, k.shape[1], s, k.shape[3])), axis=2)
    v_in = jnp.take_along_axis(
        v.astype(cache["v"].dtype),
        jnp.broadcast_to(gidx, (b, v.shape[1], s, v.shape[3])), axis=2)
    oh = wmask[:, None, :, None]
    k_cache = jnp.where(oh, k_in, cache["k"])
    v_cache = jnp.where(oh, v_in, cache["v"])
    group = cfg.num_heads // cfg.num_kv_heads
    kb = jnp.repeat(k_cache, group, axis=1)
    vb = jnp.repeat(v_cache, group, axis=1)
    logits = jnp.einsum("bhcd,bhsd->bhcs", q.astype(jnp.float32),
                        kb.astype(jnp.float32)) * scale
    valid = pidx[None, None, :] <= positions[:, :, None]         # [B, C, S]
    if cfg.window is not None:
        valid &= pidx[None, None, :] > (positions[:, :, None] - cfg.window)
    logits = jnp.where(valid[:, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhcs,bhsd->bhcd", probs, vb.astype(jnp.float32))
    out = out.astype(x.dtype)
    return (common.dense(p["wo"], _merge_heads(out)),
            {"k": k_cache, "v": v_cache})


def decode_step(p: Params, cfg: ModelConfig, x: jax.Array, cache: Params,
                pos: jax.Array, impl: str = "ref") -> tuple[jax.Array, Params]:
    """One-token step.  x: [B, 1, D]; pos: i32[B] tokens already cached."""
    b = x.shape[0]
    q, k, v = _qkv(p, cfg, x, pos[:, None])
    layout = cache_mod.layout_of(cache)
    if layout in _PAGED_MHA and layout != "paged_mha":
        scale = cfg.head_dim ** -0.5
        cap = cache["block_tables"].shape[-1] * cache["k_pages"].shape[-2]
        out, k_pages, v_pages, k_scales, v_scales = (
            kops.paged_decode_attention_quant(
                q[:, :, 0], cache["k_pages"], cache["k_scales"],
                cache["v_pages"], cache["v_scales"], cache["block_tables"],
                jnp.minimum(pos, cap - 1), k[:, :, 0], v[:, :, 0],
                scale=scale, window=cfg.window,
                use_pallas=(impl == "pallas")))
        out = out.reshape(b, 1, cfg.num_heads * cfg.head_dim).astype(x.dtype)
        return (common.dense(p["wo"], out),
                dict(cache, k_pages=k_pages, v_pages=v_pages,
                     k_scales=k_scales, v_scales=v_scales))
    if layout == "paged_mha":
        # Paged cache: O(page) write + block-table walk — no one-hot rewrite
        # of [B, Hkv, S, D].  The write is fused into the Pallas kernel; the
        # ref path is the gather oracle (kernels/ref.py).  pos is clamped to
        # the block table's capacity: past it the last slot is rewritten
        # (defined, still wrong output — callers bound generation, see
        # Engine.step / scheduler.submit) instead of an out-of-bounds
        # table read corrupting a live page.
        scale = cfg.head_dim ** -0.5
        cap = cache["block_tables"].shape[-1] * cache["k_pages"].shape[-2]
        out, k_pages, v_pages = kops.paged_decode_attention(
            q[:, :, 0], cache["k_pages"], cache["v_pages"],
            cache["block_tables"], jnp.minimum(pos, cap - 1),
            k[:, :, 0], v[:, :, 0],
            scale=scale, window=cfg.window, use_pallas=(impl == "pallas"))
        out = out.reshape(b, 1, cfg.num_heads * cfg.head_dim).astype(x.dtype)
        return (common.dense(p["wo"], out),
                dict(cache, k_pages=k_pages, v_pages=v_pages))
    # One-hot masked write instead of a scatter: a scatter at dynamic per-row
    # positions into a sequence-sharded cache forces SPMD "involuntary full
    # rematerialization" (replicates the whole cache).  The masked select is
    # elementwise, partitions along every axis, and XLA fuses it into the
    # cache-resident update.  The new-token K/V is resharded while tiny
    # ([B, Hkv, D], head-sharded from the projection) BEFORE broadcasting
    # against the cache — otherwise XLA broadcasts first and replicates the
    # full cache to reshard it.
    from repro.sharding import activation
    s = cache["k"].shape[2]
    k_tok = activation.constrain(k[:, :, 0], "batch", None, None)
    v_tok = activation.constrain(v[:, :, 0], "batch", None, None)
    # Ring indexing: token at absolute position p lives at slot p % S.  For
    # unbounded caches (S >= max pos) this is the identity; for ring caches
    # (S == window) it bounds memory while keeping exactly the attendable
    # window resident (keys carry their absolute-position RoPE).
    onehot = (jnp.arange(s, dtype=jnp.int32)[None] == (pos % s)[:, None])
    oh = onehot[:, None, :, None]
    k_cache = jnp.where(oh, k_tok[:, :, None].astype(cache["k"].dtype),
                        cache["k"])
    v_cache = jnp.where(oh, v_tok[:, :, None].astype(cache["v"].dtype),
                        cache["v"])
    kv_len = jnp.minimum(pos + 1, s)
    scale = cfg.head_dim ** -0.5
    # The dense flash-decode kernel has no window masking: only route to it
    # when no window applies or the cache IS the window (ring, sdim ==
    # window) — an unbounded cache under sliding-window attention must take
    # the masked einsum path or it would attend beyond the window.
    if impl == "pallas" and (cfg.window is None or s <= cfg.window):
        out = kops.decode_attention(q[:, :, 0], k_cache, v_cache, kv_len,
                                    scale=scale)
    else:
        # Grouped GQA einsum — no jnp.repeat: materializing broadcast KV
        # forces GSPMD to reshard the (seq-sharded) cache into head layout.
        # Contracting over the sharded seq axis instead lowers to partial
        # logits/softmax + tiny all-reduces (flash-decode schedule).
        group = cfg.num_heads // cfg.num_kv_heads
        qg = q[:, :, 0].reshape(b, cfg.num_kv_heads, group, cfg.head_dim)
        logits = jnp.einsum("bkgd,bksd->bkgs", qg, k_cache,
                            preferred_element_type=jnp.float32) * scale
        sdim = k_cache.shape[2]
        valid = jnp.arange(sdim)[None, :] < kv_len[:, None]
        if cfg.window is not None and sdim > cfg.window:
            # Unbounded cache: mask out slots older than the window.  Ring
            # caches (sdim == window) hold exactly the window — no mask.
            valid &= jnp.arange(sdim)[None, :] > (pos[:, None] - cfg.window)
        logits = jnp.where(valid[:, None, None, :], logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        out = jnp.einsum("bkgs,bksd->bkgd", probs, v_cache)
        out = out.reshape(b, cfg.num_heads, cfg.head_dim)
    out = out.reshape(b, 1, cfg.num_heads * cfg.head_dim).astype(x.dtype)
    return common.dense(p["wo"], out), {"k": k_cache, "v": v_cache}

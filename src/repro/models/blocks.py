"""Block assembly: pre-norm residual wiring for every block kind, plus the
scan-over-groups driver that keeps HLO size O(1) in depth.

Block kinds (cfg.block_pattern):
  attn / local          attention (+FFN), full or sliding-window
  moe / mla / mla_moe   attention variants with MoE or latent-KV
  rglru                 Griffin temporal block (+FFN)
  slstm / mlstm         xLSTM blocks (self-contained, no extra FFN)
  xattn                 decoder block with cross-attention (whisper)
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models import attention, common, ffn, mla, moe, rglru, xlstm
from repro.models.config import ModelConfig

Params = Any


class BlockCtx(NamedTuple):
    """Per-call context shared by all blocks."""
    positions: jax.Array                  # [B, T] (or [B] in decode)
    mask_full: Optional[jax.Array]        # bool[Tq, Tk] or None (lazy if chunked)
    mask_local: Optional[jax.Array]
    enc_out: Optional[jax.Array] = None   # [B, Te, d] (whisper decoder)
    mode: str = "full"                    # "full" | "prefill" | "decode"
    pos: Optional[jax.Array] = None       # i32[B] cache fill level (decode)
    impl: str = "ref"
    chunked: bool = False                 # blockwise attention (long T)
    prefix_len: int = 0                   # bidirectional prefix (VLM)
    lengths: Optional[jax.Array] = None   # i32[B] ragged prefill lengths
    # i32 index of the layer in the [L, ...] page-pool and expert-bank
    # stacks that the mixed step's layer scan hands its kernels whole;
    # None where a layer's pools and banks carry no layer axis.
    layer: Optional[jax.Array] = None


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def block_init(kind: str, key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 6)
    d = cfg.d_model
    if kind in ("attn", "local", "moe"):
        p = {"norm1": common.norm_init(d, cfg.norm_type),
             "attn": attention.init(ks[0], cfg)}
        if not cfg.parallel_block:
            p["norm2"] = common.norm_init(d, cfg.norm_type)
        p["ffn"] = (moe.init(ks[1], cfg) if kind == "moe"
                    else ffn.init(ks[1], cfg))
        return p
    if kind in ("mla", "mla_moe"):
        return {"norm1": common.norm_init(d, cfg.norm_type),
                "attn": mla.init(ks[0], cfg),
                "norm2": common.norm_init(d, cfg.norm_type),
                "ffn": (moe.init(ks[1], cfg) if kind == "mla_moe"
                        else ffn.init(ks[1], cfg))}
    if kind == "rglru":
        return {"norm1": common.norm_init(d, cfg.norm_type),
                "rec": rglru.init(ks[0], cfg),
                "norm2": common.norm_init(d, cfg.norm_type),
                "ffn": ffn.init(ks[1], cfg)}
    if kind == "slstm":
        return {"norm1": common.norm_init(d, cfg.norm_type),
                "cell": xlstm.slstm_init(ks[0], cfg)}
    if kind == "mlstm":
        return {"norm1": common.norm_init(d, cfg.norm_type),
                "cell": xlstm.mlstm_init(ks[0], cfg)}
    if kind == "xattn":
        return {"norm1": common.norm_init(d, cfg.norm_type),
                "attn": attention.init(ks[0], cfg),
                "norm_x": common.norm_init(d, cfg.norm_type),
                "xattn": attention.init(ks[1], cfg),
                "norm2": common.norm_init(d, cfg.norm_type),
                "ffn": ffn.init(ks[2], cfg)}
    raise ValueError(f"unknown block kind {kind}")


def cache_init(kind: str, cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, *, paged: bool = False,
               page_size: int = 64, num_pages: int | None = None) -> Params:
    """``paged=True`` pools full-attention KV (MHA and MLA latent alike);
    sliding-window layers keep their dense/ring cache (already bounded by
    the window) and stateful kinds are untouched — a mixed-pattern model
    pages only what benefits.  All layouts come from the CacheSpec registry
    (models/cache.py), which is the single source of truth for shapes."""
    from repro.models import cache as cache_mod
    return cache_mod.spec_for(kind, cfg, batch, max_len, dtype, paged=paged,
                              page_size=page_size, num_pages=num_pages).init()


# ---------------------------------------------------------------------------
# Apply
# ---------------------------------------------------------------------------

def _norm(p, cfg, x):
    return common.apply_norm(p, x, cfg.norm_type, cfg.norm_eps)


def _cross_kv(p, cfg, enc_out):
    k = attention._split_heads(common.dense(p["wk"], enc_out), cfg.num_kv_heads)
    v = attention._split_heads(common.dense(p["wv"], enc_out), cfg.num_kv_heads)
    return k, v


def _cross_attend(p, cfg, x, k, v, impl):
    q = attention._split_heads(common.dense(p["wq"], x), cfg.num_heads)
    out = attention._sdpa(q, k, v, None, cfg.head_dim ** -0.5, "ref",
                          causal=False)
    return common.dense(p["wo"], attention._merge_heads(out).astype(x.dtype))


def _moe(p, cfg, x, ctx):
    """The expert FFN over the positions that hold tokens (a mixed step's
    spans, a ragged prefill's lengths), and its aux (see ``block_apply``)."""
    valid = (None if ctx.lengths is None
             else jnp.arange(x.shape[1])[None, :] < ctx.lengths[:, None])
    f, aux, counts = moe.forward(p, cfg, x, valid, layer=ctx.layer)
    return f, jnp.concatenate([aux[None], counts])


def block_apply(kind: str, p: Params, cfg: ModelConfig, x: jax.Array,
                ctx: BlockCtx, cache: Params | None
                ) -> tuple[jax.Array, Params | None, jax.Array]:
    """Returns (x, new_cache, aux).  aux is f32[3]: the MoE load-balancing
    loss, the expert pairs computed, and the most pairs one held expert
    took (see ``moe.forward``); zeros in a block without experts."""
    aux = jnp.zeros((3,), jnp.float32)
    decode = ctx.mode == "decode"
    mixed = ctx.mode == "mixed"

    if kind in ("attn", "local", "moe"):
        h = _norm(p["norm1"], cfg, x)
        mask = ctx.mask_local if kind == "local" else ctx.mask_full
        local_cfg = cfg if kind == "local" else cfg.replace(window=None)
        if mixed:
            a, cache = attention.mixed_step(p["attn"], local_cfg, h, cache,
                                            ctx.pos, ctx.lengths,
                                            ctx.positions, ctx.impl,
                                            layer=ctx.layer)
        elif decode:
            a, cache = attention.decode_step(p["attn"], local_cfg, h, cache,
                                             ctx.pos, ctx.impl)
        elif cache is not None:
            a, cache = attention.prefill(p["attn"], local_cfg, h, cache, mask,
                                         ctx.positions, ctx.impl,
                                         chunked=ctx.chunked,
                                         prefix_len=ctx.prefix_len,
                                         lengths=ctx.lengths)
        else:
            a = attention.forward(p["attn"], local_cfg, h, mask,
                                  ctx.positions, ctx.impl,
                                  chunked=ctx.chunked,
                                  prefix_len=ctx.prefix_len)
        if cfg.parallel_block:
            f = ffn.forward(p["ffn"], cfg, h)
            return x + a + f, cache, aux
        x = x + a
        h2 = _norm(p["norm2"], cfg, x)
        if kind == "moe":
            f, aux = _moe(p["ffn"], cfg, h2, ctx)
        else:
            f = ffn.forward(p["ffn"], cfg, h2)
        return x + f, cache, aux

    if kind in ("mla", "mla_moe"):
        h = _norm(p["norm1"], cfg, x)
        if mixed:
            a, cache = mla.mixed_step(p["attn"], cfg, h, cache, ctx.pos,
                                      ctx.lengths, ctx.positions, ctx.impl,
                                      layer=ctx.layer)
        elif decode:
            a, cache = mla.decode_step(p["attn"], cfg, h, cache, ctx.pos,
                                       ctx.impl)
        elif cache is not None:
            a, cache = mla.prefill(p["attn"], cfg, h, cache, ctx.mask_full,
                                   ctx.positions, ctx.impl,
                                   chunked=ctx.chunked,
                                   prefix_len=ctx.prefix_len,
                                   lengths=ctx.lengths)
        else:
            a = mla.forward(p["attn"], cfg, h, ctx.mask_full, ctx.positions,
                            ctx.impl, chunked=ctx.chunked,
                            prefix_len=ctx.prefix_len)
        x = x + a
        h2 = _norm(p["norm2"], cfg, x)
        if kind == "mla_moe":
            f, aux = _moe(p["ffn"], cfg, h2, ctx)
        else:
            f = ffn.forward(p["ffn"], cfg, h2)
        return x + f, cache, aux

    # Recurrent kinds: the mixed mode is exactly a ragged forward — masked
    # state carry-through advances each row's state by its span, rows with
    # span 0 keep their state bit-for-bit.
    if kind == "rglru":
        h = _norm(p["norm1"], cfg, x)
        if decode:
            r, cache = rglru.decode_step(p["rec"], cfg, h, cache, ctx.pos,
                                         ctx.impl)
        else:
            r, cache = rglru.forward(p["rec"], cfg, h, cache, ctx.impl,
                                     lengths=ctx.lengths)
        x = x + r
        f = ffn.forward(p["ffn"], cfg, _norm(p["norm2"], cfg, x))
        return x + f, cache, aux

    if kind == "slstm":
        h = _norm(p["norm1"], cfg, x)
        if decode:
            y, cache = xlstm.slstm_decode(p["cell"], cfg, h, cache)
        else:
            y, cache = xlstm.slstm_forward(p["cell"], cfg, h, cache,
                                           lengths=ctx.lengths)
        return x + y, cache, aux

    if kind == "mlstm":
        h = _norm(p["norm1"], cfg, x)
        if decode:
            y, cache = xlstm.mlstm_decode(p["cell"], cfg, h, cache)
        else:
            y, cache = xlstm.mlstm_forward(p["cell"], cfg, h, cache,
                                           lengths=ctx.lengths)
        return x + y, cache, aux

    if kind == "xattn":
        h = _norm(p["norm1"], cfg, x)
        if decode:
            a, sc = attention.decode_step(
                p["attn"], cfg, h, {"k": cache["k"], "v": cache["v"]},
                ctx.pos, ctx.impl)
            cache = dict(cache, **sc)
            hx = _norm(p["norm_x"], cfg, x + a)
            q = attention._split_heads(common.dense(p["xattn"]["wq"], hx),
                                       cfg.num_heads)
            out = attention._sdpa(q, cache["xk"], cache["xv"], None,
                                  cfg.head_dim ** -0.5, "ref", causal=False)
            c = common.dense(p["xattn"]["wo"],
                             attention._merge_heads(out).astype(x.dtype))
        else:
            if cache is not None:
                a, sc = attention.prefill(
                    p["attn"], cfg, h, {"k": cache["k"], "v": cache["v"]},
                    ctx.mask_full, ctx.positions, ctx.impl,
                    chunked=ctx.chunked)
                xk, xv = _cross_kv(p["xattn"], cfg, ctx.enc_out)
                cache = dict(cache, **sc,
                             xk=xk.astype(cache["xk"].dtype),
                             xv=xv.astype(cache["xv"].dtype))
            else:
                a = attention.forward(p["attn"], cfg, h, ctx.mask_full,
                                      ctx.positions, ctx.impl,
                                      chunked=ctx.chunked)
                xk, xv = _cross_kv(p["xattn"], cfg, ctx.enc_out)
            hx = _norm(p["norm_x"], cfg, x + a)
            kx = cache["xk"] if cache is not None else xk
            vx = cache["xv"] if cache is not None else xv
            c = _cross_attend(p["xattn"], cfg, hx, kx.astype(x.dtype),
                              vx.astype(x.dtype), ctx.impl)
        x = x + a + c
        f = ffn.forward(p["ffn"], cfg, _norm(p["norm2"], cfg, x))
        return x + f, cache, aux

    raise ValueError(f"unknown block kind {kind}")

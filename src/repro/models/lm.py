"""Top-level language model: embedding → scanned block groups → head.

Depth is folded into a ``lax.scan`` over stacked per-group parameters so the
HLO (and compile time at 512 devices) is O(1) in num_layers; leading dense
layers (``cfg.first_dense``) run unstacked as "lead" blocks before the scan,
pattern remainders as "tail" blocks after it.  Supports decoder-only, prefix-LM
(VLM stub embeddings), and encoder-decoder (whisper stub frames).

All entry points are pure functions over (params, cfg, inputs) — pjit them
with the partitioner in repro.sharding.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import blocks, common
from repro.models import cache as cache_mod
from repro.models.blocks import BlockCtx
from repro.models.config import ModelConfig
from repro.sharding import activation

Params = Any

# When True, the layer-group scans are fully unrolled.  Used only by the
# dry-run's cost-extrapolation lowers: XLA's HloCostAnalysis visits while
# bodies once, so unrolled small variants give exact per-group marginals.
_UNROLL = False


import contextlib


@contextlib.contextmanager
def unrolled_scans():
    global _UNROLL
    old, _UNROLL = _UNROLL, True
    try:
        yield
    finally:
        _UNROLL = old


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init(key, cfg: ModelConfig) -> Params:
    keys = jax.random.split(key, 8)
    d, v = cfg.d_model, cfg.vocab_size
    p: dict[str, Any] = {
        "embed": {"w": (jax.random.normal(keys[0], (v, d), jnp.float32)
                        * d ** -0.5).astype(common.PARAM_DTYPE)},
        "final_norm": common.norm_init(d, cfg.norm_type),
    }
    if not cfg.tie_embeddings:
        p["head"] = common.dense_init(keys[1], d, v)

    g = cfg.pattern_groups
    groups = {}
    for i, kind in enumerate(cfg.block_pattern):
        gkeys = jax.random.split(jax.random.fold_in(keys[2], i), g)
        groups[str(i)] = jax.vmap(
            lambda k, kd=kind: blocks.block_init(kd, k, cfg))(gkeys)
    p["groups"] = groups

    for name, kinds, k in (("lead", cfg.lead_blocks, keys[6]),
                           ("tail", cfg.tail_blocks, keys[3])):
        if kinds:
            p[name] = {str(i): blocks.block_init(
                kind, jax.random.fold_in(k, i), cfg)
                for i, kind in enumerate(kinds)}

    if cfg.is_encdec:
        e = cfg.encoder
        ekeys = jax.random.split(keys[4], e.num_layers)
        p["encoder"] = {
            "pos": (jax.random.normal(keys[5], (e.seq_len, d), jnp.float32)
                    * 0.02).astype(common.PARAM_DTYPE),
            "blocks": jax.vmap(
                lambda k: blocks.block_init("attn", k, cfg))(ekeys),
            "norm": common.norm_init(d, cfg.norm_type),
        }
    return p


def abstract_params(cfg: ModelConfig) -> Params:
    """Parameter ShapeDtypeStructs without allocating (dry-run path)."""
    return jax.eval_shape(lambda: init(jax.random.PRNGKey(0), cfg))


# ---------------------------------------------------------------------------
# Helpers
# ---------------------------------------------------------------------------

def _embed(p: Params, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    x = p["embed"]["w"][tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


def _head(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        logits = x @ p["embed"]["w"].T.astype(x.dtype)
    else:
        logits = common.dense(p["head"], x)
    # Keep logits vocab-sharded through the fp32 loss (see sharding/activation).
    logits = activation.constrain(logits, "batch", None, "vocab")
    return common.softcap(logits.astype(jnp.float32), cfg.logit_softcap)


def _encode(p: Params, cfg: ModelConfig, enc_frames: jax.Array,
            impl: str) -> jax.Array:
    """Bidirectional encoder over stub frame embeddings [B, Te, d]."""
    e = p["encoder"]
    x = enc_frames + e["pos"].astype(enc_frames.dtype)[None]
    te = x.shape[1]
    ctx = BlockCtx(positions=jnp.arange(te), mask_full=None, mask_local=None,
                   mode="full", impl=impl)

    def body(carry, bp):
        y, _, _ = blocks.block_apply("attn", bp, cfg.replace(window=None),
                                     carry, ctx, None)
        return y, None

    x, _ = jax.lax.scan(body, x, e["blocks"], unroll=_UNROLL)
    return common.apply_norm(e["norm"], x, cfg.norm_type, cfg.norm_eps)


CHUNKED_THRESHOLD = 8192


def _make_ctx(cfg: ModelConfig, t: int, enc_out, impl: str,
              prefix_len: int,
              lengths: Optional[jax.Array] = None) -> BlockCtx:
    if t > CHUNKED_THRESHOLD:
        if lengths is not None:
            raise NotImplementedError(
                "ragged prefill above the chunked-attention threshold")
        # Long sequences: lazy masks + blockwise online-softmax attention
        # (materialized T×T masks/scores would be GiB-scale at 32k+).
        return BlockCtx(positions=jnp.arange(t), mask_full=None,
                        mask_local=None, enc_out=enc_out, mode="full",
                        impl=impl, chunked=True, prefix_len=prefix_len)
    mask_full = common.make_mask(t, t, causal=True, prefix_len=prefix_len)
    mask_local = (common.make_mask(t, t, causal=True, window=cfg.window,
                                   prefix_len=prefix_len)
                  if "local" in cfg.block_pattern else None)
    if lengths is not None:
        # Per-row validity: padding keys are unattendable everywhere.
        valid = jnp.arange(t)[None, :] < lengths[:, None]       # [B, T]
        mask_full = mask_full[None] & valid[:, None, :]
        if mask_local is not None:
            mask_local = mask_local[None] & valid[:, None, :]
    return BlockCtx(positions=jnp.arange(t), mask_full=mask_full,
                    mask_local=mask_local, enc_out=enc_out, mode="full",
                    impl=impl, prefix_len=prefix_len, lengths=lengths)


def _fold_aux(acc: jax.Array, a: jax.Array) -> jax.Array:
    """Accumulate one block's aux (see ``blocks.block_apply``): the loss
    and the pairs add, the busiest expert's pairs take the max."""
    return jnp.concatenate([acc[:2] + a[:2], jnp.maximum(acc[2:], a[2:])])


def _run_unstacked(p: Params, cfg: ModelConfig, name: str, kinds, x, ctx,
                   cache: Params | None, new_cache, aux):
    """The unstacked ``lead`` or ``tail`` layers, each with its own cache."""
    if name not in p:
        return x, aux
    layer_caches = cache.get(name, {}) if cache is not None else {}
    out = {}
    for i, kind in enumerate(kinds):
        x, c, a = blocks.block_apply(kind, p[name][str(i)], cfg, x, ctx,
                                     layer_caches.get(str(i)))
        out[str(i)] = c
        aux = _fold_aux(aux, a)
    if cache is not None:
        new_cache[name] = out
    return x, aux


def _split(tree, names) -> tuple[dict, Any]:
    """(the leaves of dict ``tree`` named in ``names``, the rest)."""
    if not names:
        return {}, tree
    return ({k: tree[k] for k in names},
            {k: v for k, v in tree.items() if k not in names})


def _scan_cached(p: Params, cfg: ModelConfig, x: jax.Array, ctx: BlockCtx,
                 groups: Params, aux: jax.Array):
    """The cache-carrying layer scan of every mode.

    In the mixed step no kernel gets a scan slice of a stacked buffer.  A
    Pallas call cannot fuse one, so XLA would materialise each layer's page
    pools and held-expert banks (and copy and write back the pools, which
    the kernels alias) every step.  Instead the pools (``cache.pool_leaves``:
    pool and scale roles) ride the scan's carry as whole ``[L, P, ...]``
    stacks, updated in place; the expert banks are closed over as read-only
    constants; and each kernel addresses layer ``ctx.layer`` of its stack.
    Block tables, recurrent state, dense caches and every other parameter
    stay in the scan's xs / ys, as everything does in the other modes
    (``ctx.layer`` None there)."""
    mixed = ctx.mode == "mixed"
    pools, rest, banks, gps = {}, {}, {}, {}
    for k, gc in groups.items():
        gp = p["groups"][k]
        pools[k], rest[k] = _split(gc, cache_mod.pool_leaves(
            gc, cache_mod.layout_of(gc)) if mixed else ())
        if mixed and "experts" in gp.get("ffn", {}):
            ffn = dict(gp["ffn"])
            banks[k] = ffn.pop("experts")
            gp = dict(gp, ffn=ffn)
        gps[k] = gp

    def body(carry, inp):
        y, aux, pools = carry
        gp, gc, layer = inp
        lctx = ctx._replace(layer=layer)
        new_pools, new_gc = {}, {}
        for i, kind in enumerate(cfg.block_pattern):
            k = str(i)
            lp = (dict(gp[k], ffn=dict(gp[k]["ffn"], experts=banks[k]))
                  if k in banks else gp[k])
            lc = dict(gc[k], **pools[k]) if pools[k] else gc[k]
            y, c, a = blocks.block_apply(kind, lp, cfg, y, lctx, lc)
            new_pools[k], new_gc[k] = _split(c, list(pools[k]))
            aux = _fold_aux(aux, a)
        return (y, aux, new_pools), new_gc

    layers = (jnp.arange(cfg.pattern_groups, dtype=jnp.int32) if mixed
              else None)
    (x, aux, pools), rest = jax.lax.scan(body, (x, aux, pools),
                                         (gps, rest, layers), unroll=_UNROLL)
    return x, {k: dict(rest[k], **pools[k]) if pools[k] else rest[k]
               for k in rest}, aux


def _run_blocks(p: Params, cfg: ModelConfig, x: jax.Array, ctx: BlockCtx,
                cache: Params | None, remat: bool = False
                ) -> tuple[jax.Array, Params | None, jax.Array]:
    """Lead + scanned groups + tail.  cache=None for pure training forward.

    ``remat=True`` checkpoints each scanned group (activation recompute in
    the backward pass) — the standard memory/compute trade for deep stacks.
    The aux is f32[3]: the MoE load-balancing loss and the expert pairs
    computed, summed over layers, and the most pairs any one held expert
    took in one layer.
    """
    aux0 = jnp.zeros((3,), jnp.float32)
    new_cache = dict(cache) if cache is not None else None
    x, aux0 = _run_unstacked(p, cfg, "lead", cfg.lead_blocks, x, ctx, cache,
                             new_cache, aux0)

    if cache is None:
        def body(carry, gp):
            y, aux = carry
            # Sequence-parallel residual stream: the remat-saved scan input is
            # sharded over ("batch", seq->model); GSPMD all-gathers T in front
            # of attention and reduce-scatters after (Megatron-SP schedule),
            # shrinking the per-device saved-activation footprint by the
            # model-axis size.
            y = activation.constrain(y, "batch", "seq", None)
            for i, kind in enumerate(cfg.block_pattern):
                y, _, a = blocks.block_apply(kind, gp[str(i)], cfg, y, ctx,
                                             None)
                aux = _fold_aux(aux, a)
            return (y, aux), None

        if remat:
            body = jax.checkpoint(body, prevent_cse=False)
        (x, aux), _ = jax.lax.scan(body, (x, aux0), p["groups"],
                                    unroll=_UNROLL)
    else:
        x, new_cache["groups"], aux = _scan_cached(p, cfg, x, ctx,
                                                   cache["groups"], aux0)

    x, aux = _run_unstacked(p, cfg, "tail", cfg.tail_blocks, x, ctx, cache,
                            new_cache, aux)
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Training / full-sequence forward
# ---------------------------------------------------------------------------

def forward(p: Params, cfg: ModelConfig, tokens: jax.Array,
            prefix_embeds: Optional[jax.Array] = None,
            enc_frames: Optional[jax.Array] = None,
            impl: str = "ref", remat: bool = False
            ) -> tuple[jax.Array, jax.Array]:
    """tokens: [B, Tt] -> (logits [B, T, V], moe_aux)."""
    x = _embed(p, cfg, tokens)
    prefix_len = 0
    if cfg.num_prefix_tokens and prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
        prefix_len = cfg.num_prefix_tokens
    enc_out = (_encode(p, cfg, enc_frames, impl)
               if cfg.is_encdec and enc_frames is not None else None)
    ctx = _make_ctx(cfg, x.shape[1], enc_out, impl, prefix_len)
    x, _, aux = _run_blocks(p, cfg, x, ctx, None, remat=remat)
    x = common.apply_norm(p["final_norm"], x, cfg.norm_type, cfg.norm_eps)
    return _head(p, cfg, x), aux[0]


def loss_fn(p: Params, cfg: ModelConfig, batch: dict[str, jax.Array],
            impl: str = "ref", aux_weight: float = 0.01, remat: bool = False
            ) -> tuple[jax.Array, dict[str, jax.Array]]:
    """batch: tokens [B,T], targets [B,T], loss_mask f32[B,T] (+ stub inputs)."""
    logits, aux = forward(
        p, cfg, batch["tokens"],
        prefix_embeds=batch.get("prefix_embeds"),
        enc_frames=batch.get("enc_frames"), impl=impl, remat=remat)
    # Prefix positions carry no next-token loss (logits cover prefix + text).
    logits = logits[:, -batch["tokens"].shape[1]:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    tgt = jnp.take_along_axis(logp, batch["targets"][..., None], axis=-1)[..., 0]
    mask = batch["loss_mask"].astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    xent = -jnp.sum(tgt * mask) / denom
    total = xent + aux_weight * aux
    return total, {"xent": xent, "moe_aux": aux,
                   "tokens": jnp.sum(mask)}


# ---------------------------------------------------------------------------
# Serving: prefill + single-token decode
# ---------------------------------------------------------------------------

def cache_specs(cfg: ModelConfig, batch: int, max_len: int,
                dtype=jnp.bfloat16, *, paged: bool = False,
                page_size: int = 64, num_pages: int | None = None,
                kv_quant: str = "off"):
    """The CacheSpec registry for this model — one spec per layer slot."""
    return cache_mod.model_cache_specs(cfg, batch, max_len, dtype,
                                      paged=paged, page_size=page_size,
                                      num_pages=num_pages, kv_quant=kv_quant)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, *, paged: bool = False,
               page_size: int = 64, num_pages: int | None = None,
               kv_quant: str = "off") -> Params:
    """``paged=True`` gives every full-attention layer (MHA pools, MLA
    latent pools) its own page pool + block tables; ``num_pages`` is per
    layer.  Layouts come from the CacheSpec registry (models/cache.py).
    ``kv_quant`` ("off" | "int8" | "fp8") swaps paged pools for quantized
    layouts carrying per-row scale leaves."""
    specs = cache_specs(cfg, batch, max_len, dtype, paged=paged,
                        page_size=page_size, num_pages=num_pages,
                        kv_quant=kv_quant)
    groups = {}
    for i, spec in specs["groups"].items():
        # One jitted call writes each [G, ...] stack once; eager
        # broadcast-then-copy held two transient stacks per leaf, which
        # set a paged cache's device memory peak.
        groups[i] = jax.jit(lambda spec=spec: jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.pattern_groups,) + x.shape),
            spec.init()))()
    cache: dict[str, Any] = {"groups": groups}
    for name in ("lead", "tail"):
        if name in specs:
            cache[name] = {i: spec.init()
                           for i, spec in specs[name].items()}
    return cache


# Typed traversal / block-table plumbing live in models/cache.py; these
# re-exports keep the historical lm.* entry points working.
set_block_tables = cache_mod.set_block_tables
get_block_tables = cache_mod.get_block_tables
copy_pages = cache_mod.copy_pages
copy_pages_across = cache_mod.copy_pages_across
export_pages = cache_mod.export_pages
adopt_pages = cache_mod.adopt_pages


def prefill(p: Params, cfg: ModelConfig, tokens: jax.Array, cache: Params,
            prefix_embeds: Optional[jax.Array] = None,
            enc_frames: Optional[jax.Array] = None,
            impl: str = "ref",
            lengths: Optional[jax.Array] = None) -> tuple[jax.Array, Params]:
    """Uniform-length prompt [B, P] -> (last-position logits [B, V], cache).

    ``lengths`` (i32[B]) admits a *ragged* right-padded batch: row b's
    prompt is tokens[b, :lengths[b]], logits come from its last valid
    position, and rows with ``lengths[b] == 0`` pass through untouched
    (cache preserved, output garbage) — which is what lets the scheduler
    admit new requests into freed rows while the others keep decoding.
    """
    if lengths is not None:
        # Recurrent kinds ride ragged admission through masked state
        # carry-through (padding steps are exact identities per row).
        ragged_ok = {"attn", "local", "moe", "mla", "mla_moe",
                     "rglru", "slstm", "mlstm"}
        kinds = set(cfg.all_blocks)
        if (kinds - ragged_ok or cfg.num_prefix_tokens or cfg.is_encdec):
            raise NotImplementedError(
                f"ragged prefill supports decoder-only patterns without "
                f"prefix/encoder inputs, got {cfg.block_pattern}")
    x = _embed(p, cfg, tokens)
    prefix_len = 0
    if cfg.num_prefix_tokens and prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
        prefix_len = cfg.num_prefix_tokens
    enc_out = (_encode(p, cfg, enc_frames, impl)
               if cfg.is_encdec and enc_frames is not None else None)
    ctx = _make_ctx(cfg, x.shape[1], enc_out, impl, prefix_len,
                    lengths=lengths)
    ctx = ctx._replace(mode="prefill")
    x, cache, _ = _run_blocks(p, cfg, x, ctx, cache)
    if lengths is not None:
        last = jnp.clip(lengths - 1, 0)[:, None, None]
        x = jnp.take_along_axis(x, jnp.broadcast_to(
            last, (x.shape[0], 1, x.shape[2])), axis=1)
    else:
        x = x[:, -1:]
    x = common.apply_norm(p["final_norm"], x, cfg.norm_type, cfg.norm_eps)
    return _head(p, cfg, x)[:, 0], cache


def decode_step(p: Params, cfg: ModelConfig, token: jax.Array, cache: Params,
                pos: jax.Array, impl: str = "ref"
                ) -> tuple[jax.Array, Params]:
    """token: i32[B]; pos: i32[B] cache fill -> (logits [B, V], cache)."""
    x = _embed(p, cfg, token[:, None])
    ctx = BlockCtx(positions=pos[:, None], mask_full=None, mask_local=None,
                   mode="decode", pos=pos, impl=impl)
    x, cache, _ = _run_blocks(p, cfg, x, ctx, cache)
    x = common.apply_norm(p["final_norm"], x, cfg.norm_type, cfg.norm_eps)
    return _head(p, cfg, x)[:, 0], cache


MIXED_OK = {"attn", "local", "moe", "mla", "mla_moe",
            "rglru", "slstm", "mlstm"}


def mixed_step(p: Params, cfg: ModelConfig, tokens: jax.Array, cache: Params,
               start: jax.Array, span: jax.Array, impl: str = "ref",
               all_logits: bool = False):
    """Token-budget mixed step: per-row query spans in one batched call.

    tokens: i32[B, C] right-padded span tokens; start: i32[B] tokens already
    cached per row; span: i32[B] valid new tokens in [0, C].  Row b runs a
    span of ``span[b]`` queries at positions ``start[b] + [0, span[b])`` —
    span 1 decodes one token, span C admits one prompt chunk, span 0 leaves
    the row's cache bit-for-bit untouched.  Returns (logits [B, V] at each
    row's last valid span position, cache, counts); span-0 rows' logits are
    garbage.  counts f32[2] are the step's expert pairs computed on held
    experts and the busiest held expert's pairs in any one layer (zeros
    without experts).
    With ``all_logits`` (the speculative-decoding verify mode) the head runs
    at EVERY span position and logits are [B, C, V] — position j's logits
    predict the token after span token j, so a drafted continuation can be
    verified wholesale in this one call (positions >= span[b] are garbage).

    Because every layer writes the span into the cache before attending,
    a query's math depends only on (its position, the cached prefix) —
    chunk partitioning cannot change the bits, which is what makes chunked
    admission bit-for-bit equivalent to a one-shot prefill.
    """
    kinds = set(cfg.all_blocks)
    if (kinds - MIXED_OK or cfg.num_prefix_tokens or cfg.is_encdec):
        raise NotImplementedError(
            f"mixed step supports decoder-only patterns without prefix or "
            f"encoder inputs, got {cfg.block_pattern}")
    if "local" in kinds and cfg.ring_local_cache and cfg.window:
        # A ring cache wraps under multi-token spans: a later span token can
        # overwrite a slot an earlier query's window still needs.  Windowed
        # layers over an UNBOUNDED dense cache are fine (masking handles the
        # window); only the ring layout is excluded.
        raise NotImplementedError(
            "mixed step over a ring local cache is unsupported — disable "
            "ring_local_cache (dense windowed cache) to serve chunked")
    b, c = tokens.shape
    x = _embed(p, cfg, tokens)
    positions = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    ctx = BlockCtx(positions=positions, mask_full=None, mask_local=None,
                   mode="mixed", pos=start, impl=impl, lengths=span)
    x, cache, aux = _run_blocks(p, cfg, x, ctx, cache)
    if not all_logits:
        last = jnp.clip(span - 1, 0)[:, None, None]
        x = jnp.take_along_axis(
            x, jnp.broadcast_to(last, (b, 1, x.shape[2])), axis=1)
    x = common.apply_norm(p["final_norm"], x, cfg.norm_type, cfg.norm_eps)
    logits = _head(p, cfg, x)
    if not all_logits:
        logits = logits[:, 0]
    return logits, cache, aux[1:]


def verify_step(p: Params, cfg: ModelConfig, tokens: jax.Array, cache: Params,
                start: jax.Array, span: jax.Array, impl: str = "ref"
                ) -> tuple[jax.Array, jax.Array, Params]:
    """Speculative-decoding verify: one all-logits mixed step plus per-row
    greedy accept counts.

    ``tokens[b] = [last_committed, d_1 .. d_m, pad]`` with ``span = 1 + m``
    (plain decode/admission rows ride along with their usual spans and
    count 0).  Returns (preds i32[B, C] — argmax after every span
    position, accepted i32[B] — longest accepted draft prefix per
    ``kernels.ref.speculative_accept``, cache).  The cache afterwards
    holds the whole span's writes; the caller commits positions up to its
    accept point and rolls the rejected tail back bitwise via
    ``cache.snapshot_span`` / ``restore_span`` (+ ``restore_state_rows``
    and a committed-span replay for recurrent architectures).
    """
    from repro.kernels import ref as kref
    logits, cache, _ = mixed_step(p, cfg, tokens, cache, start, span,
                                  impl=impl, all_logits=True)
    preds = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    accepted = kref.speculative_accept(
        preds, jnp.asarray(tokens, jnp.int32), jnp.asarray(span, jnp.int32))
    return preds, accepted, cache


def _state_layers(cfg: ModelConfig, cache: Params):
    """(part, index, kind) of every recurrent (state-layout) layer cache:
    part "groups" is stacked over the scan, "lead" / "tail" are not."""
    for name, kinds in (("lead", cfg.lead_blocks),
                        ("groups", cfg.block_pattern),
                        ("tail", cfg.tail_blocks)):
        if name not in cache:
            continue
        for i, kind in enumerate(kinds):
            if cache_mod.layout_for(kind, cfg, paged=False) == "state":
                yield name, str(i), kind


def reset_state_rows(cfg: ModelConfig, cache: Params, mask: jax.Array
                     ) -> Params:
    """Reset recurrent (state-layout) layers to fresh init for rows where
    ``mask`` is True — a freed row must not leak its h/conv/cell state into
    the next admitted request.  Attention caches need no reset: their writes
    overwrite and their reads are position-bounded."""
    mask = jnp.asarray(mask, bool)
    batch = int(mask.shape[0])

    def blend(kind, layer, stacked):
        spec = cache_mod.spec_for(kind, cfg, batch, 1)
        fresh = spec.init()

        def one(f, o):
            m = mask.reshape(((1,) if stacked else ()) + (batch,)
                             + (1,) * (f.ndim - 1))
            f = f.astype(o.dtype)
            return jnp.where(m, f[None] if stacked else f, o)

        return jax.tree.map(one, fresh, layer)

    out: dict[str, Any] = {}
    for name, i, kind in _state_layers(cfg, cache):
        out.setdefault(name, dict(cache[name]))[i] = blend(
            kind, cache[name][i], stacked=name == "groups")
    return dict(cache, **out)


def snapshot_state_rows(cfg: ModelConfig, cache: Params) -> Params:
    """Copy the recurrent (state-layout) carries — the whole-row half of a
    speculative-decoding rollback snapshot (attention slots are per-span,
    see ``cache.snapshot_span``).  ``jnp.copy`` forces fresh buffers so the
    snapshot survives the verify call donating the live cache."""
    out: dict[str, Any] = {"groups": {}}
    for name, i, _ in _state_layers(cfg, cache):
        out.setdefault(name, {})[i] = jax.tree.map(jnp.copy, cache[name][i])
    return out


def restore_state_rows(cfg: ModelConfig, cache: Params, snap: Params,
                       mask: jax.Array) -> Params:
    """Blend ``snap`` (from :func:`snapshot_state_rows`) back into rows
    where ``mask`` is True — rejected-draft rollback for recurrent layers.

    Unlike attention slots, a recurrent carry folds every span token
    irreversibly, so a partial rejection restores the PRE-VERIFY carry and
    the caller then replays the committed prefix (a second mixed step over
    just the accepted tokens) to advance it; the replay's attention writes
    are bitwise idempotent with the verify step's, so only the state moves.
    """
    mask = jnp.asarray(mask, bool)
    batch = int(mask.shape[0])

    def blend(snap_layer, layer, stacked):
        def one(s, o):
            nd = o.ndim - 1 - (1 if stacked else 0)
            m = mask.reshape(((1,) if stacked else ()) + (batch,)
                             + (1,) * nd)
            return jnp.where(m, s, o)

        return jax.tree.map(one, snap_layer, layer)

    out: dict[str, Any] = {}
    for name, i, _ in _state_layers(cfg, cache):
        out.setdefault(name, dict(cache[name]))[i] = blend(
            snap[name][i], cache[name][i], stacked=name == "groups")
    return dict(cache, **out)

"""CacheSpec: the typed registry of per-layer KV/state cache layouts.

Every block kind's cache is described by a :class:`CacheSpec` — its layout
name plus typed leaves (name, shape, dtype, role) — built by a registered
layout builder.  The spec is host-side metadata derived from the config; the
cache itself stays a plain pytree of arrays (jit/donation-friendly), but all
structural decisions (init shapes, which layers are paged, where the block
tables and pools live, how a pool leaf shards) are answered HERE instead of
by duck-typing dict keys at trace time.

Layouts:
  dense          [B, Hkv, S, D] K/V (ring when S == window < max_len)
  paged_mha      shared K/V pools [P, Hkv, ps, D] + block_tables [B, maxp]
  paged_mha_q8   int8 pools [P, Hkv, ps, D] + f32 scales [P, Hkv, ps]
  paged_mha_fp8  fp8 (e4m3) pools + the same scale leaves (dtype-gated)
  dense_mla      compressed latent stream [B, S, r] + RoPE key [B, S, rd]
  paged_mla      latent pool [P, ps, pad128(r + rd)] + block_tables [B, maxp]
  paged_mla_q8   int8 latent pool + f32 latent_scales [P, ps]
  paged_mla_fp8  fp8 latent pool + the same scale leaf (dtype-gated)
  state          recurrent carries (rglru/xLSTM) — opaque, never paged
  xattn          dense self-KV + once-filled cross-KV

Leaf roles drive the generic machinery:
  kv      per-row cache body (dense layouts)
  pool    shared page pool — resident memory unit, shards over heads or the
          latent-feature axis, COW page copies operate on dim 0
  scale   per-page quantization scales riding alongside a quantized pool —
          page-indexed like the pool (one f32 scale per pool row within
          each page), copied/snapshotted/restored WITH their pages so COW,
          speculative rollback and replication stay exact
  table   per-row block table — replicated, host-managed, validated shape
  state   recurrent carry

The MLA latent pool feature dim is padded to a multiple of 128 (TPU lane
width) at init so the fused kernel never pads per step; ``latent_width``
records the live width (kv_lora_rank + rope_head_dim).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

Params = Any

ROLE_KV = "kv"
ROLE_POOL = "pool"
ROLE_SCALE = "scale"
ROLE_TABLE = "table"
ROLE_STATE = "state"

# Quantization maxima are the symmetric representable ranges the
# kernels/oracles scale into.
FP8_DTYPE = jnp.float8_e4m3fn
INT8_QMAX = 127.0
FP8_QMAX = 448.0                 # e4m3 finite max

KV_QUANT_MODES = ("off", "int8", "fp8")

# pool leaf -> its scale leaf (quantized layouts only)
SCALE_LEAF = {"k_pages": "k_scales", "v_pages": "v_scales",
              "latent_pages": "latent_scales"}


def pad128(n: int) -> int:
    return -(-n // 128) * 128


@dataclass(frozen=True)
class Leaf:
    """One typed cache array: its name, full shape, dtype, and role."""
    name: str
    shape: tuple[int, ...]
    dtype: Any
    role: str
    fill: float = 0.0            # block tables init to -1, arrays to 0

    def init(self) -> jax.Array:
        if self.fill == 0.0:
            return jnp.zeros(self.shape, self.dtype)
        return jnp.full(self.shape, self.fill, self.dtype)


@dataclass(frozen=True)
class CacheSpec:
    """Layout descriptor for one layer's cache."""
    kind: str                    # block kind ("attn", "mla", ...)
    layout: str                  # dense | paged_mha | dense_mla | paged_mla
    leaves: tuple[Leaf, ...]     #        | state | xattn
    page_size: int = 0
    num_pages: int = 0
    latent_width: int = 0        # live features of a padded latent pool
    # Recurrent carries keep their module-owned init (non-zero fills,
    # nested trees); Leaf-driven init covers every attention layout.
    init_fn: Callable[[], Params] | None = None

    @property
    def paged(self) -> bool:
        return self.layout.startswith("paged")

    def leaf(self, name: str) -> Leaf:
        for l in self.leaves:
            if l.name == name:
                return l
        raise KeyError(f"{self.layout} spec has no leaf {name!r}")

    def init(self) -> Params:
        if self.init_fn is not None:
            return self.init_fn()
        return {l.name: l.init() for l in self.leaves}

    def abstract(self) -> Params:
        if self.init_fn is not None:
            return jax.eval_shape(self.init_fn)
        return {l.name: jax.ShapeDtypeStruct(l.shape, l.dtype)
                for l in self.leaves}


# ---------------------------------------------------------------------------
# Layout builders (the registry)
# ---------------------------------------------------------------------------

_LAYOUTS: dict[str, Callable[..., CacheSpec]] = {}


def register_layout(name: str):
    def deco(fn):
        _LAYOUTS[name] = fn
        return fn
    return deco


@register_layout("dense")
def _dense(kind, cfg, batch, max_len, dtype, **_) -> CacheSpec:
    shape = (batch, cfg.num_kv_heads, max_len, cfg.head_dim)
    return CacheSpec(kind, "dense", (
        Leaf("k", shape, dtype, ROLE_KV),
        Leaf("v", shape, dtype, ROLE_KV),
    ))


@register_layout("paged_mha")
def _paged_mha(kind, cfg, batch, max_len, dtype, *, page_size=64,
               num_pages=None, **_) -> CacheSpec:
    maxp = -(-max_len // page_size)
    if num_pages is None:
        num_pages = batch * maxp
    pool = (num_pages, cfg.num_kv_heads, page_size, cfg.head_dim)
    return CacheSpec(kind, "paged_mha", (
        Leaf("k_pages", pool, dtype, ROLE_POOL),
        Leaf("v_pages", pool, dtype, ROLE_POOL),
        Leaf("block_tables", (batch, maxp), jnp.int32, ROLE_TABLE, fill=-1),
    ), page_size=page_size, num_pages=num_pages)


@register_layout("dense_mla")
def _dense_mla(kind, cfg, batch, max_len, dtype, **_) -> CacheSpec:
    m = cfg.mla
    return CacheSpec(kind, "dense_mla", (
        Leaf("ckv", (batch, max_len, m.kv_lora_rank), dtype, ROLE_KV),
        Leaf("krope", (batch, max_len, m.rope_head_dim), dtype, ROLE_KV),
    ))


@register_layout("paged_mla")
def _paged_mla(kind, cfg, batch, max_len, dtype, *, page_size=64,
               num_pages=None, **_) -> CacheSpec:
    m = cfg.mla
    width = m.kv_lora_rank + m.rope_head_dim
    maxp = -(-max_len // page_size)
    if num_pages is None:
        num_pages = batch * maxp
    return CacheSpec(kind, "paged_mla", (
        Leaf("latent_pages", (num_pages, page_size, pad128(width)), dtype,
             ROLE_POOL),
        Leaf("block_tables", (batch, maxp), jnp.int32, ROLE_TABLE, fill=-1),
    ), page_size=page_size, num_pages=num_pages, latent_width=width)


def _quantized(base: str, layout: str, qdtype, kind, cfg, batch, max_len,
               dtype, **kw) -> CacheSpec:
    """Derive a quantized layout from its fp layout: pool leaves store the
    quantized dtype and each gains an f32 scale leaf of the pool shape minus
    the feature axis (one scale per pool row within each page).  Scales init
    to 1.0 — a scale is never zero, even for untouched pages."""
    spec = _LAYOUTS[base](kind, cfg, batch, max_len, dtype, **kw)
    leaves: list[Leaf] = []
    for l in spec.leaves:
        if l.role != ROLE_POOL:
            leaves.append(l)
            continue
        leaves.append(Leaf(l.name, l.shape, qdtype, ROLE_POOL))
        leaves.append(Leaf(SCALE_LEAF[l.name], l.shape[:-1], jnp.float32,
                           ROLE_SCALE, fill=1.0))
    return CacheSpec(kind, layout, tuple(leaves), page_size=spec.page_size,
                     num_pages=spec.num_pages, latent_width=spec.latent_width)


@register_layout("paged_mha_q8")
def _paged_mha_q8(kind, cfg, batch, max_len, dtype, **kw) -> CacheSpec:
    return _quantized("paged_mha", "paged_mha_q8", jnp.int8, kind, cfg,
                      batch, max_len, dtype, **kw)


@register_layout("paged_mla_q8")
def _paged_mla_q8(kind, cfg, batch, max_len, dtype, **kw) -> CacheSpec:
    return _quantized("paged_mla", "paged_mla_q8", jnp.int8, kind, cfg,
                      batch, max_len, dtype, **kw)


@register_layout("paged_mha_fp8")
def _paged_mha_fp8(kind, cfg, batch, max_len, dtype, **kw) -> CacheSpec:
    return _quantized("paged_mha", "paged_mha_fp8", FP8_DTYPE, kind, cfg,
                      batch, max_len, dtype, **kw)


@register_layout("paged_mla_fp8")
def _paged_mla_fp8(kind, cfg, batch, max_len, dtype, **kw) -> CacheSpec:
    return _quantized("paged_mla", "paged_mla_fp8", FP8_DTYPE, kind, cfg,
                      batch, max_len, dtype, **kw)


@register_layout("xattn")
def _xattn(kind, cfg, batch, max_len, dtype, **_) -> CacheSpec:
    shape = (batch, cfg.num_kv_heads, max_len, cfg.head_dim)
    xshape = (batch, cfg.num_kv_heads, cfg.encoder.seq_len, cfg.head_dim)
    return CacheSpec(kind, "xattn", (
        Leaf("k", shape, dtype, ROLE_KV),
        Leaf("v", shape, dtype, ROLE_KV),
        Leaf("xk", xshape, dtype, ROLE_KV),
        Leaf("xv", xshape, dtype, ROLE_KV),
    ))


@register_layout("state")
def _state(kind, cfg, batch, max_len, dtype, **_) -> CacheSpec:
    # Recurrent carries keep their module-owned init (non-zero fills); the
    # spec records abstract leaves so generic traversals stay total.
    from repro.models import rglru, xlstm
    init = {"rglru": lambda: rglru.init_cache(cfg, batch, dtype),
            "slstm": lambda: xlstm.slstm_state(cfg, batch),
            "mlstm": lambda: xlstm.mlstm_state(cfg, batch)}[kind]
    tree = jax.eval_shape(init)
    leaves = tuple(Leaf(str(_key_str(path[-1])), tuple(x.shape), x.dtype,
                        ROLE_STATE)
                   for path, x in jax.tree_util.tree_flatten_with_path(tree)[0])
    return CacheSpec(kind, "state", leaves, init_fn=init)


def _key_str(k) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    return str(getattr(k, "name", getattr(k, "idx", k)))


# ---------------------------------------------------------------------------
# Kind -> layout routing
# ---------------------------------------------------------------------------

def layout_for(kind: str, cfg, *, paged: bool) -> str:
    """Which layout a block kind uses under the requested paging mode."""
    if kind in ("attn", "moe"):
        return "paged_mha" if paged else "dense"
    if kind == "local":
        # Ring/windowed layers stay dense: already bounded by the window.
        return "dense"
    if kind in ("mla", "mla_moe"):
        return "paged_mla" if paged else "dense_mla"
    if kind in ("rglru", "slstm", "mlstm"):
        return "state"
    if kind == "xattn":
        return "xattn"
    raise ValueError(f"unknown block kind {kind}")


def quant_layout(layout: str, kv_quant: str) -> str:
    """Quantized variant of a paged layout (identity for 'off' / non-paged:
    dense layouts rewrite whole rows per step, so quantizing them would
    re-quantize history every token — only page pools quantize)."""
    if kv_quant in (None, "", "off"):
        return layout
    if kv_quant not in KV_QUANT_MODES:
        raise ValueError(f"unknown kv_quant {kv_quant!r}: pick one of "
                         f"{KV_QUANT_MODES}")
    if layout not in ("paged_mha", "paged_mla"):
        return layout
    return layout + ("_q8" if kv_quant == "int8" else "_fp8")


def spec_for(kind: str, cfg, batch: int, max_len: int, dtype=jnp.bfloat16,
             *, paged: bool = False, page_size: int = 64,
             num_pages: int | None = None,
             kv_quant: str = "off") -> CacheSpec:
    layout = quant_layout(layout_for(kind, cfg, paged=paged), kv_quant)
    if kind == "local" and cfg.ring_local_cache and cfg.window:
        max_len = min(max_len, cfg.window)
    return _LAYOUTS[layout](kind, cfg, batch, max_len, dtype,
                            page_size=page_size, num_pages=num_pages)


def model_cache_specs(cfg, batch: int, max_len: int, dtype=jnp.bfloat16,
                      *, paged: bool = False, page_size: int = 64,
                      num_pages: int | None = None,
                      kv_quant: str = "off") -> dict[str, Any]:
    """The full registry for one model: {"groups": {i: spec}, "lead": ...,
    "tail": ...} (lead/tail only where the model has such layers).

    Group specs describe ONE group's leaves; the stacked cache carries a
    leading [G] axis on every array (see lm.init_cache).
    """
    specs: dict[str, Any] = {"groups": {
        str(i): spec_for(kind, cfg, batch, max_len, dtype, paged=paged,
                         page_size=page_size, num_pages=num_pages,
                         kv_quant=kv_quant)
        for i, kind in enumerate(cfg.block_pattern)}}
    for name, kinds in (("lead", cfg.lead_blocks),
                        ("tail", cfg.tail_blocks)):
        if kinds:
            specs[name] = {
                str(i): spec_for(kind, cfg, batch, max_len, dtype,
                                 paged=paged, page_size=page_size,
                                 num_pages=num_pages, kv_quant=kv_quant)
                for i, kind in enumerate(kinds)}
    return specs


# ---------------------------------------------------------------------------
# Layout detection + typed traversal (replaces _map_paged_dicts duck-typing)
# ---------------------------------------------------------------------------

# A layer cache dict is identified by its leaf-name set: one entry per
# registered layout.  Detection is structural (the cache is a plain pytree
# under jit) but the *vocabulary* is owned by the registry — a new layout
# registers its leaf set here or traversals refuse it.
_LEAFSETS: dict[frozenset, str] = {
    frozenset({"k", "v"}): "dense",
    frozenset({"k_pages", "v_pages", "block_tables"}): "paged_mha",
    frozenset({"ckv", "krope"}): "dense_mla",
    frozenset({"latent_pages", "block_tables"}): "paged_mla",
    frozenset({"k", "v", "xk", "xv"}): "xattn",
    # int8 and fp8 share leaf names; layout_of disambiguates by pool dtype.
    frozenset({"k_pages", "v_pages", "k_scales", "v_scales",
               "block_tables"}): "paged_mha_q8",
    frozenset({"latent_pages", "latent_scales",
               "block_tables"}): "paged_mla_q8",
}


def layout_of(layer_cache: dict) -> str | None:
    """Layout name of one layer's cache dict (None if not a layer dict)."""
    if not isinstance(layer_cache, dict):
        return None
    name = _LEAFSETS.get(frozenset(layer_cache.keys()))
    if name in ("paged_mha_q8", "paged_mla_q8"):
        pool = layer_cache["k_pages" if "k_pages" in layer_cache
                           else "latent_pages"]
        if pool.dtype == FP8_DTYPE:
            return name[:-len("_q8")] + "_fp8"
    return name


def iter_layers(cache: Params, path: tuple[str, ...] = ()
                ) -> Iterator[tuple[tuple[str, ...], str, dict]]:
    """Yield (path, layout, layer_dict) for every recognized layer cache."""
    if not isinstance(cache, dict):
        return
    layout = layout_of(cache)
    if layout is not None:
        yield path, layout, cache
        return
    for k, v in cache.items():
        yield from iter_layers(v, path + (str(k),))


def map_layers(cache: Params, fn, *, layouts: tuple[str, ...] | None = None
               ) -> Params:
    """Rebuild the cache tree with ``fn(path, layout, layer)`` applied to
    every layer dict (matching ``layouts`` when given, all otherwise)."""
    def rec(tree, path):
        if not isinstance(tree, dict):
            return tree
        layout = layout_of(tree)
        if layout is not None:
            if layouts is None or layout in layouts:
                return fn(path, layout, tree)
            return tree
        return {k: rec(v, path + (str(k),)) for k, v in tree.items()}

    return rec(cache, ())


# Per paged layout: every leaf that travels with its pages (pools AND their
# scale leaves) -> that leaf's unstacked ndim.  The generic page machinery
# (copy_pages / snapshot_span / restore_span / swap) iterates this, so scales
# ride along with zero special-casing at the call sites.
_POOL_LEAF_NDIM: dict[str, dict[str, int]] = {
    "paged_mha": {"k_pages": 4, "v_pages": 4},
    "paged_mha_q8": {"k_pages": 4, "v_pages": 4, "k_scales": 3,
                     "v_scales": 3},
    "paged_mha_fp8": {"k_pages": 4, "v_pages": 4, "k_scales": 3,
                      "v_scales": 3},
    "paged_mla": {"latent_pages": 3},
    "paged_mla_q8": {"latent_pages": 3, "latent_scales": 2},
    "paged_mla_fp8": {"latent_pages": 3, "latent_scales": 2},
}

PAGED_LAYOUTS = tuple(_POOL_LEAF_NDIM)
QUANT_LAYOUTS = tuple(l for l in PAGED_LAYOUTS if "_q8" in l or "_fp8" in l)

# Slot axis of every pool/scale leaf within one paged layer: MHA-family
# leaves are [P, Hkv, ps, ...] (slot axis 2), MLA-family [P, ps, ...]
# (slot axis 1) — scale leaves just drop the trailing feature axis.
_SPAN_SLOT_AXIS = {l: (2 if l.startswith("paged_mha") else 1)
                   for l in PAGED_LAYOUTS}


def pool_leaves(layer: dict, layout: str) -> list[str]:
    return list(_POOL_LEAF_NDIM.get(layout, {}))


# ---------------------------------------------------------------------------
# Block tables: install / read / validate
# ---------------------------------------------------------------------------

def set_block_tables(cache: Params, block_tables: jax.Array) -> Params:
    """Install one [B, maxp] block table into every paged layer.

    Layers share the mapping (same tokens, same pages-per-row); scanned
    groups carry it stacked [G, B, maxp].  The table shape is validated
    against every paged layer's own table — a mismatched table would
    silently broadcast into the wrong pages otherwise.
    """
    bt = jnp.asarray(block_tables).astype(jnp.int32)
    for path, layout, layer in iter_layers(cache):
        if layout not in PAGED_LAYOUTS:
            continue
        want = layer["block_tables"].shape[-2:]
        if bt.shape != want:
            raise ValueError(
                f"block table shape {tuple(bt.shape)} does not match layer "
                f"{'/'.join(path)} ({layout}): expected [B, maxp] = "
                f"{tuple(want)}")

    def install(path, layout, layer):
        return dict(layer, block_tables=jnp.broadcast_to(
            bt, layer["block_tables"].shape))

    return map_layers(cache, install, layouts=PAGED_LAYOUTS)


def get_block_tables(cache: Params) -> jax.Array | None:
    """The [B, maxp] block table shared by the paged layers (None if dense)."""
    for _, layout, layer in iter_layers(cache):
        if layout in PAGED_LAYOUTS:
            bt = layer["block_tables"]
            return bt[0] if bt.ndim == 3 else bt
    return None


# ---------------------------------------------------------------------------
# Page copy (COW) — device-side page duplication across every paged layer
# ---------------------------------------------------------------------------

def copy_pages(cache: Params, src: jax.Array, dst: jax.Array) -> Params:
    """Copy pool pages ``src[i] -> dst[i]`` in every paged layer.

    src/dst: i32[N] page ids (pad unused lanes with -1: those copies drop).
    The copy-on-write path: a row about to write a shared page gets a
    private duplicate, then its block table is remapped (host side).
    """
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    if src.shape != dst.shape or src.ndim != 1:
        raise ValueError(
            f"copy_pages: src/dst page-id vectors must be matching 1-D "
            f"arrays: got src {tuple(src.shape)} vs dst {tuple(dst.shape)}")

    def cp(path, layout, layer):
        out = dict(layer)
        for name in pool_leaves(layer, layout):
            pool = layer[name]
            stacked = pool.ndim == _POOL_LEAF_NDIM[layout][name] + 1
            p = pool.shape[1] if stacked else pool.shape[0]
            safe_src = jnp.clip(src, 0, p - 1)
            tgt = jnp.where((src >= 0) & (dst >= 0), dst, p)
            if stacked:                                   # leading [G]
                rows = pool[:, safe_src]
                out[name] = pool.at[:, tgt].set(rows, mode="drop")
            else:
                rows = pool[safe_src]
                out[name] = pool.at[tgt].set(rows, mode="drop")
        return out

    return map_layers(cache, cp, layouts=PAGED_LAYOUTS)


# ---------------------------------------------------------------------------
# Cross-pool page transfer (prefill/decode disaggregation)
# ---------------------------------------------------------------------------
#
# Disaggregated serving moves *physical* page bytes between two engines'
# caches: the prefill replica fills pages and publishes them through the
# replicated prefix cache; the decode replica adopts the bytes instead of
# recomputing the prefix.  All three primitives below iterate CacheSpec
# pool leaves (_POOL_LEAF_NDIM), so dense/paged/MLA *and* quantized layouts
# move pool rows + scale rows bitwise with zero call-site special-casing.

def _leaf_mismatch(kind: str, path: tuple, layout: str, name: str,
                   src, dst) -> ValueError:
    return ValueError(
        f"{kind}: pool leaf '{name}' of layer {'/'.join(path)} ({layout}) "
        f"does not match: src {tuple(src.shape)} "
        f"({jnp.dtype(src.dtype).name}) vs dst {tuple(dst.shape)} "
        f"({jnp.dtype(dst.dtype).name})")


def export_pages(cache: Params, pages) -> dict:
    """Gather the physical rows of ``pages`` from every paged layer.

    pages: i32[N] page ids (no -1 lanes: exports are explicit).  Returns
    ``{'path/to/layer': {leaf: rows}}`` with rows shaped [N, ...] (stacked
    layers keep their leading [G] axis: [G, N, ...]) — the host-transport
    half of cross-pool adoption; pair with ``adopt_pages`` on the far side.
    Device-to-device transfers should use ``copy_pages_across`` instead,
    which never materializes the rows.
    """
    pages = jnp.asarray(pages, jnp.int32)
    out: dict = {}
    for path, layout, layer in iter_layers(cache):
        if layout not in PAGED_LAYOUTS:
            continue
        leaves = {}
        for name in pool_leaves(layer, layout):
            pool = layer[name]
            stacked = pool.ndim == _POOL_LEAF_NDIM[layout][name] + 1
            p = pool.shape[1] if stacked else pool.shape[0]
            safe = jnp.clip(pages, 0, p - 1)
            leaves[name] = pool[:, safe] if stacked else pool[safe]
        out["/".join(path)] = leaves
    return out


def adopt_pages(cache: Params, rows: dict, pages) -> Params:
    """Scatter ``rows`` (from a peer's ``export_pages`` at the same page
    ids) into ``pages`` of this cache's pools.  -1 lanes drop.  Raises with
    the offending layer name and both shapes on any leaf mismatch."""
    pages = jnp.asarray(pages, jnp.int32)

    def ad(path, layout, layer):
        key = "/".join(path)
        got = rows.get(key)
        if got is None:
            raise ValueError(
                f"adopt_pages: no exported rows for layer {key} ({layout})")
        out = dict(layer)
        for name in pool_leaves(layer, layout):
            pool = layer[name]
            src = got.get(name)
            if src is None:
                raise ValueError(
                    f"adopt_pages: exported rows for layer {key} ({layout}) "
                    f"are missing pool leaf '{name}'")
            src = jnp.asarray(src)
            stacked = pool.ndim == _POOL_LEAF_NDIM[layout][name] + 1
            p = pool.shape[1] if stacked else pool.shape[0]
            n = pages.shape[0]
            want = ((pool.shape[0], n) + pool.shape[2:]) if stacked \
                else ((n,) + pool.shape[1:])
            if tuple(src.shape) != want or src.dtype != pool.dtype:
                raise _leaf_mismatch("adopt_pages", path, layout, name,
                                     src, pool)
            tgt = jnp.where(pages >= 0, jnp.clip(pages, 0, p - 1), p)
            if stacked:
                out[name] = pool.at[:, tgt].set(src, mode="drop")
            else:
                out[name] = pool.at[tgt].set(src, mode="drop")
        return out

    return map_layers(cache, ad, layouts=PAGED_LAYOUTS)


def copy_pages_across(src_cache: Params, dst_cache: Params, src,
                      dst=None, *, use_pallas: bool = True
                      ) -> tuple[Params, int]:
    """Device-to-device page adoption: copy pool pages ``src[i]`` of
    ``src_cache`` into pages ``dst[i]`` of ``dst_cache`` in every paged
    layer (``dst`` defaults to ``src`` — the replicated server's pools
    share one global page-id space).  -1 lanes drop.

    Runs the batched Pallas gather-scatter transfer kernel per pool leaf
    (``ops.page_transfer``), so the bytes move pool-row-at-a-time without
    a host round-trip and land bitwise for every layout — quantized pools
    carry their scale leaves automatically.  The two caches must agree on
    layer structure and per-leaf row shape/dtype; page *counts* may differ.
    Returns ``(updated dst_cache, bytes_moved)``.
    """
    from repro.kernels import ops as kops

    src = jnp.asarray(src, jnp.int32)
    dst = src if dst is None else jnp.asarray(dst, jnp.int32)
    if src.shape != dst.shape or src.ndim != 1:
        raise ValueError(
            f"copy_pages_across: src/dst page-id vectors must be matching "
            f"1-D arrays: got src {tuple(src.shape)} vs dst "
            f"{tuple(dst.shape)}")
    n_valid = int(np.asarray((src >= 0) & (dst >= 0)).sum())
    src_layers = {path: (layout, layer)
                  for path, layout, layer in iter_layers(src_cache)
                  if layout in PAGED_LAYOUTS}
    moved = 0

    def xfer(path, layout, layer):
        nonlocal moved
        peer = src_layers.get(path)
        if peer is None or peer[0] != layout:
            raise ValueError(
                f"copy_pages_across: source cache has no "
                f"{layout} layer at {'/'.join(path)}"
                + (f" (found {peer[0]})" if peer else ""))
        s_layer = peer[1]
        out = dict(layer)
        for name in pool_leaves(layer, layout):
            dpool = layer[name]
            spool = s_layer.get(name)
            if spool is None or spool.ndim != dpool.ndim \
                    or spool.shape[1:] != dpool.shape[1:] \
                    or spool.dtype != dpool.dtype:
                raise _leaf_mismatch("copy_pages_across", path, layout,
                                     name, spool if spool is not None
                                     else jnp.zeros(()), dpool)
            stacked = dpool.ndim == _POOL_LEAF_NDIM[layout][name] + 1
            if stacked:
                # Flatten the leading [G] axis into the page axis with
                # per-group id offsets: one kernel call moves all groups.
                g, p_s = spool.shape[0], spool.shape[1]
                p_d = dpool.shape[1]
                row = dpool.shape[2:]
                off = jnp.arange(g, dtype=jnp.int32)[:, None]
                sids = jnp.where(src[None, :] >= 0,
                                 src[None, :] + off * p_s, -1).reshape(-1)
                dids = jnp.where(dst[None, :] >= 0,
                                 dst[None, :] + off * p_d, -1).reshape(-1)
                newp = kops.page_transfer(
                    spool.reshape((g * p_s,) + row),
                    dpool.reshape((g * p_d,) + row),
                    sids, dids, use_pallas=use_pallas)
                out[name] = newp.reshape(dpool.shape)
                page_bytes = g * int(np.prod(row, dtype=np.int64)) \
                    * dpool.dtype.itemsize
            else:
                out[name] = kops.page_transfer(spool, dpool, src, dst,
                                               use_pallas=use_pallas)
                page_bytes = int(np.prod(dpool.shape[1:], dtype=np.int64)) \
                    * dpool.dtype.itemsize
            moved += page_bytes * n_valid
        return out

    out_cache = map_layers(dst_cache, xfer, layouts=PAGED_LAYOUTS)
    return out_cache, moved


# ---------------------------------------------------------------------------
# Span snapshot / restore (speculative-decoding rollback)
# ---------------------------------------------------------------------------

def snapshot_span(cache: Params, start: jax.Array, width: int) -> Params:
    """Copy every cache slot a mixed step writing positions
    [start[b], start[b]+width) could touch — the rollback snapshot taken
    before a speculative verify step (after page growth, so the block
    tables already map the window).

    Dense layouts gather along the sequence axis; paged layouts walk the
    block tables via the ``kernels.ref`` span oracles.  The returned tree
    mirrors the cache's nesting but keeps only attention slots: recurrent
    state carries are whole-row, not per-slot — snapshot those with
    ``lm.snapshot_state_rows``.  xattn layers (not mixed-step servable)
    and unrecognized leaves are pruned so the snapshot never aliases a
    buffer that a later donated verify call would invalidate.
    """
    from repro.kernels import ref as kref

    start = jnp.asarray(start, jnp.int32)
    batch = start.shape[0]
    tpos = start[:, None] + jnp.arange(width, dtype=jnp.int32)[None, :]
    bidx = jnp.broadcast_to(jnp.arange(batch, dtype=jnp.int32)[:, None],
                            (batch, width))

    def snap_layer(layout, layer):
        out = {}
        if layout in PAGED_LAYOUTS:
            bt = layer["block_tables"]
            bt2 = bt[0] if bt.ndim == 3 else bt
            slot_axis = _SPAN_SLOT_AXIS[layout]
            for name in pool_leaves(layer, layout):
                pool = layer[name]
                core = _POOL_LEAF_NDIM[layout][name]
                if pool.ndim == core + 1:                 # leading [G]
                    out[name] = jax.vmap(
                        lambda p: kref.paged_span_gather(
                            p, bt2, start, width,
                            slot_axis=slot_axis))(pool)
                else:
                    out[name] = kref.paged_span_gather(pool, bt2, start,
                                                       width,
                                                       slot_axis=slot_axis)
            return out
        # dense / dense_mla: sequence axis is -2
        for name, arr in layer.items():
            core = 4 if layout == "dense" else 3
            stacked = arr.ndim == core + 1
            seq = arr.shape[-2]
            spos = jnp.clip(tpos, 0, seq - 1)
            if layout == "dense":
                out[name] = (arr[:, bidx, :, spos, :] if stacked
                             else arr[bidx, :, spos, :])
            else:
                out[name] = (arr[:, bidx, spos] if stacked
                             else arr[bidx, spos])
        return out

    def rec(tree):
        if not isinstance(tree, dict):
            return None                                   # prune raw leaves
        layout = layout_of(tree)
        if layout is not None:
            return snap_layer(layout, tree) if layout != "xattn" else {}
        out = {k: rec(v) for k, v in tree.items()}
        return {k: v for k, v in out.items() if v is not None}

    return rec(cache)


def restore_span(cache: Params, snap: Params, start: jax.Array,
                 lo: jax.Array, hi: jax.Array) -> Params:
    """Scatter ``snap`` (from :func:`snapshot_span`, same ``start``) back
    for positions in [lo[b], hi[b)) — the rejected-tail rollback.

    Must run against the SAME block tables the snapshot saw (i.e. before
    the host frees the tail's grown pages).  Lanes outside the window are
    routed out of bounds and dropped, so accepted positions keep the
    verify step's writes bit-for-bit.  Rows with lo == hi are untouched.
    """
    from repro.kernels import ref as kref

    start = jnp.asarray(start, jnp.int32)
    lo = jnp.asarray(lo, jnp.int32)
    hi = jnp.asarray(hi, jnp.int32)
    batch = start.shape[0]

    def restore_layer(layout, layer, s):
        out = dict(layer)
        if layout in PAGED_LAYOUTS:
            bt = layer["block_tables"]
            bt2 = bt[0] if bt.ndim == 3 else bt
            slot_axis = _SPAN_SLOT_AXIS[layout]
            for name in pool_leaves(layer, layout):
                pool = layer[name]
                core = _POOL_LEAF_NDIM[layout][name]
                if pool.ndim == core + 1:
                    out[name] = jax.vmap(
                        lambda p, sn: kref.paged_span_restore(
                            p, sn, bt2, start, lo, hi,
                            slot_axis=slot_axis))(pool, s[name])
                else:
                    out[name] = kref.paged_span_restore(
                        pool, s[name], bt2, start, lo, hi,
                        slot_axis=slot_axis)
            return out
        for name, arr in layer.items():
            core = 4 if layout == "dense" else 3
            stacked = arr.ndim == core + 1
            seq = arr.shape[-2]
            # snapshot leaf layout: dense gathers have non-adjacent advanced
            # indices so [B, W, ...] always; dense_mla's are adjacent, which
            # keeps the leading [G] in place — [G, B, W, r] when stacked.
            w = s[name].shape[2 if layout != "dense" and stacked else 1]
            tpos = start[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
            keep = (tpos >= lo[:, None]) & (tpos < hi[:, None]) \
                & (tpos < seq)
            sidx = jnp.where(keep, jnp.clip(tpos, 0, seq - 1), seq)
            bidx = jnp.broadcast_to(
                jnp.arange(batch, dtype=jnp.int32)[:, None], (batch, w))
            if layout == "dense":
                out[name] = (arr.at[:, bidx, :, sidx, :]
                             .set(s[name], mode="drop") if stacked
                             else arr.at[bidx, :, sidx, :]
                             .set(s[name], mode="drop"))
            else:
                out[name] = (arr.at[:, bidx, sidx]
                             .set(s[name], mode="drop") if stacked
                             else arr.at[bidx, sidx]
                             .set(s[name], mode="drop"))
        return out

    def rec(tree, s):
        if not isinstance(tree, dict) or not isinstance(s, dict) or not s:
            return tree
        layout = layout_of(tree)
        if layout is not None:
            return (restore_layer(layout, tree, s)
                    if layout != "xattn" else tree)
        return {k: rec(v, s.get(k)) for k, v in tree.items()}

    return rec(cache, snap)


# ---------------------------------------------------------------------------
# Tiered page memory: host-buffer swap pool (copy_pages across tiers)
# ---------------------------------------------------------------------------

def make_swap_pool(cache: Params, n_slots: int
                   ) -> dict[tuple[str, ...], dict[str, np.ndarray]]:
    """Host-memory mirror of every paged layer's pool (and scale) leaves.

    ``{layer_path: {leaf_name: np[..., n_slots, ...]}}`` — each leaf keeps
    its device shape with the page axis replaced by ``n_slots`` swap slots.
    Quantized layouts swap their int8/fp8 bytes, so a swapped page costs the
    same host bytes as its resident form (and swap-in is bit-exact).
    """
    pool: dict[tuple[str, ...], dict[str, np.ndarray]] = {}
    for path, layout, layer in iter_layers(cache):
        if layout not in PAGED_LAYOUTS:
            continue
        leaves = {}
        for name in pool_leaves(layer, layout):
            arr = layer[name]
            stacked = arr.ndim == _POOL_LEAF_NDIM[layout][name] + 1
            shape = ((arr.shape[0], n_slots) + arr.shape[2:] if stacked
                     else (n_slots,) + arr.shape[1:])
            leaves[name] = np.zeros(shape, arr.dtype)
        pool[path] = leaves
    return pool


def swap_out_pages(cache: Params, swap_pool: dict, pages, slots) -> int:
    """Copy device pool pages -> host swap slots (``pages[i] -> slots[i]``).

    The cross-tier half of :func:`copy_pages`: same page-axis gather, but the
    destination is the host swap pool.  Mutates ``swap_pool`` in place and
    returns the bytes moved (one device→host transfer per leaf).
    """
    pages = np.asarray(pages, np.int32)
    slots = np.asarray(slots, np.int32)
    moved = 0
    for path, layout, layer in iter_layers(cache):
        if layout not in PAGED_LAYOUTS:
            continue
        host = swap_pool[path]
        for name in pool_leaves(layer, layout):
            arr = layer[name]
            stacked = arr.ndim == _POOL_LEAF_NDIM[layout][name] + 1
            rows = np.asarray(arr[:, pages] if stacked else arr[pages])
            if stacked:
                host[name][:, slots] = rows
            else:
                host[name][slots] = rows
            moved += rows.nbytes
    return moved


def swap_in_pages(cache: Params, swap_pool: dict, slots, pages) -> Params:
    """Copy host swap slots -> device pool pages (``slots[i] -> pages[i]``).

    Returns the updated cache tree; the swapped bytes land bit-exactly
    (values AND scales for quantized layouts), so a swap-in victim resumes
    decoding from the identical cache it was preempted with.
    """
    slots = np.asarray(slots, np.int32)
    idx = jnp.asarray(np.asarray(pages, np.int32))

    def fn(path, layout, layer):
        host = swap_pool[path]
        out = dict(layer)
        for name in pool_leaves(layer, layout):
            arr = layer[name]
            stacked = arr.ndim == _POOL_LEAF_NDIM[layout][name] + 1
            if stacked:
                rows = jnp.asarray(host[name][:, slots])
                out[name] = arr.at[:, idx].set(rows)
            else:
                rows = jnp.asarray(host[name][slots])
                out[name] = arr.at[idx].set(rows)
        return out

    return map_layers(cache, fn, layouts=PAGED_LAYOUTS)

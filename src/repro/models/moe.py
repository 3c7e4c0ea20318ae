"""Mixture-of-Experts FFN: DeepSeekMoE-style shared + fine-grained routed
experts with top-k softmax routing, drop-free, over one chip's share.

The router scores all ``moe.num_experts`` experts (softmax in f32, top-k
over all of them, renormalised only with ``norm_topk_prob``).  This chip
holds routed experts ``[first, first + held)`` (``cfg.held_experts``):
a (token, expert) pair whose expert lies elsewhere contributes nothing
here — on one chip the layer runs without the expert-parallel exchange —
and the others are all computed, with no capacity and no dropped pair.

Live pairs are laid out grouped by held expert, each group starting on a
tile of ``tm`` rows, and run through one grouped SwiGLU
(``kernels.ops.moe_grouped_swiglu``) whose grid covers only the tiles
that hold pairs.  ``valid`` marks the positions that are real tokens (a
mixed step's span, a ragged prefill's length): padding is never routed.
The shared experts run on every position, once.

Parameter leaves are named ``w``: ``router.w [d, E]``,
``experts.{gate,up}.w [held, d, d_expert]``, ``experts.down.w
[held, d_expert, d]``, ``shared`` likewise over the shared experts.

Returns ``(y, aux, counts)``: the Switch load-balancing loss over the
routed pairs (for the trainer), and f32[2] = (pairs computed here, the
largest number of pairs one held expert took).
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.models import common
from repro.models.config import ModelConfig

Params = Any


def _bank(key, n: int, d: int, h: int) -> Params:
    kg, ku, kd = jax.random.split(key, 3)

    def w(k, shape, fan_in):
        return {"w": (jax.random.normal(k, shape, jnp.float32)
                      * fan_in ** -0.5).astype(common.PARAM_DTYPE)}

    return {"gate": w(kg, (n, d, h), d), "up": w(ku, (n, d, h), d),
            "down": w(kd, (n, h, d), h)}


def init(key, cfg: ModelConfig) -> Params:
    m = cfg.moe
    d, h = cfg.d_model, m.d_expert
    k_router, k_e, k_s = jax.random.split(key, 3)
    p = {"router": common.dense_init(k_router, d, m.num_experts),
         "experts": _bank(k_e, cfg.held_experts[1], d, h)}
    if m.num_shared:
        p["shared"] = _bank(k_s, m.num_shared, d, h)
    return p


def route(p: Params, cfg: ModelConfig, xf: jax.Array):
    """xf [N, d] -> (probs f32[N, E], gates f32[N, K], experts i32[N, K])."""
    m = cfg.moe
    logits = jnp.matmul(xf.astype(jnp.float32),
                        p["router"]["w"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk_prob:
        gates = gates / jnp.clip(jnp.sum(gates, -1, keepdims=True), 1e-9)
    return probs, gates, idx


def tile_rows(pairs: int, num_experts: int) -> int:
    """Rows per tile: the expected pairs of one expert (``pairs /
    num_experts``) rounded up to a power of two in [16, 128]."""
    want = -(-pairs // num_experts)
    return min(128, max(16, 1 << max(want - 1, 0).bit_length()))


def group_pairs(local: jax.Array, live: jax.Array, held: int, tm: int):
    """Place live pairs grouped by local expert in tiles of ``tm`` rows.

    local: i32[M] expert of each pair in [0, held); live: bool[M].
    Returns (slot i32[M] row of each pair — out of range when dead,
    tile_group i32[T], num_tiles i32[1], sizes i32[held]) with
    T = ceil(M / tm) + held, enough for any routing.
    """
    m = local.shape[0]
    e = jnp.where(live, local, held)                               # [M]
    onehot = (e[:, None] == jnp.arange(held)[None, :]).astype(jnp.int32)
    sizes = jnp.sum(onehot, axis=0)                                # [held]
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=1)
    tiles = (sizes + tm - 1) // tm
    first_tile = jnp.cumsum(tiles) - tiles
    n_tiles = -(-m // tm) + held
    slot = jnp.where(live, first_tile[jnp.clip(e, 0, held - 1)] * tm + rank,
                     n_tiles * tm)
    tile_group = jnp.repeat(jnp.arange(held, dtype=jnp.int32), tiles,
                            total_repeat_length=n_tiles)
    return (slot.astype(jnp.int32), tile_group,
            jnp.sum(tiles, keepdims=True).astype(jnp.int32), sizes)


def _swiglu_bank(bank: Params, x: jax.Array) -> jax.Array:
    """x [N, d] through every expert of ``bank``, summed: f32 [N, d]."""
    g = jnp.einsum("nd,edh->enh", x, bank["gate"]["w"].astype(x.dtype))
    u = jnp.einsum("nd,edh->enh", x, bank["up"]["w"].astype(x.dtype))
    return jnp.einsum("enh,ehd->nd", jax.nn.silu(g) * u,
                      bank["down"]["w"].astype(x.dtype),
                      preferred_element_type=jnp.float32)


def forward(p: Params, cfg: ModelConfig, x: jax.Array,
            valid: Optional[jax.Array] = None,
            layer: Optional[jax.Array] = None):
    """x: [B, T, d]; valid: bool[B, T] or None (all) -> (y, aux, counts).
    With ``layer``, the held-expert bank ``p["experts"]`` is the [L, ...]
    stack of every layer's and this layer is ``layer`` of it."""
    m = cfg.moe
    first, held = cfg.held_experts
    b, t, d = x.shape
    n = b * t
    xf = x.reshape(n, d)
    probs, gates, idx = route(p, cfg, xf)                      # [N, K]

    # Switch aux loss: E * sum_e f_e * P_e.
    onehot = jax.nn.one_hot(idx, m.num_experts, dtype=jnp.float32)
    aux = m.num_experts * jnp.sum(jnp.mean(jnp.sum(onehot, axis=1), axis=0)
                                  * jnp.mean(probs, axis=0))

    ok = (jnp.ones((n,), bool) if valid is None else valid.reshape(n))
    local = (idx - first).reshape(-1)                          # [N*K]
    live = (jnp.repeat(ok, m.top_k) & (local >= 0) & (local < held))
    tm = tile_rows(n * m.top_k, m.num_experts)
    slot, tile_group, num_tiles, sizes = group_pairs(
        jnp.clip(local, 0, held - 1), live, held, tm)
    rows = tile_group.shape[0] * tm
    # Row -> pair (M for padding rows), then the pair's token.
    pair_of_row = jnp.full((rows,), n * m.top_k, jnp.int32).at[slot].set(
        jnp.arange(n * m.top_k, dtype=jnp.int32), mode="drop")
    tok_of_row = jnp.where(pair_of_row < n * m.top_k,
                           pair_of_row // m.top_k, 0)
    ex = p["experts"]
    ys = kops.moe_grouped_swiglu(
        xf[tok_of_row], ex["gate"]["w"].astype(x.dtype),
        ex["up"]["w"].astype(x.dtype), ex["down"]["w"].astype(x.dtype),
        tile_group, num_tiles, tm=tm, layer=layer)             # [rows, d]
    # Rows of dead pairs are never read (unwritten rows may hold anything).
    y_pair = jnp.where(live[:, None],
                       ys[jnp.clip(slot, 0, rows - 1)].astype(jnp.float32)
                       * gates.reshape(-1)[:, None], 0.0)
    y = jnp.sum(y_pair.reshape(n, m.top_k, d), axis=1)

    if m.num_shared:
        y = y + _swiglu_bank(p["shared"], xf)
    counts = jnp.stack([jnp.sum(live), jnp.max(sizes)]).astype(jnp.float32)
    return y.astype(x.dtype).reshape(b, t, d), aux, counts

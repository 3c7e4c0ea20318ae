"""Shared model components: norms, RoPE, dense layers, init helpers.

Parameters are plain nested dicts of jnp arrays (bf16 storage by default;
compute promotes to fp32 where numerically required).  Everything here is a
pure function usable under jit / scan / shard_map.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

PARAM_DTYPE = jnp.bfloat16
Params = Any


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, use_bias: bool = False,
               scale: float | None = None) -> Params:
    scale = scale if scale is not None else d_in ** -0.5
    p = {"w": (jax.random.normal(key, (d_in, d_out), jnp.float32)
               * scale).astype(PARAM_DTYPE)}
    if use_bias:
        p["b"] = jnp.zeros((d_out,), PARAM_DTYPE)
    return p


def dense(p: Params, x: jax.Array) -> jax.Array:
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


def norm_init(d: int, norm_type: str) -> Params:
    if norm_type == "nonparametric":
        return {}
    if norm_type == "layernorm":
        return {"scale": jnp.ones((d,), PARAM_DTYPE),
                "bias": jnp.zeros((d,), PARAM_DTYPE)}
    return {"scale": jnp.ones((d,), PARAM_DTYPE)}    # rmsnorm


def apply_norm(p: Params, x: jax.Array, norm_type: str,
               eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    if norm_type in ("layernorm", "nonparametric"):
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        if norm_type == "layernorm":
            y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
        return y.astype(x.dtype)
    # rmsnorm
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    """DeepSeek-V2's ``yarn_get_mscale``."""
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def _yarn_correction_range(y, dim: int, theta: float) -> tuple[int, int]:
    """DeepSeek-V2's ``yarn_find_correction_range``: the rotary dims whose
    wavelength spans ``beta_fast`` .. ``beta_slow`` turns over the original
    context, clamped to [0, dim - 1]."""
    def dim_of(turns):
        return (dim * math.log(y.original_max_position
                               / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    low = math.floor(dim_of(y.beta_fast))
    high = math.ceil(dim_of(y.beta_slow))
    return max(low, 0), min(high, dim - 1)


def rope_freqs(head_dim: int, theta: float, scaling=None) -> jax.Array:
    """Inverse frequencies [D/2]; with a ``YarnConfig`` the YaRN blend:
    original frequencies below the correction range, frequencies over
    ``factor`` above it, a linear ramp between (``yarn_linear_ramp_mask``)."""
    base = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))
    if scaling is None:
        return base
    low, high = _yarn_correction_range(scaling, head_dim, theta)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                     # 1: original, 0: interpolated
    return base / scaling.factor * (1.0 - keep) + base * keep


def rope_mscale(scaling) -> float:
    """Factor on cos/sin: mscale(factor, mscale) / mscale(factor,
    mscale_all_dim) (1 when the two are equal, as in DeepSeek-V2)."""
    if scaling is None:
        return 1.0
    return (yarn_mscale(scaling.factor, scaling.mscale)
            / yarn_mscale(scaling.factor, scaling.mscale_all_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               scaling=None) -> jax.Array:
    """x: [B, H, T, D]; positions: [B, T] (or [T] broadcast); ``scaling``
    an optional ``YarnConfig``."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta, scaling)                        # [D/2]
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[:, None, :, None].astype(jnp.float32) * freqs  # [B,1,T,D/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    m = rope_mscale(scaling)
    if m != 1.0:
        cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Masks
# ---------------------------------------------------------------------------

def make_mask(tq: int, tk: int, *, causal: bool = True,
              window: int | None = None, prefix_len: int = 0) -> jax.Array:
    """bool[Tq, Tk] — True = attend.  Query rows end-aligned with keys."""
    qi = jnp.arange(tq)[:, None] + (tk - tq)
    ki = jnp.arange(tk)[None, :]
    mask = jnp.ones((tq, tk), bool)
    if causal:
        mask &= ki <= qi
    if window is not None:
        mask &= ki >= qi - window + 1
    if prefix_len > 0:       # bidirectional prefix (PaliGemma-style)
        mask |= (ki < prefix_len) & (qi < prefix_len)
        mask |= (qi >= prefix_len) & (ki < prefix_len)
    return mask


def softcap(logits: jax.Array, cap: float | None) -> jax.Array:
    if cap is None:
        return logits
    return cap * jnp.tanh(logits / cap)

"""Plain reference of DeepSeek-V2 (-Lite): latent attention (MLA) beside
shared and routed experts, on one chip's share of the routed experts.

Straightforward ``jax.numpy`` in float32 with every contraction at
``Precision.HIGHEST``: no kernels, no cache, no batching, no weight
absorption.  Each request is one forward pass over its prompt and the
tokens the system served.  It follows the published architecture read
from the configuration file's own keys (``hidden_size``,
``kv_lora_rank``, ``n_routed_experts``, ... as in the model's
``config.json``; arXiv:2405.04434 and the public modeling code):

  x = embed[tokens]
  per layer:  h = rms(x)
              q = Wq·h -> [q_nope; q_pe],   c = rms(Wdkv·h),   k_pe = Wkr·h
              K = [Wuk·c ; rope(k_pe)],     V = Wuv·c           (per head)
              x += Wo · softmax(scale · [q_nope; rope(q_pe)]·K^T, causal) V
              h = rms(x)
              x += SwiGLU(h)          the leading dense layer(s)
              x += sum_e g_e(h) SwiGLU_e(h) + sum_s SwiGLU_s(h)   the others
  logits = rms(x) · W_head

RoPE is YaRN (``rope_scaling``: ``yarn_find_correction_range``,
``yarn_linear_ramp_mask``, ``yarn_get_mscale``), and the attention scale
is (nope + rope)^-1/2 times mscale(factor, mscale_all_dim)^2.  The router
scores the published count of experts (``published.n_routed_experts``,
the router's width): softmax, top ``num_experts_per_tok``, renormalised
only if ``norm_topk_prob``, times ``routed_scaling_factor``.  This chip
holds experts 0 .. ``n_routed_experts`` - 1, and only those are computed,
as in the program; what the other chips' experts would add is left out.

Departure: the rotary dims are rotated in halves (x1, x2), where the
published code first de-interleaves them; with random weights that is a
fixed permutation of the rope columns of Wq and Wkr.

It imports nothing of the system under test.  It reads the weights the
benchmark made, laid out as the served parameter tree: ``embed.w``,
``head.w``, ``final_norm``, ``lead["0"]`` (the dense layer) and
``groups["0"]`` (the expert layers, stacked) with ``norm1``, ``attn.{w_q,
w_dkv, w_kr, w_uk, w_uv, w_o}.w``, ``attn.kv_norm``, ``norm2`` and
``ffn`` (``{gate, up, down}.w``, or ``router.w``, ``experts`` and
``shared``).  Weights are upcast to float32 one layer at a time.

``control=True`` is the same computation with every matmul operand
rounded to float8 e4m3 (one scale per row or column of the contraction),
the precision step below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
T_BLOCK = 1024          # context lengths are padded to a multiple of this
Q_BLOCK = 512           # queries per attention block
MAX_POS = 256           # positions scored per request (padded)


def arch(cfg: dict) -> dict:
    y = cfg.get("rope_scaling") or {}
    return {
        "d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
        "r": cfg["kv_lora_rank"], "nope": cfg["qk_nope_head_dim"],
        "rope": cfg["qk_rope_head_dim"], "vd": cfg["v_head_dim"],
        "v": cfg["vocab_size"], "eps": cfg["rms_norm_eps"],
        "theta": float(cfg["rope_theta"]),
        "top_k": cfg["num_experts_per_tok"],
        "norm_topk": bool(cfg["norm_topk_prob"]),
        "routed_scale": float(cfg["routed_scaling_factor"]),
        "yarn": tuple(sorted(y.items())) if y.get("type") == "yarn" else None,
        "tied": bool(cfg["tie_word_embeddings"]),
    }


def _q8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(x, w, control):
    """x [..., K] @ w [K, N], float32 at HIGHEST (fp8 operands: control)."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if control:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rms(p, x, a):
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                          + a["eps"])
    return y * p["scale"].astype(jnp.float32)


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _rope_tables(a, t):
    """cos, sin [T, rope/2] with the YaRN frequencies and cos/sin factor."""
    dim, base = a["rope"], a["theta"]
    inv = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if a["yarn"] is not None:
        y = dict(a["yarn"])
        factor = float(y["factor"])
        orig = float(y["original_max_position_embeddings"])

        def corr(turns):
            return (dim * math.log(orig / (turns * 2 * math.pi))
                    / (2 * math.log(base)))
        low = max(math.floor(corr(y["beta_fast"])), 0)
        high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
        keep = 1.0 - ramp
        inv = inv / factor * (1 - keep) + inv * keep
        m = (_mscale(factor, y["mscale"])
             / _mscale(factor, y["mscale_all_dim"]))
    else:
        m = 1.0
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang) * m, jnp.float32),
            jnp.asarray(np.sin(ang) * m, jnp.float32))


def _scale(a):
    s = (a["nope"] + a["rope"]) ** -0.5
    if a["yarn"] is not None:
        y = dict(a["yarn"])
        if y.get("mscale_all_dim"):
            s *= _mscale(float(y["factor"]), y["mscale_all_dim"]) ** 2
    return s


def _rope(x, cos, sin):
    """x [T, ..., rope]: rotate the halves by the per-position tables."""
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (cos.shape[-1],)
    c, s = cos.reshape(shape), sin.reshape(shape)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention(p, h, a, cos, sin, control):
    """Non-absorbed MLA over one sequence: h [T, d] -> [T, d]."""
    t = h.shape[0]
    nh, nope, rope = a["h"], a["nope"], a["rope"]
    q = _mm(h, p["w_q"]["w"], control).reshape(t, nh, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], cos, sin)], -1)
    c = _rms(p["kv_norm"], _mm(h, p["w_dkv"]["w"], control), a)   # [T, r]
    k_pe = _rope(_mm(h, p["w_kr"]["w"], control), cos, sin)     # [T, rope]
    k_nope = _mm(c, p["w_uk"]["w"], control).reshape(t, nh, nope)
    v = _mm(c, p["w_uv"]["w"], control).reshape(t, nh, a["vd"])
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, None], (t, nh, rope))], -1)
    if control:
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, 0)
    scale = _scale(a)
    keys = jnp.arange(t)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.einsum("qhd,thd->hqt", qb, k, precision=HI) * scale
        s = jnp.where((keys[None, :] <= rows[:, None])[None], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        if control:
            pr = _q8(pr, -1)
        return jnp.einsum("hqt,thd->qhd", pr, v, precision=HI)

    out = jax.lax.map(block, jnp.arange(t // Q_BLOCK))
    return _mm(out.reshape(t, nh * a["vd"]), p["w_o"]["w"], control)


def _swiglu(p, h, control):
    u = jax.nn.silu(_mm(h, p["gate"]["w"], control)) \
        * _mm(h, p["up"]["w"], control)
    return _mm(u, p["down"]["w"], control)


def _experts(p, h, a, control):
    """Shared experts on every token, plus this chip's routed experts
    weighted by the router over all of them."""
    probs = jax.nn.softmax(_mm(h, p["router"]["w"], control), axis=-1)
    gates, idx = jax.lax.top_k(probs, a["top_k"])
    if a["norm_topk"]:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    gates = gates * a["routed_scale"]
    held = p["experts"]["gate"]["w"].shape[0]
    weight = jnp.sum(jax.nn.one_hot(idx, held, dtype=jnp.float32)
                     * gates[..., None], axis=1)            # [T, held]

    def one(carry, e):
        w = jax.tree.map(lambda z: z[e], p["experts"])
        return carry + weight[:, e, None] * _swiglu(w, h, control), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(held))
    shared = p["shared"]["gate"]["w"].shape[0]
    for s in range(shared):
        y = y + _swiglu(jax.tree.map(lambda z: z[s], p["shared"]), h,
                        control)
    return y


def _layer(x, p, a, cos, sin, control):
    x = x + _attention(p["attn"], _rms(p["norm1"], x, a), a, cos, sin,
                       control)
    h = _rms(p["norm2"], x, a)
    if "router" in p["ffn"]:
        return x + _experts(p["ffn"], h, a, control)
    return x + _swiglu(p["ffn"], h, control)


def _hidden(params, tokens, a, control):
    """Final-normed hidden states [T, d] of one sequence."""
    x = params["embed"]["w"][tokens].astype(jnp.float32)
    cos, sin = _rope_tables(a, tokens.shape[0])
    for i in range(len(params.get("lead", {}))):
        x = _layer(x, params["lead"][str(i)], a, cos, sin, control)

    def body(x, p):
        return _layer(x, p, a, cos, sin, control), None

    x, _ = jax.lax.scan(body, x, params["groups"]["0"])
    return _rms(params["final_norm"], x, a)


def _logits(params, tokens, positions, a, control):
    h = _hidden(params, tokens, a, control)[positions]          # [n, d]
    w = (params["embed"]["w"].T if a["tied"] else params["head"]["w"])
    return _mm(h, w, control)                                   # [n, V]


@functools.partial(jax.jit, static_argnames=("a",))
def _score(params, tokens, positions, ids, a):
    lg = _logits(params, tokens, positions, dict(a), False)
    return jnp.max(lg, -1), jnp.take_along_axis(lg, ids.T, -1).T


@functools.partial(jax.jit, static_argnames=("a",))
def _pick(params, tokens, positions, a):
    return jnp.argmax(_logits(params, tokens, positions, dict(a), True), -1)


@functools.partial(jax.jit, static_argnames=("a", "control"))
def _all_logits(params, tokens, a, control):
    return _logits(params, tokens, jnp.arange(tokens.shape[0]), dict(a),
                   control)


class Reference:
    def __init__(self, cfg: dict):
        self.a = tuple(sorted(arch(cfg).items()))

    @staticmethod
    def _pad(prompt, served):
        """Tokens the served tokens were predicted from, padded to a block,
        and the positions that predicted them, padded to ``MAX_POS``."""
        toks = list(prompt) + list(served[:-1])
        t = -(-len(toks) // T_BLOCK) * T_BLOCK
        n = len(served)
        if n > MAX_POS:
            raise ValueError(f"{n} served tokens > {MAX_POS}")
        pos = np.full(MAX_POS, len(prompt) - 1, np.int32)
        pos[:n] = len(prompt) - 1 + np.arange(n)
        return (np.asarray(toks + [0] * (t - len(toks)), np.int32), pos, n)

    def logits(self, params, tokens, *, control=False) -> np.ndarray:
        """Logits [T, V] at every position of one sequence (padded to a
        whole block inside; the padding follows the sequence, so causal
        attention leaves the real positions as they are)."""
        toks = list(tokens)
        t = -(-len(toks) // T_BLOCK) * T_BLOCK
        padded = np.asarray(toks + [0] * (t - len(toks)), np.int32)
        out = _all_logits(params, padded, self.a, control)
        return np.asarray(out)[:len(toks)]

    def gaps(self, params, prompt, served, *, control=False) -> np.ndarray:
        """Per served position: how far below the reference's best logit
        lies the token served there (``control``: the token the fp8 control
        puts first)."""
        toks, pos, n = self._pad(prompt, served)
        if control:
            ids = np.asarray(_pick(params, toks, pos, self.a))
        else:
            ids = np.zeros(MAX_POS, np.int32)
            ids[:n] = served
        best, at = _score(params, toks, pos, ids[None].astype(np.int32),
                          self.a)
        return (np.asarray(best) - np.asarray(at)[0])[:n]


def make(cfg: dict) -> Reference:
    return Reference(cfg)

"""Plain reference of a dense pre-norm decoder (OLMo, StarCoder2).

Straightforward ``jax.numpy`` in float32 with every contraction at
``Precision.HIGHEST``: no kernels, no cache, no batching.  Each request is
one forward pass over its prompt and the tokens the system served, run
layer by layer in a ``lax.scan`` and with attention in blocks of queries so
that a 4096-token context fits beside the weights.  It follows the
published architecture read from the configuration file's own keys
(``hidden_size``, ``num_attention_heads``, ... as in the model's
``config.json``):

  x = embed[tokens]
  per layer:  x += Wo · attn(rope(Wq·n1(x)), rope(Wk·n1(x)), Wv·n1(x))
              x += ffn(n2(x))       SwiGLU (silu) or GELU-tanh MLP
  logits = n_f(x) · E^T (tied) or · W_head

with causal grouped-query attention (and the sliding window, where the
context could pass it), rotary embeddings on the half-split convention of
the published implementations, LayerNorm with or without affine weights,
and biases where the configuration says so.

It imports nothing of the system under test.  It reads the weights the
benchmark made, laid out as the served parameter tree: ``embed.w [V, d]``,
``head.w [d, V]`` when untied, ``final_norm``, and ``groups["0"]`` with
``norm1``, ``attn.{wq,wk,wv,wo}.{w,b}``, ``norm2`` and
``ffn.{gate,up,down}.{w,b}`` stacked over layers.

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
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {
        "d": d, "h": h, "hkv": cfg["num_key_value_heads"],
        "hd": cfg.get("head_dim") or d // h,
        "ff": cfg["intermediate_size"], "v": cfg["vocab_size"],
        "layers": cfg["num_hidden_layers"], "theta": cfg["rope_theta"],
        "eps": cfg.get("layer_norm_eps", cfg.get("norm_epsilon", 1e-5)),
        "affine": cfg["norm_type"] == "layernorm",
        "glu": cfg["hidden_act"] == "silu",
        "bias": bool(cfg.get("use_bias", cfg.get("attention_bias", False))),
        "tied": bool(cfg["tie_word_embeddings"]),
        "window": cfg.get("sliding_window"),
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


def _dense(p, x, a, control):
    y = _mm(x, p["w"], control)
    if a["bias"]:
        y = y + p["b"].astype(jnp.float32)
    return y


def _norm(p, x, a):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + a["eps"])
    if a["affine"]:
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y


def _rope(x, pos, theta):
    """x [T, H, D]: rotate the halves (x1, x2) by angle pos * theta^(-2i/D)."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, a, control):
    """Causal GQA.  q [T, H, D]; k, v [T, Hkv, D] -> [T, H*D]."""
    t = q.shape[0]
    g = a["h"] // a["hkv"]
    scale = 1.0 / math.sqrt(a["hd"])
    qg = q.reshape(t, a["hkv"], g, a["hd"])
    if control:
        qg, k, v = _q8(qg, -1), _q8(k, -1), _q8(v, 0)
    keys = jnp.arange(t)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qg, i * Q_BLOCK, Q_BLOCK, 0)
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.einsum("qkgd,tkd->kgqt", qb, k, precision=HI) * scale
        ok = keys[None, :] <= rows[:, None]
        if a["window"] is not None:
            ok &= keys[None, :] > rows[:, None] - a["window"]
        s = jnp.where(ok[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if control:
            p = _q8(p, -1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HI)

    out = jax.lax.map(block, jnp.arange(t // Q_BLOCK))
    return out.reshape(t, a["h"] * a["hd"])


def _hidden(params, tokens, a, control):
    """Final-normed hidden states [T, d] of one sequence."""
    x = params["embed"]["w"][tokens].astype(jnp.float32)
    pos = jnp.arange(tokens.shape[0])

    def layer(x, p):
        h = _norm(p.get("norm1", {}), x, a)
        at = p["attn"]
        q = _dense(at["wq"], h, a, control).reshape(-1, a["h"], a["hd"])
        k = _dense(at["wk"], h, a, control).reshape(-1, a["hkv"], a["hd"])
        v = _dense(at["wv"], h, a, control).reshape(-1, a["hkv"], a["hd"])
        q, k = _rope(q, pos, a["theta"]), _rope(k, pos, a["theta"])
        x = x + _dense(at["wo"], _attention(q, k, v, a, control), a, control)
        h = _norm(p.get("norm2", {}), x, a)
        f = p["ffn"]
        if a["glu"]:
            u = jax.nn.silu(_dense(f["gate"], h, a, control)) \
                * _dense(f["up"], h, a, control)
        else:
            u = jax.nn.gelu(_dense(f["up"], h, a, control), approximate=True)
        return x + _dense(f["down"], u, a, control), None

    x, _ = jax.lax.scan(layer, x, params["groups"]["0"])
    return _norm(params.get("final_norm", {}), x, a)


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

"""DeepSeek-V2(-Lite): latent attention with YaRN beside shared and routed
experts, one chip's share of the experts, served through the engine.

At reduced widths with seeded random weights, against the chip
benchmark's plain float32 reference (``benchmarks/chip/references/
deepseek_v2.py``), loaded by path as the benchmark's harness loads it.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.kernels import moe_grouped_matmul as gmm
from repro.kernels import ref as kref
from repro.models import common, lm, mla, moe
from repro.serving.scheduler import ContinuousBatchingEngine, Request

REF_PATH = (Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
            / "references" / "deepseek_v2.py")
HELD = 4            # routed experts held here, of the reduced config's 8


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location("chipbench_deepseek_v2",
                                                  REF_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cfg(held=HELD):
    """Reduced DeepSeek-V2-Lite: the dense layer 0 and two expert layers,
    YaRN on 8 rotary dims, top-2 of 8 experts unnormalised, 1 shared."""
    full = configs.get("deepseek-v2-lite-16b")
    cfg = configs.reduced(full, layers=3)
    return cfg.replace(experts_first=0, experts_held=held)


def _hf(cfg) -> dict:
    """The configuration file's keys, as the benchmark reference reads
    them, for a reduced program config."""
    m, x, y = cfg.mla, cfg.moe, cfg.rope_scaling
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.num_heads,
        "kv_lora_rank": m.kv_lora_rank, "qk_nope_head_dim": m.nope_head_dim,
        "qk_rope_head_dim": m.rope_head_dim, "v_head_dim": m.v_head_dim,
        "vocab_size": cfg.vocab_size, "rms_norm_eps": cfg.norm_eps,
        "rope_theta": cfg.rope_theta, "num_experts_per_tok": x.top_k,
        "norm_topk_prob": x.norm_topk_prob, "routed_scaling_factor": 1,
        "tie_word_embeddings": cfg.tie_embeddings,
        "n_routed_experts": cfg.held_experts[1],
        "rope_scaling": {"type": "yarn", "factor": y.factor,
                         "original_max_position_embeddings":
                             y.original_max_position,
                         "beta_fast": y.beta_fast, "beta_slow": y.beta_slow,
                         "mscale": y.mscale,
                         "mscale_all_dim": y.mscale_all_dim},
    }


def _params(cfg, seed=0, dtype=jnp.float32):
    """Random weights; norms and the router drawn away from their init so a
    path that dropped them would show."""
    p = lm.init(jax.random.PRNGKey(seed), cfg)
    leaves, tree = jax.tree_util.tree_flatten_with_path(p)
    key = jax.random.PRNGKey(seed + 100)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        name = str(getattr(path[-1], "key", path[-1]))
        if name == "scale":
            leaf = 1.0 + 0.1 * jax.random.normal(jax.random.fold_in(key, i),
                                                 leaf.shape)
        out.append(leaf.astype(dtype))
    return jax.tree_util.tree_unflatten(tree, out)


# -- (a) the engine against the reference's full forward pass -------------

def test_engine_logits_match_reference(reference):
    """Chunked admission (chunk 8) of two prompts that share a 16-token
    prefix (a whole page), then decoding, through paged latent pools.
    Every logit row the mixed step produced (mid-prompt chunks included)
    is compared with the reference's row at the same position.

    float32 weights and float32 latent pools (installed in place of the
    engine's bfloat16 ones), so the program and the reference differ only
    in the order of float32 sums (absorbed against expanded attention,
    grouped against dense experts): tolerance 1e-4 of the largest
    reference logit (the widest gap reads ~2e-6).  bfloat16 pools alone
    widen it to ~1e-2, and bfloat16 weights further."""
    cfg = _cfg()
    params = _params(cfg)
    eng = ContinuousBatchingEngine(cfg, params, batch=3, max_len=64,
                                   paged=True, page_size=16, num_pages=24,
                                   prefix_sharing=True, chunk_size=8)
    eng.cache = lm.set_block_tables(
        lm.init_cache(cfg, 3, 64, jnp.float32, paged=True, page_size=16,
                      num_pages=25), jnp.asarray(eng.host_bt))
    seen: dict[tuple[int, int], np.ndarray] = {}
    step = jax.jit(lambda p, c, t, s, n: lm.mixed_step(p, cfg, t, c, s, n))

    def recording(p, cache, toks, start, span, _rng):
        logits, cache, counts = step(p, cache, toks, start, span)
        logits = np.asarray(logits)
        start, span = np.asarray(start), np.asarray(span)
        for row, req in enumerate(eng.rows):
            if req is not None and span[row] > 0:
                seen[(req.rid, int(start[row] + span[row]))] = logits[row]
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        return jnp.concatenate([nxt, counts.astype(jnp.int32)]), cache

    eng._mixed = recording
    rng = np.random.default_rng(1)
    shared = rng.integers(0, cfg.vocab_size, 16).tolist()
    prompts = [shared + rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (5, 11)] + [rng.integers(0, cfg.vocab_size,
                                                 9).tolist()]
    reqs = [Request(rid=i, prompt=pr, max_new_tokens=6)
            for i, pr in enumerate(prompts)]
    eng.run(reqs)
    assert all(r.status == "completed" for r in reqs)
    assert eng.stats["shared_pages"] >= 1 and eng.stats["prefill_chunks"] > 3
    assert 0 < eng.stats["moe_pairs_max"] <= eng.stats["moe_pairs"]

    ref = reference.make(_hf(cfg))
    worst = 0.0
    for r in reqs:
        seq = list(r.prompt) + list(r.tokens)
        want = ref.logits(params, seq)                      # [T, V]
        got = {pos: lg for (rid, pos), lg in seen.items() if rid == r.rid}
        assert len(got) >= len(r.tokens)
        for pos, lg in got.items():
            w = want[pos - 1]
            worst = max(worst, float(np.abs(lg - w).max()
                                     / np.abs(w).max()))
    assert worst < 1e-4, worst


# -- (b) the shares of the experts add up to the uncut layer --------------

def test_expert_shares_sum_to_the_uncut_layer(reference):
    """Four chips hold experts [0,2), [2,4), [4,6), [6,8): the routed parts
    they compute add up to the uncut layer's routed part, with the shared
    expert (which every chip computes) counted once; the uncut layer is
    also the reference's.  float32 weights: the only difference is the
    order of additions (1e-5 relative)."""
    cfg_all = _cfg(held=0)
    p = _params(cfg_all, seed=3)["groups"]["0"]["ffn"]
    p = jax.tree.map(lambda z: z[0], p)                    # layer 1
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 7, cfg_all.d_model))
    full, _, counts = moe.forward(p, cfg_all, x)
    shared = moe._swiglu_bank(p["shared"], x.reshape(-1, x.shape[-1]))
    parts = []
    total_pairs = 0
    for first in range(0, 8, 2):
        cfg = cfg_all.replace(experts_first=first, experts_held=2)
        share = dict(p, experts=jax.tree.map(lambda z: z[first:first + 2],
                                             p["experts"]))
        y, _, c = moe.forward(share, cfg, x)
        parts.append(y.reshape(-1, x.shape[-1]) - shared)
        total_pairs += int(c[0])
    summed = sum(parts) + shared
    np.testing.assert_allclose(np.asarray(summed),
                               np.asarray(full.reshape(summed.shape)),
                               rtol=1e-5, atol=1e-5)
    assert total_pairs == int(counts[0]) == 2 * 7 * cfg_all.moe.top_k
    a = reference.arch(dict(_hf(cfg_all), n_routed_experts=8))
    want = reference._experts(p, x.reshape(-1, x.shape[-1]), a, False)
    np.testing.assert_allclose(np.asarray(full.reshape(want.shape)),
                               np.asarray(want), rtol=1e-4, atol=1e-5)


# -- (c) YaRN -------------------------------------------------------------

def test_yarn_frequencies_and_scale_at_published_settings():
    """Correction range [10, 23] of 32 rotary frequencies at factor 40,
    4096 original positions, beta 32/1; scale 192^-1/2 * (0.1 * 0.707 *
    ln 40 + 1)^2 = 0.114721; the cos/sin factor is exactly 1."""
    cfg = configs.get("deepseek-v2-lite-16b")
    y = cfg.rope_scaling
    got = np.asarray(common.rope_freqs(64, cfg.rope_theta, y))
    base = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    for i in (0, 5, 10):
        assert got[i] == pytest.approx(base[i], rel=1e-6)
    for i in (23, 28, 31):
        assert got[i] == pytest.approx(base[i] / 40, rel=1e-6)
    ramp = (16 - 10) / 13
    assert got[16] == pytest.approx(base[16] / 40 * ramp
                                    + base[16] * (1 - ramp), rel=1e-6)
    assert mla.softmax_scale(cfg) == pytest.approx(0.114721, abs=1e-6)
    assert (192 ** -0.5 * (0.1 * 0.707 * math.log(40) + 1) ** 2
            == pytest.approx(mla.softmax_scale(cfg), rel=1e-12))
    assert common.rope_mscale(y) == 1.0
    plain = cfg.replace(rope_scaling=None)
    assert mla.softmax_scale(plain) == pytest.approx(192 ** -0.5)


# -- (d) drop-free routing, padding unrouted ------------------------------

def test_all_pairs_to_one_expert_drop_nothing_and_padding_is_unrouted():
    """Every token routed to experts 0 and 1 (more pairs than a tile): all
    are computed; positions marked invalid route no pair and get only the
    shared expert."""
    cfg = _cfg(held=0)
    p = jax.tree.map(lambda z: z[0],
                     _params(cfg, seed=5)["groups"]["0"]["ffn"])
    w = np.zeros((cfg.d_model, cfg.moe.num_experts), np.float32)
    w[:, 0], w[:, 1] = 4.0, 2.0
    p = dict(p, router={"w": jnp.asarray(w)})
    b, t, d = 3, 40, cfg.d_model
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (b, t, d)))
    y, _, counts = moe.forward(p, cfg, x)
    n = b * t
    assert moe.tile_rows(n * cfg.moe.top_k, cfg.moe.num_experts) < n
    assert int(counts[0]) == 2 * n and int(counts[1]) == n
    xf = x.reshape(n, d)
    _, gates, idx = moe.route(p, cfg, xf)
    assert (np.asarray(idx) == [0, 1]).all()

    def expert(e, v):
        one = jax.tree.map(lambda z: z[e:e + 1], p["experts"])
        return moe._swiglu_bank(one, v)

    want = (gates[:, :1] * expert(0, xf) + gates[:, 1:] * expert(1, xf)
            + moe._swiglu_bank(p["shared"], xf))
    np.testing.assert_allclose(np.asarray(y.reshape(n, d)),
                               np.asarray(want), rtol=1e-5, atol=1e-5)

    valid = jnp.arange(t)[None, :] < jnp.asarray([t, 7, 0])[:, None]
    yv, _, cv = moe.forward(p, cfg, x, valid)
    assert int(cv[0]) == 2 * (t + 7)
    pad = ~np.asarray(valid).reshape(n)
    np.testing.assert_allclose(
        np.asarray(yv.reshape(n, d))[pad],
        np.asarray(moe._swiglu_bank(p["shared"], xf))[pad],
        rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(yv.reshape(n, d))[~pad],
                               np.asarray(want)[~pad], rtol=1e-5,
                               atol=1e-5)


# -- (e) the grouped kernel in interpret mode -----------------------------

def _one_layer_kernel(x, w_gate, w_up, w_down, tg, nt, *, tm):
    """The kernel, interpreted, on one layer's banks: a stack of one."""
    return gmm.moe_grouped_swiglu(x, w_gate[None], w_up[None], w_down[None],
                                  tg, nt, tm=tm,
                                  layer=jnp.zeros((1,), jnp.int32),
                                  interpret=True)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_kernel_matches_jnp_path_with_empty_experts(dtype):
    """Group sizes 0, 37, 0, 5, 16 (two empty experts, one spanning three
    tiles of 16): the kernel's live tiles equal the oracle's; the layout
    covers every live pair once."""
    rng = np.random.default_rng(7)
    held, d, h, tm = 5, 32, 48, 16
    local = np.repeat(np.arange(held), [0, 37, 0, 5, 16])
    local = rng.permutation(np.concatenate([local, np.zeros(10, int)]))
    live = np.ones(local.shape, bool)
    live[local.shape[0] - 10:] = False           # ten dead pairs
    live = rng.permutation(live)
    local = jnp.asarray(local, jnp.int32)
    slot, tg, nt, sizes = moe.group_pairs(local, jnp.asarray(live), held,
                                          tm)
    assert list(np.asarray(sizes)) == list(np.bincount(
        np.asarray(local)[live], minlength=held))
    assert int(nt[0]) == 3 + 1 + 1
    s = np.asarray(slot)[live]
    assert len(set(s.tolist())) == live.sum()
    assert (np.asarray(tg)[s // tm] == np.asarray(local)[live]).all()

    rows = tg.shape[0] * tm
    x = jnp.asarray(rng.normal(size=(rows, d)), dtype)
    w = [jnp.asarray(rng.normal(size=sh) * 0.2, dtype)
         for sh in ((held, d, h), (held, d, h), (held, h, d))]
    got = _one_layer_kernel(x, *w, tg, nt, tm=tm)
    want = kref.moe_grouped_swiglu(x, *w, tg, nt, tm=tm)
    live_rows = int(nt[0]) * tm
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got[:live_rows], np.float32),
                               np.asarray(want[:live_rows], np.float32),
                               rtol=tol, atol=tol)


def test_grouped_kernel_gradient_matches_jnp_path():
    """The kernel's VJP (XLA grouped matmuls over the same layout) gives
    the oracle's gradients, for the trainer: groups 37, 0, 5, 16 of 16-row
    tiles, rows past the live tiles unread."""
    rng = np.random.default_rng(11)
    held, d, h, tm = 4, 32, 48, 16
    local = jnp.asarray(rng.permutation(np.repeat(np.arange(held),
                                                  [37, 0, 5, 16])),
                        jnp.int32)
    _, tg, nt, _ = moe.group_pairs(local, jnp.ones(local.shape, bool),
                                   held, tm)
    rows = tg.shape[0] * tm
    x = jnp.asarray(rng.normal(size=(rows, d)), jnp.float32)
    w = [jnp.asarray(rng.normal(size=sh) * 0.2, jnp.float32)
         for sh in ((held, d, h), (held, d, h), (held, h, d))]
    r = (jnp.asarray(rng.normal(size=(rows, d)), jnp.float32)
         * (jnp.arange(rows) < int(nt[0]) * tm)[:, None])

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a) * r),
                        argnums=(0, 1, 2, 3))(x, *w)

    got = grads(lambda *a: _one_layer_kernel(*a, tg, nt, tm=tm))
    want = grads(lambda *a: kref.moe_grouped_swiglu(*a, tg, nt, tm=tm))
    for g, v in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(v),
                                   rtol=1e-5, atol=1e-5)


# -- (f) norm_topk_prob ---------------------------------------------------

def test_norm_topk_prob_false_leaves_gates_unnormalised():
    cfg = _cfg(held=0)
    p = jax.tree.map(lambda z: z[0],
                     _params(cfg, seed=8)["groups"]["0"]["ffn"])
    xf = jax.random.normal(jax.random.PRNGKey(9), (12, cfg.d_model))
    probs, gates, idx = moe.route(p, cfg, xf)
    assert not cfg.moe.norm_topk_prob
    np.testing.assert_allclose(
        np.asarray(gates),
        np.take_along_axis(np.asarray(probs), np.asarray(idx), -1))
    assert (np.asarray(gates).sum(-1) < 1 - 1e-3).all()
    normed = cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                 norm_topk_prob=True))
    _, g2, idx2 = moe.route(p, normed, xf)
    np.testing.assert_array_equal(np.asarray(idx2), np.asarray(idx))
    np.testing.assert_allclose(np.asarray(g2).sum(-1), 1.0, rtol=1e-6)

"""Paged MLA latent cache: kernel-vs-oracle sweep, bit-for-bit dense
equivalence, ragged latent prefill isolation, and the full pipeline.

The acceptance bar: the paged MLA decode's gather oracle must match the
dense MLA decode BIT-FOR-BIT in interpret mode across a page-size × batch
sweep (same einsum order, same fp32 promotion, masked lanes contribute
exact zeros), and greedy token streams must agree on every path.  The
Pallas kernel (online softmax) is held to tight f32 tolerance plus exact
argmax agreement.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.kernels import ops, ref
from repro.models import attention, lm, mla
from repro.models import cache as cache_mod


def _f32(t):
    return jax.tree.map(lambda x: x.astype(jnp.float32), t)


@pytest.fixture(scope="module")
def mla_llm():
    cfg = configs.reduced(configs.get("deepseek-v2-lite-16b"), d_model=32,
                          vocab=128)
    return cfg, _f32(lm.init(jax.random.PRNGKey(0), cfg))


# ---------------------------------------------------------------------------
# Kernel vs gather oracle (page-size × batch sweep)
# ---------------------------------------------------------------------------

def _setup(b, h, r, rd, ps, maxp, seed=0):
    rng = np.random.default_rng(seed)
    pool_n = b * maxp + 2                     # spare pages stay untouched
    dp = cache_mod.pad128(r + rd)
    q_abs = jnp.asarray(rng.normal(size=(b, h, r)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(b, h, rd)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(pool_n, ps, dp)), jnp.float32)
    bt = jnp.asarray(rng.permutation(pool_n)[:b * maxp].reshape(b, maxp)
                     .astype(np.int32))
    pos = jnp.asarray(rng.integers(0, maxp * ps, b), jnp.int32)
    lat_new = jnp.asarray(rng.normal(size=(b, dp)), jnp.float32)
    return q_abs, q_rope, pool, bt, pos, lat_new


@pytest.mark.parametrize("b,ps,maxp", [(1, 4, 3), (2, 8, 2), (3, 16, 4),
                                       (4, 8, 5)])
def test_paged_mla_kernel_matches_oracle_sweep(b, ps, maxp):
    h, r, rd = 4, 32, 8
    q_abs, q_rope, pool, bt, pos, lat = _setup(b, h, r, rd, ps, maxp)
    scale = 0.11
    o_ref, pool_ref = ref.paged_mla_decode(q_abs, q_rope, pool, bt, pos,
                                           lat, r=r, scale=scale)
    o_k, pool_k = ops.paged_mla_decode(q_abs, q_rope, pool, bt, pos, lat,
                                       scale=scale, use_pallas=True)
    np.testing.assert_allclose(np.asarray(o_ref), np.asarray(o_k),
                               rtol=2e-5, atol=2e-5)
    # The fused write must land identically: pools match exactly.
    np.testing.assert_array_equal(np.asarray(pool_ref), np.asarray(pool_k))


def test_paged_mla_unallocated_row_drops_write():
    b, h, r, rd, ps, maxp = 2, 4, 32, 8, 8, 2
    q_abs, q_rope, pool, bt, pos, lat = _setup(b, h, r, rd, ps, maxp, seed=3)
    bt = jnp.full_like(bt, -1)                # no row owns any page
    o1, p1 = ops.paged_mla_decode(q_abs, q_rope, pool, bt, pos, lat,
                                  scale=0.1, use_pallas=True)
    o2, p2 = ref.paged_mla_decode(q_abs, q_rope, pool, bt, pos, lat,
                                  r=r, scale=0.1)
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(pool))


# ---------------------------------------------------------------------------
# Bit-for-bit: paged MLA decode (oracle path) == dense MLA decode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ps,batch", [(4, 1), (4, 3), (8, 2), (8, 3),
                                      (16, 2), (16, 3)])
def test_paged_mla_decode_bitwise_matches_dense_sweep(mla_llm, ps, batch):
    """max_len % ps == 0 so the gathered stream has the dense extent —
    identical reduction shapes, identical bits."""
    cfg, params = mla_llm
    max_len = 32
    p = _f32(mla.init(jax.random.PRNGKey(1), cfg))
    rng = np.random.default_rng(ps * 100 + batch)
    t0 = 5
    x_pre = jnp.asarray(rng.normal(size=(batch, t0, cfg.d_model)),
                        jnp.float32)
    mask = jnp.tril(jnp.ones((t0, t0), bool))

    dense = mla.init_cache(cfg, batch, max_len, dtype=jnp.float32)
    paged = mla.init_cache(cfg, batch, max_len, dtype=jnp.float32,
                           paged=True, page_size=ps)
    paged = dict(paged, block_tables=attention.default_block_tables(
        batch, max_len, ps))
    yd, dense = mla.prefill(p, cfg, x_pre, dense, mask, jnp.arange(t0))
    yp, paged = mla.prefill(p, cfg, x_pre, paged, mask, jnp.arange(t0))
    np.testing.assert_array_equal(np.asarray(yd), np.asarray(yp))

    pos = jnp.full((batch,), t0, jnp.int32)
    for step in range(6):
        x = jnp.asarray(rng.normal(size=(batch, 1, cfg.d_model)), jnp.float32)
        od, dense = mla.decode_step(p, cfg, x, dense, pos)
        op, paged = mla.decode_step(p, cfg, x, paged, pos)
        np.testing.assert_array_equal(np.asarray(od), np.asarray(op)), step
        pos = pos + 1


def test_paged_mla_pipeline_matches_dense(mla_llm):
    """Full LM pipeline (ragged prefill -> greedy decode): exact tokens on
    both the oracle and the interpret-mode Pallas path."""
    cfg, params = mla_llm
    B, MAX_LEN, PS = 3, 32, 8
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(2, 100, (B, 8)), jnp.int32)
    lengths = jnp.asarray([8, 3, 5], jnp.int32)

    def run(cache, impl):
        logits, cache = lm.prefill(params, cfg, prompts, cache, impl=impl,
                                   lengths=lengths)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        pos = lengths
        out = [np.asarray(tok)]
        for _ in range(10):
            logits, cache = lm.decode_step(params, cfg, tok, cache, pos,
                                           impl=impl)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            pos = pos + 1
            out.append(np.asarray(tok))
        return np.stack(out, 1), np.asarray(logits)

    dense = lm.init_cache(cfg, B, MAX_LEN, dtype=jnp.float32)
    toks_d, logits_d = run(dense, "ref")

    def paged_cache():
        c = lm.init_cache(cfg, B, MAX_LEN, dtype=jnp.float32, paged=True,
                          page_size=PS)
        return lm.set_block_tables(
            c, attention.default_block_tables(B, MAX_LEN, PS))

    toks_p, logits_p = run(paged_cache(), "ref")
    np.testing.assert_array_equal(toks_d, toks_p)
    np.testing.assert_array_equal(logits_d, logits_p)   # bit-for-bit

    toks_k, logits_k = run(paged_cache(), "pallas")
    np.testing.assert_array_equal(toks_d, toks_k)
    np.testing.assert_allclose(logits_d, logits_k, rtol=2e-4, atol=2e-4)


def test_mla_ragged_prefill_preserves_untouched_rows(mla_llm):
    """lengths[b] == 0 rows keep their latent pages bit-for-bit."""
    cfg, params = mla_llm
    B, MAX_LEN, PS = 3, 32, 8
    rng = np.random.default_rng(2)
    prompts = jnp.asarray(rng.integers(2, 100, (B, 8)), jnp.int32)
    cache = lm.init_cache(cfg, B, MAX_LEN, dtype=jnp.float32, paged=True,
                          page_size=PS)
    cache = lm.set_block_tables(
        cache, attention.default_block_tables(B, MAX_LEN, PS))
    _, cache = lm.prefill(params, cfg, prompts, cache,
                          lengths=jnp.asarray([6, 0, 0], jnp.int32))
    bt = np.asarray(lm.get_block_tables(cache))
    pool_before = np.asarray(cache["groups"]["0"]["latent_pages"]).copy()
    _, cache = lm.prefill(params, cfg, prompts, cache,
                          lengths=jnp.asarray([0, 8, 0], jnp.int32))
    pool_after = np.asarray(cache["groups"]["0"]["latent_pages"])
    others = [p for p in range(pool_before.shape[1])
              if p not in set(bt[1].tolist())]
    np.testing.assert_array_equal(pool_before[:, others],
                                  pool_after[:, others])


def test_mla_scheduler_paged_dense_agree(mla_llm):
    """Continuous batching over an MLA model's latent page pools: batched
    token streams equal each request's solo stream."""
    from repro.serving.scheduler import ContinuousBatchingEngine, Request
    cfg, params = mla_llm
    spec = [(5, 4), (9, 3), (3, 5), (7, 2)]

    def requests():
        rng = np.random.default_rng(7)
        return [Request(rid=i,
                        prompt=[int(t) for t in rng.integers(2, 100, n)],
                        max_new_tokens=m)
                for i, (n, m) in enumerate(spec)]

    eng = ContinuousBatchingEngine(cfg, params, batch=2, max_len=32,
                                   page_size=8)
    batched = eng.run(requests())
    assert eng.stats["completed"] == len(spec)
    for got, want in zip(batched, requests()):
        ContinuousBatchingEngine(cfg, params, batch=1, max_len=32,
                                 page_size=8).run([want])
        assert got.tokens == want.tokens, got.rid

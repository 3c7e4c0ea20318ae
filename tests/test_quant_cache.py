"""Quantized page pools + tiered host-swap page memory.

Covers the quantized cache layouts end to end:

  * quant/dequant roundtrip properties — scale is never zero (all-zero
    rows quantize against scale 1.0) and int8 round-to-nearest bounds the
    per-element error by scale/2 (fp8 e4m3 by the half-ulp relative bound),
  * fused quant kernels vs the ``kernels.ref`` gather oracles — pool and
    scale writes bitwise identical between the Pallas(interpret) and ref
    paths, attention outputs tight,
  * snapshot_span/restore_span and host-swap-pool roundtrips bitwise on
    quantized pools INCLUDING the scale leaves (the generic page machinery
    iterates _POOL_LEAF_NDIM, so scales must travel with their pages),
  * the serving stack under ``kv_quant="int8"``: greedy streams identical
    to bf16 pools, COW prefix sharing, speculative rollback, replication,
  * at one KV head of 64: teacher-forced logits within a documented
    error budget of bf16 pools (int8 keeping every greedy token), and
    ≥1.8x fewer resident bytes per page than bf16,
  * tiered memory: swap-preemption recovers a long-context victim in fewer
    steps than recompute-from-scratch with identical streams and no leaked
    swap slots or device pages.

Mirrors tests/test_allocator_properties.py's optional-hypothesis pattern:
explicit seed parameters always run; when ``hypothesis`` is installed (the
CI property job) the roundtrip bounds are additionally driven by generated
inputs.  Tier-1 collects and passes without the package.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.kernels import ops, ref
from repro.models import attention, cache as cache_mod, lm
from repro.serving.scheduler import ContinuousBatchingEngine, Request

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False

QMODES = ["int8", "fp8"]


def _qdtype(mode: str):
    return jnp.int8 if mode == "int8" else cache_mod.FP8_DTYPE


def _f32(params):
    return jax.tree.map(lambda x: x.astype(jnp.float32), params)


@pytest.fixture(scope="module")
def llm():
    cfg = configs.reduced(configs.get("olmo-1b"), d_model=32, vocab=128)
    return cfg, _f32(lm.init(jax.random.PRNGKey(0), cfg))


# ---------------------------------------------------------------------------
# Quant/dequant roundtrip properties
# ---------------------------------------------------------------------------

def _check_roundtrip(x: jax.Array, mode: str) -> None:
    q, s = ref.quantize_rows(x, _qdtype(mode))
    sn = np.asarray(s)
    assert np.all(sn > 0), "scale must never be zero"
    xf = np.asarray(x, np.float32)
    err = np.abs(np.asarray(ref.dequantize_rows(q, s)) - xf)
    if mode == "int8":
        # symmetric round-to-nearest: |x - q*scale| <= scale/2
        bound = sn[..., None] * 0.5 * (1 + 1e-5) + 1e-7
    else:
        # e4m3 (~3 mantissa bits): half-ulp relative error 2^-4 for
        # normals, plus the denormal floor in q units.
        bound = np.abs(xf) * 2.0 ** -4 + sn[..., None] * 2.0 ** -9 + 1e-7
    assert np.all(err <= bound), f"max err {err.max()} over bound ({mode})"


@pytest.mark.parametrize("mode", QMODES)
@pytest.mark.parametrize("seed", range(8))
def test_quantize_rows_roundtrip_bounds(mode, seed):
    rng = np.random.default_rng(seed)
    d = int(2 ** rng.integers(2, 7))
    mag = float(2.0 ** rng.uniform(-6, 6))
    x = jnp.asarray(rng.normal(0.0, mag, (3, 5, d)), jnp.float32)
    _check_roundtrip(x, mode)


@pytest.mark.parametrize("mode", QMODES)
def test_quantize_all_zero_rows_scale_one(mode):
    q, s = ref.quantize_rows(jnp.zeros((4, 8), jnp.float32), _qdtype(mode))
    assert np.all(np.asarray(s) == 1.0)
    assert np.all(np.asarray(q.astype(jnp.float32)) == 0.0)


@pytest.mark.parametrize("mode", QMODES)
def test_quantize_mixed_zero_and_huge_rows(mode):
    x = jnp.stack([jnp.zeros((16,)), jnp.full((16,), 3e4),
                   jnp.full((16,), -1e-6)]).astype(jnp.float32)
    _check_roundtrip(x, mode)


if HAVE_HYPOTHESIS:
    @settings(max_examples=60, deadline=None)
    @given(seed=st.integers(0, 2 ** 31 - 1), mi=st.integers(0, 7),
           logmag=st.floats(-8, 8), d=st.integers(1, 48))
    def test_quantize_rows_roundtrip_hypothesis(seed, mi, logmag, d):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.normal(0.0, 2.0 ** logmag, (2, 3, d)),
                        jnp.float32)
        _check_roundtrip(x, QMODES[mi % len(QMODES)])


# ---------------------------------------------------------------------------
# Fused quant kernels vs ref oracles (interpret mode on CPU)
# ---------------------------------------------------------------------------

def _mha_quant_pool(rng, mode, P=6, Hkv=2, ps=8, D=16):
    qd = _qdtype(mode)
    kq, ks = ref.quantize_rows(
        jnp.asarray(rng.normal(0, 1, (P, Hkv, ps, D)), jnp.float32), qd)
    vq, vs = ref.quantize_rows(
        jnp.asarray(rng.normal(0, 1, (P, Hkv, ps, D)), jnp.float32), qd)
    return kq, ks, vq, vs


@pytest.mark.parametrize("mode", QMODES)
def test_paged_decode_quant_kernel_matches_oracle(mode):
    rng = np.random.default_rng(3)
    B, Hq, Hkv, D, ps, maxp = 2, 4, 2, 16, 8, 3
    kq, ks, vq, vs = _mha_quant_pool(rng, mode, P=B * maxp, Hkv=Hkv, ps=ps,
                                     D=D)
    bt = jnp.arange(B * maxp, dtype=jnp.int32).reshape(B, maxp)
    q = jnp.asarray(rng.normal(0, 1, (B, Hq, D)), jnp.float32)
    pos = jnp.asarray([5, 13], jnp.int32)
    kn = jnp.asarray(rng.normal(0, 1, (B, Hkv, D)), jnp.float32)
    vn = jnp.asarray(rng.normal(0, 1, (B, Hkv, D)), jnp.float32)
    o1, kp1, vp1, ks1, vs1 = ops.paged_decode_attention_quant(
        q, kq, ks, vq, vs, bt, pos, kn, vn, use_pallas=True)
    o2, kp2, vp2, ks2, vs2 = ops.paged_decode_attention_quant(
        q, kq, ks, vq, vs, bt, pos, kn, vn, use_pallas=False)
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), atol=1e-4)
    for a, b in ((kp1, kp2), (vp1, vp2), (ks1, ks2), (vs1, vs2)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


@pytest.mark.parametrize("mode", QMODES)
def test_paged_chunk_quant_kernel_matches_oracle(mode):
    rng = np.random.default_rng(4)
    B, Hq, Hkv, D, ps, maxp, C = 2, 2, 1, 16, 8, 4, 6
    kq, ks, vq, vs = _mha_quant_pool(rng, mode, P=B * maxp, Hkv=Hkv, ps=ps,
                                     D=D)
    bt = jnp.arange(B * maxp, dtype=jnp.int32).reshape(B, maxp)
    q = jnp.asarray(rng.normal(0, 1, (B, Hq, C, D)), jnp.float32)
    start = jnp.asarray([3, 11], jnp.int32)
    span = jnp.asarray([6, 4], jnp.int32)
    kn = jnp.asarray(rng.normal(0, 1, (B, Hkv, C, D)), jnp.float32)
    vn = jnp.asarray(rng.normal(0, 1, (B, Hkv, C, D)), jnp.float32)
    o1, kp1, vp1, ks1, vs1 = ops.paged_chunk_attention_quant(
        q, kq, ks, vq, vs, bt, start, span, kn, vn, use_pallas=True)
    o2, kp2, vp2, ks2, vs2 = ops.paged_chunk_attention_quant(
        q, kq, ks, vq, vs, bt, start, span, kn, vn, use_pallas=False)
    # Lanes past a row's span are garbage on both paths; compare valid ones.
    for b_i in range(B):
        w = int(span[b_i])
        np.testing.assert_allclose(
            np.asarray(o1[b_i, :, :w], np.float32),
            np.asarray(o2[b_i, :, :w], np.float32), atol=1e-4)
    for a, b in ((kp1, kp2), (vp1, vp2), (ks1, ks2), (vs1, vs2)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


@pytest.mark.parametrize("mode", QMODES)
def test_paged_mla_decode_quant_kernel_matches_oracle(mode):
    rng = np.random.default_rng(5)
    B, Hq, r, rd, ps, maxp = 2, 2, 16, 8, 8, 3
    dp = cache_mod.pad128(r + rd)
    pool, scales = ref.quantize_rows(
        jnp.asarray(rng.normal(0, 1, (B * maxp, ps, dp)), jnp.float32),
        _qdtype(mode))
    bt = jnp.arange(B * maxp, dtype=jnp.int32).reshape(B, maxp)
    q_abs = jnp.asarray(rng.normal(0, 1, (B, Hq, r)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(0, 1, (B, Hq, rd)), jnp.float32)
    pos = jnp.asarray([4, 12], jnp.int32)
    lat = jnp.asarray(rng.normal(0, 1, (B, dp)), jnp.float32)
    sc = 1.0 / ((r + rd) ** 0.5)
    c1, p1, s1 = ops.paged_mla_decode_quant(q_abs, q_rope, pool, scales, bt,
                                            pos, lat, scale=sc,
                                            use_pallas=True)
    c2, p2, s2 = ops.paged_mla_decode_quant(q_abs, q_rope, pool, scales, bt,
                                            pos, lat, scale=sc,
                                            use_pallas=False)
    np.testing.assert_allclose(np.asarray(c1, np.float32),
                               np.asarray(c2, np.float32), atol=1e-4)
    assert np.asarray(p1).tobytes() == np.asarray(p2).tobytes()
    assert np.asarray(s1).tobytes() == np.asarray(s2).tobytes()


# ---------------------------------------------------------------------------
# Snapshot/restore + host-swap roundtrips, bitwise on quantized pools
# ---------------------------------------------------------------------------

def _pool_leaf_bytes(cache):
    """{(path, leaf): raw bytes} for every paged pool/scale leaf."""
    out = {}
    for path, layout, layer in cache_mod.iter_layers(cache):
        for name in cache_mod.pool_leaves(layer, layout):
            out[path + (name,)] = np.asarray(layer[name]).tobytes()
    return out


def _quant_prefilled(cfg, params, mode, batch=2, max_len=32, ps=8, plen=10,
                     seed=0):
    rng = np.random.default_rng(seed)
    cache = lm.init_cache(cfg, batch, max_len, dtype=jnp.float32,
                          paged=True, page_size=ps, kv_quant=mode)
    cache = lm.set_block_tables(
        cache, attention.default_block_tables(batch, max_len, ps))
    prompts = jnp.asarray(rng.integers(2, cfg.vocab_size, (batch, plen)),
                          jnp.int32)
    _, cache = lm.prefill(params, cfg, prompts, cache)
    return cache, plen


@pytest.mark.parametrize("mode", QMODES)
def test_snapshot_restore_span_bitwise_on_quant_pools(llm, mode):
    cfg, params = llm
    cache, plen = _quant_prefilled(cfg, params, mode)
    batch, width = 2, 4
    start = jnp.full((batch,), plen, jnp.int32)
    before = _pool_leaf_bytes(cache)
    snap = cache_mod.snapshot_span(cache, start, width)
    # Clobber slots inside the window with real decode writes.
    tok = jnp.asarray([7, 9], jnp.int32)
    clob = cache
    for t in range(2):
        _, clob = lm.decode_step(params, cfg, tok + t, clob,
                                 start + t)
    assert _pool_leaf_bytes(clob) != before
    back = cache_mod.restore_span(clob, snap, start, start, start + width)
    assert _pool_leaf_bytes(back) == before


@pytest.mark.parametrize("mode", QMODES)
def test_swap_pool_roundtrip_bitwise_on_quant_pools(llm, mode):
    cfg, params = llm
    cache, _ = _quant_prefilled(cfg, params, mode)
    pages, slots = [0, 1, 3], [2, 0, 1]
    before = _pool_leaf_bytes(cache)
    swap_pool = cache_mod.make_swap_pool(cache, n_slots=4)
    moved = cache_mod.swap_out_pages(cache, swap_pool, pages, slots)
    assert moved > 0

    def zero_pages(path, layout, layer):
        out = dict(layer)
        for name in cache_mod.pool_leaves(layer, layout):
            leaf = layer[name]
            core = cache_mod._POOL_LEAF_NDIM[layout][name]
            idx = jnp.asarray(pages)
            out[name] = (leaf.at[:, idx].set(0) if leaf.ndim == core + 1
                         else leaf.at[idx].set(0))
        return out

    clob = cache_mod.map_layers(cache, zero_pages)
    assert _pool_leaf_bytes(clob) != before
    back = cache_mod.swap_in_pages(clob, swap_pool, slots, pages)
    assert _pool_leaf_bytes(back) == before


# ---------------------------------------------------------------------------
# Cross-pool page movement (disaggregation transfer primitive)
# ---------------------------------------------------------------------------

def _page_rows(cache, pages):
    """{(path, leaf): raw bytes} of the given pool pages — scale rows travel
    with their payload rows, so a quantized page is only 'moved' when BOTH
    land bitwise."""
    out = {}
    for path, layout, layer in cache_mod.iter_layers(cache):
        for name in cache_mod.pool_leaves(layer, layout):
            leaf = np.asarray(layer[name])
            core = cache_mod._POOL_LEAF_NDIM[layout][name]
            rows = leaf[:, pages] if leaf.ndim == core + 1 else leaf[pages]
            out[path + (name,)] = rows.tobytes()
    return out


@pytest.mark.parametrize("mode", QMODES)
def test_copy_pages_across_distinct_quant_pools_bitwise(llm, mode):
    """The disaggregation data plane: pool rows AND scale rows of a
    quantized page land bitwise in a DIFFERENT engine's pool, and every
    untouched destination page keeps its prior bytes."""
    cfg, params = llm
    src, _ = _quant_prefilled(cfg, params, mode)
    dst, _ = _quant_prefilled(cfg, params, mode, seed=9)
    src_ids, dst_ids = [0, 4], [2, 6]
    assert _page_rows(src, src_ids) != _page_rows(dst, dst_ids)
    newdst, moved = cache_mod.copy_pages_across(src, dst, src_ids, dst_ids)
    assert moved > 0
    assert _page_rows(newdst, dst_ids) == _page_rows(src, src_ids)
    others = [p for p in range(8) if p not in dst_ids]
    assert _page_rows(newdst, others) == _page_rows(dst, others)


@pytest.mark.parametrize("mode", QMODES)
def test_export_adopt_roundtrip_quant_bitwise(llm, mode):
    """Host-transport half (export on the prefill side, adopt on the
    decode side) moves quantized pages bitwise across distinct pools."""
    cfg, params = llm
    src, _ = _quant_prefilled(cfg, params, mode)
    dst, _ = _quant_prefilled(cfg, params, mode, seed=9)
    rows = cache_mod.export_pages(src, [1, 5])
    newdst = cache_mod.adopt_pages(dst, rows, [3, 7])
    assert _page_rows(newdst, [3, 7]) == _page_rows(src, [1, 5])
    untouched = [p for p in range(8) if p not in (3, 7)]
    assert _page_rows(newdst, untouched) == _page_rows(dst, untouched)


def test_copy_pages_across_mismatch_names_layer_and_shapes(llm):
    """A pool-leaf mismatch fails loudly with the layer path, layout and
    both shapes — not deep inside a kernel call."""
    cfg, params = llm
    src, _ = _quant_prefilled(cfg, params, "int8", ps=8)
    dst, _ = _quant_prefilled(cfg, params, "int8", ps=16)
    with pytest.raises(ValueError,
                       match=r"pool leaf '.*' of layer .* does not match"):
        cache_mod.copy_pages_across(src, dst, [0])


def test_adopt_pages_mismatch_names_layer_and_shapes(llm):
    cfg, params = llm
    src, _ = _quant_prefilled(cfg, params, "int8", ps=8)
    dst, _ = _quant_prefilled(cfg, params, "int8", ps=16)
    rows = cache_mod.export_pages(src, [0])
    with pytest.raises(ValueError,
                       match=r"pool leaf '.*' of layer .* does not match"):
        cache_mod.adopt_pages(dst, rows, [0])


# ---------------------------------------------------------------------------
# Serving stack under kv_quant
# ---------------------------------------------------------------------------

def _run_engine(cfg, params, prompts, max_new, **kw):
    reqs = [Request(rid=i, prompt=list(p), max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    eng = ContinuousBatchingEngine(cfg, params, **kw)
    eng.run(reqs)
    return eng, {r.rid: list(r.tokens) for r in reqs}


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(2, cfg.vocab_size, n)]
            for n in lens]


def test_engine_int8_streams_match_fp32(llm):
    cfg, params = llm
    prompts = _prompts(cfg, (6, 11, 4))
    kw = dict(batch=2, max_len=32, paged=True, page_size=8, chunk_size=8)
    _, s_off = _run_engine(cfg, params, prompts, 8, **kw)
    eng, s_q = _run_engine(cfg, params, prompts, 8, kv_quant="int8", **kw)
    assert s_q == s_off
    assert eng.stats["completed"] == len(prompts)


def test_engine_rejects_quant_without_paged(llm):
    cfg, params = llm
    with pytest.raises(ValueError, match="dense-cache mode"):
        ContinuousBatchingEngine(cfg, params, batch=2, max_len=32,
                                 paged=False)
    with pytest.raises(ValueError, match="dense-cache mode"):
        ContinuousBatchingEngine(cfg, params, batch=2, max_len=32,
                                 paged=False, kv_quant="int8")
    with pytest.raises(ValueError, match="kv_quant"):
        ContinuousBatchingEngine(cfg, params, batch=2, max_len=32,
                                 paged=True, kv_quant="int4")


def test_cow_prefix_sharing_with_int8_pools(llm):
    cfg, params = llm
    shared = _prompts(cfg, (13,), seed=2)[0]
    prompts = [list(shared) for _ in range(3)]
    kw = dict(batch=3, max_len=32, paged=True, page_size=8, chunk_size=8,
              kv_quant="int8")
    _, s_plain = _run_engine(cfg, params, prompts, 8, **kw)
    eng, s_cow = _run_engine(cfg, params, prompts, 8, prefix_sharing=True,
                             **kw)
    assert s_cow == s_plain
    assert eng.stats["shared_pages"] > 0
    assert eng.stats["completed"] == 3


def test_spec_decode_rollback_with_int8_pools(llm):
    cfg, params = llm
    motif = _prompts(cfg, (5,), seed=3)[0]
    prompts = [(motif * 4)[:18] for _ in range(2)]
    kw = dict(batch=2, max_len=64, paged=True, page_size=8, chunk_size=8,
              kv_quant="int8")
    _, s_off = _run_engine(cfg, params, prompts, 12, **kw)
    eng, s_spec = _run_engine(cfg, params, prompts, 12, spec_decode="ngram",
                              spec_k=4, **kw)
    assert s_spec == s_off
    assert eng.stats["accepted_tokens"] > 0


def test_replicated_server_with_int8_pools(llm):
    from repro.serving.replicated import MultiEngineServer

    cfg, params = llm
    prompts = _prompts(cfg, (9, 9, 6, 6), seed=4)
    reqs = [Request(rid=i, prompt=list(p), max_new_tokens=6)
            for i, p in enumerate(prompts)]
    server = MultiEngineServer(cfg, params, replicas=2, batch=2, max_len=32,
                               page_size=8, chunk_size=8, kv_quant="int8")
    for r in reqs:
        server.submit(r)
    while server.step():
        assert server.clock < 5_000
    server.sync()
    assert server.stats()["completed"] == len(reqs)
    assert server.converged()


@pytest.fixture(scope="module")
def head64_llm():
    """The simulated agent LLM with one KV head of 64, in bf16.  A quantized
    pool's scales amortise over the feature axis, so its gain over bf16 is
    2D/(D+4): 1.88x here (the 16-wide heads of the plain sim-LLM cap it at
    1.6x whatever the layout)."""
    from repro.agents.orchestrator import make_sim_llm
    cfg = make_sim_llm()[0].replace(num_heads=1, num_kv_heads=1,
                                    head_dim=64)
    return cfg, lm.init(jax.random.PRNGKey(0), cfg)


# Teacher-forced logit error against bf16 pools: int8's per-page-row scales
# bound an element's error by amax/254; fp8 e4m3 keeps ~3 mantissa bits, so
# its budget is looser and it may flip a near-tie argmax.
QUANT_LOGIT_TOL = {"int8": 0.25, "fp8": 0.5}


def _forced_logits(cfg, params, mode, tokens, feed=None, steps=12,
                   page_size=8, max_len=64):
    """Prefill ``tokens`` and decode ``steps`` tokens on ``mode`` pools,
    feeding ``feed`` (or the run's own greedy tokens); returns the logits
    of every step and the tokens fed."""
    b, n = tokens.shape
    maxp = -(-max_len // page_size)
    cache = lm.set_block_tables(
        lm.init_cache(cfg, b, max_len, paged=True, page_size=page_size,
                      kv_quant=mode),
        jnp.arange(b * maxp, dtype=jnp.int32).reshape(b, maxp))
    logits, cache = lm.prefill(params, cfg, tokens, cache)
    outs, fed = [np.asarray(logits, np.float32)], []
    for t in range(steps):
        nxt = (jnp.asarray(np.argmax(outs[-1], -1), jnp.int32)
               if feed is None else feed[t])
        fed.append(nxt)
        logits, cache = lm.decode_step(params, cfg, nxt, cache,
                                       jnp.full((b,), n + t, jnp.int32))
        outs.append(np.asarray(logits, np.float32))
    return outs, fed


@pytest.mark.parametrize("mode", QMODES)
def test_quant_logit_error_within_budget(head64_llm, mode):
    cfg, params = head64_llm
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        2, cfg.vocab_size, (2, 12)), jnp.int32)
    want, fed = _forced_logits(cfg, params, "off", tokens)
    got, _ = _forced_logits(cfg, params, mode, tokens, feed=fed)
    worst = max(float(np.max(np.abs(g - w))) for g, w in zip(got, want))
    assert worst <= QUANT_LOGIT_TOL[mode], worst
    if mode == "int8":
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.argmax(g, -1),
                                          np.argmax(w, -1))


@pytest.fixture(scope="module")
def head64_ragged_runs(head64_llm):
    """The full engine on bf16 and int8 pools over one ragged workload with
    mid-flight admission: six prompts of 4 to 59 tokens (log-uniform), 8
    tokens each, at batch 4, page 16, chunk 16."""
    cfg, params = head64_llm
    rng = np.random.default_rng(0)
    lens = np.exp(rng.uniform(np.log(4), np.log(59), 6)).astype(int)
    prompts = [[int(t) for t in rng.integers(2, cfg.vocab_size, n)]
               for n in lens]
    runs = {}
    for mode in ("off", "int8"):
        reqs = [Request(rid=i, prompt=list(p), max_new_tokens=8)
                for i, p in enumerate(prompts)]
        eng = ContinuousBatchingEngine(cfg, params, batch=4, max_len=128,
                                       page_size=16, chunk_size=16,
                                       kv_quant=mode)
        for r in reqs:
            eng.submit(r)
        peak = 0
        while eng.step():
            peak = max(peak, eng.resident_cache_bytes())
        assert eng.stats["completed"] == len(reqs)
        runs[mode] = (eng.stats, peak, [list(r.tokens) for r in reqs])
    return prompts, runs


def test_engine_int8_streams_match_bf16_pools(head64_ragged_runs):
    _, runs = head64_ragged_runs
    assert runs["int8"][2] == runs["off"][2]


def test_engine_int8_tokens_within_budget_on_fewer_bytes(head64_llm,
                                                         head64_ragged_runs):
    """int8 pools walk the bf16 run's pages in the same steps while pinning
    fewer bytes (a step reads each live page whole, pool and scales), and
    each token they serve scores within the int8 logit budget of the best
    under a cache-free bf16 forward of the sequence served so far."""
    cfg, params = head64_llm
    prompts, runs = head64_ragged_runs
    (s_off, peak_off, _), (s_q, peak_q, streams) = runs["off"], runs["int8"]
    for name in ("steps", "peak_pages", "prefill_chunks"):
        assert s_q[name] == s_off[name], name
    assert peak_q < peak_off
    for prompt, toks in zip(prompts, streams):
        seq = jnp.asarray([prompt + toks], jnp.int32)
        logits = np.asarray(lm.forward(params, cfg, seq)[0][0], np.float32)
        for k, tok in enumerate(toks):
            row = logits[len(prompt) + k - 1]
            assert row.max() - row[tok] <= QUANT_LOGIT_TOL["int8"], (k, tok)


@pytest.mark.parametrize("mode", QMODES)
def test_quant_pool_pins_fewer_bytes_per_page(head64_llm, mode):
    """What one resident page pins, pool and scale leaves together, against
    bf16 pools: how many more tokens the same pool memory holds."""
    cfg, params = head64_llm
    per_page = {}
    for m in ("off", mode):
        eng = ContinuousBatchingEngine(cfg, params, batch=2, max_len=64,
                                       page_size=8, chunk_size=8, kv_quant=m)
        for r, p in enumerate(_prompts(cfg, (20, 9))):
            eng.submit(Request(rid=r, prompt=p, max_new_tokens=4))
        eng.step()
        used = eng.allocator.num_pages - eng.allocator.available
        assert used > 0
        per_page[m] = eng.resident_cache_bytes() / used
    assert per_page["off"] / per_page[mode] >= 1.8


# ---------------------------------------------------------------------------
# Tiered host-swap page memory
# ---------------------------------------------------------------------------

SWAP_KW = dict(batch=2, max_len=64, paged=True, page_size=8, num_pages=6,
               chunk_size=8, swap_min_tokens=16)


def test_swap_preemption_beats_recompute_and_streams_match(llm):
    cfg, params = llm
    prompts = _prompts(cfg, (24, 6), seed=5)
    eng0, s0 = _run_engine(cfg, params, prompts, 16, swap_tier_pages=0,
                           **SWAP_KW)
    eng1, s1 = _run_engine(cfg, params, prompts, 16, swap_tier_pages=8,
                           **SWAP_KW)
    assert eng0.stats["preempt_recompute"] > 0     # scenario really preempts
    assert eng1.stats["preempt_swap"] > 0
    assert eng1.stats["swap_outs"] > 0 and eng1.stats["swap_ins"] > 0
    assert s1 == s0                                # bit-identical streams
    assert eng1.stats["steps"] < eng0.stats["steps"]


def test_swap_tier_leaks_nothing_at_drain(llm):
    cfg, params = llm
    prompts = _prompts(cfg, (24, 6), seed=5)
    eng, _ = _run_engine(cfg, params, prompts, 16, swap_tier_pages=8,
                         **SWAP_KW)
    assert eng.stats["swap_ins"] == eng.stats["swap_outs"]
    assert sorted(eng._swap_free) == list(range(8))   # all slots returned
    assert eng.allocator.available == eng.allocator.num_pages


def test_swap_composes_with_int8_pools(llm):
    cfg, params = llm
    prompts = _prompts(cfg, (24, 6), seed=5)
    _, s_plain = _run_engine(cfg, params, prompts, 16, swap_tier_pages=0,
                             kv_quant="int8", **SWAP_KW)
    eng, s_swap = _run_engine(cfg, params, prompts, 16, swap_tier_pages=8,
                              kv_quant="int8", **SWAP_KW)
    assert s_swap == s_plain
    assert eng.stats["preempt_swap"] > 0


def test_swap_disabled_for_recurrent_state(llm):
    cfg, _ = llm
    cfg = cfg.replace(block_pattern=("attn", "rglru"), num_layers=4)
    params = _f32(lm.init(jax.random.PRNGKey(2), cfg))
    eng = ContinuousBatchingEngine(cfg, params, batch=2, max_len=32,
                                   paged=True, page_size=8,
                                   swap_tier_pages=4)
    assert eng.swap_pool is None                   # recurrent rows recompute

"""Token-budget mixed serve step: chunked prefill fused with decode.

Acceptance sweep: chunked admission is equivalent to one-shot ragged
prefill across chunk sizes {1, ps/2, ps, 2·ps} on MHA, MLA, and hybrid
recurrent configs — caches bit-for-bit for every chunk size ≥ 2 (and for
MLA at every size), greedy tokens exactly equal everywhere.  Chunk width 1
reduces the query matmul to a matvec whose XLA reduction order rounds the
last bit differently, so width-1 logits are asserted at tight tolerance
plus exact argmax instead.

Everything runs in f32 + interpret mode (CPU) — the same bar the paged
decode kernels were verified at.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.kernels import ops, ref
from repro.models import attention, lm
from repro.serving import engine as engine_mod
from repro.serving.scheduler import ContinuousBatchingEngine, Request

B, MAX_LEN, PS = 3, 32, 8


def _f32(t):
    return jax.tree.map(lambda x: x.astype(jnp.float32), t)


@pytest.fixture(scope="module")
def mha_llm():
    cfg = configs.reduced(configs.get("olmo-1b"), d_model=32, vocab=128)
    cfg = cfg.replace(num_layers=2)
    return cfg, _f32(lm.init(jax.random.PRNGKey(0), cfg))


@pytest.fixture(scope="module")
def mla_llm():
    cfg = configs.reduced(configs.get("deepseek-v2-lite-16b"), d_model=32,
                          vocab=128)
    return cfg, _f32(lm.init(jax.random.PRNGKey(1), cfg))


@pytest.fixture(scope="module")
def hybrid_llm():
    """Paged full attention + RG-LRU recurrence in one pattern."""
    cfg = configs.reduced(configs.get("olmo-1b"), d_model=32, vocab=128)
    cfg = cfg.replace(block_pattern=("attn", "rglru"), num_layers=4)
    return cfg, _f32(lm.init(jax.random.PRNGKey(2), cfg))


@pytest.fixture(scope="module")
def xlstm_hybrid_llm():
    cfg = configs.reduced(configs.get("xlstm-125m"), d_model=32, vocab=128)
    cfg = cfg.replace(block_pattern=("slstm", "mlstm", "attn"),
                      num_layers=3, d_ff=128)
    return cfg, _f32(lm.init(jax.random.PRNGKey(3), cfg))


def _mk_cache(cfg, paged, batch=B, max_len=MAX_LEN, ps=PS):
    cache = lm.init_cache(cfg, batch, max_len, dtype=jnp.float32,
                          paged=paged, page_size=ps)
    if paged:
        cache = lm.set_block_tables(
            cache, attention.default_block_tables(batch, max_len, ps))
    return cache


def _chunked_admit(cfg, params, cache, prompts, lengths, chunk, impl="ref"):
    """Stream the ragged prompt batch in through mixed steps of ``chunk``."""
    filled = np.zeros(len(lengths), np.int64)
    logits = None
    while (filled < lengths).any():
        span = np.minimum(chunk, lengths - filled).clip(0)
        toks = np.zeros((len(lengths), chunk), np.int32)
        for b in range(len(lengths)):
            toks[b, :span[b]] = prompts[b, filled[b]:filled[b] + span[b]]
        lg, cache, _ = lm.mixed_step(params, cfg, jnp.asarray(toks), cache,
                                     jnp.asarray(filled, jnp.int32),
                                     jnp.asarray(span, jnp.int32), impl=impl)
        if logits is None:
            logits = np.array(lg)
        else:
            logits[span > 0] = np.asarray(lg)[span > 0]
        filled += span
    return logits, cache


# ---------------------------------------------------------------------------
# Kernel <-> oracle sweeps (pallas interpret vs pure-jnp ref)
# ---------------------------------------------------------------------------

CHUNK_CASES = [
    # (B, Hq, Hkv, page_size, maxp, D, C, window)
    (1, 1, 1, 8, 3, 32, 4, None),
    (2, 4, 1, 16, 4, 64, 8, None),        # MQA
    (3, 4, 2, 10, 3, 16, 5, None),        # unaligned sizes (interpret)
    (2, 8, 2, 8, 4, 32, 16, 11),          # span > page, sliding window
    (2, 2, 2, 8, 4, 32, 1, None),         # span 1 == fused decode
    # Served widths, so the walk takes several multi-page blocks; each
    # row is (start, span, table entries set to -1).
    pytest.param((4, 16, 16, 64, 11, 128, 32, None, (
        (626, 32, ()),                    # 11 pages: the last block partial;
                                          # the span straddles pages 9|10
        (300, 0, ()),                     # idle beside live rows
        (100, 5, tuple(range(2, 11))),    # -1 past its pages, in its block
        (191, 17, ()))), id="mha16"),     # straddles pages 2|3
    pytest.param((3, 16, 16, 64, 11, 128, 1, None, (
        (640, 1, ()), (5, 0, ()), (63, 1, tuple(range(1, 11))))),
        id="mha16-decode"),
    pytest.param((3, 48, 4, 64, 13, 128, 32, 300, (
        (760, 32, tuple(range(6))),       # -1 inside the live pages, all
                                          # below the window
        (0, 0, ()),
        (180, 20, tuple(range(4, 13))))), id="gqa12-window"),
]


def _chunk_setup(b, hq, hkv, ps, maxp, d, c, dtype, seed=0):
    r = np.random.default_rng(seed)
    pool = b * maxp + 2                       # spare pages stay untouched
    q = jnp.asarray(r.normal(size=(b, hq, c, d)), dtype)
    kp = jnp.asarray(r.normal(size=(pool, hkv, ps, d)), dtype)
    vp = jnp.asarray(r.normal(size=(pool, hkv, ps, d)), dtype)
    bt = jnp.asarray(r.permutation(pool)[:b * maxp].reshape(b, maxp)
                     .astype(np.int32))
    start = jnp.asarray(r.integers(0, maxp * ps - c, b), jnp.int32)
    span = jnp.asarray(r.integers(0, c + 1, b), jnp.int32)
    kn = jnp.asarray(r.normal(size=(b, hkv, c, d)), dtype)
    vn = jnp.asarray(r.normal(size=(b, hkv, c, d)), dtype)
    return q, kp, vp, bt, start, span, kn, vn


def _chunk_layout(b, hq, hkv, ps, maxp, d, c, dtype, rows, seed=0):
    """``_chunk_setup`` with each row's (start, span, -1 entries) given.
    Page 0 is in no table, so a -1 entry (read as page 0 by both paths)
    reads a page no row writes."""
    q, kp, vp, _, _, _, kn, vn = _chunk_setup(b, hq, hkv, ps, maxp, d, c,
                                              dtype, seed)
    r = np.random.default_rng(seed + 1)
    bt = (1 + r.permutation(kp.shape[0] - 1)[:b * maxp]).reshape(b, maxp)
    for i, (_, _, holes) in enumerate(rows):
        bt[i, list(holes)] = -1
    start = jnp.asarray([row[0] for row in rows], jnp.int32)
    span = jnp.asarray([row[1] for row in rows], jnp.int32)
    return q, kp, vp, jnp.asarray(bt, jnp.int32), start, span, kn, vn


def _tol(dtype):
    return (dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16
            else dict(rtol=2e-5, atol=2e-5))


@pytest.mark.parametrize("case", CHUNK_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_chunk_attention_kernel_matches_oracle(case, dtype):
    b, hq, hkv, ps, maxp, d, c, window, *rows = case
    setup = (functools.partial(_chunk_layout, rows=rows[0]) if rows
             else _chunk_setup)
    q, kp, vp, bt, start, span, kn, vn = setup(
        b, hq, hkv, ps, maxp, d, c, dtype)
    o1, kp1, vp1 = ops.paged_chunk_attention(q, kp, vp, bt, start, span,
                                             kn, vn, window=window)
    o2, kp2, vp2 = ref.paged_chunk_attention(q, kp, vp, bt, start, span,
                                             kn, vn, window=window)
    # Output rows beyond each row's span are garbage on both paths.
    mask = (np.arange(c)[None, :] < np.asarray(span)[:, None])
    m4 = mask[:, None, :, None]
    np.testing.assert_allclose(
        np.where(m4, np.asarray(o1, np.float32), 0.0),
        np.where(m4, np.asarray(o2, np.float32), 0.0), **_tol(dtype))
    # The fused multi-slot write must be bit-identical to the oracle's
    # scatter — and touch only the written slots.
    np.testing.assert_array_equal(np.asarray(kp1), np.asarray(kp2))
    np.testing.assert_array_equal(np.asarray(vp1), np.asarray(vp2))
    # An idle row's pages stay as they were.
    idle = np.asarray(bt)[np.asarray(span) == 0]
    idle = idle[idle >= 0]
    np.testing.assert_array_equal(np.asarray(kp1)[idle], np.asarray(kp)[idle])
    np.testing.assert_array_equal(np.asarray(vp1)[idle], np.asarray(vp)[idle])


def test_paged_chunk_span1_matches_decode_kernel_write():
    """A span-1 chunk writes exactly what the fused decode kernel writes."""
    b, hq, hkv, ps, maxp, d = 2, 4, 2, 8, 4, 32
    q, kp, vp, bt, start, _, kn, vn = _chunk_setup(
        b, hq, hkv, ps, maxp, d, 1, jnp.float32)
    one = jnp.ones((b,), jnp.int32)
    _, kp1, vp1 = ops.paged_chunk_attention(q, kp, vp, bt, start, one,
                                            kn, vn)
    _, kp2, vp2 = ops.paged_decode_attention(q[:, :, 0], kp, vp, bt, start,
                                             kn[:, :, 0], vn[:, :, 0])
    np.testing.assert_array_equal(np.asarray(kp1), np.asarray(kp2))
    np.testing.assert_array_equal(np.asarray(vp1), np.asarray(vp2))


@pytest.mark.parametrize("c", [1, 4, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_mla_chunk_kernel_matches_oracle(c, dtype):
    b, h, r, rd, ps, maxp = 2, 4, 16, 8, 8, 4
    dp = 128
    rng = np.random.default_rng(7)
    pool = b * maxp + 1
    q_abs = jnp.asarray(rng.normal(size=(b, h, c, r)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(b, h, c, rd)), jnp.float32)
    lp = jnp.asarray(rng.normal(size=(pool, ps, dp)), dtype)
    bt = jnp.asarray(rng.permutation(pool)[:b * maxp].reshape(b, maxp)
                     .astype(np.int32))
    start = jnp.asarray(rng.integers(0, maxp * ps - c, b), jnp.int32)
    span = jnp.asarray(rng.integers(0, c + 1, b), jnp.int32)
    ln = jnp.asarray(rng.normal(size=(b, c, dp)), dtype)
    ctx1, lp1 = ops.paged_mla_chunk(q_abs, q_rope, lp, bt, start, span, ln,
                                    scale=0.125)
    ctx2, lp2 = ref.paged_mla_chunk(q_abs, q_rope, lp, bt, start, span, ln,
                                    r=r, scale=0.125)
    mask = (np.arange(c)[None, :] < np.asarray(span)[:, None])[:, None, :,
                                                               None]
    np.testing.assert_allclose(
        np.where(mask, np.asarray(ctx1), 0.0),
        np.where(mask, np.asarray(ctx2), 0.0), **_tol(dtype))
    np.testing.assert_array_equal(np.asarray(lp1), np.asarray(lp2))


@pytest.mark.parametrize("kernel", ["mha", "mha_int8", "mla", "mla_int8"])
def test_chunk_kernels_write_no_padded_span_row(kernel):
    """A row whose span is shorter than the step's width carries, in its
    padded span rows, whatever the padded positions computed -- NaN
    included.  The fused write must take the span's rows alone: with NaN
    in every padded row of the new K/V (latent) rows, the pools and the
    live outputs are bit for bit those of zeros there."""
    b, h, hkv, ps, maxp, d, c = 2, 4, 2, 8, 4, 32, 8
    r, rd, dp = 16, 8, 128
    rng = np.random.default_rng(11)
    pool = b * maxp + 1
    bt = jnp.asarray(rng.permutation(pool)[:b * maxp].reshape(b, maxp)
                     .astype(np.int32))
    start = jnp.asarray([3, 11], jnp.int32)  # row 0 straddles two pages
    span = jnp.asarray([5, 2], jnp.int32)
    pad = (np.arange(c)[None, :] >= np.asarray(span)[:, None])     # [B, C]
    normal = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    if kernel.startswith("mha"):
        q = normal(b, h, c, d)
        pools = [normal(pool, hkv, ps, d) for _ in range(2)]
        if kernel == "mha_int8":
            (kp, ks), (vp, vs) = (ref.quantize_rows(p, jnp.int8)
                                  for p in pools)
            run = lambda kn, vn: ops.paged_chunk_attention_quant(
                q, kp, ks, vp, vs, bt, start, span, kn, vn)
        else:
            run = lambda kn, vn: ops.paged_chunk_attention(
                q, *pools, bt, start, span, kn, vn)
        new = [normal(b, hkv, c, d) for _ in range(2)]
        poison = jnp.asarray(pad)[:, None, :, None]
    else:
        q_abs, q_rope = normal(b, h, c, r), normal(b, h, c, rd)
        lp = normal(pool, ps, dp)
        if kernel == "mla_int8":
            lq, ls = ref.quantize_rows(lp, jnp.int8)
            run = lambda ln: ops.paged_mla_chunk_quant(
                q_abs, q_rope, lq, ls, bt, start, span, ln, scale=0.125)
        else:
            run = lambda ln: ops.paged_mla_chunk(
                q_abs, q_rope, lp, bt, start, span, ln, scale=0.125)
        new = [normal(b, c, dp)]
        poison = jnp.asarray(pad)[:, :, None]
    got = run(*(jnp.where(poison, jnp.nan, x) for x in new))
    want = run(*(jnp.where(poison, 0.0, x) for x in new))
    live = ~pad[:, None, :, None]
    np.testing.assert_array_equal(np.where(live, np.asarray(got[0]), 0),
                                  np.where(live, np.asarray(want[0]), 0))
    for g, w in zip(got[1:], want[1:]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ---------------------------------------------------------------------------
# Acceptance: chunked admission ≡ one-shot ragged prefill
# ---------------------------------------------------------------------------

CHUNK_SIZES = (1, PS // 2, PS, 2 * PS)


def _ragged_batch(seed=0):
    rng = np.random.default_rng(seed)
    lengths = np.asarray([8, 3, 5], np.int64)
    prompts = np.zeros((B, MAX_LEN), np.int32)
    for b in range(B):
        prompts[b, :lengths[b]] = rng.integers(2, 100, lengths[b])
    return prompts, lengths


@pytest.mark.parametrize("family", ["mha", "mla", "hybrid", "xlstm"])
def test_chunked_admission_equals_oneshot_prefill(family, mha_llm, mla_llm,
                                                  hybrid_llm,
                                                  xlstm_hybrid_llm):
    cfg, params = {"mha": mha_llm, "mla": mla_llm, "hybrid": hybrid_llm,
                   "xlstm": xlstm_hybrid_llm}[family]
    paged = family != "xlstm"                 # one dense-cache config too
    prompts, lengths = _ragged_batch()

    logits_a, cache_a = lm.prefill(params, cfg, jnp.asarray(prompts),
                                   _mk_cache(cfg, paged),
                                   lengths=jnp.asarray(lengths, jnp.int32))
    leaves_a = [np.asarray(x) for x in jax.tree.leaves(cache_a)]
    argmax_a = np.argmax(np.asarray(logits_a), -1)

    for chunk in CHUNK_SIZES:
        logits_b, cache_b = _chunked_admit(cfg, params,
                                           _mk_cache(cfg, paged), prompts,
                                           lengths, chunk)
        leaves_b = [np.asarray(x) for x in jax.tree.leaves(cache_b)]
        if chunk > 1:
            # Bit-for-bit: same cache bytes as the one-shot ragged prefill.
            for a, b_ in zip(leaves_a, leaves_b):
                np.testing.assert_array_equal(a, b_)
        else:
            for a, b_ in zip(leaves_a, leaves_b):
                np.testing.assert_allclose(a, b_, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(logits_a), logits_b,
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_array_equal(argmax_a, np.argmax(logits_b, -1))


def test_chunked_admission_bitwise_across_chunk_sizes(mha_llm):
    """Chunk partitioning cannot change the bits: every chunk size ≥ 2
    produces the identical cache AND identical last-position logits."""
    cfg, params = mha_llm
    prompts, lengths = _ragged_batch(seed=5)
    base = None
    for chunk in (PS // 2, PS, 2 * PS, MAX_LEN):
        logits, cache = _chunked_admit(cfg, params, _mk_cache(cfg, True),
                                       prompts, lengths, chunk)
        leaves = [np.asarray(x) for x in jax.tree.leaves(cache)]
        if base is None:
            base = (logits, leaves)
            continue
        np.testing.assert_array_equal(base[0], logits)
        for a, b_ in zip(base[1], leaves):
            np.testing.assert_array_equal(a, b_)


def test_mixed_step_span0_rows_keep_cache_bitwise(hybrid_llm):
    """Idle (span-0) rows — attention pool pages AND recurrent state — are
    untouched by other rows' spans."""
    from repro.models import cache as cache_mod
    cfg, params = hybrid_llm
    prompts, lengths = _ragged_batch(seed=9)
    cache = _mk_cache(cfg, True)
    # Row 0 prefills; rows 1, 2 idle.
    l0 = np.asarray([lengths[0], 0, 0], np.int64)
    _, cache = _chunked_admit(cfg, params, cache, prompts, l0, PS)
    bt = np.asarray(lm.get_block_tables(cache))
    row0_pages = sorted(set(bt[0].tolist()))
    before = {path: {k: np.asarray(v).copy() for k, v in layer.items()}
              for path, _, layer in cache_mod.iter_layers(cache)}
    # Now rows 1, 2 prefill; row 0 idle (span 0).
    l12 = np.asarray([0, lengths[1], lengths[2]], np.int64)
    _, cache = _chunked_admit(cfg, params, cache, prompts, l12, PS)
    for path, layout, layer in cache_mod.iter_layers(cache):
        if layout == "paged_mha":
            for name in cache_mod.pool_leaves(layer, layout):
                pool = np.asarray(layer[name])        # [G, P, Hkv, ps, D]
                np.testing.assert_array_equal(
                    pool[:, row0_pages], before[path][name][:, row0_pages])
        elif layout == "state":
            for name, v in layer.items():
                v = np.asarray(v)                     # [G, B, ...]
                np.testing.assert_array_equal(v[:, 0],
                                              before[path][name][:, 0])


# ---------------------------------------------------------------------------
# Recurrent ragged prefill (masked state carry-through)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", ["hybrid", "xlstm"])
def test_recurrent_ragged_prefill_isolates_rows(family, hybrid_llm,
                                                xlstm_hybrid_llm):
    """lm.prefill(lengths) on recurrent patterns: each row's state equals a
    solo prefill of that row alone, and zero-length rows keep state
    bit-for-bit (the ROADMAP recurrent-ragged item)."""
    cfg, params = {"hybrid": hybrid_llm, "xlstm": xlstm_hybrid_llm}[family]
    prompts, lengths = _ragged_batch(seed=11)
    paged = family == "hybrid"

    _, cache = lm.prefill(params, cfg, jnp.asarray(prompts),
                          _mk_cache(cfg, paged),
                          lengths=jnp.asarray(lengths, jnp.int32))

    for row in range(B):
        solo = lm.init_cache(cfg, 1, MAX_LEN, dtype=jnp.float32,
                             paged=paged, page_size=PS)
        if paged:
            solo = lm.set_block_tables(
                solo, attention.default_block_tables(1, MAX_LEN, PS))
        _, solo = lm.prefill(
            params, cfg, jnp.asarray(prompts[row:row + 1]), solo,
            lengths=jnp.asarray(lengths[row:row + 1], jnp.int32))
        # Compare recurrent-state leaves row-by-row (skip pools/tables,
        # whose page numbering differs between the batched and solo runs).
        from repro.models import cache as cache_mod
        batched_layers = dict(
            (path, layer) for path, layout, layer
            in cache_mod.iter_layers(cache) if layout == "state")
        for path, layout, s_layer in cache_mod.iter_layers(solo):
            if layout != "state":
                continue
            b_layer = batched_layers[path]
            for name in s_layer:
                sl, bl = np.asarray(s_layer[name]), np.asarray(b_layer[name])
                # Group layers stack [G, B, ...]; solo runs carry B == 1.
                np.testing.assert_allclose(bl[:, row], sl[:, 0],
                                           rtol=1e-6, atol=1e-6)


def test_recurrent_zero_length_rows_keep_state_bitwise(hybrid_llm):
    cfg, params = hybrid_llm
    prompts, lengths = _ragged_batch(seed=13)
    cache = _mk_cache(cfg, True)
    _, cache = lm.prefill(params, cfg, jnp.asarray(prompts), cache,
                          lengths=jnp.asarray([6, 0, 0], jnp.int32))
    from repro.models import cache as cache_mod
    before = {path: {k: np.asarray(v).copy() for k, v in layer.items()}
              for path, layout, layer in cache_mod.iter_layers(cache)
              if layout == "state"}
    _, cache = lm.prefill(params, cfg, jnp.asarray(prompts), cache,
                          lengths=jnp.asarray([0, 8, 0], jnp.int32))
    for path, layout, layer in cache_mod.iter_layers(cache):
        if layout != "state":
            continue
        for name, v in layer.items():
            v = np.asarray(v)
            old = before[path][name]
            # rows 0 and 2 were zero-length this prefill: bit-identical.
            for row in (0, 2):
                if v.ndim >= 2 and v.shape[1] == B:
                    np.testing.assert_array_equal(v[:, row], old[:, row])
                else:
                    np.testing.assert_array_equal(v[row], old[row])


# ---------------------------------------------------------------------------
# Scheduler: chunked admission end to end
# ---------------------------------------------------------------------------

def _mk_requests(rng, spec):
    return [Request(rid=i,
                    prompt=[int(t) for t in rng.integers(2, 100, n)],
                    max_new_tokens=m)
            for i, (n, m) in enumerate(spec)]


SPEC = [(5, 6), (9, 4), (13, 8), (7, 5), (4, 3), (11, 7)]


@pytest.mark.parametrize("family", ["mha", "hybrid"])
def test_scheduler_token_streams_equal_across_chunk_sizes(family, mha_llm,
                                                          hybrid_llm):
    cfg, params = {"mha": mha_llm, "hybrid": hybrid_llm}[family]
    outs = {}
    for chunk in CHUNK_SIZES:
        rng = np.random.default_rng(21)
        eng = ContinuousBatchingEngine(cfg, params, batch=2, max_len=32,
                                       paged=True, page_size=PS,
                                       chunk_size=chunk)
        outs[chunk] = [tuple(r.tokens)
                       for r in eng.run(_mk_requests(rng, SPEC))]
        assert eng.stats["completed"] == len(SPEC)
        assert eng.stats["decode_stall_steps"] == 0
    # Stalled whole-prompt admission (the old bucketed behaviour) emits the
    # same greedy streams — chunking changes scheduling, never tokens.
    rng = np.random.default_rng(21)
    eng = ContinuousBatchingEngine(cfg, params, batch=2, max_len=32,
                                   paged=True, page_size=PS,
                                   prefill_interleave=False)
    stalled = [tuple(r.tokens) for r in eng.run(_mk_requests(rng, SPEC))]
    assert eng.stats["decode_stall_steps"] > 0, \
        "stalled baseline must actually stall a decoding lane"
    for chunk in CHUNK_SIZES:
        assert outs[chunk] == outs[CHUNK_SIZES[0]], chunk
    assert stalled == outs[CHUNK_SIZES[0]]


def test_scheduler_serves_windowed_local_layers(mha_llm):
    """Sliding-window (local) layers over an unbounded dense cache ride the
    mixed step; only the ring layout is excluded (clear error)."""
    cfg, params = mha_llm
    wcfg = cfg.replace(block_pattern=("attn", "local"), num_layers=4,
                       window=8)
    wparams = _f32(lm.init(jax.random.PRNGKey(7), wcfg))
    rng = np.random.default_rng(61)
    eng = ContinuousBatchingEngine(wcfg, wparams, batch=2, max_len=32,
                                   paged=True, page_size=PS, chunk_size=4)
    reqs = eng.run(_mk_requests(rng, SPEC[:4]))
    assert eng.stats["completed"] == 4
    # Chunked == stalled greedy streams on the windowed pattern too.
    rng = np.random.default_rng(61)
    eng2 = ContinuousBatchingEngine(wcfg, wparams, batch=2, max_len=32,
                                    paged=True, page_size=PS,
                                    prefill_interleave=False)
    wants = eng2.run(_mk_requests(rng, SPEC[:4]))
    assert [r.tokens for r in reqs] == [w.tokens for w in wants]

    ring_cfg = wcfg.replace(ring_local_cache=True)
    ring_params = _f32(lm.init(jax.random.PRNGKey(7), ring_cfg))
    ring = ContinuousBatchingEngine(ring_cfg, ring_params, batch=2,
                                    max_len=32, paged=True, page_size=PS)
    ring.submit(Request(0, [3, 4, 5], 2))
    with pytest.raises(NotImplementedError, match="ring local cache"):
        ring.step()


def test_scheduler_dense_mode_agrees_with_paged(mha_llm):
    cfg, params = mha_llm
    outs = {}
    for paged in (True, False):
        rng = np.random.default_rng(23)
        eng = ContinuousBatchingEngine(cfg, params, batch=2, max_len=32,
                                       paged=paged, page_size=PS,
                                       chunk_size=PS)
        outs[paged] = [tuple(r.tokens)
                       for r in eng.run(_mk_requests(rng, SPEC))]
    assert outs[True] == outs[False]


def test_scheduler_recurrent_state_reset_on_row_reuse(hybrid_llm):
    """A freed row's recurrent state must not leak into the next request:
    back-to-back requests on one row match fresh-engine solo runs."""
    cfg, params = hybrid_llm
    rng = np.random.default_rng(31)
    reqs = _mk_requests(rng, [(6, 4), (9, 5), (5, 3)])
    eng = ContinuousBatchingEngine(cfg, params, batch=1, max_len=32,
                                   paged=True, page_size=PS, chunk_size=PS)
    eng.run(reqs)
    assert eng.stats["completed"] == 3
    rng = np.random.default_rng(31)
    for want in _mk_requests(rng, [(6, 4), (9, 5), (5, 3)]):
        solo = ContinuousBatchingEngine(cfg, params, batch=1, max_len=32,
                                        paged=True, page_size=PS,
                                        chunk_size=PS)
        solo.run([want])
        assert reqs[want.rid].tokens == want.tokens, want.rid


def test_token_budget_caps_spend_and_counts_stalls(mha_llm):
    """A starved token budget idles decode lanes — progress stays correct,
    and the starved lanes are counted."""
    cfg, params = mha_llm
    rng = np.random.default_rng(41)
    reqs = _mk_requests(rng, [(2, 12), (2, 12)])
    eng = ContinuousBatchingEngine(cfg, params, batch=2, max_len=32,
                                   paged=True, page_size=PS, chunk_size=PS,
                                   token_budget=2)
    for r in reqs:
        eng.submit(r)
    while eng.step():
        if all(r is not None and not r.admitting for r in eng.rows):
            break
    # Both rows decoding: shrink the budget below the decode demand (the
    # adaptive-controller hook) — one lane must stall per step now.
    eng.token_budget = 1
    while eng.step():
        pass
    assert eng.stats["completed"] == 2
    assert eng.stats["decode_stall_steps"] > 0
    assert eng.stats["stalled_lane_steps"] > 0
    rng = np.random.default_rng(41)
    free = ContinuousBatchingEngine(cfg, params, batch=2, max_len=32,
                                    paged=True, page_size=PS, chunk_size=PS)
    wants = free.run(_mk_requests(rng, [(2, 12), (2, 12)]))
    assert [r.tokens for r in reqs] == [w.tokens for w in wants]


def test_mid_admission_decode_does_not_stall(mha_llm):
    """While one row streams a long prompt in chunks, the other row emits a
    token EVERY step — the coordination stall the mixed step removes."""
    cfg, params = mha_llm
    rng = np.random.default_rng(43)
    long_p = [int(t) for t in rng.integers(2, 100, 24)]
    eng = ContinuousBatchingEngine(cfg, params, batch=2, max_len=64,
                                   paged=True, page_size=PS, chunk_size=4)
    a = Request(0, [int(t) for t in rng.integers(2, 100, 4)], 20)
    eng.submit(a)
    for _ in range(3):
        eng.step()                    # row 0 admitted and decoding
    n0 = len(a.tokens)
    b = Request(1, long_p, 2)
    eng.submit(b)
    admit_steps = -(-len(long_p) // 4)
    for _ in range(admit_steps):
        eng.step()
    # Row 0 gained one token per step throughout row 1's 6-step admission.
    assert len(a.tokens) == n0 + admit_steps
    assert eng.stats["decode_stall_steps"] == 0
    while eng.step():
        pass
    assert eng.stats["completed"] == 2


# ---------------------------------------------------------------------------
# Satellite: LRU preemption of COW/prefix-shared rows
# ---------------------------------------------------------------------------

def test_lru_preemption_of_prefix_shared_row(mha_llm):
    """Preempting a row whose pages are prefix-shared must drop only ITS
    references (no double-free), and its re-admission must re-share the
    pages still pinned by the surviving sharer."""
    cfg, params = mha_llm
    rng = np.random.default_rng(51)
    prompt = [int(t) for t in rng.integers(2, 100, 16)]   # 2 full pages
    # Two sharers + generation growth against a pool too small for both
    # full horizons: 2 shared prompt pages + 2×2 private generation pages
    # exceeds 5 pages, forcing a preemption mid-decode.
    reqs = [Request(rid=i, prompt=list(prompt), max_new_tokens=14)
            for i in range(2)]
    eng = ContinuousBatchingEngine(cfg, params, batch=2, max_len=32,
                                   paged=True, page_size=8, num_pages=5,
                                   prefix_sharing=True, chunk_size=8)
    eng.run(list(reqs))
    assert eng.stats["completed"] == 2
    assert eng.stats["preemptions"] >= 1
    assert eng.stats["shared_pages"] > 0
    assert all(len(r.tokens) == 14 for r in reqs)
    # No pages leaked, no double-frees raised along the way.
    assert eng.allocator.available == 5
    # Greedy streams match the unshared run bit-for-bit.
    rng = np.random.default_rng(51)
    plain = [Request(rid=i, prompt=list(prompt), max_new_tokens=14)
             for i in range(2)]
    eng2 = ContinuousBatchingEngine(cfg, params, batch=2, max_len=32,
                                    paged=True, page_size=8, num_pages=5,
                                    prefix_sharing=False, chunk_size=8)
    eng2.run(plain)
    assert [r.tokens for r in reqs] == [p.tokens for p in plain]


def test_preemption_victim_readmission_reshares(mha_llm):
    """After its eviction, the victim's re-admission lookup finds the
    sharer's still-resident prompt pages and re-shares them.  Admission is
    chunk-granular, so the clone shares the FIRST chunk's page at bind time
    and the second prompt page at growth time (growth-time re-share)."""
    cfg, params = mha_llm
    rng = np.random.default_rng(53)
    prompt = [int(t) for t in rng.integers(2, 100, 16)]
    reqs = [Request(rid=i, prompt=list(prompt), max_new_tokens=14)
            for i in range(2)]
    eng = ContinuousBatchingEngine(cfg, params, batch=2, max_len=32,
                                   paged=True, page_size=8, num_pages=5,
                                   prefix_sharing=True, chunk_size=8)
    for r in reqs:
        eng.submit(r)
    eng.admit()
    assert eng.stats["shared_pages"] >= 1  # first-chunk page shared at bind
    eng.step()                             # chunk 1 lands
    eng.step()                             # chunk 2: clone re-shares page 2
    shared_mid = eng.stats["shared_pages"]
    assert shared_mid >= 2
    assert reqs[1].pages[:2] == reqs[0].pages[:2]
    while eng.step():
        pass
    # The preempted request was re-admitted via the prefix cache: total
    # shared-page count grew beyond the in-flight clone share.
    assert eng.stats["preemptions"] >= 1
    assert eng.stats["shared_pages"] > shared_mid
    assert eng.allocator.available == 5


# ---------------------------------------------------------------------------
# Satellite: bucket_len clamps to max_len before raising
# ---------------------------------------------------------------------------

def test_bucket_len_clamps_to_max_len_before_raising():
    from repro.serving.engine import bucket_len
    # Boundary: longer than the largest bucket but within max_len — clamp.
    assert bucket_len(2000, max_len=4096) == 4096
    assert bucket_len(1025, max_len=2048) == 2048
    # Within a bucket: clamp the bucket, not the prompt.
    assert bucket_len(9, max_len=12) == 12
    assert bucket_len(9, max_len=64) == 16
    # Genuinely does not fit: still raises.
    with pytest.raises(ValueError, match="max_len"):
        bucket_len(2000, max_len=1500)
    with pytest.raises(ValueError, match="largest bucket"):
        bucket_len(2000)


def test_mixed_width_buckets():
    assert engine_mod.mixed_width_buckets(1) == (1,)
    assert engine_mod.mixed_width_buckets(8) == (1, 2, 4, 8)
    assert engine_mod.mixed_width_buckets(12) == (1, 2, 4, 8, 12)
    assert engine_mod.width_bucket(3, 8) == 4
    assert engine_mod.width_bucket(9, 8) == 8
    assert engine_mod.width_bucket(0, 8) == 1


# ---------------------------------------------------------------------------
# Satellite: cross-mode stats invariants (interleaved vs stalled admission)
# ---------------------------------------------------------------------------

def test_cross_mode_counter_invariants(mha_llm):
    """On an identical workload, the two admission modes must agree on every
    work-conservation counter — same tokens prefilled, same tokens
    generated, same completions — and differ exactly where they schedule:
    interleaved admission never stalls a decode lane
    (``decode_stall_steps == 0``) while the stalled baseline must, and its
    stalls are bounded by its own prefill-chunk count (a lane can only
    stall on steps that run a prompt chunk)."""
    cfg, params = mha_llm
    stats = {}
    toks = {}
    for interleave in (True, False):
        rng = np.random.default_rng(71)
        eng = ContinuousBatchingEngine(cfg, params, batch=2, max_len=32,
                                       paged=True, page_size=PS,
                                       chunk_size=PS,
                                       prefill_interleave=interleave)
        toks[interleave] = [tuple(r.tokens)
                            for r in eng.run(_mk_requests(rng, SPEC))]
        stats[interleave] = dict(eng.stats)
    inter, stall = stats[True], stats[False]
    # Work conservation: identical totals in both modes.
    for key in ("admitted", "completed", "prefill_tokens", "gen_tokens"):
        assert inter[key] == stall[key], key
    assert inter["prefill_tokens"] == sum(n for n, _ in SPEC)
    assert inter["gen_tokens"] == sum(m for _, m in SPEC)
    # Chunk accounting: every admission carries at least one chunk, and
    # interleaved (chunk_size-bounded) admission can only split prompts
    # more finely than the stalled whole-prompt baseline — never coarser.
    for s in (inter, stall):
        assert s["prefill_chunks"] >= s["admitted"]
        assert s["prefills"] <= s["prefill_chunks"]
    assert inter["prefill_chunks"] >= stall["prefill_chunks"]
    # Scheduling difference: interleaving is exactly the removal of stalls.
    assert inter["decode_stall_steps"] == 0
    assert inter["stalled_lane_steps"] == 0
    assert stall["decode_stall_steps"] > 0
    assert stall["stalled_lane_steps"] >= stall["decode_stall_steps"]
    # A lane only stalls on a step that carried someone else's chunk.
    assert stall["decode_stall_steps"] <= stall["prefills"]
    # Scheduling never changes tokens.
    assert toks[True] == toks[False]

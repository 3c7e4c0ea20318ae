"""Paged KV cache + continuous batching: oracle equivalence, admission,
page accounting, ring prefill, and the fused-step sampling path.

The paged-vs-dense pipeline tests run in float32 so the two cache layouts
are comparable at tight tolerance (bf16 cross-path rounding would otherwise
amplify through layers); greedy token streams must match exactly either way.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.models import attention, lm
from repro.serving.scheduler import (ContinuousBatchingEngine, PageAllocator,
                                     Request, bucket_len)

B, MAX_LEN, PS = 3, 32, 8


def _f32(params):
    return jax.tree.map(lambda x: x.astype(jnp.float32), params)


@pytest.fixture(scope="module")
def llm():
    cfg = configs.reduced(configs.get("olmo-1b"), d_model=32, vocab=128)
    return cfg, _f32(lm.init(jax.random.PRNGKey(0), cfg))


@pytest.fixture(scope="module")
def ring_llm():
    """Mixed full-attention + ring-window local pattern."""
    cfg = configs.reduced(configs.get("olmo-1b"), d_model=32, vocab=128)
    cfg = cfg.replace(block_pattern=("attn", "local"), num_layers=4,
                      window=16, ring_local_cache=True)
    return cfg, _f32(lm.init(jax.random.PRNGKey(1), cfg))


def _paged_cache(cfg, batch=B, max_len=MAX_LEN, ps=PS):
    cache = lm.init_cache(cfg, batch, max_len, dtype=jnp.float32,
                          paged=True, page_size=ps)
    return lm.set_block_tables(
        cache, attention.default_block_tables(batch, max_len, ps))


def _run_pipeline(cfg, params, cache, prompts, lengths, steps, impl="ref"):
    logits, cache = lm.prefill(params, cfg, prompts, cache, impl=impl,
                               lengths=lengths)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    pos = lengths if lengths is not None else jnp.full(
        (prompts.shape[0],), prompts.shape[1], jnp.int32)
    out = [np.asarray(tok)]
    for _ in range(steps):
        logits, cache = lm.decode_step(params, cfg, tok, cache, pos,
                                       impl=impl)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        pos = pos + 1
        out.append(np.asarray(tok))
    return np.stack(out, 1), logits, cache


# ---------------------------------------------------------------------------
# Paged == dense oracle (prefill -> decode, ragged lengths, ring configs)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_paged_matches_dense_ragged_pipeline(llm, impl):
    cfg, params = llm
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(2, 100, (B, 8)), jnp.int32)
    lengths = jnp.asarray([8, 3, 5], jnp.int32)

    dense = lm.init_cache(cfg, B, MAX_LEN, dtype=jnp.float32)
    toks_d, logits_d, _ = _run_pipeline(cfg, params, dense, prompts,
                                        lengths, steps=10)
    toks_p, logits_p, _ = _run_pipeline(cfg, params, _paged_cache(cfg),
                                        prompts, lengths, steps=10,
                                        impl=impl)
    np.testing.assert_array_equal(toks_d, toks_p)
    tol = dict(rtol=1e-4, atol=1e-4) if impl == "ref" else dict(
        rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(logits_d), np.asarray(logits_p),
                               **tol)


def test_paged_matches_dense_ring_window_config(ring_llm):
    """Mixed pattern: attn layers paged, local layers keep their ring cache
    (bounded by the window already) — still bit-compatible with dense."""
    cfg, params = ring_llm
    rng = np.random.default_rng(1)
    prompts = jnp.asarray(rng.integers(2, 100, (B, 6)), jnp.int32)
    lengths = jnp.asarray([6, 2, 4], jnp.int32)

    dense = lm.init_cache(cfg, B, MAX_LEN, dtype=jnp.float32)
    toks_d, _, _ = _run_pipeline(cfg, params, dense, prompts, lengths,
                                 steps=12)
    paged = _paged_cache(cfg)
    toks_p, _, cache_p = _run_pipeline(cfg, params, paged, prompts, lengths,
                                       steps=12)
    np.testing.assert_array_equal(toks_d, toks_p)
    # The local layer's cache really is a ring (window-sized), not paged.
    local = cache_p["groups"]["1"]
    assert "k" in local and local["k"].shape[-2] == cfg.window
    assert "k_pages" in cache_p["groups"]["0"]


def test_ragged_prefill_preserves_untouched_rows(llm):
    """lengths[b] == 0 rows keep cache bit-for-bit (admission isolation)."""
    cfg, params = llm
    rng = np.random.default_rng(2)
    prompts = jnp.asarray(rng.integers(2, 100, (B, 8)), jnp.int32)

    # Dense: row 0's [G, Hkv, S, D] slice untouched by row 1's prefill.
    dense = lm.init_cache(cfg, B, MAX_LEN, dtype=jnp.float32)
    _, dense = lm.prefill(params, cfg, prompts, dense,
                          lengths=jnp.asarray([6, 0, 0], jnp.int32))
    row0 = np.asarray(dense["groups"]["0"]["k"][:, 0]).copy()
    _, dense = lm.prefill(params, cfg, prompts, dense,
                          lengths=jnp.asarray([0, 8, 0], jnp.int32))
    np.testing.assert_array_equal(row0,
                                  np.asarray(dense["groups"]["0"]["k"][:, 0]))

    # Paged: every page EXCEPT row 1's must be untouched by row 1's prefill
    # (this includes the pool's last page — a -1 "drop" that wrapped under
    # jnp scatter semantics would corrupt it).
    paged = _paged_cache(cfg)
    _, paged = lm.prefill(params, cfg, prompts, paged,
                          lengths=jnp.asarray([6, 0, 0], jnp.int32))
    bt = np.asarray(lm.get_block_tables(paged))
    pool_before = np.asarray(paged["groups"]["0"]["k_pages"]).copy()
    _, paged = lm.prefill(params, cfg, prompts, paged,
                          lengths=jnp.asarray([0, 8, 0], jnp.int32))
    pool_after = np.asarray(paged["groups"]["0"]["k_pages"])
    others = [p for p in range(pool_before.shape[1])
              if p not in set(bt[1].tolist())]
    np.testing.assert_array_equal(pool_before[:, others],
                                  pool_after[:, others])


# ---------------------------------------------------------------------------
# Ring-cache prefill gather path (prompt longer than the ring)
# ---------------------------------------------------------------------------

def test_ring_prefill_gather_matches_decode_fill():
    """attention.prefill with t > S (ring) must leave the same cache as
    feeding the tokens through decode_step one at a time."""
    cfg = configs.reduced(configs.get("olmo-1b"), d_model=32,
                          vocab=128).replace(window=4)
    key = jax.random.PRNGKey(3)
    p = jax.tree.map(lambda x: x.astype(jnp.float32),
                     attention.init(key, cfg))
    t, s = 10, 4
    x = jnp.asarray(np.random.default_rng(3).normal(size=(1, t, cfg.d_model)),
                    jnp.float32)

    ring = attention.init_cache(cfg, 1, s, dtype=jnp.float32)
    mask = jnp.ones((t, t), bool) & (jnp.arange(t)[None, :]
                                     <= jnp.arange(t)[:, None])
    _, ring = attention.prefill(p, cfg, x, ring, mask, jnp.arange(t))

    step = attention.init_cache(cfg, 1, s, dtype=jnp.float32)
    for i in range(t):
        _, step = attention.decode_step(p, cfg, x[:, i:i + 1], step,
                                        jnp.asarray([i], jnp.int32))
    np.testing.assert_allclose(np.asarray(ring["k"]), np.asarray(step["k"]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ring["v"]), np.asarray(step["v"]),
                               rtol=1e-5, atol=1e-5)


def test_ragged_into_short_ring_raises():
    cfg = configs.reduced(configs.get("olmo-1b"), d_model=32, vocab=128)
    p = attention.init(jax.random.PRNGKey(0), cfg)
    cache = attention.init_cache(cfg, 2, 4)          # ring shorter than t
    x = jnp.zeros((2, 8, cfg.d_model), jnp.bfloat16)
    with pytest.raises(NotImplementedError, match="ragged prefill"):
        attention.prefill(p, cfg, x, cache, None, jnp.arange(8),
                          lengths=jnp.asarray([8, 2], jnp.int32))


# ---------------------------------------------------------------------------
# Continuous batching: admission, completion, page reuse
# ---------------------------------------------------------------------------

def _mk_requests(rng, spec):
    return [Request(rid=i,
                    prompt=[int(t) for t in rng.integers(2, 100, n)],
                    max_new_tokens=m)
            for i, (n, m) in enumerate(spec)]


def test_scheduler_paged_dense_solo_agree(llm):
    """Batched serving emits each request's solo stream."""
    cfg, params = llm
    spec = [(5, 6), (9, 4), (3, 8), (7, 5), (4, 3)]
    rng = np.random.default_rng(7)
    eng = ContinuousBatchingEngine(cfg, params, batch=2, max_len=32,
                                   page_size=8)
    batched = eng.run(_mk_requests(rng, spec))
    assert eng.stats["completed"] == len(spec)
    rng = np.random.default_rng(7)
    solo_reqs = _mk_requests(rng, spec)
    for r in solo_reqs:
        solo = ContinuousBatchingEngine(cfg, params, batch=1, max_len=32,
                                        page_size=8)
        solo.run([r])
    for got, want in zip(batched, solo_reqs):
        assert got.tokens == want.tokens, got.rid


def test_mid_flight_admission_reuses_pages_without_disturbing_rows(llm):
    """A finished row's pages are reallocated to the next request while the
    other row keeps decoding — its output must be unchanged vs a run with
    no admission at all."""
    cfg, params = llm
    rng = np.random.default_rng(9)
    long_req = Request(0, [int(t) for t in rng.integers(2, 100, 6)], 12)
    short_req = Request(1, [int(t) for t in rng.integers(2, 100, 4)], 2)
    late_req = Request(2, [int(t) for t in rng.integers(2, 100, 5)], 3)

    def clone(r):
        return Request(r.rid, list(r.prompt), r.max_new_tokens)

    eng3 = ContinuousBatchingEngine(cfg, params, batch=2, max_len=32,
                                    paged=True, page_size=8, num_pages=6)
    r3 = eng3.run([clone(long_req), clone(short_req), clone(late_req)])
    assert r3[2].admitted_step > 0, "late request must be admitted mid-flight"
    assert set(r3[2].pages) & set(r3[1].pages), \
        "freed pages were not reused by the admitted request"

    eng2 = ContinuousBatchingEngine(cfg, params, batch=2, max_len=32,
                                    paged=True, page_size=8, num_pages=6)
    r2 = eng2.run([clone(long_req), clone(short_req)])
    assert r3[0].tokens == r2[0].tokens, \
        "mid-flight admission perturbed an in-flight row"
    assert eng3.allocator.available == 6, "page leak"


def test_page_allocator_exhaustion_and_reuse():
    alloc = PageAllocator(4)
    assert alloc.alloc(0) == [] and alloc.available == 4   # [:-0] trap
    a = alloc.alloc(3)
    assert alloc.alloc(2) is None and alloc.available == 1
    alloc.free(a)
    assert sorted(alloc.alloc(4)) == sorted(a + [3])


def test_bucket_len():
    assert bucket_len(1) == 8 and bucket_len(8) == 8 and bucket_len(9) == 16
    with pytest.raises(ValueError):
        bucket_len(10_000)


def test_scheduler_requires_fitting_requests(llm):
    cfg, params = llm
    eng = ContinuousBatchingEngine(cfg, params, batch=1, max_len=16,
                                   paged=True, page_size=8)
    with pytest.raises(ValueError, match="max_len"):
        eng.submit(Request(0, [3] * 20, 8))
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(Request(1, [3, 4], 0))
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(Request(2, [], 4))


# ---------------------------------------------------------------------------
# Engine paged mode + orchestrator wiring + fused-step sampling
# ---------------------------------------------------------------------------

def test_engine_paged_generate_matches_dense(llm):
    from repro.serving.engine import Engine
    cfg, params = llm
    prompts = jnp.asarray([[5, 6, 7, 8], [9, 10, 11, 12]], jnp.int32)
    dense = Engine(cfg, params, batch=2, max_len=32)
    paged = Engine(cfg, params, batch=2, max_len=32, paged=True, page_size=8)
    np.testing.assert_array_equal(
        np.asarray(dense.generate(prompts, steps=6)),
        np.asarray(paged.generate(prompts, steps=6)))


def test_engine_paged_raises_when_full(llm):
    """Pages do not ring-wrap: running past max_len must fail loudly."""
    from repro.serving.engine import Engine
    cfg, params = llm
    eng = Engine(cfg, params, batch=1, max_len=8, paged=True, page_size=8)
    eng.prefill(jnp.asarray([[5, 6, 7, 8]], jnp.int32))
    with pytest.raises(ValueError, match="paged cache is full"):
        for _ in range(10):
            eng.step()


def test_orchestrator_paged_ragged_converges():
    from repro.agents.orchestrator import make_sim_llm, run_task
    from repro.agents.tasks import TASKS
    cfg, params = make_sim_llm()
    r = run_task(cfg, params, TASKS["tic_tac_toe"], mode="parallel",
                 n_agents=3, seed=1, kv="paged", prefill="ragged")
    assert r.converged and r.gen_tokens > 0
    assert r.kv_mode == "paged" and r.prefill_mode == "ragged"
    # Ragged prefill folds each prompt into one step: far fewer engine steps
    # than replay mode, which pays one decode step per replayed token.
    replay = run_task(cfg, params, TASKS["tic_tac_toe"], mode="parallel",
                      n_agents=3, seed=1)
    assert r.steps < replay.steps


def test_fused_serve_step_temperature_sampling(llm):
    from jax.sharding import Mesh
    from repro.core import doc as doc_mod, gset
    from repro.serving import engine as engine_mod
    cfg, params = llm
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    coord = engine_mod.replicate_coord(
        {"doc": doc_mod.empty(4, 16), "heartbeats": gset.GCounter.zeros(1)},
        1)
    step = engine_mod.make_fused_serve_step(cfg, mesh, ("data",),
                                            temperature=1.0)
    cache = lm.init_cache(cfg, 4, 16)
    token = jnp.asarray([3, 3, 3, 3], jnp.int32)
    pos = jnp.zeros((4,), jnp.int32)
    slots = jnp.arange(4, dtype=jnp.int32)
    active = jnp.ones((4,), bool)
    seen = set()
    key = jax.random.PRNGKey(0)
    with mesh:
        for t in range(5):
            key, sub = jax.random.split(key)
            token, cache, pos, coord = step(params, cache, token, pos,
                                            slots, active, coord,
                                            jnp.int32(t), sub)
            seen.update(np.asarray(token).tolist())
    assert len(seen) > 1, "temperature sampling had no effect in fused step"


# ---------------------------------------------------------------------------
# Reservation path, incremental growth, preemption, COW prefix sharing
# ---------------------------------------------------------------------------

def test_allocator_reservation_prevents_double_admission():
    """Two candidates checked against one availability snapshot must not
    both pass: reserve() removes pages from the free list immediately."""
    alloc = PageAllocator(4)
    assert alloc.available == 4
    res_a = alloc.reserve(3)
    assert res_a is not None and alloc.available == 1
    # Candidate B sees the truth: its 3-page ask fails even though A has
    # not been committed/prefilled yet (the double-admission race).
    assert alloc.reserve(3) is None
    pages_a = res_a.take()
    assert len(pages_a) == 3
    res_c = alloc.reserve(1)
    assert res_c is not None and alloc.available == 0
    res_c.release()
    assert alloc.available == 1
    alloc.free(pages_a)
    assert alloc.available == 4


def test_allocator_refcounts_share_and_free():
    alloc = PageAllocator(2)
    (p,) = alloc.alloc(1)
    gen0 = alloc.generation(p)
    alloc.share([p])
    assert alloc.refcount(p) == 2
    alloc.free([p])
    assert alloc.refcount(p) == 1 and alloc.available == 1
    alloc.free([p])
    assert alloc.available == 2
    with pytest.raises(ValueError):
        alloc.free([p])
    (p2,) = alloc.alloc(1)
    if p2 == p:
        assert alloc.generation(p) == gen0 + 1   # reuse is detectable


def test_admission_is_two_phase_under_page_pressure(llm):
    """With pages for only one of two head-of-queue requests, exactly one
    is admitted per round — never both against the same snapshot."""
    cfg, params = llm
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, prompt=[int(t) for t in rng.integers(2, 100, 14)],
                    max_new_tokens=2) for i in range(2)]
    eng = ContinuousBatchingEngine(cfg, params, batch=2, max_len=32,
                                   paged=True, page_size=8, num_pages=3)
    for r in reqs:
        eng.submit(r)
    eng.admit()
    assert sum(r is not None for r in eng.rows) == 1
    assert eng.allocator.available == 1        # 2 pages reserved+taken


def test_incremental_growth_allocates_on_boundary_crossing(llm):
    """Admission allocates only the prompt's pages; generation pages appear
    as decode crosses page boundaries (no whole-request up-front alloc)."""
    cfg, params = llm
    req = Request(0, [3] * 6, 18)              # 6 + 18 = 24 slots = 3 pages
    eng = ContinuousBatchingEngine(cfg, params, batch=1, max_len=32,
                                   paged=True, page_size=8)
    eng.submit(req)
    eng.admit()
    assert len(req.pages) == 1                 # ceil(6/8): prompt only
    while eng.step():
        pass
    assert eng.stats["grown_pages"] == 2       # pages 2 and 3 on crossing
    assert len(req.tokens) == 18
    assert eng.allocator.available == eng.allocator.num_pages


def test_lru_preemption_recomputes_and_completes(llm):
    """Pool too small for both rows' full horizons: the least-recently
    allocating row is preempted (pages freed, request re-queued with its
    generated tokens) and everything still completes without leaks."""
    cfg, params = llm
    rng = np.random.default_rng(5)
    reqs = [Request(rid=i, prompt=[int(t) for t in rng.integers(2, 100, 6)],
                    max_new_tokens=12) for i in range(2)]
    # Each needs ceil((6+12)/8) = 3 pages at peak; pool of 4 forces a
    # preemption when both try to grow.
    eng = ContinuousBatchingEngine(cfg, params, batch=2, max_len=32,
                                   paged=True, page_size=8, num_pages=4)
    eng.run(list(reqs))
    assert eng.stats["completed"] == 2
    assert eng.stats["preemptions"] >= 1
    assert all(len(r.tokens) == 12 for r in reqs)
    assert eng.allocator.available == 4, "page leak after preemption"


def test_prefix_sharing_cow_matches_unshared_tokens(llm):
    """Fan-out from one prompt: shared admission + COW must produce exactly
    the tokens of the non-shared run, with strictly fewer resident pages."""
    cfg, params = llm
    rng = np.random.default_rng(11)
    prompt = [int(t) for t in rng.integers(2, 100, 13)]   # 1 full + partial
    fanout = 4

    def run(share):
        reqs = [Request(rid=i, prompt=list(prompt), max_new_tokens=6)
                for i in range(fanout)]
        eng = ContinuousBatchingEngine(cfg, params, batch=fanout,
                                       max_len=32, paged=True, page_size=8,
                                       prefix_sharing=share)
        eng.run(reqs)
        assert eng.stats["completed"] == fanout
        assert eng.allocator.available == eng.allocator.num_pages
        return reqs, eng

    plain, eng_plain = run(False)
    shared, eng_shared = run(True)
    for a, b in zip(plain, shared):
        assert a.tokens == b.tokens, a.rid
    assert eng_shared.stats["shared_pages"] > 0
    assert eng_shared.stats["cow_copies"] > 0
    assert (eng_shared.stats["peak_pages"]
            < eng_plain.stats["peak_pages"]), "sharing saved no pages"


def test_prefix_share_resident_mb_below_unshared_at_fanout_4(llm):
    """Four agents forked from one 21-token prompt pin fewer resident KV
    bytes at their peak with copy-on-write prefix sharing than without."""
    cfg, params = llm
    prompt = [int(t) for t in
              np.random.default_rng(0).integers(2, cfg.vocab_size, 21)]
    peak, stats = {}, {}
    for share in (False, True):
        eng = ContinuousBatchingEngine(cfg, params, batch=4, max_len=64,
                                       page_size=8, prefix_sharing=share)
        for i in range(4):
            eng.submit(Request(rid=i, prompt=list(prompt), max_new_tokens=4))
        peak[share] = 0
        while True:
            more = eng.step()
            peak[share] = max(peak[share], eng.resident_cache_bytes())
            if not more:
                break
        stats[share] = eng.stats
    assert peak[True] < peak[False]
    assert stats[True]["shared_pages"] > 0
    assert stats[True]["completed"] == stats[False]["completed"] == 4


def test_zero_page_admission_fully_covered_by_shared_prefix(llm):
    """A clone whose prompt pages are all shared needs ZERO fresh pages at
    admission (reserve(0)) and still decodes correctly."""
    cfg, params = llm
    rng = np.random.default_rng(13)
    prompt = [int(t) for t in rng.integers(2, 100, 16)]   # exactly 2 pages
    reqs = [Request(rid=i, prompt=list(prompt), max_new_tokens=4)
            for i in range(2)]
    eng = ContinuousBatchingEngine(cfg, params, batch=2, max_len=32,
                                   paged=True, page_size=8,
                                   prefix_sharing=True)
    for r in reqs:
        eng.submit(r)
    eng.admit()
    # Clone shares both prompt pages: identical page lists, refcount 2.
    assert reqs[1].pages == reqs[0].pages
    assert all(eng.allocator.refcount(p) == 2 for p in reqs[1].pages)
    while eng.step():
        pass
    assert reqs[0].tokens == reqs[1].tokens
    assert eng.allocator.available == eng.allocator.num_pages


def test_zero_length_ragged_row_does_not_cow_shared_pages(llm):
    """Satellite: an admission prefill whose OTHER rows have length 0 must
    not touch pages still shared between live rows — no copy-on-write, no
    pool bytes moved outside the admitted row's pages."""
    cfg, params = llm
    rng = np.random.default_rng(17)
    prompt = [int(t) for t in rng.integers(2, 100, 13)]
    eng = ContinuousBatchingEngine(cfg, params, batch=3, max_len=32,
                                   paged=True, page_size=8,
                                   prefix_sharing=True)
    a = Request(0, list(prompt), 8)
    b = Request(1, list(prompt), 8)
    eng.submit(a)
    eng.submit(b)
    eng.admit()                                # rows 0,1 share prompt pages
    shared_pages = [p for p in a.pages if eng.allocator.refcount(p) > 1]
    assert shared_pages, "setup: prompt pages must be shared"
    cow_before = eng.stats["cow_copies"]
    pool_before = np.asarray(eng.cache["groups"]["0"]["k_pages"]).copy()

    # Admit a THIRD request with a different prompt into the free row: the
    # ragged prefill's other rows are zero-length, and rows 0/1's shared
    # pages must survive bit-for-bit with no COW triggered by admission.
    c = Request(2, [int(t) for t in rng.integers(2, 100, 5)], 2)
    eng.submit(c)
    eng.admit()
    assert eng.stats["cow_copies"] == cow_before
    pool_after = np.asarray(eng.cache["groups"]["0"]["k_pages"])
    np.testing.assert_array_equal(pool_before[:, shared_pages],
                                  pool_after[:, shared_pages])
    while eng.step():
        pass
    assert eng.stats["completed"] == 3
    assert eng.allocator.available == eng.allocator.num_pages


def test_freed_row_refill_under_prefix_sharing(llm):
    """A finished sharer's slot is refilled by a NEW clone while the other
    sharer still holds the prefix pages: the refill re-shares the live
    pages instead of copying them."""
    cfg, params = llm
    rng = np.random.default_rng(19)
    prompt = [int(t) for t in rng.integers(2, 100, 16)]   # 2 full pages
    long_r = Request(0, list(prompt), 12)
    short_r = Request(1, list(prompt), 2)
    late_r = Request(2, list(prompt), 3)
    eng = ContinuousBatchingEngine(cfg, params, batch=2, max_len=32,
                                   paged=True, page_size=8,
                                   prefix_sharing=True)
    eng.run([long_r, short_r, late_r])
    assert eng.stats["completed"] == 3
    assert late_r.admitted_step > 0
    # The late clone re-shared the prefix pages still pinned by long_r.
    assert late_r.pages[:2] == long_r.pages[:2]
    assert eng.allocator.available == eng.allocator.num_pages


def test_prefix_page_mapper_shares_header_across_recontextualization():
    """The orchestrator's mapper: identical full-page prefixes share pages
    across rows AND across one row's own re-contextualizations."""
    from repro.serving.scheduler import PrefixPageMapper
    ps, maxp = 8, 4
    mapper = PrefixPageMapper(2, maxp, ps, trash_page=99)
    header = list(range(100, 120))              # 20 tokens: 2 full pages

    shared0 = mapper.map_row(0, header, horizon=24)
    assert shared0 == 0
    row0 = list(mapper.host_bt[0, :3])

    # A second row with the same prompt shares the 2 full header pages.
    shared1 = mapper.map_row(1, list(header), horizon=24)
    assert shared1 == 2
    assert list(mapper.host_bt[1, :2]) == row0[:2]
    assert all(mapper.allocator.refcount(p) == 2 for p in row0[:2])

    # Row 0 re-contextualizes: same header, different tail — the header
    # pages survive the remap (self-share), the tail page is fresh.
    shared0b = mapper.map_row(0, header[:16] + [7, 8, 9], horizon=24)
    assert shared0b == 2
    assert list(mapper.host_bt[0, :2]) == row0[:2]

    # A different header shares nothing.
    assert mapper.map_row(1, list(range(200, 220)), horizon=24) == 0
    mapper.free_row(0)
    mapper.free_row(1)


def test_orchestrator_paged_sharing_stat():
    """Paged orchestrator with small pages reports shared prefix pages when
    invalidations force re-contextualization (dashboard has read edges)."""
    from repro.agents.orchestrator import make_sim_llm, run_task
    from repro.agents.tasks import TASKS
    cfg, params = make_sim_llm()
    r = run_task(cfg, params, TASKS["dashboard"], mode="parallel",
                 n_agents=3, seed=0, kv="paged", prefill="ragged",
                 page_size=8)
    assert r.converged and r.kv_mode == "paged"
    if r.invalidations > 0:
        assert r.shared_prefix_pages > 0, \
            "re-contextualization shared no header pages"

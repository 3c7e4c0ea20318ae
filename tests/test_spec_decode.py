"""Speculative decoding through the mixed step.

Acceptance bar (mirrors PR-4's chunked-prefill equivalence sweep):

* the verify step's per-row accept counts match the crafted drafts
  exactly (full / none / partial acceptance) on MHA, MLA, hybrid-rglru
  and xLSTM archs, paged and dense;
* rejected-tail cache slots are restored BITWISE to their pre-verify
  bytes (gather-by-position compare against a pre-step snapshot — raw
  pool compares are invalid across engines because allocation order
  differs), including recurrent state snapshots + committed-span replay;
* post-rollback continuation streams equal the never-drafted greedy
  reference — the token-identity guarantee (argmax is robust to the
  last-ulp reduction-width differences PR-4 documented for width-1
  matvecs, which is why the *byte* guarantee is scoped to the restored
  tail, not cross-width cache equality);
* the scheduler end-to-end: speculative streams equal non-speculative
  greedy across drafters (ngram / doc / adversarial) and chunk sizes,
  with zero page leaks — including COW prefix-shared rows (no
  double-free); on the simulated agent LLM both drafters accept drafts;
* the orchestrator: sequential agent trials are digest-identical off vs
  speculative (and doc-drafted trials take fewer steps), and uncoupled
  parallel trials too.

Everything runs in f32 interpret mode on CPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.kernels import ref as kref
from repro.models import attention, lm
from repro.models import cache as cache_mod
from repro.serving import draft as draft_mod
from repro.serving.scheduler import ContinuousBatchingEngine, Request

B, MAX_LEN, PS = 3, 32, 8
V = 128


def _f32(t):
    return jax.tree.map(lambda x: x.astype(jnp.float32), t)


def _mk(kind):
    if kind == "mha":
        cfg = configs.reduced(configs.get("olmo-1b"), d_model=32, vocab=V)
        return cfg.replace(num_layers=2)
    if kind == "mla":
        return configs.reduced(configs.get("deepseek-v2-lite-16b"),
                               d_model=32, vocab=V)
    if kind == "hybrid":
        cfg = configs.reduced(configs.get("olmo-1b"), d_model=32, vocab=V)
        return cfg.replace(block_pattern=("attn", "rglru"), num_layers=4)
    cfg = configs.reduced(configs.get("xlstm-125m"), d_model=32, vocab=V)
    return cfg.replace(block_pattern=("slstm", "mlstm", "attn"),
                       num_layers=3, d_ff=128)


@pytest.fixture(scope="module", params=["mha", "mla", "hybrid", "xlstm"])
def llm(request):
    cfg = _mk(request.param)
    return cfg, _f32(lm.init(jax.random.PRNGKey(0), cfg))


@pytest.fixture(scope="module")
def mha_llm():
    cfg = _mk("mha")
    return cfg, _f32(lm.init(jax.random.PRNGKey(0), cfg))


@pytest.fixture(scope="module")
def mla_llm():
    cfg = _mk("mla")
    return cfg, _f32(lm.init(jax.random.PRNGKey(0), cfg))


@pytest.fixture(scope="module")
def hybrid_llm():
    cfg = _mk("hybrid")
    return cfg, _f32(lm.init(jax.random.PRNGKey(0), cfg))


def _mk_cache(cfg, paged):
    cache = lm.init_cache(cfg, B, MAX_LEN, dtype=jnp.float32,
                          paged=paged, page_size=PS)
    if paged:
        cache = lm.set_block_tables(
            cache, attention.default_block_tables(B, MAX_LEN, PS))
    return cache


# ---------------------------------------------------------------------------
# Drafter units
# ---------------------------------------------------------------------------

def test_ngram_drafter_prompt_lookup():
    d = draft_mod.NgramDrafter(max_ngram=3)
    # [7 8 9] occurred earlier, followed by [4 5 6]; trailing context ends
    # in [7 8 9] -> propose the continuation.
    ctx = [1, 2, 7, 8, 9, 4, 5, 6, 7, 8, 9]
    assert d.propose(ctx, 3) == [4, 5, 6]
    assert d.propose(ctx, 2) == [4, 5]
    assert d.propose([1, 2, 3], 4) == []          # no earlier match
    assert d.propose([], 4) == []
    assert d.propose(ctx, 0) == []


def test_ngram_rightmost_longest_match_wins():
    d = draft_mod.NgramDrafter(max_ngram=3)
    # Trailing [5 1 2]: the trigram match (-> 9) beats bigram/unigram ones.
    ctx = [5, 1, 2, 9, 1, 2, 8, 5, 1, 2]
    assert d.propose(ctx, 1) == [9]


def test_doc_drafter_and_fallback():
    d = draft_mod.DocDrafter(max_ngram=3, min_ngram=2)
    d.set_docs([[1, 2, 3, 4, 5]])
    assert d.propose([9, 2, 3], 2) == [4, 5]      # doc continuation
    # No doc match, but own history repeats -> n-gram fallback kicks in.
    assert d.propose([7, 8, 6, 7, 8], 1) == [6]
    nofb = draft_mod.DocDrafter(fallback=False)
    nofb.set_docs([[1, 2, 3]])
    assert nofb.propose([7, 8, 6, 7, 8], 1) == []
    # Live lists: growing the doc after set_docs is visible.
    live = [1, 2, 3]
    d2 = draft_mod.DocDrafter()
    d2.set_docs([live])
    live.extend([4, 5])
    assert d2.propose([2, 3], 2) == [4, 5]


def test_make_drafter_factory():
    assert draft_mod.make_drafter("ngram").name == "ngram"
    assert draft_mod.make_drafter("doc").name == "doc"
    with pytest.raises(ValueError):
        draft_mod.make_drafter("nope")


def test_accept_tokens_semantics():
    preds = [10, 11, 12, 13, 14]
    # Full acceptance: all drafts + bonus.
    app, a = draft_mod.accept_tokens([10, 11, 12], 3, preds, 99, None)
    assert (app, a) == ([10, 11, 12, 13], 3)
    # Zero acceptance still commits the bonus (>= 1 token per step).
    app, a = draft_mod.accept_tokens([7, 7], 0, preds, 99, None)
    assert (app, a) == ([10], 0)
    # eos truncation is inclusive; budget cap applies after.
    app, a = draft_mod.accept_tokens([10, 11, 12], 3, preds, 99, 11)
    assert app == [10, 11]
    app, a = draft_mod.accept_tokens([10, 11, 12], 3, preds, 2, None)
    assert app == [10, 11]
    app, a = draft_mod.accept_tokens([10], 1, preds, 0, None)
    assert app == [10]                             # floor: 1 token


def test_speculative_accept_oracle():
    # preds[j] = greedy token after span position j; tokens[1:] are drafts.
    preds = jnp.asarray([[5, 6, 7, 8], [5, 6, 7, 8], [5, 6, 7, 8]])
    toks = jnp.asarray([[1, 5, 6, 7],      # full match -> 3
                        [1, 9, 6, 7],      # first draft wrong -> 0
                        [1, 5, 9, 7]])     # second wrong -> 1
    acc = kref.speculative_accept(preds, toks, jnp.asarray([4, 4, 4]))
    assert list(np.asarray(acc)) == [3, 0, 1]
    # span 1 (no drafts) -> 0 regardless of content.
    acc = kref.speculative_accept(preds, toks, jnp.asarray([1, 1, 1]))
    assert list(np.asarray(acc)) == [0, 0, 0]


def test_paged_span_gather_restore_roundtrip():
    rng = np.random.RandomState(0)
    pool = jnp.asarray(rng.randn(6, 2, PS, 4).astype(np.float32))
    bt = jnp.asarray([[0, 2, 4, 5], [1, 3, 4, 5]], jnp.int32)
    start = jnp.asarray([5, 13], jnp.int32)
    snap = kref.paged_span_gather(pool, bt, start, 4)
    assert snap.shape == (2, 4, 2, 4)
    scr = pool + 1.0                               # corrupt every slot
    back = kref.paged_span_restore(scr, snap, bt, start,
                                   jnp.asarray([5, 13], jnp.int32),
                                   jnp.asarray([9, 17], jnp.int32))
    again = kref.paged_span_gather(back, bt, start, 4)
    assert np.array_equal(np.asarray(again), np.asarray(snap))
    # Window [lo, hi) masks: restoring nothing leaves the pool untouched.
    noop = kref.paged_span_restore(scr, snap, bt, start,
                                   jnp.asarray([5, 13], jnp.int32),
                                   jnp.asarray([5, 13], jnp.int32))
    assert np.array_equal(np.asarray(noop), np.asarray(scr))


# ---------------------------------------------------------------------------
# Verify + bitwise rollback at the lm level (all archs, paged and dense)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("paged", [True, False])
def test_verify_rollback_bitwise_and_streams(llm, paged):
    cfg, params = llm
    cache = _mk_cache(cfg, paged)
    rng = np.random.RandomState(0)
    plen, k = 6, 4
    prompts = rng.randint(0, V, size=(B, plen)).astype(np.int32)
    lg, cache, _ = lm.mixed_step(params, cfg, jnp.asarray(prompts), cache,
                                 jnp.zeros(B, jnp.int32),
                                 jnp.full(B, plen, jnp.int32))
    t0 = np.asarray(jnp.argmax(lg, -1)).astype(np.int32)

    # Never-drafted greedy reference (width-1 decode) for stream identity.
    cache_ref = jax.tree.map(jnp.copy, cache)
    toks_ref, cur, pos = [], t0.copy(), np.full(B, plen, np.int64)
    for _ in range(10):
        lg2, cache_ref, _ = lm.mixed_step(
            params, cfg, jnp.asarray(cur[:, None]), cache_ref,
            jnp.asarray(pos, jnp.int32), jnp.ones(B, jnp.int32))
        cur = np.asarray(jnp.argmax(lg2, -1)).astype(np.int32)
        toks_ref.append(cur)
        pos += 1
    toks_ref = np.stack(toks_ref, 1)

    # Crafted drafts: row 0 fully right, row 1 fully wrong, row 2 wrong at
    # position 2 — acceptance must come out exactly [4, 0, 2].
    drafts = np.zeros((B, k), np.int32)
    drafts[0] = toks_ref[0, :k]
    drafts[1] = (toks_ref[1, :k] + 1) % V
    drafts[2] = toks_ref[2, :k]
    drafts[2, 2] = (drafts[2, 2] + 1) % V
    toks = np.concatenate([t0[:, None], drafts], 1)
    span = np.full(B, 1 + k, np.int32)
    start = np.full(B, plen, np.int32)

    pre = jax.tree.map(jnp.copy, cache)
    snap = cache_mod.snapshot_span(cache, jnp.asarray(start), 1 + k)
    has_state = any(cache_mod.layout_for(kd, cfg, paged=False) == "state"
                    for kd in tuple(cfg.block_pattern)
                    + tuple(cfg.tail_blocks))
    if has_state:
        st_snap = lm.snapshot_state_rows(cfg, cache)
    preds, acc, cache = lm.verify_step(params, cfg, jnp.asarray(toks),
                                       cache, jnp.asarray(start),
                                       jnp.asarray(span))
    preds, acc = np.asarray(preds), np.asarray(acc)
    assert list(acc) == [4, 0, 2]
    n_app = acc + 1
    for b in range(B):
        a = int(acc[b])
        committed = list(drafts[b, :a]) + [int(preds[b, a])]
        assert committed == list(toks_ref[b, :a + 1])

    # Roll the rejected tails back and compare the restored slots BITWISE
    # against the pre-verify bytes, gathered by position.
    cache = cache_mod.restore_span(
        cache, snap, jnp.asarray(start),
        jnp.asarray(start + n_app, jnp.int32),
        jnp.asarray(start + span, jnp.int32))
    if has_state:
        mask = n_app < span
        cache = lm.restore_state_rows(cfg, cache, st_snap,
                                      jnp.asarray(mask))
        spans2 = np.where(mask, n_app, 0).astype(np.int32)
        w2 = int(spans2.max())
        _, cache, _ = lm.mixed_step(params, cfg, jnp.asarray(toks[:, :w2]),
                                    cache, jnp.asarray(start),
                                    jnp.asarray(spans2))
    post = cache_mod.snapshot_span(cache, jnp.asarray(start), 1 + k)
    want = cache_mod.snapshot_span(pre, jnp.asarray(start), 1 + k)
    for la, lp in zip(jax.tree.leaves(post), jax.tree.leaves(want)):
        a_np, p_np = np.asarray(la), np.asarray(lp)
        for b in range(B):
            # Snapshot leaf layout: [B, W, ...] except stacked dense_mla's
            # adjacent-index gather, which keeps the group axis leading.
            sl = (slice(None), b) if a_np.shape[0] != B else (b,)
            for w in range(int(n_app[b]), 1 + k):
                assert np.array_equal(a_np[sl + (w,)], p_np[sl + (w,)])

    # Post-rollback continuation equals the never-drafted stream.
    cur = np.array([toks_ref[b, acc[b]] for b in range(B)], np.int32)
    pos = plen + n_app.astype(np.int64)
    for i in range(4):
        lg3, cache, _ = lm.mixed_step(params, cfg, jnp.asarray(cur[:, None]),
                                      cache, jnp.asarray(pos, jnp.int32),
                                      jnp.ones(B, jnp.int32))
        cur = np.asarray(jnp.argmax(lg3, -1)).astype(np.int32)
        for b in range(B):
            assert int(cur[b]) == int(toks_ref[b, int(n_app[b]) + i])
        pos += 1


# ---------------------------------------------------------------------------
# Scheduler end-to-end: stream identity, leaks, COW, guards
# ---------------------------------------------------------------------------

class _BadDrafter:
    """Adversarial: always proposes the same (almost surely wrong) run."""

    def __init__(self, tok=127):
        self.tok = tok

    def propose(self, ctx, k):
        return [self.tok] * k


def _spec_prompts(rng, n=5):
    pat = rng.randint(0, V, size=6).tolist()
    return [(pat * 4)[:12 + i] for i in range(n)]


def _run_sched(cfg, params, prompts, spec, *, drafter=None, chunk=8,
               max_new=8):
    eng = ContinuousBatchingEngine(
        cfg, params, batch=3, max_len=64, page_size=PS,
        chunk_size=chunk, spec_decode=spec, spec_k=4, drafter=drafter)
    out = eng.run([Request(rid=i, prompt=list(p), max_new_tokens=max_new)
                   for i, p in enumerate(prompts)])
    return eng, [r.tokens for r in out]


@pytest.mark.parametrize("arch", ["mha", "mla", "hybrid"])
def test_scheduler_spec_streams_match_greedy(arch, request):
    """n-gram drafts (accepted or rolled back) leave the greedy streams
    as they are and save steps; drafts of the greedy streams themselves
    save steps on every architecture."""
    cfg, params = request.getfixturevalue(f"{arch}_llm")
    prompts = _spec_prompts(np.random.RandomState(0))
    eng0, base = _run_sched(cfg, params, prompts, "off")
    eng1, got = _run_sched(cfg, params, prompts, "ngram")
    assert got == base
    assert eng1.stats["draft_tokens"] > 0
    assert eng1.stats["accepted_tokens"] > 0
    if arch == "mla":
        # The mla fixture reduces deepseek-v2-lite (routed experts, YaRN,
        # a dense lead layer): on its random weights the n-gram drafts of
        # these prompts are mostly wrong, so they may save nothing, but
        # they never cost a step.  The DocDrafter run below checks the
        # saving on this architecture.
        assert eng1.stats["steps"] <= eng0.stats["steps"]
    else:
        assert eng1.stats["steps"] < eng0.stats["steps"]
    assert eng1.allocator.available == eng1.allocator.num_pages
    doc = draft_mod.DocDrafter()
    doc.set_docs([list(p) + list(t) for p, t in zip(prompts, base)])
    eng2, got2 = _run_sched(cfg, params, prompts, "doc", drafter=doc)
    assert got2 == base
    assert eng2.stats["steps"] < eng0.stats["steps"]
    assert eng2.allocator.available == eng2.allocator.num_pages


def test_scheduler_doc_drafter_beats_ngram_on_converged_docs(mha_llm):
    cfg, params = mha_llm
    prompts = _spec_prompts(np.random.RandomState(0))
    _, base = _run_sched(cfg, params, prompts, "off")
    doc = draft_mod.DocDrafter()
    doc.set_docs([list(p) + list(t) for p, t in zip(prompts, base)])
    eng, got = _run_sched(cfg, params, prompts, "doc", drafter=doc)
    assert got == base
    # Seeded with the converged streams, doc lookup accepts nearly all.
    assert eng.spec_accept_rate > 0.5


def test_scheduler_adversarial_drafter_rolls_back_cleanly(mha_llm):
    cfg, params = mha_llm
    prompts = _spec_prompts(np.random.RandomState(0))
    _, base = _run_sched(cfg, params, prompts, "off")
    eng, got = _run_sched(cfg, params, prompts, "ngram",
                          drafter=_BadDrafter())
    assert got == base                     # streams survive 100% rejection
    assert eng.stats["rollback_tokens"] > 0
    assert eng.stats["accepted_tokens"] == 0
    assert eng.allocator.available == eng.allocator.num_pages  # no leak


@pytest.mark.parametrize("chunk", [2, 4, 16])
def test_scheduler_spec_streams_across_chunk_sizes(mha_llm, chunk):
    cfg, params = mha_llm
    prompts = _spec_prompts(np.random.RandomState(0))
    _, base = _run_sched(cfg, params, prompts, "off", chunk=chunk)
    _, got = _run_sched(cfg, params, prompts, "ngram", chunk=chunk)
    assert got == base


def test_scheduler_cow_prefix_shared_rollback_no_double_free(mha_llm):
    cfg, params = mha_llm
    prompt = np.random.RandomState(1).randint(0, V, size=17).tolist()

    def run(spec, drafter=None):
        eng = ContinuousBatchingEngine(
            cfg, params, batch=4, max_len=64, page_size=PS, chunk_size=8,
            prefix_sharing=True, spec_decode=spec, spec_k=4,
            drafter=drafter)
        rs = [Request(rid=i, prompt=list(prompt), max_new_tokens=8)
              for i in range(6)]
        eng.run(rs)
        return eng, [r.tokens for r in rs]

    _, base = run("off")
    eng, got = run("ngram", _BadDrafter(126))
    assert got == base
    assert eng.stats["rollback_tokens"] > 0
    assert eng.stats["shared_pages"] > 0          # sharing actually engaged
    assert eng.allocator.available == eng.allocator.num_pages


def test_scheduler_spec_guards(mha_llm):
    cfg, params = mha_llm
    with pytest.raises(ValueError, match="greedy"):
        ContinuousBatchingEngine(cfg, params, batch=2, max_len=64,
                                 temperature=0.5, spec_decode="ngram")
    with pytest.raises(ValueError, match="off/ngram/doc"):
        ContinuousBatchingEngine(cfg, params, batch=2, max_len=64,
                                 spec_decode="medusa")


# ---------------------------------------------------------------------------
# Orchestrator end-to-end
# ---------------------------------------------------------------------------

def test_orchestrator_spec_digest_identity():
    from repro.agents.orchestrator import make_sim_llm, run_task
    from repro.agents.tasks import TaskSpec

    cfg, params = make_sim_llm(0)
    small = TaskSpec(name="small", coupling="low", n_todos=3, deps={},
                     reads={}, base_tokens=16, par_inflation=1.0,
                     prompt_tokens=12, read_prompt_tokens=4)
    # Sequential: single writer, so the whole-trial document digest must
    # match the non-speculative run exactly.
    rs = {}
    for spec in ("off", "ngram"):
        rs[spec] = run_task(cfg, params, small, mode="sequential", seed=0,
                            max_len=128, kv="paged", prefill="chunked",
                            page_size=16, chunk_size=16, spec_decode=spec)
    assert rs["ngram"].digest == rs["off"].digest
    assert rs["ngram"].gen_tokens == rs["off"].gen_tokens
    assert rs["ngram"].draft_tokens > 0
    assert rs["ngram"].accepted_tokens > 0
    assert rs["ngram"].steps < rs["off"].steps
    assert 0.0 < rs["ngram"].accept_rate <= 1.0
    # Uncoupled parallel: no read edges, so slot content is prompt-pure
    # deterministic and digests must match despite step-clock compression.
    par = {}
    for spec in ("off", "doc"):
        par[spec] = run_task(cfg, params, small, mode="parallel",
                             n_agents=3, seed=0, max_len=128, kv="paged",
                             prefill="chunked", page_size=16,
                             chunk_size=16, spec_decode=spec)
    assert par["doc"].digest == par["off"].digest
    assert par["doc"].gen_tokens == par["off"].gen_tokens


@pytest.fixture(scope="module")
def sim_spec_runs():
    """The simulated agent LLM serving six motif-repeating 59-token prompts
    (trailing n-grams recur upstream, the case prompt lookup targets), 16
    tokens each, at batch 4, page 16, chunk 16 and ``spec_k`` 4: without
    speculation, with n-gram drafts, and with doc drafts seeded by the
    non-speculative streams (standing in for converged CRDT documents)."""
    from repro.agents.orchestrator import make_sim_llm
    cfg, params = make_sim_llm()
    rng = np.random.default_rng(0)
    prompts = []
    for _ in range(6):
        m = 4 + int(rng.integers(0, 4))
        motif = [int(t) for t in rng.integers(2, cfg.vocab_size, m)]
        tail = [int(t) for t in rng.integers(2, cfg.vocab_size, m)]
        prompts.append((motif * -(-59 // m))[:59 - m] + tail)

    def run(spec, drafter=None):
        eng = ContinuousBatchingEngine(
            cfg, params, batch=4, max_len=128, page_size=16, chunk_size=16,
            spec_decode=spec, spec_k=4, drafter=drafter)
        out = eng.run([Request(rid=i, prompt=list(p), max_new_tokens=16)
                       for i, p in enumerate(prompts)])
        return eng, [r.tokens for r in out]

    runs = {"off": run("off")}
    doc = draft_mod.DocDrafter()
    doc.set_docs([list(p) + t for p, t in zip(prompts, runs["off"][1])])
    runs["ngram"] = run("ngram")
    runs["doc"] = run("doc", drafter=doc)
    return runs


def test_sim_llm_speculation_flags(sim_spec_runs):
    """Both drafters serve the greedy streams, draft, and have drafts
    accepted."""
    greedy = sim_spec_runs["off"][1]
    failed = []
    for spec in ("ngram", "doc"):
        eng, streams = sim_spec_runs[spec]
        flags = {"streams_match": streams == greedy,
                 "all_completed": eng.stats["completed"] == len(streams),
                 "drafted": eng.stats["draft_tokens"] > 0,
                 "accept_rate_positive": eng.spec_accept_rate > 0}
        failed += [f"{spec}:{f}" for f, ok in flags.items() if not ok]
    assert not failed, failed


@pytest.fixture(scope="module")
def doc_agent_trials():
    """The tic-tac-toe task run sequentially (one writer, so the document
    digest is a function of the token streams), without speculation and
    with doc drafting."""
    from repro.agents.orchestrator import make_sim_llm, run_task
    from repro.agents.tasks import TASKS

    cfg, params = make_sim_llm()
    return {spec: run_task(cfg, params, TASKS["tic_tac_toe"],
                           mode="sequential", seed=0, max_len=256,
                           kv="paged", prefill="chunked", page_size=16,
                           chunk_size=16, spec_decode=spec, spec_k=4)
            for spec in ("off", "doc")}


def test_doc_drafted_agent_trial_digest_matches(doc_agent_trials):
    off, doc = doc_agent_trials["off"], doc_agent_trials["doc"]
    assert doc.digest == off.digest
    assert doc.gen_tokens == off.gen_tokens


def test_doc_drafted_agent_trial_takes_fewer_steps(doc_agent_trials):
    off, doc = doc_agent_trials["off"], doc_agent_trials["doc"]
    assert doc.accepted_tokens > 0
    assert doc.steps < off.steps


def test_orchestrator_spec_requires_chunked():
    from repro.agents.orchestrator import make_sim_llm, run_task
    from repro.agents.tasks import TASKS

    cfg, params = make_sim_llm(0)
    with pytest.raises(ValueError, match="mixed serve step"):
        run_task(cfg, params, TASKS["tic_tac_toe"], mode="sequential",
                 prefill="replay", spec_decode="ngram")

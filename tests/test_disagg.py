"""Prefill/decode disaggregation over the CRDT page table.

A two-replica MultiEngineServer with roles ["prefill", "decode"]: cold
prompts route to the prefill replica, which fills pages and publishes the
prefix chain through the replicated map once the bytes have landed
(publish-on-fill).  Warm prompts route to the decode replica, whose
admission hook adopts the published PHYSICAL pages — provisional share,
J_XFER_BEGIN, cross-pool transfer, commit iff the lease epoch is unchanged
— instead of recomputing the prefix.  The tests pin:

  * token streams identical to a solo single-engine run for MHA, MLA and
    int8-quantized pools (adoption is bitwise, so greedy decode cannot
    diverge),
  * the adoption counters actually fire (adopted pages, avoided prefill
    steps, transfer bytes) and ``cross_replica_hits`` counts only
    COMMITTED transfers,
  * ``adopt_pages=False`` keeps coordination (publication, role routing)
    but moves zero bytes — the local-prefill baseline, same streams,
  * an exporter crash mid-transfer (armed after J_XFER_BEGIN, before the
    commit check) rolls the adopter back: the provisional ref is returned,
    J_XFER_ABORT balances the journal, survivors converge bitwise and
    every request still completes with the correct stream.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.models import lm
from repro.serving.chaos import _xfer_balanced
from repro.serving.replicated import MultiEngineServer
from repro.serving.scheduler import ContinuousBatchingEngine, Request


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


def _requests(cfg, count=8, prompt_len=17, new_tokens=4, seed=11):
    """AABB... over two prompts: the first copy of each prompt is cold
    (prefill tier), later copies arrive after gossip has shipped the
    publication and should adopt on the decode tier."""
    rng = np.random.default_rng(seed)
    prompts = {c: [int(t) for t in rng.integers(2, cfg.vocab_size,
                                                prompt_len)]
               for c in "AB"}
    pattern = ("AABB" * ((count + 3) // 4))[:count]
    return [Request(rid=i, prompt=list(prompts[c]),
                    max_new_tokens=new_tokens)
            for i, c in enumerate(pattern)]


def _run_disagg(cfg, params, reqs, *, adopt=True, xfer_crash=False,
                replicas=2, max_len=32, page_size=8, **kw):
    """Staggered arrivals (first wave of 2, then one per step) so the
    decode tier admits AFTER the prefill tier's publications gossip."""
    roles = ["prefill"] + ["decode"] * (replicas - 1)
    server = MultiEngineServer(cfg, params, replicas=replicas, batch=2,
                               max_len=max_len, page_size=page_size,
                               sync_every=1, chunk_size=page_size,
                               roles=roles, adopt_pages=adopt, **kw)
    if xfer_crash:
        server.arm_transfer_crash(0)
    pending = list(reqs)
    for r in pending[:2]:
        server.submit(r)
    pending = pending[2:]
    while True:
        more = server.step()
        if pending:
            server.submit(pending.pop(0))
            more = True
        assert server.clock < 5_000
        if not more:
            break
    server.sync()
    return server


def _solo_streams(cfg, params, reqs, **kw):
    out = {}
    for req in reqs:
        key = tuple(req.prompt)
        if key not in out:
            solo = ContinuousBatchingEngine(cfg, params, batch=1,
                                            max_len=32, paged=True,
                                            page_size=8, chunk_size=8, **kw)
            done = solo.run([Request(0, list(req.prompt),
                                     req.max_new_tokens)])[0]
            out[key] = tuple(done.tokens)
    return out


@pytest.mark.parametrize("family", ["mha", "mla", "int8"])
def test_disagg_adoption_streams_match_local_prefill(family, mha_llm,
                                                     mla_llm):
    cfg, params = mla_llm if family == "mla" else mha_llm
    kw = {"kv_quant": "int8"} if family == "int8" else {}
    reqs = _requests(cfg)
    server = _run_disagg(cfg, params, reqs, adopt=True, **kw)
    stats = server.stats()
    assert stats["completed"] == len(reqs)
    assert server.converged()
    # The decode tier really adopted physical pages instead of re-running
    # the prefix through the model.
    assert stats["adopted_pages"] > 0
    assert stats["prefill_steps_avoided"] > 0
    assert stats["transferred_pages"] > 0
    assert stats["transfer_bytes"] > 0
    # Only committed transfers count as usable cross-replica hits.
    assert stats["cross_replica_hits"] == stats["transferred_pages"]
    # Adoption is bitwise, so greedy streams equal a solo engine's.
    solos = _solo_streams(cfg, params, reqs, **kw)
    for req in reqs:
        assert tuple(req.tokens) == solos[tuple(req.prompt)], req.rid
    # Every provisional ref was either committed or returned.
    for store in server.stores:
        assert (store.refcounts() == 0).all()
        assert (store.dec <= store.inc).all()


def test_disagg_baseline_never_moves_bytes(mha_llm):
    """adopt_pages=False keeps publication + role routing but the decode
    tier prefills locally: zero transfers, identical streams."""
    cfg, params = mha_llm
    reqs_on = _requests(cfg)
    reqs_off = _requests(cfg)
    server_on = _run_disagg(cfg, params, reqs_on, adopt=True)
    server_off = _run_disagg(cfg, params, reqs_off, adopt=False)
    s_on, s_off = server_on.stats(), server_off.stats()
    assert s_off["completed"] == len(reqs_off)
    assert s_off["transfer_bytes"] == 0
    assert s_off["transferred_pages"] == 0
    assert s_off["adopted_pages"] == 0
    assert s_off["cross_replica_hits"] == 0
    assert s_on["adopted_pages"] > 0
    assert {r.rid: list(r.tokens) for r in reqs_on} \
        == {r.rid: list(r.tokens) for r in reqs_off}


def test_disagg_exporter_crash_mid_transfer_rolls_back(mha_llm):
    """Crash the prefill exporter after J_XFER_BEGIN but before the commit
    check: the adopter must abort (return the provisional ref, journal
    J_XFER_ABORT), survivors converge, and recovery still completes every
    request with the correct stream.  Three replicas so the survivors form
    a majority that retires the crashed exporter (N=2 pins its pages — the
    documented liveness gap)."""
    cfg, params = mha_llm
    reqs = _requests(cfg)
    server = _run_disagg(cfg, params, reqs, adopt=True, xfer_crash=True,
                         replicas=3)
    assert server._xfer_crash is None          # the armed crash fired
    assert server.adopt_aborts >= 1
    # Aborted transfers are not usable hits.
    assert server.stats()["cross_replica_hits"] \
        == server.transferred_pages
    ok, detail = _xfer_balanced(server)
    assert ok, detail
    assert server.converged()
    stats = server.stats()
    assert stats["failed_requests"] == 0
    assert stats["lost_requests"] == 0
    # Recovery re-admits orphans as NEW Request objects, so stream identity
    # is checked against the replicated journal, not the submitted objects:
    # every rid reached a terminal DONE exactly once, and its journaled
    # generation equals the solo engine's greedy stream.
    store = next(s for r, s in enumerate(server.stores)
                 if not server.crashed[r])
    info = server._fold_journal(store)
    solos = _solo_streams(cfg, params, reqs)
    for req in reqs:
        d = info[req.rid]
        assert d["terminal"], req.rid
        gen = server._contiguous(d["gen"])
        assert tuple(gen) == solos[tuple(req.prompt)], req.rid


def test_disagg_role_validation(mha_llm):
    cfg, params = mha_llm
    with pytest.raises(ValueError, match="roles must name every replica"):
        MultiEngineServer(cfg, params, replicas=2, batch=2, max_len=32,
                          page_size=8, roles=["prefill"])
    with pytest.raises(ValueError, match="prefill/decode/mixed"):
        MultiEngineServer(cfg, params, replicas=2, batch=2, max_len=32,
                          page_size=8, roles=["prefill", "verifier"])


def test_disagg_deterministic_counters(mha_llm):
    """Same seed, same arrivals -> bit-identical adoption accounting (the
    property the regression gate's strict thresholds rely on)."""
    cfg, params = mha_llm
    runs = []
    for _ in range(2):
        server = _run_disagg(cfg, params, _requests(cfg), adopt=True)
        s = server.stats()
        runs.append((s["adopted_pages"], s["prefill_steps_avoided"],
                     s["transferred_pages"], s["transfer_bytes"],
                     s["adopt_aborts"], s["cross_replica_hits"],
                     s["steps"], s["sync_bytes"]))
    assert runs[0] == runs[1]


@pytest.fixture(scope="module")
def sim_disagg_flags():
    """Adoption off and on over the same staggered workload on the
    simulated agent LLM: eight requests over two 53-token prompts (three
    full 16-token pages and a partial one), 8 tokens each."""
    from repro.agents.orchestrator import make_sim_llm
    cfg, params = make_sim_llm()
    runs = {}
    for adopt in (False, True):
        reqs = _requests(cfg, count=8, prompt_len=53, new_tokens=8, seed=0)
        server = _run_disagg(cfg, params, reqs, adopt=adopt, max_len=128,
                             page_size=16)
        ttft = [r.first_token_step - r.admitted_step for r in reqs]
        runs[adopt] = (server.stats(), server.converged(), reqs,
                       sum(ttft) / len(ttft))
    (s_off, conv_off, reqs_off, ttft_off) = runs[False]
    (s_on, conv_on, reqs_on, ttft_on) = runs[True]
    return {
        "adopted_pages_positive": s_on["adopted_pages"] > 0,
        "prefill_steps_avoided_positive": s_on["prefill_steps_avoided"] > 0,
        "ttft_adopt_not_worse": ttft_on <= ttft_off,
        "streams_match": ([r.tokens for r in reqs_on]
                          == [r.tokens for r in reqs_off]),
        "baseline_never_adopts": (s_off["adopted_pages"] == 0
                                  and s_off["transfer_bytes"] == 0),
        "all_completed": (s_off["completed"] == len(reqs_off)
                          and s_on["completed"] == len(reqs_on)),
        "all_converged": conv_off and conv_on,
    }


def test_disagg_acceptance_flags(sim_disagg_flags):
    """Adoption moves pages and skips prefill chunks, never lengthens the
    mean time to first token (in steps) against local prefill on the same
    workload, leaves the token streams as they are, and the baseline
    moves no byte."""
    failed = [flag for flag, ok in sim_disagg_flags.items() if not ok]
    assert not failed, failed

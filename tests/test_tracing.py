"""Tracing of the serving step (``repro.runtime.trace``) and the step's
position counters (``stats["step_positions"]``, ``["span_positions"]``),
at tiny sizes on the CPU: what is recorded off and on, how the spans of one
step nest, the per-request intervals (with preemption), the counters over
scripted steps, tokens unchanged by tracing, and the spans' names in a
``jax.profiler`` trace."""
from __future__ import annotations

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.models import lm
from repro.runtime import trace
from repro.serving.scheduler import ContinuousBatchingEngine, Request

PHASES = ("engine.admit", "engine.compose", "engine.pages", "engine.upload",
          "engine.dispatch", "engine.sync", "engine.commit")
INTERVALS = ("engine.queued", "engine.prefill")


@pytest.fixture(scope="module")
def llm():
    cfg = configs.reduced(configs.get("olmo-1b"), d_model=32, vocab=128)
    return cfg, jax.tree.map(lambda x: x.astype(jnp.float32),
                             lm.init(jax.random.PRNGKey(0), cfg))


@pytest.fixture(autouse=True)
def recorder_off():
    """Every test starts and ends with the recorder off and empty."""
    trace.disable()
    trace.drain()
    yield
    trace.disable()
    trace.drain()


def _requests(lens, news, seed=5):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=[int(t) for t in rng.integers(2, 100, n)],
                    max_new_tokens=m)
            for i, (n, m) in enumerate(zip(lens, news))]


def _engine(llm, *, batch=2, num_pages=None, chunk=4, **kw):
    cfg, params = llm
    return ContinuousBatchingEngine(cfg, params, batch=batch, max_len=32,
                                    page_size=8, num_pages=num_pages,
                                    chunk_size=chunk, **kw)


def _drain_engine(eng, reqs):
    for r in reqs:
        eng.submit(r)
    while eng.step():
        pass


# -- off ---------------------------------------------------------------------

@pytest.mark.parametrize("case", ["span", "interval", "engine"])
def test_off_records_nothing_and_span_is_the_shared_noop(llm, case):
    off = trace.span("engine.step")
    assert off is trace.span("engine.queued", rid=3)
    if case == "span":
        with trace.span("engine.step"):
            with trace.span("engine.admit", rid=1):
                pass
    elif case == "interval":
        trace.interval("engine.queued", 1, 2, 0)
    else:
        reqs = _requests([6, 3], [2, 2])
        _drain_engine(_engine(llm), reqs)
        assert all(len(r.tokens) == 2 for r in reqs)
    assert trace.drain() == []


def test_drain_refuses_while_a_span_is_open():
    trace.enable()
    with trace.span("engine.step"):
        with pytest.raises(RuntimeError, match="open"):
            trace.drain()
    assert [s.name for s in trace.drain()] == ["engine.step"]


# -- the spans of one step -----------------------------------------------------

STEP_CASES = {
    # first step: both rows bind, both start admitting
    "admitting": (dict(batch=2), [6, 3], [2, 2],
                  ["engine.admit", "engine.compose", "engine.pages",
                   "engine.compose", "engine.upload", "engine.dispatch",
                   "engine.sync", "engine.commit"]),
    # one row, two one-token requests: the first completes in the step and
    # the freed row admits the second at its end
    "freeing": (dict(batch=1), [3, 3], [1, 1],
                ["engine.admit", "engine.compose", "engine.pages",
                 "engine.compose", "engine.upload", "engine.dispatch",
                 "engine.sync", "engine.commit", "engine.admit"]),
    # dense cache: no pages to walk
    "dense": (dict(batch=2, paged=False), [6, 3], [2, 2],
              ["engine.admit", "engine.compose", "engine.compose",
               "engine.upload", "engine.dispatch", "engine.sync",
               "engine.commit"]),
}


@pytest.mark.parametrize("case", sorted(STEP_CASES))
def test_one_step_records_its_phases_inside_engine_step(llm, case):
    kw, lens, news, want = STEP_CASES[case]
    eng = _engine(llm, **kw)
    for r in _requests(lens, news):
        eng.submit(r)
    trace.enable()
    eng.step()
    spans = trace.drain()
    steps = [i for i, s in enumerate(spans) if s.name == "engine.step"]
    assert len(steps) == 1
    top = spans[steps[0]]
    assert top.parent is None and top.rid is None
    children = [s for s in spans if s.name in PHASES]
    assert [s.name for s in children] == want
    for s in children:
        assert s.parent == steps[0]
        assert top.t0_ns <= s.t0_ns <= s.t1_ns <= top.t1_ns
    assert all(a.t1_ns <= b.t0_ns for a, b in zip(children, children[1:]))
    rest = [s for s in spans if s.name not in PHASES + ("engine.step",)]
    assert {s.name for s in rest} <= set(INTERVALS)
    assert all(s.parent is None for s in rest)


# -- per-request intervals -----------------------------------------------------

INTERVAL_CASES = {
    "plain": (dict(batch=2), [6, 3, 5], [2, 3, 2]),
    # a pool of 4 pages for two 20-token prompts: the second is preempted
    # while it is still admitting, before its first token
    "preempted_before_first": (dict(batch=2, num_pages=4), [20, 20], [2, 2]),
    # two short prompts that outgrow 4 pages while decoding: one is
    # preempted after its first token
    "preempted_after_first": (dict(batch=2, num_pages=4, chunk=32),
                              [6, 6], [12, 12]),
}


@pytest.mark.parametrize("case", sorted(INTERVAL_CASES))
def test_queued_and_prefill_intervals_per_binding_and_first_token(llm, case):
    kw, lens, news = INTERVAL_CASES[case]
    eng = _engine(llm, **kw)
    reqs = _requests(lens, news)
    trace.enable()
    _drain_engine(eng, reqs)
    spans = [s for s in trace.drain() if s.name in INTERVALS]
    assert all(len(r.tokens) == r.max_new_tokens for r in reqs)
    assert sum(s.name == "engine.queued" for s in spans) \
        == eng.stats["admitted"]
    kinds = set()
    for r in reqs:
        mine = [s for s in spans if s.rid == r.rid]
        queued = [s for s in mine if s.name == "engine.queued"]
        prefill = [s for s in mine if s.name == "engine.prefill"]
        assert len(prefill) == 1 and queued
        assert all(s.parent is None and s.t0_ns <= s.t1_ns for s in mine)
        assert queued[0].t0_ns == r.t_submit_ns
        assert r.t_submit_ns <= prefill[0].t0_ns <= prefill[0].t1_ns \
            == r.t_first_ns
        before = [q for q in queued if q.t1_ns <= prefill[0].t0_ns]
        # the prefill interval starts at the last binding before the token
        assert before and before[-1].t1_ns == prefill[0].t0_ns
        if len(before) > 1:
            kinds.add("preempted_before_first")
        if len(queued) > len(before):
            kinds.add("preempted_after_first")
            assert r.t_admit_ns == queued[-1].t1_ns > r.t_first_ns
        # a re-queue starts at the preemption, after the previous binding
        for a, b in zip(queued, queued[1:]):
            assert a.t1_ns <= b.t0_ns
    if case == "plain":
        assert eng.stats["preemptions"] == 0 and not kinds
    else:
        assert eng.stats["preemptions"] >= 1 and case in kinds


# -- position counters -----------------------------------------------------------

COUNTER_CASES = {
    # batch 2, chunk 4, prompts 6 and 3 (2 and 3 tokens out):
    #   1: spans 4, 3 -> width 4;  2: spans 2, 1 -> width 2;  3: 1, 1 -> 1
    "b2c4": (dict(batch=2, chunk=4), [6, 3], [2, 3],
             [(8, 7), (4, 3), (2, 2)]),
    # batch 3, chunk 8, prompts 5, 9, 2 (1, 2, 3 out); row 0 frees at once:
    #   1: spans 5, 8, 2 -> width 8;  2: 0, 1, 1 -> 1;  3: 0, 1, 1 -> 1
    "b3c8": (dict(batch=3, chunk=8), [5, 9, 2], [1, 2, 3],
             [(24, 15), (3, 2), (3, 2)]),
}


@pytest.mark.parametrize("case", sorted(COUNTER_CASES))
def test_step_and_span_positions_match_a_scripted_sequence(llm, case):
    kw, lens, news, want = COUNTER_CASES[case]
    eng = _engine(llm, **kw)
    for r in _requests(lens, news):
        eng.submit(r)
    got = []
    more = True
    while more:
        s0, p0 = eng.stats["step_positions"], eng.stats["span_positions"]
        more = eng.step()
        got.append((eng.stats["step_positions"] - s0,
                    eng.stats["span_positions"] - p0))
    assert got == want
    assert eng.stats["step_positions"] - eng.stats["span_positions"] \
        == sum(w - s for w, s in want)


# -- tokens do not depend on tracing ---------------------------------------------

@pytest.mark.parametrize("mode", ["paged", "dense", "speculative"])
def test_generated_tokens_identical_with_tracing_on_and_off(llm, mode):
    kw = {"paged": dict(), "dense": dict(paged=False),
          "speculative": dict(spec_decode="ngram")}[mode]
    lens, news = [6, 11, 3], [5, 4, 6]
    out = {}
    for on in (False, True):
        reqs = _requests(lens, news, seed=7)
        if on:
            trace.enable()
        _drain_engine(_engine(llm, batch=2, **kw), reqs)
        trace.disable()
        out[on] = [r.tokens for r in reqs]
        assert bool(trace.drain()) == on
    assert out[True] == out[False]


# -- the profiler's trace --------------------------------------------------------

@pytest.fixture(scope="module")
def profiled_names(llm, tmp_path_factory):
    """Event names on the host plane of a CPU profile of a few traced
    steps."""
    log_dir = str(tmp_path_factory.mktemp("profile"))
    eng = _engine(llm)
    for r in _requests([6, 3], [2, 2]):
        eng.submit(r)
    eng.step()                       # compile outside the profile
    jax.profiler.start_trace(log_dir)
    trace.enable()
    try:
        while eng.step():
            pass
    finally:
        trace.disable()
        trace.drain()
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    return {e.name for plane in data.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events}


@pytest.mark.parametrize("name", ("engine.step",) + PHASES)
def test_profiler_trace_holds_the_engine_spans_by_name(profiled_names,
                                                       name):
    assert name in profiled_names
    assert not {"serve_step", "generator"} & profiled_names

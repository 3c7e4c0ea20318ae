"""Multi-agent code-generation orchestrator (the paper's experiment loop).

Agents are rows of one batched decode engine — the TPU-native analogue of
"N concurrent LLM API calls".  Coordination is exclusively through CRDT
state (TodoBoard + per-agent SlotDoc replicas, merged through the join):
no message passing, no scheduler.  The loop implements the paper's four
observation-driven behaviours:

  completed-work detection   claims skip DONE TODOs (board observation)
  context integration        prompts embed the *current* content of read slots
  naming alignment           (same mechanism — context replay of neighbors)
  conflict avoidance         optimistic claim → LWW arbitration → losers re-pick

Invalidations: if a read slot's version advances mid-generation, the agent
re-contextualizes (replays a fresh prompt) — the measured source of the
coupled-task slowdown (paper §4.2, Table 7).

Sequential mode is the same machinery with one agent.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.agents.tasks import TaskSpec
from repro.core import delta as delta_mod
from repro.core import doc as doc_mod
from repro.core import merge as merge_mod
from repro.core import observe, protocol, todo
from repro.core.clock import Lamport
from repro.models import cache as cache_mod
from repro.models import lm
from repro.models.config import ModelConfig
from repro.serving import draft as draft_mod
from repro.serving import engine as engine_mod

IDLE, PREFILL, GEN, HALT = "idle", "prefill", "gen", "halt"
OBSERVE_EVERY = 8          # steps between observation sweeps
MAX_REPREFILL = 2          # bounded re-contextualizations per TODO
MAX_MAP_FAILURES = 3       # consecutive page-map failures before giving up
SLOT_CAP = 1024


@dataclass
class AgentState:
    row: int                            # engine batch row
    client: int                         # CRDT client id (>=1)
    phase: str = IDLE
    todo_id: int = -1
    queue: list = field(default_factory=list)     # prompt tokens to replay
    tokens_left: int = 0
    reprefills: int = 0
    snapshot: Optional[observe.Snapshot] = None
    lamport: Lamport = None
    failures: int = 0                   # consecutive page-map failures
    needs_map: bool = False             # row unmapped; waiting to retry
    retry_at: int = 0                   # step at which to retry the map
    hist: list = field(default_factory=list)  # raw prompt+generated tokens
                                        # (speculative drafting context)


@dataclass
class RunResult:
    task: str
    mode: str
    n_agents: int
    wall_s: float
    gen_tokens: int
    replay_tokens: int
    steps: int
    invalidations: int
    claim_collisions: int
    observation_events: int
    semantic_conflicts: int
    declared_symbols: int
    converged: bool
    digest: int
    merge_strategy: str = "allgather"
    sync_rounds: int = 0
    sync_bytes: int = 0     # wire bytes (see delta.full_state_wire_bytes)
    kv_mode: str = "dense"          # dense | paged KV cache
    prefill_mode: str = "replay"    # replay (token-by-token) | ragged
    shared_prefix_pages: int = 0    # prompt pages shared across (re-)prefills
    replicas: int = 1               # page-table metadata replicas
    disaggregated: bool = False     # prefill/decode role-partitioned homes
    cross_replica_prefix_hits: int = 0  # prefix pages adopted from a peer
    page_sync_bytes: int = 0        # page-table anti-entropy wire bytes
    agent_failures: int = 0         # page-map failures hit by agent loops
    agent_retries: int = 0          # successful backoff re-maps after failure
    spec_decode: str = "off"        # off | ngram | doc drafting source
    draft_tokens: int = 0           # speculative tokens proposed
    accepted_tokens: int = 0        # draft tokens the verifier accepted
    rollback_tokens: int = 0        # rejected-tail tokens rolled back

    @property
    def accept_rate(self) -> float:
        return self.accepted_tokens / max(1, self.draft_tokens)

    @property
    def tokens_per_s(self) -> float:
        return self.gen_tokens / max(self.wall_s, 1e-9)

    @property
    def s_per_1k_tokens(self) -> float:
        return 1000.0 * self.wall_s / max(self.gen_tokens, 1)

    # Response time in decode-step units: on the serving target (TPU v5e)
    # decode latency is weight-streaming-bound and batch-invariant for B≤8,
    # so steps ≡ latency; CPU wall-clock scales with batch (no idle lanes)
    # and is reported as the secondary column.  See EXPERIMENTS.md §Agents.
    @property
    def response_steps(self) -> int:
        return self.steps

    @property
    def steps_per_1k_tokens(self) -> float:
        return 1000.0 * self.steps / max(self.gen_tokens, 1)


# ---------------------------------------------------------------------------
# Content model: prompts + semantic-conflict detection
# ---------------------------------------------------------------------------

def _prompt_tokens(task: TaskSpec, todo_id: int, docs, vocab: int,
                   rng: np.random.Generator) -> list[int]:
    """Deterministic task/TODO header + current content of read slots."""
    base = np.random.default_rng(hash((task.name, todo_id)) % (2**31))
    toks = list(2 + base.integers(0, vocab - 2, size=task.prompt_tokens))
    merged = merge_mod.fold_join(docs)
    lengths = np.asarray(merged.length)
    tokens = np.asarray(merged.tokens)
    for r in task.reads.get(todo_id, ()):
        n = int(lengths[r])
        if n > 0:     # context integration: read the neighbor's latest code
            tail = tokens[r, max(0, n - task.read_prompt_tokens): n]
            toks.extend(int(t) for t in tail)
    return toks


def count_conflicts(merged: doc_mod.SlotDoc) -> tuple[int, int]:
    """Semantic conflicts: the same symbol *declared* in two different slots.

    Declaration tokens are tokens ≡ 5 (mod 13); the symbol is tok mod 64 —
    a fixed projection of real generated content into a symbol namespace
    (duplicate declarations are exactly the paper's dominant conflict class).
    Returns (conflicts, total_declarations).
    """
    lengths = np.asarray(merged.length)
    tokens = np.asarray(merged.tokens)
    declared: dict[int, int] = {}
    conflicts = 0
    total = 0
    for s in range(merged.num_slots):
        for t in tokens[s, : lengths[s]]:
            t = int(t)
            if t % 13 == 5:
                total += 1
                sym = t % 64
                if sym in declared and declared[sym] != s:
                    conflicts += 1
                else:
                    declared.setdefault(sym, s)
    return conflicts, total


# ---------------------------------------------------------------------------
# The run loop
# ---------------------------------------------------------------------------

def run_task(cfg: ModelConfig, params, task: TaskSpec, *, mode: str,
             n_agents: int = 4, seed: int = 0, max_len: int = 1024,
             merge: str = "allgather", delta_capacity: int = 64,
             kv: str = "dense", prefill: str = "replay",
             page_size: int = 64, chunk_size: int = 32, replicas: int = 1,
             spec_decode: str = "off", spec_k: int = 4,
             kv_quant: str = "off", disaggregate: bool = False,
             impl: Optional[str] = None,
             time_fn=time.perf_counter) -> RunResult:
    """``kv="paged"`` backs the agents with the paged KV cache.

    ``prefill="chunked"`` (alias ``"ragged"``) rides the token-budget mixed
    serve step: each loop iteration spends one span per agent — a ≤
    ``chunk_size`` slice of any agent's pending (re-)contextualization
    prompt AND one decode token for every generating agent, in the same
    batched call — so an agent replaying a fresh prompt after an
    invalidation never stalls its neighbours.  ``"replay"`` is the paper's
    token-by-token baseline (one decode step per prompt token).

    ``impl`` picks the attention path of the mixed and verify steps
    (``None``: the platform's, ``kernels.ops.default_impl``)."""
    assert mode in ("sequential", "parallel")
    assert merge in ("allgather", "pmax", "delta")
    assert kv in ("dense", "paged")
    assert prefill in ("replay", "ragged", "chunked")
    if replicas > 1 and kv != "paged":
        raise ValueError("--replicas > 1 requires the paged KV cache "
                         "(the replicated page table replicates page "
                         "metadata, not a dense per-row cache)")
    if kv_quant != "off" and kv != "paged":
        raise ValueError("--kv-quant requires --kv paged (quantized "
                         "layouts are page-pool layouts)")
    if disaggregate and replicas < 2:
        raise ValueError("--disaggregate requires --replicas >= 2 (one "
                         "prefill home plus at least one decode home)")
    chunked = prefill in ("ragged", "chunked")
    if spec_decode not in ("off", "ngram", "doc"):
        raise ValueError(f"spec_decode must be off/ngram/doc, got "
                         f"{spec_decode!r}")
    if spec_decode != "off" and not chunked:
        raise ValueError("--spec-decode rides the mixed serve step: "
                         "use --prefill chunked (verify widens decode "
                         "spans, which the replay baseline cannot express)")
    if mode == "sequential":
        n_agents = 1
    rng = np.random.default_rng(seed)
    k_todos = task.n_todos
    vocab = cfg.vocab_size

    # Shared coordination state (board) + per-agent document replicas.
    board = todo.empty(k_todos)
    out_lam = Lamport.create(client=100)
    deps_np = np.zeros((k_todos, k_todos), bool)
    for k, ds in task.deps.items():
        for d in ds:
            deps_np[k, d] = True

    docs = [doc_mod.empty(k_todos, SLOT_CAP) for _ in range(n_agents)]
    agents = [AgentState(row=i, client=i + 1, lamport=Lamport.create(i + 1))
              for i in range(n_agents)]
    state_bytes = delta_mod.nbytes(docs[0])
    delta_sync = (delta_mod.DeltaSync(docs[0], capacity=delta_capacity)
                  if merge == "delta" else None)

    # Jit every hot helper once: eager lax.fori_loop (claims) re-traces and
    # re-compiles per call — at one claim round per step that dominated wall
    # time (~0.5 s/step) and, worse, contaminated the seq-vs-par comparison.
    step_fn = jax.jit(engine_mod.make_serve_step(cfg))
    claims_fn = jax.jit(protocol.concurrent_claims)
    fold_fn = jax.jit(merge_mod.fold_join)
    ready_fn = jax.jit(todo.ready_mask)
    all_done_fn = jax.jit(todo.all_done)
    complete_fn = jax.jit(todo.complete)
    append_fn = jax.jit(doc_mod.append_token)
    append_run_fn = jax.jit(doc_mod.append)
    digest_fn = jax.jit(doc_mod.digest)
    mapper = None
    if kv == "paged":
        from repro.serving.scheduler import PrefixPageMapper
        # Shared-prefix admission: each (re-)contextualization maps the
        # row's pages through a refcounted pool with longest-prefix reuse —
        # the unchanged task/TODO prompt header keeps its pages across
        # invalidation replays instead of being re-pooled per agent.
        maxp = -(-max_len // page_size)
        if replicas > 1:
            from repro.serving.replicated import ReplicatedPrefixPageMapper
            # One remap-transient spare slice per metadata replica: agents
            # are partitioned round-robin, so each home partition must hold
            # its agents' pages plus one in-flight remap.
            pool_pages = (n_agents + replicas) * maxp
            mapper = ReplicatedPrefixPageMapper(
                n_agents, maxp, page_size, trash_page=pool_pages,
                replicas=replicas, num_pages=pool_pages,
                disaggregate=disaggregate)
        else:
            pool_pages = (n_agents + 1) * maxp     # +maxp: remap transient
            mapper = PrefixPageMapper(n_agents, maxp, page_size,
                                      trash_page=pool_pages,
                                      num_pages=pool_pages)
        cache = lm.init_cache(cfg, n_agents, max_len, paged=True,
                              page_size=page_size,
                              num_pages=pool_pages + 1, kv_quant=kv_quant)
        cache = mapper.install(cache)
    else:
        cache = lm.init_cache(cfg, n_agents, max_len)

    def recontextualize(a: AgentState) -> bool:
        """Map the agent's new prompt into pages (shared-prefix admission).

        Returns False when the pool cannot serve the re-map right now: the
        agent's row is released (which relieves the very pressure that made
        the map fail) and the agent backs off with deterministic jitter
        instead of the whole trial aborting.  Only after MAX_MAP_FAILURES
        consecutive failures does the pool-exhausted error propagate.
        """
        if mapper is None:
            return True
        horizon = min(len(a.queue) + gen_budget, max_len)
        try:
            mapper.map_row(a.row, a.queue, horizon)
        except RuntimeError:
            stats["agent_fail"] += 1
            a.failures += 1
            if a.failures >= MAX_MAP_FAILURES:
                raise
            mapper.free_row(a.row)
            a.needs_map = True
            a.retry_at = stats["steps"] + engine_mod.backoff_steps(
                a.client, a.failures)
            return False
        if a.needs_map:
            stats["agent_retry"] += 1
        a.needs_map = False
        a.failures = 0
        return True

    def push_tables() -> None:
        nonlocal cache
        if mapper is not None:
            cache = mapper.install(cache)
    pos = jnp.zeros((n_agents,), jnp.int32)
    token = jnp.ones((n_agents,), jnp.int32)
    key = jax.random.PRNGKey(seed)
    chunk_size = max(1, min(chunk_size, max_len))
    # Host mirrors for the chunked (mixed-step) path: positions and last
    # tokens never round-trip through the device.
    pos_h = np.zeros((n_agents,), np.int64)
    tok_h = np.ones((n_agents,), np.int64)

    mixed_fn = None
    if chunked:
        mixed_fn = jax.jit(engine_mod.make_mixed_step_fn(cfg, impl=impl))

    # Speculative decoding through the mixed step: a host-side drafter
    # widens GEN rows from span 1 to 1+k, one verify call scores the whole
    # batch (non-drafted lanes read preds at their last span position —
    # identical to greedy sampling), and rejected tails roll back bitwise
    # from a pre-verify snapshot.  The PrefixPageMapper pre-maps each row's
    # full generation horizon, so speculative writes always land in already
    # mapped pages and rollback never frees pages here.
    drafter = None
    verify_fn = snap_jit = restore_jit = None
    spec_k = max(1, int(spec_k))
    wclamp = chunk_size
    has_state = any(
        cache_mod.layout_for(k, cfg, paged=False) == "state"
        for k in tuple(cfg.block_pattern) + tuple(cfg.tail_blocks))
    if spec_decode != "off":
        drafter = draft_mod.make_drafter(spec_decode)
        wclamp = max(chunk_size, 1 + spec_k)
        verify_fn = jax.jit(engine_mod.make_verify_step_fn(cfg, impl=impl))

        def _snap_fn(c, start, width):
            out = {"spans": cache_mod.snapshot_span(c, start, width)}
            if has_state:
                out["state"] = lm.snapshot_state_rows(cfg, c)
            return out

        def _restore_fn(c, snap, start, lo, hi, smask):
            c = cache_mod.restore_span(c, snap["spans"], start, lo, hi)
            if has_state:
                c = lm.restore_state_rows(cfg, c, snap["state"], smask)
            return c

        snap_jit = jax.jit(_snap_fn, static_argnums=(2,))
        restore_jit = jax.jit(_restore_fn)

    # Warmup: compile every helper shape outside the timed region (the claim
    # helper has one shape per idle-agent count).
    _ = step_fn(params, cache, token, pos, key)
    if mixed_fn is not None:
        # One compile per span-width bucket; all-zero spans leave the cache
        # bit-for-bit as-is, so warmup is free of side effects.
        for wb in engine_mod.mixed_width_buckets(chunk_size):
            _, cache = mixed_fn(params, cache,
                                jnp.zeros((n_agents, wb), jnp.int32),
                                jnp.zeros((n_agents,), jnp.int32),
                                jnp.zeros((n_agents,), jnp.int32), key)
    if verify_fn is not None:
        # Verify + snapshot/restore per width bucket; zero spans and empty
        # rollback windows leave the cache bit-for-bit untouched.
        z = jnp.zeros((n_agents,), jnp.int32)
        for wb in engine_mod.mixed_width_buckets(wclamp):
            _, _, cache = verify_fn(params, cache,
                                    jnp.zeros((n_agents, wb), jnp.int32),
                                    z, z)
            s0 = snap_jit(cache, z, wb)
            cache = restore_jit(cache, s0, z, z, z,
                                jnp.zeros((n_agents,), bool))
    warm_board = todo.post(todo.empty(k_todos), 0,
                           jnp.zeros((k_todos,), bool), jnp.int32(1),
                           jnp.int32(100))
    for m in range(1, n_agents + 1):
        _ = claims_fn(warm_board, jnp.arange(1, m + 1, dtype=jnp.int32),
                      jnp.full((m,), 10, jnp.int32), jnp.int32(0))
    _ = complete_fn(warm_board, jnp.int32(0), jnp.int32(1), jnp.int32(5))
    _ = fold_fn(docs)
    warm = append_run_fn(docs[0], jnp.int32(0),
                         jnp.zeros((128,), jnp.int32), jnp.int32(0))
    jax.block_until_ready(warm.length)
    if delta_sync is not None:   # compile extract/apply outside timed region
        delta_mod.DeltaSync(docs[0], capacity=delta_capacity).sync(docs)

    t0 = time_fn()

    # --- Outliner: generates the skeleton, posts TODOs (both modes pay it).
    for _ in range(6 * k_todos // max(n_agents, 1) + 4):
        key, sub = jax.random.split(key)
        token, cache, pos = step_fn(params, cache, token, pos, sub)
    for k in range(k_todos):
        out_lam = out_lam.tick()
        board = todo.post(board, k, jnp.asarray(deps_np[k]), out_lam.time,
                          out_lam.client)
    pos = jnp.zeros((n_agents,), jnp.int32)

    gen_budget = int(round(task.base_tokens
                           * (task.par_inflation if mode == "parallel"
                              else 1.0)))
    stats = dict(gen=0, replay=0, steps=0, inval=0, collide=0, observe=0,
                 syncs=0, sync_bytes=0, agent_fail=0, agent_retry=0,
                 draft=0, accepted=0, rollback=0)
    merge_perm_seed = 0

    # Host-side mirrors: CRDT appends are buffered per agent and flushed at
    # observation boundaries (one jitted run-append per agent per sweep) so
    # the steady-state step costs exactly one jitted decode dispatch — the
    # LLM must dominate wall time for the seq/par comparison to be honest.
    host_len = np.zeros((k_todos,), np.int64)          # merged view lengths
    buffers: list[list[int]] = [[] for _ in range(n_agents)]
    buf_slot = [-1] * n_agents
    # Per-slot mirrors of flushed (committed) document content: the doc
    # drafter reads these LIVE lists, so anything one agent has flushed is
    # immediately draftable for every other agent — the CodeCRDT case
    # where the shared document predicts a row's continuation.
    slot_toks: list[list[int]] = [[] for _ in range(k_todos)]
    if drafter is not None and hasattr(drafter, "set_docs"):
        drafter.set_docs(slot_toks)
    done_count = 0
    board_dirty = True
    run_buf_cap = 128

    def flush_agent(i: int):
        nonlocal docs
        if buf_slot[i] < 0 or not buffers[i]:
            return
        toks = buffers[i]
        for off in range(0, len(toks), run_buf_cap):
            chunk = toks[off: off + run_buf_cap]
            arr = np.zeros((run_buf_cap,), np.int32)
            arr[: len(chunk)] = chunk
            docs[i] = append_run_fn(docs[i], jnp.int32(buf_slot[i]),
                                    jnp.asarray(arr), jnp.int32(len(chunk)))
        host_len[buf_slot[i]] += len(toks)
        slot_toks[buf_slot[i]].extend(toks)
        buffers[i] = []

    def sync_replicas():
        nonlocal docs, merge_perm_seed
        for i in range(n_agents):
            flush_agent(i)
        stats["syncs"] += 1
        if replicas > 1 and mapper is not None:
            mapper.gossip()               # page-table anti-entropy round
        if delta_sync is not None:
            docs = delta_sync.sync(docs)
            stats["sync_bytes"] = delta_sync.bytes_shipped
            return
        perm = np.random.default_rng(merge_perm_seed).permutation(n_agents)
        merge_perm_seed += 1
        m = fold_fn([docs[i] for i in perm])
        docs = [m for _ in range(n_agents)]
        stats["sync_bytes"] += delta_mod.full_state_wire_bytes(
            merge, n_agents, state_bytes)

    snap_len = {a.client: host_len.copy() for a in agents}

    def finish_agent(a: AgentState):
        nonlocal board, done_count, board_dirty
        flush_agent(a.row)
        a.lamport = a.lamport.observe(board.max_clock())
        board = complete_fn(board, jnp.int32(a.todo_id),
                            jnp.int32(a.client), a.lamport.time)
        done_count += 1
        board_dirty = True
        a.phase = IDLE
        buf_slot[a.row] = -1
        a.todo_id = -1
        sync_replicas()

    while True:
        # -- claims: all idle agents observe the SAME board snapshot --------
        idle = [a for a in agents if a.phase == IDLE]
        if idle and board_dirty:
            clients = jnp.asarray([a.client for a in idle], jnp.int32)
            clocks = jnp.asarray(
                [int(a.lamport.observe(board.max_clock()).time)
                 for a in idle], jnp.int32)
            board, ks, won = claims_fn(
                board, clients, clocks, jnp.int32(stats["steps"]))
            any_won = False
            for a, k, w, c in zip(idle, np.asarray(ks), np.asarray(won),
                                  np.asarray(clocks)):
                a.lamport = a.lamport._replace(time=jnp.int32(int(c)))
                if bool(w):
                    any_won = True
                    a.todo_id = int(k)
                    a.phase = PREFILL
                    a.reprefills = 0
                    a.queue = _prompt_tokens(task, a.todo_id, docs, vocab, rng)
                    a.hist = list(a.queue)
                    a.tokens_left = gen_budget
                    snap_len[a.client] = host_len.copy()
                    buf_slot[a.row] = a.todo_id
                    pos_h[a.row] = 0
                    if mixed_fn is None:
                        pos = pos.at[a.row].set(0)
                    recontextualize(a)
                else:
                    stats["collide"] += 1
            if not any_won:
                board_dirty = False      # wait for a completion to retry

        if all(a.phase == HALT for a in agents):
            break
        if done_count >= k_todos and all(
                a.phase in (IDLE, HALT) for a in agents):
            break
        if not any(a.phase in (PREFILL, GEN) for a in agents):
            # Deadlock guard: nothing runnable and nothing claimable yet.
            if done_count >= k_todos:
                break
            board_dirty = True
            stats["steps"] += 1
            if stats["steps"] > 20_000:
                break
            continue

        if mixed_fn is not None:
            # -- one token-budget mixed step: every pending prompt spends a
            # ≤ chunk_size slice AND every generating agent decodes one
            # token, in the same batched call — re-contextualization never
            # stalls the other agents' decode lanes.
            spans = np.zeros((n_agents,), np.int64)
            finishing: list[AgentState] = []
            for a in agents:
                if a.phase == PREFILL and a.needs_map:
                    # Unmapped row: no KV pages to write into.  Idle this
                    # lane (span 0) until the backoff expires and a re-map
                    # succeeds; positions never advanced, so nothing resets.
                    if not (stats["steps"] >= a.retry_at
                            and recontextualize(a)):
                        continue
                if a.phase == PREFILL and a.queue:
                    spans[a.row] = min(chunk_size, len(a.queue))
                elif a.phase == PREFILL:
                    a.phase = GEN
                    spans[a.row] = 1
                elif a.phase == GEN:
                    spans[a.row] = 1
            drafts: dict[int, list[int]] = {}
            if drafter is not None:
                # Widen decode lanes with drafter proposals.  The cap keeps
                # every speculative write inside the row's pre-mapped page
                # horizon AND guarantees the accepted run fits the agent's
                # remaining budget.
                for a in agents:
                    if a.phase != GEN or spans[a.row] != 1:
                        continue
                    cap = min(spec_k, a.tokens_left - 1,
                              max_len - int(pos_h[a.row]) - 1)
                    if cap <= 0:
                        continue
                    d = drafter.propose(a.hist, cap)[:cap]
                    if d:
                        drafts[a.row] = d
                        spans[a.row] = 1 + len(d)
            width = engine_mod.width_bucket(int(max(spans.max(), 1)),
                                            wclamp)
            toks = np.zeros((n_agents, width), np.int64)
            for a in agents:
                if spans[a.row] == 0:
                    continue
                if a.phase == PREFILL:
                    seg = a.queue[: int(spans[a.row])]
                    a.queue = a.queue[int(spans[a.row]):]
                    toks[a.row, :len(seg)] = seg
                    stats["replay"] += len(seg)
                else:
                    toks[a.row, 0] = tok_h[a.row]
                    d = drafts.get(a.row)
                    if d:
                        toks[a.row, 1:1 + len(d)] = d
            push_tables()
            key, sub = jax.random.split(key)
            start_h = jnp.asarray(pos_h, jnp.int32)   # pre-step positions
            if drafter is not None:
                snap = snap_jit(cache, start_h, width) if drafts else None
                preds_d, acc_d, cache = verify_fn(
                    params, cache, jnp.asarray(toks, jnp.int32), start_h,
                    jnp.asarray(spans, jnp.int32))
                preds = np.asarray(preds_d)
                acc = np.asarray(acc_d)
                sampled = preds[np.arange(n_agents),
                                np.clip(spans - 1, 0, width - 1)]
            else:
                nxt, cache = mixed_fn(params, cache,
                                      jnp.asarray(toks, jnp.int32),
                                      start_h,
                                      jnp.asarray(spans, jnp.int32), sub)
                sampled = np.asarray(nxt)
            stats["steps"] += 1
            roll_lo = np.zeros((n_agents,), np.int64)
            roll_hi = np.zeros((n_agents,), np.int64)
            replay_spans = np.zeros((n_agents,), np.int64)
            rolled = False
            for a in agents:
                if spans[a.row] == 0:
                    continue
                d = drafts.get(a.row)
                if d is not None:
                    # Speculative lane: commit the longest accepted prefix
                    # plus the verifier's bonus token; mark the rejected
                    # tail for bitwise rollback.
                    pos0 = int(pos_h[a.row])
                    appended, a_dev = draft_mod.accept_tokens(
                        d, acc[a.row], preds[a.row], a.tokens_left, None)
                    n_app = len(appended)
                    stats["draft"] += len(d)
                    stats["accepted"] += min(n_app, a_dev)
                    n_roll = int(spans[a.row]) - n_app
                    pos_h[a.row] += n_app
                    for t in appended:
                        buffers[a.row].append(int(t) % vocab)
                        a.hist.append(int(t))
                    tok_h[a.row] = int(appended[-1])
                    stats["gen"] += n_app
                    a.tokens_left -= n_app
                    if n_roll > 0:
                        stats["rollback"] += n_roll
                        roll_lo[a.row] = pos0 + n_app
                        roll_hi[a.row] = pos0 + int(spans[a.row])
                        replay_spans[a.row] = n_app
                        rolled = True
                    if a.tokens_left <= 0:
                        finishing.append(a)
                    continue
                pos_h[a.row] += int(spans[a.row])
                if a.phase == PREFILL:
                    if a.queue:
                        continue            # mid-prompt logits: discarded
                    a.phase = GEN           # chunk's last logits = 1st token
                tok_h[a.row] = int(sampled[a.row])
                buffers[a.row].append(int(sampled[a.row]) % vocab)
                if drafter is not None:
                    a.hist.append(int(sampled[a.row]))
                stats["gen"] += 1
                a.tokens_left -= 1
                if a.tokens_left <= 0:
                    finishing.append(a)
            if rolled:
                # Rejected-tail slots restored bitwise from the pre-verify
                # snapshot; recurrent state (if any) is restored to its
                # pre-verify value and re-advanced by replaying exactly the
                # committed tokens (attention re-writes are overwrites of
                # the same tokens at the same positions).
                cache = restore_jit(cache, snap, start_h,
                                    jnp.asarray(roll_lo.astype(np.int32)),
                                    jnp.asarray(roll_hi.astype(np.int32)),
                                    jnp.asarray(replay_spans > 0))
                if has_state and replay_spans.any():
                    w2 = engine_mod.width_bucket(int(replay_spans.max()),
                                                 wclamp)
                    _, _, cache = verify_fn(
                        params, cache,
                        jnp.asarray(toks[:, :w2], jnp.int32), start_h,
                        jnp.asarray(replay_spans, jnp.int32))
            for a in finishing:
                finish_agent(a)
        else:
            # -- one batched decode step (replay baseline) -------------------
            forced = np.array(token)      # writable host copy
            for a in agents:
                if a.phase == PREFILL and a.needs_map:
                    # Unmapped row: its writes land on the trash page, so
                    # the step is harmless — but its prompt must not be
                    # consumed.  On a successful re-map, restart from 0.
                    if stats["steps"] >= a.retry_at and recontextualize(a):
                        pos = pos.at[a.row].set(0)
                    else:
                        continue
                if a.phase == PREFILL and a.queue:
                    forced[a.row] = a.queue.pop(0)
                    stats["replay"] += 1
                elif a.phase == PREFILL:
                    a.phase = GEN
            token = jnp.asarray(forced)
            push_tables()
            key, sub = jax.random.split(key)
            token, cache, pos = step_fn(params, cache, token, pos, sub)
            stats["steps"] += 1
            sampled = np.array(token)

            # -- generation & completion ------------------------------------
            for a in agents:
                if a.phase != GEN:
                    continue
                buffers[a.row].append(int(sampled[a.row]) % vocab)
                stats["gen"] += 1
                a.tokens_left -= 1
                if a.tokens_left <= 0:
                    finish_agent(a)

        # -- observation sweep (paper §4.2) ----------------------------------
        if stats["steps"] % OBSERVE_EVERY == 0:
            sync_replicas()
            for a in agents:
                if a.phase not in (GEN, PREFILL):
                    continue
                delta = host_len - snap_len[a.client]
                stats["observe"] += int(delta.clip(0).sum())
                reads = task.reads.get(a.todo_id, ())
                if any(delta[r] > 0 for r in reads):
                    if a.reprefills < MAX_REPREFILL:
                        a.reprefills += 1
                        stats["inval"] += 1
                        a.queue = _prompt_tokens(task, a.todo_id, docs,
                                                 vocab, rng)
                        a.hist = list(a.queue)
                        a.phase = PREFILL
                        pos_h[a.row] = 0
                        if mixed_fn is None:
                            pos = pos.at[a.row].set(0)
                        recontextualize(a)
                    snap_len[a.client] = host_len.copy()

        if stats["steps"] > 20_000:   # safety valve
            for a in agents:
                a.phase = HALT
            break

    sync_replicas()
    if delta_sync is not None:
        # Drain capacity-overflow backlog (delta contract: convergence is
        # delayed, never lost): sync until the frontier reaches its fixed
        # point, so replicas are measurably converged before scoring.
        for _ in range(10_000):
            before = [np.asarray(x)
                      for x in jax.tree.leaves(delta_sync.frontier)]
            sync_replicas()
            after = [np.asarray(x)
                     for x in jax.tree.leaves(delta_sync.frontier)]
            if all(np.array_equal(b, a) for b, a in zip(before, after)):
                break
    wall = time_fn() - t0

    final = fold_fn(docs)
    digests = [int(digest_fn(d)) for d in docs]
    conflicts, total_decl = count_conflicts(final)
    return RunResult(
        task=task.name, mode=mode, n_agents=n_agents, wall_s=wall,
        gen_tokens=stats["gen"], replay_tokens=stats["replay"],
        steps=stats["steps"], invalidations=stats["inval"],
        claim_collisions=stats["collide"],
        observation_events=stats["observe"],
        semantic_conflicts=conflicts, declared_symbols=total_decl,
        converged=all(d == digests[0] for d in digests),
        digest=digests[0],
        merge_strategy=merge, sync_rounds=stats["syncs"],
        sync_bytes=int(stats["sync_bytes"]),
        kv_mode=kv, prefill_mode=prefill,
        shared_prefix_pages=mapper.shared_pages if mapper else 0,
        replicas=replicas,
        disaggregated=disaggregate,
        cross_replica_prefix_hits=getattr(mapper, "cross_replica_hits", 0),
        page_sync_bytes=getattr(mapper, "sync_bytes", 0),
        agent_failures=stats["agent_fail"],
        agent_retries=stats["agent_retry"],
        spec_decode=spec_decode,
        draft_tokens=stats["draft"],
        accepted_tokens=stats["accepted"],
        rollback_tokens=stats["rollback"],
    )


def make_sim_llm(seed: int = 0):
    """Tiny but real decoder used as the agents' LLM (CPU-friendly)."""
    import repro.configs as configs
    cfg = configs.reduced(configs.get("olmo-1b"), d_model=64,
                          vocab=512).replace(num_layers=2)
    params = lm.init(jax.random.PRNGKey(seed), cfg)
    return cfg, params


def main() -> None:
    """Run one task end-to-end with a chosen replica-merge strategy.

    PYTHONPATH=src python -m repro.agents.orchestrator \
        --task coupled --mode parallel --agents 4 --merge delta
    """
    import argparse
    from repro.agents.tasks import TASKS

    ap = argparse.ArgumentParser()
    ap.add_argument("--task", default=next(iter(TASKS)), choices=list(TASKS))
    ap.add_argument("--mode", default="parallel",
                    choices=["sequential", "parallel"])
    ap.add_argument("--agents", type=int, default=4)
    ap.add_argument("--merge", default="allgather",
                    choices=["allgather", "pmax", "delta"])
    ap.add_argument("--delta-capacity", type=int, default=64)
    ap.add_argument("--kv", default="dense", choices=["dense", "paged"],
                    help="KV cache layout for the agents' decode engine")
    ap.add_argument("--prefill", default="replay",
                    choices=["replay", "ragged", "chunked"],
                    help="prompt (re-)contextualization: token-by-token "
                         "replay, or chunked admission through the "
                         "token-budget mixed step ('ragged' is a "
                         "backward-compatible alias for 'chunked')")
    ap.add_argument("--page-size", type=int, default=64,
                    help="paged-KV page size; small pages (8-16) let the "
                         "task/TODO header share across re-contextualizations")
    ap.add_argument("--chunk-size", type=int, default=32,
                    help="max prompt tokens one mixed step spends per agent "
                         "while other agents keep decoding")
    ap.add_argument("--replicas", type=int, default=1,
                    help="page-table metadata replicas (> 1 requires "
                         "--kv paged): agents are partitioned round-robin "
                         "and the run reports cross-replica prefix hits "
                         "plus page-table anti-entropy bytes")
    ap.add_argument("--spec-decode", default="off",
                    choices=["off", "ngram", "doc"],
                    help="speculative decoding through the mixed step: "
                         "'ngram' drafts from each agent's own "
                         "prompt+generated history (prompt lookup), 'doc' "
                         "drafts from the shared CRDT document content "
                         "with n-gram fallback (requires --prefill chunked)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="max draft tokens proposed per agent per step")
    ap.add_argument("--kv-quant", default="off",
                    choices=["off", "int8", "fp8"],
                    help="quantized page pools (requires --kv paged): pools "
                         "store int8/fp8 values plus per-row f32 scales and "
                         "decode dequantizes inside the fused page walk")
    ap.add_argument("--disaggregate", action="store_true",
                    help="prefill/decode role partition over the metadata "
                         "replicas (requires --replicas >= 2): agent 0 "
                         "homes on the prefill replica and publishes the "
                         "shared task-header chain; the other agents home "
                         "on decode replicas and adopt it cross-replica")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    from repro.runtime import compile_cache
    compile_cache.enable()

    cfg, params = make_sim_llm(args.seed)
    # The sim-llm's 16-wide heads are narrower than the Pallas kernels'
    # 128-lane tiles, so this CLI runs the jnp path on every platform.
    r = run_task(cfg, params, TASKS[args.task], mode=args.mode, impl="ref",
                 n_agents=args.agents, seed=args.seed, merge=args.merge,
                 delta_capacity=args.delta_capacity, kv=args.kv,
                 prefill=args.prefill, page_size=args.page_size,
                 chunk_size=args.chunk_size, replicas=args.replicas,
                 spec_decode=args.spec_decode, spec_k=args.spec_k,
                 kv_quant=args.kv_quant, disaggregate=args.disaggregate)
    for k, v in sorted(vars(r).items()):
        print(f"{k}: {v}")


if __name__ == "__main__":
    main()

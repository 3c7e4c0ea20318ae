"""Serving engine: prefill/decode steps, host-side generation loop, and the
fused decode+coordination step (the paper's architecture on a mesh).

``make_serve_step`` builds the pure function the multi-pod dry-run lowers for
decode shapes.  ``make_fused_serve_step`` additionally threads the CRDT
coordination state through the step: each data-parallel replica hosts a set
of agents (its decode-batch rows), appends their tokens to its own SlotDoc
replica, and the replicas converge through a pmax (or all-gather) collective
merge — observation-driven coordination fused into the serving step, with
the collective playing the role of the paper's WebSocket relay.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import delta as delta_mod
from repro.core import doc as doc_mod
from repro.core import gset, merge as merge_mod
from repro.kernels import ops
from repro.models import lm
from repro.models.config import ModelConfig

Params = Any


def sample_token(logits: jax.Array, rng: jax.Array,
                 temperature: float = 0.0) -> jax.Array:
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(rng, logits / temperature).astype(jnp.int32)


# The dense decode and prefill constructors keep the jnp path by default on
# every platform: kernels/decode_attention.py (the dense-cache decode
# kernel) does not compile for v5e yet.  The served paged path goes through
# make_mixed_step_fn / make_verify_step_fn, which take the platform default.
def make_serve_step(cfg: ModelConfig, *, impl: str = "ref",
                    temperature: float = 0.0):
    """(params, cache, token[B], pos[B], rng) -> (next_token, cache, pos+1)."""

    def serve_step(params, cache, token, pos, rng):
        logits, cache = lm.decode_step(params, cfg, token, cache, pos,
                                       impl=impl)
        nxt = sample_token(logits, rng, temperature)
        return nxt, cache, pos + 1

    return serve_step


def make_prefill_fn(cfg: ModelConfig, *, impl: str = "ref"):
    def prefill_fn(params, cache, tokens, prefix_embeds=None, enc_frames=None):
        return lm.prefill(params, cfg, tokens, cache,
                          prefix_embeds=prefix_embeds, enc_frames=enc_frames,
                          impl=impl)

    return prefill_fn


def make_ragged_prefill_fn(cfg: ModelConfig, *, impl: str = "ref"):
    """(params, cache, tokens [B, P], lengths i32[B]) -> (logits, cache).

    Rows with ``lengths[b] == 0`` keep their cache.  This is the one-shot
    oracle the mixed step is verified against — serving itself admits
    prompts chunk by chunk through ``make_mixed_step_fn``.
    """
    def prefill_fn(params, cache, tokens, lengths):
        return lm.prefill(params, cfg, tokens, cache, impl=impl,
                          lengths=lengths)

    return prefill_fn


PROMPT_BUCKETS = (8, 16, 32, 64, 128, 256, 512, 1024)


def bucket_len(n: int, buckets=PROMPT_BUCKETS, max_len: Optional[int] = None
               ) -> int:
    """Smallest bucket >= n — bounds prefill recompiles.

    ``max_len`` clamps: a prompt longer than the largest bucket still lands
    (in one [B, max_len] call) as long as it fits the cache — the clamp is
    applied BEFORE raising, so only prompts that genuinely cannot fit fail.
    """
    if max_len is not None and n > max_len:
        raise ValueError(f"prompt length {n} exceeds max_len {max_len}")
    for b in buckets:
        if n <= b:
            return b if max_len is None else min(b, max_len)
    if max_len is not None:
        return max_len                # longer than every bucket, still fits
    raise ValueError(f"prompt length {n} exceeds largest bucket {buckets[-1]}")


# ---------------------------------------------------------------------------
# Token-budget mixed step (chunked prefill fused with decode)
# ---------------------------------------------------------------------------

def make_mixed_step_fn(cfg: ModelConfig, *, impl: Optional[str] = None,
                       temperature: float = 0.0):
    """(params, cache, tokens [B, C], start [B], span [B], rng)
    -> (next_token [B], cache).

    One call spends every row's span — 1 token for decoding rows, a prompt
    chunk for rows being admitted, 0 for idle rows — so admission never
    stalls decode.  ``next_token`` is sampled from each row's last valid
    span position (garbage for span-0 rows; callers ignore it).  A model
    with experts appends the step's two expert-pair counts (pairs computed
    on held experts, the busiest held expert's pairs in one layer):
    ``next_token`` is then i32[B + 2], read back in the same transfer.
    """
    impl = impl or ops.default_impl()
    def mixed_step(params, cache, tokens, start, span, rng):
        logits, cache, counts = lm.mixed_step(params, cfg, tokens, cache,
                                              start, span, impl=impl)
        nxt = sample_token(logits, rng, temperature)
        if cfg.moe is None:
            return nxt, cache
        return jnp.concatenate([nxt.astype(jnp.int32),
                                counts.astype(jnp.int32)]), cache

    return mixed_step


def make_verify_step_fn(cfg: ModelConfig, *, impl: Optional[str] = None):
    """(params, cache, tokens [B, C], start [B], span [B])
    -> (preds [B, C], accepted [B], cache).

    The speculative-decoding verify step (greedy only — acceptance
    compares argmax streams, so there is no rng): one all-logits mixed
    step over each row's [last_committed, draft...] span plus the per-row
    longest-accepted-prefix count.  ``preds[b, j]`` is the greedy token
    after span position j — non-drafting rows decode normally by reading
    ``preds[b, span[b]-1]``, so one compiled fn serves every lane.
    """
    impl = impl or ops.default_impl()
    def verify_step(params, cache, tokens, start, span):
        return lm.verify_step(params, cfg, tokens, cache, start, span,
                              impl=impl)

    return verify_step


def width_bucket(n: int, chunk: int) -> int:
    """Smallest power-of-two >= n, clamped to ``chunk`` — the mixed step
    compiles once per bucketed span width instead of once per width."""
    n = max(1, min(n, chunk))
    return min(1 << (n - 1).bit_length(), chunk)


def mixed_width_buckets(chunk: int) -> tuple[int, ...]:
    """Every width ``width_bucket`` can produce for spans in [1, chunk]."""
    out = []
    w = 1
    while w < chunk:
        out.append(w)
        w <<= 1
    out.append(chunk)
    return tuple(out)


def backoff_steps(rid: int, attempt: int, *, base: int = 4,
                  cap: int = 64) -> int:
    """Retry delay (in steps) for attempt ``attempt`` of request/agent
    ``rid``: capped exponential backoff plus deterministic jitter.

    The jitter is a pure hash of (rid, attempt), so re-admission order is
    reproducible across runs (the fault benches and chaos harness gate on
    deterministic counters) while still de-synchronizing retries that failed
    together — the reason jitter exists at all.
    """
    delay = min(cap, base << max(0, attempt - 1))
    h = (rid * 0x9E3779B1 + attempt * 0x85EBCA77) & 0xFFFFFFFF
    h ^= h >> 16
    return delay + h % max(1, delay // 2)


# ---------------------------------------------------------------------------
# Fused decode + CRDT coordination
# ---------------------------------------------------------------------------

def replicate_coord(coord: Any, n_replicas: int) -> Any:
    """Stack a coordination state into per-replica rows [R, ...]."""
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n_replicas,) + x.shape), coord)


def with_delta_frontier(coord: dict) -> dict:
    """Attach the delta-sync frontier to a coordination dict.

    The delta merge strategy threads a shared frontier (the previous sync
    point) alongside the CRDT state; it rides in the coord dict under
    ``"frontier"`` so the fused step's signature and shardings are unchanged.
    """
    state = {k: v for k, v in coord.items() if k != "frontier"}
    return dict(coord, frontier=delta_mod.frontier(state))


def make_coord_merge(mesh: Mesh, dp_axes: tuple[str, ...],
                     strategy: str = "pmax", *, delta_capacity: int = 64):
    """Collective merge of stacked per-replica CRDT state (leaves [R, ...]).

    For ``strategy="delta"`` the coord dict must carry a ``"frontier"`` entry
    (see ``with_delta_frontier``); deltas beyond it ring-circulate instead of
    the full state.
    """
    axis_sizes = tuple(mesh.shape[a] for a in dp_axes)

    def local(stacked):
        state = jax.tree.map(lambda x: jnp.squeeze(x, 0), stacked)
        if strategy == "delta":
            fr = state.pop("frontier")
            merged, fr = merge_mod.delta_merge(
                state, fr, dp_axes, axis_sizes, capacity=delta_capacity)
            merged = dict(merged, frontier=fr)
        else:
            merged = merge_mod.collective_merge(state, dp_axes, strategy)
        return jax.tree.map(lambda x: x[None], merged)

    def merge_fn(coord_stacked):
        specs = jax.tree.map(
            lambda x: P(dp_axes, *([None] * (x.ndim - 1))), coord_stacked)
        return jax.shard_map(local, mesh=mesh, in_specs=(specs,),
                             out_specs=specs, check_vma=False)(coord_stacked)

    return merge_fn


class GossipFns:
    """Jitted delta-sync triple for host-side replica gossip.

    The host analogue of ``make_coord_merge(strategy="delta")``: where the
    fused step syncs coordination state through an in-mesh ``ppermute``
    ring, host-level replicas (multi-engine serving, the replica simulator)
    gossip the same frontiers/deltas over an explicit — possibly faulty —
    channel.  One instance per state *template*: the jitted callables cache
    on the pytree structure, so every replica of the same store shares the
    compilations.
    """

    def __init__(self, template: Any, capacity: int):
        self.capacity = capacity
        self.genesis = delta_mod.frontier_jit(template)
        self._apply = delta_mod.apply_jit

    def extract(self, state: Any, frontier: Any) -> tuple[Any, Any]:
        """(delta beyond ``frontier``, frontier actually shipped)."""
        return delta_mod.extract_jit(state, frontier, self.capacity)

    def apply(self, state: Any, delta: Any) -> Any:
        return self._apply(state, delta)


def make_gossip_fns(template: Any, capacity: int = 32) -> GossipFns:
    """Build the jitted (genesis frontier, extract, apply) gossip triple for
    a CRDT state template (any registered type or dict container)."""
    return GossipFns(template, capacity)


def make_fused_serve_step(cfg: ModelConfig, mesh: Mesh,
                          dp_axes: tuple[str, ...], *, impl: str = "ref",
                          merge_strategy: str = "pmax",
                          merge_every: int = 1, delta_capacity: int = 64,
                          temperature: float = 0.0):
    """Decode one token per agent stream AND converge coordination state.

    Inputs (leading dims):
      params                     model-sharded
      cache                      batch-sharded over dp_axes
      token, pos: [B]            agent streams (B rows = N agents × replicas)
      slots: [B] i32             each agent's claimed doc slot
      active: [B] bool           streams still generating
      coord: {doc: SlotDoc, heartbeats: GCounter} leaves stacked [R, ...]
      step: i32                  global step (for merge cadence)

    The local replica appends its rows' tokens into its own doc replica;
    the collective join then makes every replica observe everyone's edits —
    deterministic convergence with one-collective staleness.  ``merge_every``
    trades staleness for collective bytes (a §Perf axis; the paper's 50 ms
    sync delay is the analogous knob).

    With ``merge_strategy="delta"`` the coord dict additionally carries a
    ``"frontier"`` entry (build it with ``with_delta_frontier``) and each
    sync ships O(Δ) delta buffers around the replica ring instead of O(S)
    state — see core/delta.py.

    ``temperature > 0`` samples instead of argmax-decoding; pass an rng key
    as the trailing ``rng`` argument (split per step by the caller).

    ``impl`` stays ``"ref"`` on every platform: the step is one GSPMD
    program over the mesh, and XLA cannot partition a Mosaic kernel.
    """
    merge_fn = make_coord_merge(mesh, dp_axes, merge_strategy,
                                delta_capacity=delta_capacity)
    n_rep = 1
    for a in dp_axes:
        n_rep *= mesh.shape[a]

    def append_local(coord_stacked, token, slots, active):
        def local(stacked, tok, sl, act):
            state = jax.tree.map(lambda x: jnp.squeeze(x, 0), stacked)
            d = doc_mod.append_token_batch(state["doc"], sl, tok, act)
            hb = state["heartbeats"]
            hb = gset.GCounter(hb.counts + 1)          # every worker beats
            out = dict(state, doc=d, heartbeats=hb)
            return jax.tree.map(lambda x: x[None], out)

        specs = jax.tree.map(
            lambda x: P(dp_axes, *([None] * (x.ndim - 1))), coord_stacked)
        bspec = P(dp_axes)
        return jax.shard_map(local, mesh=mesh,
                             in_specs=(specs, bspec, bspec, bspec),
                             out_specs=specs, check_vma=False)(
            coord_stacked, token, slots, active)

    def serve_step(params, cache, token, pos, slots, active, coord, step,
                   rng=None):
        logits, cache = lm.decode_step(params, cfg, token, cache, pos,
                                       impl=impl)
        if temperature > 0.0 and rng is None:
            raise ValueError("temperature > 0 requires an rng key")
        nxt = sample_token(logits, rng, temperature)
        nxt = jnp.where(active, nxt, token)
        coord = append_local(coord, nxt, slots, active)
        if merge_every == 1:
            coord = merge_fn(coord)
        else:
            coord = jax.lax.cond(step % merge_every == 0,
                                 merge_fn, lambda c: c, coord)
        pos = pos + jnp.where(active, 1, 0)
        return nxt, cache, pos, coord

    return serve_step


# ---------------------------------------------------------------------------
# Host-side engine (CPU benchmarks / agents layer)
# ---------------------------------------------------------------------------

class Engine:
    """Single-process serving engine wrapping jitted prefill/decode.

    Supports continuous batching at token granularity: rows carry per-row
    position and active flags; new requests can be swapped into inactive
    rows between steps.
    """

    def __init__(self, cfg: ModelConfig, params: Params, *, batch: int,
                 max_len: int, impl: str = "ref", temperature: float = 0.0,
                 paged: bool = False, page_size: int = 64):
        self.cfg = cfg
        self.params = params
        self.batch = batch
        self.max_len = max_len
        self.paged = paged
        self.page_size = page_size
        # Donate the cache: without donation XLA keeps the input and output
        # KV cache alive simultaneously — 2x resident HBM on the largest
        # buffer in the system — and loses the in-place cache update.
        self._prefill = jax.jit(make_prefill_fn(cfg, impl=impl),
                                donate_argnums=(1,))
        self._step = jax.jit(make_serve_step(cfg, impl=impl,
                                             temperature=temperature),
                             donate_argnums=(1,))
        self.reset()

    def reset(self):
        self.cache = lm.init_cache(self.cfg, self.batch, self.max_len,
                                   paged=self.paged,
                                   page_size=self.page_size)
        if self.paged:
            from repro.models import attention
            self.cache = lm.set_block_tables(
                self.cache, attention.default_block_tables(
                    self.batch, self.max_len, self.page_size))
        self.pos = jnp.zeros((self.batch,), jnp.int32)
        self.token = jnp.zeros((self.batch,), jnp.int32)
        self.rng = jax.random.PRNGKey(0)
        # Host mirror of max(pos): the paged-full guard must not force a
        # device sync per step.  Callers doing per-row pos surgery reset
        # rows to 0, which can only lower the true max — the mirror stays
        # a safe upper bound.
        self._pos_ceiling = 0

    def prefill(self, tokens: jax.Array, **stubs):
        """Uniform prompt for all rows. tokens: [B, P]."""
        logits, self.cache = self._prefill(self.params, self.cache, tokens,
                                           **stubs)
        self.pos = jnp.full((self.batch,),
                            tokens.shape[1] + self.cfg.num_prefix_tokens,
                            jnp.int32)
        self._pos_ceiling = tokens.shape[1] + self.cfg.num_prefix_tokens
        self.token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return self.token

    def step(self) -> jax.Array:
        if self.paged and self._pos_ceiling >= self.max_len:
            raise ValueError(
                f"paged cache is full (pos {self._pos_ceiling} >= max_len "
                f"{self.max_len}); a dense cache ring-wraps, pages do not — "
                "bound generation or raise max_len")
        self.rng, sub = jax.random.split(self.rng)
        self.token, self.cache, self.pos = self._step(
            self.params, self.cache, self.token, self.pos, sub)
        self._pos_ceiling += 1
        return self.token

    def generate(self, tokens: jax.Array, steps: int, **stubs) -> jax.Array:
        outs = [self.prefill(tokens, **stubs)]
        for _ in range(steps - 1):
            outs.append(self.step())
        return jnp.stack(outs, axis=1)

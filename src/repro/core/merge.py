"""Replica merge strategies — "the collective IS the relay" (DESIGN.md §2).

The paper syncs replicas through a Hocuspocus WebSocket relay (median 50 ms).
On a TPU mesh the natural substitute is a collective over the replica axis.
Because every CRDT in repro.core is a join-semilattice whose join is an
elementwise (masked) max, two strategies are available:

  * ``allgather_merge`` — gather all N replicas, fold the exact join locally.
    O(N·S) bytes on the interconnect.  This is the paper-faithful baseline:
    every agent observes every other replica's full state (the O(N×U)
    observation overhead made literal).

  * ``pmax_merge`` — express the join directly as ``lax.pmax``:
      - G-types (counter/set/log/RGA/SlotDoc): masked elementwise max is the
        join itself;
      - LWW banks: pack (clock, client) into one int32 key, pmax resolves the
        lexicographic winner, then a second masked pmax carries each payload
        field (exact since (clock, client) pairs are unique across writers).
    O(S) bytes independent of N — the beyond-paper optimization of the
    coordination layer.

  * ``delta_merge`` — delta-state sync (core/delta.py): each replica extracts
    the ops beyond a shared frontier into a fixed-capacity buffer, the
    buffers circulate the replica ring via ``lax.ppermute`` (N-1 hops), and
    every hop joins the received delta locally.  O(Δ) bytes per link per
    sync — the winning strategy when edits per sync interval are small
    relative to state size (measured in benchmarks/bench_merge.py).

All three are exact joins: they commute, associate, and are idempotent, so
the merged state is identical on every replica — strong eventual consistency
with *bounded* (one-collective) staleness.  ``delta_merge`` additionally
threads a frontier: overflowing deltas (edits beyond the buffer capacity)
stay local and ship on a later sync, delaying convergence without ever
losing it.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core import counter as counter_mod
from repro.core import delta as delta_mod
from repro.core import doc as doc_mod
from repro.core import gset, lww, rga, todo
from repro.core.clock import unpack_key

INT32_MIN = jnp.iinfo(jnp.int32).min

# ---------------------------------------------------------------------------
# Local (pairwise) joins — registry keyed by CRDT type.
# ---------------------------------------------------------------------------

_JOINS: dict[type, Callable[[Any, Any], Any]] = {
    lww.LWWBank: lww.merge,
    gset.GCounter: lambda a, b: a.join(b),
    gset.GSet: lambda a, b: a.join(b),
    gset.GLog: lambda a, b: a.join(b),
    rga.RGA: rga.merge,
    doc_mod.SlotDoc: doc_mod.merge,
    todo.TodoBoard: lambda a, b: todo.TodoBoard(lww.merge(a.bank, b.bank)),
    counter_mod.PNCounter: lambda a, b: a.join(b),
}


def is_crdt(x: Any) -> bool:
    return type(x) in _JOINS


def join(a: Any, b: Any) -> Any:
    """Pairwise join of two replica states (any registered CRDT or a
    container pytree whose CRDT nodes are treated atomically)."""
    fn = _JOINS.get(type(a))
    if fn is not None:
        return fn(a, b)
    return jax.tree.map(join, a, b, is_leaf=is_crdt)


def fold_join(states: list[Any]) -> Any:
    """Exact join of many replicas (host-side list)."""
    return functools.reduce(join, states)


def tree_join_stacked(stacked: Any) -> Any:
    """Join replicas stacked on a leading axis (from all_gather)."""
    n = jax.tree.leaves(stacked)[0].shape[0]
    take = lambda s, i: jax.tree.map(lambda x: x[i], s)

    def body(i, acc):
        return join(acc, take(stacked, i))

    return jax.lax.fori_loop(1, n, body, take(stacked, 0))


# ---------------------------------------------------------------------------
# Collective merges (use inside shard_map over ``axis_name``).
# ---------------------------------------------------------------------------

def allgather_merge(state: Any, axis_name: str) -> Any:
    """Paper-faithful: every replica observes every replica, folds locally."""
    gathered = jax.tree.map(
        lambda x: jax.lax.all_gather(x, axis_name, axis=0), state)
    return tree_join_stacked(gathered)


def _pmax(x: jax.Array, axis_name: str) -> jax.Array:
    if x.dtype == jnp.bool_:
        return jax.lax.pmax(x.astype(jnp.int32), axis_name).astype(jnp.bool_)
    return jax.lax.pmax(x, axis_name)


def _masked_pmax(x: jax.Array, valid: jax.Array, axis_name: str) -> jax.Array:
    """pmax where invalid lanes contribute the identity (-inf / INT_MIN)."""
    v = valid.reshape(valid.shape + (1,) * (x.ndim - valid.ndim))
    if x.dtype == jnp.bool_:
        # Non-winners contribute False, so OR returns exactly the winner's bits.
        return _pmax(x & v, axis_name)
    if jnp.issubdtype(x.dtype, jnp.floating):
        neutral = jnp.asarray(-jnp.inf, x.dtype)
    else:
        neutral = jnp.asarray(jnp.iinfo(x.dtype).min, x.dtype)
    out = jax.lax.pmax(jnp.where(v, x, neutral), axis_name)
    # Lanes no replica has observed keep their (identical) local default so
    # the merged state is bit-equal to the fold join.  Payloads never carry
    # the neutral value themselves (tokens/clocks/lengths are >= -1).
    return jnp.where(out == neutral, x, out)


def _pmax_lww(bank: lww.LWWBank, axis_name: str) -> lww.LWWBank:
    key = bank.key
    win_key = jax.lax.pmax(key, axis_name)
    i_win = key == win_key
    payload = {
        name: _masked_pmax(arr, i_win, axis_name)
        for name, arr in bank.payload.items()
    }
    clock, client = unpack_key(win_key)
    return lww.LWWBank(clock=clock, client=client, payload=payload)


def pmax_merge(state: Any, axis_name: str) -> Any:
    """O(S)-byte join via pmax collectives (see module docstring)."""
    t = type(state)
    if t is lww.LWWBank:
        return _pmax_lww(state, axis_name)
    if t is todo.TodoBoard:
        return todo.TodoBoard(_pmax_lww(state.bank, axis_name))
    if t in (gset.GCounter, gset.GSet, counter_mod.PNCounter):
        return jax.tree.map(lambda x: _pmax(x, axis_name), state)
    if t is gset.GLog:
        valid = state.valid_mask()
        fields = {k: _masked_pmax(v, valid, axis_name)
                  for k, v in state.fields.items()}
        return gset.GLog(count=_pmax(state.count, axis_name), fields=fields)
    if t is rga.RGA:
        valid = state.valid_mask()
        return rga.RGA(
            count=_pmax(state.count, axis_name),
            op_clock=_masked_pmax(state.op_clock, valid, axis_name),
            origin=_masked_pmax(state.origin, valid, axis_name),
            token=_masked_pmax(state.token, valid, axis_name),
            deleted=_pmax(state.deleted, axis_name),
        )
    if t is doc_mod.SlotDoc:
        valid = doc_mod.valid_mask(state)
        return doc_mod.SlotDoc(
            tokens=_masked_pmax(state.tokens, valid, axis_name),
            length=_pmax(state.length, axis_name),
            owner=_pmax(state.owner, axis_name),
        )
    # Container pytree: recurse into CRDT nodes.
    return jax.tree.map(lambda s: pmax_merge(s, axis_name), state, is_leaf=is_crdt)


def _pmin(x: jax.Array, axis_name) -> jax.Array:
    if x.dtype == jnp.bool_:
        # AND across replicas: only bits everyone has set survive.
        return ~_pmax(~x, axis_name)
    return jax.lax.pmin(x, axis_name)


def delta_merge(state: Any, frontier: Any, axis_names, axis_sizes,
                *, capacity: int = 64) -> tuple[Any, Any]:
    """Delta-state ring sync across the replica axis (use inside shard_map).

    ``frontier`` must be the SHARED frontier of the previous sync round
    (identical on every replica; initially ``delta.frontier(initial_state)``
    replicated).  Each replica extracts its delta beyond the frontier, the
    deltas circulate the ring in N-1 ``ppermute`` hops, and each hop joins
    the received delta.  Multi-axis replica grids (e.g. ("pod", "data"))
    sync as sequential per-axis rings — after the first axis' ring all
    members of that axis agree, so the next axis' ring forwards the already-
    combined deltas.

    Returns ``(merged_state, new_frontier)``.  The new frontier is the pmin
    of every replica's post-merge observation watermark — exactly the ops
    that reached EVERY replica — so it is identical everywhere and anything
    that overflowed ``capacity`` on any hop (and therefore missed some
    replicas) stays ahead of the frontier and re-ships next round.
    """
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    if isinstance(axis_sizes, int):
        axis_sizes = (axis_sizes,)

    for axis_name, n in zip(axis_names, axis_sizes):
        d, _ = delta_mod.extract(state, frontier, capacity)
        perm = [(i, (i + 1) % n) for i in range(n)]
        for _ in range(n - 1):
            d = jax.tree.map(
                lambda x: jax.lax.ppermute(x, axis_name, perm), d)
            state = delta_mod.apply(state, d)
    new_frontier = jax.tree.map(
        lambda x: _pmin(x, axis_names), delta_mod.frontier(state))
    return state, new_frontier


def collective_merge(state: Any, axis_name: str, strategy: str = "pmax") -> Any:
    if strategy == "pmax":
        return pmax_merge(state, axis_name)
    if strategy == "allgather":
        return allgather_merge(state, axis_name)
    if strategy == "delta":
        raise ValueError(
            "delta merge threads a frontier — call merge.delta_merge (or "
            "engine.make_coord_merge(strategy='delta')) instead")
    raise ValueError(f"unknown merge strategy: {strategy}")

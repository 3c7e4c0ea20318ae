"""Spans of the serving step, in memory and on the profiler's clock.

One recorder per process, off by default; importing this module changes
nothing.  ``enable()`` turns it on, ``disable()`` off, and ``drain()``
returns what it recorded and forgets it.  It writes no file: whoever turns
it on reads ``drain()``.

  * ``span(name, rid=None)`` is a context manager.  Off, it returns one
    shared no-op context after a single flag check.  On, it opens
    ``jax.profiler.TraceAnnotation(name)``, so the span lands on the
    profiler's host plane, beside the device ops, whenever a profile is
    running; and it records a ``Span`` in memory.
  * ``interval(name, t0_ns, t1_ns, rid)`` records, only while on, a
    finished interval whose ends the caller stamped (per-request times).

A ``Span`` is ``(name, t0_ns, t1_ns, parent, rid)``.  Times are
``time.perf_counter_ns()``; ``parent`` is the index, in the same
``drain()`` list, of the span that was open when this one opened (None at
the top and for intervals).  A span's self time is its duration minus what
its children cover.  Spans nest per thread of control: the recorder keeps
one stack, which is right for the engine's single-threaded step.

Names ``ContinuousBatchingEngine`` records (``serving/scheduler.py``):

  engine.step      one ``step()`` call; the spans below sit directly in it
  engine.admit     deadlines, shedding and ``admit()`` (again after rows free)
  engine.compose   the spans of each row, the drafts, then the token matrix
                   (twice a step: page growth in between can zero a span)
  engine.pages     page growth, copy-on-write copies, the block-table push
  engine.upload    the tokens, starts and spans sent to the device
  engine.dispatch  the rng split and the mixed (or verify) step call; both
                   return before the device finishes
  engine.sync      reading the sampled tokens back: the wait for the device
  engine.commit    appending tokens, freeing rows, rolling back drafts
  engine.queued    interval, per binding of a request to a row: from its
                   submission, or from the preemption that re-queued it,
                   to the binding
  engine.prefill   interval, once per request: from the last binding
                   before its first token to that token's append

A request preempted before its first token records ``engine.queued``
twice and ``engine.prefill`` once, from its last binding, so the prefill
interval leaves out the work the preemption threw away (that shows as
queueing and as a longer TTFT).  One preempted after its first token
records another ``engine.queued`` and no second ``engine.prefill``.
"""
from __future__ import annotations

import contextlib
import time
from typing import NamedTuple, Optional

import jax


class Span(NamedTuple):
    name: str
    t0_ns: int
    t1_ns: int
    parent: Optional[int]
    rid: Optional[int]


_OFF = contextlib.nullcontext()    # what ``span()`` returns while off
_on = False
_spans: list = []        # Span, or None for one still open
_stack: list[int] = []   # indices in ``_spans`` of the open spans


class _On:
    __slots__ = ("name", "rid", "idx", "parent", "t0", "ann")

    def __init__(self, name: str, rid: Optional[int]):
        self.name = name
        self.rid = rid

    def __enter__(self):
        self.ann = jax.profiler.TraceAnnotation(self.name)
        self.ann.__enter__()
        self.parent = _stack[-1] if _stack else None
        self.idx = len(_spans)
        _spans.append(None)
        _stack.append(self.idx)
        self.t0 = time.perf_counter_ns()
        return None

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        _stack.pop()
        _spans[self.idx] = Span(self.name, self.t0, t1, self.parent,
                                self.rid)
        self.ann.__exit__(*exc)
        return False


def span(name: str, rid: Optional[int] = None):
    """Context manager around one named piece of host work."""
    if not _on:
        return _OFF
    return _On(name, rid)


def interval(name: str, t0_ns: int, t1_ns: int, rid: Optional[int]) -> None:
    """Record a finished interval (memory only, only while on)."""
    if _on:
        _spans.append(Span(name, t0_ns, t1_ns, None, rid))


def enable() -> None:
    global _on
    _on = True


def disable() -> None:
    global _on
    _on = False


def drain() -> list[Span]:
    """The spans and intervals recorded so far, in the order they opened,
    and forget them.  Call it between steps, with no span open."""
    global _spans
    if _stack:
        raise RuntimeError(f"drain() with {len(_stack)} span(s) open")
    out, _spans = _spans, []
    return out

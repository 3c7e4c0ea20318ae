"""Profiler trace: start and stop it, read the ``.xplane.pb`` it writes, and
reduce it to device busy time, kernel time and idle gaps.

Device operations are the events of each ``/device:TPU:<n>`` plane's
"XLA Ops" line; host spans are the events of the host plane's Python
thread, where ``StepTraceAnnotation("serve_step")`` and
``TraceAnnotation("generator")`` from ``harness/drive.py`` land beside JAX's own
dispatch spans.  All events share the profiler's clock (nanoseconds).
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
STEP_SPAN = "serve_step"
# The layer scan's while loop spans the ops it runs: it counts in the busy
# union, but not in the list of ops that took most time.
CONTAINER = "while"


def op_name(text: str) -> str:
    """``%fusion.93 = f32[32,32] fusion(...)`` -> ``fusion.93``."""
    return text.split(" = ", 1)[0].lstrip("%")


def start(log_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # Python function tracing floods the host
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


@dataclass
class Trace:
    # device id -> [(name, start_ns, dur_ns, stats)]
    ops: dict[int, list[tuple]] = field(default_factory=dict)
    # host Python-thread spans: [(name, start_ns, dur_ns)]
    host: list[tuple] = field(default_factory=list)

    @property
    def steps(self) -> list[tuple]:
        return [h for h in self.host if h[0] == STEP_SPAN]

    @property
    def window(self) -> tuple[float, float] | None:
        """From the first traced step's start to the last one's end."""
        s = self.steps
        if not s:
            return None
        return (min(h[1] for h in s), max(h[1] + h[2] for h in s))

    def ops_in_window(self, dev: int) -> list[tuple]:
        w = self.window
        return [o for o in self.ops.get(dev, [])
                if w and o[1] < w[1] and o[1] + o[2] > w[0]]


def load(log_dir: str) -> Trace:
    import jax
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise RuntimeError(f"the profiler wrote no .xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    tr = Trace()
    for plane in data.planes:
        name = plane.name
        if name.startswith("/device:TPU:") and "Core" not in name:
            dev = int(name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    tr.ops[dev] = [(op_name(e.name), e.start_ns,
                                    e.duration_ns, {k: v for k, v in e.stats})
                                   for e in line.events]
        elif name == "/host:CPU":
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.duration_ns)
                       for e in line.events]
                if any(e[0] == STEP_SPAN for e in evs):
                    tr.host = evs
    return tr


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_intervals(tr: Trace, dev: int) -> list[tuple[float, float]]:
    w = tr.window
    return union((max(s, w[0]), min(s + d, w[1]))
                 for _, s, d, _ in tr.ops_in_window(dev))


def busy_s(tr: Trace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    devs = [d for d in tr.ops if tr.ops_in_window(d)]
    if not devs:
        return 0.0
    tot = sum(sum(e - s for s, e in busy_intervals(tr, d)) for d in devs)
    return tot / len(devs) * 1e-9


def window_s(tr: Trace) -> float:
    w = tr.window
    return 0.0 if w is None else (w[1] - w[0]) * 1e-9


def top_ops(tr: Trace, n: int = 10) -> list[list]:
    """Device operations that took most time in the window, by name."""
    tot: dict[str, float] = {}
    for dev in tr.ops:
        for name, _, d, _ in tr.ops_in_window(dev):
            if name.split(".")[0] != CONTAINER:
                tot[name] = tot.get(name, 0.0) + d * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: Trace, n: int = 10) -> list[list]:
    """Idle device time in the window, summed by what the host was doing
    at each gap's midpoint (the innermost host span covering it)."""
    w = tr.window
    if w is None or not tr.ops:
        return []
    dev = min(tr.ops)
    busy = busy_intervals(tr, dev)
    gaps = []
    prev = w[0]
    for s, e in busy:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if prev < w[1]:
        gaps.append((prev, w[1]))
    host = sorted(tr.host, key=lambda h: h[1])
    tot: dict[str, float] = {}
    active: list[tuple] = []           # host spans open at the sweep point
    i = 0
    for s, e in gaps:                  # gaps come sorted by start
        mid = 0.5 * (s + e)
        while i < len(host) and host[i][1] <= mid:
            active.append(host[i])
            i += 1
        active = [h for h in active if h[1] + h[2] >= mid]
        inner = min(active, key=lambda h: h[2]) if active else None
        key = inner[0] if inner else "(no host span)"
        tot[key] = tot.get(key, 0.0) + (e - s) * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

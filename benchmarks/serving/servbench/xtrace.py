"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

Read with ``jax.profiler.ProfileData``: every TPU core is a plane named
``/device:TPU:<n>``, whose ``XLA Ops`` line holds one event per operation
the core ran; host threads are lines of the ``/host:CPU`` plane, where the
benchmark's own ``TraceAnnotation`` spans (``bench.*``) sit beside the
runtime's.  Device and host events share one clock.

* busy time: the union of a core's operation intervals inside the window,
  averaged over the cores used; the idle share is 1 − busy / window;
* kernel time: the summed durations of the operations whose name contains
  the kernel's name;
* idle gaps: the stretches of the window with no operation on a core, each
  named after the innermost host span that covers its midpoint on the
  benchmark's thread.
"""
from __future__ import annotations

import collections
import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
Interval = Tuple[float, float]


@dataclasses.dataclass
class Op:
    name: str
    start: float          # seconds on the trace's clock
    end: float


@dataclasses.dataclass
class Reduced:
    window: Interval
    ops: List[List[Op]]                   # per core, clipped to the window
    host: List[Tuple[str, float, float]]  # the benchmark thread's spans

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_s(self) -> float:
        """Seconds with an operation running, averaged over the cores."""
        if not self.ops:
            return 0.0
        return sum(_union([(o.start, o.end) for o in core])
                   for core in self.ops) / len(self.ops)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def op_seconds(self, contains: str) -> float:
        """Device seconds of operations whose name contains ``contains``,
        averaged over the cores."""
        if not self.ops:
            return 0.0
        return sum(o.end - o.start for core in self.ops for o in core
                   if contains in o.name) / len(self.ops)

    def top_ops(self, n: int = 10) -> List[List]:
        """Device seconds by operation kind and result shape, over the
        operations that hold no other (a loop's body ops, not the loop),
        averaged over the cores; largest first."""
        tot: Dict[str, float] = collections.Counter()
        for core in self.ops:
            for o in _leaves(core):
                tot[short_name(o.name)] += (o.end - o.start) / len(self.ops)
        return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self) -> List[Tuple[float, float]]:
        """Idle stretches of the first core's timeline inside the window."""
        if not self.ops:
            return [self.window]
        busy = _merge([(o.start, o.end) for o in self.ops[0]])
        gaps, t = [], self.window[0]
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < self.window[1]:
            gaps.append((t, self.window[1]))
        return gaps

    def idle_by_host(self, n: int = 10) -> List[List]:
        """Idle seconds summed by the host span that was open at each gap's
        midpoint (the innermost one), largest first."""
        tot: Dict[str, float] = collections.Counter()
        for s, e in self.idle_gaps():
            tot[self.host_at((s + e) / 2)] += e - s
        return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def host_at(self, t: float) -> str:
        best: Optional[Tuple[str, float, float]] = None
        for name, s, e in self.host:
            if s <= t < e and (best is None or e - s < best[2] - best[1]):
                best = (name, s, e)
        return best[0] if best else "no host span"


_SHAPE = re.compile(r"\b[a-z]+\d*\[[\d,]*\]")


def short_name(hlo: str) -> str:
    """``%fusion.194 = (f32[8,32]{...}, ...) fusion(...)`` → ``fusion
    f32[8,32]``: the instruction's name without its number, and the first
    result shape."""
    name, _, rest = hlo.partition(" = ")
    name = re.sub(r"\.\d+$", "", name.lstrip("%"))
    shape = _SHAPE.search(rest)
    return f"{name} {shape.group(0)}" if shape else name


def _leaves(ops: Sequence[Op]) -> List[Op]:
    """The operations inside which no other operation starts."""
    ops = sorted(ops, key=lambda o: (o.start, -o.end))
    return [o for i, o in enumerate(ops)
            if i + 1 == len(ops) or ops[i + 1].start >= o.end]


def _merge(iv: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _union(iv: Sequence[Interval]) -> float:
    return sum(e - s for s, e in _merge(iv))


def _events(line):
    for ev in line.events:
        yield ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9


def reduce(path: str, window_span: str = WINDOW_SPAN) -> Reduced:
    """Reduce the trace at ``path`` over the host span named
    ``window_span`` (the traced sub-window)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    window, host = None, []
    cores = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    cores.append(list(_events(line)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = list(_events(line))
                if any(n == window_span for n, _, _ in evs):
                    host = evs
                    window = next((s, e) for n, s, e in evs if n == window_span)
    if window is None:
        raise ValueError(f"no {window_span!r} span in {path}")
    lo, hi = window
    ops = [[Op(n, max(s, lo), min(e, hi)) for n, s, e in core
            if e > lo and s < hi] for core in cores]
    host = [h for h in host if h[2] > lo and h[1] < hi and h[0] != window_span]
    return Reduced(window=window, ops=ops, host=host)

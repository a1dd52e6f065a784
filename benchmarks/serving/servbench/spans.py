"""Per-layer metrics read from the program's own spans in the trace.

The scheduler annotates every ``step()`` as ``sched.step`` and each phase
inside it as ``sched.<phase>`` (``jax.profiler.TraceAnnotation``), so they
sit on the benchmark thread's host line of the traced sub-window, on the
device's clock.  Phases nest; a phase's self time is its span less the
spans directly inside it.  A program without these spans gives None.
"""
from __future__ import annotations

import collections
from typing import Dict, List, Optional, Sequence, Tuple

from .readers import FORWARD_PHASES

PREFIX = "sched."
STEP = PREFIX + "step"
Span = Tuple[str, float, float]


def self_seconds(spans: Sequence[Span]) -> Dict[str, float]:
    """Self seconds by name of properly nested spans (name, start, end)."""
    tot: Dict[str, float] = collections.Counter()
    stack: List[List] = []                 # [name, end, own seconds]
    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            n, _, own = stack.pop()
            tot[n] += own
        if stack:
            stack[-1][2] -= e - s
        stack.append([name, e, e - s])
    for n, _, own in stack:
        tot[n] += own
    return tot


def step_host_ms(ctx) -> Optional[float]:
    """Host ms per scheduler step on the program's clock: the ``sched.step``
    spans less the self time of their forward phases (prefill, decode,
    draft, verify), over the steps of the traced sub-window."""
    if ctx.trace is None:
        return None
    spans = [h for h in ctx.trace.host if h[0].startswith(PREFIX)]
    steps = [h for h in spans if h[0] == STEP]
    if not steps:
        return None
    own = self_seconds(spans)
    fwd = sum(own.get(PREFIX + p, 0.0) for p in FORWARD_PHASES)
    wall = sum(e - s for _, s, e in steps)
    return (wall - fwd) * 1e3 / len(steps)

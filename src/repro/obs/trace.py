"""Low-overhead ring-buffer event tracer (docs/observability.md).

The tracer records structured ``Event`` records into a bounded deque — a
fixed-capacity *ring buffer*, so a long serving run keeps the most recent
window of events instead of growing without bound.  Emission is a time read
plus a tuple append on the host; it never touches JAX, PRNG state, or the
scheduler's decisions, so a traced run produces bit-identical tokens to an
untraced one (regression-tested in tests/test_obs.py).

Event phases mirror the Chrome trace-event format the timeline exporter
targets:

* ``X`` — a *complete span* with a duration (``Tracer.span`` context manager)
* ``B`` / ``E`` — begin/end of a long-lived span (request residency in a slot)
* ``i`` — an instant event (submit, admit, alloc, free, preempt, …)
* ``C`` — a counter sample (pool blocks in use, occupied slots)

Every event carries a ``track`` — the timeline row it renders on:
``"scheduler"`` (step and phase spans), ``"pool"`` (block churn), and
``"slot<i>"`` (per-slot request lifecycles).

Disabled tracers (``Tracer(enabled=False)`` or the shared ``NULL_TRACER``)
reduce every emit to one attribute check, so instrumented code paths need no
``if tracer:`` guards.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Any, Dict, Iterator, List, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class Event:
    """One trace record.  ``ts``/``dur`` are seconds relative to the
    tracer's origin (monotonic ``perf_counter`` clock)."""
    name: str
    ph: str                      # "X" | "B" | "E" | "i" | "C"
    ts: float
    track: str = "scheduler"
    cat: str = "event"
    dur: float = 0.0             # "X" only
    args: Tuple[Tuple[str, Any], ...] = ()

    def arg(self, key: str, default: Any = None) -> Any:
        for k, v in self.args:
            if k == key:
                return v
        return default

    def args_dict(self) -> Dict[str, Any]:
        return dict(self.args)


class Tracer:
    """Bounded event recorder.  ``capacity`` is the ring size in events —
    older events are dropped once full (``dropped`` counts them), which
    bounds memory for arbitrarily long runs while keeping the recent window
    the stuck-scheduler diagnostics and the timeline export need."""

    def __init__(self, capacity: int = 65536, enabled: bool = True):
        self.enabled = enabled
        self.capacity = capacity
        self._buf: collections.deque = collections.deque(maxlen=capacity)
        self.origin = time.perf_counter()
        self.emitted = 0                    # lifetime emits (≥ len(events()))

    # -- clock --------------------------------------------------------------
    def now(self) -> float:
        return time.perf_counter() - self.origin

    # -- emission -----------------------------------------------------------
    def _emit(self, ev: Event) -> None:
        self._buf.append(ev)
        self.emitted += 1

    def instant(self, name: str, track: str = "scheduler",
                cat: str = "event", **args: Any) -> None:
        if not self.enabled:
            return
        self._emit(Event(name, "i", self.now(), track, cat,
                         args=tuple(args.items())))

    def counter(self, name: str, value: float, track: str = "scheduler",
                cat: str = "counter") -> None:
        if not self.enabled:
            return
        self._emit(Event(name, "C", self.now(), track, cat,
                         args=(("value", value),)))

    def begin(self, name: str, track: str = "scheduler",
              cat: str = "event", **args: Any) -> None:
        if not self.enabled:
            return
        self._emit(Event(name, "B", self.now(), track, cat,
                         args=tuple(args.items())))

    def end(self, name: str, track: str = "scheduler",
            cat: str = "event", **args: Any) -> None:
        if not self.enabled:
            return
        self._emit(Event(name, "E", self.now(), track, cat,
                         args=tuple(args.items())))

    @contextlib.contextmanager
    def span(self, name: str, track: str = "scheduler", cat: str = "span",
             **args: Any) -> Iterator[None]:
        """Time a block as one complete ("X") event.  The event is appended
        at *exit* (Chrome's complete-event convention: ``ts`` start + ``dur``),
        so a span that raises still records its duration."""
        if not self.enabled:
            yield
            return
        t0 = self.now()
        try:
            yield
        finally:
            self._emit(Event(name, "X", t0, track, cat,
                             dur=self.now() - t0, args=tuple(args.items())))

    # -- introspection ------------------------------------------------------
    @property
    def dropped(self) -> int:
        return self.emitted - len(self._buf)

    def events(self) -> List[Event]:
        return list(self._buf)

    def last(self, n: int) -> List[Event]:
        if n <= 0:
            return []
        return list(self._buf)[-n:]

    def clear(self) -> None:
        self._buf.clear()

    def format_tail(self, n: int = 30) -> str:
        """Human-readable last-``n`` events — attached to stuck-scheduler
        exceptions so the failure carries its own flight recorder."""
        if not self.enabled:
            return "(tracing disabled — pass a Tracer to the scheduler for "\
                   "an event tail here)"
        tail = self.last(n)
        if not tail:
            return "(no events recorded)"
        lines = [f"last {len(tail)} of {self.emitted} events "
                 f"({self.dropped} dropped from the ring):"]
        for ev in tail:
            args = " ".join(f"{k}={v}" for k, v in ev.args)
            lines.append(f"  [{ev.ts * 1e3:10.3f}ms] {ev.track:>10s} "
                         f"{ev.ph} {ev.name}" + (f" {args}" if args else ""))
        return "\n".join(lines)


#: Shared disabled tracer — the default for instrumented components, so
#: tracing costs one attribute check per emit site when nobody is listening.
NULL_TRACER = Tracer(capacity=1, enabled=False)

"""The program's one span API: nested spans and point events into a bounded
ring, and the same spans on the JAX profiler's clock.

``with TRACER.span("engine.chunk", valid=4096): ...`` is the only way the
program opens a span. It has two sinks, each switched on its own:

* the **ring** -- a bounded in-process ``deque`` of begin/end records (span
  id, enclosing span id, attrs) and point events (``event("token", ...)``):
  one dict append per record, no I/O on the hot path, exported whole as
  JSONL (``export_jsonl``). Offline tooling (``benchmarks/verify_obs.py``)
  rebuilds the nesting and each request's lifecycle from it, and the
  benchmark's span readers (``bench/spans.py``) read it after a run.
  ``ServerConfig(trace=True)`` or :meth:`Tracer.enable` switch it on.
* the **profiler** -- each span also enters a
  ``jax.profiler.TraceAnnotation`` of the same name, so it lands in the
  ``.xplane.pb`` of a profiling session on the device trace's clock.
  :meth:`Tracer.enable_profiling` switches it on.

``REPRO_PROFILE=1`` in the environment at import switches on both sinks of
the process :data:`TRACER`. With both off, ``span()`` returns one shared
no-op context after a single attribute check: no generator, no record, no
per-call object. Span names are dotted by layer (``engine.*``, ``plan.*``,
``serving.*``, ``router.*``) and are the same in both sinks. Spans wrap
host-side dispatch only and never touch traced jax values, so outputs are
bitwise identical and jit caches unchanged with either sink on or off
(tests/test_obs.py).

The module-level :data:`TRACER` is the process tracer: the jit-cached
executors in ``core.plan`` and the lru-cached step closures in
``serving.server`` are process-global and cannot hold a per-server tracer,
so they emit here.

The ring's clock is injectable and monotonic. :data:`default_clock` is the
ONE sanctioned wall-clock source for the serving path -- serving modules
take it as their injectable default instead of calling ``time.monotonic``
directly (ci.sh's static checker rejects violations). On Linux it is the
same ``CLOCK_MONOTONIC`` as ``time.perf_counter``, so ring times compare
with a driver's ``perf_counter`` stamps directly. The profiler's copy of a
span is timed by the profiler, relative to its session.

Stdlib-only at import (same import-order constraint as obs.registry):
``jax.profiler`` is imported at the first profiled span.
"""

from __future__ import annotations

import collections
import json
import os
import time
from typing import Callable

__all__ = ["Tracer", "TRACER", "get_tracer", "span", "event",
           "default_clock", "ManualClock", "NO_SPAN"]

#: The sanctioned serving clock (monotonic; immune to wall-clock steps).
default_clock: Callable[[], float] = time.monotonic


class ManualClock:
    """Deterministic, manually-advanced monotonic clock — a drop-in for
    :data:`default_clock` wherever a clock is injectable (the tracer,
    serving metrics, the multi-host router's heartbeats). Reading it never
    moves it; ``advance()`` moves virtual time forward. Tests and the
    chaos bench drive one of these a fixed amount per router step, so
    heartbeat timeouts and straggler timings replay identically regardless
    of host speed."""

    def __init__(self, start: float = 0.0) -> None:
        self._t = float(start)

    def __call__(self) -> float:
        return self._t

    def advance(self, dt: float) -> float:
        """Move virtual time forward ``dt`` seconds (monotonic — negative
        steps are rejected); returns the new time."""
        if dt < 0:
            raise ValueError(f"ManualClock cannot go backwards (dt={dt})")
        self._t += float(dt)
        return self._t


def _json_default(o):
    return str(o)


class _NoSpan:
    """The shared context ``span()`` returns with both sinks off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> None:
        pass


#: The one no-op span (``span()`` with both sinks off).
NO_SPAN = _NoSpan()


def _trace_annotation(name: str):
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)


class _Span:
    """One open span: a begin/end record pair in the ring and/or a profiler
    annotation, as the tracer's sinks stood when the span was made."""

    __slots__ = ("_tracer", "_name", "_attrs", "_to_ring", "_sid", "_ann")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict) -> None:
        self._tracer, self._name, self._attrs = tracer, name, attrs
        self._to_ring = tracer._enabled
        self._sid = None
        self._ann = _trace_annotation(name) if tracer._profiling else None

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        if self._to_ring:
            tr = self._tracer
            tr._next_id += 1
            self._sid = tr._next_id
            stack = tr._stack
            tr._ring.append({
                "t": tr._clock(), "name": self._name, "kind": "begin",
                "span": self._sid, "parent": stack[-1] if stack else None,
                "attrs": self._attrs})
            stack.append(self._sid)
        return self

    def __exit__(self, *exc) -> bool:
        if self._to_ring:
            tr = self._tracer
            tr._stack.pop()
            tr._ring.append({"t": tr._clock(), "name": self._name,
                             "kind": "end", "span": self._sid, "attrs": {}})
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False

    def set(self, **attrs) -> None:
        """Add attrs known only inside the span (they land on its begin
        record)."""
        self._attrs.update(attrs)


class Tracer:
    """Bounded ring of trace records, plus the profiler sink. Records are
    plain dicts:

    ``{"t": float, "name": str, "kind": "event"|"begin"|"end",
       "span": id-or-None, ["parent": id-or-None,] "attrs": {...}}``

    ``span`` on an ``"event"`` record is the *enclosing* span's id (None at
    top level); on ``"begin"``/``"end"`` it is the span's own id, with the
    enclosing id in ``"parent"``."""

    def __init__(self, capacity: int = 65536,
                 clock: Callable[[], float] = default_clock,
                 enabled: bool = False, profiling: bool = False) -> None:
        self._ring: collections.deque = collections.deque(
            maxlen=int(capacity))
        self._clock = clock
        self._enabled = bool(enabled)
        self._profiling = bool(profiling)
        self._on = self._enabled or self._profiling
        self._next_id = 0
        self._stack: list[int] = []

    # -- switches ------------------------------------------------------------
    @property
    def enabled(self) -> bool:
        """Whether the ring records."""
        return self._enabled

    @property
    def profiling(self) -> bool:
        """Whether spans also go to the JAX profiler."""
        return self._profiling

    def _switch(self, *, ring: bool | None = None,
                profiling: bool | None = None) -> None:
        if ring is not None:
            self._enabled = ring
        if profiling is not None:
            self._profiling = profiling
        self._on = self._enabled or self._profiling

    def enable(self) -> None:
        self._switch(ring=True)

    def disable(self) -> None:
        self._switch(ring=False)

    def enable_profiling(self) -> None:
        self._switch(profiling=True)

    def disable_profiling(self) -> None:
        self._switch(profiling=False)

    def configure(self, *, capacity: int | None = None,
                  clock: Callable[[], float] | None = None) -> None:
        """Resize/re-clock the tracer; clears the ring (records from two
        clocks or two capacities don't mix)."""
        if capacity is not None:
            self._ring = collections.deque(maxlen=int(capacity))
        if clock is not None:
            self._clock = clock
        self.clear()

    def clear(self) -> None:
        self._ring.clear()
        self._stack.clear()
        self._next_id = 0

    # -- recording -----------------------------------------------------------
    def event(self, name: str, **attrs) -> None:
        """One point event in the ring (one append; no-op when the ring is
        off)."""
        if not self._enabled:
            return
        self._ring.append({
            "t": self._clock(), "name": name, "kind": "event",
            "span": self._stack[-1] if self._stack else None,
            "attrs": attrs})

    def span(self, name: str, **attrs):
        """Nested span context: a ``begin`` record on entry and an ``end``
        on exit in the ring, and a profiler annotation around it; point
        events inside carry this span's id. ``as s`` gives a handle whose
        ``s.set(**attrs)`` adds attrs known only later."""
        if not self._on:
            return NO_SPAN
        return _Span(self, name, attrs)

    # -- export --------------------------------------------------------------
    def events(self) -> list[dict]:
        return list(self._ring)

    def to_jsonl(self) -> str:
        if not self._ring:
            return ""
        return "\n".join(json.dumps(e, default=_json_default)
                         for e in self._ring) + "\n"

    def export_jsonl(self, path) -> int:
        """Write the ring as JSONL (one record per line); returns the
        record count."""
        with open(path, "w") as f:
            f.write(self.to_jsonl())
        return len(self._ring)


_PROFILE = os.environ.get("REPRO_PROFILE", "") not in ("", "0")

#: Process tracer: both sinks off by default, both on under
#: ``REPRO_PROFILE=1``; ``ServerConfig(trace=True)`` switches the ring on,
#: and benches size it via ``configure(capacity=...)``.
TRACER = Tracer(enabled=_PROFILE, profiling=_PROFILE)


def get_tracer() -> Tracer:
    return TRACER


def span(name: str, **attrs):
    return TRACER.span(name, **attrs)


def event(name: str, **attrs) -> None:
    TRACER.event(name, **attrs)

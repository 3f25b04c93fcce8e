"""Nestable wall-clock tracing spans.

``Telemetry.span(name)`` returns a context manager; on exit it emits a
``span`` event carrying duration, nesting depth, and parent name.  Two
properties matter for correctness of the numbers:

* **Device barriers.**  JAX dispatch is async — ``f(x)`` returns before
  the computation finishes.  ``span.sync(out)`` registers ``out`` to be
  ``jax.block_until_ready``-ed at span exit, so the span measures real
  compute, not dispatch latency.  (Blocking happens *inside* the span,
  before the end timestamp is taken.)
* **Zero cost when disabled.**  A disabled tracer hands out the one
  shared ``NULL_SPAN``; entering/exiting it touches no clock, allocates
  nothing, and ``sync`` is the identity — instrumented hot paths run the
  same ops as uninstrumented ones.

Spans measure *host* wall-clock; they are meaningless inside a ``jit``
trace (they would time tracing, not execution), so callers instrumenting
dispatch-layer code must skip tracers (see ``repro.kernels.ops``).

A live span is also a ``jax.profiler.TraceAnnotation`` of its name while it
is open, so under ``jax.profiler`` it lands on the trace's host plane, on
the device ops' clock: an idle gap of the device can be put down to it.
"""

from __future__ import annotations

import time


class NullSpan:
    """Shared no-op span: the disabled path (also the no-op telemetry's)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def sync(self, x):
        return x

    def set(self, **attrs) -> None:
        pass


NULL_SPAN = NullSpan()


class Span:
    """One live span; created by ``Telemetry.span`` only."""

    __slots__ = ("_tele", "name", "attrs", "_t0", "_sync", "depth", "parent",
                 "_annotation")

    def __init__(self, tele, name: str, attrs: dict):
        self._tele = tele
        self.name = name
        self.attrs = attrs
        self._t0 = None
        self._sync = None
        self.depth = 0
        self.parent = None
        self._annotation = None

    def sync(self, x):
        """Register a jax value/pytree to block on at exit; returns it."""
        self._sync = x
        return x

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def __enter__(self):
        import jax
        stack = self._tele._span_stack
        self.depth = len(stack)
        self.parent = stack[-1].name if stack else None
        stack.append(self)
        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._sync is not None:
            import jax
            jax.block_until_ready(self._sync)
        dur = time.perf_counter() - self._t0
        self._annotation.__exit__(exc_type, exc, tb)
        stack = self._tele._span_stack
        if stack and stack[-1] is self:
            stack.pop()
        ev = {"name": self.name, "dur_s": dur, "depth": self.depth,
              "parent": self.parent}
        if exc_type is not None:
            ev["error"] = exc_type.__name__
        ev.update(self.attrs)
        self._tele.emit("span", **ev)
        return False

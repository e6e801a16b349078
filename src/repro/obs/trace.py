"""Lightweight host-side span tracer.

Spans time *host* work around jit boundaries — the driver's
``prepare`` / ``begin_variant`` / ``step`` phases, a session's cohort
draw, an async commit's group rounds — never code inside a traced
function (a ``time.perf_counter`` call cannot appear in a jaxpr, and a
span around a dispatch measures dispatch, not device time; that is
exactly the contract here: the wall-clock an end user waits through).

Spans nest (``with trace.span("step"): ... with trace.span("launch")``)
and every *closed* span reports ``(name, duration, depth)`` to the
telemetry object, which attributes it to the round currently executing
(or to the setup phase outside any round). Aggregation is by name, so
the driver keeps phase names sibling-disjoint where per-phase totals
should partition the round wall-clock.

Each span of the enabled tracer is also a ``jax.profiler``
``TraceAnnotation`` of the same name, carrying the round index as its
``round`` statistic: under an active profiler session the spans land on
the host plane of the same trace as the device's operations, on its
clock. With no profiler running an annotation costs about a
microsecond; the disabled ``NullTracer`` opens none.
"""
from __future__ import annotations

import time
from typing import Callable

from jax.profiler import TraceAnnotation


class _Span:
    """One active span; re-entrant use is not supported (make a new one
    via ``Tracer.span``)."""

    __slots__ = ("_tracer", "name", "_t0", "_annotation")

    def __init__(self, tracer: "Tracer", name: str):
        self._tracer = tracer
        self.name = name
        self._t0 = 0.0
        self._annotation = None

    def __enter__(self) -> "_Span":
        rnd = self._tracer.round
        self._annotation = (TraceAnnotation(self.name) if rnd is None
                            else TraceAnnotation(self.name, round=rnd))
        self._annotation.__enter__()
        self._tracer._depth += 1
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        dur = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        self._tracer._depth -= 1
        self._tracer._report(self.name, dur, self._tracer._depth)
        return False


class Tracer:
    """Factory for nestable timing spans.

    ``report(name, duration_s, depth)`` is called once per closed span;
    ``depth`` is 0 for top-level spans. The telemetry runtime installs
    its round-attribution callback here, and sets ``round`` to the index
    of the round executing (None outside any round).
    """

    def __init__(self, report: Callable[[str, float, int], None]):
        self._report = report
        self._depth = 0
        self.round: "int | None" = None

    def span(self, name: str) -> _Span:
        return _Span(self, name)


class _NullSpan:
    """Shared no-op span: the zero-overhead path when telemetry is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False


NULL_SPAN = _NullSpan()


class NullTracer:
    """Tracer stand-in whose spans cost one attribute lookup + one
    (shared, stateless) context-manager enter/exit."""

    __slots__ = ()

    def span(self, name: str) -> _NullSpan:
        return NULL_SPAN


NULL_TRACER = NullTracer()

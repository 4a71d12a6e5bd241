"""The nullable observability handle (DESIGN.md §17).

Every run loop in the repo accepts ``obs=None``: an :class:`Obs` bundles
an optional :class:`~repro.obs.timeline.Timeline` and an optional
:class:`~repro.obs.metrics.MetricsRegistry`, and the loops guard every
recording with ``if obs`` — disabled observability is a single falsy
check per chunk, no op added to traced code, zero extra compiles (the
``recompile.watch`` gate in tests/test_obs.py and the
``obs_overhead_frac`` gate in benchmarks/fed_scale_bench.py hold the
enabled path to the same contract: < 3% wall-clock, 0 steady-state
compiles).

:func:`span` is the loops' one way to mark host work: a profiler
annotation named ``repro.<name>`` (so a device trace shows what the host
did between programs) plus, with a live timeline, the same span on its
HOST track.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Dict, Iterator, Optional

import jax

from repro.obs.metrics import (Counter, Gauge, Histogram, JsonlSink,
                               MetricsRegistry)
from repro.obs.timeline import COMPILER, HOST, Timeline


@dataclasses.dataclass
class Obs:
    """Observability handle: ``timeline`` and/or ``metrics``, either may
    be None.  Falsy when both are None, so run loops can guard with a
    bare ``if obs:``."""

    timeline: Optional[Timeline] = None
    metrics: Optional[MetricsRegistry] = None

    def __bool__(self) -> bool:
        return self.timeline is not None or self.metrics is not None

    # -- constructors -----------------------------------------------------

    @classmethod
    def full(cls, label: str = "campaign",
             labels: Optional[Dict[str, Any]] = None) -> "Obs":
        """Timeline + in-memory metrics — the interactive default."""
        return cls(timeline=Timeline(label),
                   metrics=MetricsRegistry(labels=labels))

    @classmethod
    def metrics_only(cls, *sinks,
                     labels: Optional[Dict[str, Any]] = None) -> "Obs":
        """Metrics without a timeline — the big-n campaign default (per
        -client timeline events at n = 10^4+ would swamp the host)."""
        return cls(metrics=MetricsRegistry(*sinks, labels=labels))

    @classmethod
    def to_jsonl(cls, path: str,
                 labels: Optional[Dict[str, Any]] = None) -> "Obs":
        return cls.metrics_only(JsonlSink(path), labels=labels)

    # -- guarded instrument access ---------------------------------------

    def counter(self, name: str) -> Optional[Counter]:
        return None if self.metrics is None else self.metrics.counter(name)

    def gauge(self, name: str) -> Optional[Gauge]:
        return None if self.metrics is None else self.metrics.gauge(name)

    def histogram(self, name: str) -> Optional[Histogram]:
        return None if self.metrics is None \
            else self.metrics.histogram(name)

    def flush(self) -> None:
        if self.metrics is not None:
            self.metrics.flush()

    def close(self) -> None:
        if self.metrics is not None:
            self.metrics.close()

    # -- compile capture --------------------------------------------------

    @contextlib.contextmanager
    def compile_spans(self) -> Iterator["Obs"]:
        """Record backend compiles that happen inside the block onto the
        timeline's ``compiler`` track (wall seconds since the timeline
        epoch) and into a ``compiles`` counter — via the
        :mod:`repro.analysis.recompile` listener, so the capture sees
        every compile regardless of which jit cache issued it.  A no-op
        when the handle has no timeline and no metrics."""
        if not self:
            yield self
            return
        from repro.analysis import recompile
        tl, ctr = self.timeline, self.counter("compiles")

        def on_compile(event: str, duration: float) -> None:
            if ctr is not None:
                ctr.inc()
            if tl is not None:
                end = tl.now()
                tl.span(COMPILER, "backend_compile",
                        max(end - duration, 0.0), end,
                        duration_s=round(duration, 6))

        recompile.subscribe(on_compile)
        try:
            yield self
        finally:
            recompile.unsubscribe(on_compile)


#: module-level null handle — ``obs or NULL`` never allocates
NULL = Obs()


@contextlib.contextmanager
def maybe(obs: Optional[Obs]) -> Iterator[Obs]:
    """Normalize an ``obs=`` argument: yields a (possibly null) Obs with
    compile capture active exactly when the handle is live."""
    h = obs or NULL
    with h.compile_spans():
        yield h


#: prefix of the program's host spans in a profiler trace
SPAN_PREFIX = "repro."


@contextlib.contextmanager
def span(obs: Optional[Obs], name: str, **args) -> Iterator[None]:
    """Mark the host work inside the block as ``name``.

    Always opens a ``jax.profiler.TraceAnnotation`` named ``repro.<name>``,
    which is one inactive check when no profiler runs; when ``obs`` has a
    timeline, also records the block as a HOST-track wall span ``name``
    with ``args``."""
    tl = None if obs is None else obs.timeline
    t0 = 0.0 if tl is None else tl.now()
    with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
        yield
    if tl is not None:
        tl.span(HOST, name, t0, tl.now(), **args)

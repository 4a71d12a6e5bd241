"""repro.obs — campaign telemetry (DESIGN.md §17).

Four layers, all host-side (observability adds no op to traced code —
attaching it adds zero compiles and < 3% wall-clock, both gated):

* :mod:`~repro.obs.timeline` — event timelines: per-client message
  lifetimes, round/coin barriers, cohort draws, chunk and slab spans,
  compile events; exported as Perfetto/Chrome-trace JSON.
* :mod:`~repro.obs.metrics` — typed counters/gauges/histograms with
  pluggable sinks (in-memory, JSONL; the JSONL line schema is stable
  for external tooling).
* :mod:`~repro.obs.attrib` — per-client straggler attribution: barrier
  blame decomposition + markdown report.
* :mod:`~repro.obs.vecreplay` — post-hoc timeline reconstruction for
  :class:`repro.fed.vecsim.VecFedSim` campaigns, event-for-event equal
  to the heap oracle's live recording.

Entry point: build an :class:`Obs` handle and pass it as ``obs=`` to
``FedSim.run`` / ``VecFedSim.run`` / ``Driver.run`` / ``Sweeper.run``.
The run loops mark their host work with :func:`span` (a ``repro.<name>``
profiler annotation, and a HOST-track span when a timeline is live).
"""
from .attrib import Attribution, ClientStats, attribute, report
from .handle import NULL, Obs, maybe, span
from .metrics import (Counter, Gauge, Histogram, JsonlSink, MemorySink,
                      MetricsRegistry, read_jsonl)
from .timeline import (COMPILER, HOST, SERVER, Timeline, TimelineEvent,
                       client_track, merge, record_fed_round)
from .vecreplay import reconstruct_vec_timeline

__all__ = [
    "Attribution", "ClientStats", "attribute", "report",
    "NULL", "Obs", "maybe", "span",
    "Counter", "Gauge", "Histogram", "JsonlSink", "MemorySink",
    "MetricsRegistry", "read_jsonl",
    "COMPILER", "HOST", "SERVER", "Timeline", "TimelineEvent",
    "client_track", "merge", "record_fed_round",
    "reconstruct_vec_timeline",
]

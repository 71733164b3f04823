"""repro.obs — zero-dependency tracing & metrics for the N3H-Core stack.

Three pieces, one contract:

* :class:`Tracer` / :data:`NULL_TRACER` — Chrome trace-event (Perfetto)
  span collection from the cycle-accurate simulator (simulated FPGA
  cycles, not chip time); off by default via the null-object fast
  path.
* :class:`Counters` — derived per-core cycle accounting whose
  decomposition must *close*: busy + sync + stall + idle == the
  ``simulate_program`` makespan on every core track.
* :class:`MetricsRegistry` / :data:`METRICS` — structured
  counters/gauges/observations for serving and DSE with CSV/JSON
  export.

What the executors do on the chip is traced by the JAX profiler:
``repro.obs.spans`` (which, alone here, imports JAX) names the host
spans the executor chain and decode sessions open on its clock.

See ``docs/observability.md`` for usage.
"""
from .counters import Counters, TrackCounters
from .metrics import METRICS, MetricsRegistry
from .report import profile_report
from .trace import NULL_TRACER, NullTracer, Tracer, validate_chrome_trace

__all__ = [
    "Counters", "TrackCounters",
    "METRICS", "MetricsRegistry",
    "profile_report",
    "NULL_TRACER", "NullTracer", "Tracer", "validate_chrome_trace",
]

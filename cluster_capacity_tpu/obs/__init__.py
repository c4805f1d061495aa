"""obs/: solve telemetry — spans, runtime metrics, trace export.

Three sinks fed from one choke point (runtime/guard.run, the dispatch
boundary irgate's GD001 audit proves every device call crosses):

1. metrics — the upgraded utils/metrics.Registry: site×rung duration
   histograms, outcome/degradation/fault-injection counters, sweep progress
   gauges, and a backend-recompile counter (obs/recompile.py);
2. spans — nested, bounded, always-on (obs/spans.py), exported as
   Chrome-trace-event/Perfetto JSONL (obs/export.py);
3. CLI surfaces — `--metrics-dump` (Prometheus text) and `--trace-out`
   (trace JSONL) on both CLIs; every span is also a jax.profiler
   annotation, so a profiler trace (`--profile-out`) shows the program's
   host layers beside the device ops.

The deep-profiling layer (PR 9) builds three more surfaces on the same tap:
obs/profile.py (device-time/memory attribution + jax.profiler capture),
obs/costmodel.py (measured cost vs irgate's static budgets → per-entry
efficiency ratios), and obs/flight.py (bounded fault flight recorder:
self-contained triage bundles dumped at the guard's fault boundary).

Import discipline: obs imports only utils and stdlib — runtime/ imports obs,
never the reverse (flight/profile reach jax and the faults harness only
lazily, inside post-mortem / explicitly-enabled paths).  Nothing in this
package touches a jax value, so it can never force a device sync inside a
jit boundary (jaxlint's host-sync rules police this: obs/ is a hot dir).
"""

from . import costmodel, flight, names, profile  # noqa: F401
from .spans import (Collector, Span, default_collector, guard_span,  # noqa: F401
                    span)
from .export import trace_events, write_metrics, write_trace  # noqa: F401
from .recompile import install_recompile_hook  # noqa: F401

__all__ = ["names", "profile", "costmodel", "flight", "Collector", "Span",
           "default_collector", "guard_span", "span", "trace_events",
           "write_metrics", "write_trace", "install_recompile_hook"]

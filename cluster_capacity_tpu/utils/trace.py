"""The `--trace` stderr printer.

The reference wraps each scheduling cycle in utiltrace spans
("Snapshotting scheduler cache and node infos done" —
vendor/.../schedule_one.go:431-471).  Here the coarse phases are the
snapshot build and the solve: each is a span of the obs/ collector (so
`--trace-out` and profiler traces carry it), and with `--trace` its
duration is printed to stderr as it closes.
"""

from __future__ import annotations

import contextlib
import sys
from dataclasses import dataclass

SPAN_SNAPSHOT = "Snapshotting cluster state into device tensors"
SPAN_SOLVE = "Running placement scan"


@dataclass
class Tracer:
    enabled: bool = False

    def enable(self) -> None:
        self.enabled = True

    @contextlib.contextmanager
    def span(self, name: str):
        from ..obs.spans import default_collector
        try:
            with default_collector.span(name) as sp:
                yield
        finally:
            if self.enabled:
                print(f'Trace: "{name}" took {sp.duration_s * 1000:.1f}ms',
                      file=sys.stderr)


default_tracer = Tracer()

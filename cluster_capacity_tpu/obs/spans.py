"""Span collection: the telemetry backbone threaded through guard.run.

Every guarded dispatch (and the coarser framework/degrade phases) opens a
Span carrying the dispatch site, ladder rung, compile/execute phase, batch
shape and outcome.  Spans nest via a thread-local stack — a ladder descent
under injected faults leaves one parent `degrade.solve_one` span with a
child guard span per rung attempted, each stamped with the fault code that
ended it.  The collector is always on: a span costs two perf_counter reads
and a dict, nothing here ever touches a jax value or forces a device sync,
and the buffer is bounded (oldest spans drop, counted).

Every span is also a `jax.profiler.TraceAnnotation` of the same name, its
scalar attributes as the annotation's args, so whenever a profiler session
runs (`--profile-out`, `hypercc profile`, a benchmark's traced run) the
program's spans land in the `.xplane.pb` beside the device ops, on the
same clock.  An annotation is host-only: with no session it costs well
under a microsecond and records nothing.  jax is never imported from here:
the annotation class is taken from an already-imported jax, and before jax
is imported no session can be running.

Rung inheritance: a span opened without an explicit rung inherits the
nearest enclosing span's rung, so low-level dispatches inside a rung attempt
are attributed to that rung without plumbing the string through every call.

The guard's deadline watchdog runs `fn` on a worker thread, so backend
compiles can land on a thread with an empty span stack; `active_sited()`
exposes the most recently opened still-open *sited* span process-wide as the
attribution target for the jax.monitoring compile listener
(obs/recompile.py).  Device dispatch is effectively serialized in this
codebase, so the last-opened sited span is the right owner.
"""

from __future__ import annotations

import collections
import contextlib
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from ..utils import metrics as metrics_mod
from . import names

MAX_SPANS = 65536

# jax.profiler.TraceAnnotation once jax is imported (None before)
_annotation = None


def _no_annotation(name: str, **args):
    return contextlib.nullcontext()


def _annotate():
    global _annotation
    if _annotation is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        if profiler is None:
            return _no_annotation
        _annotation = profiler.TraceAnnotation
    return _annotation


def _scalar_args(sp: "Span") -> Dict[str, Any]:
    args = {k: v for k, v in sp.attrs.items()
            if isinstance(v, (int, float, str))}
    for k in ("site", "rung", "phase"):
        if getattr(sp, k):
            args[k] = getattr(sp, k)
    if sp.batch is not None:
        args["batch"] = sp.batch
    return args


@dataclass
class Span:
    name: str
    span_id: int
    parent_id: Optional[int]
    thread_id: int
    start_s: float                       # epoch seconds at open (export ts)
    site: str = ""                       # dispatch site ("" = phase span)
    rung: str = ""                       # ladder rung serving this attempt
    phase: str = ""                      # guard.PHASE_COMPILE / _EXECUTE
    batch: Optional[int] = None          # group size for batched dispatches
    first_call: bool = False             # first dispatch ever at this site
    outcome: str = ""                    # "ok" or fault code once closed
    duration_s: Optional[float] = None
    compile_s: float = 0.0               # backend-compile seconds attributed
    attrs: Dict[str, Any] = field(default_factory=dict)


class Collector:
    """Bounded, thread-aware span collector."""

    def __init__(self, max_spans: int = MAX_SPANS):
        self.max_spans = max_spans
        self._lock = threading.Lock()
        self._spans: collections.deque = collections.deque(
            maxlen=max_spans)  # cc-guarded-by: _lock
        self._local = threading.local()
        self._open_sited: List[Span] = []  # cc-guarded-by: _lock
        self._seen_sites: set = set()  # cc-guarded-by: _lock
        self._next_id = 1  # cc-guarded-by: _lock
        self.dropped = 0  # cc-guarded-by: _lock

    def _stack(self) -> List[Span]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def current(self) -> Optional[Span]:
        st = self._stack()
        return st[-1] if st else None

    def active_sited(self) -> Optional[Span]:
        """Innermost open span that has a dispatch site, any thread."""
        with self._lock:
            return self._open_sited[-1] if self._open_sited else None

    def spans(self) -> List[Span]:
        with self._lock:
            return list(self._spans)

    def reset(self) -> None:
        with self._lock:
            self._spans.clear()
            self._open_sited.clear()
            self._seen_sites.clear()
            self.dropped = 0

    @contextlib.contextmanager
    def span(self, name: str, *, site: str = "", rung: str = "",
             phase: str = "", batch: Optional[int] = None, **attrs):
        stack = self._stack()
        parent = stack[-1] if stack else None
        if not rung:
            for s in reversed(stack):
                if s.rung:
                    rung = s.rung
                    break
        with self._lock:
            span_id = self._next_id
            self._next_id += 1
            first = bool(site) and site not in self._seen_sites
            if site:
                self._seen_sites.add(site)
        sp = Span(name=name, span_id=span_id,
                  parent_id=parent.span_id if parent else None,
                  thread_id=threading.get_ident(), start_s=time.time(),
                  site=site, rung=rung, phase=phase, batch=batch,
                  first_call=first, attrs=dict(attrs))
        with self._lock:
            full = len(self._spans) == self.max_spans
            if full:
                self.dropped += 1
            self._spans.append(sp)
            if site:
                self._open_sited.append(sp)
        if full:
            metrics_mod.default_registry.inc(names.SPANS_DROPPED)
        stack.append(sp)
        t0 = time.perf_counter()
        try:
            args = _scalar_args(sp)
            with _annotate()(name, **args) as ann:
                yield sp
                # attributes set on the open span reach the trace too
                if ann is not None and (late := _scalar_args(sp)) != args:
                    ann.set_metadata(**late)
            if not sp.outcome:
                sp.outcome = "ok"
        except BaseException as exc:
            if not sp.outcome:
                sp.outcome = getattr(exc, "code", "") or type(exc).__name__
            raise
        finally:
            sp.duration_s = time.perf_counter() - t0
            if stack and stack[-1] is sp:
                stack.pop()
            if site:
                with self._lock:
                    try:
                        self._open_sited.remove(sp)
                    except ValueError:
                        pass


default_collector = Collector()


def span(name: str, **kw):
    """Convenience: open a span on the default collector."""
    return default_collector.span(name, **kw)


@contextlib.contextmanager
def guard_span(*, site: str, phase: str, rung: str = "",
               batch: Optional[int] = None,
               mesh_shape: Optional[dict] = None):
    """The guard.run span: records the dispatch span AND feeds the metric
    sinks (site×rung duration histogram, outcome counter, first-call
    counter).  The inner collector span closes before this function's
    finally runs, so `sp.outcome`/`sp.rung` are final by metric time.
    `mesh_shape` ({'batch': B, 'nodes': N}) rides the span attrs so profile
    attribution and flight bundles identify sharded dispatches."""
    reg = metrics_mod.default_registry
    sp: Optional[Span] = None
    t0 = time.perf_counter()
    attrs = {}
    if mesh_shape:
        attrs["mesh_shape"] = mesh_shape
        if batch:
            # batch rows each shard actually carries (after pad-to-multiple)
            nb = max(1, int(mesh_shape.get("batch", 1)))
            attrs["per_shard_batch"] = -(-int(batch) // nb)
    try:
        with default_collector.span(f"guard:{site}", site=site, rung=rung,
                                    phase=phase, batch=batch, **attrs) as sp:
            yield sp
    finally:
        dur = time.perf_counter() - t0
        if sp is not None:
            lab = dict(site=site, rung=sp.rung or "-", phase=phase)
            reg.observe(names.GUARD_DURATION, dur, **lab)
            reg.inc(names.GUARD_RUNS, outcome=sp.outcome or "error", **lab)
            if sp.first_call:
                reg.inc(names.GUARD_FIRST_CALLS, site=site)
            # memory-watermark sample (fast no-op unless profiling enabled
            # it); lazy import keeps spans importable before profile
            from . import profile as profile_mod
            profile_mod.maybe_sample(sp)

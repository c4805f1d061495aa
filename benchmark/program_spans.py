"""The program's own spans, reduced to host seconds per layer over the
measured window.

The program opens a span named `cc.<layer>` at each of its layer
boundaries (cluster_capacity_tpu/obs/spans.py); each is a record in its
in-process span collector and, while a profiler session runs, a host event
of the same name in the `.xplane.pb`, its scalar attributes as the
event's args.  Either source gives the same records,
(name, thread, start ns, end ns, args):

- `from_trace(path)` reads them from a profiler trace file;
- `from_collector()` reads them from the collector of the process that
  ran the window.  The harness's traced run answers in-process and removes
  its trace directory before the per-layer readers run, so the readers
  take this one: the window is the trace's `window_s`, ending where the
  window's last answer ended (its last `cc.` span).

`reduce` works on the records alone: per name, the seconds spent inside
the span (inclusive) and outside any `cc.` span nested in it on the same
thread (self); per name and arg, the sum of the arg over the spans that
start in the window; and the window's seconds covered by no `cc.` span
on any thread.  The readers in `metrics/` divide by the window's answers.
"""

from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Optional, Tuple

PREFIX = "cc."
# (name, thread, start ns, end ns, {arg: number})
Record = Tuple[str, object, int, int, Dict[str, float]]


def _numeric(args) -> Dict[str, float]:
    return {k: v for k, v in dict(args).items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def from_trace(path: str) -> List[Record]:
    """The host events named `cc.*` of one xplane file; a thread is its
    (plane, line) pair."""
    from jax.profiler import ProfileData
    out: List[Record] = []
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    s = int(ev.start_ns)
                    out.append((ev.name, (plane.name, i), s,
                                s + int(ev.duration_ns),
                                _numeric(ev.stats)))
    return out


def from_collector() -> List[Record]:
    """The closed `cc.*` spans of the program's default span collector
    (none where the program has no such spans)."""
    try:
        from cluster_capacity_tpu.obs.spans import default_collector
    except ImportError:
        return []
    out: List[Record] = []
    for sp in default_collector.spans():
        if sp.name.startswith(PREFIX) and sp.duration_s is not None:
            s = int(sp.start_s * 1e9)
            out.append((sp.name, sp.thread_id, s,
                        s + int(sp.duration_s * 1e9), _numeric(sp.attrs)))
    return out


def _union_ns(intervals: List[Tuple[int, int]]) -> int:
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def reduce(records: List[Record], window: Tuple[int, int]) -> dict:
    """Program seconds over `window` (ns, on the records' clock):
    `program_s` and `program_self_s` per name, `program_args` per name and
    arg, `lane_steps` (the sum of steps x lanes over `cc.issue`), and
    `program_outside_s`."""
    lo, hi = window
    incl: Dict[str, float] = defaultdict(float)
    self_s: Dict[str, float] = defaultdict(float)
    args: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    lane_steps = 0.0
    by_thread: Dict[object, List[Record]] = defaultdict(list)
    for rec in records:
        name, thread, s, e, a = rec
        if lo <= s < hi:
            for k, v in a.items():
                args[name][k] += v
            if name == PREFIX + "issue":
                lane_steps += a.get("steps", 0) * a.get("lanes", 1)
        if e > lo and s < hi:
            by_thread[thread].append(rec)
    covered = []
    for recs in by_thread.values():
        # spans of one thread nest: a span's self time is its clipped
        # length less that of the spans directly inside it
        recs.sort(key=lambda r: (r[2], -r[3]))
        stack: List[list] = []          # [name, end, self ns]
        for name, _t, s, e, _a in recs:
            cs, ce = max(s, lo), min(e, hi)
            while stack and stack[-1][1] <= s:
                done = stack.pop()
                self_s[done[0]] += done[2] * 1e-9
            if stack:
                stack[-1][2] -= ce - cs
            incl[name] += (ce - cs) * 1e-9
            stack.append([name, e, ce - cs])
            covered.append((cs, ce))
        for name, _e, rest in stack:
            self_s[name] += rest * 1e-9
    return {"program_s": dict(incl),
            "program_self_s": dict(self_s),
            "program_args": {k: dict(v) for k, v in args.items()},
            "lane_steps": lane_steps,
            "program_outside_s": (hi - lo - _union_ns(covered)) * 1e-9}


def of_run(ctx: dict) -> Optional[dict]:
    """The reduction of the traced run's window from the collector, once
    per run (kept in `ctx`); None where the trace has no device plane, no
    `cc.` span ran, or the collector dropped spans of the window."""
    if "program" not in ctx:
        ctx["program"] = None
        trace = ctx["trace"]
        records = from_collector() if trace["devices"] else []
        if records and trace["window_s"] > 0:
            hi = max(r[3] for r in records)
            lo = hi - int(trace["window_s"] * 1e9)
            from cluster_capacity_tpu.obs.spans import default_collector
            if not default_collector.dropped or \
                    min(r[2] for r in records) <= lo:
                ctx["program"] = dict(reduce(records, (lo, hi)),
                                      window_s=trace["window_s"])
    return ctx["program"]


def ms_per_answer(ctx: dict, name: str, inclusive: bool = False
                  ) -> Optional[float]:
    """Seconds of span `name` in the window per answer, in ms: inclusive
    or self time; None where the span never ran in the window."""
    red = of_run(ctx)
    table = (red or {}).get("program_s" if inclusive else "program_self_s",
                            {})
    if name not in table or ctx["answers"] <= 0:
        return None
    return table[name] / ctx["answers"] * 1e3

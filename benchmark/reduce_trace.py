"""From a JAX profiler trace to the numbers the per-layer metrics read.

Two halves.  `extract` reads an `.xplane.pb` with
`jax.profiler.ProfileData` and returns plain lists: the device's op
events, its program (XLA module) events, and the harness's own host spans
(`jax.profiler.TraceAnnotation` names that start with "bench.").
`reduce` works on those lists alone: the union of device-busy intervals
inside the window, device seconds per op and per program name, and the
idle gaps, each labelled by the host span that covers most of it.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Tuple

Interval = Tuple[str, int, int]          # (name, start ns, end ns)

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
IDLE_UNLABELLED = "no bench span"


def short_name(name: str) -> str:
    """An op event is named by its whole HLO instruction; keep the
    instruction's name ("%fusion.3", "%tpu_custom_call.1")."""
    return name.split(" = ", 1)[0]


def xplane_path(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(found)}")
    return found[0]


def extract(path: str) -> Dict[str, object]:
    """{"ops": {device: [Interval]}, "modules": {device: [Interval]},
    "spans": [Interval]} from one xplane file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Interval]] = {}
    modules: Dict[str, List[Interval]] = {}
    spans: List[Interval] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                dest = ops if line.name == OPS_LINE else modules
                dest[plane.name] = [
                    (short_name(ev.name), int(ev.start_ns), int(ev.start_ns)
                     + int(ev.duration_ns)) for ev in line.events]
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, int(ev.start_ns),
                                      int(ev.start_ns) + int(ev.duration_ns)))
    return {"ops": ops, "modules": modules, "spans": spans}


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv: List[Interval], lo: int, hi: int) -> List[Interval]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in iv
            if e > lo and s < hi]


def _label(gap: Tuple[int, int], spans: List[Interval]) -> str:
    """The innermost-named host span that overlaps the gap most."""
    best, best_ov = IDLE_UNLABELLED, 0
    for name, s, e in spans:
        if name == "bench.window":
            continue
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best_ov:
            best, best_ov = name, ov
    return best


def reduce(extracted: Dict[str, object], window: Tuple[int, int]) -> dict:
    """Busy and idle time of the device(s) inside `window` (ns, on the
    trace's clock), averaged over devices; device seconds per op name and
    per program name (summed over devices); idle seconds per host span."""
    lo, hi = window
    ops_by_dev = extracted["ops"]
    spans = _clip(extracted["spans"], lo, hi)
    devices = sorted(ops_by_dev)
    per_op: Dict[str, float] = defaultdict(float)
    per_module: Dict[str, float] = defaultdict(float)
    idle_by_span: Dict[str, float] = defaultdict(float)
    busy_total = 0.0
    for dev in devices:
        ops = _clip(ops_by_dev[dev], lo, hi)
        for name, s, e in ops:
            per_op[name] += (e - s) * 1e-9
        busy = union([(s, e) for _, s, e in ops])
        busy_total += sum(e - s for s, e in busy) * 1e-9
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for k in range(0, len(edges), 2):
            gap = (edges[k], edges[k + 1])
            if gap[1] > gap[0]:
                idle_by_span[_label(gap, spans)] += (gap[1] - gap[0]) * 1e-9
        for name, s, e in _clip(extracted["modules"].get(dev, []), lo, hi):
            per_module[name] += (e - s) * 1e-9
    n_dev = max(len(devices), 1)
    return {"devices": len(devices),
            "window_s": (hi - lo) * 1e-9,
            "busy_s": busy_total / n_dev,
            "per_op_s": dict(per_op),
            "per_module_s": dict(per_module),
            "idle_by_span_s": {k: v / n_dev for k, v in idle_by_span.items()}}


def window_of(extracted: Dict[str, object]) -> Tuple[int, int]:
    """The harness's `bench.window` span: the measured window."""
    wins = [(s, e) for n, s, e in extracted["spans"] if n == "bench.window"]
    if len(wins) != 1:
        raise RuntimeError(f"expected one bench.window span, found "
                           f"{len(wins)}")
    return wins[0]


def top(d: Dict[str, float], k: int = 10) -> List[list]:
    return [[name, secs] for name, secs in
            sorted(d.items(), key=lambda kv: -kv[1])[:k]]


# What the trace calls the program's kernels and programs today.  Neither
# pallas_call carries a name=: both kernels are the only `tpu_custom_call`
# ops, and the chunk counters tell which one ran.  The fast path's device
# program is the jitted `run` of engine/fast_path.py, an XLA module named
# "jit_run(<hash>)".
KERNEL_OP = "tpu_custom_call"
FAST_PATH_MODULE = "jit_run("


def _seconds(table: Dict[str, float], pattern: str) -> float:
    return sum(v for k, v in table.items() if pattern in k)


def _module_seconds(table: Dict[str, float], prefix: str) -> float:
    return sum(v for k, v in table.items() if k.startswith(prefix))


def kernel_us_per_placement(ctx: dict, counter: str) -> "float | None":
    other = "chunks" if counter == "batched_chunks" else "batched_chunks"
    chunks = ctx["chunks"]
    if chunks[counter] <= 0 or chunks[other] or ctx["placements"] <= 0:
        return None
    secs = _seconds(ctx["trace"]["per_op_s"], KERNEL_OP)
    if secs <= 0:
        return None
    return secs / ctx["placements"] * 1e6


def fast_path_ms_per_answer(ctx: dict) -> "float | None":
    chunks = ctx["chunks"]
    if chunks["chunks"] or chunks["batched_chunks"] or ctx["answers"] <= 0:
        return None
    secs = _module_seconds(ctx["trace"]["per_module_s"], FAST_PATH_MODULE)
    if secs <= 0:
        return None
    return secs / ctx["answers"] * 1e3

"""One run of one benchmark cell.

The cell names a configuration (`configs/<name>.json`) and a traffic mix
(`traffic/<name>.json`); `BENCHMARK.json` names the metrics, and each
per-layer metric has a reader of its own (`metrics/<base>.py`, `<base>`
being the name up to its first "."; what follows only says which
end-to-end metric it moves).  Nothing in this file knows a cell, a mix
or a metric by name.

A run is one closed-loop client asking what-if questions one after
another against a snapshot built once in set-up, as `cluster-capacity
--watch` keeps it resident:

- a `single` question is `ClusterCapacity(pod, max_limit)` on the
  snapshot, then `.run()` and `.report()`;
- a `sweep` question is `parallel.sweep.sweep(snapshot, pods, max_limit)`
  and `utils.report.build_review`.

Set-up builds the cluster and the snapshot from the seed and asks every
question of the catalogue once, so that every program is compiled (or
loaded from the compile cache) and every runtime cross-check has run
before the window.  The window closes at the first answer after
`--seconds`.  Then every answer of the window is compared by compare.py
with the plain reference that the configuration names ("reference").
"""

from __future__ import annotations

import gc
import json
import os
import shutil
import statistics
import subprocess
import sys
import tempfile
import time
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import compare  # noqa: E402
import gen  # noqa: E402
import reduce_trace  # noqa: E402


class NoChip(Exception):
    """JAX sees no accelerator, or fewer chips than the cell asks for."""


class NoEncoder(Exception):
    """The native snapshot encoder is absent and could not be built: the
    Python encoder would time another host path than a checkout that has
    it."""


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def load_cell(spec: dict, workload: str) -> dict:
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg = gen.load_json(configs[cell["config"]]["file"])
    traffic = gen.load_json(f"benchmark/traffic/{cell['traffic']}.json")

    def reported(m):
        return workload in m.get("workloads", [workload])

    e2e = [m for m in spec["end_to_end"] if reported(m)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if reported(m)
                 and m["moves"] in e2e_names]
    return {"cell": cell, "config": cfg, "traffic": traffic, "e2e": e2e,
            "per_layer": per_layer}


def check_device(chips: int, allow_cpu: bool) -> dict:
    import jax
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu" and not allow_cpu:
        raise NoChip(f"JAX's first device is {d.platform}, not an "
                     f"accelerator")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devices)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def enable_compile_cache() -> str:
    """$JAX_COMPILATION_CACHE_DIR when set (JAX reads it itself), else
    <checkout>/.jax_cache; every compile is cached."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def native_encoder(build: bool) -> str:
    """Build the native snapshot encoder only if the checkout lacks it;
    returns the encoder that will run.  A failed build raises NoEncoder."""
    lib = os.path.join(ROOT, "cluster_capacity_tpu", "models",
                       "libccsnap.so")
    if not os.path.exists(lib) and build:
        r = subprocess.run(["make", "native"], cwd=ROOT, capture_output=True,
                           text=True, timeout=300)
        if r.returncode != 0 or not os.path.exists(lib):
            raise NoEncoder(f"make native failed ({r.returncode}): "
                            f"{r.stderr[-500:]}")
    return "native" if os.path.exists(lib) else "python"


class CompileCount:
    """Backend compiles seen by jax.monitoring while `active`."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.active = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.active and event == self.EVENT:
            self.n += 1


def _kernel_counts() -> Dict[str, int]:
    from cluster_capacity_tpu.engine import fused
    return {"chunks": fused.STATS.get("chunks", 0),
            "batched_chunks": fused.STATS.get("batched_chunks", 0)}


class Asker:
    """Asks one question of the traffic and returns (answers, review)."""

    def __init__(self, traffic: dict, snapshot, pods: List[dict]):
        self.kind = traffic["question"]
        self.max_limit = int(traffic["max_limit"])
        self.snapshot = snapshot
        self.pods = pods

    def ask(self, k: Optional[int]):
        import jax
        from cluster_capacity_tpu import ClusterCapacity
        from cluster_capacity_tpu.parallel.sweep import sweep
        from cluster_capacity_tpu.utils.report import build_review
        if self.kind == "single":
            with jax.profiler.TraceAnnotation("bench.build"):
                cc = ClusterCapacity(self.pods[k], max_limit=self.max_limit)
                cc.set_snapshot(self.snapshot)
            with jax.profiler.TraceAnnotation("bench.run"):
                cc.run()
            with jax.profiler.TraceAnnotation("bench.report"):
                review = cc.report()
            keys = [k]
        else:
            with jax.profiler.TraceAnnotation("bench.sweep"):
                results = sweep(self.snapshot, self.pods,
                                max_limit=self.max_limit)
            with jax.profiler.TraceAnnotation("bench.report"):
                review = build_review(self.pods, results)
            keys = list(range(len(self.pods)))
        answers = [(key, compare.from_review_pod(p))
                   for key, p in zip(keys, review.pods)]
        return answers, review


def quantile(values: List[float], q: int) -> float:
    if len(values) == 1:
        return values[0]
    return statistics.quantiles(values, n=100, method="inclusive")[q - 1]


def load_reader(name: str):
    base = name.split(".")[0]
    return gen.load_module(f"benchmark/metrics/{base}.py").read


def run(workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, rehearsal: Optional[dict] = None) -> dict:
    """One run; returns the result line.  `rehearsal` is the test-only
    hook: {"config": {...overrides}, "traffic": {...overrides}} lets the
    CPU stand in for the chip at a tiny size."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    c = load_cell(spec, workload)
    cfg, traffic = c["config"], c["traffic"]
    if rehearsal:
        cfg.update(rehearsal.get("config", {}))
        traffic.update(rehearsal.get("traffic", {}))

    device = check_device(c["cell"]["chips"], allow_cpu=bool(rehearsal))
    cache_dir = enable_compile_cache()
    encoder = native_encoder(build=not rehearsal)
    import jax
    from cluster_capacity_tpu.models.podspec import default_pod, validate_pod
    from cluster_capacity_tpu.models.snapshot import ClusterSnapshot
    compiles = CompileCount()

    cluster = gen.make_cluster(cfg, seed)
    snapshot = ClusterSnapshot.from_objects(cluster["nodes"],
                                            cluster["pods"])
    raw = gen.templates(traffic)
    pods = [default_pod(p) for p in raw]
    for p in pods:
        validate_pod(p)
    asker = Asker(traffic, snapshot, pods)
    rounds = gen.round_order(traffic, len(raw), seed)

    # warm-up: one round, every question of the catalogue once
    k0 = _kernel_counts()
    for k in rounds:
        answers, review = asker.ask(k)
    kw = _kernel_counts()
    log(f"setup: setup_s={time.perf_counter() - t_start} "
        f"config={cfg['name']} nodes={len(cluster['nodes'])} "
        f"resident_pods={len(cluster['pods'])} seed={seed} "
        f"encoder={encoder} compile_cache={cache_dir} "
        f"warm_answers={len(rounds)} rung={review.rung or '-'} "
        f"kernel_chunks={kw['chunks'] - k0['chunks']} "
        f"batched_chunks={kw['batched_chunks'] - k0['batched_chunks']}")

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # host TraceMe spans and the device
        opts.host_tracer_level = 1        # only: no Python call tracing
        opts.raise_error_on_start_failure = True
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    latencies: List[float] = []
    kept: List[tuple] = []         # (template, answer) of every answer
    placements = attempted = failed = 0
    rungs: Dict[str, int] = {}
    compiles.active = True
    k_start = _kernel_counts()
    setup_s = time.perf_counter() - t_start
    q = 0
    w0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            t0 = time.perf_counter()
            attempted += 1
            try:
                answers, review = asker.ask(rounds[q % len(rounds)])
            except Exception as e:     # an answer that raises has failed
                failed += 1
                log(f"answer {q} raised {type(e).__name__}: {e}")
                answers, review = [], None
            t1 = time.perf_counter()
            q += 1
            latencies.append(t1 - t0)
            if review is not None:
                placements += review.replicas
                rungs[review.rung or "-"] = rungs.get(review.rung or "-",
                                                      0) + 1
                if review.degraded:
                    failed += 1
                kept.extend(answers)
            # the window closes at the first round boundary after
            # `seconds`, so every window asks whole rounds of the catalogue
            if t1 - w0 >= seconds and q % len(rounds) == 0:
                break
    window_s = time.perf_counter() - w0
    compiles.active = False
    k_end = _kernel_counts()
    if trace:
        jax.profiler.stop_trace()
    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    chunks = {k: k_end[k] - k_start[k] for k in k_end}
    log(f"window: answers={attempted} failed={failed} "
        f"placements={placements} window_s={window_s} "
        f"rungs={json.dumps(rungs, sort_keys=True)} "
        f"kernel_chunks={chunks['chunks']} "
        f"batched_chunks={chunks['batched_chunks']} "
        f"window_compiles={compiles.n}")

    result = {"correct": False, "attempted": attempted, "failed": failed,
              "metrics": {}, "device": device}
    values = {
        "placements_per_s": placements / window_s,
        "answer_p50_ms": quantile(latencies, 50) * 1e3,
        "answer_p95_ms": quantile(latencies, 95) * 1e3,
        "setup_s": setup_s,
    }
    if trace:
        ctx = {"placements": placements, "answers": attempted,
               "chunks": chunks}
        path = reduce_trace.xplane_path(trace_dir)
        extracted = reduce_trace.extract(path)
        red = reduce_trace.reduce(extracted,
                                  reduce_trace.window_of(extracted))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx["trace"] = red
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        for m in c["per_layer"]:
            v = load_reader(m["name"])(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
        result["breakdown"] = {
            "device_ops": reduce_trace.top(red["per_op_s"]),
            "idle_gaps": reduce_trace.top(red["idle_by_span_s"])}
        log("trace: " + json.dumps({
            "busy_s": red["busy_s"], "window_s": red["window_s"],
            "programs": reduce_trace.top(red["per_module_s"])}))
    else:
        for m in c["e2e"]:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}

    # correctness: every answer of the window against the reference
    del asker, snapshot, answers, review
    gc.collect()
    checks = check(gen.load_module(cfg["reference"]), cluster, raw, traffic,
                   kept, failed)
    result["correct"] = checks.pop("_correct")
    result["checks"] = checks
    return result


def check(reference, cluster: dict, templates: List[dict], traffic: dict,
          kept: list, failed: int) -> dict:
    """Compare every kept answer with the `reference` module's answer to
    its template (the raw template, as the traffic file gives it); returns
    each number the traffic file limits, with its limit, and `_correct`.
    An answer that raised never came, so any failed one is not correct."""
    t0 = time.perf_counter()
    ref_cluster = reference.Cluster(cluster["nodes"], cluster["pods"])
    refs = {k: compare.from_reference(reference.solve(
        ref_cluster, templates[k], int(traffic["max_limit"])))
        for k in sorted({k for k, _ in kept})}
    worst = compare.worst(compare.gaps(a, refs[k]) for k, a in kept)
    limits = traffic["limits"]
    out = {name: {"value": worst[name], "limit": limits[name]}
           for name in limits}
    out["failed_answers"] = {"value": failed, "limit": 0}
    log(f"reference: {len(refs)} template(s), {len(kept)} answer(s) "
        f"compared in {time.perf_counter() - t0:.3f} s")
    for name, v in out.items():
        print(f"check {name}={v['value']} limit={v['limit']}",
              file=sys.stderr, flush=True)
    out["_correct"] = bool(kept) and all(
        v["value"] <= v["limit"] for v in out.values())
    return out


def main(argv=None, rehearsal: Optional[dict] = None,
         t_start: Optional[float] = None) -> int:
    import argparse
    p = argparse.ArgumentParser(description="Run one benchmark cell once.")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace), t_start or time.perf_counter(),
                     rehearsal=rehearsal)
    except (NoChip, NoEncoder) as e:
        log(f"no result: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0

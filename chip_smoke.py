"""Chip smoke: drive `cluster-capacity` on one TPU at Kubernetes' limits.

The cluster sits at the limits of kubernetes.io "Considerations for large
clusters" (5,000 nodes, at most 110 pods per node, 150,000 pods): 5,000
nodes in 3 zones with 16/32/64 cores and 64/128/256 GiB, and 100,000
resident pods spread over them, all made from `--seed`.  Every question is
asked through the CLI's own entry point (`cli.cluster_capacity.run`), in
this process, with `--strict -o json`, so a degraded ladder rung fails the
run.  Phases, one line each:

1. device     the first JAX device is a TPU (there is no CPU path);
2. fast       an unconstrained pod: the analytic fast path;
3. scan       a zonal DoNotSchedule spread: the single-template Pallas
              kernel must run, and its placements must equal the XLA scan's
              on the same chip (kernel off via CC_TPU_FUSED=0);
4. batched    8 spread pods in one invocation: the batched kernel must run,
              and each answer must equal the same pod asked alone;
5. oracle     one spread question under a small --max-limit must equal the
              sequential host oracle (engine/oracle.py).

`--chips 4` runs only the phase-4 sweep on a 2x2 mesh and on one chip,
asserts equal placements and that the sharded carry sits on 4 devices.

Exit 0 prints, as the last line, {"ok": true, "device": {...}}; any failed
phase exits non-zero and prints no such line.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
ZONE = "topology.kubernetes.io/zone"

# kubernetes.io "Considerations for large clusters"
NODES = 5000
RESIDENT_PODS = 100_000
ZONES = 3
# Oracle placements in phase 5: the host oracle is pure Python over every
# node and resident pod per placement, so this keeps it near a minute.
ORACLE_LIMIT = 40
BATCH = 8


class SmokeFailure(Exception):
    pass


def make_cluster(n_nodes: int, n_pods: int, seed: int) -> dict:
    """Nodes as in bench.py:_make_nodes (16/32/64 cores, 64/128/256 GiB, 110
    pods) in ZONES zones, and resident pods placed in proportion to node
    cores.  Pod labels app=svc-0..svc-39; the smoke's templates select
    svc-0..svc-7, so their spread starts from uneven zone counts."""
    import numpy as np
    rng = np.random.default_rng(seed)
    cores = rng.choice([16, 32, 64], size=n_nodes)
    mem_gi = rng.choice([64, 128, 256], size=n_nodes)
    nodes = [{
        "metadata": {"name": f"node-{i:05d}",
                     "labels": {"kubernetes.io/hostname": f"node-{i:05d}",
                                ZONE: f"zone-{i % ZONES}"}},
        "spec": {},
        "status": {"allocatable": {"cpu": str(int(cores[i])),
                                   "memory": f"{int(mem_gi[i])}Gi",
                                   "pods": "110"}},
    } for i in range(n_nodes)]
    host = rng.choice(n_nodes, size=n_pods, p=cores / cores.sum())
    cpu_m = rng.choice([100, 250, 500, 1000], size=n_pods,
                       p=[0.4, 0.3, 0.2, 0.1])
    mem_mi = rng.choice([128, 256, 512, 1024, 2048], size=n_pods)
    app = rng.integers(0, 40, size=n_pods)
    pods = [{
        "metadata": {"name": f"res-{j:06d}", "namespace": "default",
                     "labels": {"app": f"svc-{int(app[j])}"}},
        "spec": {"nodeName": f"node-{int(host[j]):05d}",
                 "containers": [{"name": "c", "resources": {"requests": {
                     "cpu": f"{int(cpu_m[j])}m",
                     "memory": f"{int(mem_mi[j])}Mi"}}}]},
        "status": {"phase": "Running"},
    } for j in range(n_pods)]
    return {"nodes": nodes, "pods": pods}


def fast_pod() -> dict:
    return {"apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": "probe-fast", "namespace": "default",
                         "labels": {"app": "probe"}},
            "spec": {"containers": [{"name": "c", "resources": {
                "requests": {"cpu": "500m", "memory": "1Gi"}}}]}}


def spread_pod(k: int) -> dict:
    """Template k: a Deployment-style pod with a zonal DoNotSchedule spread
    over its own label; requests and maxSkew vary with k so no two
    templates share an answer."""
    app = f"svc-{k}"
    return {"apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": f"spread-{k}", "namespace": "default",
                         "labels": {"app": app}},
            "spec": {
                "containers": [{"name": "c", "resources": {"requests": {
                    "cpu": f"{500 + 250 * k}m",
                    "memory": f"{1024 + 512 * (k % 4)}Mi"}}}],
                "topologySpreadConstraints": [{
                    "maxSkew": 1 + k % 3, "topologyKey": ZONE,
                    "whenUnsatisfiable": "DoNotSchedule",
                    "labelSelector": {"matchLabels": {"app": app}}}]}}


def _line(**kv) -> None:
    print("chip_smoke: " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


@contextlib.contextmanager
def _env(**kv):
    old = {k: os.environ.get(k) for k in kv}
    os.environ.update(kv)
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


@contextlib.contextmanager
def _fail_on_degrade():
    """`--strict` flags a degraded answer only after a lower rung served
    it, and at 5,000 nodes the oracle rung would take hours: fail at the
    first ladder transition instead."""
    from cluster_capacity_tpu.runtime import degrade
    record = degrade._record

    def fail(fault, next_rung):
        record(fault, next_rung)
        raise SmokeFailure(f"{fault}; the ladder would descend to "
                           f"{next_rung}")
    degrade._record = fail
    try:
        yield
    finally:
        degrade._record = record


class Smoke:
    def __init__(self, workdir: str, cluster: dict):
        self.workdir = workdir
        self.snapshot = os.path.join(workdir, "cluster.json")
        with open(self.snapshot, "w") as f:
            json.dump(cluster, f)
        self.cluster = cluster

    def podspec(self, pod: dict) -> str:
        path = os.path.join(self.workdir, pod["metadata"]["name"] + ".json")
        with open(path, "w") as f:
            json.dump(pod, f)
        return path

    def ask(self, pods, *extra: str) -> dict:
        """One in-process `cluster-capacity` call.  Returns the JSON status
        plus compile and solve seconds and the kernels' chunk counts during
        the call."""
        from cluster_capacity_tpu.cli.cluster_capacity import run as cli_run
        from cluster_capacity_tpu.engine import fused
        from cluster_capacity_tpu.obs.recompile import CompileTally
        argv = ["--snapshot", self.snapshot, "--strict", "-o", "json"]
        for pod in pods:
            argv += ["--podspec", self.podspec(pod)]
        argv += list(extra)
        chunks0 = fused.STATS["chunks"]
        bchunks0 = fused.STATS.get("batched_chunks", 0)
        out = io.StringIO()
        t0 = time.perf_counter()
        with CompileTally() as tally, contextlib.redirect_stdout(out), \
                _fail_on_degrade():
            rc = cli_run(argv)
        wall = time.perf_counter() - t0
        if rc != 0:
            raise SmokeFailure(f"cluster-capacity {' '.join(extra)} exited "
                               f"{rc} (3 = a degraded rung served)")
        review = json.loads(out.getvalue())
        status = review["status"]
        if status.get("degraded"):
            raise SmokeFailure(f"degraded answer on rung {status.get('rung')}")
        # solve seconds: the call's wall time less its backend compiles
        # (snapshot load and host encode included)
        return {"status": status, "compile": tally.seconds,
                "solve": wall - tally.seconds,
                "chunks": fused.STATS["chunks"] - chunks0,
                "batched_chunks": fused.STATS.get("batched_chunks", 0)
                - bchunks0}


def _answer(status: dict) -> list:
    """Per-template answer: replicas per node in first-placement order,
    plus the failure summary."""
    return [(p["podName"], [(r["nodeName"], r["replicas"])
                            for r in p["replicasOnNodes"]],
             p.get("failSummary")) for p in status["pods"]]


def _no_failed_kernels() -> None:
    from cluster_capacity_tpu.engine import fused, fused_batched
    if fused._failed_metas or fused_batched._failed_keys:
        raise SmokeFailure("a Pallas kernel was marked failed")


def _report(phase: str, r: dict, kernel: str, **extra) -> None:
    _line(phase=phase, instances=r["status"]["replicas"],
          rung=r["status"].get("rung") or "-", kernel=kernel,
          compile_s=f"{r['compile']:.3f}", solve_s=f"{r['solve']:.3f}",
          **extra)


def phase_device(allow_cpu: bool, chips: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    _line(phase="device", platform=d.platform,
          kind=json.dumps(d.device_kind), count=len(devs))
    if d.platform != "tpu" and not allow_cpu:
        raise SmokeFailure(f"no TPU: JAX's first device is {d.platform}")
    if len(devs) < chips:
        raise SmokeFailure(f"--chips {chips} but JAX sees {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def phase_fast(s: Smoke) -> None:
    r = s.ask([fast_pod()])
    if r["chunks"] or r["batched_chunks"]:
        raise SmokeFailure("fast-path question ran the scan kernel")
    if r["status"]["replicas"] <= 0:
        raise SmokeFailure("fast path placed nothing")
    _report("fast", r, kernel="none")


def phase_scan(s: Smoke) -> None:
    pod = spread_pod(0)
    r = s.ask([pod])
    if r["chunks"] <= 0:
        raise SmokeFailure("spread question did not run the fused kernel")
    _no_failed_kernels()
    with _env(CC_TPU_FUSED="0"):
        x = s.ask([pod])
    if x["chunks"]:
        raise SmokeFailure("CC_TPU_FUSED=0 still ran the kernel")
    same = _answer(r["status"]) == _answer(x["status"])
    _report("scan", r, kernel=f"fused:{r['chunks']}chunks",
            xla_compile_s=f"{x['compile']:.3f}",
            xla_solve_s=f"{x['solve']:.3f}", equal_to_xla=same)
    if not same:
        raise SmokeFailure("fused kernel and XLA scan disagree")


def phase_batched(s: Smoke) -> None:
    pods = [spread_pod(k) for k in range(BATCH)]
    r = s.ask(pods)
    if r["batched_chunks"] <= 0:
        raise SmokeFailure("8-pod sweep did not run the batched kernel")
    _no_failed_kernels()
    one = []
    for pod in pods:
        one += _answer(s.ask([pod])["status"])
    same = _answer(r["status"]) == one
    _report("batched", r, kernel=f"fused_batched:{r['batched_chunks']}chunks",
            templates=len(pods), equal_to_one_at_a_time=same)
    if not same:
        raise SmokeFailure("batched sweep disagrees with one-at-a-time")


def phase_oracle(s: Smoke, limit: int) -> None:
    from cluster_capacity_tpu.engine import oracle
    from cluster_capacity_tpu.models.podspec import default_pod
    from cluster_capacity_tpu.models.snapshot import ClusterSnapshot
    from cluster_capacity_tpu.utils.config import SchedulerProfile
    pod = spread_pod(1)
    r = s.ask([pod], "--max-limit", str(limit))
    t0 = time.perf_counter()
    snap = ClusterSnapshot.from_objects(s.cluster["nodes"],
                                        s.cluster["pods"], use_native=False)
    placements, _ = oracle.simulate(snap, default_pod(pod),
                                    SchedulerProfile(), max_limit=limit)
    oracle_s = time.perf_counter() - t0
    order, counts = [], {}
    for i in placements:
        name = snap.node_names[i]
        if name not in counts:
            order.append(name)
            counts[name] = 0
        counts[name] += 1
    want = [(n, counts[n]) for n in order]
    got = _answer(r["status"])[0][1]
    same = got == want
    _report("oracle", r, kernel=f"fused:{r['chunks']}chunks",
            max_limit=limit, oracle_s=f"{oracle_s:.3f}",
            equal_to_oracle=same)
    if not same:
        raise SmokeFailure("device placements differ from the oracle's")


def phase_mesh(s: Smoke) -> None:
    from cluster_capacity_tpu.obs import names
    from cluster_capacity_tpu.utils import metrics
    pods = [spread_pod(k) for k in range(BATCH)]
    one = s.ask(pods)
    mesh = s.ask(pods, "--mesh", "2x2")
    devices = int(metrics.default_registry.get_gauge(
        names.SHARDED_CARRY_DEVICES))
    same = _answer(one["status"]) == _answer(mesh["status"])
    _report("sweep_1chip", one, kernel=f"fused_batched:"
            f"{one['batched_chunks']}chunks", templates=len(pods))
    _report("sweep_mesh2x2", mesh, kernel="none", templates=len(pods),
            carry_devices=devices, equal_to_1chip=same)
    if mesh["status"].get("rung") != "sharded_batched":
        raise SmokeFailure("--mesh 2x2 was not served by the sharded rung")
    if devices != 4:
        raise SmokeFailure(f"sharded carry sits on {devices} device(s)")
    if not same:
        raise SmokeFailure("2x2 mesh sweep disagrees with one chip")


def build_native() -> str:
    """Build the native snapshot encoder from source, or remove any stale
    build so the pure-Python encoder runs.  Returns which one is in use."""
    lib = os.path.join(REPO, "cluster_capacity_tpu", "models", "libccsnap.so")
    try:
        r = subprocess.run(["make", "-B", "native"], cwd=REPO,
                           capture_output=True, text=True, timeout=300)
        if r.returncode == 0 and os.path.exists(lib):
            return "native"
    except (OSError, subprocess.TimeoutExpired):
        pass
    if os.path.exists(lib):
        os.remove(lib)
    return "python"


def run(chips: int = 1, seed: int = 0, nodes: int = NODES,
        pods: int = RESIDENT_PODS, oracle_limit: int = ORACLE_LIMIT,
        allow_cpu: bool = False) -> dict:
    """All phases; returns the device dict for the last line.  `allow_cpu`
    is the test-only hook for the CPU rehearsal (interpret-mode kernels)."""
    device = phase_device(allow_cpu, chips)
    encoder = build_native()
    t0 = time.perf_counter()
    cluster = make_cluster(nodes, pods, seed)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work:
        s = Smoke(work, cluster)
        _line(phase="cluster", nodes=nodes, resident_pods=pods, zones=ZONES,
              seed=seed, encoder=encoder,
              setup_s=f"{time.perf_counter() - t0:.3f}")
        if chips > 1:
            phase_mesh(s)
            return device
        phase_fast(s)
        phase_scan(s)
        phase_batched(s)
        phase_oracle(s, oracle_limit)
    return device


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4),
                   help="4: run only the 2x2-mesh sweep against one chip")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    try:
        from cluster_capacity_tpu.utils.compile_cache import enable
    except ImportError as e:
        print(f"chip_smoke: the repository is not here ({e})",
              file=sys.stderr)
        return 2
    enable()
    try:
        device = run(chips=args.chips, seed=args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

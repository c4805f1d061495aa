"""Multi-host DCN proof: 2 CPU processes, 4 virtual devices each, joined via
jax.distributed into one 8-device mesh; host-sharded snapshot loading; the
sharded solve must agree with the single-process engine exactly.

Gated behind the `dist` marker (spawns subprocesses):
    python -m pytest tests/test_distributed.py -m dist -q
"""

import json
import os
import socket
import subprocess
import sys
import time

import pytest

from cluster_capacity_tpu import SchedulerProfile
from cluster_capacity_tpu.engine import encode as enc
from cluster_capacity_tpu.engine import simulator as sim
from cluster_capacity_tpu.models.podspec import default_pod
from cluster_capacity_tpu.models.snapshot import ClusterSnapshot
from cluster_capacity_tpu.parallel import distributed as dist


def _cluster_objects():
    nodes = []
    for i in range(16):
        nodes.append({
            "metadata": {"name": f"n{i:02d}",
                         "labels": {"kubernetes.io/hostname": f"n{i:02d}",
                                    "topology.kubernetes.io/zone": f"z{i % 4}"}},
            "spec": {},
            "status": {"allocatable": {"cpu": "4000m",
                                       "memory": str(8 * 1024 ** 3),
                                       "pods": "16"}}})
    pod = {"metadata": {"name": "p", "labels": {"app": "d"}},
           "spec": {"containers": [{"name": "c", "resources": {
               "requests": {"cpu": "300m", "memory": "512Mi"}}}],
               "topologySpreadConstraints": [{
                   "maxSkew": 2, "topologyKey": "topology.kubernetes.io/zone",
                   "whenUnsatisfiable": "DoNotSchedule",
                   "labelSelector": {"matchLabels": {"app": "d"}}}]}}
    return nodes, pod


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _wait_workers(procs, deadline_s=420):
    """Wait for all workers, but bail out early when any worker dies
    nonzero: its peers are then wedged on the collective barrier and would
    otherwise idle out the full deadline."""
    t0 = time.monotonic()
    while time.monotonic() - t0 < deadline_s:
        codes = [p.poll() for p in procs]
        if all(c is not None for c in codes):
            return
        if any(c not in (None, 0) for c in codes):
            time.sleep(5)   # grace: let the peer notice on its own
            break
        time.sleep(0.2)
    for p in procs:
        if p.poll() is None:
            p.kill()
            p.wait()


def _check_workers(procs, logs):
    """Assert every worker exited clean; skip (not fail) when the installed
    jaxlib's CPU backend cannot run multiprocess collectives at all — an
    environment limitation, not a scheduler regression."""
    tails = []
    for pid, p in enumerate(procs):
        logs[pid].seek(0)
        tails.append(logs[pid].read().decode(errors="replace")[-2000:])
        logs[pid].close()
    if any(p.returncode != 0 for p in procs) and any(
            "Multiprocess computations aren't implemented" in t for t in tails):
        pytest.skip("jaxlib CPU backend lacks multiprocess collectives")
    for pid, p in enumerate(procs):
        assert p.returncode == 0, f"worker {pid}: {tails[pid]}"


@pytest.mark.dist
def test_two_process_sharded_solve(tmp_path):
    nodes, pod = _cluster_objects()
    limit = 40

    # single-process reference
    snapshot = ClusterSnapshot.from_objects(nodes)
    pb = enc.encode_problem(snapshot, default_pod(pod),
                            SchedulerProfile.parity())
    ref = sim.solve(pb, max_limit=limit)

    base = str(tmp_path / "snap")
    dist.write_sharded_snapshot(base, nodes, num_shards=2)
    with open(base + ".pod.json", "w") as f:
        json.dump(pod, f)
    out = str(tmp_path / "out.json")

    port = _free_port()
    procs = []
    logs = []
    try:
        for pid in range(2):
            env = dict(os.environ)
            env.update({
                "CC_COORDINATOR": f"127.0.0.1:{port}",
                "CC_NUM_PROCESSES": "2",
                "CC_PROCESS_ID": str(pid),
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
                "PYTHONPATH": os.pathsep.join(
                    [os.getcwd()] +
                    env.get("PYTHONPATH", "").split(os.pathsep)),
            })
            # log files, not PIPEs: a chatty worker can fill a 64KB pipe and
            # deadlock the collective barrier
            log = open(str(tmp_path / f"worker{pid}.log"), "w+b")
            logs.append(log)
            procs.append(subprocess.Popen(
                [sys.executable, os.path.join(os.path.dirname(__file__),
                                              "dist_worker.py"),
                 base, out, str(limit)],
                env=env, stdout=log, stderr=log))
        _wait_workers(procs)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    _check_workers(procs, logs)

    with open(out) as f:
        got = json.load(f)
    assert got["processes"] == 2 and got["devices"] == 8
    assert got["placements"] == ref.placements
    assert got["fail_type"] == ref.fail_type
    assert got["fail_message"] == ref.fail_message


@pytest.mark.dist
def test_two_process_interleave_smoke(tmp_path):
    """Interleaved multi-template race on the 2-process runtime: each process
    runs the stacked-template solve on its local-device mesh (replicated host
    control — see distributed.interleave_on_mesh) and the per-template results
    must be bit-identical to the single-process tensor reference."""
    from cluster_capacity_tpu.parallel import interleave as il

    nodes, pod = _cluster_objects()
    limit = 24
    templates = []
    for i, cpu in enumerate(("300m", "600m", "900m")):
        t = json.loads(json.dumps(pod))
        t["metadata"]["name"] = f"p{i}"
        t["spec"]["containers"][0]["resources"]["requests"]["cpu"] = cpu
        templates.append(t)

    # single-process reference (tensor path, no mesh)
    snapshot = ClusterSnapshot.from_objects(nodes)
    ref = il.solve_interleaved_tensor(
        snapshot, [default_pod(t) for t in templates],
        SchedulerProfile.parity(), max_total=limit)

    base = str(tmp_path / "snap")
    dist.write_sharded_snapshot(base, nodes, num_shards=2)
    with open(base + ".templates.json", "w") as f:
        json.dump(templates, f)
    out = str(tmp_path / "out.json")

    port = _free_port()
    procs = []
    logs = []
    try:
        for pid in range(2):
            env = dict(os.environ)
            env.update({
                "CC_COORDINATOR": f"127.0.0.1:{port}",
                "CC_NUM_PROCESSES": "2",
                "CC_PROCESS_ID": str(pid),
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
                "PYTHONPATH": os.pathsep.join(
                    [os.getcwd()] +
                    env.get("PYTHONPATH", "").split(os.pathsep)),
            })
            log = open(str(tmp_path / f"ilworker{pid}.log"), "w+b")
            logs.append(log)
            procs.append(subprocess.Popen(
                [sys.executable, os.path.join(os.path.dirname(__file__),
                                              "dist_worker.py"),
                 base, out, str(limit)],
                env=env, stdout=log, stderr=log))
        _wait_workers(procs)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    _check_workers(procs, logs)

    with open(out) as f:
        got = json.load(f)
    assert got["processes"] == 2 and got["devices"] == 8
    assert len(got["interleave"]) == len(ref)
    for g, r in zip(got["interleave"], ref):
        assert g["placements"] == r.placements
        assert g["fail_type"] == r.fail_type
        assert g["fail_message"] == r.fail_message
        assert g["rung"] == "interleave_sharded"


def test_shard_roundtrip(tmp_path):
    """Single-process pieces: sharded write/load reproduces the object set
    and snapshot ordering."""
    nodes, pod = _cluster_objects()
    base = str(tmp_path / "s")
    dist.write_sharded_snapshot(base, nodes, num_shards=3,
                                pods=[], services=[])
    gathered = []
    for k in range(3):
        gathered.extend(dist.load_shard(base, k)["nodes"])
    assert [n["metadata"]["name"] for n in gathered] == \
        [n["metadata"]["name"] for n in nodes]

    snap = dist.load_snapshot_distributed(base)   # process_count()==1 path
    assert snap.num_nodes == len(nodes)
    assert snap.node_names == sorted(n["metadata"]["name"] for n in nodes)

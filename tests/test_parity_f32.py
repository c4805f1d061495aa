"""Measured f32==f64 parity demonstration.

TPU hardware has no native float64, so a fused-kernel f64 mode cannot be a
TPU fast path.  The parity story is instead a measured chain:

  fused kernel (f32)  ==  XLA scan (f32)   — enforced bit-identically by
                                             tests/test_fused.py and the
                                             runtime 48-step + mid-solve
                                             cross-checks on hardware
  XLA scan (f32)      ==  XLA scan (f64)   — demonstrated HERE across
                                             adversarial and mixed-family
                                             corpora (odd byte counts stress
                                             the f32 mantissa exactly where
                                             int64 reference arithmetic
                                             could drift)

together: fused-f32 placements equal the f64 parity protocol's, so a TPU
number from the f32 kernel is a parity-protocol number.  bench.py's
"parity" scenario re-runs the comparison on the bench cluster at full
scale; one test below also closes the loop kernel-vs-f64 directly in
interpret mode.  Reference arithmetic being matched: int64 score math in
runtime/framework.go:1137-1240.
"""

import os

import numpy as np
import pytest

from cluster_capacity_tpu.engine import encode as enc
from cluster_capacity_tpu.engine import simulator as sim
from cluster_capacity_tpu.models.podspec import default_pod
from cluster_capacity_tpu.models.snapshot import ClusterSnapshot
from cluster_capacity_tpu.utils.config import SchedulerProfile

from helpers import build_test_node
from test_fuzz import fuzz_cluster, fuzz_pod


def _odd_cluster(rng, n_nodes):
    """Capacities with odd byte/milli offsets: the values whose f32
    representations round, so score-floor boundaries get stressed."""
    nodes = []
    for i in range(n_nodes):
        mem = int(rng.choice([4, 8, 16])) * 1024 ** 3 \
            + int(rng.randint(0, 10 ** 7))
        cpu = int(rng.choice([3000, 7000, 13000])) + int(rng.randint(0, 999))
        nodes.append(build_test_node(
            f"n{i:05d}", cpu, mem, 110,
            labels={"kubernetes.io/hostname": f"n{i:05d}",
                    "topology.kubernetes.io/zone": f"z{i % 16}"}))
    return nodes


def _odd_pod(rng, spread=True):
    pod = {"metadata": {"name": "p", "labels": {"app": "x"}},
           "spec": {"containers": [{"name": "c", "resources": {"requests": {
               "cpu": f"{int(rng.choice([133, 277, 391]))}m",
               "memory": str(333 * 1024 ** 2 + int(rng.randint(1, 999)))}}}]}}
    if spread:
        pod["spec"]["topologySpreadConstraints"] = [{
            "maxSkew": 8, "topologyKey": "topology.kubernetes.io/zone",
            "whenUnsatisfiable": "DoNotSchedule",
            "labelSelector": {"matchLabels": {"app": "x"}}}]
    return default_pod(pod)


def _compare(snapshot, pod, limit, seed_note=""):
    p32 = SchedulerProfile()                  # float32 (TPU fast path)
    p64 = SchedulerProfile.parity()           # float64 (parity protocol)
    r32 = sim.solve(enc.encode_problem(snapshot, pod, p32), max_limit=limit)
    r64 = sim.solve(enc.encode_problem(snapshot, pod, p64), max_limit=limit)
    first_div = next(
        (i for i, (a, b) in enumerate(
            zip(r32.placements, r64.placements)) if a != b),
        min(len(r32.placements), len(r64.placements)))
    assert r32.placements == r64.placements, (
        f"{seed_note}: f32/f64 divergence at step {first_div}")
    assert r32.fail_message == r64.fail_message, seed_note


@pytest.mark.parametrize("seed", range(4))
def test_f32_matches_f64_odd_capacities(seed):
    rng = np.random.RandomState(seed)
    snapshot = ClusterSnapshot.from_objects(_odd_cluster(rng, 1000))
    _compare(snapshot, _odd_pod(rng), limit=400, seed_note=f"seed {seed}")


@pytest.mark.parametrize("seed", range(3100, 3106))
def test_f32_matches_f64_mixed_families(seed):
    """The mixed-family fuzz generator (spread + IPA + taints + node
    affinity + ports co-occurring) under both dtypes."""
    rng = np.random.RandomState(seed)
    nodes, pods = fuzz_cluster(rng, int(rng.choice([10, 16, 24])))
    pod = default_pod(fuzz_pod(rng))
    snapshot = ClusterSnapshot.from_objects(
        nodes, pods, namespaces=[{"metadata": {"name": "default"}}])
    _compare(snapshot, pod, limit=40, seed_note=f"seed {seed}")


def test_kernel_f32_matches_f64_directly(monkeypatch):
    """Close the chain end-to-end once: the fused KERNEL's placements (f32,
    interpret mode) equal the f64 XLA parity placements."""
    rng = np.random.RandomState(99)
    snapshot = ClusterSnapshot.from_objects(_odd_cluster(rng, 48))
    pod = _odd_pod(rng)
    monkeypatch.setenv("CC_TPU_FUSED", "1")
    r_kernel = sim.solve(enc.encode_problem(snapshot, pod,
                                            SchedulerProfile()),
                         max_limit=120)
    monkeypatch.setenv("CC_TPU_FUSED", "0")
    r64 = sim.solve(enc.encode_problem(snapshot, pod,
                                       SchedulerProfile.parity()),
                    max_limit=120)
    assert r_kernel.placements == r64.placements
    assert r_kernel.fail_message == r64.fail_message


@pytest.mark.fuzz
@pytest.mark.parametrize("seed", range(4))
def test_f32_matches_f64_10k_nodes(seed):
    """Full 10k-node scale (the bench cluster's size class), 1500 steps."""
    rng = np.random.RandomState(seed)
    snapshot = ClusterSnapshot.from_objects(_odd_cluster(rng, 10000))
    _compare(snapshot, _odd_pod(rng), limit=1500, seed_note=f"seed {seed}")

"""Fast-path solver (engine/fast_path.py): the analytic sorted-prefix solve
must produce bit-identical results to the sequential scan engine whenever it
declares itself eligible."""

import numpy as np
import pytest

from cluster_capacity_tpu import SchedulerProfile
from cluster_capacity_tpu.engine import encode as enc
from cluster_capacity_tpu.engine import fast_path
from cluster_capacity_tpu.engine import simulator as sim
from cluster_capacity_tpu.models.podspec import default_pod
from cluster_capacity_tpu.models.snapshot import ClusterSnapshot

from helpers import build_test_node, build_test_pod


def _compare(nodes, pod, limit=0, profile=None):
    profile = profile or SchedulerProfile.parity()
    snapshot = ClusterSnapshot.from_objects(nodes)
    pb = enc.encode_problem(snapshot, default_pod(pod), profile)
    fast = fast_path.solve_fast(pb, max_limit=limit)
    assert fast is not None, "expected fast-path eligibility"
    slow = sim.solve(pb, max_limit=limit)
    assert fast.placements == slow.placements
    assert fast.placed_count == slow.placed_count
    assert fast.fail_type == slow.fail_type
    assert fast.fail_message == slow.fail_message
    assert fast.fail_counts == slow.fail_counts
    return fast


@pytest.mark.parametrize("seed", range(6))
def test_fast_equals_scan_random(seed):
    rng = np.random.RandomState(seed)
    nodes = [build_test_node(
        f"n{i:02d}", int(rng.choice([500, 1000, 2000, 4000])),
        int(rng.choice([1, 2, 4, 8])) * 1024 ** 3,
        int(rng.choice([5, 10, 30])))
        for i in range(int(rng.choice([3, 7, 12])))]
    pod = build_test_pod("p", int(rng.choice([100, 150, 333])),
                         int(rng.choice([64, 100, 300])) * 1024 ** 2)
    _compare(nodes, pod, limit=int(rng.choice([0, 17])))


def test_fast_readme_demo():
    nodes = [build_test_node(f"kube-node-{i}", 2000, 4 * 1024 ** 3, 110)
             for i in range(1, 5)]
    pod = build_test_pod("small-pod", 150, 100 * 1024 ** 2)
    fast = _compare(nodes, pod)
    assert fast.placed_count == 52
    assert fast.fail_message == "0/4 nodes are available: 4 Insufficient cpu."


def test_fast_most_allocated():
    profile = SchedulerProfile.parity()
    profile.fit_strategy.type = "MostAllocated"
    nodes = [build_test_node(f"n{i}", 2000, 4 * 1024 ** 3, 20)
             for i in range(3)]
    pod = build_test_pod("p", 300, 200 * 1024 ** 2)
    # MostAllocated is INCREASING in k → monotonicity check must reject and
    # fall back (solve_fast returns None).
    snapshot = ClusterSnapshot.from_objects(nodes)
    pb = enc.encode_problem(snapshot, default_pod(pod), profile)
    assert fast_path.solve_fast(pb) is None
    # solve_auto still answers, via the scan.
    res = fast_path.solve_auto(pb)
    assert res.placed_count > 0


def test_fast_ineligible_with_spread():
    nodes = [build_test_node(f"n{i}", 2000, 4 * 1024 ** 3, 20,
                             labels={"zone": "a"}) for i in range(3)]
    pod = build_test_pod("p", 100, 0, labels={"app": "x"})
    pod["spec"]["topologySpreadConstraints"] = [{
        "maxSkew": 1, "topologyKey": "zone",
        "whenUnsatisfiable": "DoNotSchedule",
        "labelSelector": {"matchLabels": {"app": "x"}}}]
    snapshot = ClusterSnapshot.from_objects(nodes)
    pb = enc.encode_problem(snapshot, default_pod(pod),
                            SchedulerProfile.parity())
    assert not fast_path.eligible(pb)


# --- widened eligibility: uniform static-score classes ----

@pytest.mark.parametrize("seed", range(8))
def test_fast_uniform_taint_class(seed):
    """Every node carries the SAME PreferNoSchedule taint (a dedicated
    pool): the reverse-normalized score is a constant, so the fast path is
    exact — fuzzed against the scan."""
    rng = np.random.RandomState(100 + seed)
    taints = [{"key": "pool", "value": "batch", "effect": "PreferNoSchedule"}]
    nodes = [build_test_node(
        f"n{i:02d}", int(rng.choice([500, 1000, 2000])),
        int(rng.choice([2, 4])) * 1024 ** 3, int(rng.choice([5, 20])),
        taints=list(taints))
        for i in range(int(rng.choice([3, 9])))]
    pod = build_test_pod("p", int(rng.choice([100, 250])),
                         int(rng.choice([64, 200])) * 1024 ** 2)
    _compare(nodes, pod, limit=int(rng.choice([0, 11])))


@pytest.mark.parametrize("seed", range(8))
def test_fast_uniform_preferred_affinity_class(seed):
    """A preferred node-affinity term matching EVERY node normalizes to a
    constant 100 — fast path exact on the widened class."""
    rng = np.random.RandomState(200 + seed)
    nodes = [build_test_node(
        f"n{i:02d}", int(rng.choice([500, 1000, 2000])),
        int(rng.choice([2, 4])) * 1024 ** 3, int(rng.choice([5, 20])),
        labels={"kubernetes.io/os": "linux"})
        for i in range(int(rng.choice([3, 9])))]
    pod = build_test_pod("p", int(rng.choice([100, 250])),
                         int(rng.choice([64, 200])) * 1024 ** 2)
    pod["spec"]["affinity"] = {"nodeAffinity": {
        "preferredDuringSchedulingIgnoredDuringExecution": [{
            "weight": 7, "preference": {"matchExpressions": [{
                "key": "kubernetes.io/os", "operator": "In",
                "values": ["linux"]}]}}]}}
    _compare(nodes, pod, limit=int(rng.choice([0, 11])))


def test_fast_nonuniform_taint_still_ineligible():
    """One differently-tainted node keeps the class on the scan engine."""
    taints = [{"key": "pool", "value": "batch", "effect": "PreferNoSchedule"}]
    nodes = [build_test_node(f"n{i}", 1000, 2 * 1024 ** 3, 10,
                             taints=list(taints)) for i in range(3)]
    nodes.append(build_test_node("n3", 1000, 2 * 1024 ** 3, 10))
    pod = build_test_pod("p", 100, 64 * 1024 ** 2)
    snapshot = ClusterSnapshot.from_objects(nodes)
    pb = enc.encode_problem(snapshot, default_pod(pod),
                            SchedulerProfile.parity())
    assert not fast_path.eligible(pb)


def test_fast_nonuniform_taint_on_statically_excluded_node_ok():
    """Raw-score variance confined to statically-infeasible nodes (here: a
    NoSchedule-tainted node the pod does not tolerate) does not break
    uniformity over the eligible set."""
    nodes = [build_test_node(f"n{i}", 1000, 2 * 1024 ** 3, 10)
             for i in range(3)]
    nodes.append(build_test_node(
        "n3", 1000, 2 * 1024 ** 3, 10,
        taints=[{"key": "dedicated", "value": "x", "effect": "NoSchedule"},
                {"key": "p", "value": "q", "effect": "PreferNoSchedule"}]))
    pod = build_test_pod("p", 100, 64 * 1024 ** 2)
    fast = _compare(nodes, pod)
    assert all(fast.node_names[i] != "n3" for i in fast.placements)


def test_fast_retrace_pin():
    """solve_fast traces its device kernel EXACTLY once per static config:
    explain on/off, bounds on/off (via solve_auto), and different
    max_limit values must all replay the same cached trace — the r04→r06
    throughput bleed was exactly this invariant eroding call by call."""
    nodes = [build_test_node(f"n{i}", 2000, 4 * 1024 ** 3, 20)
             for i in range(8)]
    pod = build_test_pod("p", 100, 64 * 1024 ** 2)
    snapshot = ClusterSnapshot.from_objects(nodes)
    pb = enc.encode_problem(snapshot, default_pod(pod),
                            SchedulerProfile.parity())
    fast_path._fast_solve_device.cache_clear()
    before = fast_path.trace_count()
    expected = None
    for explain in (False, True):
        for limit in (0, 3, 17):
            r = fast_path.solve_fast(pb, max_limit=limit, explain=explain)
            assert r is not None
            if limit == 3:
                if expected is None:
                    expected = r.placements
                assert r.placements == expected      # kwargs never change it
    for bounds in (False, True):
        r = fast_path.solve_auto(pb, max_limit=3, bounds=bounds)
        assert r.placements == expected
    assert fast_path.trace_count() - before == 1


def test_fast_retrace_pin_new_static_config_traces_again():
    """The counter is per static config, not global: a different node
    count (new static shape) costs one more trace, then replays too."""
    profile = SchedulerProfile.parity()
    nodes = [build_test_node(f"n{i}", 2000, 4 * 1024 ** 3, 20)
             for i in range(8)]
    pod = build_test_pod("p", 100, 64 * 1024 ** 2)
    snapshot = ClusterSnapshot.from_objects(nodes)
    pb = enc.encode_problem(snapshot, default_pod(pod), profile)
    nodes2 = nodes + [build_test_node("n8", 2000, 4 * 1024 ** 3, 20)]
    pb2 = enc.encode_problem(ClusterSnapshot.from_objects(nodes2),
                             default_pod(pod), profile)
    fast_path._fast_solve_device.cache_clear()
    before = fast_path.trace_count()
    assert fast_path.solve_fast(pb, max_limit=5) is not None
    assert fast_path.solve_fast(pb2, max_limit=5) is not None
    assert fast_path.trace_count() - before == 2
    fast_path.solve_fast(pb, max_limit=9, explain=True)
    fast_path.solve_fast(pb2, max_limit=9, explain=True)
    assert fast_path.trace_count() - before == 2

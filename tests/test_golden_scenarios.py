"""Runner for the golden scenario files in tests/golden/.

Executes every `*.json` (hand-written; expected = reference-doc outcomes or
hand arithmetic) and `*.recorded.json` (decisions recorded verbatim from a
real kube-scheduler on a Go-toolchain machine) through the framework and
compares placements, counts, and FitError strings.  Schema + mechanism:
cluster_capacity_tpu/utils/golden.py.
"""

import glob
import os

import pytest

from cluster_capacity_tpu.utils import golden

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")
SCENARIOS = sorted(glob.glob(os.path.join(GOLDEN_DIR, "*.json")))


def test_scenarios_exist():
    """The mechanism is only real if fixtures ride it;
    round 4 grew the corpus to 19 (preemption pickOneNode criteria, RTC
    shapes, minDomains edges, IPA symmetric weights);
    round 5 to 24 (WFFC + CSIStorageCapacity edges, IPA namespaceSelector
    asymmetries)."""
    assert len(SCENARIOS) >= 24


@pytest.mark.parametrize(
    "path", SCENARIOS, ids=[os.path.basename(p) for p in SCENARIOS])
def test_golden_scenario(path):
    data = golden.load_scenario(path)
    res = golden.run_scenario(data)
    problems = golden.compare_result(data, res)
    assert not problems, f"{os.path.basename(path)}: " + "; ".join(problems)


def test_recorded_roundtrip(tmp_path):
    """--record-golden output is itself a valid, passing scenario."""
    from cluster_capacity_tpu.framework import ClusterCapacity
    from cluster_capacity_tpu.models.podspec import default_pod
    from cluster_capacity_tpu.utils.config import SchedulerProfile

    from helpers import build_test_node

    nodes = [build_test_node(f"n{i}", 1000, 2 * 1024 ** 3, 10)
             for i in range(2)]
    pod = default_pod({"metadata": {"name": "p"}, "spec": {"containers": [
        {"name": "c", "resources": {"requests": {"cpu": "300m"}}}]}})
    profile = SchedulerProfile.parity()
    cc = ClusterCapacity(pod, profile=profile)
    cc.sync_with_objects(nodes)
    res = cc.run()

    out = tmp_path / "roundtrip.json"
    golden.record_scenario(str(out), pod, {"nodes": nodes}, profile,
                           max_limit=0, res=res)
    data = golden.load_scenario(str(out))
    assert data["derivation"] == "self-recorded"
    assert data["expected"]["placed_count"] == res.placed_count
    res2 = golden.run_scenario(data)
    assert golden.compare_result(data, res2) == []


def test_recorded_roundtrip_exclude_and_node_order(tmp_path):
    """Scenarios carry --exclude-nodes and --node-order: a recording made
    with either replays identically (review-found gap: both were dropped,
    so such recordings failed as goldens immediately)."""
    from cluster_capacity_tpu.framework import ClusterCapacity
    from cluster_capacity_tpu.models.podspec import default_pod
    from cluster_capacity_tpu.utils.config import SchedulerProfile

    from helpers import build_test_node

    nodes = [build_test_node("small", 500, 2 * 1024 ** 3, 10),
             build_test_node("big", 4000, 8 * 1024 ** 3, 20)]
    pod = default_pod({"metadata": {"name": "p"}, "spec": {"containers": [
        {"name": "c", "resources": {"requests": {"cpu": "300m"}}}]}})
    profile = SchedulerProfile.parity()
    cc = ClusterCapacity(pod, profile=profile, exclude_nodes=["big"])
    cc.sync_with_objects(nodes)
    res = cc.run()
    assert set(res.per_node_counts) == {"small"}

    out = tmp_path / "excl.json"
    golden.record_scenario(str(out), pod, {"nodes": nodes}, profile,
                           max_limit=0, res=res, exclude_nodes=["big"])
    data = golden.load_scenario(str(out))
    assert golden.compare_result(data, golden.run_scenario(data)) == []

    znodes = [build_test_node(
        f"{p}1", 1000, 4 * 1024 ** 3, 10,
        labels={"topology.kubernetes.io/zone": z})
        for p, z in (("a", "za"), ("b", "zb"), ("c", "za"))]
    cc = ClusterCapacity(pod, max_limit=3, profile=profile)
    cc.sync_with_objects(znodes, node_order="zone-round-robin")
    zres = cc.run()
    out2 = tmp_path / "order.json"
    golden.record_scenario(str(out2), pod, {"nodes": znodes}, profile,
                           max_limit=3, res=zres,
                           node_order="zone-round-robin")
    data2 = golden.load_scenario(str(out2))
    assert data2["node_order"] == "zone-round-robin"
    assert golden.compare_result(data2, golden.run_scenario(data2)) == []

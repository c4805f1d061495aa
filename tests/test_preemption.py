"""DefaultPreemption PostFilter parity tests (engine/preemption.py;
reference semantics from vendor/.../framework/preemption/preemption.go)."""

from cluster_capacity_tpu import ClusterCapacity, SchedulerProfile
from cluster_capacity_tpu.engine.preemption import (may_find_victims,
                                                    resolve_priority)
from cluster_capacity_tpu.models.snapshot import ClusterSnapshot
from cluster_capacity_tpu.models.podspec import default_pod

from helpers import build_test_node, build_test_pod


def _run(pod, nodes, pods=(), limit=0, profile=None, **extra):
    cc = ClusterCapacity(default_pod(pod), max_limit=limit,
                         profile=profile or SchedulerProfile.parity())
    cc.sync_with_objects(nodes, pods, **extra)
    return cc.run()


def test_resolve_priority():
    pcs = [{"metadata": {"name": "high"}, "value": 1000},
           {"metadata": {"name": "low"}, "value": -10, "globalDefault": True}]
    assert resolve_priority({"spec": {"priority": 7}}, pcs) == 7
    assert resolve_priority({"spec": {"priorityClassName": "high"}}, pcs) == 1000
    assert resolve_priority({"spec": {}}, pcs) == -10
    assert resolve_priority({"spec": {}}, []) == 0


def test_preemption_evicts_lower_priority():
    nodes = [build_test_node("n1", 1000, int(1e9), 10)]
    squatter = build_test_pod("squatter", 800, 0, node_name="n1")
    squatter["spec"]["priority"] = -1
    incoming = build_test_pod("vip", 600, 0)
    incoming["spec"]["priority"] = 100
    res = _run(incoming, nodes, pods=[squatter])
    # without preemption 1000-800=200 < 600 → 0; with it the squatter is
    # evicted and 1000/600 → 1 pod fits
    assert res.placed_count == 1


def test_no_preemption_among_equal_priority():
    nodes = [build_test_node("n1", 1000, int(1e9), 10)]
    squatter = build_test_pod("squatter", 800, 0, node_name="n1")
    incoming = build_test_pod("peer", 600, 0)
    res = _run(incoming, nodes, pods=[squatter])
    assert res.placed_count == 0
    assert res.fail_counts.get("Insufficient cpu") == 1


def test_may_find_victims():
    """The framework skips the per-node preemption pass exactly when no
    node could yield victims."""
    nodes = [build_test_node("n1", 1000, int(1e9), 10)]
    squatter = build_test_pod("squatter", 800, 0, node_name="n1")
    snap = ClusterSnapshot.from_objects(nodes, [squatter])
    incoming = build_test_pod("vip", 600, 0)
    assert not may_find_victims(snap, incoming)          # equal priority
    incoming["spec"]["priority"] = 100
    assert may_find_victims(snap, incoming)
    incoming["spec"]["preemptionPolicy"] = "Never"
    assert not may_find_victims(snap, incoming)


def test_preemption_prefers_fewest_victims():
    """Node with one big victim beats node with two small victims."""
    nodes = [build_test_node("two-victims", 1000, int(1e9), 10),
             build_test_node("one-victim", 1000, int(1e9), 10)]
    pods = []
    for i in (1, 2):
        p = build_test_pod(f"small-{i}", 400, 0, node_name="two-victims")
        p["spec"]["priority"] = 0
        pods.append(p)
    big = build_test_pod("big", 800, 0, node_name="one-victim")
    big["spec"]["priority"] = 0
    pods.append(big)
    incoming = build_test_pod("vip", 900, 0)
    incoming["spec"]["priority"] = 10
    res = _run(incoming, nodes, pods=pods, limit=1)
    assert res.placed_count == 1
    assert res.node_names[res.placements[0]] == "one-victim"


def test_preemption_policy_never():
    nodes = [build_test_node("n1", 1000, int(1e9), 10)]
    squatter = build_test_pod("squatter", 800, 0, node_name="n1")
    squatter["spec"]["priority"] = -1
    incoming = build_test_pod("gentle", 600, 0)
    incoming["spec"]["priority"] = 100
    incoming["spec"]["preemptionPolicy"] = "Never"
    res = _run(incoming, nodes, pods=[squatter])
    assert res.placed_count == 0


def test_preemption_respects_pdb_choice():
    """Victims protected by a zero-disruption PDB push the choice to the
    unprotected node (fewest PDB violations criterion)."""
    nodes = [build_test_node("protected", 1000, int(1e9), 10),
             build_test_node("open", 1000, int(1e9), 10)]
    protected = build_test_pod("guarded", 800, 0, node_name="protected",
                               labels={"app": "guarded"})
    protected["spec"]["priority"] = 0
    open_pod = build_test_pod("plain", 800, 0, node_name="open")
    open_pod["spec"]["priority"] = 0
    pdb = {"metadata": {"name": "pdb", "namespace": "default"},
           "spec": {"selector": {"matchLabels": {"app": "guarded"}}},
           "status": {"disruptionsAllowed": 0}}
    incoming = build_test_pod("vip", 600, 0)
    incoming["spec"]["priority"] = 50
    res = _run(incoming, nodes, pods=[protected, open_pod], limit=1,
               pdbs=[pdb])
    assert res.placed_count == 1
    assert res.node_names[res.placements[0]] == "open"


def test_preemption_message_clause():
    profile = SchedulerProfile.parity()
    profile.include_preemption_message = True
    nodes = [build_test_node("n1", 1000, int(1e9), 10)]
    squatter = build_test_pod("squatter", 900, 0, node_name="n1")
    incoming = build_test_pod("peer", 600, 0)
    res = _run(incoming, nodes, pods=[squatter], profile=profile)
    assert "preemption: 0/1 nodes are available: " \
        "1 No preemption victims found for incoming pod." in res.fail_message


def test_preemption_cascade_capacity():
    """Capacity counting continues after eviction: evicting the squatter
    frees room for multiple clones."""
    nodes = [build_test_node("n1", 1000, int(1e9), 10)]
    squatter = build_test_pod("squatter", 900, 0, node_name="n1")
    squatter["spec"]["priority"] = -5
    incoming = build_test_pod("vip", 250, 0)
    incoming["spec"]["priority"] = 10
    res = _run(incoming, nodes, pods=[squatter])
    # first round: 100m free → 0 fit? 1000-900=100 < 250 → preempt squatter
    # → 1000 free → 4 x 250m
    assert res.placed_count == 4


def test_pod_key_metadata_less_pods_never_cross_match():
    """Regression (advisor r2): a victim with neither name nor uid must
    only match by object identity — a ('default','','') key would evict
    every other metadata-less pod on every node."""
    from cluster_capacity_tpu.engine.preemption import pod_key as _pod_key
    assert _pod_key({}) is None
    assert _pod_key({"metadata": {}}) is None
    assert _pod_key({"metadata": {"namespace": "ns"}}) is None
    assert _pod_key({"metadata": {"name": "a"}}) == ("default", "a", "")
    assert _pod_key({"metadata": {"uid": "u1"}}) == ("default", "", "u1")

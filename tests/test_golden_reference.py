"""Golden outcomes that do NOT flow through this repo's oracle.

The differential suite's oracle is same-author;
these fixtures pin outcomes whose expected values come from somewhere else:
the reference repository's own documented/asserted results, or step-by-step
manual arithmetic on reduced profiles (see tests/golden/README.md).
"""

import numpy as np

from cluster_capacity_tpu import ClusterCapacity, SchedulerProfile
from cluster_capacity_tpu.engine import encode as enc
from cluster_capacity_tpu.engine import simulator as sim
from cluster_capacity_tpu.models.podspec import default_pod
from cluster_capacity_tpu.models.snapshot import ClusterSnapshot

from helpers import build_test_node, build_test_pod


def test_golden_readme_demo():
    """reference README "Demonstration": 4 nodes x 2 CPU / 4 GB, pod
    150m/100Mi -> exactly 52 instances, 13 per node, stop reason
    Insufficient cpu.  (Derivation: reference-doc — the README's own printed
    output.)"""
    pod = default_pod({"metadata": {"name": "small-pod"}, "spec": {
        "containers": [{"name": "c", "resources": {"requests": {
            "cpu": "150m", "memory": "100Mi"}}}]}})
    nodes = [build_test_node(f"kubemark-{i}", 2000, 4 * 1024 ** 3, 110)
             for i in range(4)]
    cc = ClusterCapacity(pod, profile=SchedulerProfile.parity())
    cc.sync_with_objects(nodes)
    res = cc.run()
    assert res.placed_count == 52
    assert res.per_node_counts == {f"kubemark-{i}": 13 for i in range(4)}
    assert res.fail_type == "Unschedulable"
    assert "Insufficient cpu" in res.fail_message


def test_golden_prediction_failtypes():
    """pkg/framework/simulator_test.go:154-177 asserts FailType only:
    limit=6 -> LimitReached; unlimited -> Unschedulable.  Manual arithmetic
    pins the exact counts on top: nodes allow 3 pods each (pod-count slot),
    pod 100m/5e6 fits >=3x everywhere -> 9 placements total; every node then
    reports "Too many pods", and test-node-1 (300m) additionally has 0 cpu
    free < 100m -> "Insufficient cpu" (fitsRequest reports every failing
    resource per node, fit.go:564-660).  (Derivation: reference-doc +
    manual-arithmetic.)"""
    nodes = [build_test_node("test-node-1", 300, int(1e9), 3),
             build_test_node("test-node-2", 400, int(2e9), 3),
             build_test_node("test-node-3", 1200, int(1e9), 3)]
    pod = default_pod(build_test_pod("simulated-pod", 100, int(5e6)))

    cc = ClusterCapacity(pod, max_limit=6, profile=SchedulerProfile.parity())
    cc.sync_with_objects(nodes)
    res = cc.run()
    assert res.fail_type == "LimitReached" and res.placed_count == 6

    cc = ClusterCapacity(pod, profile=SchedulerProfile.parity())
    cc.sync_with_objects(nodes)
    res = cc.run()
    assert res.fail_type == "Unschedulable"
    assert res.placed_count == 9
    assert res.fail_message == \
        "0/3 nodes are available: 1 Insufficient cpu, 3 Too many pods."


def test_golden_colocation_properties():
    """test/benchmark/pod_colocation_test.go asserts every replica of a
    self-affine pod lands on ONE node (single-node case) / in ONE zone
    (9 nodes, 3 zones).  (Derivation: reference-doc.)"""
    pod = default_pod({
        "metadata": {"name": "app", "labels": {"app": "colo"}},
        "spec": {"containers": [{"name": "c", "resources": {"requests": {
            "cpu": "100m", "memory": "50Mi"}}}],
            "affinity": {"podAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": [{
                    "topologyKey": "kubernetes.io/hostname",
                    "labelSelector": {"matchLabels": {"app": "colo"}}}]}}}})
    nodes = [build_test_node(f"node-{i}", 2000, 4 * 1024 ** 3, 20,
                             labels={"kubernetes.io/hostname": f"node-{i}"})
             for i in range(5)]
    cc = ClusterCapacity(pod, profile=SchedulerProfile.parity())
    cc.sync_with_objects(nodes)
    res = cc.run()
    assert res.placed_count > 1 and len(res.per_node_counts) == 1

    zone_pod = default_pod({
        "metadata": {"name": "zapp", "labels": {"app": "zcolo"}},
        "spec": {"containers": [{"name": "c", "resources": {"requests": {
            "cpu": "100m"}}}],
            "affinity": {"podAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": [{
                    "topologyKey": "topology.kubernetes.io/zone",
                    "labelSelector": {"matchLabels": {"app": "zcolo"}}}]}}}})
    znodes = [build_test_node(
        f"zn-{i}", 1000, 4 * 1024 ** 3, 20,
        labels={"kubernetes.io/hostname": f"zn-{i}",
                "topology.kubernetes.io/zone": f"zone-{i % 3}"})
        for i in range(9)]
    cc = ClusterCapacity(zone_pod, profile=SchedulerProfile.parity())
    cc.sync_with_objects(znodes)
    res = cc.run()
    zones = {int(name.split("-")[1]) % 3 for name in res.per_node_counts}
    assert res.placed_count > 1 and len(zones) == 1


def _reduced_profile():
    """Fit filter + LeastAllocated score only — tractable by hand."""
    profile = SchedulerProfile.parity()
    profile.score_weights = {"NodeResourcesFit": 1}
    return profile


def test_golden_least_allocated_sequence():
    """Manual arithmetic (least_allocated.go:30-60 with
    calculateResourceAllocatableRequest INCLUDING the incoming pod,
    resource_allocation.go:88-99), reduced profile.

    Nodes: n0 = 10000m cpu, n1 = 1000m cpu; both 1 TB memory, 200 pod
    slots.  Pod requests 100m cpu, no memory; the scoring request uses the
    NonZero defaults (100m cpu, 200 MB=2.097152e8 memory).

    With k clones already on a node, the scored request is (k+1) pods:
      mem score (both nodes): floor((1e12 - 2.097152e8(k+1))*100/1e12)
        = floor(100 - 0.0209..(k+1)) = 99 for 1 <= k+1 <= 47.
      n0 cpu: floor((10000 - 100(k+1))*100/10000) = 99 - k
      n1 cpu: floor((1000 - 100(j+1))*100/1000)  = 90 - 10j
    -> s0(k) = floor((99-k+99)/2) = 99 - ceil(k/2);  s1(0) = floor(189/2)=94.

    Greedy with lowest-index tie-break: s0(k) for k=0..10 is
    99,98,98,97,97,96,96,95,95,94,94 — all >= 94, ties at k=9,10 go to n0
    -> eleven placements on n0; k=11 gives 93 < 94 -> n1.
    Expected first 12: [n0 x11, n1].  (Derivation: manual-arithmetic.)"""
    nodes = [build_test_node("n0", 10000, int(1e12), 200),
             build_test_node("n1", 1000, int(1e12), 200)]
    pod = default_pod(build_test_pod("p", 100, -1))
    snapshot = ClusterSnapshot.from_objects(nodes)
    pb = enc.encode_problem(snapshot, pod, _reduced_profile())
    res = sim.solve(pb, max_limit=12)
    assert res.placements == [0] * 11 + [1]


def test_golden_spread_skew_sequence():
    """Manual arithmetic (filtering.go:311-357 skew rule), reduced profile.

    Zones: z0 = {n0: 10000m, 200 slots}, z1 = {n1: 1000m, 2 pod slots}.
    Pod: 500m cpu, hard zone constraint maxSkew=1, selector matches the
    clones.  Scores (incoming pod included; mem column floor()=99
    throughout): s0(k) = floor((floor(100-5(k+1)) + 99)/2) -> 97, 94, 92 for
    k=0,1,2; s1(j) = floor((100-50(j+1) + 99)/2) -> 74, 49 for j=0,1.
    Counts (c0, c1) start (0,0); placing needs cnt+1-min <= 1.

      step 1: both allowed; 97 > 74 -> n0                   -> (1,0)
      step 2: n0: 1+1-0=2 >1 blocked; n1 -> (1,1)
      step 3: min=1; both ok; 94 > 49 -> n0                 -> (2,1)
      step 4: n0: 2+1-1=2 blocked; n1 ok (2nd pod slot)     -> (2,2)
      step 5: min=2; n0: 2+1-2=1 ok -> n0                   -> (3,2)
      step 6: n0: 3+1-2=2 blocked; n1 fails fit BOTH ways (pods 2+1>2 ->
              "Too many pods"; cpu free 0 < 500m -> "Insufficient cpu") ->
              STOP after 5 placements.
    (Derivation: manual-arithmetic.)"""
    nodes = [build_test_node(
        "n0", 10000, int(1e12), 200,
        labels={"kubernetes.io/hostname": "n0",
                "topology.kubernetes.io/zone": "z0"}),
        build_test_node(
        "n1", 1000, int(1e12), 2,
        labels={"kubernetes.io/hostname": "n1",
                "topology.kubernetes.io/zone": "z1"})]
    pod = default_pod({
        "metadata": {"name": "p", "labels": {"app": "s"}, "namespace": "default"},
        "spec": {"containers": [{"name": "c", "resources": {"requests": {
            "cpu": "500m"}}}],
            "topologySpreadConstraints": [{
                "maxSkew": 1, "topologyKey": "topology.kubernetes.io/zone",
                "whenUnsatisfiable": "DoNotSchedule",
                "labelSelector": {"matchLabels": {"app": "s"}}}]}})
    snapshot = ClusterSnapshot.from_objects(nodes)
    pb = enc.encode_problem(snapshot, pod, _reduced_profile())
    res = sim.solve(pb)
    assert res.placements == [0, 1, 0, 1, 0]
    assert res.fail_message == (
        "0/2 nodes are available: 1 Insufficient cpu, 1 Too many pods, "
        "1 node(s) didn't match pod topology spread constraints.")


def test_golden_anti_affinity_one_per_zone():
    """Manual arithmetic: required anti-affinity on zone against its own
    selector -> exactly one clone per zone, chosen in node-index order, then
    every node fails the incoming-pod anti-affinity probe
    (ErrReasonAntiAffinityRulesNotMatch wording).  (Derivation:
    manual-arithmetic + plugin message constant.)"""
    nodes = [build_test_node(
        f"n{i}", 2000, 4 * 1024 ** 3, 20,
        labels={"kubernetes.io/hostname": f"n{i}",
                "topology.kubernetes.io/zone": f"z{i % 3}"})
        for i in range(6)]
    pod = default_pod({
        "metadata": {"name": "p", "labels": {"app": "a"}, "namespace": "default"},
        "spec": {"containers": [{"name": "c", "resources": {"requests": {
            "cpu": "100m"}}}],
            "affinity": {"podAntiAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": [{
                    "topologyKey": "topology.kubernetes.io/zone",
                    "labelSelector": {"matchLabels": {"app": "a"}}}]}}}})
    snapshot = ClusterSnapshot.from_objects(nodes)
    pb = enc.encode_problem(snapshot, pod, _reduced_profile())
    res = sim.solve(pb)
    assert res.placements == [0, 1, 2]
    assert res.fail_message == ("0/6 nodes are available: 6 node(s) didn't "
                                "match pod anti-affinity rules.")


def test_golden_missing_extended_resource():
    """fit.go:585-600: a requested extended resource no node publishes reads
    as allocatable 0 -> every node "Insufficient <name>".  (Derivation:
    manual-arithmetic; regression for the fuzz-found seed-5025 bug.)"""
    nodes = [build_test_node(f"n{i}", 2000, 4 * 1024 ** 3, 20)
             for i in range(3)]
    pod = default_pod(build_test_pod("p", 100, 0))
    pod["spec"]["containers"][0]["resources"]["requests"]["example.com/fpga"] = "1"
    snapshot = ClusterSnapshot.from_objects(nodes)
    pb = enc.encode_problem(snapshot, pod, SchedulerProfile.parity())
    res = sim.solve(pb)
    assert res.placed_count == 0
    assert res.fail_message == \
        "0/3 nodes are available: 3 Insufficient example.com/fpga."


def test_golden_preferred_anti_affinity_round_robin():
    """Manual arithmetic (scoring.go:268-300 min-max normalize + the 2x
    both-directions dynamic weight), reduced profile with ONLY the
    InterPodAffinity score active (weight 2).

    3 identical nodes (2 pod slots each); pod has preferred self
    anti-affinity on hostname, weight 10 (dynamic per-placement weight
    2x10=20, negative).

      step 1: all raw 0 -> max==min -> all normalize to 0 -> tie -> n0
      step 2: raw n0=-20, others 0 -> norm: n0=0, n1=n2=floor(100*20/20)
              =100 -> tie at 100 -> n1
      step 3: raw n0=n1=-20, n2=0 -> n2=100 wins -> n2
      step 4: all raw -20 -> max==min -> all 0 -> tie -> n0
      steps 5-6: repeat the rotation -> n1, n2
      step 7: every node at its 2-pod slot cap -> STOP:
              "0/3 nodes are available: 3 Too many pods."
    Expected: [n0, n1, n2, n0, n1, n2].  (Derivation: manual-arithmetic.)"""
    profile = SchedulerProfile.parity()
    profile.score_weights = {"InterPodAffinity": 2}
    nodes = [build_test_node(f"n{i}", 4000, int(1e12), 2,
                             labels={"kubernetes.io/hostname": f"n{i}"})
             for i in range(3)]
    pod = default_pod({
        "metadata": {"name": "p", "labels": {"app": "rr"},
                     "namespace": "default"},
        "spec": {"containers": [{"name": "c", "resources": {"requests": {
            "cpu": "100m"}}}],
            "affinity": {"podAntiAffinity": {
                "preferredDuringSchedulingIgnoredDuringExecution": [{
                    "weight": 10, "podAffinityTerm": {
                        "topologyKey": "kubernetes.io/hostname",
                        "labelSelector": {
                            "matchLabels": {"app": "rr"}}}}]}}}})
    snapshot = ClusterSnapshot.from_objects(nodes)
    pb = enc.encode_problem(snapshot, pod, profile)
    res = sim.solve(pb)
    assert res.placements == [0, 1, 2, 0, 1, 2]
    assert res.fail_message == "0/3 nodes are available: 3 Too many pods."


def test_golden_extender_preemption_victim_merge():
    """ProcessPreemption victim-merge semantics (extender.go:343-373 +
    preemption.go callExtenders): an extender's response keeps a candidate
    node either with UPDATED full victim pods or with a non-list /
    MetaVictims payload — the latter must retain the LOCALLY computed
    victims, not drop the node.

    Derivation: two 1000m nodes each hosting one 900m priority-0 victim;
    the preemptor asks 900m at priority 10, so each node's minimal victim
    set is its own pod.  pickOneNode criteria (preemption.go:583-653) all
    tie (no PDBs, equal priorities, equal victim counts, no start times)
    -> first candidate in node order, n0.

    (1) An extender answering {n0: <non-list>, n1: <full local list>}
    keeps BOTH candidates (n0 via the merge-keeps-local rule), so the
    choice stays n0 — a merge that dropped non-list entries would flip the
    answer to n1.
    (2) An extender answering only {n1: <non-list>} removes n0 from the
    candidate map entirely (intersection), so the preemptor lands on n1."""
    def make_cluster():
        nodes = [build_test_node(f"n{i}", 1000, 4 * 1024 ** 3, 5,
                                 labels={"kubernetes.io/hostname": f"n{i}"})
                 for i in range(2)]
        pods = []
        for i in range(2):
            p = build_test_pod(f"low-{i}", 900, 0, node_name=f"n{i}")
            p["spec"]["priority"] = 0
            pods.append(p)
        return nodes, pods

    from cluster_capacity_tpu.engine.extenders import ExtenderConfig

    vip = default_pod(build_test_pod("vip", 900, 0))
    vip["spec"]["priority"] = 10

    def keeps_both_meta(pod, node_to_victims):
        # n0 keyed with a non-list payload (the MetaVictims shape after
        # transport) -> local victims retained; n1 echoed in full
        return {"n0": {"Pods": None}, "n1": list(node_to_victims["n1"])}

    nodes, pods = make_cluster()
    profile = SchedulerProfile.parity()
    profile.extenders = [ExtenderConfig(preempt_callable=keeps_both_meta)]
    cc = ClusterCapacity(vip, max_limit=1, profile=profile)
    cc.sync_with_objects(nodes, pods)
    res = cc.run()
    assert res.placed_count == 1 and res.placements == [0], \
        "merge must keep n0 with its local victims"

    def only_n1_meta(pod, node_to_victims):
        return {"n1": {"Pods": None}}

    nodes, pods = make_cluster()
    profile2 = SchedulerProfile.parity()
    profile2.extenders = [ExtenderConfig(preempt_callable=only_n1_meta)]
    cc2 = ClusterCapacity(vip, max_limit=1, profile=profile2)
    cc2.sync_with_objects(nodes, pods)
    res2 = cc2.run()
    assert res2.placed_count == 1 and res2.placements == [1], \
        "intersection must drop the unreturned candidate n0"

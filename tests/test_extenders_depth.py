"""Extender Bind/ProcessPreemption verbs, managedResources filtering,
addedAffinity preferred-term scoring, and config validation.

Reference: vendor/k8s.io/kubernetes/pkg/scheduler/extender.go:318-380,
plugins/nodeaffinity/node_affinity.go:98-106 + :260,
cmd/cluster-capacity/app/server.go:111 (config validation).
"""

import pytest

from cluster_capacity_tpu import ClusterCapacity, SchedulerProfile
from cluster_capacity_tpu.engine import encode as enc
from cluster_capacity_tpu.engine import simulator as sim
from cluster_capacity_tpu.engine.extenders import (ExtenderConfig,
                                                   solve_with_extenders)
from cluster_capacity_tpu.models.podspec import default_pod
from cluster_capacity_tpu.models.snapshot import ClusterSnapshot
from cluster_capacity_tpu.utils.config import (ConfigValidationError,
                                               load_scheduler_config)

from helpers import build_test_node, build_test_pod


def _pb(nodes, pod, profile=None):
    snapshot = ClusterSnapshot.from_objects(nodes)
    return enc.encode_problem(snapshot, default_pod(pod),
                              profile or SchedulerProfile.parity())


def test_bind_verb_called_per_placement():
    nodes = [build_test_node(f"n{i}", 1000, 4 * 1024 ** 3, 5)
             for i in range(2)]
    pod = build_test_pod("p", 400, 0)
    bound = []

    ext = ExtenderConfig(bind_callable=lambda p, node: bound.append(node) or {})
    res = solve_with_extenders(_pb(nodes, pod), [ext], max_limit=3)
    assert res.placed_count == 3
    assert bound == [res.node_names[i] for i in res.placements]


def test_bind_error_fails_loudly():
    nodes = [build_test_node("n0", 1000, 4 * 1024 ** 3, 5)]
    pod = build_test_pod("p", 100, 0)
    ext = ExtenderConfig(bind_callable=lambda p, n: {"Error": "no capacity"})
    with pytest.raises(RuntimeError, match="extender bind failed"):
        solve_with_extenders(_pb(nodes, pod), [ext], max_limit=2)


def test_managed_resources_gates_interest():
    """An extender managing example.com/gpu must be skipped for pods that
    don't request it (extender.go IsInterested)."""
    nodes = [build_test_node(f"n{i}", 1000, 4 * 1024 ** 3, 5,
                             extra_alloc={"example.com/gpu": "2"})
             for i in range(2)]
    calls = []

    def deny_all(pod, names):
        calls.append(len(names))
        return {"NodeNames": []}

    ext = ExtenderConfig(filter_callable=deny_all,
                         managed_resources=["example.com/gpu"])

    plain = build_test_pod("plain", 100, 0)
    res = solve_with_extenders(_pb(nodes, plain), [ext], max_limit=2)
    assert res.placed_count == 2 and not calls     # not interested -> skipped

    gpu = build_test_pod("gpu", 100, 0)
    gpu["spec"]["containers"][0]["resources"]["requests"]["example.com/gpu"] = "1"
    res = solve_with_extenders(_pb(nodes, gpu), [ext], max_limit=2)
    assert res.placed_count == 0 and calls         # interested -> denied


def test_process_preemption_restricts_candidates():
    """The preemption extender keeps only the nodes it returns; the
    evaluator must pick among them (preemption.go callExtenders)."""
    nodes = [build_test_node(f"n{i}", 1000, 4 * 1024 ** 3, 5)
             for i in range(3)]
    pods = []
    for i in range(3):
        p = build_test_pod(f"low-{i}", 900, 0, node_name=f"n{i}")
        p["spec"]["priority"] = 0
        pods.append(p)
    vip = default_pod(build_test_pod("vip", 900, 0))
    vip["spec"]["priority"] = 10

    # without the extender: pickOneNode takes the first node in order (n0)
    profile = SchedulerProfile.parity()
    cc = ClusterCapacity(vip, max_limit=1, profile=profile)
    cc.snapshot = ClusterSnapshot.from_objects(nodes, pods)
    baseline = cc.run()
    assert baseline.placed_count == 1 and baseline.placements == [0]

    # the extender only accepts n2 as a preemption candidate
    def only_n2(pod, node_to_victims):
        return {n: v for n, v in node_to_victims.items() if n == "n2"}

    profile2 = SchedulerProfile.parity()
    profile2.extenders = [ExtenderConfig(preempt_callable=only_n2)]
    cc2 = ClusterCapacity(vip, max_limit=1, profile=profile2)
    cc2.snapshot = ClusterSnapshot.from_objects(nodes, pods)
    res = cc2.run()
    assert res.placed_count == 1 and res.placements == [2]


def test_added_affinity_preferred_terms_score():
    """NodeAffinityArgs.addedAffinity preferred terms steer scoring for every
    pod of the profile (node_affinity.go:98-106)."""
    nodes = [build_test_node("big", 8000, 16 * 1024 ** 3, 50,
                             labels={"tier": "standard"}),
             build_test_node("small", 2000, 16 * 1024 ** 3, 50,
                             labels={"tier": "preferred"})]
    pod = build_test_pod("p", 100, 0)
    profile = SchedulerProfile.parity()
    base = sim.solve(_pb(nodes, pod, profile), max_limit=1)
    assert base.placements == [0]      # least-allocated prefers the big node

    profile2 = SchedulerProfile.parity()
    profile2.added_affinity = {
        "preferredDuringSchedulingIgnoredDuringExecution": [{
            "weight": 100,
            "preference": {"matchExpressions": [{
                "key": "tier", "operator": "In",
                "values": ["preferred"]}]}}]}
    res = sim.solve(_pb(nodes, pod, profile2), max_limit=1)
    assert res.placements == [1]       # weight-100 preference wins


def test_config_validation_rejects(tmp_path):
    bad = tmp_path / "bad.yaml"
    bad.write_text("""
apiVersion: kubescheduler.config.k8s.io/v1
kind: KubeSchedulerConfiguration
profiles:
- plugins:
    score:
      enabled:
      - name: NodeResourcesFitt
""")
    with pytest.raises(ConfigValidationError, match="NodeResourcesFitt"):
        load_scheduler_config(str(bad))

    bad2 = tmp_path / "bad2.yaml"
    bad2.write_text("""
kind: SomethingElse
profiles: []
""")
    with pytest.raises(ConfigValidationError, match="kind"):
        load_scheduler_config(str(bad2))

    bad3 = tmp_path / "bad3.yaml"
    bad3.write_text("""
profiles:
- percentageOfNodesToScore: 250
""")
    with pytest.raises(ConfigValidationError, match="percentageOfNodesToScore"):
        load_scheduler_config(str(bad3))

    ok = tmp_path / "ok.yaml"
    ok.write_text("""
apiVersion: kubescheduler.config.k8s.io/v1
kind: KubeSchedulerConfiguration
profiles:
- plugins:
    score:
      enabled:
      - name: NodeResourcesFit
        weight: 5
""")
    prof = load_scheduler_config(str(ok))
    assert prof.score_weights["NodeResourcesFit"] == 5


def test_config_extender_verbs_parse(tmp_path):
    cfgf = tmp_path / "ext.yaml"
    cfgf.write_text("""
apiVersion: kubescheduler.config.k8s.io/v1
kind: KubeSchedulerConfiguration
extenders:
- urlPrefix: http://127.0.0.1:9999/scheduler
  filterVerb: filter
  bindVerb: bind
  preemptVerb: preempt
  weight: 2
  managedResources:
  - name: example.com/gpu
    ignoredByScheduler: true
profiles:
- plugins: {}
""")
    prof = load_scheduler_config(str(cfgf))
    assert len(prof.extenders) == 1
    ext = prof.extenders[0]
    assert ext.is_binder and ext.supports_preemption
    assert ext.managed_resources == ["example.com/gpu"]


def test_config_validation_malformed_types(tmp_path):
    """Regression: malformed TYPES raise ConfigValidationError, not raw
    tracebacks."""
    bad = tmp_path / "types.yaml"
    bad.write_text("""
profiles:
- plugins:
    filter:
    - name: NodeAffinity
""")
    with pytest.raises(ConfigValidationError):
        load_scheduler_config(str(bad))

    bad2 = tmp_path / "weight.yaml"
    bad2.write_text("""
profiles:
- plugins:
    score:
      enabled:
      - name: NodeResourcesFit
        weight: abc
""")
    with pytest.raises(ConfigValidationError, match="weight"):
        load_scheduler_config(str(bad2))

    bad3 = tmp_path / "noprefix.yaml"
    bad3.write_text("""
extenders:
- filterVerb: filter
  managedResources:
  - name: example.com/gpu
""")
    with pytest.raises(ConfigValidationError, match="urlPrefix"):
        load_scheduler_config(str(bad3))


def test_preempt_callable_cannot_invent_nodes():
    """Regression: a preempt callable returning unknown nodes must not crash
    or resurrect non-candidates."""
    nodes = [build_test_node(f"n{i}", 1000, 4 * 1024 ** 3, 5)
             for i in range(2)]
    pods = []
    for i in range(2):
        p = build_test_pod(f"low-{i}", 900, 0, node_name=f"n{i}")
        p["spec"]["priority"] = 0
        pods.append(p)
    vip = default_pod(build_test_pod("vip", 900, 0))
    vip["spec"]["priority"] = 10

    def invent(pod, node_to_victims):
        out = dict(node_to_victims)
        out["ghost-node"] = []
        return out

    profile = SchedulerProfile.parity()
    profile.extenders = [ExtenderConfig(preempt_callable=invent)]
    cc = ClusterCapacity(vip, max_limit=1, profile=profile)
    cc.snapshot = ClusterSnapshot.from_objects(nodes, pods)
    res = cc.run()
    assert res.placed_count == 1 and res.placements == [0]


def test_preempt_extender_json_roundtrip_victims():
    """Regression: an HTTP-style extender returns NEW victim dicts (JSON
    round-trip); eviction must still work (key-based matching), no infinite
    preemption loop."""
    import copy

    nodes = [build_test_node("n0", 1000, 4 * 1024 ** 3, 5)]
    low = build_test_pod("low", 900, 0, node_name="n0")
    low["spec"]["priority"] = 0
    vip = default_pod(build_test_pod("vip", 900, 0))
    vip["spec"]["priority"] = 10

    def roundtrip(pod, node_to_victims):
        return {n: [copy.deepcopy(p) for p in v]
                for n, v in node_to_victims.items()}

    profile = SchedulerProfile.parity()
    profile.extenders = [ExtenderConfig(preempt_callable=roundtrip)]
    cc = ClusterCapacity(vip, max_limit=1, profile=profile)
    cc.snapshot = ClusterSnapshot.from_objects(nodes, [low])
    res = cc.run()
    assert res.placed_count == 1 and res.placements == [0]

"""Multi-chip sharding: the full solve step jitted over a (batch, nodes) mesh
on the 8-device virtual CPU topology, plus sharded-vs-unsharded equivalence."""

import jax
import numpy as np
import pytest

needs_8 = pytest.mark.skipif(len(jax.devices()) < 8,
                             reason="needs 8 virtual devices")


@needs_8
def test_dryrun_multichip():
    import __graft_entry__ as g
    g.dryrun_multichip(8)


@needs_8
def test_sharded_sweep_matches_unsharded():
    from cluster_capacity_tpu import SchedulerProfile
    from cluster_capacity_tpu.models.snapshot import ClusterSnapshot
    from cluster_capacity_tpu.models.podspec import default_pod
    from cluster_capacity_tpu.parallel import mesh as mesh_lib
    from cluster_capacity_tpu.parallel.sweep import sweep

    from helpers import build_test_node, build_test_pod

    nodes = [build_test_node(f"n{i:02d}", 8000, 32 * 1024 ** 3, 50)
             for i in range(16)]
    snapshot = ClusterSnapshot.from_objects(nodes)
    templates = [default_pod(build_test_pod(f"t{k}", 100 * (k + 1),
                                            (k + 1) * 512 * 1024 ** 2))
                 for k in range(4)]
    profile = SchedulerProfile.parity()
    plain = sweep(snapshot, templates, profile=profile, max_limit=40)
    mesh = mesh_lib.make_mesh(n_node_shards=4, n_batch_shards=2)
    sharded = sweep(snapshot, templates, profile=profile, max_limit=40,
                    mesh=mesh)
    for a, b in zip(plain, sharded):
        assert a.placements == b.placements
        assert a.fail_type == b.fail_type


@needs_8
def test_sharded_topology_state_matches_unsharded():
    """Carried spread/IPA per-node counts sharded over the node axis must
    reproduce the unsharded placements exactly."""
    from cluster_capacity_tpu import SchedulerProfile
    from cluster_capacity_tpu.models.snapshot import ClusterSnapshot
    from cluster_capacity_tpu.models.podspec import default_pod
    from cluster_capacity_tpu.parallel import mesh as mesh_lib
    from cluster_capacity_tpu.parallel.sweep import sweep

    nodes = []
    for i in range(24):
        nodes.append({
            "metadata": {"name": f"n{i:02d}",
                         "labels": {"kubernetes.io/hostname": f"n{i:02d}",
                                    "topology.kubernetes.io/zone": f"z{i % 3}"}},
            "spec": {},
            "status": {"allocatable": {"cpu": "4000m",
                                       "memory": str(8 * 1024 ** 3),
                                       "pods": "20"}}})
    snapshot = ClusterSnapshot.from_objects(nodes)

    templates = [
        default_pod({"metadata": {"name": "sp", "labels": {"app": "sp"}},
                     "spec": {"containers": [{"name": "c", "resources": {
                         "requests": {"cpu": "300m", "memory": "512Mi"}}}],
                     "topologySpreadConstraints": [{
                         "maxSkew": 1,
                         "topologyKey": "topology.kubernetes.io/zone",
                         "whenUnsatisfiable": "DoNotSchedule",
                         "labelSelector": {"matchLabels": {"app": "sp"}}}]}}),
        default_pod({"metadata": {"name": "anti", "labels": {"app": "anti"}},
                     "spec": {"containers": [{"name": "c", "resources": {
                         "requests": {"cpu": "200m"}}}],
                     "affinity": {"podAntiAffinity": {
                         "requiredDuringSchedulingIgnoredDuringExecution": [{
                             "topologyKey": "topology.kubernetes.io/zone",
                             "labelSelector": {
                                 "matchLabels": {"app": "anti"}}}]}}}}),
        default_pod({"metadata": {"name": "aff", "labels": {"app": "aff"}},
                     "spec": {"containers": [{"name": "c", "resources": {
                         "requests": {"cpu": "250m"}}}],
                     "affinity": {"podAffinity": {
                         "requiredDuringSchedulingIgnoredDuringExecution": [{
                             "topologyKey": "topology.kubernetes.io/zone",
                             "labelSelector": {
                                 "matchLabels": {"app": "aff"}}}]}}}}),
    ]
    profile = SchedulerProfile.parity()
    plain = sweep(snapshot, templates, profile=profile, max_limit=30)
    mesh = mesh_lib.make_mesh(n_node_shards=4, n_batch_shards=2)
    sharded = sweep(snapshot, templates, profile=profile, max_limit=30,
                    mesh=mesh)
    for t, a, b in zip(templates, plain, sharded):
        name = t["metadata"]["name"]
        assert a.placements == b.placements, name
        assert a.fail_type == b.fail_type, name
        assert a.fail_message == b.fail_message, name


@needs_8
def test_sharded_small_limit_sweep_matches_unsharded():
    """Small-limit sweeps use the single-device batched analytic solve
    ONLY without a mesh; under a mesh the spread group (2 templates ->
    a real batchable group) runs the SHARDED scan and the plain templates
    the unbounded analytic path — all equal to the meshless solve."""
    from cluster_capacity_tpu import SchedulerProfile
    from cluster_capacity_tpu.models.podspec import default_pod
    from cluster_capacity_tpu.models.snapshot import ClusterSnapshot
    from cluster_capacity_tpu.parallel import mesh as mesh_lib
    from cluster_capacity_tpu.parallel.sweep import sweep

    from helpers import build_test_node, build_test_pod

    nodes = [build_test_node(f"n{i:02d}", 8000, 32 * 1024 ** 3, 50,
                             labels={"kubernetes.io/hostname": f"n{i:02d}",
                                     "topology.kubernetes.io/zone":
                                         f"z{i % 2}"})
             for i in range(16)]
    snapshot = ClusterSnapshot.from_objects(nodes)
    templates = [default_pod(build_test_pod(f"t{k}", 150 * (k + 1),
                                            (k + 1) * 256 * 1024 ** 2))
                 for k in range(4)]
    for name in ("sp-a", "sp-b"):      # 2 same-shape spread templates ->
        spread = build_test_pod(name, 200, 0, labels={"app": name})
        spread["spec"]["topologySpreadConstraints"] = [{
            "maxSkew": 1, "topologyKey": "topology.kubernetes.io/zone",
            "whenUnsatisfiable": "DoNotSchedule",
            "labelSelector": {"matchLabels": {"app": name}}}]
        templates.append(default_pod(spread))  # a real sharded scan group
    profile = SchedulerProfile.parity()
    plain = sweep(snapshot, templates, profile=profile, max_limit=5)
    mesh = mesh_lib.make_mesh(n_node_shards=4, n_batch_shards=2)
    sharded = sweep(snapshot, templates, profile=profile, max_limit=5,
                    mesh=mesh)
    for a, b in zip(plain, sharded):
        assert a.placements == b.placements
        assert a.fail_type == b.fail_type

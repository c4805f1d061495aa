"""Differential tests: fused Pallas kernel vs the XLA scan step.

Runs the kernel in interpreter mode (no TPU needed) and requires bit-identical
placement sequences, stop messages, and carried state against engine.simulator
solves with the kernel disabled.  On real TPU hardware the same guarantee is
enforced at runtime by make_runner's 48-step cross-check.
"""

import os

import numpy as np
import pytest

from cluster_capacity_tpu.engine import encode as enc
from cluster_capacity_tpu.engine import fused
from cluster_capacity_tpu.engine import simulator as sim
from cluster_capacity_tpu.models.podspec import default_pod
from cluster_capacity_tpu.models.snapshot import ClusterSnapshot
from cluster_capacity_tpu.utils.config import SchedulerProfile


def _nodes(n, seed=0, zones=4, taints=False):
    rng = np.random.RandomState(seed)
    out = []
    for i in range(n):
        node = {
            "metadata": {"name": f"node-{i:04d}",
                         "labels": {"kubernetes.io/hostname": f"node-{i:04d}",
                                    "topology.kubernetes.io/zone": f"z{i % zones}"}},
            "spec": {},
            "status": {"allocatable": {
                "cpu": f"{int(rng.choice([2000, 4000, 8000]))}m",
                "memory": str(int(rng.choice([4, 8, 16])) * 1024 ** 3),
                "pods": "32"}},
        }
        if taints and i % 3 == 0:
            node["spec"]["taints"] = [{"key": "dedicated", "value": "x",
                                       "effect": "PreferNoSchedule"}]
        out.append(node)
    return out


def _solve_both(nodes, pod, profile=None, max_limit=0, existing=None):
    """Solve with the fused kernel forced on, then with it off; compare."""
    profile = profile or SchedulerProfile()
    snap = ClusterSnapshot.from_objects(nodes, pods=existing or [])
    pb = enc.encode_problem(snap, default_pod(pod), profile)
    cfg = sim.static_config(pb)

    os.environ["CC_TPU_FUSED"] = "1"
    fused._failed_metas.clear()
    chunks_before = fused.STATS["chunks"]
    try:
        assert fused.eligible(cfg, pb), "scenario must be kernel-eligible"
        r_fused = sim.solve(pb, max_limit=max_limit, chunk_size=128)
        # guard against a vacuous pass: the cross-check silently falling
        # back to XLA would make the comparison XLA-vs-XLA
        assert not fused._failed_metas, \
            "kernel diverged from the XLA step (cross-check fallback fired)"
        assert fused.STATS["chunks"] > chunks_before, "kernel never ran"
    finally:
        os.environ["CC_TPU_FUSED"] = "0"
    r_xla = sim.solve(pb, max_limit=max_limit, chunk_size=128)
    os.environ.pop("CC_TPU_FUSED", None)

    assert r_fused.placements == r_xla.placements
    assert r_fused.fail_type == r_xla.fail_type
    assert r_fused.fail_message == r_xla.fail_message
    return r_fused


def test_fit_only():
    pod = {"metadata": {"name": "p"}, "spec": {"containers": [
        {"name": "c", "resources": {"requests": {"cpu": "700m",
                                                 "memory": "1Gi"}}}]}}
    r = _solve_both(_nodes(40), pod)
    assert r.placed_count > 0


def test_spread_hard():
    pod = {"metadata": {"name": "p", "labels": {"app": "web"}}, "spec": {
        "containers": [{"name": "c", "resources": {
            "requests": {"cpu": "500m", "memory": "1Gi"}}}],
        "topologySpreadConstraints": [{
            "maxSkew": 2, "topologyKey": "topology.kubernetes.io/zone",
            "whenUnsatisfiable": "DoNotSchedule",
            "labelSelector": {"matchLabels": {"app": "web"}}}]}}
    r = _solve_both(_nodes(50, zones=5), pod)
    assert r.placed_count > 0


def test_spread_hard_hostname_and_zone():
    pod = {"metadata": {"name": "p", "labels": {"app": "db"}}, "spec": {
        "containers": [{"name": "c", "resources": {
            "requests": {"cpu": "300m"}}}],
        "topologySpreadConstraints": [
            {"maxSkew": 1, "topologyKey": "kubernetes.io/hostname",
             "whenUnsatisfiable": "DoNotSchedule",
             "labelSelector": {"matchLabels": {"app": "db"}}},
            {"maxSkew": 1, "topologyKey": "topology.kubernetes.io/zone",
             "whenUnsatisfiable": "DoNotSchedule",
             "labelSelector": {"matchLabels": {"app": "db"}}}]}}
    _solve_both(_nodes(24, zones=3), pod)


def test_taints_and_sampling():
    pod = {"metadata": {"name": "p"}, "spec": {"containers": [
        {"name": "c", "resources": {"requests": {"cpu": "900m"}}}]}}
    profile = SchedulerProfile()
    profile.percentage_of_nodes_to_score = 40
    r = _solve_both(_nodes(120, taints=True), pod, profile=profile)
    assert r.placed_count > 0


def test_inter_pod_affinity_colocate():
    pod = {"metadata": {"name": "p", "labels": {"app": "a"}}, "spec": {
        "containers": [{"name": "c", "resources": {
            "requests": {"cpu": "400m"}}}],
        "affinity": {"podAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": [{
                "topologyKey": "topology.kubernetes.io/zone",
                "labelSelector": {"matchLabels": {"app": "a"}}}]}}}}
    r = _solve_both(_nodes(30, zones=3), pod)
    zones = {i % 3 for i in r.placements}
    assert len(zones) == 1   # colocated in one zone


def test_anti_affinity_one_per_zone():
    pod = {"metadata": {"name": "p", "labels": {"app": "b"}}, "spec": {
        "containers": [{"name": "c", "resources": {
            "requests": {"cpu": "100m"}}}],
        "affinity": {"podAntiAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": [{
                "topologyKey": "topology.kubernetes.io/zone",
                "labelSelector": {"matchLabels": {"app": "b"}}}]}}}}
    r = _solve_both(_nodes(20, zones=4), pod)
    assert r.placed_count == 4   # one per zone


def test_preferred_affinity_scoring():
    existing = [{"metadata": {"name": "seed", "labels": {"tier": "cache"},
                              "namespace": "default"},
                 "spec": {"nodeName": "node-0002", "containers": [
                     {"name": "c", "resources": {
                         "requests": {"cpu": "100m"}}}]}}]
    pod = {"metadata": {"name": "p", "labels": {"app": "c"}}, "spec": {
        "containers": [{"name": "c", "resources": {
            "requests": {"cpu": "600m"}}}],
        "affinity": {"podAffinity": {
            "preferredDuringSchedulingIgnoredDuringExecution": [{
                "weight": 50, "podAffinityTerm": {
                    "topologyKey": "topology.kubernetes.io/zone",
                    "labelSelector": {"matchLabels": {"tier": "cache"}}}}]}}}}
    _solve_both(_nodes(16, zones=4), pod, existing=existing)


def test_max_limit_and_ports():
    pod = {"metadata": {"name": "p"}, "spec": {"containers": [
        {"name": "c", "ports": [{"hostPort": 8080}],
         "resources": {"requests": {"cpu": "100m"}}}]}}
    r = _solve_both(_nodes(12), pod)
    assert r.placed_count == 12   # one per node (host port conflict)
    r2 = _solve_both(_nodes(12), pod, max_limit=5)
    assert r2.placed_count == 5 and r2.fail_type == sim.FAIL_LIMIT_REACHED


def test_most_allocated_strategy():
    profile = SchedulerProfile()
    profile.fit_strategy.type = "MostAllocated"
    pod = {"metadata": {"name": "p"}, "spec": {"containers": [
        {"name": "c", "resources": {"requests": {"cpu": "500m",
                                                 "memory": "512Mi"}}}]}}
    _solve_both(_nodes(25), pod, profile=profile)


def test_runtime_mismatch_disables(monkeypatch):
    """A divergent kernel must be rejected by the 48-step cross-check."""
    pod = {"metadata": {"name": "p"}, "spec": {"containers": [
        {"name": "c", "resources": {"requests": {"cpu": "100m"}}}]}}
    snap = ClusterSnapshot.from_objects(_nodes(30))
    pb = enc.encode_problem(snap, default_pod(pod), SchedulerProfile())
    cfg = sim.static_config(pb)
    consts = sim.build_consts(pb)
    carry = sim._init_carry(pb, consts, 0)

    class Bad(fused.FusedRunner):
        def run_chunk(self, c, k):
            nc, chosen = super().run_chunk(c, k)
            chosen = chosen.copy()
            if len(chosen):
                chosen[0] = (chosen[0] + 1) % 30
            return nc, chosen

    monkeypatch.setenv("CC_TPU_FUSED", "1")
    fused._failed_metas.clear()
    monkeypatch.setattr(fused, "FusedRunner", Bad)
    runner = fused.make_runner(cfg, pb, consts,
                               verify_against=(consts, carry, 48))
    assert runner is None and fused._failed_metas
    fused._failed_metas.clear()


@pytest.mark.parametrize("batched", [False, True])
def test_chip_kernel_failure_raises(batched):
    """On the chip (interpret=False) a kernel failure is a KernelFault that
    crosses the guard, never a silent switch to the XLA scan."""
    from types import SimpleNamespace
    from cluster_capacity_tpu.engine import fused_batched
    from cluster_capacity_tpu.runtime.errors import KernelFault
    runner = SimpleNamespace(pk=SimpleNamespace(meta=SimpleNamespace(n=5000)),
                             interpret=False, b=8, key=("k",))
    mark = fused_batched._mark_failed if batched else fused.mark_failed
    with pytest.raises(KernelFault, match="n=5000: divergence"):
        mark(runner, "divergence", ValueError("x"))
    assert not fused._failed_metas and not fused_batched._failed_keys


def _fuzz_pod_f32(rng):
    """Kernel-eligible mixed-family pod: fit + taints + hard AND soft
    spread + IPA."""
    pod = {"metadata": {"name": "t", "labels": {"app": str(rng.choice(
        ["web", "db", "cache"]))}},
        "spec": {"containers": [{"name": "c", "resources": {"requests": {
            "cpu": f"{int(rng.choice([100, 300, 700]))}m",
            "memory": str(int(rng.choice([128, 512])) * 1024 ** 2)}}}]}}
    if rng.rand() < 0.5:
        pod["spec"]["topologySpreadConstraints"] = [{
            "maxSkew": int(rng.choice([1, 2])),
            "topologyKey": str(rng.choice(["topology.kubernetes.io/zone",
                                           "kubernetes.io/hostname"])),
            "whenUnsatisfiable": str(rng.choice(["DoNotSchedule",
                                                 "ScheduleAnyway"])),
            "labelSelector": {"matchLabels": dict(pod["metadata"]["labels"])}}]
    aff = {}
    if rng.rand() < 0.3:
        aff["podAffinity"] = {
            "requiredDuringSchedulingIgnoredDuringExecution": [{
                "topologyKey": "topology.kubernetes.io/zone",
                "labelSelector": {"matchLabels": {
                    "app": str(rng.choice(["web", "db"]))}}}]}
    if rng.rand() < 0.3:
        aff["podAntiAffinity"] = {
            "requiredDuringSchedulingIgnoredDuringExecution": [{
                "topologyKey": "kubernetes.io/hostname",
                "labelSelector": {"matchLabels": {
                    "app": str(rng.choice(["web", "db"]))}}}]}
    if aff:
        pod["spec"]["affinity"] = aff
    if rng.rand() < 0.3:
        pod["spec"]["tolerations"] = [{"key": "dedicated",
                                       "operator": "Exists"}]
    return pod


def _run_fused_fuzz(seed):
    rng = np.random.RandomState(seed)
    nodes = _nodes(int(rng.choice([12, 24, 40])), seed=seed,
                   zones=int(rng.choice([3, 4])), taints=bool(rng.rand() < 0.5))
    profile = SchedulerProfile()          # float32 — kernel-eligible
    if rng.rand() < 0.3:
        profile.percentage_of_nodes_to_score = int(rng.choice([40, 70]))
    pod = _fuzz_pod_f32(rng)
    snap = ClusterSnapshot.from_objects(
        nodes, namespaces=[{"metadata": {"name": "default"}}])
    pb = enc.encode_problem(snap, default_pod(pod), profile)
    cfg = sim.static_config(pb)
    if not (cfg.deterministic and not cfg.dtype64):
        return

    os.environ["CC_TPU_FUSED"] = "1"
    fused._failed_metas.clear()
    try:
        r_fused = sim.solve(pb, max_limit=60, chunk_size=64)
        assert not fused._failed_metas, f"seed {seed}: kernel diverged"
    finally:
        os.environ["CC_TPU_FUSED"] = "0"
    r_xla = sim.solve(pb, max_limit=60, chunk_size=64)
    os.environ.pop("CC_TPU_FUSED", None)
    assert r_fused.placements == r_xla.placements, f"seed {seed}"
    assert r_fused.fail_message == r_xla.fail_message, f"seed {seed}"


@pytest.mark.parametrize("seed", range(7000, 7006))
def test_fused_fuzz_slice(seed):
    _run_fused_fuzz(seed)


@pytest.mark.fuzz
@pytest.mark.parametrize("seed", range(7100, 7160))
def test_fused_fuzz_full(seed):
    _run_fused_fuzz(seed)


def test_soft_spread_scoring():
    """Soft (ScheduleAnyway) spread scoring in the kernel: zone + hostname
    constraints, carried counts + distinct-domain sizing."""
    pod = {"metadata": {"name": "p", "labels": {"app": "soft"}}, "spec": {
        "containers": [{"name": "c", "resources": {
            "requests": {"cpu": "400m"}}}],
        "topologySpreadConstraints": [
            {"maxSkew": 1, "topologyKey": "topology.kubernetes.io/zone",
             "whenUnsatisfiable": "ScheduleAnyway",
             "labelSelector": {"matchLabels": {"app": "soft"}}},
            {"maxSkew": 2, "topologyKey": "kubernetes.io/hostname",
             "whenUnsatisfiable": "ScheduleAnyway",
             "labelSelector": {"matchLabels": {"app": "soft"}}}]}}
    r = _solve_both(_nodes(24, zones=3), pod)
    assert r.placed_count > 0
    # soft zone spreading must actually spread across the 3 zones
    zones = {i % 3 for i in r.placements[:3]}
    assert len(zones) == 3


def test_soft_and_hard_spread_mixed():
    pod = {"metadata": {"name": "p", "labels": {"app": "mix"}}, "spec": {
        "containers": [{"name": "c", "resources": {
            "requests": {"cpu": "600m"}}}],
        "topologySpreadConstraints": [
            {"maxSkew": 2, "topologyKey": "topology.kubernetes.io/zone",
             "whenUnsatisfiable": "DoNotSchedule",
             "labelSelector": {"matchLabels": {"app": "mix"}}},
            {"maxSkew": 1, "topologyKey": "topology.kubernetes.io/zone",
             "whenUnsatisfiable": "ScheduleAnyway",
             "labelSelector": {"matchLabels": {"app": "mix"}}}]}}
    _solve_both(_nodes(30, zones=5), pod)


def test_system_default_spreading():
    """Service-selected pods with no explicit constraints get the system
    default soft spreading (zone skew 3, hostname skew 5) — the common
    real-cluster shape the kernel must cover."""
    pod = {"metadata": {"name": "p", "labels": {"app": "svc"},
                        "namespace": "default"},
           "spec": {"containers": [{"name": "c", "resources": {
               "requests": {"cpu": "300m"}}}]}}
    profile = SchedulerProfile()
    snap = ClusterSnapshot.from_objects(
        _nodes(20, zones=4),
        services=[{"metadata": {"name": "s", "namespace": "default"},
                   "spec": {"selector": {"app": "svc"}}}],
        namespaces=[{"metadata": {"name": "default"}}])
    pb = enc.encode_problem(snap, default_pod(pod), profile)
    cfg = sim.static_config(pb)

    os.environ["CC_TPU_FUSED"] = "1"
    fused._failed_metas.clear()
    chunks_before = fused.STATS["chunks"]
    try:
        assert fused.eligible(cfg, pb)
        r_fused = sim.solve(pb, max_limit=40, chunk_size=128)
        assert not fused._failed_metas
        assert fused.STATS["chunks"] > chunks_before
    finally:
        os.environ["CC_TPU_FUSED"] = "0"
    r_xla = sim.solve(pb, max_limit=40, chunk_size=128)
    os.environ.pop("CC_TPU_FUSED", None)
    assert r_fused.placements == r_xla.placements
    assert r_fused.fail_message == r_xla.fail_message


def test_requested_to_capacity_ratio_strategy():
    """RTC scoring strategy in both paths, sharing one piecewise helper.
    Shape prefers ~50% utilization -> medium nodes win over empty big ones."""
    profile = SchedulerProfile()
    profile.fit_strategy.type = "RequestedToCapacityRatio"
    profile.fit_strategy.shape_utilization = [0.0, 50.0, 100.0]
    profile.fit_strategy.shape_score = [0.0, 10.0, 0.0]
    pod = {"metadata": {"name": "p"}, "spec": {"containers": [
        {"name": "c", "resources": {"requests": {"cpu": "400m",
                                                 "memory": "512Mi"}}}]}}
    r = _solve_both(_nodes(25), pod, profile=profile)
    assert r.placed_count > 0


def test_rtc_shape_behavior():
    """Engine-level RTC semantics: a utilization-50-peaked shape places on
    the half-full node first (requested_to_capacity_ratio.go:60)."""
    import sys
    sys.path.insert(0, "tests")
    from helpers import build_test_node, build_test_pod

    profile = SchedulerProfile.parity()
    profile.fit_strategy.type = "RequestedToCapacityRatio"
    profile.fit_strategy.shape_utilization = [0.0, 50.0, 100.0]
    profile.fit_strategy.shape_score = [0.0, 10.0, 0.0]
    nodes = [build_test_node("empty", 1000, int(1e12), 50),
             build_test_node("half", 1000, int(1e12), 50)]
    existing = [build_test_pod("e0", 400, 0, node_name="half")]
    snap = ClusterSnapshot.from_objects(nodes, pods=existing)
    pb = enc.encode_problem(snap, default_pod(build_test_pod("p", 100, -1)),
                            profile)
    res = sim.solve(pb, max_limit=1)
    # empty: util (0+100)/1000 = 10 -> score 2*10=20ish; half: util 50 -> peak
    assert res.placements == [snap.node_names.index("half")]


def test_pack_unpack_roundtrip():
    """FusedRunner.pack/unpack must preserve the carry exactly — a plane
    ordering or padding bug here would corrupt every chunk boundary."""
    import jax

    pod = {"metadata": {"name": "p", "labels": {"app": "rt"}}, "spec": {
        "containers": [{"name": "c", "resources": {
            "requests": {"cpu": "300m", "memory": "512Mi"}}}],
        "topologySpreadConstraints": [{
            "maxSkew": 1, "topologyKey": "topology.kubernetes.io/zone",
            "whenUnsatisfiable": "DoNotSchedule",
            "labelSelector": {"matchLabels": {"app": "rt"}}}],
        "affinity": {"podAntiAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": [{
                "topologyKey": "kubernetes.io/hostname",
                "labelSelector": {"matchLabels": {"app": "rt"}}}]}}}}
    snap = ClusterSnapshot.from_objects(_nodes(30, zones=3))
    pb = enc.encode_problem(snap, default_pod(pod), SchedulerProfile())
    cfg = sim.static_config(pb)
    consts = sim.build_consts(pb)
    carry = sim._init_carry(pb, consts, 0)
    # advance a few steps so the carry is non-trivial
    run = sim._chunk_runner()
    carry, _ = run(cfg, consts, carry, 5)

    runner = fused.FusedRunner(cfg, pb, consts, interpret=True)
    state = runner.pack(carry)
    back = runner.unpack(state, carry)
    for name in ("requested", "nonzero", "placed", "sh_cnt", "aff_cnt",
                 "anti_cnt", "placed_count", "stopped", "next_start",
                 "aff_total"):
        a = np.asarray(getattr(carry, name))
        b = np.asarray(getattr(back, name))
        assert np.array_equal(a, b), name


# ---------------------------------------------------------------------------
# Mid-solve verification checkpoints
# ---------------------------------------------------------------------------

def _ckpt_problem():
    nodes = _nodes(6, seed=11)
    pod = {"metadata": {"name": "p", "labels": {"app": "ck"}},
           "spec": {"containers": [{"name": "c", "resources": {"requests": {
               "cpu": "10m"}}}]}}
    snap = ClusterSnapshot.from_objects(nodes)
    pb = enc.encode_problem(snap, default_pod(pod), SchedulerProfile())
    return pb


def test_verify_checkpoints_shape():
    assert fused.verify_checkpoints(100000, 4096) == (4096, 16384, 65536)
    assert fused.verify_checkpoints(300000, 4096) == (
        4096, 16384, 65536, 262144)
    assert fused.verify_checkpoints(1000, 4096) == ()
    assert fused.verify_checkpoints(200, 32) == (32,)


def test_midsolve_checkpoint_verifies(monkeypatch):
    """With a small fused chunk, a long solve crosses checkpoints and each
    gets verified against the XLA step exactly once per kernel shape."""
    monkeypatch.setenv("CC_TPU_FUSED", "1")
    monkeypatch.setattr(sim, "_FUSED_CHUNK", 32)
    monkeypatch.setattr(
        fused, "verify_checkpoints",
        lambda budget, chunk: tuple(c for c in (chunk, 96) if c < budget))
    fused._verified_windows.clear()
    before = len(fused.STATS["verified_windows"])
    pb = _ckpt_problem()
    r1 = sim.solve(pb, max_limit=200, chunk_size=32)
    windows = fused.STATS["verified_windows"][before:]
    assert [c for c, _n in windows] == [32, 96]
    monkeypatch.setenv("CC_TPU_FUSED", "0")
    r2 = sim.solve(pb, max_limit=200, chunk_size=32)
    assert r1.placements == r2.placements
    monkeypatch.setenv("CC_TPU_FUSED", "1")
    # second solve of the SAME problem: checkpoints memoized, no re-pay
    before = len(fused.STATS["verified_windows"])
    sim.solve(pb, max_limit=200, chunk_size=32)
    assert fused.STATS["verified_windows"][before:] == []
    # same kernel shape but DIFFERENT cluster data: must re-verify (the
    # memo key includes a fingerprint of the kernel's inputs)
    nodes2 = _nodes(6, seed=12)
    snap2 = ClusterSnapshot.from_objects(nodes2)
    pod2 = {"metadata": {"name": "p", "labels": {"app": "ck"}},
            "spec": {"containers": [{"name": "c", "resources": {"requests": {
                "cpu": "10m"}}}]}}
    pb2 = enc.encode_problem(snap2, default_pod(pod2), SchedulerProfile())
    before = len(fused.STATS["verified_windows"])
    sim.solve(pb2, max_limit=200, chunk_size=32)
    assert [c for c, _n in fused.STATS["verified_windows"][before:]] \
        == [32, 96]


def test_midsolve_divergence_falls_back(monkeypatch):
    """A kernel that goes wrong AFTER the initial 48-step check is caught at
    the next checkpoint: placements truncate to the verified snapshot and
    the XLA scan finishes the solve — the final answer matches pure XLA."""
    monkeypatch.setenv("CC_TPU_FUSED", "1")
    monkeypatch.setattr(sim, "_FUSED_CHUNK", 32)
    monkeypatch.setattr(
        fused, "verify_checkpoints",
        lambda budget, chunk: (chunk,) if chunk < budget else ())
    fused._verified_windows.clear()
    fused._failed_metas.clear()
    pb = _ckpt_problem()

    orig_collect = fused.FusedRunner.collect

    def corrupt_collect(self, window):
        chosen, stopped = orig_collect(self, window)
        calls[0] += 1
        if calls[0] >= 2:       # windows after the first: corrupt the trace
            chosen = chosen.copy()
            chosen[: len(chosen) // 2] = 0
        return chosen, stopped

    calls = [0]
    monkeypatch.setattr(fused.FusedRunner, "collect", corrupt_collect)
    r1 = sim.solve(pb, max_limit=200, chunk_size=32)
    monkeypatch.setattr(fused.FusedRunner, "collect", orig_collect)

    monkeypatch.setenv("CC_TPU_FUSED", "0")
    r2 = sim.solve(pb, max_limit=200, chunk_size=32)
    assert r1.placements == r2.placements
    assert r1.fail_message == r2.fail_message
    fused._failed_metas.clear()


# ---------------------------------------------------------------------------
# The verification memo's key: a fingerprint of the kernel's inputs
# ---------------------------------------------------------------------------

def _fp_objects():
    """Six zoned nodes, three resident pods (app=other) and a template with
    a zonal DoNotSchedule spread over app=ck: (nodes, pods, template)."""
    nodes = _nodes(6, seed=11, zones=2)
    pods = [{"metadata": {"name": f"r{i}", "namespace": "default",
                          "labels": {"app": "other"}},
             "spec": {"nodeName": f"node-{i:04d}", "containers": [{
                 "name": "c", "resources": {"requests": {"cpu": "100m"}}}]}}
            for i in range(3)]
    pod = {"metadata": {"name": "p", "labels": {"app": "ck"}},
           "spec": {"containers": [{"name": "c", "resources": {"requests": {
               "cpu": "10m"}}}],
               "topologySpreadConstraints": [{
                   "maxSkew": 4, "topologyKey": "topology.kubernetes.io/zone",
                   "whenUnsatisfiable": "DoNotSchedule",
                   "labelSelector": {"matchLabels": {"app": "ck"}}}]}}
    return nodes, pods, pod


def _fp_problem(nodes, pods, pod, seed=0):
    snap = ClusterSnapshot.from_objects(nodes, pods)
    return enc.encode_problem(snap, default_pod(pod),
                              SchedulerProfile(seed=seed))


def _fp_digest(pb):
    return fused.kernel_input_fingerprint(sim.cached_static_config(pb), pb)


@pytest.fixture
def two_checkpoints(monkeypatch):
    """Fused kernel in interpret mode with 32-step chunks and checkpoints at
    steps 32 and 96; returns solve(pb) -> the checkpoints it verified."""
    monkeypatch.setenv("CC_TPU_FUSED", "1")
    monkeypatch.setattr(sim, "_FUSED_CHUNK", 32)
    monkeypatch.setattr(
        fused, "verify_checkpoints",
        lambda budget, chunk: tuple(c for c in (chunk, 96) if c < budget))
    fused._verified_windows.clear()

    def solve(pb):
        before = len(fused.STATS["verified_windows"])
        sim.solve(pb, max_limit=200, chunk_size=32)
        return [c for c, _n in fused.STATS["verified_windows"][before:]]
    return solve


def test_reencoded_problem_finds_its_checkpoints_verified(two_checkpoints):
    """The benchmark, `--period` and `--watch` re-encode the same cluster and
    template into a new problem object for every answer: the second object
    hashes the same and verifies no checkpoint again."""
    nodes, pods, pod = _fp_objects()
    snap = ClusterSnapshot.from_objects(nodes, pods)
    pb1 = enc.encode_problem(snap, default_pod(pod), SchedulerProfile())
    pb2 = enc.encode_problem(snap, default_pod(pod), SchedulerProfile())
    assert pb1 is not pb2
    assert two_checkpoints(pb1) == [32, 96]
    assert _fp_digest(pb2) == _fp_digest(pb1)
    assert two_checkpoints(pb2) == []


def _set_alloc(nodes, pods):
    nodes[4]["status"]["allocatable"]["cpu"] = "6000m"


def _set_resident_request(nodes, pods):
    pods[1]["spec"]["containers"][0]["resources"]["requests"]["cpu"] = "300m"


def _set_resident_label(nodes, pods):
    pods[2]["metadata"]["labels"]["app"] = "ck"


def _set_seed(nodes, pods):
    return 7


@pytest.mark.parametrize("change", [
    _set_alloc, _set_resident_request, _set_resident_label, _set_seed],
    ids=["node_allocatable", "resident_request", "resident_spread_label",
         "profile_seed"])
def test_kernel_visible_change_reverifies(two_checkpoints, change):
    """Every change the kernel can see gives a new digest, and the solve of
    the re-encoded problem verifies its checkpoints again.  A change edits
    the objects in place and may return a new profile seed."""
    nodes, pods, pod = _fp_objects()
    base = _fp_problem(nodes, pods, pod)
    assert two_checkpoints(base) == [32, 96]
    seed = change(nodes, pods) or 0
    changed = _fp_problem(nodes, pods, pod, seed=seed)
    assert _fp_digest(changed) != _fp_digest(base)
    assert two_checkpoints(changed) == [32, 96]


@pytest.mark.parametrize("change", ["rename", "annotate"])
def test_kernel_invisible_change_keeps_digest(change):
    """Pod names and annotations that nothing reads never reach the kernel:
    the digest stays, so no checkpoint is paid for again."""
    nodes, pods, pod = _fp_objects()
    base = _fp_digest(_fp_problem(nodes, pods, pod))
    if change == "rename":
        pods[0]["metadata"]["name"] = "renamed"
    else:
        pods[0]["metadata"]["annotations"] = {"note": "read by nothing"}
    assert _fp_digest(_fp_problem(nodes, pods, pod)) == base


def _const_keys():
    nodes, pods, pod = _fp_objects()
    return sorted(sim.build_consts(_fp_problem(nodes, pods, pod),
                                   device=False))


@pytest.mark.parametrize("key", _const_keys())
def test_every_const_is_in_the_digest(monkeypatch, key):
    """Changing any one array of the host const dict changes the digest, so
    a const added later is covered without an edit to the fingerprint."""
    nodes, pods, pod = _fp_objects()
    base = _fp_digest(_fp_problem(nodes, pods, pod))
    build = sim.build_consts

    def perturbed(pb, *a, **kw):
        consts = dict(build(pb, *a, **kw))
        arr = np.array(consts[key])
        if arr.size == 0:
            arr = np.zeros((1,) + arr.shape[1:], arr.dtype)
        elif arr.dtype == bool:
            arr.flat[0] = not arr.flat[0]
        else:
            arr.flat[0] += 1
        consts[key] = arr
        return consts
    monkeypatch.setattr(sim, "build_consts", perturbed)
    assert _fp_digest(_fp_problem(nodes, pods, pod)) != base


def test_verify_lookup_span_counts_due_checkpoints(two_checkpoints):
    """The `cc.verify` span around the memo lookup carries `due`, the
    checkpoints still unverified for the key: both on the first solve,
    none on the repeat."""
    from cluster_capacity_tpu import obs

    def due_of(pb):
        obs.default_collector.reset()
        two_checkpoints(pb)
        return [s.attrs["due"] for s in obs.default_collector.spans()
                if s.name == "cc.verify" and "due" in s.attrs]

    pb = _fp_problem(*_fp_objects())
    assert due_of(pb) == [2]
    assert due_of(pb) == [0]

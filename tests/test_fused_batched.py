"""Differential tests: batched fused kernel vs the vmapped XLA scan.

The batched kernel (engine/fused_batched.py) runs a whole padded template
group per Pallas call with per-template scalars in SMEM; it must be
bit-identical to _batched_solve's vmapped XLA path (which itself is proven
equal to per-template sequential solves in test_sweep_batched.py).  Runs in
interpreter mode on CPU; on TPU the 48-step runtime cross-check enforces the
same guarantee.
"""

import os

import numpy as np
import pytest

from cluster_capacity_tpu.engine import encode as enc
from cluster_capacity_tpu.engine import fused_batched
from cluster_capacity_tpu.engine import simulator as sim
from cluster_capacity_tpu.models.podspec import default_pod
from cluster_capacity_tpu.models.snapshot import ClusterSnapshot
from cluster_capacity_tpu.parallel import sweep as sweep_mod
from cluster_capacity_tpu.utils.config import SchedulerProfile

from test_sweep_batched import _cluster, _templates


def setup_module():
    os.environ["CC_TPU_FUSED"] = "1"


def teardown_module():
    os.environ.pop("CC_TPU_FUSED", None)


def _groups(snap, templates, profile):
    pbs = [enc.encode_problem(snap, default_pod(t), profile)
           for t in templates]
    groups = {}
    for pb in pbs:
        if sweep_mod._batchable(pb):
            key = sweep_mod._group_key(pb, sim.static_config(pb))
            groups.setdefault(key, []).append(pb)
    return [g for g in groups.values() if len(g) >= 2]


def _run_both(group, max_limit=40):
    """The same group through _batched_solve with the kernel on and off."""
    calls = {"n": 0}
    orig = fused_batched.BatchedFusedRunner.run_packed

    def counting(self, state, k):
        calls["n"] += 1
        return orig(self, state, k)

    fused_batched.BatchedFusedRunner.run_packed = counting
    try:
        res_kernel = sweep_mod._batched_solve(list(group),
                                              max_limit=max_limit)
    finally:
        fused_batched.BatchedFusedRunner.run_packed = orig
    assert calls["n"] > 0, "batched kernel never engaged"

    os.environ["CC_TPU_FUSED"] = "0"
    try:
        res_xla = sweep_mod._batched_solve(list(group), max_limit=max_limit)
    finally:
        os.environ["CC_TPU_FUSED"] = "1"
    return res_kernel, res_xla


def _assert_equal(res_kernel, res_xla):
    for a, b in zip(res_kernel, res_xla):
        assert a.placements == b.placements
        assert a.placed_count == b.placed_count
        assert a.fail_type == b.fail_type
        assert a.fail_message == b.fail_message


def test_mixed_topology_group_bit_identical():
    """The heterogeneous spread/IPA mix from test_sweep_batched must solve
    identically through the batched kernel."""
    snap = _cluster()
    profile = SchedulerProfile()
    for group in _groups(snap, _templates(), profile):
        _assert_equal(*_run_both(group))


def test_unlimited_run_to_unschedulable():
    """No max_limit: every template runs to its own Unschedulable stop (the
    stop flags and diagnosis must survive the kernel round-trip)."""
    snap = _cluster(24)
    profile = SchedulerProfile()
    groups = _groups(snap, _templates(), profile)
    assert groups
    res_kernel, res_xla = _run_both(groups[0], max_limit=0)
    _assert_equal(res_kernel, res_xla)
    assert any(r.fail_type == sim.FAIL_UNSCHEDULABLE for r in res_kernel)


def test_sampling_active_group():
    """numFeasibleNodesToFind sampling (binary-searched threshold + rotating
    start) inside the batched kernel: 120 nodes, 50%% sampling."""
    rng = np.random.RandomState(3)
    nodes = []
    for i in range(120):
        nodes.append({
            "metadata": {"name": f"n-{i:03d}",
                         "labels": {"kubernetes.io/hostname": f"n-{i:03d}",
                                    "topology.kubernetes.io/zone": f"z{i % 3}"}},
            "spec": {},
            "status": {"allocatable": {
                "cpu": f"{int(rng.choice([2000, 4000]))}m",
                "memory": str(int(rng.choice([4, 8])) * 1024 ** 3),
                "pods": "16"}}})
    snap = ClusterSnapshot.from_objects(nodes)
    profile = SchedulerProfile(percentage_of_nodes_to_score=50)
    templates = [t for t in _templates()
                 if t["metadata"]["name"] in ("plain", "sp1", "soft")]
    # same fit shape; spread counts pad — one group after normalization
    groups = _groups(snap, templates, profile)
    assert groups, "expected at least one batchable group"
    for group in groups:
        cfg = sweep_mod._pad_group(list(group))[1]
        assert cfg.sample_k > 0, "sampling not active; test is vacuous"
        _assert_equal(*_run_both(group, max_limit=60))


def test_structural_cache_shared_across_groups():
    """Two groups with identical structure but different request numbers
    must reuse one compiled call (numerics live in SMEM, not the program)."""
    snap = _cluster(24)
    profile = SchedulerProfile()

    def tpl(name, cpu):
        return {"metadata": {"name": name, "labels": {"app": name}},
                "spec": {"containers": [{"name": "c", "resources": {
                    "requests": {"cpu": cpu, "memory": "1Gi"}}}]}}

    g1 = [enc.encode_problem(snap, default_pod(tpl("a", "300m")), profile),
          enc.encode_problem(snap, default_pod(tpl("b", "500m")), profile)]
    g2 = [enc.encode_problem(snap, default_pod(tpl("c", "700m")), profile),
          enc.encode_problem(snap, default_pod(tpl("d", "900m")), profile)]

    fused_batched._compiled_batched_call.cache_clear()
    sweep_mod._batched_solve(g1, max_limit=10)
    info1 = fused_batched._compiled_batched_call.cache_info()
    sweep_mod._batched_solve(g2, max_limit=10)
    info2 = fused_batched._compiled_batched_call.cache_info()
    assert info2.misses == info1.misses, \
        "second group recompiled despite identical structure"
    assert info2.hits > info1.hits


def test_divergence_disables_group(monkeypatch):
    """A cross-check mismatch must fall back to XLA loudly, not return
    wrong placements."""
    snap = _cluster(24)
    profile = SchedulerProfile()
    groups = _groups(snap, _templates(), profile)
    group = groups[0]

    orig = fused_batched.BatchedFusedRunner.run_chunk

    def corrupted(self, carry, k_steps):
        new_carry, chosen = orig(self, carry, k_steps)
        chosen = np.array(chosen)
        chosen[0, 0] = (chosen[0, 0] + 1) % self.pk.meta.n   # flip one pick
        return new_carry, chosen

    monkeypatch.setattr(fused_batched.BatchedFusedRunner, "run_chunk",
                        corrupted)
    fused_batched._verified_keys.clear()
    try:
        res_bad = sweep_mod._batched_solve(list(group), max_limit=20)
    finally:
        monkeypatch.undo()
        fused_batched._failed_keys.clear()
    os.environ["CC_TPU_FUSED"] = "0"
    try:
        res_ref = sweep_mod._batched_solve(list(group), max_limit=20)
    finally:
        os.environ["CC_TPU_FUSED"] = "1"
    _assert_equal(res_bad, res_ref)


def test_vmem_budget_refuses_oversized():
    """eligible() must refuse plane stacks over the VMEM budget instead of
    letting Mosaic fail at runtime."""
    from cluster_capacity_tpu.engine import fused

    pk = fused._Packing(
        meta=None, const_names=tuple(f"c{i}" for i in range(30)),
        carry_names=tuple(f"y{i}" for i in range(12)))

    class _M:
        s = 512                      # 65536 nodes
    pk = pk._replace(meta=_M())
    assert not fused.vmem_ok(pk)     # 30 + 24 + 16 planes @ 256 KiB >> 12 MiB

    class _M2:
        s = 32                       # 4096 nodes
    pk2 = pk._replace(meta=_M2())
    assert fused.vmem_ok(pk2)


def test_large_group_segments(monkeypatch):
    """Groups over MAX_BATCH split into segments (bounding the kernel's HBM
    slab and the vmapped working set) with lossless concatenation."""
    snap = _cluster(24)
    profile = SchedulerProfile()

    def tpl(k):
        return {"metadata": {"name": f"t{k}", "labels": {"app": f"t{k}"}},
                "spec": {"containers": [{"name": "c", "resources": {
                    "requests": {"cpu": f"{200 + 100 * (k % 3)}m",
                                 "memory": "1Gi"}}}]}}

    pbs = [enc.encode_problem(snap, default_pod(tpl(k)), profile)
           for k in range(7)]
    monkeypatch.setattr(fused_batched, "MAX_BATCH", 3)
    res_seg = sweep_mod._batched_solve(list(pbs), max_limit=10)
    monkeypatch.setattr(fused_batched, "MAX_BATCH", 256)
    res_one = sweep_mod._batched_solve(list(pbs), max_limit=10)
    assert len(res_seg) == len(res_one) == 7
    _assert_equal(res_seg, res_one)

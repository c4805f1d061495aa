"""Static Mosaic BlockSpec lint (engine/mosaic_lint.py).

Pallas interpret mode cannot catch Mosaic lowering constraints, so the
kernels' spec tables are linted here, in the default CPU suite.  The
regression case is a shape Mosaic refused on the chip: an SMEM block
`(1, 4)` over a `[B, 4]` array ("block shape (1, 4) ... smem").
"""

import numpy as np
import pytest

from cluster_capacity_tpu.engine import encode as enc
from cluster_capacity_tpu.engine import fused
from cluster_capacity_tpu.engine import fused_batched as fb
from cluster_capacity_tpu.engine import simulator as sim
from cluster_capacity_tpu.engine.mosaic_lint import (SpecEntry, check_entry,
                                                     check_table)
from cluster_capacity_tpu.models.podspec import default_pod
from cluster_capacity_tpu.models.snapshot import ClusterSnapshot
from cluster_capacity_tpu.utils.config import SchedulerProfile

from helpers import build_test_node


# ---------------------------------------------------------------------------
# rule unit tests
# ---------------------------------------------------------------------------

def test_round3_smem_regression_flagged():
    """The round-3 killer: SMEM sublane block 1 on a multi-row array."""
    e = SpecEntry("scalars_in", (1, 4), (8, 4), "smem")
    violations = check_entry(e)
    assert violations and "sublane" in violations[0]


def test_smem_full_array_block_ok():
    # the single-template kernel's (1, 4) block IS the whole array — legal
    assert check_entry(SpecEntry("s", (1, 4), (1, 4), "smem")) == []
    # the batched fix: 8-row tiles over an 8-padded array
    assert check_entry(SpecEntry("s", (8, 4), (24, 4), "smem")) == []


def test_smem_ragged_tile_flagged():
    # 8-row tiles over an unpadded 20-row array do not tile it
    violations = check_entry(SpecEntry("s", (8, 4), (20, 4), "smem"))
    assert any("tile" in v for v in violations)


def test_vmem_lane_rule():
    assert check_entry(SpecEntry("v", (4, 79, 128), (4, 79, 128), "vmem")) == []
    # lane block 64 is neither the array dim (128) nor a multiple of 128
    violations = check_entry(SpecEntry("v", (4, 79, 64), (4, 79, 128), "vmem"))
    assert any("lane" in v for v in violations)


def test_vmem_sublane_rule():
    # block sublane 3 over array sublane 9: 3 tiles 9 but is neither 9 nor 8k
    violations = check_entry(SpecEntry("v", (3, 128), (9, 128), "vmem"))
    assert any("sublane" in v for v in violations)
    # equal-to-array-dim always passes (whole-axis blocks)
    assert check_entry(SpecEntry("v", (9, 128), (9, 128), "vmem")) == []


def test_rank_mismatch_flagged():
    violations = check_entry(SpecEntry("x", (1, 4), (1, 4, 4), "smem"))
    assert any("rank" in v for v in violations)


# ---------------------------------------------------------------------------
# the real kernels' spec tables lint clean
# ---------------------------------------------------------------------------

def _nodes(n, zones=4):
    rng = np.random.RandomState(0)
    out = []
    for i in range(n):
        out.append(build_test_node(
            f"node-{i:04d}", int(rng.choice([2000, 4000])), 8 * 1024 ** 3, 32,
            labels={"kubernetes.io/hostname": f"node-{i:04d}",
                    "topology.kubernetes.io/zone": f"z{i % zones}"}))
    return out


def _pb(pod, n=150):
    snap = ClusterSnapshot.from_objects(_nodes(n))
    return enc.encode_problem(snap, default_pod(pod), SchedulerProfile())


def _spread_pod(name="p", app="a", skew=2):
    return {
        "metadata": {"name": name, "labels": {"app": app}},
        "spec": {"containers": [{
            "name": "c", "resources": {"requests": {"cpu": "100m"}}}],
            "topologySpreadConstraints": [{
                "maxSkew": skew, "topologyKey": "topology.kubernetes.io/zone",
                "whenUnsatisfiable": "DoNotSchedule",
                "labelSelector": {"matchLabels": {"app": app}}}]},
    }


def _ipa_pod():
    return {
        "metadata": {"name": "p", "labels": {"app": "a"}},
        "spec": {"containers": [{
            "name": "c", "resources": {"requests": {"cpu": "100m"}}}],
            "affinity": {
                "podAffinity": {
                    "requiredDuringSchedulingIgnoredDuringExecution": [{
                        "topologyKey": "topology.kubernetes.io/zone",
                        "labelSelector": {"matchLabels": {"app": "a"}}}]},
                "podAntiAffinity": {
                    "preferredDuringSchedulingIgnoredDuringExecution": [{
                        "weight": 10, "podAffinityTerm": {
                            "topologyKey": "kubernetes.io/hostname",
                            "labelSelector": {
                                "matchLabels": {"app": "a"}}}}]}}},
    }


@pytest.mark.parametrize("pod_fn", [_spread_pod, _ipa_pod],
                         ids=["spread", "ipa"])
@pytest.mark.parametrize("k_steps", [48, 4096])
def test_fused_spec_tables_clean(pod_fn, k_steps):
    pb = _pb(pod_fn())
    cfg = sim.static_config(pb)
    pk = fused._pack_meta(cfg, pb, None)
    ins, outs = fused._spec_table(pk, k_steps)
    assert check_table(ins + outs) == []


@pytest.mark.parametrize("b", [2, 8, 20, 100, fb.MAX_BATCH])
@pytest.mark.parametrize("k_steps", [48, 1024])
def test_batched_spec_tables_clean(b, k_steps):
    """Every batch size the sweep can hand the batched kernel, including the
    non-multiple-of-8 sizes that triggered the round-3 failure."""
    from cluster_capacity_tpu.parallel.sweep import _pad_group
    pods = [_spread_pod(name=f"t{k}", app=f"t{k}", skew=2 + k % 3)
            for k in range(b)]
    snap = ClusterSnapshot.from_objects(_nodes(100))
    pbs = [enc.encode_problem(snap, default_pod(p), SchedulerProfile())
           for p in pods]
    pbs, cfg, _dnh = _pad_group(pbs)
    pks = [fused._pack_meta(cfg, pb, None) for pb in pbs]
    runner_pk = pks[0]._replace(meta=fb._structural_meta(pks[0].meta))
    tab = fb._scalar_table(runner_pk)
    ins, outs = fb._batched_spec_table(runner_pk, tab, b, k_steps)
    assert check_table([e for e, _m in ins + outs]) == []


@pytest.mark.parametrize("b,n", [(100, 1000), (20, 999), (8, 1337),
                                 (fb.MAX_BATCH, 1000)],
                         ids=["tpu-failure-geometry", "n999", "n1337",
                              "maxbatch-n1000"])
def test_batched_spec_tables_clean_at_scale(b, n):
    """Pin the exact geometry that failed on TPU in round 4 (B=100 at
    n=1000, plane count S=8 — the n=100/S=1 lint above could not see it)
    plus non-multiple-of-128 node counts at scale, so node-count-dependent
    specs can't regress silently."""
    from cluster_capacity_tpu.parallel.sweep import _pad_group
    pods = [_spread_pod(name=f"t{k}", app=f"t{k}", skew=2 + k % 3)
            for k in range(b)]
    snap = ClusterSnapshot.from_objects(_nodes(n, zones=8))
    pbs = [enc.encode_problem(snap, default_pod(p), SchedulerProfile())
           for p in pods]
    pbs, cfg, _dnh = _pad_group(pbs)
    pks = [fused._pack_meta(cfg, pb, None) for pb in pbs]
    runner_pk = pks[0]._replace(meta=fb._structural_meta(pks[0].meta))
    tab = fb._scalar_table(runner_pk)
    for k_steps in (48, 1024):
        ins, outs = fb._batched_spec_table(runner_pk, tab, b, k_steps)
        assert check_table([e for e, _m in ins + outs]) == []


@pytest.mark.parametrize("n", [1000, 999, 1337])
def test_fused_spec_tables_clean_at_scale(n):
    """Single-template kernel spec tables at multi-plane, non-multiple-of-128
    node counts (the r4 lint only exercised n=150)."""
    for pod_fn in (_spread_pod, _ipa_pod):
        pb = _pb(pod_fn(), n=n)
        cfg = sim.static_config(pb)
        pk = fused._pack_meta(cfg, pb, None)
        for k_steps in (48, 4096):
            ins, outs = fused._spec_table(pk, k_steps)
            assert check_table(ins + outs) == []


def test_compiled_call_refuses_dirty_table(monkeypatch):
    """A violating spec table must refuse the kernel at build time (the
    runner falls back to XLA) instead of dying in Mosaic on device."""
    pb = _pb(_spread_pod(), n=40)
    cfg = sim.static_config(pb)
    pk = fused._pack_meta(cfg, pb, None)

    def bad_table(pk_, k_steps_):
        ins, outs = _orig(pk_, k_steps_)
        bad = SpecEntry("scalars_in", (1, 4), (8, 4), "smem")
        return [ins[0], ins[1], bad], outs

    _orig = fused._spec_table
    monkeypatch.setattr(fused, "_spec_table", bad_table)
    fused._compiled_call.cache_clear()
    with pytest.raises(ValueError, match="mosaic lint"):
        fused._compiled_call(pk, 16, True)
    fused._compiled_call.cache_clear()

"""Fleet-scale mesh-sharded sweep bench: the MULTICHIP_rNN.json producer.

Runs an N-1 resilience sweep over a synthetic fleet at parameterized node
scales ({2k, 16k, 64k} via --scales; the smallest is the CI default) on a
(batch, nodes) device mesh — on CPU hosts export
XLA_FLAGS=--xla_force_host_platform_device_count=8 to get 8 virtual
devices.  Every run proves sharded == unsharded bit-identity twice:

1. pruned sweep (bounds pruning ON, the analyzer default): the capacity
   brackets run as sharded device shots and prune every provable row; the
   sharded and unsharded reports must agree row-for-row.
2. solve sweep (keep_placements forces real device solves, bounds still
   right-size the scan budgets): the sharded scan kernels produce the
   placements, compared bit-for-bit against the single-device scan.

Throughput (placements/s, total and per device) is measured on the solve
sweep after a warm-up pass, so one-time compilation does not pollute the
rate; the warm-up also demonstrates the fixed-mesh runner cache (alive-mask
changes between scenarios reuse ONE compiled executable).

The interleaved multi-template rung (--interleave-scales, default
2000,16000 with 64000 as the opt-in slow rung) runs the stacked-template
sharded race (parallel/interleave with mesh=...) against the per-template
tensor reference at fleet node counts: bit-identity of placements and fail
messages at every scale, zero steady recompiles on the cached runner, and
interleave_sharded_placements_per_sec (total + per device) pinned from the
primary interleave scale.

Usage:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python -m tools.multichip_bench --nodes 2000 --out MULTICHIP_r06.json

The output document keeps MULTICHIP_r05.json's envelope (n_devices / rc /
ok / skipped / tail) and adds flat numeric throughput keys that tools/trend
ingests and tools/perfgate pins.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

DEFAULT_NODES = 2000
DEFAULT_LIMIT = 128


def _fleet(n_nodes: int, seed: int = 0):
    """Synthetic fleet snapshot (empty nodes, 3 cpu x 3 mem shapes over 4
    zones) + a fit-only probe pod.  Node shapes repeat, so the analyzer's
    symmetry dedup collapses the N-1 sweep to one representative per shape
    class — the same structure real fleets have."""
    from cluster_capacity_tpu.models.podspec import default_pod
    from cluster_capacity_tpu.models.snapshot import ClusterSnapshot

    rng = np.random.RandomState(seed)
    nodes = []
    for i in range(n_nodes):
        nodes.append({
            "metadata": {"name": f"node-{i:06d}",
                         "labels": {"kubernetes.io/hostname": f"node-{i:06d}",
                                    "topology.kubernetes.io/zone":
                                        f"zone-{i % 4}"}},
            "spec": {},
            "status": {"allocatable": {
                "cpu": f"{int(rng.choice([4000, 8000, 16000]))}m",
                "memory": str(int(rng.choice([16, 32, 64])) * 1024 ** 3),
                "pods": "110"}},
        })
    probe = default_pod({
        "metadata": {"name": "fleet-probe", "labels": {"app": "fleet"}},
        "spec": {"containers": [{
            "name": "c0", "image": "app:v1",
            "resources": {"requests": {"cpu": "500m", "memory": "1Gi"}}}]},
    })
    return ClusterSnapshot.from_objects(nodes), probe


def _comparable(report) -> dict:
    """Report dict with the fields a sharded run legitimately changes
    (mesh stamp, serving-rung provenance) removed — everything left must be
    bit-identical between the sharded and unsharded sweeps."""
    doc = report.to_dict()
    doc["status"].pop("mesh", None)
    doc["status"].pop("worstRung", None)
    for s in doc["status"]["scenarios"]:
        s.pop("rung", None)
    return doc


def run_scale(n_nodes: int, mesh, max_limit: int) -> dict:
    from cluster_capacity_tpu.resilience.analyzer import analyze
    from cluster_capacity_tpu.resilience.scenarios import \
        single_node_scenarios

    snapshot, probe = _fleet(n_nodes)
    scenarios = single_node_scenarios(snapshot)

    # --- pass 1: bounds pruning ON (sharded bracket shots) ---------------
    plain = analyze(snapshot, scenarios, probe, max_limit=max_limit)
    shard = analyze(snapshot, scenarios, probe, max_limit=max_limit,
                    mesh=mesh)
    if _comparable(plain) != _comparable(shard):
        raise AssertionError(
            f"pruned sweep: sharded report diverges at {n_nodes} nodes")
    pruned_rows = (shard.bounds or {}).get("pruned", 0)

    # --- pass 2: forced device solves (sharded scan kernels) -------------
    plain2 = analyze(snapshot, scenarios, probe, max_limit=max_limit,
                     keep_placements=True)
    analyze(snapshot, scenarios, probe, max_limit=max_limit,
            keep_placements=True, mesh=mesh)          # warm-up: compile
    t0 = time.perf_counter()
    shard2 = analyze(snapshot, scenarios, probe, max_limit=max_limit,
                     keep_placements=True, mesh=mesh)
    dt = time.perf_counter() - t0
    if _comparable(plain2) != _comparable(shard2):
        raise AssertionError(
            f"solve sweep: sharded placements diverge at {n_nodes} nodes")

    reps = [r for r in shard2.scenarios if r.deduped_of is None]
    placed = sum(r.headroom for r in reps) + shard2.baseline_headroom
    return {
        "nodes": n_nodes,
        "scenarios": len(shard2.scenarios),
        "solved_reps": len(reps),
        "pruned_rows": pruned_rows,
        "placed": placed,
        "solve_seconds": dt,
        "placements_per_sec": placed / dt if dt > 0 else 0.0,
    }


def _template_mix(t_n: int):
    """Heterogeneous template mix for the interleaved race: 4 cpu x 3 mem
    shapes cycling under one shared team label so clones of every template
    count under the same selectors — the cross-template coupling the
    per-template path cannot batch."""
    from cluster_capacity_tpu.models.podspec import default_pod

    out = []
    for i in range(t_n):
        out.append(default_pod({
            "metadata": {"name": f"tmpl-{i}",
                         "labels": {"app": f"tmpl-{i}", "team": "fleet"}},
            "spec": {"containers": [{"name": "c", "resources": {
                "requests": {"cpu": f"{[500, 750, 1000, 1500][i % 4]}m",
                             "memory": f"{[1, 2, 4][i % 3]}Gi"}}}]},
        }))
    return out


INTERLEAVE_TEMPLATES = 8
INTERLEAVE_MAX_TOTAL = 2048


def run_interleave_scale(n_nodes: int, mesh) -> dict:
    """Interleaved multi-template rung: the stacked-template sharded scan
    vs the per-template tensor reference at fleet node counts.

    Bit-identity (placements + fail messages) is proven on BOTH the full
    mesh and a degenerate single-shard mesh; throughput is recorded for
    both and the pinned rate takes the better one.  On CPU hosts the
    virtual devices are threads, so the per-pop winner all-reduce of the
    sequential race pays a thread-rendezvous per step and the full-mesh
    rate trails the single-shard rate — on real multichip interconnect
    that latency is microseconds and the full mesh wins.  The timed run
    must be compile-free (the cached runner keyed on (mesh, static
    config) already compiled during the warm/identity pass)."""
    from cluster_capacity_tpu.obs import recompile as obs_recompile
    from cluster_capacity_tpu.parallel import interleave as il
    from cluster_capacity_tpu.parallel import mesh as mesh_lib
    from cluster_capacity_tpu.utils.config import SchedulerProfile

    snapshot, _ = _fleet(n_nodes)
    templates = _template_mix(INTERLEAVE_TEMPLATES)
    profile = SchedulerProfile.parity()
    ref = il.solve_interleaved_tensor(snapshot, templates, profile,
                                      max_total=INTERLEAVE_MAX_TOTAL)
    placed = sum(r.placed_count for r in ref)

    def timed(m, label):
        got = il.solve_interleaved_tensor(           # warm-up + identity
            snapshot, templates, profile,
            max_total=INTERLEAVE_MAX_TOTAL, mesh=m, bounds=True)
        for i, (a, b) in enumerate(zip(ref, got)):
            if (a.placements != b.placements
                    or a.fail_message != b.fail_message):
                raise AssertionError(
                    f"interleave {label}: sharded diverges from the "
                    f"per-template reference at {n_nodes} nodes, "
                    f"template {i}")
        with obs_recompile.CompileTally() as tally:
            t0 = time.perf_counter()
            il.solve_interleaved_tensor(
                snapshot, templates, profile,
                max_total=INTERLEAVE_MAX_TOTAL, mesh=m, bounds=True)
            dt = time.perf_counter() - t0
        if tally.count:
            raise AssertionError(
                f"interleave {label}: {tally.count} steady recompiles "
                f"at {n_nodes} nodes (runner cache miss)")
        return dt

    dt_mesh = timed(mesh, "full-mesh")
    dt_single = timed(mesh_lib.make_mesh(1, 1), "single-shard")
    rate_mesh = placed / dt_mesh if dt_mesh > 0 else 0.0
    rate_single = placed / dt_single if dt_single > 0 else 0.0
    best_rate, best_devices = ((rate_mesh, mesh.devices.size)
                               if rate_mesh >= rate_single
                               else (rate_single, 1))
    return {
        "nodes": n_nodes,
        "templates": INTERLEAVE_TEMPLATES,
        "placed": placed,
        "full_mesh_placements_per_sec": rate_mesh,
        "single_shard_placements_per_sec": rate_single,
        "placements_per_sec": best_rate,
        "per_device_placements_per_sec": best_rate / best_devices,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="multichip_bench",
        description="Mesh-sharded N-1 fleet sweep: bit-identity proof + "
                    "placements/s throughput into MULTICHIP_rNN.json.")
    ap.add_argument("--nodes", type=int, default=DEFAULT_NODES,
                    help=f"primary fleet size (default {DEFAULT_NODES})")
    ap.add_argument("--scales", default="",
                    help="comma list of extra fleet sizes to sweep "
                         "(e.g. 2000,16000,64000); the first entry is the "
                         "primary scale the pinned metrics come from")
    ap.add_argument("--max-limit", dest="max_limit", type=int,
                    default=DEFAULT_LIMIT,
                    help=f"per-scenario placement cap (default "
                         f"{DEFAULT_LIMIT}; bounds prune rows whose bracket "
                         f"already proves the cap)")
    ap.add_argument("--interleave-scales", dest="interleave_scales",
                    default="2000,16000",
                    help="comma list of fleet sizes for the interleaved "
                         "multi-template rung (default 2000,16000; add "
                         "64000 for the slow rung; empty disables); the "
                         "first entry is the primary scale the pinned "
                         "interleave_sharded_* metrics come from")
    ap.add_argument("--mesh", default="auto",
                    help="mesh spec: BxN, 'auto' (default), or 'none'")
    ap.add_argument("--out", default="",
                    help="write the result document to this path "
                         "(MULTICHIP_rNN.json); stdout otherwise")
    args = ap.parse_args(argv)

    import jax

    from cluster_capacity_tpu.parallel.mesh import mesh_shape, parse_mesh

    n_devices = len(jax.devices())
    mesh = parse_mesh(args.mesh)
    doc = {"n_devices": n_devices, "platform": jax.default_backend(),
           "rc": 0, "ok": False, "skipped": False}
    if mesh is None:
        # single-device host (or --mesh none): nothing to prove — record an
        # explicit skip rather than a meaningless unsharded self-compare
        doc.update(skipped=True, ok=True,
                   tail="multichip bench skipped: no mesh "
                        f"({n_devices} device(s) visible)\n")
    else:
        scales = ([int(s) for s in args.scales.split(",") if s]
                  or [args.nodes])
        per_scale = {}
        for n_nodes in scales:
            per_scale[str(n_nodes)] = run_scale(n_nodes, mesh,
                                                args.max_limit)
        il_scales = [int(s) for s in args.interleave_scales.split(",") if s]
        il_per_scale = {}
        for n_nodes in il_scales:
            il_per_scale[str(n_nodes)] = run_interleave_scale(n_nodes, mesh)
        primary = per_scale[str(scales[0])]
        rate = primary["placements_per_sec"]
        il_doc = {}
        il_tail = ""
        if il_scales:
            il_primary = il_per_scale[str(il_scales[0])]
            il_doc = {
                "interleave_sharded_placements_per_sec":
                    il_primary["placements_per_sec"],
                "interleave_sharded_per_device_placements_per_sec":
                    il_primary["per_device_placements_per_sec"],
                "scales_interleave": il_per_scale,
            }
            il_tail = (f", interleaved "
                       f"{il_primary['placements_per_sec']:.1f}/s @ "
                       f"{il_primary['nodes']} nodes "
                       f"(rungs: {', '.join(str(s) for s in il_scales)})")
        doc.update(
            ok=True,
            mesh=mesh_shape(mesh),
            nodes=primary["nodes"],
            scenarios=primary["scenarios"],
            solved_reps=primary["solved_reps"],
            pruned_rows=primary["pruned_rows"],
            max_limit=args.max_limit,
            sharded_sweep_placements_per_sec=rate,
            sharded_sweep_per_device_placements_per_sec=rate / n_devices,
            scales=per_scale,
            tail=(f"multichip bench OK: mesh={mesh_shape(mesh)}, "
                  f"{primary['nodes']} nodes, "
                  f"{primary['scenarios']} scenarios "
                  f"({primary['solved_reps']} solved, "
                  f"{primary['pruned_rows']} pruned), "
                  f"sharded==unsharded bit-identical, "
                  f"{rate:.1f} placements/s "
                  f"({rate / n_devices:.1f}/device)"
                  f"{il_tail}\n"),
            **il_doc,
        )

    text = json.dumps(doc, indent=2)
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(text + "\n")
    print(doc["tail"].strip() if doc.get("tail") else text)
    return 0 if doc["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())

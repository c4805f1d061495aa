"""Cluster generator `proportional`, copied from chip_smoke.py
`make_cluster`.  Nodes of random size classes in zones, resident pods
placed in proportion to node cores."""

from __future__ import annotations

import numpy as np


def make(cfg: dict, seed: int) -> dict:
    rng = np.random.default_rng(seed)
    n, n_pods, zones = cfg["nodes"], cfg["resident_pods"], cfg["zones"]
    cores = rng.choice(cfg["node_cores"], size=n)
    mem_gi = rng.choice(cfg["node_memory_gi"], size=n)
    names = [cfg["node_name"].format(i=i) for i in range(n)]
    nodes = [{
        "metadata": {"name": names[i],
                     "labels": {cfg["hostname_key"]: names[i],
                                cfg["zone_key"]: cfg["zone_name"].format(
                                    z=i % zones)}},
        "spec": {},
        "status": {"allocatable": {"cpu": str(int(cores[i])),
                                   "memory": f"{int(mem_gi[i])}Gi",
                                   "pods": str(cfg["pods_per_node"])}},
    } for i in range(n)]
    host = rng.choice(n, size=n_pods, p=cores / cores.sum())
    cpu_m = rng.choice(cfg["resident_cpu_m"], size=n_pods,
                       p=cfg["resident_cpu_p"])
    mem_mi = rng.choice(cfg["resident_memory_mi"], size=n_pods)
    app = rng.integers(0, cfg["resident_apps"], size=n_pods)
    pods = [{
        "metadata": {"name": f"res-{j:06d}",
                     "namespace": cfg["resident_namespace"],
                     "labels": {"app": f"svc-{int(app[j])}"}},
        "spec": {"nodeName": names[int(host[j])],
                 "containers": [{"name": "c", "resources": {"requests": {
                     "cpu": f"{int(cpu_m[j])}m",
                     "memory": f"{int(mem_mi[j])}Mi"}}}]},
        "status": {"phase": "Running"},
    } for j in range(n_pods)]
    return {"nodes": nodes, "pods": pods}

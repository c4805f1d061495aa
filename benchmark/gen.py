"""Seeded generators: a cluster from a configuration file, and the stream
of questions from a traffic file.  Everything is drawn from `--seed`, so
the same seed gives the same cluster and the same questions.

Cluster generators (the configuration's "generator" key):

- `proportional`: copied from chip_smoke.py `make_cluster`.  Nodes of
  random size classes in zones, resident pods placed in proportion to node
  cores.
- `scheduler_perf`: kube-scheduler's scheduler_perf layout.  Identical
  nodes from a node template with a unique hostname label each, and init
  pods from a pod template, one to a node on distinct seeded nodes.
"""

from __future__ import annotations

import copy
import json
import os
from typing import List

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_json(rel: str) -> dict:
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def _proportional(cfg: dict, seed: int) -> dict:
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


def _scheduler_perf(cfg: dict, seed: int) -> dict:
    rng = np.random.default_rng(seed)
    n = cfg["nodes"]
    node_tpl = load_json(cfg["node_template"])["object"]
    nodes = []
    for i in range(n):
        node = copy.deepcopy(node_tpl)
        name = cfg["node_name"].format(i=i)
        meta = node["metadata"]
        meta.pop("generateName", None)
        meta["name"] = name
        meta.setdefault("labels", {})[cfg["unique_label"]] = name
        nodes.append(node)
    pod_tpl = load_json(cfg["init_pod_template"])["object"]
    hosts = rng.choice(n, size=cfg["init_pods"], replace=False)
    pods = []
    for j, h in enumerate(hosts):
        pod = copy.deepcopy(pod_tpl)
        meta = pod["metadata"]
        meta.pop("generateName", None)
        meta["name"] = cfg["init_pod_name"].format(j=j)
        meta["namespace"] = cfg["init_namespace"]
        pod["spec"]["nodeName"] = nodes[int(h)]["metadata"]["name"]
        pod["status"] = {"phase": "Running"}
        pods.append(pod)
    return {"nodes": nodes, "pods": pods}


GENERATORS = {"proportional": _proportional,
              "scheduler_perf": _scheduler_perf}


def make_cluster(cfg: dict, seed: int) -> dict:
    """{"nodes": [...], "pods": [...]} as Kubernetes objects."""
    return GENERATORS[cfg["generator"]](cfg, seed)


def templates(traffic: dict) -> List[dict]:
    """The traffic's catalogue as pod objects, each with its own name and
    the traffic's namespace where it states one.  A traffic file may name
    another's catalogue ("templates_from") instead of listing its own."""
    if "templates_from" in traffic:
        listed = load_json(f"benchmark/traffic/{traffic['templates_from']}"
                           f".json")["templates"]
    else:
        listed = traffic["templates"]
    out = []
    for t in listed:
        if "file" in t:
            pod = copy.deepcopy(load_json(t["file"])["object"])
            pod["metadata"].pop("generateName", None)
            pod["metadata"]["name"] = t["name"]
        else:
            pod = copy.deepcopy(t)
        if "namespace" in traffic:
            pod["metadata"]["namespace"] = traffic["namespace"]
        out.append(pod)
    return out


def round_order(traffic: dict, n: int, seed: int) -> list:
    """One round of questions: each of the catalogue's `n` templates once,
    in an order drawn from the seed.  A sweep asks the whole catalogue in
    one question, so its round is [None].  The client repeats the round."""
    if traffic["question"] == "sweep":
        return [None]
    return [int(k) for k in np.random.default_rng([seed, 1]).permutation(n)]

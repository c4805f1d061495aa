"""Cluster generator `scheduler_perf`: kube-scheduler's scheduler_perf
layout.  Identical nodes from a node template with a unique hostname label
each, and init pods from a pod template, one to a node on distinct seeded
nodes."""

from __future__ import annotations

import copy

import numpy as np

from gen import load_json


def make(cfg: dict, seed: int) -> dict:
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

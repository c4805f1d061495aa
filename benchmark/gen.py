"""Seeded generators: a cluster from a configuration file, and the stream
of questions from a traffic file.  Everything is drawn from `--seed`, so
the same seed gives the same cluster and the same questions.

A configuration names its cluster generator ("generator"): the file
`generators/<name>.py`, whose `make(cfg, seed)` returns the cluster.  Each
generator says in its docstring what it lays out.  `load_module` loads
that file, and every other file a data file names (a configuration's
reference, a traffic file's control, a metric's reader), by its path.
"""

from __future__ import annotations

import copy
import importlib.util
import json
import os
import sys
from typing import List

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_json(rel: str) -> dict:
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def load_module(rel: str):
    """The Python file at `rel` (a path under benchmark/, relative to the
    checkout), loaded once per process.  Its module name is its path under
    benchmark/ with dots for slashes, the name a plain `import` of the same
    file gives, so a file that imports another by name shares the module
    loaded here."""
    bench = os.path.realpath(BENCH)
    path = os.path.realpath(os.path.join(ROOT, rel))
    if os.path.commonpath([path, bench]) != bench or \
            not path.endswith(".py"):
        raise ValueError(f"{rel!r} is not a Python file under benchmark/")
    name = os.path.relpath(path, bench)[:-3].replace(os.sep, ".")
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[name]
            raise
    return sys.modules[name]


def make_cluster(cfg: dict, seed: int) -> dict:
    """{"nodes": [...], "pods": [...]} as Kubernetes objects, from the
    configuration's generator."""
    return load_module(
        f"benchmark/generators/{cfg['generator']}.py").make(cfg, seed)


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

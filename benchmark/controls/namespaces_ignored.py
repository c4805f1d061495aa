"""Control `namespaces_ignored`: the reference in float32 on a cluster and
a catalogue whose pod affinity terms have lost their `namespaces` lists,
so each term matches pods of its owner's namespace alone: the guarantee
that a term matches the namespaces it lists, broken.  For cells whose
every decision is a filter on whole counts and a tie among identical
nodes, where no precision changes an answer."""

from __future__ import annotations

import copy

import numpy as np


def _without_namespaces(pods: list) -> list:
    """Copies of `pods` whose pod (anti-)affinity terms list no
    namespaces."""
    out = copy.deepcopy(pods)
    for pod in out:
        aff = (pod.get("spec") or {}).get("affinity") or {}
        for kind in ("podAffinity", "podAntiAffinity"):
            part = aff.get(kind) or {}
            terms = list(part.get(
                "requiredDuringSchedulingIgnoredDuringExecution") or [])
            terms += [t["podAffinityTerm"] for t in part.get(
                "preferredDuringSchedulingIgnoredDuringExecution") or []]
            for t in terms:
                t.pop("namespaces", None)
    return out


def apply(pods: list, templates: list):
    return _without_namespaces(pods), _without_namespaces(templates), \
        np.float32

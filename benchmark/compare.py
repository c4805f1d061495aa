"""The comparison that decides `correct`: each answer the window produced
against the plain reference's answer to the same question.

An answer is {"per_node": {node name: replicas}, "reasons": {reason:
nodes}}, read from the program's report (`replicasOnNodes` and the
per-reason node counts).  Two numbers, each the worst over the answers
compared, each with its limit from the traffic file:

- `node_gap`: the share of placements that sit on other nodes than the
  reference's, sum |a_i - b_i| / (2 * max(placed)).  0 when every node
  holds as many replicas as in the reference; it counts a different total
  as well.
- `reason_gap`: the same share over the per-reason node counts of a
  refused pod.  0 when both refused it for the same reasons on as many
  nodes, or when neither was refused.
"""

from __future__ import annotations

from typing import Dict


def _share(a: Dict[str, int], b: Dict[str, int]) -> float:
    total = max(sum(a.values()), sum(b.values()))
    if total == 0:
        return 0.0
    diff = sum(abs(a.get(k, 0) - b.get(k, 0)) for k in set(a) | set(b))
    return diff / (2 * total)


def gaps(answer: dict, ref: dict) -> Dict[str, float]:
    return {"node_gap": _share(answer["per_node"], ref["per_node"]),
            "reason_gap": _share(answer["reasons"], ref["reasons"])}


def worst(readings) -> Dict[str, float]:
    out = {"node_gap": 0.0, "reason_gap": 0.0}
    for r in readings:
        for k in out:
            out[k] = max(out[k], r[k])
    return out


def from_review_pod(pod_result) -> dict:
    """An answer from one `PodResult` of the program's report."""
    return {"per_node": {r.node_name: r.replicas
                         for r in pod_result.replicas_on_nodes},
            "reasons": dict(pod_result.reasons or {})}


def from_reference(ans) -> dict:
    return {"per_node": ans.per_node(), "reasons": dict(ans.reasons)}

"""Plain reference: kube-scheduler's one-pod-at-a-time placement loop, in
numpy, for the plugins the benchmark's configurations use.

It is written from kube-scheduler's semantics (pkg/scheduler/framework/
plugins: noderesources fit.go, least_allocated.go, balanced_allocation.go;
podtopologyspread filtering.go; interpodaffinity filtering.go and
scoring.go; tainttoleration scoring) and imports nothing of the program.
Each step filters every node, scores the feasible ones with the default
profile's weights, and places the pod on the highest score, taking the
lowest node index among equal scores (the program's deterministic mode).
Nodes are ordered by name.

Every quantity is held and computed in `dtype`: numpy float32 for the
configurations' stated precision, and a lower one (bfloat16) for the
control.  Counts of pods are held in `dtype` as well, as the engine holds
them.  A feature this reference does not model raises `Unsupported`, so a
new cell can never be checked against a silently wrong reference.

A configuration names the reference it is checked against ("reference",
a path under benchmark/); the harness and the control load that file by
its path.  Every reference keeps this module's contract: `Cluster(nodes,
pods)` from the generated Kubernetes objects, and `solve(cluster, pod,
max_limit, dtype=...)` returning an answer with `per_node()` (node name
to replicas) and `reasons` (refusal reason to nodes, where the answer
ended on a pod no node takes).  Another reference may `import reference`
and extend it; like this one, it imports nothing of the program.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from decimal import Decimal
from typing import Dict, List, Optional

import numpy as np

# Default MultiPoint score weights (kube-scheduler default_plugins.go)
W_FIT, W_BALANCED, W_TAINT, W_IPA = 1, 1, 3, 2
DEFAULT_MILLI_CPU = 100
DEFAULT_MEMORY = 200 * 1024 * 1024

R_TOO_MANY = "Too many pods"
R_CPU = "Insufficient cpu"
R_MEMORY = "Insufficient memory"
R_SPREAD = "node(s) didn't match pod topology spread constraints"
R_ANTI = "node(s) didn't match pod anti-affinity rules"
R_EXISTING_ANTI = "node(s) didn't satisfy existing pods anti-affinity rules"

_SUFFIX = {"": 1, "k": 10**3, "M": 10**6, "G": 10**9, "T": 10**12,
           "Ki": 2**10, "Mi": 2**20, "Gi": 2**30, "Ti": 2**40}


class Unsupported(Exception):
    """The question or cluster uses a feature this reference does not
    model."""


def quantity(q, milli: bool = False) -> int:
    """A Kubernetes quantity as an integer (milli-units for cpu)."""
    s = str(q).strip()
    for suf in sorted(_SUFFIX, key=len, reverse=True):
        if suf and s.endswith(suf):
            num, mult = Decimal(s[:-len(suf)]), _SUFFIX[suf]
            break
    else:
        if s.endswith("m"):
            num, mult = Decimal(s[:-1]) / 1000, 1
        else:
            num, mult = Decimal(s), 1
    v = num * mult * (1000 if milli else 1)
    return int(v.to_integral_value(rounding="ROUND_CEILING"))


def requests(pod: dict, nonzero: bool = False):
    """(milli-cpu, memory bytes) summed over the pod's containers; with
    `nonzero`, a container without a cpu or memory request counts the
    scheduler's defaults (100m, 200MiB)."""
    spec = pod.get("spec") or {}
    if spec.get("initContainers") or spec.get("overhead"):
        raise Unsupported("init containers or pod overhead")
    cpu = mem = 0
    for c in spec.get("containers") or []:
        req = (c.get("resources") or {}).get("requests") or {}
        extra = set(req) - {"cpu", "memory"}
        if extra:
            raise Unsupported(f"requests of {sorted(extra)}")
        if "cpu" in req:
            cpu += quantity(req["cpu"], milli=True)
        elif nonzero:
            cpu += DEFAULT_MILLI_CPU
        if "memory" in req:
            mem += quantity(req["memory"])
        elif nonzero:
            mem += DEFAULT_MEMORY
    return cpu, mem


def _labels(obj: dict) -> dict:
    return (obj.get("metadata") or {}).get("labels") or {}


def _ns(pod: dict) -> str:
    return (pod.get("metadata") or {}).get("namespace") or "default"


def selector_matches(sel: Optional[dict], labels: dict) -> bool:
    """metav1.LabelSelector: matchLabels and matchExpressions."""
    if sel is None:
        return False
    for k, v in (sel.get("matchLabels") or {}).items():
        if labels.get(k) != v:
            return False
    for e in sel.get("matchExpressions") or []:
        key, op, vals = e["key"], e["operator"], e.get("values") or []
        if op == "In" and labels.get(key) not in vals:
            return False
        if op == "NotIn" and key in labels and labels[key] in vals:
            return False
        if op == "Exists" and key not in labels:
            return False
        if op == "DoesNotExist" and key in labels:
            return False
    return True


def term_matches(term: dict, owner_ns: str, pod: dict) -> bool:
    """A pod (anti-)affinity term of a pod in `owner_ns` against `pod`."""
    if term.get("namespaceSelector") is not None:
        raise Unsupported("namespaceSelector in a pod affinity term")
    namespaces = term.get("namespaces") or [owner_ns]
    return _ns(pod) in namespaces and selector_matches(
        term.get("labelSelector"), _labels(pod))


def _affinity(pod: dict) -> dict:
    return (pod.get("spec") or {}).get("affinity") or {}


def _required(pod: dict, kind: str) -> List[dict]:
    return (_affinity(pod).get(kind) or {}).get(
        "requiredDuringSchedulingIgnoredDuringExecution") or []


def _preferred(pod: dict, kind: str) -> List[dict]:
    return (_affinity(pod).get(kind) or {}).get(
        "preferredDuringSchedulingIgnoredDuringExecution") or []


@dataclass
class Answer:
    placements: List[int]
    node_names: List[str]
    reasons: Dict[str, int] = field(default_factory=dict)

    def per_node(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for i in self.placements:
            out[self.node_names[i]] = out.get(self.node_names[i], 0) + 1
        return out


class Cluster:
    """The cluster's nodes and resident pods, ordered by node name."""

    def __init__(self, nodes: List[dict], pods: List[dict]):
        nodes = sorted(nodes, key=lambda n: n["metadata"]["name"])
        self.names = [n["metadata"]["name"] for n in nodes]
        self.labels = [_labels(n) for n in nodes]
        index = {name: i for i, name in enumerate(self.names)}
        n = len(nodes)
        self.alloc = np.zeros((n, 3), dtype=np.int64)   # cpu m, bytes, pods
        for i, node in enumerate(nodes):
            spec = node.get("spec") or {}
            if spec.get("taints") or spec.get("unschedulable"):
                raise Unsupported("node taints or unschedulable nodes")
            status = node.get("status") or {}
            if status.get("images"):
                raise Unsupported("node images (ImageLocality)")
            a = status.get("allocatable") or {}
            self.alloc[i] = (quantity(a.get("cpu", 0), milli=True),
                             quantity(a.get("memory", 0)),
                             quantity(a.get("pods", 0)))
        self.requested = np.zeros((n, 2), dtype=np.int64)
        self.nonzero = np.zeros((n, 2), dtype=np.int64)
        self.pod_count = np.zeros(n, dtype=np.int64)
        self.pods_on: List[List[dict]] = [[] for _ in range(n)]
        for pod in pods:
            if ((pod.get("status") or {}).get("phase")) in ("Succeeded",
                                                            "Failed"):
                continue
            i = index.get((pod.get("spec") or {}).get("nodeName"))
            if i is None:
                continue
            self.requested[i] += requests(pod)
            self.nonzero[i] += requests(pod, nonzero=True)
            self.pod_count[i] += 1
            self.pods_on[i].append(pod)

    def domain_index(self, key: str):
        """(per-node domain index, -1 where the label is absent; the
        domain values)."""
        values: Dict[str, int] = {}
        idx = np.full(len(self.names), -1, dtype=np.int64)
        for i, lab in enumerate(self.labels):
            if key in lab:
                idx[i] = values.setdefault(lab[key], len(values))
        return idx, list(values)


def _check_pod(pod: dict) -> None:
    spec = pod.get("spec") or {}
    for key in ("nodeName", "nodeSelector", "tolerations", "volumes",
                "schedulingGates", "resourceClaims", "priority",
                "priorityClassName"):
        if spec.get(key):
            raise Unsupported(f"pod spec.{key}")
    aff = _affinity(pod)
    if aff.get("nodeAffinity") or _required(pod, "podAffinity"):
        raise Unsupported("node affinity or required pod affinity")
    for c in spec.get("containers") or []:
        if any(p.get("hostPort") for p in c.get("ports") or []):
            raise Unsupported("host ports")
    for c in spec.get("topologySpreadConstraints") or []:
        if c.get("whenUnsatisfiable", "DoNotSchedule") != "DoNotSchedule":
            raise Unsupported("ScheduleAnyway spread constraints")
        if c.get("minDomains") or c.get("matchLabelKeys") or \
                c.get("nodeAffinityPolicy") or c.get("nodeTaintsPolicy"):
            raise Unsupported("spread constraint options")


class _Scores:
    """Per-node score terms that depend only on the node's own state:
    NodeResourcesFit (LeastAllocated, cpu and memory weight 1),
    NodeResourcesBalancedAllocation and TaintToleration (no taints)."""

    def __init__(self, dtype, pod_req, pod_nz):
        self.dt = dtype
        self.req = np.asarray(pod_req, dtype=dtype)
        self.nz = np.asarray(pod_nz, dtype=dtype)

    def __call__(self, alloc, requested, nonzero):
        dt = self.dt
        hundred, zero = dt(100), dt(0)
        least = []
        for r in range(2):
            a = alloc[:, r]
            want = nonzero[:, r] + self.nz[r]
            s = np.floor((a - want) * hundred / a)
            least.append(np.where((want > a) | (a <= zero), zero, s))
        fit = np.floor((least[0] + least[1]) / dt(2))
        frac = [np.minimum((requested[:, r] + self.req[r]) / alloc[:, r],
                           dt(1)) for r in range(2)]
        std = np.abs(frac[0] - frac[1]) / dt(2)
        balanced = np.trunc((dt(1) - std) * hundred)
        taint = hundred          # no node has a PreferNoSchedule taint
        return (fit * dt(W_FIT) + balanced * dt(W_BALANCED)
                + taint * dt(W_TAINT)).astype(dt)


def solve(cluster: Cluster, pod: dict, max_limit: int = 0,
          dtype=np.float32) -> Answer:
    """Place clones of `pod` one at a time until no node fits or
    `max_limit` are placed."""
    _check_pod(pod)
    dt = dtype
    n = len(cluster.names)
    own_ns, own_labels = _ns(pod), _labels(pod)
    alloc = cluster.alloc.astype(dt)
    requested = cluster.requested.astype(dt)
    nonzero = cluster.nonzero.astype(dt)
    count = cluster.pod_count.astype(dt)
    pod_req = requests(pod)
    pod_nz = requests(pod, nonzero=True)
    preq = np.asarray(pod_req, dtype=dt)
    pnz = np.asarray(pod_nz, dtype=dt)
    one = dt(1)
    scores = _Scores(dt, pod_req, pod_nz)

    def fits(req, cnt):
        """NodeResourcesFit on every node: (too many pods, insufficient
        cpu, insufficient memory)."""
        free = alloc[:, :2] - req
        return (cnt + one > alloc[:, 2],
                (preq[0] > 0) & (preq[0] > free[:, 0]),
                (preq[1] > 0) & (preq[1] > free[:, 1]))

    # A node's own score and fit change only as clones land on it, so both
    # are tabulated for k = 0, 1, ... clones on every node at once, adding
    # the clone's requests in `dtype` as the placement loop would.
    rows = int(cluster.alloc[:, 2].max()) + 2
    base_tab = np.empty((rows, n), dtype=dt)
    fit_tab = np.empty((rows, n), dtype=bool)
    req_tab = np.empty((rows, n, 2), dtype=dt)
    cnt_tab = np.empty((rows, n), dtype=dt)
    req_k, nz_k, cnt_k = requested, nonzero, count
    for k in range(rows):
        base_tab[k] = scores(alloc, req_k, nz_k)
        too_many, cpu, mem = fits(req_k, cnt_k)
        fit_tab[k] = ~(too_many | cpu | mem)
        req_tab[k], cnt_tab[k] = req_k, cnt_k
        req_k, nz_k, cnt_k = req_k + preq, nz_k + pnz, cnt_k + one
    placed = np.zeros(n, dtype=np.int64)
    neg_inf = dt(-np.inf)

    # PodTopologySpread, DoNotSchedule constraints
    spread = []
    for c in (pod.get("spec") or {}).get("topologySpreadConstraints") or []:
        dom, values = cluster.domain_index(c["topologyKey"])
        if (dom < 0).any():
            raise Unsupported("nodes without a spread constraint's key")
        counts = np.zeros(len(values), dtype=dt)
        for i in range(n):
            if dom[i] >= 0:
                counts[dom[i]] += dt(sum(
                    1 for p in cluster.pods_on[i]
                    if _ns(p) == own_ns and not (p.get("metadata") or {})
                    .get("deletionTimestamp")
                    and selector_matches(c.get("labelSelector"), _labels(p))))
        spread.append({"dom": dom, "counts": counts,
                       "skew": dt(int(c.get("maxSkew", 1))),
                       "self": dt(1 if selector_matches(
                           c.get("labelSelector"), own_labels) else 0)})

    # InterPodAffinity: the incoming pod's required anti-affinity terms,
    # existing pods' required anti-affinity against the incoming pod, and
    # the preferred terms both ways, all keyed by (topologyKey, value)
    keys: Dict[str, tuple] = {}

    def key_index(key):
        if key not in keys:
            keys[key] = cluster.domain_index(key)
        return keys[key]

    anti = []          # incoming required anti terms: blocked domain counts
    for t in _required(pod, "podAntiAffinity"):
        dom, values = key_index(t["topologyKey"])
        anti.append({"term": t, "dom": dom,
                     "cnt": np.zeros(len(values), dtype=dt)})
    existing_block: Dict[str, np.ndarray] = {}   # key -> blocked domains
    pref_w: Dict[str, np.ndarray] = {}           # key -> summed weights
    ipa_version = [0]     # bumped whenever a filter tally changes

    def add_pod(i, p):
        """Account pod `p` on node i in the affinity tallies."""
        p_ns = _ns(p)
        for a in anti:
            if a["dom"][i] >= 0 and term_matches(a["term"], own_ns, p):
                a["cnt"][a["dom"][i]] += one
                ipa_version[0] += 1
        for t in _required(p, "podAntiAffinity"):
            if term_matches(t, p_ns, pod):
                dom, values = key_index(t["topologyKey"])
                if dom[i] >= 0:
                    blk = existing_block.setdefault(
                        t["topologyKey"], np.zeros(len(values), dtype=bool))
                    blk[dom[i]] = True
                    ipa_version[0] += 1
        contrib = []
        for t in _preferred(pod, "podAffinity"):
            if term_matches(t["podAffinityTerm"], own_ns, p):
                contrib.append((t["podAffinityTerm"], t["weight"]))
        for t in _preferred(pod, "podAntiAffinity"):
            if term_matches(t["podAffinityTerm"], own_ns, p):
                contrib.append((t["podAffinityTerm"], -t["weight"]))
        for t in _preferred(p, "podAffinity"):
            if term_matches(t["podAffinityTerm"], p_ns, pod):
                contrib.append((t["podAffinityTerm"], t["weight"]))
        for t in _preferred(p, "podAntiAffinity"):
            if term_matches(t["podAffinityTerm"], p_ns, pod):
                contrib.append((t["podAffinityTerm"], -t["weight"]))
        for t in _required(p, "podAffinity"):
            if term_matches(t, p_ns, pod):
                contrib.append((t, 1))
        for term, w in contrib:
            dom, values = key_index(term["topologyKey"])
            if dom[i] >= 0:
                acc = pref_w.setdefault(term["topologyKey"],
                                        np.zeros(len(values), dtype=dt))
                acc[dom[i]] += dt(w)

    for i in range(n):
        for p in cluster.pods_on[i]:
            add_pod(i, p)

    def ipa_ok():
        ok = np.ones(n, dtype=bool)
        for a in anti:
            blocked = (a["dom"] >= 0) & (a["cnt"][np.maximum(a["dom"], 0)]
                                         > 0)
            ok &= ~blocked
        ok_existing = np.ones(n, dtype=bool)
        for key, blk in existing_block.items():
            dom = keys[key][0]
            ok_existing &= ~((dom >= 0) & blk[np.maximum(dom, 0)])
        return ok, ok_existing

    def ipa_score(feasible):
        """Normalized InterPodAffinity score; None when no term counts."""
        if not pref_w:
            return None
        raw = np.zeros(n, dtype=dt)
        for key, acc in pref_w.items():
            dom = keys[key][0]
            raw = raw + np.where(dom >= 0, acc[np.maximum(dom, 0)], dt(0))
        raw = np.trunc(raw)
        mx, mn = raw[feasible].max(), raw[feasible].min()
        diff = mx - mn
        if diff <= 0:
            return np.zeros(n, dtype=dt)
        return np.trunc(dt(100) * ((raw - mn) / diff))

    clone = {"metadata": {"namespace": own_ns, "labels": own_labels},
             "spec": {"affinity": _affinity(pod)}}
    placements: List[int] = []
    cols = np.arange(n)
    own = base_tab[0].copy()          # each node's own score terms
    fit_ok = fit_tab[0].copy()
    own_fit = np.where(fit_ok, own, neg_inf)
    # filter state (spread domains allowed, affinity tallies) -> masks:
    # (spread ok, anti ok, existing anti ok, all three, all nodes ok)
    others = {}
    while not (max_limit and len(placements) >= max_limit):
        dom_ok = tuple(s["counts"] + s["self"] - s["counts"].min()
                       <= s["skew"] for s in spread)
        key = (b"".join(d.tobytes() for d in dom_ok), ipa_version[0])
        if key not in others:
            sp_ok = np.ones(n, dtype=bool)
            for s, d in zip(spread, dom_ok):
                sp_ok &= d[s["dom"]]
            others[key] = (sp_ok,) + ipa_ok()
            other_ok = others[key][0] & others[key][1] & others[key][2]
            others[key] += (other_ok, bool(other_ok.all()))
        masks = others[key]
        if pref_w:
            feasible = fit_ok & masks[3]
            total = own
            ipa = ipa_score(feasible)
            if ipa is not None:
                total = own + ipa * dt(W_IPA)
            masked = np.where(feasible, total, neg_inf)
        elif masks[4]:
            masked = own_fit
        else:
            masked = np.where(masks[3], own_fit, neg_inf)
        i = int(np.argmax(masked))
        if masked[i] == neg_inf:
            return Answer(placements, cluster.names, _reasons(
                fits(req_tab[placed, cols], cnt_tab[placed, cols]),
                *masks[:3]))
        placements.append(i)
        placed[i] += 1
        own[i] = base_tab[placed[i], i]
        fit_ok[i] = fit_tab[placed[i], i]
        own_fit[i] = own[i] if fit_ok[i] else neg_inf
        for s in spread:
            if s["self"] > 0:
                s["counts"][s["dom"][i]] += one
        add_pod(i, clone)
    return Answer(placements, cluster.names, {})


def _reasons(fit, spread_ok, ok_anti, ok_existing) -> Dict[str, int]:
    """Per reason, how many nodes refused the pod: every failing fit check
    of a node that fails NodeResourcesFit, else the first failing
    plugin after it."""
    too_many, cpu, mem = fit
    fit_fail = too_many | cpu | mem
    out: Dict[str, int] = {}
    for name, mask in ((R_TOO_MANY, too_many), (R_CPU, cpu),
                       (R_MEMORY, mem),
                       (R_SPREAD, ~fit_fail & ~spread_ok),
                       (R_ANTI, ~fit_fail & spread_ok & ~ok_anti),
                       (R_EXISTING_ANTI,
                        ~fit_fail & spread_ok & ok_anti & ~ok_existing)):
        k = int(np.count_nonzero(mask))
        if k:
            out[name] = k
    return out

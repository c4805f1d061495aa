"""Tensor interleave engine vs the object-level queue loop (its oracle).

parallel/interleave.py runs the shared-state multi-template queue study on
device; parallel/sweep.sweep_interleaved is the object-level parity path.
Every eligible study must match it bit-for-bit: placements, fail types,
fail messages.  Reference semantics: backend/queue/scheduling_queue.go pop
loop + one scheduling cycle per pop (schedule_one.go:66-150).
"""

import numpy as np
import pytest

from cluster_capacity_tpu.models.podspec import default_pod
from cluster_capacity_tpu.models.snapshot import ClusterSnapshot
from cluster_capacity_tpu.parallel import interleave as il
from cluster_capacity_tpu.parallel.sweep import sweep_interleaved
from cluster_capacity_tpu.utils.config import SchedulerProfile


def _nodes(n, zones=3, cpus=(2000, 4000), pods=16, seed=0):
    rng = np.random.RandomState(seed)
    out = []
    for i in range(n):
        out.append({
            "metadata": {"name": f"n{i:03d}", "labels": {
                "kubernetes.io/hostname": f"n{i:03d}",
                "topology.kubernetes.io/zone": f"z{i % zones}",
                "disk": "ssd" if i % 2 else "hdd"}},
            "spec": {},
            "status": {"allocatable": {
                "cpu": f"{int(rng.choice(cpus))}m",
                "memory": str(int(rng.choice([4, 8])) * 1024 ** 3),
                "pods": str(pods)}}})
    return out


def _template(name, cpu, mem_gi=0, ns="default", spread=None, soft=None,
              aff=None, anti=None, pref_anti=None, labels=None):
    req = {"cpu": f"{cpu}m"}
    if mem_gi:
        req["memory"] = f"{mem_gi}Gi"
    pod = {"metadata": {"name": name, "namespace": ns,
                        "labels": dict(labels or {"app": name})},
           "spec": {"containers": [{"name": "c", "resources": {
               "requests": req}}]}}
    tsc = []
    if spread:
        tsc.append({"maxSkew": spread[0], "topologyKey": spread[1],
                    "whenUnsatisfiable": "DoNotSchedule",
                    "labelSelector": {"matchLabels": spread[2]}})
    if soft:
        tsc.append({"maxSkew": soft[0], "topologyKey": soft[1],
                    "whenUnsatisfiable": "ScheduleAnyway",
                    "labelSelector": {"matchLabels": soft[2]}})
    if tsc:
        pod["spec"]["topologySpreadConstraints"] = tsc
    affinity = {}
    if aff:
        affinity["podAffinity"] = {
            "requiredDuringSchedulingIgnoredDuringExecution": [
                {"topologyKey": aff[0],
                 "labelSelector": {"matchLabels": aff[1]}}]}
    if anti:
        affinity.setdefault("podAntiAffinity", {})[
            "requiredDuringSchedulingIgnoredDuringExecution"] = [
            {"topologyKey": anti[0],
             "labelSelector": {"matchLabels": anti[1]}}]
    if pref_anti:
        affinity.setdefault("podAntiAffinity", {})[
            "preferredDuringSchedulingIgnoredDuringExecution"] = [
            {"weight": pref_anti[0], "podAffinityTerm": {
                "topologyKey": pref_anti[1],
                "labelSelector": {"matchLabels": pref_anti[2]}}}]
    if affinity:
        pod["spec"]["affinity"] = affinity
    return default_pod(pod)


def _assert_same(ref, got, label=""):
    assert got is not None, f"{label}: tensor path fell back"
    for i, (r, g) in enumerate(zip(ref, got)):
        assert r.placements == g.placements, \
            f"{label}[{i}]: {r.placements} != {g.placements}"
        assert r.fail_type == g.fail_type, f"{label}[{i}]"
        assert r.fail_message == g.fail_message, \
            f"{label}[{i}]: {r.fail_message!r} != {g.fail_message!r}"


def test_plain_mix_matches_object_path():
    snap = ClusterSnapshot.from_objects(_nodes(10))
    ts = [_template("a", 600), _template("b", 450, mem_gi=1),
          _template("c", 900)]
    prof = SchedulerProfile.parity()
    _assert_same(sweep_interleaved(snap, ts, prof),
                 il.solve_interleaved_tensor(snap, ts, prof), "plain")


def test_topology_mix_matches_object_path():
    """Spread + IPA cross-template coupling: b's clones count under a's
    selector (shared app label), anti-affinity blocks across templates."""
    snap = ClusterSnapshot.from_objects(_nodes(12))
    shared = {"tier": "web"}
    ts = [
        _template("a", 500, spread=(2, "topology.kubernetes.io/zone", shared),
                  labels={"app": "a", "tier": "web"}),
        _template("b", 400, labels={"app": "b", "tier": "web"}),
        _template("c", 300, anti=("kubernetes.io/hostname", {"app": "c"})),
        _template("d", 350, soft=(1, "topology.kubernetes.io/zone",
                                  {"app": "d"})),
    ]
    prof = SchedulerProfile.parity()
    _assert_same(sweep_interleaved(snap, ts, prof),
                 il.solve_interleaved_tensor(snap, ts, prof), "topo")


def test_cross_template_affinity_and_add_requeue():
    """a requires affinity to b's clones: a parks first, b's placements
    reactivate it (pod-ADD hint) — both engines must agree."""
    snap = ClusterSnapshot.from_objects(_nodes(9))
    ts = [
        _template("a", 400, aff=("topology.kubernetes.io/zone",
                                 {"app": "b"})),
        _template("b", 700),
    ]
    prof = SchedulerProfile.parity()
    ref = sweep_interleaved(snap, ts, prof)
    got = il.solve_interleaved_tensor(snap, ts, prof)
    _assert_same(ref, got, "aff-requeue")
    assert ref[0].placed_count > 0          # the requeue actually fired


def test_sampling_scale_matches_object_path():
    """>100 nodes with percentageOfNodesToScore active: the rotating
    per-template sampling windows must stay in lockstep."""
    snap = ClusterSnapshot.from_objects(_nodes(130, zones=5, seed=3))
    ts = [_template("a", 800), _template("b", 650, mem_gi=1)]
    prof = SchedulerProfile.parity()
    prof.percentage_of_nodes_to_score = 60
    _assert_same(sweep_interleaved(snap, ts, prof, max_total=120),
                 il.solve_interleaved_tensor(snap, ts, prof, max_total=120),
                 "sampling")


def test_max_total_and_gated_templates():
    snap = ClusterSnapshot.from_objects(_nodes(6))
    gated = default_pod({"metadata": {"name": "g"}, "spec": {
        "containers": [{"name": "c", "resources": {
            "requests": {"cpu": "100m"}}}],
        "schedulingGates": [{"name": "w"}]}})
    ts = [gated, _template("a", 500), _template("b", 500)]
    prof = SchedulerProfile.parity()
    _assert_same(sweep_interleaved(snap, ts, prof, max_total=7),
                 il.solve_interleaved_tensor(snap, ts, prof, max_total=7),
                 "max-total")


def test_fuzz_mixed_families():
    """Randomized differential: template mixes over spread/soft/IPA/plain
    with namespaces and existing pods."""
    rng = np.random.RandomState(11)
    for seed in range(6):
        n = int(rng.choice([8, 14, 20]))
        nodes = _nodes(n, zones=int(rng.choice([2, 3])), seed=seed)
        existing = []
        for j in range(int(rng.choice([0, 3]))):
            existing.append({
                "metadata": {"name": f"pre{j}", "namespace": "default",
                             "labels": {"tier": "web"}},
                "spec": {"containers": [{"name": "c", "resources": {
                    "requests": {"cpu": "300m"}}}],
                    "nodeName": f"n{j % n:03d}"}})
        snap = ClusterSnapshot.from_objects(nodes, existing)
        ts = []
        for k in range(int(rng.choice([2, 4]))):
            kind = rng.choice(["plain", "spread", "soft", "anti",
                               "port", "disk", "pref"])
            cpu = int(rng.choice([300, 500, 800]))
            if kind == "plain":
                ts.append(_template(f"t{k}", cpu))
            elif kind == "spread":
                ts.append(_template(
                    f"t{k}", cpu,
                    spread=(int(rng.choice([1, 2])),
                            "topology.kubernetes.io/zone",
                            {"tier": "web"}),
                    labels={"app": f"t{k}", "tier": "web"}))
            elif kind == "soft":
                ts.append(_template(
                    f"t{k}", cpu,
                    soft=(1, "topology.kubernetes.io/zone",
                          {"app": f"t{k}"})))
            elif kind == "anti":
                ts.append(_template(
                    f"t{k}", cpu,
                    anti=("kubernetes.io/hostname", {"app": f"t{k}"})))
            elif kind == "port":
                t = _template(f"t{k}", cpu)
                t["spec"]["containers"][0]["ports"] = [
                    {"hostPort": int(rng.choice([8080, 9090]))}]
                ts.append(t)
            elif kind == "disk":
                t = _template(f"t{k}", cpu)
                t["spec"]["volumes"] = [{"name": "v", "gcePersistentDisk": {
                    "pdName": f"pd-{int(rng.choice([1, 2]))}"}}]
                ts.append(t)
            else:
                ts.append(_template(
                    f"t{k}", cpu,
                    pref_anti=(10, "kubernetes.io/hostname",
                               {"tier": "web"}),
                    labels={"app": f"t{k}", "tier": "web"}))
        prof = SchedulerProfile.parity()
        _assert_same(sweep_interleaved(snap, ts, prof),
                     il.solve_interleaved_tensor(snap, ts, prof),
                     f"fuzz-{seed}")


def test_cross_matrix_diagonals_equal_self_increments():
    """xinc[t, t] must reproduce the single-template self increments."""
    from cluster_capacity_tpu.engine import encode as enc
    from cluster_capacity_tpu.ops import inter_pod_affinity as ipa_ops
    from cluster_capacity_tpu.parallel import sweep as sweep_mod

    snap = ClusterSnapshot.from_objects(_nodes(8))
    ts = [
        _template("a", 400, spread=(1, "topology.kubernetes.io/zone",
                                    {"app": "a"})),
        _template("b", 300, spread=(2, "topology.kubernetes.io/zone",
                                    {"app": "b"}),
                  anti=("kubernetes.io/hostname", {"app": "b"})),
    ]
    prof = SchedulerProfile.parity()
    keys = il.union_topology_keys(ts)
    pbs = [enc.encode_problem(snap, t, prof, ipa_extra_keys=keys)
           for t in ts]
    pbs, _cfg, _dnh = sweep_mod._pad_group(pbs)
    sh = il._spread_xinc(pbs, "spread_hard")
    for t, pb in enumerate(pbs):
        got = sh[t, t, :pb.spread_hard.self_match.shape[0]]
        assert (got.astype(bool) == pb.spread_hard.self_match).all()
    x = il._ipa_xinc(pbs)
    for t, pb in enumerate(pbs):
        _ga, _gn, aff_g, anti_g, pref_g = ipa_ops.group_fold(pb.ipa)
        assert (x["aff_xinc"][t, t] == aff_g).all()
        assert (x["anti_xinc"][t, t] == anti_g).all()
        assert (x["pref_xinc"][t, t] == pref_g).all()


def test_fallback_reasons():
    snap = ClusterSnapshot.from_objects(_nodes(6))
    prof = SchedulerProfile.parity()

    # priorities differing no longer falls back (tier-ranked pops are
    # native) — covered differentially below

    # extenders no longer fall back (r5): one static host
    # round per template — covered differentially below

    # host ports / inline disks / RWOP run natively as of r5 — covered
    # differentially below; shared-DRA colocation still falls back
    slices = [{"metadata": {"name": "s0"},
               "spec": {"nodeName": "n000", "driver": "gpu.example.com",
                        "devices": [{"name": "d0",
                                     "deviceClassName": "gpu.example.com"}]}}]
    claim = {"metadata": {"name": "shared", "namespace": "default"},
             "spec": {"devices": {"requests": [
                 {"name": "r0", "deviceClassName": "gpu.example.com",
                  "count": 1}]}}}
    snap_dra = ClusterSnapshot.from_objects(
        _nodes(6), resource_slices=slices, resource_claims=[claim])
    shared = _template("sh", 300)
    shared["spec"]["resourceClaims"] = [
        {"name": "gpu", "resourceClaimName": "shared"}]
    assert il.solve_interleaved_tensor(snap_dra, [shared], prof) is None

    # the auto front door still answers (object fallback)
    res = il.sweep_interleaved_auto(snap_dra, [shared], prof, max_total=3)
    assert res[0].placed_count == 3


def test_curability_transition_matches_object_path():
    """Regression (review r3): a template whose park reason DEGRADES from
    curable (absent affinity anchor) to non-curable (Insufficient cpu) must
    stop requeueing exactly when the object path does — wrong staleness
    shows up as LimitReached-vs-Unschedulable flips at quota boundaries."""
    nodes = [{"metadata": {"name": f"n{i}", "labels": {
                "kubernetes.io/hostname": f"n{i}",
                "topology.kubernetes.io/zone": "z1"}},
              "spec": {},
              "status": {"allocatable": {"cpu": "1000m",
                                         "memory": str(8 * 1024 ** 3),
                                         "pods": "20"}}} for i in range(2)]
    snap = ClusterSnapshot.from_objects(nodes)
    a = _template("a", 600, aff=("topology.kubernetes.io/zone",
                                 {"app": "missing-anchor"}))
    b = _template("b", 400)
    c = _template("c", 100)
    prof = SchedulerProfile.parity()
    for mt in (0, 3, 5, 6, 8, 9, 12):
        ref = sweep_interleaved(snap, [a, b, c], prof, max_total=mt)
        got = il.solve_interleaved_tensor(snap, [a, b, c], prof,
                                          max_total=mt)
        _assert_same(ref, got, f"transition mt={mt}")


# --- priority tiers + preemption --------------------------

def _victim_pod(name, node, cpu_m, priority, labels=None):
    return {"metadata": {"name": name, "namespace": "default",
                         "labels": dict(labels or {})},
            "spec": {"nodeName": node, "priority": priority,
                     "containers": [{"name": "c", "resources": {
                         "requests": {"cpu": f"{cpu_m}m"}}}]}}


def test_priority_tiers_without_victims():
    """Tiered templates, no preemption possible (no pod below the floor):
    high tier drains first, FIFO within tiers — placement-for-placement
    parity with the object queue loop."""
    snap = ClusterSnapshot.from_objects(_nodes(8, pods=6))
    ts = []
    for k in range(6):
        t = _template(f"t{k}", 300 + 50 * k)
        t["spec"]["priority"] = (k % 3) * 10          # three tiers
        ts.append(t)
    prof = SchedulerProfile.parity()
    _assert_same(sweep_interleaved(snap, ts, prof),
                 il.solve_interleaved_tensor(snap, ts, prof), "tiers")


def test_preemption_single_eviction():
    """A high-priority template preempts an existing low-priority pod;
    both engines must agree on the eviction's downstream placements."""
    nodes = _nodes(3, cpus=(1000,), pods=8)
    victims = [_victim_pod(f"v{i}", f"n{i:03d}", 900, 5) for i in range(3)]
    snap = ClusterSnapshot.from_objects(nodes, pods=victims)
    hi = _template("hi", 800)
    hi["spec"]["priority"] = 100
    prof = SchedulerProfile.parity()
    ref = sweep_interleaved(snap, [hi], prof)
    got = il.solve_interleaved_tensor(snap, [hi], prof)
    _assert_same(ref, got, "single-eviction")
    assert ref[0].placed_count == 3        # one per node after evictions


def test_preemption_tiered_templates_with_victims():
    """Two template tiers racing; the high tier evicts the low tier's
    already-placed clones when capacity runs out (the cross-template
    victim path) — exact parity including bind-time accounting (evicted
    clones stay in their owner's report)."""
    nodes = _nodes(4, cpus=(1000,), pods=8)
    snap = ClusterSnapshot.from_objects(nodes)
    lo = _template("lo", 600)
    lo["spec"]["priority"] = 0
    hi = _template("hi", 700)
    hi["spec"]["priority"] = 50
    prof = SchedulerProfile.parity()
    _assert_same(sweep_interleaved(snap, [hi, lo], prof),
                 il.solve_interleaved_tensor(snap, [hi, lo], prof),
                 "tiered-victims")


def test_preemption_pdb_protected_victims():
    """PDB-protected victims count as violations in pickOneNode; parity
    through the shared evaluator."""
    nodes = _nodes(2, cpus=(1000,), pods=8)
    victims = [_victim_pod("va", "n000", 900, 1, labels={"guard": "y"}),
               _victim_pod("vb", "n001", 900, 1)]
    pdb = {"metadata": {"name": "guard", "namespace": "default"},
           "spec": {"selector": {"matchLabels": {"guard": "y"}}},
           "status": {"disruptionsAllowed": 0}}
    snap = ClusterSnapshot.from_objects(nodes, pods=victims, pdbs=[pdb])
    hi = _template("hi", 800)
    hi["spec"]["priority"] = 100
    prof = SchedulerProfile.parity()
    ref = sweep_interleaved(snap, [hi], prof)
    got = il.solve_interleaved_tensor(snap, [hi], prof)
    _assert_same(ref, got, "pdb")
    # the unprotected victim's node must be chosen first
    assert ref[0].placements[0] == 1


@pytest.mark.parametrize("seed", range(4))
def test_fuzz_tiered_preemption_corpus(seed):
    """Randomized priority-tiered corpora with existing lower-priority
    pods: spread + affinity templates
    over three tiers, victims present."""
    rng = np.random.RandomState(400 + seed)
    nodes = _nodes(int(rng.choice([5, 8])), zones=3,
                   cpus=(2000,), pods=10, seed=seed)
    victims = [_victim_pod(f"v{i}", f"n{int(rng.randint(len(nodes))):03d}",
                           int(rng.choice([500, 1500])), int(rng.choice([0, 3])),
                           labels={"app": "victim"})
               for i in range(int(rng.choice([2, 4])))]
    snap = ClusterSnapshot.from_objects(nodes, pods=victims)
    ts = []
    for k in range(int(rng.choice([3, 5]))):
        kind = k % 3
        if kind == 0:
            t = _template(f"t{k}", int(rng.choice([400, 700])),
                          spread=(int(rng.choice([1, 2])),
                                  "topology.kubernetes.io/zone",
                                  {"app": f"t{k}"}))
        elif kind == 1:
            t = _template(f"t{k}", int(rng.choice([400, 700])),
                          pref_anti=(10, "kubernetes.io/hostname",
                                     {"app": f"t{k}"}))
        else:
            t = _template(f"t{k}", int(rng.choice([400, 700])))
        t["spec"]["priority"] = int(rng.choice([0, 10, 20]))
        ts.append(t)
    prof = SchedulerProfile.parity()
    _assert_same(sweep_interleaved(snap, ts, prof),
                 il.solve_interleaved_tensor(snap, ts, prof),
                 f"tier-fuzz-{seed}")


# --------------------------------------------------------------------------
# extender host-callback rounds (r5)
# --------------------------------------------------------------------------

def _http_extender_server(filter_fn=None, prioritize_fn=None,
                          with_bind=False):
    """Tiny local HTTP extender (extender/v1 payload shapes); returns
    (ExtenderConfig, calls, shutdown)."""
    import json
    import threading
    from http.server import BaseHTTPRequestHandler, HTTPServer

    from cluster_capacity_tpu.engine.extenders import ExtenderConfig

    calls = []

    class H(BaseHTTPRequestHandler):
        def do_POST(self):
            body = json.loads(self.rfile.read(
                int(self.headers["Content-Length"])).decode())
            verb = self.path.rsplit("/", 1)[-1]
            calls.append((verb, body))
            if verb == "filter":
                names = body.get("NodeNames") or []
                out = {"NodeNames": filter_fn(body["Pod"], names)
                       if filter_fn else list(names)}
            elif verb == "prioritize":
                names = body.get("NodeNames") or []
                out = [{"Host": n,
                        "Score": prioritize_fn(body["Pod"], n)
                        if prioritize_fn else 0}
                       for n in names]
            elif verb == "bind":
                out = {}
            else:
                out = {"Error": f"unknown verb {verb}"}
            payload = json.dumps(out).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(payload)))
            self.end_headers()
            self.wfile.write(payload)

        def log_message(self, *a):
            pass

    srv = HTTPServer(("127.0.0.1", 0), H)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    cfg = ExtenderConfig(
        url_prefix=f"http://127.0.0.1:{srv.server_port}/scheduler",
        filter_verb="filter", prioritize_verb="prioritize",
        bind_verb="bind" if with_bind else "", weight=10,
        node_cache_capable=True)

    def shutdown():
        srv.shutdown()
        srv.server_close()
    return cfg, calls, shutdown


def test_extender_http_mix_matches_object_path():
    """Mixed spread/plain corpus through a REAL HTTP extender (filter drops
    even-numbered nodes; prioritize favors zone z1): the tensor engine's
    static per-template rounds must reproduce the object path's per-cycle
    webhook calls placement-for-placement, including the extender-filter
    FitError bucket when the filter empties a template's window."""
    snap = ClusterSnapshot.from_objects(_nodes(9))

    def filt(pod, names):
        # drop even nodes for template "b" only; keep all for others
        if (pod.get("metadata") or {}).get("name") == "b":
            return [n for n in names if int(n[1:]) % 2 == 1]
        return list(names)

    def prio(pod, name):
        return 3 if int(name[1:]) % 3 == 1 else 0

    cfg, calls, shutdown = _http_extender_server(filt, prio)
    try:
        prof_ref = SchedulerProfile.parity()
        prof_ref.extenders = [cfg]
        ts = [_template("a", 600, spread=(1, "topology.kubernetes.io/zone",
                                          {"app": "a"})),
              _template("b", 450), _template("c", 700)]
        ref = sweep_interleaved(snap, ts, prof_ref)
        got = il.solve_interleaved_tensor(snap, ts, prof_ref)
        _assert_same(ref, got, "http-ext")
    finally:
        shutdown()


def test_extender_empties_window_parks_with_bucket():
    """An extender rejecting EVERY node for one template parks it with the
    extender-filter bucket while other templates keep placing — both paths
    agree."""
    snap = ClusterSnapshot.from_objects(_nodes(6))

    def filt(pod, names):
        if (pod.get("metadata") or {}).get("name") == "blocked":
            return []
        return list(names)

    cfg, calls, shutdown = _http_extender_server(filt)
    try:
        prof = SchedulerProfile.parity()
        prof.extenders = [cfg]
        ts = [_template("blocked", 100), _template("free", 500)]
        ref = sweep_interleaved(snap, ts, prof)
        got = il.solve_interleaved_tensor(snap, ts, prof)
        _assert_same(ref, got, "ext-blocked")
        from cluster_capacity_tpu.engine.extenders import (
            REASON_EXTENDER_FILTER)
        assert got[0].placed_count == 0
        assert got[0].fail_counts.get(REASON_EXTENDER_FILTER) == 6
        assert got[1].placed_count > 0
    finally:
        shutdown()


def test_extender_bind_drain_order():
    """Binder extenders fire once per placement, in placement order, with
    the clone (not the template) as the payload."""
    snap = ClusterSnapshot.from_objects(_nodes(4))
    cfg, calls, shutdown = _http_extender_server(with_bind=True)
    try:
        prof = SchedulerProfile.parity()
        prof.extenders = [cfg]
        ts = [_template("a", 900), _template("b", 700)]
        got = il.solve_interleaved_tensor(snap, ts, prof, max_total=6)
        assert got is not None
        binds = [b for v, b in calls if v == "bind"]
        assert len(binds) == sum(r.placed_count for r in got) == 6
        # clone names carry the per-clone suffix, alternating a/b pops
        assert binds[0]["PodName"].startswith("a-")
        assert binds[1]["PodName"].startswith("b-")
    finally:
        shutdown()


def test_extender_callable_with_priority_tiers_and_preemption():
    """Callable extenders compose with native tiers + preemption: a
    high-priority template preempts through the extender-vetted candidate
    set; both engines agree."""
    from cluster_capacity_tpu.engine.extenders import ExtenderConfig
    snap = ClusterSnapshot.from_objects(
        _nodes(5, pods=2),
        priority_classes=[{"metadata": {"name": "high"}, "value": 1000}])

    def filt(pod, names):
        return {"NodeNames": [n for n in names if n != "n000"]}

    prof = SchedulerProfile.parity()
    prof.extenders = [ExtenderConfig(filter_callable=filt)]
    hi = _template("hi", 300)
    hi["spec"]["priorityClassName"] = "high"
    hi["spec"]["priority"] = 1000
    lo = _template("lo", 300)
    lo["spec"]["priority"] = 0
    ref = sweep_interleaved(snap, [hi, lo], prof)
    got = il.solve_interleaved_tensor(snap, [hi, lo], prof)
    _assert_same(ref, got, "ext-tiers")


def test_tensor_extenders_opt_out():
    """profile.tensor_extenders=False routes extender studies to the
    object path (the escape hatch for stateful webhooks)."""
    from cluster_capacity_tpu.engine.extenders import ExtenderConfig
    snap = ClusterSnapshot.from_objects(_nodes(4))
    prof = SchedulerProfile.parity()
    prof.extenders = [ExtenderConfig(
        filter_callable=lambda p, names: {"NodeNames": list(names)})]
    prof.tensor_extenders = False
    assert il.solve_interleaved_tensor(snap, [_template("a", 300)],
                                       prof) is None
    res = il.sweep_interleaved_auto(snap, [_template("a", 300)], prof,
                                    max_total=3)
    assert res[0].placed_count == 3


# --------------------------------------------------------------------------
# host-port templates on the tensor engine (r5)
# --------------------------------------------------------------------------

def _port_template(name, cpu, port, labels=None):
    t = _template(name, cpu, labels=labels)
    t["spec"]["containers"][0]["ports"] = [{"hostPort": port,
                                            "protocol": "TCP"}]
    return t


def test_host_ports_cross_template_matches_object_path():
    """Templates sharing hostPort 8080 block each other's nodes (and their
    own); a disjoint-port template and a portless template interleave
    freely — every placement and FitError must match the object path."""
    snap = ClusterSnapshot.from_objects(_nodes(5))
    ts = [_port_template("a", 300, 8080),
          _port_template("b", 300, 8080),
          _port_template("c", 300, 9090),
          _template("d", 400)]
    prof = SchedulerProfile.parity()
    ref = sweep_interleaved(snap, ts, prof)
    got = il.solve_interleaved_tensor(snap, ts, prof)
    _assert_same(ref, got, "ports")
    # 5 nodes shared by a+b (same port): together at most 5 clones
    assert ref[0].placed_count + ref[1].placed_count == 5
    assert ref[2].placed_count == 5          # disjoint port: own 5
    assert "free ports" in ref[0].fail_message


def test_host_ports_wildcard_ip_and_existing_pods():
    """hostIP 0.0.0.0 wildcards against specific IPs; existing pods' ports
    fold into the static mask — differential across both engines."""
    nodes = _nodes(4)
    existing = {"metadata": {"name": "squatter", "namespace": "default"},
                "spec": {"nodeName": "n000",
                         "containers": [{"name": "c",
                                         "resources": {"requests": {
                                             "cpu": "100m"}},
                                         "ports": [{"hostPort": 8080,
                                                    "hostIP": "10.0.0.1"}]}]}}
    snap = ClusterSnapshot.from_objects(nodes, [existing])
    ts = [_port_template("w", 300, 8080),     # 0.0.0.0 → clashes with n000
          _template("p", 500)]
    prof = SchedulerProfile.parity()
    ref = sweep_interleaved(snap, ts, prof)
    got = il.solve_interleaved_tensor(snap, ts, prof)
    _assert_same(ref, got, "ports-wildcard")
    assert ref[0].placed_count == 3           # n000 statically blocked


def test_host_ports_with_preemption_rebuild():
    """A priority-500 port template must EVICT an existing priority-0
    squatter holding its port, forcing the eviction rebuild: surviving
    clones' ports re-bake into the static mask and tpl_placed restarts at
    zero — both engines agree through the whole sequence, and the
    preemption genuinely fires (the template ends with BOTH nodes)."""
    nodes = _nodes(2, pods=3)
    squatter = {"metadata": {"name": "squat", "namespace": "default"},
                "spec": {"nodeName": "n000", "priority": 0,
                         "containers": [{"name": "c",
                                         "resources": {"requests": {
                                             "cpu": "100m"}},
                                         "ports": [{"hostPort": 7070}]}]}}
    snap = ClusterSnapshot.from_objects(
        nodes, [squatter],
        priority_classes=[{"metadata": {"name": "high"}, "value": 500}])
    hi = _port_template("hi", 300, 7070)
    hi["spec"]["priorityClassName"] = "high"
    hi["spec"]["priority"] = 500
    free = _template("free", 400)
    prof = SchedulerProfile.parity()
    ref = sweep_interleaved(snap, [hi, free], prof)
    got = il.solve_interleaved_tensor(snap, [hi, free], prof)
    _assert_same(ref, got, "ports-preempt")
    # n000 starts port-blocked by the squatter; placing there requires the
    # eviction — 2 clones means the preemption+rebuild actually ran
    assert ref[0].placed_count == 2
    assert sorted(ref[0].placements) == [0, 1]


# --------------------------------------------------------------------------
# inline-disk and RWOP self-conflicts on the tensor engine (r5)
# --------------------------------------------------------------------------

def test_inline_disk_self_conflict_native():
    """An inline GCE-PD template places at most one clone per node (disk
    self-conflict) while a plain template fills the rest — both engines
    agree on placements and the disk FitError."""
    snap = ClusterSnapshot.from_objects(_nodes(4))
    disk = _template("d", 300)
    disk["spec"]["volumes"] = [
        {"name": "v", "gcePersistentDisk": {"pdName": "pd-1"}}]
    plain = _template("p", 500)
    prof = SchedulerProfile.parity()
    ref = sweep_interleaved(snap, [disk, plain], prof)
    got = il.solve_interleaved_tensor(snap, [disk, plain], prof)
    _assert_same(ref, got, "disk-self")
    assert got is not None                    # ran natively, no fallback
    assert ref[0].placed_count == 4           # one per node
    assert sorted(ref[0].placements) == [0, 1, 2, 3]
    assert "no available disk" in ref[0].fail_message


def test_rwop_single_clone_native():
    """A ReadWriteOncePod-claim template binds exactly ONE clone cluster-
    wide; its park carries the RWOP reason; the plain template interleaves
    unaffected."""
    pvcs = [{"metadata": {"name": "exclusive", "namespace": "default"},
             "spec": {"accessModes": ["ReadWriteOncePod"],
                      "volumeName": "vol1"}}]
    pvs = [{"metadata": {"name": "vol1"},
            "spec": {"accessModes": ["ReadWriteOncePod"]}}]
    snap = ClusterSnapshot.from_objects(_nodes(3), pvcs=pvcs, pvs=pvs)
    rwop = _template("r", 300)
    rwop["spec"]["volumes"] = [
        {"name": "v", "persistentVolumeClaim": {"claimName": "exclusive"}}]
    plain = _template("p", 500)
    prof = SchedulerProfile.parity()
    ref = sweep_interleaved(snap, [rwop, plain], prof)
    got = il.solve_interleaved_tensor(snap, [rwop, plain], prof)
    _assert_same(ref, got, "rwop")
    assert got is not None
    assert ref[0].placed_count == 1
    assert "ReadWriteOncePod" in ref[0].fail_message


def test_disk_rwop_port_mix_with_spread():
    """All three native gates plus a spread template racing through one
    cluster — full differential."""
    snap = ClusterSnapshot.from_objects(_nodes(6))
    disk = _template("d", 250)
    disk["spec"]["volumes"] = [
        {"name": "v", "gcePersistentDisk": {"pdName": "pd-x"}}]
    port = _port_template("q", 250, 8080)
    spread = _template("s", 250, spread=(1, "topology.kubernetes.io/zone",
                                         {"app": "s"}))
    plain = _template("p", 400)
    prof = SchedulerProfile.parity()
    ts = [disk, port, spread, plain]
    ref = sweep_interleaved(snap, ts, prof)
    got = il.solve_interleaved_tensor(snap, ts, prof)
    _assert_same(ref, got, "mix-gates")
    assert got is not None


def test_disk_self_conflict_through_preemption_rebuild():
    """A disk template's clone survives an eviction rebuild: its node must
    stay blocked (the clone's inline disk re-bakes into the static mask)
    while the eviction frees capacity elsewhere — differential through the
    whole preempt + rebuild sequence."""
    nodes = _nodes(2, pods=2)
    squatter = {"metadata": {"name": "squat", "namespace": "default"},
                "spec": {"nodeName": "n000", "priority": 0,
                         "containers": [{"name": "c", "resources": {
                             "requests": {"cpu": "1500m"}}}]}}
    snap = ClusterSnapshot.from_objects(
        nodes, [squatter],
        priority_classes=[{"metadata": {"name": "high"}, "value": 500}])
    disk = _template("d", 200)
    disk["spec"]["volumes"] = [
        {"name": "v", "gcePersistentDisk": {"pdName": "pd-1"}}]
    hi = _template("hi", 1500)
    hi["spec"]["priorityClassName"] = "high"
    hi["spec"]["priority"] = 500
    prof = SchedulerProfile.parity()
    ref = sweep_interleaved(snap, [disk, hi], prof)
    got = il.solve_interleaved_tensor(snap, [disk, hi], prof)
    _assert_same(ref, got, "disk-preempt")
    assert ref[0].placed_count >= 1          # the disk template placed
    assert len(set(ref[0].placements)) == ref[0].placed_count  # 1/node max
    assert 0 in ref[1].placements            # the eviction freed n000


def test_rwop_with_preemption_falls_back():
    """RWOP + possible preemption keeps the object path (the tensor gate
    rides bind-ever counts, which evictions must not freeze) — and the
    object path re-places an evicted RWOP clone."""
    pvcs = [{"metadata": {"name": "exclusive", "namespace": "default"},
             "spec": {"accessModes": ["ReadWriteOncePod"],
                      "volumeName": "vol1"}}]
    pvs = [{"metadata": {"name": "vol1"},
            "spec": {"accessModes": ["ReadWriteOncePod"]}}]
    snap = ClusterSnapshot.from_objects(
        _nodes(2, pods=2), pvcs=pvcs, pvs=pvs,
        priority_classes=[{"metadata": {"name": "high"}, "value": 500}])
    rwop = _template("r", 100)
    rwop["spec"]["volumes"] = [
        {"name": "v", "persistentVolumeClaim": {"claimName": "exclusive"}}]
    rwop["spec"]["priority"] = 0
    hi = _template("hi", 1800)
    hi["spec"]["priorityClassName"] = "high"
    hi["spec"]["priority"] = 500
    prof = SchedulerProfile.parity()
    assert il.solve_interleaved_tensor(snap, [rwop, hi], prof) is None
    res = il.sweep_interleaved_auto(snap, [rwop, hi], prof)
    assert res[0].placed_count >= 1

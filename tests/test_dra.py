"""DynamicResources (DRA) reduced model (ops/dynamic_resources.py): device
pools from ResourceSlices, per-clone claim templates, shared-claim
colocation, missing-object pod-level failures."""

import pytest

from cluster_capacity_tpu import ClusterCapacity, SchedulerProfile
from cluster_capacity_tpu.models.podspec import default_pod

from helpers import build_test_node, build_test_pod


def _slice(node, n_devices, cls="gpu.example.com"):
    return {"metadata": {"name": f"slice-{node}"},
            "spec": {"nodeName": node, "driver": cls,
                     "devices": [{"name": f"dev{i}",
                                  "deviceClassName": cls}
                                 for i in range(n_devices)]}}


def _claim_template(name, count=1, cls="gpu.example.com"):
    return {"metadata": {"name": name, "namespace": "default"},
            "spec": {"spec": {"devices": {"requests": [
                {"name": "r0", "deviceClassName": cls, "count": count}]}}}}


def _pod_with_template_claim(name, claim_tmpl):
    pod = build_test_pod(name, 100, 0)
    pod["spec"]["resourceClaims"] = [
        {"name": "gpu", "resourceClaimTemplateName": claim_tmpl}]
    return pod


def test_device_capacity_bounds_placements():
    nodes = [build_test_node("n1", 100000, int(1e11), 500),
             build_test_node("n2", 100000, int(1e11), 500)]
    slices = [_slice("n1", 4), _slice("n2", 2)]
    tmpl = _claim_template("one-gpu", count=1)
    cc = ClusterCapacity(default_pod(_pod_with_template_claim("p", "one-gpu")),
                         profile=SchedulerProfile.parity())
    cc.sync_with_objects(nodes, resource_slices=slices,
                         resource_claim_templates=[tmpl])
    res = cc.run()
    assert res.placed_count == 6
    assert res.per_node_counts == {"n1": 4, "n2": 2}
    assert res.fail_counts.get("cannot allocate all claims") == 2


def test_multi_device_claims():
    nodes = [build_test_node("n1", 100000, int(1e11), 500)]
    slices = [_slice("n1", 5)]
    tmpl = _claim_template("two-gpus", count=2)
    cc = ClusterCapacity(default_pod(_pod_with_template_claim("p", "two-gpus")),
                         profile=SchedulerProfile.parity())
    cc.sync_with_objects(nodes, resource_slices=slices,
                         resource_claim_templates=[tmpl])
    res = cc.run()
    assert res.placed_count == 2   # 5 devices / 2 per pod


def test_existing_pod_devices_counted():
    nodes = [build_test_node("n1", 100000, int(1e11), 500)]
    slices = [_slice("n1", 3)]
    tmpl = _claim_template("one-gpu", count=1)
    existing = _pod_with_template_claim("existing", "one-gpu")
    existing["spec"]["nodeName"] = "n1"
    cc = ClusterCapacity(default_pod(_pod_with_template_claim("p", "one-gpu")),
                         profile=SchedulerProfile.parity())
    cc.sync_with_objects(nodes, [existing], resource_slices=slices,
                         resource_claim_templates=[tmpl])
    res = cc.run()
    assert res.placed_count == 2   # 3 devices - 1 in use


def test_shared_claim_colocates():
    nodes = [build_test_node("n1", 100000, int(1e11), 500),
             build_test_node("n2", 100000, int(1e11), 500)]
    slices = [_slice("n1", 8), _slice("n2", 8)]
    claim = {"metadata": {"name": "shared", "namespace": "default"},
             "spec": {"devices": {"requests": [
                 {"name": "r0", "deviceClassName": "gpu.example.com",
                  "count": 1}]}}}
    pod = build_test_pod("p", 100, 0)
    pod["spec"]["resourceClaims"] = [{"name": "gpu",
                                      "resourceClaimName": "shared"}]
    cc = ClusterCapacity(default_pod(pod), max_limit=6,
                         profile=SchedulerProfile.parity())
    cc.sync_with_objects(nodes, resource_slices=slices,
                         resource_claims=[claim])
    res = cc.run()
    assert res.placed_count == 6
    assert len(res.per_node_counts) == 1   # all share one allocation node


def test_missing_claim_pod_level():
    nodes = [build_test_node("n1", 1000, int(1e9), 10)]
    pod = build_test_pod("p", 100, 0)
    pod["spec"]["resourceClaims"] = [{"name": "gpu",
                                      "resourceClaimName": "ghost"}]
    cc = ClusterCapacity(default_pod(pod), profile=SchedulerProfile.parity())
    cc.sync_with_objects(nodes, resource_slices=[_slice("n1", 1)])
    res = cc.run()
    assert res.placed_count == 0
    assert 'resourceclaim "ghost" not found' in res.fail_message


def test_shared_claim_devices_charged_once():
    """An unallocated shared claim allocates once: capacity is bounded by pod
    slots / cpu, not devices-per-clone."""
    nodes = [build_test_node("n1", 1000, int(1e11), 500)]
    slices = [_slice("n1", 1)]     # ONE device
    claim = {"metadata": {"name": "shared", "namespace": "default"},
             "spec": {"devices": {"requests": [
                 {"name": "r0", "deviceClassName": "gpu.example.com",
                  "count": 1}]}}}
    pod = build_test_pod("p", 100, 0)
    pod["spec"]["resourceClaims"] = [{"name": "gpu",
                                      "resourceClaimName": "shared"}]
    cc = ClusterCapacity(default_pod(pod), profile=SchedulerProfile.parity())
    cc.sync_with_objects(nodes, resource_slices=slices,
                         resource_claims=[claim])
    res = cc.run()
    # 10 x 100m cpu bound, NOT 1 (the single device serves all users)
    assert res.placed_count == 10


def test_allocated_claim_pins_to_node():
    nodes = [build_test_node("n1", 100000, int(1e11), 500,
                             labels={"kubernetes.io/hostname": "n1"}),
             build_test_node("n2", 100000, int(1e11), 500,
                             labels={"kubernetes.io/hostname": "n2"})]
    slices = [_slice("n1", 8), _slice("n2", 8)]
    claim = {"metadata": {"name": "pinned", "namespace": "default"},
             "spec": {"devices": {"requests": [
                 {"name": "r0", "deviceClassName": "gpu.example.com",
                  "count": 2}]}},
             "status": {"allocation": {"nodeSelector": {
                 "nodeSelectorTerms": [{"matchExpressions": [
                     {"key": "kubernetes.io/hostname", "operator": "In",
                      "values": ["n2"]}]}]}}}}
    pod = build_test_pod("p", 100, 0)
    pod["spec"]["resourceClaims"] = [{"name": "gpu",
                                      "resourceClaimName": "pinned"}]
    cc = ClusterCapacity(default_pod(pod), max_limit=4,
                         profile=SchedulerProfile.parity())
    cc.sync_with_objects(nodes, resource_slices=slices,
                         resource_claims=[claim])
    res = cc.run()
    assert res.placed_count == 4
    assert set(res.per_node_counts) == {"n2"}


def test_unpublished_device_class_unschedulable():
    nodes = [build_test_node("n1", 1000, int(1e9), 10)]
    tmpl = _claim_template("exotic", cls="tpu.example.com")
    cc = ClusterCapacity(default_pod(_pod_with_template_claim("p", "exotic")),
                         profile=SchedulerProfile.parity())
    cc.sync_with_objects(nodes, resource_slices=[_slice("n1", 2)],
                         resource_claim_templates=[tmpl])
    res = cc.run()
    assert res.placed_count == 0
    assert "cannot allocate all claims" in res.fail_message


# --- structured allocation: CEL selectors / admin access / partitions ------

def _attr_slice(node, devices, driver="gpu.example.com", counters=None):
    """devices: list of dicts {name, attributes, capacity, consumesCounters}."""
    spec = {"nodeName": node, "driver": driver,
            "devices": [dict(d, deviceClassName=d.get("deviceClassName",
                                                      driver))
                        for d in devices]}
    if counters:
        spec["sharedCounters"] = counters
    return {"metadata": {"name": f"slice-{node}"}, "spec": spec}


def _sel_template(name, expr=None, count=1, admin=False, mode=None,
                  cls="gpu.example.com"):
    req = {"name": "r0", "deviceClassName": cls, "count": count}
    if expr:
        req["selectors"] = [{"cel": {"expression": expr}}]
    if admin:
        req["adminAccess"] = True
    if mode:
        req["allocationMode"] = mode
    return {"metadata": {"name": name, "namespace": "default"},
            "spec": {"spec": {"devices": {"requests": [req]}}}}


def _run_dra(pod, nodes, **extra):
    cc = ClusterCapacity(default_pod(pod), profile=SchedulerProfile.parity())
    cc.sync_with_objects(nodes, **extra)
    return cc.run()


def test_cel_selector_narrows_devices():
    """device.attributes CEL selector: only a100 devices satisfy the claim
    (dynamicresources.go:898 + structured allocator)."""
    nodes = [build_test_node("n1", 100000, int(1e11), 500)]
    devices = [
        {"name": "d0", "attributes": {"gpu.example.com/model": {"string": "a100"}}},
        {"name": "d1", "attributes": {"gpu.example.com/model": {"string": "a100"}}},
        {"name": "d2", "attributes": {"gpu.example.com/model": {"string": "t4"}}},
    ]
    tmpl = _sel_template(
        "a100", expr='device.attributes["gpu.example.com"].model == "a100"')
    res = _run_dra(_pod_with_template_claim("p", "a100"), nodes,
                   resource_slices=[_attr_slice("n1", devices)],
                   resource_claim_templates=[tmpl])
    assert res.placed_count == 2          # only the two a100s
    assert res.fail_counts.get("cannot allocate all claims") == 1


def test_cel_capacity_comparison():
    nodes = [build_test_node("n1", 100000, int(1e11), 500)]
    devices = [
        {"name": "d0", "capacity": {"gpu.example.com/memory": "40Gi"}},
        {"name": "d1", "capacity": {"gpu.example.com/memory": "16Gi"}},
    ]
    tmpl = _sel_template(
        "big", expr='device.capacity["gpu.example.com"].memory >= 34359738368')
    res = _run_dra(_pod_with_template_claim("p", "big"), nodes,
                   resource_slices=[_attr_slice("n1", devices)],
                   resource_claim_templates=[tmpl])
    assert res.placed_count == 1


def test_admin_access_does_not_consume():
    """adminAccess requests require the device to exist but never consume
    it — unlimited monitoring pods."""
    nodes = [build_test_node("n1", 100000, int(1e11), 500),
             build_test_node("n2", 100000, int(1e11), 500)]
    devices = [{"name": "d0"}]
    tmpl = _sel_template("mon", admin=True)
    cc = ClusterCapacity(default_pod(_pod_with_template_claim("p", "mon")),
                         max_limit=7, profile=SchedulerProfile.parity())
    cc.sync_with_objects(nodes, resource_slices=[_attr_slice("n1", devices)],
                         resource_claim_templates=[tmpl])
    res = cc.run()
    assert res.placed_count == 7
    assert set(res.per_node_counts) == {"n1"}   # n2 publishes no device


def test_partitionable_devices_share_counters():
    """Partitions consume sharedCounters: two half-partitions exhaust the
    pool even though four partition devices are published."""
    nodes = [build_test_node("n1", 100000, int(1e11), 500)]
    devices = [
        {"name": f"p{i}",
         "consumesCounters": [{"counterSet": "gpu0",
                               "counters": {"memory": {"value": "20Gi"}}}]}
        for i in range(4)
    ]
    counters = [{"name": "gpu0", "counters": {"memory": {"value": "40Gi"}}}]
    tmpl = _sel_template("part", count=1)
    res = _run_dra(_pod_with_template_claim("p", "part"), nodes,
                   resource_slices=[_attr_slice("n1", devices,
                                                counters=counters)],
                   resource_claim_templates=[tmpl])
    assert res.placed_count == 2          # 40Gi pool / 20Gi per partition
    assert res.fail_counts.get("cannot allocate all claims") == 1


def test_allocation_mode_all():
    """All-mode claims take every matching device: exactly one clone."""
    nodes = [build_test_node("n1", 100000, int(1e11), 500)]
    devices = [{"name": f"d{i}"} for i in range(3)]
    tmpl = _sel_template("all", mode="All")
    res = _run_dra(_pod_with_template_claim("p", "all"), nodes,
                   resource_slices=[_attr_slice("n1", devices)],
                   resource_claim_templates=[tmpl])
    assert res.placed_count == 1


def test_device_class_selectors_apply():
    """DeviceClass.spec.selectors narrow devices for every claim of the
    class (the class's CEL runs before the claim's)."""
    nodes = [build_test_node("n1", 100000, int(1e11), 500)]
    devices = [
        {"name": "d0", "attributes": {"gpu.example.com/tier": {"string": "prod"}}},
        {"name": "d1", "attributes": {"gpu.example.com/tier": {"string": "dev"}}},
    ]
    dc = {"metadata": {"name": "gpu.example.com"},
          "spec": {"selectors": [{"cel": {"expression":
              'device.attributes["gpu.example.com"].tier == "prod"'}}]}}
    tmpl = _sel_template("any", count=1)
    res = _run_dra(_pod_with_template_claim("p", "any"), nodes,
                   resource_slices=[_attr_slice("n1", devices)],
                   resource_claim_templates=[tmpl], device_classes=[dc])
    assert res.placed_count == 1          # only the prod device


def test_cel_string_literal_true_not_mangled():
    """Regression: a selector comparing to the STRING "true" must not be
    rewritten to the boolean literal."""
    nodes = [build_test_node("n1", 100000, int(1e11), 500)]
    devices = [{"name": "d0",
                "attributes": {"gpu.example.com/sriov": {"string": "true"}}}]
    tmpl = _sel_template(
        "sriov", expr='device.attributes["gpu.example.com"].sriov == "true"')
    res = _run_dra(_pod_with_template_claim("p", "sriov"), nodes,
                   resource_slices=[_attr_slice("n1", devices)],
                   resource_claim_templates=[tmpl])
    assert res.placed_count == 1


def test_allocation_mode_all_requires_a_device():
    """Regression: All-mode with zero matching devices must be infeasible
    (resource/v1 types.go: at least one device must exist)."""
    nodes = [build_test_node("n1", 100000, int(1e11), 500)]
    devices = [{"name": "d0",
                "attributes": {"gpu.example.com/model": {"string": "t4"}}}]
    tmpl = _sel_template(
        "all-a100", mode="All",
        expr='device.attributes["gpu.example.com"].model == "a100"')
    res = _run_dra(_pod_with_template_claim("p", "all-a100"), nodes,
                   resource_slices=[_attr_slice("n1", devices)],
                   resource_claim_templates=[tmpl])
    assert res.placed_count == 0
    assert "cannot allocate all claims" in res.fail_message


# --- CEL sandbox hardening (advisor r2) ------------------------------------

def _mem_device(mem):
    from cluster_capacity_tpu.ops.dynamic_resources import Device
    return Device(name="d", device_class="gpu.example.com",
                  driver="gpu.example.com",
                  capacity={"gpu.example.com": {"memory": mem}})


def test_cel_literal_arithmetic_rejected():
    """A hostile selector must not allocate unbounded memory: CEL has no
    repetition operator, so 'X * 10**9' over a list/string is a TYPE error
    (→ non-match) in the tree-walking evaluator — never an allocation."""
    from cluster_capacity_tpu.ops.dynamic_resources import cel_matches
    dev = _mem_device(4)
    assert cel_matches("[0] * 1000000000 == []", dev) is False
    # list CONCATENATION is real CEL (bounded by expression length)
    assert cel_matches("[0, 1] + [2] == [0, 1, 2]", dev) is True
    assert cel_matches('"a" * 1000000000 == ""', dev) is False
    # nested: the hostile operand hides one arithmetic node down
    assert cel_matches("([0] * 2) * 1000000000 == []", dev) is False
    # device-SOURCED strings must not reach arithmetic either
    assert cel_matches('device.driver * 1000000000 != ""', dev) is False
    assert cel_matches('device.driver[0] * 1000000000 != ""', dev) is False
    # subscripted/bool-op containers must not smuggle strs or lists into
    # arithmetic ('or' over strings is itself a CEL type error)
    assert cel_matches('["a"][0] * 1000000000 != ""', dev) is False
    assert cel_matches('[[0]][0] * 1000000000 != []', dev) is False
    assert cel_matches('("a" or "b") * 1000000000 != ""', dev) is False
    dev2 = _mem_device(4)
    dev2.attributes = {"gpu.example.com": {"model": "a100"}}
    assert cel_matches(
        'device.attributes["gpu.example.com"].model * 1000000000 != ""',
        dev2) is False
    # ...while comparisons and `in` over the same strings still work
    assert cel_matches(
        'device.attributes["gpu.example.com"].model == "a100"', dev2) is True
    assert cel_matches(
        'device.attributes["gpu.example.com"].model in ["a100", "h100"]',
        dev2) is True


def test_cel_numeric_arithmetic_still_works():
    from cluster_capacity_tpu.ops.dynamic_resources import cel_matches
    dev = _mem_device(4)
    assert cel_matches(
        'device.capacity["gpu.example.com"].memory + 1 >= 5', dev) is True
    assert cel_matches(
        'device.capacity["gpu.example.com"].memory * 2 == 8', dev) is True


def test_cel_division_truncates_toward_zero():
    """CEL / and % truncate toward zero (cel-spec int arithmetic); Python
    floors — the evaluator must implement the CEL behavior."""
    from cluster_capacity_tpu.ops.dynamic_resources import cel_matches
    dev = _mem_device(4)
    assert cel_matches(
        'device.capacity["gpu.example.com"].memory / 2 >= 1', dev) is True
    assert cel_matches(
        'device.capacity["gpu.example.com"].memory % 3 == 1', dev) is True
    # negative operands: CEL -7/2 == -3 (Python floors to -4) and
    # -7 % 2 == -1 (Python gives +1)
    assert cel_matches("(0 - 7) / 2 == 0 - 3", dev) is True
    assert cel_matches("(0 - 7) % 2 == 0 - 1", dev) is True
    assert cel_matches("-7 / 2 == -3", dev) is True
    # division by zero is a CEL error -> non-match
    assert cel_matches("1 / 0 == 0", dev) is False


def test_cel_string_indexing_non_matching():
    """CEL has no string index operator; the reference's CEL runtime
    errors and the device is non-matching."""
    from cluster_capacity_tpu.ops.dynamic_resources import cel_matches
    dev = _mem_device(4)
    assert cel_matches('device.driver[0] == "g"', dev) is False


def test_cel_bignum_attribute_non_matching():
    """Cluster-sourced ints outside CEL's int64 range are a CEL error
    (non-match) — and refusing them stops bignum arithmetic
    amplification."""
    from cluster_capacity_tpu.ops.dynamic_resources import cel_matches
    dev = _mem_device(10 ** 100)
    assert cel_matches(
        'device.capacity["gpu.example.com"].memory >= 1', dev) is False
    ok = _mem_device(2 ** 62)
    assert cel_matches(
        'device.capacity["gpu.example.com"].memory >= 1', ok) is True


def test_cel_list_attribute_non_matching():
    """A hostile slice smuggling a LIST-typed attribute value must not
    reach arithmetic ('attr * 10**9' would allocate gigabytes); CEL has
    no list attribute type, so it is a type error → non-match."""
    from cluster_capacity_tpu.ops.dynamic_resources import cel_matches
    dev = _mem_device(4)
    dev.attributes = {"gpu.example.com": {"l": ["a", "b"]}}
    assert cel_matches(
        'device.attributes["gpu.example.com"].l * 1000000000 == []',
        dev) is False
    assert cel_matches(
        'device.attributes["gpu.example.com"].l == ["a", "b"]', dev) is False


def test_cel_expression_length_capped():
    from cluster_capacity_tpu.ops.dynamic_resources import cel_matches
    dev = _mem_device(4)
    assert cel_matches("1 == 1" + " && 1 == 1" * 2000, dev) is False


def test_counter_pool_count_matches_linear_probe():
    """With shared counters, the slot count must equal the best feasible k
    from a direct downward scan.  Through r4 this fixture answered 2 (the
    greedy lower bound: first-fit grabs the 30Gi partition and strands the
    pool); the r5 exact backtracking allocator finds the true 4 x 10Gi
    assignment."""
    from cluster_capacity_tpu.ops.dynamic_resources import _fits_k_clones
    nodes = [build_test_node("n1", 100000, int(1e11), 500)]
    # heterogeneous partitions: big ones starve the pool for later clones
    devices = [
        {"name": f"p{i}",
         "consumesCounters": [{"counterSet": "gpu0",
                               "counters": {"memory": {"value": v}}}]}
        for i, v in enumerate(["30Gi", "10Gi", "10Gi", "10Gi", "10Gi"])
    ]
    counters = [{"name": "gpu0", "counters": {"memory": {"value": "40Gi"}}}]
    tmpl = _sel_template("part", count=1)
    res = _run_dra(_pod_with_template_claim("p", "part"), nodes,
                   resource_slices=[_attr_slice("n1", devices,
                                                counters=counters)],
                   resource_claim_templates=[tmpl])
    gi = 1024 ** 3
    consumes = [{("gpu0", "memory"): 30 * gi}] + \
        [{("gpu0", "memory"): 10 * gi}] * 4
    pools = {("gpu0", "memory"): 40 * gi}
    units = [[0, 1, 2, 3, 4]]
    best = 0
    for k in range(5, 0, -1):
        if _fits_k_clones(k, units, 5, consumes, pools):
            best = k
            break
    assert best == 4
    assert res.placed_count == best


def _shared_claim(name="shared", expr=None, count=1, mode=None,
                  cls="gpu.example.com"):
    req = {"name": "r0", "deviceClassName": cls, "count": count}
    if expr:
        req["selectors"] = [{"cel": {"expression": expr}}]
    if mode:
        req["allocationMode"] = mode
    return {"metadata": {"name": name, "namespace": "default"},
            "spec": {"devices": {"requests": [req]}}}


def _pod_with_shared_claim(name, claim="shared"):
    pod = build_test_pod(name, 100, 0)
    pod["spec"]["resourceClaims"] = [{"name": "gpu",
                                      "resourceClaimName": claim}]
    return pod


def test_shared_claim_with_cel_selector_structured():
    """A shared named claim WITH a CEL selector must run the structured
    allocator (it used to degrade to count-based matching):
    only the node whose devices match the selector can host the one
    allocation; all clones colocate there."""
    nodes = [build_test_node("n1", 100000, int(1e11), 500),
             build_test_node("n2", 100000, int(1e11), 500)]
    a100s = [{"name": f"d{i}", "attributes": {
        "gpu.example.com/model": {"string": "a100"}}} for i in range(2)]
    t4s = [{"name": f"d{i}", "attributes": {
        "gpu.example.com/model": {"string": "t4"}}} for i in range(2)]
    claim = _shared_claim(
        expr='device.attributes["gpu.example.com"].model == "a100"',
        count=2)
    cc = ClusterCapacity(default_pod(_pod_with_shared_claim("p")),
                         max_limit=5, profile=SchedulerProfile.parity())
    cc.sync_with_objects(
        nodes, resource_slices=[_attr_slice("n1", a100s),
                                _attr_slice("n2", t4s)],
        resource_claims=[claim])
    res = cc.run()
    # count-based degrade would accept n2's two t4s; structured must not
    assert res.placed_count == 5
    assert set(res.per_node_counts) == {"n1"}


def test_shared_claim_selector_no_matching_node():
    nodes = [build_test_node("n1", 100000, int(1e11), 500)]
    t4s = [{"name": "d0", "attributes": {
        "gpu.example.com/model": {"string": "t4"}}}]
    claim = _shared_claim(
        expr='device.attributes["gpu.example.com"].model == "a100"')
    cc = ClusterCapacity(default_pod(_pod_with_shared_claim("p")),
                         profile=SchedulerProfile.parity())
    cc.sync_with_objects(nodes, resource_slices=[_attr_slice("n1", t4s)],
                         resource_claims=[claim])
    res = cc.run()
    assert res.placed_count == 0
    assert res.fail_counts.get("cannot allocate all claims") == 1


def test_shared_structured_claim_plus_template_claim():
    """Shared structured claim + per-clone template claim share one device
    pool: the shared allocation reserves its devices first, per-clone
    slots come from the remainder."""
    nodes = [build_test_node("n1", 100000, int(1e11), 500)]
    devs = [{"name": f"d{i}", "attributes": {
        "gpu.example.com/model": {"string": "a100"}}} for i in range(4)]
    claim = _shared_claim(
        expr='device.attributes["gpu.example.com"].model == "a100"')
    tmpl = _sel_template(
        "clone-gpu",
        expr='device.attributes["gpu.example.com"].model == "a100"')
    pod = build_test_pod("p", 100, 0)
    pod["spec"]["resourceClaims"] = [
        {"name": "shared-gpu", "resourceClaimName": "shared"},
        {"name": "own-gpu", "resourceClaimTemplateName": "clone-gpu"}]
    cc = ClusterCapacity(default_pod(pod),
                         profile=SchedulerProfile.parity())
    cc.sync_with_objects(nodes, resource_slices=[_attr_slice("n1", devs)],
                         resource_claims=[claim],
                         resource_claim_templates=[tmpl])
    res = cc.run()
    # 4 matching devices: 1 reserved by the shared allocation -> 3 clones
    assert res.placed_count == 3
    assert res.fail_counts.get("cannot allocate all claims") == 1


# --- sharedCounters exactness (r5: backtracking replaces the greedy bound) -

def test_partitionable_greedy_stranding_exact():
    """The canonical greedy-failure family: first-fit hands
    the counter-hungry partition to the first clone and strands the pool.
    Pool 20Gi; partitions big{20Gi}, small1{10Gi}, small2{10Gi}: greedy
    takes `big` (device order) and answers 1 clone — the exact backtracking
    search allocates small1+small2 for the true maximum of 2."""
    nodes = [build_test_node("n1", 100000, int(1e11), 500)]
    devices = [
        {"name": "big",
         "consumesCounters": [{"counterSet": "gpu0",
                               "counters": {"memory": {"value": "20Gi"}}}]},
        {"name": "small1",
         "consumesCounters": [{"counterSet": "gpu0",
                               "counters": {"memory": {"value": "10Gi"}}}]},
        {"name": "small2",
         "consumesCounters": [{"counterSet": "gpu0",
                               "counters": {"memory": {"value": "10Gi"}}}]},
    ]
    counters = [{"name": "gpu0", "counters": {"memory": {"value": "20Gi"}}}]
    tmpl = _sel_template("part", count=1)
    res = _run_dra(_pod_with_template_claim("p", "part"), nodes,
                   resource_slices=[_attr_slice("n1", devices,
                                                counters=counters)],
                   resource_claim_templates=[tmpl])
    assert res.placed_count == 2
    assert res.fail_counts.get("cannot allocate all claims") == 1


def _brute_max_clones(units_per_clone, consumes, pools, n_devices):
    """Exhaustive oracle: max k such that k clones' units all get distinct
    eligible devices under the counter pools."""
    from itertools import permutations

    def feasible(units):
        u = len(units)
        if u > n_devices:
            return False
        for perm in permutations(range(n_devices), u):
            if any(perm[i] not in units[i] for i in range(u)):
                continue
            rem = dict(pools)
            ok = True
            for d in perm:
                for key, v in consumes[d].items():
                    rem[key] = rem.get(key, 0) - v
                    if rem[key] < -1e-9:
                        ok = False
                        break
                if not ok:
                    break
            if ok:
                return True
        return False

    k = 0
    while k < n_devices and feasible(units_per_clone * (k + 1)):
        k += 1
    return k


@pytest.mark.parametrize("seed", range(40))
def test_fits_k_clones_exact_vs_bruteforce(seed):
    """Random partitionable-device configs: the binary search over
    _fits_k_clones (greedy fast-accept + backtracking settle) must equal
    the exhaustive oracle."""
    import numpy as np
    from cluster_capacity_tpu.ops import dynamic_resources as dra

    rng = np.random.RandomState(8000 + seed)
    n_dev = int(rng.randint(1, 6))
    pools = {("s", "c0"): int(rng.randint(0, 5))}
    if rng.rand() < 0.5:
        pools[("s", "c1")] = int(rng.randint(0, 5))
    consumes = []
    for _ in range(n_dev):
        c = {}
        for key in pools:
            if rng.rand() < 0.7:
                c[key] = int(rng.randint(0, 4))
        consumes.append(c)
    n_units = int(rng.randint(1, 3))
    units = [[d for d in range(n_dev) if rng.rand() < 0.8]
             for _ in range(n_units)]

    cap = n_dev // max(1, n_units)
    lo, hi = 0, cap
    while lo < hi:
        mid = (lo + hi + 1) // 2
        if dra._fits_k_clones(mid, units, n_dev, consumes, pools):
            lo = mid
        else:
            hi = mid - 1
    brute = _brute_max_clones([set(u) for u in units], consumes, pools,
                              n_dev)
    assert lo == brute, (seed, units, consumes, pools)


def test_shared_claim_joint_exactness_with_counters():
    """A shared structured claim must be searched JOINTLY with the clone
    units: pool c=2 with devices A{c:2}, B{c:1}, C{c:1} — a greedy shared
    reservation takes A and drains the pool (0 clones); the joint
    backtracking places the shared claim on B and one clone on C."""
    nodes = [build_test_node("n1", 100000, int(1e11), 500)]
    devices = [
        {"name": "A",
         "consumesCounters": [{"counterSet": "s",
                               "counters": {"c": {"value": "2"}}}]},
        {"name": "B",
         "consumesCounters": [{"counterSet": "s",
                               "counters": {"c": {"value": "1"}}}]},
        {"name": "C",
         "consumesCounters": [{"counterSet": "s",
                               "counters": {"c": {"value": "1"}}}]},
    ]
    counters = [{"name": "s", "counters": {"c": {"value": "2"}}}]
    claim = _shared_claim()
    tmpl = _sel_template("clone-dev")
    pod = build_test_pod("p", 100, 0)
    pod["spec"]["resourceClaims"] = [
        {"name": "shared-dev", "resourceClaimName": "shared"},
        {"name": "own-dev", "resourceClaimTemplateName": "clone-dev"}]
    cc = ClusterCapacity(default_pod(pod), profile=SchedulerProfile.parity())
    cc.sync_with_objects(nodes,
                         resource_slices=[_attr_slice("n1", devices,
                                                      counters=counters)],
                         resource_claims=[claim],
                         resource_claim_templates=[tmpl])
    res = cc.run()
    assert res.placed_count == 1

"""irgate gate pins: IR contracts on synthetic jaxprs, cost-model pins,
budget comparison mechanics, the guard-dispatch audit (tree must be clean,
fixtures must be flagged), the chaos × irgate interaction (post-fault rungs
stay contract-clean), and full-gate subprocess runs (the committed
budgets.json must hold on the current tree; a seeded synthetic regression
must fail with the entry, primitive and delta named).

Budget-pinning runs go through a subprocess because conftest.py enables
jax_enable_x64 process-wide, which changes lowered dtypes; the committed
budgets assume the CLI's canonical x64-off CPU environment."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tools.irgate import budgets as budgets_mod
from tools.irgate import capture as cap
from tools.irgate import contracts, costs, entries, guard_audit
from tools.irgate.contracts import Policy

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# subprocess driver
# ---------------------------------------------------------------------------

def _run_gate(*extra, timeout=600):
    env = dict(os.environ)
    for k in ("CC_TPU_FUSED", "CC_INJECT_FAULT", "JAX_ENABLE_X64"):
        env.pop(k, None)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "-m", "tools.irgate", *extra],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=timeout)


@pytest.fixture(scope="module")
def gate(tmp_path_factory):
    """One full-gate run shared by the budget-pinning tests."""
    out = tmp_path_factory.mktemp("irgate") / "report.json"
    proc = _run_gate("--json-out", str(out))
    doc = json.loads(out.read_text()) if out.exists() else None
    return proc, doc


# ---------------------------------------------------------------------------
# full gate: committed budgets hold on the current tree
# ---------------------------------------------------------------------------

def test_gate_clean_on_tree(gate):
    proc, doc = gate
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert doc is not None and doc["clean"] and doc["findings"] == []


def test_all_ladder_rungs_budgeted(gate):
    """Every rung of the PR-4 degradation ladder has a pinned entry."""
    from cluster_capacity_tpu.runtime.degrade import LADDER
    _, doc = gate
    rungs = {e["rung"] for e in doc["entries"].values()}
    assert set(LADDER) <= rungs
    pinned = budgets_mod.load()["entries"]
    assert set(doc["entries"]) == set(pinned)
    for name, delta in doc["budget_delta_pct"].items():
        for metric, pct in delta.items():
            assert pct == 0.0, f"{name}/{metric} drifted {pct}%"


def test_oracle_rung_dispatches_nothing(gate):
    """The host-side refuge rung must not launch device computations."""
    _, doc = gate
    oracle = [e for n, e in doc["entries"].items() if n.startswith("oracle")]
    assert oracle and all(e["primitives"] == 0 and not e["computations"]
                          for e in oracle)


def test_pallas_rungs_captured(gate):
    _, doc = gate
    fused = doc["entries"]["fused/n8"]
    batched = doc["entries"]["fused_batched/n8b3"]
    assert fused["histogram"].get("pallas_call") == 1
    assert batched["histogram"].get("pallas_call") == 1
    for e in doc["entries"].values():
        assert e["histogram"].get("while", 0) == 0


def test_budget_trend_fields(gate):
    """--json-out payload carries the BENCH_*-style trend numbers."""
    _, doc = gate
    scan = doc["entries"]["scan/n8"]
    assert scan["primitives"] > 0 and scan["flops"] > 0 \
        and scan["live_bytes"] > 0
    assert doc["guard_audit"]["findings"] == 0
    assert doc["mosaic"]["findings"] == 0


# ---------------------------------------------------------------------------
# seeded synthetic regressions must fail loudly (subprocess, --only skips
# the canonical ladder for speed)
# ---------------------------------------------------------------------------

def test_seeded_budget_regression_names_entry_and_primitive(tmp_path):
    fixture = tmp_path / "fixture_budget.py"
    fixture.write_text(textwrap.dedent('''\
        """Seeded regression: extra broadcast_in_dim beyond the pin."""


        def make_entries():
            from tools.irgate.entries import EntrySpec

            def driver():
                import jax
                import jax.numpy as jnp

                @jax.jit
                def bloated(x):
                    return jnp.broadcast_to(x, (3, 4, 4)).sum() + x.sum()

                bloated(jnp.ones((4, 4), jnp.float32))

            return [EntrySpec("fixture/bloat", "aux", driver)]


        BUDGETS = {"fixture/bloat": {
            "primitives": 2, "flops": 20, "live_bytes": 64,
            "histogram": {"reduce_sum": 2}}}
    '''))
    proc = _run_gate("--fixture", str(fixture), "--only", "fixture")
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "fixture/bloat" in proc.stdout
    assert "broadcast_in_dim" in proc.stdout      # offending primitive named
    assert "%" in proc.stdout                     # delta named


def test_seeded_f64_cast_fails_contracts(tmp_path):
    fixture = tmp_path / "fixture_f64.py"
    fixture.write_text(textwrap.dedent('''\
        """Seeded regression: an f64 cast in a float32 program."""


        def make_entries():
            from tools.irgate.entries import EntrySpec

            def driver():
                import jax
                import jax.numpy as jnp
                jax.config.update("jax_enable_x64", True)

                @jax.jit
                def widened(x):
                    return x.astype(jnp.float64).sum()

                widened(jnp.ones((4, 4), jnp.float32))

            return [EntrySpec("fixture/f64", "aux", driver)]
    '''))
    proc = _run_gate("--fixture", str(fixture), "--only", "fixture")
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "fixture/f64" in proc.stdout
    assert "IC002" in proc.stdout and "float64" in proc.stdout


# ---------------------------------------------------------------------------
# IR contracts on synthetic computations (in-process)
# ---------------------------------------------------------------------------

@pytest.fixture
def captured_jits():
    """Install the capture patch for a test, restore afterwards."""
    cap.install()
    try:
        yield cap
    finally:
        cap.uninstall()


def _capture_one(fn, *args):
    jitted = jax.jit(fn)
    with cap.capturing() as records:
        jitted(*args)
    assert records, "jit dispatch was not captured"
    return records[-1]


def _rules(findings):
    return {f.rule for f in findings}


def test_ic001_host_callback(captured_jits):
    def leaky(x):
        return jax.pure_callback(
            lambda v: np.asarray(v), jax.ShapeDtypeStruct((4,), x.dtype), x)

    rec = _capture_one(leaky, jnp.ones(4, jnp.float32))
    found = contracts.check_captured("t", rec, Policy(
        check_dtype_flow=False, check_stablehlo=False))
    assert "IC001" in _rules(found)


def test_ic002_f64_cast(captured_jits):
    def widened(x):
        return x.astype(jnp.float64).sum()

    rec = _capture_one(widened, jnp.ones(4, jnp.float32))
    found = contracts.check_captured("t", rec, Policy(check_stablehlo=False))
    assert "IC002" in _rules(found)
    assert any("float64" in f.message for f in found)


def test_ic003_data_dependent_while(captured_jits):
    def dynamic(x):
        return jax.lax.while_loop(lambda v: v[0] < 100.0,
                                  lambda v: v * 2.0, x)

    rec = _capture_one(dynamic, jnp.ones(4, jnp.float32))
    found = contracts.check_captured("t", rec, Policy(
        check_dtype_flow=False, check_stablehlo=False))
    assert "IC003" in _rules(found)

    def static(x):
        return jax.lax.fori_loop(0, 7, lambda i, v: v * 2.0, x)

    rec2 = _capture_one(static, jnp.ones(4, jnp.float32))
    found2 = contracts.check_captured("t", rec2, Policy(
        check_dtype_flow=False, check_stablehlo=False))
    assert "IC003" not in _rules(found2)


def test_ic004_donated_but_unused(captured_jits):
    def ignores_first(a, b):
        return b * 2.0

    jitted = jax.jit(ignores_first, donate_argnums=(0,))
    with cap.capturing() as records:
        jitted(jnp.ones(4, jnp.float32), jnp.ones(4, jnp.float32))
    rec = records[-1]
    found = contracts.check_captured("t", rec, Policy(
        check_dtype_flow=False, check_stablehlo=False))
    assert "IC004" in _rules(found)

    def uses_both(a, b):
        return a + b

    jitted2 = jax.jit(uses_both, donate_argnums=(0,))
    with cap.capturing() as records2:
        jitted2(jnp.ones(4, jnp.float32), jnp.ones(4, jnp.float32))
    found2 = contracts.check_captured("t", records2[-1], Policy(
        check_dtype_flow=False, check_stablehlo=False))
    assert "IC004" not in _rules(found2)


def test_ic005_dtype_flow(captured_jits):
    def f64_input(x):
        return x + 1.0

    rec = _capture_one(f64_input, jnp.ones(4, jnp.float64))
    found = contracts.check_captured("t", rec, Policy(check_stablehlo=False))
    assert "IC005" in _rules(found)


def test_clean_program_passes_contracts(captured_jits):
    def clean(x):
        return (x * 2.0 + 1.0).sum()

    rec = _capture_one(clean, jnp.ones((4, 4), jnp.float32))
    assert contracts.check_captured("t", rec, Policy(
        check_stablehlo=False)) == []


def test_capture_dedup_and_labels(captured_jits):
    def f(x):
        return x + 1.0

    jitted = jax.jit(f)
    with cap.capturing() as records:
        jitted(jnp.ones(4, jnp.float32))
        jitted(jnp.ones(4, jnp.float32))       # same signature → dedup
        jitted(jnp.ones(8, jnp.float32))       # new shape → new key
    uniq = cap.dedup(records)
    assert len(records) == 3 and len(uniq) == 2
    assert all("#" in r.key for r in uniq)


# ---------------------------------------------------------------------------
# cost models
# ---------------------------------------------------------------------------

def test_cost_dot_general_flops():
    m, k, n = 8, 16, 4

    def mm(a, b):
        return a @ b

    closed = jax.make_jaxpr(mm)(jnp.ones((m, k), jnp.float32),
                                jnp.ones((k, n), jnp.float32))
    assert costs.estimate_flops(closed) == 2 * m * n * k
    hist = costs.primitive_histogram(closed)
    assert hist["dot_general"] == 1


def test_cost_scan_multiplies_body_by_length():
    def stepper(x):
        return jax.lax.scan(lambda c, _: (c * 2.0, None), x,
                            None, length=10)[0]

    closed = jax.make_jaxpr(stepper)(jnp.ones(4, jnp.float32))
    # one mul of 4 elements per step × 10 steps
    assert costs.estimate_flops(closed) == 40


def test_cost_peak_live_bytes():
    def f(x):
        y = x * 2.0           # +64B while x (64B) still live
        return y.sum()

    closed = jax.make_jaxpr(f)(jnp.ones((4, 4), jnp.float32))
    peak = costs.peak_live_bytes(closed)
    assert peak >= 2 * 4 * 4 * 4


def test_cost_summary_shape():
    closed = jax.make_jaxpr(lambda x: x + 1.0)(jnp.ones(4, jnp.float32))
    s = costs.cost_summary(closed)
    assert set(s) == {"primitives", "flops", "live_bytes", "histogram"}
    merged = costs.merge_summaries([s, s])
    assert merged["primitives"] == 2 * s["primitives"]


# ---------------------------------------------------------------------------
# budget comparison mechanics
# ---------------------------------------------------------------------------

def _pins(**entries_):
    return {"tolerance_pct": dict(budgets_mod.DEFAULT_TOLERANCE),
            "entries": entries_}


def test_budget_delta_names_primitive():
    pinned = _pins(**{"scan/n8": {
        "primitives": 10, "flops": 100, "live_bytes": 100,
        "histogram": {"broadcast_in_dim": 3, "add": 7}}})
    measured = {"scan/n8": {
        "primitives": 16, "flops": 100, "live_bytes": 100,
        "histogram": {"broadcast_in_dim": 9, "add": 7}}}
    found = budgets_mod.compare(measured, pinned)
    assert len(found) == 1 and found[0].rule == "BG002"
    assert "broadcast_in_dim +6" in found[0].message
    assert "+60.0%" in found[0].message


def test_budget_within_tolerance_is_clean():
    pinned = _pins(**{"e": {"primitives": 100, "flops": 1000,
                            "live_bytes": 1000, "histogram": {}}})
    measured = {"e": {"primitives": 102, "flops": 1100, "live_bytes": 900,
                      "histogram": {}}}
    assert budgets_mod.compare(measured, pinned) == []


def test_budget_unpinned_and_stale_entries():
    pinned = _pins(**{"gone": {"primitives": 1, "flops": 1,
                               "live_bytes": 1, "histogram": {}}})
    measured = {"new": {"primitives": 1, "flops": 1, "live_bytes": 1,
                        "histogram": {}}}
    rules = {f.rule for f in budgets_mod.compare(measured, pinned)}
    assert rules == {"BG001", "BG003"}


# ---------------------------------------------------------------------------
# guard-dispatch audit
# ---------------------------------------------------------------------------

def test_guard_audit_tree_is_clean():
    findings, scanned = guard_audit.audit_tree(REPO)
    assert scanned > 40
    assert findings == [], [f.render() for f in findings]


_RAW_FIXTURE = '''"""fixture: raw dispatch."""
from cluster_capacity_tpu.engine import simulator as sim


def sneaky(pb):
    return sim.solve(pb, max_limit=1)
'''

_GUARDED_FIXTURE = '''"""fixture: guarded dispatch."""
from cluster_capacity_tpu.engine import simulator as sim
from cluster_capacity_tpu.runtime import guard


def supervised(pb):
    return guard.run(lambda: sim.solve(pb, max_limit=1),
                     site="engine.solve", validate_nodes=4)
'''


def test_guard_audit_flags_raw_fixture():
    found = guard_audit.audit_source(
        _RAW_FIXTURE, "fixture.py", "fixture", exempt=False)
    assert len(found) == 1 and found[0].rule == "GD001"
    assert "engine.simulator.solve" in found[0].message


def test_guard_audit_accepts_guarded_fixture():
    assert guard_audit.audit_source(
        _GUARDED_FIXTURE, "fixture.py", "fixture", exempt=False) == []


def test_guard_audit_allows_internal_composition():
    src = '''"""fixture: dispatch-set member composing internally."""
from cluster_capacity_tpu.engine import fast_path


def solve_auto(pb):
    return fast_path.solve_fast(pb)
'''
    assert guard_audit.audit_source(
        src, "fixture.py", "cluster_capacity_tpu.engine.fast_path",
        exempt=False) == []


# ---------------------------------------------------------------------------
# chaos × irgate: post-fault rungs stay contract-clean (satellite)
# ---------------------------------------------------------------------------

def test_degraded_rung_jaxprs_contract_clean(captured_jits):
    """Inject a persistent group OOM: the ladder falls from the batched
    rung to per-item solves; every computation dispatched by the fallback
    rung must satisfy the same IR contracts as the healthy path."""
    from cluster_capacity_tpu.runtime import degrade, faults

    # affinity keeps the problems off the analytic fast path so the
    # fallback rung actually dispatches device computations to inspect
    pbs = [entries._problem(6, affinity=True) for _ in range(3)]
    with faults.inject("parallel.solve_group:oom:1:0"):
        with cap.capturing() as records:
            results = degrade.solve_group_guarded(pbs)
    assert all(r is not None for r in results)
    assert all(r.degraded for r in results)
    comps = cap.dedup(records)
    assert comps, "fallback rung dispatched no computations"
    # conftest enables x64 process-wide, which legitimately widens some
    # transferred arrays — so pin only the x64-insensitive contracts here;
    # the dtype contracts are pinned by the subprocess gate run.
    x64 = jax.config.jax_enable_x64
    policy = Policy(forbid_f64=not x64, check_dtype_flow=not x64,
                    check_stablehlo=False)
    for comp in comps:
        found = contracts.check_captured("chaos", comp, policy)
        assert found == [], [f.render() for f in found]


def test_extender_dispatch_routes_through_guard():
    """SITE_EXTENDERS: an injected OOM at the new boundary surfaces as a
    structured DeviceOOM from the framework loop (not a raw crash)."""
    from cluster_capacity_tpu import ClusterCapacity
    from cluster_capacity_tpu.engine.extenders import ExtenderConfig
    from cluster_capacity_tpu.models.podspec import default_pod
    from cluster_capacity_tpu.runtime import faults
    from cluster_capacity_tpu.runtime.errors import DeviceOOM
    from cluster_capacity_tpu.utils.config import SchedulerProfile

    nodes = [entries._node("n1", 1000, int(1e9), 10)]
    profile = SchedulerProfile()
    profile.extenders = [ExtenderConfig(
        bind_callable=lambda p, n: {})]
    cc = ClusterCapacity(default_pod(entries._pod("probe", 100, int(1e6))),
                         max_limit=2, profile=profile)
    cc.sync_with_objects(nodes, [])
    with faults.inject("engine.extenders:oom"):
        with pytest.raises(DeviceOOM):
            cc.run()


def test_interleave_dispatch_degrades_to_object_loop():
    """SITE_INTERLEAVE: a classified fault on the tensor path falls back
    to the object-level queue loop instead of crashing the sweep."""
    from cluster_capacity_tpu.models.snapshot import ClusterSnapshot
    from cluster_capacity_tpu.parallel import interleave
    from cluster_capacity_tpu.parallel.sweep import sweep_interleaved
    from cluster_capacity_tpu.runtime import faults

    snapshot = ClusterSnapshot.from_objects(
        [entries._node(f"n{i}", 2000, int(1e9), 8) for i in range(3)], [])
    templates = [entries._pod("a", 200, int(1e6)),
                 entries._pod("b", 300, int(1e6))]
    with faults.inject("parallel.interleave:oom"):
        res = interleave.sweep_interleaved_auto(
            snapshot, templates, max_total=4)
    ref = sweep_interleaved(snapshot, templates, max_total=4)
    assert [r.placements for r in res] == [r.placements for r in ref]


# ---------------------------------------------------------------------------
# mosaic fold-in (satellite)
# ---------------------------------------------------------------------------

def test_mosaic_fold_in_clean_on_tree():
    assert entries.mosaic_findings() == []


def test_mosaic_fold_in_reports_bad_spec():
    from cluster_capacity_tpu.engine.mosaic_lint import SpecEntry, check_entry
    bad = SpecEntry("x", (1, 3), (8, 3), "vmem")   # lane dim not 128
    assert check_entry(bad)

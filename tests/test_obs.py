"""Solve telemetry suite (cluster_capacity_tpu/obs/ + tools/perfgate/).

Invariants under test: every ladder rung attempted under injected faults
leaves a correctly-attributed span (site, rung, outcome, parentage); the
metrics registry renders deterministic Prometheus text (golden-pinned); the
event recorder ring retains exactly the newest max_events; trace export is
valid Chrome-trace-event JSONL; and the perfgate throughput gate fails a
doctored bench artifact naming the metric and the delta (including the real
r04→r05 fast_path regression from the committed artifacts).
"""

import json
import os
import sys

import pytest

from cluster_capacity_tpu import SchedulerProfile, obs
from cluster_capacity_tpu.engine import encode as enc
from cluster_capacity_tpu.models.podspec import default_pod
from cluster_capacity_tpu.models.snapshot import ClusterSnapshot
from cluster_capacity_tpu.obs import names as obs_names
from cluster_capacity_tpu.runtime import degrade, faults
from cluster_capacity_tpu.utils import metrics
from cluster_capacity_tpu.utils.events import Recorder, default_recorder
from cluster_capacity_tpu.utils.metrics import default_registry

from helpers import build_test_node, build_test_pod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tools.perfgate import gate as pg  # noqa: E402
from tools.perfgate.__main__ import main as perfgate_main  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_telemetry():
    faults.clear()
    obs.default_collector.reset()
    default_registry.reset()
    default_recorder.clear()
    yield
    faults.clear()
    obs.default_collector.reset()
    default_registry.reset()
    default_recorder.clear()


def _pb(num_nodes=4, cpu=2000, pods=8):
    nodes = [build_test_node(f"n{i}", cpu, 4 * 1024 ** 3, pods)
             for i in range(num_nodes)]
    snap = ClusterSnapshot.from_objects(nodes)
    return enc.encode_problem(snap, default_pod(build_test_pod("probe", 500)),
                              SchedulerProfile())


# --- span collection ---------------------------------------------------------

def test_ladder_descent_leaves_span_per_rung():
    """oom at fused + fast_path rungs → one parent degrade span with a
    child guard span per rung attempted, each stamped with the fault code
    that ended it; the serving oracle span closes ok."""
    with faults.inject("engine.solve:oom", "engine.fast_path:oom"):
        res = degrade.solve_one_guarded(_pb())
    assert res.rung == degrade.RUNG_ORACLE and res.degraded

    spans = {s.name: s for s in obs.default_collector.spans()}
    parent = spans["degrade.solve_one"]
    assert parent.outcome == "ok"

    solve = spans["guard:engine.solve"]
    assert (solve.rung, solve.outcome) == (degrade.RUNG_FUSED, "DeviceOOM")
    assert solve.first_call and solve.parent_id == parent.span_id

    fp = spans["guard:engine.fast_path"]
    assert (fp.rung, fp.outcome) == (degrade.RUNG_FAST_PATH, "DeviceOOM")
    assert fp.parent_id == parent.span_id

    oracle = spans["guard:engine.oracle"]
    assert (oracle.rung, oracle.outcome) == (degrade.RUNG_ORACLE, "ok")
    assert oracle.parent_id == parent.span_id
    assert all(s.duration_s is not None for s in (solve, fp, oracle))

    # metric sinks saw the same story
    assert default_registry.get(
        obs_names.FAULTS_INJECTED, site="engine.solve", kind="oom") == 1
    assert default_registry.get(
        obs_names.DEGRADATIONS, site="engine.solve", fault="DeviceOOM",
        to_rung=degrade.RUNG_FAST_PATH) == 1
    assert default_registry.get(
        obs_names.GUARD_RUNS, site="engine.oracle",
        rung=degrade.RUNG_ORACLE, phase="execute", outcome="ok") == 1
    # fault events landed in the recorder alongside the transitions
    assert default_recorder.by_reason("DeviceOOM")
    assert default_recorder.by_reason("SolveDegraded")


def test_rung_inheritance_and_first_call():
    c = obs.Collector()
    with c.span("outer", rung="fused"):
        with c.span("inner", site="x.y"):
            pass
        with c.span("inner2", site="x.y"):
            pass
    inner, inner2 = [s for s in c.spans() if s.name.startswith("inner")]
    assert inner.rung == "fused"          # inherited from enclosing span
    assert inner.first_call and not inner2.first_call


def test_span_buffer_bounded():
    c = obs.Collector(max_spans=8)
    for i in range(20):
        with c.span(f"s{i}"):
            pass
    spans = c.spans()
    assert len(spans) == 8 and c.dropped == 12
    assert spans[-1].name == "s19"        # newest retained


def test_guard_span_outcome_and_histogram():
    with pytest.raises(ValueError):
        with obs.guard_span(site="t.site", phase="execute", rung="fused"):
            raise ValueError("boom")
    assert default_registry.get(
        obs_names.GUARD_RUNS, site="t.site", rung="fused", phase="execute",
        outcome="ValueError") == 1
    # the duration histogram saw exactly one observation for the series
    key = None
    for (name, labels) in default_registry.histograms:
        if name == obs_names.GUARD_DURATION and ("site", "t.site") in labels:
            key = (name, labels)
    assert key is not None
    assert default_registry.histograms[key].count == 1


def test_span_buffer_at_cap_keeps_newest_and_counts_drops():
    c = obs.Collector(max_spans=8)
    for i in range(20):
        with c.span(f"outer{i}"):
            with c.span(f"inner{i}"):
                pass
    assert [s.name for s in c.spans()] == [
        f"{kind}{i}" for i in range(16, 20) for kind in ("outer", "inner")]
    assert c.dropped == 32
    assert default_registry.counter_total(obs_names.SPANS_DROPPED) == 32
    c.reset()
    assert c.spans() == [] and c.dropped == 0


def _profiler_events(trace_dir):
    """{name: [ProfileEvent]} of the host events named cc.* in the one
    .xplane.pb a jax.profiler session wrote under `trace_dir`."""
    import glob

    from jax.profiler import ProfileData
    [path] = glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                    "*", "*.xplane.pb"))
    out = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("cc."):
                    out.setdefault(ev.name, []).append(ev)
    return out


def test_span_lands_in_the_profiler_trace_with_its_args(tmp_path):
    import jax
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("cc.outer", steps=7, lanes=2, site="t.site"):
            with obs.span("cc.inner", note="x", skipped=[1, 2]):
                pass
    events = _profiler_events(tmp_path)
    [outer], [inner] = events["cc.outer"], events["cc.inner"]
    assert dict(outer.stats) == {"steps": 7, "lanes": 2, "site": "t.site"}
    assert dict(inner.stats) == {"note": "x"}     # scalars only
    assert outer.start_ns <= inner.start_ns
    assert inner.start_ns + inner.duration_ns <= \
        outer.start_ns + outer.duration_ns
    # the collector keeps them too, nested
    spans = {s.name: s for s in obs.default_collector.spans()}
    assert spans["cc.inner"].parent_id == spans["cc.outer"].span_id


def test_attribute_set_on_an_open_span_lands_in_the_trace(tmp_path):
    """A count known only inside the span (cc.verify's `due`) reaches the
    profiler's args as well as the collector."""
    import jax
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("cc.late", steps=1) as sp:
            sp.attrs["due"] = 2
    [ev] = _profiler_events(tmp_path)["cc.late"]
    assert dict(ev.stats) == {"steps": 1, "due": 2}
    [late] = [s for s in obs.default_collector.spans() if s.name == "cc.late"]
    assert late.attrs == {"steps": 1, "due": 2}


def test_span_without_a_profiler_session_is_still_collected(monkeypatch):
    from cluster_capacity_tpu.obs import spans as spans_mod
    c = obs.Collector()
    with c.span("cc.issue", steps=3, lanes=1):
        pass
    # before jax is imported no session can run: no annotation at all
    monkeypatch.setattr(spans_mod, "_annotation", None)
    monkeypatch.delitem(sys.modules, "jax")
    with c.span("cc.wait", site="t.site", batch=2, note="x"):
        pass
    assert spans_mod._annotation is None
    issue, wait = c.spans()
    assert (issue.name, issue.attrs) == ("cc.issue", {"steps": 3, "lanes": 1})
    assert wait.name == "cc.wait" and wait.outcome == "ok"
    assert (wait.site, wait.batch, wait.attrs) == ("t.site", 2, {"note": "x"})
    assert issue.duration_s >= 0.0 and wait.duration_s >= 0.0


def _zoned_snapshot(n=8):
    nodes = [build_test_node(
        f"n{i}", 2000, 4 * 1024 ** 3, 8,
        labels={"kubernetes.io/hostname": f"n{i}",
                "topology.kubernetes.io/zone": f"z{i % 2}"})
        for i in range(n)]
    return ClusterSnapshot.from_objects(nodes)


def _spread_pod(name, cpu_milli):
    pod = build_test_pod(name, cpu_milli, labels={"app": name})
    pod["spec"]["topologySpreadConstraints"] = [
        {"maxSkew": 1, "topologyKey": "topology.kubernetes.io/zone",
         "whenUnsatisfiable": "DoNotSchedule",
         "labelSelector": {"matchLabels": {"app": name}}}]
    return default_pod(pod)


def _cc_spans():
    return [s for s in obs.default_collector.spans()
            if s.name.startswith("cc.")]


def test_scan_answer_emits_each_layer_span_in_order():
    from cluster_capacity_tpu import ClusterCapacity
    cc = ClusterCapacity(_spread_pod("spread", 500))
    cc.set_snapshot(_zoned_snapshot())
    cc.run()
    review = cc.report()
    assert review.replicas == 32 and review.fail_type == "Unschedulable"
    spans = _cc_spans()
    names = [s.name for s in spans]
    first = {n: names.index(n) for n in reversed(names)}
    last = {n: len(names) - 1 - names[::-1].index(n) for n in names}
    assert first["cc.encode"] < first["cc.setup"] < first["cc.issue"] \
        < first["cc.wait"] < first["cc.diagnose"] < first["cc.report"]
    assert last["cc.wait"] < first["cc.diagnose"]
    enc_span = spans[first["cc.encode"]]
    inside = {s.name for s in spans if s.parent_id == enc_span.span_id}
    assert inside == {"cc.encode.spread", "cc.encode.affinity"}
    issues = [s for s in spans if s.name == "cc.issue"]
    assert all(s.attrs["lanes"] == 1 and s.attrs["steps"] > 0
               for s in issues)
    assert sum(s.attrs["steps"] for s in issues) >= review.replicas
    assert "cc.fast.sort" not in names


def test_fast_path_answer_emits_its_sort_span():
    from cluster_capacity_tpu import ClusterCapacity
    cc = ClusterCapacity(default_pod(build_test_pod("plain", 500)),
                         max_limit=5)
    cc.set_snapshot(_zoned_snapshot())
    cc.run()
    assert cc.report().replicas == 5
    names = [s.name for s in _cc_spans()]
    for name in ("cc.encode", "cc.setup", "cc.issue", "cc.wait",
                 "cc.fast.sort", "cc.report"):
        assert name in names
    assert names.index("cc.wait") < names.index("cc.fast.sort")


def test_batched_sweep_issue_carries_the_group_lanes():
    from cluster_capacity_tpu.parallel.sweep import sweep
    results = sweep(_zoned_snapshot(),
                    [_spread_pod("a", 500), _spread_pod("b", 700)])
    assert [r.placed_count for r in results] == [32, 16]
    spans = _cc_spans()
    issues = [s for s in spans if s.name == "cc.issue"]
    assert issues and all(s.attrs["lanes"] == 2 for s in issues)
    names = [s.name for s in spans]
    assert names.count("cc.encode") == 2
    assert names.count("cc.diagnose") == 2


def test_trace_flag_prints_the_snapshot_and_solve_phases(monkeypatch,
                                                         capsys):
    from cluster_capacity_tpu.cli.cluster_capacity import run
    from cluster_capacity_tpu.utils import trace
    monkeypatch.setattr(trace.default_tracer, "enabled", False)
    rc = run(["--podspec", os.path.join(ROOT, "examples", "pod.yaml"),
              "--snapshot", os.path.join(ROOT, "examples",
                                         "cluster-snapshot.yaml"),
              "--trace"])
    assert rc == 0
    err = capsys.readouterr().err
    assert f'Trace: "{trace.SPAN_SNAPSHOT}" took ' in err
    assert f'Trace: "{trace.SPAN_SOLVE}" took ' in err
    names = {s.name for s in obs.default_collector.spans()}
    assert {trace.SPAN_SNAPSHOT, trace.SPAN_SOLVE, "cc.encode"} <= names


# --- metrics rendering -------------------------------------------------------

def test_prometheus_render_golden():
    reg = metrics.Registry()
    reg.inc(obs_names.GUARD_RUNS, outcome="DeviceOOM", site="engine.solve",
            rung="fused", phase="execute", amount=2.0)
    reg.inc(obs_names.GUARD_RUNS, outcome="ok", site="engine.solve",
            rung="fused", phase="execute")
    reg.set_gauge(obs_names.SWEEP_GROUPS, 3, mode="batched")
    reg.observe(obs_names.GUARD_DURATION, 0.0015, site="engine.solve",
                rung="fused", phase="execute")
    reg.observe(obs_names.GUARD_DURATION, 5.0, site="engine.solve",
                rung="fused", phase="execute")

    hist_labels = 'phase="execute",rung="fused",site="engine.solve"'
    bucket_counts = [("0.001", 0)] + [
        (le, 1) for le in ("0.002", "0.004", "0.008", "0.016", "0.032",
                           "0.064", "0.128", "0.256", "0.512", "1.024",
                           "2.048", "4.096")] + [("8.192", 2), ("+Inf", 2)]
    golden = "\n".join(
        ['cc_guard_runs_total{outcome="DeviceOOM",phase="execute",'
         'rung="fused",site="engine.solve"} 2',
         'cc_guard_runs_total{outcome="ok",phase="execute",'
         'rung="fused",site="engine.solve"} 1',
         'cc_sweep_groups{mode="batched"} 3'] +
        [f'cc_guard_run_duration_seconds_bucket{{{hist_labels},le="{le}"}} '
         f'{c}' for le, c in bucket_counts] +
        [f'cc_guard_run_duration_seconds_sum{{{hist_labels}}} 5.0015',
         f'cc_guard_run_duration_seconds_count{{{hist_labels}}} 2']) + "\n"
    assert reg.render() == golden


def test_render_is_valid_prometheus_text():
    import re
    with faults.inject("engine.solve:oom"):
        degrade.solve_one_guarded(_pb())
    text = default_registry.render()
    assert "cc_guard_runs_total" in text
    assert "cc_guard_run_duration_seconds_bucket" in text
    line_re = re.compile(
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
        r'(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"(,[a-zA-Z_][a-zA-Z0-9_]*='
        r'"[^"]*")*\})? -?(\d+(\.\d+)?([eE][+-]?\d+)?|\+?Inf|NaN)$')
    for line in text.splitlines():
        assert line_re.match(line), f"not Prometheus text: {line!r}"


# --- event recorder ring -----------------------------------------------------

def test_recorder_ring_keeps_newest():
    r = Recorder(max_events=5)
    for i in range(12):
        r.eventf("obj", "R", f"e{i}")
    assert len(r.events) == 5 and r.dropped == 7
    assert [e.message for e in r.events] == [f"e{i}" for i in range(7, 12)]
    r.clear()
    assert not r.events and r.dropped == 0


# --- trace export ------------------------------------------------------------

def test_trace_export_jsonl(tmp_path):
    with faults.inject("engine.solve:oom", "engine.fast_path:oom"):
        degrade.solve_one_guarded(_pb())
    out = tmp_path / "trace.jsonl"
    n = obs.write_trace(str(out))
    lines = out.read_text().splitlines()
    assert n == len(lines) >= 4
    events = [json.loads(ln) for ln in lines]
    for ev in events:
        assert ev["ph"] == "X" and ev["pid"] == 1
        assert isinstance(ev["ts"], float) and isinstance(ev["dur"], float)
    by_name = {ev["name"]: ev for ev in events}
    solve = by_name["guard:engine.solve"]
    assert solve["args"]["site"] == "engine.solve"
    assert solve["args"]["rung"] == degrade.RUNG_FUSED
    assert solve["args"]["outcome"] == "DeviceOOM"
    oracle = by_name["guard:engine.oracle"]
    assert oracle["args"]["rung"] == degrade.RUNG_ORACLE
    assert oracle["args"]["parent_id"] == \
        by_name["degrade.solve_one"]["args"]["span_id"]


# --- recompile counter -------------------------------------------------------

def test_recompile_hook_counts_backend_compiles():
    import jax
    import jax.numpy as jnp

    obs.install_recompile_hook()
    before = default_registry.counter_total(obs_names.RECOMPILES)
    # a fresh lambda is a fresh jit cache entry → guaranteed backend compile
    f = jax.jit(lambda x: x * 2 + 1)
    with obs.span("holder", site="test.compile") as sp:
        f(jnp.ones((3, 5))).block_until_ready()
    after = default_registry.counter_total(obs_names.RECOMPILES)
    assert after >= before + 1
    assert default_registry.counter_total(obs_names.COMPILE_SECONDS) > 0.0
    # the compile seconds were attributed to the open sited span
    assert sp.compile_s > 0.0


# --- perfgate ----------------------------------------------------------------

def _bench(**over):
    b = {"metric": "scan_engine_spread_placements_per_sec_10000_nodes",
         "value": 1000.0, "unit": "placements/s", "platform": "cpu",
         "fast_path_placements_per_sec": 50000.0,
         "sweep_spread_nodes": 10000,          # not *_per_sec: never gated
         "phases": {"fast": {"warmup_s": 1.2, "steady_s": 0.4,
                             "recompiles": 3, "backend_compile_s": 0.9}}}
    b.update(over)
    return b


def test_perfgate_clean_on_pin_source():
    bench = _bench()
    pins = pg.make_pins(bench, "BENCH_r98.json")
    assert set(pins["platforms"]) == {"cpu"}
    assert set(pins["platforms"]["cpu"]["metrics"]) == {
        "scan_engine_spread_placements_per_sec_10000_nodes",
        "fast_path_placements_per_sec"}
    findings, skip = pg.compare(bench, pins)
    assert findings == [] and skip is None
    # within the 10% band: still clean
    findings, _ = pg.compare(
        _bench(fast_path_placements_per_sec=46000.0), pins)
    assert findings == []


def test_perfgate_regression_names_metric_delta_and_phases():
    pins = pg.make_pins(_bench(), "BENCH_r98.json")
    findings, skip = pg.compare(
        _bench(fast_path_placements_per_sec=40000.0), pins)
    assert skip is None and len(findings) == 1
    f = findings[0]
    assert (f.metric, f.rule) == ("fast_path_placements_per_sec", "PG002")
    assert "50000.00 -> 40000.00" in f.message
    assert "-20.0%" in f.message
    assert "phases[fast]" in f.message and "warmup 1.2s" in f.message
    assert "recompiles 3" in f.message


def test_perfgate_new_and_stale_metrics():
    pins = pg.make_pins(_bench(), "BENCH_r98.json")
    grown = _bench(resilience_scenarios_per_sec=12.5)
    findings, _ = pg.compare(grown, pins)
    assert [(f.metric, f.rule) for f in findings] == [
        ("resilience_scenarios_per_sec", "PG001")]
    shrunk = _bench()
    del shrunk["fast_path_placements_per_sec"]
    findings, _ = pg.compare(shrunk, pins)
    assert [(f.metric, f.rule) for f in findings] == [
        ("fast_path_placements_per_sec", "PG003")]


def test_perfgate_platform_change_skips():
    pins = pg.make_pins(_bench(), "BENCH_r98.json")
    findings, skip = pg.compare(_bench(platform="tpu",
                                       fast_path_placements_per_sec=1.0),
                                pins)
    assert findings == [] and "platform changed" in skip


def test_perfgate_legacy_flat_pins_still_compare():
    """The pre-platform-keyed pins layout (top-level platform/metrics)
    normalizes into a one-slot platforms map on load/compare."""
    legacy = {"platform": "cpu", "source": "BENCH_r98.json",
              "tolerance_pct": 10.0,
              "metrics": {"fast_path_placements_per_sec": 50000.0}}
    findings, skip = pg.compare(_bench(), legacy)
    assert skip is None
    assert [(f.metric, f.rule) for f in findings] == [
        ("scan_engine_spread_placements_per_sec_10000_nodes", "PG001")]


def test_perfgate_repin_preserves_other_platform_slots():
    """--update-pins on one platform must not clobber another platform's
    floors (cpu numbers can never gate — or erase — a tpu pin)."""
    cpu_pins = pg.make_pins(_bench(), "BENCH_r98.json")
    cpu_pins["platforms"]["cpu"]["efficiency_floors"] = {"scan/n8": 0.01}
    both = pg.make_pins(_bench(platform="tpu",
                               fast_path_placements_per_sec=9e6),
                        "BENCH_r99.json", prev=cpu_pins)
    assert set(both["platforms"]) == {"cpu", "tpu"}
    cpu_slot = both["platforms"]["cpu"]
    assert cpu_slot["metrics"]["fast_path_placements_per_sec"] == 50000.0
    assert cpu_slot["efficiency_floors"] == {"scan/n8": 0.01}
    assert both["platforms"]["tpu"]["metrics"][
        "fast_path_placements_per_sec"] == 9e6
    # each platform gates only against its own slot
    findings, skip = pg.compare(_bench(), both)
    assert findings == [] and skip is None


def test_perfgate_merge_rates_folds_multichip_metrics():
    """The multichip sweep artifact's rate keys fold into the bench doc for
    one compare/pin pass; workload descriptors (nodes, counts) do not."""
    mdoc = {"ok": True, "skipped": False, "platform": "cpu",
            "nodes": 2000, "scenarios": 2000,
            "sharded_sweep_placements_per_sec": 3500.0,
            "sharded_sweep_per_device_placements_per_sec": 437.5}
    merged = pg.merge_rates(_bench(), mdoc)
    pins = pg.make_pins(merged, "BENCH_r98.json")
    metrics = pins["platforms"]["cpu"]["metrics"]
    assert metrics["sharded_sweep_placements_per_sec"] == 3500.0
    assert metrics["sharded_sweep_per_device_placements_per_sec"] == 437.5
    assert "nodes" not in metrics
    findings, skip = pg.compare(merged, pins)
    assert findings == [] and skip is None
    # the sharded sweep regressing trips PG002 like any bench metric
    slow = pg.merge_rates(_bench(), dict(
        mdoc, sharded_sweep_placements_per_sec=2000.0))
    findings, _ = pg.compare(slow, pins)
    assert [(f.metric, f.rule) for f in findings] == [
        ("sharded_sweep_placements_per_sec", "PG002")]


def test_perfgate_cli_exit_codes(tmp_path, capsys):
    pins_path = str(tmp_path / "pins.json")
    pg.save_pins(pg.make_pins(_bench(), "BENCH_r98.json"), pins_path)
    # doctored artifact, wrapped in the driver envelope ({"parsed": ...})
    doctored = str(tmp_path / "BENCH_r99.json")
    with open(doctored, "w") as f:
        json.dump({"n": 99, "rc": 0,
                   "parsed": _bench(fast_path_placements_per_sec=40000.0)},
                  f)
    rc = perfgate_main([doctored, "--pins", pins_path])
    out = capsys.readouterr().out
    assert rc == 1
    assert "fast_path_placements_per_sec" in out and "PG002" in out
    assert "-20.0%" in out

    clean = str(tmp_path / "BENCH_r100.json")
    with open(clean, "w") as f:
        json.dump(_bench(), f)
    assert perfgate_main([clean, "--pins", pins_path]) == 0
    # missing pins file → PG000 failure, not a crash
    rc = perfgate_main([clean, "--pins", str(tmp_path / "nope.json")])
    assert rc == 1 and "PG000" in capsys.readouterr().out


def test_perfgate_catches_the_real_r05_regression(tmp_path):
    """The committed r04→r05 artifacts contain a real −13% fast_path drop
    (measurement noise, per BASELINE.md round 5) — pinning r04 must make
    the gate fail r05 naming that metric."""
    r04 = os.path.join(ROOT, "BENCH_r04.json")
    r05 = os.path.join(ROOT, "BENCH_r05.json")
    if not (os.path.exists(r04) and os.path.exists(r05)):
        pytest.skip("committed bench artifacts not present")
    pins = pg.make_pins(pg.load_bench(r04), r04)
    findings, skip = pg.compare(pg.load_bench(r05), pins)
    assert skip is None
    hits = [f for f in findings
            if (f.metric, f.rule) == ("fast_path_placements_per_sec",
                                      "PG002")]
    assert len(hits) == 1 and "-13.0%" in hits[0].message


def test_perfgate_bench_files_numeric_sort(tmp_path):
    for n in (2, 11, 100):
        (tmp_path / f"BENCH_r{n:02d}.json").write_text("{}")
    names = [os.path.basename(p) for p in pg.bench_files(str(tmp_path))]
    assert names == ["BENCH_r02.json", "BENCH_r11.json", "BENCH_r100.json"]


def test_perfgate_floor_guardrail_names_metric_and_delta():
    """--update-pins must refuse to quietly lower a committed floor >10%
    (the r05/r06 bleed rode exactly such re-pins); raising floors and new
    metrics never refuse."""
    prev = pg.make_pins(_bench(), "BENCH_r98.json")
    lowered = pg.make_pins(
        _bench(fast_path_placements_per_sec=40000.0,
               resilience_scenarios_per_sec=12.5),    # new metric: fine
        "BENCH_r99.json", prev=prev)
    refusals = pg.floor_guardrail(lowered, prev)
    assert len(refusals) == 1
    assert "fast_path_placements_per_sec" in refusals[0]
    assert "50000.00 -> 40000.00" in refusals[0]
    assert "-20.0%" in refusals[0]
    # within the guard band (or improving): no refusal
    ok = pg.make_pins(_bench(fast_path_placements_per_sec=46000.0,
                             value=2000.0), "BENCH_r99.json", prev=prev)
    assert pg.floor_guardrail(ok, prev) == []
    # no committed pins yet: nothing to guard
    assert pg.floor_guardrail(lowered, None) == []


def test_perfgate_update_pins_guardrail_cli(tmp_path, capsys):
    """The CLI refuses to save a guard-tripping re-pin without
    --allow-lower, and saves it with the flag."""
    pins_path = str(tmp_path / "pins.json")
    pg.save_pins(pg.make_pins(_bench(), "BENCH_r98.json"), pins_path)
    slow = str(tmp_path / "BENCH_r99.json")
    with open(slow, "w") as f:
        json.dump(_bench(fast_path_placements_per_sec=40000.0), f)
    rc = perfgate_main([slow, "--pins", pins_path, "--update-pins"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "refusing to lower" in out
    assert "fast_path_placements_per_sec" in out and "--allow-lower" in out
    assert pg.load_pins(pins_path)["platforms"]["cpu"]["metrics"][
        "fast_path_placements_per_sec"] == 50000.0    # unchanged on refusal
    rc = perfgate_main([slow, "--pins", pins_path, "--update-pins",
                        "--allow-lower"])
    assert rc == 0
    assert pg.load_pins(pins_path)["platforms"]["cpu"]["metrics"][
        "fast_path_placements_per_sec"] == 40000.0


def test_perfgate_steady_recompiles_fail_pg005():
    """A bench scenario reporting backend compiles after its steady mark is
    a PG005 finding even with every throughput floor green."""
    pins = pg.make_pins(_bench(), "BENCH_r98.json")
    dirty = _bench()
    dirty["phases"]["fast"].update(
        {"warmup_recompiles": 3, "steady_recompiles": 2,
         "warmup_compile_s": 0.9, "steady_compile_s": 0.31})
    findings, skip = pg.compare(dirty, pins)
    assert skip is None
    assert [(f.metric, f.rule) for f in findings] == [
        ("phases.fast", "PG005")]
    assert "2 backend compile(s)" in findings[0].message
    assert "0.31" in findings[0].message
    # an explicit zero (the healthy split) stays clean
    clean = _bench()
    clean["phases"]["fast"]["steady_recompiles"] = 0
    findings, _ = pg.compare(clean, pins)
    assert findings == []


def test_perfgate_compile_budget_pins_and_findings():
    """compile_findings: over-budget is PG005 naming the entry and the
    delta; unpinned entries are PG001; stale budgets are PG003; the noise
    band (pct + absolute slack) absorbs small wall jitter; re-pins carry
    budgets through like efficiency floors."""
    measured = {"fast_path/n8b3": {"compile_s": 0.2, "compiles": 1,
                                   "wall_s": 0.3}}
    pins = pg.make_pins(_bench(), "BENCH_r98.json",
                        compile_budgets={"fast_path/n8b3": 0.2})
    assert pins["compile_tolerance_pct"] == pg.DEFAULT_COMPILE_TOLERANCE_PCT
    assert pins["compile_min_delta_s"] == pg.DEFAULT_COMPILE_MIN_DELTA_S
    assert pg.compile_findings(measured, pins, "cpu") == []
    # inside the band: budget*1.5 + 0.5s
    ok = {"fast_path/n8b3": {"compile_s": 0.75, "compiles": 2,
                             "wall_s": 0.9}}
    assert pg.compile_findings(ok, pins, "cpu") == []
    over = {"fast_path/n8b3": {"compile_s": 1.1, "compiles": 9,
                               "wall_s": 1.3}}
    findings = pg.compile_findings(over, pins, "cpu")
    assert [(f.metric, f.rule) for f in findings] == [
        ("compile.fast_path/n8b3", "PG005")]
    assert "0.200s pinned -> 1.100s measured" in findings[0].message
    assert "+0.900s" in findings[0].message
    # unpinned entry → PG001; budget with no entry → PG003
    findings = pg.compile_findings(
        {"scan/n8": {"compile_s": 0.1, "compiles": 1, "wall_s": 0.2}},
        pins, "cpu")
    assert sorted((f.metric, f.rule) for f in findings) == [
        ("compile.fast_path/n8b3", "PG003"), ("compile.scan/n8", "PG001")]
    # other platform has no slot → no findings (like compare's skip)
    assert pg.compile_findings(over, pins, "tpu") == []
    # budgets carry through a re-pin that doesn't remeasure
    repin = pg.make_pins(_bench(), "BENCH_r99.json", prev=pins)
    assert repin["platforms"]["cpu"]["compile_budgets"] == {
        "fast_path/n8b3": 0.2}


def test_compile_tally_scoped_measurement():
    """CompileTally counts only the backend compiles fired inside its
    scope, stacking with the process-wide counters."""
    import jax
    import jax.numpy as jnp

    from cluster_capacity_tpu.obs import recompile as rc

    with rc.CompileTally() as outside:
        pass
    with rc.CompileTally() as tally:
        f = jax.jit(lambda x: x * 3 + 2)
        f(jnp.ones((4, 7))).block_until_ready()
    assert tally.count >= 1
    assert tally.seconds > 0.0
    assert outside.count == 0 and outside.seconds == 0.0
    assert rc._tallies == []            # scope exits deregister


@pytest.mark.slow
def test_compilegate_fails_on_seeded_trace_bloat(monkeypatch):
    """Seeded compile-time regression: inflate the least_allocated score
    graph (the strategy the fast_path ladder entry uses) and the measured
    cold-cache compile seconds for that entry must blow past a budget
    pinned at the healthy cost, with PG005 naming the entry and the
    delta.  Each injected copy perturbs its input (CSE would otherwise
    fold identical subgraphs and hide the bloat)."""
    from cluster_capacity_tpu.ops import node_resources_fit as nrf
    from tools.perfgate import compilebudget

    healthy = compilebudget.measure(only=("fast_path/n8b3",))
    entry = healthy["fast_path/n8b3"]
    assert entry["compiles"] >= 1

    orig = nrf.least_allocated_score

    def bloated(alloc, *a, **kw):
        total = orig(alloc, *a, **kw)
        for i in range(1, 500):
            total = total + orig(alloc * (1.0 + i * 1e-9), *a, **kw) * 0.0
        return total

    monkeypatch.setattr(nrf, "least_allocated_score", bloated)
    regressed = compilebudget.measure(only=("fast_path/n8b3",))
    pins = pg.make_pins(_bench(), "BENCH_r98.json",
                        compile_budgets={
                            "fast_path/n8b3": entry["compile_s"]})
    findings = pg.compile_findings(regressed, pins, "cpu")
    assert [(f.metric, f.rule) for f in findings] == [
        ("compile.fast_path/n8b3", "PG005")]
    assert "compile budget exceeded" in findings[0].message
    got = regressed["fast_path/n8b3"]["compile_s"]
    assert f"{got:.3f}s measured" in findings[0].message
    # and the healthy measurement itself stays inside its own band
    assert pg.compile_findings(healthy, pins, "cpu") == []


# --- CLI surfaces ------------------------------------------------------------

def test_resilience_cli_dumps_metrics_and_trace(tmp_path):
    """A fault-injected resilience sweep must emit valid Prometheus text
    and a trace JSONL whose spans show the degradation rung-by-rung."""
    from cluster_capacity_tpu.cli.resilience import run

    snap = os.path.join(ROOT, "examples", "cluster-snapshot.yaml")
    if not os.path.exists(snap):
        pytest.skip("example snapshot not present")
    mpath = str(tmp_path / "metrics.prom")
    tpath = str(tmp_path / "trace.jsonl")
    # bounds off: the drill needs the batched group solve to actually
    # dispatch (and OOM), which the capacity brackets would prove away
    rc = run(["--snapshot", snap, "--nodes", "-o", "json", "--no-bounds",
              "--inject-fault", "parallel.solve_group:oom:1:99",
              "--metrics-dump", mpath, "--trace-out", tpath])
    assert rc == 0
    text = open(mpath).read()
    assert "cc_guard_runs_total" in text
    assert "cc_faults_injected_total" in text
    assert 'cc_resilience_scenarios{state="completed"}' in text
    events = [json.loads(ln) for ln in open(tpath)]
    oom = [ev for ev in events
           if ev["args"].get("site") == "parallel.solve_group"
           and ev["args"]["outcome"] == "DeviceOOM"]
    assert oom, "no failed batched-group span in the trace"
    served = [ev for ev in events
              if ev["args"].get("outcome") == "ok"
              and ev["args"].get("rung")]
    assert served, "no serving rung span in the trace"

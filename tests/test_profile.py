"""Deep-profiling layer suite (obs/profile.py, obs/costmodel.py,
obs/flight.py + the `hypercc profile` subcommand).

Invariants under test: the calibration math is exact on synthetic fixtures
(measured == budget → efficiency 1.0 everywhere; an inflated measurement is
flagged by name with its ratio; zero-FLOPs host entries are at par by
convention); guarded dispatches accumulate device-seconds attribution rows
keyed site × rung × phase; a classified fault under an armed flight
recorder dumps a bounded, loadable bundle whose repro spec re-triggers the
same fault code; and telemetry dumps are atomic (temp + rename, no .tmp
residue) so a watch loop stays scrapeable mid-flight.
"""

import json
import os
import sys

import pytest

from cluster_capacity_tpu import SchedulerProfile, obs
from cluster_capacity_tpu.cli import profile as profile_cli
from cluster_capacity_tpu.engine import encode as enc
from cluster_capacity_tpu.models.podspec import default_pod
from cluster_capacity_tpu.models.snapshot import ClusterSnapshot
from cluster_capacity_tpu.obs import costmodel, flight
from cluster_capacity_tpu.obs import names as obs_names
from cluster_capacity_tpu.obs import profile as obs_profile
from cluster_capacity_tpu.runtime import degrade, faults
from cluster_capacity_tpu.utils.events import default_recorder
from cluster_capacity_tpu.utils.metrics import default_registry

from helpers import build_test_node, build_test_pod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tools import trend  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_telemetry():
    faults.clear()
    flight.uninstall()
    obs.default_collector.reset()
    default_registry.reset()
    default_recorder.clear()
    yield
    faults.clear()
    flight.uninstall()
    obs.default_collector.reset()
    default_registry.reset()
    default_recorder.clear()


def _pb(num_nodes=4, cpu=2000, pods=8):
    nodes = [build_test_node(f"n{i}", cpu, 4 * 1024 ** 3, pods)
             for i in range(num_nodes)]
    snap = ClusterSnapshot.from_objects(nodes)
    return enc.encode_problem(snap, default_pod(build_test_pod("probe", 500)),
                              SchedulerProfile())


# --- cost-model calibration --------------------------------------------------

_BUDGETS = {
    "entries": {
        "fused/n8": {"flops": 1000.0, "live_bytes": 4096},
        "scan/n8": {"flops": 2000.0, "live_bytes": 8192},
        "fast_path/n8b3": {"flops": 500.0, "live_bytes": 2048},
        "oracle/n4": {"flops": 0, "live_bytes": 0},
    },
}


def test_calibration_at_par_is_exactly_one():
    """Every entry achieving the same FLOPs rate == the median rate, so
    efficiency is exactly 1.0 across the board and nothing is flagged."""
    measured = {
        "fused/n8": {"device_s": 1.0, "rung": "fused"},
        "scan/n8": {"device_s": 2.0, "rung": "fused"},
        "fast_path/n8b3": {"device_s": 0.5, "rung": "fast_path"},
    }
    report = costmodel.calibrate(measured, _BUDGETS, platform="cpu")
    assert report["schema"] == costmodel.CALIBRATION_SCHEMA
    assert report["calibrated_flops_per_sec"] == 1000.0
    for name, entry in report["entries"].items():
        assert entry["efficiency"] == 1.0, name
    assert report["flagged"] == []


def test_calibration_flags_inflated_entry_by_name_and_ratio():
    """One entry measured 4x slower than budget shows efficiency 0.25 and
    is flagged with its name and ratio; the others stay at par (median
    yardstick — the drifted kernel cannot move it)."""
    measured = {
        "fused/n8": {"device_s": 4.0, "rung": "fused"},   # 4x too slow
        "scan/n8": {"device_s": 2.0, "rung": "fused"},
        "fast_path/n8b3": {"device_s": 0.5, "rung": "fast_path"},
    }
    report = costmodel.calibrate(measured, _BUDGETS, platform="cpu")
    assert report["entries"]["fused/n8"]["efficiency"] == 0.25
    assert report["entries"]["scan/n8"]["efficiency"] == 1.0
    assert len(report["flagged"]) == 1
    flag = report["flagged"][0]
    assert flag["entry"] == "fused/n8"
    assert flag["efficiency"] == 0.25
    assert "fused/n8" in flag["message"] and "0.25" in flag["message"]
    rendered = costmodel.render_calibration(report)
    assert "FLAGGED" in rendered and "fused/n8" in rendered


def test_calibration_zero_flops_entry_at_par_by_convention():
    measured = {"oracle/n4": {"device_s": 0.3, "rung": "oracle"},
                "fused/n8": {"device_s": 1.0, "rung": "fused"}}
    report = costmodel.calibrate(measured, _BUDGETS, platform="cpu")
    oracle = report["entries"]["oracle/n4"]
    assert oracle["efficiency"] == 1.0
    assert oracle["flops_per_sec"] is None
    assert "zero-FLOPs" in oracle["note"]
    assert report["flagged"] == []


def test_calibration_memory_ratio_from_watermark():
    measured = {"fused/n8": {"device_s": 1.0, "rung": "fused",
                             "mem_peak_bytes": 8192}}
    report = costmodel.calibrate(measured, _BUDGETS, platform="cpu")
    # 8192 peak vs 4096 budgeted live bytes
    assert report["entries"]["fused/n8"]["mem_ratio"] == 2.0


def test_calibration_exports_kernel_efficiency_gauges():
    measured = {"fused/n8": {"device_s": 4.0, "rung": "fused"},
                "scan/n8": {"device_s": 2.0, "rung": "fused"},
                "fast_path/n8b3": {"device_s": 0.5, "rung": "fast_path"}}
    report = costmodel.calibrate(measured, _BUDGETS, platform="cpu")
    costmodel.to_registry(report)
    assert default_registry.get_gauge(obs_names.KERNEL_EFFICIENCY,
                                      entry="fused/n8", rung="fused") == 0.25
    assert default_registry.get_gauge(obs_names.KERNEL_EFFICIENCY,
                                      entry="scan/n8", rung="fused") == 1.0


def test_write_calibration_atomic(tmp_path):
    report = costmodel.calibrate(
        {"fused/n8": {"device_s": 1.0}}, _BUDGETS, platform="cpu")
    path = str(tmp_path / "calibration.json")
    costmodel.write_calibration(path, report)
    assert not os.path.exists(path + ".tmp")
    with open(path, encoding="utf-8") as fh:
        assert json.load(fh)["schema"] == costmodel.CALIBRATION_SCHEMA


# --- device-time attribution -------------------------------------------------

def test_guarded_dispatch_accumulates_attribution_rows():
    """A degraded solve leaves one attribution row per site × rung × phase
    with the fault counted on the failing site, and the guard's duration
    histogram grows with the same labels."""
    with faults.inject("engine.solve:oom"):
        res = degrade.solve_one_guarded(_pb())
    assert res.degraded

    rows = obs_profile.attribution()
    by_site = {r["site"]: r for r in rows}
    assert by_site["engine.solve"]["faults"] == 1
    assert by_site["engine.solve"]["rung"] == degrade.RUNG_FUSED
    assert "engine.fast_path" in by_site          # ladder served here
    assert by_site["engine.fast_path"]["faults"] == 0
    for r in rows:
        assert r["calls"] >= 1 and r["device_s"] >= 0.0

    guarded_s = sum(h.total for (name, _), h
                    in default_registry.histograms.items()
                    if name == obs_names.GUARD_DURATION)
    assert guarded_s > 0.0
    summary = obs_profile.device_summary()
    assert summary["device_s"] == pytest.approx(
        sum(r["device_s"] for r in rows), abs=1e-6)
    assert set(summary["sites"]) == set(by_site)

    rendered = obs_profile.render_attribution(rows)
    assert "engine.solve" in rendered and "device_s" in rendered


def test_write_attribution_schema_and_atomicity(tmp_path):
    degrade.solve_one_guarded(_pb())
    path = str(tmp_path / "attribution.json")
    obs_profile.write_attribution(path, extra={"scenario": "solve"})
    assert not os.path.exists(path + ".tmp")
    with open(path, encoding="utf-8") as fh:
        doc = json.load(fh)
    assert doc["schema"] == obs_profile.ATTRIBUTION_SCHEMA
    assert doc["scenario"] == "solve"
    assert any(r["site"] == "engine.solve" for r in doc["rows"])


def test_capture_restores_memory_sampling_flag():
    obs_profile.enable_memory_sampling(False)
    with obs_profile.capture(None, memory=True):
        assert obs_profile.memory_sampling_enabled()
    assert not obs_profile.memory_sampling_enabled()


# --- flight recorder ---------------------------------------------------------

def test_flight_bundle_round_trip_and_repro(tmp_path):
    """Injected OOM under an armed recorder: the bundle loads back with the
    fault identity, the injected specs, spans/metrics snapshots, and a repro
    spec that re-triggers the same fault code through the real classifier."""
    fdir = str(tmp_path / "flight")
    flight.install(fdir, argv=["cluster-capacity", "--podspec", "p.yaml"])
    with faults.inject("engine.solve:oom"):
        res = degrade.solve_one_guarded(_pb())
    assert res.degraded

    bundles = flight.bundle_paths()
    assert len(bundles) == 1
    assert os.path.basename(bundles[0]).endswith("-DeviceOOM")

    bundle = flight.load_bundle(bundles[0])
    man = bundle["manifest"]
    assert man["schema"] == flight.FLIGHT_SCHEMA
    assert man["fault"]["code"] == "DeviceOOM"
    assert man["fault"]["site"] == "engine.solve"
    assert man["injected"] == ["engine.solve:oom"]
    assert bundle["spans"], "span tail missing"
    assert "cc_" in bundle["metrics"]
    # the failing site maps to a canonical jitted entry -> jaxpr captured
    assert bundle["jaxpr"] and "jaxpr" in man["ir"].get("file", "jaxpr.txt")

    repro = man["repro"]
    assert repro["env"] == {faults.ENV_VAR: "engine.solve:oom"}
    assert "CC_INJECT_FAULT=engine.solve:oom" in repro["line"]
    assert "cluster-capacity" in repro["line"]

    # re-running the repro spec re-triggers the same fault code
    faults.clear()
    with faults.inject(repro["env"][faults.ENV_VAR]):
        res2 = degrade.solve_one_guarded(_pb())
    assert res2.degraded
    bundles = flight.bundle_paths()
    assert len(bundles) == 2
    man2 = flight.load_bundle(bundles[-1])["manifest"]
    assert man2["fault"]["code"] == "DeviceOOM"
    assert man2["fault"]["site"] == "engine.solve"
    # the second bundle saw the first ladder transition in its ring
    assert any("DeviceOOM@engine.solve" in d for d in man2["degradations"])


def test_flight_recorder_is_bounded(tmp_path):
    fdir = str(tmp_path / "flight")
    flight.install(fdir, max_bundles=2, capture_ir=False)
    for _ in range(3):
        with faults.inject("engine.solve:oom"):
            degrade.solve_one_guarded(_pb())
    on_disk = [n for n in os.listdir(fdir) if n.startswith("flight-")]
    assert len(on_disk) == 2
    # the newest two survived the prune (sequence numbers are process-wide
    # and monotonic, so lexicographic order is creation order)
    assert flight.bundle_paths() == sorted(
        os.path.join(fdir, n) for n in on_disk)
    assert default_registry.get(obs_names.FLIGHT_BUNDLES,
                                code="DeviceOOM") == 3


def test_flight_strict_failure_bundles_without_exception(tmp_path):
    fdir = str(tmp_path / "flight")
    flight.install(fdir, capture_ir=False)
    path = flight.on_strict("--strict: solve served by degraded rung oracle")
    assert path and os.path.isdir(path)
    man = flight.load_bundle(path)["manifest"]
    assert man["fault"]["code"] == "StrictDegraded"
    assert "degraded" in man["fault"]["message"]


def test_flight_noop_when_not_installed():
    with faults.inject("engine.solve:oom"):
        res = degrade.solve_one_guarded(_pb())
    assert res.degraded          # fault path ran, no recorder, no crash
    assert flight.bundle_paths() == []


# --- atomic telemetry dumps --------------------------------------------------

def test_export_atomic_writes_leave_no_temp_files(tmp_path):
    degrade.solve_one_guarded(_pb())
    mpath = str(tmp_path / "metrics.prom")
    tpath = str(tmp_path / "trace.jsonl")
    obs.write_metrics(mpath, atomic=True)
    n = obs.write_trace(tpath, atomic=True)
    assert n > 0
    for p in (mpath, tpath):
        assert os.path.exists(p)
        assert not os.path.exists(p + ".tmp")
    with open(tpath, encoding="utf-8") as fh:
        for line in fh:
            json.loads(line)


def test_watch_loop_rewrites_telemetry_atomically(tmp_path):
    """--period loop: the metrics/trace dumps are rewritten inside the loop
    (temp + rename) so a scraper reading mid-watch never sees a torn file,
    and no .tmp residue survives the run."""
    from cluster_capacity_tpu.cli import cluster_capacity as cc_cli
    mpath = str(tmp_path / "metrics.prom")
    tpath = str(tmp_path / "trace.jsonl")
    rc = cc_cli.run([
        "--podspec", os.path.join(ROOT, "examples", "pod.yaml"),
        "--snapshot", os.path.join(ROOT, "examples",
                                   "cluster-snapshot.yaml"),
        "--period", "0.01", "--period-iterations", "2",
        "--metrics-dump", mpath, "--trace-out", tpath])
    assert rc == 0
    assert not os.path.exists(mpath + ".tmp")
    assert not os.path.exists(tpath + ".tmp")
    with open(mpath, encoding="utf-8") as fh:
        assert "cc_" in fh.read()
    with open(tpath, encoding="utf-8") as fh:
        events = [json.loads(line) for line in fh if line.strip()]
    assert events


# --- trend phase attribution -------------------------------------------------

def test_trend_names_regression_phase():
    """A cross-round throughput drop is attributed to the phase whose cost
    grew: execute (device time grew with steady), host (steady grew, device
    flat), compile (recompiles / backend compile seconds grew)."""
    before = {"steady_s": 1.0, "recompiles": 0, "backend_compile_s": 0.5,
              "device": {"device_s": 0.9}}
    execute = {"steady_s": 2.0, "recompiles": 0, "backend_compile_s": 0.5,
               "device": {"device_s": 1.8}}
    host = {"steady_s": 2.0, "recompiles": 0, "backend_compile_s": 0.5,
            "device": {"device_s": 0.95}}
    compile_ = {"steady_s": 1.05, "recompiles": 3,
                "backend_compile_s": 4.0, "device": {"device_s": 0.9}}
    assert trend.name_phase(before, execute) == "execute"
    assert trend.name_phase(before, host) == "host"
    assert trend.name_phase(before, compile_) == "compile"
    assert trend.name_phase(None, execute) == ""   # no baseline, no verdict

    data = {
        "rounds": [1, 2],
        "metrics": {"sweep_spread_templates_placements_per_sec":
                    {1: 100.0, 2: 50.0}},
        "phases": {1: {"sweep": before}, 2: {"sweep": host}},
        "gates": {},
    }
    regs = trend.regressions(data)
    assert len(regs) == 1
    assert regs[0]["phase"] == "host" and regs[0]["scenario"] == "sweep"
    md = trend.render_markdown(data, regs)
    assert "suspect phase: host" in md


# --- hypercc profile CLI -----------------------------------------------------

def test_profile_cli_attribution_table(capsys):
    rc = profile_cli.run(["solve", "--nodes", "6", "--no-calibrate"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "engine.solve" in out and "device_s" in out


def test_profile_cli_json_with_fault_and_flight(tmp_path, capsys):
    fdir = str(tmp_path / "flight")
    rc = profile_cli.run(["solve", "--nodes", "6", "--no-calibrate",
                          "-o", "json", "--flight-dir", fdir,
                          "--inject-fault", "engine.solve:oom"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["scenario"] == "solve"
    sites = {r["site"] for r in doc["attribution"]}
    assert "engine.solve" in sites
    bundles = [n for n in os.listdir(fdir) if n.startswith("flight-")]
    assert bundles and "DeviceOOM" in bundles[0]

"""CPU rehearsal: every cell runs end to end at a tiny size through the
harness's test-only hook, and prints its result as the last line."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from helpers import BENCH, ROOT, TINY, cells, last_json, spec

import harness

KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.mark.parametrize("workload,config", cells())
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearsal(workload, config, trace, capsys):
    rc = harness.main(["--workload", workload, "--seed", "4000000001",
                       "--seconds", "1", "--trace", str(trace)],
                      rehearsal=TINY[config], t_start=time.perf_counter())
    assert rc == 0
    line = last_json(capsys.readouterr().out)
    assert list(line)[:5] == KEYS and list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    want = {m["name"] for m in spec()["end_to_end"]
            if workload in m.get("workloads", [workload])}
    if trace:
        assert "breakdown" in line and "busy_s" in line["device"]
        # the CPU has no device plane: no per-layer metric can be read
        assert line["metrics"] == {}
    else:
        assert set(line["metrics"]) == want
        assert all(v["value"] > 0 for v in line["metrics"].values())


def _copy_checkout(tmp_path, with_program: bool):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    if with_program:
        os.symlink(os.path.join(ROOT, "cluster_capacity_tpu"),
                   root / "cluster_capacity_tpu")
    return root


def test_new_traffic_file_found_by_name(tmp_path):
    """A cell added by data alone (a traffic file and a BENCHMARK.json
    entry) runs with no edit to the harness."""
    root = _copy_checkout(tmp_path, with_program=True)
    with open(root / "benchmark" / "traffic" / "spread-full.json") as f:
        traffic = json.load(f)
    traffic["templates"] = traffic["templates"][:2]
    with open(root / "benchmark" / "traffic" / "spread-two.json", "w") as f:
        json.dump(traffic, f)
    with open(root / "BENCHMARK.json") as f:
        s = json.load(f)
    s["workloads"].append({"name": "k8s5k-spread-two",
                           "config": "k8s-large-5k", "traffic": "spread-two",
                           "chips": 1, "why": "rehearsal"})
    s["end_to_end"][0]["workloads"].append("k8s5k-spread-two")
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(s, f)
    code = ("import sys, time; sys.path.insert(0, sys.argv[1]); "
            "import harness; sys.exit(harness.main(['--workload', "
            "'k8s5k-spread-two', '--seed', '7', '--seconds', '1', "
            "'--trace', '0'], rehearsal=" + repr(TINY["k8s-large-5k"])
            + ", t_start=time.perf_counter()))")
    r = subprocess.run([sys.executable, "-c", code,
                        str(root / "benchmark")], cwd=root,
                       capture_output=True, text=True, timeout=600,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-2000:]
    line = last_json(r.stdout)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"placements_per_s", "setup_s"}


def test_no_accelerator_exits_without_result():
    r = subprocess.run([sys.executable, "benchmark/run.py", "--workload",
                        "sched5k-basic-1k", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, capture_output=True,
                       text=True, timeout=600,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert '"correct"' not in r.stdout


def test_benchmark_alone_exits_without_result(tmp_path):
    root = _copy_checkout(tmp_path, with_program=False)
    r = subprocess.run([sys.executable, "benchmark/run.py", "--workload",
                        "sched5k-basic-1k", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=root, capture_output=True,
                       text=True, timeout=600,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert '"correct"' not in r.stdout


def test_failed_encoder_build_gives_no_result(tmp_path, monkeypatch):
    """Where the native encoder is absent and `make native` fails, the run
    ends with no result rather than time the Python encoder."""
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    with pytest.raises(harness.NoEncoder):
        harness.native_encoder(build=True)

"""CPU rehearsal: every cell runs end to end at a tiny size through the
harness's test-only hook, and prints its result as the last line.  A
configuration, with its generator, reference and control, is added as new
files alone."""

import hashlib
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from helpers import BENCH, ROOT, cells, last_json, rehearsal, spec

import harness

KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.mark.parametrize("workload,config", cells())
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearsal(workload, config, trace, capsys):
    rc = harness.main(["--workload", workload, "--seed", "4000000001",
                       "--seconds", "1", "--trace", str(trace)],
                      rehearsal=rehearsal(config),
                      t_start=time.perf_counter())
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


@pytest.mark.parametrize("config", [c["name"] for c in spec()["configs"]])
def test_config_states_rehearsal_size(config):
    sizes = rehearsal(config)
    assert sizes.get("config") and set(sizes) <= {"config", "traffic"}


def _copy_checkout(tmp_path, with_program: bool, with_tests: bool = False):
    root = tmp_path / "checkout"
    skip = ("__pycache__",) if with_tests else ("__pycache__", "tests")
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns(*skip))
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
            "'--trace', '0'], rehearsal=" + repr(rehearsal("k8s-large-5k"))
            + ", t_start=time.perf_counter()))")
    r = subprocess.run([sys.executable, "-c", code,
                        str(root / "benchmark")], cwd=root,
                       capture_output=True, text=True, timeout=600,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-2000:]
    line = last_json(r.stdout)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"placements_per_s", "setup_s"}


# A deployment that no existing file knows: its own cluster generator,
# reference, control, traffic mix and rehearsal size, each a new file.
NEW_FILES = {
    "benchmark/configs/grid-test.json": json.dumps({
        "name": "grid-test", "generator": "grid", "nodes": 40,
        "reference": "benchmark/references/grid.py",
        "precision": "float32",
        "rehearsal": {"config": {"nodes": 12},
                      "traffic": {"max_limit": 30}}}),
    "benchmark/generators/grid.py": '''
import numpy as np


def make(cfg, seed):
    rng = np.random.default_rng(seed)
    names = [f"grid-{i:04d}" for i in range(cfg["nodes"])]
    nodes = [{"metadata": {"name": n,
                           "labels": {"kubernetes.io/hostname": n}},
              "spec": {},
              "status": {"allocatable": {"cpu": "4", "memory": "8Gi",
                                         "pods": "20"}}} for n in names]
    cpu = rng.choice([250, 500, 1000, 2000], size=len(names))
    pods = [{"metadata": {"name": f"res-{i:04d}", "namespace": "default",
                          "labels": {"app": "res"}},
             "spec": {"nodeName": n, "containers": [{"name": "c",
                      "resources": {"requests": {"cpu": f"{int(cpu[i])}m",
                                                 "memory": "1Gi"}}}]},
             "status": {"phase": "Running"}} for i, n in enumerate(names)]
    return {"nodes": nodes, "pods": pods}
''',
    "benchmark/references/grid.py": '''
import numpy as np

import reference
from reference import Cluster  # noqa: F401


def solve(cluster, pod, max_limit=0, dtype=np.float32):
    if (pod.get("spec") or {}).get("runtimeClassName"):
        raise reference.Unsupported("pod spec.runtimeClassName")
    return reference.solve(cluster, pod, max_limit, dtype=dtype)
''',
    "benchmark/controls/residents_ignored.py": '''
import numpy as np


def apply(pods, templates):
    return [], templates, np.float32
''',
    "benchmark/traffic/grid-fill.json": json.dumps({
        "name": "grid-fill", "question": "single", "max_limit": 0,
        "templates": [{"metadata": {"name": "fill", "namespace": "default"},
                       "spec": {"containers": [{"name": "c", "resources": {
                           "requests": {"cpu": "500m",
                                        "memory": "512Mi"}}}]}}],
        "limits": {"node_gap": 0.01}, "control": "residents_ignored"}),
}

NEW_RUN = """
import json, sys, time
sys.path.insert(0, "benchmark")
import control, gen, harness, reference
cfg = gen.load_json("benchmark/configs/grid-test.json")
rc = harness.main(["--workload", "grid-fill", "--seed", "2200000007",
                   "--seconds", "1", "--trace", "0"],
                  rehearsal=cfg["rehearsal"], t_start=time.perf_counter())
checked_by_grid = "references.grid" in sys.modules
ref = gen.load_module(cfg["reference"])
try:
    ref.solve(None, {"spec": {"runtimeClassName": "gvisor"}})
    refused = False
except reference.Unsupported:
    refused = True
checks = control.readings("grid-fill", 2200000007, cfg["rehearsal"])
loaded = "controls.residents_ignored" in sys.modules
print(json.dumps({"rc": rc, "checked_by_grid": checked_by_grid,
                  "refused": refused, "control": checks["control"],
                  "control_loaded": loaded,
                  "control_correct": checks["_correct"],
                  "control_node_gap": checks["node_gap"]["value"]}))
"""


def _digests(root):
    """sha256 of every file the copy holds (the program is linked in, not
    copied)."""
    files = [root / "BENCHMARK.json"] + sorted(
        p for p in (root / "benchmark").rglob("*") if p.is_file())
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in files}


def _only_added(old, new) -> bool:
    """`new` is `old` with entries appended to its lists, nothing else."""
    if isinstance(old, dict):
        return isinstance(new, dict) and set(old) == set(new) and all(
            _only_added(old[k], new[k]) for k in old)
    if isinstance(old, list):
        return isinstance(new, list) and len(new) >= len(old) and all(
            _only_added(a, b) for a, b in zip(old, new))
    return old == new


def _add_configuration(root, files, config_entry, workload):
    for rel, text in files.items():
        path = root / rel
        assert not path.exists(), rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(text)
    with open(root / "BENCHMARK.json") as f:
        s = json.load(f)
    s["configs"].append(config_entry)
    if workload:
        s["workloads"].append(workload)
        for m in s["end_to_end"]:
            if m["name"] == "answer_p50_ms":
                m["workloads"].append(workload["name"])
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(s, f, indent=1)


def test_new_configuration_added_as_files(tmp_path):
    """A configuration with its own generator, reference, control and
    rehearsal size runs, is checked, and is caught by its control, with
    no file of the checkout changed but entries added to BENCHMARK.json."""
    root = _copy_checkout(tmp_path, with_program=True)
    before = _digests(root)
    with open(root / "BENCHMARK.json") as f:
        old_spec = json.load(f)
    _add_configuration(root, NEW_FILES, {
        "name": "grid-test", "source": "https://example.org/grid",
        "file": "benchmark/configs/grid-test.json", "reduced": [],
        "why": "rehearsal"}, {
        "name": "grid-fill", "config": "grid-test", "traffic": "grid-fill",
        "chips": 1, "why": "rehearsal"})
    r = subprocess.run([sys.executable, "-c", NEW_RUN], cwd=root,
                       capture_output=True, text=True, timeout=600,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    result, out = json.loads(lines[-2]), json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"answer_p50_ms", "setup_s"}
    assert out == {"rc": 0, "checked_by_grid": True, "refused": True,
                   "control": "residents_ignored", "control_loaded": True,
                   "control_correct": False,
                   "control_node_gap": out["control_node_gap"]}
    assert out["control_node_gap"] > 0.01
    after = _digests(root)
    changed = {k for k in before if after.get(k) != before[k]}
    assert changed == {"BENCHMARK.json"}
    with open(root / "BENCHMARK.json") as f:
        assert _only_added(old_spec, json.load(f))


def test_config_without_rehearsal_fails_by_name(tmp_path):
    """A configuration whose file states no rehearsal size fails
    test_config_states_rehearsal_size, and the failure names the key."""
    root = _copy_checkout(tmp_path, with_program=False, with_tests=True)
    cfg = json.loads(NEW_FILES["benchmark/configs/grid-test.json"])
    del cfg["rehearsal"]
    _add_configuration(root, {"benchmark/configs/grid-test.json":
                              json.dumps(cfg)}, {
        "name": "grid-test", "source": "https://example.org/grid",
        "file": "benchmark/configs/grid-test.json", "reduced": [],
        "why": "rehearsal"}, None)
    r = subprocess.run([sys.executable, "-m", "pytest", "-q",
                        "-p", "no:cacheprovider", "-p", "no:randomly",
                        "benchmark/tests/test_rehearsal.py::"
                        "test_config_states_rehearsal_size"],
                       cwd=root, capture_output=True, text=True, timeout=600,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 1, r.stdout[-2000:]
    assert "FAILED benchmark/tests/test_rehearsal.py::" \
        "test_config_states_rehearsal_size[grid-test]" in r.stdout
    assert f"{len(spec()['configs'])} passed" in r.stdout
    assert 'has no "rehearsal" key' in r.stdout, r.stdout[-2000:]


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


ALONE_RUN = """
import json, sys
sys.path.insert(0, "benchmark")
import gen, harness
with open("BENCHMARK.json") as f:
    c = harness.load_cell(json.load(f), sys.argv[1])
sizes = json.loads(sys.argv[2])
c["config"].update(sizes.get("config", {}))
c["traffic"].update(sizes.get("traffic", {}))
cluster = gen.make_cluster(c["config"], 5)
ref = gen.load_module(c["config"]["reference"])
rc = ref.Cluster(cluster["nodes"], cluster["pods"])
placed = [sum(ref.solve(rc, t, int(c["traffic"]["max_limit"]))
              .per_node().values()) for t in gen.templates(c["traffic"])]
print(json.dumps({"placed": placed, "program": sorted(
    m for m in sys.modules if m.startswith("cluster_capacity_tpu"))}))
"""


@pytest.mark.parametrize("workload,config", cells())
def test_reference_runs_without_the_program(workload, config, tmp_path):
    """Each configuration's reference loads and answers its cell's
    catalogue at rehearsal size in a checkout that holds no program."""
    root = _copy_checkout(tmp_path, with_program=False)
    r = subprocess.run([sys.executable, "-c", ALONE_RUN, workload,
                        json.dumps(rehearsal(config))], cwd=root,
                       capture_output=True, text=True, timeout=600,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-2000:]
    out = last_json(r.stdout)
    assert out["program"] == [] and out["placed"]
    assert all(n > 0 for n in out["placed"])


def test_failed_encoder_build_gives_no_result(tmp_path, monkeypatch):
    """Where the native encoder is absent and `make native` fails, the run
    ends with no result rather than time the Python encoder."""
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    with pytest.raises(harness.NoEncoder):
        harness.native_encoder(build=True)

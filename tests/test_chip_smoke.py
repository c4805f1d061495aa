"""chip_smoke.py rehearsed on the CPU at a tiny size: every phase's control
flow, the kernels in interpret mode, and the --chips 4 comparison on four
of the virtual CPU devices.  The phase-1 TPU check is bypassed through
run()'s test-only `allow_cpu` hook; on the chip nothing bypasses it."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@pytest.fixture
def smoke(monkeypatch):
    import chip_smoke
    monkeypatch.setenv("CC_TPU_FUSED", "1")     # interpret-mode kernels
    return chip_smoke


@pytest.mark.parametrize("chips", [1, 4])
def test_smoke_phases_pass_on_cpu(smoke, chips, capsys):
    device = smoke.run(chips=chips, nodes=24, pods=200, oracle_limit=6,
                       allow_cpu=True)
    assert device["platform"] == "cpu"
    lines = capsys.readouterr().out.splitlines()
    phases = [ln.split()[1] for ln in lines]
    if chips == 1:
        assert phases == ["phase=device", "phase=cluster", "phase=fast",
                          "phase=scan", "phase=batched", "phase=oracle"]
        assert "equal_to_xla=True" in lines[3]
        assert "equal_to_oracle=True" in lines[5]
    else:
        assert phases[-2:] == ["phase=sweep_1chip", "phase=sweep_mesh2x2"]
        assert "carry_devices=4" in lines[-1]


def test_smoke_refuses_cpu_without_hook():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_smoke_alone_fails(tmp_path):
    """In a directory holding chip_smoke.py and nothing else of the repo."""
    import shutil
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert not any(json.loads(ln).get("ok") for ln in r.stdout.splitlines()
                   if ln.startswith("{"))

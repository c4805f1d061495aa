"""Test harness setup: force an 8-device virtual CPU mesh before JAX loads,
and enable x64 so float arithmetic reproduces the reference's int64 score
math bit-exactly (the parity protocol in BASELINE.md)."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags +
                               " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

# Build the native snapshot compiler when the toolchain is present so the
# native differential tests run by default (they skip when it is absent).
import shutil  # noqa: E402
import subprocess  # noqa: E402

_repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_lib = os.path.join(_repo, "cluster_capacity_tpu", "models", "libccsnap.so")
if not os.path.exists(_lib) and shutil.which("g++") and shutil.which("make"):
    subprocess.run(["make", "native"], cwd=_repo, capture_output=True)

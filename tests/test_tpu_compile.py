"""Compile the chip's main-path programs for a described TPU v5e.

The single-template Pallas kernel at 5,000 and 65,536 nodes, the batched
kernel at 8 x 5,000, and the XLA scan chunk at 5,000 are lowered and
compiled by the TPU compiler for a `v5e:2x2` topology that is described,
not attached: what Mosaic or XLA would refuse on the chip (tiling, VMEM,
HBM) fails here at no chip time.  Nothing runs, so nothing here says a
kernel is correct or fast; chip_smoke.py does that on the chip.

The topology is described inside a fixture, never at import time: only
one process may load libtpu, and every pytest-xdist worker imports this
file.  The persistent compilation cache is off around these compiles (an
entry written for a described chip cannot be read back without one), and
x64 is off as it is on the CLI's default (float32) path.
"""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from jax.experimental import topologies
    was = (jax.config.jax_enable_compilation_cache,
           jax.config.jax_enable_x64)

    def restore():
        jax.config.update("jax_enable_compilation_cache", was[0])
        jax.config.update("jax_enable_x64", was[1])
        compilation_cache.reset_cache()

    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    compilation_cache.reset_cache()
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        restore()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield t
    restore()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _problem(n_nodes: int, k: int = 0):
    """The chip smoke's spread question on an n-node cluster (shapes only
    depend on the node count and the constraint set)."""
    import chip_smoke
    from cluster_capacity_tpu.engine.encode import encode_problem
    from cluster_capacity_tpu.models.podspec import default_pod
    from cluster_capacity_tpu.models.snapshot import ClusterSnapshot
    from cluster_capacity_tpu.utils.config import SchedulerProfile
    cluster = chip_smoke.make_cluster(n_nodes, 0, seed=0)
    snap = ClusterSnapshot.from_objects(cluster["nodes"], use_native=False)
    return encode_problem(snap, default_pod(chip_smoke.spread_pod(k)),
                          SchedulerProfile(compute_dtype="float32"))


def _shape(a, sharding):
    import jax
    a = np.asarray(a)
    return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n_nodes", [5000, 65536])
def test_fused_kernel_compiles(one_chip, n_nodes, monkeypatch):
    import jax
    import jax.numpy as jnp
    from cluster_capacity_tpu.engine import fused
    from cluster_capacity_tpu.engine import simulator as sim
    pb = _problem(n_nodes)
    cfg = sim.static_config(pb)
    monkeypatch.setenv("CC_TPU_FUSED", "1")     # eligible as on the chip
    assert fused.eligible(cfg, pb)
    pk = fused._pack_meta(cfg, pb, None)
    ins, _outs = fused._spec_table(pk, 4096)
    dtypes = (jnp.float32, jnp.float32, jnp.float32)
    args = [jax.ShapeDtypeStruct(e.array_shape, dt, sharding=one_chip)
            for e, dt in zip(ins, dtypes)]
    compiled = fused._compiled_call(pk, 4096, False).lower(*args).compile()
    assert _has_kernel(compiled)


def test_batched_kernel_compiles(one_chip, monkeypatch):
    import jax
    import jax.numpy as jnp
    from cluster_capacity_tpu.engine import fused, fused_batched
    from cluster_capacity_tpu.parallel import sweep
    pbs, cfg, dnh = sweep._pad_group([_problem(5000, k) for k in range(8)])
    monkeypatch.setenv("CC_TPU_FUSED", "1")
    assert fused_batched.batched_eligible(cfg, pbs)
    pk0 = fused._pack_meta(cfg, pbs[0], None)
    assert fused.vmem_ok(pk0, pipelined=True)
    pk = pk0._replace(meta=fused_batched._structural_meta(pk0.meta))
    tab = fused_batched._scalar_table(pk)
    ins, _outs = fused_batched._batched_spec_table(pk, tab, len(pbs), 1024)
    args = [jax.ShapeDtypeStruct(e.array_shape, jnp.float32,
                                 sharding=one_chip) for e, _m in ins]
    call = fused_batched._compiled_batched_call(pk, tab, len(pbs), 1024,
                                                max(1, dnh), False)
    compiled = call.lower(*args).compile()
    assert _has_kernel(compiled)


def test_xla_scan_chunk_compiles(one_chip):
    import jax
    from cluster_capacity_tpu.engine import simulator as sim
    pb = _problem(5000)
    cfg = sim.static_config(pb)
    consts = sim.build_consts(pb, device=False)
    carry = sim._init_carry(pb, consts, pb.profile.seed, device=False)
    c_args = jax.tree.map(lambda a: _shape(a, one_chip), consts)
    y_args = jax.tree.map(lambda a: _shape(a, one_chip), carry)
    compiled = sim._chunk_runner().lower(
        cfg=cfg, consts=c_args, carry=y_args, n=1024).compile()
    mem = compiled.memory_analysis()
    assert mem is None or mem.argument_size_in_bytes > 0

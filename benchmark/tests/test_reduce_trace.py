"""The trace reduction: device-busy union, device time per op and per
program, idle gaps labelled by the harness's host spans."""

import os

import pytest

from helpers import TESTS

import reduce_trace

WINDOW = ("bench.window", 0, 100)


def _extracted(ops, spans, modules=()):
    return {"ops": {"/device:TPU:0": list(ops)},
            "modules": {"/device:TPU:0": list(modules)},
            "spans": [WINDOW] + list(spans)}


def test_busy_union_and_idle_labels():
    ex = _extracted(
        ops=[("kernel", 10, 20), ("fusion", 15, 30), ("kernel", 50, 60),
             ("late", 95, 130)],
        spans=[("bench.run", 0, 45), ("bench.report", 45, 100)],
        modules=[("jit_step", 10, 30), ("jit_step", 50, 60)])
    red = reduce_trace.reduce(ex, reduce_trace.window_of(ex))
    assert red["window_s"] == pytest.approx(100e-9)
    # (10, 30) + (50, 60) + (95, 100 clipped)
    assert red["busy_s"] == pytest.approx(35e-9)
    assert red["per_op_s"]["kernel"] == pytest.approx(20e-9)
    assert red["per_op_s"]["late"] == pytest.approx(5e-9)
    assert red["per_module_s"]["jit_step"] == pytest.approx(30e-9)
    idle = red["idle_by_span_s"]
    # a gap goes whole to the span that covers most of it: (0,10) and
    # (30,50) to run, (60,95) to report
    assert idle["bench.run"] == pytest.approx(30e-9)
    assert idle["bench.report"] == pytest.approx(35e-9)
    assert red["busy_s"] + sum(idle.values()) == pytest.approx(100e-9)


def test_no_device_reads_nothing():
    ex = {"ops": {}, "modules": {}, "spans": [WINDOW]}
    red = reduce_trace.reduce(ex, reduce_trace.window_of(ex))
    assert red["devices"] == 0 and red["busy_s"] == 0
    ctx = {"trace": red, "chunks": {"chunks": 3, "batched_chunks": 0},
           "placements": 10, "answers": 2}
    assert reduce_trace.kernel_us_per_placement(ctx, "chunks") is None
    assert reduce_trace.fast_path_ms_per_answer(ctx) is None


def test_kernel_reader_needs_its_own_kernel_alone():
    red = {"devices": 1, "window_s": 1.0, "busy_s": 0.5,
           "per_op_s": {reduce_trace.KERNEL_OP: 0.25}, "per_module_s": {},
           "idle_by_span_s": {}}
    ctx = {"trace": red, "chunks": {"chunks": 4, "batched_chunks": 0},
           "placements": 1000, "answers": 2}
    assert reduce_trace.kernel_us_per_placement(ctx, "chunks") == \
        pytest.approx(250.0)
    assert reduce_trace.kernel_us_per_placement(ctx, "batched_chunks") \
        is None


def test_host_spans_of_a_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.run"):
                jnp.arange(1000.0).sum().block_until_ready()
    ex = reduce_trace.extract(reduce_trace.xplane_path(str(tmp_path)))
    names = {n for n, _, _ in ex["spans"]}
    assert {"bench.window", "bench.run"} <= names
    lo, hi = reduce_trace.window_of(ex)
    assert hi > lo


RECORDED = os.path.join(TESTS, "data", "antiaffinity-tpu.xplane.pb")


def test_recorded_tpu_trace():
    """A trace recorded on one TPU v5 lite: a 2.2 s window of 12
    anti-affinity questions bounded at 1,000 placements on the
    scheduler_perf cluster, the single-template kernel in 12 chunks."""
    ex = reduce_trace.extract(RECORDED)
    assert list(ex["ops"]) == ["/device:TPU:0"]
    red = reduce_trace.reduce(ex, reduce_trace.window_of(ex))
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(2.224719586)
    assert red["busy_s"] == pytest.approx(0.009456451)
    assert red["per_op_s"]["%tpu_custom_call.1"] == pytest.approx(0.009217618)
    assert set(red["idle_by_span_s"]) <= {"bench.build", "bench.run",
                                         "bench.report",
                                         reduce_trace.IDLE_UNLABELLED}
    ctx = {"trace": red, "chunks": {"chunks": 12, "batched_chunks": 0},
           "placements": 12000, "answers": 12}
    assert reduce_trace.kernel_us_per_placement(ctx, "chunks") == \
        pytest.approx(0.7681348333)

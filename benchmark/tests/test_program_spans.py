"""The program's `cc.` spans reduced to per-layer host seconds, and the
readers that divide them by the window's answers."""

import importlib.util
import os

import pytest

from helpers import BENCH, TESTS

import program_spans
import reduce_trace

WINDOW = (100, 200)


def _reader(base):
    path = os.path.join(BENCH, "metrics", base + ".py")
    spec = importlib.util.spec_from_file_location("m_" + base, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_inclusive_and_self_seconds_nest_per_thread():
    recs = [("cc.encode", 1, 110, 150, {}),
            ("cc.encode.spread", 1, 115, 125, {}),
            ("cc.encode.affinity", 1, 125, 145, {}),
            ("cc.setup", 1, 150, 170, {}),
            ("cc.verify", 1, 155, 165, {}),
            # another thread's span never takes self time from thread 1
            ("cc.wait", 2, 120, 140, {})]
    red = program_spans.reduce(recs, WINDOW)
    inc, own = red["program_s"], red["program_self_s"]
    assert inc["cc.encode"] == pytest.approx(40e-9)
    assert own["cc.encode"] == pytest.approx(10e-9)
    assert own["cc.encode.spread"] == pytest.approx(10e-9)
    assert own["cc.encode.affinity"] == pytest.approx(20e-9)
    assert own["cc.setup"] == pytest.approx(10e-9)
    assert own["cc.verify"] == pytest.approx(10e-9)
    assert own["cc.wait"] == pytest.approx(20e-9)
    # (110, 170) covered on thread 1; thread 2 adds nothing outside it
    assert red["program_outside_s"] == pytest.approx(40e-9)
    # thread 1's self times tile what it covers
    assert sum(v for k, v in own.items() if k != "cc.wait") == \
        pytest.approx(60e-9)


def test_window_clips_spans_and_counts_args_by_start():
    recs = [("cc.issue", 1, 90, 110, {"steps": 1024, "lanes": 8}),
            ("cc.issue", 1, 120, 130, {"steps": 512, "lanes": 8}),
            ("cc.issue", 2, 150, 160, {"steps": 100, "lanes": 1}),
            ("cc.wait", 1, 190, 230, {}),
            ("cc.report", 1, 20, 60, {})]
    red = program_spans.reduce(recs, WINDOW)
    assert red["program_s"]["cc.issue"] == pytest.approx(30e-9)
    assert red["program_s"]["cc.wait"] == pytest.approx(10e-9)
    assert "cc.report" not in red["program_s"]
    # args: spans starting in the window only, summed across threads
    assert red["program_args"]["cc.issue"] == {"steps": 612, "lanes": 9}
    assert red["lane_steps"] == 512 * 8 + 100
    assert red["program_outside_s"] == pytest.approx(60e-9)


def test_no_span_covers_nothing():
    red = program_spans.reduce([], WINDOW)
    assert red["program_s"] == {} and red["lane_steps"] == 0
    assert red["program_outside_s"] == pytest.approx(100e-9)


def _ctx(devices=1, window_s=1.0, answers=4, placements=4000):
    return {"trace": {"devices": devices, "window_s": window_s},
            "answers": answers, "placements": placements,
            "chunks": {"chunks": 0, "batched_chunks": 0}}


READERS = ["encode_ms", "affinity_encode_ms", "spread_encode_ms",
           "solve_setup_ms", "kernel_verify_ms", "device_wait_ms",
           "diagnose_ms", "fast_sort_ms", "report_ms", "host_untraced_pct",
           "kernel_useful_pct"]


@pytest.mark.parametrize("base", READERS)
def test_reader_reads_nothing_without_a_device_plane(base, monkeypatch):
    recs = [("cc.encode", 1, 0, 10, {})]
    monkeypatch.setattr(program_spans, "from_collector", lambda: recs)
    assert _reader(base)(_ctx(devices=0)) is None


@pytest.mark.parametrize("base", READERS)
def test_reader_reads_nothing_where_no_program_span_ran(base, monkeypatch):
    monkeypatch.setattr(program_spans, "from_collector", lambda: [])
    assert _reader(base)(_ctx()) is None


def test_readers_divide_by_the_window_answers(monkeypatch):
    s = 10 ** 9
    recs = [("cc.report", 1, 0, 1 * s, {}),                    # warm-up
            ("cc.encode", 1, 2 * s, 3 * s, {}),
            ("cc.encode.affinity", 1, 2 * s, 2 * s + s // 2, {}),
            ("cc.issue", 1, 3 * s, 3 * s + s // 4,
             {"steps": 2000, "lanes": 2}),
            ("cc.report", 1, 3 * s + s // 2, 4 * s, {})]
    monkeypatch.setattr(program_spans, "from_collector", lambda: recs)
    ctx = _ctx(window_s=2.0, answers=2, placements=3000)
    read = {b: _reader(b)(ctx) for b in READERS}
    assert read["encode_ms"] == pytest.approx(500.0)          # inclusive
    assert read["affinity_encode_ms"] == pytest.approx(250.0)
    assert read["report_ms"] == pytest.approx(250.0)          # window only
    assert read["kernel_useful_pct"] == pytest.approx(75.0)
    # (2, 4) s: 1 s + 0.25 s + 0.5 s covered
    assert read["host_untraced_pct"] == pytest.approx(12.5)
    for b in ("spread_encode_ms", "solve_setup_ms", "kernel_verify_ms",
              "device_wait_ms", "diagnose_ms", "fast_sort_ms"):
        assert read[b] is None, b


def test_dropped_window_spans_read_nothing(monkeypatch):
    from cluster_capacity_tpu.obs.spans import default_collector
    s = 10 ** 9
    monkeypatch.setattr(program_spans, "from_collector",
                        lambda: [("cc.report", 1, 3 * s, 4 * s, {})])
    monkeypatch.setattr(default_collector, "dropped", 5)
    assert _reader("report_ms")(_ctx(window_s=2.0)) is None


def test_collector_records_of_a_cpu_solve():
    """The program's spans, read back from its collector, cover an
    answer's layers; the same spans land in a CPU profiler trace."""
    import tempfile

    import jax

    from cluster_capacity_tpu import ClusterCapacity
    from cluster_capacity_tpu.models.podspec import default_pod
    from cluster_capacity_tpu.models.snapshot import ClusterSnapshot
    from cluster_capacity_tpu.obs.spans import default_collector
    nodes = [{"metadata": {"name": f"n{i}"}, "spec": {},
              "status": {"allocatable": {"cpu": "2", "memory": "4Gi",
                                         "pods": "110"}}}
             for i in range(4)]
    pod = default_pod({"metadata": {"name": "p"}, "spec": {"containers": [
        {"name": "c", "resources": {"requests": {"cpu": "150m",
                                                 "memory": "100Mi"}}}]}})
    default_collector.reset()
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            cc = ClusterCapacity(pod)
            cc.set_snapshot(ClusterSnapshot.from_objects(nodes))
            cc.run()
            assert cc.report().replicas == 52
        traced = program_spans.from_trace(reduce_trace.xplane_path(d))
    collected = program_spans.from_collector()
    assert sorted(r[0] for r in traced) == sorted(r[0] for r in collected)
    names = {r[0] for r in collected}
    assert {"cc.encode", "cc.setup", "cc.report"} <= names
    lo = min(r[2] for r in collected)
    hi = max(r[3] for r in collected)
    red = program_spans.reduce(collected, (lo, hi))
    assert sum(red["program_self_s"].values()) \
        + red["program_outside_s"] == pytest.approx((hi - lo) * 1e-9)


RECORDED = os.path.join(TESTS, "data", "spread-tpu-spans.xplane.pb")


def test_recorded_tpu_trace_with_program_spans():
    """A trace recorded on one TPU v5 lite with `--trace 1`: a 21.4 s
    window of eight full spread-full answers (k8s-large-5k, seed
    2300000002), the single-template kernel in 107 chunks.  The program's
    spans tile the window with what no span covers."""
    ex = reduce_trace.extract(RECORDED)
    window = reduce_trace.window_of(ex)
    red = reduce_trace.reduce(ex, window)
    assert red["window_s"] == pytest.approx(21.430579856)
    recs = program_spans.from_trace(RECORDED)
    assert len(recs) == 136 and len({r[1] for r in recs}) == 1
    prog = program_spans.reduce(recs, window)
    assert prog["program_s"]["cc.encode"] == pytest.approx(2.647904, abs=1e-6)
    own = prog["program_self_s"]
    assert {k: round(v, 6) for k, v in own.items()} == {
        "cc.encode": 0.005159, "cc.encode.affinity": 1.721126,
        "cc.encode.spread": 0.921619, "cc.setup": 0.197349,
        "cc.verify": 17.370441, "cc.issue": 0.048296, "cc.wait": 0.309444,
        "cc.diagnose": 0.129014, "cc.report": 0.068976}
    assert prog["program_outside_s"] == pytest.approx(0.659155, abs=1e-6)
    assert sum(own.values()) + prog["program_outside_s"] == \
        pytest.approx(red["window_s"])
    assert prog["program_args"]["cc.issue"] == {"steps": 438272,
                                                "lanes": 32}
    # 421,496 placements over the steps issued
    assert 100 * 421496 / prog["lane_steps"] == pytest.approx(96.172240)

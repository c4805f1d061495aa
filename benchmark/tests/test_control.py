"""`correct` must come out false where the answers are wrong: for the
control (the reference in the program's place, with the property its
traffic file names broken) and for each fault a cell can have, planted
under the timed path."""

import time

import pytest

from helpers import cells, last_json, question, rehearsal

import control
import harness


@pytest.mark.parametrize("workload,config", cells())
def test_control_is_not_correct(workload, config):
    checks = control.readings(workload, 5, rehearsal(config))
    checks.pop("control")
    assert checks.pop("_correct") is False
    assert any(v["value"] > v["limit"] for v in checks.values())


# The control's numbers at rehearsal size on seed 5, as they read before
# each configuration's generator, reference and control became files of
# their own: the same cluster, reference and arithmetic give the same
# numbers.
READINGS = {
    "k8s5k-spread-full": ("bfloat16", {"node_gap": 0.10132450331125828,
                                       "reason_gap": 0.6666666666666666,
                                       "failed_answers": 0}),
    "sched5k-antiaffinity-full": ("namespaces_ignored",
                                  {"node_gap": 0.1, "failed_answers": 0}),
    "sched5k-basic-1k": ("bfloat16", {"node_gap": 0.58,
                                      "failed_answers": 0}),
    "k8s5k-sweep8-full": ("bfloat16", {"node_gap": 0.10132450331125828,
                                       "reason_gap": 0.6666666666666666,
                                       "failed_answers": 0}),
}


@pytest.mark.parametrize("workload", sorted(READINGS))
def test_control_readings_pinned(workload):
    config = dict(cells())[workload]
    checks = control.readings(workload, 5, rehearsal(config))
    kind, want = READINGS[workload]
    assert checks.pop("control") == kind
    assert checks.pop("_correct") is False
    assert {k: v["value"] for k, v in checks.items()} == want


def _placements_unchanged(result):
    """Every step returns the state it was given: the first node wins
    every step."""
    if result.placements:
        result.placements = [result.placements[0]] * len(result.placements)
    return result


def _answer_altered(result):
    """Each placement names the next node."""
    n = len(result.node_names)
    result.placements = [(i + 1) % n for i in result.placements]
    return result


def _half_left_out(results):
    """The second half of the batch answered with the first half's rows."""
    half = len(results) // 2
    for k in range(half, len(results)):
        src = results[k - half]
        results[k].placements = list(src.placements)
        results[k].placed_count = src.placed_count
        results[k].fail_counts = dict(src.fail_counts)
    return results


FAULTS = {"state_unchanged": _placements_unchanged,
          "answer_altered": _answer_altered,
          "half_batch_left_out": None}


def _plant(monkeypatch, workload, fault):
    from cluster_capacity_tpu.parallel import sweep as sweep_mod
    from cluster_capacity_tpu.runtime import degrade
    if question(workload) == "sweep":
        real = sweep_mod.sweep

        def broken(*a, **kw):
            results = real(*a, **kw)
            if fault == "half_batch_left_out":
                return _half_left_out(results)
            return [FAULTS[fault](r) for r in results]
        monkeypatch.setattr(sweep_mod, "sweep", broken)
    else:
        real = degrade.solve_one_guarded

        def broken(*a, **kw):
            return FAULTS[fault](real(*a, **kw))
        monkeypatch.setattr(degrade, "solve_one_guarded", broken)


CASES = [(w, c, f) for w, c in cells() for f in FAULTS
         if f != "half_batch_left_out" or question(w) == "sweep"]


@pytest.mark.parametrize("workload,config,fault", CASES)
def test_fault_is_not_correct(workload, config, fault, monkeypatch, capsys):
    _plant(monkeypatch, workload, fault)
    rc = harness.main(["--workload", workload, "--seed", "4000000003",
                       "--seconds", "1", "--trace", "0"],
                      rehearsal=rehearsal(config),
                      t_start=time.perf_counter())
    assert rc == 0
    line = last_json(capsys.readouterr().out)
    assert line["correct"] is False

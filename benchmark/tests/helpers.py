"""Shared set-up for the benchmark's own tests: paths, the CPU, and the
tiny sizes that stand in for each configuration in a rehearsal."""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {
    "k8s-large-5k": {"config": {"nodes": 60, "resident_pods": 1200}},
    "sched-perf-5k": {"config": {"nodes": 200, "init_pods": 40},
                      "traffic": {"max_limit": 50}},
    "sched-perf-5k-antiaffinity": {"config": {"nodes": 200, "init_pods": 40}},
}


def spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cells():
    return [(w["name"], w["config"]) for w in spec()["workloads"]]


def last_json(text: str) -> dict:
    return json.loads(text.strip().splitlines()[-1])

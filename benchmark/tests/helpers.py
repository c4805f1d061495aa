"""Shared set-up for the benchmark's own tests: paths, the CPU, and the
tiny sizes that stand in for each configuration in a rehearsal (its
file's "rehearsal" key)."""

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


def spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cells():
    return [(w["name"], w["config"]) for w in spec()["workloads"]]


def config(name: str) -> dict:
    """The configuration file that BENCHMARK.json names `name`."""
    entry = {c["name"]: c for c in spec()["configs"]}[name]
    with open(os.path.join(ROOT, entry["file"])) as f:
        return json.load(f)


def rehearsal(name: str) -> dict:
    """The overrides that cut configuration `name` to a CPU rehearsal:
    {"config": {...}, "traffic": {...}}, from its file's "rehearsal"
    key."""
    cfg = config(name)
    if "rehearsal" not in cfg:
        raise KeyError(f'{name}: the configuration file has no "rehearsal" '
                       'key, the sizes of its CPU rehearsal')
    return cfg["rehearsal"]


def question(workload: str) -> str:
    """The traffic file's question kind ("single" or "sweep") of a cell."""
    import harness
    return harness.load_cell(spec(), workload)["traffic"]["question"]


def last_json(text: str) -> dict:
    return json.loads(text.strip().splitlines()[-1])

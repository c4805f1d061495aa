"""The control for `correct`: the configuration's plain reference put in
the program's place with one of its stated properties broken.  It answers
every question of the cell's catalogue on the cell's cluster, and its
answers go through the same comparison as the program's.  The comparison
must find it not correct.

The traffic file names the control ("control"; "bfloat16" where it
names none): the file `controls/<name>.py`, whose `apply(pods,
templates)` returns (resident pods, catalogue, dtype) for the reference
to answer with.  Each control says in its docstring what it breaks.

    python benchmark/control.py --workload <name> --seeds 1,2,3

prints one JSON line per seed with the numbers compared and their limits.
It uses no accelerator and imports nothing of the program, so it can run
beside a benchmark run on the chip's host.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import compare  # noqa: E402
import gen  # noqa: E402
import harness  # noqa: E402


def readings(workload: str, seed: int, overrides: dict = None) -> dict:
    """The control's numbers for one seed: every template of the cell's
    catalogue answered by the control, compared as a run's answers are."""
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        c = harness.load_cell(json.load(f), workload)
    cfg, traffic = c["config"], c["traffic"]
    for key, part in (("config", cfg), ("traffic", traffic)):
        part.update((overrides or {}).get(key, {}))
    cluster = gen.make_cluster(cfg, seed)
    templates = gen.templates(traffic)
    kind = traffic.get("control", "bfloat16")
    pods, asked, dtype = gen.load_module(
        f"benchmark/controls/{kind}.py").apply(cluster["pods"], templates)
    reference = gen.load_module(cfg["reference"])
    rc = reference.Cluster(cluster["nodes"], pods)
    kept = [(k, compare.from_reference(reference.solve(
        rc, t, int(traffic["max_limit"]), dtype=dtype)))
        for k, t in enumerate(asked)]
    checks = harness.check(reference, cluster, templates, traffic, kept, 0)
    checks["control"] = kind
    return checks


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    args = p.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        checks = readings(args.workload, seed)
        kind = checks.pop("control")
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": kind,
                          "control_correct": checks.pop("_correct"),
                          "checks": checks,
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

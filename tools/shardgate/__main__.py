"""shardgate CLI: `python -m tools.shardgate`.

Default run = lower the full (entry x mesh) matrix on the virtual
8-device CPU backend, then run SP001-SP005 and the budget comparison.
Nothing executes a solve: trace, lower, and XLA-compile only.
Exit 0 = clean, 1 = findings.

Flags:

  --update-budgets   re-pin the collective budgets from this run
                     (tightening only — see --allow-looser)
  --allow-looser     permit --update-budgets to RAISE a collective
                     ceiling; the loosenings are printed so the commit
                     message can name them
  --json             print the machine-readable report to stdout
  --json-out FILE    write the same report to FILE (tools/ci.py runs
                     steps without a shell, so `>` is not available)
  --budgets PATH     compare against an alternate budgets file
  --fixture FILE     module defining make_cells() -> List[Cell] appended
                     to the matrix; may define BUDGETS, a dict merged
                     over the committed doc (tests seed regressions here)
  --only SUBSTR      run only entries whose name contains SUBSTR
  --meshes CSV       mesh lanes to run (default: ctl,1x1,2x4,4x2,8x1)
  --list             list the matrix and exit
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(_HERE))

# The mesh matrix needs 8 devices; the CPU backend fakes them.  All of
# this must land before anything imports jax (this jax build reads
# XLA_FLAGS and JAX_PLATFORMS at import).  CC_TPU_FUSED=0 keeps the
# Pallas fused path out of the lowering we budget.
_FLAG = "--xla_force_host_platform_device_count=8"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " " + _FLAG).strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["CC_TPU_FUSED"] = "0"


def _load_fixture(path: str):
    spec = importlib.util.spec_from_file_location("shardgate_fixture", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m tools.shardgate")
    ap.add_argument("--update-budgets", action="store_true")
    ap.add_argument("--allow-looser", action="store_true")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--json-out", metavar="FILE")
    ap.add_argument("--budgets", metavar="PATH")
    ap.add_argument("--fixture", metavar="FILE")
    ap.add_argument("--only", metavar="SUBSTR")
    ap.add_argument("--meshes", metavar="CSV")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args(argv)

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", False)

    from . import MESH_MATRIX, SCALE_LADDER
    from . import budgets as budgets_mod
    from . import comms, memory, padcheck, partition, readback
    from .entries import ENTRIES
    from .lowering import CTL, build_cells

    entries = tuple(e for e in ENTRIES
                    if not args.only or args.only in e)
    lanes = tuple((args.meshes or ",".join((CTL,) + MESH_MATRIX)).split(","))
    if args.list:
        for e in entries:
            for m in lanes:
                print(f"{e}|{m}")
        return 0

    doc0 = budgets_mod.load(args.budgets or budgets_mod.DEFAULT_PATH)
    if doc0 is None:
        print("shardgate: no budgets file — seed one with --update-budgets",
              file=sys.stderr)
        return 1
    partial = bool(args.only or args.meshes)

    t0 = time.time()
    cells, findings = build_cells(
        mesh_names=tuple(m for m in lanes if m != CTL),
        entries=entries, include_ctl=CTL in lanes)

    fixture_mod = None
    if args.fixture:
        fixture_mod = _load_fixture(args.fixture)
        make_cells = getattr(fixture_mod, "make_cells", None)
        if make_cells is not None:
            cells = list(cells) + list(make_cells())
        fb = dict(getattr(fixture_mod, "BUDGETS", {}))
        merged = dict(doc0)
        for key, val in fb.items():
            if isinstance(val, dict) and isinstance(merged.get(key), dict):
                merged[key] = {**merged[key], **val}
            else:
                merged[key] = val
        doc0 = merged

    # SP001 partition coverage, SP004 padding — per cell, trace layer only
    for cell in cells:
        try:
            findings.extend(padcheck.check_padding(cell))
            findings.extend(partition.check_partition(cell, doc0))
        except Exception as e:                            # noqa: BLE001
            from . import Finding
            findings.append(Finding(
                cell.entry, cell.mesh_name, "SP000",
                f"rule crashed: {type(e).__name__}: {e}"))

    # SP002 communication audit (compiles every cell), SP003 memory model
    coll_table = {}
    comm_findings = comms.check_comms(cells, doc0, coll_table)
    mem_table = {}
    findings.extend(memory.check_memory(cells, doc0, mem_table))
    verdicts = memory.verdicts(mem_table, doc0, cells)

    # SP005 host-readback audit — repo-level, once
    findings.extend(readback.check_readbacks(ROOT, doc0))

    # budgets: re-pin or compare
    if args.update_budgets:
        if partial:
            print("shardgate: refusing --update-budgets on a partial run "
                  "(--only/--meshes)", file=sys.stderr)
            return 1
        new_pins = comms.repin(coll_table)
        wrote, worse = budgets_mod.update(
            doc0, new_pins, allow_looser=args.allow_looser,
            path=args.budgets or budgets_mod.DEFAULT_PATH)
        for line in worse:
            print(f"shardgate: LOOSER pin: {line}")
        if not wrote:
            print("shardgate: refused to loosen collective pins "
                  "(re-run with --allow-looser to accept)", file=sys.stderr)
            return 1
        print(f"shardgate: pinned collective budgets for "
              f"{len(new_pins)} cell(s)")
    else:
        findings.extend(comm_findings)

    # report
    report = {
        "shardgate": 1,
        "clean": not findings,
        "elapsed_s": round(time.time() - t0, 2),
        "scales": list(SCALE_LADDER),
        "findings": [
            {"entry": f.entry, "mesh": f.mesh, "rule": f.rule,
             "scale": f.scale, "message": f.message}
            for f in findings],
        "cells": {c.name: dict(c.meta) for c in cells},
        "collectives": coll_table,
        "memory": {name: {str(s): b for s, b in row.items()}
                   for name, row in sorted(mem_table.items())},
        "verdicts": verdicts,
    }
    if args.json_out:
        with open(args.json_out, "w", encoding="utf-8") as fh:
            json.dump(report, fh, indent=2)
            fh.write("\n")
    if args.json:
        print(json.dumps(report, indent=2))
    else:
        for f in findings:
            print(f.render())
        hbm = int(doc0["device_hbm_bytes"])
        for entry in sorted(verdicts):
            v = verdicts[entry]
            parts = []
            for scale in ("65536", "100000"):
                d = v[scale]
                state = "fits" if d["fits"] else \
                    f"SHORT {d['shortfall_bytes']:,}B"
                parts.append(f"{int(scale) // 1000}k {state} "
                             f"[{d['best_mesh']}] "
                             f"{d['per_device_bytes'] / 2**30:.2f}GiB")
            print(f"SHARDGATE_{entry}: {' | '.join(parts)} "
                  f"(hbm {hbm / 2**30:.0f}GiB)")
        print(f"shardgate: {len(cells)} cells, {len(findings)} finding(s) "
              f"in {report['elapsed_s']}s")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())

"""irgate CLI: `python -m tools.irgate`.

Default run = guard-dispatch audit + Mosaic BlockSpec lint (folded in from
engine/mosaic_lint) + IR contracts + budget comparison over the canonical
entry ladder.  Exit 0 = clean, 1 = findings.

Flags:

  --update-budgets   rewrite tools/irgate/budgets.json from this run
  --json             print the machine-readable report to stdout
  --json-out FILE    write the same report to FILE (tools/ci.py runs steps
                     without a shell, so `>` redirection is not available)
  --budgets PATH     compare against an alternate budgets file
  --fixture FILE     also load EntrySpecs from FILE (module must define
                     make_entries() -> List[EntrySpec]; may define BUDGETS,
                     a dict merged over the committed pins — used by tests
                     to seed synthetic regressions)
  --only SUBSTR      run only entries whose name contains SUBSTR (skips
                     stale-budget checks, since the run is partial)
  --list             list canonical entries and exit
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

# irgate is CPU-only by contract: lowering needs no accelerator, and the
# committed budgets assume the CPU lowering path with x64 disabled.
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _load_fixture(path: str):
    spec = importlib.util.spec_from_file_location("irgate_fixture", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m tools.irgate")
    ap.add_argument("--update-budgets", action="store_true")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--json-out", metavar="FILE")
    ap.add_argument("--budgets", metavar="PATH")
    ap.add_argument("--fixture", metavar="FILE")
    ap.add_argument("--only", metavar="SUBSTR")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args(argv)

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)

    import jax

    jax.config.update("jax_enable_x64", False)

    from . import budgets as budgets_mod
    from . import capture as cap
    from . import contracts, costs, entries, guard_audit

    specs = entries.canonical_entries()
    fixture_budgets = {}
    if args.fixture:
        fx = _load_fixture(args.fixture)
        specs = list(specs) + list(fx.make_entries())
        fixture_budgets = dict(getattr(fx, "BUDGETS", {}))
    if args.only:
        specs = [s for s in specs if args.only in s.name]
    if args.list:
        for s in specs:
            print(f"{s.name:24s} rung={s.rung} env={s.env}")
        return 0

    t0 = time.time()
    findings = []          # list of (kind, render_str, dict)

    def add(kind, obj):
        doc = {"kind": kind, "rule": getattr(obj, "rule", kind),
               "message": getattr(obj, "message", str(obj))}
        for attr in ("entry", "computation", "path", "line"):
            if hasattr(obj, attr):
                doc[attr] = getattr(obj, attr)
        findings.append((obj.render() if hasattr(obj, "render")
                         else f"irgate: {obj}", doc))

    # 1. guard-dispatch audit (pure AST, no jax needed)
    audit_findings, audited = guard_audit.audit_tree(ROOT)
    for f in audit_findings:
        add("guard_audit", f)

    # 2. Mosaic BlockSpec lint fold-in (satellite: same diagnostic stream)
    mosaic = entries.mosaic_findings()
    for v in mosaic:
        findings.append((f"irgate: mosaic ML001: {v}",
                         {"kind": "mosaic", "rule": "ML001", "message": v}))

    # 3. capture + contracts + costs over the entry ladder
    cap.install()
    measured = {}
    entry_docs = {}
    for spec in specs:
        ec = entries.run_entry(spec)
        comps = ec.computations
        if spec.expect_no_dispatch and comps:
            add("contract", contracts.IrFinding(
                spec.name, comps[0].key, "IC006",
                f"entry must not dispatch device computations but "
                f"captured {len(comps)} (the {spec.rung} rung is the "
                f"host-side refuge)"))
        summaries = {}
        for comp in comps:
            for f in contracts.check_captured(spec.name, comp, spec.policy):
                add("contract", f)
            summaries[comp.key] = costs.cost_summary(comp.closed_jaxpr)
        rollup = costs.merge_summaries(summaries.values())
        measured[spec.name] = rollup
        entry_docs[spec.name] = {
            "rung": spec.rung,
            **rollup,
            "computations": summaries,
        }
    cap.uninstall()

    # 4. budgets
    budget_path = args.budgets or budgets_mod.DEFAULT_PATH
    if args.update_budgets:
        budgets_mod.save(measured, budget_path)
        print(f"irgate: wrote {len(measured)} entry budget(s) to "
              f"{os.path.relpath(budget_path, ROOT)}")
        pins = budgets_mod.load(budget_path)
    else:
        pins = budgets_mod.load(budget_path)
        if pins and fixture_budgets:
            pins = dict(pins)
            pins["entries"] = {**pins.get("entries", {}), **fixture_budgets}
        budget_findings = budgets_mod.compare(measured, pins)
        if args.only:
            budget_findings = [f for f in budget_findings
                               if f.rule != "BG003"]
        for f in budget_findings:
            add("budget", f)

    delta = budgets_mod.deltas(measured, pins)

    # 5. report
    doc = {
        "irgate": 1,
        "clean": not findings,
        "elapsed_s": round(time.time() - t0, 2),
        "findings": [d for _, d in findings],
        "entries": entry_docs,
        "budget_delta_pct": delta,
        "guard_audit": {"files": audited, "findings": len(audit_findings)},
        "mosaic": {"findings": len(mosaic)},
    }
    if args.json_out:
        with open(args.json_out, "w", encoding="utf-8") as fh:
            json.dump(doc, fh, indent=2)
            fh.write("\n")
    if args.json:
        print(json.dumps(doc, indent=2))
    else:
        for line, _ in findings:
            print(line)
        for name in sorted(delta):
            d = delta[name]
            print(f"IRGATE_{name}: prims {d['primitives']:+.1f}% "
                  f"flops {d['flops']:+.1f}% live {d['live_bytes']:+.1f}%")
        n_comp = sum(len(e["computations"]) for e in entry_docs.values())
        print(f"irgate: {len(entry_docs)} entries, {n_comp} computations, "
              f"{audited} modules audited, {len(findings)} finding(s) "
              f"in {doc['elapsed_s']}s")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())

"""Self-contained lint gate (`make lint`).

The reference verifies formatting and boilerplate in CI (`make
verify-gofmt`, golangci-lint, `verify/verify-boilerplate.sh` —
/root/reference/Makefile:41,54-66).  This image ships no Python linter, so
this checker implements the equivalent gate with the standard library only:

- every .py file byte-compiles (syntax gate);
- no trailing whitespace, no tab indentation, no CRLF line endings,
  files end with exactly one newline;
- boilerplate analog: every non-test module starts with a docstring
  (modules are required to carry their reference citations there);
- no debugger-invocation leftovers.

Exit code 0 = clean; 1 = findings (printed one per line, file:line: msg).
"""

from __future__ import annotations

import ast
import py_compile
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
TARGETS = ["cluster_capacity_tpu", "tests", "bench.py",
           "__graft_entry__.py", "tools"]
SKIP_PARTS = {"__pycache__", ".git", "build", "dist"}


def py_files():
    for t in TARGETS:
        p = ROOT / t
        if p.is_file():
            yield p
        elif p.is_dir():
            for f in sorted(p.rglob("*.py")):
                if not (SKIP_PARTS & set(f.parts)):
                    yield f


def main() -> int:
    findings = []

    def add(f: Path, line, msg: str):
        findings.append(f"{f.relative_to(ROOT)}:{line}: {msg}")

    for f in py_files():
        raw = f.read_bytes()
        try:
            py_compile.compile(str(f), doraise=True, cfile=None)
        except py_compile.PyCompileError as e:
            add(f, getattr(e.exc_value, "lineno", 0), f"syntax error: {e.msg}")
            continue
        if b"\r\n" in raw:
            add(f, 0, "CRLF line endings")
        if raw and not raw.endswith(b"\n"):
            add(f, 0, "missing trailing newline")
        if raw.endswith(b"\n\n\n"):
            add(f, 0, "multiple trailing blank lines")
        text = raw.decode("utf-8", errors="replace")
        for i, line in enumerate(text.splitlines(), 1):
            if line != line.rstrip():
                add(f, i, "trailing whitespace")
            stripped_prefix = line[:len(line) - len(line.lstrip())]
            if "\t" in stripped_prefix:
                add(f, i, "tab indentation")
            if "breakpoint" + "()" in line or "pdb.set_" + "trace" in line:
                add(f, i, "debugger leftover")
        # boilerplate: non-test, non-__init__ modules carry a docstring
        rel = f.relative_to(ROOT)
        if rel.parts[0] == "cluster_capacity_tpu" and \
                f.name != "__init__.py":
            tree = ast.parse(text)
            if ast.get_docstring(tree) is None:
                add(f, 1, "module missing docstring (reference citations "
                          "live there)")

    for line in findings:
        print(line)
    n = len(findings)
    print(f"lint: {n} finding(s) in {sum(1 for _ in py_files())} files"
          if n else f"lint: clean ({sum(1 for _ in py_files())} files)")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())

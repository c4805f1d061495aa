"""Structured error taxonomy for the hardened runtime.

Every failure the solve supervisor knows how to recover from is a
RuntimeFault subclass with a stable `code` (machine-readable, shows up in
reports and journals), the injection/dispatch `site` it was observed at, and
a free-form `detail` dict.  Anything that is NOT a RuntimeFault — an XLA
INVALID_ARGUMENT, a plain Python bug — propagates raw on purpose: degrading
to a lower rung would paper over an engine defect and silently serve wrong
numbers, while OOM/timeout/corruption are environmental and the ladder's
rungs are proven bit-identical.

This module is a leaf (no package imports) so models/ and utils/ can raise
these without cycles.
"""

from __future__ import annotations

from typing import Optional


class RuntimeFault(Exception):
    """Base class: a classified, recoverable solve failure."""

    code = "RuntimeFault"

    def __init__(self, message: str = "", *, site: str = "",
                 detail: Optional[dict] = None):
        super().__init__(message)
        self.site = site
        self.detail = dict(detail or {})

    def __str__(self) -> str:
        base = super().__str__()
        return f"[{self.code}@{self.site}] {base}" if self.site \
            else f"[{self.code}] {base}"


class DeviceOOM(RuntimeFault):
    """Accelerator allocation failure (XLA RESOURCE_EXHAUSTED / host
    MemoryError).  Recoverable: split the batch or drop a rung."""

    code = "DeviceOOM"


class CompileTimeout(RuntimeFault):
    """Compilation did not finish within the wall-clock deadline (the
    pathological-geometry XLA/Mosaic compile hang)."""

    code = "CompileTimeout"


class ExecuteTimeout(RuntimeFault):
    """A dispatched computation did not produce results within the
    wall-clock deadline."""

    code = "ExecuteTimeout"


class NumericCorruption(RuntimeFault):
    """A solve returned planes that cannot be valid: NaN counts, negative
    placement indices, counts disagreeing with the placement list."""

    code = "NumericCorruption"


class KernelFault(RuntimeFault):
    """A Pallas kernel compiled for the chip failed to compile, failed at
    run time, or disagreed with the XLA scan it is cross-checked against.
    The kernel never hands its work to the XLA scan in silence: the fault
    crosses the guard like any other, so the ladder records it and
    `--strict` fails on it."""

    code = "KernelFault"


class SnapshotValidationError(RuntimeFault):
    """Malformed or partial snapshot input.  `field_path` names the exact
    offending field (e.g. ``nodes[3].status.allocatable.cpu``) instead of
    surfacing a bare KeyError/IndexError from deep inside encoding."""

    code = "SnapshotValidation"

    def __init__(self, message: str = "", *, field_path: str = "",
                 site: str = "", detail: Optional[dict] = None):
        detail = dict(detail or {})
        if field_path:
            detail.setdefault("field_path", field_path)
        super().__init__(message, site=site, detail=detail)
        self.field_path = field_path

    def __str__(self) -> str:
        base = Exception.__str__(self)
        path = f" at {self.field_path}" if self.field_path else ""
        return f"[{self.code}{path}] {base}"


class CheckpointCorruption(RuntimeFault):
    """A .npz checkpoint bundle or scenario journal failed its checksum,
    is truncated, or belongs to a different run."""

    code = "CheckpointCorruption"

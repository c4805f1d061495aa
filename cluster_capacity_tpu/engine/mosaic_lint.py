"""Static Mosaic BlockSpec constraint checks, runnable OFF hardware.

The batched kernel's SMEM BlockSpec `(1, 4)` on a `[B, 4]` array once
violated Mosaic's sublane-divisibility rule ("block shape (1, 4) ...
smem"), found only at run time on the chip.  Pallas in interpret mode (the
CPU test suite) cannot catch lowering constraints — they only exist in the
Mosaic compiler — so this module encodes the constraint set statically and
the kernels' spec tables are linted in the default CPU suite
(tests/test_mosaic_lint.py) and again when a kernel is built (a violation
raises before anything reaches the device; tests/test_tpu_compile.py
compiles the kernels for a described v5e as the fuller check).

Rules encoded (Pallas/Mosaic TPU, float32/int32 operands — the only dtypes
these kernels move through blocked refs):

1. A blocked dimension must tile the array dimension exactly
   (array_dim % block_dim == 0) — a ragged final block changes the
   program's shape per grid step, which Mosaic rejects for these kernels.
2. VMEM: the last (lane) block dim must equal the array dim or be a
   multiple of 128; the second-to-last (sublane) block dim must equal the
   array dim or be a multiple of 8 (float32 min tile (8, 128)).
3. SMEM: scalars move as >=2-D blocks; the sublane (second-to-last) block
   dim must equal the array dim or be a multiple of 8 — the exact rule the
   round-3 `(1, 4)` block violated (1 != B and 1 % 8 != 0).

The kernels build a _SpecTable (plain data: block shape + array shape +
memory space per operand) through one code path shared by the real
pl.pallas_call construction and this linter, so the lint cannot drift from
what actually lowers.
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence, Tuple

SUBLANE = 8          # float32 sublane tile
LANE = 128           # lane tile (all dtypes)


class SpecEntry(NamedTuple):
    name: str                        # operand label for messages
    block_shape: Tuple[int, ...]     # concrete block dims (no None/mapped)
    array_shape: Tuple[int, ...]     # full operand shape
    memory_space: str                # "vmem" | "smem"


def check_entry(e: SpecEntry) -> List[str]:
    """Violation strings for one operand spec (empty = clean)."""
    out: List[str] = []
    bs, ash = e.block_shape, e.array_shape
    if len(bs) != len(ash):
        out.append(f"{e.name}: block rank {len(bs)} != array rank {len(ash)}")
        return out
    for d, (b, a) in enumerate(zip(bs, ash)):
        if b <= 0:
            out.append(f"{e.name}: dim {d}: non-positive block dim {b}")
        elif a % b != 0:
            out.append(f"{e.name}: dim {d}: block {b} does not tile "
                       f"array dim {a}")
    if e.memory_space == "smem":
        if len(bs) < 2:
            out.append(f"{e.name}: smem blocks must be >= 2-D, got rank "
                       f"{len(bs)}")
        else:
            b, a = bs[-2], ash[-2]
            if b != a and b % SUBLANE != 0:
                out.append(
                    f"{e.name}: smem sublane block dim {b} is neither the "
                    f"array dim {a} nor a multiple of {SUBLANE}")
    elif e.memory_space == "vmem":
        if len(bs) >= 1:
            b, a = bs[-1], ash[-1]
            if b != a and b % LANE != 0:
                out.append(
                    f"{e.name}: vmem lane block dim {b} is neither the "
                    f"array dim {a} nor a multiple of {LANE}")
        if len(bs) >= 2:
            b, a = bs[-2], ash[-2]
            if b != a and b % SUBLANE != 0:
                out.append(
                    f"{e.name}: vmem sublane block dim {b} is neither the "
                    f"array dim {a} nor a multiple of {SUBLANE}")
    else:
        out.append(f"{e.name}: unknown memory space {e.memory_space!r}")
    return out


def check_table(entries: Sequence[SpecEntry]) -> List[str]:
    out: List[str] = []
    for e in entries:
        out.extend(check_entry(e))
    return out


def assert_clean(entries: Sequence[SpecEntry], what: str) -> None:
    """Raise ValueError listing every violation (kernel-build guard: on the
    chip the runner turns it into a KernelFault, in interpret mode into a
    logged fallback to the XLA scan)."""
    violations = check_table(entries)
    if violations:
        raise ValueError(
            f"mosaic lint: {what}: " + "; ".join(violations))

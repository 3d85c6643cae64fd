"""Bytes the fixpoint sweeps cannot do without.

One Jacobi sweep has to read every register once and write it once
(``2 * n * J`` int8 bytes) and read each real edge's operands once
(source, destination, hash, interval start and threshold: 20 bytes).
The count of sweeps is the plain reference's on the same data, so it
depends on the data and never on the implementation.
"""
from __future__ import annotations

EDGE_OPERAND_BYTES = 20


def sweep_least_bytes(n: int, m: int, registers: int) -> int:
    return 2 * n * registers + EDGE_OPERAND_BYTES * m


def fixpoint_least_bytes(n: int, m: int, registers: int, sweeps: int) -> int:
    return sweeps * sweep_least_bytes(n, m, registers)

"""Operations and bytes that a solve needs, from its shapes alone.

A roofline share divides the least time the chip could take by the time
it took.  The least time comes from the compulsory traffic: what the
problem says must be read and written, never what an implementation
happens to move, and never a count that grows with iterations.
"""
from __future__ import annotations

F32 = 4
#: per-device leaves a round's solve must read: distance, bandwidth,
#: energy budget, dataset size, cycles per sample, CPU clock, objective
#: weight and the round's channel gain
SOLVE_INPUTS = 8
#: per-device answers it must write: a* and P*
SOLVE_OUTPUTS = 2


def fused_solve_bytes(n_devices: int) -> int:
    """Compulsory HBM bytes of one round's solve of ``n_devices``."""
    return n_devices * F32 * (SOLVE_INPUTS + SOLVE_OUTPUTS)


def roofline_share(nbytes: float, seconds: float, bytes_per_s: float
                   ) -> float:
    """Percent of the bandwidth roofline reached: (bytes / peak) / time."""
    return 100.0 * (nbytes / bytes_per_s) / seconds


"""Plain reference for problem (7): Algorithm 2 of arXiv:2401.07756.

The configurations ``drifting_metro`` and ``paper_cells_1m`` both state
one deployment of the same problem, so they share this reference.  It is
written from the paper's equations in NumPy, imports nothing of the
program under test, and takes the generator's raw arrays
(``gen.DEVICE_FIELDS`` plus the channel gains ``g``):

* rate ``r(P) = B log2(1 + P g / (d^2 sigma^2))``, time ``T(P) = S / r(P)``
  (eq. 1);
* computation energy ``E^c = kappa C |D| gamma^2`` (eq. 5);
* selection step, eq. (13) with the time term ``tau / T(P)`` (the paper
  prints ``tau / (S T)``, which violates its own constraint (7c)):
  ``a = min(1, tau / T(P), E^max / (P T(P) + E^c))``, and ``a = 0`` at
  ``P = 0``;
* power step, the optimum of the fractional program (9) that Algorithm 1
  (Dinkelbach) converges to: the ratio ``P / log(1 + cP)`` increases in
  ``P``, so ``P* = min(P^min(a), P^max)`` with
  ``P^min(a) = (2^{a S / (B tau)} - 1) / (g / (d^2 sigma^2))``;
* the energy test of Algorithm 2 line 4: the element keeps its previous
  ``a`` unless ``a P* T(P*) <= E^max - a E^c`` and ``P^min(a) <= P^max``;
* start at ``P^max`` and stop when no element's ``a`` moved by ``eps``.

``dtype`` is the arithmetic of every step: ``float64`` for the
reference, and the next precision below the configuration's ``float32``
(``bfloat16``) for the control that a comparison must fail.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

PRECISIONS = {"float64": np.float64, "float32": np.float32,
              "bfloat16": ml_dtypes.bfloat16}


def solve(dev: dict, gain: np.ndarray, statics: dict, *,
          dtype: str = "float64", eps: float = 1e-7, max_iters: int = 50,
          ) -> tuple[np.ndarray, np.ndarray]:
    """``(a*, P*)`` for every element of ``gain``'s shape.

    ``dev`` maps each per-device field to an array that broadcasts
    against ``gain`` (``[N]`` against ``[N]``, or ``[M, N]`` for ``M``
    cells at once).  The arrays come back in ``dtype``.
    """
    dt = PRECISIONS[dtype]

    def c(x):
        return np.asarray(x, dt)

    zero, one = c(0.0), c(1.0)
    s_bits, tau, p_max = (c(statics["grad_size_bits"]), c(statics["tau_th"]),
                          c(statics["p_max"]))
    d = c(dev["distance_m"])
    bw = c(dev["bandwidth_hz"])
    emax = c(dev["energy_budget_j"])
    pg = c(gain) / (d * d * c(statics["noise_power"]))
    ec = (c(statics["kappa"]) * c(dev["cycles_per_sample"])
          * c(dev["dataset_size"]) * c(dev["cpu_hz"]) * c(dev["cpu_hz"]))

    def tx_time(p):
        rate = bw * np.log2(one + p * pg)
        return s_bits / np.maximum(rate, c(1e-30))

    def select(p):
        t = tx_time(p)
        a = np.minimum(np.minimum(one, tau / t),
                       emax / np.maximum(p * t + ec, c(1e-30)))
        return np.where(p > zero, a, zero)

    def p_min(a):
        x = np.minimum(a * s_bits / (bw * tau), c(120.0)) * c(np.log(2.0))
        return np.where(pg > zero, np.expm1(x) / np.where(pg > zero, pg, one),
                        c(np.inf))

    p_star = np.broadcast_to(p_max, pg.shape).astype(dt)
    a = select(p_star)
    for _ in range(max_iters):
        pm = np.maximum(p_min(a), zero)
        p_star = np.minimum(pm, p_max)
        energy = np.where(a > zero, a * p_star * tx_time(p_star), zero)
        ok = (energy <= emax - a * ec + c(1e-9)) & \
            (pm <= p_max * c(1.0 + 1e-6))
        a_new = np.where(ok, select(p_star), a)
        moved = np.max(np.abs(a_new.astype(np.float64)
                              - a.astype(np.float64)), initial=0.0)
        a = a_new
        if moved < eps:
            break
    return a.astype(dt), p_star.astype(dt)

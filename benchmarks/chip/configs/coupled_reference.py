"""Plain reference for a coupled metro tick (``metro_uma_hex``).

C cells interfere with each other and share one backhaul budget.
:func:`layout_coupling` builds the layout's coupling ``G [C, C]`` (W/W) from
the configuration file's own constants and the points' seed.  Given
a tick's data (the generator's device arrays ``[C, N]``, the channel
gains ``[C, N]``, the coupling ``G [C, C]`` in W/W, the budget in bits)
and an interference estimate ``I [C]`` in W, :func:`solve`:

* solves each cell by Algorithm 2 (``alg2_reference``) at the raised
  noise floor ``sigma^2 + I_c``: the SINR per watt is
  ``g / (d^2 (sigma^2 + I_c))``;
* projects the per-device caps ``a*`` onto the budget
  ``sum a S <= budget`` with an exact continuous knapsack
  (:func:`knapsack`): fill by decreasing weight, ties in the order of
  the devices, with one fractional marginal device, whose weight is the
  budget's price ``mu`` (0 where the caps fit);
* recomputes the interference those answers make,
  ``I_c = sum_c' G[c, c'] sum_i a_c'i P_c'i`` (:func:`interference`).

:func:`equilibrium` iterates that map, damped, to its fixed point.  It
is written from the equations in NumPy and imports nothing of the
program under test.  ``dtype`` is the arithmetic of every step, as in
``alg2_reference``: ``float64`` for the reference, ``bfloat16`` for the
control that a comparison must fail.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np


def _alg2():
    path = Path(__file__).with_name("alg2_reference.py")
    spec = importlib.util.spec_from_file_location("coupled_alg2_reference",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


alg2 = _alg2()
PRECISIONS = alg2.PRECISIONS


def sites(rings: int, isd_m: float) -> np.ndarray:
    """``[1 + 3 R (R + 1), 2]`` site positions (m): every axial pair
    ``(q, r)`` with ``|q|, |r|, |q + r| <= R``, ``q`` the outer loop and
    ``r`` the inner one, each from ``-R`` up, at ``isd (q + r / 2,
    r sqrt(3) / 2)``."""
    out = [(isd_m * (q + r / 2.0), isd_m * r * np.sqrt(3.0) / 2.0)
           for q in range(-rings, rings + 1)
           for r in range(-rings, rings + 1) if abs(q + r) <= rings]
    return np.array(out, np.float64)


def layout_coupling(cfg: dict, seed: int, *,
                    dtype: str = "float64") -> np.ndarray:
    """``G [C, C]`` of the configuration's TR 38.901 UMa metro (W/W).

    Cell ``c = 3 s + k`` is sector ``k`` of site ``s`` (:func:`sites`),
    boresight ``cfg["sector_boresights_deg"][k]``.  Its points are drawn
    as ``u = numpy.random.default_rng(seed).uniform(size=(S, 3, P, 2))``
    with ``P = cfg["coupling_points"]``: point ``(s, k, j)`` is the site
    plus ``u[..., 0]`` times the hexagon corner 60 degrees clockwise of
    the boresight and ``u[..., 1]`` times the one 60 degrees
    anticlockwise, each ``isd / sqrt(3)`` from the site (the third of
    the site's hexagon that the sector faces).  ``G[c, c']`` is the mean
    over sector c''s points of ``10^((A_c(phi) - PL(d3D)) / 10)``:
    ``PL = a + b log10(d3D) + c log10(fc) - e (h_UT - 1.5)`` with
    ``(a, b, c, e) = cfg["pl_uma_nlos_db"]``, ``d3D`` from the ground
    distance to cell c's site (at least ``min_distance_2d_m``) and the
    height ``h_BS - h_UT``; ``A_c(phi) = -min(12 (phi / phi_3dB)^2,
    A_m)`` at the point's bearing from cell c's site off its boresight,
    wrapped to [-180, 180).  The diagonal is 0.  ``dtype`` is the
    arithmetic of the gains: ``bfloat16`` is the control.
    """
    dt = PRECISIONS[dtype]
    isd = float(cfg["isd_m"])
    where = sites(int(cfg["rings"]), isd)
    bore = np.radians(np.asarray(cfg["sector_boresights_deg"], np.float64))
    radius = isd / np.sqrt(3.0)
    cw = radius * np.stack([np.cos(bore - np.pi / 3),
                            np.sin(bore - np.pi / 3)], axis=-1)    # [3, 2]
    acw = radius * np.stack([np.cos(bore + np.pi / 3),
                             np.sin(bore + np.pi / 3)], axis=-1)
    u = np.random.default_rng(seed).uniform(
        size=(len(where), 3, int(cfg["coupling_points"]), 2))
    x = (where[:, None, None, 0] + u[..., 0] * cw[None, :, None, 0]
         + u[..., 1] * acw[None, :, None, 0]).reshape(len(where) * 3, -1)
    y = (where[:, None, None, 1] + u[..., 0] * cw[None, :, None, 1]
         + u[..., 1] * acw[None, :, None, 1]).reshape(len(where) * 3, -1)
    a_db, b_db, c_db, e_db = (float(v) for v in cfg["pl_uma_nlos_db"])
    h_ut = float(cfg["h_ut_m"])
    height = dt(float(cfg["h_bs_m"]) - h_ut)
    pl_fixed = dt(a_db + c_db * np.log10(float(cfg["fc_ghz"]))
                  - e_db * (h_ut - 1.5))
    phi_3db = dt(cfg["sector_phi_3db_deg"])
    a_m = dt(cfg["sector_a_m_db"])
    n = 3 * len(where)
    g = np.zeros((n, n), np.float64)
    for s, (sx, sy) in enumerate(where):     # the three sectors of a site
        dx, dy = x - sx, y - sy                                    # [C, P]
        ground = np.maximum(np.sqrt(dx * dx + dy * dy),
                            float(cfg["min_distance_2d_m"])).astype(dt)
        pl = pl_fixed + dt(b_db) * np.log10(np.sqrt(ground * ground
                                                    + height * height))
        bearing = np.degrees(np.arctan2(dy, dx))
        for k, b in enumerate(np.asarray(cfg["sector_boresights_deg"])):
            off = (bearing - float(b)) % 360.0
            off = np.where(off >= 180.0, off - 360.0, off).astype(dt)
            pattern = -np.minimum(dt(12.0) * (off / phi_3db) ** 2, a_m)
            gain = np.power(dt(10.0), (pattern - pl) / dt(10.0))
            g[3 * s + k] = np.mean(gain.astype(np.float64), axis=1)
    np.fill_diagonal(g, 0.0)
    return g


def coupling_gap(got: np.ndarray, ref: np.ndarray) -> float:
    """Widest entry-wise relative gap of ``got`` from ``ref`` (an entry
    that ``ref`` holds at 0, the diagonal, counts its whole value)."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        return float("inf")
    return float(np.max(np.abs(got - ref)
                        / np.where(ref == 0.0, 1e-300, np.abs(ref)),
                        initial=0.0))


def interference(coupling: np.ndarray, a: np.ndarray, p: np.ndarray, *,
                 dtype: str = "float64") -> np.ndarray:
    """``[C]`` interference at each cell's base station (W)."""
    dt = PRECISIONS[dtype]
    tx = np.sum(np.asarray(a, dt) * np.asarray(p, dt), axis=1)
    return np.asarray(coupling, dt) @ tx


def knapsack(caps: np.ndarray, weights: np.ndarray, s_bits: float,
             budget: float, *, dtype: str = "float64"
             ) -> tuple[np.ndarray, float, float]:
    """``(a, mu, load)``: the most ``sum w a`` with ``0 <= a <= caps`` and
    ``sum a s_bits <= budget``."""
    dt = PRECISIONS[dtype]
    caps = np.asarray(caps, dt)
    flat = caps.reshape(-1)
    w = np.asarray(weights, dt).reshape(-1)
    bits = flat * dt(s_bits)
    total = np.sum(bits)
    if float(total) <= budget:
        return caps, 0.0, float(total)
    # decreasing weight; equal weights in the order of the devices
    order = np.lexsort((np.arange(flat.size), -w.astype(np.float64)))
    spent = np.cumsum(bits[order])
    j = int(np.argmax(spent.astype(np.float64) >= budget))
    a = np.zeros_like(flat)
    a[order[:j]] = flat[order[:j]]
    before = spent[j - 1] if j > 0 else dt(0.0)
    a[order[j]] = (dt(budget) - before) / dt(s_bits)
    return a.reshape(caps.shape), float(w[order[j]]), float(budget)


def solve(dev: dict, gain: np.ndarray, coupling: np.ndarray, statics: dict,
          budget: float, solved_at: np.ndarray, *, dtype: str = "float64"
          ) -> tuple[np.ndarray, np.ndarray, float, float, np.ndarray]:
    """``(a, P, mu, load, I)`` of one tick at the estimate ``solved_at``:
    the answers ``[C, N]``, the price, the load in bits and the
    interference the answers make."""
    noise = statics["noise_power"] + np.asarray(solved_at, np.float64)
    caps, p = alg2.solve(dev, gain, dict(statics, noise_power=noise[:, None]),
                         dtype=dtype)
    a, mu, load = knapsack(caps, dev["weights"], statics["grad_size_bits"],
                           budget, dtype=dtype)
    return a, p, mu, load, interference(coupling, a, p, dtype=dtype)


def relative_gap(old: np.ndarray, new: np.ndarray) -> float:
    """Widest change from ``old`` to ``new``, over the larger of their
    widest entries."""
    old = np.asarray(old, np.float64)
    new = np.asarray(new, np.float64)
    scale = max(float(np.max(np.abs(old), initial=0.0)),
                float(np.max(np.abs(new), initial=0.0)), 1e-30)
    return float(np.max(np.abs(new - old), initial=0.0)) / scale


def equilibrium(dev: dict, gain: np.ndarray, coupling: np.ndarray,
                statics: dict, budget: float, *, tol: float,
                damping: float, max_steps: int = 500) -> np.ndarray:
    """An estimate ``I`` whose answers (in float64) recompute it to within
    ``tol`` (:func:`relative_gap`): the map iterated from ``I = 0``, each
    step moving ``damping`` of the way to the recomputed value."""
    cur = np.zeros(len(coupling))
    for _ in range(max_steps):
        *_, nxt = solve(dev, gain, coupling, statics, budget, cur)
        if relative_gap(cur, nxt) <= tol:
            return cur
        cur = cur + damping * (nxt - cur)
    raise RuntimeError(f"no fixed point within {tol} in {max_steps} steps")

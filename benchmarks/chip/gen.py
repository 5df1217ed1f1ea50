"""The benchmark's own generators: deployments and arrivals from a seed.

Everything a run feeds the system is drawn here from ``--seed`` and the
numbers in a configuration file (``configs/<name>.json``) and a traffic
file (``traffic/<name>.json``).  Nothing here imports the program: the
same arrays go to the system under test and to the plain reference.

Devices follow the paper's simulation set-up (arXiv:2401.07756 Sec. V-A):
uniform positions in a cell's square around its base station, the cell's
bandwidth shared equally by its ``devices_per_cell`` devices, per-round
energy budgets log-uniform, dataset sizes from a Dirichlet(2) split over
the devices drawn together.  Drawing the devices of many cells at once
gives each its own cell of that shape.  Channels drift as first-order Gauss-Markov
Rayleigh fading (arXiv:2201.07912): ``h_k = rho h_{k-1} + sqrt(1-rho^2)
w_k`` and the power gain is ``|h_k|^2``.

The arrival processes are adapted from the program's open-loop generator
(``serve/load_gen.py``: ``poisson_trace`` and ``bursty_trace``) and kept
here, so that a later change to the program cannot change the yardstick.
Unlike the originals they fix the number of requests and the requests per
cell from the mix alone, so a seed changes the order of the work and
never its amount.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

#: per-device leaves of a deployment, in the order the reference takes them
DEVICE_FIELDS = ("distance_m", "bandwidth_hz", "energy_budget_j",
                 "dataset_size", "cycles_per_sample", "cpu_hz", "weights")


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per purpose, for any seed up to 2**63."""
    return np.random.default_rng([int(seed) % (1 << 63), stream])


def devices(cfg: dict, rng: np.random.Generator, n: int) -> dict:
    """``n`` devices, each in a cell of the configuration's shape:
    ``{field: float32 [n]}``."""
    area = float(cfg["area_m"])
    xy = rng.uniform(0.0, area, size=(n, 2))
    d = np.maximum(np.linalg.norm(xy - area / 2.0, axis=1), 1.0)
    props = rng.dirichlet(np.full(n, 2.0))
    sizes = np.maximum(np.round(props * float(cfg["dataset_total"])), 10.0)
    lo, hi = cfg["energy_budget_range_j"]
    budgets = np.exp(rng.uniform(np.log(lo), np.log(hi), size=n))
    out = {
        "distance_m": d,
        "bandwidth_hz": np.full(n, float(cfg["total_bandwidth_hz"])
                                / int(cfg["devices_per_cell"])),
        "energy_budget_j": budgets,
        "dataset_size": sizes,
        "cycles_per_sample": rng.uniform(*cfg["cycles_per_sample_range"],
                                         size=n),
        "cpu_hz": rng.uniform(*cfg["cpu_hz_range"], size=n),
        "weights": sizes / sizes.sum(),
    }
    return {k: np.asarray(v, np.float32) for k, v in out.items()}


def gauss_markov_gains(rng: np.random.Generator, shape: tuple,
                       n_rounds: int, coherence: float) -> np.ndarray:
    """Power gains ``[*shape, n_rounds]`` of a drifting Rayleigh channel."""
    def cn():
        return (rng.standard_normal(shape)
                + 1j * rng.standard_normal(shape)) / np.sqrt(2.0)

    out = np.empty(shape + (n_rounds,), np.float32)
    h = cn()
    out[..., 0] = np.abs(h) ** 2
    scale = np.sqrt(1.0 - coherence ** 2)
    for k in range(1, n_rounds):
        h = coherence * h + scale * cn()
        out[..., k] = np.abs(h) ** 2
    return out


def statics(cfg: dict) -> dict:
    """The problem's scalar constants, under the program's field names."""
    return {"grad_size_bits": float(cfg["grad_size_bits"]),
            "noise_power": float(cfg["noise_power_w"]),
            "p_max": float(cfg["p_max_w"]),
            "tau_th": float(cfg["tau_th_s"]),
            "kappa": float(cfg["kappa"])}


# --------------------------------------------------------------- arrivals

class Arrival(NamedTuple):
    """Request ``i``: cell ``cell``'s round ``round_k`` due ``t`` seconds
    after the window opens."""

    t: float
    cell: int
    round_k: int


def _poisson_times(rng, rate_hz: float, seconds: float) -> np.ndarray:
    """A Poisson process on ``[0, seconds)`` conditioned on its mean count:
    ``round(rate * seconds)`` uniform times, sorted.  Every seed then
    offers the same number of requests; only their order differs."""
    n = int(round(rate_hz * seconds))
    return np.sort(rng.uniform(0.0, seconds, size=n))


def _bursty_times(rng, burst_rate_hz: float, burst_len: int, idle_s: float,
                  seconds: float) -> np.ndarray:
    """ON/OFF bursts: each period is a burst of ``burst_len`` Poisson
    arrivals at ``burst_rate_hz`` (conditioned on that count, as above)
    followed by ``idle_s`` of silence; as many whole periods as fit."""
    on_s = burst_len / burst_rate_hz
    period = on_s + idle_s
    n_bursts = max(1, int(seconds // period))
    starts = np.arange(n_bursts)[:, None] * period
    offsets = np.sort(rng.uniform(0.0, on_s, size=(n_bursts, burst_len)),
                      axis=1)
    return (starts + offsets).reshape(-1)


def arrivals(traffic: dict, n_cells: int, seconds: float,
             rng: np.random.Generator, first_round: int = 1
             ) -> list[Arrival]:
    """Open-loop arrivals over ``[0, seconds)`` at the mix's fixed rate.

    ``traffic["arrivals"]`` is ``"poisson"`` (``rate_hz``) or ``"bursty"``
    (``burst_rate_hz``, ``burst_len``, ``idle_s``).  The cells take turns
    in a seeded random order, each as often as the others (to within
    one), and each arrival takes its cell's next round, starting at
    ``first_round`` (round 0 is served during set-up).
    """
    kind = traffic["arrivals"]
    if kind == "poisson":
        times = _poisson_times(rng, float(traffic["rate_hz"]), seconds)
    elif kind == "bursty":
        times = _bursty_times(rng, float(traffic["burst_rate_hz"]),
                              int(traffic["burst_len"]),
                              float(traffic["idle_s"]), seconds)
    else:
        raise ValueError(f"unknown arrival process {kind!r}")
    cells = rng.permutation(np.resize(np.arange(n_cells), len(times)))
    nxt = np.full(n_cells, first_round)
    out = []
    for t, c in zip(times, cells):
        out.append(Arrival(t=float(t), cell=int(c), round_k=int(nxt[c])))
        nxt[c] += 1
    return out


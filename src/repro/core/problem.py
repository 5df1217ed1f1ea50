"""Problem specification for joint probabilistic selection + power allocation.

Implements the system model of Section II of the paper:

* OFDMA uplink rate  r_ik(P) = B_i log2(1 + P g_ik / (d_i^2 sigma^2))   (g=1 paper)
  (multi-cell: sigma^2 -> sigma^2 + I_ik with cross-cell interference I,
  see core.multicell and docs/multicell.md)
* transmission time  T_ik(P) = S / r_ik(P)                               (eq. 1)
* computation energy E^c_i   = kappa * C_i * |D_i| * gamma_i^2           (eq. 5)
* upload energy      E^u_ik  = P_ik * T_ik(P_ik)

All per-device quantities are jnp arrays of shape ``[N]`` (or ``[N, K]``
when per-round fading is enabled — a beyond-paper generalisation the
closed forms support unchanged because the problem is separable per
``(i, k)``).

Broadcasting contract (``[N]`` vs ``[N, K]``)
---------------------------------------------

Every method taking per-device decision variables (``a``, ``power``)
accepts either rank on any problem, and broadcasts all operands to the
*highest* rank present — the path gain's rank on a fading problem:

* 1-d input on a fading problem means "the same value, evaluated at each
  round's channel draw": the result has shape ``[N, K]``, column k equal
  to the call with that column explicitly (bit-for-bit — see
  ``tests/test_problem_broadcast.py``).
* 2-d input on a static problem broadcasts the per-device constants
  (``bandwidth_hz``, ``energy_budget_j``, ...) across the trailing round
  axis; the result keeps the input's ``[N, K]`` shape.
* matching ranks pass through elementwise.

Internally the rule is: broadcast 1-d operands with ``x[:, None]``
against the ``[N, K]`` path gain, never the reverse — mixing a raw
``[N]`` with an ``[N, K]`` array only "works" when K == N (and is then
silently wrong).  ``core.power`` / ``core.selection`` follow the same
contract through ``_pg`` / ``_bcast_like``.  The contract (with the
equation-by-equation code map) is documented in docs/equations.md
("Broadcasting contract"); ``interference`` follows the same rank
rules as ``fading``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

LN2 = float(np.log(2.0))


# The closed forms P^min = expm1(x) / pg and r = B log2(1 + P pg) are
# inverse to each other: at P = P^min(a) the eq.-13 time term returns a
# exactly, so the selection iterate is stationary there only as far as
# the two transcendentals invert each other.  A TPU v5e's default f32
# transcendentals are approximations (exp up to 57 ulp, expm1 up to 986
# ulp below x = ln 2, log up to 2206 ulp); at that noise a cold batch of
# 100-device cells did not meet the fused solve's 1e-7 stopping rule in
# its 50 steps.  The closed forms therefore ask XLA for its most
# accurate implementation (within 1.5 ulp on the v5e; one step to
# converge, as on the CPU), which on the CPU is the default one
# (bit-identical results).
_ACCURATE = jax.lax.AccuracyMode.HIGHEST


def accurate_expm1(x):
    """``expm1`` at XLA's highest accuracy (see above)."""
    return jax.lax.expm1(x, accuracy=_ACCURATE)


def accurate_log2(x):
    """``log2`` at XLA's highest accuracy; the same ``log(x) / ln 2`` as
    ``jnp.log2``."""
    x = jnp.asarray(x)
    return jax.lax.log(x, accuracy=_ACCURATE) / np.asarray(LN2, x.dtype)


# Uncompressed payload of the paper's model: 199_210 fp32 parameters.
# The single source of truth for the magic number — examples, benchmarks
# and the bit-allocation code all import it from here.
GRAD_SIZE_BITS_FP32 = 199_210 * 32.0

# neutral per-device fills used to overwrite unhealthy device rows (see
# ``WirelessFLProblem.sanitize``): a zero energy budget makes every solver
# self-deselect the slot (a* = 0, P* = 0) while distance/bandwidth 1 keep
# all closed forms finite, and weight 0 removes it from the objective.
# ``core.batch._PAD_VALUES`` aliases this dict — padded slots and
# sanitized devices are the same idiom.
NEUTRAL_FILLS = dict(distance_m=1.0, bandwidth_hz=1.0, energy_budget_j=0.0,
                     dataset_size=1.0, cycles_per_sample=1.0, cpu_hz=1.0,
                     weights=0.0)
_FADING_FILL = 1.0
_INTERFERENCE_FILL = 0.0
_BITS_FILL = 32.0


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class WirelessFLProblem:
    """Static description of the joint selection/power problem (7).

    Array fields are leaves (shape ``[N]`` unless noted); python floats are
    static metadata. ``K`` rounds share the same constraint data in the
    paper (channel is static), so solutions are round-independent unless
    ``fading`` (shape ``[N, K]``) is provided.
    """

    # --- per-device wireless/compute state ------------------------------
    distance_m: jax.Array          # d_i, metres to the server
    bandwidth_hz: jax.Array        # B_i
    energy_budget_j: jax.Array     # E_i^max, per-round energy budget
    dataset_size: jax.Array        # |D_i| (float for weighting math)
    cycles_per_sample: jax.Array   # C_i
    cpu_hz: jax.Array              # gamma_i
    weights: jax.Array             # w_i, objective weights (sum to 1)
    fading: Optional[jax.Array] = None   # g_ik in (0, inf), [N, K]; None => 1
    # cross-cell interference power I_ik (W) received at this cell's BS,
    # [N] or [N, K] (per-round rank-2 requires a fading problem so the
    # solution rank stays fading-driven); None => 0 (single cell).  Set
    # by the multi-cell outer loop (core.multicell) — raises the
    # effective noise floor sigma^2 -> sigma^2 + I_ik in the SINR.
    interference: Optional[jax.Array] = None
    # per-device uplink quantisation width b_i in (0, 32] bits/parameter,
    # [N] or [N, K] (per-round rank-2 requires a fading problem so the
    # solution rank stays fading-driven, same rule as ``interference``);
    # None => full-precision fp32 payload (bit-identical to the pre-bits
    # code path).  Scales the effective payload S_i = S * b_i / 32 in
    # ``tx_time`` / ``p_min`` / ``upload_energy`` (docs/compression.md).
    bits: Optional[jax.Array] = None

    # --- shared constants (static) ---------------------------------------
    grad_size_bits: float = dataclasses.field(default=GRAD_SIZE_BITS_FP32, metadata=dict(static=True))
    noise_power: float = dataclasses.field(default=1e-12, metadata=dict(static=True))       # sigma^2
    p_max: float = dataclasses.field(default=1.0, metadata=dict(static=True))               # P^max (W)
    tau_th: float = dataclasses.field(default=0.08, metadata=dict(static=True))             # tau^th (s)
    kappa: float = dataclasses.field(default=1e-28, metadata=dict(static=True))             # switched capacitance
    n_rounds: int = dataclasses.field(default=1, metadata=dict(static=True))                # K

    # ---------------------------------------------------------------- api
    @property
    def n_devices(self) -> int:
        return int(self.distance_m.shape[0])

    def path_gain(self) -> jax.Array:
        """g_ik / (d_i^2 (sigma^2 + I_ik)) — SINR per transmitted watt.

        With ``interference=None`` this is the paper's single-cell SNR
        g/(d^2 sigma^2), shape [N] or [N, K]; the ``interference`` leaf
        raises the effective noise floor (docs/multicell.md).  The
        no-interference path is kept byte-identical to the pre-multicell
        expression so single-cell results cannot drift.
        """
        g = 1.0 if self.fading is None else self.fading
        d2s = jnp.square(self.distance_m) * self.noise_power
        base = 1.0 / d2s
        if self.interference is None:
            if self.fading is None:
                return base
            # a corrupted channel draw (g = 0, NaN) against a tiny d2s
            # must gate the device out (gain 0 => P^min = inf), not emit
            # 0 * inf = NaN; g > 0 leaves healthy draws bit-identical.
            # A rank-1 fading (round-invariant draw) stays rank 1: lifting
            # base to [:, None] against an [N] g builds [N, N] garbage
            # that broadcasts silently whenever K == N.
            return jnp.where(g > 0, g * _bcast_like(base, g.ndim), 0.0)
        # d^2 sigma^2 + d^2 I: the I == 0 case reduces to d^2 sigma^2
        # exactly (adding a true zero is exact in IEEE), so zero
        # interference matches interference=None bit-for-bit.
        d2 = jnp.square(self.distance_m)
        rank = 2 if ((self.fading is not None and self.fading.ndim == 2)
                     or self.interference.ndim == 2) else 1
        iv = _bcast_like(self.interference, rank)
        denom = _bcast_like(d2s, rank) + _bcast_like(d2, rank) * iv
        pg = 1.0 / denom
        if self.fading is None:
            return pg
        gv = _bcast_like(g, pg.ndim)
        return jnp.where(gv > 0, gv * pg, 0.0)

    def _pg(self, like: jax.Array) -> jax.Array:
        """path_gain broadcast to the rank of ``like`` ([N] or [N, K])."""
        pg = self.path_gain()
        if like.ndim > pg.ndim:
            pg = pg[:, None]
        return pg

    def rate(self, power: jax.Array) -> jax.Array:
        """Achievable uplink rate r_ik(P) in bits/s (paper, Sec II-A).

        A 1-d power on a fading ([N, K]) problem broadcasts across rounds:
        the same transmit power, evaluated at each round's channel draw.
        """
        pg = self._pg(power)
        p = power if power.ndim >= pg.ndim else power[:, None]
        bw = self.bandwidth_hz
        if max(p.ndim, pg.ndim) > bw.ndim:
            bw = bw[:, None]
        return bw * accurate_log2(1.0 + p * pg)

    def payload_bits(self, rank: int = 1):
        """Effective uplink payload S_i = S * b_i / 32 in bits.

        Returns the static python float ``grad_size_bits`` unchanged when
        ``bits is None`` — every consumer then traces the exact same
        constant-folded expression as before the bits leaf existed, which
        is what keeps ``bits=None`` problems byte-identical.  With a bits
        leaf the result is an array broadcast to ``rank``.
        """
        if self.bits is None:
            return self.grad_size_bits
        return self.grad_size_bits * _bcast_like(self.bits, rank) / 32.0

    def tx_time(self, power: jax.Array) -> jax.Array:
        """Transmission time T_ik(P) = S_i / r_ik(P)  (eq. 1, bit-scaled).

        A rank-2 ``bits`` table lifts the result to ``[N, K]`` even for a
        rank-1 power (per-round payloads at a fixed transmit power) —
        the same highest-rank rule every other leaf follows.
        """
        r = jnp.maximum(self.rate(power), 1e-30)
        rank = r.ndim if self.bits is None else max(r.ndim, self.bits.ndim)
        return self.payload_bits(rank) / _bcast_like(r, rank)

    def compute_energy(self) -> jax.Array:
        """E^c_i = kappa C_i |D_i| gamma_i^2  (eq. 5)."""
        return self.kappa * self.cycles_per_sample * self.dataset_size * jnp.square(self.cpu_hz)

    def upload_energy(self, power: jax.Array) -> jax.Array:
        """E^u_ik = P T_ik(P)."""
        t = self.tx_time(power)
        p = power if power.ndim >= t.ndim else power[:, None]
        return p * t

    def round_energy(self, power: jax.Array) -> jax.Array:
        """E_ik = E^c_i + E^u_ik  (eq. 6)."""
        eu = self.upload_energy(power)
        ec = self.compute_energy()
        if eu.ndim > ec.ndim:
            ec = ec[:, None]
        return ec + eu

    def p_min(self, a: jax.Array) -> jax.Array:
        """Minimum power meeting the time constraint (7c) at probability a.

        P^min_ik = (2^{a S / (B_i tau)} - 1) / path_gain  — below this the
        expected transmission time a*T exceeds tau^th.

        A 1-d ``a`` on a fading ([N, K]) problem broadcasts across rounds
        (same probability, each round's channel), exactly like ``rate``.
        """
        pg = self._pg(a)
        rank = max(a.ndim, pg.ndim)
        if self.bits is not None:
            rank = max(rank, self.bits.ndim)
        av = _bcast_like(a, rank)
        pgv = _bcast_like(pg, rank)
        bw = _bcast_like(self.bandwidth_hz, rank)
        exponent = av * self.payload_bits(rank) / (bw * self.tau_th)
        # exp2 overflows fast; clamp exponent so infeasible entries give a
        # huge-but-finite P^min (> p_max), which downstream logic treats as
        # "infeasible at this a" rather than producing NaNs.
        exponent = jnp.minimum(exponent, 120.0)
        num = accurate_expm1(exponent * LN2)
        # zero/NaN gain (deep fade to zero, corrupted channel): P^min = inf
        # is the infeasible-device gate; the unguarded num / pg emits NaN
        # at a = 0 (0 / 0) and poisons every downstream update
        return jnp.where(pgv > 0, num / jnp.where(pgv > 0, pgv, 1.0),
                         jnp.inf)

    def objective(self, a: jax.Array) -> jax.Array:
        """Weighted sum of selection probabilities (7a) for one round."""
        w = self.weights if a.ndim == 1 else self.weights[:, None]
        return jnp.sum(a * w)

    def constraints_satisfied(self, a: jax.Array, power: jax.Array,
                              rtol: float = 1e-4) -> jax.Array:
        """Boolean feasibility of (7b)-(7e) per element (with tolerance).

        ``a`` and ``power`` may be ``[N]`` or ``[N, K]`` independently;
        1-d operands broadcast across the fading rounds (module
        docstring contract) and the result takes the highest rank.
        """
        t = self.tx_time(power)
        rank = max(a.ndim, power.ndim, t.ndim)
        av = _bcast_like(a, rank)
        pv = _bcast_like(power, rank)
        tv = _bcast_like(t, rank)
        eu = pv * tv                        # E^u = P T_ik(P), as upload_energy
        energy_ok = av * (eu + _bcast_like(self.compute_energy(), rank)) \
            <= _bcast_like(self.energy_budget_j, rank) * (1 + rtol) + 1e-12
        time_ok = av * tv <= self.tau_th * (1 + rtol)
        p_ok = (pv >= -1e-12) & (pv <= self.p_max * (1 + rtol))
        a_ok = (av >= -1e-12) & (av <= 1 + rtol)
        return energy_ok & time_ok & p_ok & a_ok

    # ------------------------------------------------ boundary hardening

    def health_mask(self, xp=jnp) -> jax.Array:
        """Per-device boolean mask, True where every field is well-formed.

        A device is *unhealthy* when any of its constraint data is
        non-finite, when a strictly-positive quantity (distance,
        bandwidth, fading gain, dataset size, CPU parameters) is <= 0, or
        when a non-negative quantity (energy budget, weight,
        interference) is negative.  Works on single-instance ``[N]``
        leaves and on batched ``[B, N]`` leaves alike (per-round fading /
        interference reduce over the trailing round axis: one bad round
        marks the device — device granularity, see docs/robustness.md).

        ``xp=np`` evaluates on the host (the serving submit path checks
        every request without a device round-trip); ``xp=jnp`` is
        jit-compatible.
        """
        def finite(x):
            return xp.isfinite(xp.asarray(x))

        positive = ("distance_m", "bandwidth_hz", "dataset_size",
                    "cycles_per_sample", "cpu_hz")
        nonneg = ("energy_budget_j", "weights")
        ok = None
        for name in positive + nonneg:
            x = xp.asarray(getattr(self, name))
            good = finite(x) & (x > 0 if name in positive else x >= 0)
            ok = good if ok is None else ok & good
        rank = xp.asarray(self.distance_m).ndim
        if self.fading is not None:
            f = xp.asarray(self.fading)
            f_ok = finite(f) & (f > 0)
            if f.ndim > rank:
                f_ok = f_ok.all(axis=-1)
            ok = ok & f_ok
        if self.interference is not None:
            iv = xp.asarray(self.interference)
            i_ok = finite(iv) & (iv >= 0)
            if iv.ndim > rank:
                i_ok = i_ok.all(axis=-1)
            ok = ok & i_ok
        if self.bits is not None:
            bv = xp.asarray(self.bits)
            b_ok = finite(bv) & (bv > 0)
            if bv.ndim > rank:
                b_ok = b_ok.all(axis=-1)
            ok = ok & b_ok
        return ok

    def sanitize(self, health: Optional[jax.Array] = None
                 ) -> tuple["WirelessFLProblem", jax.Array]:
        """Replace unhealthy device rows with :data:`NEUTRAL_FILLS`.

        Returns ``(problem, health)``.  Sanitized devices self-deselect
        in every solver (zero energy budget => a* = 0, P* = 0) instead of
        poisoning the fused while-loop with NaN/Inf; healthy rows pass
        through bit-for-bit (``where`` with an all-True mask is the
        identity).  ``health`` defaults to :meth:`health_mask`.

        The hardening is one compiled program (:func:`_sanitize_leaves`)
        per tree of leaves and their shapes, not one dispatch per
        primitive; inside an enclosing ``jit`` it is inlined.
        """
        leaves = {f.name: getattr(self, f.name)
                  for f in dataclasses.fields(self)
                  if not f.metadata.get("static")}
        leaves, health = _sanitize_leaves(leaves, health)
        return dataclasses.replace(self, **leaves), health

    def validate(self) -> None:
        """Raise ``ValueError`` naming the unhealthy devices, if any.

        The strict counterpart of :meth:`sanitize` for callers that want
        malformed input rejected rather than degraded around.
        """
        health = np.asarray(self.health_mask(xp=np))
        if not health.all():
            bad = np.flatnonzero(~health.reshape(-1))
            raise ValueError(
                f"{bad.size} device slot(s) carry non-finite or "
                f"out-of-domain constraint data (flat indices "
                f"{bad[:8].tolist()}{'...' if bad.size > 8 else ''}); "
                "sanitize() degrades them to self-deselecting no-ops")


@jax.jit
def _sanitize_leaves(leaves: dict, health: Optional[jax.Array]
                     ) -> tuple[dict, jax.Array]:
    """:meth:`WirelessFLProblem.sanitize` over the problem's array leaves.

    The static constants do not enter the hardening, so they are left
    out of the program's key: one program per set of present optional
    leaves and their shapes and dtypes.
    """
    problem = WirelessFLProblem(**leaves)
    if health is None:
        health = problem.health_mask()
    health = jnp.asarray(health, bool)
    repl = {}
    for name, fill in NEUTRAL_FILLS.items():
        x = leaves[name]
        repl[name] = jnp.where(health, x, jnp.asarray(fill, x.dtype))
    rank = problem.distance_m.ndim
    for name, fill in (("fading", _FADING_FILL),
                       ("interference", _INTERFERENCE_FILL),
                       ("bits", _BITS_FILL)):
        x = leaves[name]
        if x is not None:
            h = health[..., None] if x.ndim > rank else health
            repl[name] = jnp.where(h, x, fill)
    return repl, health


def _bcast_like(x: jax.Array, rank: int) -> jax.Array:
    """Broadcast a per-device ``[N]`` vector to ``[N, 1]`` when the
    surrounding expression is per-round ``[N, K]`` (rank 2)."""
    return x if x.ndim >= rank else x[:, None]


def sample_problem(rng: np.random.Generator | int,
                   n_devices: int = 100,
                   *,
                   area_m: float = 1000.0,
                   total_bandwidth_hz: float = 10e6,
                   tau_th: float = 0.08,
                   p_max: float = 1.0,
                   grad_size_bits: float = GRAD_SIZE_BITS_FP32,
                   n_rounds: int = 1,
                   energy_budget_range: tuple[float, float] = (1e-3, 100.0),
                   dataset_total: int = 60_000,
                   dirichlet_sizes: Optional[np.ndarray] = None,
                   with_fading: bool = False) -> WirelessFLProblem:
    """Draw a random scenario matching the paper's simulation setup (Sec V-A).

    100 devices uniform in 1 km^2, server at the centre, B = 10 MHz shared
    equally, sigma^2 = 1e-12, per-round energy budgets log-uniform in
    [1e-3, 100] J.
    """
    rng = np.random.default_rng(rng) if not isinstance(rng, np.random.Generator) else rng
    xy = rng.uniform(0.0, area_m, size=(n_devices, 2))
    centre = np.array([area_m / 2, area_m / 2])
    d = np.maximum(np.linalg.norm(xy - centre, axis=1), 1.0)

    if dirichlet_sizes is not None:
        sizes = np.asarray(dirichlet_sizes, dtype=np.float64)
    else:
        props = rng.dirichlet(np.full(n_devices, 2.0))
        sizes = np.maximum(np.round(props * dataset_total), 10.0)

    lo, hi = energy_budget_range
    budgets = np.exp(rng.uniform(np.log(lo), np.log(hi), size=n_devices))

    fading = None
    if with_fading:
        # Rayleigh block fading per round (beyond-paper option).
        fading = rng.exponential(1.0, size=(n_devices, n_rounds))

    return WirelessFLProblem(
        distance_m=jnp.asarray(d, jnp.float32),
        bandwidth_hz=jnp.full((n_devices,), total_bandwidth_hz / n_devices, jnp.float32),
        energy_budget_j=jnp.asarray(budgets, jnp.float32),
        dataset_size=jnp.asarray(sizes, jnp.float32),
        cycles_per_sample=jnp.asarray(rng.uniform(1e4, 5e4, n_devices), jnp.float32),
        cpu_hz=jnp.asarray(rng.uniform(0.5e9, 2e9, n_devices), jnp.float32),
        weights=jnp.asarray(sizes / sizes.sum(), jnp.float32),
        fading=None if fading is None else jnp.asarray(fading, jnp.float32),
        grad_size_bits=float(grad_size_bits),
        noise_power=1e-12,
        p_max=float(p_max),
        tau_th=float(tau_th),
        kappa=1e-28,
        n_rounds=int(n_rounds),
    )

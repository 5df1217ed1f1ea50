"""Open-loop fleet control plane: deadlines, continuous batching, warmup.

The serving problem: a control plane serving many base-station cells
receives a *stream* of per-cell solve requests — "here is my cell's
current channel/energy state, give me (a*, P*) for the next round" — as
an open-loop arrival process.  A round's solution is worthless after the
channel decorrelates, so every request carries a latency budget; the
solvers (``repro.core.batch``) are at their best on big padded batches;
and successive requests from the same cell are nearly identical on a
coherent channel (``drifting_metro``), so most of each solve is
recomputation the warm-start path can skip.

:class:`FleetControlService` packs those observations into one loop:

* **arrival queue + deadlines** — ``submit`` stamps each request with an
  arrival time and an absolute deadline (``deadline_s`` budget, else
  ``ServiceConfig.default_deadline_s``, else unbounded);
* **continuous batching** — requests accumulate until the adaptive
  close policy (:func:`batch_close_reason`, the LLM-serving idiom)
  closes the micro-batch: when it is *full*, when the batch's tightest
  remaining *deadline* budget drops below the bucket's measured solve
  cost (EWMA, :class:`BucketCostModel`), or when the oldest request has
  *lingered* past the latency bound for deadline-less traffic.  ``poll``
  is the non-blocking heartbeat that applies the policy; ``step`` forces
  a close (the legacy synchronous mode); ``run`` drains the queue;
* **priority lanes** — a request whose cell has cached state but whose
  quantised feature key no longer matches it (the channel drifted past
  the quantisation step) enters the priority lane and preempts normal
  traffic: its stale cached solution is the one most urgently wrong;
* **AOT warmup** — ``warmup()`` pre-executes every power-of-two device
  bucket's jit program (cold and warm init signatures) at startup, so no
  live request ever eats a trace/compile;
* **micro-batching** — queued requests with compatible static metadata
  are packed into a padded :class:`~repro.core.batch.ProblemBatch` of
  fixed slot shape (``max_batch`` instance slots, device axis padded to
  a power-of-two bucket; :func:`repro.core.batch.stack_problems` builds
  it in one host pass and one upload), so jit compiles one program per
  bucket instead of one per request shape;
* **warm starts** — each solved request's ``(a*, P*)`` is cached and fed
  back as ``init`` for the cell's next solve (bit-identical solutions,
  collapsed inner iterations — see ``core.alternating``'s warm-start
  notes), keyed both on quantised problem features
  (:func:`quantized_problem_key`) and per cell;
* **accounting** — sustained solves/sec, p50/p99 request latency,
  deadline-miss rate, queue wait, preemption and close-reason counters,
  cache hit rates and inner-iteration counts (:class:`ServiceStats`; the
  ``fleet_service_throughput`` / ``fleet_service_openloop`` benchmarks
  and CI gate consume these);
* **profiler spans** — while a ``jax.profiler`` trace is being taken,
  ``submit`` and every answered batch write ``fleet_service.*``
  annotations (``SPAN_*``) on the trace's host clock: a request's intake
  and key, a batch's packing, seeding, solve, read back and responses;
  a coupled metro tick and its padding (``fleet_service.metro``,
  ``fleet_service.metro_pad``), around the outer loop's own
  ``multicell.*`` spans.
  With no trace running each call pays one ``is_enabled`` check and
  builds no annotation (``docs/serving.md``, "Observing the service").

The loop stays deliberately synchronous — the unit of work is one
compiled batched solve, and a thread pump around it would only blur the
accounting.  ``repro.serve.load_gen`` provides the seeded Poisson/bursty
open-loop arrival generator and the driver that calls ``poll``.

Clock domains: with no ``now`` argument everything runs on
``time.perf_counter()`` wall time.  Passing explicit ``now`` stamps to
``submit``/``poll``/``step`` runs the service on a caller-supplied
(virtual) clock — batch composition, deadline misses, and every
non-latency counter then become deterministic functions of the arrival
trace (the golden/determinism suites pin this).  Use one domain
consistently per service instance.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import hashlib
import time
from typing import Hashable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.alternating import JointSolution, WarmStart
from repro.core.batch import (
    _PAD_VALUES,
    _STATIC_FIELDS,
    solve_joint_batch,
    stack_problems,
)
from repro.core.multicell import (
    CoupledDuals,
    MultiCellProblem,
    MultiCellSolution,
    pad_metro,
)
from repro.core.multicell import solve_coupled as solve_coupled_core
from repro.core.problem import WirelessFLProblem

_INF = float("inf")

# close reasons reported by the batch-close policy / ServiceStats
CLOSE_FULL = "full"          # the bucket's instance slots are exhausted
CLOSE_DEADLINE = "deadline"  # tightest budget ~ the bucket's solve cost
CLOSE_LINGER = "linger"      # oldest request hit the linger latency bound
CLOSE_FORCED = "forced"      # explicit step()/run() drain

# profiler spans, written only while a jax.profiler trace is being taken
SPAN_SUBMIT = "fleet_service.submit"      # one request's intake (seq, cell,
#                                           lane)
SPAN_KEY = "fleet_service.key"            # its quantised feature key
SPAN_SERVE = "fleet_service.serve"        # one answered batch, solved or
#                                           shed (batch, size, bucket,
#                                           reason, lane, seqs)
SPAN_PACK = "fleet_service.pack"          # stack + pad the batch
SPAN_SEED = "fleet_service.seed"          # warm seeds looked up and uploaded
SPAN_SOLVE = "fleet_service.solve"        # the batch solve, to completion
SPAN_READBACK = "fleet_service.readback"  # device -> host reads
SPAN_RESPOND = "fleet_service.respond"    # responses, caches, accounting
SPAN_METRO = "fleet_service.metro"        # one coupled metro tick
SPAN_METRO_PAD = "fleet_service.metro_pad"  # pad_metro to the tick's bucket

_NO_SPAN = contextlib.nullcontext()


def _span(on: bool, name: str):
    """Profiler span ``name`` when ``on`` (a trace is running), else a
    shared no-op: with tracing off no annotation is built."""
    return TraceAnnotation(name) if on else _NO_SPAN


def _lane(priority: bool) -> str:
    return "priority" if priority else "normal"


def _queue_wait_us(reqs, t_close: float) -> int:
    """Whole microseconds ``reqs`` waited from submit to their batch's
    close stamp, summed (integers per request: deterministic sums)."""
    return sum(round((t_close - r.t_submit) * 1e6) for r in reqs)


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    """Knobs of the fleet control plane."""

    max_batch: int = 16           # micro-batch instance slots
    min_device_bucket: int = 8    # smallest padded device-axis bucket
    method: str = "fused"         # "fused" | "alternating"
    power_solver: Optional[str] = None   # None => the method's default
    eps: float = 1e-7
    max_iters: int = 50
    warm_start: bool = True       # feed cached solutions back as init
    cache_size: int = 4096        # LRU entries (feature-keyed + per-cell)
    quant_decimals: int = 2       # log10 rounding of the cache key
    latency_window: int = 8192    # latencies kept for the percentiles
    # ---- open-loop control (continuous batching) -----------------------
    default_deadline_s: Optional[float] = None  # per-request budget; None
    #                                            = unbounded (linger rules)
    close_safety: float = 1.5     # close when budget <= safety * est cost
    max_linger_s: float = 5e-3    # universal max wait of the oldest request
    prior_solve_s: float = 5e-3   # cost-model prior before measurements
    cost_smoothing: float = 0.3   # EWMA weight of new measurements; 0
    #                               freezes the prior (deterministic
    #                               close decisions under a virtual clock)
    record_batches: bool = False  # keep a BatchRecord log (golden tests)
    # ---- fault tolerance (docs/robustness.md) --------------------------
    sanitize: bool = True         # map non-finite/non-positive device
    #                               features to self-deselecting no-ops at
    #                               submit (WirelessFLProblem.sanitize)
    retry_unconverged: bool = True  # re-solve an unconverged batch once
    #                                 through the reference path
    retry_max_iters: int = 200    # outer-iteration budget of the retry
    retry_backoff_s: float = 1e-3  # base of the exponential backoff
    #                                *accounted* per consecutive failure
    #                                (no sleeping — determinism)
    breaker_threshold: int = 3    # consecutive failed batches per bucket
    #                               before the circuit breaker opens
    breaker_cooldown: int = 8     # batches shed while the breaker is open


class SolveRequest(NamedTuple):
    cell_id: Hashable
    problem: WirelessFLProblem
    t_submit: float
    t_deadline: float = _INF      # absolute, same clock domain as t_submit
    priority: bool = False        # routed through the priority lane
    fkey: Optional[bytes] = None  # quantised feature key (warm_start only)
    ckey: Optional[tuple] = None  # static-compatibility key (micro-batching)
    seq: int = 0                  # submission order, unique per service
    n_unhealthy: int = 0          # devices degraded to no-ops at submit


class SolveResponse(NamedTuple):
    cell_id: Hashable
    # padding stripped.  NOTE: with the fused method the solver reports
    # one inner-iteration count for the whole flattened element set, so
    # ``solution.inner_iters`` is the *micro-batch total* shared by every
    # response of the batch (per-request attribution does not exist on
    # that path); the alternating method attributes it per instance.
    solution: JointSolution
    warm_started: bool            # solve was seeded from cached state
    cache_hit: bool               # the feature-keyed LRU supplied the seed
    latency_s: float              # submit -> response time (request clock)
    deadline_missed: bool = False  # completed after the request's deadline
    seq: int = 0                  # the request's submission sequence number
    # ---- health/degradation surface (docs/robustness.md) ---------------
    converged: bool = True        # the solver reported convergence for
    #                               this instance (after any retry)
    n_iters: int = 0              # outer iterations attributed to it
    n_unhealthy: int = 0          # devices sanitised to no-ops at submit
    retried: bool = False         # batch was re-solved via the reference
    #                               path after an unconverged first pass
    shed: bool = False            # served degraded (cached-or-zero) by an
    #                               open circuit breaker, not solved


class CoupledResponse(NamedTuple):
    """One served metro tick (:meth:`FleetControlService.solve_coupled`).

    ``solution`` keeps the bucket-padded shapes (padded cells/devices are
    masked out and carry ``a = 0``); ``n_cells`` is the metro's true cell
    count — extract per-cell answers with ``solution.batch.instance(c)``
    for ``c < n_cells``.
    """

    metro_id: Hashable
    solution: MultiCellSolution
    n_cells: int                  # true (unpadded) cell count
    warm_started: bool            # duals seeded from the previous tick
    latency_s: float              # submit -> response time


class BatchRecord(NamedTuple):
    """One served micro-batch (``ServiceConfig.record_batches``): enough
    to replay the exact solve offline — the golden suites rebuild the
    same padded batch from ``seqs`` and compare bitwise."""

    seqs: tuple[int, ...]         # request seqs, slot order
    cell_ids: tuple               # matching cell ids
    n_bucket: int                 # padded device-axis bucket
    reason: str                   # CLOSE_* that closed the batch
    priority: bool                # served from the priority lane


class ServiceStats:
    """Steady-state throughput/latency counters (host-side, cheap).

    ``solve_seconds`` is the host time of each solved batch from packing
    through the convergence check (the ``t0..t1`` the close policy's
    cost model observes), plus each metro tick's wall time: not the
    device solve alone.  The ``fleet_service.*`` profiler spans split it
    (``docs/serving.md``, "Observing the service").
    """

    def __init__(self, latency_window: int = 8192):
        self._window = latency_window
        self.reset()

    def reset(self) -> None:
        """Zero every counter — call after warm-up so compile time does
        not pollute the steady-state figures."""
        self.n_requests = 0
        self.n_solved = 0
        self.n_batches = 0
        self.n_warm = 0
        self.n_cache_hits = 0
        self.n_priority = 0
        self.n_deadline_misses = 0
        self.n_preemptions = 0
        self.queue_wait_us = 0        # sum of (close stamp - t_submit)
        #                               over answered requests
        self.closes = collections.Counter()
        self.solve_seconds = 0.0
        self.outer_iters = 0
        self.inner_iters = 0
        self.n_metro_ticks = 0        # coupled multi-cell ticks served
        self.metro_outer_iters = 0    # dual-decomposition iterations
        self.n_metro_warm = 0         # ticks seeded from cached duals
        self.n_metro_caps = 0         # ticks returning best-so-far at cap
        # ---- fault tolerance (docs/robustness.md) -----------------------
        self.n_unconverged = 0        # responses delivered unconverged
        self.n_retries = 0            # batches re-solved via reference path
        self.n_shed = 0               # responses shed by an open breaker
        self.n_unhealthy_devices = 0  # devices sanitised to no-ops
        self.breaker_opens = 0        # circuit-breaker open transitions
        self.retry_backoff_s = 0.0    # accounted (not slept) backoff
        self.latencies = collections.deque(maxlen=self._window)

    # ---- recording (service-internal) ----------------------------------
    def record_batch(self, responses, solve_s: float, outer: int,
                     inner: int, reason: str = CLOSE_FORCED,
                     preempted: bool = False,
                     retried: bool = False,
                     queue_wait_us: int = 0) -> None:
        self.n_batches += 1
        self.n_solved += len(responses)
        self.queue_wait_us += queue_wait_us
        self.solve_seconds += solve_s
        self.outer_iters += outer
        self.inner_iters += inner
        self.closes[reason] += 1
        self.n_preemptions += bool(preempted)
        self.n_retries += bool(retried)
        for r in responses:
            self.n_warm += bool(r.warm_started)
            self.n_cache_hits += bool(r.cache_hit)
            self.n_deadline_misses += bool(r.deadline_missed)
            self.n_unconverged += not r.converged
            self.n_shed += bool(r.shed)
            self.n_unhealthy_devices += int(r.n_unhealthy)
            self.latencies.append(r.latency_s)

    def record_metro(self, solve_s: float, outer: int,
                     warm: bool, hit_cap: bool = False) -> None:
        """Account one coupled metro tick (no per-request latency — a
        tick is a single synchronous call, not queued traffic)."""
        self.n_metro_ticks += 1
        self.metro_outer_iters += outer
        self.n_metro_warm += bool(warm)
        self.n_metro_caps += bool(hit_cap)
        self.solve_seconds += solve_s

    # ---- derived figures ------------------------------------------------
    @property
    def solves_per_sec(self) -> float:
        """Requests answered per second of ``solve_seconds`` (packing
        through the convergence check, not the device solve alone)."""
        return self.n_solved / self.solve_seconds if self.solve_seconds else 0.0

    def latency_percentile(self, q: float) -> float:
        """Latency percentile (seconds) over the sliding sample window.

        Semantics, pinned by ``tests/test_fleet_service.py``:

        * empty window -> ``nan`` — never ``0.0``, which would read as
          "infinitely fast" in dashboards and bench gates;
        * one sample -> that sample, for every ``q``;
        * otherwise numpy's default linear interpolation between order
          statistics (the p50 of two samples is their midpoint);
        * the window keeps the newest ``latency_window`` samples — older
          requests fall off the edge and stop influencing percentiles.
        """
        if not self.latencies:
            return float("nan")
        return float(np.percentile(np.asarray(self.latencies), q))

    @property
    def warm_fraction(self) -> float:
        return self.n_warm / self.n_solved if self.n_solved else 0.0

    @property
    def deadline_miss_rate(self) -> float:
        return self.n_deadline_misses / self.n_solved if self.n_solved else 0.0

    @property
    def mean_inner_iters(self) -> float:
        """Mean inner (Algorithm-1) iterations per micro-batch solve —
        the figure warm starts collapse (0.0 in analytic mode)."""
        return self.inner_iters / self.n_batches if self.n_batches else 0.0

    def counter_summary(self) -> dict:
        """The integer counters only — no wall-clock-derived field.

        Under a virtual clock (explicit ``now`` stamps) every entry is a
        deterministic function of the arrival trace; the golden suites
        compare this dict across runs and processes."""
        return {
            "requests": self.n_requests,
            "solved": self.n_solved,
            "batches": self.n_batches,
            "warm": self.n_warm,
            "cache_hits": self.n_cache_hits,
            "priority": self.n_priority,
            "deadline_misses": self.n_deadline_misses,
            "preemptions": self.n_preemptions,
            "queue_wait_us": self.queue_wait_us,
            "closes": dict(self.closes),
            "outer_iters": self.outer_iters,
            "inner_iters": self.inner_iters,
            "metro_ticks": self.n_metro_ticks,
            "metro_outer_iters": self.metro_outer_iters,
            "metro_warm": self.n_metro_warm,
            "metro_caps": self.n_metro_caps,
            "unconverged": self.n_unconverged,
            "retries": self.n_retries,
            "shed": self.n_shed,
            "unhealthy_devices": self.n_unhealthy_devices,
            "breaker_opens": self.breaker_opens,
        }

    def summary(self) -> dict:
        """Counters and derived figures.  ``solves_per_sec`` divides by
        ``solve_seconds``: each batch's host time from packing through
        the convergence check, which the ``fleet_service.*`` spans
        split into pack, seed, solve and read back."""
        return {
            "requests": self.n_requests,
            "solved": self.n_solved,
            "batches": self.n_batches,
            "solves_per_sec": self.solves_per_sec,
            "p50_latency_s": self.latency_percentile(50),
            "p99_latency_s": self.latency_percentile(99),
            "warm_fraction": self.warm_fraction,
            "cache_hit_fraction": (self.n_cache_hits / self.n_solved
                                   if self.n_solved else 0.0),
            "deadline_miss_rate": self.deadline_miss_rate,
            "preemptions": self.n_preemptions,
            "priority_fraction": (self.n_priority / self.n_requests
                                  if self.n_requests else 0.0),
            "closes": dict(self.closes),
            "mean_outer_iters": (self.outer_iters / self.n_batches
                                 if self.n_batches else 0.0),
            "mean_inner_iters": self.mean_inner_iters,
            "metro_ticks": self.n_metro_ticks,
            "mean_metro_outer_iters": (self.metro_outer_iters
                                       / self.n_metro_ticks
                                       if self.n_metro_ticks else 0.0),
            "metro_warm_fraction": (self.n_metro_warm / self.n_metro_ticks
                                    if self.n_metro_ticks else 0.0),
            "metro_caps": self.n_metro_caps,
            "unconverged": self.n_unconverged,
            "retries": self.n_retries,
            "shed": self.n_shed,
            "unhealthy_devices": self.n_unhealthy_devices,
            "breaker_opens": self.breaker_opens,
            "retry_backoff_s": self.retry_backoff_s,
        }


# the per-device leaves that discriminate problems; fading is appended
# when present.  Raw leaves rather than derived path gain / compute
# energy: same information, no recomputation on the request path.
_KEY_FIELDS = ("distance_m", "bandwidth_hz", "energy_budget_j",
               "dataset_size", "cycles_per_sample", "cpu_hz", "weights")


def _quantize(arr: np.ndarray, decimals: int) -> np.ndarray:
    return np.round(np.log10(np.maximum(np.abs(arr), 1e-300)), decimals)


def quantized_problem_key(problem: WirelessFLProblem,
                          decimals: int = 2) -> bytes:
    """Cache key: the problem's constraint data, log-quantised.

    Two problems map to the same key iff every per-device feature
    (distances, bandwidths, energy budgets, compute parameters, weights,
    fading) rounds to the same ``decimals`` digits in log10 and the
    static metadata matches exactly.  On a drifting channel this buckets
    "the same cell a moment later" together while separating genuinely
    different problems; the log domain makes the tolerance relative
    (energy budgets span 1e-4..1e2 J).
    """
    h = hashlib.sha1()
    h.update(repr([(f, getattr(problem, f))
                   for f in _STATIC_FIELDS]).encode())
    feats = [getattr(problem, f) for f in _KEY_FIELDS]
    if problem.fading is not None:
        feats.append(problem.fading)
    if problem.interference is not None:
        # the noise floor shifts the solution like any other feature;
        # offset by sigma^2 so log-quantisation stays relative to the
        # total noise (a zero-interference leaf keys like None modulo
        # the shape marker below)
        feats.append(np.asarray(problem.interference, np.float64)
                     + problem.noise_power)
        h.update(repr(problem.interference.shape).encode())
    if problem.bits is not None:
        # the payload scale changes tx time / P^min like bandwidth does;
        # shape marker separates an all-32 leaf from a bits=None problem
        # (their solutions coincide but their compiled programs differ)
        feats.append(np.asarray(problem.bits, np.float64))
        h.update(repr(problem.bits.shape).encode())
    for x in feats:
        q = _quantize(np.asarray(x, np.float64), decimals)
        h.update(repr(q.shape).encode())
        h.update(np.ascontiguousarray(q).tobytes())
    return h.digest()


def _compat_key(problem: WirelessFLProblem) -> tuple:
    """Requests sharing this key can be stacked into one ProblemBatch."""
    return (tuple(getattr(problem, f) for f in _STATIC_FIELDS),
            problem.fading is not None,
            None if problem.fading is None else problem.fading.shape[1],
            None if problem.interference is None
            else problem.interference.ndim,
            None if problem.bits is None else problem.bits.ndim)


def _next_pow2(n: int, floor: int = 1) -> int:
    """Smallest power of two >= ``max(n, floor, 1)``.

    The floor itself is rounded *up* to a power of two (``floor=12``
    yields 16, never 12), so every bucket the service registers — and
    ``warmup`` pre-compiles — is a true power of two.  Pinned by unit
    tests in ``tests/test_fleet_service.py``.
    """
    return 1 << (max(n, floor, 1) - 1).bit_length()


def batch_close_reason(batch: Sequence[SolveRequest], now: float,
                       est_cost_s: float,
                       config: ServiceConfig) -> Optional[str]:
    """The adaptive batch-close policy (continuous-batching idiom).

    Given the candidate micro-batch ``batch`` (the FIFO head-compatible
    prefix of one lane), decide whether it must close *now* rather than
    keep accumulating arrivals:

    * :data:`CLOSE_FULL` — all ``max_batch`` instance slots are taken;
      waiting longer cannot improve amortisation.
    * :data:`CLOSE_DEADLINE` — the tightest remaining budget
      ``min(deadline) - now`` has dropped to ``close_safety`` times the
      bucket's estimated solve cost: closing any later would make that
      request infeasible even with a perfect solve.  With continuous
      polling and an accurate estimate, a request whose budget covered
      the solve cost at submission is therefore *never* closed after its
      deadline (property-tested).
    * :data:`CLOSE_LINGER` — the oldest request has waited
      ``max_linger_s``, the universal wait bound: sparse traffic (and
      deadline-less traffic in particular) gets predictable latency
      instead of waiting forever for a full bucket.  Under load this
      rule stops firing on its own — the backlog reaches ``max_batch``
      between solves and the *full* rule takes over, which is exactly
      the continuous-batching degradation curve (small batches / low
      latency when idle, full buckets at saturation).

    Pure host-side function of (batch, clock, cost estimate, config) —
    the hypothesis suite drives it directly.  Returns the close reason,
    or ``None`` to keep accumulating.
    """
    if not batch:
        return None
    if len(batch) >= config.max_batch:
        return CLOSE_FULL
    budget = min(r.t_deadline for r in batch) - now
    if budget <= est_cost_s * config.close_safety:
        return CLOSE_DEADLINE
    if now - batch[0].t_submit >= config.max_linger_s:
        return CLOSE_LINGER
    return None


class BucketCostModel:
    """EWMA of measured per-bucket solve wall time (seconds).

    The close policy needs "how long will this bucket's solve take" to
    spend a request's remaining budget accumulating arrivals instead of
    closing too early.  Estimates start at ``prior_s`` and track
    measurements with weight ``alpha``; ``alpha=0`` freezes the prior,
    making close decisions a deterministic function of the arrival trace
    (the golden/determinism suites run in that mode).
    """

    def __init__(self, prior_s: float, alpha: float):
        self.prior_s = float(prior_s)
        self.alpha = float(alpha)
        self._est: dict[int, float] = {}

    def estimate(self, bucket: int) -> float:
        return self._est.get(bucket, self.prior_s)

    def observe(self, bucket: int, seconds: float) -> None:
        if self.alpha <= 0.0:
            return
        prev = self._est.get(bucket)
        self._est[bucket] = seconds if prev is None else \
            (1.0 - self.alpha) * prev + self.alpha * seconds

    def scale(self, factor: float) -> None:
        """Multiply the prior and every estimate by ``factor`` — the
        chaos harness's cost-spike hook (``repro.serve.faults``): an
        inflated estimate makes the close policy fire CLOSE_DEADLINE
        early, which is exactly how a real cost-model excursion degrades
        batching.  Measurements pull the estimates back (EWMA)."""
        self.prior_s *= float(factor)
        for bucket in self._est:
            self._est[bucket] *= float(factor)


class _LRU:
    """Tiny ordered-dict LRU (host-side; values are small jnp arrays)."""

    def __init__(self, capacity: int):
        self.capacity = capacity
        self._d: collections.OrderedDict = collections.OrderedDict()

    def get(self, key):
        if key not in self._d:
            return None
        self._d.move_to_end(key)
        return self._d[key]

    def put(self, key, value) -> None:
        self._d[key] = value
        self._d.move_to_end(key)
        while len(self._d) > self.capacity:
            self._d.popitem(last=False)

    def __len__(self) -> int:
        return len(self._d)


def _resize_problem(problem: WirelessFLProblem,
                    n: int) -> WirelessFLProblem:
    """A copy of ``problem`` with exactly ``n`` devices (leaves truncated
    or cyclically tiled).  ``warmup``'s dummy-instance builder: the
    values only pin jit input shapes/dtypes, never answers."""
    kw = {}
    for f in _PAD_VALUES:
        v = np.asarray(getattr(problem, f))
        kw[f] = jnp.asarray(np.resize(v, (n,) + v.shape[1:]))
    fad = problem.fading
    if fad is not None:
        fad = np.asarray(fad)
        fad = jnp.asarray(np.resize(fad, (n,) + fad.shape[1:]))
    itf = problem.interference
    if itf is not None:
        itf = np.asarray(itf)
        itf = jnp.asarray(np.resize(itf, (n,) + itf.shape[1:]))
    bits = problem.bits
    if bits is not None:
        bits = np.asarray(bits)
        bits = jnp.asarray(np.resize(bits, (n,) + bits.shape[1:]))
    return dataclasses.replace(problem, fading=fad, interference=itf,
                               bits=bits, **kw)


class FleetControlService:
    """The open-loop, continuously-batching, warm-starting control plane."""

    def __init__(self, config: Optional[ServiceConfig] = None):
        self.config = config = config if config is not None else ServiceConfig()
        self.stats = ServiceStats(config.latency_window)
        # two arrival lanes; the priority lane preempts the normal one
        self._queue: collections.deque[SolveRequest] = collections.deque()
        self._prio: collections.deque[SolveRequest] = collections.deque()
        # feature-keyed LRU: quantised problem -> WarmStart (unpadded)
        self._feature_cache = _LRU(config.cache_size)
        # per-cell last solution: the fallback seed when the channel
        # drifted past the quantisation step (new feature key)
        self._cell_cache = _LRU(config.cache_size)
        # per-cell last feature key — the drift detector feeding the
        # priority lane (cached state exists but its key went stale)
        self._cell_fkey = _LRU(config.cache_size)
        self._cost = BucketCostModel(config.prior_solve_s,
                                     config.cost_smoothing)
        # per-metro dual/warm state: metro_id -> CoupledDuals of the last
        # tick (padded bucket shapes; shape-checked on reuse)
        self._metro_duals = _LRU(config.cache_size)
        self.warmed_buckets: set[int] = set()   # AOT-precompiled buckets
        self.buckets_used: set[int] = set()     # buckets served so far
        self.batch_log: list[BatchRecord] = []  # when record_batches
        self._seq = 0
        # per-bucket circuit breaker: consecutive unconverged batches,
        # and remaining shed-batches while the breaker is open
        self._fail_streak: dict[int, int] = {}
        self._breaker_open: dict[int, int] = {}

    # ------------------------------------------------------------- warmup
    def warmup(self, template: WirelessFLProblem, *,
               max_devices: Optional[int] = None,
               warm: Optional[bool] = None) -> dict[int, float]:
        """AOT-precompile every power-of-two device bucket up front.

        Executes one dummy padded solve per (bucket, cold/warm-init)
        jit signature — ``template`` pins the request leaf dtypes and
        fading shape (pass a ``slice_round`` problem when serving sliced
        rounds), buckets run from ``min_device_bucket`` up to
        ``_next_pow2(max_devices)`` (default: the template's fleet
        size).  After warmup no live request pays a trace/compile: the
        first request's latency sits within the steady-state band
        (asserted by the warmup test and the openloop bench gate).

        ``stats`` are untouched; the caches are untouched (the dummy
        solves bypass the request path).  Returns ``{bucket: seconds}``
        (compile + execute wall time per bucket).
        """
        cfg = self.config
        hi = _next_pow2(max(max_devices or 0, template.n_devices),
                        cfg.min_device_bucket)
        warm = cfg.warm_start if warm is None else warm
        timings: dict[int, float] = {}
        b = _next_pow2(1, cfg.min_device_bucket)
        while b <= hi:
            prob = _resize_problem(template, b)
            batch = stack_problems([prob], batch_size=cfg.max_batch,
                                   n_max=b)
            t0 = time.perf_counter()
            jax.block_until_ready(self._solve(batch, init=None).a)
            if warm:
                z = jnp.zeros(self._sol_shape(batch), jnp.float32)
                jax.block_until_ready(
                    self._solve(batch, init=WarmStart(a=z, power=z)).a)
            timings[b] = time.perf_counter() - t0
            self.warmed_buckets.add(b)
            b *= 2
        return timings

    # ------------------------------------------------------------- intake
    def submit(self, cell_id: Hashable, problem: WirelessFLProblem, *,
               deadline_s: Optional[float] = None,
               priority: Optional[bool] = None,
               now: Optional[float] = None) -> SolveRequest:
        """Queue one per-cell solve request.

        ``deadline_s`` is the request's latency budget (defaults to
        ``ServiceConfig.default_deadline_s``; ``None`` = unbounded).
        ``priority=None`` auto-routes: a cell whose cached solution's
        feature key no longer matches the incoming problem has drifted
        past the quantisation step and jumps the priority lane (its
        cached answer is the most urgently wrong one).  ``now`` pins the
        arrival stamp for virtual-clock runs.

        With ``ServiceConfig.sanitize`` (the default), devices whose
        features are non-finite or non-positive — a corrupted channel, a
        deep fade to zero gain — are degraded to self-deselecting no-ops
        (``a = 0``, zero power) *before* the request enters the queue,
        so one poisoned device cannot NaN a whole micro-batch.  The
        count lands on ``SolveRequest.n_unhealthy`` and the response;
        a fully healthy problem takes this path untouched (bitwise).
        """
        trace = TraceAnnotation.is_enabled()
        with _span(trace, SPAN_SUBMIT) as span:
            now = time.perf_counter() if now is None else now
            cfg = self.config
            n_unhealthy = 0
            if cfg.sanitize:
                # host-side health check first: the all-healthy hot path
                # never allocates a sanitised copy
                health = problem.health_mask(xp=np)
                if not health.all():
                    n_unhealthy = int(health.size) - int(health.sum())
                    problem, _ = problem.sanitize(health=health)
            fkey = None
            if cfg.warm_start:
                with _span(trace, SPAN_KEY):
                    fkey = quantized_problem_key(problem, cfg.quant_decimals)
            if priority is None:
                last = self._cell_fkey.get(cell_id) if fkey is not None \
                    else None
                priority = last is not None and last != fkey
            if deadline_s is None:
                deadline_s = cfg.default_deadline_s
            self._seq += 1
            req = SolveRequest(
                cell_id=cell_id, problem=problem, t_submit=now,
                t_deadline=_INF if deadline_s is None else now + deadline_s,
                priority=bool(priority), fkey=fkey,
                ckey=_compat_key(problem), seq=self._seq,
                n_unhealthy=n_unhealthy)
            if trace:
                span.set_metadata(seq=req.seq, cell=cell_id,
                                  lane=_lane(req.priority))
            self.stats.n_requests += 1
            self.stats.n_priority += bool(req.priority)
            (self._prio if req.priority else self._queue).append(req)
            return req

    @property
    def pending(self) -> int:
        return len(self._prio) + len(self._queue)

    # ------------------------------------------------------------ serving
    def _eligible(self, lane) -> list[SolveRequest]:
        """The micro-batch that *would* close: the first ``max_batch``
        requests of ``lane`` stackable with its head (same static
        metadata / fading-ness), in FIFO order, without popping."""
        if not lane:
            return []
        key = lane[0].ckey
        out = []
        for req in lane:
            if req.ckey == key:
                out.append(req)
                if len(out) >= self.config.max_batch:
                    break
        return out

    def _take_micro_batch(self, lane) -> list[SolveRequest]:
        """Pop the ``_eligible`` requests; later incompatible requests
        keep their lane order."""
        if not lane:
            return []
        key = lane[0].ckey
        taken: list[SolveRequest] = []
        kept: collections.deque = collections.deque()
        while lane and len(taken) < self.config.max_batch:
            req = lane.popleft()
            (taken if req.ckey == key else kept).append(req)
        kept.extend(lane)
        lane.clear()
        lane.extend(kept)
        return taken

    def poll(self, now: Optional[float] = None) -> list[SolveResponse]:
        """The open-loop heartbeat: serve at most one micro-batch *iff*
        a lane's close condition holds (:func:`batch_close_reason`;
        priority lane checked first), else return ``[]`` immediately.

        Call it from the arrival driver between submissions.  ``now``
        runs the check (and stamps completions) on a virtual clock;
        omitted, wall ``perf_counter`` time is used throughout.
        """
        t = time.perf_counter() if now is None else now
        for lane, is_prio in ((self._prio, True), (self._queue, False)):
            elig = self._eligible(lane)
            if not elig:
                continue
            bucket = _next_pow2(max(r.problem.n_devices for r in elig),
                                self.config.min_device_bucket)
            reason = batch_close_reason(elig, t, self._cost.estimate(bucket),
                                        self.config)
            if reason is not None:
                return self._serve(self._take_micro_batch(lane), reason,
                                   priority_lane=is_prio, t_close=t,
                                   now=now)
        return []

    def step(self, now: Optional[float] = None) -> list[SolveResponse]:
        """Force-close one micro-batch (priority lane first) regardless
        of the close policy — the legacy synchronous mode, and the drain
        path (:data:`CLOSE_FORCED`)."""
        lane, is_prio = (self._prio, True) if self._prio \
            else (self._queue, False)
        reqs = self._take_micro_batch(lane)
        if not reqs:
            return []
        t_close = time.perf_counter() if now is None else now
        return self._serve(reqs, CLOSE_FORCED, priority_lane=is_prio,
                           t_close=t_close, now=now)

    def run(self, requests=None) -> list[SolveResponse]:
        """Submit ``requests`` (``(cell_id, problem)`` pairs, optional)
        and drain the queue with forced closes; responses in completion
        order (priority lane first)."""
        for cell_id, problem in (requests or []):
            self.submit(cell_id, problem)
        out = []
        while self.pending:
            out.extend(self.step())
        return out

    # ------------------------------------------------------------ resume
    def seed_cell(self, cell_id: Hashable, problem: WirelessFLProblem,
                  solution) -> None:
        """Re-seed the warm caches from an externally held solution.

        The crash-recovery hook (``fl.closed_loop`` checkpoint resume):
        a fresh service re-seeded with round k's checkpointed problem
        and solution warm-starts round k+1 exactly as the uninterrupted
        service would have — same seeds, same warm/cache-hit counters.
        ``solution`` is anything with ``.a`` / ``.power`` (a
        :class:`~repro.core.alternating.JointSolution` or ``WarmStart``).
        No-op when warm starts are disabled.
        """
        if not self.config.warm_start:
            return
        if self.config.sanitize:
            # mirror submit(): the caches are keyed on the sanitised
            # problem, so the seed must be too
            health = problem.health_mask(xp=np)
            if not health.all():
                problem, _ = problem.sanitize(health=health)
        fkey = quantized_problem_key(problem, self.config.quant_decimals)
        state = WarmStart(a=jnp.asarray(solution.a),
                          power=jnp.asarray(solution.power))
        self._feature_cache.put(fkey, state)
        self._cell_cache.put(cell_id, state)
        self._cell_fkey.put(cell_id, fkey)

    # ---------------------------------------------------- coupled metros
    def solve_coupled(self, metro_id: Hashable, metro: MultiCellProblem, *,
                      outer_iters: int = 25, outer_tol: float = 1e-3,
                      damping: float = 0.5) -> CoupledResponse:
        """Serve one coupled metro tick (``core.multicell.solve_coupled``).

        A metro tick is one synchronous unit of work — C cells coupled by
        interference and/or a shared backhaul budget cannot be answered
        per-cell, so it bypasses the per-request queue and runs the
        dual-decomposition loop directly, reusing the service machinery:

        * **buckets** — the metro is padded to power-of-two (cell,
          device) slot shapes via :func:`repro.core.multicell.pad_metro`,
          so jit compiles once per bucket across metros of drifting size;
        * **warm duals** — the converged ``(I, mu)`` prices and element
          iterates are cached per ``metro_id`` and seed the next tick
          (``CoupledDuals``); on a coherent channel the outer loop then
          collapses to one or two iterations (shape-mismatched state is
          dropped, so metro reconfigurations just run cold);
        * **accounting** — ``stats`` gains ``metro_ticks`` /
          ``metro_outer_iters`` / ``metro_warm`` / ``metro_caps``
          counters;
        * **spans** — under a profiler trace, :data:`SPAN_METRO` covers
          the tick, :data:`SPAN_METRO_PAD` its padding, and the outer
          loop writes ``multicell.*`` spans inside it.

        Uses the service's configured method/power solver/warm-start
        policy; ``outer_*`` and ``damping`` are per-call because the
        coupling strength is a property of the metro, not the service.
        """
        cfg = self.config
        trace = TraceAnnotation.is_enabled()
        with _span(trace, SPAN_METRO):
            t0 = time.perf_counter()
            n_cells = metro.n_cells
            bucket_c = _next_pow2(n_cells)
            bucket_n = _next_pow2(metro.cells.n_max, cfg.min_device_bucket)
            with _span(trace, SPAN_METRO_PAD):
                padded = pad_metro(metro, n_cells=bucket_c, n_max=bucket_n)
            per_round = padded.cells.problem.fading is not None
            i_shape = (bucket_c, padded.cells.problem.fading.shape[-1]) \
                if per_round else (bucket_c,)
            init: Optional[CoupledDuals] = \
                self._metro_duals.get(metro_id) if cfg.warm_start else None
            if init is not None and np.shape(init.interference) != i_shape:
                init = None               # metro resized: run cold
            sol = solve_coupled_core(
                padded, outer_iters=outer_iters, outer_tol=outer_tol,
                damping=damping, method=cfg.method,
                power_solver=cfg.power_solver, eps=cfg.eps,
                max_iters=cfg.max_iters, warm_start=cfg.warm_start,
                init=init, sanitize=cfg.sanitize)
            jax.block_until_ready(sol.batch.a)
            t1 = time.perf_counter()
            if cfg.warm_start:
                self._metro_duals.put(metro_id, sol.resume)
            self.buckets_used.add(bucket_n)
            self.stats.record_metro(t1 - t0, sol.outer_iters,
                                    warm=init is not None,
                                    hit_cap=sol.hit_iter_cap)
        return CoupledResponse(metro_id=metro_id, solution=sol,
                               n_cells=n_cells,
                               warm_started=init is not None,
                               latency_s=t1 - t0)

    # ------------------------------------------------------------- solve
    def _sol_shape(self, batch) -> tuple:
        return batch.mask.shape if batch.problem.fading is None \
            else batch.mask.shape + (batch.problem.fading.shape[-1],)

    def _solve(self, batch, init):
        cfg = self.config
        return solve_joint_batch(batch, method=cfg.method,
                                 power_solver=cfg.power_solver,
                                 eps=cfg.eps, max_iters=cfg.max_iters,
                                 init=init)

    def _lookup_seed(self, cell_id, fkey: bytes,
                     shape) -> tuple[Optional[WarmStart], bool]:
        """(seed, from_feature_cache) for one request, shape-checked."""
        seed = self._feature_cache.get(fkey)
        if seed is not None and seed.a.shape == shape:
            return seed, True
        seed = self._cell_cache.get(cell_id)
        if seed is not None and seed.a.shape == shape:
            return seed, False
        return None, False

    def _batch_span(self, trace: bool, reqs: list[SolveRequest],
                    reason: str, bucket: int, priority_lane: bool):
        """The batch's :data:`SPAN_SERVE` span and its metadata when
        ``trace``, else a no-op.  ``batch`` is the batch's index in
        ``stats`` (its ``batches`` count before it); ``seqs`` lists the
        answered requests, each of which has a ``submit`` span of that
        ``seq``."""
        if not trace:
            return _NO_SPAN
        return TraceAnnotation(
            SPAN_SERVE, batch=self.stats.n_batches, size=len(reqs),
            bucket=bucket, reason=reason, lane=_lane(priority_lane),
            seqs=" ".join(str(r.seq) for r in reqs))

    def _shed(self, reqs: list[SolveRequest], reason: str, bucket: int, *,
              priority_lane: bool, t_close: float,
              now: Optional[float] = None) -> list[SolveResponse]:
        """Degraded service while the bucket's circuit breaker is open:
        answer from the per-cell cache where a shape-matched solution
        exists, zeros (total self-deselection) otherwise — never a solve.
        Every response carries ``shed=True`` and ``converged=False``; the
        drain loops keep their liveness (requests always complete)."""
        trace = TraceAnnotation.is_enabled()
        with self._batch_span(trace, reqs, reason, bucket, priority_lane):
            t_done = time.perf_counter() if now is None else now
            responses = []
            for req in reqs:
                n = req.problem.n_devices
                shape = (n,) if req.problem.fading is None \
                    else (n, req.problem.fading.shape[1])
                seed = self._cell_cache.get(req.cell_id)
                cached = seed is not None and seed.a.shape == shape
                a = np.asarray(seed.a) if cached \
                    else np.zeros(shape, np.float32)
                p = np.asarray(seed.power) if cached \
                    else np.zeros(shape, np.float32)
                inst = JointSolution(
                    a=jnp.asarray(a), power=jnp.asarray(p),
                    objective=jnp.float32(0.0), n_iters=jnp.int32(0),
                    converged=jnp.asarray(False), inner_iters=jnp.int32(0))
                responses.append(SolveResponse(
                    cell_id=req.cell_id, solution=inst, warm_started=cached,
                    cache_hit=False, latency_s=t_done - req.t_submit,
                    deadline_missed=t_done > req.t_deadline, seq=req.seq,
                    converged=False, n_iters=0, n_unhealthy=req.n_unhealthy,
                    retried=False, shed=True))
            if self.config.record_batches:
                self.batch_log.append(BatchRecord(
                    seqs=tuple(r.seq for r in reqs),
                    cell_ids=tuple(r.cell_id for r in reqs),
                    n_bucket=bucket, reason=reason, priority=priority_lane))
            self.stats.record_batch(
                responses, 0.0, 0, 0, reason=reason, preempted=False,
                queue_wait_us=_queue_wait_us(reqs, t_close))
            return responses

    def _serve(self, reqs: list[SolveRequest], reason: str, *,
               priority_lane: bool, t_close: float,
               now: Optional[float] = None) -> list[SolveResponse]:
        """Pack one micro-batch, warm-start, solve, account.

        ``t_close`` is the clock reading at which ``poll``/``step``
        closed the batch (the queue-wait stamp); ``now`` pins the
        completion stamp on a virtual clock.  While a profiler trace
        runs, the batch's ``serve`` span holds five children that tile
        it: ``pack``, ``seed``, ``solve``, ``readback``, ``respond``."""
        cfg = self.config
        virtual = now is not None
        # a priority batch preempts whenever normal traffic is left waiting
        preempted = priority_lane and bool(self._queue)
        bucket = _next_pow2(max(r.problem.n_devices for r in reqs),
                            cfg.min_device_bucket)
        # open circuit breaker: shed this batch, burn one cooldown tick;
        # at zero the next batch is the half-open probe (a real solve)
        if self._breaker_open.get(bucket, 0) > 0:
            self._breaker_open[bucket] -= 1
            return self._shed(reqs, reason, bucket,
                              priority_lane=priority_lane, t_close=t_close,
                              now=now)
        trace = TraceAnnotation.is_enabled()
        with self._batch_span(trace, reqs, reason, bucket, priority_lane):
            t0 = time.perf_counter()
            with _span(trace, SPAN_PACK):
                batch = stack_problems([r.problem for r in reqs],
                                       batch_size=cfg.max_batch,
                                       n_max=bucket)
                sizes = [r.problem.n_devices for r in reqs]

            # per-request warm seeds, packed to the padded slot shape
            # (zero rows = "no previous state" = cold,
            # element_warm_lambda's fallback)
            with _span(trace, SPAN_SEED):
                sol_shape = self._sol_shape(batch)
                per_round = (len(sol_shape) == 3)
                init = None
                warm_flags = [False] * len(reqs)
                hit_flags = [False] * len(reqs)
                if cfg.warm_start:
                    a0 = np.zeros(sol_shape, np.float32)
                    p0 = np.zeros(sol_shape, np.float32)
                    for i, req in enumerate(reqs):
                        shape = (sizes[i], sol_shape[-1]) if per_round \
                            else (sizes[i],)
                        seed, hit = self._lookup_seed(req.cell_id, req.fkey,
                                                      shape)
                        if seed is None:
                            continue
                        warm_flags[i], hit_flags[i] = True, hit
                        a0[i, :shape[0]] = seed.a
                        p0[i, :shape[0]] = seed.power
                    if any(warm_flags):
                        init = WarmStart(a=jnp.asarray(a0),
                                         power=jnp.asarray(p0))

            retried = False
            with _span(trace, SPAN_SOLVE):
                sol = self._solve(batch, init=init)
                jax.block_until_ready(sol.a)
            with _span(trace, SPAN_READBACK):
                conv_real = np.asarray(sol.converged)[:len(reqs)]

            # graceful degradation: an unconverged batch gets ONE retry
            # through the reference path (alternating + Dinkelbach) with
            # a larger iteration budget; its result is taken wholesale.
            # The fast path stays bitwise untouched for converged batches.
            if cfg.retry_unconverged and not conv_real.all():
                retried = True
                with _span(trace, SPAN_SOLVE):
                    sol = solve_joint_batch(batch, method="alternating",
                                            power_solver="dinkelbach",
                                            eps=cfg.eps,
                                            max_iters=cfg.retry_max_iters,
                                            init=init)
                    jax.block_until_ready(sol.a)
                with _span(trace, SPAN_READBACK):
                    conv_real = np.asarray(sol.converged)[:len(reqs)]

            # per-bucket circuit breaker: consecutive still-unconverged
            # batches accumulate exponential backoff (accounted, never
            # slept — determinism) and eventually open the breaker
            if conv_real.all():
                self._fail_streak[bucket] = 0
            else:
                streak = self._fail_streak.get(bucket, 0) + 1
                self._fail_streak[bucket] = streak
                self.stats.retry_backoff_s += \
                    cfg.retry_backoff_s * (2.0 ** (min(streak, 24) - 1))
                if streak >= cfg.breaker_threshold:
                    self._breaker_open[bucket] = cfg.breaker_cooldown
                    self.stats.breaker_opens += 1

            t1 = time.perf_counter()
            self._cost.observe(bucket, t1 - t0)
            self.buckets_used.add(bucket)
            t_done = now if virtual else t1

            # one transfer per field for the whole batch, then numpy
            # slicing
            with _span(trace, SPAN_READBACK):
                a_np = np.asarray(sol.a)
                p_np = np.asarray(sol.power)
                obj_np = np.asarray(sol.objective)
                conv_np = np.asarray(sol.converged)
                outer_np = np.asarray(sol.n_iters)
                inner_np = np.asarray(sol.inner_iters)

            with _span(trace, SPAN_RESPOND):
                responses = []
                outer = int(np.max(outer_np))
                inner = int(np.sum(inner_np))
                for i, req in enumerate(reqs):
                    n = sizes[i]
                    inst = JointSolution(
                        a=a_np[i, :n], power=p_np[i, :n],
                        objective=obj_np[i],
                        n_iters=outer_np[i] if outer_np.ndim else outer_np,
                        converged=conv_np[i],
                        inner_iters=inner_np[i] if inner_np.ndim
                        else inner_np)
                    if cfg.warm_start:
                        state = inst.resume
                        self._feature_cache.put(req.fkey, state)
                        self._cell_cache.put(req.cell_id, state)
                        self._cell_fkey.put(req.cell_id, req.fkey)
                    responses.append(SolveResponse(
                        cell_id=req.cell_id, solution=inst,
                        warm_started=warm_flags[i], cache_hit=hit_flags[i],
                        latency_s=t_done - req.t_submit,
                        deadline_missed=t_done > req.t_deadline,
                        seq=req.seq, converged=bool(conv_np[i]),
                        n_iters=int(outer_np[i] if outer_np.ndim
                                    else outer_np),
                        n_unhealthy=req.n_unhealthy, retried=retried))
                if cfg.record_batches:
                    self.batch_log.append(BatchRecord(
                        seqs=tuple(r.seq for r in reqs),
                        cell_ids=tuple(r.cell_id for r in reqs),
                        n_bucket=bucket, reason=reason,
                        priority=priority_lane))
                self.stats.record_batch(
                    responses, t1 - t0, outer, inner, reason=reason,
                    preempted=preempted, retried=retried,
                    queue_wait_us=_queue_wait_us(reqs, t_close))
                return responses

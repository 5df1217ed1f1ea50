"""Scan-fused FL engine: whole training trajectories as one compiled program.

``repro.fl.engine.run_fl`` (the reference path) drives Algorithm 3 with a
Python ``for`` over rounds — one jit dispatch, several eager jnp calls and
a handful of host/device syncs per round.  That is fine for a single run
but dominates wall-clock for the paper's strategy-comparison grids
(probabilistic vs deterministic vs uniform vs equally-weighted, averaged
over seeds — Figures 1-2 / Tables I-IV).

This module compiles the *entire trajectory* instead:

* the round loop is a single :func:`jax.lax.scan` whose carry
  ``(params, key, cum_time, cum_energy)`` is donated by XLA between
  iterations — the scheduler's per-round Bernoulli participation draw and
  the power/tx-time lookup are fused into the scan body, and the server
  update (eq. 4) runs as either the fused weighted-loss backward pass or
  the stacked per-client path whose reduction is the ``masked_aggregate``
  Pallas kernel (on-device on TPU, interpret mode elsewhere);
* a whole sweep — (seed x strategy x scenario) — is ``jax.vmap`` of that
  scanned trajectory over a stacked :class:`TrajectoryPlan`, jitted once
  and optionally sharded over the local device mesh along the trajectory
  axis (``repro.core.batch.batch_sharding``).

Everything the scan body needs is precomputed into the plan: selection
probabilities per round, the tx-time/energy tables at the planned powers
(Sec. II-C), and the minibatch index schedule.  The plan mirrors the
reference engine's RNG streams exactly — the same jax key-split sequence
for participation and the same numpy ``Generator`` consumption for
minibatch choice — so a scanned trajectory reproduces ``run_fl`` to
floating-point tolerance (see ``tests/test_fl_scan.py``).

Strategy sampling is encoded as data so one compiled program serves every
scheduler: ``mode`` selects Bernoulli (probabilistic), fixed-mask
(deterministic / equally-weighted) or exact-M uniform sampling inside the
scan body via ``lax.switch``.

Typical use::

    plans = [plan_trajectory(problem, sch, parts, cfg) for sch, cfg in grid]
    sweep = run_fl_sweep(stack_plans(plans), train, test, cfg_static)
    res0  = sweep.result(0)        # FLResult, same layout as run_fl's
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.batch import ProblemBatch, batch_sharding
from repro.core.problem import WirelessFLProblem
from repro.core.schedulers import (
    DeterministicScheduler,
    EquallyWeightedScheduler,
    GreedyChannelScheduler,
    LyapunovScheduler,
    ProbabilisticScheduler,
    SchedulerState,
    UniformScheduler,
)
from repro.data.synthetic import Dataset
from repro.fl.engine import FLConfig, FLHistory, FLResult, _quantize_tree
from repro.kernels import resolve_interpret
from repro.kernels.masked_aggregate.ops import (masked_aggregate_pytree,
                                                quantized_aggregate_pytree)
from repro.models import cnn

# participation-sampling modes fused into the scan body (lax.switch index)
MODE_BERNOULLI = 0   # probabilistic: m_i ~ Bernoulli(a_ik)
MODE_FIXED = 1       # deterministic / equally-weighted: m_i = [a_ik > 0]
MODE_UNIFORM = 2     # uniform: exactly M clients via a random permutation


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TrajectoryPlan:
    """Everything one scanned trajectory needs, precomputed to tables.

    Per-round tables are ``[K, N]`` (round-major so the scan consumes them
    as xs); ``stack_plans`` adds a leading trajectory axis to every leaf.
    The tx-time/energy tables are evaluated at the scheduler's planned
    powers, so the scan body never touches the wireless problem — the
    power lookup reduces to reading the k-th row.
    """

    probs: jax.Array        # [K, N] selection probabilities a_ik
    tx_time: jax.Array      # [K, N] T_ik at the planned power P*_ik (eq. 1)
    round_energy: jax.Array  # [K, N] E^c_i + P*_ik T_ik per participant (eq. 6)
    comp_time: jax.Array    # [N] local computation time (include_compute_time)
    agg_weights: jax.Array  # [N] alpha_i for the server update (eq. 4)
    batch_idx: jax.Array    # [K, N, b] int32 planned client minibatches
    key: jax.Array          # PRNG key driving the in-scan participation draws
    lr: jax.Array           # scalar f32 learning rate
    mode: jax.Array         # scalar i32 sampling mode (MODE_*)
    m: jax.Array            # scalar i32 participant count (MODE_UNIFORM)
    unbiased: jax.Array     # scalar bool: alpha_i / a_ik correction
    dataset_id: jax.Array   # scalar i32 row into the stacked train/test sets
    # [K, N] bool, True = device i's round-k upload is LOST (chaos
    # injection, ``repro.serve.faults.dropout_mask``): the device is
    # masked out of the eq.-4 aggregation but its tx/compute energy is
    # still charged and the round still waits on it — the attempt
    # happened.  ``None`` (the default) keeps the fault-free compiled
    # program byte-identical; see docs/robustness.md.
    drops: Optional[jax.Array] = None
    # [K, N] f32 per-device per-round uplink bit widths b_ik: each
    # client's round-k gradient is stochastically rounded to b_ik bits
    # before the eq.-4 aggregation (``engine.quantize_stochastic``'s
    # stream, fused into the masked-sum kernel when ``use_kernel``).
    # ``None`` (the default) keeps the full-precision compiled program
    # byte-identical; see docs/compression.md.
    bits: Optional[jax.Array] = None

    @property
    def n_rounds(self) -> int:
        return int(self.probs.shape[-2])

    @property
    def n_devices(self) -> int:
        return int(self.probs.shape[-1])


class SweepResult(NamedTuple):
    """Stacked output of ``run_fl_sweep`` (leading trajectory axis)."""

    params: Any                  # pytree, every leaf [T, ...]
    histories: list[FLHistory]   # per-trajectory, same layout as run_fl's

    def result(self, t: int) -> FLResult:
        params = jax.tree_util.tree_map(lambda x: x[t], self.params)
        return FLResult(params=params, history=self.histories[t])


# ------------------------------------------------------------- sampling

def _draw_mask(sub: jax.Array, a_k: jax.Array, mode: jax.Array,
               m: jax.Array) -> jax.Array:
    """One round's participation mask; bit-identical to the schedulers'
    ``sample`` for the same subkey (the key stream is ``split`` per round
    exactly as in ``run_fl``)."""
    n = a_k.shape[0]

    def bernoulli(_):
        return jax.random.bernoulli(sub, a_k)

    def fixed(_):
        return a_k > 0

    def uniform(_):
        # UniformScheduler sets mask[perm[:m]]; equivalently rank(i) < m.
        perm = jax.random.permutation(sub, n)
        return jnp.argsort(perm) < m

    return jax.lax.switch(mode, (bernoulli, fixed, uniform), None)


def _subkey_stream(key0: jax.Array, n_rounds: int) -> jax.Array:
    """The reference engine's per-round subkeys: key, sub = split(key)."""
    def body(key, _):
        key, sub = jax.random.split(key)
        return key, sub

    _, subs = jax.lax.scan(body, key0, None, length=n_rounds)
    return subs


@jax.jit
def _mask_stream(key0: jax.Array, probs: jax.Array, mode: jax.Array,
                 m: jax.Array) -> jax.Array:
    """All rounds' participation masks [K, N] — the planner's preview of
    the draws the scan body will re-derive from the same key."""
    subs = _subkey_stream(key0, probs.shape[0])
    return jax.vmap(_draw_mask, in_axes=(0, 0, None, None))(subs, probs,
                                                            mode, m)


# ------------------------------------------------------------- planning

def _scheduler_mode(scheduler) -> tuple[int, int, bool]:
    """(mode, m, unbiased) encoding of a scheduler's sampling behaviour."""
    if isinstance(scheduler, ProbabilisticScheduler):
        return MODE_BERNOULLI, 0, bool(scheduler.unbiased_aggregation)
    if isinstance(scheduler, (DeterministicScheduler, EquallyWeightedScheduler,
                              GreedyChannelScheduler, LyapunovScheduler)):
        return MODE_FIXED, 0, False
    if isinstance(scheduler, UniformScheduler):
        return MODE_UNIFORM, int(scheduler.m), False
    raise TypeError(
        f"cannot fuse scheduler {type(scheduler).__name__}; expected one of "
        "Probabilistic/Deterministic/Uniform/EquallyWeighted/"
        "GreedyChannel/Lyapunov")


def _per_round(x: np.ndarray, n_rounds: int, name: str) -> np.ndarray:
    """[N] or [N, K_sol] -> round-major [K, N]."""
    x = np.asarray(x, np.float32)
    if x.ndim == 1:
        return np.broadcast_to(x, (n_rounds, x.shape[0]))
    if x.shape[1] < n_rounds:
        raise ValueError(
            f"{name} covers {x.shape[1]} fading rounds but the config asks "
            f"for {n_rounds}; regenerate the scenario with n_rounds >= that")
    return np.ascontiguousarray(x[:, :n_rounds].T)


def plan_trajectory(problem: WirelessFLProblem,
                    scheduler,
                    parts: Sequence[np.ndarray],
                    config: FLConfig,
                    *,
                    state: Optional[SchedulerState] = None,
                    dataset_id: int = 0,
                    drops: Optional[np.ndarray] = None,
                    bits: Optional[np.ndarray] = None) -> TrajectoryPlan:
    """Build one trajectory's plan, mirroring ``run_fl``'s RNG streams.

    ``state`` lets callers reuse one (possibly batched) ``precompute``
    across many seeds — the solve is by far the most expensive part of
    planning.  The minibatch schedule consumes a
    ``np.random.default_rng(config.seed)`` exactly as the reference
    engine does (draws happen only on rounds with at least one
    participant), so the scanned trajectory is reproducible against it.

    ``drops`` is an optional ``[K, N]`` bool upload-loss table (True =
    the round-k upload from device i never arrives); it rides on the
    plan and switches the sweep into degraded-aggregation mode.

    ``bits`` is an optional ``[N]`` or ``[K, N]`` uplink bit-width table
    (e.g. ``solve_joint_fused(..., bit_menu=...)``'s per-device choice);
    ``config.uplink_bits`` is shorthand for a uniform table.  Either
    switches the sweep into quantized-aggregation mode, which needs
    ``aggregate='stacked'`` (per-client gradients must exist to
    quantise) and mirrors ``run_fl``'s quantiser key stream exactly.
    """
    if config.uplink_bits is not None:
        if bits is not None:
            raise ValueError(
                "pass either config.uplink_bits (uniform) or a per-device "
                "bits table, not both")
        bits = np.full(problem.n_devices, float(config.uplink_bits),
                       np.float32)
    if bits is not None and config.aggregate != "stacked":
        raise ValueError("uplink quantisation requires aggregate='stacked' "
                         "(per-client gradients must exist to quantise)")
    n = problem.n_devices
    assert len(parts) == n
    k_rounds = config.n_rounds
    b = config.batch_per_client
    state = scheduler.precompute(problem) if state is None else state
    mode, m, unbiased = _scheduler_mode(scheduler)

    probs = _per_round(np.asarray(state.a), k_rounds, "selection probabilities")
    t_table = _per_round(np.asarray(problem.tx_time(state.power)), k_rounds,
                         "tx-time table")
    ec = np.asarray(problem.compute_energy(), np.float32)
    e_up = _per_round(np.asarray(problem.upload_energy(state.power)),
                      k_rounds, "upload-energy table")
    comp = np.asarray(problem.cycles_per_sample * problem.dataset_size
                      / problem.cpu_hz, np.float32)

    key0 = jax.random.PRNGKey(config.seed)
    masks = np.asarray(_mask_stream(key0, jnp.asarray(probs),
                                    jnp.int32(mode), jnp.int32(m)))

    # minibatch schedule: same generator, same consumption order as run_fl
    rng = np.random.default_rng(config.seed)
    batch_idx = np.zeros((k_rounds, n, b), np.int32)
    for k in range(k_rounds):
        if masks[k].any():
            batch_idx[k] = np.stack([
                rng.choice(parts[i], size=b, replace=len(parts[i]) < b)
                for i in range(n)])

    return TrajectoryPlan(
        probs=jnp.asarray(probs),
        tx_time=jnp.asarray(t_table),
        round_energy=jnp.asarray(e_up + ec[None, :]),
        comp_time=jnp.asarray(comp),
        agg_weights=jnp.asarray(state.agg_weights, jnp.float32),
        batch_idx=jnp.asarray(batch_idx),
        key=key0,
        lr=jnp.float32(config.lr),
        mode=jnp.int32(mode),
        m=jnp.int32(m),
        unbiased=jnp.asarray(unbiased),
        dataset_id=jnp.int32(dataset_id),
        drops=None if drops is None else jnp.asarray(drops, bool),
        bits=None if bits is None else jnp.asarray(
            _per_round(np.asarray(bits), k_rounds, "bit-width table")),
    )


def plans_from_batch(batch: ProblemBatch,
                     scheduler: ProbabilisticScheduler,
                     parts_list: Sequence[Sequence[np.ndarray]],
                     configs: Sequence[FLConfig],
                     dataset_ids: Optional[Sequence[int]] = None,
                     **solve_kw) -> list[TrajectoryPlan]:
    """One batched solve (PR 1's ``precompute_batch``) -> per-instance plans.

    All instances must share a fleet size (ragged batches pad device
    slots, and the sweep's uniform sampler draws over the padded axis, so
    padding would change the Uniform strategy's stream).  Use this to
    drive a registry-scenario ensemble through the sweep engine with a
    single device-sharded solve.
    """
    sizes = np.asarray(batch.fleet_sizes)
    if not (sizes == sizes[0]).all():
        raise ValueError(
            f"plans_from_batch needs a uniform fleet size, got {sizes}; "
            "stack equal-N instances (no padding) for the FL sweep")
    state = scheduler.precompute_batch(batch, **solve_kw)
    problems = batch.unstack()
    if dataset_ids is None:
        dataset_ids = range(len(problems))
    plans = []
    for i, (problem, parts, cfg, ds) in enumerate(
            zip(problems, parts_list, configs, dataset_ids)):
        st = SchedulerState(a=state.a[i], power=state.power[i],
                            agg_weights=state.agg_weights[i])
        plans.append(plan_trajectory(problem, scheduler, parts, cfg,
                                     state=st, dataset_id=int(ds)))
    return plans


def stack_plans(plans: Sequence[TrajectoryPlan]) -> TrajectoryPlan:
    """Stack per-trajectory plans along a new leading sweep axis."""
    if not plans:
        raise ValueError("stack_plans needs at least one plan")
    with_drops = sum(p.drops is not None for p in plans)
    if 0 < with_drops < len(plans):
        raise ValueError(
            "cannot stack plans with and without drop tables; give the "
            "fault-free plans an all-False [K, N] drops array")
    with_bits = sum(p.bits is not None for p in plans)
    if 0 < with_bits < len(plans):
        raise ValueError(
            "cannot stack plans with and without bit-width tables; give "
            "the full-precision plans an all-32 [K, N] bits array")
    ref = plans[0]
    for p in plans[1:]:
        if (p.n_rounds, p.n_devices, p.batch_idx.shape) != (
                ref.n_rounds, ref.n_devices, ref.batch_idx.shape):
            raise ValueError(
                "all plans in a sweep must share (n_rounds, n_devices, "
                f"batch_per_client); got {p.probs.shape} vs {ref.probs.shape}")
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *plans)


# ----------------------------------------------------------- compiled core

class _Static(NamedTuple):
    """Hashable compile-time configuration of the sweep program."""

    n_rounds: int
    batch_per_client: int
    aggregate: str              # "fused" | "stacked"
    renormalize: bool
    include_compute_time: bool
    eval_rounds: tuple[int, ...]
    use_kernel: bool            # stacked path: masked_aggregate Pallas kernel
    kernel_interpret: bool
    donate: bool
    faulted: bool               # plan carries a drops table (degraded mode)
    quantized: bool             # plan carries a bits table (uplink quantise)


def _eval_rounds(config: FLConfig) -> tuple[int, ...]:
    """The reference engine's eval schedule: every eval_every-th round plus
    the final one."""
    ks = [k for k in range(config.n_rounds)
          if (k + 1) % config.eval_every == 0 or k == config.n_rounds - 1]
    return tuple(dict.fromkeys(ks))


@functools.lru_cache(maxsize=32)
def _sweep_fn(static: _Static):
    """Build (and cache) the jitted vmapped whole-sweep program."""
    b = static.batch_per_client
    fused = static.aggregate == "fused"

    def aggregate(gstack, coef):
        if static.use_kernel:
            return masked_aggregate_pytree(gstack, coef,
                                           interpret=static.kernel_interpret)
        return jax.tree_util.tree_map(
            lambda g: jnp.tensordot(coef, g, axes=((0,), (0,))), gstack)

    def trajectory(plan: TrajectoryPlan, params0,
                   train_x, train_y, test_x, test_y):
        n = plan.n_devices
        images = train_x[plan.dataset_id]      # [n_train, 28, 28, 1]
        labels = train_y[plan.dataset_id]

        def round_body(carry, xs):
            params, key, cum_t, cum_e = carry
            a_k, t_k, e_k, idx = xs[:4]
            rest = list(xs[4:])
            drop_k = rest.pop(0) if static.faulted else None
            bits_k = rest.pop(0) if static.quantized else None
            key, sub = jax.random.split(key)
            mask = _draw_mask(sub, a_k, plan.mode, plan.m)
            fmask = mask.astype(jnp.float32)
            any_part = jnp.any(mask)

            # -- accounting (paper Sec. V-B): straggler tx time, summed E --
            # charged over the *attempted* mask even in degraded mode: a
            # lost upload still spent its tx/compute energy and the round
            # still waited on the straggler (docs/robustness.md)
            t_eff = t_k + plan.comp_time if static.include_compute_time else t_k
            round_time = jnp.where(
                any_part, jnp.max(jnp.where(mask, t_eff, -jnp.inf)), 0.0)
            round_energy = jnp.sum(jnp.where(mask, e_k, 0.0))

            # -- server update (eq. 4) --------------------------------------
            # degraded mode: survivors = attempted minus lost uploads; only
            # they enter the aggregation (renormalize redistributes their
            # weight, else the update is simply smaller)
            if drop_k is not None:
                mask = mask & ~drop_k
                fmask = mask.astype(jnp.float32)
            alpha = plan.agg_weights
            alpha = jnp.where(plan.unbiased,
                              alpha / jnp.maximum(a_k, 1e-6), alpha)
            coef = alpha * fmask
            if static.renormalize:
                coef = coef / jnp.maximum(coef.sum(), 1e-12)
            img = images[idx]                  # [N, b, 28, 28, 1]
            lab = labels[idx]
            if fused:
                sw = (jnp.repeat(coef, b) / b).astype(jnp.float32)
                grads = jax.grad(cnn.loss_fn)(
                    params, img.reshape(n * b, 28, 28, 1),
                    lab.reshape(n * b), sw)
            else:
                def client_grad(ci, cl):
                    return jax.grad(cnn.loss_fn)(params, ci, cl)
                gstack = jax.vmap(client_grad)(img, lab)
                if bits_k is not None:
                    # fold_in (not split): same quantiser key stream as
                    # the reference engine's stacked path
                    qkey = jax.random.fold_in(sub, 1)
                    if static.use_kernel:
                        grads = quantized_aggregate_pytree(
                            gstack, coef, qkey, bits_k,
                            interpret=static.kernel_interpret)
                    else:
                        grads = aggregate(
                            _quantize_tree(gstack, qkey, bits_k), coef)
                else:
                    grads = aggregate(gstack, coef)
            # an all-zero coef (empty round) makes grads exactly zero, so
            # the update is a no-op — same outcome as the reference's skip
            params = jax.tree_util.tree_map(
                lambda p, g: p - plan.lr * g, params, grads)

            carry = (params, key, cum_t + round_time, cum_e + round_energy)
            return carry, (round_time, round_energy,
                           jnp.sum(mask).astype(jnp.int32))

        xs = (plan.probs, plan.tx_time, plan.round_energy, plan.batch_idx)
        if static.faulted:
            xs = xs + (plan.drops,)
        if static.quantized:
            xs = xs + (plan.bits,)
        carry = (params0, plan.key, jnp.float32(0.0), jnp.float32(0.0))
        ys_parts, accs = [], []
        start = 0
        for end in static.eval_rounds:         # static segment boundaries
            seg = jax.tree_util.tree_map(
                lambda x, s=start, e=end: x[s:e + 1], xs)
            carry, ys = jax.lax.scan(round_body, carry, seg)
            ys_parts.append(ys)
            logits = cnn.apply(carry[0], test_x[plan.dataset_id])
            accs.append(jnp.mean(
                (jnp.argmax(logits, -1) == test_y[plan.dataset_id]
                 ).astype(jnp.float32)))
            start = end + 1

        ys = jax.tree_util.tree_map(
            lambda *parts: jnp.concatenate(parts), *ys_parts)
        return carry[0], ys, jnp.stack(accs)

    def sweep(plans, params0, train_x, train_y, test_x, test_y):
        return jax.vmap(trajectory, in_axes=(0, 0, None, None, None, None))(
            plans, params0, train_x, train_y, test_x, test_y)

    donate = (1,) if static.donate else ()
    return jax.jit(sweep, donate_argnums=donate)


# ------------------------------------------------------------- public API

def _stack_datasets(data: Dataset | Sequence[Dataset]):
    if isinstance(data, Dataset):
        data = [data]
    x = jnp.asarray(np.stack([d.images for d in data]))
    y = jnp.asarray(np.stack([d.labels for d in data]))
    return x, y


def run_fl_sweep(plans: TrajectoryPlan,
                 train: Dataset | Sequence[Dataset],
                 test: Dataset | Sequence[Dataset],
                 config: FLConfig,
                 init_params: Any,
                 *,
                 use_kernel: bool = False,
                 kernel_interpret: Optional[bool] = None,
                 shard: bool = True,
                 donate_params: Optional[bool] = None) -> SweepResult:
    """Run every trajectory of a stacked plan as one jitted, sharded call.

    ``plans`` is a ``stack_plans`` output ([T, ...] leaves);
    ``init_params`` a per-trajectory stacked params pytree (the reference
    engine inits from ``PRNGKey(seed + 17)`` — see ``init_sweep_params``).
    ``train``/``test`` may be a single shared dataset or one per
    ``dataset_id``.  ``use_kernel`` routes the stacked aggregation through
    the ``masked_aggregate`` Pallas kernel (compiled on TPU; interpret
    mode elsewhere unless ``kernel_interpret`` overrides).  ``shard``
    splits the trajectory axis over the local devices.  ``donate_params``
    donates the init-params buffers to the call (default: on accelerators
    only — donation invalidates the caller's copy).
    """
    n_traj = int(plans.probs.shape[0])
    if plans.n_rounds != config.n_rounds:
        raise ValueError(f"plan has {plans.n_rounds} rounds, "
                         f"config.n_rounds={config.n_rounds}")
    kernel_interpret = resolve_interpret(kernel_interpret)
    if donate_params is None:
        donate_params = jax.default_backend() != "cpu"
    static = _Static(
        n_rounds=config.n_rounds, batch_per_client=config.batch_per_client,
        aggregate=config.aggregate, renormalize=config.renormalize,
        include_compute_time=config.include_compute_time,
        eval_rounds=_eval_rounds(config), use_kernel=use_kernel,
        kernel_interpret=kernel_interpret, donate=donate_params,
        faulted=plans.drops is not None,
        quantized=plans.bits is not None)
    if config.aggregate not in ("fused", "stacked"):
        raise ValueError(f"unknown aggregate mode {config.aggregate!r}")
    if use_kernel and config.aggregate != "stacked":
        raise ValueError("use_kernel requires aggregate='stacked'")
    if plans.bits is not None and config.aggregate != "stacked":
        raise ValueError("quantized plans (bits tables) require "
                         "aggregate='stacked'")
    if config.uplink_bits is not None and plans.bits is None:
        raise ValueError("config.uplink_bits is set but the stacked plans "
                         "carry no bits table; build them with "
                         "plan_trajectory(..., config) so the table exists")

    train_x, train_y = _stack_datasets(train)
    test_x, test_y = _stack_datasets(test)

    sharding = batch_sharding(n_traj) if shard else None
    if sharding is not None:
        plans = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, sharding), plans)
        init_params = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, sharding), init_params)

    params, ys, accs = _sweep_fn(static)(
        plans, init_params, train_x, train_y, test_x, test_y)
    round_time, round_energy, participants = jax.device_get(ys)
    accs = np.asarray(jax.device_get(accs))

    eval_rounds = np.asarray(static.eval_rounds)
    histories = []
    for t in range(n_traj):
        # float64 cumulation matches the reference engine's python-float
        # accumulation of per-round float32 increments
        sim_time = np.cumsum(round_time[t], dtype=np.float64)
        energy = np.cumsum(round_energy[t], dtype=np.float64)
        histories.append(FLHistory(
            rounds=np.arange(config.n_rounds),
            sim_time=sim_time, energy=energy,
            participants=np.asarray(participants[t]),
            eval_rounds=eval_rounds,
            eval_time=sim_time[eval_rounds],
            eval_acc=accs[t]))
    return SweepResult(params=params, histories=histories)


def init_sweep_params(configs: Sequence[FLConfig]) -> Any:
    """Per-trajectory model inits, stacked — the reference engine's
    ``cnn.init(PRNGKey(seed + 17))`` per config."""
    inits = [cnn.init(jax.random.PRNGKey(c.seed + 17)) for c in configs]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *inits)


def run_fl_scan(problem: WirelessFLProblem,
                scheduler,
                train: Dataset,
                parts: Sequence[np.ndarray],
                test: Dataset,
                config: FLConfig,
                init_params: Any | None = None,
                **sweep_kw) -> FLResult:
    """Drop-in scan-fused replacement for ``run_fl`` (one trajectory).

    Same signature and history layout as the reference engine; the
    trajectory agrees with it to float tolerance (same participation and
    minibatch streams, same eq.-4 update, same accounting).
    """
    plan = plan_trajectory(problem, scheduler, parts, config)
    plans = jax.tree_util.tree_map(lambda x: x[None], plan)
    if init_params is None:
        params0 = init_sweep_params([config])
    else:
        params0 = jax.tree_util.tree_map(lambda x: x[None], init_params)
    sweep = run_fl_sweep(plans, train, test, config, params0, **sweep_kw)
    return sweep.result(0)

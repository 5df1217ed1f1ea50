"""Core library: the paper's joint probabilistic client selection and
power allocation for federated learning (Marnissi et al., 2024)."""
from repro.core.alternating import (
    FleetElements,
    JointSolution,
    WarmStart,
    fused_fixed_point,
    fused_fixed_point_flat,
    problem_elements,
    select_best_bits,
    solve_joint,
    solve_joint_fused,
    solve_joint_trace,
)
from repro.core.batch import (
    BatchSolution,
    ProblemBatch,
    batch_elements,
    pad_batch,
    shard_batch,
    solve_joint_batch,
    stack_problems,
)
from repro.core.multicell import (
    CoupledDuals,
    MultiCellProblem,
    MultiCellSolution,
    cell_interference,
    grid_coupling,
    hex_coupling,
    make_multicell,
    solve_coupled,
    solve_coupled_loop,
)
from repro.core.optimal import solve_joint_optimal
from repro.core.power import PowerSolution, analytic_power, dinkelbach_power, energy_bound_ok
from repro.core.problem import (GRAD_SIZE_BITS_FP32, WirelessFLProblem,
                                sample_problem)
from repro.core.schedulers import (
    SCHEDULERS,
    DeterministicScheduler,
    EquallyWeightedScheduler,
    GreedyChannelScheduler,
    LyapunovScheduler,
    ParticipationDraw,
    ProbabilisticScheduler,
    SchedulerState,
    UniformScheduler,
    make_scheduler,
)
from repro.core.scenarios import (
    SCENARIOS,
    Scenario,
    gauss_markov_fading,
    make_batch,
    make_mixed_batch,
    make_problem,
    slice_round,
)
from repro.core.selection import optimal_selection

__all__ = [
    "WirelessFLProblem", "sample_problem", "GRAD_SIZE_BITS_FP32",
    "select_best_bits",
    "ProblemBatch", "BatchSolution", "stack_problems", "shard_batch",
    "solve_joint_batch", "batch_elements", "pad_batch", "WarmStart",
    "Scenario", "SCENARIOS", "make_problem", "make_batch", "make_mixed_batch",
    "gauss_markov_fading", "slice_round",
    "PowerSolution", "dinkelbach_power", "analytic_power", "energy_bound_ok",
    "optimal_selection",
    "JointSolution", "solve_joint", "solve_joint_trace", "solve_joint_optimal",
    "solve_joint_fused", "FleetElements", "problem_elements",
    "fused_fixed_point", "fused_fixed_point_flat",
    "MultiCellProblem", "MultiCellSolution", "CoupledDuals",
    "make_multicell", "grid_coupling", "hex_coupling", "cell_interference",
    "solve_coupled", "solve_coupled_loop",
    "ParticipationDraw", "SchedulerState",
    "ProbabilisticScheduler", "DeterministicScheduler", "UniformScheduler",
    "EquallyWeightedScheduler", "GreedyChannelScheduler", "LyapunovScheduler",
    "SCHEDULERS", "make_scheduler",
]

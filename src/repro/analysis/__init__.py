"""Jaxpr-level static analysis gate.

Four passes, each usable standalone and wired into CI by
``tools/run_analysis.py --gate``:

* :mod:`repro.analysis.recompile` — :class:`CompileBudget`, the
  XLA-compilation counter/sentinel.
* :mod:`repro.analysis.hotpaths` — registered production hot paths and
  their steady-state compile budgets (``analysis/budgets.json``).
* :mod:`repro.analysis.prng` — PRNG key-reuse detector over jaxprs.
* :mod:`repro.analysis.rank` — exhaustive [N]/[N,K] rank-contract
  sweeps over ``WirelessFLProblem``.
* :mod:`repro.analysis.hygiene` — host-sync / donation / weak-type
  audits of the traced code.

See ``docs/analysis.md`` for the pass catalog and how to register new
hot paths or problem leaves.
"""
from repro.analysis.hotpaths import (HOT_PATHS, default_budgets_path,
                                     load_budgets, measure, measure_all,
                                     register_hot_path)
from repro.analysis.hygiene import (HygieneFinding, run_hygiene,
                                    scan_host_syncs, weak_scalar_findings)
from repro.analysis.prng import (PRNG_PROGRAMS, KeyReuseFinding,
                                 analyze_jaxpr, check_key_reuse)
from repro.analysis.rank import (RankFinding, broadcastable_leaves,
                                 sweep_rank_contract)
from repro.analysis.recompile import CompileBudget, CompileBudgetExceeded

__all__ = [
    "HOT_PATHS",
    "PRNG_PROGRAMS",
    "CompileBudget",
    "CompileBudgetExceeded",
    "HygieneFinding",
    "KeyReuseFinding",
    "RankFinding",
    "analyze_jaxpr",
    "broadcastable_leaves",
    "check_key_reuse",
    "default_budgets_path",
    "load_budgets",
    "measure",
    "measure_all",
    "register_hot_path",
    "run_hygiene",
    "scan_host_syncs",
    "sweep_rank_contract",
    "weak_scalar_findings",
]

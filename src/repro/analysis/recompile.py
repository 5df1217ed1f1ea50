"""Recompile sentinel: count XLA compilations per entry point.

The repo's worst perf regression (PR 7) was invisible to every
correctness test: bare ``solve_joint_fused`` re-traced its eager
``while_loop`` on every call, and the C=64 multicell bench died of mmap
exhaustion before any assertion could fire.  This module makes "how
many XLA programs did this block of code build?" a first-class,
assertable quantity.

Mechanism
---------
``jax.monitoring`` emits ``/jax/core/compile/backend_compile_duration``
once per *actual* backend compilation — cache hits (both the in-process
jit cache and the persistent compilation cache) emit nothing, which is
exactly the semantics a steady-state budget wants.  Each
``CompileBudget`` registers its own listener on entry and unregisters
it on exit, so nothing outlives the block.

Compiled-program names come from the ``jax._src.dispatch`` debug log
("Finished XLA compilation of jit(<name>) ...") — captured with a
handler only while a ``CompileBudget`` is active, so steady-state
overhead is zero.  Names are best-effort (internal log format); the
*count* is the contract.

Usage::

    with CompileBudget(budget=0, name="steady-state step") as cb:
        service.step()
    # raises CompileBudgetExceeded listing the offending programs

Budgets for the registered hot paths live in ``analysis/budgets.json``
and are enforced by ``tools/run_analysis.py --gate`` (see
``repro.analysis.hotpaths``).
"""
from __future__ import annotations

import logging
import re
import threading
from typing import Optional

import jax

__all__ = [
    "CompileBudget",
    "CompileBudgetExceeded",
]

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


# "Finished XLA compilation of jit(solve) in 0.123 sec"
_NAME_RE = re.compile(r"Finished XLA compilation of (?P<name>.+) in ")
_DISPATCH_LOGGER = "jax._src.dispatch"


class _NameCapture(logging.Handler):
    """Collects compiled-program names while attached."""

    def __init__(self) -> None:
        super().__init__(level=logging.DEBUG)
        self.names: list[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        m = _NAME_RE.search(record.getMessage())
        if m:
            self.names.append(m.group("name"))


class CompileBudgetExceeded(RuntimeError):
    """More XLA compilations happened inside a ``CompileBudget`` block
    than its budget allows."""


class CompileBudget:
    """Context manager that counts XLA compilations in its block.

    ``budget=None`` only measures; an integer budget raises
    ``CompileBudgetExceeded`` on exit when exceeded (unless
    ``strict=False``, for callers that want to inspect ``count``
    themselves — the pytest fixtures do).

    Attributes after exit: ``count`` (backend compilations inside the
    block) and ``names`` (best-effort compiled-program names).
    """

    def __init__(self, budget: Optional[int] = 0, *,
                 name: str = "", strict: bool = True) -> None:
        self.budget = budget
        self.name = name
        self.strict = strict
        self.count: int = 0
        self.names: list[str] = []
        self._lock = threading.Lock()
        self._handler: Optional[_NameCapture] = None
        self._prev_level: Optional[int] = None
        self._prev_propagate: Optional[bool] = None

    def __enter__(self) -> "CompileBudget":
        logger = logging.getLogger(_DISPATCH_LOGGER)
        self._handler = _NameCapture()
        self._prev_level = logger.level
        self._prev_propagate = logger.propagate
        logger.addHandler(self._handler)
        # the dispatch timers always log; at DEBUG unless jax_log_compiles.
        # Propagation is paused so lowering the level does not spray the
        # debug stream onto the root handlers while we capture.
        if logger.getEffectiveLevel() > logging.DEBUG:
            logger.setLevel(logging.DEBUG)
            logger.propagate = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == _COMPILE_EVENT:
            with self._lock:
                self.count += 1

    def __exit__(self, exc_type, exc, tb) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)
        logger = logging.getLogger(_DISPATCH_LOGGER)
        if self._handler is not None:
            self.names = list(self._handler.names)
            logger.removeHandler(self._handler)
            self._handler = None
        if self._prev_level is not None:
            logger.setLevel(self._prev_level)
            self._prev_level = None
        if self._prev_propagate is not None:
            logger.propagate = self._prev_propagate
            self._prev_propagate = None
        if (exc_type is None and self.strict
                and self.budget is not None and self.count > self.budget):
            label = f" [{self.name}]" if self.name else ""
            raise CompileBudgetExceeded(
                f"compile budget exceeded{label}: {self.count} XLA "
                f"compilation(s), budget {self.budget}; programs: "
                f"{self.names or '<names unavailable>'}")

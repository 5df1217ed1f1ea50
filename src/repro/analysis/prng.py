"""PRNG key-reuse detector: jaxpr-level key equivalence-class tracking.

The RNG-parity contract (``run_fl`` == scan engine == quantized
aggregation, bit-for-bit) currently rests on example-based tests.  This
pass makes the *structural* half machine-checked: no ``random.*``
consumer may be reached by the same key equivalence class twice without
an interleaved ``split`` / ``fold_in``.  That is exactly the bug class
PR 9's quantizer audit found by hand (one subkey feeding both the
participation draw and the quantiser noise).

How it works
------------
``jax.make_jaxpr`` traces the program; the walker interprets the jaxpr
abstractly, mapping every variable that carries PRNG state (typed
``key<fry>`` arrays *or* raw ``uint32[..., 2]`` buffers flowing through
``random_wrap``/``random_unwrap``) to a *key class* — a hashable path
identifying the logical key:

* roots: each distinct input/constant key is its own class;
* ``random_split``: each statically-sliced child gets class
  ``parent + ('split', eqn, i)``; consuming the whole child *array*
  (e.g. vmapped draws) is one consumption of the array's class;
* ``random_fold_in``: ``parent + ('fold', literal)`` — so two
  ``fold_in(k, 1)`` of the same ``k`` correctly *collide*;
* consumption: ``random_bits`` (every jax.random distribution bottoms
  out there); two consumptions of one class = finding.

Control flow: any other eqn whose params hold a sub-jaxpr of matching
arity (``jit``, ``closed_call``, ``custom_jvp_call``, ``checkpoint``,
...) is walked inline with the caller's classes and a shared
consumption counter.  ``cond``/``switch`` branches each see a *copy* of
the counter and merge by max (branches are exclusive at runtime).
``scan``/``while`` bodies run once with the carry's incoming classes; a
key that is consumed in the body *and* is the same in every iteration
(carried through unchanged, or a loop constant, which is what jax makes
of such a carry) is flagged as cross-iteration reuse (iteration 2 would
redraw with iteration 1's key).

Limits (documented in docs/analysis.md): dynamic indexing into a split
array yields a fresh conservative class (no reuse detectable through
it); host-side ``numpy.random`` streams are invisible to jaxprs; and
equal *seed literals* at two ``PRNGKey`` call sites are two distinct
roots (intentional — seeding policy is the caller's contract).
"""
from __future__ import annotations

import contextlib
import dataclasses
from collections import Counter
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.extend import core as jax_core

__all__ = [
    "KeyReuseFinding",
    "PRNG_PROGRAMS",
    "analyze_jaxpr",
    "check_key_reuse",
    "register_prng_program",
]

KeyClass = tuple  # hashable path, e.g. ('invar', 0, 'split', 17, 1)


class KeyReuseFinding(NamedTuple):
    key_class: str        # printable class path
    n_consumed: int       # number of random_bits consumptions
    sites: tuple[str, ...]  # printable consumption sites
    kind: str             # "reuse" | "carry-reuse"

    def __str__(self) -> str:
        return (f"[{self.kind}] key {self.key_class} consumed "
                f"{self.n_consumed}x at {', '.join(self.sites)}")


def _is_key_aval(aval) -> bool:
    dt = getattr(aval, "dtype", None)
    if dt is None:
        return False
    try:
        return jnp.issubdtype(dt, jax.dtypes.prng_key)
    except TypeError:
        return False


@dataclasses.dataclass
class _State:
    """Mutable walker state shared across inlined sub-jaxprs."""

    consumed: Counter
    sites: dict[KeyClass, list[str]]
    fresh: int = 0

    def consume(self, cls: KeyClass, site: str) -> None:
        self.consumed[cls] += 1
        self.sites.setdefault(cls, []).append(site)

    def fresh_class(self, why: str) -> KeyClass:
        self.fresh += 1
        return ("fresh", why, self.fresh)

    def copy(self) -> "_State":
        st = _State(consumed=Counter(self.consumed),
                    sites={k: list(v) for k, v in self.sites.items()})
        st.fresh = self.fresh
        return st

    def merge_max(self, branches: list["_State"]) -> None:
        """Exclusive control flow: a class's count is the max over
        branches (plus anything new a branch saw)."""
        base = Counter(self.consumed)
        merged: Counter = Counter()
        keys = set(base)
        for b in branches:
            keys |= set(b.consumed)
        for k in keys:
            merged[k] = max([base.get(k, 0)]
                            + [b.consumed.get(k, 0) for b in branches])
        self.consumed = merged
        for b in branches:
            for k, v in b.sites.items():
                mine = self.sites.setdefault(k, [])
                for s in v:
                    if s not in mine:
                        mine.append(s)
            self.fresh = max(self.fresh, b.fresh)


def _read(env: dict, var) -> Any:
    if isinstance(var, jax_core.Literal):
        return None
    return env.get(var)


def _site(eqn, where: str) -> str:
    # source_info_util is private; degrade to the structural path alone
    # if a jax upgrade moves it
    with contextlib.suppress(ImportError, AttributeError):
        from jax._src import source_info_util
        summary = source_info_util.summarize(eqn.source_info)
        if summary:
            return f"{where} ({summary})"
    return where


def _slice_descriptor(eqn) -> Optional[tuple]:
    """Static descriptor of which child a ``slice`` picks from a split
    array: the (axis, start, limit) of every *narrowed* axis.  Under
    ``vmap`` the split axis is not axis 0 (a batch axis leads), so the
    narrowed-axes form is what keeps sibling subkeys distinct."""
    start = eqn.params.get("start_indices")
    limit = eqn.params.get("limit_indices")
    if start is None or limit is None:
        return None
    in_shape = getattr(eqn.invars[0].aval, "shape", None)
    if in_shape is None:
        return None
    narrowed = tuple((ax, int(s), int(lim))
                     for ax, (s, lim, dim) in enumerate(
                         zip(start, limit, in_shape, strict=False))
                     if (lim - s) != dim)
    return narrowed


def _call_jaxpr(eqn):
    """The sub-jaxpr a call-like eqn applies to its own operands, if any:
    a ``Jaxpr``/``ClosedJaxpr`` param whose arity matches the eqn's.
    Primitive names change across jax versions (``pjit`` became ``jit``),
    so the walker keys on structure instead of a list of names."""
    for val in eqn.params.values():
        if isinstance(val, jax_core.ClosedJaxpr):
            val = val.jaxpr
        if (isinstance(val, jax_core.Jaxpr)
                and len(val.invars) == len(eqn.invars)):
            return val
    return None


def _walk(jaxpr, env: dict, state: _State, where: str) -> list:
    """Interpret ``jaxpr`` abstractly; returns outvar values."""
    # jax reuses one traced jaxpr for repeated traces of a function (two
    # scans over the same body), so an untracked variable names a key
    # only within one walk
    walk_id = state.fresh_class("walk")[2]
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        invals = [_read(env, v) for v in eqn.invars]

        if prim in ("cond", "switch"):
            branches = eqn.params.get("branches", ())
            branch_states, branch_outs = [], []
            for br in branches:
                st = state.copy()
                br_jaxpr = br.jaxpr
                sub_env = dict(zip(br_jaxpr.invars, invals[1:], strict=False))
                branch_outs.append(_walk(br_jaxpr, sub_env, st,
                                         f"{where}/{prim}"))
                branch_states.append(st)
            state.merge_max(branch_states)
            for i, var in enumerate(eqn.outvars):
                vals = [o[i] for o in branch_outs
                        if i < len(o) and o[i] is not None]
                if vals and all(v == vals[0] for v in vals):
                    env[var] = vals[0]
            continue
        if prim == "scan":
            _walk_scan(eqn, invals, env, state, where)
            continue
        if prim == "while":
            _walk_while(eqn, invals, env, state, where)
            continue
        sub_jaxpr = _call_jaxpr(eqn)
        if sub_jaxpr is not None:
            # call-like higher-order primitive: walk inline, bind outvars
            sub_env = dict(zip(sub_jaxpr.invars, invals, strict=False))
            outs = _walk(sub_jaxpr, sub_env, state,
                         f"{where}/{eqn.params.get('name', prim)}")
            for var, val in zip(eqn.outvars, outs, strict=False):
                if val is not None:
                    env[var] = val
            continue

        out = None
        if prim == "random_wrap":
            raw = invals[0]
            src = eqn.invars[0]
            if raw is not None:
                out = raw  # re-wrapping a tracked raw buffer: same class
            elif isinstance(src, jax_core.Literal):
                out = ("wrap-lit", repr(getattr(src, "val", None)))
            else:
                out = ("wrap", walk_id, id(src))
        elif prim == "random_unwrap":
            out = invals[0]
        elif prim == "random_split":
            parent = invals[0] or state.fresh_class(f"split@{where}")
            out = ("splitarr", parent, id(eqn))
        elif prim == "random_fold_in":
            parent = invals[0] or state.fresh_class(f"fold@{where}")
            data = eqn.invars[1]
            if isinstance(data, jax_core.Literal):
                tag = repr(data.val)
            else:
                tag = f"dyn{id(eqn)}"
            out = parent + ("fold", tag)
        elif prim == "random_bits":
            cls = invals[0]
            if cls is not None:
                state.consume(cls, _site(eqn, where))
        elif prim in ("slice", "squeeze", "reshape", "broadcast_in_dim",
                      "transpose", "convert_element_type", "copy",
                      "device_put"):
            val = invals[0]
            if val is not None:
                if prim == "slice" and isinstance(val, tuple) \
                        and val and val[0] == "splitarr":
                    idx = _slice_descriptor(eqn)
                    out = val[1] + ("split", id(eqn.invars[0]), idx) \
                        if idx is not None \
                        else state.fresh_class(f"dynslice@{where}")
                else:
                    out = val
        elif prim in ("select_n", "select"):
            # batched cond/switch threads operands through a select; the
            # class survives only when every selectable case agrees
            cases = invals[1:]
            if cases and all(c is not None and c == cases[0] for c in cases):
                out = cases[0]
        elif prim in ("dynamic_slice", "gather"):
            # data-dependent pick out of a key array: conservative fresh
            # class per eqn (reuse through it is invisible — documented)
            if invals[0] is not None:
                out = state.fresh_class(f"{prim}@{where}")

        if out is not None and eqn.outvars:
            env[eqn.outvars[0]] = out
    return [_read(env, v) for v in jaxpr.outvars]


def _carry_findings(state: _State, in_classes, out_classes, before: Counter,
                    where: str) -> None:
    """A carried key consumed in the body and passed through unchanged
    re-feeds the same class to iteration 2: cross-iteration reuse."""
    for cin, cout in zip(in_classes, out_classes, strict=False):
        if cin is None or cin != cout:
            continue
        if state.consumed.get(cin, 0) > before.get(cin, 0):
            # mark so analyze_jaxpr reports it as carry-reuse
            state.consume(("carry-reuse",) + tuple(cin),
                          f"{where} (carried key consumed in body and "
                          "returned unchanged)")


def _derives_from(cls, root) -> bool:
    """``cls`` is ``root`` or a static descendant of it (split children,
    literal ``fold_in``s, the split array itself)."""
    if not isinstance(cls, tuple) or not isinstance(root, tuple):
        return False
    if cls[:len(root)] == root:
        return True
    return len(cls) > 1 and cls[0] == "splitarr" and _derives_from(cls[1],
                                                                  root)


def _invariant_findings(state: _State, consts, before: Counter,
                        where: str) -> None:
    """A key consumed in a loop body whose class derives statically from
    a loop *constant* is the same key in every iteration — the same bug
    as a carried key returned unchanged (jax may hoist such a carry into
    a constant).  A ``fold_in`` of traced data (the iteration counter)
    makes the class per-iteration, so it is not flagged."""
    roots = [c for c in consts if c is not None]
    for cls, n in list(state.consumed.items()):
        if n <= before.get(cls, 0) or cls[0] == "carry-reuse":
            continue
        dynamic = any(isinstance(t, str) and t.startswith("dyn") for t in cls)
        if not dynamic and any(_derives_from(cls, r) for r in roots):
            state.consume(("carry-reuse",) + tuple(cls),
                          f"{where} (loop-invariant key consumed in body)")


def _walk_scan(eqn, invals, env, state: _State, where: str) -> None:
    body = eqn.params["jaxpr"].jaxpr
    n_consts = eqn.params["num_consts"]
    n_carry = eqn.params["num_carry"]
    consts = invals[:n_consts]
    carry = invals[n_consts:n_consts + n_carry]
    # xs enter sliced per iteration: track the stacked class itself so a
    # per-iteration slice of a split array keeps its identity
    xs = invals[n_consts + n_carry:]
    sub_env = dict(zip(body.invars, consts + carry + xs, strict=False))
    before = Counter(state.consumed)
    outs = _walk(body, sub_env, state, f"{where}/scan")
    _carry_findings(state, carry, outs[:n_carry], before, f"{where}/scan")
    if eqn.params["length"] > 1:
        _invariant_findings(state, consts, before, f"{where}/scan")
    for var, val in zip(eqn.outvars[:n_carry], outs[:n_carry],
                        strict=False):
        if val is not None:
            env[var] = val


def _walk_while(eqn, invals, env, state: _State, where: str) -> None:
    body = eqn.params["body_jaxpr"].jaxpr
    n_c = eqn.params["body_nconsts"]
    cond_nc = eqn.params["cond_nconsts"]
    carry = invals[cond_nc + n_c:]
    consts = invals[cond_nc:cond_nc + n_c]
    sub_env = dict(zip(body.invars, consts + carry, strict=False))
    before = Counter(state.consumed)
    outs = _walk(body, sub_env, state, f"{where}/while")
    _carry_findings(state, carry, outs, before, f"{where}/while")
    _invariant_findings(state, consts, before, f"{where}/while")
    for var, val in zip(eqn.outvars, outs, strict=False):
        if val is not None:
            env[var] = val


def analyze_jaxpr(closed) -> list[KeyReuseFinding]:
    """Walk a ``ClosedJaxpr``; return key-reuse findings (empty = clean)."""
    jaxpr = closed.jaxpr
    env: dict = {}
    for i, var in enumerate(jaxpr.invars):
        if _is_key_aval(var.aval) or _is_raw_key_aval(var.aval):
            env[var] = ("invar", i)
    for i, (var, val) in enumerate(
            zip(jaxpr.constvars, closed.consts, strict=False)):
        if _is_key_aval(var.aval) or _looks_like_raw_key(val):
            env[var] = ("const", i)
    state = _State(consumed=Counter(), sites={})
    _walk(jaxpr, env, state, "<top>")

    findings = []
    for cls, n in sorted(state.consumed.items(), key=repr):
        if cls and cls[0] == "carry-reuse":
            findings.append(KeyReuseFinding(
                key_class=repr(cls[1:]), n_consumed=n,
                sites=tuple(state.sites.get(cls, [])), kind="carry-reuse"))
        elif n >= 2:
            findings.append(KeyReuseFinding(
                key_class=repr(cls), n_consumed=n,
                sites=tuple(state.sites.get(cls, [])), kind="reuse"))
    return findings


def _is_raw_key_aval(aval) -> bool:
    """Raw ``uint32[..., 2]`` buffers are threefry keys by convention."""
    shape = getattr(aval, "shape", None)
    dt = getattr(aval, "dtype", None)
    return (shape is not None and len(shape) >= 1 and shape[-1] == 2
            and dt == jnp.uint32)


def _looks_like_raw_key(val) -> bool:
    try:
        return _is_raw_key_aval(jax.eval_shape(lambda x: x, val))
    except (TypeError, ValueError):
        return False


def check_key_reuse(fn: Callable, *args, **kwargs) -> list[KeyReuseFinding]:
    """Trace ``fn`` on ``args`` and analyze the jaxpr for key reuse."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return analyze_jaxpr(closed)


# --------------------------------------------------------------------------
# registered production programs (the gate's clean set)
# --------------------------------------------------------------------------

PRNG_PROGRAMS: dict[str, Callable[[], list[KeyReuseFinding]]] = {}


def register_prng_program(name: str):
    def wrap(fn):
        PRNG_PROGRAMS[name] = fn
        return fn
    return wrap


def _sweep_static_and_args(*, uplink_bits=None, aggregate: str = "fused",
                           drops: bool = False, donate: bool = False):
    """The jitted scan-engine sweep program plus concrete call args —
    shared by the key-reuse gate and the hygiene donation audit."""
    from repro.analysis.hotpaths import _build_sweep_inputs
    from repro.fl import scan_engine

    plans, train, test, config, params = _build_sweep_inputs(
        uplink_bits=uplink_bits, seeds=[0, 1], aggregate=aggregate)
    if drops:
        import numpy as np
        tables = np.zeros((2, config.n_rounds, plans.probs.shape[2]), bool)
        tables[:, 1, 0] = True
        plans = dataclasses.replace(plans, drops=jnp.asarray(tables))
    static = scan_engine._Static(
        n_rounds=config.n_rounds, batch_per_client=config.batch_per_client,
        aggregate=aggregate, renormalize=config.renormalize,
        include_compute_time=config.include_compute_time,
        eval_rounds=scan_engine._eval_rounds(config), use_kernel=False,
        kernel_interpret=True, donate=donate,
        faulted=plans.drops is not None, quantized=plans.bits is not None)
    fn = scan_engine._sweep_fn(static)
    train_x, train_y = scan_engine._stack_datasets(train)
    test_x, test_y = scan_engine._stack_datasets(test)
    return fn, (plans, params, train_x, train_y, test_x, test_y)


def _sweep_findings(*, uplink_bits, aggregate, drops: bool = False):
    fn, args = _sweep_static_and_args(
        uplink_bits=uplink_bits, aggregate=aggregate, drops=drops)
    return check_key_reuse(fn, *args)


@register_prng_program("scan_engine_sweep")
def _prng_scan_engine():
    """The fused-aggregation sweep: per-round ``split`` stream."""
    return _sweep_findings(uplink_bits=None, aggregate="fused")


@register_prng_program("scan_engine_quantized")
def _prng_scan_engine_quantized():
    """The quantized-uplink sweep: participation draw uses ``sub``, the
    quantiser uses ``fold_in(sub, 1)`` — distinct classes by design
    (the exact invariant PR 9's audit checked by hand)."""
    return _sweep_findings(uplink_bits=8, aggregate="stacked")


@register_prng_program("scan_engine_faulted")
def _prng_scan_engine_faulted():
    """The chaos-harness path: degraded aggregation (drop tables) must
    not disturb the key stream (closed-loop replans replay it)."""
    return _sweep_findings(uplink_bits=None, aggregate="stacked",
                           drops=True)


@register_prng_program("mask_stream")
def _prng_mask_stream():
    """The planner's participation-mask preview (shared with the closed
    loop's drift replans): one subkey per round, no reuse."""
    from repro.fl.scan_engine import _mask_stream

    key0 = jax.random.PRNGKey(0)
    probs = jnp.full((4, 6), 0.3)
    return check_key_reuse(_mask_stream, key0, probs, jnp.int32(0),
                           jnp.int32(2))

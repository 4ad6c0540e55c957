"""Op registry — one canonical name, two lowerings (reference / Pallas).

This is the load-bearing piece of the portability core.  PHAST ships every
algorithm once, as templated C++ whose innermost layers are specialized per
target at compile time.  The JAX analogue: each performance-critical op is
*registered* under a canonical name with

    reference : pure-jnp callable (the oracle; always correct; runs anywhere)
    pallas    : Pallas TPU kernel wrapper (same signature)

``dispatch(name)`` returns the callable selected by the active policy.  Ops
fall back to ``reference`` when no kernel exists (and record that fact, so
tests can assert full coverage where the paper's Table 1 asserts pass rates).

The tuning side-table mirrors PHAST's "tuning parameters without source
change": per-(op, key) kernel parameters (block shapes etc.) that kernels
look up at trace time.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Dict, Iterator, Optional, Sequence, Tuple

from repro.core.policy import Backend, current_backend


@dataclasses.dataclass
class OpEntry:
    name: str
    reference: Callable[..., Any]
    pallas: Optional[Callable[..., Any]] = None
    doc: str = ""
    # Coverage-lint declarations (repro.analysis.coverage).  ``tuning``
    # names the tuning-table keys the Pallas lowering resolves via
    # ``get_tuning`` — ``()`` declares "no tunable parameters", ``None``
    # means undeclared (a C102 finding for ops with a lowering).
    # ``reference_only=True`` records that the op intentionally has no
    # Pallas lowering (silences C101).
    tuning: Optional[Tuple[str, ...]] = None
    reference_only: bool = False

    def resolve(self, backend: Backend) -> Callable[..., Any]:
        if backend is Backend.PALLAS and self.pallas is not None:
            return self.pallas
        return self.reference


_OPS: Dict[str, OpEntry] = {}
_TUNING: Dict[tuple, Dict[str, Any]] = {}


def register_op(
    name: str,
    *,
    reference: Callable[..., Any],
    pallas: Optional[Callable[..., Any]] = None,
    doc: str = "",
    tuning: Optional[str | Sequence[str]] = None,
    reference_only: bool = False,
) -> OpEntry:
    if name in _OPS:
        raise ValueError(f"op {name!r} already registered")
    if reference_only and pallas is not None:
        raise ValueError(
            f"op {name!r}: reference_only=True with a pallas lowering"
        )
    if isinstance(tuning, str):
        tuning = (tuning,)
    elif tuning is not None:
        tuning = tuple(tuning)
    entry = OpEntry(
        name=name,
        reference=reference,
        pallas=pallas,
        doc=doc,
        tuning=tuning,
        reference_only=reference_only,
    )
    _OPS[name] = entry
    return entry


def attach_pallas(name: str, fn: Callable[..., Any]) -> None:
    """Attach/replace the Pallas lowering of an already-registered op."""
    _OPS[name].pallas = fn


def get_op(name: str) -> OpEntry:
    try:
        return _OPS[name]
    except KeyError as e:
        raise KeyError(
            f"op {name!r} not registered; known: {sorted(_OPS)}"
        ) from e


def dispatch(name: str) -> Callable[..., Any]:
    """Resolve op ``name`` under the current backend policy."""
    return get_op(name).resolve(current_backend())


def list_ops() -> Dict[str, OpEntry]:
    return dict(_OPS)


def coverage() -> Dict[str, bool]:
    """name -> has a Pallas lowering (the 'ported to PHAST' bit per block)."""
    return {name: e.pallas is not None for name, e in _OPS.items()}


# ---------------------------------------------------------------------------
# Tuning registry: per-(op, key) kernel parameters.
#
# Three layers resolve at trace time, lowest to highest precedence:
#
#     call-site defaults (the hand-set values baked into kernel source)
#         < persisted table (op, "default") < persisted table (op, key)
#         < set_tuning (op, "default")      < set_tuning (op, key)
#
# The persisted table is the committed artifact of the autotuning sweep
# (src/repro/tuning/tuning_table.json, format documented in the
# ``repro.tuning`` package docstring); ``key`` is normally a shape class
# (``repro.tuning.shapes.shape_class``).  A ``key`` that misses every
# layer falls back cleanly to the call-site defaults — and the table
# always supersedes the hand-set defaults for classes it covers, while
# an explicit ``set_tuning`` (tests, experiments, config overrides)
# always beats the table.
# ---------------------------------------------------------------------------

_TABLE: Optional[Dict[tuple, Dict[str, Any]]] = None
_LAST_RESOLVED: Dict[str, str] = {}
_PLANS: Dict[str, Dict[tuple, tuple]] = {}
_LAST_PLAN: Dict[str, Any] = {}


def _table() -> Dict[tuple, Dict[str, Any]]:
    """Lazily load the persisted tuning table (flattened view)."""
    global _TABLE
    if _TABLE is None:
        from repro.tuning import table as _tt

        path = _tt.resolved_path()
        if path is None:
            _TABLE = {}
        else:
            try:
                _TABLE = _tt.flatten(_tt.load(path))
            except ValueError:
                # a corrupt table must not brick every op; the coverage
                # lint (C104/C105) is the loud path for table problems
                _TABLE = {}
    return _TABLE


def load_tuning_table(source: Any = None) -> int:
    """(Re)load the persisted table; returns the number of entries.

    ``source`` may be a path, a table document (dict with ``entries``),
    an already-flattened ``{(op, key): params}`` mapping, or ``None``
    for the default path (``REPRO_TUNING_TABLE`` respected).
    """
    global _TABLE
    from repro.tuning import table as _tt

    if source is None:
        _TABLE = None
        return len(_table())
    if isinstance(source, dict):
        if "entries" in source:
            _TABLE = _tt.flatten(source)
        else:
            _TABLE = {k: dict(v) for k, v in source.items()}
    else:
        _TABLE = _tt.flatten(_tt.load(source))
    return len(_TABLE)


@contextlib.contextmanager
def tuning_table(source: Any) -> Iterator[None]:
    """Scoped table replacement; ``{}`` (or ``None``) disables the table.

    Used by the autotuner (sweep against a clean slate) and by the perf
    snapshot (measure the hand-set defaults the table supersedes).
    """
    global _TABLE
    saved = _TABLE
    try:
        if source is None:
            _TABLE = {}
        else:
            _TABLE = None
            load_tuning_table(source)
        yield
    finally:
        _TABLE = saved


def set_tuning(op: str, key: str = "default", **params: Any) -> None:
    _TUNING[(op, key)] = dict(params)


@contextlib.contextmanager
def tuning_overrides(op: str, key: str = "default",
                     **params: Any) -> Iterator[None]:
    """Scoped ``set_tuning`` — the autotuner's per-candidate install."""
    saved = _TUNING.get((op, key))
    _TUNING[(op, key)] = dict(params)
    try:
        yield
    finally:
        if saved is None:
            _TUNING.pop((op, key), None)
        else:
            _TUNING[(op, key)] = saved


def get_tuning(op: str, key: str = "default", **defaults: Any) -> Dict[str, Any]:
    out = dict(defaults)
    tab = _table()
    out.update(tab.get((op, "default"), {}))
    if key != "default":
        out.update(tab.get((op, key), {}))
    out.update(_TUNING.get((op, "default"), {}))
    if key != "default":
        out.update(_TUNING.get((op, key), {}))
    _LAST_RESOLVED[op] = key
    return out


def last_resolved(op: str) -> Optional[str]:
    """The ``key`` the most recent ``get_tuning(op, ...)`` resolved.

    A debugging/self-check aid: the autotuner asserts its cell drivers
    classify shapes exactly like the kernel call sites do.
    """
    return _LAST_RESOLVED.get(op)


def record_plan(op: str, shape: tuple, plan: Any) -> None:
    """Record the blocks (a ``NamedTuple``) that a traced call of ``op`` at
    ``shape`` resolved."""
    _PLANS.setdefault(op, {})[tuple(shape)] = plan
    _LAST_PLAN[op] = plan


def resolved_plans(op: str) -> Dict[tuple, tuple]:
    """``shape -> plan`` of every call of ``op`` traced in this process, the
    latest plan per shape; for the GEMM ``(m, k, n) -> (bm, bn, bk, grid
    steps)``.  Like ``last_resolved``, a self-check aid: a reader counts the
    grid steps of each program from it."""
    return dict(_PLANS.get(op, {}))


def last_plan(op: str) -> Dict[str, int]:
    """The fields of the plan the most recent traced call of ``op``
    recorded, ``{}`` where it records none.  The autotuner sweeps a
    kernel whose blocks come from the call's shape around this plan."""
    plan = _LAST_PLAN.get(op)
    return plan._asdict() if plan is not None else {}


def clear_tuning() -> None:
    _TUNING.clear()

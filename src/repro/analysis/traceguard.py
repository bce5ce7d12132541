"""TraceGuard: hard retrace assertions for jitted serving-stack functions.

The engine's trace-count stats (``stats["decode_traces"]``) are
hand-incremented inside the traced bodies — informative, but nothing fails
when a new protocol combination sneaks in a retrace. :class:`TraceGuard`
hooks the one chokepoint every jit trace passes through
(``jax._src.interpreters.partial_eval.trace_to_jaxpr``) and raises
:class:`TraceGuardError` — with the offending avals and every aval set seen
before — the moment a watched function traces more often than its budget.

Usage::

    with TraceGuard(max_traces={"decode": 1, "sprefill": n_buckets}) as tg:
        run_engine(...)
    assert tg.counts["decode"] == 1

Only functions whose ``__name__`` matches a ``max_traces`` key are
constrained; everything else (jnp-internal primitive jits, unrelated user
functions) is recorded in :attr:`counts` but never raises. The XLA C++
fastpath serves cache hits without re-entering Python, so a count of 1 means
"traced exactly once" — there is no double-counting on steady-state steps.

The hooked entry point is private to JAX and has moved between releases. So
``__enter__`` traces a fresh canary function and raises if the hook did not
see it: a guard that can no longer count traces fails loudly instead of
passing every budget with counts of zero.

``conftest.py`` exposes this as the ``trace_guard`` pytest fixture.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
from jax._src.interpreters import partial_eval as _pe

_CANARY = "_traceguard_canary"


class TraceGuardError(AssertionError):
    """A watched function re-traced past its budget."""


class TraceGuard:
    """Context manager counting jit traces by traced-function name.

    Args:
        max_traces: name -> maximum number of traces allowed while the
            guard is active. A watched name exceeding its budget raises
            :class:`TraceGuardError` at the offending trace, not at exit.
        exact: optional name -> exact required count, checked at ``__exit__``
            (a watched function that never traced at all is also a failure
            when listed here).
    """

    def __init__(self, max_traces: Optional[Dict[str, int]] = None,
                 exact: Optional[Dict[str, int]] = None) -> None:
        self.max_traces = dict(max_traces or {})
        self.exact = dict(exact or {})
        for name, want in self.exact.items():
            cap = self.max_traces.get(name, want)
            self.max_traces[name] = min(cap, want)
        self.counts: Dict[str, int] = {}
        self.avals: Dict[str, List[Tuple[Any, ...]]] = {}
        self._orig: Any = None
        self._active = False

    # ------------------------------------------------------------- plumbing
    def __enter__(self) -> "TraceGuard":
        if self._active:
            raise RuntimeError("TraceGuard is not re-entrant")
        self._active = True
        self._orig = _pe.trace_to_jaxpr
        guard = self

        def traced(fun: Any, in_avals: Any, *args: Any, **kwargs: Any) -> Any:
            guard._record(fun, in_avals)
            return guard._orig(fun, in_avals, *args, **kwargs)

        _pe.trace_to_jaxpr = traced
        try:
            self._check_canary()
        except BaseException:
            _pe.trace_to_jaxpr = self._orig
            self._active = False
            raise
        return self

    def _check_canary(self) -> None:
        """Trace a function no cache has seen; the hook must count it."""
        def canary(x: Any) -> Any:
            return x + 1

        canary.__name__ = _CANARY
        jax.jit(canary).trace(0)
        seen = self.counts.pop(_CANARY, 0)
        self.avals.pop(_CANARY, None)
        if seen != 1:
            raise RuntimeError(
                "TraceGuard cannot see jit traces: its hook on "
                "jax._src.interpreters.partial_eval.trace_to_jaxpr counted "
                f"{seen} trace(s) of a fresh function (jax {jax.__version__}). "
                "The tracing entry point moved; re-point the hook.")

    def __exit__(self, exc_type: Any, exc: Any, tb: Any) -> None:
        _pe.trace_to_jaxpr = self._orig
        self._active = False
        if exc_type is None:
            for name, want in self.exact.items():
                got = self.counts.get(name, 0)
                if got != want:
                    raise TraceGuardError(
                        f"TraceGuard: '{name}' traced {got} time(s), "
                        f"expected exactly {want}; aval history: "
                        f"{self._history(name)}")

    # -------------------------------------------------------------- helpers
    def _fun_name(self, fun: Any) -> str:
        f = getattr(fun, "f", None)
        name = getattr(f, "__name__", None) or getattr(fun, "__name__", "")
        return str(name)

    def _record(self, fun: Any, in_avals: Any) -> None:
        name = self._fun_name(fun)
        if not name:
            return
        self.counts[name] = self.counts.get(name, 0) + 1
        try:
            sig = tuple(str(a) for a in in_avals)
        except TypeError:
            sig = (str(in_avals),)
        self.avals.setdefault(name, []).append(sig)
        cap = self.max_traces.get(name)
        if cap is not None and self.counts[name] > cap:
            raise TraceGuardError(
                f"TraceGuard: '{name}' traced {self.counts[name]} time(s), "
                f"budget is {cap}. Retrace avals:\n  "
                + "\n  ".join(sig)
                + f"\nPrevious trace(s):{self._history(name, skip_last=True)}"
            )

    def _history(self, name: str, skip_last: bool = False) -> str:
        hist = self.avals.get(name, [])
        if skip_last and hist:
            hist = hist[:-1]
        if not hist:
            return " (never traced)"
        out = []
        for i, sig in enumerate(hist):
            out.append(f"\n  trace {i}: " + ", ".join(sig))
        return "".join(out)

"""Runtime compile budgets: the dynamic half of reprolint.

The static rules prove lexical discipline; ``compile_guard`` proves the
invariant that actually matters at runtime — **how many times XLA compiled
each named executor** inside a region. It rides JAX's own compile logging
(``jax_log_compiles`` makes the lowering path emit one
"Compiling <name> with global shapes..." record per cache miss, carrying
the jitted function's ``__name__``), so there is no dependence on private
cache internals and no interference with donation or sharding.

Trivial primitive compiles (``jnp.ones`` → ``broadcast_in_dim`` etc.) also
log; pass ``track=`` with a regex over the executor names you care about —
this repo names its executors distinctively (``hsgd_round``,
``serve_decode``, ``llm_round``, ...) precisely so budgets are attributable.

    with compile_guard(track=r"hsgd_cohort_round") as g:
        for A in (2, 4, 8, 4, 2):
            runner.cohort_round_fn(2, 1, A)(state, data, w, idx, 0.05)
    assert g.total == 3          # one compile per pow2 cohort bucket

Budgets can be declared up front and enforced at region exit:

    with compile_guard(track=r"serve_", exact={"serve_decode": 1}):
        engine.generate(prompts, 8)   # raises CompileBudgetError on miss

``jax`` is imported lazily at region entry so the lint CLI (and the CI
lint job) never pays for — or requires — a jax import.
"""
from __future__ import annotations

import logging
import re
import threading
from collections import Counter
from typing import Dict, List, Optional, Union

__all__ = ["CompileBudgetError", "CompileGuard", "compile_guard"]


class CompileBudgetError(AssertionError):
    """A compile_guard region compiled more (or other) than budgeted."""


_COMPILE_RE = re.compile(r"Compiling\s+([^\s]+)")
_LOGGER_NAMES = ("jax._src.interpreters.pxla", "jax._src.dispatch")


class _CompileLogHandler(logging.Handler):
    """Fans each compile event out to every active guard (guards nest)."""

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.guards: List["CompileGuard"] = []

    def emit(self, record: logging.LogRecord) -> None:
        try:
            msg = record.getMessage()
        except Exception:
            return
        if "with global shapes" not in msg:
            return
        m = _COMPILE_RE.search(msg)
        if not m:
            return
        name = m.group(1)
        # JAX 0.9 logs "Compiling jit(name) ..."; budgets key on the bare name
        wrapped = re.fullmatch(r"jit\((.*)\)", name)
        if wrapped:
            name = wrapped.group(1)
        for g in list(self.guards):
            g._record(name)


_lock = threading.Lock()
_handler = _CompileLogHandler()
_saved: Optional[dict] = None


def _install() -> None:
    """First guard in: flip jax_log_compiles on, attach the handler, and
    mute console propagation for the region (restored on last guard out)."""
    global _saved
    import jax

    saved = {"log_compiles": jax.config.jax_log_compiles, "loggers": []}
    jax.config.update("jax_log_compiles", True)
    for name in _LOGGER_NAMES:
        logger = logging.getLogger(name)
        saved["loggers"].append((logger, logger.propagate))
        logger.addHandler(_handler)
        logger.propagate = False
    _saved = saved


def _uninstall() -> None:
    global _saved
    import jax

    if _saved is None:
        return
    jax.config.update("jax_log_compiles", _saved["log_compiles"])
    for logger, propagate in _saved["loggers"]:
        logger.removeHandler(_handler)
        logger.propagate = propagate
    _saved = None


class CompileGuard:
    """Context manager counting XLA compiles by executor name.

    Parameters
    ----------
    track:
        Regex; only compile events whose function name matches are counted.
        Without it every compile in the region counts, including trivial
        primitive compiles — fine for "nothing compiled here" assertions
        (``exact=0``), noisy for anything else.
    exact:
        Budget enforced at region exit. An int pins the total tracked
        count; a dict maps name-regexes to pinned counts. Violations raise
        :class:`CompileBudgetError` (an AssertionError, so pytest reports
        it as a plain failure).
    max_compiles:
        Upper bound on the total tracked count, enforced at exit.

    After exit, ``total``, ``names``, ``by_name`` and ``count(pattern)``
    remain readable for ≤-style assertions the budgets can't express.
    """

    def __init__(self, track: Optional[str] = None,
                 exact: Optional[Union[int, Dict[str, int]]] = None,
                 max_compiles: Optional[int] = None):
        self._track = re.compile(track) if track else None
        self._exact = exact
        self._max = max_compiles
        self.names: List[str] = []

    # -- recording ----------------------------------------------------------

    def _record(self, name: str) -> None:
        if self._track is not None and not self._track.search(name):
            return
        self.names.append(name)

    @property
    def total(self) -> int:
        return len(self.names)

    @property
    def by_name(self) -> Counter:
        return Counter(self.names)

    def count(self, pattern: str) -> int:
        pat = re.compile(pattern)
        return sum(1 for n in self.names if pat.search(n))

    # -- context protocol ---------------------------------------------------

    def __enter__(self) -> "CompileGuard":
        with _lock:
            if not _handler.guards:
                _install()
            _handler.guards.append(self)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        with _lock:
            if self in _handler.guards:
                _handler.guards.remove(self)
            if not _handler.guards:
                _uninstall()
        if exc_type is not None:
            return False
        self._enforce()
        return False

    # -- budgets ------------------------------------------------------------

    def _enforce(self) -> None:
        seen = dict(self.by_name)
        if self._max is not None and self.total > self._max:
            raise CompileBudgetError(
                f"compile budget exceeded: {self.total} compiles > "
                f"max_compiles={self._max}; saw {seen}")
        if self._exact is None:
            return
        if isinstance(self._exact, int):
            if self.total != self._exact:
                raise CompileBudgetError(
                    f"compile budget missed: expected exactly {self._exact} "
                    f"compile(s), saw {self.total}: {seen}")
            return
        for pattern, want in self._exact.items():
            got = self.count(pattern)
            if got != want:
                raise CompileBudgetError(
                    f"compile budget missed for /{pattern}/: expected "
                    f"{want}, saw {got}; all tracked compiles: {seen}")


def compile_guard(track: Optional[str] = None,
                  exact: Optional[Union[int, Dict[str, int]]] = None,
                  max_compiles: Optional[int] = None) -> CompileGuard:
    """Build a :class:`CompileGuard` region. See the class for semantics."""
    return CompileGuard(track=track, exact=exact, max_compiles=max_compiles)

"""Profiling hooks: jit compile-count tracking, host-transfer counting,
and an optional ``jax.profiler`` trace toggle (PR 10 tentpole, part 3).

``CompileLog`` promotes the compile-count guards that were duplicated
across test files (``fn._cache_size()`` probes, ``FlatServer.
compile_count`` property reads) into one reusable API: register named
targets, read their compile counts, assert bounds.  A target without a
count raises instead of reporting "unknown", so a recompile guard can
never pass vacuously.

The module-level transfer counter backs the engine's "one host
transfer per run" invariant: ``DeviceMetricsRing.flush`` /
``flush_sched`` record themselves here, and ``TransferScope`` measures
the delta across any code region.

Nothing here imports jax at module scope — the obs package stays
importable (and the report CLI runnable) without touching the
accelerator runtime.
"""
from __future__ import annotations

import collections
import contextlib
from typing import Any, Dict, Optional

# ---------------------------------------------------------------------
# compile-count tracking
# ---------------------------------------------------------------------


def cache_size(fn) -> int:
    """Compiled-program count of a jitted function (jax's private
    ``_cache_size`` probe)."""
    return int(fn._cache_size())


class CompileLog:
    """Named registry of jit-compile-count targets.

    A target is either a jitted function (probed via
    :func:`cache_size`), an object exposing a ``compile_count``
    property (e.g. ``FlatServer``), or — with ``attr=`` — any object
    whose named attribute holds the count.
    """

    def __init__(self):
        self._targets: Dict[str, Any] = {}

    def track(self, name: str, target, attr: Optional[str] = None
              ) -> "CompileLog":
        self._targets[name] = (target, attr)
        return self

    def count(self, name: str) -> int:
        target, attr = self._targets[name]
        if attr is not None:
            return int(getattr(target, attr))
        if callable(getattr(target, "_cache_size", None)):
            return cache_size(target)
        return int(target.compile_count)

    def counts(self) -> Dict[str, int]:
        return {name: self.count(name) for name in self._targets}

    def assert_at_most(self, name: str, bound: int) -> int:
        c = self.count(name)
        assert 0 <= c <= bound, (
            f"{name}: {c} compiled programs > bound {bound}")
        return c

    def assert_exactly(self, name: str, n: int) -> int:
        c = self.count(name)
        assert c == n, f"{name}: {c} compiled programs != {n}"
        return c


def engine_compile_log(eng) -> CompileLog:
    """CompileLog pre-wired for an ``FLEngine``: the server step program,
    the streaming fold program (when the streaming channel is on) and
    the batched client program as ``"wave"`` (once a batched run has
    built it: the semi-async wave, or the sync round)."""
    log = CompileLog().track("server_step", eng._server)
    if getattr(eng, "_streaming", False):
        log.track("server_fold", eng._server, attr="fold_compile_count")
    wave_fn = getattr(eng, "_wave_fn", None)
    if wave_fn is not None:
        log.track("wave", wave_fn)
    return log


# ---------------------------------------------------------------------
# host-transfer counting
# ---------------------------------------------------------------------

_TRANSFERS: "collections.Counter[str]" = collections.Counter()


def record_transfer(tag: str) -> None:
    """Record one device->host transfer under ``tag`` (called by the
    transfer sites themselves, e.g. ``DeviceMetricsRing.flush``)."""
    _TRANSFERS[str(tag)] += 1


class TransferScope:
    """Context manager measuring host transfers inside the scope::

        with TransferScope() as ts:
            eng.run(rounds)
        assert ts.count("metrics_ring.flush") == 1
    """

    def __enter__(self) -> "TransferScope":
        self._t0 = collections.Counter(_TRANSFERS)
        self._t1: Optional[collections.Counter] = None
        return self

    def __exit__(self, *exc) -> bool:
        self._t1 = collections.Counter(_TRANSFERS)
        return False

    def delta(self) -> Dict[str, int]:
        end = self._t1 if self._t1 is not None \
            else collections.Counter(_TRANSFERS)
        return {k: v for k, v in (end - self._t0).items() if v}

    def count(self, tag: str) -> int:
        return self.delta().get(str(tag), 0)


# ---------------------------------------------------------------------
# jax.profiler toggle
# ---------------------------------------------------------------------


@contextlib.contextmanager
def jax_profile(trace_dir: str, enabled: bool = True):
    """Wrap a region in a ``jax.profiler`` trace when enabled (a no-op
    when disabled or when ``trace_dir`` is empty).  A profiler that
    fails to start or stop raises: a run asked for a trace must not
    quietly come back without one."""
    if not (enabled and trace_dir):
        yield
        return
    import jax

    jax.profiler.start_trace(trace_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()

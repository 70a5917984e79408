"""Jit dispatch accounting shared by the training and serving hot paths.

``instrumented_jit`` is ``jax.jit`` plus a process-wide program-launch
counter.  It started life inside ``repro.arms.fused`` (DESIGN.md §7) where
``benchmarks/hotpath.py`` uses it to certify the fused round-step's
O(1)-dispatches-per-round contract; the serving tier (``repro.serve``,
DESIGN.md §9) asserts the same invariant for steady-state decode — one
program launch per token — so the counter lives here, neutral ground
below both subsystems.  ``repro.arms.fused`` re-exports every name, so
arm code and benchmarks keep importing it from there.

The count is a structural metric, not a timer: eager jnp ops are not
included, so it measures "how many compiled programs does this phase
launch" — O(H) on the legacy round loop vs O(1) fused; O(prompt_len) on
the legacy Python prefill vs O(1) on the jitted prefill program.

Thread-safety: ``python -m repro.serve --train-rounds N`` runs a trainer
thread concurrently with the decode loop, so both paths dispatch through
this module at once.  The counter increments under a lock (an unguarded
``+= 1`` loses ticks under contention, which would fake sub-O(1) dispatch
rates in the benchmarks), and the active executor is **thread-local**: a
mesh backend's ``execution_context`` install is visible only to the
thread that opened it, so a concurrent serving thread can never be routed
through another thread's mesh executor.

When ``repro.obs`` recording is enabled, every dispatch additionally
records a ``jit_dispatch`` span bracketing the launch, which is what lets
obs phase breakdowns attribute wall time to compiled-program dispatch; the
number of those spans is the recorded dispatch count (``python -m
repro.obs`` prints it), so no obs counter repeats it.  Recording off means
exactly the pre-obs behavior (a lock, an int, nothing else).

``execution_context`` routes every instrumented call through an installed
executor (the SPMD ``MeshExecutor`` in ``launch/federated.py``) so a mesh
backend can re-stage the same program with explicit shardings.
"""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import Callable

import jax

import repro.obs as obs

_count_lock = threading.Lock()
_jit_dispatch_count = 0  # guarded by _count_lock

# Active cohort-program executor (DESIGN.md §8), per-thread.  ``None`` means
# plain jit on the default device; an SPMD backend installs a
# ``launch.federated`` MeshExecutor for the duration of each fused round,
# which re-dispatches the same program onto a device mesh with explicit
# shardings.
_tls = threading.local()


@contextlib.contextmanager
def execution_context(executor):
    """Route this THREAD's ``instrumented_jit`` calls through ``executor``
    while open (other threads keep their own executor, or none)."""
    prev = getattr(_tls, "executor", None)
    _tls.executor = executor
    try:
        yield
    finally:
        _tls.executor = prev


def active_executor():
    return getattr(_tls, "executor", None)


def instrumented_jit(fn: Callable, **jit_kwargs) -> Callable:
    """``jax.jit`` that counts program launches (``jit_dispatches()``).

    The wrapper carries the raw ``fn`` and its jit kwargs so a mesh
    executor (``execution_context``) can re-stage the same program with
    explicit shardings instead of the plain single-device jit.
    """
    compiled = jax.jit(fn, **jit_kwargs)

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        global _jit_dispatch_count
        with _count_lock:
            _jit_dispatch_count += 1
        executor = getattr(_tls, "executor", None)
        t0 = obs.now()  # None when recording is off
        if executor is not None:
            out = executor.execute(wrapper, args, kwargs)
        else:
            out = compiled(*args, **kwargs)
        if t0 is not None:
            obs.complete("jit_dispatch", t0, cat="jit",
                         fn=getattr(fn, "__name__", "<fn>"))
        return out

    wrapper.jitted = compiled
    wrapper.fn = fn
    wrapper.jit_kwargs = dict(jit_kwargs)
    return wrapper


def instrumented_jit_pair(fn: Callable, *, reduced_pos: int = 1,
                          **jit_kwargs) -> tuple[Callable, Callable]:
    """(full, slim) jits of a cohort function whose output tuple carries the
    in-jit cohort reduction at ``reduced_pos``.  The slim variant drops that
    output, so XLA dead-code-eliminates the reduction entirely — backends
    that can't consume it (sim transport, SecAgg uploads) don't pay for it.
    """

    def dropped(*args, **kwargs):
        out = fn(*args, **kwargs)
        return out[:reduced_pos] + out[reduced_pos + 1:]

    return (
        instrumented_jit(fn, **jit_kwargs),
        instrumented_jit(dropped, **jit_kwargs),
    )


def jit_dispatches() -> int:
    """Total instrumented jit program launches since the last reset."""
    with _count_lock:
        return _jit_dispatch_count


def reset_jit_dispatches() -> None:
    global _jit_dispatch_count
    with _count_lock:
        _jit_dispatch_count = 0

"""Faults planted under the timed path, for the check that ``correct``
comes out false when the program goes wrong.  Each is a context manager
that patches one function of the program for its duration.

* ``state_unchanged``: the SGD step returns the parameters it was given.
* ``half_batch``: every hospital's draw loses its second half of real
  examples, and the aggregate batch counts only the rest, so the gradient
  is the mean over the examples that remain.
"""

from __future__ import annotations

import contextlib
import dataclasses


@contextlib.contextmanager
def _patched(module, name: str, replacement):
    original = getattr(module, name)
    setattr(module, name, replacement(original))
    try:
        yield
    finally:
        setattr(module, name, original)


def state_unchanged():
    from repro.arms import decaph

    return _patched(decaph, "sgd_update",
                    lambda _orig: lambda params, grads, lr, wd: params)


def half_batch():
    from repro.arms import fused

    def halve(stack_poisson):
        def halved(*args, **kwargs):
            cb = stack_poisson(*args, **kwargs)
            masks, counts = cb.masks.copy(), cb.counts.copy()
            for s, k in enumerate(counts.tolist()):
                masks[s, k // 2:k] = 0.0
                counts[s] = k // 2
            return dataclasses.replace(cb, masks=masks, counts=counts,
                                       sizes=[int(c) for c in counts])
        return halved

    return _patched(fused, "stack_poisson", halve)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch}

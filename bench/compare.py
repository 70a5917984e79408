"""The numbers that decide ``correct``: the program against the reference.

Both sides are described by what they produced over the first rounds:
the parameters after round 1 and after round 3 (flat dicts), the loss each
reported for rounds 1-3, and the epsilon reported at the end of the run.
The initial parameters ``p0`` and the round-1 noise are the reference's.

* ``loss``: the largest relative gap of a round's reported loss.
* ``grad_norm``: the first gradient as the optimizer applied it,
  ``(p0 - p1) / lr``; the gap of its norm, by the worst leaf.
* ``update_norm``: the change ``p3 - p0``; the gap of its norm, by the
  worst leaf.
* ``signal``: the first gradient with the reference's noise taken out,
  ``(p0 - p1) / lr * B - noise``: the norm of the difference, by the worst
  leaf.  Both norms above are dominated by the DP noise, which both sides
  draw from the same keys, so they cannot see an error in the clipped sum;
  this one can.
* ``epsilon``: the relative gap of the reported epsilon.

A leaf's gap is taken against its reference norm or the median leaf's,
whichever is larger.  ``signal`` leaves out leaves whose reference signal is
under a thousandth of the median leaf's (nought to rounding).
"""

from __future__ import annotations

import math
import statistics

import numpy as np

NAMES = ("loss", "grad_norm", "update_norm", "signal", "epsilon")


def _norms(tree: dict) -> dict[str, float]:
    return {k: float(np.linalg.norm(v.astype(np.float64).ravel()))
            for k, v in tree.items()}


def _worst(gaps: dict[str, float], ref: dict[str, float],
           keep=None) -> float:
    med = statistics.median(ref.values())
    keep = ref if keep is None else keep
    return max(gaps[k] / max(ref[k], med) for k in keep)


def _grad(p0: dict, p1: dict, lr: float) -> dict:
    return {k: (p0[k].astype(np.float64) - p1[k].astype(np.float64)) / lr
            for k in p0}


def _norm_gap(a: dict, b: dict) -> float:
    na, nb = _norms(a), _norms(b)
    return _worst({k: abs(na[k] - nb[k]) for k in nb}, nb)


def readings(prog: dict, ref, eps_ref: float, lr: float) -> dict[str, float]:
    """``prog``: ``losses`` (rounds 1-3), ``p1``, ``p3`` (flat dicts) and
    ``epsilon``; ``ref``: a ``reference.Trajectory`` of three rounds."""
    p0 = ref.params[0]
    loss = max(abs(a - b) / abs(b)
               for a, b in zip(prog["losses"], ref.losses))
    g_prog, g_ref = _grad(p0, prog["p1"], lr), _grad(p0, ref.params[1], lr)
    d_prog = {k: prog["p3"][k].astype(np.float64) - p0[k] for k in p0}
    d_ref = {k: ref.params[3][k].astype(np.float64) - p0[k] for k in p0}
    b, noise = ref.batches[0], ref.noise0
    s_prog = {k: g_prog[k] * b - noise[k] for k in p0}
    s_ref = {k: g_ref[k] * b - noise[k] for k in p0}
    n_ref = _norms(s_ref)
    med = statistics.median(n_ref.values())
    keep = [k for k in n_ref if n_ref[k] >= 1e-3 * med]
    signal = _worst({k: float(np.linalg.norm((s_prog[k] - s_ref[k]).ravel()))
                     for k in keep}, n_ref, keep)
    return {
        "loss": loss,
        "grad_norm": _norm_gap(g_prog, g_ref),
        "update_norm": _norm_gap(d_prog, d_ref),
        "signal": signal,
        "epsilon": abs(prog["epsilon"] - eps_ref) / eps_ref,
    }


def judge(values: dict[str, float], limits: dict[str, float]) -> bool:
    """Every compared number is finite and within its limit."""
    return all(math.isfinite(values[k]) and values[k] <= limits[k]
               for k in limits)

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


def _norm(v: np.ndarray) -> float:
    return float(np.linalg.norm(v.astype(np.float64).ravel()))


def _worst(gaps: dict[str, float], ref: dict[str, float],
           keep=None) -> float:
    med = statistics.median(ref.values())
    keep = ref if keep is None else keep
    return max(gaps[k] / max(ref[k], med) for k in keep)


def _norm_gap(na: dict[str, float], nb: dict[str, float]) -> float:
    return _worst({k: abs(na[k] - nb[k]) for k in nb}, nb)


def readings(prog: dict, ref, eps_ref: float, lr: float) -> dict[str, float]:
    """``prog``: ``losses`` (rounds 1-3), ``p1``, ``p3`` (flat dicts) and
    ``epsilon``; ``ref``: a ``reference.Trajectory`` of three rounds.
    Norms are taken leaf by leaf, so that one leaf's float64 copies are all
    a large model holds on the host at a time."""
    p0 = ref.params[0]
    loss = max(abs(a - b) / abs(b)
               for a, b in zip(prog["losses"], ref.losses))
    b, noise = ref.batches[0], ref.noise0
    n = {side: {} for side in ("g_prog", "g_ref", "d_prog", "d_ref",
                               "s_ref", "s_gap")}
    for k in p0:
        base = p0[k].astype(np.float64)
        g_prog = (base - prog["p1"][k].astype(np.float64)) / lr
        g_ref = (base - ref.params[1][k].astype(np.float64)) / lr
        s_prog, s_ref = g_prog * b - noise[k], g_ref * b - noise[k]
        n["g_prog"][k], n["g_ref"][k] = _norm(g_prog), _norm(g_ref)
        n["s_ref"][k] = _norm(s_ref)
        n["s_gap"][k] = float(np.linalg.norm((s_prog - s_ref).ravel()))
        n["d_prog"][k] = _norm(prog["p3"][k].astype(np.float64) - p0[k])
        n["d_ref"][k] = _norm(ref.params[3][k].astype(np.float64) - p0[k])
    med = statistics.median(n["s_ref"].values())
    keep = [k for k in n["s_ref"] if n["s_ref"][k] >= 1e-3 * med]
    return {
        "loss": loss,
        "grad_norm": _norm_gap(n["g_prog"], n["g_ref"]),
        "update_norm": _norm_gap(n["d_prog"], n["d_ref"]),
        "signal": _worst(n["s_gap"], n["s_ref"], keep),
        "epsilon": abs(prog["epsilon"] - eps_ref) / eps_ref,
    }


def judge(values: dict[str, float], limits: dict[str, float]) -> bool:
    """Every compared number is finite and within its limit."""
    return all(math.isfinite(values[k]) and values[k] <= limits[k]
               for k in limits)

"""Reference privacy accountant: RDP of the sampled Gaussian mechanism.

Written from Mironov, Talwar and Zhang, "Renyi Differential Privacy of the
Sampled Gaussian Mechanism" (2019): integer orders by the binomial expansion,
fractional orders by the two-sided series split at z0 with log-erfc, linear
composition over steps, and the conversion
``eps = min_alpha rdp(alpha) + log(1/delta) / (alpha - 1)``.

Every intermediate is a numpy scalar of ``dtype``: float64 for the
reference, float32 for the control that must fail the comparison.
"""

from __future__ import annotations

import numpy as np
from scipy import special


def _log_add(a, b):
    hi, lo = (a, b) if a > b else (b, a)
    if lo == -np.inf:
        return hi
    return hi + np.log1p(np.exp(lo - hi))


def _log_sub(a, b):
    if b == -np.inf:
        return a
    if a <= b:
        return a.dtype.type(-np.inf)
    return a + np.log1p(-np.exp(b - a))


def _log_erfc(x):
    # erfc(x) = 2 * Phi(-x * sqrt(2)); log_ndtr stays finite far in the tail
    f = type(x)
    return f(np.log(f(2.0))) + special.log_ndtr(-x * f(np.sqrt(2.0)))


def _log_a_int(q, sigma, alpha: int, f):
    log_a = f(-np.inf)
    for k in range(alpha + 1):
        term = (f(special.gammaln(f(alpha + 1)) - special.gammaln(f(k + 1))
                  - special.gammaln(f(alpha - k + 1)))
                + f(k) * np.log(q) + f(alpha - k) * np.log1p(-q)
                + f(k * k - k) / (f(2.0) * sigma * sigma))
        log_a = _log_add(log_a, term)
    return log_a


def _log_a_frac(q, sigma, alpha, f):
    log_a0, log_a1 = f(-np.inf), f(-np.inf)
    z0 = sigma * sigma * np.log(f(1.0) / q - f(1.0)) + f(0.5)
    i = 0
    while True:
        coef = f(special.binom(alpha, f(i)))
        log_coef = np.log(np.abs(coef))
        j = alpha - f(i)
        log_t0 = log_coef + f(i) * np.log(q) + j * np.log1p(-q)
        log_t1 = log_coef + j * np.log(q) + f(i) * np.log1p(-q)
        log_e0 = np.log(f(0.5)) + _log_erfc((f(i) - z0) / (f(np.sqrt(2.0)) * sigma))
        log_e1 = np.log(f(0.5)) + _log_erfc((z0 - j) / (f(np.sqrt(2.0)) * sigma))
        log_s0 = log_t0 + f(i * i - i) / (f(2.0) * sigma * sigma) + log_e0
        log_s1 = log_t1 + (j * j - j) / (f(2.0) * sigma * sigma) + log_e1
        if coef > 0:
            log_a0 = _log_add(log_a0, log_s0)
            log_a1 = _log_add(log_a1, log_s1)
        else:
            log_a0 = _log_sub(log_a0, log_s0)
            log_a1 = _log_sub(log_a1, log_s1)
        i += 1
        if max(log_s0, log_s1) < -30 or i > 4096:
            break
    return _log_add(log_a0, log_a1)


def epsilon(q: float, sigma: float, steps: int, delta: float,
            orders, dtype=np.float64) -> float:
    """(epsilon, delta)-DP after ``steps`` rounds at Poisson rate ``q`` and
    noise multiplier ``sigma``, over the RDP ``orders``."""
    if steps == 0:
        return 0.0
    f = np.dtype(dtype).type
    q, sigma = f(q), f(sigma)
    best = f(np.inf)
    for alpha in orders:
        a = f(alpha)
        if float(alpha).is_integer():
            log_a = _log_a_int(q, sigma, int(alpha), f)
        else:
            log_a = _log_a_frac(q, sigma, a, f)
        rdp = f(steps) * log_a / (a - f(1.0))
        eps = rdp + np.log(f(1.0) / f(delta)) / (a - f(1.0))
        if eps < best:
            best = eps
    return float(best)

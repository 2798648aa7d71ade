"""Fits the coefficients of `models/layers.py::gelu`'s erf form.

The exact GeLU is `x Phi(x)`, and for `a = |x|`

    Phi(-a) = exp(-a^2 / 2) * G(a),    G(a) = erfcx(a / sqrt 2) / 2,

where G falls from 1/2 like `1 / (a sqrt(2 pi))`, so in `t = 1 / (a + c)` it
is `t` times something nearly flat: `G(a) ~ t * P(t)`, P a polynomial.
`gelu(x) = max(x, 0) - a * t * P(t) * exp(-a^2 / 2)` is then one branch, one
divide and one `exp` for every x, and the tail keeps RELATIVE accuracy
because the `exp` carries all of its decay.

The fit is minimax in relative error (Lawson's reweighted least squares on a
grid that is uniform in t), tight where a float32 GeLU is larger than its
absolute floor of 2^-30 (a <= TIGHT) and `LOOSE` beyond, where every
bfloat16 or float32 output is within a rounding of zero on that scale and
only a bfloat16 ulp of relative accuracy is asked for. Run it to reproduce
`layers.GELU_C` and `layers.GELU_K` (printed as float32); `--scan` lists the
fit's error over other `c` (3.5 is exact in bfloat16 and among the best: a
larger c fits the tail better but its coefficients cancel more in float32):

    python tools/fit_gelu.py [--degree 7] [--c 3.5] [--scan]
"""
import argparse
import math

import numpy as np
from scipy.special import erfcx

A_MAX = 14.0      # `layers.GELU_CLAMP`: exp(-a^2 / 2) is 0 in float32 past 13.3
TIGHT = 6.6       # a * Phi(-a) < 2^-30 from 6.3 on
LOOSE = 2e-4      # relative, past TIGHT: a twentieth of a bfloat16 ulp
FLOAT32 = 2.0 ** -24


def g(a: np.ndarray) -> np.ndarray:
    """Phi(-a) exp(a^2 / 2) in float64."""
    return 0.5 * erfcx(a / math.sqrt(2.0))


def fit(c: float, degree: int, points: int = 30000, rounds: int = 150):
    """(coefficients k of `G ~ sum k_j t^(j+1)`, worst relative error on
    [0, TIGHT], worst on [0, A_MAX]) for `t = 1 / (a + c)`."""
    t = np.linspace(1 / (A_MAX + c), 1 / c, points)
    a = 1 / t - c
    target = g(a) / t
    allowed = np.where(a <= TIGHT, 1.0, LOOSE / FLOAT32)
    # columns scaled to [0, 1]: t reaches 1 / c only
    basis = np.vander(t * c, degree + 1, increasing=True)
    weight, best = np.ones(points), None
    for _ in range(rounds):
        scale = weight / target / allowed
        k, *_ = np.linalg.lstsq(basis * scale[:, None], target * scale,
                                rcond=None)
        err = np.abs(basis @ k - target) / target
        worst = (err / allowed).max()
        if best is None or worst < best[0]:
            best = (worst, k * c ** np.arange(degree + 1),
                    err[a <= TIGHT].max(), err.max())
        weight = weight * (err / allowed / worst + 1e-3)
        weight /= weight.mean()
    return best[1:]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--degree", type=int, default=7)
    ap.add_argument("--c", type=float, default=3.5)
    ap.add_argument("--scan", action="store_true")
    args = ap.parse_args()
    for c in np.arange(2.5, 5.01, 0.25) if args.scan else ():
        _, tight, whole = fit(float(c), args.degree)
        print(f"c = {c}: relative error {tight:.3g} / {whole:.3g}")
    k, tight, whole = fit(args.c, args.degree)
    print(f"degree {args.degree}: c = {args.c}, relative error {tight:.3g} "
          f"on [0, {TIGHT}], {whole:.3g} on [0, {A_MAX}]")
    print("GELU_C =", repr(float(np.float32(args.c))))
    print("GELU_K = (" + ", ".join(repr(float(np.float32(v))) for v in k)
          + ")")


if __name__ == "__main__":
    main()

"""The plain reference: the DPRT, its inverse and circular convolution,
written straight from their definitions in numpy.

Nothing here imports the program under test.  With N prime:

    R(m, d) = sum_i f(i, <d + m*i>_N)                   0 <= m < N
    R(N, d) = sum_j f(d, j)
    f(i, j) = (sum_m R(m, <j - m*i>_N) - S + R(N, i)) / N,   S = sum_d R(0, d)
    (f * g)(x, y) = sum_{a,b} g(a, b) f(<x - a>_N, <y - b>_N)

``acc`` is the accumulator: int64 for the reference itself.  The
control (:mod:`bench.control`) passes int16, the integer precision below
the int32 the configurations state, to show that the comparison catches
it.  Integer sums wrap in ``acc`` as they would on the device.
"""
from __future__ import annotations

import numpy as np


def dprt(f: np.ndarray, acc=np.int64) -> np.ndarray:
    """Forward DPRT of ``(..., N, N)`` images: ``(..., N+1, N)``."""
    n = f.shape[-1]
    fa = f.astype(acc)
    i = np.arange(n)[:, None]
    d = np.arange(n)[None, :]
    out = np.empty(f.shape[:-2] + (n + 1, n), acc)
    for m in range(n):
        out[..., m, :] = fa[..., i, (d + m * i) % n].sum(axis=-2, dtype=acc)
    out[..., n, :] = fa.sum(axis=-1, dtype=acc)
    return out


def idprt(r: np.ndarray, acc=np.int64) -> np.ndarray:
    """Inverse DPRT of ``(..., N+1, N)`` projections: ``(..., N, N)``.

    The numerator is a multiple of N for every true projection set; the
    division is floor division, so an inconsistent input still has one
    defined answer."""
    n = r.shape[-1]
    ra = r.astype(acc)
    i = np.arange(n)[:, None]
    j = np.arange(n)[None, :]
    z = np.zeros(r.shape[:-2] + (n, n), acc)
    for m in range(n):
        z += ra[..., m, (j - m * i) % n]
    s = ra[..., 0, :].sum(axis=-1, dtype=acc)[..., None, None]
    return ((z - s + ra[..., n, :, None]) // n).astype(acc)


def circ_conv2d(f: np.ndarray, g: np.ndarray, acc=np.int64) -> np.ndarray:
    """Circular 2-D convolution of ``(..., N, N)`` images with one small
    ``(k1, k2)`` kernel placed at the torus origin."""
    fa = f.astype(acc)
    out = np.zeros(f.shape, acc)
    for a in range(g.shape[0]):
        for b in range(g.shape[1]):
            if g[a, b]:
                out += acc(g[a, b]) * np.roll(fa, (a, b), axis=(-2, -1))
    return out

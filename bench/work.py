"""Operations that the definitions of the transforms need, counted from
shapes alone, whatever the program does to compute them.

An addition, a subtraction, a division and a multiply-add each count as
one operation (the DPRT's work is integer adds; the convolution's
per-direction taps are multiply-adds).  See :mod:`bench.reference` for
the definitions.
"""
from __future__ import annotations


def forward_ops(n: int) -> int:
    """N+1 directions of N sums of N terms each."""
    return (n + 1) * n * (n - 1)


def inverse_ops(n: int) -> int:
    """Per pixel: a sum of N terms, then -S, +R(N, i) and the division
    by N; plus the N-1 adds of S itself."""
    return n * n * (n - 1) + 3 * n * n + (n - 1)


def conv_ops(n: int) -> int:
    """Forward, N+1 circular 1-D convolutions of length N (N multiply-
    adds per output), then the inverse: the projection-domain definition
    of one filtered image."""
    return forward_ops(n) + (n + 1) * n * n + inverse_ops(n)


OPS = {"forward": forward_ops, "inverse": inverse_ops, "conv": conv_ops}


def ops_per_image(kind: str, n: int) -> int:
    return OPS[kind](n)

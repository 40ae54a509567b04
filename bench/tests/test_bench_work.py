"""bench/work.py against operations counted while running the naive
definitions at tiny N."""
import pytest

from bench import work


class Tally:
    """An integer that counts every +, -, * and // done with it."""

    def __init__(self, value, log):
        self.value, self.log = value, log

    def _op(self, kind, other, fn):
        self.log[kind] = self.log.get(kind, 0) + 1
        o = other.value if isinstance(other, Tally) else other
        return Tally(fn(self.value, o), self.log)

    def __add__(self, o):
        return self._op("add", o, lambda a, b: a + b)

    def __sub__(self, o):
        return self._op("add", o, lambda a, b: a - b)

    def __mul__(self, o):
        return self._op("mul", o, lambda a, b: a * b)

    def __floordiv__(self, o):
        return self._op("add", o, lambda a, b: a // b)


def naive_forward(f, n):
    r = [[None] * n for _ in range(n + 1)]
    for m in range(n):
        for d in range(n):
            acc = f[0][d]
            for i in range(1, n):
                acc = acc + f[i][(d + m * i) % n]
            r[m][d] = acc
    for d in range(n):
        acc = f[d][0]
        for j in range(1, n):
            acc = acc + f[d][j]
        r[n][d] = acc
    return r


def naive_inverse(r, n):
    s = r[0][0]
    for d in range(1, n):
        s = s + r[0][d]
    out = [[None] * n for _ in range(n)]
    for i in range(n):
        for j in range(n):
            acc = r[0][j]
            for m in range(1, n):
                acc = acc + r[m][(j - m * i) % n]
            out[i][j] = (acc - s + r[n][i]) // n
    return out


def naive_conv_stage(rf, rg, n):
    out = []
    for m in range(n + 1):
        row = []
        for d in range(n):
            acc = rf[m][0] * rg[m][d]
            for t in range(1, n):
                acc = acc + rf[m][t] * rg[m][(d - t) % n]
            row.append(acc)
        out.append(row)
    return out


def tallied(n, log):
    return [[Tally((7 * i + 3 * j) % 11, log) for j in range(n)]
            for i in range(n)]


@pytest.mark.parametrize("n", [3, 5, 7])
def test_forward_and_inverse_counts(n):
    log = {}
    r = naive_forward(tallied(n, log), n)
    assert log == {"add": work.forward_ops(n)}
    log.clear()
    naive_inverse(r, n)
    assert log == {"add": work.inverse_ops(n)}


@pytest.mark.parametrize("n", [3, 5])
def test_conv_counts_one_op_per_multiply_add(n):
    log = {}
    rf = naive_forward(tallied(n, log), n)
    rg = [[v.value for v in row] for row in naive_forward(tallied(n, {}), n)]
    rc = naive_conv_stage(rf, rg, n)
    naive_inverse(rc, n)
    # the taps' adds ride their multiplies: a multiply-add is one op
    taps = log["mul"]
    assert taps == (n + 1) * n * n
    assert log["add"] - (n + 1) * n * (n - 1) + taps == work.conv_ops(n)
    assert work.ops_per_image("conv", n) == work.conv_ops(n)

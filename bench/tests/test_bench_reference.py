"""bench/reference.py agrees with the program's own numpy oracles, with
which it shares no code, and its jnp port (bench/reference_jnp.py) agrees
with it in int32; the int16 control does not."""
import numpy as np
import pytest

from bench import control, reference, reference_jnp
from repro.core import (circ_conv2d_direct, dprt_oracle_np,
                        idprt_oracle_np)

KERNEL = np.array([[1, 2, 3, 2, 1], [2, 4, 6, 4, 2], [3, 6, 9, 6, 3],
                   [2, 4, 6, 4, 2], [1, 2, 3, 2, 1]])


@pytest.mark.parametrize("n", [5, 7, 31])
def test_reference_matches_oracles(n):
    rng = np.random.default_rng(n)
    f = rng.integers(0, 256, (3, n, n), dtype=np.uint8)
    r = reference.dprt(f)
    for b in range(3):
        assert (r[b] == dprt_oracle_np(f[b])).all()
        assert (reference.idprt(r[b]) == idprt_oracle_np(r[b])).all()
    assert (reference.idprt(r) == f).all()
    g = np.zeros((n, n), np.int64)
    k = KERNEL[:n, :n]
    g[:k.shape[0], :k.shape[1]] = k
    want = np.asarray(circ_conv2d_direct(f[0].astype(np.int32),
                                         g.astype(np.int32)))
    assert (reference.circ_conv2d(f[0], k) == want).all()


@pytest.mark.parametrize("n", [7, 31])
def test_control_at_full_precision_is_the_reference(n):
    import jax.numpy as jnp
    rng = np.random.default_rng(n + 1)
    f = rng.integers(0, 256, (2, n, n), dtype=np.uint8)
    r = np.asarray(reference_jnp.dprt(jnp.asarray(f), jnp.int32))
    assert (r == reference.dprt(f)).all()
    back = reference_jnp.idprt(jnp.asarray(r), jnp.int32)
    assert (np.asarray(back) == f).all()
    c = np.asarray(reference_jnp.conv(jnp.asarray(f), KERNEL, jnp.int32))
    assert (c == reference.circ_conv2d(f, KERNEL)).all()


def test_int16_control_fails_where_sums_leave_int16():
    import jax.numpy as jnp
    n = 137                       # 137 * 250 > 2**15: forward sums wrap
    f = np.random.default_rng(0).integers(250, 256, (1, n, n),
                                          dtype=np.uint8)
    want = reference.dprt(f)
    got = np.asarray(reference_jnp.dprt(jnp.asarray(f), control.ACC))
    got = got.astype(np.int64)
    assert (got != want).any()
    got_inv = np.asarray(reference_jnp.idprt(
        jnp.asarray(want.astype(np.int32)), control.ACC))
    assert (got_inv.astype(np.int64) != f).any()
    assert (reference.dprt(f, np.int16).astype(np.int64) != want).any()

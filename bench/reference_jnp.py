"""The definitions of :mod:`bench.reference` ported to jnp, with the
accumulator as a parameter, so that they run on the device.

The control (:mod:`bench.control`) runs them in int16, the integer
precision below the int32 the configurations state; the open-loop
generator makes its inverse payloads with them in int32.  Like the
reference, nothing here imports the program under test.
"""
from __future__ import annotations


def _jnp():
    import jax
    import jax.numpy as jnp
    return jax, jnp


def dprt(x, acc):
    """(..., N, N) -> (..., N+1, N), sums wrapping in ``acc``."""
    jax, jnp = _jnp()
    n = x.shape[-1]
    xa = x.astype(acc)
    i = jnp.arange(n)[:, None]
    d = jnp.arange(n)[None, :]

    def direction(m):
        return xa[..., i, (d + m * i) % n].sum(axis=-2, dtype=acc)
    r = jax.lax.map(direction, jnp.arange(n))          # (N, ..., N)
    r = jnp.moveaxis(r, 0, -2)
    return jnp.concatenate([r, xa.sum(axis=-1, dtype=acc)[..., None, :]],
                           axis=-2)


def idprt(r, acc):
    jax, jnp = _jnp()
    n = r.shape[-1]
    ra = r.astype(acc)
    i = jnp.arange(n)[:, None]
    j = jnp.arange(n)[None, :]

    def add(m, z):
        return z + ra[..., m, :][..., (j - m * i) % n]
    z = jax.lax.fori_loop(0, n, add, jnp.zeros(r.shape[:-2] + (n, n), acc))
    s = ra[..., 0, :].sum(axis=-1, dtype=acc)[..., None, None]
    return (z - s + ra[..., n, :, None]) // n


def conv(x, kernel, acc):
    """Projection-domain circular convolution with ``acc`` sums."""
    jax, jnp = _jnp()
    n = x.shape[-1]
    g = jnp.zeros((n, n), acc).at[:kernel.shape[0], :kernel.shape[1]].set(
        jnp.asarray(kernel, acc))
    rf = dprt(x, acc)                                  # (..., N+1, N)
    rg = dprt(g, acc)                                  # (N+1, N)

    def tap(t, rc):     # rc(m, d) += rf(m, t) rg(m, <d - t>)
        col = jax.lax.dynamic_index_in_dim(rf, t, axis=-1, keepdims=True)
        return rc + col * jnp.roll(rg, t, axis=-1)
    rc = jax.lax.fori_loop(0, n, tap, jnp.zeros(rf.shape, acc))
    return idprt(rc, acc)

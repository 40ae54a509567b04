"""Discrete Periodic Radon Transform (DPRT) and its exact inverse.

Implements the transforms of Carranza/Llamocca/Pattichis in three
strategies that mirror the paper's architecture space:

* ``gather``  -- per-direction shear via ``take_along_axis`` (the "memory
  indexing" formulation the paper's hardware *avoids*; kept as oracle and
  as the systolic-architecture analog).
* ``horner``  -- the paper's shift-and-add dataflow: a Horner recurrence
  over image rows where each step circularly shifts the accumulator and
  adds one row (CLS registers + adder trees, Sec. III-B).
* ``strips``  -- the scalable SFDPRT (Sec. III-A/B): the image is split
  into K = ceil(N/H) strips of H rows, each strip produces a *partial*
  DPRT via the Horner recurrence, and partial results are aligned
  (one circular roll) and accumulated -- eq. (7)-(8) of the paper.
* ``pallas``  -- the fused, batched Pallas TPU kernel family
  (:mod:`repro.kernels`): the strip decomposition mapped onto a
  (batch, m-block, strip) grid whose Horner step is one native strided
  lane rotate on a periodically extended accumulator, with the
  forward/inverse epilogues fused in-kernel; block shapes come
  from the ``repro.kernels.tuning`` table unless given explicitly.
* ``sharded`` / ``sharded_pallas`` -- the shard_map super-strip paths
  (:mod:`repro.core.distributed`); need ``mesh=``.  ``sharded_pallas``
  runs the fused Pallas kernel per device shard (one kernel call + one
  collective) and is the ``method="auto"`` pick under a mesh.

Method dispatch lives in :mod:`repro.core.plan` (the backend registry);
this module owns the transform *primitives* (Horner scans, strip
partials, alignment rolls) that the registered backends are built from,
plus the thin public entry points.  ``method="auto"`` picks the best
registered backend for the call site.

Inputs may be any ``(H, W)`` or ``(B, H, W)`` geometry: non-square or
non-prime images are zero-embedded into the smallest prime
``P >= max(H, W)`` (see :mod:`repro.core.geometry`), so :func:`dprt`
returns ``(P+1, P)`` projections.  The pad metadata is recorded on the
cached plan -- ``plan.inverse(plan.forward(f)) == f`` bit-exactly for
any integer image (:func:`repro.core.plan.get_plan`).

All integer inputs are transformed with exact fixed-point arithmetic
(the paper's motivation vs. floating-point FFTs); the inverse divides by
N exactly and ``idprt(dprt(f)) == f`` holds bit-for-bit.

Definitions (N prime):

    R(m,d) = sum_i f(i, <d + m*i>_N)    0 <= m < N
    R(N,d) = sum_j f(d, j)

    f(i,j) = (1/N) [ sum_m R(m, <j - m*i>_N) - S + R(N,i) ]
"""
from __future__ import annotations

import math
from typing import Literal, Optional

import jax
import jax.numpy as jnp
import numpy as np

Method = Literal["auto", "gather", "horner", "strips", "pallas", "sharded",
                 "sharded_pallas"]

__all__ = [
    "is_prime",
    "next_prime",
    "dprt",
    "idprt",
    "dprt_batched",
    "idprt_batched",
    "skew_sum",
    "strip_partial",
    "align_partial",
    "accum_dtype_for",
    "float_dtype_for",
    "int32_accum_exact",
]


# ---------------------------------------------------------------------------
# primes
# ---------------------------------------------------------------------------
def is_prime(n: int) -> bool:
    if n < 2:
        return False
    if n < 4:
        return True
    if n % 2 == 0:
        return False
    f = 3
    while f * f <= n:
        if n % f == 0:
            return False
        f += 2
    return True


def next_prime(n: int) -> int:
    """Smallest prime >= n."""
    while not is_prime(n):
        n += 1
    return n


#: worst-case growth of the exact inverse intermediates: for pixels of
#: magnitude <= v the CRS core Z is <= v*N^2 (N direction rows, each an
#: N-term sum of <= v*N projections... bounded by v*N per row) and the
#: -S + R(N, i) correction adds up to v*N more, so |Z - S + R(N, i)| <=
#: v*N*(N+1).  Forward-only growth is just v*N (one N-term sum).
_INT32_MAX = 2**31 - 1
_X64_WARNED = False


def int32_accum_exact(n: int, dtype) -> bool:
    """True when an int32 accumulator provably cannot overflow the
    inverse's ``v*N*(N+1)`` worst case for full-range pixels of this
    integer dtype at transform size N (prime).

    ``v`` is the dtype's max magnitude: for uint8 (v=255) the bound
    gives N*(N+1) <= (2^31-1)/255, i.e. int32 stays exact up to prime
    N <= 2897 -- and FAILS at the next prime 2903 (255*2903*2904 >
    2^31).  For int16 (v=32767) the cliff is already at N=257.
    """
    dtype = jnp.dtype(dtype)
    if not jnp.issubdtype(dtype, jnp.integer):
        raise TypeError(f"int32_accum_exact is an integer bound: {dtype}")
    info = jnp.iinfo(dtype)
    v = max(int(info.max), -int(info.min))
    return v * n * (n + 1) <= _INT32_MAX


def accum_dtype_for(dtype, n: Optional[int] = None, *,
                    warn: bool = True) -> jnp.dtype:
    """Accumulator dtype with enough headroom for exact sums.

    Forward growth is +ceil(log2 N) bits; inverse adds another
    ceil(log2 N) plus the -S + R(N, i) correction (paper Sec. IV-B),
    so the worst intermediate for pixels of magnitude <= v is
    ``v*N*(N+1)`` (:func:`int32_accum_exact`).  For 8-bit pixels int32
    therefore stays exact up to prime N <= 2897; int16 pixels already
    need promotion at N >= 257.

    When the transform size ``n`` is given, *narrow* integer inputs
    (int8/uint8/int16/uint16 -- dtypes whose full range is a true pixel
    bound) are promoted to int64 whenever the int32 bound fails, so the
    giant-N geometries (N >= 2903 for 8-bit data) stay exact under x64.
    int32/uint32 inputs keep the int32 accumulator regardless (their
    dtype max is not a pixel bound; pass int64 inputs under x64 for a
    guarantee, as before).  Without ``n`` the legacy dtype-only rule
    applies unchanged.

    ``warn=False`` suppresses the no-x64 warning: call sites that only
    need the accumulator's *itemsize* for block sizing (plan build,
    kernel tuning) or its name for metadata must not claim an overflow
    that no integer accumulation will ever hit -- e.g. a solver that
    promotes the same geometry to float residual arithmetic
    (:func:`float_dtype_for`) before any sum runs.
    """
    dtype = jnp.dtype(dtype)
    if dtype in (jnp.int64, jnp.uint64):
        return jnp.dtype(jnp.int64)
    if jnp.issubdtype(dtype, jnp.integer):
        if (n is not None and dtype.itemsize < 4
                and not int32_accum_exact(int(n), dtype)):
            if jax.config.jax_enable_x64:
                return jnp.dtype(jnp.int64)
            global _X64_WARNED
            if warn and not _X64_WARNED:  # pragma: no cover - x64 flag
                _X64_WARNED = True
                import warnings
                warnings.warn(
                    f"{dtype.name} pixels at N={n} exceed the int32 "
                    f"accumulator bound v*N*(N+1) <= 2^31-1 but x64 is "
                    f"disabled; enable jax_enable_x64 for an exact int64 "
                    f"accumulator (falling back to int32, sums may "
                    f"overflow)", stacklevel=2)
        return jnp.dtype(jnp.int32)
    if dtype == jnp.float64:
        return jnp.dtype(jnp.float64)
    return jnp.dtype(jnp.float32)


def float_dtype_for(dtype) -> jnp.dtype:
    """Float dtype for residual/solver arithmetic over ``dtype`` data.

    Iterative reconstruction (:mod:`repro.radon.solve`) runs CG/LSQR/
    Landweber residual updates in floating point regardless of the
    sinogram's storage dtype: float64 stays float64; 64-bit integers
    promote to float64 when x64 is enabled (their magnitudes exceed a
    float32 mantissa); everything else -- float32/16 and all the pixel
    integer dtypes -- solves in float32.  Integer inputs never route
    through the integer-accumulator rules, so the int64-under-x64
    warning of :func:`accum_dtype_for` cannot fire for a solve.
    """
    dtype = jnp.dtype(dtype)
    if dtype == jnp.float64:
        return jnp.dtype(jnp.float64)
    if (jnp.issubdtype(dtype, jnp.integer) and dtype.itemsize >= 8
            and jax.config.jax_enable_x64):
        return jnp.dtype(jnp.float64)
    return jnp.dtype(jnp.float32)


# ---------------------------------------------------------------------------
# the skew-sum primitive
#
#   skew_sum(g, sign)[m, d] = sum_i g(i, <d + sign*m*i>_N)
#
# Forward DPRT core is sign=+1 applied to the image; the inverse core
# (sum over m of R(m, <j - i*m>)) is sign=-1 applied to R[:N].
# ---------------------------------------------------------------------------
def _step_indices(n: int, sign: int) -> jnp.ndarray:
    """idx[m, d] = <d + sign*m>_N : one Horner step's shift per direction."""
    m = jnp.arange(n, dtype=jnp.int32)[:, None]
    d = jnp.arange(n, dtype=jnp.int32)[None, :]
    return (d + sign * m) % n


def _skew_sum_gather(g: jnp.ndarray, sign: int, block_m: int = 32) -> jnp.ndarray:
    """Oracle/systolic analog: one shear (gather) per direction, then sum."""
    n = g.shape[0]
    acc_dtype = accum_dtype_for(g.dtype, n)
    gacc = g.astype(acc_dtype)
    i = jnp.arange(n, dtype=jnp.int32)[:, None]
    d = jnp.arange(n, dtype=jnp.int32)[None, :]

    def one_direction(m):
        idx = (d + sign * m * i) % n
        return jnp.take_along_axis(gacc, idx, axis=1).sum(axis=0)

    ms = jnp.arange(n, dtype=jnp.int32)
    return jax.lax.map(one_direction, ms, batch_size=min(block_m, n))


def _horner_scan(strip: jnp.ndarray, n: int, sign: int,
                 acc_dtype) -> jnp.ndarray:
    """Horner recurrence over the rows of ``strip`` (shape (H, N)).

    Returns U[m, d] = sum_{i<H} strip(i, <d + sign*m*i>_N), for all N
    directions m.  Each scan step is the paper's single clock cycle:
    circularly shift the (direction x d) accumulator by one step of m
    and add the next row.
    """
    idx = _step_indices(n, sign)

    def step(t, row):
        t = jnp.take_along_axis(t, idx, axis=1) + row[None, :]
        return t, None

    rows = strip[::-1].astype(acc_dtype)  # process bottom row first (T_H = 0)
    # zeros derived from the data so the carry inherits any shard_map
    # varying-axis annotations (required for scan under shard_map).
    t0 = jnp.zeros((n, n), acc_dtype) + (rows[0] * 0)[None, :]
    t, _ = jax.lax.scan(step, t0, rows)
    return t


def _skew_sum_horner(g: jnp.ndarray, sign: int) -> jnp.ndarray:
    n = g.shape[0]
    return _horner_scan(g, n, sign, accum_dtype_for(g.dtype, n))


def strip_partial(strip: jnp.ndarray, n: int, sign: int = 1,
                  acc_dtype=None) -> jnp.ndarray:
    """Partial skew-sum of one strip (paper eq. (7), before alignment)."""
    if acc_dtype is None:
        acc_dtype = accum_dtype_for(strip.dtype, n)
    return _horner_scan(strip, n, sign, acc_dtype)


def align_partial(u: jnp.ndarray, row_offset, sign: int = 1) -> jnp.ndarray:
    """Align a strip's partial result: R'(r,m,d) = U_r(<d + sign*m*rH>_N).

    ``row_offset`` is the strip's first global row (r*H); it may be a
    traced scalar (used by the shard_map distributed path).
    """
    n = u.shape[1]
    m = jnp.arange(n, dtype=jnp.int32)[:, None]
    d = jnp.arange(n, dtype=jnp.int32)[None, :]
    idx = (d + sign * m * jnp.asarray(row_offset, jnp.int32)) % n
    return jnp.take_along_axis(u, idx, axis=1)


def _skew_sum_strips(g: jnp.ndarray, sign: int, strip_rows: int) -> jnp.ndarray:
    """The scalable strip decomposition (paper eq. (5)-(8))."""
    n = g.shape[0]
    h = int(strip_rows)
    if not (1 <= h <= n):
        raise ValueError(f"strip_rows must be in [1, {n}], got {h}")
    k = math.ceil(n / h)
    acc_dtype = accum_dtype_for(g.dtype, n)
    pad = k * h - n
    gp = jnp.pad(g, ((0, pad), (0, 0)))  # zero rows contribute nothing
    strips = gp.reshape(k, h, n)

    partial = jax.vmap(lambda s: _horner_scan(s, n, sign, acc_dtype))(strips)
    offsets = jnp.arange(k, dtype=jnp.int32) * h
    aligned = jax.vmap(lambda u, off: align_partial(u, off, sign))(partial,
                                                                   offsets)
    return aligned.sum(axis=0)  # MEM_OUT accumulation, eq. (8)


def skew_sum(g: jnp.ndarray, sign: int, method: Method = "horner",
             strip_rows: Optional[int] = None,
             m_block: Optional[int] = None, mesh=None) -> jnp.ndarray:
    """skew_sum(g, sign)[m, d] = sum_i g(i, <d + sign*m*i>_N).

    Routed through the backend registry (:mod:`repro.core.plan`); any
    registered method name (or ``"auto"``) is accepted.
    """
    from .plan import dispatch_skew_sum  # lazy: plan imports this module
    return dispatch_skew_sum(g, sign, method=method, strip_rows=strip_rows,
                             m_block=m_block, mesh=mesh)


# ---------------------------------------------------------------------------
# public transforms: thin deprecation shims over repro.radon operators
#
# The per-call kwarg surface below predates the operator API; it now
# resolves its knobs (explicit > ambient radon.config scope > legacy
# default) and routes through the SAME cached, differentiable,
# trace-counted appliers as `radon.DPRT(...)`.  New code should build
# operators instead -- these wrappers warn once per process when the
# legacy knob plumbing is used.
# ---------------------------------------------------------------------------
_LEGACY_KNOB_WARNED = False


def _warn_legacy_knobs() -> None:
    global _LEGACY_KNOB_WARNED
    if _LEGACY_KNOB_WARNED:
        return
    _LEGACY_KNOB_WARNED = True
    import sys
    import warnings
    # point the warning at the caller's code, not at this module's
    # internals: skip however many shim frames (dprt -> dprt_batched
    # etc.) sit between here and the first out-of-module frame
    stacklevel, frame = 1, sys._getframe()
    while frame is not None and frame.f_code.co_filename == __file__:
        frame = frame.f_back
        stacklevel += 1
    warnings.warn(
        "passing method=/strip_rows=/m_block=/... per call to "
        "repro.core.dprt functions is deprecated: build an operator once "
        "with repro.radon.DPRT(shape, dtype, method=..., ...) or set an "
        "ambient scope with repro.radon.config(...). The kwargs keep "
        "working (this warns once per process).",
        DeprecationWarning, stacklevel=stacklevel)


def _legacy_operator(shape, dtype, method, strip_rows, m_block, batch_impl,
                     block_rows, block_batch, mesh, stream_rows=None):
    """Resolve legacy per-call knobs into a cached radon operator."""
    if any(k is not None for k in (method, strip_rows, m_block, block_rows,
                                   stream_rows, block_batch, mesh)
           ) or batch_impl not in (None, "auto"):
        _warn_legacy_knobs()
    from repro.radon import DPRT, ambient  # lazy: radon imports this module
    # legacy default was method="horner" -- EXCEPT under a mesh (explicit
    # or ambient), where "auto" routes to the mesh-aware registry pick
    # (sharded_pallas / sharded); ambient scopes override either default
    mesh = ambient.resolve("mesh", mesh)
    fallback = "horner" if mesh is None else "auto"
    return DPRT(shape, dtype,
                method=ambient.resolve("method", method, fallback),
                strip_rows=strip_rows, m_block=m_block,
                batch_impl=batch_impl, block_rows=block_rows,
                stream_rows=stream_rows, block_batch=block_batch, mesh=mesh)


def dprt(f: jnp.ndarray, method: Optional[Method] = None,
         strip_rows: Optional[int] = None,
         m_block: Optional[int] = None,
         batch_impl: Optional[str] = None,
         block_rows: Optional[int] = None,
         block_batch: Optional[int] = None,
         mesh=None, stream_rows: Optional[int] = None) -> jnp.ndarray:
    """Forward DPRT: (H, W) image -> (P+1, P) projections. Exact for ints.

    Deprecation shim over ``repro.radon.DPRT(f.shape, f.dtype, ...)``;
    same numerics, same caches, and now differentiable (`jax.grad` /
    `jax.jvp` hit the exact adjoint rules).  Any geometry is accepted:
    square prime-N images transform natively (P = N); everything else is
    zero-embedded into the smallest prime P >= max(H, W).  A
    ``(B, H, W)`` stack transforms batched (for ``method="pallas"``: ONE
    fused pallas_call).  Unset knobs resolve against the ambient
    :func:`repro.radon.config` scope, then the legacy default
    (``horner``); use the operator's ``.inverse`` when you need the
    crop-back inverse of a padded geometry.
    """
    op = _legacy_operator(f.shape, f.dtype, method, strip_rows, m_block,
                          batch_impl, block_rows, block_batch, mesh,
                          stream_rows=stream_rows)
    return op(f)


def idprt(r: jnp.ndarray, method: Optional[Method] = None,
          strip_rows: Optional[int] = None,
          m_block: Optional[int] = None,
          batch_impl: Optional[str] = None,
          block_rows: Optional[int] = None,
          block_batch: Optional[int] = None,
          mesh=None, stream_rows: Optional[int] = None) -> jnp.ndarray:
    """Inverse DPRT: (N+1, N) projections -> (N, N) image.

    Deprecation shim over ``repro.radon.DPRT((N, N), ...).inverse``.
    Exact integer reconstruction: the bracketed sum is always divisible
    by N (property-tested), so integer inputs round-trip bit-for-bit.
    Batched ``(B, N+1, N)`` stacks are accepted.  Projections always
    live in the prime domain; to recover the original (H, W) of an
    embedded image, call ``.inverse`` on the operator/plan that produced
    the projections (it crops the recorded padding).
    """
    if r.ndim not in (2, 3) or r.shape[-2] != r.shape[-1] + 1:
        raise ValueError(
            f"iDPRT input must be (N+1, N) or (B, N+1, N), got {r.shape}")
    n = r.shape[-1]
    if not is_prime(n):
        raise ValueError(f"iDPRT needs prime N, got N={n}")
    shape = (n, n) if r.ndim == 2 else (r.shape[0], n, n)
    op = _legacy_operator(shape, r.dtype, method, strip_rows, m_block,
                          batch_impl, block_rows, block_batch, mesh,
                          stream_rows=stream_rows)
    return op.inverse(r)


def dprt_batched(f: jnp.ndarray, method: Optional[Method] = None,
                 strip_rows: Optional[int] = None,
                 batch_impl: Optional[str] = None,
                 m_block: Optional[int] = None,
                 block_batch: Optional[int] = None,
                 mesh=None) -> jnp.ndarray:
    """Batched :func:`dprt` over a leading axis (requires (B, H, W)).

    ``method="pallas"`` transforms the whole stack in ONE fused
    pallas_call (the paper's Sec. V-B coprocessor throughput scenario).
    Other backends batch via ``batch_impl``: 'vmap' | 'map' | 'auto'
    (auto: `lax.map` on CPU, vmap on TPU -- measured EXPERIMENTS.md
    §Perf).  ``block_batch`` streams the stack through the backend in
    bounded-size chunks.
    """
    if f.ndim != 3:
        raise ValueError(f"dprt_batched needs (B, H, W), got {f.shape}")
    return dprt(f, method=method, strip_rows=strip_rows, m_block=m_block,
                batch_impl=batch_impl, block_batch=block_batch, mesh=mesh)


def idprt_batched(r: jnp.ndarray, method: Optional[Method] = None,
                  strip_rows: Optional[int] = None,
                  batch_impl: Optional[str] = None,
                  m_block: Optional[int] = None,
                  block_batch: Optional[int] = None,
                  mesh=None) -> jnp.ndarray:
    """Batched :func:`idprt` over a leading axis (requires (B, N+1, N))."""
    if r.ndim != 3:
        raise ValueError(f"idprt_batched needs (B, N+1, N), got {r.shape}")
    return idprt(r, method=method, strip_rows=strip_rows, m_block=m_block,
                 batch_impl=batch_impl, block_batch=block_batch, mesh=mesh)


# ---------------------------------------------------------------------------
# numpy oracle (used by tests; deliberately independent of the jax paths)
# ---------------------------------------------------------------------------
def dprt_oracle_np(f: np.ndarray) -> np.ndarray:
    n = f.shape[0]
    assert f.shape == (n, n) and is_prime(n)
    out = np.zeros((n + 1, n), dtype=np.int64)
    cols = np.arange(n)
    for m in range(n):
        for i in range(n):
            out[m] += f[i, (cols + m * i) % n].astype(np.int64)
    out[n] = f.sum(axis=1)
    return out


def idprt_oracle_np(r: np.ndarray) -> np.ndarray:
    n = r.shape[1]
    assert r.shape == (n + 1, n) and is_prime(n)
    s = int(r[0].sum())
    f = np.zeros((n, n), dtype=np.int64)
    cols = np.arange(n)
    for i in range(n):
        z = np.zeros(n, dtype=np.int64)
        for m in range(n):
            z += r[m, (cols - m * i) % n].astype(np.int64)
        f[i] = (z - s + int(r[n, i]))
    assert (f % n == 0).all(), "inverse DPRT numerator must be divisible by N"
    return f // n

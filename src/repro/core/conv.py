"""Exact fixed-point 2-D convolution via the DPRT convolution property.

The paper's headline application (Sec. I, VI): because the DPRT satisfies a
discrete Fourier-slice theorem, the DPRT of a 2-D *circular* convolution is
the per-direction 1-D circular convolution of the DPRTs:

    R_{f ** g}(m, .) = R_f(m, .) (*)_N R_g(m, .)     for all N+1 directions m

so 2-D convolution = DPRT -> (N+1) independent 1-D circular convolutions ->
inverse DPRT, entirely in integer arithmetic (no floating-point FFT).

All DPRT work routes through the transform-plan dispatch
(:mod:`repro.core.plan`), so ``method`` may be any registered backend
name (including ``"auto"`` and ``"pallas"``), and geometry handling
comes from :mod:`repro.core.geometry`:

* **Linear convolution** of arbitrary rectangular operands zero-pads
  both to the next prime P >= out_h/out_w *per axis* (the paper's
  density-of-primes argument: a power-of-two FFT must pad up to ~2x,
  the next prime is only O(log P) away on average).
* **Blocked linear convolution** (``block_size=``) realizes the
  companion paper's overlap-add scheme (arXiv 2112.13150) on the plan
  layer: the image is tiled into ``block_size``-sized square tiles,
  every tile convolves against the small kernel at the much smaller
  tile prime q = next_prime(block + k - 1) -- one batched fused-kernel
  call over the whole tile stack -- and per-tile results overlap-add
  onto the output canvas (`lax.scan`, one tile live at a time).  Exact
  in integers: tile padding is zeros, and overlap-add of exact tile
  linear convolutions is the exact full linear convolution.
* **Circular convolution** of square prime operands uses the direct
  transform-domain route above; any other (equal) geometry is convolved
  on its true (H, W) torus by folding the exact prime-embedded linear
  convolution (:func:`repro.core.geometry.fold_mod`) -- still bit-exact.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import geometry as G
from .dprt import accum_dtype_for, is_prime, next_prime

__all__ = [
    "circ_conv1d_exact",
    "circ_conv2d_dprt",
    "circ_conv2d_direct",
    "circ_conv2d_fft",
    "linear_conv2d_dprt",
    "linear_conv2d_direct",
    "prime_vs_pow2_padding",
]


def circ_conv1d_exact(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Batched exact 1-D circular convolution along the last axis.

    a, b: (..., N) with broadcastable leading dims.
    out[..., d] = sum_t a[..., t] * b[..., <d-t>_N].  O(N^2) integer
    MACs per row -- these run on the MXU as a matmul with the circulant
    of ``b``.  The circulant is only ever materialized from the
    *unbatched* operand (convolution commutes, so a batched ``b`` swaps
    with ``a``; two batched operands stream through `lax.map`), keeping
    the peak intermediate at O(rows * N^2) instead of O(B * rows * N^2).
    """
    n = a.shape[-1]
    acc = accum_dtype_for(jnp.result_type(a.dtype, b.dtype))
    out_lead = jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1])
    while a.ndim >= 3 and a.shape[0] == 1:   # size-1 batches broadcast
        a = a[0]
    while b.ndim >= 3 and b.shape[0] == 1:
        b = b[0]
    if b.ndim > a.ndim:
        a, b = b, a              # build the circulant from the smaller side
    if b.ndim >= 3 and b.ndim == a.ndim:   # both batched at the same
        if a.shape[:-2] != b.shape[:-2]:   # rank: one pair live at a time
            raise ValueError(
                f"batched circ_conv1d operands need matching leading "
                f"dims, got {a.shape} vs {b.shape}")
        out = jax.lax.map(lambda ab: circ_conv1d_exact(*ab), (a, b))
    else:  # circulant from the lower-rank side, broadcast over the rest
        d = jnp.arange(n)[:, None]
        t = jnp.arange(n)[None, :]
        bc = b.astype(acc)[..., (d - t) % n]   # bc[..., d, t] = b[<d-t>]
        out = jnp.einsum("...t,...dt->...d", a.astype(acc), bc)
    return jnp.broadcast_to(out, (*out_lead, n))


def _resolve_knobs(method, strip_rows, m_block) -> tuple:
    """Full ambient-knob snapshot (see ``ambient.snapshot_knobs``),
    taken OUTSIDE the jit boundaries below so the whole scope is part
    of each trace-cache key.  The fallback method is ``"auto"``: the
    registry's best backend for the geometry (the fused pipeline-capable
    Pallas kernel for int/float images)."""
    from repro.radon import ambient  # lazy: radon imports repro.core
    return ambient.snapshot_knobs(method, strip_rows, m_block,
                                  fallback_method="auto")


def _operator(shape, dtype, knobs: tuple):
    """The cached radon operator for one operand geometry."""
    from repro.radon import operator_for  # lazy: radon imports repro.core
    return operator_for(shape, dtype, knobs)


def _use_pipeline(plan, fuse: Optional[bool]) -> bool:
    """The staged-fallback rule: fuse when the backend declares the
    pipeline capability (and no ``block_rows`` streaming is requested);
    ``fuse=False`` forces the staged path, ``fuse=True`` asks for the
    pipeline dispatch (which itself falls back to staged stages on
    non-capable backends, bit-exactly)."""
    if fuse is not None:
        return bool(fuse)
    return plan.backend.pipeline is not None and plan.block_rows is None


def _circ_prime(f: jnp.ndarray, g: jnp.ndarray, knobs: tuple,
                fuse: Optional[bool]) -> jnp.ndarray:
    """Transform-domain circular convolution of square prime operands.

    Fused route (pipeline-capable backends): transform, per-direction
    1-D circular convolution and inverse as ONE kernel launch -- the
    projections never round-trip through HBM; a kernel shared by a
    stack is forwarded once by the backend's pipeline.  Staged route:
    forward both operands, 1-D convolve (circulant built from the
    unbatched side only), inverse.
    """
    from repro.radon import pipeline_apply  # lazy: radon imports repro.core

    def fwd(x):
        return _operator(x.shape, x.dtype, knobs)(x)

    plan = _operator(f.shape, f.dtype, knobs).plan
    if _use_pipeline(plan, fuse):
        if g.ndim > f.ndim:      # convolution commutes: pipeline the stack
            return _circ_prime(g, f, knobs, fuse)
        return pipeline_apply(plan, f, "conv", g)
    rf, rg = fwd(f), fwd(g)
    rc = circ_conv1d_exact(rf, rg)      # all N+1 directions at once
    n = rc.shape[-1]
    shape = (n, n) if rc.ndim == 2 else (rc.shape[0], n, n)
    inv = _operator(shape, rc.dtype, knobs).inverse
    return inv(rc)


@functools.partial(jax.jit, static_argnames=("knobs", "block_size", "fuse"))
def _circ_conv2d_jit(f: jnp.ndarray, g: jnp.ndarray, knobs: tuple,
                     block_size: Optional[int],
                     fuse: Optional[bool]) -> jnp.ndarray:
    fh, fw = f.shape[-2:]
    if fh == fw and is_prime(fh) and block_size is None:
        return _circ_prime(f, g, knobs, fuse)
    lin = _linear_conv2d_jit(f, g, knobs, block_size, fuse)
    return G.fold_mod(lin, fh, fw)


def circ_conv2d_dprt(f: jnp.ndarray, g: jnp.ndarray,
                     method: Optional[str] = None,
                     strip_rows: Optional[int] = None,
                     m_block: Optional[int] = None,
                     block_size: Optional[int] = None,
                     fuse: Optional[bool] = None) -> jnp.ndarray:
    """Exact 2-D circular convolution of equal-geometry integer images.

    Square prime (N, N) operands take the paper's direct transform-
    domain route; on pipeline-capable backends (``method="auto"``
    resolves the fused Pallas kernel for int/float images) the whole
    transform -> per-direction 1-D convolution -> inverse chain runs as
    ONE kernel launch with the projections resident in VMEM/registers.
    Either operand may be a batched (B, N, N) stack.  Any other (H, W)
    geometry is convolved on its true (H, W) torus by folding the exact
    prime-embedded linear convolution -- bit-exact for integers on
    every route.  ``block_size`` streams the non-native path
    tile-by-tile (overlap-add; see :func:`linear_conv2d_dprt`).
    ``fuse=False`` forces the staged (separate-launches) path; the
    default fuses exactly when the resolved backend declares the
    pipeline capability.  All DPRT stages run through
    :mod:`repro.radon`; unset knobs resolve against the ambient
    :func:`repro.radon.config` scope, and ``jax.grad`` is exact through
    both routes.
    """
    fh, fw = f.shape[-2:]
    gh, gw = g.shape[-2:]
    if (fh, fw) != (gh, gw):
        raise ValueError(
            f"circular convolution needs equal operand geometry, got "
            f"{f.shape} vs {g.shape}")
    knobs = _resolve_knobs(method, strip_rows, m_block)
    return _circ_conv2d_jit(f, g, knobs, block_size, fuse)


def circ_conv2d_direct(f: jnp.ndarray, g: jnp.ndarray) -> jnp.ndarray:
    """O(N^4) direct oracle for circular convolution (exact)."""
    n = f.shape[0]
    acc = accum_dtype_for(jnp.result_type(f.dtype, g.dtype))
    i = jnp.arange(n)
    # out[x, y] = sum_{u,v} f[u, v] g[<x-u>, <y-v>]
    gx = g.astype(acc)[(i[:, None] - i[None, :]) % n]          # (x, u, N)
    gxy = gx[:, :, (i[:, None] - i[None, :]) % n]              # (x, u, y, v)
    return jnp.einsum("uv,xuyv->xy", f.astype(acc), gxy)


def circ_conv2d_fft(f: jnp.ndarray, g: jnp.ndarray) -> jnp.ndarray:
    """Floating-point FFT path (the approach the paper's hardware avoids)."""
    out = jnp.fft.ifft2(jnp.fft.fft2(f) * jnp.fft.fft2(g)).real
    if jnp.issubdtype(f.dtype, jnp.integer):
        return jnp.round(out)
    return out


def _linear_conv_blocked(f: jnp.ndarray, g: jnp.ndarray, block: int,
                         knobs: tuple, fuse: Optional[bool]) -> jnp.ndarray:
    """Overlap-add linear convolution on prime-sized tiles.

    ``f``: (…, A_h, A_w) image(s); ``g``: one small (C_h, C_w) kernel.
    Each tile's circular convolution at q = next_prime(block + k - 1)
    IS its full linear convolution (no wraparound: q >= tile + k - 1),
    and the per-tile results overlap-add exactly to the full linear
    convolution -- the companion paper's scalable scheme.  On pipeline-
    capable backends the whole tile stack rides the batched fused
    pipeline: the kernel's projections are computed once (one small
    forward launch) and every tile's transform -> 1-D conv -> inverse
    runs as one batched kernel launch.
    """
    if g.ndim != 2:
        raise ValueError(
            f"blocked convolution needs a single 2-D kernel, got {g.shape}")
    ah, aw = f.shape[-2:]
    ch, cw = g.shape[-2:]
    block = int(block)
    q = next_prime(block + max(ch, cw) - 1)

    tiles, offsets = G.image_to_tiles(f, block)   # (…, T, block, block)
    tq = G.pad2d(tiles, q - block, q - block)
    gq = G.pad2d(g, q - ch, q - cw)
    rg = _operator(gq.shape, gq.dtype, knobs)(gq)

    t = tq.shape[-3]
    stack = tq.reshape(-1, q, q)                  # (B*T or T, q, q)
    stack_op = _operator(stack.shape, stack.dtype, knobs)
    if _use_pipeline(stack_op.plan, fuse):
        from repro.radon import pipeline_apply    # lazy: radon -> core
        outs = pipeline_apply(stack_op.plan, stack, "conv", rg)
    else:
        rt = stack_op(stack)                      # one fused forward call
        rc = circ_conv1d_exact(rt, rg)            # broadcast over the stack
        inv = _operator((rc.shape[0], q, q), rc.dtype, knobs).inverse
        outs = inv(rc)                            # (B*T or T, q, q)

    oh, ow = block + ch - 1, block + cw - 1       # useful tile output
    tile_out = outs[..., :oh, :ow]
    th, tw = -(-ah // block), -(-aw // block)
    canvas = (th * block + ch - 1, tw * block + cw - 1)

    def assemble(tiles_one):
        return G.overlap_add(tiles_one, offsets, canvas)

    if f.ndim == 3:
        lin = jax.lax.map(assemble,
                          tile_out.reshape(f.shape[0], t, oh, ow))
    else:
        lin = assemble(tile_out)
    return lin[..., : ah + ch - 1, : aw + cw - 1]


@functools.partial(jax.jit, static_argnames=("knobs", "block_size", "fuse"))
def _linear_conv2d_jit(f: jnp.ndarray, g: jnp.ndarray, knobs: tuple,
                       block_size: Optional[int],
                       fuse: Optional[bool]) -> jnp.ndarray:
    ah, aw = f.shape[-2:]
    ch, cw = g.shape[-2:]
    out_h, out_w = ah + ch - 1, aw + cw - 1
    if block_size is not None:
        return _linear_conv_blocked(f, g, block_size, knobs, fuse)
    p = next_prime(max(out_h, out_w))
    fp = G.pad2d(f, p - ah, p - aw)
    gp = G.pad2d(g, p - ch, p - cw)
    res = _circ_prime(fp, gp, knobs, fuse)
    return res[..., :out_h, :out_w]


def linear_conv2d_dprt(f: jnp.ndarray, g: jnp.ndarray,
                       method: Optional[str] = None,
                       strip_rows: Optional[int] = None,
                       m_block: Optional[int] = None,
                       block_size: Optional[int] = None,
                       fuse: Optional[bool] = None) -> jnp.ndarray:
    """Exact full linear convolution of arbitrary rectangular operands.

    Whole-image route: zero-pad both operands to the next prime that
    covers the full (out_h, out_w) support -- rows and columns padded
    independently, so rectangular inputs are handled exactly.  With
    ``block_size``, the overlap-add route tiles ``f`` into
    ``block_size``-square tiles and convolves each against the (small)
    kernel ``g`` at the tile prime instead of one giant image prime --
    the companion paper's resource-fitting scheme (bounded working set,
    batched tile stack riding the fused pipeline).  ``f`` may be a
    (B, H, W) stack in either route.  On pipeline-capable backends each
    route's transform -> 1-D conv -> inverse chain is a single kernel
    launch (``fuse=False`` forces the staged path).  Unset knobs resolve
    against the ambient :func:`repro.radon.config` scope.
    """
    knobs = _resolve_knobs(method, strip_rows, m_block)
    return _linear_conv2d_jit(f, g, knobs, block_size, fuse)


def linear_conv2d_direct(f: jnp.ndarray, g: jnp.ndarray) -> jnp.ndarray:
    """numpy oracle for full linear convolution (exact, int64)."""
    fa = np.asarray(f, dtype=np.int64)
    ga = np.asarray(g, dtype=np.int64)
    ah, aw = fa.shape
    ch, cw = ga.shape
    out = np.zeros((ah + ch - 1, aw + cw - 1), dtype=np.int64)
    for u in range(ah):
        for v in range(aw):
            out[u:u + ch, v:v + cw] += fa[u, v] * ga
    return out


def prime_vs_pow2_padding(size: int, kernel: int) -> dict:
    """Paper Sec. I: transform-size overhead of prime vs power-of-two padding."""
    need = size + kernel - 1
    p = next_prime(need)
    pow2 = 1 << max(0, (need - 1).bit_length())
    return {
        "required": need,
        "prime_pad": p,
        "pow2_pad": pow2,
        "prime_overhead": p / need,
        "pow2_overhead": pow2 / need,
    }

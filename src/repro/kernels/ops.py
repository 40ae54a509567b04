"""Public jit'd wrappers around the fused Pallas DPRT kernels.

This is the layer ``repro.core.dprt`` dispatches to for
``method="pallas"``: each wrapper accepts a single (N, N) image or a
batched (B, N, N) stack (transformed in ONE ``pallas_call`` via the
kernel's leading batch grid dimension), resolves block shapes through
the :mod:`.tuning` table when not given explicitly, and uses
:func:`repro.core.dprt.accum_dtype_for` for overflow-safe accumulators
(int64 inputs stay int64, never silently truncated to int32).

The forward/inverse epilogues (R(N, d) row-sum; -S + R(N, i) correction
plus exact divide-by-N) are fused *inside* the kernel -- see
:mod:`.sfdprt` -- so there are no post-kernel passes here, only slicing.

``interpret`` defaults to auto: Pallas interpret mode off-TPU, compiled
Mosaic on a TPU.  It chooses only how the kernels run, not what they
run: interpret mode executes the chip's own datapath (the strided-rotate
Horner step, 128-padded lanes, the in-kernel DMA strip stream, one image
a grid step), so the CPU tests check the lowering the chip compiles.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import spans
from repro.core.dprt import accum_dtype_for, is_prime
from .sfdprt import (PIPELINE_OPS, dprt_pallas_raw, idprt_pallas_raw,
                     pipeline_pallas_raw, skew_sum_pallas_raw)
from .tuning import resolve_blocks, resolve_pipeline_blocks

__all__ = ["dprt_pallas", "idprt_pallas", "skew_sum_pallas",
           "skew_sum_pallas_strip", "dprt_pallas_strip",
           "projection_pipeline_pallas", "pipeline_tail_pallas"]


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def _resolve_blocks(n: int, strip_rows: Optional[int],
                    m_block: Optional[int], dtype,
                    stream_rows: Optional[int] = None) -> tuple[int, int]:
    # delegate to the shared resolver so the plan layer ("auto") and
    # direct pallas calls agree on block shapes.  Deliberately does NOT
    # consult the ambient radon.config scope: these wrappers may run
    # inside a caller's jit trace, where a scope read would be baked
    # into the cached executable and replayed after the scope exits.
    # Ambient knobs apply at (eager) plan/operator construction instead.
    return resolve_blocks(n,
                          jnp.dtype(accum_dtype_for(dtype, n,
                                                    warn=False)).itemsize,
                          strip_rows, m_block, stream_rows=stream_rows)


def _stream_int(stream_rows: Optional[int]) -> Optional[int]:
    return None if stream_rows is None else int(stream_rows)


def skew_sum_pallas(g: jnp.ndarray, sign: int = 1,
                    strip_rows: Optional[int] = None,
                    m_block: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    stream_rows: Optional[int] = None) -> jnp.ndarray:
    """Bare skew-sum: (N, N), or a (B, N, N) stack in ONE pallas_call.

    The batched form serves the plan layer's batched-native adjoint
    datapath (exact VJPs through ``method="pallas"``) as well as the
    core-mode tests.  ``stream_rows`` switches to the streamed-strip
    kernel (double-buffered DMA; giant N).
    """
    h, mb = _resolve_blocks(g.shape[-1], strip_rows, m_block, g.dtype,
                            stream_rows)
    return skew_sum_pallas_raw(g, sign=sign, strip_rows=h, m_block=mb,
                               interpret=_auto_interpret(interpret),
                               stream_rows=_stream_int(stream_rows))


def skew_sum_pallas_strip(g: jnp.ndarray, sign: int = 1, *,
                          row_offset=0,
                          strip_rows: Optional[int] = None,
                          m_block: Optional[int] = None,
                          interpret: Optional[bool] = None,
                          stream_rows: Optional[int] = None) -> jnp.ndarray:
    """Shard-local partial skew-sum: a (rows, N) or (B, rows, N) row
    strip whose first *global* image row is ``row_offset`` (static int or
    traced scalar, e.g. ``axis_index * rows_per_dev`` inside shard_map).

    Returns the (…, N, N) partial aligned to global rows -- the fused
    kernel's alignment roll-select ladder absorbs the offset at zero
    extra datapath cost (eq. 7 with rH -> row_offset + rH), replacing
    the distributed path's per-ray Horner roll loop.  Summing these
    partials over devices (``psum``/``psum_scatter``) yields the full
    skew-sum; block shapes default to the :mod:`.tuning` table for N.
    """
    n = g.shape[-1]
    h, mb = _resolve_blocks(n, strip_rows, m_block, g.dtype, stream_rows)
    return skew_sum_pallas_raw(g, sign=sign, strip_rows=h, m_block=mb,
                               interpret=_auto_interpret(interpret),
                               row_offset=row_offset,
                               stream_rows=_stream_int(stream_rows))


def dprt_pallas_strip(g: jnp.ndarray, *, row_offset=0,
                      strip_rows: Optional[int] = None,
                      m_block: Optional[int] = None,
                      interpret: Optional[bool] = None,
                      stream_rows: Optional[int] = None) -> jnp.ndarray:
    """Shard-local partial *forward* DPRT: a (rows, N) or (B, rows, N)
    row strip starting at global image row ``row_offset`` -> the
    (…, N+1, N) partial transform, R(N, d) row-sum epilogue fused
    in-kernel at the strip's global lane positions.  Summing the
    partials over devices (one ``psum``) yields the exact full forward
    -- the whole distributed datapath is one fused kernel call plus one
    collective per device."""
    n = g.shape[-1]
    single = g.ndim == 2
    gb = g[None] if single else g
    h, mb = _resolve_blocks(n, strip_rows, m_block, g.dtype, stream_rows)
    out = dprt_pallas_raw(gb, strip_rows=h, m_block=mb,
                          interpret=_auto_interpret(interpret),
                          row_offset=row_offset,
                          stream_rows=_stream_int(stream_rows))
    return out[0] if single else out


def dprt_pallas(f: jnp.ndarray, strip_rows: Optional[int] = None,
                m_block: Optional[int] = None,
                interpret: Optional[bool] = None,
                stream_rows: Optional[int] = None) -> jnp.ndarray:
    """Forward DPRT via the fused SFDPRT kernel.

    (N, N) -> (N+1, N), or batched (B, N, N) -> (B, N+1, N) in a single
    pallas_call.  Block shapes default to the :mod:`.tuning` table.
    ``stream_rows`` streams H-row strips through ONE launch
    (double-buffered HBM->VMEM DMA) for images too large to sit whole in
    VMEM.
    """
    single = f.ndim == 2
    fb = f[None] if single else f
    if fb.ndim != 3 or fb.shape[-1] != fb.shape[-2]:
        raise ValueError(f"DPRT needs (B, N, N) or (N, N), got {f.shape}")
    n = fb.shape[-1]
    if not is_prime(n):
        raise ValueError(f"DPRT needs prime N, got {n}")
    h, mb = _resolve_blocks(n, strip_rows, m_block, fb.dtype, stream_rows)
    out = dprt_pallas_raw(fb, strip_rows=h, m_block=mb,
                          interpret=_auto_interpret(interpret),
                          stream_rows=_stream_int(stream_rows))
    return out[0] if single else out


def idprt_pallas(r: jnp.ndarray, strip_rows: Optional[int] = None,
                 m_block: Optional[int] = None,
                 interpret: Optional[bool] = None,
                 stream_rows: Optional[int] = None) -> jnp.ndarray:
    """Inverse DPRT via the fused kernel (CRS core + in-kernel epilogue).

    (N+1, N) -> (N, N), or batched (B, N+1, N) -> (B, N, N) in a single
    pallas_call; exact for integer inputs (accumulator from
    ``accum_dtype_for``, so int64 survives).
    """
    single = r.ndim == 2
    rb = r[None] if single else r
    n = rb.shape[-1]
    if rb.ndim != 3 or rb.shape[-2] != n + 1 or not is_prime(n):
        raise ValueError(
            f"iDPRT input must be (B, N+1, N) or (N+1, N) with N prime: "
            f"{r.shape}")
    h, mb = _resolve_blocks(n, strip_rows, m_block, rb.dtype, stream_rows)
    out = idprt_pallas_raw(rb, strip_rows=h, m_block=mb,
                           interpret=_auto_interpret(interpret),
                           stream_rows=_stream_int(stream_rows))
    return out[0] if single else out


def projection_pipeline_pallas(f, op: str = "conv", operand=None,
                               operand_form: Optional[str] = None,
                               m_block: Optional[int] = None,
                               group: Optional[int] = None,
                               interpret: Optional[bool] = None):
    """Fused projection-domain pipeline: inverse(op(forward(f))) in ONE
    ``pallas_call`` -- the projections never round-trip through HBM.

    ``f``: (N, N) or a (B, N, N) stack, N prime.  ``op``:

    * ``"conv"`` -- per-direction 1-D circular convolution against the
      second operand (the paper's Sec. VI convolution property), i.e.
      exact 2-D circular convolution.  ``operand`` is the other image
      ((N, N) shared or (B, N, N) matched; its forward runs in-kernel)
      or its precomputed projections ((N+1, N) / (B, N+1, N)) with
      ``operand_form="proj"``.  An image shared by a stack of more than
      one image is forwarded once here, by one small forward launch,
      and the kernel takes its projections: every grid step would
      otherwise forward the same operand again.
    * ``"mul"``  -- pointwise projection-domain multiply by an
      (N+1, N) / (B, N+1, N) weight array (``inv @ pointwise @ fwd``
      operator fusion).
    * ``"none"`` -- inverse(forward(f)): the fused round trip.

    Returns the (…, N, N) result in the accumulator dtype; bit-exact for
    integer inputs (both stages and the epilogue run the same exact
    integer datapath as the staged kernels).
    """
    if op not in PIPELINE_OPS:
        raise ValueError(f"pipeline op must be one of {PIPELINE_OPS}: {op!r}")
    single = f.ndim == 2
    fb = f[None] if single else f
    if fb.ndim != 3 or fb.shape[-1] != fb.shape[-2]:
        raise ValueError(f"pipeline needs (B, N, N) or (N, N), got {f.shape}")
    n = fb.shape[-1]
    if not is_prime(n):
        raise ValueError(f"pipeline needs prime N, got {n}")
    acc = accum_dtype_for(fb.dtype, n)
    wb = None
    if op != "none":
        if operand is None:
            raise ValueError(f"pipeline op {op!r} needs an operand")
        wb = operand[None] if operand.ndim == 2 else operand
        if operand_form is None:
            operand_form = "image" if (op == "conv"
                                       and wb.shape[-2] == n) else "proj"
        want = (n, n) if (op == "conv" and operand_form == "image") \
            else (n + 1, n)
        if wb.shape[-2:] != want:
            raise ValueError(
                f"pipeline operand for op={op!r}/{operand_form} must be "
                f"(…, {want[0]}, {want[1]}), got {operand.shape}")
        if wb.shape[0] not in (1, fb.shape[0]):
            raise ValueError(
                f"batched pipeline operand must match the stack batch "
                f"({fb.shape[0]}), got {operand.shape}")
        wb = wb.astype(acc)
    interp = _auto_interpret(interpret)
    if op == "conv" and operand_form == "image" and wb.shape[0] == 1 \
            and fb.shape[0] > 1:
        spans.count("sfdprt_pipeline_operand_shared")
        wb = dprt_pallas(wb, interpret=interp).astype(acc)
        operand_form = "proj"
    mb, grp = resolve_pipeline_blocks(n, jnp.dtype(acc).itemsize,
                                      m_block, group)
    out, _aux = pipeline_pallas_raw(fb.astype(acc), wb, op=op,
                                    operand_form=operand_form or "proj",
                                    m_block=mb, group=grp, interpret=interp)
    out = out[:, :n, :n]
    return out[0] if single else out


def pipeline_tail_pallas(rows, op: str = "conv", operand=None, *,
                         row_offset=0, n: Optional[int] = None,
                         m_block: Optional[int] = None,
                         group: Optional[int] = None,
                         interpret: Optional[bool] = None):
    """Shard-local pipeline tail: already-assembled projection rows in,
    per-direction epilogue + inverse skew sum out (correction deferred).

    ``rows``: (dirs_local, N) or (B, dirs_local, N) -- this device's
    shard of direction rows, first global direction ``row_offset``
    (static or traced).  ``operand``: the full (N+1, N) projections /
    weights (replicated; the kernel slices this shard's window).
    Returns ``(z, aux)`` partials -- one cross-device ``psum`` of both
    plus the shared -S + R'(N, i) / N epilogue reconstructs exactly;
    see :func:`repro.core.distributed.projection_pipeline_sharded`.
    """
    single = rows.ndim == 2
    rb = rows[None] if single else rows
    if n is None:
        n = rb.shape[-1]
    acc = accum_dtype_for(rb.dtype, n)
    interp = _auto_interpret(interpret)
    mb, grp = resolve_pipeline_blocks(n, jnp.dtype(acc).itemsize,
                                      m_block, group)
    mb = min(mb, math.ceil(rb.shape[-2] / 8) * 8)
    wb = None
    if op != "none":
        wb = operand[None] if operand.ndim == 2 else operand
        wb = wb.astype(acc)
    z, aux = pipeline_pallas_raw(rb.astype(acc), wb, op=op,
                                 operand_form="proj", source="proj",
                                 m_block=mb, group=grp,
                                 interpret=interp, row_offset=row_offset,
                                 n_rows=n)
    z = z[:, :n, :n]
    if single:
        return z[0], aux[0]
    return z, aux

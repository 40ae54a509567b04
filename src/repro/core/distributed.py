"""Distributed DPRT: the paper's strip decomposition lifted onto a mesh.

The SFDPRT computes per-strip *partial* DPRTs and accumulates them in
MEM_OUT (eq. 8).  Across a TPU pod the same algebra shards: each device
owns a contiguous block of image rows (a "super-strip"), computes its
partial skew-sum locally with zero inter-device traffic, applies its
alignment roll, and the partial results are combined with one
collective:

* ``psum``          -> every device holds the full (N+1, N) transform
                       (MEM_OUT replicated),
* ``psum_scatter``  -> each device keeps only its slice of directions
                       (MEM_OUT sharded; 1/devices the collective bytes,
                       the beyond-paper option used by the perf pass), or
* ``ring``          -> the same direction-sharded result built from an
                       explicit ``ppermute`` (collective_permute) ring:
                       devices exchange one direction chunk per step and
                       accumulate in place, so per-step wire volume is
                       O(N^2 / devices) and never the full transform.

The ``sharded_pallas`` forward now *defaults* to the direction-sharded
layout (``psum_scatter``), and the inverse consumes that layout in
place: its row super-strips are the forward's direction shards
(``ceil((N+1)/devices)`` rows per device, global rows >= N masked
in-shard), so a forward -> inverse round trip re-shards nothing.

Image *batches* shard over the data axes on top of this (2-D
``data x model`` meshes: batch shards over ``data``, row super-strips
over ``model``).

Two shard-local datapaths are registered in the transform plan registry
(:mod:`repro.core.plan`):

* ``"sharded"``         -- the legacy path: per-device Horner
  shift-and-add scan (:func:`repro.core.dprt.strip_partial`) plus an
  explicit alignment gather.
* ``"sharded_pallas"``  -- each device runs the fused SFDPRT Pallas
  kernel (:func:`repro.kernels.skew_sum_pallas_strip`) over its local
  row strip or batch shard: the strip kernels' datapath with the
  device's first global row folded into the alignment ladder (one
  ``pallas_call`` per shard, batched stacks native).  All four plan
  datapaths (forward / inverse / adjoint / inverse_adjoint)
  ride this skew-sum, so ``jax.grad`` and ``op.T`` stay exact through
  the distributed path.  Declared mesh-aware with higher priority than
  ``"sharded"``, so ``method="auto"`` under a mesh resolves here.
"""
from __future__ import annotations

import functools
import math
from typing import Literal, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .dprt import (accum_dtype_for, align_partial, is_prime, strip_partial)

__all__ = [
    "dprt_sharded",
    "idprt_sharded",
    "dprt_batch_sharded",
    "idprt_batch_sharded",
    "skew_sum_sharded_pallas",
    "dprt_sharded_pallas",
    "idprt_sharded_pallas",
    "projection_pipeline_sharded",
    "batch_partition_spec",
]

Reduce = Literal["psum", "psum_scatter", "ring"]

#: axes a batch may shard over (leading mesh axes of the standard
#: production meshes); the row super-strips take the remaining axis.
BATCH_AXES = ("pod", "data")


def _shard_map(fn, mesh, in_specs, out_specs):
    """shard_map without the varying-manual-axes checker: ``pallas_call``
    has no rule for it, and the psum'd outputs below are replicated by
    construction."""
    return shard_map(fn, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


def _row_axis(mesh: Mesh) -> str:
    """Row-sharding axis: 'model' if present, else the mesh's last axis
    (leading axes are batch/data axes by convention)."""
    if "model" in mesh.shape:
        return "model"
    return tuple(mesh.shape)[-1]


def _batch_axes(mesh: Mesh, row_axis: str) -> tuple:
    """Data axes a batched stack shards over (never the row axis)."""
    return tuple(a for a in BATCH_AXES
                 if a in mesh.shape and a != row_axis)


def _bspec(baxes: tuple):
    """PartitionSpec entry for a batch dim sharded over ``baxes``."""
    return (baxes if len(baxes) > 1 else baxes[0]) if baxes else None


def batch_partition_spec(mesh: Mesh) -> P:
    """The mesh-natural PartitionSpec of a (B, rows, N) stack: batch over
    the mesh's data axes, rows/lanes unsharded.  The single convention
    point shared by the shard_map in/out specs here and the operator
    layer's AOT input shardings (``RadonOperator.input_sharding``)."""
    return P(_bspec(_batch_axes(mesh, _row_axis(mesh))), None, None)


# ---------------------------------------------------------------------------
# legacy "sharded" backend: per-device Horner scan + alignment gather
# ---------------------------------------------------------------------------
def _skew_sum_local(g_local: jnp.ndarray, n: int, sign: int, axis: str,
                    rows_per_dev: int) -> jnp.ndarray:
    """Partial skew-sum of this device's row block, aligned to global rows."""
    r = jax.lax.axis_index(axis)
    u = strip_partial(g_local, n, sign=sign,
                      acc_dtype=accum_dtype_for(g_local.dtype, n))
    return align_partial(u, r * rows_per_dev, sign=sign)


@functools.partial(jax.jit,
                   static_argnames=("mesh", "axis", "reduce", "sign"))
def _skew_sum_sharded(g: jnp.ndarray, mesh: Mesh, axis: str = "model",
                      reduce: Reduce = "psum", sign: int = 1) -> jnp.ndarray:
    n = g.shape[1]
    devs = mesh.shape[axis]
    rows_per_dev = math.ceil(g.shape[0] / devs)
    gp = jnp.pad(g, ((0, devs * rows_per_dev - g.shape[0]), (0, 0)))

    n_out_pad = math.ceil(n / devs) * devs

    def local(gl):
        part = _skew_sum_local(gl, n, sign, axis, rows_per_dev)
        if reduce == "psum":
            return jax.lax.psum(part, axis)
        part = jnp.pad(part, ((0, n_out_pad - n), (0, 0)))
        return jax.lax.psum_scatter(part, axis, scatter_dimension=0,
                                    tiled=True)

    out_spec = P(None, None) if reduce == "psum" else P(axis, None)
    fn = shard_map(local, mesh=mesh, in_specs=P(axis, None),
                   out_specs=out_spec)
    out = fn(gp)
    return out[:n]


def dprt_sharded(f: jnp.ndarray, mesh: Mesh, axis: str = "model",
                 reduce: Reduce = "psum") -> jnp.ndarray:
    """Forward DPRT of one (N, N) image with rows sharded over ``axis``.

    Returns the (N+1, N) transform; direction rows are sharded over
    ``axis`` when ``reduce='psum_scatter'``, else replicated.
    """
    n = f.shape[0]
    if not is_prime(n):
        raise ValueError(f"DPRT needs prime N, got {n}")
    core = _skew_sum_sharded(f, mesh, axis, reduce, sign=1)
    last = f.astype(accum_dtype_for(f.dtype, n)).sum(axis=1)
    return jnp.concatenate([core, last[None, :]], axis=0)


def idprt_sharded(r: jnp.ndarray, mesh: Mesh, axis: str = "model",
                  reduce: Reduce = "psum") -> jnp.ndarray:
    """Inverse DPRT with the projection rows sharded over ``axis``."""
    n = r.shape[1]
    if r.shape[0] != n + 1 or not is_prime(n):
        raise ValueError(f"iDPRT input must be (N+1, N), N prime: {r.shape}")
    acc = accum_dtype_for(r.dtype, n)
    z = _skew_sum_sharded(r[:n], mesh, axis, reduce, sign=-1)
    s = r[0].astype(acc).sum()
    num = z - s + r[n].astype(acc)[:, None]
    if jnp.issubdtype(acc, jnp.integer):
        return num // n
    return num / n


def _batch_shard(xb: jnp.ndarray, mesh: Mesh, batch_axes) -> tuple:
    """Constrain a stack's leading axis onto the mesh's data axes."""
    axes = tuple(a for a in batch_axes if a in mesh.shape)
    if not axes:
        return xb, None
    spec = P(axes if len(axes) > 1 else axes[0], None, None)
    return jax.lax.with_sharding_constraint(
        xb, NamedSharding(mesh, spec)), spec


def dprt_batch_sharded(fb: jnp.ndarray, mesh: Mesh,
                       batch_axes=BATCH_AXES,
                       method: str = "horner") -> jnp.ndarray:
    """DPRT of a batch of images, batch sharded over the data axes.

    This is the FPGA-coprocessor service pattern of Sec. V-B scaled out:
    every device transforms its own images; no collectives at all.
    """
    from .plan import get_plan  # local import to avoid cycle

    fb, spec = _batch_shard(fb, mesh, batch_axes)
    out = get_plan(fb.shape, fb.dtype, method).forward(fb)
    if spec is None:
        # mesh has no data axis to shard the batch over (e.g. a pure
        # "model" mesh): every device computes the full batch locally
        return out
    return jax.lax.with_sharding_constraint(out, NamedSharding(mesh, spec))


def idprt_batch_sharded(rb: jnp.ndarray, mesh: Mesh,
                        batch_axes=BATCH_AXES,
                        method: str = "horner") -> jnp.ndarray:
    """Inverse DPRT of a (B, N+1, N) stack, batch sharded over the data
    axes -- the missing mirror of :func:`dprt_batch_sharded`: every
    device reconstructs its own images, no collectives at all."""
    from .plan import get_plan  # local import to avoid cycle

    n = rb.shape[-1]
    if rb.ndim != 3 or rb.shape[-2] != n + 1 or not is_prime(n):
        raise ValueError(
            f"idprt_batch_sharded needs (B, N+1, N), N prime: {rb.shape}")
    rb, spec = _batch_shard(rb, mesh, batch_axes)
    out = get_plan((rb.shape[0], n, n), rb.dtype, method).inverse(rb)
    if spec is None:
        return out
    return jax.lax.with_sharding_constraint(out, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# "sharded_pallas" backend: per-shard fused SFDPRT kernel + one collective
# ---------------------------------------------------------------------------
def _ring_reduce_scatter(part: jnp.ndarray, axis: str,
                         devs: int) -> jnp.ndarray:
    """Reduce-scatter ``part`` over its row dim (-2) with an explicit
    ``ppermute`` ring instead of ``psum_scatter``.

    Device r ends holding the fully reduced chunk r (identical layout to
    ``psum_scatter(..., tiled=True)``).  Each of the devs-1 steps moves
    ONE chunk (rows/devs of the partial) to the right neighbour and
    accumulates the local contribution for the chunk's eventual owner --
    per-step wire volume is O(N^2 / devs), never the whole transform,
    which is the layout the giant-N streamed kernels need to keep
    per-host memory flat.  Rows of ``part`` must be a devs multiple.
    """
    if devs == 1:
        return part
    rows = part.shape[-2] // devs
    r = jax.lax.axis_index(axis)

    def chunk(i):
        return jax.lax.dynamic_slice_in_dim(part, i * rows, rows, axis=-2)

    perm = [(d, (d + 1) % devs) for d in range(devs)]
    buf = chunk((r - 1) % devs)
    for t in range(devs - 1):
        buf = jax.lax.ppermute(buf, axis, perm)
        buf = buf + chunk((r - t - 2) % devs)
    return buf


def _reduce_partial(part: jnp.ndarray, axis: str, devs: int,
                    out_rows: int, out_pad: int,
                    reduce: str) -> jnp.ndarray:
    """Apply the configured collective to a per-device partial."""
    if reduce == "psum":
        return jax.lax.psum(part, axis)
    ppad = [(0, 0)] * part.ndim
    ppad[-2] = (0, out_pad - out_rows)
    part = jnp.pad(part, ppad)
    if reduce == "ring":
        return _ring_reduce_scatter(part, axis, devs)
    return jax.lax.psum_scatter(part, axis,
                                scatter_dimension=part.ndim - 2,
                                tiled=True)



def _shard_layout(g: jnp.ndarray, mesh: Mesh, axis: Optional[str],
                  batch_axes: Optional[tuple]) -> tuple:
    """The single convention point for laying a (…, rows, N) input onto
    a mesh: resolves the row axis and batch axes, pads rows to a
    devs-multiple and the batch to a data-devices multiple, and returns
    ``(gp, axis, baxes, devs, rows_per_dev, b)`` -- shared by every
    per-shard kernel datapath so the padding rules cannot diverge."""
    batched = g.ndim == 3
    if axis is None:
        axis = _row_axis(mesh)
    baxes = () if not batched else (
        _batch_axes(mesh, axis) if batch_axes is None
        else tuple(a for a in batch_axes if a in mesh.shape and a != axis))
    devs = mesh.shape[axis]
    rows_per_dev = math.ceil(g.shape[-2] / devs)
    pad = [(0, 0)] * g.ndim
    pad[-2] = (0, devs * rows_per_dev - g.shape[-2])
    b = g.shape[0] if batched else None
    if baxes:
        bdevs = math.prod(mesh.shape[a] for a in baxes)
        pad[0] = (0, math.ceil(b / bdevs) * bdevs - b)
    return jnp.pad(g, pad), axis, baxes, devs, rows_per_dev, b
@functools.partial(jax.jit,
                   static_argnames=("mesh", "mode", "sign", "axis",
                                    "batch_axes", "reduce", "strip_rows",
                                    "m_block", "stream_rows",
                                    "mask_rows_from"))
def _sharded_pallas_partials(g: jnp.ndarray, mesh: Mesh, mode: str = "core",
                             sign: int = 1,
                             axis: Optional[str] = None,
                             batch_axes: Optional[tuple] = None,
                             reduce: Reduce = "psum",
                             strip_rows: Optional[int] = None,
                             m_block: Optional[int] = None,
                             stream_rows: Optional[int] = None,
                             mask_rows_from: Optional[int] = None
                             ) -> jnp.ndarray:
    """Shared mesh datapath: per-device fused kernel + one collective.

    Rows of ``g`` (…, rows, N) shard over the mesh's row axis, a batch
    dim over its data axes.  Inside ``shard_map`` every device runs ONE
    fused Pallas kernel call over its local (B_local, rows_per_dev, N)
    block: the strip kernels' datapath with the device's first global
    row (``axis_index * rows_per_dev``, a traced value) folded into the
    alignment ladder.  ``mode="core"`` computes
    the bare skew-sum partial; ``mode="forward"`` additionally fuses
    the R(N, d) row-sum epilogue in-kernel at global lane positions, so
    the full forward transform is exactly one kernel + one collective.
    One ``psum`` (replicated MEM_OUT), ``psum_scatter`` (output rows
    stay sharded over the row axis) or ``ring`` (same sharded layout via
    an explicit ppermute ring) assembles eq. 8.

    ``stream_rows`` engages the in-launch streamed strip kernel on each
    shard (still one pallas_call per device; the shard's rows stream
    HBM -> VMEM inside it).  ``mask_rows_from`` zeroes global input rows
    >= the bound in-shard BEFORE the kernel -- how the inverse consumes
    a direction-sharded (dirs-padded) forward layout in place without a
    global slice-and-reshard.
    """
    from repro.kernels.ops import (dprt_pallas_strip,  # no import cycle
                                   skew_sum_pallas_strip)

    n = g.shape[-1]
    out_rows = n + 1 if mode == "forward" else n
    batched = g.ndim == 3
    gp, axis, baxes, devs, rows_per_dev, b = _shard_layout(
        g, mesh, axis, batch_axes)

    out_pad = math.ceil(out_rows / devs) * devs

    def local(gl):
        r = jax.lax.axis_index(axis)
        off = r * rows_per_dev
        if mask_rows_from is not None:
            keep = (off + jnp.arange(gl.shape[-2]) < mask_rows_from)
            gl = jnp.where(keep[:, None], gl, jnp.zeros((), gl.dtype))
        if mode == "forward":
            part = dprt_pallas_strip(gl, row_offset=off,
                                     strip_rows=strip_rows, m_block=m_block,
                                     stream_rows=stream_rows)
        else:
            part = skew_sum_pallas_strip(gl, sign, row_offset=off,
                                         strip_rows=strip_rows,
                                         m_block=m_block,
                                         stream_rows=stream_rows)
        return _reduce_partial(part, axis, devs, out_rows, out_pad, reduce)

    bspec = (_bspec(baxes),) if batched else ()
    row_spec = None if reduce == "psum" else axis
    fn = _shard_map(local, mesh,
                    in_specs=P(*bspec, axis, None),
                    out_specs=P(*bspec, row_spec, None))
    out = fn(gp)[..., :out_rows, :]
    return out[:b] if batched and baxes else out


def skew_sum_sharded_pallas(g: jnp.ndarray, mesh: Mesh, sign: int = 1,
                            axis: Optional[str] = None,
                            batch_axes: Optional[tuple] = None,
                            reduce: Reduce = "psum",
                            strip_rows: Optional[int] = None,
                            m_block: Optional[int] = None,
                            stream_rows: Optional[int] = None) -> jnp.ndarray:
    """skew_sum of (rows, N) -- or a (B, rows, N) stack -- with rows
    sharded over the mesh's row axis and the batch over its data axes;
    one fused Pallas kernel call per device, one collective."""
    return _sharded_pallas_partials(g, mesh, mode="core", sign=sign,
                                    axis=axis, batch_axes=batch_axes,
                                    reduce=reduce, strip_rows=strip_rows,
                                    m_block=m_block, stream_rows=stream_rows)


def dprt_sharded_pallas(f: jnp.ndarray, mesh: Mesh,
                        reduce: Reduce = "psum_scatter",
                        strip_rows: Optional[int] = None,
                        m_block: Optional[int] = None,
                        stream_rows: Optional[int] = None) -> jnp.ndarray:
    """Forward DPRT of (N, N) -- or a (B, N, N) stack -- via the
    per-shard fused kernel: the R(N, d) row-sum epilogue runs in-kernel
    at global lane positions, so the whole distributed forward is one
    pallas_call per device plus one collective.  Default layout is
    direction-sharded (``psum_scatter``): each device keeps only its
    output direction shard, 1/devices the collective bytes of the old
    all-directions ``psum`` assembly (still available as
    ``reduce="psum"``; ``reduce="ring"`` builds the same sharded layout
    from explicit ppermute steps)."""
    n = f.shape[-1]
    if f.shape[-2] != n or not is_prime(n):
        raise ValueError(f"DPRT needs prime (…, N, N), got {f.shape}")
    return _sharded_pallas_partials(f, mesh, mode="forward", reduce=reduce,
                                    strip_rows=strip_rows, m_block=m_block,
                                    stream_rows=stream_rows)


def idprt_sharded_pallas(r: jnp.ndarray, mesh: Mesh,
                         reduce: Reduce = "psum_scatter",
                         strip_rows: Optional[int] = None,
                         m_block: Optional[int] = None,
                         stream_rows: Optional[int] = None) -> jnp.ndarray:
    """Inverse DPRT of (N+1, N) -- or a (B, N+1, N) stack -- via the
    per-shard Pallas path.

    Consumes the forward's direction-sharded layout IN PLACE: the full
    (N+1)-row input (not a [:N] slice) shards over the row axis in the
    same ``ceil((N+1)/devices)``-row chunks ``psum_scatter`` produced,
    and global rows >= N (the R(N, d) row plus dirs padding) are zeroed
    in-shard before the kernel -- algebraically identical to slicing,
    with no cross-device re-shard between a forward and its inverse.
    The -S + R(N, i) and exact divide-by-N epilogue needs the *global*
    sums, so it runs post-collective -- O(N^2) elementwise."""
    n = r.shape[-1]
    if r.shape[-2] != n + 1 or not is_prime(n):
        raise ValueError(
            f"iDPRT input must be (…, N+1, N), N prime: {r.shape}")
    from .plan import _inverse_epilogue  # lazy: no cycle
    z = _sharded_pallas_partials(r, mesh, mode="core", sign=-1,
                                 reduce=reduce, strip_rows=strip_rows,
                                 m_block=m_block, stream_rows=stream_rows,
                                 mask_rows_from=n)
    return _inverse_epilogue(z, r, n)


# ---------------------------------------------------------------------------
# mesh-composed projection-domain pipeline (fused conv / filter)
# ---------------------------------------------------------------------------
@functools.partial(jax.jit,
                   static_argnames=("mesh", "op", "axis", "batch_axes",
                                    "strip_rows", "m_block", "stream_rows"))
def projection_pipeline_sharded(f: jnp.ndarray, mesh: Mesh, op: str = "conv",
                                operand: Optional[jnp.ndarray] = None,
                                axis: Optional[str] = None,
                                batch_axes: Optional[tuple] = None,
                                strip_rows: Optional[int] = None,
                                m_block: Optional[int] = None,
                                stream_rows: Optional[int] = None
                                ) -> jnp.ndarray:
    """The fused projection pipeline on a mesh: per shard, TWO kernel
    launches with a SINGLE collective between forward and inverse.

    Every O(N^3) stage shards: device r forward-transforms its local row
    super-strip (one fused kernel, eq. 7 alignment at its global row
    offset), a ``psum_scatter`` re-shards the summed projections over
    *directions* -- the one collective between forward and inverse --
    and the per-shard tail kernel applies the per-direction epilogue
    (1-D circular convolution / pointwise multiply) and the inverse
    ladder for its direction shard only.  A final ``psum_scatter`` over
    *image rows* (each device keeps its output row shard -- 1/devices
    the closing-collective bytes of the old full ``psum``) plus the tiny
    -S + R'(N, i) / N correction (whose aux sums ARE psum'd -- 2 rows)
    assembles the reconstruction.

    ``operand``: conv operand as a replicated (N, N) image (its full
    projections are computed once via :func:`dprt_sharded_pallas`) or
    projections/weights (…, N+1, N); a batched operand shards over the
    data axes with the image batch.  Exact for integers, like every
    other datapath here.
    """
    from repro.kernels.ops import (dprt_pallas_strip,   # no import cycle
                                   pipeline_tail_pallas)

    n = f.shape[-1]
    if f.shape[-2] != n or not is_prime(n):
        raise ValueError(f"pipeline needs prime (…, N, N), got {f.shape}")
    acc = accum_dtype_for(f.dtype, n)
    batched = f.ndim == 3
    gp, axis, baxes, devs, rows_per_dev, b = _shard_layout(
        f, mesh, axis, batch_axes)
    dirs_pad = math.ceil((n + 1) / devs) * devs
    dirs_loc = dirs_pad // devs

    wp = None
    w_batched = False
    if op != "none":
        if operand is None:
            raise ValueError(f"pipeline op {op!r} needs an operand")
        if op == "conv" and operand.shape[-2:] == (n, n):
            # one sharded forward (kernel + psum) turns the image operand
            # into its replicated projections
            operand = dprt_sharded_pallas(operand, mesh, reduce="psum",
                                          strip_rows=strip_rows,
                                          m_block=m_block,
                                          stream_rows=stream_rows)
        wp = operand.astype(acc)
        w_batched = wp.ndim == 3 and batched and wp.shape[0] == f.shape[0]
        if w_batched and baxes:
            bdevs = math.prod(mesh.shape[a] for a in baxes)
            wpad = [(0, math.ceil(b / bdevs) * bdevs - b), (0, 0), (0, 0)]
            wp = jnp.pad(wp, wpad)
        elif wp.ndim == 3 and not w_batched:
            if wp.shape[0] != 1:    # same contract as the unsharded path
                raise ValueError(
                    f"batched pipeline operand must match the stack batch "
                    f"({f.shape[0] if batched else 'unbatched'}), got "
                    f"{operand.shape}")
            wp = wp[0]

    bspec = (_bspec(baxes),) if batched else ()
    img_pad = math.ceil(n / devs) * devs

    def local(gl, wl):
        r = jax.lax.axis_index(axis)
        part = dprt_pallas_strip(gl, row_offset=r * rows_per_dev,
                                 strip_rows=strip_rows, m_block=m_block,
                                 stream_rows=stream_rows)
        ppad = [(0, 0)] * part.ndim
        ppad[-2] = (0, dirs_pad - (n + 1))
        part = jnp.pad(part, ppad)
        # collective ONE of two: re-shard the summed projections over
        # directions (1/devs the bytes of a full psum)
        rc_loc = jax.lax.psum_scatter(part, axis,
                                      scatter_dimension=part.ndim - 2,
                                      tiled=True)
        z, aux = pipeline_tail_pallas(rc_loc, op, wl,
                                      row_offset=r * dirs_loc, n=n,
                                      m_block=None)
        # collective TWO: scatter the reconstruction over image rows --
        # each device keeps only its output row shard (the aux rows the
        # deferred correction needs really are global sums, but they are
        # 2 rows: psum them)
        zpad = [(0, 0)] * z.ndim
        zpad[-2] = (0, img_pad - n)
        z_loc = jax.lax.psum_scatter(jnp.pad(z, zpad), axis,
                                     scatter_dimension=z.ndim - 2,
                                     tiled=True)
        return z_loc, jax.lax.psum(aux, axis)

    if op == "none":
        def local1(gl):
            return local(gl, None)
        fn = _shard_map(local1, mesh,
                        in_specs=P(*bspec, axis, None),
                        out_specs=(P(*bspec, axis, None),
                                   P(*bspec, None, None)))
        z, aux = fn(gp)
    else:
        wspec = P(_bspec(baxes), None, None) if w_batched else P(None, None)
        fn = _shard_map(local, mesh,
                        in_specs=(P(*bspec, axis, None), wspec),
                        out_specs=(P(*bspec, axis, None),
                                   P(*bspec, None, None)))
        z, aux = fn(gp, wp)

    if batched and baxes:
        z, aux = z[:b], aux[:b]
    # deferred correction: needs the globally summed Z / aux rows
    s = aux[..., 0, :n].sum(axis=-1)[..., None, None]
    cn = aux[..., 1, :n][..., :, None]
    num = z[..., :n, :n] - s + cn
    if jnp.issubdtype(acc, jnp.integer):
        return num // n
    return num / n

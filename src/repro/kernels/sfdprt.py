"""Fused, batched Pallas TPU kernels for the scalable DPRT (SFDPRT core).

Maps the paper's SFDPRT_core / iSFDPRT_core (Fig. 2/8/16) onto a TPU as
one kernel family with three modes:

* ``core``    -- the bare skew-sum (used by :func:`skew_sum_pallas_raw`),
* ``forward`` -- skew-sum plus the fused R(N, d) row-sum epilogue: the
  extra projection is accumulated *while each strip is VMEM-resident*,
  eliminating the separate post-kernel pass over the image in HBM,
* ``inverse`` -- skew-sum with CRS (sign=-1) plus the fused
  ``(Z - S + R(N, i)) / N`` correction and exact divide (the paper's
  pipelined array divider, Sec. IV-B) applied on the final strip, so the
  reconstruction never round-trips through HBM before the epilogue.

A fourth family, the **projection-domain pipeline**
(:func:`pipeline_pallas_raw`, bottom of this module), chains
forward -> per-direction epilogue (1-D circular convolution / pointwise
multiply) -> inverse in ONE launch -- the Sec. I/VI convolution
application with the projections never leaving VMEM/registers.

Dataflow (per grid step):

* a strip of H image rows is the VMEM-resident register array
  (``BlockSpec((1, H, N))``); a leading *batch* grid dimension transforms
  a (B, N, N) stack in a single ``pallas_call`` (the FPGA-coprocessor
  throughput scenario of Sec. V-B),
* a block of M directions lives in the sublane axis of the accumulator,
* each Horner step ``T <- row_i + roll(T, m)`` is the paper's single
  clock cycle: circular-shift registers + adder tree,
* the roll amount m varies across sublanes, and it must wrap at the
  logical N, not at the padded lane width.  The compiled step therefore
  carries the accumulator as a **periodic extension**: on lanes
  ``[n_pad, n_pad + N)`` of a ``2*n_pad`` lane tile, with a copy below
  it.  A direction block's amounts are linear in the sublane
  (``m0 + r``), so the per-direction roll is ONE native strided lane
  rotate (``pltpu.roll(ext, m0, 1, stride=1, stride_axis=0)``) that
  never leaves the periodic range, and after the row add one static
  rotate and one select rebuild the copy -- four whole-tile ops a
  cycle, none of them a gather or an unaligned lane slice,
* the rotate moves lanes one way only (a stride of +1 per sublane), so
  every mode computes the CRS skew sum (sign=-1); a forward skew sum's
  direction m is the CRS direction <N - m>_N, and the wrapper reorders
  the direction rows after the launch.

**Hoisted setup.**  Everything a Horner cycle needs is derived ONCE per
(m-block, strip) and closed over by the ``fori_loop`` body: the rotate's
shift from the m-block's first direction, the upper-lane mask, and the
eq. (7) alignment roll R'(r,m,d) = U_r(<d + m*rH>) -- which is
not linear in the sublane once reduced mod N, runs once per strip, and
stays a ceil(log2 N)-step **binary roll-select ladder**
(:func:`apply_roll_ladder`, masks ``(amt >> b) & 1`` from
:func:`ladder_select_masks`).  On the interpret/CPU ``"permute"``
lowering the permutations are materialized in index space instead and
the alignment is ONE gather.  Nothing is re-derived on a Horner cycle.

**Shard-local partials.**  Every mode accepts ``rows < N`` inputs plus
a (possibly traced) ``row_offset`` scalar operand: the mesh-distributed
backend (:mod:`repro.core.distributed`) runs this kernel per device
over its local row super-strip, with the device's first global row
folded into the alignment roll amount at zero extra datapath cost.

**Lane padding.**  Off the interpret path the lane axis is padded to a
multiple of 128 so Mosaic tiling is aligned; the periodic extension and
the alignment ladder (which slices at the *logical* N,
``[s:n] ++ [:s] ++ [n:]``) keep the wraparound at N exact, and the lane
tail is masked back to zero once per strip.

**Masked final m-block.**  Direction rows beyond N-1 in the last m-block
(the ``% N`` wrapped duplicates the seed kernel silently computed and
discarded) are masked to zero; in ``forward`` mode the first wasted slot
(global row N) is recycled to hold the fused R(N, d) row-sum.

Accumulators use :func:`repro.core.dprt.accum_dtype_for` (int32/int64/
float) rather than a hardcoded int32, so batched large-N integer inputs
cannot silently overflow.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import spans
from repro.core.dprt import accum_dtype_for

# (batch, m-block) grid axes are independent; the innermost strip axis
# accumulates into a resident output block, so it must run in order
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))
_COMPILER_PARAMS_2D = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))

__all__ = [
    "skew_sum_pallas_raw",
    "dprt_pallas_raw",
    "idprt_pallas_raw",
    "isfdprt_core",
    "roll_rows_ladder_spec",
    "ladder_select_masks",
    "apply_roll_ladder",
    "pipeline_pallas_raw",
    "PIPELINE_OPS",
]

LANE = 128  # TPU lane width; Mosaic tiles want the last axis % 128 == 0


def _num_bits(n: int) -> int:
    return max(1, math.ceil(math.log2(n)))


def _ladder_rungs(n: int):
    """Static rotate amounts 2^b < n used by the roll-select ladder."""
    return [1 << b for b in range(_num_bits(n)) if (1 << b) < n]


def roll_rows_ladder_spec(n: int) -> int:
    """Rotate+select pairs per variable roll (and per-block mask setups):
    the ladder issues ceil(log2 N) of each."""
    return _num_bits(n)


def ladder_select_masks(amt: jnp.ndarray, n: int):
    """Hoisted ladder setup: per-bit select masks for a (M, 1) roll amount.

    Computed once per (m-block, strip), before the Horner loop, for the
    strip kernels' alignment roll and the pipeline kernel's rolls
    (<= ceil(log2 N) shift+compare ops total, never per cycle).
    """
    return [((amt >> b) & 1) == 1 for b in range(len(_ladder_rungs(n)))]


def apply_roll_ladder(acc: jnp.ndarray, masks, n: int) -> jnp.ndarray:
    """out[j, d] = acc[j, <d + amt[j]>_n] for d < n, given hoisted masks.

    ``acc`` is (M, n_pad) with n_pad >= n; lanes >= n are a zero tail that
    is carried through unrotated (wraparound happens at the logical N).
    Every rotate is a static lane-slice pair, every select a per-sublane
    mask -- no gathers, no index arithmetic.
    """
    for b, sel in enumerate(masks):
        s = 1 << b
        rolled = jnp.concatenate([acc[:, s:n], acc[:, :s], acc[:, n:]],
                                 axis=1)
        acc = jnp.where(sel, rolled, acc)
    return acc


def _strip_block_partial(read_row, *, h: int, n: int, n_pad: int,
                         m_block: int, m0, m_vec, valid, offset,
                         step_impl: str, acc_dtype):
    """Aligned, masked partial CRS skew-sum of ONE H-row strip for one
    m-block: ``out[r, d] = sum_j row_j(<d - m_r*(offset + j)>_N)`` with
    ``m_r = m0 + r`` (``m_vec`` is the same, masked).

    This is the shared per-strip datapath of the fused (`_sfdprt_kernel`)
    and streamed (`_stream_grid_kernel` / `_stream_dma_kernel`) kernels:
    hoisted roll setup (per strip, not per cycle), H Horner cycles over
    ``read_row(j)`` (j = 0 is the strip's top row), the eq. (7)
    alignment roll for the strip's first global row ``offset`` (static
    or traced), and the wrapped-duplicate row and lane-tail mask.
    ``step_impl`` picks the per-cycle roll:

    * ``"roll"`` (compiled) -- the loop carries the accumulator on lanes
      ``[n_pad, n_pad + N)`` of an ``L = 2*n_pad`` lane tile whose lanes
      ``[n_pad - N, n_pad)`` hold a second copy, so the tile is periodic,
      ``ext[y] = acc[(y - n_pad) mod N]``, over ``(n_pad - N, n_pad + N)``.
      Sublane ``r`` reads ``ext[y - m_r]``, which never leaves that
      range: ONE native strided lane rotate by ``m0 + r`` (``pltpu.roll``
      with ``stride=1`` over the sublanes).  Mosaic rotates each vreg's
      8 sublanes exactly only while none moves past one vreg width: it
      holds because ``m0`` is a multiple of 8 (Mosaic tiles an m-block
      in 8 sublanes, or there is one block and ``m0 = 0``), and it is
      why the rotate never runs backwards (a stride of ``L - 1``).
      After the row add
      (an aligned concat), one static rotate by ``L - N`` and one select
      rebuild the copy for the next cycle.
    * ``"permute"`` (interpret/CPU) -- the step and alignment
      permutations materialized in index space once, one
      ``take_along_axis`` per cycle (a gather is cheap there).

    The alignment roll ``-m*offset mod N`` is not linear in the sublane
    once reduced, and runs once per strip: the compiled path keeps it on
    :func:`apply_roll_ladder`.
    """
    zero = jnp.zeros((), acc_dtype)
    # reduce the offset mod N before the multiply: streamed/sharded
    # offsets can exceed N (row padding), so m_vec * offset alone could
    # overflow int32 near the top-end N; with the reduction
    # m_vec * (offset % N) <= (N-1)^2 < 2^31 for every supported N
    align_amt = jnp.mod(-m_vec * (offset % n), n)
    lane_iota = jax.lax.broadcasted_iota(jnp.int32, (m_block, n_pad), 1)

    if step_impl == "permute":
        in_tail = lane_iota >= n
        perm = jnp.where(in_tail, lane_iota,
                         (lane_iota + (n - m_vec)) % n)
        align_perm = jnp.where(in_tail, lane_iota,
                               (lane_iota + align_amt) % n)

        def body(i, acc):
            # T_i = f(i, .) + roll(T_{i+1}, -m): one "clock cycle"
            acc = jnp.take_along_axis(acc, perm, axis=1)
            return acc + read_row(h - 1 - i)[None, :].astype(acc.dtype)

        acc = jax.lax.fori_loop(0, h, body,
                                jnp.zeros((m_block, n_pad), acc_dtype))
        # alignment roll: R'(r, m, d) = U_r(<d - m*rH>_n)   (eq. 7)
        acc = jnp.take_along_axis(acc, align_perm, axis=1)
    else:
        spans.count("sfdprt_step_roll")
        ext_w = 2 * n_pad
        upper = jax.lax.broadcasted_iota(jnp.int32,
                                         (m_block, ext_w), 1) >= n_pad
        below = jnp.zeros((1, n_pad), acc_dtype)
        align_sel = ladder_select_masks(align_amt, n)

        def body(i, ext):
            # jnp.roll semantics: sublane r's lane y reads ext[y - m0 - r]
            rot = pltpu.roll(ext, m0, 1, stride=1, stride_axis=0)
            row = read_row(h - 1 - i)[None, :].astype(acc_dtype)
            rot = rot + jnp.concatenate([below, row], axis=1)
            return jnp.where(upper, rot, pltpu.roll(rot, ext_w - n, 1))

        ext = jax.lax.fori_loop(0, h, body,
                                jnp.zeros((m_block, ext_w), acc_dtype))
        # lanes >= n of the slice are left over from the rotate: zeroed
        # below
        acc = apply_roll_ladder(ext[:, n_pad:], align_sel, n)
    return jnp.where(jnp.logical_and(valid, lane_iota < n), acc, zero)


def _mirror_directions(out: jnp.ndarray, n: int) -> jnp.ndarray:
    """The kernels compute the CRS skew sum; its direction row p is the
    forward (sign=+1) skew sum's direction <N - p>_N.  Reorder the
    direction rows of a (B, R, lanes) kernel output to the forward's;
    rows N and above (the fused row sum, masked rows) stay."""
    return jnp.concatenate([out[:, :1], out[:, n - 1:0:-1], out[:, n:]],
                           axis=1)


def _resolve_step(step_impl: str | None, interpret: bool) -> str:
    """The strip kernels' per-cycle step: ``"roll"`` compiled,
    ``"permute"`` interpreted, unless the caller names one."""
    if step_impl is None:
        return "permute" if interpret else "roll"
    if step_impl not in ("roll", "permute"):
        raise ValueError(f"step_impl must be 'roll' or 'permute': "
                         f"{step_impl!r}")
    return step_impl


def _sfdprt_kernel(f_ref, *rest, n: int, n_pad: int, h: int, m_block: int,
                   k_steps: int, mode: str, acc_dtype,
                   step_impl: str, with_offset: bool = False):
    """One (batch, m-block, strip) grid step of the fused SFDPRT.

    Grid is (B, MB, K) with K innermost ("arbitrary"): for a fixed
    (batch, m-block) the output block stays resident while strips
    accumulate into it -- the paper's MEM_OUT (eq. 8).

    ``step_impl`` picks how each Horner cycle realizes the roll: the
    native strided rotate on a periodically extended accumulator
    (``"roll"``, compiled) or a hoisted index-space permutation
    (``"permute"``, interpret) -- see :func:`_strip_block_partial`.

    ``with_offset`` threads a (1, 1) scalar operand holding the strip's
    first *global* image row (the mesh-sharded path: each device's local
    row block starts at ``axis_index * rows_per_dev``, a traced value).
    The offset merely shifts the alignment ladder's roll amount
    (eq. 7 with rH -> row_offset + rH) -- zero extra datapath work.
    """
    rest = list(rest)
    off_ref = rest.pop(0) if with_offset else None
    if mode == "inverse":
        corr_ref, out_ref = rest
    else:
        (out_ref,) = rest
    mb = pl.program_id(1)
    k = pl.program_id(2)

    zero = jnp.zeros((), acc_dtype)
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (m_block, 1), 0)
    grow = mb * m_block + row_iota            # global output row
    valid = grow < n                          # mask wrapped-duplicate rows
    m_vec = jnp.where(valid, grow, 0)

    # ---- hoisted ladder setup + H Horner cycles + eq. (7) alignment ------
    # (the shared per-strip datapath; the "permute" lowering hoists the
    # step AND alignment permutations into index space ONCE per m-block)
    offset = k * h                            # strip's first global row rH
    if with_offset:                           # shard-local: + the block's
        offset = offset + off_ref[0, 0]       # first global image row
    acc = _strip_block_partial(
        lambda j: f_ref[0, j, :], h=h, n=n, n_pad=n_pad, m_block=m_block,
        m0=mb * m_block, m_vec=m_vec, valid=valid, offset=offset,
        step_impl=step_impl, acc_dtype=acc_dtype)

    @pl.when(k == 0)
    def _init():
        out_ref[0] = acc

    @pl.when(k > 0)
    def _accum():
        out_ref[0] = out_ref[0] + acc

    if mode == "forward":
        # Fused epilogue: R(N, d) = sum_j f(d, j).  Each strip owns the
        # disjoint lane range [rH, rH+H); its row-sums are placed there and
        # dropped into the recycled slot row N while the strip is in VMEM.
        # Only the (static) m-block that holds global row N pays for it.
        @pl.when(mb == n // m_block)
        def _rowsum():
            rsum = jnp.sum(f_ref[0].astype(acc_dtype), axis=1, keepdims=True)
            lane = jax.lax.broadcasted_iota(jnp.int32, (h, n_pad), 1)
            srow = jax.lax.broadcasted_iota(jnp.int32, (h, n_pad), 0)
            placed = jnp.sum(jnp.where(lane == offset + srow, rsum, zero),
                             axis=0)
            out_ref[0] = out_ref[0] + jnp.where(grow == n, placed[None, :],
                                                zero)

    if mode == "inverse":
        # Fused epilogue on the last strip: f = (Z - S + R(N, i)) / N with
        # corr[i] = R(N, i) - S precomputed per row; exact integer divide
        # (the paper's pipelined array divider, Sec. IV-B).
        @pl.when(k == k_steps - 1)
        def _epilogue():
            total = out_ref[0] + corr_ref[0].astype(acc_dtype)
            if jnp.issubdtype(jnp.dtype(acc_dtype), jnp.integer):
                res = total // n
            else:
                res = total / n
            out_ref[0] = jnp.where(valid, res, zero)


def _pallas_skew_call(g: jnp.ndarray, *, sign: int, mode: str,
                      strip_rows: int, m_block: int, interpret: bool,
                      corr: jnp.ndarray | None = None,
                      lane_pad: bool | None = None,
                      step_impl: str | None = None,
                      row_offset: jnp.ndarray | int | None = None
                      ) -> jnp.ndarray:
    """Shared fused pallas_call: g is (B, rows, N) already in the
    accumulator dtype (rows == N for whole images; rows < N for a
    shard-local row strip); returns (B, R, n_pad) with
    R = ceil(out_rows/m_block)*m_block -- callers slice to the logical
    output.  The kernel computes the CRS (sign=-1) skew sum; a forward
    (``sign=+1``) call reorders its direction rows after the launch
    (:func:`_mirror_directions`), one pass over the output in XLA.

    ``lane_pad`` (default: pad iff compiled) rounds the lane axis up to a
    128-multiple for Mosaic tile alignment; it is overridable so the
    wraparound-at-logical-N path is testable in interpret mode.
    ``step_impl`` (default: "permute" in interpret mode, "roll"
    compiled) picks the per-cycle roll realization -- see
    :func:`_strip_block_partial`.  ``row_offset`` (static or traced scalar)
    is the first *global* image row of ``g``'s row block -- the
    shard-local partial of the mesh path; it feeds the alignment ladder
    only (core mode).
    """
    b, rows, n = g.shape
    acc_dtype = g.dtype
    h = max(1, min(int(strip_rows), rows))
    k_steps = math.ceil(rows / h)
    if lane_pad is None:
        lane_pad = not interpret
    step_impl = _resolve_step(step_impl, interpret)
    n_pad = ((n + LANE - 1) // LANE) * LANE if lane_pad else n
    out_rows = n + 1 if mode == "forward" else n
    r_blocks = math.ceil(out_rows / m_block)

    gp = jnp.pad(g, ((0, 0), (0, k_steps * h - rows), (0, n_pad - n)))
    in_specs = [pl.BlockSpec((1, h, n_pad), lambda bb, i, j: (bb, j, 0))]
    operands = [gp]
    with_offset = row_offset is not None
    if with_offset:
        off = jnp.asarray(row_offset, jnp.int32).reshape(1, 1)
        in_specs.append(pl.BlockSpec((1, 1), lambda bb, i, j: (0, 0)))
        operands.append(off)
    if mode == "inverse":
        corr_p = jnp.pad(corr.astype(acc_dtype),
                         ((0, 0), (0, r_blocks * m_block - n)))[..., None]
        in_specs.append(pl.BlockSpec((1, m_block, 1),
                                     lambda bb, i, j: (bb, i, 0)))
        operands.append(corr_p)

    out = pl.pallas_call(
        functools.partial(_sfdprt_kernel, n=n, n_pad=n_pad, h=h,
                          m_block=m_block, k_steps=k_steps,
                          mode=mode, acc_dtype=acc_dtype,
                          step_impl=step_impl, with_offset=with_offset),
        grid=(b, r_blocks, k_steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, m_block, n_pad),
                               lambda bb, i, j: (bb, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, r_blocks * m_block, n_pad),
                                       acc_dtype),
        compiler_params=None if interpret else _COMPILER_PARAMS,
        interpret=interpret,
        name=f"sfdprt_{mode}",
    )(*operands)
    return _mirror_directions(out, n) if sign > 0 else out


# ===========================================================================
# In-launch strip streaming (the giant-N path).
#
# The fused kernel above holds one (1, H, N) strip in VMEM per grid step
# and revisits the output block across the innermost strip dimension --
# fine while ceil(N/H) output revisits are free (they stay VMEM-resident)
# but it leans on the BlockSpec pipeline for every strip fetch and keeps
# the whole (B, N, N) operand eligible for pipelining.  For images that
# do NOT fit whole-image-in-VMEM (N >= 2048) the streamed variants below
# process the image as ONE ``pallas_call`` with an explicit strip loop
# and a VMEM scratch accumulator:
#
# * ``stream_impl="grid"`` -- the strip loop stays a grid dimension, but
#   partial skew-sums accumulate into a VMEM scratch tile; ``out_ref`` is
#   written exactly once, on the final strip (the interpret/CPU
#   emulation of the DMA path: block-indexed strip fetches, identical
#   numerics and revisit structure),
# * ``stream_impl="dma"`` -- the operand stays in HBM
#   (``memory_space=ANY``); the kernel drives its own strip loop with
#   double-buffered ``pltpu.make_async_copy`` HBM->VMEM copies (2 strip
#   slots + 2 DMA semaphores): strip k+1's copy is launched before strip
#   k is consumed, so the Horner datapath hides the HBM fetch latency
#   (the Mosaic path).  Exactly ONE strip buffer pair is live regardless
#   of ceil(N/H) -- memory is O(H*N), not O(N^2).
#
# Both variants replace the plan layer's scan-of-launches ``block_rows``
# fallback on pallas-capable backends: one launch, one jaxpr, partial
# sums never round-tripping through HBM between strips.
# ===========================================================================


def _stream_grid_kernel(f_ref, *rest, n: int, n_pad: int, h: int,
                        m_block: int, k_steps: int, mode: str,
                        acc_dtype, step_impl: str, with_offset: bool):
    """One (batch, m-block, strip) step of the streamed kernel, strip loop
    on the grid: partial skew-sums accumulate in a VMEM scratch tile and
    ``out_ref`` is written once, on the final strip."""
    rest = list(rest)
    off_ref = rest.pop(0) if with_offset else None
    corr_ref = rest.pop(0) if mode == "inverse" else None
    out_ref, acc_ref = rest
    mb = pl.program_id(1)
    k = pl.program_id(2)

    zero = jnp.zeros((), acc_dtype)
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (m_block, 1), 0)
    grow = mb * m_block + row_iota
    valid = grow < n
    m_vec = jnp.where(valid, grow, 0)
    offset = k * h
    if with_offset:
        offset = offset + off_ref[0, 0]

    acc = _strip_block_partial(
        lambda j: f_ref[0, j, :], h=h, n=n, n_pad=n_pad, m_block=m_block,
        m0=mb * m_block, m_vec=m_vec, valid=valid, offset=offset,
        step_impl=step_impl, acc_dtype=acc_dtype)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = acc

    @pl.when(k > 0)
    def _accum():
        acc_ref[...] = acc_ref[...] + acc

    if mode == "forward":
        # fused R(N, d) epilogue: this strip owns lanes [offset, offset+H)
        @pl.when(mb == n // m_block)
        def _rowsum():
            rsum = jnp.sum(f_ref[0].astype(acc_dtype), axis=1, keepdims=True)
            lane = jax.lax.broadcasted_iota(jnp.int32, (h, n_pad), 1)
            srow = jax.lax.broadcasted_iota(jnp.int32, (h, n_pad), 0)
            placed = jnp.sum(jnp.where(lane == offset + srow, rsum, zero),
                             axis=0)
            acc_ref[...] = acc_ref[...] + jnp.where(
                grow == n, placed[None, :], zero)

    @pl.when(k == k_steps - 1)
    def _flush():
        total = acc_ref[...]
        if mode == "inverse":
            total = total + corr_ref[0].astype(acc_dtype)
            if jnp.issubdtype(jnp.dtype(acc_dtype), jnp.integer):
                res = total // n
            else:
                res = total / n
            out_ref[0] = jnp.where(valid, res, zero)
        else:
            out_ref[0] = total


def _stream_dma_kernel(f_ref, *rest, n: int, n_pad: int, h: int,
                       m_block: int, k_steps: int, mode: str,
                       acc_dtype, step_impl: str, with_offset: bool):
    """One (batch, m-block) step of the streamed kernel, strip loop in
    the kernel: the operand stays in HBM (``memory_space=ANY``) and the
    ``fori_loop`` below double-buffers H-row strips into a 2-slot VMEM
    scratch with ``make_async_copy`` -- strip k+1's DMA is started before
    strip k's partial skew-sum runs, so compute hides the fetch."""
    rest = list(rest)
    off_ref = rest.pop(0) if with_offset else None
    corr_ref = rest.pop(0) if mode == "inverse" else None
    out_ref, buf_ref, sem_ref = rest
    bb = pl.program_id(0)
    mb = pl.program_id(1)

    zero = jnp.zeros((), acc_dtype)
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (m_block, 1), 0)
    grow = mb * m_block + row_iota
    valid = grow < n
    m_vec = jnp.where(valid, grow, 0)
    off0 = off_ref[0, 0] if with_offset else 0

    def copy_in(slot, k):
        return pltpu.make_async_copy(
            f_ref.at[bb, pl.ds(k * h, h), :],
            buf_ref.at[slot],
            sem_ref.at[slot])

    copy_in(0, 0).start()

    def body(k, acc):
        slot = jax.lax.rem(k, 2)

        @pl.when(k + 1 < k_steps)
        def _prefetch():                       # overlap: next strip's DMA
            copy_in(jax.lax.rem(k + 1, 2), k + 1).start()

        copy_in(slot, k).wait()
        offset = k * h + off0
        acc = acc + _strip_block_partial(
            lambda j: buf_ref[slot, j, :], h=h, n=n, n_pad=n_pad,
            m_block=m_block, m0=mb * m_block, m_vec=m_vec, valid=valid,
            offset=offset, step_impl=step_impl, acc_dtype=acc_dtype)
        if mode == "forward":
            # fused R(N, d) epilogue while the strip is VMEM-resident;
            # mb is traced here (loop-carried value, not a ref), so the
            # owning-block condition folds into the placement mask
            rsum = jnp.sum(buf_ref[slot].astype(acc_dtype), axis=1,
                           keepdims=True)
            lane = jax.lax.broadcasted_iota(jnp.int32, (h, n_pad), 1)
            srow = jax.lax.broadcasted_iota(jnp.int32, (h, n_pad), 0)
            placed = jnp.sum(jnp.where(lane == offset + srow, rsum, zero),
                             axis=0)
            owns = jnp.logical_and(mb == n // m_block, grow == n)
            acc = acc + jnp.where(owns, placed[None, :], zero)
        return acc

    acc = jax.lax.fori_loop(0, k_steps, body,
                            jnp.zeros((m_block, n_pad), acc_dtype))

    if mode == "inverse":
        total = acc + corr_ref[0].astype(acc_dtype)
        if jnp.issubdtype(jnp.dtype(acc_dtype), jnp.integer):
            res = total // n
        else:
            res = total / n
        out_ref[0] = jnp.where(valid, res, zero)
    else:
        out_ref[0] = acc


def _pallas_stream_call(g: jnp.ndarray, *, sign: int, mode: str,
                        stream_rows: int, m_block: int, interpret: bool,
                        corr: jnp.ndarray | None = None,
                        lane_pad: bool | None = None,
                        step_impl: str | None = None,
                        stream_impl: str | None = None,
                        row_offset: jnp.ndarray | int | None = None
                        ) -> jnp.ndarray:
    """Streamed fused pallas_call: like :func:`_pallas_skew_call` but the
    strip loop accumulates into a VMEM scratch (``stream_impl="grid"``)
    or is driven in-kernel with double-buffered HBM->VMEM DMA copies
    (``stream_impl="dma"``, default off-interpret).  ``stream_rows`` is
    the streamed strip height H; VMEM footprint is O(m_block*N + H*N)
    per grid step regardless of ceil(N/H)."""
    b, rows, n = g.shape
    acc_dtype = g.dtype
    h = max(1, min(int(stream_rows), rows))
    k_steps = math.ceil(rows / h)
    if lane_pad is None:
        lane_pad = not interpret
    step_impl = _resolve_step(step_impl, interpret)
    if stream_impl is None:
        stream_impl = "grid" if interpret else "dma"
    if stream_impl not in ("grid", "dma"):
        raise ValueError(f"stream_impl must be 'grid' or 'dma': "
                         f"{stream_impl!r}")
    n_pad = ((n + LANE - 1) // LANE) * LANE if lane_pad else n
    out_rows = n + 1 if mode == "forward" else n
    r_blocks = math.ceil(out_rows / m_block)
    grid_rank = 3 if stream_impl == "grid" else 2

    gp = jnp.pad(g, ((0, 0), (0, k_steps * h - rows), (0, n_pad - n)))
    if stream_impl == "grid":
        in_specs = [pl.BlockSpec((1, h, n_pad), lambda bb, i, j: (bb, j, 0))]
    else:
        # the operand never enters the BlockSpec pipeline: it stays in
        # HBM and the kernel DMAs strips on its own schedule
        in_specs = [pl.BlockSpec(memory_space=pl.ANY)]
    operands = [gp]
    with_offset = row_offset is not None
    if with_offset:
        off = jnp.asarray(row_offset, jnp.int32).reshape(1, 1)
        in_specs.append(pl.BlockSpec(
            (1, 1), (lambda bb, i, j: (0, 0)) if grid_rank == 3
            else (lambda bb, i: (0, 0))))
        operands.append(off)
    if mode == "inverse":
        corr_p = jnp.pad(corr.astype(acc_dtype),
                         ((0, 0), (0, r_blocks * m_block - n)))[..., None]
        in_specs.append(pl.BlockSpec(
            (1, m_block, 1), (lambda bb, i, j: (bb, i, 0)) if grid_rank == 3
            else (lambda bb, i: (bb, i, 0))))
        operands.append(corr_p)

    kw = dict(n=n, n_pad=n_pad, h=h, m_block=m_block, k_steps=k_steps,
              mode=mode, acc_dtype=acc_dtype, step_impl=step_impl,
              with_offset=with_offset)
    if stream_impl == "grid":
        kernel = functools.partial(_stream_grid_kernel, **kw)
        grid = (b, r_blocks, k_steps)
        out_spec = pl.BlockSpec((1, m_block, n_pad),
                                lambda bb, i, j: (bb, i, 0))
        scratch = [pltpu.VMEM((m_block, n_pad), acc_dtype)]
        cparams = None if interpret else _COMPILER_PARAMS
    else:
        kernel = functools.partial(_stream_dma_kernel, **kw)
        grid = (b, r_blocks)
        out_spec = pl.BlockSpec((1, m_block, n_pad), lambda bb, i: (bb, i, 0))
        # exactly ONE double-buffer pair, however many strips stream
        scratch = [pltpu.VMEM((2, h, n_pad), acc_dtype),
                   pltpu.SemaphoreType.DMA((2,))]
        cparams = None if interpret else _COMPILER_PARAMS_2D

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((b, r_blocks * m_block, n_pad),
                                       acc_dtype),
        scratch_shapes=scratch,
        compiler_params=cparams,
        interpret=interpret,
        name=f"sfdprt_stream_{mode}",
    )(*operands)
    return _mirror_directions(out, n) if sign > 0 else out


@functools.partial(jax.jit,
                   static_argnames=("sign", "strip_rows", "m_block",
                                    "interpret", "step_impl",
                                    "stream_rows", "stream_impl"))
def skew_sum_pallas_raw(g: jnp.ndarray, sign: int = 1, strip_rows: int = 16,
                        m_block: int = 8, interpret: bool = True,
                        step_impl: str | None = None,
                        row_offset=None, stream_rows: int | None = None,
                        stream_impl: str | None = None) -> jnp.ndarray:
    """Bare skew_sum via the strip kernel (core mode, no fused epilogue).

    g: (rows, N) or a batched (B, rows, N) stack, N prime.  Returns the
    same rank (N direction rows) in the accumulator dtype with
    out[..., m, d] = sum_i g(..., i, <d + sign*m*(row_offset+i)>_N); a
    stack runs in ONE pallas_call via the kernel's leading batch grid
    dimension (this is the datapath the exact-adjoint/VJP rules ride).
    Wrapped-duplicate direction rows in the final m-block are masked
    (never computed as "useful" output) and sliced away.

    ``rows < N`` with a (possibly traced) ``row_offset`` computes the
    *partial* skew-sum of a row strip aligned to global rows -- the
    shard-local entry of the mesh-distributed path (eq. 7 with the
    device's first global row folded into the alignment ladder).
    """
    single = g.ndim == 2
    gb = g[None] if single else g
    n = gb.shape[-1]
    ga = gb.astype(accum_dtype_for(g.dtype, n))
    if stream_rows is not None:
        out = _pallas_stream_call(ga, sign=sign, mode="core",
                                  stream_rows=stream_rows, m_block=m_block,
                                  interpret=interpret, step_impl=step_impl,
                                  stream_impl=stream_impl,
                                  row_offset=row_offset)
    else:
        out = _pallas_skew_call(ga, sign=sign, mode="core",
                                strip_rows=strip_rows, m_block=m_block,
                                interpret=interpret, step_impl=step_impl,
                                row_offset=row_offset)
    out = out[:, :n, :n]
    return out[0] if single else out


@functools.partial(jax.jit,
                   static_argnames=("strip_rows", "m_block", "interpret",
                                    "step_impl", "stream_rows",
                                    "stream_impl"))
def dprt_pallas_raw(f: jnp.ndarray, strip_rows: int = 16, m_block: int = 8,
                    interpret: bool = True,
                    step_impl: str | None = None,
                    row_offset=None, stream_rows: int | None = None,
                    stream_impl: str | None = None) -> jnp.ndarray:
    """Fused batched forward DPRT: (B, N, N) -> (B, N+1, N) in ONE
    pallas_call; the R(N, d) row-sum row is produced by the in-kernel
    epilogue rather than a second pass over the image.

    With ``rows < N`` and a ``row_offset`` this is the *partial* forward
    of a shard-local row strip: both the skew-sum directions AND the
    fused row-sum row carry the device's global row placement, so one
    cross-device ``psum`` of the partials is the exact full transform.
    """
    _, _, n = f.shape
    fa = f.astype(accum_dtype_for(f.dtype, n))
    if stream_rows is not None:
        out = _pallas_stream_call(fa, sign=1, mode="forward",
                                  stream_rows=stream_rows, m_block=m_block,
                                  interpret=interpret, step_impl=step_impl,
                                  stream_impl=stream_impl,
                                  row_offset=row_offset)
    else:
        out = _pallas_skew_call(fa, sign=1, mode="forward",
                                strip_rows=strip_rows, m_block=m_block,
                                interpret=interpret, step_impl=step_impl,
                                row_offset=row_offset)
    return out[:, :n + 1, :n]


@functools.partial(jax.jit,
                   static_argnames=("strip_rows", "m_block", "interpret",
                                    "step_impl", "stream_rows",
                                    "stream_impl"))
def idprt_pallas_raw(r: jnp.ndarray, strip_rows: int = 16, m_block: int = 8,
                     interpret: bool = True,
                     step_impl: str | None = None,
                     stream_rows: int | None = None,
                     stream_impl: str | None = None) -> jnp.ndarray:
    """Fused batched inverse DPRT: (B, N+1, N) -> (B, N, N) in ONE
    pallas_call; the -S + R(N, i) correction and exact divide-by-N run
    in-kernel on the final strip (no post-kernel pass)."""
    _, _, n = r.shape
    acc = accum_dtype_for(r.dtype, n)
    ra = r.astype(acc)
    corr = ra[:, n, :] - ra[:, 0, :].sum(axis=1, keepdims=True)
    if stream_rows is not None:
        out = _pallas_stream_call(ra[:, :n, :], sign=-1, mode="inverse",
                                  stream_rows=stream_rows, m_block=m_block,
                                  interpret=interpret, corr=corr,
                                  step_impl=step_impl,
                                  stream_impl=stream_impl)
    else:
        out = _pallas_skew_call(ra[:, :n, :], sign=-1, mode="inverse",
                                strip_rows=strip_rows, m_block=m_block,
                                interpret=interpret, corr=corr,
                                step_impl=step_impl)
    return out[:, :n, :n]


# The inverse core (iSFDPRT_core, paper Sec. III-C / Fig. 16) is the
# forward skew-sum with circular *right* shifts: CRS == sign=-1.  The
# -S / +R(N,i) correction and exact divide-by-N run in-kernel in
# :func:`idprt_pallas_raw` (``mode="inverse"``); this alias is the bare
# un-corrected Z for callers that want it (formerly kernels/isfdprt.py).
isfdprt_core = functools.partial(skew_sum_pallas_raw, sign=-1)


# ===========================================================================
# Projection-domain pipeline: forward -> per-direction op -> inverse in ONE
# kernel launch (the conv/DFT fusion of the paper's Sec. I/VI application).
#
# Grid is (lane-group, m-block): each step forward-skew-sums the whole image
# for one block of directions (optionally the second conv operand too),
# applies the per-direction epilogue IN REGISTERS -- a Horner-style 1-D
# circular convolution against the operand's projections ("conv"), or a
# pointwise projection-domain multiply ("mul") -- and immediately feeds the
# block's direction rows through the inverse skew-sum ladder onto the full
# output image.  The (N+1, N) projections never exist outside VMEM/registers;
# MEM_OUT is only ever the final (N, N) image.
#
# The -S + R'(N, i) correction and exact /N divide need two *global* rows of
# the convolved projections (row 0 for S, row N for the correction column);
# they are accumulated into a tiny ``aux`` output block as their owning
# m-blocks pass through, and the final m-block applies the whole correction
# in-kernel -- or leaves it to the caller (``defer=True``, the mesh-sharded
# path, where the division must wait for the cross-device ``psum``).
#
# **Batch-in-lanes.**  A batched stack packs ``lane_batch`` images side by
# side along the lane axis (segment s owns lanes [s*n_pad, (s+1)*n_pad));
# every roll/gather/select then acts per segment, so transforming LB images
# costs the same op count as one image with LB-times-wider tiles -- the
# layout that keeps the CPU-interpret path from paying per-image dispatch
# overhead.  On TPU, ``lane_batch=1`` recovers the per-image grid.
#
# **Tail mode** (``source="proj"``).  The input rows are already-assembled
# projection rows (a shard of directions, first global direction
# ``row_offset``); the kernel applies the epilogue and the inverse ladder
# for those directions only.  This is the second (per-shard) launch of the
# mesh-distributed pipeline: forward partials are psum_scatter'd over
# directions between the two launches -- the single collective between
# forward and inverse.
# ===========================================================================

PIPELINE_OPS = ("none", "mul", "conv")


def _seg_perm(amt, n: int, n_pad: int, lb: int, rows_out: int) -> jnp.ndarray:
    """Per-segment rotation gather index for a wide (rows_out, lb*n_pad)
    tile: idx[r, s*n_pad + d] = s*n_pad + <d + amt[r]>_n for d < n,
    identity on each segment's zero tail.  ``amt`` is (rows_out, 1) in
    [0, n)."""
    d = jax.lax.broadcasted_iota(jnp.int32, (1, 1, n_pad), 2)
    base = jax.lax.broadcasted_iota(jnp.int32, (1, lb, 1), 1) * n_pad
    rot = d + amt[:, :, None]                 # (rows_out, 1, n_pad)
    rot = jnp.where(rot >= n, rot - n, rot)
    rot = jnp.where(d >= n, d, rot)
    return jnp.broadcast_to(rot + base, (rows_out, lb, n_pad)).reshape(
        rows_out, lb * n_pad)


def _seg_roller(amt, n: int, n_pad: int, lb: int, rows_out: int,
                step_impl: str):
    """The hoisted per-step roll for a wide tile: a closure applying
    out[r, s, d] = acc[r, s, <d + amt[r]>_n].  ``"permute"`` materializes
    ONE gather index (interpret/CPU); ``"ladder"`` uses the binary
    rotate+select ladder per segment (static lane slices -- Mosaic)."""
    if step_impl == "permute":
        idx = _seg_perm(amt, n, n_pad, lb, rows_out)

        def roll(acc):
            return jnp.take_along_axis(acc, idx, axis=1)
    else:
        masks = [m[:, :, None] for m in ladder_select_masks(amt, n)]

        def roll(acc):
            a3 = acc.reshape(rows_out, lb, n_pad)
            for b, sel in enumerate(masks):
                s = 1 << b
                rolled = jnp.concatenate(
                    [a3[:, :, s:n], a3[:, :, :s], a3[:, :, n:]], axis=2)
                a3 = jnp.where(sel, rolled, a3)
            return a3.reshape(rows_out, lb * n_pad)
    return roll


def _seg_roll_static(acc3: jnp.ndarray, k: int, n: int) -> jnp.ndarray:
    """Rotate every segment of a (rows, lb, n_pad) tile right by the
    *static* amount k at logical width n (zero tails carried through)."""
    k %= n
    if k == 0:
        return acc3
    parts = [acc3[:, :, n - k:n], acc3[:, :, :n - k], acc3[:, :, n:]]
    return jnp.concatenate([p for p in parts if p.shape[2]], axis=2)


def _conv_epilogue(rf: jnp.ndarray, rg3: jnp.ndarray, n: int, n_pad: int,
                   lb: int, group: int, acc_dtype) -> jnp.ndarray:
    """In-register per-direction 1-D circular convolution (Horner form):

        rc[m, s, d] = sum_t rf[m, s, t] * rg[m, s|0, <d - t>_n]

    K taps are consumed per cycle against K statically pre-rotated copies
    of the operand rows, so the loop body is K multiply-adds plus ONE
    static rotate-by-K of the accumulator -- no gathers, no index math.
    The cycle's taps sit in lanes [0, K) of a carried copy of ``rf``
    that rotates by K per cycle, so every lane index is static (Mosaic
    lowers no dynamic lane slice of a value).
    """
    m_block = rf.shape[0]
    k = max(1, min(group, n - 1))
    rf3 = rf.reshape(m_block, lb, n_pad)
    rgs = [rg3]
    for _ in range(1, k):
        rgs.append(_seg_roll_static(rgs[-1], 1, n))
    nk = math.ceil(n / k)
    width = max(n_pad, nk * k)
    if width > n_pad:         # taps beyond the lane pad: zero (rf tail is 0)
        rf3 = jnp.pad(rf3, ((0, 0), (0, 0), (0, width - n_pad)))
    # cycle j consumes taps [t0, t0 + K) with t0 = (nk - 1 - j) * K: start
    # rotated left by the first t0, then rotate right by K per cycle
    taps = _seg_roll_static(rf3, width - (nk - 1) * k, width)

    def body(_, carry):
        acc, taps = carry
        acc = _seg_roll_static(acc, k, n)
        for u in range(k):
            acc = acc + taps[:, :, u:u + 1] * rgs[u]
        return acc, _seg_roll_static(taps, k, width)

    acc = jnp.zeros((m_block, lb, n_pad), acc_dtype)
    acc, _ = jax.lax.fori_loop(0, nk, body, (acc, taps))
    return acc.reshape(m_block, lb * n_pad)


def _pipeline_kernel(*refs, n: int, n_pad: int, rows: int, m_block: int,
                     nr_pad: int, mb_total: int, lb: int, op: str,
                     source: str, operand_form: str, w_wide: bool,
                     defer: bool, acc_dtype, group: int, step_impl: str,
                     with_offset: bool):
    """One (lane-group, m-block) grid step of the fused pipeline."""
    refs = list(refs)
    off_ref = refs.pop(0) if with_offset else None
    f_ref = refs.pop(0)
    g_ref = refs.pop(0) if (op == "conv" and operand_form == "image") else None
    w_ref = refs.pop(0) if (op == "mul" or (op == "conv"
                                            and operand_form == "proj")) \
        else None
    out_ref, aux_ref, rc_ref = refs
    # one count per body traced, credited to the executable being built:
    # which roll step it took, and whether every grid step re-runs the
    # conv operand's forward
    if step_impl == "ladder":
        spans.count("sfdprt_pipeline_ladder")
    if g_ref is not None:
        spans.count("sfdprt_pipeline_operand_fwd")

    mb = pl.program_id(1)
    zero = jnp.zeros((), acc_dtype)
    wide = lb * n_pad

    row_iota = jax.lax.broadcasted_iota(jnp.int32, (m_block, 1), 0)
    dir0 = mb * m_block
    if with_offset:
        dir0 = dir0 + off_ref[0, 0]
    grow = dir0 + row_iota                    # global direction row
    valid_fwd = grow < n
    m_vec = jnp.where(valid_fwd, grow, 0)
    last = mb == mb_total - 1

    # ---- forward stage: whole-rows Horner per direction block ------------
    def fwd_of(x_ref):
        roll = _seg_roller(m_vec, n, n_pad, lb, m_block, step_impl)

        def body(i, acc):
            row = x_ref[0, rows - 1 - i, :]
            return roll(acc) + row[None, :].astype(acc_dtype)

        acc = jax.lax.fori_loop(0, rows, body,
                                jnp.zeros((m_block, wide), acc_dtype))
        return jnp.where(valid_fwd, acc, zero)

    def rowsum_of(x_ref):
        # R(N, d): each image row's sum placed at its own lane -- per
        # segment -- and dropped into the grow == n direction slot.
        x3 = x_ref[0].reshape(rows, lb, n_pad)
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows, 1, n_pad), 2)
        rsum = jnp.sum(jnp.where(lane < n, x3.astype(acc_dtype), zero),
                       axis=2)[:, :, None]               # (rows, lb, 1)
        srow = jax.lax.broadcasted_iota(jnp.int32, (rows, 1, n_pad), 0)
        placed = jnp.sum(jnp.where(lane == srow, rsum, zero),
                         axis=0).reshape(1, wide)        # (1, lb*n_pad)
        return jnp.where(grow == n, placed, zero)

    # the row-sum row lives in exactly one m-block; pay for its placement
    # there alone (a traced condition in tail mode, static otherwise)
    def with_rowsum(r, x_ref):
        owns = jnp.logical_and(dir0 <= n, n < dir0 + m_block)
        return jax.lax.cond(owns, lambda v: v + rowsum_of(x_ref),
                            lambda v: v, r)

    if source == "proj":
        rf = jnp.where(grow <= n, f_ref[0].astype(acc_dtype), zero)
    else:
        rf = with_rowsum(fwd_of(f_ref), f_ref)

    # ---- per-direction epilogue ------------------------------------------
    def w_block3():
        """This block's operand rows as (m_block, lb|1, n_pad)."""
        rows_w = w_ref[0].astype(acc_dtype)
        if w_wide:
            return rows_w.reshape(m_block, lb, n_pad)
        return rows_w[:, None, :]

    if op == "conv":
        if operand_form == "image":
            rg3 = with_rowsum(fwd_of(g_ref), g_ref).reshape(
                m_block, lb, n_pad)
        else:
            rg3 = w_block3()
        rc = _conv_epilogue(rf, rg3, n, n_pad, lb, group, acc_dtype)
    elif op == "mul":
        rc = (rf.reshape(m_block, lb, n_pad) * w_block3()).reshape(
            m_block, wide)
    else:
        rc = rf

    # ---- stash the correction rows (row 0 -> S, row N -> column) ---------
    aux = jnp.stack([
        jnp.sum(jnp.where(grow == 0, rc, zero), axis=0),
        jnp.sum(jnp.where(grow == n, rc, zero), axis=0),
    ])

    @pl.when(mb == 0)
    def _aux_init():
        aux_ref[0, :2] = aux

    @pl.when(mb > 0)
    def _aux_accum():
        aux_ref[0, :2] = aux_ref[0, :2] + aux

    # ---- inverse stage: this block's directions onto ALL image rows ------
    # The output rows are processed in cache-sized sub-blocks (the same
    # tile height the dedicated inverse kernel tunes to): one (IB, wide)
    # accumulator + its gather index stay resident per sub-block instead
    # of a single (nr_pad, wide) mega-tile thrashing L2.
    # staged in VMEM scratch: the Horner below reads one direction row
    # per cycle at a traced index, which Mosaic lowers only from a ref
    rc_ref[...] = jnp.where(valid_fwd, rc, zero)
    ib_rows = min(64, nr_pad)
    zs = []
    for i0 in range(0, nr_pad, ib_rows):
        rows_ib = min(ib_rows, nr_pad - i0)
        i_iota = i0 + jax.lax.broadcasted_iota(jnp.int32, (rows_ib, 1), 0)
        i_valid = i_iota < n
        i_vec = jnp.where(i_valid, i_iota, 0)
        neg_i = jnp.where(i_vec == 0, 0, n - i_vec)
        roll_inv = _seg_roller(neg_i, n, n_pad, lb, rows_ib, step_impl)

        def ibody(t, acc):
            return roll_inv(acc) + rc_ref[pl.ds(m_block - 1 - t, 1), :]

        z = jax.lax.fori_loop(0, m_block, ibody,
                              jnp.zeros((rows_ib, wide), acc_dtype))
        # alignment: the Horner above assumed the block's first direction
        # is 0; roll each output row i by <-i * dir0>_n (eq. 7, m -> i)
        align_amt = jnp.mod(-i_vec * (dir0 % n), n)
        z = _seg_roller(align_amt, n, n_pad, lb, rows_ib, step_impl)(z)
        zs.append(jnp.where(i_valid, z, zero))
    z = jnp.concatenate(zs, axis=0) if len(zs) > 1 else zs[0]

    @pl.when(mb == 0)
    def _init():
        out_ref[0] = z

    @pl.when(mb > 0)
    def _accum():
        out_ref[0] = out_ref[0] + z

    if not defer:
        @pl.when(last)
        def _final():
            # f = (Z - S + R'(N, i)) / N per segment, exact for integers
            aux3 = aux_ref[0, :2].reshape(2, lb, n_pad)
            lane = jax.lax.broadcasted_iota(jnp.int32, (nr_pad, 1, n_pad), 2)
            srow = jax.lax.broadcasted_iota(jnp.int32, (nr_pad, 1, n_pad), 0)
            s = jnp.sum(jnp.where(lane[0] < n, aux3[0], zero),
                        axis=1)[None, :, None]            # (1, lb, 1)
            cn = jnp.sum(jnp.where(lane == srow, aux3[1][None], zero),
                         axis=2, keepdims=True)           # (nr_pad, lb, 1)
            num = out_ref[0].reshape(nr_pad, lb, n_pad) - s + cn
            if jnp.issubdtype(jnp.dtype(acc_dtype), jnp.integer):
                res = num // n
            else:
                res = num / n
            keep = (srow < n) & (lane < n)
            out_ref[0] = jnp.where(keep, res, zero).reshape(nr_pad, wide)


def _pack_lanes(x: jnp.ndarray, lb: int, n_pad: int) -> jnp.ndarray:
    """(B, rows, N) -> (ceil(B/lb), rows, lb*n_pad) batch-in-lanes layout
    (zero images pad the last group; zero lane tails pad each segment)."""
    b, rows, n = x.shape
    bg = math.ceil(b / lb)
    x = jnp.pad(x, ((0, bg * lb - b), (0, 0), (0, n_pad - n)))
    return jnp.transpose(x.reshape(bg, lb, rows, n_pad),
                         (0, 2, 1, 3)).reshape(bg, rows, lb * n_pad)


def _unpack_lanes(y: jnp.ndarray, b: int, lb: int, n_pad: int) -> jnp.ndarray:
    """(BG, rows, lb*n_pad) -> (B, rows, n_pad): inverse of _pack_lanes."""
    bg, rows, _ = y.shape
    y = jnp.transpose(y.reshape(bg, rows, lb, n_pad), (0, 2, 1, 3))
    return y.reshape(bg * lb, rows, n_pad)[:b]


@functools.partial(
    jax.jit, static_argnames=("op", "operand_form", "source", "m_block",
                              "group", "lane_batch", "defer", "interpret",
                              "step_impl", "n_rows"))
def pipeline_pallas_raw(f: jnp.ndarray, operand: jnp.ndarray | None = None,
                        op: str = "none", operand_form: str = "proj",
                        source: str = "image", m_block: int = 32,
                        group: int = 4, lane_batch: int = 1,
                        defer: bool = False, interpret: bool = True,
                        step_impl: str | None = None,
                        row_offset=None, n_rows: int | None = None
                        ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The fused projection-domain pipeline in ONE ``pallas_call``.

    ``f``: (B, N, N) image stack (``source="image"``) or a (B, rows, N)
    shard of already-assembled projection rows (``source="proj"``, first
    global direction ``row_offset`` -- the mesh tail).  ``operand``:
    the second conv operand as images (B|1, N, N), its projections
    (B|1, N+1, N), or pointwise projection-domain weights (B|1, N+1, N),
    depending on (op, operand_form); batched operands must match ``f``'s
    batch.  Returns ``(out, aux)`` where out is (B, nr_pad, n_pad) --
    the reconstruction (or, with ``defer=True``, the raw inverse-ladder
    partial Z) -- and aux is (B, 2, n_pad) holding the convolved rows 0
    and N for the deferred -S + R'(N, i) correction.  Callers slice to
    (…, N, N).  ``n_rows`` is the transform size N when ``source="proj"``
    rows don't imply it.
    """
    if op not in PIPELINE_OPS:
        raise ValueError(f"pipeline op must be one of {PIPELINE_OPS}: {op!r}")
    b, rows, n = f.shape
    if source == "proj":
        n = f.shape[-1] if n_rows is None else n_rows
    acc_dtype = f.dtype
    lb = max(1, min(int(lane_batch), b))
    if step_impl is None:
        step_impl = "permute" if interpret else "ladder"
    lane_pad = not interpret
    n_pad = ((n + LANE - 1) // LANE) * LANE if lane_pad else n
    nr_pad = ((n + 7) // 8) * 8
    bg = math.ceil(b / lb)
    wide = lb * n_pad

    if source == "proj":
        mb_total = math.ceil(rows / m_block)
        rows_pad = mb_total * m_block
        fp4 = jnp.pad(f, ((0, bg * lb - b), (0, rows_pad - rows),
                          (0, n_pad - n)))
        fp = jnp.transpose(fp4.reshape(bg, lb, rows_pad, n_pad),
                           (0, 2, 1, 3)).reshape(bg, rows_pad, wide)
        in_specs = [pl.BlockSpec((1, m_block, wide),
                                 lambda bb, i: (bb, i, 0))]
        defer = True                      # correction needs the global psum
    else:
        mb_total = math.ceil((n + 1) / m_block)
        fp = _pack_lanes(f, lb, n_pad)
        in_specs = [pl.BlockSpec((1, rows, wide), lambda bb, i: (bb, 0, 0))]

    operands = [fp]
    with_offset = row_offset is not None
    if with_offset:
        off = jnp.asarray(row_offset, jnp.int32).reshape(1, 1)
        in_specs.insert(0, pl.BlockSpec((1, 1), lambda bb, i: (0, 0)))
        operands.insert(0, off)

    w_wide = False
    if op == "conv" and operand_form == "image":
        gb = operand
        if gb.shape[0] == b:
            gp = _pack_lanes(gb, lb, n_pad)
            in_specs.append(pl.BlockSpec((1, rows, wide),
                                         lambda bb, i: (bb, 0, 0)))
        else:   # one shared operand image, tiled across segments
            gp = _pack_lanes(jnp.broadcast_to(gb, (lb, *gb.shape[1:])),
                             lb, n_pad)
            in_specs.append(pl.BlockSpec((1, rows, wide),
                                         lambda bb, i: (0, 0, 0)))
        operands.append(gp.astype(acc_dtype))
    elif op == "mul" or (op == "conv" and operand_form == "proj"):
        wb = operand if operand.ndim == 3 else operand[None]
        if source == "proj":
            # cut this shard's window of direction rows here, so the
            # kernel reads one m-block of operand rows per grid step like
            # the image source does (Mosaic loads a traced sublane window
            # only at a provable 8-row alignment, which a shard's first
            # direction need not have).  The zero slack keeps the window
            # in bounds, unclamped; its rows meet masked directions only.
            wb = jnp.pad(wb, ((0, 0), (0, rows_pad), (0, 0)))
            wb = jax.lax.dynamic_slice_in_dim(
                wb, jnp.asarray(0 if row_offset is None else row_offset,
                                jnp.int32), rows_pad, axis=1)
        w_rows = mb_total * m_block
        if wb.shape[0] == b and b > 1:
            w_wide = True
            wp = jnp.pad(wb, ((0, bg * lb - b), (0, w_rows - wb.shape[1]),
                              (0, n_pad - n)))
            wp = jnp.transpose(wp.reshape(bg, lb, w_rows, n_pad),
                               (0, 2, 1, 3)).reshape(bg, w_rows, wide)
            in_specs.append(pl.BlockSpec((1, m_block, wide),
                                         lambda bb, i: (bb, i, 0)))
        else:
            wp = jnp.pad(wb[0], ((0, w_rows - wb.shape[1]),
                                 (0, n_pad - n)))[None]
            in_specs.append(pl.BlockSpec((1, m_block, n_pad),
                                         lambda bb, i: (0, i, 0)))
        operands.append(wp.astype(acc_dtype))

    cparams = None if interpret else _COMPILER_PARAMS_2D

    out, aux = pl.pallas_call(
        functools.partial(
            _pipeline_kernel, n=n, n_pad=n_pad, rows=rows,
            m_block=m_block, nr_pad=nr_pad, mb_total=mb_total, lb=lb,
            op=op, source=source, operand_form=operand_form, w_wide=w_wide,
            defer=defer, acc_dtype=acc_dtype, group=group,
            step_impl=step_impl, with_offset=with_offset),
        grid=(bg, mb_total),
        in_specs=in_specs,
        out_specs=(pl.BlockSpec((1, nr_pad, wide), lambda bb, i: (bb, 0, 0)),
                   pl.BlockSpec((1, 8, wide), lambda bb, i: (bb, 0, 0))),
        out_shape=(jax.ShapeDtypeStruct((bg, nr_pad, wide), acc_dtype),
                   jax.ShapeDtypeStruct((bg, 8, wide), acc_dtype)),
        scratch_shapes=[pltpu.VMEM((m_block, wide), acc_dtype)],
        compiler_params=cparams,
        interpret=interpret,
        name=f"sfdprt_pipeline_{op}",
    )(*operands)
    return (_unpack_lanes(out, b, lb, n_pad),
            _unpack_lanes(aux, b, lb, n_pad)[:, :2])

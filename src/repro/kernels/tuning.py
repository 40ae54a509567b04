"""(N -> strip_rows H, m_block M) tuning for the fused SFDPRT kernels.

The kernel's cost surface (paper Fig. 19/20 Pareto front, transplanted to
TPU blocks): grid steps per image = ceil(N/H) * ceil((N+1)/M); VMEM per
step = (H + 2M) * N_pad * itemsize; the hoisted-ladder setup
(<= ceil(log2 N) mask derivations + alignment rotate+selects) is paid
once per (m-block, strip), so *larger* blocks amortize setup while
*smaller* blocks cut VMEM and wasted rows in the final m-block.

``PALLAS_TUNE`` pins measured-good choices for the primes the repo's
tests and benchmarks exercise (CPU interpret measurements; Mosaic-aligned
sublane counts for the TPU path).  :func:`pallas_block_spec` is the
dispatch-time lookup with a heuristic fallback for unlisted N.
"""
from __future__ import annotations

import math
import warnings

__all__ = ["PALLAS_TUNE", "pallas_block_spec", "resolve_blocks",
           "PIPELINE_TUNE", "pipeline_block_spec", "resolve_pipeline_blocks",
           "wasted_direction_rows",
           "SERVE_WARM_BATCHES", "warm_batch_sizes", "nearest_warm_batch"]

# N values we already warned about (once per process per N): a giant-N
# heuristic fallback should be loud exactly once, not per dispatch.
_FALLBACK_WARNED: set = set()
_PIPELINE_FALLBACK_WARNED: set = set()


def _warn_off_table(n: int, table: dict, warned: set, kind: str) -> None:
    """Warn ONCE when N falls off the top of a measured table: the
    heuristic extrapolates block shapes that nobody has timed at this
    size, which is exactly when silent mis-tuning hurts most."""
    top = max(table)
    if n > top and n not in warned:
        warned.add(n)
        warnings.warn(
            f"N={n} is beyond the largest measured {kind} tuning row "
            f"(N={top}); using the heuristic block-shape fallback. "
            f"Pass strip_rows/m_block (or stream_rows) explicitly, or "
            f"add a measured entry, if performance matters at this size.",
            stacklevel=3)

# N: (strip_rows H, m_block M).  M multiples of 8 keep int32 sublane
# tiling aligned off the interpret path (and the compiled step's rotate
# needs each m-block's first direction to be one).  N=251 is measured on
# a TPU v5e with the strided-rotate Horner step, B=256 uint8 images, one
# compiled forward / inverse, H=N: M=32 103.0 / 102.4 ms, M=64 63.1 /
# 62.9 ms, M=128 44.1 / 44.8 ms, M=256 33.0 / 32.8 ms -- one m-block
# holds all 252 direction rows, and the per-cycle cost grows less than
# the rows it carries.  On real TPUs H bounds the VMEM-resident strip
# (H*N_pad*4B), which every pinned H below respects by a wide margin
# against the ~16 MB/core budget.
PALLAS_TUNE = {
    2: (2, 8),
    3: (3, 8),
    5: (5, 8),
    7: (7, 8),
    11: (11, 8),
    13: (13, 8),
    17: (17, 8),
    31: (31, 8),
    61: (61, 16),
    127: (127, 16),
    251: (251, 256),
    509: (256, 32),
    1021: (256, 64),
    # giant-N rows (the streamed-strip kernels): H=256 keeps one strip +
    # double buffer at (2*256 + 2*64) * N_pad * 4B < 6 MB VMEM even at
    # N=4099; M=64 amortizes the hoisted ladder over a full sublane tile
    2053: (256, 64),
    4099: (256, 64),
}


def pallas_block_spec(n: int, itemsize: int = 4) -> tuple[int, int]:
    """Tuned (strip_rows, m_block) for prime N; heuristic off-table.

    ``itemsize`` is the *accumulator* element size in bytes (8 for int64
    under x64).  The heuristic keeps one strip + accumulators within a
    ~2 MB VMEM budget and rounds the direction block to a sublane
    multiple (8/16/64), so the final m-block can carry up to m_block-1
    masked rows; :func:`wasted_direction_rows` reports the exact count
    per (N, m_block) and the benchmarks surface it as useful_row_frac.
    """
    if n in PALLAS_TUNE:
        return PALLAS_TUNE[n]
    _warn_off_table(n, PALLAS_TUNE, _FALLBACK_WARNED, "pallas")
    if n <= 32:
        return n, 8
    h = min(n, 128)
    m_block = 64 if n >= 128 else 16
    # shrink until (H + 2M) * N_pad * itemsize fits the budget: H first
    # (strip residency), then the direction block, flooring both at the
    # 8-row sublane tile
    n_pad = ((n + 127) // 128) * 128
    budget = 2 * 1024 * 1024
    while (h + 2 * m_block) * n_pad * itemsize > budget:
        if h > 8:
            h //= 2
        elif m_block > 8:
            m_block //= 2
        else:
            break
    return max(h, 1), m_block


def resolve_blocks(n: int, itemsize: int = 4,
                   strip_rows=None, m_block=None, block_rows=None,
                   stream_rows=None) -> tuple[int, int]:
    """Fill missing (strip_rows, m_block) from the table, validate given.

    The single knob-resolution used by both the Pallas op wrappers and
    the transform-plan layer (``repro.core.plan``), so ``method="auto"``
    and explicit ``method="pallas"`` land on identical block shapes.

    ``block_rows`` (the scan-of-launches staged fallback) and
    ``stream_rows`` (the in-launch streamed kernel) both partition the
    image into row strips; asking for BOTH is ambiguous and rejected
    here rather than silently preferring one.
    """
    if block_rows is not None and stream_rows is not None:
        raise ValueError(
            f"block_rows={block_rows} and stream_rows={stream_rows} are "
            "mutually exclusive: block_rows scans separate kernel "
            "launches over row strips (the staged fallback), stream_rows "
            "streams strips through ONE fused launch. Pick one.")
    if stream_rows is not None and int(stream_rows) < 1:
        raise ValueError(f"stream_rows must be >= 1, got {stream_rows}")
    th, tm = pallas_block_spec(n, itemsize)
    h = th if strip_rows is None else int(strip_rows)
    mb = tm if m_block is None else int(m_block)
    if h < 1 or mb < 1:
        raise ValueError(f"strip_rows/m_block must be >= 1, got {h}/{mb}")
    return h, mb


def wasted_direction_rows(n: int, m_block: int, forward: bool = True) -> int:
    """Masked (non-useful) rows in the final m-block -- reported by the
    benchmarks so padded work is never counted as useful throughput."""
    rows = n + 1 if forward else n
    return math.ceil(rows / m_block) * m_block - rows


# ---------------------------------------------------------------------------
# projection-domain pipeline (fused fwd -> per-direction op -> inverse)
# ---------------------------------------------------------------------------
# N: (m_block M, conv tap group K).  The pipeline kernel always runs the
# whole image as ONE strip (H = N: the conv epilogue needs each
# direction's complete projection before it can run), so its only block
# knobs are the direction block M and the Horner conv tap group K.
# N=251 is measured on a TPU v5e with the strided-rotate Horner step,
# B=64 images convolved by a 5x5 image operand, K=4: M=64 0.682,
# M=128 0.524, M=256 0.430 ms per image -- one m-block holds all 252
# direction rows, as in PALLAS_TUNE.  The other rows are CPU-interpret
# picks (M=64/K=4 beat M=32/K=8 there); small primes are a single
# m-block.  On real TPUs M bounds the accumulator sublanes ((M +
# N_pad_rows) * N_pad * itemsize VMEM per step) -- re-measure on Mosaic
# before trusting these.
# Every M is a multiple of 8: Mosaic tiles a block's second-minor axis in
# 8-row sublane groups and refuses the operand blocks otherwise.
PIPELINE_TUNE = {
    61: (64, 4),
    127: (64, 4),
    251: (256, 4),
    509: (64, 4),
    1021: (64, 4),
    2053: (64, 4),
    4099: (64, 4),
}


def pipeline_block_spec(n: int, itemsize: int = 4) -> tuple[int, int]:
    """Tuned (m_block, conv tap group) for the fused pipeline kernel."""
    if n in PIPELINE_TUNE:
        return PIPELINE_TUNE[n]
    _warn_off_table(n, PIPELINE_TUNE, _PIPELINE_FALLBACK_WARNED, "pipeline")
    if n <= 61:                 # one m-block covers every direction row
        return math.ceil((n + 1) / 8) * 8, 4
    return 64, 4


# ---------------------------------------------------------------------------
# serving tier: warm batch sizes
# ---------------------------------------------------------------------------
# The dynamic batcher pads coalesced request groups up to one of these
# batch sizes, so the service only ever needs |SERVE_WARM_BATCHES| AOT
# executables per (geometry, dtype, datapath) -- every admitted group
# hits a pre-compiled stack shape instead of compiling its exact count.
# Powers of two bound padding waste at < 2x and match the measured
# fused-kernel batched sweet spot (B=16 rows in BENCH_dprt.json: the
# one-call pallas stack is 2.4-7.5x per-image efficiency over
# single-image calls on CPU interpret and the 8-device mesh alike).
SERVE_WARM_BATCHES = (1, 2, 4, 8, 16)


def warm_batch_sizes(max_batch: int) -> tuple:
    """The warm sizes a service with admission limit ``max_batch`` keeps
    compiled: table entries up to ``max_batch``, plus ``max_batch``
    itself (an off-table limit still gets an exact-fit executable)."""
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    sizes = [b for b in SERVE_WARM_BATCHES if b <= max_batch]
    if not sizes or sizes[-1] != max_batch:
        sizes.append(max_batch)
    return tuple(sizes)


#: routed keys at/above this geometry get a trimmed warm ladder -- a
#: giant-N executable is hundreds of MB of compiled program, and a
#: multi-tenant router keeping the full (1, 2, 4, 8, 16) ladder for
#: every resident geometry would blow the executable budget the LRU
#: eviction exists to bound.
ROUTER_TRIM_N = 509


def router_warm_sizes(n: int, max_batch: int) -> tuple:
    """Warm batch sizes for one routed ``(geometry, dtype, datapath)``
    key: the full :func:`warm_batch_sizes` ladder for small geometries,
    trimmed to ``(1, max_batch)`` once ``n >= ROUTER_TRIM_N`` (padding
    waste is bounded by the batcher's coalescing at large N, executable
    residency is not)."""
    if n >= ROUTER_TRIM_N and max_batch > 1:
        return (1, int(max_batch))
    return warm_batch_sizes(max_batch)


def nearest_warm_batch(count: int, sizes) -> int:
    """Smallest warm size >= ``count`` (the padding target for one
    coalesced batch).  ``count`` above every size is a caller bug: the
    admission loop never collects more than the largest warm size."""
    for b in sizes:
        if b >= count:
            return int(b)
    raise ValueError(f"batch of {count} exceeds warm sizes {tuple(sizes)}")


def resolve_pipeline_blocks(n: int, itemsize: int = 4,
                            m_block=None, group=None) -> tuple[int, int]:
    """Fill missing pipeline (m_block, group) from the table, validate."""
    tm, tg = pipeline_block_spec(n, itemsize)
    mb = tm if m_block is None else int(m_block)
    k = tg if group is None else int(group)
    if mb < 1 or k < 1:
        raise ValueError(f"m_block/group must be >= 1, got {mb}/{k}")
    return mb, k

"""The paper's analytical cost/resource models and Pareto front.

Implements, as executable code:

* Table I   -- forward-DPRT cycle counts (serial / systolic / SFDPRT / FDPRT)
* Table II  -- inverse-DPRT cycle counts
* Table III -- resource usage (register bits, adder-tree flip-flops,
               1-bit additions, MUXes, RAM bits)
* Fig. 22   -- ``tree_resources`` (adder-tree resource recurrence)
* eq. (11)  -- the Pareto-front membership test over strip heights H
* the TPU-analog cost model used by the §Roofline/§Perf analysis: VMEM
  working-set bytes and VPU op counts per (strip H, direction block M).

The unit tests pin these against the concrete numbers quoted in the paper
(N=251, B=8: FDPRT = 511 cycles; systolic = 63,253 cycles and 516,096
flip-flops; H=84 runs 36x faster than systolic with ~25% fewer FFs).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List

__all__ = [
    "tree_resources",
    "cycles_serial", "cycles_systolic", "cycles_sfdprt", "cycles_fdprt",
    "cycles_isfdprt", "cycles_ifdprt",
    "flipflops_sfdprt", "flipflops_systolic", "flipflops_serial",
    "flipflops_fdprt",
    "adders_sfdprt", "adders_systolic", "adders_serial", "adders_fdprt",
    "pareto_front", "pareto_points",
    "TPUStripCost", "tpu_strip_cost",
]


def _n(x: int) -> int:
    return math.ceil(math.log2(x))


# ---------------------------------------------------------------------------
# Fig. 22: adder-tree resources for X operands of B bits
# ---------------------------------------------------------------------------
def tree_resources(x: int, b: int) -> Dict[str, int]:
    """Returns {'fa': 1-bit additions, 'ff': flip-flops, 'mux': 2-to-1 muxes}."""
    h = _n(x) if x > 1 else 0
    a_ff = a_fa = a_mux = 0
    a = x
    for z in range(1, h + 1):
        r = a % 2
        a = a // 2
        a_fa += a * (b + z - 1)
        a_mux += a * b
        a = a + r
        a_ff += a * (b + z)
    return {"fa": a_fa, "ff": a_ff, "mux": a_mux}


# ---------------------------------------------------------------------------
# Table I: forward cycle counts
# ---------------------------------------------------------------------------
def cycles_serial(n: int) -> int:
    return n ** 3 + 2 * n ** 2 + n


def cycles_systolic(n: int) -> int:
    return n ** 2 + n + 1


def cycles_sfdprt(n: int, h: int) -> int:
    k = math.ceil(n / h)
    return k * (n + 3 * h + 3) + n + _n(h) + 1


def cycles_fdprt(n: int) -> int:
    return 2 * n + _n(n) + 1


# ---------------------------------------------------------------------------
# Table II: inverse cycle counts
# ---------------------------------------------------------------------------
def cycles_isfdprt(n: int, h: int, b: int) -> int:
    k = math.ceil(n / h)
    return k * (n + h) + 2 * _n(n) + _n(h) + b + 3


def cycles_ifdprt(n: int, b: int) -> int:
    return 2 * n + 3 * _n(n) + b + 2


# ---------------------------------------------------------------------------
# Table III: resources (flip-flops = register-array bits + adder-tree FFs,
# matching how Fig. 19 counts them)
# ---------------------------------------------------------------------------
def flipflops_serial(n: int, b: int) -> int:
    return n * (b + _n(n)) + (3 * b + 2 * _n(n))


def flipflops_systolic(n: int, b: int) -> int:
    return n * (n + 1) * _n(n) + (n + 1) * (3 * b + 2 * _n(n))


def flipflops_sfdprt(n: int, h: int, b: int) -> int:
    return n * h * b + n * tree_resources(h, b)["ff"]


def flipflops_fdprt(n: int, b: int) -> int:
    return n * n * b + n * tree_resources(n, b)["ff"]


def adders_serial(n: int, b: int) -> int:
    return b + _n(n)


def adders_systolic(n: int, b: int) -> int:
    return (n + 1) * (b + _n(n))


def adders_sfdprt(n: int, h: int, b: int) -> int:
    return n * tree_resources(h, b)["fa"] + n * (b + _n(n))


def adders_fdprt(n: int, b: int) -> int:
    return n * tree_resources(n, b)["fa"]


# ---------------------------------------------------------------------------
# eq. (11): Pareto front over H
# ---------------------------------------------------------------------------
def pareto_front(n: int) -> List[int]:
    """H in {2..(N-1)/2} with ceil(N/H) < ceil(N/(H-1))."""
    return [h for h in range(2, (n - 1) // 2 + 1)
            if math.ceil(n / h) < math.ceil(n / (h - 1))]


def pareto_points(n: int, b: int) -> List[Dict[str, int]]:
    """(H, cycles, flip-flops, 1-bit adders) along the front, plus H=N."""
    pts = [{"h": h,
            "cycles": cycles_sfdprt(n, h),
            "ff": flipflops_sfdprt(n, h, b),
            "fa": adders_sfdprt(n, h, b)} for h in pareto_front(n)]
    pts.append({"h": n, "cycles": cycles_fdprt(n),
                "ff": flipflops_fdprt(n, b), "fa": adders_fdprt(n, b)})
    return pts


# ---------------------------------------------------------------------------
# TPU-analog cost model for the strip kernel (used by §Perf block sweeps).
#
# A (H-row strip) x (M-direction block) tile keeps in VMEM:
#   strip rows        H  x Npad  x in_bytes
#   output block      M  x Npad  x 4            (int32, double-buffered)
#   Horner carry      M  x L     x 4            (L = 2 * Npad)
#   per-step work: one strided rotate, one static rotate, one select and
#   one add on the (M, L) periodic extension; per strip, the alignment
#   ladder's ceil(log2 N) roll+select pairs on (M, Npad).
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class TPUStripCost:
    h: int
    m_block: int
    n: int
    n_pad: int
    vmem_bytes: int
    vpu_ops: int            # scalar-equivalent VPU lane-ops for the full DPRT
    hbm_bytes: int          # image reads + output writes (one pass)
    ai: float               # arithmetic intensity (ops/HBM byte)


def tpu_strip_cost(n: int, h: int, m_block: int, in_bytes: int = 4,
                   lanes: int = 128, sublanes: int = 8) -> TPUStripCost:
    n_pad = math.ceil(n / lanes) * lanes
    k = math.ceil(n / h)
    mb = math.ceil((n + 1) / m_block)
    ext = 2 * n_pad
    ladder = max(1, _n(n))
    vmem = (h * n_pad * in_bytes + m_block * n_pad * 4 * 2
            + m_block * ext * 4)
    # per (strip, m-block): H steps x (2 rotates + 1 select + 1 add)
    per_tile = h * 4 * m_block * ext
    align = (2 * ladder) * m_block * n_pad                 # alignment roll
    vpu = k * mb * (per_tile + align)
    hbm = k * mb * h * n_pad * in_bytes + (n + 1) * n_pad * 4
    return TPUStripCost(h=h, m_block=m_block, n=n, n_pad=n_pad,
                        vmem_bytes=vmem, vpu_ops=vpu, hbm_bytes=hbm,
                        ai=vpu / max(hbm, 1))

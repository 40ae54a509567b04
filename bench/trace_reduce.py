"""Reduction of a profiler trace (``*.xplane.pb``) to the numbers the
per-layer readers use.

Only events inside the harness's ``bench.window`` span count.  On each
device plane (``/device:TPU:<i>``) the ``XLA Ops`` line holds one event
per operation run:

* busy time is the union of those events' intervals;
* a kernel is an operation that ran a Mosaic kernel (a TPU custom call);
* a collective is an all-reduce, all-gather, reduce-scatter, all-to-all
  or collective-permute;
* a transfer is a host-to-device or device-to-host copy, on a device
  line of its own or as an operation.

The idle gaps are the longest stretches inside the window in which the
first device ran nothing, each named by the innermost ``bench.*`` span
the host was in at the gap's middle.
"""
from __future__ import annotations

import glob
import os
import re
from typing import List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?([.\-_]|$)")
TRANSFER = re.compile(r"(?i)(host.?to.?device|device.?to.?host|"
                      r"transfer.?to.?(device|host)|\bH2D\b|\bD2H\b)")
KERNEL_MARK = "tpu_custom_call"
#: an operation named as a custom call or after a kernel function
#: (``_sfdprt_kernel``), where its stats do not say what it ran
KERNEL_NAME = re.compile(r"custom[-_]call|_kernel\b")
TOP = 10

Interval = Tuple[int, int]


def newest_xplane(trace_dir) -> str:
    files = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def _stats(event) -> dict:
    try:
        return {k: v for k, v in event.stats}
    except Exception:           # a stat the reader cannot decode
        return {}


def is_kernel(name: str, stats: dict) -> bool:
    for key in ("hlo_category", "long_name", "tf_op"):
        value = str(stats.get(key, ""))
        if KERNEL_MARK in value or "custom-call" == value:
            return True
    return KERNEL_MARK in name or bool(KERNEL_NAME.search(name))


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(start: int, end: int, lo: int, hi: int) -> Optional[Interval]:
    s, e = max(start, lo), min(end, hi)
    return (s, e) if e > s else None


def host_spans(planes) -> List[Tuple[int, int, str]]:
    spans = []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("bench."):
                    spans.append((int(ev.start_ns), int(ev.end_ns), ev.name))
    return spans


def reduce(profile, n_devices: int) -> dict:
    """The reduced trace of a ``jax.profiler.ProfileData``."""
    planes = list(profile.planes)
    spans = host_spans(planes)
    windows = [(s, e) for s, e, name in spans if name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    lo, hi = windows[-1]
    devices = sorted((int(DEVICE_PLANE.match(p.name).group(1)), p)
                     for p in planes if DEVICE_PLANE.match(p.name))
    devices = [p for _, p in devices][:n_devices]

    busy, collective, op_time = [], [], {}
    kernel_ns = transfer_ns = 0
    first_busy: List[Interval] = []
    for k, plane in enumerate(devices):
        intervals, coll_ns = [], 0
        for line in plane.lines:
            is_ops = line.name == OPS_LINE
            line_transfer = bool(TRANSFER.search(line.name))
            for ev in line.events:
                iv = clip(int(ev.start_ns), int(ev.end_ns), lo, hi)
                if iv is None:
                    continue
                dur = iv[1] - iv[0]
                if line_transfer or (is_ops and TRANSFER.search(ev.name)):
                    transfer_ns += dur
                if not is_ops:
                    continue
                intervals.append(iv)
                op_time[ev.name] = op_time.get(ev.name, 0) + dur
                if COLLECTIVE.match(ev.name):
                    coll_ns += dur
                elif is_kernel(ev.name, _stats(ev)):
                    kernel_ns += dur
        merged = merge(intervals)
        if k == 0:
            first_busy = merged
        busy.append(sum(e - s for s, e in merged))
        collective.append(coll_ns / 1e9)

    window_s = (hi - lo) / 1e9
    busy_s = sum(busy) / len(busy) / 1e9 if busy else 0.0
    gaps = []
    cursor = lo
    for s, e in first_busy + [(hi, hi)]:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    named_gaps = [[label_at(spans, (s + e) // 2), (e - s) / 1e9]
                  for s, e in gaps[:TOP]]
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "devices": len(devices),
        "kernel_s": kernel_ns / 1e9,
        "collective_s": collective,
        "transfer_s": transfer_ns / 1e9,
        "breakdown": {"device_ops": [[n, t / 1e9] for n, t in top_ops],
                      "idle_gaps": named_gaps},
    }


def label_at(spans, t: int) -> str:
    """The innermost ``bench.*`` span around host time ``t``."""
    best: Optional[Tuple[int, str]] = None
    for s, e, name in spans:
        if name != WINDOW_SPAN and s <= t <= e:
            if best is None or e - s < best[0]:
                best = (e - s, name)
    return best[1] if best else "host: outside bench spans"


def reduce_dir(trace_dir, n_devices: int) -> dict:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(newest_xplane(trace_dir)), n_devices)


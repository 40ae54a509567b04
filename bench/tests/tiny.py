"""A copy of the benchmark at sizes a CPU test run can hold.

``tiny_root(tmp)`` copies ``BENCHMARK.json`` (with the entries of the
pending cells below added) and ``bench/`` into ``tmp`` and rewrites the
copied configurations and traffic mixes in place: the same cells,
entries and code, with small images, small stacks and a slow arrival
rate.
"""
from __future__ import annotations

import json
import shutil
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

#: per configuration file: the keys changed for the CPU
TINY_CONFIG = {
    "dprt251_u8.json": {"n": 7, "batch": 4},
    "conv251_u8.json": {"n": 7, "batch": 4},
    "dprt251_u8_2x2.json": {"n": 13, "batch": 4},
}
TINY_TRAFFIC = {
    "batch.json": {},
    "open_loop.json": {"rate_per_s": 40, "pool": 8, "check_requests": 12,
                       "router": {"max_batch": 2}},
}


#: cells whose files are under bench/ but which are not yet in
#: BENCHMARK.json, since they have not been proven on the chip: the copy
#: adds their entries, and the metrics only they report, so that their
#: tests keep running
def _metric(name, unit, better, source, layer=None, moves=None,
            bound=None):
    m = {"name": name, "unit": unit, "better": better, "source": source}
    if bound is not None:
        m["bound"] = bound
    else:
        m.update(layer=layer, moves=moves)
    m["workloads"] = []
    return m


PENDING = [
    {"workloads": {"name": "dprt251.serve", "config": "dprt251_u8",
                   "traffic": "open_loop", "chips": 1, "why": "test"},
     "end_to_end": [_metric("latency_p50_ms", "ms", "lower", "host_clock",
                            bound=0.25),
                    _metric("latency_p95_ms", "ms", "lower", "host_clock",
                            bound=0.25)],
     "per_layer": [
         _metric("kernel_ms_per_image.serve", "ms", "lower", "device_trace",
                 "kernels", "latency_p95_ms"),
         _metric("device_idle_pct.serve", "%", "lower", "device_trace",
                 "device", "latency_p95_ms"),
         _metric("transfer_ms_per_batch.serve", "ms", "lower",
                 "device_trace", "host-device transfer", "latency_p95_ms"),
         _metric("mean_batch.serve", "images", "higher", "program_counter",
                 "admission and batching", "latency_p95_ms"),
         _metric("client_late_ms_p95.serve", "ms", "lower", "host_clock",
                 "client", "latency_p95_ms")],
     "reports": ["latency_p50_ms", "latency_p95_ms",
                 "kernel_ms_per_image.serve", "device_idle_pct.serve",
                 "transfer_ms_per_batch.serve", "mean_batch.serve",
                 "client_late_ms_p95.serve"]},
    {"configs": {"name": "conv251_u8", "source": "test",
                 "file": "bench/configs/conv251_u8.json", "reduced": [],
                 "why": "test"},
     "workloads": {"name": "conv251.batch", "config": "conv251_u8",
                   "traffic": "batch", "chips": 1, "why": "test"},
     "reports": ["images_per_s", "kernel_ms_per_image.batch",
                 "kernel_gop_per_s.batch", "device_idle_pct.batch"]},
    {"configs": {"name": "dprt251_u8_2x2", "source": "test",
                 "file": "bench/configs/dprt251_u8_2x2.json", "reduced": [],
                 "why": "test"},
     "workloads": {"name": "dprt251_2x2.batch", "config": "dprt251_u8_2x2",
                   "traffic": "batch", "chips": 4, "why": "test"},
     "per_layer": [
         _metric("collective_ms_per_step.mesh", "ms", "lower",
                 "device_trace", "collectives", "images_per_s")],
     "reports": ["images_per_s", "kernel_ms_per_image.batch",
                 "kernel_gop_per_s.batch", "device_idle_pct.batch",
                 "collective_ms_per_step.mesh"]},
]


def with_pending(doc: dict) -> dict:
    for cell in PENDING:
        name = cell["workloads"]["name"]
        if name in {w["name"] for w in doc["workloads"]}:
            continue
        doc["workloads"].append(dict(cell["workloads"]))
        have = {c["name"] for c in doc["configs"]}
        if "configs" in cell and cell["configs"]["name"] not in have:
            doc["configs"].append(dict(cell["configs"]))
        for key in ("end_to_end", "per_layer"):
            have = {m["name"] for m in doc[key]}
            for m in cell.get(key, []):
                if m["name"] not in have:
                    doc[key].append(dict(m, workloads=[]))
        for m in doc["end_to_end"] + doc["per_layer"]:
            if m["name"] in cell["reports"]:
                m["workloads"] = m["workloads"] + [name]
    return doc


def tiny_root(tmp) -> Path:
    tmp = Path(tmp)
    doc = with_pending(json.loads((REPO / "BENCHMARK.json").read_text()))
    (tmp / "BENCHMARK.json").write_text(json.dumps(doc))
    shutil.copytree(REPO / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for folder, changes in (("configs", TINY_CONFIG),
                            ("traffic", TINY_TRAFFIC)):
        for name, change in changes.items():
            path = tmp / "bench" / folder / name
            doc = json.loads(path.read_text())
            doc.update(change)
            path.write_text(json.dumps(doc))
    return tmp


def run(root, cell, patch=None, seed=2**33 + 17, seconds=0.3):
    """One run of ``cell`` under ``root`` on whatever JAX finds."""
    from bench import harness
    return harness.run_cell(root, cell, seed, seconds, False,
                            t_start=time.perf_counter(), require_tpu=False,
                            patch=patch)

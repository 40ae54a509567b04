"""One run of one cell: set up, warm, measure the window, compare with
the reference, and build the result line.

The generator of the cell's traffic kind (``bench/traffic/<kind>.py``)
provides four functions, called in this order:

* ``setup(h) -> state``: builds the program's entry from the
  configuration, makes the inputs from the seed and warms every shape
  the window uses;
* ``run_window(h, state, seconds) -> window``: the measured window;
  returns ``attempted``, ``failed``, ``metrics`` (end-to-end values by
  name) and ``counts`` (what the per-layer readers divide by);
* ``collect(h, state, window) -> data``: host copies of what the
  comparison needs, taken before the device state is freed;
* ``check(h, data) -> {name: (value, limit)}``: the comparison with
  :mod:`bench.reference`; the run is correct when every value is at most
  its limit.
"""
from __future__ import annotations

import gc
import math
import shutil
import sys
import time
from pathlib import Path
from typing import Callable, Optional

import numpy as np

from bench import spec as spec_mod
from bench import trace_reduce, work

#: seconds of the window that a ``--trace 1`` run measures under the profiler
TRACE_SECONDS = 4.0


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class MissingMetric(RuntimeError):
    """A per-layer metric listed for the cell read nothing in its traced
    run: the trace holds none of what its reader looks for."""


class Harness:
    """What a generator sees of the run: the cell, its seed and devices,
    seeded random streams and the harness's trace spans."""

    def __init__(self, cell: spec_mod.Cell, seed: int, devices):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.seed = int(seed)
        self.devices = devices

    def rng(self, stream: str) -> np.random.Generator:
        """An independent numpy stream per purpose, from the seed."""
        words = [self.seed & 0xFFFFFFFF, self.seed >> 32 & 0xFFFFFFFF]
        words += [ord(c) for c in stream]
        return np.random.default_rng(words)

    def jax_key(self):
        import jax
        key = jax.random.key(self.seed & 0xFFFFFFFF)
        return jax.random.fold_in(key, self.seed >> 32 & 0x7FFFFFFF)

    def mesh(self):
        shape = self.config.get("mesh")
        if not shape:
            return None
        from jax.sharding import Mesh
        devs = np.asarray(self.devices[:math.prod(shape)]).reshape(shape)
        return Mesh(devs, tuple(self.config["mesh_axes"]))

    @staticmethod
    def span(name: str, **kw):
        import jax
        return jax.profiler.TraceAnnotation(name, **kw)


class CompileCounter:
    """Counts XLA backend compiles and persistent-cache hits and misses
    through ``jax.monitoring``."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        self.active = True
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if not self.active:
            return
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event, duration, **_):
        if self.active and event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1


def device_info(devices) -> dict:
    d0 = devices[0]
    out = {"platform": d0.platform, "kind": d0.device_kind,
           "count": len(devices)}
    peaks = []
    for d in devices:
        try:
            ms = d.memory_stats()
        except Exception:           # backend without memory statistics
            ms = None
        if ms and "peak_bytes_in_use" in ms:
            peaks.append(int(ms["peak_bytes_in_use"]))
    out["memory_peak_bytes"] = max(peaks) if peaks else 0
    return out


def trace_summary(reduced: dict) -> str:
    keys = ("window_s", "busy_s", "devices", "kernel_s", "collective_s",
            "transfer_s")
    return " ".join(f"{k}={reduced[k]}" for k in keys)


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def run_cell(root: Path, cell_name: str, seed: int, seconds: float,
             trace: bool, *, t_start: float, require_tpu: bool = True,
             bench_dir: Optional[Path] = None,
             patch: Optional[Callable] = None) -> dict:
    """Run one cell once and return its result line as a dict.

    ``patch(state)``, where given, runs after set-up and may replace what
    the window drives (the control and the fault tests use it)."""
    root = Path(root)
    bench_dir = Path(bench_dir) if bench_dir else root / "bench"
    cell = spec_mod.load_cell(root, cell_name, bench_dir)
    gen = spec_mod.load_generator(cell, bench_dir)

    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX found {devices[0].platform!r}")
    if len(devices) < cell.chips:
        raise NoAccelerator(f"cell {cell_name} needs {cell.chips} chips, "
                            f"JAX found {len(devices)}")
    devices = devices[:cell.chips]
    counter = CompileCounter()
    h = Harness(cell, seed, devices)
    log(f"cell={cell_name} seed={seed} seconds={seconds} trace={int(trace)} "
        f"device={devices[0].device_kind} x{len(devices)}")

    with h.span("bench.setup"):
        state = gen.setup(h)
    if patch is not None:
        patch(state)
    setup_s = time.perf_counter() - t_start
    log(f"setup_s={setup_s:.3f} compiles={counter.compiles} "
        f"cache_hits={counter.hits} cache_misses={counter.misses}")

    compiles_before = counter.compiles
    reduced = None
    if trace:
        tdir = root / ".bench_traces" / cell_name
        shutil.rmtree(tdir, ignore_errors=True)
        window_len = min(float(seconds), TRACE_SECONDS)
        # host spans (TraceAnnotation) and device events only: the Python
        # tracer would slow the host it is meant to observe
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(str(tdir), profiler_options=options)
        try:
            with h.span(trace_reduce.WINDOW_SPAN):
                window = gen.run_window(h, state, window_len)
        finally:
            jax.profiler.stop_trace()
    else:
        window = gen.run_window(h, state, float(seconds))
    in_window = counter.compiles - compiles_before
    counter.active = False
    log(f"window: {window['elapsed_s']:.3f} s attempted={window['attempted']}"
        f" failed={window['failed']} compiles_in_window={in_window}")
    if window.get("info"):
        log("window info: " + " ".join(f"{k}={v}" for k, v in
                                       window["info"].items()))
    device = device_info(devices)

    data = gen.collect(h, state, window)
    del state
    gc.collect()
    if trace:
        reduced = trace_reduce.reduce_dir(tdir, len(devices))
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        log("trace: " + trace_summary(reduced))

    t_check = time.perf_counter()
    compared = gen.check(h, data)
    log(f"reference check took {time.perf_counter() - t_check:.3f} s")
    correct = all(v <= lim for v, lim in compared.values())

    metrics = {}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if trace:
        ctx = {"trace": reduced, "counts": window["counts"],
               "config": cell.config, "work": work}
        for name, reader in cell.readers.items():
            value = reader.read(ctx)
            if value is None:
                raise MissingMetric(
                    f"per-layer metric {name} read nothing in cell "
                    f"{cell_name}; trace: {trace_summary(reduced)}")
            metrics[name] = {"value": float(value), "unit": units[name]}
    else:
        values = dict(window["metrics"], setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": int(window["attempted"]),
              "failed": int(window["failed"]), "metrics": metrics,
              "device": device}
    if trace:
        result["breakdown"] = reduced["breakdown"]
    for name, (value, limit) in compared.items():
        log(f"compared {name}={value} limit={limit}")
    result["compared"] = {name: {"value": value, "limit": limit}
                          for name, (value, limit) in compared.items()}
    return result

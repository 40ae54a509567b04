"""Serving-tier rows: dynamic batching + the persistent AOT cache.

Two claims gate here (``serve/*`` rows in ``BENCH_dprt.json``):

* **Coalescing.**  ``serve/coalesced`` drives the async service
  (:class:`repro.launch.service.DPRTService`) with concurrent
  single-image requests that the batcher coalesces into the fused
  batched kernel; ``serve/seq_per_request`` is the same traffic served
  one image at a time (what a front-end without dynamic batching
  does).  At small geometries the per-call dispatch overhead dominates
  the kernel, which is exactly where a high-QPS image service lives --
  the coalesced path amortizes it across the batch.
* **Routing.**  ``serve/router_mixed`` drives the fault-tolerant
  multiplexer (:class:`repro.launch.router.ServiceRouter`) with traffic
  interleaving two geometries -- the production shape where one
  front-end owns every geometry -- and ``serve/router_overhead`` sends
  the exact single-geometry traffic of ``serve/coalesced`` through the
  router, so their ratio isolates what admission, deadline tracking and
  the retry seam cost on the happy path.
* **Process isolation.**  ``serve/pool_workers2`` serves the N=31
  traffic through a :class:`repro.launch.supervisor.WorkerPool` of two
  ``serve --jsonl`` subprocesses -- pricing the pipe transport, JSON
  payload codec and supervision protocol against the in-process
  ``serve/router_overhead`` row (on a single-core host the pool cannot
  win; the row exists so regressions in the wire path are caught).
* **Warm restarts.**  ``serve/aot_cold_compile`` times XLA compilation
  of a warm-size executable; ``serve/aot_warm_restore`` times
  restoring the same executable from its serialized blob
  (``import_executable``) -- the path a process restart takes through
  :class:`repro.radon.PersistentAOTCache`, skipping XLA entirely.

Wall-clock service numbers on shared single-core hosts are the
noisiest in the suite: every row is a min over several full passes
(the passes share one event loop via ``run_requests(repeats=)``, as a
real deployment would), responses are checked bit-exact against the
sequential baseline before anything is timed, and the rows carry loose
``guard_tol`` values -- the guard is here to catch a lost batching
path or a broken restore, not scheduler jitter.
"""
import json
import os
import subprocess
import sys
import tempfile
import textwrap
import time

import jax.numpy as jnp
import numpy as np

from repro import radon
from repro.checkpoint.store import save_blob
from repro.launch.router import ServiceRouter
from repro.launch.service import DPRTService
from repro.launch.supervisor import WorkerPool, refuse_chip_children

from .common import emit

N = 31           # dispatch-overhead-bound geometry: where coalescing wins
N_SMALL = 13     # second routed geometry for the multiplexing row
MAX_BATCH = 16   # the B=16-equivalent load of the acceptance criterion
REQUESTS = 64
PASSES = 9


def main() -> None:
    svc = DPRTService((N, N), jnp.int32, max_batch=MAX_BATCH)
    svc.warmup()
    rng = np.random.default_rng(0)
    imgs = [rng.integers(0, 256, (N, N), dtype=np.int32)
            for _ in range(REQUESTS)]

    # correctness first: every coalesced response must equal the
    # per-request baseline bit-for-bit (this pass also warms both paths)
    ref, _ = svc.run_sequential(imgs)
    for got, want in zip(svc.run_requests(imgs, repeats=2), ref):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    seq_walls = []
    for _ in range(PASSES):
        seq_walls.append(sum(svc.run_sequential(imgs)[1]))
    svc.run_requests(imgs, repeats=PASSES)
    coal = min(svc.last_pass_walls) / REQUESTS
    seq = min(seq_walls) / REQUESTS
    emit(f"serve/coalesced/N{N}/b{MAX_BATCH}", 1e6 * coal,
         f"x_vs_seq={seq / coal:.2f} imgs_per_s={1 / coal:.0f}",
         kind="serve", variant="coalesced", method="auto", n=N,
         batch=MAX_BATCH, requests=REQUESTS, guard_tol=2.0)
    emit(f"serve/seq_per_request/N{N}/b{MAX_BATCH}", 1e6 * seq,
         "per-request baseline, no coalescing", kind="serve",
         variant="seq_per_request", method="auto", n=N, batch=MAX_BATCH,
         requests=REQUESTS, guard_tol=2.5)

    # the fault-tolerant router: mixed-geometry multiplexing, plus the
    # single-geometry overhead row against the direct service above
    router = ServiceRouter(max_batch=MAX_BATCH, queue_cap=REQUESTS,
                           max_inflight=2 * REQUESTS)
    router.prefill([{"n": N}, {"n": N_SMALL}])
    small = [rng.integers(0, 256, (N_SMALL, N_SMALL), dtype=np.int32)
             for _ in range(REQUESTS // 2)]
    mixed, want = [], []
    oracle = radon.DPRT((1, N_SMALL, N_SMALL), jnp.int32)
    for i in range(REQUESTS):
        if i % 2:
            mixed.append(({"n": N}, imgs[i]))
            want.append(np.asarray(ref[i]))
        else:
            img = small[i // 2]
            mixed.append(({"n": N_SMALL}, img))
            want.append(np.asarray(oracle(jnp.asarray(img[None])))[0])
    for got, exp in zip(router.run_requests(mixed, repeats=2), want):
        np.testing.assert_array_equal(np.asarray(got), exp)
    router.run_requests(mixed, repeats=PASSES)
    rmixed = min(router.last_pass_walls) / REQUESTS
    router.run_requests([({"n": N}, img) for img in imgs],
                        repeats=PASSES)
    rover = min(router.last_pass_walls) / REQUESTS
    assert router.verdict() == "OK", router.healthz()   # clean happy path
    emit(f"serve/router_mixed/N{N_SMALL}_{N}/b{MAX_BATCH}", 1e6 * rmixed,
         f"imgs_per_s={1 / rmixed:.0f} routes=2", kind="serve",
         variant="router_mixed", method="auto", n=N, batch=MAX_BATCH,
         requests=REQUESTS, guard_tol=2.5)
    emit(f"serve/router_overhead/N{N}/b{MAX_BATCH}", 1e6 * rover,
         f"x_vs_direct={rover / coal:.2f}", kind="serve",
         variant="router_overhead", method="auto", n=N, batch=MAX_BATCH,
         requests=REQUESTS, guard_tol=2.5)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "PYTHONPATH": os.path.join(repo, "src")}

    # the supervised multi-process pool: the same N=31 traffic served
    # by two router subprocesses over pipes.  On a single-core host the
    # pool cannot beat the in-process router (same silicon plus
    # serialize/fork overhead) -- the row prices process isolation and
    # the supervision protocol, it does not claim a speedup here.
    with tempfile.TemporaryDirectory() as d:
        pool = WorkerPool(2, aot_dir=d, manifest=[{"n": N}],
                          max_batch=MAX_BATCH,
                          pending_cap=4 * REQUESTS, env=env)
        try:
            pool.start()
            if not pool.wait_ready(600.0):
                raise TimeoutError("pool workers never became ready")
            futs = [pool.submit({"n": N}, img) for img in imgs]
            for fut, want in zip(futs, ref):          # bit-exact first
                np.testing.assert_array_equal(
                    np.asarray(fut.result(timeout=300)),
                    np.asarray(want))
            pool_walls = []
            for _ in range(3):
                t0 = time.perf_counter()
                futs = [pool.submit({"n": N}, img) for img in imgs]
                for fut in futs:
                    fut.result(timeout=300)
                pool_walls.append(time.perf_counter() - t0)
            assert pool.verdict() == "OK", pool.healthz()
        finally:
            pool.drain()
        ppool = min(pool_walls) / REQUESTS
        emit(f"serve/pool_workers2/N{N}/b{MAX_BATCH}", 1e6 * ppool,
             f"x_vs_router={ppool / rover:.2f} workers=2 "
             f"imgs_per_s={1 / ppool:.0f}", kind="serve",
             variant="pool_workers2", method="auto", n=N,
             batch=MAX_BATCH, requests=REQUESTS, guard_tol=3.0)

    # persistent AOT: cold start vs warm restart, each in a FRESH
    # process -- in-process re-compiles hit jax's lowering caches and
    # would flatter the "cold" number.  The warm child also asserts the
    # compile counters: a restore must take ZERO traces.
    refuse_chip_children("serve/aot_cold_compile and aot_warm_restore")
    with tempfile.TemporaryDirectory() as d:
        op = radon.DPRT((MAX_BATCH, N, N), jnp.int32)
        save_blob(d, op.cache_token(), op.export_executable(),
                  meta={"fingerprint": radon.aot_fingerprint()})
        child = textwrap.dedent(f"""
            import json, sys, time
            import jax.numpy as jnp
            from repro import radon
            op = radon.DPRT(({MAX_BATCH}, {N}, {N}), jnp.int32)
            mode = sys.argv[1]
            t0 = time.perf_counter()
            if mode == "cold":
                op.compile()
            else:
                cache = radon.PersistentAOTCache({d!r})
                cache.get_or_compile(op)
                assert cache.hits == 1, cache.stats()
            dt = time.perf_counter() - t0
            want = 1 if mode == "cold" else 0
            assert radon.trace_count() == want, radon.trace_counts()
            print(json.dumps({{"s": dt}}))
        """)

        def restart(mode):
            out = subprocess.run([sys.executable, "-c", child, mode],
                                 env=env, capture_output=True, text=True,
                                 timeout=300)
            if out.returncode != 0:
                raise RuntimeError(f"serve/aot_{mode}: subprocess failed: "
                                   f"{out.stderr.strip()[-2000:]}")
            return json.loads(out.stdout.strip().splitlines()[-1])["s"]

        cold, warm = restart("cold"), restart("warm")
    emit(f"serve/aot_cold_compile/N{N}/b{MAX_BATCH}", 1e6 * cold,
         f"x_vs_restore={cold / warm:.1f}", kind="serve",
         variant="aot_cold_compile", method="auto", n=N,
         batch=MAX_BATCH, guard_tol=2.5)
    emit(f"serve/aot_warm_restore/N{N}/b{MAX_BATCH}", 1e6 * warm,
         "fresh-process restore: deserialize only, zero traces, "
         "no XLA compilation", kind="serve",
         variant="aot_warm_restore", method="auto", n=N,
         batch=MAX_BATCH, guard_tol=2.5)


if __name__ == "__main__":
    main()

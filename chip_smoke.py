"""Smoke run of the DPRT serving path, compiled, on a TPU.

Drives the paper's 251x251 8-bit workload (``configs/radon_251.py``)
through the public entry points in ONE process (a chip serves one
process) and checks every result against the repo's numpy references:

  (a) operators  ``radon.DPRT`` forward + inverse, B=256 images, bit-exact
                 against ``dprt_oracle_np`` / ``idprt_oracle_np``;
  (b) service    ``DPRTService(max_batch=16)`` with the fallback off:
                 concurrent forward / inverse / roundtrip requests, conv
                 requests and one masked-direction CG solve;
  (c) giant N    one streamed N=2053 round trip (``stream_rows=256``:
                 the in-kernel DMA strip kernel).

``--four-chips`` runs only the ``sharded_pallas`` path: forward + inverse
at N=251, B=16 on a (2, 2) ``data x model`` mesh, bit-exact against the
single-device ``pallas`` result and the oracle, outputs spanning all 4
devices.

Earlier lines give compile seconds and a few latencies per phase (smoke
timings, not metrics), whether each executable holds a Mosaic kernel
(``tpu_custom_call``) and the service counters.  The last line is the
JSON verdict.  With no TPU visible, or on any failed check, it exits
nonzero and prints no verdict.

    python chip_smoke.py [--four-chips] [--seed S]
"""
from __future__ import annotations

import argparse
import asyncio
import json
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent
GIANT_N = 2053
STREAM_ROWS = 256
MAX_BATCH = 16
SHARDED_BATCH = 16
#: the CG settings and float tolerance tests/test_service.py holds the
#: masked solve datapath to
SOLVE_TOL, SOLVE_MAXITER, SOLVE_RTOL, SOLVE_ATOL = 1e-6, 100, 1e-4, 1e-4


class SmokeFailure(Exception):
    pass


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def wall(fn):
    """(result, seconds) of ``fn()``, which must block until done."""
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def roundtrip(fwd, inv, x):
    r = fwd(x)
    back = inv(r)
    back.block_until_ready()
    return r, back


def expect_mosaic(exe, label: str) -> None:
    has = "tpu_custom_call" in exe.as_text()
    log(f"  {label}: tpu_custom_call={has}")
    check(has, f"{label}: the executable holds no Mosaic kernel")


def fmt_ms(seconds) -> str:
    return "[" + ", ".join(f"{1e3 * s:.2f}" for s in seconds) + "] ms"


def direct_circ_conv(img: np.ndarray, kernel: np.ndarray) -> np.ndarray:
    """Circular convolution from the numpy linear-convolution oracle,
    folded onto the N x N torus (``circ_conv2d_direct`` materializes an
    N^4 tensor: 16 GB at N=251)."""
    from repro.core import linear_conv2d_direct
    n = img.shape[0]
    full = linear_conv2d_direct(img, kernel)
    out = np.zeros((n, n), np.int64)
    for a in range(0, full.shape[0], n):
        for b in range(0, full.shape[1], n):
            blk = full[a:a + n, b:b + n]
            out[:blk.shape[0], :blk.shape[1]] += blk
    return out


def phase_operators(rng):
    """(a) the paper's workload through the operator API."""
    import jax.numpy as jnp
    from repro import radon
    from repro.configs.radon_251 import config
    from repro.core import dprt_oracle_np, idprt_oracle_np

    cfg = config()
    n, b = cfg.n, cfg.batch
    log(f"(a) operators: radon.DPRT B={b} N={n} uint{cfg.bits}")
    f = rng.integers(0, 1 << cfg.bits, (b, n, n), dtype=np.uint8)
    op = radon.DPRT(f.shape, jnp.uint8)
    check(op.plan.method == "pallas",
          f"(a) method=auto resolved to {op.plan.method!r}, not pallas")
    (fwd, inv), compile_s = wall(lambda: (op.compile(),
                                          op.inverse.compile()))
    log(f"  compile_s={compile_s:.3f} (forward + inverse)")
    expect_mosaic(fwd, f"(a) forward B={b}")
    expect_mosaic(inv, f"(a) inverse B={b}")

    x = jnp.asarray(f)
    lats = []
    for _ in range(3):
        (r, back), dt = wall(lambda: roundtrip(fwd, inv, x))
        lats.append(dt)
    r_np, back_np = np.asarray(r), np.asarray(back)
    check((back_np == f).all(), "(a) inverse(forward(f)) != f")
    check((r_np[:, n] == f.sum(axis=2)).all(), "(a) row-sum direction")
    check((r_np[:, 0] == f.sum(axis=1)).all(), "(a) direction 0")
    for i in (0, b // 2, b - 1):
        check((r_np[i] == dprt_oracle_np(f[i])).all(),
              f"(a) forward image {i} != dprt_oracle_np")
        check((idprt_oracle_np(r_np[i]) == back_np[i]).all(),
              f"(a) inverse image {i} != idprt_oracle_np")
    log(f"  exact: round trip over all {b} images; images 0, {b // 2}, "
        f"{b - 1} vs the oracles")
    log(f"  smoke timing (not a metric): forward+inverse of B={b}: "
        f"{fmt_ms(lats)}")
    return f, r_np


def phase_service(f, r_np, aot_dir: str):
    """(b) the dynamic-batching service on every datapath."""
    import jax.numpy as jnp
    from repro import radon
    from repro.launch.service import DPRTService

    n = f.shape[-1]
    log(f"(b) service: DPRTService(({n}, {n}), max_batch={MAX_BATCH}, "
        f"fallback=False)")
    kernel = np.arange(1, 10, dtype=np.uint8).reshape(3, 3)
    mask = radon.direction_mask(n, [2])
    # the solve reference traces before any warmup: every service's
    # retrace count is taken on the process-wide trace counter
    masked = radon.MaskedDPRT(radon.DPRT((n, n), jnp.int32), mask=mask)
    sino = np.asarray(masked(jnp.asarray(f[-1], jnp.float32)))
    want_solve = np.asarray(radon.solve(masked, jnp.asarray(sino), "cg",
                                        tol=SOLVE_TOL,
                                        maxiter=SOLVE_MAXITER).image)

    common = dict(max_batch=MAX_BATCH, fallback=False, aot_dir=aot_dir)
    svcs = {
        "forward": DPRTService((n, n), jnp.uint8, datapath="forward",
                               **common),
        "inverse": DPRTService((n, n), jnp.uint8, datapath="inverse",
                               **common),
        "roundtrip": DPRTService((n, n), jnp.uint8, datapath="roundtrip",
                                 **common),
        "conv": DPRTService((n, n), jnp.uint8, datapath="conv",
                            conv_kernel=jnp.asarray(kernel), **common),
        "solve": DPRTService((n, n), jnp.int32, datapath="solve",
                             solve_mask=mask, solver="cg",
                             solve_tol=SOLVE_TOL,
                             solve_maxiter=SOLVE_MAXITER, **common),
    }
    for name, svc in svcs.items():
        info = svc.warmup()
        log(f"  {name}: compile_s={info['warmup_s']:.3f} "
            f"executables={info['executables']} "
            f"warm_sizes={info['warm_sizes']}")
        for bsz, chain in svc.executables().items():
            for k, exe in enumerate(chain):
                expect_mosaic(exe, f"(b) {name} b={bsz} stage={k}")
    # the trace counter is process-wide: warmup() again (nothing left to
    # compile) puts every service's retrace baseline after all warmups
    for svc in svcs.values():
        svc.warmup()

    b = f.shape[0]
    traffic = {name: [(start + k) % b for k in range(count)]
               for name, start, count in (("forward", 0, 24),
                                          ("inverse", 24, 24),
                                          ("roundtrip", 48, 24),
                                          ("conv", 72, 4))}
    payload = {name: [r_np[i] if name == "inverse" else f[i] for i in ids]
               for name, ids in traffic.items()}
    payload["solve"] = [sino]

    async def drive():
        for svc in svcs.values():
            await svc.start()
        futs = {name: [svcs[name].submit_nowait(x) for x in xs]
                for name, xs in payload.items()}
        try:
            return {name: await asyncio.gather(*fs)
                    for name, fs in futs.items()}
        finally:
            for svc in svcs.values():
                await svc.shutdown()

    out, dt = wall(lambda: asyncio.run(drive()))
    total = sum(len(v) for v in payload.values())
    log(f"  {total} concurrent requests over 5 datapaths served in "
        f"{dt:.3f} s (smoke timing, not a metric)")

    for i, got in zip(traffic["forward"], out["forward"]):
        check((np.asarray(got) == r_np[i]).all(), f"(b) forward req {i}")
    for i, got in zip(traffic["inverse"], out["inverse"]):
        check((np.asarray(got) == f[i]).all(), f"(b) inverse req {i}")
    for i, got in zip(traffic["roundtrip"], out["roundtrip"]):
        check((np.asarray(got) == f[i]).all(), f"(b) roundtrip req {i}")
    for i, got in zip(traffic["conv"], out["conv"]):
        check((np.asarray(got) == direct_circ_conv(f[i], kernel)).all(),
              f"(b) conv req {i} != the direct circular convolution")
    got_solve = np.asarray(out["solve"][0])
    err = float(np.max(np.abs(got_solve - want_solve)))
    check(np.allclose(got_solve, want_solve, rtol=SOLVE_RTOL,
                      atol=SOLVE_ATOL),
          f"(b) solve differs from radon.solve by {err}")
    exact = sum(len(traffic[k]) for k in ("forward", "inverse",
                                          "roundtrip"))
    log(f"  exact: forward/inverse/roundtrip ({exact} requests), conv "
        f"({len(traffic['conv'])}, vs the direct convolution); solve "
        f"max|diff| vs radon.solve "
        f"= {err:.3g} (rtol={SOLVE_RTOL}, atol={SOLVE_ATOL})")

    for name, svc in svcs.items():
        s = svc.stats()
        p = s["persistent"]
        lat = s["latency"]
        log(f"  {name}: requests={s['requests']} failures={s['failures']} "
            f"fallback_uses={s['fallback_uses']} "
            f"degraded_compiles={p['degraded_compiles']} "
            f"aot_errors={p['errors']} "
            f"steady_state_retraces={s['steady_state_retraces']} "
            f"batches={s['batch_size_counts']}")
        log(f"  {name} smoke timing (not a metric): latency "
            f"p50={lat['p50_ms']:.2f} max={lat['max_ms']:.2f} ms")
        check(s["requests"] == len(payload[name]) and s["failures"] == 0,
              f"(b) {name}: requests/failures {s['requests']}/"
              f"{s['failures']}")
        check(s["fallback_uses"] == 0, f"(b) {name}: fallback answered")
        check(p["degraded_compiles"] == 0 and p["errors"] == 0,
              f"(b) {name}: persistent AOT cache {p}")
        check(s["steady_state_retraces"] == 0,
              f"(b) {name}: {s['steady_state_retraces']} retraces after "
              f"warmup")


def phase_giant(rng):
    """(c) one streamed giant-N round trip."""
    import jax.numpy as jnp
    from repro import radon

    n = GIANT_N
    log(f"(c) giant N: radon.DPRT N={n} stream_rows={STREAM_ROWS}")
    op = radon.DPRT((n, n), jnp.uint8, stream_rows=STREAM_ROWS)
    check(op.plan.method == "pallas"
          and op.plan.stream_rows == STREAM_ROWS,
          f"(c) plan {op.plan.method!r} stream_rows={op.plan.stream_rows}")
    (fwd, inv), compile_s = wall(lambda: (op.compile(),
                                          op.inverse.compile()))
    log(f"  compile_s={compile_s:.3f} (forward + inverse)")
    expect_mosaic(fwd, "(c) streamed forward")
    expect_mosaic(inv, "(c) streamed inverse")

    f = rng.integers(0, 256, (n, n), dtype=np.uint8)
    x = jnp.asarray(f)
    lats = []
    for _ in range(3):
        (r, back), dt = wall(lambda: roundtrip(fwd, inv, x))
        lats.append(dt)
    r_np, back_np = np.asarray(r), np.asarray(back)
    check((back_np == f).all(), "(c) inverse(forward(f)) != f")
    rows = np.arange(n)[:, None]
    cols = np.arange(n)[None, :]
    f64 = f.astype(np.int64)
    for m in (0, 1, n // 2, n - 1):    # the oracle's sum, one direction
        want = f64[rows, (cols + m * rows) % n].sum(axis=0)
        check((r_np[m] == want).all(), f"(c) direction {m}")
    check((r_np[n] == f64.sum(axis=1)).all(), "(c) row-sum direction")
    log(f"  exact: round trip; directions 0, 1, {n // 2}, {n - 1} and the "
        f"row sums vs the oracle's sum")
    log(f"  smoke timing (not a metric): forward+inverse: {fmt_ms(lats)}")


def phase_four_chips(rng):
    """The sharded_pallas path on a (2, 2) data x model mesh."""
    import jax
    import jax.numpy as jnp
    from repro import radon
    from repro.configs.radon_251 import config
    from repro.core import dprt_oracle_np, idprt_oracle_np

    devs = jax.devices()
    check(len(devs) == 4, f"--four-chips needs 4 devices, found {len(devs)}")
    n, b = config().n, SHARDED_BATCH
    mesh = jax.make_mesh((2, 2), ("data", "model"))
    log(f"four chips: radon.DPRT B={b} N={n} on mesh {dict(mesh.shape)}")
    f = rng.integers(0, 256, (b, n, n), dtype=np.uint8)
    op = radon.DPRT(f.shape, jnp.uint8, mesh=mesh)
    check(op.plan.method == "sharded_pallas",
          f"mesh method=auto resolved to {op.plan.method!r}")
    (fwd, inv), compile_s = wall(lambda: (op.compile(),
                                          op.inverse.compile()))
    log(f"  compile_s={compile_s:.3f} (forward + inverse)")
    for label, exe in (("forward", fwd), ("inverse", inv)):
        expect_mosaic(exe, f"sharded {label}")
        txt = exe.as_text()
        log(f"  sharded {label} collectives: " + ", ".join(
            c for c in ("all-reduce", "reduce-scatter", "all-gather",
                        "collective-permute") if c in txt))

    x = jax.device_put(jnp.asarray(f), op.input_sharding)
    lats = []
    for _ in range(3):
        (r, back), dt = wall(lambda: roundtrip(fwd, inv, x))
        lats.append(dt)
    for label, y in (("forward", r), ("inverse", back)):
        span = len(y.sharding.device_set)
        log(f"  sharded {label} output spans {span} devices: {y.sharding}")
        check(span == 4, f"sharded {label} output spans {span} devices")

    single = radon.DPRT(f.shape, jnp.uint8, method="pallas")
    ref = np.asarray(single(jnp.asarray(f)))
    r_np, back_np = np.asarray(r), np.asarray(back)
    check((r_np == ref).all(), "sharded forward != single-device pallas")
    check((back_np == f).all(), "sharded inverse(forward(f)) != f")
    for i in (0, b - 1):
        check((r_np[i] == dprt_oracle_np(f[i])).all(),
              f"sharded forward image {i} != dprt_oracle_np")
        check((idprt_oracle_np(r_np[i]) == back_np[i]).all(),
              f"sharded inverse image {i} != idprt_oracle_np")
    log(f"  exact: forward == single-device pallas over all {b} images; "
        f"round trip; images 0, {b - 1} vs the oracles")
    log(f"  smoke timing (not a metric): forward+inverse: {fmt_ms(lats)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded_pallas path on a (2, 2) "
                         "mesh over 4 chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated images")
    args = ap.parse_args(argv)

    if not (REPO / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {REPO / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(REPO / "src"))

    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r} ({len(devs)} device(s))", file=sys.stderr)
        return 1

    from repro.core import spans     # counts compile-cache hits/misses
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    log(f"platform={dev.platform} device_kind={dev.device_kind} "
        f"device_count={len(devs)} jax={jax.__version__}")
    log(f"compile cache: {cache_dir}")
    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            phase_four_chips(rng)
        else:
            f, r_np = phase_operators(rng)
            with tempfile.TemporaryDirectory() as aot_dir:
                phase_service(f, r_np, aot_dir)
            phase_giant(rng)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    counters = spans.snapshot()["counters"]
    log(f"compile cache: hits={counters.get('compile_cache_hits', 0)} "
        f"misses={counters.get('compile_cache_misses', 0)}")
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The async dynamic-batching serve tier (``repro.launch.service``).

Covers: the latency-statistics helpers shared across serving surfaces,
warm-batch-size resolution, admission/padding/occupancy accounting,
coalesced-vs-direct bit-exactness on every datapath, the persistent AOT
executable cache (warm restart restores with ZERO traces; corrupt and
stale blobs degrade to recompiles), the Conv2D AOT surface, and the
``/healthz`` reports (service + module level).
"""
import asyncio

import numpy as np
import jax.numpy as jnp
import pytest

from repro import radon
from repro.checkpoint.store import save_blob
from repro.kernels.tuning import nearest_warm_batch, warm_batch_sizes
from repro.launch import serve
from repro.launch.service import (DPRTService, format_latency,
                                  latency_summary, percentile)
from repro.radon import healthz

N = 13


def _imgs(count, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 100, (N, N), dtype=np.int32)
            for _ in range(count)]


# ---------------------------------------------------------------------------
# latency statistics helpers (shared: service, serve --mode radon, benches)
# ---------------------------------------------------------------------------
def test_percentile_math():
    xs = [1.0, 2.0, 3.0, 4.0, 5.0]
    assert percentile(xs, 0) == 1.0
    assert percentile(xs, 50) == 3.0
    assert percentile(xs, 100) == 5.0
    assert percentile([0.0, 10.0], 75) == pytest.approx(7.5)
    with pytest.raises(ValueError):
        percentile([], 50)
    with pytest.raises(ValueError):
        percentile(xs, 101)


def test_latency_summary_and_format():
    s = latency_summary([0.004, 0.001, 0.003, 0.002])  # order-insensitive
    assert s["n"] == 4
    assert s["p50_ms"] == pytest.approx(2.5)
    assert s["max_ms"] == pytest.approx(4.0)
    assert s["mean_ms"] == pytest.approx(2.5)
    line = format_latency(s, imgs_per_s=123.4)
    assert "p50=2.50" in line and "p99=" in line
    assert line.endswith("123.4 img/s")
    assert latency_summary([]) == {"n": 0}
    assert format_latency({"n": 0}) == "latency: no samples"


def test_warm_batch_size_resolution():
    assert warm_batch_sizes(16) == (1, 2, 4, 8, 16)
    assert warm_batch_sizes(5) == (1, 2, 4, 5)   # off-table limit kept warm
    assert warm_batch_sizes(1) == (1,)
    with pytest.raises(ValueError):
        warm_batch_sizes(0)
    assert nearest_warm_batch(3, (1, 2, 4)) == 4
    assert nearest_warm_batch(4, (1, 2, 4)) == 4
    with pytest.raises(ValueError):
        nearest_warm_batch(5, (1, 2, 4))


# ---------------------------------------------------------------------------
# admission contract
# ---------------------------------------------------------------------------
def test_constructor_validation():
    with pytest.raises(ValueError, match="geometry"):
        DPRTService((N,), jnp.int32)
    with pytest.raises(ValueError, match="datapath"):
        DPRTService((N, N), jnp.int32, datapath="sideways")
    with pytest.raises(ValueError, match="conv_kernel"):
        DPRTService((N, N), jnp.int32, datapath="conv")   # kernel missing
    with pytest.raises(ValueError, match="conv_kernel"):
        DPRTService((N, N), jnp.int32,
                    conv_kernel=jnp.ones((3, 3), jnp.int32))
    with pytest.raises(ValueError, match="max_wait_us"):
        DPRTService((N, N), jnp.int32, max_wait_us=-1.0)


def test_traffic_rejected_before_warmup_or_loop():
    svc = DPRTService((N, N), jnp.int32, max_batch=2)
    with pytest.raises(RuntimeError, match="warmup"):
        svc.run_sequential(_imgs(1))
    with pytest.raises(RuntimeError, match="warmup"):
        svc.submit_nowait(np.zeros((N, N), np.int32))
    svc.warmup()
    with pytest.raises(RuntimeError, match="start"):
        svc.submit_nowait(np.zeros((N, N), np.int32))     # no event loop


def test_request_shape_dtype_validation():
    svc = DPRTService((N, N), jnp.int32, max_batch=2, max_wait_us=100.0)
    svc.warmup()

    async def go():
        await svc.start()
        with pytest.raises(ValueError, match="shape"):
            svc.submit_nowait(np.zeros((N, N + 1), np.int32))
        with pytest.raises(ValueError, match="dtype"):
            svc.submit_nowait(np.zeros((N, N), np.float32))
        out = await svc.submit(np.zeros((N, N), np.int32))
        await svc.shutdown()
        return out

    out = asyncio.run(go())
    assert out.shape == (N + 1, N)        # (P+1, P) projections per request


# ---------------------------------------------------------------------------
# coalescing: correctness + padding/occupancy accounting
# ---------------------------------------------------------------------------
def test_coalesced_matches_direct_and_pads():
    imgs = _imgs(7)
    # ground truth from the plain operator, computed BEFORE warmup so
    # its traces don't count against the service's steady state
    op = radon.DPRT((N, N), jnp.int32)
    ref = [np.asarray(op(img)) for img in imgs]

    svc = DPRTService((N, N), jnp.int32, max_batch=8, max_wait_us=100.0)
    svc.warmup()
    got = svc.run_requests(imgs)
    for g, r in zip(got, ref):
        np.testing.assert_array_equal(np.asarray(g), r)

    s = svc.stats()
    assert s["requests"] == 7 and s["failures"] == 0
    assert s["batches"] == 1              # burst of 7 coalesces into one
    assert s["batch_size_counts"] == {7: 1}
    assert s["padded_slots"] == 1         # 7 padded up to warm size 8
    assert s["batch_occupancy"] == pytest.approx(7 / 8)
    assert s["steady_state_retraces"] == 0
    assert svc.healthy()


def test_batcher_splits_at_max_batch():
    imgs = _imgs(6)
    svc = DPRTService((N, N), jnp.int32, max_batch=4, max_wait_us=100.0)
    svc.warmup()
    ref, _ = svc.run_sequential(imgs)
    got = svc.run_requests(imgs)
    for g, r in zip(got, ref):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))
    s = svc.stats()
    assert s["requests"] == 6
    assert s["batch_size_counts"] == {4: 1, 2: 1}   # full batch + remainder
    assert s["padded_slots"] == 0                   # 2 is itself a warm size
    assert s["queue_depth_max"] >= 1


def test_spaced_arrivals_and_repeats():
    imgs = _imgs(4, seed=3)
    svc = DPRTService((N, N), jnp.int32, max_batch=4, max_wait_us=500.0)
    svc.warmup()
    ref, _ = svc.run_sequential(imgs)
    got = svc.run_requests(imgs, arrival_us=200.0, repeats=2)
    for g, r in zip(got, ref):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))
    assert len(svc.last_pass_walls) == 2            # one wall per pass
    assert svc.stats()["requests"] == 2 * len(imgs)


def test_roundtrip_and_conv_datapaths():
    imgs = _imgs(3, seed=5)
    kernel = jnp.asarray(np.arange(9, dtype=np.int32).reshape(3, 3))
    conv_ref = np.asarray(
        radon.Conv2D((1, N, N), kernel, jnp.int32)(imgs[0][None]))[0]

    rt = DPRTService((N, N), jnp.int32, datapath="roundtrip", max_batch=2,
                     max_wait_us=100.0)
    rt.warmup()
    for g, img in zip(rt.run_requests(imgs), imgs):
        np.testing.assert_array_equal(np.asarray(g), img)   # bit-exact

    cv = DPRTService((N, N), jnp.int32, datapath="conv", max_batch=2,
                     conv_kernel=kernel, max_wait_us=100.0)
    cv.warmup()
    np.testing.assert_array_equal(
        np.asarray(cv.run_requests(imgs[:1])[0]), conv_ref)


def test_solve_datapath_serves_reconstructions():
    # requests are sinograms; responses are least-squares reconstructions
    imgs = _imgs(3, seed=6)
    fwd = radon.DPRT((N, N), jnp.int32)
    sinos = [np.asarray(fwd(jnp.asarray(x))).astype(np.float32)
             for x in imgs]

    svc = DPRTService((N, N), jnp.int32, datapath="solve", max_batch=2,
                      max_wait_us=100.0)
    assert svc.request_shape == (N + 1, N)
    assert svc.request_dtype == jnp.float32
    svc.warmup()
    for got, img in zip(svc.run_requests(sinos), imgs):
        # unmasked -> the Sherman-Morrison closed form == exact inverse
        np.testing.assert_allclose(np.asarray(got), img, atol=1e-3)
    assert svc.healthy()
    assert svc.stats()["datapath"] == "solve"

    # masked-direction CG datapath: the service must agree with a direct
    # radon.solve of the same masked operator
    mask = radon.direction_mask(N, [2])
    m = radon.MaskedDPRT(fwd, mask=mask)
    msinos = [np.asarray(m(jnp.asarray(x, jnp.float32))) for x in imgs]
    # reference solves trace BEFORE warmup: the retrace counter is
    # process-global and healthy() asserts zero post-warmup traces
    want = [np.asarray(radon.solve(m, jnp.asarray(s), "cg", tol=1e-6,
                                   maxiter=100).image) for s in msinos]
    svc2 = DPRTService((N, N), jnp.int32, datapath="solve", max_batch=2,
                       max_wait_us=100.0, solve_mask=mask, solver="cg",
                       solve_tol=1e-6, solve_maxiter=100)
    svc2.warmup()
    for got, ref in zip(svc2.run_requests(msinos), want):
        np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-4,
                                   atol=1e-4)
    assert svc2.healthy()


def test_reset_metrics_keeps_executables():
    imgs = _imgs(2)
    svc = DPRTService((N, N), jnp.int32, max_batch=2, max_wait_us=100.0)
    assert svc.executables() == {}
    svc.warmup()
    exes = svc.executables()
    assert set(exes) == set(svc.sizes) and all(len(c) == 1
                                               for c in exes.values())
    svc.run_requests(imgs)
    svc.reset_metrics()
    s = svc.stats()
    assert s["requests"] == 0 and s["batches"] == 0
    assert s["latency"] == {"n": 0}
    assert s["steady_state_retraces"] == 0          # warmup baseline kept
    assert svc.run_requests(imgs)                   # still serves, no warmup


# ---------------------------------------------------------------------------
# persistent AOT executable cache
# ---------------------------------------------------------------------------
def test_persistent_cache_warm_restart_zero_traces(tmp_path):
    radon.aot_cache_clear()       # fresh in-memory cache: disk must decide
    svc1 = DPRTService((N, N), jnp.int32, max_batch=2,
                       aot_dir=str(tmp_path), max_wait_us=100.0)
    info1 = svc1.warmup()
    p1 = info1["persistent"]
    assert p1["misses"] == info1["executables"] and p1["hits"] == 0

    # simulated restart: in-memory executables gone, blobs remain
    radon.aot_cache_clear()
    t0 = radon.trace_count()
    svc2 = DPRTService((N, N), jnp.int32, max_batch=2,
                       aot_dir=str(tmp_path), max_wait_us=100.0)
    info2 = svc2.warmup()
    p2 = info2["persistent"]
    assert p2["hits"] == info2["executables"]
    assert p2["misses"] == 0 and p2["errors"] == 0
    assert radon.trace_count() == t0      # restore took ZERO traces/compiles

    out = svc2.run_requests([np.ones((N, N), np.int32)])
    assert np.asarray(out[0]).shape == (N + 1, N)
    assert svc2.healthy()
    assert "persistent_aot hits=" in svc2.healthz()


def test_persistent_cache_corrupt_and_stale_blobs(tmp_path):
    radon.aot_cache_clear()
    op = radon.DPRT((2, N, N), jnp.int32)
    first = radon.PersistentAOTCache(str(tmp_path))
    first.get_or_compile(op)
    s = first.stats()
    assert s["directory"] == str(tmp_path)
    assert (s["hits"], s["misses"], s["errors"]) == (0, 1, 0)
    assert s["degraded_compiles"] == 0
    # uncontended cold compile: the cross-process lock engaged cleanly
    assert s["lock_steals"] == 0 and s["lock_degraded"] == 0

    # torn blob on disk: counted as an error, recompiled, re-persisted
    # -- and surfaced as a DEGRADED compile (a blob existed, the
    # restart still had to pay XLA)
    blob = next(tmp_path.glob("*.blob"))
    blob.write_bytes(b"\xff" * 32)
    radon.aot_cache_clear()
    torn = radon.PersistentAOTCache(str(tmp_path))
    torn.get_or_compile(op)
    assert torn.errors == 1 and torn.misses == 1 and torn.hits == 0
    assert torn.degraded_compiles == 1

    # the recompile healed the blob: a clean restart now hits
    radon.aot_cache_clear()
    healed = radon.PersistentAOTCache(str(tmp_path))
    healed.get_or_compile(op)
    assert healed.hits == 1 and healed.misses == 0 and healed.errors == 0

    # stale environment fingerprint: a silent miss (recompile), not an
    # error -- the blob is valid, just compiled for another world
    save_blob(str(tmp_path), op.cache_token(), b"\x00",
              meta={"fingerprint": "jax=0.0.0;backend=nowhere"})
    radon.aot_cache_clear()
    stale = radon.PersistentAOTCache(str(tmp_path))
    stale.get_or_compile(op)
    assert stale.misses == 1 and stale.errors == 0 and stale.hits == 0
    assert stale.degraded_compiles == 1   # blob present, restore cold


def test_conv2d_aot_export_import_roundtrip():
    kernel = jnp.ones((3, 3), jnp.int32)
    op = radon.Conv2D((1, N, N), kernel, jnp.int32)
    x = np.arange(N * N, dtype=np.int32).reshape(1, N, N)
    want = np.asarray(op(x))
    op.compile()
    token = op.cache_token()
    assert token.startswith("conv2d_") and f"{N}x{N}" in token
    data = op.export_executable()
    radon.aot_cache_clear()
    exe = op.import_executable(data)
    np.testing.assert_array_equal(np.asarray(exe(x)), want)
    assert radon.aot_cache_info()["currsize"] == 1  # import installs + pins


# ---------------------------------------------------------------------------
# healthz surfaces
# ---------------------------------------------------------------------------
def test_service_healthz_report():
    svc = DPRTService((N, N), jnp.int32, max_batch=2, max_wait_us=100.0)
    svc.warmup()
    svc.run_requests(_imgs(3))
    text = svc.healthz()
    assert text.startswith("[healthz] OK ")
    assert "plan_cache hits=" in text and "evictions=" in text
    assert "latency p50=" in text
    assert "steady_state_retraces=0" in text
    s = svc.stats()
    assert isinstance(s["method"], str) and s["imgs_per_s"] > 0


def test_healthz_module_snapshot_and_report():
    radon.DPRT((N, N), jnp.int32)(np.ones((N, N), np.int32))  # warm a plan
    snap = healthz.snapshot()
    for key in ("fingerprint", "plan_cache", "plans", "traces_total",
                "traces", "aot_cache"):
        assert key in snap, key
    assert snap["traces_total"] == sum(snap["traces"].values())
    text = healthz.report()
    assert "[healthz]" in text and "plan_cache" in text
    assert healthz.main() == 0


def test_serve_cli_service_smoke(capsys):
    serve.main(["--mode", "service", "--smoke", "--batch", "2",
                "--iters", "1", "--max-wait-us", "200"])
    out = capsys.readouterr().out
    assert "[serve-service] warmup:" in out
    assert "coalescing speedup" in out
    assert "[healthz] OK " in out

"""Projection-domain pipeline: fused conv/DFT dispatch, bit-exactness
against the staged path on every registered backend, exact autodiff
through the fused operators, and the circulant memory-regression guard."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import conv as C
from repro.core import dft as F
from repro.core.dprt import dprt_oracle_np
from repro.core.plan import available_backends, backend_capabilities, \
    get_backend, get_plan
from repro.kernels.ops import (pipeline_tail_pallas,
                               projection_pipeline_pallas)
from repro import radon


def _nonmesh_backends():
    return [n for n in available_backends()
            if not get_backend(n).mesh_aware]


def _capable_backends():
    return [n for n in _nonmesh_backends()
            if get_backend(n).pipeline is not None]


# ---------------------------------------------------------------------------
# kernel level
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,m_block,group",
                         [(5, 4, 1), (7, 8, 3), (13, 4, 8), (13, 16, 4)])
def test_pipeline_kernel_conv_matches_oracle(n, m_block, group):
    rng = np.random.default_rng(n)
    fb = jnp.asarray(rng.integers(0, 30, (3, n, n)), jnp.int32)
    g = jnp.asarray(rng.integers(0, 9, (n, n)), jnp.int32)
    out = projection_pipeline_pallas(fb, "conv", g, m_block=m_block,
                                     group=group)
    for i in range(3):
        np.testing.assert_array_equal(
            np.asarray(out[i], np.int64),
            np.asarray(C.circ_conv2d_direct(fb[i], g)))
    # round trip (op="none") and all-ones pointwise weights == identity
    np.testing.assert_array_equal(
        np.asarray(projection_pipeline_pallas(
            fb, "none", m_block=m_block, group=group)), np.asarray(fb))
    np.testing.assert_array_equal(
        np.asarray(projection_pipeline_pallas(
            fb, "mul", jnp.ones((n + 1, n), jnp.int32), m_block=m_block,
            group=group)), np.asarray(fb))


def test_pipeline_kernel_operand_forms_agree():
    rng = np.random.default_rng(0)
    n = 13
    fb = jnp.asarray(rng.integers(0, 30, (4, n, n)), jnp.int32)
    g = jnp.asarray(rng.integers(0, 9, (n, n)), jnp.int32)
    gb = jnp.asarray(rng.integers(0, 9, (4, n, n)), jnp.int32)
    rg = jnp.asarray(dprt_oracle_np(np.asarray(g)), jnp.int32)
    img = projection_pipeline_pallas(fb, "conv", g)
    proj = projection_pipeline_pallas(fb, "conv", rg, operand_form="proj")
    np.testing.assert_array_equal(np.asarray(img), np.asarray(proj))
    # per-image batched operand
    outb = projection_pipeline_pallas(fb, "conv", gb)
    for i in range(4):
        np.testing.assert_array_equal(
            np.asarray(outb[i], np.int64),
            np.asarray(C.circ_conv2d_direct(fb[i], gb[i])))


def test_pipeline_kernel_float_roundtrip():
    rng = np.random.default_rng(1)
    ff = jnp.asarray(rng.random((2, 7, 7)), jnp.float32)
    out = projection_pipeline_pallas(ff, "none")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ff),
                               rtol=1e-5, atol=1e-5)


def test_pipeline_tail_partials_sum_to_full_inverse():
    """Tail mode (the mesh phase 2): direction shards with offsets must
    psum to the exact staged convolution."""
    rng = np.random.default_rng(2)
    n = 13
    f = jnp.asarray(rng.integers(0, 30, (n, n)), jnp.int32)
    g = jnp.asarray(rng.integers(0, 9, (n, n)), jnp.int32)
    rfull = jnp.asarray(dprt_oracle_np(np.asarray(f)), jnp.int32)
    rg = jnp.asarray(dprt_oracle_np(np.asarray(g)), jnp.int32)
    want = np.asarray(C.circ_conv2d_direct(f, g))

    half = (n + 2) // 2
    zs, auxs = [], []
    for r in range(2):
        rows = rfull[r * half:(r + 1) * half]
        if rows.shape[0] < half:
            rows = jnp.pad(rows, ((0, half - rows.shape[0]), (0, 0)))
        z, aux = pipeline_tail_pallas(rows, "conv", rg,
                                      row_offset=r * half, n=n)
        zs.append(z)
        auxs.append(aux)
    z, aux = zs[0] + zs[1], auxs[0] + auxs[1]
    s = aux[0, :n].sum()
    cn = aux[1, :n][:, None]
    np.testing.assert_array_equal(
        np.asarray((z[:n, :n] - s + cn) // n, np.int64), want)


def test_pipeline_kernel_rejects_bad_operands():
    f = jnp.zeros((5, 5), jnp.int32)
    with pytest.raises(ValueError):
        projection_pipeline_pallas(f, "conv")          # missing operand
    with pytest.raises(ValueError):
        projection_pipeline_pallas(f, "warp", f)       # unknown op
    with pytest.raises(ValueError):
        projection_pipeline_pallas(f, "mul", jnp.zeros((4, 5), jnp.int32))
    with pytest.raises(ValueError):                    # batch mismatch
        projection_pipeline_pallas(jnp.zeros((3, 5, 5), jnp.int32), "conv",
                                   jnp.zeros((2, 5, 5), jnp.int32))
    with pytest.raises(ValueError):                    # non-prime
        projection_pipeline_pallas(jnp.zeros((6, 6), jnp.int32), "none")


# ---------------------------------------------------------------------------
# plan-level dispatch: fused == staged on every registered backend
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("method", _nonmesh_backends())
def test_plan_pipeline_bit_exact_vs_staged(method):
    rng = np.random.default_rng(3)
    n = 13
    f = jnp.asarray(rng.integers(0, 30, (n, n)), jnp.int32)
    g = jnp.asarray(rng.integers(0, 9, (n, n)), jnp.int32)
    plan = get_plan((n, n), jnp.int32, method)
    want = np.asarray(C.circ_conv2d_direct(f, g))
    np.testing.assert_array_equal(
        np.asarray(plan.pipeline(f, "conv", g), np.int64), want)
    rg = jnp.asarray(dprt_oracle_np(np.asarray(g)), jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(plan.pipeline(f, "conv", rg), np.int64), want)
    np.testing.assert_array_equal(np.asarray(plan.pipeline(f, "none")),
                                  np.asarray(f))


def test_plan_pipeline_validations():
    plan = get_plan((6, 8), jnp.int32, "pallas")   # embedded geometry
    f = jnp.zeros((6, 8), jnp.int32)
    with pytest.raises(ValueError):                # conv needs native
        plan.pipeline(f, "conv", f)
    with pytest.raises(ValueError):
        plan.pipeline(f, "mul")                    # operand missing
    # mul on an embedded geometry is the literal fused composition
    w = jnp.ones(plan.geometry.transform_shape, jnp.int32)
    np.testing.assert_array_equal(np.asarray(plan.pipeline(f + 3, "mul", w)),
                                  np.asarray(f + 3))


def test_capability_table_has_pipeline_column():
    rows = {r["name"]: r for r in backend_capabilities()}
    assert rows["pallas"]["pipeline"] is True
    assert rows["sharded_pallas"]["pipeline"] is True
    assert rows["horner"]["pipeline"] is False
    assert rows["gather"]["pipeline"] is False


# ---------------------------------------------------------------------------
# conv/dft entry points: fused vs staged
# ---------------------------------------------------------------------------
def test_circ_conv_fused_equals_staged_batched():
    rng = np.random.default_rng(4)
    n = 13
    fb = jnp.asarray(rng.integers(0, 200, (5, n, n)), jnp.int32)
    g = jnp.asarray(rng.integers(0, 16, (n, n)), jnp.int32)
    fused = C.circ_conv2d_dprt(fb, g)
    staged = C.circ_conv2d_dprt(fb, g, fuse=False)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(staged))
    # both operands batched
    gb = jnp.asarray(rng.integers(0, 16, (5, n, n)), jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(C.circ_conv2d_dprt(fb, gb)),
        np.asarray(C.circ_conv2d_dprt(fb, gb, fuse=False)))
    # batched g against single f (commuted pipeline)
    np.testing.assert_array_equal(
        np.asarray(C.circ_conv2d_dprt(fb[0], g)),
        np.asarray(C.circ_conv2d_dprt(fb[0], g, fuse=False)))


def test_linear_conv_fused_equals_staged_rectangular():
    rng = np.random.default_rng(5)
    f = jnp.asarray(rng.integers(0, 200, (9, 6)), jnp.int32)
    g = jnp.asarray(rng.integers(0, 16, (3, 4)), jnp.int32)
    fused = np.asarray(C.linear_conv2d_dprt(f, g))
    staged = np.asarray(C.linear_conv2d_dprt(f, g, fuse=False))
    np.testing.assert_array_equal(fused, staged)
    np.testing.assert_array_equal(fused, C.linear_conv2d_direct(f, g))


def test_linear_conv_blocked_fused_equals_staged():
    """Overlap-add tiles ride the batched pipeline; result must match
    the staged tile path and the whole-image result bit-for-bit."""
    rng = np.random.default_rng(6)
    f = jnp.asarray(rng.integers(0, 200, (13, 17)), jnp.int32)
    g = jnp.asarray(rng.integers(0, 16, (3, 3)), jnp.int32)
    fused = np.asarray(C.linear_conv2d_dprt(f, g, block_size=5))
    staged = np.asarray(C.linear_conv2d_dprt(f, g, block_size=5,
                                             fuse=False))
    np.testing.assert_array_equal(fused, staged)
    np.testing.assert_array_equal(fused, C.linear_conv2d_direct(f, g))
    # batched stack through the blocked route
    fb = jnp.asarray(rng.integers(0, 200, (2, 10, 8)), jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(C.linear_conv2d_dprt(fb, g, block_size=4)),
        np.asarray(C.linear_conv2d_dprt(fb, g, block_size=4, fuse=False)))


def test_circ_conv_torus_fused_equals_staged():
    rng = np.random.default_rng(7)
    f = jnp.asarray(rng.integers(0, 50, (6, 8)), jnp.int32)
    g = jnp.asarray(rng.integers(0, 10, (6, 8)), jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(C.circ_conv2d_dprt(f, g)),
        np.asarray(C.circ_conv2d_dprt(f, g, fuse=False)))


@pytest.mark.parametrize("method", _nonmesh_backends())
def test_dft2_bit_exact_across_backends(method):
    """The DFT's integer stage must be bit-identical on every backend,
    so the float spectra match exactly (same FFT on the same ints)."""
    rng = np.random.default_rng(8)
    n = 13
    f = jnp.asarray(rng.integers(0, 256, (n, n)), jnp.int32)
    base = np.asarray(F.dft2_via_dprt(f))
    np.testing.assert_array_equal(np.asarray(F.dft2_via_dprt(
        f, method=method)), base)
    fb = jnp.asarray(rng.integers(0, 256, (3, n, n)), jnp.int32)
    baseb = np.asarray(F.dft2_via_dprt_batched(fb))
    np.testing.assert_array_equal(np.asarray(F.dft2_via_dprt_batched(
        fb, method=method)), baseb)


# ---------------------------------------------------------------------------
# memory regression: circ_conv1d_exact must not materialize per-batch
# circulants
# ---------------------------------------------------------------------------
def _max_intermediate_size(fn, *avals) -> int:
    jaxpr = jax.make_jaxpr(fn)(*avals)

    def walk(jpr):
        worst = 0
        for eqn in jpr.eqns:
            for v in eqn.outvars:
                if hasattr(v.aval, "shape"):
                    size = int(np.prod(v.aval.shape)) if v.aval.shape else 1
                    worst = max(worst, size)
            for sub in eqn.params.values():
                if hasattr(sub, "jaxpr"):
                    worst = max(worst, walk(sub.jaxpr))
        return worst
    return walk(jaxpr.jaxpr)


def test_circ_conv1d_batched_peak_size_bounded():
    b, rows, n = 8, 14, 13
    a = jax.ShapeDtypeStruct((b, rows, n), jnp.int32)
    bb = jax.ShapeDtypeStruct((b, rows, n), jnp.int32)
    peak = _max_intermediate_size(C.circ_conv1d_exact, a, bb)
    # one (rows, N, N) circulant at a time -- never the O(B * rows * N^2)
    # blow-up the un-streamed gather produced
    assert peak < b * rows * n * n, peak
    assert peak >= rows * n * n
    # and a batched b against unbatched a commutes to the small circulant
    a1 = jax.ShapeDtypeStruct((rows, n), jnp.int32)
    peak2 = _max_intermediate_size(C.circ_conv1d_exact, a1, bb)
    assert peak2 < b * rows * n * n, peak2


def test_circ_conv1d_batched_correctness():
    rng = np.random.default_rng(9)
    a = jnp.asarray(rng.integers(-50, 50, (3, 4, 11)), jnp.int32)
    b = jnp.asarray(rng.integers(-10, 10, (3, 4, 11)), jnp.int32)
    got = np.asarray(C.circ_conv1d_exact(a, b))
    for i in range(3):
        for j in range(4):
            want = [sum(int(a[i, j, t]) * int(b[i, j, (d - t) % 11])
                        for t in range(11)) for d in range(11)]
            np.testing.assert_array_equal(got[i, j], want)
    # unbatched-vs-batched swap path
    got2 = np.asarray(C.circ_conv1d_exact(a[0], b))
    for i in range(3):
        want = np.asarray(C.circ_conv1d_exact(a[0], b[i]))
        np.testing.assert_array_equal(got2[i], want)
    with pytest.raises(ValueError):
        C.circ_conv1d_exact(a, b[:2])


# ---------------------------------------------------------------------------
# operators: Conv2D / ProjectionFilter / composite fusion + exact grads
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [5, 7, 13])
@pytest.mark.parametrize("method", _capable_backends() + ["horner"])
def test_conv2d_grad_matches_dense_oracle(n, method):
    rng = np.random.default_rng(n)
    f = jnp.asarray(rng.random((n, n)), jnp.float32)
    kern = jnp.asarray(rng.random((3, 3)), jnp.float32)
    u = jnp.asarray(rng.random((n, n)), jnp.float32)
    op = radon.Conv2D((n, n), kern, jnp.float32, method)
    dense = np.asarray(op.as_matrix(), np.float64)
    # grad of <C f, u> w.r.t. f is C^T u
    grad = jax.grad(lambda x: (op(x) * u).sum())(f)
    np.testing.assert_allclose(np.asarray(grad).ravel(),
                               dense.T @ np.asarray(u).ravel(),
                               rtol=3e-4, atol=3e-4)
    # op.T applies the same matrix transpose
    np.testing.assert_allclose(np.asarray(op.T(u)).ravel(),
                               dense.T @ np.asarray(u).ravel(),
                               rtol=3e-4, atol=3e-4)


def test_conv2d_grad_wrt_kernel():
    rng = np.random.default_rng(11)
    n = 7
    f = jnp.asarray(rng.random((n, n)), jnp.float32)
    u = jnp.asarray(rng.random((n, n)), jnp.float32)
    plan = get_plan((n, n), jnp.float32, "pallas")
    kern = jnp.asarray(rng.random((n, n)), jnp.float32)
    gk = jax.grad(lambda y: (radon.pipeline_apply(plan, f, "conv", y)
                             * u).sum())(kern)
    dense_g = np.zeros((n * n, n * n))
    for j in range(n * n):
        e = np.zeros((n, n), np.float32)
        e.flat[j] = 1
        dense_g[:, j] = np.asarray(
            C.circ_conv2d_direct(f, jnp.asarray(e))).ravel()
    np.testing.assert_allclose(np.asarray(gk).ravel(),
                               dense_g.T @ np.asarray(u).ravel(),
                               rtol=3e-4, atol=3e-4)


def test_conv2d_exact_int_and_torus():
    rng = np.random.default_rng(12)
    f = jnp.asarray(rng.integers(0, 100, (13, 13)), jnp.int32)
    kern = jnp.asarray(rng.integers(0, 9, (4, 4)), jnp.int32)
    op = radon.Conv2D((13, 13), kern)
    want = C.circ_conv2d_direct(
        f, jnp.pad(kern, ((0, 9), (0, 9))))
    np.testing.assert_array_equal(np.asarray(op(f), np.int64),
                                  np.asarray(want))
    # non-prime torus geometry
    f2 = jnp.asarray(rng.integers(0, 50, (6, 8)), jnp.int32)
    op2 = radon.Conv2D((6, 8), kern)
    want2 = C.circ_conv2d_dprt(f2, jnp.pad(kern, ((0, 2), (0, 4))))
    np.testing.assert_array_equal(np.asarray(op2(f2)), np.asarray(want2))


def test_composite_recognizes_inv_pointwise_fwd():
    rng = np.random.default_rng(13)
    n = 13
    f = jnp.asarray(rng.random((n, n)), jnp.float32)
    w = jnp.asarray(rng.random((n + 1, n)), jnp.float32)
    dp = radon.DPRT((n, n), jnp.float32, "pallas")
    comp = dp.inverse @ radon.ProjectionFilter(w) @ dp
    assert len(comp.ops) == 1
    assert isinstance(comp.ops[0], radon.FusedProjectionPipeline)
    got = comp(f)
    want = dp.inverse(w * dp(f))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # grads agree with the unfused composition
    gc = jax.grad(lambda x: (comp(x) ** 2).sum())(f)
    gs = jax.grad(lambda x: ((dp.inverse(w * dp(x))) ** 2).sum())(f)
    np.testing.assert_allclose(np.asarray(gc), np.asarray(gs),
                               rtol=3e-4, atol=3e-4)
    # .T round-trips through the adjoint datapaths
    u = jnp.asarray(rng.random((n, n)), jnp.float32)
    lhs = float((comp(f) * u).sum())
    rhs = float((f * comp.ops[0].T(u)).sum())
    assert abs(lhs - rhs) < 1e-2 * max(1.0, abs(lhs))


def test_composite_fusion_requires_matching_plan():
    n = 13
    dp = radon.DPRT((n, n), jnp.float32, "pallas")
    other = radon.DPRT((n, n), jnp.float32, "horner")
    w = jnp.ones((n + 1, n), jnp.float32)
    comp = dp.inverse @ radon.ProjectionFilter(w) @ other
    # plans differ -> NOT fused, still correct
    assert len(comp.ops) == 3
    f = jnp.ones((n, n), jnp.float32)
    np.testing.assert_allclose(np.asarray(comp(f)), np.asarray(f),
                               rtol=2e-5, atol=2e-5)


def test_pipeline_trace_counting_and_retrace_guard():
    n = 13
    rng = np.random.default_rng(14)
    f = jnp.asarray(rng.integers(0, 30, (n, n)), jnp.int32)
    g = jnp.asarray(rng.integers(0, 9, (n, n)), jnp.int32)
    C.circ_conv2d_dprt(f, g)   # first call traces
    with radon.retrace_guard(max_traces=0):
        for _ in range(3):     # steady state: zero retraces
            C.circ_conv2d_dprt(f + 1, g)


@pytest.mark.parametrize("n", [31, 61])
def test_conv2d_u8_stack_matches_oracle(n, conv251_kernel, pipeline_step):
    """The 2-D filtering deployment's entry, ``radon.Conv2D`` of uint8
    stacks by its 5x5 kernel, compiled through the plan to the fused
    pipeline kernel: bit for bit the numpy oracle."""
    pipeline_step()
    rng = np.random.default_rng(n)
    x = rng.integers(0, 256, (4, n, n), dtype=np.uint8)
    exe = radon.Conv2D((4, n, n), jnp.asarray(conv251_kernel),
                       jnp.uint8).compile()
    out = np.asarray(exe(jnp.asarray(x)))
    k = conv251_kernel.shape[0]
    g = jnp.asarray(np.pad(conv251_kernel, ((0, n - k), (0, n - k))),
                    jnp.int32)
    for i in range(4):
        np.testing.assert_array_equal(
            out[i], np.asarray(C.circ_conv2d_direct(
                jnp.asarray(x[i], jnp.int32), g)))


def test_fused_composite_aot_survives_plan_cache_clear():
    """Regression: evicting plans used to crash on composite AOT keys
    containing filter/fused 4-tuple entries (and never actually dropped
    them)."""
    n = 13
    dp = radon.DPRT((n, n), jnp.float32, "pallas")
    w = jnp.ones((n + 1, n), jnp.float32)
    comp = dp.inverse @ radon.ProjectionFilter(w) @ dp
    exe = comp.compile()
    f = jnp.ones((n, n), jnp.float32)
    np.testing.assert_allclose(np.asarray(exe(f)), np.asarray(f),
                               rtol=1e-5, atol=1e-5)
    before = radon.aot_cache_info()["currsize"]
    assert before >= 1
    radon.plan_cache_clear()       # must not raise, must drop the entry
    assert radon.aot_cache_info()["currsize"] < before


def test_fused_composite_keeps_forward_input_dtype():
    """The fusion rewrite must not change a composite's input signature:
    dtype_in stays the forward operator's image dtype."""
    n = 13
    dp = radon.DPRT((n, n), jnp.uint8, "pallas")
    w = jnp.ones((n + 1, n), jnp.int32)
    comp = dp.inverse @ radon.ProjectionFilter(w) @ dp
    assert isinstance(comp.ops[0], radon.FusedProjectionPipeline)
    assert comp.dtype_in == jnp.dtype(jnp.uint8)
    img = jnp.arange(n * n, dtype=jnp.uint8).reshape(n, n)
    exe = comp.compile()           # AOT signature accepts uint8 images
    np.testing.assert_array_equal(np.asarray(exe(img)),
                                  np.asarray(img.astype(jnp.int32)))


def test_operator_inverse_errors_are_informative():
    n = 13
    w = jnp.ones((n + 1, n), jnp.float32)
    with pytest.raises(TypeError, match="no exact inverse"):
        radon.ProjectionFilter(w).inverse
    with pytest.raises(TypeError, match="no exact inverse"):
        radon.Conv2D((n, n), w[:2, :2]).inverse
    dp = radon.DPRT((n, n), jnp.float32, "pallas")
    comp = dp.inverse @ radon.ProjectionFilter(w) @ dp
    with pytest.raises(TypeError, match="no exact inverse"):
        comp.inverse


def test_sharded_pipeline_rejects_mismatched_operand_batch():
    from repro.core.distributed import projection_pipeline_sharded
    mesh = jax.make_mesh((1,), ("model",))
    fb = jnp.zeros((5, 13, 13), jnp.int32)
    bad = jnp.zeros((3, 14, 13), jnp.int32)
    with pytest.raises(ValueError, match="must match the stack batch"):
        projection_pipeline_sharded(fb, mesh, "conv", bad)


def test_circ_conv1d_mixed_rank_broadcast():
    """Regression: a higher-rank `a` against a lower-rank batched `b`
    broadcasts (the circulant still comes from the lower-rank side)."""
    rng = np.random.default_rng(16)
    a = jnp.asarray(rng.integers(-9, 9, (2, 3, 4, 11)), jnp.int32)
    b = jnp.asarray(rng.integers(-9, 9, (3, 4, 11)), jnp.int32)
    got = np.asarray(C.circ_conv1d_exact(a, b))
    assert got.shape == (2, 3, 4, 11)
    for i in range(2):
        np.testing.assert_array_equal(
            got[i], np.asarray(C.circ_conv1d_exact(a[i], b)))


def test_filter_composite_lowers_for_weights_shape():
    """inverse @ ProjectionFilter (projection-domain input) AOT-lowers
    using the weights' own shape instead of crashing on the wildcard."""
    n = 13
    dp = radon.DPRT((n, n), jnp.float32, "pallas")
    w = jnp.ones((n + 1, n), jnp.float32)
    comp = dp.inverse @ radon.ProjectionFilter(w)
    exe = comp.compile()
    r = dp(jnp.ones((n, n), jnp.float32))
    np.testing.assert_allclose(np.asarray(exe(r)),
                               np.asarray(dp.inverse(w * r)),
                               rtol=1e-5, atol=1e-5)


def test_conv2d_transpose_keeps_plan_knobs():
    op = radon.Conv2D((4, 13, 13), jnp.ones((3, 3), jnp.int32),
                      block_batch=2)
    assert op.T.plan.block_batch == 2
    assert op.T.plan.batch_impl == op.plan.batch_impl


def test_pipeline_block_batch_with_batched_operand():
    """block_batch must bound the fused pipeline even when the conv
    operand is per-image batched (image and operand chunk together)."""
    rng = np.random.default_rng(17)
    n = 13
    fb = jnp.asarray(rng.integers(0, 50, (5, n, n)), jnp.int32)
    gb = jnp.asarray(rng.integers(0, 9, (5, n, n)), jnp.int32)
    whole = get_plan((5, n, n), jnp.int32, "pallas")
    chunked = get_plan((5, n, n), jnp.int32, "pallas", block_batch=2)
    np.testing.assert_array_equal(
        np.asarray(chunked.pipeline(fb, "conv", gb)),
        np.asarray(whole.pipeline(fb, "conv", gb)))
    # shared operand keeps chunking too
    np.testing.assert_array_equal(
        np.asarray(chunked.pipeline(fb, "conv", gb[0])),
        np.asarray(whole.pipeline(fb, "conv", gb[0])))

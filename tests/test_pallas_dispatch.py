"""The fused/batched Pallas kernel family behind ``method="pallas"``:

* bit-exactness vs the numpy oracle across primes where strip_rows does
  NOT divide N and m_block does NOT divide N (incl. the paper's N=251),
* forward/inverse round-trips, batched-vs-loop equivalence (one
  pallas_call per stack),
* the hoisted-setup contract: mask derivation happens once per
  m-block, never inside the Horner loop,
* the compiled per-cycle step (one strided lane rotate on a periodically
  extended accumulator) forced in interpret mode with padded lanes,
  bit-exact against the oracles, and its loop body's shape,
* masked final m-block + lane padding: no wrapped-duplicate garbage,
* overflow-safe accumulators (int64 survives under x64),
* conv routing through the dispatch.
"""
import numpy as np
import jax
import jax.extend.core as jex_core
import jax.numpy as jnp
import pytest

import importlib
D = importlib.import_module("repro.core.dprt")
C = importlib.import_module("repro.core.conv")
from repro.kernels import dprt_pallas, idprt_pallas, pallas_block_spec
from repro.kernels.ref import skew_sum_ref
from repro.kernels.sfdprt import (_pallas_skew_call, _pallas_stream_call,
                                  dprt_pallas_raw, roll_rows_ladder_spec)


def rand_img(n, seed=0, shape=None):
    return np.random.default_rng(seed).integers(
        0, 256, shape or (n, n)).astype(np.int32)


# ---------------------------------------------------------------------------
# exactness on awkward tilings (H does not divide N, M does not divide N)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [5, 7, 13])
@pytest.mark.parametrize("h", [1, 3, None])   # None -> H = N
@pytest.mark.parametrize("mb", [3, 5, 8])
def test_dispatch_forward_inverse_vs_oracle(n, h, mb):
    h = n if h is None else h
    f = rand_img(n, seed=n * 31 + h * 7 + mb)
    ref = D.dprt_oracle_np(f)
    r = D.dprt(jnp.asarray(f), method="pallas", strip_rows=h, m_block=mb)
    np.testing.assert_array_equal(np.asarray(r), ref)
    back = D.idprt(r, method="pallas", strip_rows=h, m_block=mb)
    np.testing.assert_array_equal(np.asarray(back), f)


def test_paper_n251_tuned_blocks():
    """The paper's headline size through the dispatch with tuned blocks."""
    n = 251
    f = rand_img(n, seed=1)
    ref = D.dprt_oracle_np(f)
    r = D.dprt(jnp.asarray(f), method="pallas")
    np.testing.assert_array_equal(np.asarray(r), ref)
    back = D.idprt(r, method="pallas")
    np.testing.assert_array_equal(np.asarray(back), f)


def test_skew_sum_dispatch_matches_ref():
    from repro.kernels import skew_sum_ref
    n = 13
    g = rand_img(n, seed=9)
    for sign in (1, -1):
        a = np.asarray(D.skew_sum(jnp.asarray(g), sign, method="pallas"))
        np.testing.assert_array_equal(
            a, np.asarray(skew_sum_ref(jnp.asarray(g), sign)))


# ---------------------------------------------------------------------------
# batched: one pallas_call == loop of singles
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [7, 13])
def test_batched_equals_loop(n):
    fb = rand_img(n, seed=n, shape=(8, n, n))
    rb = np.asarray(D.dprt_batched(jnp.asarray(fb), method="pallas"))
    assert rb.shape == (8, n + 1, n)
    for i in range(8):
        np.testing.assert_array_equal(rb[i], D.dprt_oracle_np(fb[i]))
    back = np.asarray(D.idprt_batched(jnp.asarray(rb.astype(np.int32)),
                                      method="pallas"))
    np.testing.assert_array_equal(back, fb)


def test_batched_kernel_wrappers_accept_2d_and_3d():
    n = 7
    fb = rand_img(n, seed=3, shape=(9, n, n))
    rb = np.asarray(dprt_pallas(jnp.asarray(fb)))
    r0 = np.asarray(dprt_pallas(jnp.asarray(fb[0])))
    np.testing.assert_array_equal(rb[0], r0)
    bb = np.asarray(idprt_pallas(jnp.asarray(rb.astype(np.int32))))
    b0 = np.asarray(idprt_pallas(jnp.asarray(r0.astype(np.int32))))
    np.testing.assert_array_equal(bb[0], b0)
    np.testing.assert_array_equal(bb, fb)


# ---------------------------------------------------------------------------
# hoisted setup: the alignment masks are derived outside the Horner loop
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("h", [1, 4, 13])
def test_ladder_setup_hoisted_out_of_horner_loop(h):
    """All (amt >> b) & 1 mask derivations (and the index-permute setup)
    run BEFORE the fori_loop: the traced loop body contains no
    shift-right ops, for every strip height."""
    n = 13
    f = jnp.zeros((1, n, n), jnp.int32)
    for impl in ("roll", "permute"):
        jaxpr = str(jax.make_jaxpr(
            lambda x, hh=h, im=impl: dprt_pallas_raw(
                x, strip_rows=hh, m_block=8, interpret=True,
                step_impl=im))(f))
        loop_tok = next((t for t in ("while[", "scan[") if t in jaxpr), None)
        assert loop_tok is not None, "Horner loop was not traced as a loop"
        _, _, after_loop_start = jaxpr.partition(loop_tok)
        # ALL setup (alignment masks, permute indices) is
        # emitted before the loop; the loop body and everything after it
        # must re-derive nothing.
        n_shifts_total = jaxpr.count("shift_right")
        n_shifts_after = after_loop_start.count("shift_right")
        assert n_shifts_after == 0, (
            f"{n_shifts_after} mask derivations inside/after the Horner "
            f"loop (impl={impl}, H={h})")
        # and the total setup is bounded by the two ladders' bit counts
        assert n_shifts_total <= 2 * roll_rows_ladder_spec(n)


def test_ladder_setup_independent_of_strip_height():
    """Setup op count must not scale with H (it is per m-block)."""
    n = 13
    f = jnp.zeros((1, n, n), jnp.int32)
    counts = []
    for h in (1, 13):
        jaxpr = str(jax.make_jaxpr(
            lambda x, hh=h: dprt_pallas_raw(x, strip_rows=hh, m_block=8,
                                            interpret=True,
                                            step_impl="roll"))(f))
        counts.append(jaxpr.count("shift_right"))
    assert counts[0] == counts[1]


# ---------------------------------------------------------------------------
# the compiled step, forced in interpret mode with Mosaic's lane padding
# ---------------------------------------------------------------------------
def _roll_call(x, mode, h, mb, row_offset=None, sign=1, stream_impl=None):
    """The chip's own per-cycle step (``step_impl="roll"``) on 128-padded
    lanes, in interpret mode; returns the logical output as numpy."""
    n = x.shape[-1]
    kw = dict(mode=mode, m_block=mb, interpret=True, lane_pad=True,
              step_impl="roll", row_offset=row_offset)
    if mode == "inverse":
        xa = x.astype(np.int32)
        kw["corr"] = jnp.asarray(xa[:, n] - xa[:, 0].sum(axis=1,
                                                         keepdims=True))
        x, kw["sign"] = x[:, :n], -1
    else:
        kw["sign"] = sign
    if stream_impl is None:
        out = _pallas_skew_call(jnp.asarray(x), strip_rows=h, **kw)
    else:
        out = _pallas_stream_call(jnp.asarray(x), stream_rows=h,
                                  stream_impl=stream_impl, **kw)
    out = np.asarray(out)
    if mode != "inverse":     # the inverse's /N epilogue spans all lanes
        assert (out[:, :, n:] == 0).all(), "lane padding must stay zero"
    rows = n + 1 if mode == "forward" else n
    assert (out[:, rows:] == 0).all(), "wrapped-duplicate rows must be 0"
    return out[:, :rows, :n]


def _check_roll_modes(mode, n, h, mb, off, stream_impl=None):
    f = rand_img(n, seed=n + h + mb, shape=(2, n, n))
    if mode == "forward":
        got = _roll_call(f, mode, h, mb, stream_impl=stream_impl)
        for i in range(2):
            np.testing.assert_array_equal(got[i], D.dprt_oracle_np(f[i]))
    elif mode == "inverse":
        r = np.stack([D.dprt_oracle_np(x) for x in f])
        got = _roll_call(r, mode, h, mb, stream_impl=stream_impl)
        for i in range(2):
            np.testing.assert_array_equal(got[i], D.idprt_oracle_np(r[i]))
            np.testing.assert_array_equal(got[i], f[i])
    else:
        # a row strip starting at global row ``off``: the oracle is the
        # whole-image skew sum of the strip placed at its rows
        off = off or 0
        placed = f.copy()
        placed[:, :off] = 0
        for sign in (1, -1):
            got = _roll_call(f[:, off:], mode, h, mb,
                             row_offset=off if off else None, sign=sign,
                             stream_impl=stream_impl)
            for i in range(2):
                np.testing.assert_array_equal(
                    got[i], np.asarray(skew_sum_ref(placed[i], sign)))


# (N, strip_rows, m_block, core row_offset): no m_block divides N + 1,
# and the second case of each N has H < N (the alignment roll runs)
ROLL_CASES = [(5, 5, 4, None), (5, 2, 4, 1),
              (13, 13, 8, None), (13, 4, 5, 6),
              (61, 61, 16, None), (61, 7, 24, 20),
              (251, 251, 32, None), (251, 64, 40, 100)]


@pytest.mark.parametrize("n,h,mb,off", ROLL_CASES)
@pytest.mark.parametrize("mode", ["forward", "inverse", "core"])
def test_roll_step_matches_oracles(mode, n, h, mb, off):
    """The compiled step is bit-exact against the definition: forward
    (with the fused row sum), inverse (with the exact /N) and the bare
    skew sum in both directions with a nonzero row offset."""
    _check_roll_modes(mode, n, h, mb, off)


@pytest.mark.parametrize("stream_impl", ["grid", "dma"])
@pytest.mark.parametrize("mode", ["forward", "inverse", "core"])
def test_roll_step_streamed_matches_oracles(mode, stream_impl):
    """The streamed kernels take the same step: N=13 in strips of 4."""
    _check_roll_modes(mode, 13, 4, 5, 3, stream_impl=stream_impl)


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(x, jex_core.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jex_core.Jaxpr):
                yield x


def _all_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _all_eqns(sub)


def _strided(eqn):
    return eqn.primitive.name == "roll" and eqn.params["stride"] is not None


def test_roll_step_counted_once_per_kernel_trace():
    """``sfdprt_step_roll`` counts each kernel body traced with the
    compiled step, and only those; an unknown step is refused."""
    from repro.core import spans

    def rolls():
        return spans.snapshot()["counters"].get("sfdprt_step_roll", 0)

    x = jnp.zeros((1, 7, 7), jnp.int32)
    for impl, grows in (("roll", 1), ("permute", 0)):
        before = rolls()
        jax.make_jaxpr(lambda g, im=impl: _pallas_skew_call(
            g, sign=1, mode="forward", strip_rows=7, m_block=8,
            interpret=True, step_impl=im))(x)
        assert rolls() - before == grows, impl
    with pytest.raises(ValueError, match="step_impl"):
        _pallas_skew_call(x, sign=1, mode="core", strip_rows=7, m_block=8,
                          interpret=True, step_impl="ladder")


@pytest.mark.parametrize("n,h", [(13, 4), (251, 251)])
@pytest.mark.parametrize("mode", ["forward", "inverse"])
def test_roll_step_loop_body_is_one_native_rotate(mode, n, h):
    """The traced Horner loop body of the compiled step holds exactly one
    strided rotate and one static rotate, no concatenate of lane slices
    at offsets off the 128-lane tile, and no mask derivation."""
    x = jnp.zeros((1, n, n), jnp.int32)
    corr = jnp.zeros((1, n), jnp.int32) if mode == "inverse" else None
    closed = jax.make_jaxpr(lambda g: _pallas_skew_call(
        g, sign=1 if mode == "forward" else -1, mode=mode, strip_rows=h,
        m_block=8, interpret=True, lane_pad=True, step_impl="roll",
        corr=corr))(x)
    loops = [e for e in _all_eqns(closed.jaxpr)
             if e.primitive.name in ("scan", "while")
             and any(_strided(b) for s in _sub_jaxprs(e)
                     for b in _all_eqns(s))]
    assert len(loops) == 1, "one Horner loop carries the strided rotate"
    body = [b for s in _sub_jaxprs(loops[0]) for b in _all_eqns(s)]
    rolls = [b for b in body if b.primitive.name == "roll"]
    assert sum(map(_strided, rolls)) == 1
    assert len(rolls) == 2, "one strided and one static rotate"
    for b in body:
        if b.primitive.name == "concatenate":
            d = b.params["dimension"]
            cuts = np.cumsum([v.aval.shape[d] for v in b.invars])[:-1]
            assert (cuts % 128 == 0).all(), f"unaligned lane cut {cuts}"
        assert "shift_right" not in b.primitive.name


# ---------------------------------------------------------------------------
# masked final m-block + lane padding
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [5, 13])
def test_padded_rows_and_lanes_are_zero(n):
    """Wrapped-duplicate direction rows and padded lanes are masked to
    zero -- never computed as (and never mistakable for) useful output."""
    f = rand_img(n, seed=n, shape=(2, n, n))
    out = np.asarray(_pallas_skew_call(
        jnp.asarray(f), sign=1, mode="forward", strip_rows=3, m_block=4,
        interpret=True, lane_pad=True))
    assert out.shape[-1] == 128  # lane axis padded to the Mosaic tile
    for i in range(2):
        np.testing.assert_array_equal(out[i, :n + 1, :n],
                                      D.dprt_oracle_np(f[i]))
        assert (out[i, :, n:] == 0).all()
        assert (out[i, n + 1:, :] == 0).all()


def test_tuning_table_sane():
    for n in [5, 13, 251, 521, 1021, 4099]:
        h, mb = pallas_block_spec(n)
        assert 1 <= h <= n
        assert mb >= 1


# ---------------------------------------------------------------------------
# dtypes / overflow
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [np.uint8, np.int16, np.int32])
def test_integer_dtypes_accumulate_exactly(dtype):
    n = 13
    hi = min(np.iinfo(dtype).max, 255)
    f = np.random.default_rng(7).integers(0, hi, (n, n)).astype(dtype)
    r = np.asarray(D.dprt(jnp.asarray(f), method="pallas"))
    assert r.dtype == np.int32  # accum_dtype_for, not the input dtype
    np.testing.assert_array_equal(r, D.dprt_oracle_np(f.astype(np.int32)))


def test_float32_roundtrip_close():
    n = 7
    f = np.random.default_rng(5).random((n, n)).astype(np.float32)
    r = D.dprt(jnp.asarray(f), method="pallas")
    assert np.asarray(r).dtype == np.float32
    back = np.asarray(D.idprt(r, method="pallas"))
    np.testing.assert_allclose(back, f, rtol=1e-5, atol=1e-4)


@pytest.mark.slow
def test_int64_accumulator_survives_x64(subproc):
    """The fused inverse must keep int64 inputs in int64 (the seed's
    idprt_pallas cast S and R(N, i) to int32 unconditionally)."""
    subproc("""
import numpy as np, jax, jax.numpy as jnp
jax.config.update("jax_enable_x64", True)
from repro.core.dprt import dprt_oracle_np, accum_dtype_for
from repro.kernels import dprt_pallas, idprt_pallas
assert accum_dtype_for(jnp.int64) == jnp.int64
n = 13
big = 1 << 40  # row sums overflow int32 by a factor of ~2^22
f = (np.random.default_rng(0).integers(0, 256, (n, n)).astype(np.int64)
     * (big // 256))
r = dprt_pallas(jnp.asarray(f))
assert r.dtype == jnp.int64, r.dtype
np.testing.assert_array_equal(np.asarray(r), dprt_oracle_np(f))
back = idprt_pallas(r)
assert back.dtype == jnp.int64, back.dtype
np.testing.assert_array_equal(np.asarray(back), f)
print("OK int64")
""", devices=1, extra_env={"JAX_ENABLE_X64": "1"})


# ---------------------------------------------------------------------------
# conv routing
# ---------------------------------------------------------------------------
def test_conv_via_pallas_dispatch():
    n = 11
    f = rand_img(n, seed=1)
    g = np.random.default_rng(2).integers(0, 16, (n, n)).astype(np.int32)
    got = np.asarray(C.circ_conv2d_dprt(jnp.asarray(f), jnp.asarray(g),
                                        method="pallas"))
    want = np.asarray(C.circ_conv2d_direct(jnp.asarray(f), jnp.asarray(g)))
    np.testing.assert_array_equal(got, want)


def test_conv_batched_stack_single_kernel():
    n = 7
    fb = rand_img(n, seed=4, shape=(8, n, n))
    g = np.random.default_rng(6).integers(0, 16, (n, n)).astype(np.int32)
    got = np.asarray(C.circ_conv2d_dprt(jnp.asarray(fb), jnp.asarray(g),
                                        method="pallas"))
    for i in range(8):
        want = np.asarray(C.circ_conv2d_direct(jnp.asarray(fb[i]),
                                               jnp.asarray(g)))
        np.testing.assert_array_equal(got[i], want)

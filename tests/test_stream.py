"""In-launch streamed-strip SFDPRT kernels (``stream_rows``) and the
direction-sharded collective layout.

The streamed kernels process an N x N image that does not fit
whole-image-in-VMEM as ONE ``pallas_call``: an in-kernel DMA
double-buffer loop walks row strips and accumulates partial skew-sums
in registers.  Everything here must stay
bit-exact against the whole-image kernel and the numpy oracle --
including awkward primes where the strip count does not divide N (the
final strip is masked padding).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.dprt import dprt_oracle_np
from repro.core.plan import available_backends, get_backend, get_plan
from repro.kernels.sfdprt import (dprt_pallas_raw, idprt_pallas_raw,
                                  skew_sum_pallas_raw)
from repro.kernels.tuning import resolve_blocks
from repro import radon


def _img(n, b=None, seed=0, lo=0, hi=250):
    rng = np.random.default_rng(seed)
    shape = (n, n) if b is None else (b, n, n)
    return rng.integers(lo, hi, shape, dtype=np.int32)


# ---------------------------------------------------------------------------
# streamed vs whole-image: bit-exact, partial strips
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,sr", [(13, 5), (61, 7)])
def test_streamed_raw_kernels_bitexact(n, sr):
    """Raw streamed kernels == whole-image kernels == oracle, at strip
    heights that do NOT divide N (final strip is a masked partial)."""
    assert n % sr != 0, "test wants a masked final strip"
    fb = jnp.asarray(_img(n, b=3, seed=n))
    whole = dprt_pallas_raw(fb, strip_rows=n, m_block=8)
    got = dprt_pallas_raw(fb, stream_rows=sr, m_block=8)
    assert (np.asarray(got) == np.asarray(whole)).all()
    for b in range(3):
        assert (np.asarray(got[b]) == dprt_oracle_np(np.asarray(fb[b]))).all()
    back = idprt_pallas_raw(got, stream_rows=sr, m_block=8)
    assert (np.asarray(back) == np.asarray(fb)).all()
    # bare skew-sum, both signs (adjoint datapaths ride this)
    for sign in (1, -1):
        want = skew_sum_pallas_raw(fb, sign, strip_rows=n, m_block=8)
        got = skew_sum_pallas_raw(fb, sign, stream_rows=sr, m_block=8)
        assert (np.asarray(got) == np.asarray(want)).all(), sign


def test_streamed_row_offset_partials():
    """A streamed partial over a shard-local strip (row_offset) matches
    the fused strip kernel -- the contract the sharded backend uses."""
    n, rows, off = 13, 6, 7
    g = jnp.asarray(_img(n, seed=5)[:rows])
    want = skew_sum_pallas_raw(g, 1, strip_rows=rows, m_block=8,
                               row_offset=off)
    got = skew_sum_pallas_raw(g, 1, stream_rows=4, m_block=8,
                              row_offset=off)
    assert (np.asarray(got) == np.asarray(want)).all()


# (N, stream_rows): the last strip is ragged; (mode, row_offset): a row
# strip from global row 9 is a shard-local partial (the inverse takes
# whole projections only)
RAGGED = [(31, 8), (127, 16)]
RAGGED_MODES = [("forward", None), ("forward", 9), ("inverse", None),
                ("core", None), ("core", 9)]


@pytest.mark.parametrize("mode,off", RAGGED_MODES)
@pytest.mark.parametrize("n,sr", RAGGED)
def test_dma_stream_ragged_last_strip(n, sr, mode, off):
    """The DMA stream with a ragged last strip, in each mode and with or
    without a row offset, is bit-exact against the numpy oracles (a
    partial's oracle is the whole image with the rows above the offset
    zeroed)."""
    from repro.core.dprt import idprt_oracle_np
    from repro.kernels.ref import skew_sum_ref
    f = _img(n, b=2, seed=n + sr)
    rows = f[:, off or 0:]
    assert rows.shape[1] % sr != 0, "test wants a ragged last strip"
    placed = f.copy()
    placed[:, :off or 0] = 0
    if mode == "inverse":
        r = np.stack([dprt_oracle_np(x) for x in f]).astype(np.int32)
        got = np.asarray(idprt_pallas_raw(jnp.asarray(r), stream_rows=sr,
                                          m_block=32))
        for b in range(2):
            np.testing.assert_array_equal(got[b], idprt_oracle_np(r[b]))
            np.testing.assert_array_equal(got[b], f[b])
    elif mode == "forward":
        got = np.asarray(dprt_pallas_raw(jnp.asarray(rows), stream_rows=sr,
                                         m_block=32, row_offset=off))
        for b in range(2):
            np.testing.assert_array_equal(got[b], dprt_oracle_np(placed[b]))
    else:
        for sign in (1, -1):
            got = np.asarray(skew_sum_pallas_raw(
                jnp.asarray(rows), sign, stream_rows=sr, m_block=32,
                row_offset=off))
            for b in range(2):
                np.testing.assert_array_equal(
                    got[b], np.asarray(skew_sum_ref(placed[b], sign)))


def test_streamed_plan_roundtrip_and_adjoint():
    """Plan-level: stream_rows on the pallas backend stays bit-exact
    through forward / inverse / adjoint."""
    n, sr = 61, 7
    f = jnp.asarray(_img(n, seed=2))
    oracle = dprt_oracle_np(np.asarray(f))
    whole = get_plan(f.shape, f.dtype, "pallas")
    p = get_plan(f.shape, f.dtype, "pallas", stream_rows=sr)
    assert p.stream_rows == sr
    assert p.describe()["stream_rows"] == sr
    r = p.forward(f)
    assert (np.asarray(r) == oracle).all()
    assert (np.asarray(r) == np.asarray(whole.forward(f))).all()
    assert (np.asarray(p.inverse(r)) == np.asarray(f)).all()
    ra = jnp.asarray(oracle.astype(np.int32))
    assert (np.asarray(p.adjoint(ra))
            == np.asarray(whole.adjoint(ra))).all()


def test_streamed_noncapable_backends_fall_back_to_scan():
    """stream_rows on a backend without streamed kernels takes the
    staged scan fallback at the same strip height -- still exact."""
    n, sr = 13, 5
    f = jnp.asarray(_img(n, seed=3))
    oracle = dprt_oracle_np(np.asarray(f))
    for method in available_backends():
        be = get_backend(method)
        if be.mesh_aware or be.takes_stream_rows:
            continue
        p = get_plan(f.shape, f.dtype, method, stream_rows=sr)
        assert p._scan_rows == sr, method
        assert (np.asarray(p.forward(f)) == oracle).all(), method
        assert (np.asarray(p.inverse(jnp.asarray(oracle.astype(np.int32))))
                == np.asarray(f)).all(), method
    # capable backends must NOT take the scan fallback
    assert get_plan(f.shape, f.dtype, "pallas",
                    stream_rows=sr)._scan_rows is None


def test_streamed_ambient_config_carries_through():
    """radon.config(stream_rows=...) resolves eagerly into the plan."""
    n = 61
    f = jnp.asarray(_img(n, seed=4))
    with radon.config(method="pallas", stream_rows=9):
        op = radon.DPRT(f.shape, f.dtype)
    assert op.plan.stream_rows == 9
    assert op.plan.method == "pallas"
    assert (np.asarray(op(f)) == dprt_oracle_np(np.asarray(f))).all()
    assert (np.asarray(op.inverse(op(f))) == np.asarray(f)).all()


# ---------------------------------------------------------------------------
# knob conflict rejection
# ---------------------------------------------------------------------------
def test_block_rows_stream_rows_conflict_rejected():
    with pytest.raises(ValueError, match="mutually exclusive"):
        resolve_blocks(61, 4, block_rows=8, stream_rows=7)
    with pytest.raises(ValueError, match="mutually exclusive"):
        get_plan((61, 61), jnp.int32, "pallas", block_rows=8, stream_rows=7)
    # conflict fires for every backend, not just block-taking ones
    with pytest.raises(ValueError, match="mutually exclusive"):
        get_plan((61, 61), jnp.int32, "horner", block_rows=8, stream_rows=7)
    with pytest.raises(ValueError, match="stream_rows"):
        get_plan((61, 61), jnp.int32, "pallas", stream_rows=0)


# ---------------------------------------------------------------------------
# single-launch structure: one pallas_call, no scan-of-launches, and the
# jaxpr does not grow with the strip count (one live buffer pair)
# ---------------------------------------------------------------------------
def _walk_eqns(jaxpr, inside_loop, pallas_found, counter):
    for eqn in jaxpr.eqns:
        counter[0] += 1
        name = eqn.primitive.name
        if name == "pallas_call":
            pallas_found.append(inside_loop)
            continue        # kernel body size is checked via the total
        nested_loop = inside_loop or name in ("scan", "while")
        for val in eqn.params.values():
            for sub in _subjaxprs(val):
                _walk_eqns(sub, nested_loop, pallas_found, counter)


def _subjaxprs(val):
    if hasattr(val, "eqns"):
        yield val
    elif isinstance(val, (list, tuple)):
        for v in val:
            yield from _subjaxprs(v)


def _jaxpr_stats(fn, x):
    jaxpr = jax.make_jaxpr(fn)(x)
    pallas_found, counter = [], [0]
    _walk_eqns(jaxpr.jaxpr, False, pallas_found, counter)
    return pallas_found, counter[0]


def test_streamed_is_one_launch_constant_size():
    n = 61
    fb = jnp.asarray(_img(n, b=1, seed=6))

    def fwd(sr):
        return lambda x: dprt_pallas_raw(x, stream_rows=sr, m_block=8)

    found, size_a = _jaxpr_stats(fwd(4), fb)
    assert len(found) == 1, "streamed forward must be ONE pallas_call"
    assert not found[0], "pallas_call must not sit under a scan/while"
    # doubling the strip count must not grow the program: only one strip
    # buffer (pair) is ever live, the rest is grid/loop bounds
    found_b, size_b = _jaxpr_stats(fwd(8), fb)
    assert len(found_b) == 1 and not found_b[0]
    assert size_a == size_b, (size_a, size_b)


def test_streamed_plan_forward_is_one_launch():
    """Through the plan layer too: no scan-of-launches on the
    stream-capable backend (the scan survives only as the
    block_rows/non-capable fallback)."""
    n = 61
    f = jnp.asarray(_img(n, seed=7))
    p = get_plan(f.shape, f.dtype, "pallas", stream_rows=7)
    found, _ = _jaxpr_stats(p.forward, f)
    assert len(found) == 1 and not found[0]
    # while the block_rows staged fallback leaves the fused kernel
    # entirely (a scanned Horner datapath: zero pallas_calls)
    pb = get_plan(f.shape, f.dtype, "pallas", block_rows=16)
    found_b, _ = _jaxpr_stats(pb.forward, f)
    assert len(found_b) == 0, "block_rows fallback must not be fused"


# ---------------------------------------------------------------------------
# giant-N and the direction-sharded collectives (forced-host subprocesses)
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_giant_n_2053_streamed_roundtrip():
    """N=2053 forward + inverse, bit-exact for integer images, through
    the streamed kernel as ONE pallas_call (the acceptance geometry)."""
    n = 2053
    rng = np.random.default_rng(11)
    f = jnp.asarray(rng.integers(0, 256, (n, n), dtype=np.int32))
    p = get_plan(f.shape, f.dtype, "pallas", stream_rows=256)
    found, _ = _jaxpr_stats(p.forward, f)
    assert len(found) == 1 and not found[0]
    r = p.forward(f)
    cols = np.arange(n)
    fnp = np.asarray(f, dtype=np.int64)
    for m in (0, 1, n - 1):      # oracle spot-check: full O(N^3) is slow
        want = np.zeros(n, dtype=np.int64)
        for i in range(n):
            want += fnp[i, (cols + m * i) % n]
        assert (np.asarray(r[m]) == want).all(), m
    assert (np.asarray(r[n]) == fnp.sum(axis=1)).all()
    assert (np.asarray(p.inverse(r)) == np.asarray(f)).all()


@pytest.mark.slow
def test_sharded_direction_layout_and_ring(subproc):
    """8-device direction-sharded forward/inverse (the new default) ==
    oracle; the explicit ppermute ring == psum_scatter; streamed
    per-shard kernels compose with both."""
    subproc("""
import numpy as np, jax, jax.numpy as jnp
from repro.core.distributed import dprt_sharded_pallas, idprt_sharded_pallas
from repro.core.dprt import dprt_oracle_np
rng = np.random.default_rng(13)
n = 61
f = jnp.asarray(rng.integers(0, 256, (n, n)), jnp.int32)
ref = dprt_oracle_np(np.asarray(f))
mesh = jax.make_mesh((8,), ("model",))
# the direction-sharded default round-trips exactly
r = dprt_sharded_pallas(f, mesh)
assert (np.asarray(r) == ref).all()
assert (np.asarray(idprt_sharded_pallas(r, mesh)) == np.asarray(f)).all()
# ring == psum_scatter == psum, forward and inverse
for reduce in ("psum", "psum_scatter", "ring"):
    r = dprt_sharded_pallas(f, mesh, reduce=reduce)
    assert (np.asarray(r) == ref).all(), reduce
    back = idprt_sharded_pallas(r, mesh, reduce=reduce)
    assert (np.asarray(back) == np.asarray(f)).all(), reduce
# streamed per-shard kernel under the sharded layouts
for reduce in ("psum_scatter", "ring"):
    r = dprt_sharded_pallas(f, mesh, reduce=reduce, stream_rows=3)
    assert (np.asarray(r) == ref).all(), ("stream", reduce)
    back = idprt_sharded_pallas(r, mesh, reduce=reduce, stream_rows=3)
    assert (np.asarray(back) == np.asarray(f)).all(), ("stream-inv", reduce)
# batched 2-D mesh with a non-dividing batch
mesh2 = jax.make_mesh((2, 4), ("data", "model"))
fb = jnp.asarray(rng.integers(0, 256, (5, n, n)), jnp.int32)
rb = dprt_sharded_pallas(fb, mesh2)
for b in range(5):
    assert (np.asarray(rb[b]) == dprt_oracle_np(np.asarray(fb[b]))).all()
bb = idprt_sharded_pallas(rb, mesh2)
assert (np.asarray(bb) == np.asarray(fb)).all()
print("OK")
""")


@pytest.mark.slow
def test_sharded_plan_stream_rows(subproc):
    """stream_rows reaches the sharded_pallas backend through the plan
    layer (mesh auto-routing) and the pipeline stays exact."""
    subproc("""
import numpy as np, jax, jax.numpy as jnp
from repro.core.plan import get_plan, select_backend
from repro.core.dprt import dprt_oracle_np
from repro.core.distributed import projection_pipeline_sharded
mesh = jax.make_mesh((8,), ("model",))
assert select_backend(61, jnp.int32, mesh=mesh) == "sharded_pallas"
rng = np.random.default_rng(17)
f = jnp.asarray(rng.integers(0, 256, (61, 61)), jnp.int32)
p = get_plan(f.shape, f.dtype, "auto", mesh=mesh, stream_rows=3)
assert p.method == "sharded_pallas" and p.stream_rows == 3
r = p.forward(f)
assert (np.asarray(r) == dprt_oracle_np(np.asarray(f))).all()
assert (np.asarray(p.inverse(r)) == np.asarray(f)).all()
# twice-scattered pipeline (psum_scatter fwd collective + image-row
# scatter on the close) reconstructs exactly
out = projection_pipeline_sharded(f, mesh, op="none")
assert (np.asarray(out) == np.asarray(f)).all()
print("OK")
""")

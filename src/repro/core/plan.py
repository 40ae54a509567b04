"""Transform plans: the single dispatch point for every DPRT in the repo.

Two layers live here:

1. **Backend registry.**  Each transform strategy (``gather`` /
   ``horner`` / ``strips`` / ``pallas`` / the ``sharded`` and
   ``sharded_pallas`` shard_map paths from
   :mod:`repro.core.distributed`) registers a :class:`Backend`
   object declaring its capabilities -- batched-native, needs
   ``strip_rows``, takes ``m_block``, mesh-aware, supported dtype kinds
   -- plus uniform callables for the skew-sum core and the full
   forward/inverse transforms.  Every public entry point
   (:mod:`repro.core.dprt`, :mod:`repro.core.conv`,
   :mod:`repro.core.dft`, ``launch/serve.py``) resolves methods here,
   so there is exactly one ``method`` string -> implementation mapping
   in the repo.  ``method="auto"`` picks the best registered backend
   for (shape, dtype, batch, active mesh), consulting the
   :mod:`repro.kernels.tuning` table for block shapes.

2. **RadonPlan.**  A cached, frozen plan for arbitrary ``(H, W)`` or
   ``(B, H, W)`` inputs: the image is zero-embedded into the smallest
   prime ``P >= max(H, W)`` (:mod:`repro.core.geometry` records the
   pad), transformed by the resolved backend, and the inverse crops
   back -- so ``plan.inverse(plan.forward(f)) == f`` holds bit-exactly
   for any integer image.  Zero padding is exact by linearity: padded
   rows/columns contribute 0 to every projection sum and the exact
   integer inverse reproduces them as 0, so the crop discards only
   zeros.  Plans also carry the paper's Sec. III-C resource-fitting
   knobs: ``block_rows`` streams the strip decomposition (eq. 7-8)
   through a `lax.scan` so only one strip partial is live at a time,
   and ``block_batch`` streams batched stacks through the fused Pallas
   kernel in bounded-size chunks via `lax.map`.

Plans are cached by (shape, dtype, method, knobs, mesh) in a *bounded*
LRU cache -- building one is pure Python shape math, so repeat traffic
on the same geometry (the serving scenario) hits the cache; see
:func:`plan_cache_info` (which also reports evictions) and the
``REPRO_PLAN_CACHE_MAXSIZE`` environment variable.

:class:`RadonPlan` is registered as a JAX **pytree with zero leaves**
(the whole plan is static aux data), so plans can be closed over,
passed as `jit`/`vmap`/`shard_map` arguments, and nested in argument
pytrees without ever retracing: two calls with the same plan produce
the same treedef and hit the same executable.  The differentiable /
AOT-compiled operator surface on top of plans lives in
:mod:`repro.radon`.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import os
import threading
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from . import geometry as G
from .dprt import (accum_dtype_for, align_partial, strip_partial,
                   _skew_sum_gather, _skew_sum_horner, _skew_sum_strips)
from .spans import span
from repro.kernels.tuning import resolve_blocks

__all__ = [
    "Backend",
    "register_backend",
    "get_backend",
    "available_backends",
    "backend_capabilities",
    "select_backend",
    "RadonPlan",
    "get_plan",
    "plan_cache_info",
    "plan_cache_entries",
    "plan_cache_clear",
    "plan_cache_discard",
    "set_plan_cache_maxsize",
    "dispatch_skew_sum",
]


# ---------------------------------------------------------------------------
# backend registry
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Backend:
    """One transform strategy and its declared capabilities.

    All callables share the uniform keyword surface
    ``(x, *, strip_rows=None, m_block=None, mesh=None)`` (plus ``sign``
    for ``skew_sum``); adapters ignore knobs they do not use.  A ``None``
    ``forward_batched``/``inverse_batched`` means the dispatch wraps the
    single-image callable with `lax.map`/`vmap` (``batch_impl``).
    """

    name: str
    skew_sum: Callable
    forward: Callable
    inverse: Callable
    forward_batched: Optional[Callable] = None
    inverse_batched: Optional[Callable] = None
    skew_batched: Optional[Callable] = None  # (B, N, N) stacks in one call
    #: fused projection-domain pipeline (forward -> per-direction op ->
    #: inverse without materializing the projections): callable
    #: ``(fp, op, operand, operand_form, *, strip_rows, m_block, mesh)``
    #: on prime-domain inputs.  ``None`` means the dispatch runs the
    #: STAGED fallback (forward, 1-D stage, inverse as separate steps) --
    #: the rule every backend without the capability inherits.
    pipeline: Optional[Callable] = None
    batched_native: bool = False
    needs_strip_rows: bool = False
    takes_m_block: bool = False
    #: understands the ``stream_rows`` knob natively (the in-launch
    #: streamed-strip kernels).  Backends without it degrade a
    #: ``stream_rows`` request to the plan layer's scan-of-launches
    #: ``block_rows`` fallback -- same partial-sum algebra, bounded
    #: memory, just one launch per strip instead of one total.
    takes_stream_rows: bool = False
    mesh_aware: bool = False
    dtype_kinds: Optional[Tuple[str, ...]] = None  # None = any dtype
    priority: int = 0  # higher wins under method="auto"
    note: str = ""

    def supports_dtype(self, dtype) -> bool:
        if self.dtype_kinds is None:
            return True
        return jnp.dtype(dtype).kind in self.dtype_kinds


_REGISTRY: dict = {}


def register_backend(backend: Backend) -> Backend:
    """Register (or replace) a backend; returns it for chaining."""
    _REGISTRY[backend.name] = backend
    if "_cached_plan" in globals():  # cached plans may pin a stale choice
        plan_cache_clear()           # (guard: built-ins register first)
    return backend


def get_backend(name: str) -> Backend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown method {name!r}; registered backends: "
            f"{available_backends()} (or 'auto')") from None


def available_backends() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def backend_capabilities() -> list:
    """Capability rows (dicts) for docs, ``serve --list-backends``, tests."""
    rows = []
    for name in available_backends():
        b = _REGISTRY[name]
        rows.append({
            "name": b.name,
            "batched_native": b.batched_native,
            "needs_strip_rows": b.needs_strip_rows,
            "takes_m_block": b.takes_m_block,
            "stream": b.takes_stream_rows,
            "mesh_aware": b.mesh_aware,
            "pipeline": b.pipeline is not None,
            "dtypes": "any" if b.dtype_kinds is None
                      else ",".join(b.dtype_kinds),
            "priority": b.priority,
            "note": b.note,
        })
    return rows


def _active_mesh():
    """The ambient `with mesh:` context's mesh, if any (best effort)."""
    try:  # pragma: no cover - exercised only under an active mesh
        from jax._src.mesh import thread_resources
        m = thread_resources.env.physical_mesh
        if m is not None and not m.empty and m.size > 1:
            return m
    except Exception:
        pass
    return None


def select_backend(n: int, dtype, batch: Optional[int] = None,
                   mesh=None) -> str:
    """``method="auto"``: best registered backend for the call site.

    An explicit mesh routes to the highest-priority mesh-aware backend
    whose dtype capability matches (with the shipped registry:
    ``sharded_pallas`` -- the per-shard fused-kernel path -- for every
    int/float image, falling back to the legacy ``sharded``); otherwise
    the highest-priority non-mesh backend wins -- the fused ``pallas``
    kernel for every int/float image, falling back to ``horner``.
    Block shapes come from :mod:`repro.kernels.tuning` at plan-build
    time.  (Ambient ``with mesh:`` contexts are resolved by the
    *callers* -- :func:`get_plan` and the public transform wrappers --
    before any cache, so a cached decision is never pinned to a stale
    context.)
    """
    best = None
    for name in available_backends():
        b = _REGISTRY[name]
        if b.mesh_aware != (mesh is not None) or not b.supports_dtype(dtype):
            continue
        if best is None or b.priority > best.priority:
            best = b
    if best is None:
        raise ValueError(f"no registered backend supports dtype {dtype}"
                         + (" under a mesh" if mesh is not None else ""))
    return best.name


# ---------------------------------------------------------------------------
# shared transform epilogues (the only copies in the repo)
# ---------------------------------------------------------------------------
def _attach_row_sum(core: jnp.ndarray, f: jnp.ndarray) -> jnp.ndarray:
    """Append the R(N, d) = sum_j f(d, j) projection row.

    Rank-polymorphic: (N, N) images or (…, N, N) stacks alike (the
    batched-native mesh backends ride the same epilogue)."""
    last = f.astype(core.dtype).sum(axis=-1)[..., None, :]
    return jnp.concatenate([core, last], axis=-2)


def _inverse_epilogue(z: jnp.ndarray, r: jnp.ndarray, n: int) -> jnp.ndarray:
    """-S + R(N, i) correction and the exact divide-by-N (paper eq. 3-4).

    Rank-polymorphic: accepts (N+1, N) or batched (…, N+1, N) stacks."""
    acc = z.dtype
    s = r[..., 0, :].astype(acc).sum(axis=-1)[..., None, None]
    num = z - s + r[..., n, :].astype(acc)[..., :, None]
    if jnp.issubdtype(acc, jnp.integer):
        return num // n
    return num / n


def _make_forward(skew: Callable) -> Callable:
    def fwd(f, *, strip_rows=None, m_block=None, mesh=None):
        core = skew(f, +1, strip_rows=strip_rows, m_block=m_block, mesh=mesh)
        return _attach_row_sum(core, f)
    return fwd


def _make_inverse(skew: Callable) -> Callable:
    def inv(r, *, strip_rows=None, m_block=None, mesh=None):
        n = r.shape[-1]
        z = skew(r[:n], -1, strip_rows=strip_rows, m_block=m_block, mesh=mesh)
        return _inverse_epilogue(z, r, n)
    return inv


# ---------------------------------------------------------------------------
# exact transposes (adjoints) of the two transforms
#
# The forward DPRT A : R^{NxN} -> R^{(N+1)xN} is linear, and so is the
# inverse B = A^{-1}.  Working out <A f, r> = <f, A^T r> entrywise:
#
#   (A^T r)[i, j]  = sum_{m<N} r(m, <j - m*i>_N) + r(N, i)
#                  = skew_sum(r[:N], -1)[i, j] + r(N, i)
#   (B^T g)        = ( [skew_sum(g, +1) ; row-sums of g] - total(g)*E00 ) / N
#                  = ( A g - total(g) * (e_0 1^T) ) / N
#
# i.e. both adjoints are built from the SAME registry skew-sum primitive
# as the transforms themselves (with the sign flipped), so an "exact
# adjoint through backend X" is exact for every registered backend,
# including the fused Pallas kernels.  These epilogues are
# rank-polymorphic: they accept (N+1, N) / (N, N) or batched stacks.
# ---------------------------------------------------------------------------
def _adjoint_epilogue(z: jnp.ndarray, r: jnp.ndarray, n: int) -> jnp.ndarray:
    """z = skew_sum(r[..., :N, :], -1); add the row-sum row's transpose."""
    return z + r[..., n, :].astype(z.dtype)[..., :, None]


def _inverse_adjoint_epilogue(core: jnp.ndarray, g: jnp.ndarray,
                              n: int) -> jnp.ndarray:
    """core = skew_sum(g, +1); build (A g - total(g) E00) / N."""
    acc = core.dtype
    rowsum = g.astype(acc).sum(axis=-1)[..., None, :]      # (…, 1, N)
    out = jnp.concatenate([core, rowsum], axis=-2)          # = A g
    total = g.astype(acc).sum(axis=(-2, -1))
    out = out.at[..., 0, :].add(-total[..., None])
    if jnp.issubdtype(acc, jnp.integer):
        # matches the inverse's floor-division convention; the true
        # adjoint of the float inverse is the float path below
        return out // n
    return out / n


# ---------------------------------------------------------------------------
# built-in backends
# ---------------------------------------------------------------------------
def _gather_skew(g, sign, *, strip_rows=None, m_block=None, mesh=None):
    return _skew_sum_gather(g, sign)


def _horner_skew(g, sign, *, strip_rows=None, m_block=None, mesh=None):
    return _skew_sum_horner(g, sign)


def _strips_skew(g, sign, *, strip_rows=None, m_block=None, mesh=None):
    if strip_rows is None:  # plan-level resolution supplies the tuned H;
        # direct callers get the same table lookup (real accum itemsize)
        itemsize = jnp.dtype(
            accum_dtype_for(g.dtype, g.shape[-1], warn=False)).itemsize
        strip_rows = resolve_blocks(g.shape[-1], itemsize)[0]
    return _skew_sum_strips(g, sign, strip_rows)


def _pallas_skew(g, sign, *, strip_rows=None, m_block=None, mesh=None,
                 stream_rows=None):
    from repro.kernels.ops import skew_sum_pallas  # lazy: no import cycle
    return skew_sum_pallas(g, sign, strip_rows=strip_rows, m_block=m_block,
                           stream_rows=stream_rows)


# the pallas skew wrapper accepts (N, N) and (B, N, N) alike, so the
# batched-native adjoint datapaths reuse the same adapter
_pallas_skew_batched = _pallas_skew


def _pallas_forward(f, *, strip_rows=None, m_block=None, mesh=None,
                    stream_rows=None):
    from repro.kernels.ops import dprt_pallas
    return dprt_pallas(f, strip_rows=strip_rows, m_block=m_block,
                       stream_rows=stream_rows)


def _pallas_inverse(r, *, strip_rows=None, m_block=None, mesh=None,
                    stream_rows=None):
    from repro.kernels.ops import idprt_pallas
    return idprt_pallas(r, strip_rows=strip_rows, m_block=m_block,
                        stream_rows=stream_rows)


def _pallas_pipeline(fp, op, operand, operand_form, *, strip_rows=None,
                     m_block=None, mesh=None, stream_rows=None):
    # m_block here is the PIPELINE direction block (its own tune table),
    # distinct from the transform kernels' m_block; plan-level callers
    # pass None and let the pipeline table decide
    from repro.kernels.ops import projection_pipeline_pallas
    return projection_pipeline_pallas(fp, op, operand,
                                      operand_form=operand_form)


def _require_mesh(mesh):
    if mesh is None:
        raise ValueError(
            "the 'sharded' backend needs mesh= (jax.sharding.Mesh); "
            "pass it explicitly or select it via an active `with mesh:`")
    return mesh


def _mesh_axis(mesh) -> str:
    """Row-sharding axis: 'model' if present, else the mesh's first axis."""
    if "model" in mesh.shape:
        return "model"
    return next(iter(mesh.shape))


def _sharded_skew(g, sign, *, strip_rows=None, m_block=None, mesh=None):
    from .distributed import _skew_sum_sharded
    mesh = _require_mesh(mesh)
    return _skew_sum_sharded(g, mesh, axis=_mesh_axis(mesh), sign=sign)


def _sharded_forward(f, *, strip_rows=None, m_block=None, mesh=None):
    from .distributed import dprt_sharded
    mesh = _require_mesh(mesh)
    return dprt_sharded(f, mesh, axis=_mesh_axis(mesh))


def _sharded_inverse(r, *, strip_rows=None, m_block=None, mesh=None):
    from .distributed import idprt_sharded
    mesh = _require_mesh(mesh)
    return idprt_sharded(r, mesh, axis=_mesh_axis(mesh))


def _sharded_forward_batched(fb, *, strip_rows=None, m_block=None, mesh=None):
    from .distributed import dprt_batch_sharded
    return dprt_batch_sharded(fb, _require_mesh(mesh))


def _sharded_inverse_batched(rb, *, strip_rows=None, m_block=None, mesh=None):
    from .distributed import idprt_batch_sharded
    return idprt_batch_sharded(rb, _require_mesh(mesh))


# the sharded_pallas entry points accept (N, N) and (B, N, N) alike, so
# one adapter each serves the single-image AND batched-native datapaths.
# The plan datapath pins reduce="psum": AOT executables chain forward ->
# inverse by exact input-sharding match, which needs the stable
# replicated projection layout (slicing the N+1 real rows off the
# direction-sharded padded layout re-lays-out anyway at operator
# geometry).  The direction-sharded default lives on the raw
# core.distributed API, where a round trip consumes the shards in place.
def _sharded_pallas_skew(g, sign, *, strip_rows=None, m_block=None,
                         mesh=None, stream_rows=None):
    from .distributed import skew_sum_sharded_pallas
    return skew_sum_sharded_pallas(g, _require_mesh(mesh), sign=sign,
                                   reduce="psum",
                                   strip_rows=strip_rows, m_block=m_block,
                                   stream_rows=stream_rows)


def _sharded_pallas_forward(f, *, strip_rows=None, m_block=None, mesh=None,
                            stream_rows=None):
    from .distributed import dprt_sharded_pallas
    return dprt_sharded_pallas(f, _require_mesh(mesh), reduce="psum",
                               strip_rows=strip_rows, m_block=m_block,
                               stream_rows=stream_rows)


def _sharded_pallas_inverse(r, *, strip_rows=None, m_block=None, mesh=None,
                            stream_rows=None):
    from .distributed import idprt_sharded_pallas
    return idprt_sharded_pallas(r, _require_mesh(mesh), reduce="psum",
                                strip_rows=strip_rows, m_block=m_block,
                                stream_rows=stream_rows)


def _sharded_pallas_pipeline(fp, op, operand, operand_form, *,
                             strip_rows=None, m_block=None, mesh=None,
                             stream_rows=None):
    from .distributed import projection_pipeline_sharded
    return projection_pipeline_sharded(fp, _require_mesh(mesh), op=op,
                                       operand=operand,
                                       strip_rows=strip_rows,
                                       m_block=m_block,
                                       stream_rows=stream_rows)


register_backend(Backend(
    name="gather",
    skew_sum=_gather_skew,
    forward=_make_forward(_gather_skew),
    inverse=_make_inverse(_gather_skew),
    priority=10,
    note="per-direction shear oracle (systolic analog)",
))
register_backend(Backend(
    name="horner",
    skew_sum=_horner_skew,
    forward=_make_forward(_horner_skew),
    inverse=_make_inverse(_horner_skew),
    priority=50,
    note="paper Sec. III-B shift-and-add dataflow",
))
register_backend(Backend(
    name="strips",
    skew_sum=_strips_skew,
    forward=_make_forward(_strips_skew),
    inverse=_make_inverse(_strips_skew),
    needs_strip_rows=True,
    priority=30,
    note="scalable SFDPRT strip decomposition (eq. 5-8)",
))
register_backend(Backend(
    name="pallas",
    skew_sum=_pallas_skew,
    forward=_pallas_forward,
    inverse=_pallas_inverse,
    forward_batched=_pallas_forward,   # same wrappers take (B, N, N)
    inverse_batched=_pallas_inverse,
    skew_batched=_pallas_skew_batched,
    pipeline=_pallas_pipeline,
    batched_native=True,
    takes_m_block=True,
    takes_stream_rows=True,
    dtype_kinds=("i", "u", "f"),
    priority=100,
    note="fused batched SFDPRT TPU kernel (one pallas_call per stack)",
))
register_backend(Backend(
    name="sharded",
    skew_sum=_sharded_skew,
    forward=_sharded_forward,
    inverse=_sharded_inverse,
    forward_batched=_sharded_forward_batched,
    inverse_batched=_sharded_inverse_batched,
    mesh_aware=True,
    priority=0,  # mesh-only; sharded_pallas outranks it under auto
    note="legacy shard_map super-strips (Horner scan) + one psum",
))
register_backend(Backend(
    name="sharded_pallas",
    skew_sum=_sharded_pallas_skew,
    forward=_sharded_pallas_forward,
    inverse=_sharded_pallas_inverse,
    forward_batched=_sharded_pallas_forward,   # same wrappers take (B, …)
    inverse_batched=_sharded_pallas_inverse,
    skew_batched=_sharded_pallas_skew,
    pipeline=_sharded_pallas_pipeline,
    batched_native=True,
    takes_m_block=True,
    takes_stream_rows=True,
    mesh_aware=True,
    dtype_kinds=("i", "u", "f"),
    priority=20,  # mesh-only: beats legacy "sharded" under method="auto"
    note="per-shard fused SFDPRT pallas kernel + one psum "
         "(mesh data x model; core/distributed.py)",
))


# ---------------------------------------------------------------------------
# blocked (resource-fitting) execution helpers
# ---------------------------------------------------------------------------
def _blocked_skew_sum(gmat: jnp.ndarray, sign: int, block_rows: int,
                      acc_dtype) -> jnp.ndarray:
    """Strip decomposition streamed through `lax.scan` (bounded memory).

    Identical algebra to ``method="strips"`` (partial Horner per strip,
    one alignment roll, accumulate -- paper eq. 7-8) but only ONE strip
    partial is live at a time instead of all ceil(N/H) of them: the
    Sec. III-C "fit the architecture to available resources" scheme.
    """
    n = gmat.shape[-1]
    h = int(block_rows)
    if h < 1:
        raise ValueError(f"block_rows must be >= 1, got {h}")
    k = math.ceil(gmat.shape[0] / h)
    gp = jnp.pad(gmat, ((0, k * h - gmat.shape[0]), (0, 0)))
    strips = gp.reshape(k, h, n)
    offsets = jnp.arange(k, dtype=jnp.int32) * h

    def step(acc, xs):
        s, off = xs
        u = strip_partial(s, n, sign=sign, acc_dtype=acc_dtype)
        return acc + align_partial(u, off, sign), None

    acc0 = jnp.zeros((n, n), acc_dtype)
    acc, _ = jax.lax.scan(step, acc0, (strips, offsets))
    return acc


def _map_chunk_pairs(fn: Callable, xb: jnp.ndarray, wb: jnp.ndarray,
                     chunk: int) -> jnp.ndarray:
    """`_map_chunks` for a paired (image stack, batched operand): both
    chunk together so e.g. a fused conv against per-image kernels keeps
    the ``block_batch`` memory bound."""
    b = xb.shape[0]
    chunk = int(chunk)
    if chunk < 1:
        raise ValueError(f"block_batch must be >= 1, got {chunk}")
    if chunk >= b:
        return fn(xb, wb)
    nb = math.ceil(b / chunk)
    pad = nb * chunk - b
    xp = jnp.pad(xb, ((0, pad),) + ((0, 0),) * (xb.ndim - 1))
    wp = jnp.pad(wb, ((0, pad),) + ((0, 0),) * (wb.ndim - 1))
    out = jax.lax.map(lambda xw: fn(*xw),
                      (xp.reshape(nb, chunk, *xb.shape[1:]),
                       wp.reshape(nb, chunk, *wb.shape[1:])))
    return out.reshape(nb * chunk, *out.shape[2:])[:b]


def _map_chunks(fn: Callable, xb: jnp.ndarray, chunk: int) -> jnp.ndarray:
    """Apply a stack-level ``fn`` over batch chunks via `lax.map`.

    Bounds live memory to one ``chunk``-sized stack (plus the output);
    zero-image padding on the last chunk is sliced back off.
    """
    b = xb.shape[0]
    chunk = int(chunk)
    if chunk < 1:
        raise ValueError(f"block_batch must be >= 1, got {chunk}")
    if chunk >= b:
        return fn(xb)
    nb = math.ceil(b / chunk)
    pad = nb * chunk - b
    xp = jnp.pad(xb, ((0, pad),) + ((0, 0),) * (xb.ndim - 1))
    out = jax.lax.map(fn, xp.reshape(nb, chunk, *xb.shape[1:]))
    return out.reshape(nb * chunk, *out.shape[2:])[:b]


# ---------------------------------------------------------------------------
# RadonPlan
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class RadonPlan:
    """A resolved, cached recipe for transforming one input geometry.

    ``forward`` embeds into the prime domain and returns the
    ``(…, P+1, P)`` projections; ``inverse`` reconstructs and crops back
    to ``(…, H, W)``.  Bit-exact round trip for integer images by
    construction (zero pad in, zero pad out, crop).
    """

    geometry: G.Geometry
    method: str                      # resolved backend name (never "auto")
    requested_method: str            # what the caller asked for
    strip_rows: Optional[int] = None
    m_block: Optional[int] = None
    batch_impl: str = "auto"
    block_rows: Optional[int] = None
    #: stream H-row strips through ONE fused kernel launch (VMEM scratch
    #: accumulation / double-buffered HBM DMA) on backends declaring
    #: ``takes_stream_rows``; other backends degrade to the
    #: ``block_rows``-style scan with the same strip height.
    stream_rows: Optional[int] = None
    block_batch: Optional[int] = None
    mesh: Optional[object] = None
    # part of the plan's identity (eq/hash) so the per-plan caches
    # downstream (jitted appliers, AOT executables, trace counters) are
    # exactly as granular as the plan cache itself: evicting one
    # dtype's plan can never drop a different dtype's live state
    dtype_name: Optional[str] = None

    @property
    def backend(self) -> Backend:
        return get_backend(self.method)

    def _knobs(self) -> dict:
        knobs = {"strip_rows": self.strip_rows, "m_block": self.m_block,
                 "mesh": self.mesh}
        if self.backend.takes_stream_rows:
            knobs["stream_rows"] = self.stream_rows
        return knobs

    @property
    def _scan_rows(self) -> Optional[int]:
        """Strip height when the scan-of-launches fallback must run.

        An explicit ``block_rows`` always scans (the paper's staged
        Sec. III-C scheme); ``stream_rows`` on a backend WITHOUT the
        streamed-kernel capability degrades to the same scan -- memory
        stays bounded either way, capable backends just do it in one
        launch.  ``None`` means the resolved backend runs natively.
        """
        if self.block_rows is not None:
            return self.block_rows
        if self.stream_rows is not None \
                and not self.backend.takes_stream_rows:
            return self.stream_rows
        return None

    def _batch_impl(self) -> str:
        if self.batch_impl != "auto":
            return self.batch_impl
        # Measured (EXPERIMENTS.md §Perf): on CPU `lax.map` hits the
        # 16x-single ideal while vmap pays +60%; on TPU vmap wins.
        return "map" if jax.default_backend() == "cpu" else "vmap"

    # -- prime-domain single image ----------------------------------------
    def _forward_prime(self, fp: jnp.ndarray) -> jnp.ndarray:
        if self._scan_rows is not None:
            core = _blocked_skew_sum(fp, +1, self._scan_rows,
                                     accum_dtype_for(fp.dtype, fp.shape[-1]))
            return _attach_row_sum(core, fp)
        return self.backend.forward(fp, **self._knobs())

    def _inverse_prime(self, r: jnp.ndarray) -> jnp.ndarray:
        if self._scan_rows is not None:
            n = r.shape[-1]
            acc = accum_dtype_for(r.dtype, n)
            z = _blocked_skew_sum(r[:n], -1, self._scan_rows, acc)
            return _inverse_epilogue(z, r, n)
        return self.backend.inverse(r, **self._knobs())

    def _skew_prime(self, x: jnp.ndarray, sign: int) -> jnp.ndarray:
        if self._scan_rows is not None:
            return _blocked_skew_sum(x, sign, self._scan_rows,
                                     accum_dtype_for(x.dtype, x.shape[-1]))
        return self.backend.skew_sum(x, sign, **self._knobs())

    def _adjoint_prime(self, r: jnp.ndarray) -> jnp.ndarray:
        n = self.geometry.prime
        return _adjoint_epilogue(self._skew_prime(r[:n], -1), r, n)

    def _inverse_adjoint_prime(self, g: jnp.ndarray) -> jnp.ndarray:
        return _inverse_adjoint_epilogue(self._skew_prime(g, +1), g,
                                         self.geometry.prime)

    # -- batched stacks ----------------------------------------------------
    def _stack(self, xb: jnp.ndarray, native: Optional[Callable],
               one: Callable) -> jnp.ndarray:
        if native is not None and self._scan_rows is None:
            fn = lambda chunk: native(chunk, **self._knobs())
        elif self._batch_impl() == "map":
            fn = lambda chunk: jax.lax.map(one, chunk)
        else:
            fn = lambda chunk: jax.vmap(one)(chunk)
        if self.block_batch is not None:
            return _map_chunks(fn, xb, self.block_batch)
        return fn(xb)

    # -- public ------------------------------------------------------------
    def forward(self, f: jnp.ndarray) -> jnp.ndarray:
        """(…, H, W) image(s) -> (…, P+1, P) exact projections."""
        g = self.geometry
        if f.shape != g.image_shape:
            raise ValueError(
                f"plan built for {g.image_shape}, got image {f.shape}")
        fp = G.embed(f, g)
        if not g.batched:
            return self._forward_prime(fp)
        be = self.backend
        if be.mesh_aware and be.forward_batched is None:
            raise ValueError(f"{be.name} has no batched forward")
        native = (be.forward_batched
                  if be.batched_native or be.mesh_aware else None)
        return self._stack(fp, native, self._forward_prime)

    def inverse(self, r: jnp.ndarray) -> jnp.ndarray:
        """(…, P+1, P) projections -> (…, H, W) exact reconstruction."""
        g = self.geometry
        if r.shape != g.transform_shape:
            raise ValueError(
                f"plan expects projections {g.transform_shape}, "
                f"got {r.shape}")
        if not g.batched:
            return G.crop(self._inverse_prime(r), g)
        be = self.backend
        # mesh-aware backends with a batched-native inverse (both sharded
        # paths, via dprt/idprt_batch_sharded or the per-shard kernel) go
        # native; anything else takes the generic _stack path (map/vmap
        # of the single-image inverse).  block_batch chunking respected.
        native = (be.inverse_batched
                  if be.batched_native or be.mesh_aware else None)
        return G.crop(self._stack(r, native, self._inverse_prime), g)

    def adjoint(self, r: jnp.ndarray) -> jnp.ndarray:
        """Exact transpose of :meth:`forward`: (…, P+1, P) -> (…, H, W).

        ``adjoint`` is A^T for the *linear map* the plan's forward
        realizes (embed -> transform), so its adjoint crops back:
        crop == embed^T.  Distinct from :meth:`inverse` -- A^T A != I --
        and the VJP rule :mod:`repro.radon.autodiff` installs on every
        backend's forward.
        """
        g = self.geometry
        if r.shape != g.transform_shape:
            raise ValueError(
                f"plan adjoint expects projections {g.transform_shape}, "
                f"got {r.shape}")
        if not g.batched:
            return G.crop(self._adjoint_prime(r), g)
        be = self.backend
        native = None
        if be.skew_batched is not None and self._scan_rows is None:
            n = g.prime

            def native(rb, **knobs):
                z = be.skew_batched(rb[:, :n], -1, **knobs)
                return _adjoint_epilogue(z, rb, n)

        return G.crop(self._stack(r, native, self._adjoint_prime), g)

    def inverse_adjoint(self, f: jnp.ndarray) -> jnp.ndarray:
        """Exact transpose of :meth:`inverse`: (…, H, W) -> (…, P+1, P).

        (A^{-1})^T = (A^T)^{-1}; realized as (A g - total(g) E00) / N
        from the same backend skew-sum, so the VJP through the inverse
        stays on the selected backend too.  Integer inputs follow the
        inverse's floor-division convention; use floats for the true
        adjoint (AD always does).
        """
        g = self.geometry
        if f.shape != g.image_shape:
            raise ValueError(
                f"plan inverse_adjoint expects image {g.image_shape}, "
                f"got {f.shape}")
        fp = G.embed(f, g)                  # embed == crop^T
        if not g.batched:
            return self._inverse_adjoint_prime(fp)
        be = self.backend
        native = None
        if be.skew_batched is not None and self._scan_rows is None:
            n = g.prime

            def native(fb, **knobs):
                return _inverse_adjoint_epilogue(
                    be.skew_batched(fb, +1, **knobs), fb, n)

        return self._stack(fp, native, self._inverse_adjoint_prime)

    # -- projection-domain pipeline ----------------------------------------
    def pipeline(self, f: jnp.ndarray, op: str = "conv",
                 operand: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        """Fused ``inverse(per_direction_op(forward(f)))`` -- transform,
        1-D projection-domain stage and inverse as ONE dispatch.

        ``op``: ``"conv"`` (per-direction 1-D circular convolution
        against a second operand -- exact 2-D circular convolution by the
        paper's Sec. VI property), ``"mul"`` (pointwise projection-domain
        multiply: the ``inv @ pointwise @ fwd`` operator fusion), or
        ``"none"`` (the fused round trip).  ``operand`` is the conv
        operand as a prime-domain image (``(P, P)`` shared or matching
        ``f``'s batch) or as projections/weights (``(…, P+1, P)``), with
        the form inferred from its trailing shape.

        Backends declaring the ``pipeline`` capability run it as a single
        kernel launch with the projections resident in VMEM/registers;
        every other backend (and any plan streaming strips through
        ``block_rows``) takes the STAGED fallback -- forward, exact 1-D
        stage, inverse through the same registry -- so results are
        bit-exact for integers either way.  ``"conv"`` needs native prime
        geometry (zero-embedding would change the convolution's torus;
        :mod:`repro.core.conv` folds non-native geometries before
        dispatching here); ``"mul"``/``"none"`` fuse the literal
        embed -> transform -> weight -> inverse -> crop composition, so
        any geometry is accepted.
        """
        g = self.geometry
        if op not in ("none", "mul", "conv"):
            raise ValueError(f"pipeline op must be none|mul|conv: {op!r}")
        if f.shape != g.image_shape:
            raise ValueError(
                f"plan built for {g.image_shape}, got image {f.shape}")
        if op == "conv" and not g.native:
            raise ValueError(
                f"conv pipeline needs native square prime geometry, plan "
                f"is {g.image_shape} embedded in P={g.prime}")
        p = g.prime
        operand_form = None
        if op != "none":
            if operand is None:
                raise ValueError(f"pipeline op {op!r} needs an operand")
            if op == "conv" and operand.shape[-2:] == (p, p):
                operand_form = "image"
            elif operand.shape[-2:] == (p + 1, p):
                operand_form = "proj"
            else:
                raise ValueError(
                    f"pipeline operand must be (…, {p}, {p}) images or "
                    f"(…, {p + 1}, {p}) projections/weights for op={op!r}, "
                    f"got {operand.shape}")
            if operand.ndim == 3 and g.batch not in (None, operand.shape[0]) \
                    and operand.shape[0] != 1:
                raise ValueError(
                    f"batched pipeline operand {operand.shape} does not "
                    f"match plan batch {g.batch}")

        be = self.backend
        if be.pipeline is not None and self.block_rows is None \
                and self.stream_rows is None:
            fp = G.embed(f, g)
            if g.batched and self.block_batch is not None:
                if operand is None or operand.ndim == 2:
                    out = _map_chunks(
                        lambda chunk: be.pipeline(chunk, op, operand,
                                                  operand_form,
                                                  **self._knobs()),
                        fp, self.block_batch)
                else:   # batched operand: chunk image and operand together
                    out = _map_chunk_pairs(
                        lambda chunk, wch: be.pipeline(chunk, op, wch,
                                                       operand_form,
                                                       **self._knobs()),
                        fp, operand, self.block_batch)
            else:
                out = be.pipeline(fp, op, operand, operand_form,
                                  **self._knobs())
            return G.crop(out, g)

        # staged fallback: same three stages, separate launches
        rf = self.forward(f)
        if op == "conv":
            if operand_form == "image":
                if operand.shape == g.image_shape:
                    rg = self.forward(operand)
                else:  # one shared (P, P) operand for a batched plan
                    rg = get_plan((p, p), self.dtype_name, self.method,
                                  strip_rows=self.strip_rows,
                                  m_block=self.m_block,
                                  mesh=self.mesh).forward(operand)
            else:
                rg = operand
            from .conv import circ_conv1d_exact  # lazy: conv imports radon
            rc = circ_conv1d_exact(rf, rg)
        elif op == "mul":
            rc = rf * operand.astype(rf.dtype)
        else:
            rc = rf
        return self.inverse(rc.astype(rf.dtype))

    def describe(self) -> dict:
        g = self.geometry
        return {
            "image_shape": g.image_shape,
            "prime": g.prime,
            "pad": (g.pad_rows, g.pad_cols),
            "native": g.native,
            "dtype": self.dtype_name,
            "method": self.method,
            "requested_method": self.requested_method,
            "strip_rows": self.strip_rows,
            "m_block": self.m_block,
            "block_rows": self.block_rows,
            "stream_rows": self.stream_rows,
            "block_batch": self.block_batch,
            "mesh": None if self.mesh is None else repr(self.mesh),
        }


# RadonPlan is a pytree with ZERO leaves: the whole plan is static aux
# data.  Plans therefore cross jit/vmap/shard_map boundaries as
# arguments or closures without contributing tracers, and the treedef
# (== the plan, by hash/eq of the frozen dataclass) becomes part of the
# trace-cache key -- same plan, same executable, no retrace.
jax.tree_util.register_pytree_node(
    RadonPlan,
    lambda plan: ((), plan),
    lambda plan, _: plan,
)


# ---------------------------------------------------------------------------
# plan construction + cache (bounded LRU)
# ---------------------------------------------------------------------------
PlanCacheInfo = collections.namedtuple(
    "PlanCacheInfo", ["hits", "misses", "maxsize", "currsize", "evictions"])


def _env_cache_maxsize() -> Optional[int]:
    """``REPRO_PLAN_CACHE_MAXSIZE``: plans kept live (<= 0 => unbounded)."""
    raw = os.environ.get("REPRO_PLAN_CACHE_MAXSIZE", "512")
    try:
        size = int(raw)
    except ValueError:
        raise ValueError(
            f"REPRO_PLAN_CACHE_MAXSIZE must be an integer, got {raw!r}")
    return None if size <= 0 else size


class _PlanLRU:
    """A small LRU with an eviction counter (``functools.lru_cache``
    reports hits/misses but not evictions, which is the number a
    long-running serve process actually alarms on).

    Eviction hooks let the downstream per-plan caches (the jitted
    differentiable appliers and AOT executables in :mod:`repro.radon`)
    release their -- much heavier -- state in lockstep, so bounding THIS
    cache actually bounds the process."""

    def __init__(self, maxsize: Optional[int]):
        self.maxsize = maxsize
        self._data: "collections.OrderedDict" = collections.OrderedDict()
        self._lock = threading.Lock()
        self._evict_hooks: list = []
        self.hits = self.misses = self.evictions = 0

    def add_evict_hook(self, fn: Callable) -> None:
        """``fn(plan)`` is called for every plan dropped from the cache
        (eviction, resize, or clear)."""
        self._evict_hooks.append(fn)

    def _shrink_locked(self) -> list:
        dropped = []
        while self.maxsize is not None and len(self._data) > self.maxsize:
            dropped.append(self._data.popitem(last=False)[1])
            self.evictions += 1
        return dropped

    def _fire(self, dropped: list) -> None:
        for plan in dropped:        # outside the lock: hooks may be slow
            for fn in self._evict_hooks:
                fn(plan)

    def get_or_build(self, key, builder: Callable):
        with self._lock:
            if key in self._data:
                self._data.move_to_end(key)
                self.hits += 1
                return self._data[key]
        value = builder()          # build outside the lock (pure python)
        with self._lock:
            if key in self._data:  # racer built it first: keep theirs
                self._data.move_to_end(key)
                return self._data[key]
            self.misses += 1
            self._data[key] = value
            dropped = self._shrink_locked()
        self._fire(dropped)
        return value

    def info(self) -> PlanCacheInfo:
        with self._lock:
            return PlanCacheInfo(self.hits, self.misses, self.maxsize,
                                 len(self._data), self.evictions)

    def values(self) -> list:
        with self._lock:
            return list(self._data.values())

    def clear(self) -> None:
        with self._lock:
            dropped = list(self._data.values())
            self._data.clear()
        self._fire(dropped)

    def discard(self, plans) -> int:
        """Drop exactly the given plans (if cached), firing the evict
        hooks for each -- the targeted form of eviction the serving
        router uses to release one retired geometry's executables
        without disturbing its neighbours."""
        wanted = {id(p) for p in plans}
        with self._lock:
            keys = [k for k, v in self._data.items() if id(v) in wanted]
            dropped = [self._data.pop(k) for k in keys]
            self.evictions += len(dropped)
        self._fire(dropped)
        return len(dropped)

    def resize(self, maxsize: Optional[int]) -> None:
        with self._lock:
            self.maxsize = maxsize
            dropped = self._shrink_locked()
        self._fire(dropped)


_PLAN_CACHE = _PlanLRU(_env_cache_maxsize())


def add_plan_evict_hook(fn: Callable) -> None:
    """Register ``fn(plan)`` to run whenever a plan leaves the cache --
    the mechanism the radon layer uses to drop jitted appliers and AOT
    executables for geometries the bounded cache has let go."""
    _PLAN_CACHE.add_evict_hook(fn)


def set_plan_cache_maxsize(maxsize: Optional[int]) -> None:
    """Re-bound the plan cache (None or <= 0 => unbounded); evicts LRU
    entries immediately if the new bound is tighter."""
    if maxsize is not None and maxsize <= 0:
        maxsize = None
    _PLAN_CACHE.resize(maxsize)


def _cached_plan(shape: tuple, dtype_name: str, method: str,
                 strip_rows: Optional[int], m_block: Optional[int],
                 batch_impl: str, block_rows: Optional[int],
                 stream_rows: Optional[int],
                 block_batch: Optional[int], mesh) -> RadonPlan:
    key = (shape, dtype_name, method, strip_rows, m_block, batch_impl,
           block_rows, stream_rows, block_batch, mesh)

    def build() -> RadonPlan:
        # runs on a miss only; hits and misses stay in plan_cache_info()
        with span("radon.plan", shape=shape, dtype=dtype_name,
                  method=method, strip_rows=strip_rows, m_block=m_block,
                  batch_impl=batch_impl, block_rows=block_rows,
                  stream_rows=stream_rows, block_batch=block_batch,
                  mesh=None if mesh is None else dict(mesh.shape)):
            return _build_plan(*key)
    return _PLAN_CACHE.get_or_build(key, build)


def _build_plan(shape: tuple, dtype_name: str, method: str,
                strip_rows: Optional[int], m_block: Optional[int],
                batch_impl: str, block_rows: Optional[int],
                stream_rows: Optional[int],
                block_batch: Optional[int], mesh) -> RadonPlan:
    geom = G.normalize_geometry(shape)
    dtype = jnp.dtype(dtype_name)
    requested = method
    if method == "auto":
        method = select_backend(geom.prime, dtype, batch=geom.batch,
                                mesh=mesh)
    be = get_backend(method)
    if not be.supports_dtype(dtype):
        raise ValueError(
            f"backend {be.name!r} does not support dtype {dtype_name} "
            f"(kinds: {be.dtype_kinds})")
    if batch_impl not in ("auto", "map", "vmap"):
        raise ValueError(f"batch_impl must be auto|map|vmap: {batch_impl!r}")
    # warn=False: sizing only -- a plan built for block-shape metadata
    # (e.g. to hand its geometry to the float-promoting solver) must not
    # claim an integer-accumulator overflow that never runs
    itemsize = jnp.dtype(
        accum_dtype_for(dtype, geom.prime, warn=False)).itemsize
    # always resolves (even for backends without block knobs): the
    # resolver owns the block_rows/stream_rows conflict rejection
    th, tm = resolve_blocks(geom.prime, itemsize, strip_rows, m_block,
                            block_rows=block_rows, stream_rows=stream_rows)
    if be.needs_strip_rows or be.takes_m_block:
        strip_rows = th
        m_block = tm if be.takes_m_block else None
    return RadonPlan(geometry=geom, method=method, requested_method=requested,
                     strip_rows=strip_rows, m_block=m_block,
                     batch_impl=batch_impl, block_rows=block_rows,
                     stream_rows=stream_rows, block_batch=block_batch,
                     mesh=mesh, dtype_name=dtype.name)


def get_plan(shape, dtype, method: str = "auto", *,
             strip_rows: Optional[int] = None,
             m_block: Optional[int] = None,
             batch_impl: str = "auto",
             block_rows: Optional[int] = None,
             stream_rows: Optional[int] = None,
             block_batch: Optional[int] = None,
             mesh=None) -> RadonPlan:
    """Cached :class:`RadonPlan` for an input shape/dtype and knobs.

    An ambient ``with mesh:`` context is resolved HERE, before the
    lru-cache lookup, so the context participates in the effective cache
    key -- a plan built outside a mesh is never returned inside one (or
    vice versa).
    """
    if method == "auto" and mesh is None:
        mesh = _active_mesh()
    shape = tuple(int(s) for s in shape)
    return _cached_plan(shape, jnp.dtype(dtype).name, method,
                        None if strip_rows is None else int(strip_rows),
                        None if m_block is None else int(m_block),
                        batch_impl,
                        None if block_rows is None else int(block_rows),
                        None if stream_rows is None else int(stream_rows),
                        None if block_batch is None else int(block_batch),
                        mesh)


def plan_cache_info() -> PlanCacheInfo:
    """(hits, misses, maxsize, currsize, evictions) of the plan cache."""
    return _PLAN_CACHE.info()


def plan_cache_entries() -> list:
    """``describe()`` dicts for every live cached plan, LRU-oldest first
    -- the geometry census a serving process reports in its health
    endpoint (which geometries are warm, with which backend/knobs)."""
    return [plan.describe() for plan in _PLAN_CACHE.values()]


def plan_cache_clear() -> None:
    _PLAN_CACHE.clear()


def plan_cache_discard(plans) -> int:
    """Evict exactly the given plans from the cache, firing the same
    evict hooks as LRU pressure would -- so their jitted appliers and
    AOT executables are released in lockstep.  Returns how many were
    actually cached.  The serving router calls this when it retires a
    cold geometry, passing only plans no surviving route shares."""
    return _PLAN_CACHE.discard(plans)


def dispatch_skew_sum(g: jnp.ndarray, sign: int, method: str = "horner",
                      strip_rows: Optional[int] = None,
                      m_block: Optional[int] = None, mesh=None) -> jnp.ndarray:
    """Registry-routed skew-sum primitive (prime-domain (N, N) input)."""
    n = g.shape[-1]
    if method == "auto":
        method = select_backend(n, g.dtype, mesh=mesh)
    be = get_backend(method)
    if be.needs_strip_rows and strip_rows is None:
        itemsize = jnp.dtype(
            accum_dtype_for(g.dtype, n, warn=False)).itemsize
        strip_rows = resolve_blocks(n, itemsize, None, None)[0]
    return be.skew_sum(g, sign, strip_rows=strip_rows, m_block=m_block,
                       mesh=mesh)

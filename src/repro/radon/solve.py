"""Iterative reconstruction over DPRT operators: ``radon.solve``.

The paper's motivating application is reconstruction from projections.
With exact transforms, exact adjoints, and the fused projection-domain
pipeline already in place, weighted/partial-data least squares

    min_x || d * (A x - b) ||_2^2,      d = mask * weight

closes the loop.  This module is the solver subsystem:

* **Sherman-Morrison fast path** (``method="sherman"``, the ``"auto"``
  choice when nothing is masked): the frame identity
  ``A^T A = P I + 1 1^T`` (tested at small N since PR 4) inverts in
  closed form,

      (P I + 1 1^T)^{-1} y = y/P - sum(y) / (P (P + H W)),

  so the unmasked least-squares solution is ONE adjoint plus a rank-1
  correction -- no iteration (``iterations == 0``).
* **CG on the normal equations** (``method="cg"``, the masked
  default): each application of ``M^T M`` is one fused
  ``pipeline("mul", d^2)`` launch plus a column-sum reduction
  (:meth:`repro.radon.masking.MaskedDPRT.normal_apply`), optionally
  preconditioned by the exact unmasked inverse (``precond="sherman"``,
  SPD) or a :class:`~repro.radon.ProjectionFilter` /  ``(…, P+1, P)``
  weight array riding the same fused pipeline (flexible PCG: a filter
  preconditioner is not guaranteed SPD -- convergence is then
  heuristic, the residual history is the audit trail).
* **LSQR** (Golub-Kahan bidiagonalization on ``M = D A`` itself) and
  **Landweber** (``x += tau (M^T b_w - M^T M x)``, default step
  ``tau = 1 / (max(d)^2 (P + H W))`` from the exact spectral bound
  ``||A||^2 = P + H W``) complete the classic trio.

Solver bodies are ``lax.while_loop``s under ``jit``, cached per
``(plan, method, maxiter, precond-kind)`` in the same per-plan store as
the transform appliers -- one trace per geometry
(:func:`repro.radon.retrace_guard`-clean), batched over ``(B, H, W)``
stacks, mesh-capable through the ordinary plan dispatch.  Results come
back as a :class:`SolveResult` ``(image, residual_norms, iterations,
converged)`` with a NaN-padded relative residual history.

Differentiation: at convergence the solve is the *linear* map
``b -> G^+ A^T D^2 b`` (``G = M^T M`` symmetric), so its JVP is the
solver applied to the tangent sinogram and its transpose is
``ct -> d^2 * A (G^+ ct)`` -- staged through ``linear_call`` exactly
like :mod:`repro.radon.autodiff` stages the raw transforms.  Gradients
are implicit-function-theorem exact at convergence (run tight ``tol``
when comparing against finite differences); masks, weights and
preconditioners are non-differentiable inputs and raise if perturbed.
Integer sinograms promote to :func:`repro.core.dprt.float_dtype_for`
before any plan arithmetic, so the int64-under-x64 accumulator warning
can never fire for a solve.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.custom_derivatives import linear_call

from .autodiff import _CACHE_LOCK, _JITTED, _note_trace
from .fusion import _is_zero_tangent
from .masking import MaskedDPRT
from .operators import (_AOT_CACHE, _AOT_PINS, _compile_once,
                        _export_compiled, _import_compiled,
                        _topology_token, DPRT, ProjectionFilter)

__all__ = ["METHODS", "SolveResult", "solve", "solve_operator",
           "ReconstructionOperator"]

#: registered solve methods; "auto" resolves to sherman (unmasked) / cg
METHODS = ("sherman", "cg", "lsqr", "landweber")


class SolveResult(NamedTuple):
    """The reconstruction and its convergence record.

    ``image``: the (…, H, W) solution.  ``residual_norms``: relative
    residual history, shape ``(maxiter + 1, *batch)`` -- entry 0 is 1.0,
    entry k the norm after k iterations scaled by the initial one,
    ``NaN`` past the final iteration (direct methods record
    ``[1.0, final]``).  ``iterations``: int32 count taken.
    ``converged``: scalar bool, every batch element within ``tol``.
    """
    image: jnp.ndarray
    residual_norms: jnp.ndarray
    iterations: jnp.ndarray
    converged: jnp.ndarray


def _bdot(u: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """Per-batch-element inner product over the trailing two axes."""
    return (u * v).sum(axis=(-2, -1))


def _bnorm(v: jnp.ndarray) -> jnp.ndarray:
    return jnp.sqrt(_bdot(v, v))


def _bx(s: jnp.ndarray) -> jnp.ndarray:
    """Broadcast a (*batch,) scalar field over the trailing two axes."""
    return s[..., None, None]


# ---------------------------------------------------------------------------
# the jitted, differentiable solver bodies (cached per plan, like the
# transform appliers -- entries drop with plan-cache evictions)
# ---------------------------------------------------------------------------
def _jitted_solve(plan, method: str, maxiter: int, precond_kind: str):
    key = (plan, ("solve", method, int(maxiter), precond_kind))
    with _CACHE_LOCK:
        cached = _JITTED.get(key)
    if cached is not None:
        return cached

    geom = plan.geometry
    p = geom.prime
    h, w = geom.image_shape[-2:]
    hw = h * w
    maxiter = int(maxiter)

    def atd(r):
        """A^T r = P * B r + S(r) * 1 (adjoint via the inverse epilogue
        identity; see :mod:`repro.radon.masking`)."""
        s = r[..., 0, :].sum(axis=-1)
        return p * plan.inverse(r) + _bx(s)

    def normal(x, d2, srow):
        """M^T M x: one fused pipeline launch + a column-sum term."""
        y = plan.pipeline(x, "mul", d2)
        s = (srow * x.sum(axis=-2)).sum(axis=-1)
        return p * y + _bx(s)

    def sherman_inv(y):
        """(A^T A)^{-1} y = y/P - sum(y) / (P (P + HW)), exact."""
        s = y.sum(axis=(-2, -1), keepdims=True)
        return y / p - s / (p * (p + hw))

    def make_precond(g_w):
        if precond_kind == "sherman":
            return sherman_inv
        if precond_kind == "filter":
            return lambda r: plan.pipeline(r, "mul", g_w)
        return lambda r: r

    # -- normal-equation loops (image-space rhs) ---------------------------
    def cg_loop(rhs, d2, srow, g_w, tol):
        # Masked normal matrices are SINGULAR (removed directions leave a
        # null space), so past the dtype noise floor CG's rounding noise
        # grows unboundedly along null directions.  Two defenses: return
        # the best-residual iterate ever seen (xb), and freeze a batch
        # element outright once its residual rebounds far above that
        # best (stall) or curvature is lost (pq <= 0).
        ref = _bnorm(rhs)
        safe = jnp.where(ref > 0, ref, 1).astype(rhs.dtype)
        precond = make_precond(g_w)
        hist = jnp.full((maxiter + 1,) + rhs.shape[:-2], jnp.nan,
                        rhs.dtype)
        rn0 = jnp.where(ref > 0, 1.0, 0.0).astype(rhs.dtype)
        hist = hist.at[0].set(rn0)
        x = jnp.zeros_like(rhs)
        r = rhs
        z = precond(r)
        rz = _bdot(r, z)
        conv = ref <= 0
        stall = jnp.zeros_like(conv)

        def cond(st):
            k = st[0]
            cv, sl = st[-2], st[-1]
            return (k < maxiter) & ~(cv | sl).all()

        def step(st):
            k, x, xb, r, pdir, rz, best, hist, conv, stall = st
            q = normal(pdir, d2, srow)
            pq = _bdot(pdir, q)
            # frozen batch elements take alpha = beta = 0 rather than 0/0
            ok = ~(conv | stall) & (pq > 0)
            alpha = jnp.where(ok, rz / jnp.where(pq == 0, 1, pq), 0)
            x = x + _bx(alpha) * pdir
            r = r - _bx(alpha) * q
            z = precond(r)
            rz_new = _bdot(r, z)
            beta = jnp.where(ok & (rz > 0),
                             rz_new / jnp.where(rz == 0, 1, rz), 0)
            pdir = jnp.where(_bx(ok), z + _bx(beta) * pdir, pdir)
            rn = _bnorm(r) / safe
            improved = ok & (rn < best)
            xb = jnp.where(_bx(improved), x, xb)
            best = jnp.where(improved, rn, best)
            conv = conv | (ok & (rn <= tol))
            stall = stall | (~conv & ((pq <= 0) | (rn > 100 * best)))
            hist = hist.at[k + 1].set(rn.astype(hist.dtype))
            return (k + 1, x, xb, r, pdir, rz_new, best, hist, conv,
                    stall)

        st = jax.lax.while_loop(
            cond, step, (0, x, x, r, z, rz, rn0, hist, conv, stall))
        k, xb, hist, conv = st[0], st[2], st[-3], st[-2]
        return xb, hist, k, conv.all()

    def landweber_loop(rhs, d2, srow, tol, tau):
        ref = _bnorm(rhs)
        safe = jnp.where(ref > 0, ref, 1).astype(rhs.dtype)
        # default step from the exact bound ||M||^2 <= max(d^2)(P + HW)
        dmax2 = jnp.maximum(d2.max(), jnp.asarray(1e-30, rhs.dtype))
        tau = jnp.where(jnp.isnan(tau),
                        1.0 / (dmax2 * (p + hw)), tau).astype(rhs.dtype)
        hist = jnp.full((maxiter + 1,) + rhs.shape[:-2], jnp.nan,
                        rhs.dtype)
        hist = hist.at[0].set(jnp.where(ref > 0, 1.0, 0.0))
        x = jnp.zeros_like(rhs)
        conv = ref <= 0

        def cond(st):
            k, _x, _hist, cv = st
            return (k < maxiter) & ~cv.all()

        def step(st):
            k, x, hist, conv = st
            r = rhs - normal(x, d2, srow)
            x = x + jnp.where(_bx(conv), 0, tau * r)
            rn = _bnorm(r) / safe
            conv = conv | (rn <= tol)
            hist = hist.at[k + 1].set(rn.astype(hist.dtype))
            return (k + 1, x, hist, conv)

        k, x, hist, conv = jax.lax.while_loop(
            cond, step, (0, x, hist, conv))
        return x, hist, k, conv.all()

    # -- LSQR: Golub-Kahan bidiagonalization on M = D A itself -------------
    def lsqr_loop(bw, d, tol):
        def m_apply(v):
            return d * plan.forward(v)

        def mt_apply(u):
            return atd(d * u)

        beta = _bnorm(bw)
        u = jnp.where(_bx(beta > 0), bw / _bx(jnp.where(beta > 0, beta, 1)),
                      0)
        v0 = mt_apply(u)
        alpha = _bnorm(v0)
        v = jnp.where(_bx(alpha > 0),
                      v0 / _bx(jnp.where(alpha > 0, alpha, 1)), 0)
        ref = alpha * beta            # == ||M^T b_w|| by construction
        safe = jnp.where(ref > 0, ref, 1).astype(bw.dtype)
        hist = jnp.full((maxiter + 1,) + beta.shape, jnp.nan, bw.dtype)
        hist = hist.at[0].set(jnp.where(ref > 0, 1.0, 0.0))
        x = jnp.zeros_like(v)
        conv = ref <= 0
        st0 = (0, x, u, v, v, beta, alpha, alpha, hist, conv)
        # carry: k, x, u, v, w_dir, phibar, rhobar, alpha, hist, conv

        def cond(st):
            k, *_rest, cv = st
            return (k < maxiter) & ~cv.all()

        def step(st):
            k, x, u, v, w_dir, phibar, rhobar, alpha, hist, conv = st
            un = m_apply(v) - _bx(alpha) * u
            beta = _bnorm(un)
            u = jnp.where(_bx(beta > 0),
                          un / _bx(jnp.where(beta > 0, beta, 1)), 0)
            vn = mt_apply(u) - _bx(beta) * v
            alpha = _bnorm(vn)
            v = jnp.where(_bx(alpha > 0),
                          vn / _bx(jnp.where(alpha > 0, alpha, 1)), 0)
            rho = jnp.sqrt(rhobar * rhobar + beta * beta)
            rho_s = jnp.where(rho > 0, rho, 1)
            c = rhobar / rho_s
            s = beta / rho_s
            theta = s * alpha
            rhobar = -c * alpha
            phi = c * phibar
            phibar = s * phibar
            gain = jnp.where(conv, 0, phi / rho_s)
            x = x + _bx(gain) * w_dir
            w_dir = jnp.where(_bx(conv), w_dir,
                              v - _bx(theta / rho_s) * w_dir)
            # Paige-Saunders estimate ||M^T r_k|| = phibar_k alpha_k |c_k|
            rn = phibar * alpha * jnp.abs(c) / safe
            conv = conv | (rn <= tol)
            hist = hist.at[k + 1].set(rn.astype(hist.dtype))
            return (k + 1, x, u, v, w_dir, phibar, rhobar, alpha, hist,
                    conv)

        st = jax.lax.while_loop(cond, step, st0)
        k, x = st[0], st[1]
        hist, conv = st[-2], st[-1]
        return x, hist, k, conv.all()

    # -- assembled method bodies -------------------------------------------
    def d2_parts(d):
        d2 = d * d
        return d2, d2[..., 0, :w]

    if method == "sherman":
        def body(b, d, g_w, tol, tau):
            rhs = atd(b)
            x = sherman_inv(rhs)
            # closed-form normal residual: A^T A x = P x + total(x) 1
            gx = p * x + x.sum(axis=(-2, -1), keepdims=True)
            ref = _bnorm(rhs)
            rel = _bnorm(rhs - gx) / jnp.where(ref > 0, ref, 1)
            hist = jnp.stack([jnp.ones_like(rel), rel.astype(rhs.dtype)])
            return SolveResult(x, hist, jnp.asarray(0, jnp.int32),
                               jnp.asarray(True))

        def image_of(v, d, g_w, tol, tau):
            return sherman_inv(atd(v))

        def transpose(ct, d, g_w, tol, tau):
            # L = C A^T with C = (A^T A)^{-1} symmetric => L^T = A C
            return plan.forward(sherman_inv(ct))
    else:
        def normal_solve(rhs, d, g_w, tol, tau):
            d2, srow = d2_parts(d)
            if method == "landweber":
                return landweber_loop(rhs, d2, srow, tol, tau)
            return cg_loop(rhs, d2, srow, g_w, tol)

        def body(b, d, g_w, tol, tau):
            if method == "lsqr":
                x, hist, k, conv = lsqr_loop(d * b, d, tol)
            else:
                d2, _srow = d2_parts(d)
                x, hist, k, conv = normal_solve(atd(d2 * b), d, g_w, tol,
                                                tau)
            return SolveResult(x, hist, k.astype(jnp.int32), conv)

        def image_of(v, d, g_w, tol, tau):
            # the converged linear map b -> G^+ A^T D^2 b, applied to a
            # tangent sinogram (LSQR's tangent routes through the same
            # normal-equation solve: the fixed points agree)
            d2, _srow = d2_parts(d)
            return normal_solve(atd(d2 * v), d, g_w, tol, tau)[0]

        def transpose(ct, d, g_w, tol, tau):
            # L^T = D^2 A G^+ (G symmetric): solve with ct as the rhs,
            # then push forward through the masked operator
            d2, _srow = d2_parts(d)
            x = normal_solve(ct, d, g_w, tol, tau)[0]
            return d2 * plan.forward(x)

    @jax.custom_jvp
    def run(b, d, g_w, tol, tau):
        _note_trace(plan, f"solve:{method}", b)
        return body(b, d, g_w, tol, tau)

    # symbolic_zeros: unperturbed diagonals/knobs must arrive as
    # SymbolicZero, not instantiated zero arrays -- grad w.r.t. the
    # sinogram alone is the supported (and common) case
    @partial(run.defjvp, symbolic_zeros=True)
    def _run_jvp(primals, tangents):
        b, d, g_w, tol, tau = primals
        db, dd, dg, dtol, dtau = tangents
        out = run(b, d, g_w, tol, tau)
        for name, t in (("mask/weight diagonal", dd),
                        ("preconditioner", dg), ("tol", dtol),
                        ("tau", dtau)):
            if not _is_zero_tangent(t):
                raise ValueError(
                    f"radon.solve is linear in the sinogram only; the "
                    f"{name} is not a differentiable input")
        if _is_zero_tangent(db):
            tan_img = jnp.zeros(out.image.shape, out.image.dtype)
        else:
            res = jax.lax.stop_gradient((d, g_w, tol, tau))
            tan_img = linear_call(
                lambda r, vb: image_of(vb, *r),
                lambda r, ct: transpose(ct, *r),
                res, db)
        tan = SolveResult(
            tan_img,
            jnp.zeros(out.residual_norms.shape, out.residual_norms.dtype),
            np.zeros(out.iterations.shape, jax.dtypes.float0),
            np.zeros(out.converged.shape, jax.dtypes.float0))
        return out, tan

    with _CACHE_LOCK:
        return _JITTED.setdefault(key, jax.jit(run))


# ---------------------------------------------------------------------------
# the public entry point
# ---------------------------------------------------------------------------
def _resolve_precond(precond, fdtype):
    if precond is None:
        return "none", None
    if isinstance(precond, str):
        if precond != "sherman":
            raise ValueError(
                f"unknown precond {precond!r}: 'sherman', a "
                f"ProjectionFilter, or a (…, P+1, P) weight array")
        return "sherman", None
    if isinstance(precond, ProjectionFilter):
        return "filter", precond.weights.astype(fdtype)
    g_w = jnp.asarray(precond, fdtype)
    if g_w.ndim < 2 or g_w.shape[-2] != g_w.shape[-1] + 1:
        raise ValueError(
            f"precond weights must be (…, P+1, P), got {g_w.shape}")
    return "filter", g_w


def solve(op, sinogram, method: str = "auto", *, mask=None, weight=None,
          precond=None, tol: float = 1e-6, maxiter: int = 100,
          tau: Optional[float] = None) -> SolveResult:
    """Reconstruct an image (stack) from (masked/weighted) projections.

    ``op`` is a forward :class:`~repro.radon.RadonOperator` (``mask`` /
    ``weight`` build the :class:`~repro.radon.MaskedDPRT` here) or an
    already-built ``MaskedDPRT``.  ``method``: ``"auto"`` picks the
    non-iterative Sherman-Morrison closed form when nothing is masked
    and CG on the normal equations otherwise; ``"cg"`` accepts
    ``precond`` (``"sherman"`` for the exact unmasked inverse -- SPD --
    or a ``ProjectionFilter``/weight array riding the fused pipeline).
    ``tau`` is the Landweber step (default: the exact spectral bound).

    Returns a :class:`SolveResult`; see the module docstring for the
    convergence, batching, and differentiation contracts.
    """
    if isinstance(op, MaskedDPRT):
        if mask is not None or weight is not None:
            raise ValueError(
                "pass mask/weight either to MaskedDPRT or to solve(), "
                "not both")
        if op._adjoint:
            raise ValueError("solve() expects the forward measurement "
                             "operator, got its adjoint")
        m = op
    else:
        m = MaskedDPRT(op, mask=mask, weight=weight)
    plan = m.plan
    b = jnp.asarray(sinogram)
    if b.shape != plan.geometry.transform_shape:
        raise ValueError(
            f"sinogram shape {b.shape} != operator projections "
            f"{plan.geometry.transform_shape}")
    b = b.astype(m.fdtype)

    unmasked = m.is_identity_diagonal
    if method == "auto":
        method = "sherman" if unmasked else "cg"
    if method not in METHODS:
        raise ValueError(f"unknown method {method!r}; one of {METHODS}")
    if method == "sherman" and not unmasked:
        raise ValueError(
            "the Sherman-Morrison fast path is exact only for the "
            "unmasked, unweighted operator; use cg/lsqr/landweber")
    precond_kind, g_w = _resolve_precond(precond, m.fdtype)
    if precond_kind != "none" and method != "cg":
        raise ValueError(
            f"precond applies to method='cg' only (sherman is direct, "
            f"lsqr/landweber run unpreconditioned); got method={method!r}")
    if int(maxiter) < 1 and method != "sherman":
        raise ValueError(f"maxiter must be >= 1, got {maxiter}")

    fn = _jitted_solve(plan, method,
                       0 if method == "sherman" else int(maxiter),
                       precond_kind)
    g_in = g_w if g_w is not None else jnp.zeros((), m.fdtype)
    tol_in = jnp.asarray(float(tol), m.fdtype)
    tau_in = jnp.asarray(np.nan if tau is None else float(tau), m.fdtype)
    return fn(b, m.d, g_in, tol_in, tau_in)


# ---------------------------------------------------------------------------
# the servable operator surface (AOT like Conv2D: the service tier and
# the persistent executable cache consume this unchanged)
# ---------------------------------------------------------------------------
class ReconstructionOperator:
    """``sinogram -> reconstructed image`` as a compilable operator.

    Wraps one :class:`~repro.radon.MaskedDPRT` + solver configuration
    into the AOT surface the serving tier expects (``shape_in`` /
    ``dtype_in`` contract, ``lower()``/``compile()``, persistent-cache
    ``cache_token()``/``export_executable()``).  ``__call__`` returns
    the image only -- diagnostics stay on :func:`solve` -- so compiled
    executables chain like any other stage.
    """

    __slots__ = ("masked", "solver", "tol", "maxiter", "tau",
                 "precond_kind", "precond_w")

    def __init__(self, masked: MaskedDPRT, solver: str = "auto", *,
                 tol: float = 1e-6, maxiter: int = 50,
                 tau: Optional[float] = None, precond=None):
        if not isinstance(masked, MaskedDPRT) or masked._adjoint:
            raise ValueError(
                f"ReconstructionOperator wraps a forward MaskedDPRT, "
                f"got {masked!r}")
        if solver == "auto":
            solver = "sherman" if masked.is_identity_diagonal else "cg"
        if solver not in METHODS:
            raise ValueError(f"unknown solver {solver!r}; one of {METHODS}")
        kind, g_w = _resolve_precond(precond, masked.fdtype)
        object.__setattr__(self, "masked", masked)
        object.__setattr__(self, "solver", solver)
        object.__setattr__(self, "tol", float(tol))
        object.__setattr__(self, "maxiter", int(maxiter))
        object.__setattr__(self, "tau",
                           None if tau is None else float(tau))
        object.__setattr__(self, "precond_kind", kind)
        object.__setattr__(self, "precond_w", g_w)

    def __setattr__(self, name, value):
        raise AttributeError("ReconstructionOperator is immutable")

    @property
    def plan(self):
        return self.masked.plan

    @property
    def shape_in(self):
        return self.plan.geometry.transform_shape

    @property
    def shape_out(self):
        return self.plan.geometry.image_shape

    @property
    def dtype_in(self):
        return self.masked.fdtype

    dtype_out = dtype_in

    def __call__(self, sinogram: jnp.ndarray) -> jnp.ndarray:
        precond = (self.precond_w if self.precond_kind == "filter"
                   else ("sherman" if self.precond_kind == "sherman"
                         else None))
        return solve(self.masked, sinogram, self.solver, precond=precond,
                     tol=self.tol, maxiter=self.maxiter,
                     tau=self.tau).image

    def __matmul__(self, other):
        from .operators import _compose
        return _compose(self, other)

    # -- AOT / persistent executable export --------------------------------
    def _aot_key(self):
        return ("recon", self.plan, self.solver, self.maxiter, self.tol,
                self.tau, self.precond_kind, id(self.masked.d))

    def _aot_pins(self):
        pins = (self.masked.d,)
        if self.precond_w is not None:
            pins += (self.precond_w,)
        return pins

    def lower(self):
        spec = jax.ShapeDtypeStruct(self.shape_in, self.dtype_in)
        return jax.jit(self.__call__).lower(spec)

    def compile(self):
        return _compile_once(self, self._aot_key(), "recon",
                             self._aot_pins())

    def cache_token(self) -> str:
        import hashlib
        pl = self.plan
        shape = "x".join(str(s) for s in self.shape_in)
        blob = np.asarray(self.masked.d).tobytes()
        if self.precond_w is not None:
            blob += np.asarray(self.precond_w).tobytes()
        digest = hashlib.sha1(blob).hexdigest()[:16]
        knobs = "h{}_m{}_sr{}_br{}_bb{}".format(
            pl.strip_rows, pl.m_block, pl.stream_rows, pl.block_rows,
            pl.block_batch)
        return (f"recon_{shape}_{self.dtype_in.name}_{pl.method}_"
                f"{self.solver}_t{self.tol:g}_i{self.maxiter}_"
                f"p{self.precond_kind}_d{digest}_{knobs}_"
                f"{_topology_token(pl.mesh)}")

    def export_executable(self) -> bytes:
        return _export_compiled(self.compile())

    def import_executable(self, data: bytes):
        exe = _import_compiled(data)
        key = self._aot_key()
        with _CACHE_LOCK:
            _AOT_CACHE[key] = exe
            _AOT_PINS.setdefault(key, self._aot_pins())
        return exe

    def describe(self) -> dict:
        d = dict(self.plan.describe())
        d.update(kind="recon", solver=self.solver, tol=self.tol,
                 maxiter=self.maxiter, precond=self.precond_kind,
                 shape_in=self.shape_in, shape_out=self.shape_out)
        return d

    def __repr__(self) -> str:
        return (f"ReconstructionOperator({self.shape_in}->"
                f"{self.shape_out}, solver={self.solver!r}, "
                f"tol={self.tol:g}, maxiter={self.maxiter}, "
                f"method={self.plan.method!r})")


def solve_operator(shape, dtype=jnp.float32, *, mask=None, weight=None,
                   solver: str = "auto", tol: float = 1e-6,
                   maxiter: int = 50, tau: Optional[float] = None,
                   precond=None, method: Optional[str] = None,
                   **knobs) -> ReconstructionOperator:
    """Build a servable reconstruction operator for one image geometry.

    ``shape`` is the image geometry ``(H, W)`` or ``(B, H, W)``;
    ``method`` / ``**knobs`` are the usual transform-plan knobs
    (backend, blocking, mesh), ``solver``/``tol``/``maxiter``/``tau``/
    ``precond`` the solver configuration, ``mask``/``weight`` the
    projection-domain diagonal.  The sinogram contract is
    ``(…, P+1, P)`` in :func:`repro.core.dprt.float_dtype_for` of
    ``dtype``.
    """
    fwd = DPRT(shape, dtype, method, **knobs)
    masked = MaskedDPRT(fwd, mask=mask, weight=weight)
    return ReconstructionOperator(masked, solver, tol=tol,
                                  maxiter=maxiter, tau=tau,
                                  precond=precond)

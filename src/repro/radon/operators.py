"""First-class linear-operator objects over cached transform plans.

``op = radon.DPRT(shape, dtype)`` builds (or fetches -- plans and trace
caches are shared) the forward DPRT operator for one input geometry.
Operators are immutable views of a ``(plan, datapath)`` pair and expose
the full linear-operator algebra:

    op(f)            # apply: (…, H, W) -> (…, P+1, P), differentiable
    op.inverse       # the exact inverse transform (crops the embedding)
    op.T             # the exact adjoint -- A^T, NOT the inverse
    op.inverse.T     # adjoint of the inverse == (A^T)^-1
    op2 @ op1        # composition (applied right-to-left)
    op.lower()       # AOT: trace+lower for the declared input aval
    op.compile()     # AOT: cached per-geometry compiled executable
    op.as_matrix()   # dense (out_size, in_size) matrix (small N; tests)

Every application routes through :mod:`repro.radon.autodiff`, so
``jax.grad``/``jax.jvp`` are exact for every registered backend and
each geometry traces exactly once no matter how many operators,
legacy-wrapper calls, or serve workers touch it.
"""
from __future__ import annotations

import threading
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.dprt import accum_dtype_for
from repro.core.plan import RadonPlan, add_plan_evict_hook, get_plan
from repro.core.spans import span

from . import ambient
from .autodiff import (_CACHE_LOCK, INVERSE_OF, TRANSPOSE_OF, jitted_apply,
                       trace_count)
from .fusion import flip_image, pipeline_apply

__all__ = ["DPRT", "Conv2D", "ProjectionFilter", "RadonOperator",
           "CompositeOperator", "operator_for",
           "aot_cache_info", "aot_cache_clear",
           "PersistentAOTCache", "aot_fingerprint"]

#: (plan, kind, dtype) -- or a tuple of per-operator key entries for
#: composites (filter/conv entries are ("proj_filter"|"fused_mul"|
#: "conv2d", …, id(array)) 4-tuples) -- -> jax compiled executable; the
#: per-geometry AOT cache behind ``op.compile()`` (and
#: ``serve --warmup``).  Entries drop in lockstep with plan-cache
#: evictions, like the jitted appliers.
_AOT_CACHE: dict = {}

#: key -> arrays whose id() participates in the key.  Pinning them for
#: the life of the cache entry keeps the id from being recycled by the
#: allocator, so a dead weights array can never alias a live key.
_AOT_PINS: dict = {}

#: cache_token -> threading.Lock serializing concurrent
#: PersistentAOTCache.get_or_compile of the same executable (two
#: services/routers sharing an aot_dir must not double-compile).
#: Guarded by _CACHE_LOCK; never dropped -- a few dozen tokens of locks.
_COMPILE_LOCKS: dict = {}


def _drop_plan_executables(plan) -> None:
    def involves(key) -> bool:
        if isinstance(key[0], tuple):   # composite: one entry per operator
            return any(plan in entry for entry in key)
        return key[0] == plan
    with _CACHE_LOCK:
        for key in [k for k in _AOT_CACHE if involves(k)]:
            del _AOT_CACHE[key]
            _AOT_PINS.pop(key, None)


add_plan_evict_hook(_drop_plan_executables)


def _aot_key_label(key) -> str:
    if isinstance(key[0], tuple):   # composite: one entry per operator
        return "@".join(str(e[0] if isinstance(e[0], str) else e[1])
                        for e in key)
    return str(key[1])


def aot_cache_info() -> dict:
    with _CACHE_LOCK:
        return {"currsize": len(_AOT_CACHE),
                "keys": sorted(_aot_key_label(k) for k in _AOT_CACHE)}


def aot_cache_clear() -> None:
    with _CACHE_LOCK:
        _AOT_CACHE.clear()
        _AOT_PINS.clear()


def aot_fingerprint() -> str:
    """Environment stamp persisted next to exported executables: a blob
    compiled under a different jax version / backend / device census is
    rejected at load time instead of crashing inside the runtime."""
    devs = jax.devices()
    kinds = ",".join(sorted({d.device_kind for d in devs}))
    return f"jax={jax.__version__};backend={jax.default_backend()};" \
           f"devices={len(devs)};kinds={kinds}"


def _topology_token(mesh) -> str:
    """The device-topology component of a persistent cache token."""
    if mesh is None:
        return f"{jax.default_backend()}{len(jax.devices())}"
    return ("mesh_" + "_".join(f"{a}{s}"
                               for a, s in dict(mesh.shape).items())
            + f"_{jax.default_backend()}")


def _export_compiled(exe) -> bytes:
    """Serialize one AOT-compiled executable to restorable bytes."""
    import pickle
    from jax.experimental import serialize_executable as _se
    payload, in_tree, out_tree = _se.serialize(exe)
    return pickle.dumps((payload, in_tree, out_tree))


def _import_compiled(data: bytes):
    """Deserialize :func:`_export_compiled` bytes into a loaded
    executable -- no tracing, no XLA compilation."""
    import pickle
    from jax.experimental import serialize_executable as _se
    payload, in_tree, out_tree = pickle.loads(data)
    return _se.deserialize_and_load(payload, in_tree, out_tree)


def _compile_once(op, key, kind: str, pins: tuple = ()):
    """The executable under ``key`` in the process-wide AOT cache, built
    on a miss only: a ``radon.compile`` span with two children,
    ``radon.lower`` (trace and lowering, Pallas to Mosaic included) and
    ``radon.backend_compile`` (an XLA compile or a load from JAX's
    persistent cache).  ``pins`` stay alive with the entry."""
    with _CACHE_LOCK:
        exe = _AOT_CACHE.get(key)
    if exe is not None:
        return exe
    with span("radon.compile", kind=kind, shape_in=op.shape_in,
              dtype=op.dtype_in.name):
        with span("radon.lower"):
            lowered = op.lower()
        with span("radon.backend_compile"):
            built = lowered.compile()
    with _CACHE_LOCK:
        exe = _AOT_CACHE.setdefault(key, built)
        if pins:    # keep id()-keyed arrays alive with the entry
            _AOT_PINS.setdefault(key, pins)
    return exe


class PersistentAOTCache:
    """Disk-backed executable cache: ``jax.export``-style serialized AOT
    executables (via ``jax.experimental.serialize_executable``) keyed by
    :meth:`RadonOperator.cache_token` and stored through the
    :mod:`repro.checkpoint.store` blob machinery (atomic rename, header
    + payload).  A warm process restart deserializes the compiled
    executable instead of re-running XLA -- measured ~15-40x cheaper
    than a cold compile on the fused pallas plans.

    ``get_or_compile(op)`` is the whole surface: in-memory AOT cache
    first, then disk (fingerprint-checked), then compile-and-persist.
    Corrupt or stale blobs count as misses (``errors`` tallies them) and
    are overwritten; serialization failures degrade to plain in-memory
    compilation, never to an outage.  ``degraded_compiles`` counts the
    restores that had a blob on disk but still had to cold-compile
    (torn/rotten/stale blob) -- the number a restarted service surfaces
    in ``healthz`` to say "I came up, but not warm".

    Concurrent ``get_or_compile`` of the same token is serialized at two
    scopes: a process-wide lock table (two services, two routers in one
    process) and a cross-process :func:`~repro.checkpoint.store.blob_lock`
    file lock (N worker *processes* cold-starting over one ``aot_dir``).
    A waiter re-reads the blob once it holds the file lock, so whichever
    process compiled first publishes and everyone else restores -- one
    compile per unique executable across the whole pool.  Lock files
    left by SIGKILLed workers carry the holder PID and are stolen once
    the PID is dead (``lock_steals`` counts these); a filesystem that
    cannot do O_EXCL degrades to unlocked operation (``lock_degraded``)
    rather than refusing to serve.
    """

    def __init__(self, directory: str, *, lock_stale_s: float = 120.0,
                 lock_timeout_s: float = 600.0):
        self.directory = str(directory)
        self.lock_stale_s = float(lock_stale_s)
        self.lock_timeout_s = float(lock_timeout_s)
        self.hits = self.misses = self.errors = 0
        self.degraded_compiles = 0
        self.lock_steals = 0
        self.lock_degraded = 0
        self.lock_wait_s = 0.0

    def _compile_lock(self, key: str):
        with _CACHE_LOCK:
            return _COMPILE_LOCKS.setdefault(key, threading.Lock())

    def get_or_compile(self, op):
        """Return the executable for any operator exposing the AOT
        surface (``RadonOperator`` and ``Conv2D`` both do)."""
        from repro.checkpoint.store import blob_lock
        with _CACHE_LOCK:
            exe = _AOT_CACHE.get(op._aot_key())
        if exe is not None:
            return exe                      # in-memory: not a disk event
        key = op.cache_token()
        with self._compile_lock(key):
            with _CACHE_LOCK:               # racer finished while we waited
                exe = _AOT_CACHE.get(op._aot_key())
            if exe is not None:
                return exe
            try:
                with blob_lock(self.directory, key,
                               stale_s=self.lock_stale_s,
                               timeout_s=self.lock_timeout_s) as lk:
                    self.lock_steals += lk["steals"]
                    self.lock_wait_s += lk["waited_s"]
                    return self._restore_or_compile(op, key)
            except OSError:                 # O_EXCL unsupported / RO dir:
                self.lock_degraded += 1     # unlocked is worse, outage is
                return self._restore_or_compile(op, key)   # worse still

    def _restore_or_compile(self, op, key: str):
        """Disk-restore-else-compile for ``key``; caller holds both the
        in-process token lock and (normally) the cross-process file
        lock, so the load here observes any blob a peer process
        published while we waited."""
        from repro.checkpoint.store import load_blob, save_blob
        data = None
        had_blob = False
        with span("radon.aot_restore", token=key):
            try:
                data, meta = load_blob(self.directory, key)
                had_blob = data is not None
            except ValueError:          # torn/corrupt blob: overwrite
                self.errors += 1
                had_blob = True
            if data is not None \
                    and meta.get("fingerprint") == aot_fingerprint():
                try:
                    exe = op.import_executable(data)
                    self.hits += 1
                    return exe
                except Exception:       # undeserializable: recompile
                    self.errors += 1
        self.misses += 1
        if had_blob:                    # blob existed but could not
            self.degraded_compiles += 1  # restore: degraded cold start
        exe = op.compile()
        try:
            save_blob(self.directory, key, op.export_executable(),
                      meta={"fingerprint": aot_fingerprint()})
        except Exception:               # read-only disk etc.: serve
            self.errors += 1            # from memory, count it
        return exe

    def stats(self) -> dict:
        return {"directory": self.directory, "hits": self.hits,
                "misses": self.misses, "errors": self.errors,
                "degraded_compiles": self.degraded_compiles,
                "lock_steals": self.lock_steals,
                "lock_degraded": self.lock_degraded,
                "lock_wait_s": round(self.lock_wait_s, 6)}

    def __repr__(self) -> str:
        return (f"PersistentAOTCache({self.directory!r}, hits={self.hits}, "
                f"misses={self.misses}, errors={self.errors}, "
                f"degraded_compiles={self.degraded_compiles})")


class RadonOperator:
    """One linear datapath of a :class:`~repro.core.plan.RadonPlan`.

    ``kind`` is one of ``forward`` / ``inverse`` / ``adjoint`` /
    ``inverse_adjoint``; ``dtype`` is the *image* dtype the operator was
    declared for (transform-domain inputs/outputs use its accumulator
    dtype, exactly as the transforms themselves do).
    """

    __slots__ = ("plan", "kind", "dtype")

    def __init__(self, plan: RadonPlan, kind: str, dtype):
        if kind not in TRANSPOSE_OF:
            raise ValueError(f"unknown operator kind {kind!r}")
        object.__setattr__(self, "plan", plan)
        object.__setattr__(self, "kind", kind)
        object.__setattr__(self, "dtype", jnp.dtype(dtype))

    def __setattr__(self, name, value):
        raise AttributeError("RadonOperator is immutable")

    # -- shapes / dtypes ---------------------------------------------------
    @property
    def _image_side(self) -> bool:
        """True when the INPUT lives in image space (H, W)."""
        return self.kind in ("forward", "inverse_adjoint")

    @property
    def shape_in(self) -> Tuple[int, ...]:
        g = self.plan.geometry
        return g.image_shape if self._image_side else g.transform_shape

    @property
    def shape_out(self) -> Tuple[int, ...]:
        g = self.plan.geometry
        return g.transform_shape if self._image_side else g.image_shape

    @property
    def dtype_in(self):
        # forward consumes raw images; every other datapath consumes
        # transform-domain / cotangent values, which live in the
        # accumulator dtype the transforms emit
        if self.kind == "forward":
            return self.dtype
        return jnp.dtype(accum_dtype_for(self.dtype, self.plan.geometry.prime))

    @property
    def dtype_out(self):
        return jnp.dtype(accum_dtype_for(self.dtype, self.plan.geometry.prime))

    # -- application -------------------------------------------------------
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        return jitted_apply(self.plan, self.kind)(x)

    # -- algebra -----------------------------------------------------------
    @property
    def T(self) -> "RadonOperator":
        """The exact adjoint (transpose).  ``op.T`` satisfies
        ``<op(x), y> == <x, op.T(y)>`` -- it is NOT the inverse."""
        return RadonOperator(self.plan, TRANSPOSE_OF[self.kind], self.dtype)

    @property
    def inverse(self) -> "RadonOperator":
        """The exact inverse transform (bit-exact round trip on ints)."""
        return RadonOperator(self.plan, INVERSE_OF[self.kind], self.dtype)

    def __matmul__(self, other):
        return _compose(self, other)

    def _aot_key(self):
        return (self.plan, self.kind, self.dtype_in.name)

    # -- AOT ---------------------------------------------------------------
    @property
    def input_sharding(self):
        """The mesh-natural sharding of this operator's input (``None``
        for non-mesh plans): batched stacks shard over the mesh's data
        axes, everything else is replicated.  Matches the output
        sharding of the paired datapath, so AOT-compiled forward/inverse
        executables chain without resharding -- ``device_put`` inputs
        here before calling a ``.compile()``d executable under a mesh."""
        mesh = self.plan.mesh
        if mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.core.distributed import batch_partition_spec
        if self.plan.geometry.batched:
            return NamedSharding(mesh, batch_partition_spec(mesh))
        return NamedSharding(
            mesh, PartitionSpec(*([None] * len(self.shape_in))))

    def _input_aval(self) -> jax.ShapeDtypeStruct:
        sharding = self.input_sharding
        if sharding is None:
            return jax.ShapeDtypeStruct(self.shape_in, self.dtype_in)
        return jax.ShapeDtypeStruct(self.shape_in, self.dtype_in,
                                    sharding=sharding)

    def lower(self):
        """Trace + lower this operator for its declared input aval
        (``jax.jit(...).lower``); ``.compile()`` the result for an AOT
        executable, or use :meth:`compile` for the cached one."""
        return jitted_apply(self.plan, self.kind).lower(self._input_aval())

    def compile(self):
        """The AOT-compiled executable for this geometry, built at most
        once per (plan, datapath, dtype) process-wide.  The returned
        executable is callable and never retraces -- the serve path's
        steady state."""
        return _compile_once(self, self._aot_key(), self.kind)

    # -- persistent AOT (executable export/import) -------------------------
    def cache_token(self) -> str:
        """A process-independent identity string for this operator's
        compiled executable: geometry, dtype, resolved method + block
        knobs, and the device topology it was compiled for.  Used as the
        key of the persistent on-disk executable cache -- two processes
        on identical topology/geometry agree on the token, a different
        mesh or dtype never collides."""
        p = self.plan
        shape = "x".join(str(s) for s in self.shape_in)
        knobs = "h{}_m{}_sr{}_br{}_bb{}".format(
            p.strip_rows, p.m_block, p.stream_rows, p.block_rows,
            p.block_batch)
        return (f"{self.kind}_{shape}_{self.dtype_in.name}_{p.method}_"
                f"{knobs}_{_topology_token(p.mesh)}")

    def export_executable(self) -> bytes:
        """Serialize this operator's AOT-compiled executable (compiling
        first if needed) to restorable bytes: a future process calls
        :meth:`import_executable` and serves without paying XLA
        compilation (only tracing-free deserialization)."""
        return _export_compiled(self.compile())

    def import_executable(self, data: bytes):
        """Deserialize executable bytes from :meth:`export_executable`
        and install them in the in-process AOT cache under this
        operator's key -- subsequent :meth:`compile` calls return the
        imported executable without compiling anything."""
        exe = _import_compiled(data)
        with _CACHE_LOCK:
            _AOT_CACHE[self._aot_key()] = exe
        return exe

    # -- introspection -----------------------------------------------------
    @property
    def trace_count(self) -> int:
        """Traces taken for this (plan, datapath) so far (all geometries
        of the plan's shape; exactly 1 after any number of same-shape
        calls)."""
        return trace_count(self.plan, self.kind)

    def as_matrix(self) -> jnp.ndarray:
        """Dense (out_size, in_size) matrix of this linear map.

        Materializes one basis vector per input element -- O(P^4) memory
        -- so this is for small primes (tests, reference checks) only.
        """
        size_in = 1
        for s in self.shape_in:
            size_in *= s
        basis = jnp.eye(size_in, dtype=self.dtype_in)
        cols = jax.vmap(lambda e: self(e.reshape(self.shape_in)).ravel())(
            basis)
        return cols.T  # vmap rows are images of basis vectors == columns

    def describe(self) -> dict:
        d = dict(self.plan.describe())
        d.update(kind=self.kind, dtype=self.dtype.name,
                 shape_in=self.shape_in, shape_out=self.shape_out)
        return d

    def __repr__(self) -> str:
        return (f"RadonOperator({self.kind}, {self.shape_in}->"
                f"{self.shape_out}, {self.dtype.name}, "
                f"method={self.plan.method!r})")

    # operators are value objects: equal views of equal plans compare ==
    def __eq__(self, other):
        return (isinstance(other, RadonOperator)
                and self.plan == other.plan and self.kind == other.kind
                and self.dtype == other.dtype)

    def __hash__(self):
        return hash((self.plan, self.kind, self.dtype))


def _compose(left, right):
    """``left @ right``: flatten into one CompositeOperator (which then
    recognizes fusible patterns)."""
    if not (_is_operator_like(left) and _is_operator_like(right)):
        return NotImplemented
    lops = left.ops if isinstance(left, CompositeOperator) else (left,)
    rops = right.ops if isinstance(right, CompositeOperator) else (right,)
    return CompositeOperator(lops + rops)


def _is_operator_like(x) -> bool:
    return callable(x) and hasattr(x, "shape_in") and hasattr(x, "shape_out")


def _fuse_ops(ops: Tuple) -> Tuple:
    """Recognize ``inv @ pointwise @ fwd`` triples over one plan and
    replace them with the fused projection pipeline (one kernel launch on
    capable backends; staged fallback otherwise -- same dispatch rule as
    everything else)."""
    fused, i = [], 0
    while i < len(ops):
        a = ops[i]
        if (i + 2 < len(ops)
                and isinstance(a, RadonOperator) and a.kind == "inverse"
                and isinstance(ops[i + 1], ProjectionFilter)
                and isinstance(ops[i + 2], RadonOperator)
                and ops[i + 2].kind == "forward"
                and a.plan == ops[i + 2].plan
                and tuple(ops[i + 1].weights.shape[-2:])
                == tuple(a.plan.geometry.transform_shape[-2:])):
            fused.append(FusedProjectionPipeline(
                a.plan, ops[i + 1].weights, ops[i + 2].dtype))
            i += 3
        else:
            fused.append(a)
            i += 1
    return tuple(fused)


class CompositeOperator:
    """Right-to-left composition of operators: ``(g @ f)(x) == g(f(x))``.

    Supports the same algebra (``.T`` reverses and transposes,
    ``.inverse`` reverses and inverts) plus AOT lowering of the fused
    pipeline.  Shape chaining is validated at construction, and
    ``inverse @ ProjectionFilter @ forward`` triples over one plan are
    rewritten into the fused projection-domain pipeline (a single kernel
    launch on pipeline-capable backends).
    """

    __slots__ = ("ops",)

    def __init__(self, ops: Tuple):
        if not ops:
            raise ValueError("CompositeOperator needs at least one operator")
        ops = _fuse_ops(tuple(ops))
        for outer, inner in zip(ops[:-1], ops[1:]):
            if (outer.shape_in is not None and inner.shape_out is not None
                    and outer.shape_in != inner.shape_out):
                raise ValueError(
                    f"cannot compose {outer!r} after {inner!r}: "
                    f"{inner.shape_out} does not feed {outer.shape_in}")
        object.__setattr__(self, "ops", tuple(ops))

    def __setattr__(self, name, value):
        raise AttributeError("CompositeOperator is immutable")

    @property
    def shape_in(self):
        return self.ops[-1].shape_in

    @property
    def shape_out(self):
        return self.ops[0].shape_out

    @property
    def dtype_in(self):
        return self.ops[-1].dtype_in

    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        for op in reversed(self.ops):
            x = op(x)
        return x

    @property
    def T(self) -> "CompositeOperator":
        return CompositeOperator(tuple(op.T for op in reversed(self.ops)))

    @property
    def inverse(self) -> "CompositeOperator":
        return CompositeOperator(
            tuple(op.inverse for op in reversed(self.ops)))

    def __matmul__(self, other):
        return _compose(self, other)

    def lower(self):
        shape = self.shape_in
        if shape is None:
            # a shape-polymorphic input-side operator (ProjectionFilter):
            # lower for its weights' own shape, the natural unbatched aval
            inner = self.ops[-1]
            weights = getattr(inner, "weights", None)
            if weights is None:
                raise ValueError(
                    f"cannot AOT-lower a composite whose input operator "
                    f"{inner!r} has no declared input shape")
            shape = tuple(weights.shape)
        spec = jax.ShapeDtypeStruct(shape, self.dtype_in)
        return jax.jit(self.__call__).lower(spec)

    def compile(self):
        # dtype is part of the key: plans are dtype-agnostic (equal
        # across dtypes of one geometry) but compiled executables are not
        key = tuple(op._aot_key() for op in self.ops)
        pins = tuple(p for op in self.ops
                     for p in getattr(op, "_aot_pins", lambda: ())())
        return _compile_once(self, key, "composite", pins)

    def as_matrix(self) -> jnp.ndarray:
        mats = [op.as_matrix() for op in self.ops]
        out = mats[-1]
        for m in reversed(mats[:-1]):
            out = m @ out
        return out

    def __repr__(self) -> str:
        return " @ ".join(repr(op) for op in self.ops)

    def __eq__(self, other):
        return (isinstance(other, CompositeOperator)
                and self.ops == other.ops)

    def __hash__(self):
        return hash(self.ops)


class ProjectionFilter:
    """Pointwise projection-domain filter: ``r -> weights * r``.

    A diagonal (self-adjoint) linear operator on ``(…, P+1, P)``
    projections.  On its own it is a plain elementwise multiply; its
    value is in *composition*: ``op.inverse @ ProjectionFilter(w) @ op``
    is recognized by :class:`CompositeOperator` and rewritten into the
    fused projection-domain pipeline, so the filtered reconstruction
    runs as ONE kernel launch on pipeline-capable backends (staged
    fallback elsewhere).  Shape-polymorphic over leading batch dims
    (``shape_in``/``shape_out`` are ``None`` wildcards for chaining).
    """

    __slots__ = ("weights",)

    def __init__(self, weights):
        weights = jnp.asarray(weights)
        if weights.ndim not in (2, 3) or \
                weights.shape[-2] != weights.shape[-1] + 1:
            raise ValueError(
                f"projection weights must be (…, P+1, P), "
                f"got {weights.shape}")
        object.__setattr__(self, "weights", weights)

    def __setattr__(self, name, value):
        raise AttributeError("ProjectionFilter is immutable")

    shape_in = None   # polymorphic: any (…, P+1, P) matching the weights
    shape_out = None

    @property
    def dtype_in(self):
        return self.weights.dtype

    def __call__(self, r: jnp.ndarray) -> jnp.ndarray:
        return r * self.weights.astype(r.dtype)

    @property
    def T(self) -> "ProjectionFilter":
        return self    # diagonal and real: self-adjoint

    @property
    def inverse(self):
        raise TypeError(
            "ProjectionFilter has no exact inverse (1/weights is not an "
            "integer-exact operation); build the reciprocal filter "
            "explicitly if that is what you mean")

    def __matmul__(self, other):
        return _compose(self, other)

    def _aot_key(self):
        return ("proj_filter", self.weights.shape,
                self.weights.dtype.name, id(self.weights))

    def _aot_pins(self):
        return (self.weights,)

    def __repr__(self) -> str:
        return f"ProjectionFilter({self.weights.shape})"


class FusedProjectionPipeline:
    """``inverse @ ProjectionFilter @ forward`` collapsed onto one plan:
    applied via the fused projection-domain pipeline (one kernel launch
    on capable backends; staged registry fallback otherwise), with exact
    autodiff through :mod:`repro.radon.fusion`."""

    __slots__ = ("plan", "weights", "dtype")

    def __init__(self, plan: RadonPlan, weights, dtype):
        object.__setattr__(self, "plan", plan)
        object.__setattr__(self, "weights", jnp.asarray(weights))
        object.__setattr__(self, "dtype", jnp.dtype(dtype))

    def __setattr__(self, name, value):
        raise AttributeError("FusedProjectionPipeline is immutable")

    @property
    def shape_in(self):
        return self.plan.geometry.image_shape

    shape_out = property(lambda self: self.plan.geometry.image_shape)

    @property
    def dtype_in(self):
        # same contract as the forward operator it swallowed: the fused
        # pipeline consumes raw images of the plan's declared dtype (the
        # fusion rewrite must not change a composite's input signature)
        return jnp.dtype(self.dtype)

    def __call__(self, f: jnp.ndarray) -> jnp.ndarray:
        return pipeline_apply(self.plan, f, "mul", self.weights)

    @property
    def T(self):
        """(B W A)^T = A^T W B^T: the exact-adjoint datapaths around the
        self-adjoint filter (not itself a fusible pattern)."""
        return CompositeOperator((
            RadonOperator(self.plan, "adjoint", self.dtype),
            ProjectionFilter(self.weights),
            RadonOperator(self.plan, "inverse_adjoint", self.dtype)))

    @property
    def inverse(self):
        raise TypeError(
            "FusedProjectionPipeline (inverse @ filter @ forward) has no "
            "exact inverse: the pointwise filter is not invertible in "
            "exact arithmetic")

    def __matmul__(self, other):
        return _compose(self, other)

    def _aot_key(self):
        return ("fused_mul", self.plan, self.dtype.name, id(self.weights))

    def _aot_pins(self):
        return (self.weights,)

    def __repr__(self) -> str:
        return (f"FusedProjectionPipeline({self.shape_in}, "
                f"method={self.plan.method!r})")


class Conv2D:
    """Exact circular 2-D convolution by a fixed kernel, as an operator.

    ``Conv2D(shape, kernel)`` convolves ``(H, W)`` images (or
    ``(B, H, W)`` stacks) with ``kernel`` on the ``(H, W)`` torus --
    the paper's Sec. VI application surfaced as operator fusion.  On
    square prime geometries the application is the fused projection-
    domain pipeline (transform, per-direction 1-D convolution, and
    inverse in ONE kernel launch on pipeline-capable backends); other
    geometries fold the exact prime-embedded linear convolution onto
    the torus.  ``jax.grad`` is exact in both the image and (via
    ``kernel=``-differentiation) the kernel, through every backend.

    ``op.T`` is the exact adjoint -- circular *correlation*, i.e.
    convolution by the flipped kernel.  ``as_matrix()`` materializes the
    dense circulant for small-N tests.
    """

    __slots__ = ("plan", "kernel", "dtype")

    def __init__(self, shape, kernel, dtype=None, method: Optional[str] = None,
                 *, strip_rows: Optional[int] = None,
                 m_block: Optional[int] = None,
                 batch_impl: Optional[str] = None,
                 block_rows: Optional[int] = None,
                 stream_rows: Optional[int] = None,
                 block_batch: Optional[int] = None,
                 mesh=None):
        kernel = jnp.asarray(kernel)
        shape = tuple(int(s) for s in shape)
        h, w = shape[-2:]
        if kernel.ndim != 2 or kernel.shape[0] > h or kernel.shape[1] > w:
            raise ValueError(
                f"kernel must be 2-D and fit the {shape[-2:]} torus, "
                f"got {kernel.shape}")
        if dtype is None:
            dtype = kernel.dtype
        # the kernel lives zero-padded on the full (H, W) torus
        kernel = jnp.pad(kernel.astype(dtype),
                         ((0, h - kernel.shape[0]), (0, w - kernel.shape[1])))
        plan = DPRT(shape, dtype, method, strip_rows=strip_rows,
                    m_block=m_block, batch_impl=batch_impl,
                    block_rows=block_rows, stream_rows=stream_rows,
                    block_batch=block_batch, mesh=mesh).plan
        object.__setattr__(self, "plan", plan)
        object.__setattr__(self, "kernel", kernel)
        object.__setattr__(self, "dtype", jnp.dtype(dtype))

    def __setattr__(self, name, value):
        raise AttributeError("Conv2D is immutable")

    @property
    def shape_in(self):
        return self.plan.geometry.image_shape

    shape_out = property(lambda self: self.plan.geometry.image_shape)

    @property
    def dtype_in(self):
        return self.dtype

    @property
    def dtype_out(self):
        return jnp.dtype(accum_dtype_for(self.dtype, self.plan.geometry.prime))

    def __call__(self, f: jnp.ndarray) -> jnp.ndarray:
        g = self.plan.geometry
        if g.native:
            return pipeline_apply(self.plan, f, "conv", self.kernel)
        # non-native: the true (H, W)-torus convolution = fold of the
        # exact linear convolution (conv.py routes its DPRT stages
        # through the same differentiable pipeline appliers).  The
        # plan's remaining knobs (mesh, batch/blocking) travel via an
        # ambient scope: conv resolves them eagerly per call.
        from repro.core.conv import circ_conv2d_dprt  # lazy: conv -> radon
        with ambient.config(mesh=self.plan.mesh,
                            batch_impl=self.plan.batch_impl,
                            block_rows=self.plan.block_rows,
                            stream_rows=self.plan.stream_rows,
                            block_batch=self.plan.block_batch):
            return circ_conv2d_dprt(f, self.kernel,
                                    method=self.plan.method,
                                    strip_rows=self.plan.strip_rows,
                                    m_block=self.plan.m_block)

    @property
    def T(self) -> "Conv2D":
        """Circular correlation: convolution by the torus-flipped kernel
        (same plan knobs, blocking/batching included)."""
        return Conv2D(self.shape_in, flip_image(self.kernel), self.dtype,
                      self.plan.method, strip_rows=self.plan.strip_rows,
                      m_block=self.plan.m_block,
                      batch_impl=self.plan.batch_impl,
                      block_rows=self.plan.block_rows,
                      stream_rows=self.plan.stream_rows,
                      block_batch=self.plan.block_batch,
                      mesh=self.plan.mesh)

    def __matmul__(self, other):
        return _compose(self, other)

    @property
    def inverse(self):
        raise TypeError(
            "Conv2D has no exact inverse (deconvolution is not an "
            "integer-exact operation)")

    def as_matrix(self) -> jnp.ndarray:
        """Dense (H*W, H*W) circulant of this convolution (small N)."""
        size = 1
        for s in self.shape_in:
            size *= s
        basis = jnp.eye(size, dtype=self.dtype)
        cols = jax.vmap(lambda e: self(e.reshape(self.shape_in)).ravel())(
            basis)
        return cols.T

    def _aot_key(self):
        return ("conv2d", self.plan, self.dtype.name, id(self.kernel))

    def _aot_pins(self):
        return (self.kernel,)

    # -- AOT / persistent executable export --------------------------------
    def lower(self):
        """Trace + lower the convolution for its declared input aval."""
        spec = jax.ShapeDtypeStruct(self.shape_in, self.dtype_in)
        return jax.jit(self.__call__).lower(spec)

    def compile(self):
        """The AOT-compiled executable for this (geometry, kernel),
        cached process-wide alongside the transform executables (the
        kernel array is pinned for the life of the entry)."""
        return _compile_once(self, self._aot_key(), "conv2d",
                             self._aot_pins())

    def cache_token(self) -> str:
        """Persistent-cache identity: like the transform operators',
        plus a digest of the kernel taps -- the weights are baked into
        the compiled executable, so different kernels must never share
        a blob."""
        import hashlib
        import numpy as _np
        p = self.plan
        shape = "x".join(str(s) for s in self.shape_in)
        digest = hashlib.sha1(
            _np.asarray(self.kernel).tobytes()).hexdigest()[:16]
        knobs = "h{}_m{}_sr{}_br{}_bb{}".format(
            p.strip_rows, p.m_block, p.stream_rows, p.block_rows,
            p.block_batch)
        return (f"conv2d_{shape}_{self.dtype.name}_{p.method}_k{digest}_"
                f"{knobs}_{_topology_token(p.mesh)}")

    def export_executable(self) -> bytes:
        """Serialize the AOT executable (see
        :meth:`RadonOperator.export_executable`)."""
        return _export_compiled(self.compile())

    def import_executable(self, data: bytes):
        """Install executable bytes from :meth:`export_executable` in
        the in-process AOT cache under this operator's key."""
        exe = _import_compiled(data)
        key = self._aot_key()
        with _CACHE_LOCK:
            _AOT_CACHE[key] = exe
            _AOT_PINS.setdefault(key, self._aot_pins())
        return exe

    def describe(self) -> dict:
        d = dict(self.plan.describe())
        d.update(kind="conv2d", kernel_shape=tuple(self.kernel.shape),
                 pipeline=self.plan.backend.pipeline is not None)
        return d

    def __repr__(self) -> str:
        return (f"Conv2D({self.shape_in}, kernel={self.kernel.shape}, "
                f"{self.dtype.name}, method={self.plan.method!r})")


# operators cross jit boundaries as zero-leaf pytrees, like their plans
jax.tree_util.register_pytree_node(
    RadonOperator,
    lambda op: ((), op),
    lambda op, _: op,
)
jax.tree_util.register_pytree_node(
    CompositeOperator,
    lambda op: ((), op),
    lambda op, _: op,
)


def DPRT(shape, dtype=jnp.int32, method: Optional[str] = None, *,
         strip_rows: Optional[int] = None,
         m_block: Optional[int] = None,
         batch_impl: Optional[str] = None,
         block_rows: Optional[int] = None,
         stream_rows: Optional[int] = None,
         block_batch: Optional[int] = None,
         mesh=None) -> RadonOperator:
    """The forward DPRT operator for one input geometry.

    ``shape`` is ``(H, W)`` or ``(B, H, W)`` -- any size; non-prime
    geometries are zero-embedded into the next prime and ``op.inverse``
    crops back (bit-exact round trip for integer images).  Knobs left
    unset resolve against the ambient :func:`repro.radon.config` scope,
    then fall back to ``method="auto"`` (the registry's best backend for
    the shape/dtype/mesh).

    The returned operator is a cheap immutable view: plans, traces and
    AOT executables are cached per geometry process-wide, so building
    the "same" operator twice costs a dict lookup and shares all
    compilation state.
    """
    plan = get_plan(
        tuple(int(s) for s in shape), dtype,
        ambient.resolve("method", method, "auto"),
        strip_rows=ambient.resolve("strip_rows", strip_rows),
        m_block=ambient.resolve("m_block", m_block),
        batch_impl=ambient.resolve("batch_impl", batch_impl, "auto"),
        block_rows=ambient.resolve("block_rows", block_rows),
        stream_rows=ambient.resolve("stream_rows", stream_rows),
        block_batch=ambient.resolve("block_batch", block_batch),
        mesh=ambient.resolve("mesh", mesh))
    return RadonOperator(plan, "forward", dtype)


def operator_for(shape, dtype, knobs: tuple) -> RadonOperator:
    """The cached forward operator for one geometry from an
    :func:`repro.radon.ambient.snapshot_knobs` tuple -- the shared
    builder for call sites (``core/conv``, ``core/dft``) that carry the
    full knob snapshot through their own jit static arguments."""
    return DPRT(shape, dtype, **ambient.knobs_kwargs(knobs))

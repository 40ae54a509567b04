"""Closed-loop batch traffic: one caller runs whole stacks back to back.

Each step runs the configuration's entry on one stack of ``batch``
images and waits for it on the device:

* ``op: "dprt"``: the forward of one stack and the inverse of another
  stack's projections, through the compiled ``radon.DPRT`` executables
  (``op.compile()``, ``op.inverse.compile()``), on a mesh where the
  configuration gives one.  2 x batch images a step.  The projections
  the inverse takes are made at set-up by the same forward executable;
  the inverse is compared with the reference's inverse of the
  reference's forward of the images they came from, so no expected
  value rests on what the program made.
* ``op: "conv"``: the compiled ``radon.Conv2D`` executable on one stack,
  with the configuration's fixed kernel.  batch images a step.

The mix's ``stacks`` stacks are made on the device from the seed and
rotate, so no step repeats its predecessor's input.  Outputs stay on
the device; the first step's, the last step's and ``keep_random`` more
drawn from the seed are kept for the comparison with the reference, on
``check_images`` images each drawn from the seed, half of them from each
half of the batch.  The configuration's ``knobs`` go to the operator's
constructor as they are (``stream_rows``, ``method``, ...).
"""
from __future__ import annotations

import time

import numpy as np

from bench import reference


def _entry(cfg: dict, mesh):
    import jax.numpy as jnp
    from repro import radon

    shape = (int(cfg["batch"]), int(cfg["n"]), int(cfg["n"]))
    dtype = jnp.dtype(cfg["dtype"])
    knobs = dict(cfg.get("knobs") or {})
    if cfg["op"] == "dprt":
        op = radon.DPRT(shape, dtype, mesh=mesh, **knobs)
        return op, (op.compile(), op.inverse.compile())
    if cfg["op"] == "conv":
        kernel = jnp.asarray(np.asarray(cfg["conv_kernel"], dtype))
        op = radon.Conv2D(shape, kernel, dtype, mesh=mesh, **knobs)
        return op, (op.compile(),)
    raise ValueError(f"unknown op {cfg['op']!r}")


def make_stacks(h, shape, dtype, count: int, sharding):
    """``count`` stacks of uniform pixels from the seed, in one jitted
    call on the device."""
    import jax
    import jax.numpy as jnp

    hi = int(np.iinfo(np.dtype(dtype)).max) + 1

    def make(key):
        keys = jax.random.split(key, count)
        return tuple(jax.random.randint(k, shape, 0, hi, jnp.int32)
                     .astype(dtype) for k in keys)
    out_sh = None if sharding is None else (sharding,) * count
    return list(jax.jit(make, out_shardings=out_sh)(h.jax_key()))


class State:
    def __init__(self, h):
        cfg = h.config
        self.kind = cfg["op"]
        self.batch = int(cfg["batch"])
        self.n = int(cfg["n"])
        self.mesh = h.mesh()
        self.op, self.exes = _entry(cfg, self.mesh)
        sharding = getattr(self.op, "input_sharding", None)
        shape = (self.batch, self.n, self.n)
        self.stacks = make_stacks(h, shape, cfg["dtype"],
                                  int(h.traffic["stacks"]), sharding)
        if self.kind == "dprt":
            fwd = self.exes[0]
            self.projs = [fwd(x) for x in self.stacks]
        else:
            self.projs = None
        self.kernel = (np.asarray(cfg["conv_kernel"], np.int64)
                       if self.kind == "conv" else None)
        self.step_fn = self.default_step
        self.kept = {}
        self.images_per_step = self.batch * (2 if self.kind == "dprt" else 1)

    def default_step(self, s: int):
        k = s % len(self.stacks)
        if self.kind == "dprt":
            fwd, inv = self.exes
            return fwd(self.stacks[k]), inv(
                self.projs[(k + 1) % len(self.stacks)])
        return (self.exes[0](self.stacks[k]),)


def setup(h):
    import jax

    st = State(h)
    # warm every executable the window calls, on every stack
    for s in range(len(st.stacks)):
        jax.block_until_ready(st.step_fn(s))
    return st


def run_window(h, st, seconds: float) -> dict:
    import jax

    rng = h.rng("keep")
    want_random = int(h.traffic["keep_random"])
    reservoir, seen = [], 0
    steps = 0
    t0 = time.perf_counter()
    while True:
        with h.span("bench.step", step=steps):
            out = st.step_fn(steps)
            jax.block_until_ready(out)
        if steps == 0:
            st.kept[0] = out
        else:
            # reservoir sampling over the steps after the first, drawn
            # from the seed; the newest step is always held as the last
            seen += 1
            if len(reservoir) < want_random:
                reservoir.append(steps)
            else:
                j = int(rng.integers(0, seen))
                if j < want_random:
                    st.kept.pop(reservoir[j], None)
                    reservoir[j] = steps
            st.kept[steps] = out
            for s in list(st.kept):
                if s not in (0, steps) and s not in reservoir:
                    del st.kept[s]
        steps += 1
        if time.perf_counter() - t0 >= seconds:
            break
    elapsed = time.perf_counter() - t0
    images = steps * st.images_per_step
    return {
        "attempted": images, "failed": 0, "elapsed_s": elapsed,
        "metrics": {"images_per_s": images / elapsed},
        "counts": {"steps": steps, "images": images,
                   "ops": images_by_kind(st, steps)},
    }


def images_by_kind(st, steps: int) -> dict:
    if st.kind == "dprt":
        return {"forward": steps * st.batch, "inverse": steps * st.batch}
    return {"conv": steps * st.batch}


def collect(h, st, window: dict) -> dict:
    """Host copies of what the comparison needs; the device state can go."""
    rng = h.rng("check")
    per = int(h.traffic["check_images"])
    half = st.batch // 2
    samples = []
    for s, out in sorted(st.kept.items()):
        idx = np.concatenate([
            rng.choice(half, per // 2, replace=False),
            half + rng.choice(st.batch - half, per - per // 2,
                              replace=False)])
        idx = np.sort(idx)
        k = s % len(st.stacks)
        item = {"step": s, "idx": idx,
                "x": np.asarray(st.stacks[k][idx]),
                "out": [np.asarray(o[idx]) for o in out]}
        if st.kind == "dprt":
            # the images whose projections the inverse was given
            src = st.stacks[(k + 1) % len(st.stacks)]
            item["x_inv"] = np.asarray(src[idx])
        samples.append(item)
    return {"kind": st.kind, "samples": samples, "kernel": st.kernel}


def compare(got: np.ndarray, want: np.ndarray) -> int:
    if got.shape != want.shape:
        return int(want.size)
    return int(np.count_nonzero(got.astype(np.int64) != want))


def check(h, col: dict) -> dict:
    """{name: (value, limit)}: mismatched output elements against the
    reference.  The transforms are exact integer arithmetic, so every
    limit is 0."""
    ref, acc = reference, np.int64
    out = {}
    if col["kind"] == "dprt":
        fwd = inv = 0
        for it in col["samples"]:
            fwd += compare(it["out"][0], ref.dprt(it["x"], acc))
            inv += compare(it["out"][1],
                           ref.idprt(ref.dprt(it["x_inv"], acc), acc))
        out["fwd_mismatch"] = (fwd, 0)
        out["inv_mismatch"] = (inv, 0)
    else:
        bad = 0
        for it in col["samples"]:
            bad += compare(it["out"][0],
                           ref.circ_conv2d(it["x"], col["kernel"], acc))
        out["conv_mismatch"] = (bad, 0)
    return out

"""The control: the plain reference put in the program's place, computed
with int16 accumulators, the integer precision below the int32 that the
configurations state.  Its outputs have to come out as not correct.

    python3 bench/control.py --workload dprt251.batch --seed 11 --seconds 4

runs the cell as ``bench/run.py`` does, with every call the window makes
into the program answered by the control instead
(:mod:`bench.reference_jnp`: the reference's sums, wrapping in int16).  The
convolution's control is the projection-domain definition (forward,
per-direction 1-D circular convolution, inverse), which is what the
configuration computes in int32; a direct spatial convolution of 8-bit
pixels by this kernel never leaves int16's range, so it would be no
control.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
from bench import reference_jnp as ref  # noqa: E402

ACC = "int16"


def install(state) -> None:
    """Put the control in the place of what the window drives."""
    import jax
    import jax.numpy as jnp
    if hasattr(state, "step_fn"):                      # batch traffic
        if state.kind == "dprt":
            f = jax.jit(lambda x: ref.dprt(x, ACC).astype(jnp.int32))
            g = jax.jit(lambda r: ref.idprt(r, ACC).astype(jnp.int32))
            k = len(state.stacks)

            def step(s):
                i = s % k
                return f(state.stacks[i]), g(state.projs[(i + 1) % k])
        else:
            kern = jnp.asarray(state.kernel)
            c = jax.jit(lambda x: ref.conv(x, kern, ACC).astype(jnp.int32))

            def step(s):
                return (c(state.stacks[s % len(state.stacks)]),)
        state.step_fn = step
        for s in range(len(state.stacks)):             # compile outside
            jax.block_until_ready(step(s))             # the window
        return
    fns = {dp: jax.jit(lambda x, f=getattr(ref, name):
                       f(x, ACC).astype(jnp.int32))
           for dp, name in state.mix["reference"].items()}
    for datapath, svc in state.services.items():      # open-loop traffic
        fn = fns[datapath]

        def execute(stack, fn=fn, svc=svc):
            b = stack.shape[0]
            warm = min(w for w in svc.sizes if w >= b)
            pad = np.zeros((warm - b,) + stack.shape[1:], stack.dtype)
            full = jnp.asarray(np.concatenate([stack, pad]))
            return np.asarray(fn(full))[:b]
        for b in svc.sizes:
            execute(np.zeros((b,) + svc.request_shape,
                             np.dtype(svc.request_dtype.name)))
        svc.execute = execute


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from bench import run
    if not run.prepare():
        return 2
    from bench import harness
    try:
        result = harness.run_cell(ROOT, args.workload, args.seed,
                                  args.seconds, False, t_start=T_START,
                                  patch=install)
    except harness.NoAccelerator as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

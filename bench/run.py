"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload dprt251.batch --seed 7 --seconds 20 --trace 0
    python3 -m bench.run ...               (the same, from the checkout's root)

The cell, its configuration, its traffic mix and its metrics come from
``BENCHMARK.json`` and the files it names under ``bench/``.  With
``--trace 0`` the line holds the cell's end-to-end metrics; with
``--trace 1`` a few seconds of the window run under the profiler and the
line holds its per-layer metrics, the device's busy time and the
breakdown.  The last lines on standard error, and the result's last
key, give each number compared with the reference beside its limit.

Exit codes: 0 for a result (correct or not), 2 for a malformed spec or
a checkout without the program, 3 when JAX finds no TPU or fewer chips
than the cell asks for, 4 when a per-layer metric listed for the cell
reads nothing in its traced run.  Only a 0 prints a result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def prepare() -> bool:
    """Put the program and the benchmark on the path and JAX's compile
    cache at its fixed place in the checkout.  False, with a message,
    where the checkout holds no program."""
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under {ROOT / 'src'}", file=sys.stderr)
        return False
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not prepare():
        return 2

    from bench import harness, spec
    try:
        result = harness.run_cell(ROOT, args.workload, args.seed,
                                  args.seconds, bool(args.trace),
                                  t_start=T_START)
    except harness.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    except spec.SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    except harness.MissingMetric as e:
        print(f"bench: {e}", file=sys.stderr)
        return 4
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

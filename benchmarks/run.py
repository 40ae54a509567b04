"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (units in ``derived`` where the
quantity is a model count rather than wall time) and writes the
``BENCH_dprt.json`` artifact (method x N x batch rows from the DPRT
implementation shoot-out) at the repo root so subsequent PRs have a
structured perf baseline to regress against.

Regression workflow (see ``benchmarks/check_regression.py``):

    python -m benchmarks.run             # full run, REWRITES the baseline
    python -m benchmarks.run --check     # full run, COMPARES against the
                                         # committed baseline instead of
                                         # rewriting; exit 1 on slowdown
    python -m benchmarks.run --only serve  # just the serve/* modules;
                                         # without --check this MERGES the
                                         # fresh rows into the baseline
                                         # (other rows kept verbatim)
    python -m benchmarks.check_regression  # guarded rows only (DPRT
                                         # shoot-out + conv/DFT pipelines
                                         # + sharded/stream/serve rows)
"""
import argparse
import sys
import traceback


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--check", action="store_true",
                    help="compare against the committed baseline instead "
                         "of rewriting it; exit 1 on regression")
    ap.add_argument("--only", default=None, metavar="PREFIX",
                    help="run only the modules producing rows under this "
                         "baseline prefix (e.g. serve, conv, dprt_impl)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import (table1_forward_cycles, table2_inverse_cycles,
                   table3_resources, fig17_runtime_vs_n, fig19_20_pareto,
                   bench_conv, bench_dprt_impl, bench_dprt_sharded,
                   bench_recon, bench_serve, bench_stream, bench_lm_step,
                   roofline_report, check_regression, common)

    # guarded-prefix -> producing module; --only selects through this
    prefix_modules = {
        "dprt_impl/": bench_dprt_impl,
        "conv/": bench_conv,
        "dft/": bench_conv,
        "stream/": bench_stream,
        "sharded_stream/": bench_stream,
        "serve/": bench_serve,
        "recon/": bench_recon,
    }
    all_modules = [table1_forward_cycles, table2_inverse_cycles,
                   table3_resources, fig17_runtime_vs_n, fig19_20_pareto,
                   bench_conv, bench_dprt_impl, bench_dprt_sharded,
                   bench_recon, bench_serve, bench_stream, bench_lm_step,
                   roofline_report]
    if args.only is None:
        modules, prefixes = all_modules, common.BENCH_PREFIXES
    else:
        prefixes = tuple(p for p in prefix_modules
                         if p.startswith(args.only))
        if not prefixes:
            raise SystemExit(
                f"--only {args.only!r} matches no guarded prefix "
                f"(choose from {sorted(prefix_modules)})")
        modules = list(dict.fromkeys(prefix_modules[p] for p in prefixes))

    print("name,us_per_call,derived")
    failed = []
    for mod in modules:
        try:
            mod.main()
        except Exception:
            failed.append(mod)
            print(f"{mod.__name__},ERROR,", file=sys.stderr)
            traceback.print_exc()
    if any(prefix_modules[p] in failed for p in prefixes):
        print("# baseline NOT touched (a guarded-row module failed)",
              file=sys.stderr)
    elif args.check:
        # guard mode: gate perf against the committed baseline -- and,
        # on full runs, the public-API health smoke with it (a partial
        # --only run keeps the quick path quick; scripts/ci.sh runs
        # selfcheck as its own step)
        fresh = [r for r in common.ROWS if r["name"].startswith(prefixes)]
        guard_failed = check_regression.run_guard(
            fresh, prefixes=None if args.only is None else prefixes) != 0
        if args.only is None:
            import contextlib
            from repro.radon import selfcheck
            with contextlib.redirect_stdout(sys.stderr):  # stdout CSV-pure
                if selfcheck.run(run_bench=False) != 0:
                    print("# FAIL: repro.radon.selfcheck", file=sys.stderr)
                    guard_failed = True
        if guard_failed:
            raise SystemExit(1)
    elif args.only is not None:
        # partial rerun: refresh ONLY the measured prefixes in the
        # artifact, keep every other committed row byte-identical
        common.merge_json(common.BENCH_DPRT_PATH, prefixes)
    else:
        # never clobber the committed perf baseline with partial rows
        common.dump_json(common.BENCH_DPRT_PATH,
                         prefix=common.BENCH_PREFIXES)
    if failed:
        raise SystemExit(f"{len(failed)} benchmark modules failed")


if __name__ == "__main__":
    main(sys.argv[1:])

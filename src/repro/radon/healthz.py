"""Process-level cache/trace health report: ``python -m repro.radon.healthz``.

The ``/healthz``-style counterpart to :mod:`repro.radon.selfcheck`:
where selfcheck *exercises* the API, this module *inspects* a process --
plan-cache hit/miss/eviction counters, the warm plan entries themselves,
per-datapath trace counts (the zero-retrace serving property as data),
the in-memory AOT executable census, and the environment fingerprint
persistent executable blobs are keyed against.  The same counters back
:meth:`repro.launch.service.DPRTService.healthz`, which prepends its
admission-queue and latency sections.

The ``spans`` section (:mod:`repro.core.spans`) comes last: per span
name, how many times it ran, its total and self seconds and the counts
credited to it, then the process counters.  ``radon.plan`` is one plan
build (a plan-cache miss); ``radon.compile`` one executable built for
``op.compile()`` (an in-memory AOT-cache miss), with its two children
``radon.lower`` (trace and lowering, Pallas to Mosaic included) and
``radon.backend_compile`` (an XLA compile or a load from JAX's
persistent cache); ``radon.aot_restore`` one load of a serialized
executable by :class:`~repro.radon.PersistentAOTCache`.  The counters
``compile_cache_hits``, ``compile_cache_misses`` and
``backend_compiles`` come from :mod:`jax.monitoring`, credited to the
innermost open span and rolled up into its parents.  Last come the
newest ``radon.compile`` and ``radon.aot_restore`` records, one
``built`` line each: which executable, how long, how many cache misses.
:func:`span_lines` formats the section, and
:meth:`~repro.launch.service.DPRTService.healthz` and
:meth:`~repro.launch.router.ServiceRouter.healthz` end with it, so the
serving process reports its own set-up; a standalone
``python -m repro.radon.healthz`` has built nothing, so its section
lists no span.

``report()`` returns the formatted text; :func:`snapshot` the raw dict
(for tests and structured scrapes).  Exit code is always 0 -- counters
are a readout, not a judgement; the service healthz is what gates.
"""
from __future__ import annotations

__all__ = ["snapshot", "report", "span_lines", "main"]


def snapshot() -> dict:
    """The raw counter dict behind :func:`report`."""
    import os

    from repro.core import spans

    from . import (aot_cache_info, aot_fingerprint, plan_cache_entries,
                   plan_cache_info, trace_count, trace_counts)
    # distinct plans (different knobs/mesh) can share a (shape, dtype,
    # kind) label: aggregate, so the per-path counts still sum to the
    # process total
    traces: dict = {}
    for (plan, kind, shape, dtype), n in trace_counts().items():
        label = f"{shape}/{dtype}/{kind}"
        traces[label] = traces.get(label, 0) + n
    return {
        "fingerprint": aot_fingerprint(),
        "plan_cache": plan_cache_info()._asdict(),
        "plans": plan_cache_entries(),
        "traces_total": trace_count(),
        "traces": dict(sorted(traces.items())),
        "aot_cache": aot_cache_info(),
        # armed chaos spec, if any (REPRO_FAULTS): echoed so "why is
        # this worker misbehaving" is answerable from its healthz alone
        "faults_env": os.environ.get("REPRO_FAULTS") or None,
        "spans": spans.snapshot(),
    }


#: spans whose records :func:`span_lines` lists one by one
BUILT = ("radon.compile", "radon.aot_restore")
#: newest such records listed
BUILT_SHOWN = 16


def span_lines(snap=None) -> list:
    """The ``spans`` section: per span name its count, total and self
    seconds and the counts credited to it; the process counters; then
    the newest executables built or restored, each with its attributes,
    seconds and credits.  ``snap`` is a :func:`repro.core.spans.snapshot`
    (taken now if omitted)."""
    if snap is None:
        from repro.core import spans
        snap = spans.snapshot()
    lines = ["[healthz] spans"]
    for name, agg in sorted(snap["spans"].items()):
        extra = "".join(f" {k}={v}" for k, v in sorted(agg.items())
                        if k not in ("count", "total_s", "self_s"))
        lines.append(f"[healthz]   span {name} x{agg['count']} "
                     f"total={agg['total_s']:.6f}s "
                     f"self={agg['self_s']:.6f}s{extra}")
    for name, n in sorted(snap["counters"].items()):
        lines.append(f"[healthz]   counter {name}={n}")
    built = [r for r in snap["records"] if r["name"] in BUILT]
    for r in built[-BUILT_SHOWN:]:
        fields = {**r["attrs"], **r["credits"]}
        lines.append(f"[healthz]   built {r['name']} "
                     f"{(r['end_ns'] - r['start_ns']) / 1e9:.6f}s"
                     + "".join(f" {k}={v}" for k, v in fields.items()))
    return lines


def report() -> str:
    """Format :func:`snapshot` as the ``[healthz]`` text block."""
    s = snapshot()
    lines = [
        f"[healthz] {s['fingerprint']}",
        "[healthz] plan_cache hits={hits} misses={misses} "
        "currsize={currsize} maxsize={maxsize} evictions={evictions}"
        .format(**s["plan_cache"]),
    ]
    for p in s["plans"]:
        lines.append(f"[healthz]   plan {p.get('image_shape')} "
                     f"method={p.get('method')}")
    lines.append(f"[healthz] traces total={s['traces_total']}")
    for path, count in s["traces"].items():
        lines.append(f"[healthz]   trace {path} x{count}")
    aot = s["aot_cache"]
    lines.append(f"[healthz] aot_executables currsize={aot['currsize']}")
    for key in aot["keys"]:
        lines.append(f"[healthz]   aot {key}")
    if s.get("faults_env"):
        lines.append(f"[healthz] faults_env {s['faults_env']}")
    lines += span_lines(s["spans"])
    return "\n".join(lines)


def main(argv=None) -> int:
    print(report())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

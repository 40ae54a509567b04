"""Set-up spans and counters (:mod:`repro.core.spans`) and where the
program opens them: plan builds, executable builds and restores, and
their readout in :mod:`repro.radon.healthz`."""
import time

import jax
import jax.numpy as jnp
import pytest

from repro import radon
from repro.core import get_plan, spans
from repro.launch.router import ServiceRouter
from repro.launch.service import DPRTService
from repro.radon import healthz


def _since(t0):
    """The shared recorder's records of spans opened after ``t0``
    (``perf_counter_ns``); the ring may already be full."""
    return [r for r in spans.snapshot()["records"] if r["start_ns"] >= t0]


def test_nesting_sets_parent():
    rec = spans.Recorder()
    with rec.span("outer", k=1):
        with rec.span("inner"):
            pass
    inner, outer = rec.snapshot()["records"]
    assert (inner["name"], inner["parent"]) == ("inner", "outer")
    assert outer["parent"] is None and outer["attrs"] == {"k": 1}
    assert outer["start_ns"] <= inner["start_ns"] <= inner["end_ns"] \
        <= outer["end_ns"]


def test_self_time_excludes_children():
    rec = spans.Recorder()
    with rec.span("outer"):
        with rec.span("inner"):
            time.sleep(0.05)
    agg = rec.snapshot()["spans"]
    assert agg["inner"]["self_s"] == agg["inner"]["total_s"] >= 0.05
    assert agg["outer"]["total_s"] >= agg["inner"]["total_s"]
    assert agg["outer"]["self_s"] < 0.05
    assert agg["outer"]["self_s"] + agg["inner"]["total_s"] == \
        pytest.approx(agg["outer"]["total_s"])


def test_aggregates_count_and_sum():
    rec = spans.Recorder()
    for _ in range(3):
        with rec.span("step"):
            pass
    snap = rec.snapshot()
    durations = [r["end_ns"] - r["start_ns"] for r in snap["records"]]
    assert snap["spans"]["step"]["count"] == 3
    assert snap["spans"]["step"]["total_s"] == \
        pytest.approx(sum(durations) / 1e9)


def test_record_ring_keeps_the_newest_256():
    rec = spans.Recorder()
    for i in range(300):
        with rec.span(f"s{i}"):
            pass
    records = rec.snapshot()["records"]
    assert len(records) == spans.RING == 256
    assert records[0]["name"] == "s44" and records[-1]["name"] == "s299"


def test_span_that_raises_is_recorded():
    rec = spans.Recorder()
    with pytest.raises(ValueError):
        with rec.span("outer"):
            with rec.span("failing"):
                raise ValueError("boom")
    assert [r["name"] for r in rec.snapshot()["records"]] == \
        ["failing", "outer"]
    with rec.span("after"):             # the stack unwound
        pass
    assert rec.snapshot()["records"][-1]["parent"] is None


def test_count_credits_innermost_span_and_rolls_up():
    rec = spans.Recorder()
    rec.count("hits")                    # outside any span: counter only
    with rec.span("outer"):
        with rec.span("inner"):
            rec.count("hits", 2)
        rec.count("hits")
    snap = rec.snapshot()
    assert snap["counters"] == {"hits": 4}
    assert snap["spans"]["inner"]["hits"] == 2
    assert snap["spans"]["outer"]["hits"] == 3
    assert snap["records"][0]["credits"] == {"hits": 2}


def test_monitoring_cache_event_credited_to_innermost_span():
    before = spans.snapshot()["counters"].get("compile_cache_misses", 0)
    with spans.span("test.outer"):
        with spans.span("test.inner"):
            jax.monitoring.record_event(
                "/jax/compilation_cache/cache_misses")
    snap = spans.snapshot()
    assert snap["counters"]["compile_cache_misses"] == before + 1
    inner = [r for r in snap["records"] if r["name"] == "test.inner"][-1]
    assert inner["credits"] == {"compile_cache_misses": 1}
    assert snap["spans"]["test.outer"]["compile_cache_misses"] >= 1


def test_plan_build_opens_one_span_per_miss():
    shape = (3, 13, 13)
    t0 = time.perf_counter_ns()
    get_plan(shape, jnp.int16, "horner")
    get_plan(shape, jnp.int16, "horner")       # plan-cache hit: no span
    new = _since(t0)
    assert [r["name"] for r in new] == ["radon.plan"]
    assert new[0]["attrs"]["shape"] == shape
    assert new[0]["attrs"]["method"] == "horner"


def test_compile_records_lower_and_backend_compile_once():
    radon.aot_cache_clear()
    op = radon.DPRT((2, 7, 7), jnp.uint8)
    t0 = time.perf_counter_ns()
    op.compile()
    new = _since(t0)
    compiles = [r for r in new if r["name"] == "radon.compile"]
    assert len(compiles) == 1
    assert compiles[0]["attrs"] == {"kind": "forward",
                                    "shape_in": (2, 7, 7), "dtype": "uint8"}
    children = sorted(r["name"] for r in new
                      if r["parent"] == "radon.compile")
    assert children == ["radon.backend_compile", "radon.lower"]
    assert compiles[0]["credits"].get("backend_compiles") == 1

    t1 = time.perf_counter_ns()
    op.compile()                        # in-memory AOT hit: no span
    assert _since(t1) == []


def test_persistent_restore_opens_aot_restore_span(tmp_path):
    radon.aot_cache_clear()
    op = radon.DPRT((2, 11, 11), jnp.int32)
    radon.PersistentAOTCache(str(tmp_path)).get_or_compile(op)
    radon.aot_cache_clear()
    t0 = time.perf_counter_ns()
    warm = radon.PersistentAOTCache(str(tmp_path))
    warm.get_or_compile(op)
    assert warm.hits == 1
    names = [r["name"] for r in _since(t0)]
    assert names == ["radon.aot_restore"]


def test_healthz_reports_spans():
    radon.DPRT((2, 7, 7), jnp.uint8).compile()
    snap = healthz.snapshot()
    assert {"spans", "counters", "records"} <= set(snap["spans"])
    assert snap["spans"]["spans"]["radon.compile"]["count"] >= 1
    text = healthz.report()
    assert "[healthz] spans" in text
    assert "[healthz]   span radon.compile x" in text
    assert "[healthz]   span radon.lower x" in text


def test_span_lines_list_each_build_with_attrs_and_credits():
    rec = spans.Recorder()
    with rec.span("radon.plan"):
        pass
    with rec.span("radon.compile", kind="forward", shape_in=(2, 7, 7)):
        rec.count("compile_cache_misses")
    lines = healthz.span_lines(rec.snapshot())
    assert lines[0] == "[healthz] spans"
    built = [ln for ln in lines if "built" in ln]
    assert len(built) == 1              # plans are not listed one by one
    assert built[0].startswith("[healthz]   built radon.compile ")
    assert built[0].endswith(
        " kind=forward shape_in=(2, 7, 7) compile_cache_misses=1")
    assert "[healthz]   counter compile_cache_misses=1" in lines


def test_span_lines_show_only_the_newest_builds():
    rec = spans.Recorder()
    for i in range(healthz.BUILT_SHOWN + 4):
        with rec.span("radon.aot_restore", token=f"t{i}"):
            pass
    built = [ln for ln in healthz.span_lines(rec.snapshot())
             if "built" in ln]
    assert len(built) == healthz.BUILT_SHOWN
    assert built[-1].endswith(f" token=t{healthz.BUILT_SHOWN + 3}")


def test_service_healthz_ends_with_its_set_up_spans():
    radon.aot_cache_clear()
    svc = DPRTService((9, 9), jnp.int32, max_batch=2, max_wait_us=100.0)
    svc.warmup()
    text = svc.healthz()
    assert text.startswith("[healthz] OK ")
    tail = text[text.index("[healthz] spans"):]
    assert "[healthz]   span radon.compile x" in tail
    assert "[healthz]   built radon.compile " in tail
    assert "shape_in=(2, 9, 9)" in tail     # the warmed batch size 2


def test_router_healthz_ends_with_set_up_spans():
    radon.aot_cache_clear()
    router = ServiceRouter(max_batch=2, max_wait_us=100.0)
    router.prefill([{"n": 11}])         # warms synchronously, no loop
    text = router.healthz()
    assert "[healthz] spans" in text
    assert "[healthz]   built radon.compile " in text


def test_conv2d_compile_credits_pipeline_counters(conv251_kernel,
                                                  pipeline_step):
    """One ``Conv2D`` compile credits the fused pipeline kernel's
    counters to its ``radon.compile``: the in-kernel forward of the
    conv operand (the kernel embeds as an image operand) and the
    strided-rotate step, which every body takes; both show on its
    ``built`` line."""
    pipeline_step()
    t0 = time.perf_counter_ns()
    radon.Conv2D((2, 13, 13), jnp.asarray(conv251_kernel),
                 jnp.uint8).compile()
    compiles = [r for r in _since(t0) if r["name"] == "radon.compile"]
    assert len(compiles) == 1
    assert compiles[0]["attrs"]["kind"] == "conv2d"
    credits = compiles[0]["credits"]
    assert credits.get("sfdprt_pipeline_operand_fwd", 0) >= 1
    assert credits.get("sfdprt_pipeline_step_roll", 0) >= 1
    built = [ln for ln in healthz.span_lines()
             if "built radon.compile" in ln and "kind=conv2d" in ln]
    assert "sfdprt_pipeline_operand_fwd=" in built[-1]
    assert "sfdprt_pipeline_step_roll=" in built[-1]
    assert "sfdprt_pipeline_ladder" not in built[-1]

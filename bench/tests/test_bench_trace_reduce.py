"""bench/trace_reduce.py on a synthetic trace whose answer is known."""
import pytest

from bench import trace_reduce

SYNTHETIC = """
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 40000000 }
    events { metadata_id: 3 offset_ps: 41000000 duration_ps: 60000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.step" } }
  event_metadata { key: 3 value { id: 3 name: "bench.check" } }
}
planes {
  id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 11000000
             stats { metadata_id: 9 str_value: "custom-call" } }
    events { metadata_id: 2 offset_ps: 21000000 duration_ps: 10000000 }
    events { metadata_id: 3 offset_ps: 71000000 duration_ps: 5000000 }
  }
  lines { id: 2 name: "Host to Device" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 31000000 duration_ps: 2000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "_sfdprt_kernel" } }
  event_metadata { key: 2 value { id: 2 name: "all-reduce.3" } }
  event_metadata { key: 3 value { id: 3 name: "fusion.1" } }
  event_metadata { key: 4 value { id: 4 name: "copy" } }
  stat_metadata { key: 9 value { id: 9 name: "hlo_category" } }
}
planes {
  id: 3 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 20000000
             stats { metadata_id: 9 str_value: "custom-call" } }
  }
  event_metadata { key: 1 value { id: 1 name: "_sfdprt_kernel" } }
  stat_metadata { key: 9 value { id: 9 name: "hlo_category" } }
}
"""


def test_synthetic_trace():
    from jax.profiler import ProfileData
    r = trace_reduce.reduce(ProfileData.from_text_proto(SYNTHETIC), 2)
    ms = 1e-6     # the offsets above are in picoseconds: units of 1 us
    assert r["window_s"] == pytest.approx(100 * ms)
    # device 0: 10 (kernel, clipped at the window) + 10 + 5; device 1: 20
    assert r["busy_s"] == pytest.approx((25 + 20) / 2 * ms)
    assert r["kernel_s"] == pytest.approx(30 * ms)
    assert r["collective_s"] == pytest.approx([10 * ms, 0.0])
    assert r["transfer_s"] == pytest.approx(2 * ms)
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0] == ["bench.check", pytest.approx(40 * ms)]
    assert gaps[1] == ["bench.check", pytest.approx(25 * ms)]
    assert gaps[2] == ["bench.step", pytest.approx(10 * ms)]
    assert r["breakdown"]["device_ops"][0] == ["_sfdprt_kernel",
                                               pytest.approx(30 * ms)]
    assert len(r["breakdown"]["device_ops"]) <= trace_reduce.TOP

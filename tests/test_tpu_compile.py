"""The main path's Pallas kernels compile for a TPU v5e (Mosaic).

Every other test runs the kernels in interpret mode on the CPU, which
takes the interpret lowerings and skips Mosaic's tiling, layout and
VMEM checks.  These tests compile the chip's own lowerings (the strided
rotate Horner step, the DMA strip stream, the projection pipeline and
the 2-D filtering deployment's ``Conv2D`` through it, the per-shard
strip kernel) at real sizes for a *described* ``v5e:2x2`` topology:
nothing runs, but the chip's compiler refuses here what it would refuse
on the chip.  Each compile asserts a Mosaic kernel (``tpu_custom_call``)
is in the program under its stable name (the ``name=`` of its
``pallas_call``, which the profiler's trace shows) and prints
``memory_analysis()``.

The topology is described inside a module fixture, never at import: a
process that describes it loads the TPU library and keeps its lock, so
only the worker that runs this file may do it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro import radon
from repro.core import spans
from repro.core.distributed import dprt_sharded_pallas
from repro.kernels.ops import (dprt_pallas, idprt_pallas,
                               projection_pipeline_pallas)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described-chip compile is written to the persistent cache but
        # cannot be read back without a chip: keep the cache out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _counters() -> dict:
    return dict(spans.snapshot()["counters"])


#: one kernel body traced with the strided rotate Horner step
ONE_ROLL = {"sfdprt_step_roll": 1}


def _compile(fn, *avals, name, counts=None):
    """Compile ``fn`` for the described chip; ``counts`` is how much each
    named counter must grow (kernel bodies traced with a given step)."""
    before = _counters()
    compiled = jax.jit(fn).lower(*avals).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel"
    assert name in text, f"no kernel named {name}"
    after = _counters()
    for key, grows in (counts or {}).items():
        assert after.get(key, 0) - before.get(key, 0) == grows, key
    print(compiled.memory_analysis())
    return compiled


@pytest.mark.parametrize("direction", ["forward", "inverse"])
def test_fused_kernels_compile_n251_b16(one_chip, direction):
    n = 251
    if direction == "forward":
        aval = jax.ShapeDtypeStruct((16, n, n), jnp.int32, sharding=one_chip)
        _compile(lambda f: dprt_pallas(f, interpret=False), aval,
                 name="sfdprt_forward", counts=ONE_ROLL)
    else:
        aval = jax.ShapeDtypeStruct((16, n + 1, n), jnp.int32,
                                    sharding=one_chip)
        _compile(lambda r: idprt_pallas(r, interpret=False), aval,
                 name="sfdprt_inverse", counts=ONE_ROLL)


@pytest.mark.parametrize("direction", ["forward", "inverse"])
def test_dma_stream_compiles_n2053(one_chip, direction):
    n, rows = 2053, 256
    if direction == "forward":
        aval = jax.ShapeDtypeStruct((1, n, n), jnp.int32, sharding=one_chip)
        _compile(lambda f: dprt_pallas(f, stream_rows=rows, interpret=False),
                 aval, name="sfdprt_stream_forward", counts=ONE_ROLL)
    else:
        aval = jax.ShapeDtypeStruct((1, n + 1, n), jnp.int32,
                                    sharding=one_chip)
        _compile(lambda r: idprt_pallas(r, stream_rows=rows,
                                        interpret=False), aval,
                 name="sfdprt_stream_inverse", counts=ONE_ROLL)


@pytest.mark.parametrize("n", [61, 251])
@pytest.mark.parametrize("op", ["conv", "mul"])
def test_pipeline_compiles(one_chip, op, n):
    """The fused projection pipeline with its tuned direction block:
    ``conv`` against an image operand (its forward runs in-kernel) and
    ``mul`` against shared projection-domain weights."""
    f = jax.ShapeDtypeStruct((2, n, n), jnp.int32, sharding=one_chip)
    rows = n if op == "conv" else n + 1
    w = jax.ShapeDtypeStruct((rows, n), jnp.int32, sharding=one_chip)
    _compile(lambda x, y: projection_pipeline_pallas(x, op, y,
                                                     interpret=False), f, w,
             name=f"sfdprt_pipeline_{op}")


def test_conv2d_u8_compiles_b64_n251(one_chip, conv251_kernel,
                                     pipeline_step, monkeypatch):
    """The 2-D filtering deployment's entry at its own shape,
    ``radon.Conv2D((64, 251, 251), 5x5 kernel, uint8)``, through the
    plan to one fused pipeline kernel: its body traced once, with the
    ladder step and the conv operand's in-kernel forward."""
    pipeline_step()
    # the kernel wrappers pick interpret mode from the process's backend
    # (the CPU here); steer them to the chip's lowering
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    op = radon.Conv2D((64, 251, 251), jnp.asarray(conv251_kernel),
                      jnp.uint8)
    aval = jax.ShapeDtypeStruct(op.shape_in, jnp.uint8, sharding=one_chip)
    _compile(lambda f: op(f), aval, name="sfdprt_pipeline_conv",
             counts={"sfdprt_pipeline_ladder": 1,
                     "sfdprt_pipeline_operand_fwd": 1})


def test_sharded_strip_kernel_compiles_on_2x2(topo, monkeypatch):
    """The per-shard strip kernel under ``shard_map``: its traced
    ``row_offset`` (axis_index * rows per device) feeds the alignment
    ladder, with the batch over ``data`` and rows over ``model``."""
    # the kernel wrappers pick interpret mode from the process's backend
    # (the CPU here); steer them to the chip's lowering
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = jax.make_mesh((2, 2), ("data", "model"), devices=topo.devices)
    n = 251
    aval = jax.ShapeDtypeStruct((16, n, n), jnp.int32,
                                sharding=NamedSharding(mesh,
                                                       P("data", None, None)))
    compiled = _compile(lambda f: dprt_sharded_pallas(f, mesh), aval,
                        name="sfdprt_forward", counts=ONE_ROLL)
    assert "reduce-scatter" in compiled.as_text() or \
        "all-reduce" in compiled.as_text()

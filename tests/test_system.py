"""End-to-end behaviour tests for the system (deliverable c)."""
import json
import os
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_radon_service_end_to_end():
    """The paper's workload as a service: phantom batch -> DPRT -> filter in
    the transform domain -> exact inverse."""
    from repro.core import (circ_conv2d_dprt, dprt_batched, idprt_batched)
    from repro.data import radon_images
    imgs = jnp.asarray(radon_images(31, 4, kind="phantom"))
    r = dprt_batched(imgs)
    back = idprt_batched(r)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(imgs))
    # convolution property on a real phantom
    kern = jnp.zeros((31, 31), jnp.int32).at[0, 0].set(2).at[0, 1].set(1)
    out = circ_conv2d_dprt(imgs[0], kern)
    want = 2 * imgs[0] + jnp.roll(imgs[0], 1, axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_train_cli_smoke(tmp_path):
    from repro.launch.train import main
    out = main(["--arch", "tinyllama-1.1b", "--smoke", "--steps", "8",
                "--batch", "2", "--seq", "32",
                "--ckpt-dir", str(tmp_path / "ck")])
    assert np.isfinite(out["last_loss"])


def test_compile_cache_dir_is_fixed_or_from_env(monkeypatch):
    from repro.launch.compile_cache import (DEFAULT_CACHE_DIR,
                                            enable_compile_cache)
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = enable_compile_cache()
        assert DEFAULT_CACHE_DIR == Path(REPO).resolve() / ".jax_cache"
        assert path == str(DEFAULT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == path
        assert jax.config.jax_persistent_cache_min_compile_time_secs < 1.0
        # where the variable is set, JAX reads it; the helper sets no dir
        jax.config.update("jax_compilation_cache_dir", "/elsewhere")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == "/elsewhere"
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)


def test_serve_cli_radon_smoke():
    from repro.launch.serve import main
    r = main(["--mode", "radon", "--smoke", "--batch", "4"])
    assert r.shape[0] == 4


def test_serve_cli_lm_smoke():
    from repro.launch.serve import main
    gen = main(["--mode", "lm", "--arch", "qwen3-0.6b", "--smoke",
                "--batch", "2", "--prompt-len", "16", "--gen-tokens", "4"])
    assert gen.shape == (2, 4)


def test_dryrun_artifacts_complete():
    """The committed dry-run matrix covers every (arch x shape x mesh) cell
    and every non-skipped cell compiled."""
    d = os.path.join(REPO, "experiments", "dryrun")
    if not os.path.isdir(d) or len(os.listdir(d)) < 80:
        pytest.skip("dry-run matrix not yet generated")
    from repro.configs import ARCH_IDS
    from repro.launch.shapes import SHAPES
    cells = {}
    for f in os.listdir(d):
        with open(os.path.join(d, f)) as fh:
            c = json.load(fh)
        cells[(c["arch"], c["shape"], c["mesh"])] = c
    missing, errors = [], []
    for arch in ARCH_IDS:
        for shape in SHAPES:
            for mesh in ["16x16", "2x16x16"]:
                c = cells.get((arch, shape, mesh))
                if c is None:
                    missing.append((arch, shape, mesh))
                elif c["status"] == "error":
                    errors.append((arch, shape, mesh, c.get("error")))
    assert not missing, f"missing cells: {missing}"
    assert not errors, f"failed cells: {errors}"
    # skips are exactly the documented long_500k full-attention cells
    skips = [k for k, c in cells.items() if c["status"] == "skipped"]
    assert all(k[1] == "long_500k" for k in skips)
    assert len(skips) == 16


@pytest.mark.slow
def test_dryrun_production_mesh_one_cell(subproc):
    """Actually build the 16x16 production mesh (256 fake devices) and
    compile one full-config cell in-process -- deliverable (e) smoke."""
    subproc("""
from repro.launch.dryrun import run_cell
r = run_cell("qwen3_0_6b", "decode_32k", multi_pod=False, outdir="")
assert r["status"] == "ok", r
assert r["roofline"]["chips"] == 256
print("OK", r["roofline"]["dominant"])
""", devices=512, timeout=900,
        extra_env={"REPRO_DRYRUN_DEVICES": "512"})


def test_roofline_parser_units():
    from repro.launch.roofline import parse_collectives, roofline_terms
    hlo = """
  %all-reduce.1 = f32[1024,512]{1,0} all-reduce(%x), replica_groups=[2,4]<=[8]
  %all-gather.2 = bf16[64,1024]{1,0} all-gather(%y), replica_groups=[4,2]<=[8], dimensions={1}
  %reduce-scatter.3 = f32[128]{0} reduce-scatter(%z), replica_groups=[1,8]<=[8]
"""
    c = parse_collectives(hlo)
    assert c["all-reduce"] == 1024 * 512 * 4
    assert c["all-gather"] == 64 * 1024 * 2 // 2
    assert c["reduce-scatter"] == 128 * 4 * 8
    t = roofline_terms(197e12, 819e9, 50e9, 256)
    assert abs(t["compute_s"] - 1.0) < 1e-9
    assert abs(t["memory_s"] - 1.0) < 1e-9
    assert abs(t["collective_s"] - 1.0) < 1e-9
    assert t["dominant"] in ("compute", "memory", "collective")


def test_hlo_cost_trip_counts():
    """The trip-count-aware walker fixes XLA's while-body undercount."""
    from repro.launch.hlo_cost import analyze_hlo, compiled_cost_dict

    def scanned(x, w):
        def body(h, wi):
            return jnp.tanh(h @ wi), None
        h, _ = jax.lax.scan(body, x, w)
        return h

    a = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((6, 256, 256), jnp.float32)
    compiled = jax.jit(scanned).lower(a, w).compile()
    r = analyze_hlo(compiled.as_text())
    expected = 6 * 2 * 128 * 256 * 256
    assert 0.95 < r["flops"] / expected < 1.1, r
    raw = compiled_cost_dict(compiled).get("flops", 0)
    assert raw < 0.5 * expected  # the bug we are correcting

"""The serve cell and the mesh cell at CPU sizes, as in
test_bench_faults.py; and the exit codes of a run with no TPU and of a
checkout that holds only the benchmark."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench.tests.tiny import REPO, run, tiny_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("tiny"))


# -- faults planted in the service the router calls -----------------------
def wrap_execute(change):
    def patch(state):
        for svc in state.services.values():
            inner = svc.execute
            svc.execute = (lambda stack, inner=inner:
                           change(np.array(inner(stack))))
    return patch


def serve_altered(out):
    out[0].flat[0] += 1
    return out


def serve_half_left_out(out):
    out[out.shape[0] // 2:] = 0
    return out


SERVE_FAULTS = {"altered_answer": serve_altered,
                "half_batch_left_out": serve_half_left_out}


def test_sound_serve_run_is_correct(root):
    cell = "dprt251.serve"
    out = run(root, cell)
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "compared"
    assert all(v["value"] == 0 and v["limit"] == 0
               for v in out["compared"].values())


@pytest.mark.parametrize("fault", sorted(SERVE_FAULTS))
def test_serve_fault_is_caught(root, fault):
    out = run(root, "dprt251.serve", wrap_execute(SERVE_FAULTS[fault]))
    assert out["correct"] is False, (fault, out["compared"])


def test_serve_control_in_the_programs_place_is_caught(tmp_path):
    """The int16 control answering the router's batches, at a size where
    the inverse's sums leave int16."""
    from bench import control
    root = tiny_root(tmp_path)
    path = root / "bench/configs/dprt251_u8.json"
    cfg = json.loads(path.read_text())
    cfg.update(n=137)
    path.write_text(json.dumps(cfg))
    out = run(root, "dprt251.serve", control.install)
    assert out["correct"] is False
    assert out["compared"]["inv_mismatch"]["value"] > 0


MESH = """
import json, sys, time
sys.path[:0] = [{src!r}, {repo!r}]
import jax
from bench import harness
if {fault!r}:
    jax.lax.psum = lambda x, axis_name, **kw: x   # the exchange left out
out = harness.run_cell({root!r}, "dprt251_2x2.batch", 5, 0.3, False,
                       t_start=time.perf_counter(), require_tpu=False)
print(json.dumps(out))
"""


@pytest.mark.parametrize("fault", [False, True])
def test_mesh_exchange_left_out_is_caught(root, fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = MESH.format(src=str(REPO / "src"), repo=str(REPO),
                       root=str(root), fault=fault)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["device"]["count"] == 4
    assert out["correct"] is (not fault), out["compared"]


def test_no_tpu_exits_nonzero_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "dprt251.batch", "--seed", "0", "--seconds", "10",
                        "--trace", "0"], cwd=REPO, capture_output=True,
                       text=True, env=env, timeout=120)
    assert r.returncode == 3 and r.stdout == ""
    assert "no TPU" in r.stderr


def test_checkout_without_the_program_exits_nonzero(tmp_path):
    root = tiny_root(tmp_path)
    r = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "dprt251.batch", "--seed", "0", "--seconds", "10",
                        "--trace", "0"], cwd=root, capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == 2 and r.stdout == ""

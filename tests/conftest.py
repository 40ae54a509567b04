import functools
import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

# The target container has no hypothesis and pip installs are forbidden;
# fall back to the deterministic shim so property tests still run.
try:
    import hypothesis  # noqa: F401
except ImportError:
    _spec = importlib.util.spec_from_file_location(
        "hypothesis", os.path.join(os.path.dirname(__file__),
                                   "_hypothesis_shim.py"))
    _shim = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(_shim)
    sys.modules["hypothesis"] = _shim
    sys.modules["hypothesis.strategies"] = _shim.strategies


def pytest_configure(config):
    # Quick tier: `pytest -m "not slow"` skips the forced-host subprocess
    # tests (each spawns a fresh 8-device python, ~10-60 s apiece).
    config.addinivalue_line(
        "markers",
        "slow: forced-host subprocess tests (sharded meshes, int64-x64); "
        "deselect with -m 'not slow' for the quick tier")


def run_subprocess(code: str, devices: int = 8, timeout: int = 600,
                   extra_env=None):
    """Run python code in a fresh process with N fake host devices.

    Needed because the main pytest process must keep the default single
    CPU device (smoke tests and benches see 1 device per the assignment).
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra_env or {})
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=REPO, timeout=timeout, env=env)
    if r.returncode != 0:
        raise AssertionError(
            f"subprocess failed (rc={r.returncode})\n--- stdout ---\n"
            f"{r.stdout[-4000:]}\n--- stderr ---\n{r.stderr[-4000:]}")
    return r.stdout


@pytest.fixture
def subproc():
    return run_subprocess


@pytest.fixture
def conv251_kernel():
    """The 5x5 integer kernel of the ``conv251_u8`` benchmark
    configuration, as uint8."""
    import numpy as np
    path = os.path.join(REPO, "bench", "configs", "conv251_u8.json")
    with open(path) as fh:
        return np.asarray(json.load(fh)["conv_kernel"], np.uint8)


@pytest.fixture
def pipeline_step(monkeypatch):
    """``pipeline_step(step_impl)``: drop every cached plan, executable
    and trace of the fused pipeline kernel, so the next build traces its
    body anew -- with ``step_impl`` forced on every call when given
    (``None``: the kernel's own default).  The caches are dropped again
    after the test."""
    from repro import radon
    from repro.kernels import ops, sfdprt

    def clear():
        radon.aot_cache_clear()
        radon.plan_cache_clear()
        sfdprt.pipeline_pallas_raw.clear_cache()

    def fresh(step_impl=None):
        clear()
        raw = sfdprt.pipeline_pallas_raw
        if step_impl is not None:
            raw = functools.partial(raw, step_impl=step_impl)
        monkeypatch.setattr(ops, "pipeline_pallas_raw", raw)
    yield fresh
    clear()             # no later test reuses a trace of a forced step

"""A whole run of each batch cell at a CPU size, past the harness's look
for a chip: sound, it is correct; with the timed path broken underneath
in each way the cell can break, ``correct`` comes out false.  The
control in the program's place comes out false too."""
import json

import pytest

from bench.tests.tiny import run, tiny_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("tiny"))


# -- faults planted in what the batch window drives -----------------------
def wrap_step(change):
    def patch(state):
        inner = state.step_fn
        state.step_fn = lambda s: change(state, s, inner(s))
    return patch


def altered_answer(state, s, outs):
    return (outs[0].at[0, 0, 0].add(1),) + tuple(outs[1:])


def half_batch_left_out(state, s, outs):
    return tuple(o.at[o.shape[0] // 2:].set(0) for o in outs)


def stale_outputs(state, s, outs):
    # every step hands back the first step's outputs, computing nothing new
    if not hasattr(state, "_first"):
        state._first = outs
    return state._first


BATCH_FAULTS = {"altered_answer": altered_answer,
                "half_batch_left_out": half_batch_left_out,
                "stale_outputs": stale_outputs}


@pytest.mark.parametrize("cell", ["dprt251.batch", "conv251.batch"])
def test_sound_run_is_correct(root, cell):
    out = run(root, cell)
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "compared"
    assert all(v["value"] == 0 and v["limit"] == 0
               for v in out["compared"].values())


@pytest.mark.parametrize("cell", ["dprt251.batch", "conv251.batch"])
@pytest.mark.parametrize("fault", sorted(BATCH_FAULTS))
def test_batch_fault_is_caught(root, cell, fault):
    out = run(root, cell, wrap_step(BATCH_FAULTS[fault]))
    assert out["correct"] is False, (fault, out["compared"])



def test_control_in_the_programs_place_is_caught(tmp_path):
    """The int16 control at a size where its sums leave int16."""
    from bench import control
    root = tiny_root(tmp_path)
    path = root / "bench/configs/dprt251_u8.json"
    cfg = json.loads(path.read_text())
    cfg.update(n=137, batch=4)
    path.write_text(json.dumps(cfg))
    out = run(root, "dprt251.batch", control.install)
    assert out["correct"] is False
    assert out["compared"]["inv_mismatch"]["value"] > 0


def test_conv_control_in_the_programs_place_is_caught(tmp_path):
    """The int16 control of the convolution: at N=31 its forward sums
    still fit int16, and its per-direction tap sums do not."""
    from bench import control
    root = tiny_root(tmp_path)
    path = root / "bench/configs/conv251_u8.json"
    cfg = json.loads(path.read_text())
    cfg.update(n=31, batch=4)
    path.write_text(json.dumps(cfg))
    out = run(root, "conv251.batch", control.install)
    assert out["correct"] is False
    assert out["compared"]["conv_mismatch"]["value"] > 0

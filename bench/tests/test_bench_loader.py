"""Adding a configuration, a traffic mix, a cell and a per-layer metric
is adding files and entries: the harness finds them by name."""
import json
import time

import pytest

from bench import harness, spec
from bench.tests.tiny import tiny_root

READER = '''"""Images completed in the traced window."""


def read(ctx):
    return ctx["counts"]["images"] or None
'''


@pytest.fixture
def root(tmp_path):
    return tiny_root(tmp_path)


def add_cell(root):
    doc = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "bench/configs/dprt251_u8.json").read_text())
    cfg.update(name="dprt11_u8", n=11, batch=2)
    (root / "bench/configs/dprt11_u8.json").write_text(json.dumps(cfg))
    mix = json.loads((root / "bench/traffic/batch.json").read_text())
    mix.update(stacks=2, check_images=2)
    (root / "bench/traffic/batch_small.json").write_text(json.dumps(mix))
    (root / "bench/layer_metrics/images_seen.new.py").write_text(READER)
    doc["configs"].append({"name": "dprt11_u8", "source": "test",
                           "file": "bench/configs/dprt11_u8.json",
                           "reduced": [], "why": "test"})
    doc["workloads"].append({"name": "dprt11.small", "config": "dprt11_u8",
                             "traffic": "batch_small", "chips": 1,
                             "why": "test"})
    doc["end_to_end"][0]["workloads"].append("dprt11.small")
    doc["per_layer"].append({"name": "images_seen.new", "unit": "images",
                             "better": "higher", "source": "host_clock",
                             "layer": "test", "moves": "images_per_s",
                             "workloads": ["dprt11.small"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))


def test_new_files_are_found_by_name(root):
    add_cell(root)
    cell = spec.load_cell(root, "dprt11.small")
    assert cell.config["n"] == 11 and cell.traffic["stacks"] == 2
    assert set(cell.readers) == {"images_seen.new"}
    assert [m["name"] for m in cell.end_to_end] == ["images_per_s",
                                                     "setup_s"]
    out = harness.run_cell(root, "dprt11.small", 3, 0.2, True,
                           t_start=time.perf_counter(), require_tpu=False)
    assert out["correct"] is True
    assert out["metrics"]["images_seen.new"]["value"] > 0
    assert list(out)[-1] == "compared"


def test_listed_metric_that_reads_nothing_is_an_error(root):
    add_cell(root)
    (root / "bench/layer_metrics/images_seen.new.py").write_text(
        "def read(ctx):\n    return None\n")
    with pytest.raises(harness.MissingMetric, match="images_seen.new"):
        harness.run_cell(root, "dprt11.small", 3, 0.2, True,
                         t_start=time.perf_counter(), require_tpu=False)


@pytest.mark.parametrize("where,key,bad", [
    ("workloads", "name", "dprt 251.batch"),
    ("workloads", "name", "a/b"),
    ("end_to_end", "unit", "images per second"),
    ("per_layer", "name", "kernel,ms"),
    ("end_to_end", "unit", "µs"),
])
def test_names_and_units_outside_the_allowed_characters_are_refused(
        root, where, key, bad):
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc[where][0][key] = bad
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    with pytest.raises(spec.SpecError):
        spec.load_cell(root, "dprt251.batch")


def test_unknown_cell_is_refused(root):
    with pytest.raises(spec.SpecError):
        spec.load_cell(root, "nope.batch")


def test_config_knobs_reach_the_operator(root, monkeypatch):
    from repro import radon
    seen = []
    real = radon.DPRT

    def spy(*args, **kw):
        seen.append(kw)
        return real(*args, **kw)
    monkeypatch.setattr(radon, "DPRT", spy)
    path = root / "bench/configs/dprt251_u8.json"
    cfg = json.loads(path.read_text())
    cfg["knobs"] = {"method": "pallas"}
    path.write_text(json.dumps(cfg))
    out = harness.run_cell(root, "dprt251.batch", 5, 0.2, False,
                           t_start=time.perf_counter(), require_tpu=False)
    assert out["correct"] is True
    assert seen and all(kw.get("method") == "pallas" for kw in seen)


def test_new_open_loop_mix_is_data_alone(root):
    mix = json.loads((root / "bench/traffic/open_loop.json").read_text())
    mix.update(shares={"forward": 1.0}, payload={"forward": None},
               reference={"forward": "dprt"})
    (root / "bench/traffic/open_loop_fwd.json").write_text(json.dumps(mix))
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["workloads"].append({"name": "dprt251.serve_fwd",
                             "config": "dprt251_u8",
                             "traffic": "open_loop_fwd", "chips": 1,
                             "why": "test"})
    for m in doc["end_to_end"]:
        if "dprt251.serve" in m.get("workloads", []):
            m["workloads"].append("dprt251.serve_fwd")
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    out = harness.run_cell(root, "dprt251.serve_fwd", 5, 0.3, False,
                           t_start=time.perf_counter(), require_tpu=False)
    assert out["correct"] is True, out["compared"]
    assert set(out["compared"]) == {"unanswered", "fwd_mismatch"}

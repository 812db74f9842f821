"""The harness finds every part of a cell by name, from files alone."""
import json
import math
import types

import pytest

from bench import cells, run
from bench.tests import helpers

ROOT = helpers.BENCH.parent


def test_new_cell_config_mix_and_metric_resolve_from_new_files(tmp_path):
    per_layer = [{"name": "calls_seen", "unit": "calls", "better": "higher",
                  "source": "host_clock", "layer": "test",
                  "moves": "gstencil_s", "workloads": ["c.mix"]}]
    t = helpers.tree(tmp_path, {"c": ("2d5p", helpers.TAPS_2D5P, (64, 128))},
                     {"c.mix": ("c", 8, "auto", 1)}, per_layer=per_layer)
    (t / "metrics" / "calls_seen.py").write_text(
        "def read(ctx):\n    return float(ctx.n_calls)\n")
    cell = cells.load_cell(t, "c.mix", t)
    assert cell.config["shape"] == [64, 128]
    assert cell.traffic["steps_per_call"] == 8
    assert [m["name"] for m in cell.per_layer] == ["calls_seen"]
    got = run.read_per_layer(cell, types.SimpleNamespace(n_calls=3), t)
    assert got == {"calls_seen": {"value": 3.0, "unit": "calls"}}


def test_metric_that_finds_nothing_is_left_out(tmp_path):
    per_layer = [{"name": "nothing", "unit": "%", "better": "higher",
                  "source": "device_trace", "layer": "test",
                  "moves": "gstencil_s"}]
    t = helpers.tree(tmp_path, {"c": ("1d3p", helpers.TAPS_1D3P, (4096,))},
                     {"c.mix": ("c", 4, "auto", 1)}, per_layer=per_layer)
    (t / "metrics" / "nothing.py").write_text("def read(ctx):\n    return None\n")
    cell = cells.load_cell(t, "c.mix", t)
    assert run.read_per_layer(cell, types.SimpleNamespace(), t) == {}


def test_unknown_workload_is_an_error(tmp_path):
    t = helpers.tree(tmp_path, {"c": ("1d3p", helpers.TAPS_1D3P, (4096,))},
                     {"c.mix": ("c", 4, "auto", 1)})
    with pytest.raises(KeyError):
        cells.load_cell(t, "nope", t)


def test_every_cell_of_the_benchmark_resolves():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = cells.load_cell(ROOT, w["name"])
        assert cell.chips == w["chips"] == cell.config["chips"]
        assert math.prod(cell.config.get("mesh", [1])) == cell.chips
        assert float(cell.limits["rel_err"]["limit"]) > 0
        for m in cell.per_layer:
            assert callable(cells.metric_reader(m["name"]).read)
    names = {m["name"] for m in spec["end_to_end"] + spec["per_layer"]}
    assert len(names) == len(spec["end_to_end"]) + len(spec["per_layer"])


def test_configuration_taps_are_the_engine_s_stencil():
    """The reference's taps (the configuration's own) describe the same
    stencil the engine is asked to run."""
    from repro.core import stencils
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        mine = sorted((tuple(o), v) for o, v in cfg["taps"])
        engine = sorted(stencils.make(cfg["stencil"]).taps)
        assert mine == engine

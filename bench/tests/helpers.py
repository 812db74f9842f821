"""A throwaway benchmark tree at CPU sizes, for driving the harness."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

TAPS_2D5P = [[[0, 0], 0.5], [[-1, 0], 0.125], [[1, 0], 0.125],
             [[0, -1], 0.125], [[0, 1], 0.125]]
TAPS_1D3P = [[[0], 0.5], [[-1], 0.25], [[1], 0.25]]


def tree(tmp: Path, configs: dict, cells: dict, limit: float = 1e-5,
         per_layer=None) -> Path:
    """Write BENCHMARK.json, configs, one traffic file per mix and limits
    under ``tmp``; metrics and peaks are the real ones.

    ``configs``: name -> (stencil, taps, shape) or (stencil, taps, shape,
    mesh); ``cells``: workload -> (config, steps_per_call, planner,
    chips)."""
    for sub in ("configs", "traffic", "limits"):
        (tmp / sub).mkdir(parents=True, exist_ok=True)
    shutil.copytree(BENCH / "metrics", tmp / "metrics", dirs_exist_ok=True)
    shutil.copy(BENCH / "peaks.json", tmp / "peaks.json")
    spec = {"command": ["python3", "bench/run.py"], "paths": ["bench"],
            "run_seconds": 1, "configs": [], "workloads": [],
            "end_to_end": json.loads((BENCH.parent / "BENCHMARK.json")
                                     .read_text())["end_to_end"],
            "per_layer": per_layer or []}
    for name, (stencil, taps, shape, *mesh) in configs.items():
        cfg = {"name": name, "stencil": stencil, "taps": taps,
               "shape": list(shape), "dtype": "float32",
               "boundary": "periodic"}
        if mesh:
            cfg["mesh"] = list(mesh[0])
        (tmp / "configs" / f"{name}.json").write_text(json.dumps(cfg))
        spec["configs"].append({"name": name, "source": "test",
                                "file": f"configs/{name}.json",
                                "reduced": [], "why": "test"})
    for w, (config, steps, planner, chips) in cells.items():
        mix = f"{planner}{steps}"
        (tmp / "traffic" / f"{mix}.json").write_text(json.dumps(
            {"steps_per_call": steps, "planner": planner,
             "check_calls": 2}))
        (tmp / "limits" / f"{w}.json").write_text(json.dumps(
            {"rel_err": {"limit": limit}}))
        spec["workloads"].append({"name": w, "config": config,
                                  "traffic": mix, "chips": chips,
                                  "why": "test"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def last_json(text: str) -> dict:
    return json.loads(text.strip().splitlines()[-1])

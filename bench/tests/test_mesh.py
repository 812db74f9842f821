"""A field on a four-chip mesh: made in shards, checked against the mesh
reference with no gather, and ``correct`` false for each fault of a
sharded cell.  The cases run in one process with four forced host
devices (``_mesh_cases.py``); each is reported here on its own."""
import json
import os
import subprocess
import sys

import pytest

from bench import run
from bench.tests import _mesh_cases, helpers

CASES = list(_mesh_cases.CASES) + list(_mesh_cases.PATHS)


@pytest.fixture(scope="module")
def report(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4 "
               + os.environ.get("XLA_FLAGS", ""))
    proc = subprocess.run(
        [sys.executable, _mesh_cases.__file__,
         str(tmp_path_factory.mktemp("mesh"))],
        env=env, capture_output=True, text=True, timeout=900)
    rows = {}
    for line in proc.stdout.splitlines():
        if line.startswith('{"case"'):
            row = json.loads(line)
            rows[row["case"]] = row
    return proc, rows


@pytest.mark.parametrize("name", CASES)
def test_mesh_case(report, name):
    proc, rows = report
    assert name in rows, (proc.returncode, proc.stderr[-4000:])
    assert rows[name]["ok"], rows[name]["detail"]


@pytest.mark.parametrize("chips,mesh", [(4, (2, 1)), (1, (2, 2)),
                                        (4, (4,)), (4, None)])
def test_a_mesh_that_does_not_make_the_cell_s_chips_exits_2(
        tmp_path, capsys, chips, mesh):
    cfg = ("2d5p", helpers.TAPS_2D5P, (64, 256)) + ((mesh,) if mesh else ())
    t = helpers.tree(tmp_path, {"m": cfg}, {"m.long": ("m", 8, "auto", chips)})
    rc = run.main(["--workload", "m.long", "--seed", "1", "--seconds", "0.1"],
                  root=t, bench_dir=t, require_chip=False)
    out, err = capsys.readouterr()
    assert rc == 2 and out == ""
    assert "m.long needs" in err

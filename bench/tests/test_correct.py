"""``correct`` comes out false for the control and for each fault the
cells can have, and true for the program, with the real limits."""
import json

import pytest

from bench import limits, run
from bench.tests import helpers

LIMIT = json.loads((helpers.BENCH / "limits" / "2d5p.long.json")
                   .read_text())["rel_err"]["limit"]
ARGS = ["--workload", "t.long", "--seed", "3000000019", "--seconds", "0.5",
        "--trace", "0"]


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return helpers.tree(
        tmp_path_factory.mktemp("bench"),
        {"t": ("2d5p", helpers.TAPS_2D5P, (64, 256))},
        {"t.long": ("t", 8, "auto", 1)}, limit=LIMIT)


def result(tiny, capsys, path=None) -> dict:
    rc = run.main(ARGS, root=tiny, bench_dir=tiny, require_chip=False,
                  path=path)
    assert rc == 0
    res = helpers.last_json(capsys.readouterr().out)
    assert list(res)[-1] == "checks"
    assert res["checks"]["rel_err"]["limit"] == LIMIT
    return res


def wrapped(fault):
    """The program's path with ``fault`` applied to what each call returns."""
    def path(cell):
        prog = run.program_path(cell)
        return run.Setup(call=lambda x: fault(prog.call(x), x),
                         plan=prog.plan, plan_cache=prog.plan_cache)
    return path


def test_program_is_correct(tiny, capsys):
    res = result(tiny, capsys)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1


def test_control_in_lower_precision_is_not_correct(tiny, capsys):
    res = result(tiny, capsys, path=lambda cell: limits.control_path(
        cell, run.program_path(cell)))
    assert res["correct"] is False
    assert res["checks"]["rel_err"]["value"] > LIMIT


def test_state_returned_unchanged_is_not_correct(tiny, capsys):
    res = result(tiny, capsys, path=wrapped(lambda y, x: x))
    assert res["correct"] is False


def test_answer_altered_where_produced_is_not_correct(tiny, capsys):
    res = result(tiny, capsys, path=wrapped(
        lambda y, x: y.at[3, 5].multiply(1.001)))
    assert res["correct"] is False


def test_non_finite_answer_is_not_correct(tiny, capsys):
    res = result(tiny, capsys, path=wrapped(
        lambda y, x: y.at[0, 0].set(float("nan"))))
    assert res["correct"] is False


def test_a_run_that_tunes_says_so(tmp_path, capsys):
    """The first run in a checkout finds no plan in the cache and tunes;
    the next one finds it."""
    t = helpers.tree(tmp_path, {"t": ("1d3p", helpers.TAPS_1D3P, (4096,))},
                     {"t.short": ("t", 4, "auto", 1)}, limit=LIMIT)
    args = ["--workload", "t.short", "--seed", "11", "--seconds", "0.2",
            "--trace", "0"]
    seen = []
    for _ in range(2):
        assert run.main(args, root=t, bench_dir=t, require_chip=False) == 0
        out, err = capsys.readouterr()
        res = helpers.last_json(out)
        assert list(res)[-2:] == ["plan_cache", "checks"]
        seen.append((res["plan_cache"], "this run tunes" in err))
    assert seen == [("miss", True), ("hit", False)]

"""Host spans of the run path, read back from a profiler trace on the CPU.

``StencilProblem.run`` opens ``repro.run`` around its body, ``repro.plan``
around resolving a named plan and ``repro.dispatch`` around the engine
call; ``autotune.tune`` opens ``repro.tune`` only when it searches (a
cache miss), so a profile shows which call paid for tuning.  The
benchmark's per-layer metrics read the first three.
"""
import glob
import os

import jax
import pytest
from jax.profiler import ProfileData

from repro.core import autotune
from repro.core.api import StencilProblem


def _spans(trace_dir: str) -> list[tuple[str, int, int]]:
    """(name, start ns, end ns) of every ``repro.*`` host event."""
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.name.startswith("repro.")]
    return sorted(out, key=lambda s: s[1])


def _traced(tmp_path, fn) -> list[tuple[str, int, int]]:
    d = str(tmp_path / "trace")
    with jax.profiler.trace(d):
        jax.block_until_ready(fn())
    return _spans(d)


def test_run_opens_plan_then_dispatch_inside_run(tmp_path):
    p = StencilProblem("1d3p", (64,))
    x = p.init(0)
    jax.block_until_ready(p.run(x, 4, plan="default"))   # compile first
    spans = _traced(tmp_path, lambda: p.run(x, 4, plan="default"))
    assert [s[0] for s in spans] == ["repro.run", "repro.plan",
                                     "repro.dispatch"]
    run, plan, dispatch = spans
    assert run[1] <= plan[1] <= plan[2] <= dispatch[1] <= dispatch[2] \
        <= run[2]


def test_explicit_plan_opens_no_plan_span(tmp_path):
    p = StencilProblem("1d3p", (64,))
    x = p.init(0)
    plan = p.default_plan()
    jax.block_until_ready(p.run(x, 4, plan))
    spans = _traced(tmp_path, lambda: p.run(x, 4, plan))
    assert [s[0] for s in spans] == ["repro.run", "repro.dispatch"]


def test_tune_span_appears_only_on_a_miss(tmp_path, monkeypatch):
    monkeypatch.setattr(autotune, "_caches", {})
    monkeypatch.setenv("REPRO_PLAN_AUDIT", "0")
    cache_path = str(tmp_path / "plans.json")
    p = StencilProblem("1d3p", (256,))

    def tune():
        return autotune.tune(p, backend="jnp", steps=4,
                             cache_path=cache_path,
                             timer=lambda fn, plan: 1.0).plan.k

    miss = _traced(tmp_path / "miss", tune)
    hit = _traced(tmp_path / "hit", tune)
    assert [s[0] for s in miss] == ["repro.tune"]
    assert hit == []


def test_batched_entries_open_plan_and_dispatch(tmp_path):
    p = StencilProblem("1d3p", (64,))
    xs = [p.init(0), p.init(1)]
    jax.block_until_ready(p.run_batched_parts(xs, 4, plan="default"))
    spans = _traced(tmp_path,
                    lambda: p.run_batched_parts(xs, 4, plan="default"))
    names = [s[0] for s in spans]
    assert names == ["repro.plan", "repro.dispatch"]
    assert spans[0][2] <= spans[1][1]
    # as in ``run``, a plan given as such opens no plan span
    plan = p.default_plan()
    jax.block_until_ready(p.run_batched_parts(xs, 4, plan))
    spans = _traced(tmp_path / "explicit",
                    lambda: p.run_batched_parts(xs, 4, plan))
    assert [s[0] for s in spans] == ["repro.dispatch"]


@pytest.mark.parametrize("bad", ["fastest", ""])
def test_unknown_plan_name_is_refused(bad):
    p = StencilProblem("1d3p", (64,))
    with pytest.raises(ValueError, match="unknown plan"):
        p.run(p.init(0), 2, plan=bad)
    with pytest.raises(ValueError, match="unknown plan"):
        p.run_batched(p.init(0)[None], 2, plan=bad)

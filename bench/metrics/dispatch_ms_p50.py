"""Median length of the program's dispatch in one call, in ms.

``StencilProblem.run`` opens the host span ``repro.dispatch`` once the
plan is resolved, around the engine call up to its return (the jitted
program's dispatch); the window's spans are read from the trace.
"""
from statistics import median

SPAN = "repro.dispatch"


def read(ctx):
    spans = [(o.end - o.start) * 1e3 for o in ctx.trace.host
             if o.name == SPAN]
    return median(spans) if spans else None

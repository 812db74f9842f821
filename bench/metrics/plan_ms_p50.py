"""Median length of the program's plan resolution in one call, in ms.

``StencilProblem.run`` opens the host span ``repro.plan`` around
resolving a named plan (``plan="auto"``: the plan cache's key, its file
and the record's plan); the window's spans are read from the trace.
"""
from statistics import median

SPAN = "repro.plan"


def read(ctx):
    spans = [(o.end - o.start) * 1e3 for o in ctx.trace.host
             if o.name == SPAN]
    return median(spans) if spans else None

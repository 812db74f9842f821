"""Sweep-kernel launches per call: the ops tagged ``{"repro": "sweep"}``
in the traced window, per device, over the window's calls.

It equals the launch count of the plan's ``sweep_schedule``: a plan
that fuses more steps into one launch reads lower.
"""

PATTERN = r'"repro"\s*:\s*"sweep"'


def read(ctx):
    per_device = ctx.trace.select(".*", (PATTERN,))
    launches = sum(len(ops) for ops in per_device)
    if not launches or not ctx.n_calls:
        return None
    return launches / len(per_device) / ctx.n_calls

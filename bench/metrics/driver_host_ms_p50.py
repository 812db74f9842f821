"""Median host time of one call into ``StencilProblem.run``, in ms.

The benchmark's own span around each call, on the host clock: from
entering ``run`` (plan lookup, jit dispatch) to its return, before the
wait for the result.
"""


def read(ctx):
    spans = sorted((ret - due) * 1e3 for due, ret, _ in ctx.calls)
    if not spans:
        return None
    n = len(spans)
    return spans[n // 2] if n % 2 else (spans[n // 2 - 1] + spans[n // 2]) / 2

"""Share of device busy time spent in ops that carry no program tag, in %.

The program tags each Pallas kernel launch (``{"repro": ...}`` in the
custom call's ``kernel_metadata``); what is left is XLA's own work around
the kernels: the copies and transposes of the layout transform, the copy
of the loop-carried field before each launch and the ``while`` loop
itself.  None where the trace holds no tagged op at all.
"""

TAG = r'"repro"\s*:'


def read(ctx):
    busy = ctx.trace.busy_s
    if busy <= 0 or ctx.trace.layer_s(".*", (TAG,)) <= 0:
        return None
    return 100.0 * ctx.trace.layer_s(".*", (), (TAG,)) / busy

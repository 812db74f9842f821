"""Share of device busy time spent copying the field inside the sweep
loop, in %.

Each launch of the sweep loop (``jax.lax.fori_loop`` over the sweep
kernels) copies the whole loop-carried field before the kernel reads it:
an XLA ``copy`` of a ``%get-tuple-element`` of the loop's state, as a TPU
trace names it today.  It is work no stencil needs.
"""

KINDS = "copy"
PATTERNS = (r"copy\([^%]*%get-tuple-element",)


def read(ctx):
    busy = ctx.trace.busy_s
    copies = ctx.trace.layer_s(KINDS, PATTERNS)
    if busy <= 0 or copies <= 0:
        return None
    return 100.0 * copies / busy

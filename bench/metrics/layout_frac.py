"""Share of device busy time spent in the layout transform, in %.

The run driver moves the field into the kernels' (nb, m, vl) layout and
back once per call.  On the n-D path these are XLA copies (and
transposes) outside the sweep loop (``core/layouts.py``); on the 1-D path
they are the Pallas block-transpose kernels, the custom calls that take
the reshaped input (``%bitcast``) or the loop's result (``%while``).
Copies of a loop-carried value (``%get-tuple-element``) are not the
layout's and are left out.  Names as a TPU trace shows the HLO
instructions today.
"""

COPIES = ("copy|transpose", (), (r"\([^%]*%get-tuple-element",))
TRANSPOSE_KERNELS = ("custom-call", (r'custom_call_target="tpu_custom_call"',
                                     r"custom-call\([^%]*%(bitcast|while)\b"),
                     ())


def read(ctx):
    busy = ctx.trace.busy_s
    layout = (ctx.trace.layer_s(*COPIES)
              + ctx.trace.layer_s(*TRANSPOSE_KERNELS))
    if busy <= 0 or layout <= 0:
        return None
    return 100.0 * layout / busy

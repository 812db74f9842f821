"""Share of the sweep kernels' roofline, in %, over the kernels that the
program tags as sweeps.

Each Pallas sweep kernel carries ``{"repro": "sweep"}`` in its custom
call's ``kernel_metadata``, which a TPU trace shows in the op's text;
kernel time is the self time of the ops so tagged.  The least time is
``least_s``: the larger of the least operations of every call at the VPU
rate measured in the same run, and one read and one write of the grid per
call at the published HBM rate (``work.py``'s counts).  It is the bound
of ``sweep_roofline``, which selects the kernels by operand and keeps a
copy of it until it is retired.
"""

KINDS = ".*"
PATTERNS = (r'"repro"\s*:\s*"sweep"',)


def read(ctx):
    kernel_s = ctx.trace.layer_s(KINDS, PATTERNS)
    if kernel_s <= 0 or not ctx.vpu_ops_per_s:
        return None
    return 100.0 * least_s(ctx) / kernel_s


def least_s(ctx):
    """Least seconds per chip that the window's calls need."""
    compute_s = ctx.ops_per_call * ctx.n_calls / ctx.chips / ctx.vpu_ops_per_s
    memory_s = (ctx.bytes_per_call * ctx.n_calls / ctx.chips
                / ctx.peak["hbm_bytes_per_s"])
    return max(compute_s, memory_s)

"""Share of the sweep kernels' roofline, in %.

Kernel time is the self time of the Pallas sweep kernels in the traced
window.  A TPU trace names each op by its HLO instruction; the sweeps are
the Mosaic custom calls (``tpu_custom_call``) except the 1-D block
transposes, which take the reshaped input (``%bitcast``) or the sweep
loop's result (``%while``).  The least time the same work needs is the
larger of two bounds, both counted from the problem alone (``work.py``):
the least operations of every call (one multiply per distinct
coefficient, one add per further tap, per point and step) at the VPU rate
measured in the same run, and one read and one write of the grid per call
at the published HBM rate.  The reader notes which bound binds: on a
v5e (5.43e12 f32 VPU ops/s measured) the compute bound in ``2d5p.long``
and ``1d3p.long`` (64 and 128 steps a call), the memory bound in
``2d5p.snap8`` (8 steps a call; the two bounds lie within 10%).
"""

KINDS = "custom-call"
PATTERNS = (r'custom_call_target="tpu_custom_call"',)
EXCLUDE = (r"custom-call\([^%]*%(bitcast|while)\b",)


def read(ctx):
    kernel_s = ctx.trace.layer_s(KINDS, PATTERNS, EXCLUDE)
    if kernel_s <= 0 or not ctx.vpu_ops_per_s:
        return None
    compute_s = ctx.ops_per_call * ctx.n_calls / ctx.chips / ctx.vpu_ops_per_s
    memory_s = (ctx.bytes_per_call * ctx.n_calls / ctx.chips
                / ctx.peak["hbm_bytes_per_s"])
    bound = "compute" if compute_s >= memory_s else "memory"
    ctx.note(f"sweep_roofline: kernel {kernel_s:.6f} s, compute bound "
             f"{compute_s:.6f} s, memory bound {memory_s:.6f} s: {bound}")
    return 100.0 * max(compute_s, memory_s) / kernel_s

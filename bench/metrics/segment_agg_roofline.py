"""Kernel (``kernels/segment_agg``): per cent of the bandwidth roofline of
the write steps' segment reductions. The bytes they need (the real edges of
the blocks each write step reduced, read at the payload's own width with
their destination index, and the rows written; ``trace_reduce.reduce_bytes``)
over the device time of the kernel's calls inside the write-step programs
times the chip's peak HBM bandwidth. Moves ``ops_per_s``."""

from bench import trace_reduce as tr

# the Pallas segment-reduction kernel's operations in the device trace: the
# trace names an operation by its HLO instruction, "%segment_agg_call.3 =
# f32[...] custom-call(...)", so the pattern is anchored on its own name
KERNELS = (r"^%segment_agg_call[.\w]* = ",)
# the jitted write-step programs whose reductions the bytes count: plain and
# frontier-sparse bodies, and the fused write + alert evaluation
WRITE_PROGRAMS = (r"^jit_(_write_body|write_step|alert_write_step)",)


def read(ctx):
    if ctx.trace is None or not ctx.need_bytes or not ctx.peaks:
        return None
    secs = tr.kernel_seconds(ctx.trace, ctx.window, KERNELS,
                             within=WRITE_PROGRAMS)
    return tr.roofline_share(ctx.need_bytes, secs,
                             float(ctx.peaks["hbm_bytes_per_s"]))

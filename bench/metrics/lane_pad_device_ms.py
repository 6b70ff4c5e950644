"""Kernel (``kernels/segment_agg/ops.py``): device milliseconds per
write-step execution in leaf operations under the program's
``eagr.lane_pad`` scope: each chunk's slice, cast and pad of the edge
values to 128 lanes, the padded accumulator's initialisation and its
relayout to the payload's width. Moves ``ops_per_s``."""

from bench import program_trace as pt


def read(ctx):
    return pt.scope_ms_per_step(pt.for_context(ctx), ctx.window,
                                "eagr.lane_pad")

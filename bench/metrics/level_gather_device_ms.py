"""Engine steps (``core/engine.py``): device milliseconds per write-step
execution in leaf operations under the program's ``eagr.level_gather``
scope: each level's table slices, the gather of its edges' source values
and the sign multiply. Moves ``ops_per_s``."""

from bench import program_trace as pt


def read(ctx):
    return pt.scope_ms_per_step(pt.for_context(ctx), ctx.window,
                                "eagr.level_gather")

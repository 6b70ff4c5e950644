"""Engine steps (``core/engine.py``): per cent of the plan's padded edge
blocks (``L x n_blocks``) that a dense write step's level sweep visits, the
blocks up to each level's live extent rounded up to whole chunks, mean over
the dense write steps dispatched in the window. Each dense step's
``eagr.put`` span carries both counts as its ``live_blocks`` and
``padded_blocks`` arguments, from the host copy of the tables' extents.
Moves ``ops_per_s``."""

from bench import program_trace as pt


def read(ctx):
    t = pt.for_context(ctx)
    if t is None:
        return None
    got = [s for s in t.named("eagr.put", ctx.window)
           if s.arg("live_blocks") is not None and s.arg("padded_blocks")]
    if not got:
        return None
    return 100.0 * sum(s.arg("live_blocks") / s.arg("padded_blocks")
                       for s in got) / len(got)

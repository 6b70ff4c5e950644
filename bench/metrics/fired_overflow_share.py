"""Alerts (``streams/alerts.py``): per cent of the device batches collected
in the window whose compact fired buffer was full, so the dense fired and
measure vectors were read back: ``AlertStats.overflow_batches`` over
``AlertStats.batches_collected``. Each ``eagr.fired_readback`` span is one
batch collected and carries that batch's increment of ``overflow_batches``
as its ``overflow`` argument, which is how the counter reaches the trace.
Moves ``alert_p95_ms``."""

from bench import program_trace as pt


def read(ctx):
    return pt.arg_share(pt.for_context(ctx), ctx.window,
                        "eagr.fired_readback", "overflow")

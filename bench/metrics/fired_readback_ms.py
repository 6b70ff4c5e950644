"""Alerts (``streams/alerts.py``): host milliseconds per device batch whose
fired set was read back, from the program's ``eagr.fired_readback`` spans
(one per batch collected): the count, the compact buffer and, when that
overflowed, the dense fired/measure vectors. Moves ``alert_p95_ms``."""

from bench import program_trace as pt


def read(ctx):
    return pt.mean_ms(pt.for_context(ctx), ctx.window, "eagr.fired_readback")

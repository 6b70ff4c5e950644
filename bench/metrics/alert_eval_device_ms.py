"""Alerts (``streams/alerts.py``): device milliseconds per write-step
execution in leaf operations under the program's ``eagr.alert_eval``
scope, the standing-alert evaluation fused into the write step. Moves
``alert_p95_ms``."""

from bench import program_trace as pt


def read(ctx):
    return pt.scope_ms_per_step(pt.for_context(ctx), ctx.window,
                                "eagr.alert_eval")

"""Device (one v5e): per cent of the traced window in which no operation ran
on the device, one minus the union of device-operation intervals over the
window. Moves ``ops_per_s``."""

from bench import trace_reduce as tr


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    busy = tr.busy_seconds(ctx.trace, ctx.window)
    return 100.0 * (1.0 - busy / (ctx.window.dur_ns * 1e-9))

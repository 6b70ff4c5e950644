"""Engine steps (``core/engine.py``, ``streams/alerts.py``): device
milliseconds per execution of a write-step program, from the profiler
trace's XLA program lines. Moves ``ops_per_s``."""

from bench import trace_reduce as tr

# jitted write-step programs: plain and frontier-sparse bodies, and the
# fused write + alert evaluation
PROGRAMS = (r"^jit_(_write_body|write_step|alert_write_step)",)


def read(ctx):
    if ctx.trace is None:
        return None
    n, secs = tr.program_executions(ctx.trace, ctx.window, PROGRAMS)
    return 1e3 * secs / n if n else None
